# tpu-multiraft build/test entry points (SURVEY.md §2 #27: the quality gate
# is the test suite; native code builds lazily but can be forced here).

PY ?= python

# The exact file set the static-analysis gates run over — keep `make lint`,
# `make typecheck`, CI, and docs/STATIC_ANALYSIS.md in sync by changing it
# here only.
CHECK_PATHS = raft_tpu tests docs README.md CHANGES.md

.PHONY: all test test-fast native examples clean \
	lint typecheck check obligations jaxpr-budget

all: native test

# The library is keyed on the source's content hash
# (cpp/libmultiraft.<hash>.so, raft_tpu/multiraft/native.py) and built on
# first use; this target forces the build.
native:
	$(PY) -c "from raft_tpu.multiraft import native; native.load_library(); print(native.library_path())"

test:
	$(PY) -m pytest tests/ -q

# Static analysis (docs/STATIC_ANALYSIS.md): graftcheck always runs (the
# AST/engine layers are zero-dependency; --engine adds the cross-module
# abstract-interpretation rules GC007-GC010 plus the GC016 registry-closure
# and GC017 stale-marker audits, and the mtime run cache keeps
# an unchanged tree under ~2s).  The trace layer (--trace, GC011-GC014)
# proves properties of the LOWERED graphs and therefore needs jax: it runs
# whenever jax imports (an unchanged inventory replays from the cache in
# ~0.3s; a cold full-inventory trace is ~60s of XLA compiles) and is
# skipped LOUDLY otherwise — the graftcheck-trace CI job is the backstop.
# ruff runs when installed (CI installs it).
lint:
	@if $(PY) -c "import importlib.util, sys; sys.exit(importlib.util.find_spec('jax') is None)" >/dev/null 2>&1; then \
		$(PY) -m tools.graftcheck --engine --trace $(CHECK_PATHS); \
	else \
		echo "jax not installed; trace rules GC011-GC014 skipped" \
			"(the graftcheck-trace CI job runs them)"; \
		$(PY) -m tools.graftcheck --engine $(CHECK_PATHS); \
	fi
	@if $(PY) -c "import ruff" 2>/dev/null || command -v ruff >/dev/null; \
	then ruff check .; \
	else echo "ruff not installed; skipped (CI runs it)"; fi

# Regenerate the GC010 parity-obligations baseline after an intentional
# kernel/oracle change; CI diffs the extraction against this committed file.
obligations:
	$(PY) -m tools.graftcheck --emit-obligations \
		tools/graftcheck/parity_obligations.json raft_tpu/multiraft tests

# Regenerate the GC014 jaxpr-size budget after an intentional graph change:
# re-traces the whole graph
# inventory and rewrites tools/graftcheck/jaxpr_budget.json — commit the
# result so the growth is paid visibly in review (docs/STATIC_ANALYSIS.md).
jaxpr-budget:
	$(PY) -m tools.graftcheck --update-budget raft_tpu

# mypy is a dev-only dependency; the target fails loudly if it's missing so
# a silent skip can never masquerade as a green typecheck.
typecheck:
	@$(PY) -c "import mypy" 2>/dev/null \
	|| { echo "mypy not installed (pip install mypy); the CI typecheck job runs it"; exit 1; }
	$(PY) -m mypy

check: lint typecheck test

test-fast:
	$(PY) -m pytest tests/ -q --ignore=tests/test_pallas_step.py

examples:
	$(PY) examples/single_mem_node.py
	$(PY) examples/five_mem_node.py

clean:
	rm -f cpp/libmultiraft*.so
	find . -name __pycache__ -type d -exec rm -rf {} +
