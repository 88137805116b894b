"""GC011-GC013 over built artifacts + the inventory trace driver.

This module imports jax and must only be loaded behind ``--trace``
(``trace/__init__.run_trace`` imports it lazily); the descriptors and
budget logic stay jax-free so ``--list-rules`` and the unit tests work
in jax-less environments.

What each rule proves, and why the SOURCE-level twin cannot:

* **GC011 donation-audit** — for every graph whose production wrapper
  declares ``donate_argnums``, every donated buffer must appear in the
  compiled executable's input->output alias map.  XLA silently DECLINES
  donations it cannot honor (a lowering UserWarning at best); a declined
  donation on the [P, P, G] planes doubles the hot path's HBM at 100k
  groups with zero test-visible effect.  No AST pass can see what XLA
  decided — only the compiled artifact knows.
* **GC012 constant-capture** — no jaxpr const (at any nesting depth)
  above the spec's byte budget.  A closed-over device array is baked
  into the graph: HBM-resident per executable, re-traced and re-compiled
  for every new closure value (compile-cache defeat), invisible in the
  call signature.
* **GC013 host-sync-in-graph** — no callback/debug/transfer primitive
  anywhere in a hot graph.  The runtime-truth twin of AST rule GC002:
  GC002 bans the host-sync SPELLINGS in the kernel modules, but a
  callback smuggled through a helper in another module still lands an
  eqn in the traced graph — and that eqn, not the spelling, is what
  serializes every dispatch.
* **GC015 collective-audit** (ISSUE 14) — the sharded inventory rows,
  compiled over the multi-device audit mesh, must contain EXACTLY the
  cross-partition collectives registered for them in COLLECTIVE_ALLOW:
  zero for the steady step/scan graphs (the "embarrassingly parallel
  across G" claim of sharding.py, machine-checked), the psum/pmin set
  for the status/drain reductions.  Only the PARTITIONED executable
  knows what GSPMD inserted — a global reduction that looks innocent in
  the jaxpr (a cond predicate, a stat fold) lowers to a per-round
  all-reduce on the mesh.
"""

from __future__ import annotations

import re
import warnings
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Set, Tuple

import jax
import jax.tree_util as jtu

from ..core import Context, Violation
from . import budget as budget_mod
from .inventory import (
    COLLECTIVE_ALLOW,
    DONATION_ALLOW,
    REGISTRY,
    Built,
    GraphSpec,
)

GC011, GC011_SLUG = "GC011", "donation-audit"
GC012, GC012_SLUG = "GC012", "constant-capture"
GC013, GC013_SLUG = "GC013", "host-sync-in-graph"
GC015, GC015_SLUG = "GC015", "collective-audit"

# Cross-partition collective opcodes in optimized HLO text; -start/-done
# async pairs normalize to the base opcode.  `partition-id` and
# `replica-id` are deliberately absent: they are cheap local reads, not
# cross-chip traffic.
_COLLECTIVE_RE = re.compile(
    r"=\s+\S+\s+("
    r"all-reduce|all-gather|all-to-all|collective-permute|"
    r"reduce-scatter|collective-broadcast|ragged-all-to-all"
    r")(?:-start|-done)?\("
)

# Primitives that move control or data across the host boundary (or pin a
# transfer) inside a traced graph.  jax.debug.print traces to a
# `debug_print` equation, jax.debug.callback to `debug_callback`.
HOST_SYNC_PRIMITIVES: Set[str] = {
    "pure_callback",
    "io_callback",
    "debug_callback",
    "debug_print",
    "callback",
    "infeed",
    "outfeed",
    "device_put",
    "copy_to_host_async",
}

_ALIAS_ENTRY_RE = re.compile(r"\{[0-9,\s]*\}:\s*\(([0-9]+),")


# --- jaxpr walking ----------------------------------------------------------


def _sub_jaxprs(params: dict) -> Iterator[object]:
    """Every Jaxpr/ClosedJaxpr reachable through one eqn's params (cond
    branches, scan/while bodies, pjit calls, pallas kernels, custom_*)."""
    for value in params.values():
        items: Iterable[object] = (
            value if isinstance(value, (list, tuple)) else (value,)
        )
        for item in items:
            if hasattr(item, "eqns") or hasattr(item, "jaxpr"):
                yield item


def walk_jaxprs(closed) -> Iterator[object]:
    """Preorder over the ClosedJaxpr/Jaxpr tree, root first."""
    stack = [closed]
    while stack:
        node = stack.pop()
        yield node
        jaxpr = getattr(node, "jaxpr", node)
        for eqn in getattr(jaxpr, "eqns", ()):
            stack.extend(_sub_jaxprs(eqn.params))


def count_eqns(closed) -> int:
    """Total equations at every nesting depth — the budget metric.  The
    recursive count (not the top-level one) is what tracks compile time:
    XLA compiles every sub-jaxpr, and a cond counts both branches."""
    return sum(
        len(getattr(getattr(node, "jaxpr", node), "eqns", ()))
        for node in walk_jaxprs(closed)
    )


def collect_consts(closed) -> List[object]:
    """Every array-valued const at every nesting depth."""
    out = []
    for node in walk_jaxprs(closed):
        for const in getattr(node, "consts", ()):
            if hasattr(const, "nbytes"):
                out.append(const)
    return out


def collect_primitives(closed) -> Set[str]:
    prims: Set[str] = set()
    for node in walk_jaxprs(closed):
        jaxpr = getattr(node, "jaxpr", node)
        for eqn in getattr(jaxpr, "eqns", ()):
            prims.add(eqn.primitive.name)
    return prims


# --- the rules --------------------------------------------------------------


def _v(spec: GraphSpec, rule_id: str, slug: str, message: str) -> Violation:
    return Violation(spec.anchor, 1, rule_id, slug, message)


def parse_alias_params(hlo_text: str) -> Set[int]:
    """Parameter numbers appearing in the compiled module's
    ``input_output_alias={ {out}: (param, {index}, kind), ... }`` header.
    The segment is extracted with a brace counter (entries themselves
    contain ``{}``), so stray braces elsewhere cannot confuse it."""
    start = hlo_text.find("input_output_alias={")
    if start < 0:
        return set()
    i = hlo_text.index("{", start)
    depth, j = 0, i
    for j in range(i, min(len(hlo_text), i + 200_000)):
        if hlo_text[j] == "{":
            depth += 1
        elif hlo_text[j] == "}":
            depth -= 1
            if depth == 0:
                break
    segment = hlo_text[i : j + 1]
    return {int(g.group(1)) for g in _ALIAS_ENTRY_RE.finditer(segment)}


def check_donation(
    spec: GraphSpec, built: Built, compiled_text: str, args_info
) -> Tuple[List[Violation], Set[Tuple[str, str]]]:
    """GC011 over one compiled artifact; returns (violations, declined
    keys) — declined keys include allow-listed declines, so the stale
    check can tell a used allow entry from a rotten one.

    ``args_info`` is ``Lowered.args_info`` — its flattened order IS the
    executable's parameter numbering, and each leaf carries the
    ``donated`` flag jax actually lowered with (so registry drift from
    the production wrapper is caught too)."""
    violations: List[Violation] = []
    declined: Set[Tuple[str, str]] = set()
    flat = jtu.tree_flatten_with_path(args_info)[0]
    donated_params: Dict[int, str] = {}
    declared_argnums: Set[int] = set()
    for param_no, (path, info) in enumerate(flat):
        path_str = jtu.keystr(path)
        if getattr(info, "donated", False):
            donated_params[param_no] = path_str
            # args_info nests the positional args one level down (the
            # outer [0] is the args tuple itself), so the ARGNUM is the
            # second path entry, not the first.
            if len(path) >= 2:
                argnum = getattr(path[1], "idx", None)
                if argnum is not None:
                    declared_argnums.add(int(argnum))
    if declared_argnums != set(built.donate):
        violations.append(
            _v(
                spec,
                GC011,
                GC011_SLUG,
                f"graph {spec.name!r}: the registry declares donate_argnums="
                f"{tuple(sorted(built.donate))} but the lowering donated "
                f"argnums {tuple(sorted(declared_argnums))} — the production "
                "wrapper and the inventory entry disagree; fix whichever "
                "drifted (tools/graftcheck/trace/inventory.py)",
            )
        )
    aliased = parse_alias_params(compiled_text)
    for param_no, path_str in sorted(donated_params.items()):
        if param_no in aliased:
            continue
        key = (spec.name, path_str)
        declined.add(key)
        if str(DONATION_ALLOW.get(key, "")).strip():
            continue
        violations.append(
            _v(
                spec,
                GC011,
                GC011_SLUG,
                f"graph {spec.name!r}: donated buffer {path_str} (parameter "
                f"{param_no}) is MISSING from the executable's input->output "
                "alias map — XLA declined the donation, so this plane is "
                "double-buffered every call (2x HBM at production G); make "
                "an output of matching shape/dtype reuse it, stop donating "
                "it, or register the decline in DONATION_ALLOW with a reason",
            )
        )
    return violations, declined


def check_stale_donation_allows(
    declined_seen: Set[Tuple[str, str]],
    audited: Set[str],
    spec_names: Set[str],
) -> Iterator[Violation]:
    """A DONATION_ALLOW entry that matches no currently-declined donation
    is rot (the GC000 discipline for the trace layer's escape hatch).
    That includes entries whose graph NAME matches nothing traced — a
    typo'd or removed graph, or one with no donation audit at all —
    which would otherwise suppress nothing and rot forever."""
    for key, reason in sorted(DONATION_ALLOW.items()):
        name, path_str = key
        if name not in audited and name in spec_names:
            yield Violation(
                "tools/graftcheck/trace/inventory.py",
                1,
                GC011,
                GC011_SLUG,
                f"DONATION_ALLOW entry {key!r} names graph {name!r}, whose "
                "registry row sets audit_donation=False — the entry can "
                "never match a decline; delete it (or re-enable the audit)",
            )
        elif name not in spec_names:
            yield Violation(
                "tools/graftcheck/trace/inventory.py",
                1,
                GC011,
                GC011_SLUG,
                f"DONATION_ALLOW entry {key!r} names no inventoried graph "
                f"({name!r} is not in the registry) — typo'd or removed; "
                "delete the stale entry",
            )
        elif key not in declined_seen:
            yield Violation(
                "tools/graftcheck/trace/inventory.py",
                1,
                GC011,
                GC011_SLUG,
                f"DONATION_ALLOW entry {key!r} matches no declined "
                "donation — XLA accepts this buffer now; delete the stale "
                "entry",
            )
        if not str(reason).strip():
            yield Violation(
                "tools/graftcheck/trace/inventory.py",
                1,
                GC011,
                GC011_SLUG,
                f"DONATION_ALLOW entry {key!r} has no justification; "
                "explain why XLA declines it and why that is acceptable",
            )


def check_consts(spec: GraphSpec, closed) -> Iterator[Violation]:
    """GC012 over one traced graph."""
    for const in collect_consts(closed):
        nbytes = int(const.nbytes)
        if nbytes <= spec.const_budget:
            continue
        shape = tuple(getattr(const, "shape", ()))
        dtype = getattr(const, "dtype", "?")
        yield _v(
            spec,
            GC012,
            GC012_SLUG,
            f"graph {spec.name!r} bakes a {nbytes}-byte const "
            f"({dtype}{list(shape)}) into its jaxpr (budget "
            f"{spec.const_budget}B) — a closed-over plane is HBM-resident "
            "per executable and defeats the compile cache; pass it as an "
            "argument (cf. runner.schedule_args, the registry-derived "
            "flat schedule tuple)",
        )


def collect_collectives(hlo_text: str) -> Set[str]:
    """Base opcodes of every cross-partition collective in the compiled
    module's text."""
    return {m.group(1) for m in _COLLECTIVE_RE.finditer(hlo_text)}


def check_collectives(
    spec: GraphSpec, compiled_text: str
) -> Tuple[List[Violation], Set[Tuple[str, str]]]:
    """GC015 over one compiled artifact (ISSUE 14): the module's
    collective-op set must equal EXACTLY the opcodes registered for this
    graph in COLLECTIVE_ALLOW.  Zero registered opcodes is the strongest
    claim — the steady sharded step/scan graphs carry NO cross-chip
    traffic (sharding.py's "embarrassingly parallel across G", machine-
    checked the GC011 way).  Returns (violations, used allow keys) so the
    stale-entry check can spot rot."""
    violations: List[Violation] = []
    used: Set[Tuple[str, str]] = set()
    found = collect_collectives(compiled_text)
    for op in sorted(found):
        key = (spec.name, op)
        if str(COLLECTIVE_ALLOW.get(key, "")).strip():
            used.add(key)
            continue
        violations.append(
            _v(
                spec,
                GC015,
                GC015_SLUG,
                f"graph {spec.name!r} contains a `{op}` collective that is "
                "NOT registered for it in COLLECTIVE_ALLOW — cross-chip "
                "traffic crept into a graph audited as "
                + (
                    "collective-free (the steady mesh path must stay "
                    "embarrassingly parallel across G)"
                    if not any(
                        n == spec.name for n, _ in COLLECTIVE_ALLOW
                    )
                    else "having exactly its registered reduction set"
                )
                + "; remove the reduction from the hot graph or register "
                "it with a justification "
                "(tools/graftcheck/trace/inventory.py)",
            )
        )
    return violations, used


def check_stale_collective_allows(
    used: Set[Tuple[str, str]],
    audited: Set[str],
    compiled_ok: Set[str],
    spec_names: Set[str],
    full_registry: bool = True,
) -> Iterator[Violation]:
    """A COLLECTIVE_ALLOW entry that matches no compiled collective is rot
    (the GC000 discipline, mirroring the donation allow-registry).
    `audited` is the REGISTRY intent (audit_collectives=True rows) and
    `compiled_ok` the graphs whose compile actually succeeded: a graph
    that failed to build already reported a GC000 finding, and its allow
    entries must NOT be misread as stale (deleting them on that advice
    would fail the build again once the graph compiles).  On a partial
    run (fixture specs, --rule subsets) entries naming graphs outside
    the selected set are SKIPPED rather than misread as typos — only the
    full-registry run can tell rot from not-selected."""
    anchor = "tools/graftcheck/trace/inventory.py"
    for key, reason in sorted(COLLECTIVE_ALLOW.items()):
        name, op = key
        if not full_registry and name not in spec_names:
            continue
        if name not in spec_names:
            yield Violation(
                anchor, 1, GC015, GC015_SLUG,
                f"COLLECTIVE_ALLOW entry {key!r} names no inventoried "
                f"graph ({name!r} is not in the registry) — typo'd or "
                "removed; delete the stale entry",
            )
        elif name not in audited:
            yield Violation(
                anchor, 1, GC015, GC015_SLUG,
                f"COLLECTIVE_ALLOW entry {key!r} names graph {name!r}, "
                "whose registry row does not set audit_collectives=True — "
                "the entry can never match; delete it (or enable the "
                "audit)",
            )
        elif name in compiled_ok and key not in used:
            yield Violation(
                anchor, 1, GC015, GC015_SLUG,
                f"COLLECTIVE_ALLOW entry {key!r} matches no collective in "
                "the compiled graph — the reduction is gone; delete the "
                "stale entry",
            )
        if not str(reason).strip():
            yield Violation(
                anchor, 1, GC015, GC015_SLUG,
                f"COLLECTIVE_ALLOW entry {key!r} has no justification; "
                "explain why this cross-chip reduction belongs in the "
                "graph",
            )


def check_host_sync(spec: GraphSpec, closed) -> Iterator[Violation]:
    """GC013 over one traced graph."""
    bad = sorted(collect_primitives(closed) & HOST_SYNC_PRIMITIVES)
    for prim in bad:
        yield _v(
            spec,
            GC013,
            GC013_SLUG,
            f"graph {spec.name!r} contains a `{prim}` equation — a "
            "host-boundary primitive inside a hot graph serializes every "
            "dispatch (the runtime twin of GC002); hoist it to the drain "
            "boundary or behind an instrumentation flag",
        )


# --- the driver -------------------------------------------------------------


def _pin_audit_mesh() -> None:
    """Pin the canonical audit environment: the virtual 8-device CPU mesh
    tests/conftest.py uses.  The GC015 collective audit inspects the
    PARTITIONED executables, so the sharded inventory rows need a real
    multi-device mesh; jaxpr eqn counts and alias maps are device-count
    independent, so the other rules are unaffected.  Only engages when
    the process targets CPU (JAX_PLATFORMS unset or cpu — a real TPU
    host keeps its devices) and is a guarded no-op once a backend is
    live (force_virtual_cpu swallows the late-config RuntimeError)."""
    import os

    plat = os.environ.get("JAX_PLATFORMS", "").split(",")[0]
    if plat not in ("", "cpu"):
        return
    try:
        from raft_tpu.platform import force_virtual_cpu

        force_virtual_cpu(8)
    except Exception:
        pass


def trace_inventory(
    specs: Optional[Sequence[GraphSpec]] = None,
) -> Tuple[List[Violation], Dict[str, int]]:
    """Build every inventoried graph and run GC011-GC013 + GC015; returns
    the violations plus the measured eqn counts for GC014 (budget.py)."""
    full_registry = specs is None
    if specs is None:
        specs = REGISTRY
    _pin_audit_mesh()
    try:
        # GC011 pays real XLA compiles; the persistent cache (the same one
        # the tier-1 job fills) makes repeated trace runs cheap.
        # Best-effort by design.
        from raft_tpu import platform

        platform.enable_compile_cache()
    except Exception:
        pass
    violations: List[Violation] = []
    measured: Dict[str, int] = {}
    declined_seen: Set[Tuple[str, str]] = set()
    audited: Set[str] = set()
    collective_used: Set[Tuple[str, str]] = set()
    collective_compiled: Set[str] = set()
    multi_device = jax.device_count() >= 2
    # Registry INTENT, not compile success: a row whose build fails must
    # not make the stale-allow sweep misadvise deleting its entries.
    collective_audited: Set[str] = {
        s.name for s in specs if s.audit_collectives
    }
    if not multi_device and any(s.audit_collectives for s in specs):
        import sys

        print(
            "graftcheck: GC015 collective audit SKIPPED — only one device "
            "visible (needs the virtual multi-device mesh; the multichip "
            "CI job is the backstop)",
            file=sys.stderr,
        )
    for spec in specs:
        try:
            built = spec.build()
            closed = jax.make_jaxpr(built.fn)(*built.args)
        except Exception as e:  # a graph that fails to TRACE is a finding
            violations.append(
                _v(
                    spec,
                    "GC000",
                    "trace-build-error",
                    f"graph {spec.name!r} failed to build/trace: "
                    f"{type(e).__name__}: {e}",
                )
            )
            continue
        measured[spec.name] = count_eqns(closed)
        violations.extend(check_consts(spec, closed))
        violations.extend(check_host_sync(spec, closed))
        audit_coll = spec.audit_collectives and multi_device
        if spec.audit_donation:
            # Registry intent (pre-compile): matches the collective set's
            # discipline — a build failure is its own GC000 finding, not
            # a license to misread allow entries as stale.
            audited.add(spec.name)
        if spec.audit_donation or audit_coll:
            try:
                with warnings.catch_warnings():
                    # The "donated buffers were not usable" UserWarning is
                    # what GC011 turns into a structured violation below.
                    warnings.simplefilter("ignore")
                    lowered = built.fn.lower(*built.args)
                    # The drift check must be BIDIRECTIONAL: a wrapper
                    # that starts donating while its registry row still
                    # declares none is drift too, so every graph pays the
                    # cheap lower(); the expensive compile runs only when
                    # either side declares a donation — or when GC015
                    # needs the partitioned module's collective set.
                    flat_info = jtu.tree_flatten_with_path(
                        lowered.args_info
                    )[0]
                    lowering_donates = any(
                        getattr(info, "donated", False)
                        for _, info in flat_info
                    )
                    compiled_text = (
                        lowered.compile().as_text()
                        if built.donate or lowering_donates or audit_coll
                        else ""
                    )
            except Exception as e:
                violations.append(
                    _v(
                        spec,
                        "GC000",
                        "trace-build-error",
                        f"graph {spec.name!r} failed to compile for the "
                        f"donation/collective audit: "
                        f"{type(e).__name__}: {e}",
                    )
                )
                continue
            if spec.audit_donation:
                donation_violations, declined = check_donation(
                    spec, built, compiled_text, lowered.args_info
                )
                violations.extend(donation_violations)
                declined_seen.update(declined)
            if audit_coll:
                collective_compiled.add(spec.name)
                coll_violations, used = check_collectives(
                    spec, compiled_text
                )
                violations.extend(coll_violations)
                collective_used.update(used)
    violations.extend(
        check_stale_donation_allows(
            declined_seen, audited, {spec.name for spec in specs}
        )
    )
    if multi_device:
        violations.extend(
            check_stale_collective_allows(
                collective_used,
                collective_audited,
                collective_compiled,
                {spec.name for spec in specs},
                full_registry=full_registry,
            )
        )
    return violations, measured


def run_trace(
    ctx: Context,
    update_budget: bool = False,
    diff_out: Optional[str] = None,
    specs: Optional[Sequence[GraphSpec]] = None,
) -> List[Violation]:
    """The ``--trace`` entry point: trace/compile the inventory, run
    GC011-GC013, then GC014 against the committed budget (or regenerate
    it with ``update_budget``).  ``diff_out`` writes the budget-diff
    artifact JSON (CI uploads it)."""
    import json
    from pathlib import Path

    from raft_tpu.multiraft import schedules

    violations, measured = trace_inventory(specs)
    variants = schedules.runner_variants()
    bpath = budget_mod.budget_path(ctx.repo_root)
    versions = jax_versions()
    if update_budget:
        bpath.parent.mkdir(parents=True, exist_ok=True)
        phase_doc = budget_mod.derive_phase_doc(
            measured, variants, schedules.PHASE_TOLERANCE_PCT
        )
        bpath.write_text(
            budget_mod.render_budget(
                measured, versions, phase_doc=phase_doc
            ),
            encoding="utf-8",
        )
    doc = budget_mod.load_budget(bpath)
    anchor = "tools/graftcheck/" + budget_mod.BUDGET_NAME
    budget_violations, diff = budget_mod.check_budget(
        measured, doc, anchor, measured_versions=versions
    )
    violations.extend(budget_violations)
    phase_violations, phase_diff = budget_mod.check_phase_budget(
        measured, doc, anchor, variants, full_registry=specs is None
    )
    violations.extend(phase_violations)
    diff["phase_budget"] = phase_diff
    if diff.get("version_mismatch"):
        import sys

        print(
            f"graftcheck: --trace measured under {versions} but the "
            f"committed budget was stamped {diff.get('versions')} — eqn "
            "deltas may be upstream jax changes (the diff artifact records "
            "the mismatch)",
            file=sys.stderr,
        )
    if diff_out:
        diff["measured_versions"] = versions
        out = Path(diff_out)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(
            json.dumps(diff, indent=2, sort_keys=True) + "\n",
            encoding="utf-8",
        )
    violations.sort(key=lambda v: (v.path, v.line, v.rule_id))
    return violations


def jax_versions() -> Dict[str, str]:
    import jaxlib

    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__}
