"""GC018 runner-closure: the schedule registry is the single source of truth.

``raft_tpu/multiraft/schedules.py`` declares one ScheduleSpec row per
compiled schedule array, one ScheduleFamily per pipeline, and one
RunnerVariant per compiled runner graph; the unified runner
(``raft_tpu/multiraft/runner.py``), the host twins, and the trace
inventory all consume it.  GC018 proves that loop is closed in BOTH
directions, the way GC016 does for the plane registry:

  * registry rows are well-formed: unique per family, known gather/dtype
    vocabulary, packing families resolve against planes.PACKED_PLANES,
    gating flags exist as SimConfig fields, runner variants cover every
    GC019 phase with exactly one probe;
  * each family's compiled NamedTuple carries exactly the registry's
    rows, in order, with matching ``# gc:`` anchors — an orphan registry
    row (no tuple field) and an unregistered schedule array (no registry
    row) both fail;
  * each family has exactly one host twin, unique across families,
    resolving to a real top-level def/class;
  * the unified runner derives its flat runtime-arg tuples from the
    registry accessors, binds every actions-family plane as a runtime
    arg, and no nested (traced) function closes over a schedule array
    from an enclosing scope — the closure-const form of the GC012
    constant-capture hazard, caught at the SOURCE level;
  * no runner module hand-lists a schedule tuple (three or more fields
    of one family off one object in a display) — the drift the registry
    exists to delete;
  * the trace inventory derives its runner GraphSpec rows from
    ``runner_variants()`` and hand-lists no runner graph name.

Zero-dependency like the rest of the engine: schedules.py is stdlib-only
by contract and is loaded standalone from the SCANNED tree, exactly like
GC016 loads planes.py — fixture trees carry fixture registries.
"""

from __future__ import annotations

import ast
import fnmatch
import importlib.util
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

from ..core import Context, SourceFile, Violation
from .registry import _ann_fields, _anchor_text, _class_def, _module_file

GC018 = "GC018"
GC018_SLUG = "runner-closure"

# Closed vocabularies for ScheduleSpec enum-ish fields; a typo'd gather
# string would silently fall out of every accessor filter.
_GATHERS = {"round", "phase", "op", "fire", "fold"}
_DTYPES = {"int32", "uint32", "bool"}

# The modules whose schedule handling must go through the registry
# accessors — the runner factory (with the shared scan body,
# runner._runner_body), the schedule modules, the autopilot, and sim.py's
# dispatch sites.
_RUNNER_MODULES = (
    "chaos", "reconfig", "workload", "autopilot", "runner", "sim",
)

_INVENTORY_REL = "tools/graftcheck/trace/inventory.py"


def _v(path: str, line: int, msg: str) -> Violation:
    return Violation(path, line, GC018, GC018_SLUG, msg)


def _load_standalone(sf: SourceFile, tag: str):
    """Standalone-exec a stdlib-only module from the SCANNED tree (the
    GC016 discipline: the rule checks the tree it is pointed at)."""
    spec = importlib.util.spec_from_file_location(tag, sf.path)
    assert spec is not None and spec.loader is not None, sf.path
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def check_runners(
    files: Sequence[SourceFile], ctx: Context
) -> Iterator[Violation]:
    sched_sf = _module_file(files, "raft_tpu/multiraft/schedules.py")
    if sched_sf is None:
        # No schedule registry in the scanned tree (a fixture about other
        # rules); the real tree always scans raft_tpu.
        return
    try:
        sched = _load_standalone(sched_sf, "_gc018_schedule_registry")
    except Exception as e:
        yield _v(
            sched_sf.display_path, 1,
            f"schedules.py failed to load standalone ({e}) — the registry "
            "must stay stdlib-only and import-clean",
        )
        return
    path = sched_sf.display_path
    yield from _check_rows(sched, path, files)
    yield from _check_variants(sched, path)
    yield from _check_families(sched, path, files, ctx)
    runner_sf = _module_file(files, "raft_tpu/multiraft/runner.py")
    if runner_sf is not None:
        yield from _check_runner_module(sched, runner_sf)
    yield from _check_hand_lists(sched, files)
    yield from _check_inventory(sched, ctx)


# --- registry well-formedness ------------------------------------------------


def _check_rows(
    sched, path: str, files: Sequence[SourceFile]
) -> Iterator[Violation]:
    family_names = {f.name for f in sched.families()}
    seen: Set[Tuple[str, str]] = set()
    for r in sched.rows():
        key = (r.family, r.name)
        if key in seen:
            yield _v(path, 1, f"duplicate schedule row {r.family}.{r.name}")
        seen.add(key)
        if r.family not in family_names:
            yield _v(
                path, 1,
                f"row {r.family}.{r.name} names no FAMILIES entry "
                f"(known: {sorted(family_names)})",
            )
        if r.gather not in _GATHERS:
            yield _v(
                path, 1,
                f"row {r.family}.{r.name} has unknown gather {r.gather!r} "
                f"(known: {sorted(_GATHERS)})",
            )
        if r.dtype not in _DTYPES:
            yield _v(
                path, 1,
                f"row {r.family}.{r.name} has unknown dtype {r.dtype!r}",
            )
    for f in sched.families():
        if not sched.rows(f.name):
            yield _v(path, 1, f"family {f.name!r} has no schedule rows")
        if f.phase not in sched.phases():
            yield _v(
                path, 1,
                f"family {f.name!r} names unknown GC019 phase {f.phase!r}",
            )
    # Packing families resolve against the plane registry's GC008
    # PACKED_PLANES (planes.py, loaded standalone the GC016 way).
    planes_sf = _module_file(files, "raft_tpu/multiraft/planes.py")
    if planes_sf is not None:
        try:
            planes = _load_standalone(planes_sf, "_gc018_plane_registry")
        except Exception:
            planes = None  # GC016 reports the broken registry
        if planes is not None:
            packed = set(planes.PACKED_PLANES)
            for fam_name in sched.packing_families():
                if fam_name not in packed:
                    yield _v(
                        path, 1,
                        f"schedule packing family {fam_name!r} does not "
                        "resolve against planes.PACKED_PLANES "
                        f"({sorted(packed)}) — the word-packing bound "
                        "registry (GC008) is the source of truth",
                    )
    # Gating flags exist as SimConfig fields.
    sim_sf = _module_file(files, "raft_tpu/multiraft/sim.py")
    if sim_sf is not None:
        cfg = _class_def(sim_sf, "SimConfig")
        cfg_fields = (
            {n for n, _ in _ann_fields(cfg)} if cfg is not None else set()
        )
        for flag in sched.gating_flags():
            if flag not in cfg_fields:
                yield _v(
                    path, 1,
                    f"schedule gating flag {flag!r} is not a SimConfig "
                    "field",
                )


def _check_variants(sched, path: str) -> Iterator[Violation]:
    phases = tuple(sched.phases())
    names: Set[str] = set()
    probes: Dict[str, List[str]] = {p: [] for p in phases}
    for v in sched.runner_variants():
        if v.name in names:
            yield _v(path, 1, f"duplicate runner variant {v.name!r}")
        names.add(v.name)
        if not v.builder:
            yield _v(
                path, 1,
                f"runner variant {v.name!r} has no inventory builder key",
            )
        if not v.base:
            yield _v(
                path, 1,
                f"runner variant {v.name!r} has no base graph — GC019 "
                "needs an anchor for the phase decomposition",
            )
        for p in v.phases:
            if p not in phases:
                yield _v(
                    path, 1,
                    f"runner variant {v.name!r} names unknown phase {p!r}",
                )
        if v.probe_for:
            if v.probe_for not in phases:
                yield _v(
                    path, 1,
                    f"runner variant {v.name!r} probes unknown phase "
                    f"{v.probe_for!r}",
                )
            elif v.probe_for not in v.phases:
                yield _v(
                    path, 1,
                    f"runner variant {v.name!r} probes phase "
                    f"{v.probe_for!r} it does not itself lower",
                )
            else:
                probes[v.probe_for].append(v.name)
    for p in phases:
        if len(probes.get(p, [])) != 1:
            yield _v(
                path, 1,
                f"GC019 phase {p!r} has {len(probes.get(p, []))} probe "
                "variants (need exactly one) — the phase budget is "
                "underdetermined or overdetermined at regen time",
            )


# --- family closure: compiled tuples + host twins ----------------------------


def _top_level_names(
    mod: str, files: Sequence[SourceFile], ctx: Context,
    cache: Dict[str, Optional[Set[str]]],
) -> Optional[Set[str]]:
    if mod in cache:
        return cache[mod]
    suffix = f"raft_tpu/multiraft/{mod}.py"
    sf = _module_file(files, suffix)
    tree: Optional[ast.AST] = sf.ast_tree if sf is not None else None
    if tree is None:
        try:
            tree = ast.parse(
                (ctx.repo_root / suffix).read_text(encoding="utf-8")
            )
        except (OSError, SyntaxError):
            cache[mod] = None
            return None
    names = {
        n.name
        for n in ast.iter_child_nodes(tree)
        if isinstance(n, (ast.FunctionDef, ast.ClassDef))
    }
    cache[mod] = names
    return names


def _check_families(
    sched, path: str, files: Sequence[SourceFile], ctx: Context
) -> Iterator[Violation]:
    cache: Dict[str, Optional[Set[str]]] = {}
    twins: Dict[str, str] = {}
    for fam in sched.families():
        # Exactly one host twin per family, unique across families,
        # resolving to a top-level def/class (the GC016 oracle style).
        mod, _, sym = fam.host_twin.partition(".")
        if not sym:
            yield _v(
                path, 1,
                f"family {fam.name!r} host twin {fam.host_twin!r} is not "
                "of the form 'module.Symbol'",
            )
        else:
            if fam.host_twin in twins:
                yield _v(
                    path, 1,
                    f"families {twins[fam.host_twin]!r} and {fam.name!r} "
                    f"share host twin {fam.host_twin!r} — each schedule "
                    "pipeline needs its own numpy replay",
                )
            twins[fam.host_twin] = fam.name
            names = _top_level_names(mod, files, ctx, cache)
            if names is not None and sym not in names:
                yield _v(
                    path, 1,
                    f"family {fam.name!r} host twin {fam.host_twin!r} does "
                    f"not resolve: no top-level def/class {sym} in "
                    f"raft_tpu/multiraft/{mod}.py",
                )
        if not fam.compiled:
            continue  # bare-plane family; consumption checked in runner.py
        cmod, _, csym = fam.compiled.partition(".")
        if not csym:
            yield _v(
                path, 1,
                f"family {fam.name!r} compiled {fam.compiled!r} is not of "
                "the form 'module.Symbol'",
            )
            continue
        sf = _module_file(files, f"raft_tpu/multiraft/{cmod}.py")
        if sf is None:
            continue  # fixture tree without the owner module
        cls = _class_def(sf, csym)
        if cls is None:
            yield _v(
                path, 1,
                f"family {fam.name!r} compiled tuple {fam.compiled!r} not "
                f"found in raft_tpu/multiraft/{cmod}.py",
            )
            continue
        anchored = [
            (n, stmt)
            for n, stmt in _ann_fields(cls)
            if _anchor_text(sf, stmt.lineno)
        ]
        got = tuple(n for n, _ in anchored)
        want = sched.array_fields(fam.name)
        if got != want:
            yield _v(
                sf.display_path, cls.lineno,
                f"{csym}'s anchored fields {list(got)} != schedule "
                f"registry {fam.name!r} rows {list(want)} (order included "
                "— the registry row order IS the flat runtime-arg order): "
                "an orphan registry row or an unregistered schedule "
                "array; update schedules.py in lockstep with the "
                "NamedTuple",
            )
            continue
        for name, stmt in anchored:
            r = sched.row(fam.name, name)
            anchor = _anchor_text(sf, stmt.lineno)
            if not anchor.startswith(r.anchor_text):
                yield _v(
                    sf.display_path, stmt.lineno,
                    f"{csym}.{name}'s `# gc:` anchor {anchor!r} does not "
                    f"match its schedule row ({r.anchor_text!r}) — the "
                    "GC007 anchor and the ScheduleSpec dtype/shape must "
                    "agree",
                )


# --- the unified runner ------------------------------------------------------


def _bound_names(func: ast.FunctionDef) -> Set[str]:
    """Names bound in `func`'s own scope: parameters plus assignment
    targets, not descending into nested defs."""
    from ..core import walk_local

    args = func.args
    out = {
        a.arg
        for a in (
            args.posonlyargs + args.args + args.kwonlyargs
            + ([args.vararg] if args.vararg else [])
            + ([args.kwarg] if args.kwarg else [])
        )
    }
    for node in walk_local(func):
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Store):
            out.add(node.id)
        elif isinstance(node, ast.arg):  # lambda params inside the body
            out.add(node.arg)
        elif isinstance(node, ast.FunctionDef):
            out.add(node.name)
    return out


def _check_runner_module(sched, sf: SourceFile) -> Iterator[Violation]:
    # The flat runtime-arg tuples must derive from the registry.
    uses_accessor = any(
        isinstance(node, ast.Attribute) and node.attr == "array_fields"
        for node in ast.walk(sf.ast_tree)
    )
    if not uses_accessor:
        yield _v(
            sf.display_path, 1,
            "runner.py does not consult schedules.array_fields() — the "
            "flat runtime-arg order of the jit boundary must derive from "
            "the registry, not a hand-listed tuple",
        )
    # Bare-plane families (no compiled tuple): every row must be bound as
    # a runtime name somewhere in the unified runner — the consumption
    # proof the compiled-tuple closure gives the other families.
    bound_anywhere: Set[str] = set()
    for node in ast.walk(sf.ast_tree):
        if isinstance(node, ast.arg):
            bound_anywhere.add(node.arg)
        elif isinstance(node, ast.Name) and isinstance(node.ctx, ast.Store):
            bound_anywhere.add(node.id)
    for fam in sched.families():
        if fam.compiled:
            continue
        for r in sched.rows(fam.name):
            if r.name not in bound_anywhere:
                yield _v(
                    sf.display_path, 1,
                    f"schedule row {fam.name}.{r.name} is never bound in "
                    "runner.py — the registry row is orphaned (every "
                    "bare-plane schedule enters the unified runner as a "
                    "runtime jit arg)",
                )
    # Closure-const: a nested (traced) def reading a schedule array off
    # an object closed over from the enclosing function smuggles the
    # plane into the jaxpr as a const — the source-level twin of GC012.
    arrays = {
        r.name
        for r in sched.rows()
        if r.gather != "fold"
    }
    call_funcs = {
        id(node.func)
        for node in ast.walk(sf.ast_tree)
        if isinstance(node, ast.Call)
    }
    for top in ast.iter_child_nodes(sf.ast_tree):
        if isinstance(top, ast.FunctionDef):
            yield from _closure_consts(
                sf, top, set(), arrays, call_funcs,
                _traced_params(sf.ast_tree, top),
            )


def _traced_params(tree: ast.Module, top: ast.FunctionDef) -> Set[str]:
    """Parameters of the PRIVATE top-level body factory `top` (the scan
    body runner._runner_body) that provably arrive traced: every call of
    `top` in the module sits inside a nested def — a traced run function,
    never a constructor's own scope — and hands the parameter a name bound
    in that def's own scope (a schedule rebuilt from the jit's runtime
    args).  The factory's nested body may read schedule arrays off such a
    parameter; with no call in the module, or one that passes anything
    else, the parameter is a closure const like any other."""
    from ..core import walk_local

    if not top.name.startswith("_"):
        return set()
    positional = [a.arg for a in top.args.posonlyargs + top.args.args]
    ok: Set[str] = set(positional) | {a.arg for a in top.args.kwonlyargs}
    seen = False

    def visit(func: ast.FunctionDef, depth: int) -> None:
        nonlocal seen
        bound = _bound_names(func)
        for node in walk_local(func):
            if isinstance(node, ast.FunctionDef):
                visit(node, depth + 1)
            elif (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id == top.name
            ):
                seen = True
                passed = dict(zip(positional, node.args))
                passed.update(
                    {kw.arg: kw.value for kw in node.keywords if kw.arg}
                )
                for name, value in passed.items():
                    if isinstance(value, ast.Constant):
                        continue
                    if not (
                        depth > 0
                        and isinstance(value, ast.Name)
                        and value.id in bound
                    ):
                        ok.discard(name)

    for node in ast.iter_child_nodes(tree):
        if isinstance(node, ast.FunctionDef):
            visit(node, 0)
    return ok if seen else set()


def _closure_consts(
    sf: SourceFile,
    func: ast.FunctionDef,
    outer: Set[str],
    arrays: Set[str],
    call_funcs: Set[int],
    traced: Set[str] = frozenset(),
) -> Iterator[Violation]:
    from ..core import walk_local

    bound = _bound_names(func) - traced
    nested: List[ast.FunctionDef] = []
    for node in walk_local(func):
        if isinstance(node, ast.FunctionDef):
            nested.append(node)
            continue
        if (
            isinstance(node, ast.Attribute)
            and node.attr in arrays
            and id(node) not in call_funcs
            and isinstance(node.value, ast.Name)
            and node.value.id in outer
            and node.value.id not in bound
        ):
            yield _v(
                sf.display_path, node.lineno,
                f"`{node.value.id}.{node.attr}` reads the schedule array "
                f"{node.attr!r} off a closure variable inside a nested "
                "function — a closed-over schedule bakes the plane into "
                "the traced graph as a const (the GC012 hazard at trace "
                "time); thread it as a runtime jit arg through "
                "runner.schedule_args instead",
            )
    for child in nested:
        yield from _closure_consts(
            sf, child, outer | bound, arrays, call_funcs
        )


# --- hand-listed schedule tuples ---------------------------------------------


def _check_hand_lists(
    sched, files: Sequence[SourceFile]
) -> Iterator[Violation]:
    fam_arrays = {
        fam.name: {
            r.name for r in sched.rows(fam.name) if r.gather != "fold"
        }
        for fam in sched.families()
    }
    for mod in _RUNNER_MODULES:
        sf = _module_file(files, f"raft_tpu/multiraft/{mod}.py")
        if sf is None:
            continue
        for node in ast.walk(sf.ast_tree):
            if not isinstance(node, (ast.Tuple, ast.List)):
                continue
            # Store-context displays are unpacking TARGETS (the host
            # twins receive the one compile walk's arrays) — the drift
            # GC018 hunts is hand-ASSEMBLING a flat schedule tuple, a
            # Load-context display.
            if not isinstance(node.ctx, ast.Load):
                continue
            by_base: Dict[str, Set[str]] = {}
            for e in node.elts:
                if isinstance(e, ast.Attribute) and isinstance(
                    e.value, ast.Name
                ):
                    by_base.setdefault(e.value.id, set()).add(e.attr)
            for base, attrs in sorted(by_base.items()):
                for fname, arrays in sorted(fam_arrays.items()):
                    if len(attrs & arrays) >= 3:
                        yield _v(
                            sf.display_path, node.lineno,
                            f"hand-listed schedule tuple: {len(attrs & arrays)} "
                            f"{fname!r}-family arrays spelled off "
                            f"`{base}` in a display — the flat schedule "
                            "tuple must come from the registry "
                            "(runner.schedule_args / "
                            "schedules.array_fields), never be "
                            "re-enumerated (the drift GC018 exists to "
                            "delete)",
                        )
                        break  # one finding per display node


# --- the trace inventory -----------------------------------------------------


def _check_inventory(sched, ctx: Context) -> Iterator[Violation]:
    """inventory.py (outside the scanned set — tools/) must derive its
    runner rows from runner_variants() and hand-list no runner graph
    name (the GC016 overflow-drift discipline for the trace layer)."""
    path = ctx.repo_root / "tools" / "graftcheck" / "trace" / "inventory.py"
    if not path.is_file():
        return  # fixture repo_root: no linter checkout to audit
    try:
        tree = ast.parse(
            path.read_text(encoding="utf-8"), filename=_INVENTORY_REL
        )
    except (OSError, SyntaxError):
        yield _v(
            _INVENTORY_REL, 1,
            "inventory.py unreadable for the runner-derivation check",
        )
        return
    variant_names = {v.name for v in sched.runner_variants()}
    uses_accessor = any(
        isinstance(node, ast.Attribute)
        and node.attr == "runner_variants"
        for node in ast.walk(tree)
    )
    if not uses_accessor:
        yield _v(
            _INVENTORY_REL, 1,
            "inventory.py does not call runner_variants() — the compiled-"
            "runner GraphSpec rows must be derived from the schedule "
            "registry (schedules.py RUNNER_VARIANTS), never hand-listed",
        )
    for node in ast.walk(tree):
        literal: Optional[str] = None
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            if node.value in variant_names:
                literal = node.value
        elif isinstance(node, ast.JoinedStr):
            # f"reconfig_split{K}@..." hand-lists the name just as hard;
            # match the constant fragments with holes wildcarded.
            pat = "".join(
                v.value
                if isinstance(v, ast.Constant) and isinstance(v.value, str)
                else "*"
                for v in node.values
            )
            for name in sorted(variant_names):
                if fnmatch.fnmatchcase(name, pat):
                    literal = name
                    break
        if literal is not None:
            yield _v(
                _INVENTORY_REL, node.lineno,
                f"string literal matches runner variant {literal!r} — a "
                "hand-listed runner graph row; derive it from "
                "schedules.runner_variants() (GC018)",
            )
