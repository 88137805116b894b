"""100 * (self time of the device ops the program did NOT name) / busy, by
what they are.  Five classes partition busy:

  scoped      the op's name stack passes a scope or kernel of the program's
              catalogue (`raft_tpu.profiling`, read as `run.program_names`
              reads it: an older program is held against its own, smaller
              catalogue) — what the `scope_share` files read by name;
  copies      NO name stack and the instruction is a `copy`, `copy-start`,
              `copy-done` or their sliced forms `slice-start` /
              `slice-done` (COPIES): the compiler's own data movement —
              layout changes, materialised carries, prefetches into the
              fast memory;
  containers  the `while` / `conditional` / `call` events themselves.  They
              carry no name stack; self time (`trace.self_seconds`) is the
              event's less the ops nested in it: the loop's bookkeeping, the
              conditional's hand-over;
  fusions     NO name stack and the instruction is a `fusion`: a fusion is
              named after its root, and the compiler's layout fusions and
              its multi-output fusions (a tuple for a root: the stacked
              outputs of an unrolled loop) have none to give;
  unscoped    every other event: a named op the program leaves outside its
              catalogue, and the odd unnamed instruction of another kind.

`args["what"]` picks the class (`copies`, `containers`, `fusions`,
`unscoped`).  With `{"what": "copies" | "fusions", "near": <scope>}` only
the events whose nearest PRECEDING named op on the same device line carries
`<scope>` count: one program's ops run in scheduled order and a section's
ops are consecutive, so a copy between two ops of a section is that
section's, and one before the line's first named op is nobody's.  A
`copy-start` / `copy-done` pair is two events on the `XLA Ops` line, the
issue and the wait; the transfer between them runs beside the ops that
follow the start and shows in THEIR time, not here.  Each of the two is
placed by the named op before it, so a pair around a section's last op is
split between that section and the next.

The reader asks the program for no name, so it has no `names`: it reads a
number off a program of any age.  A class that no op of the window is in
reads 0.0, a measured zero; None only where the window holds no device op,
or no op carries the scope `near` asks for (nothing to be near to).

One pass over the capture serves every metric file of a run: the result is
kept in `facts` under the key `unnamed_share`, beside the capture it was
made from.
"""

import re
from typing import Dict, Optional

from .. import program_trace as pt
from .. import trace

# By the instruction's opcode or by its own name: the sliced prefetch is an
# `async-start` / `async-done` pair NAMED `slice-start.4` / `slice-done.4`, and
# jax's `lax.cond` is a `conditional` named `cond.38`.
COPIES = frozenset({"copy", "copy-start", "copy-done", "slice-start", "slice-done"})
CONTAINERS = frozenset({"while", "conditional", "call", "cond"})
_OPCODE = re.compile(r"[\)\}\]] ([a-z][a-z0-9\-]*)\(")  # `...} fusion(`: trace.short_name's


def catalogue() -> frozenset:
    """The scopes and kernels of the program under test, as
    `run.program_names` reads them: none on a program without a catalogue."""
    try:
        from raft_tpu import profiling
    except ImportError:
        return frozenset()
    return frozenset(getattr(profiling, "SCOPES", ())) | frozenset(getattr(profiling, "KERNELS", ()))


def kinds(text: str) -> frozenset:
    """What an op event's name (the whole instruction text, `%copy-done.7 =
    s32[5,100000]{...} copy-done(...)`) says the instruction is: its own
    name up to the first dot and, where a recording did not cut the text
    before it, its HLO opcode."""
    head, sep, rest = text.partition(" = ")
    found = _OPCODE.search(rest) if sep else None
    stem = head.strip().lstrip("%").split(".")[0]
    return frozenset({stem, found.group(1)} if found else {stem})


def classify(name: str, path: str, known) -> str:
    if path:
        parts = path.rstrip(":").split("/")
        return "scoped" if any(p in known for p in parts) else "unscoped"
    what = kinds(name)
    if what & COPIES:
        return "copies"
    if what & CONTAINERS:
        return "containers"
    # XLA names a fusion `fusion.7` or after what it fused, `..._fusion.7`.
    fused = any(k == "fusion" or k.endswith("_fusion") for k in what)
    return "fusions" if fused else "unscoped"


NEARABLE = ("copies", "fusions")  # placed by the named op before them


def parts(facts: dict) -> Optional[dict]:
    """{"scoped" | "containers" | "unscoped": self seconds, "copies" |
    "fusions": {name stack of the named op before it ("" where none): self
    seconds}, "paths": the name stacks seen}, mean over the device planes;
    None where no device op ran in the window."""
    cap = facts["capture"]
    kept = facts.get("unnamed_share")
    if kept is not None and kept[0] is cap:
        return kept[1]
    planes = pt.planes(cap)
    out = None
    if planes and cap.ops:
        lo, hi = pt.window(cap)
        known = catalogue()
        out = {"scoped": 0.0, "containers": 0.0, "unscoped": 0.0,
               "copies": {}, "fusions": {}, "paths": set()}
        for plane in planes:
            before = ""  # the name stack of the last named op on this line
            events = []
            for op in sorted((o for o in cap.ops if o.plane == plane),
                             key=lambda o: (o.start_ns, -o.dur_ns)):
                cls = classify(op.name, op.path, known)
                if op.path:
                    before = op.path
                    out["paths"].add(op.path)
                label = f"{cls} {before}" if cls in NEARABLE else cls
                events.append(trace.Event(op.plane, op.line, label, op.start_ns, op.dur_ns))
            for label, (sec, _calls) in trace.self_seconds(events, lo, hi).items():
                cls, _sep, near = label.partition(" ")
                if cls in NEARABLE:
                    out[cls][near] = out[cls].get(near, 0.0) + sec / len(planes)
                else:
                    out[cls] += sec / len(planes)
    facts["unnamed_share"] = (cap, out)
    return out


def read(facts, args):
    found = parts(facts)
    if found is None:
        return None
    what, near = args["what"], args.get("near")
    if what in NEARABLE:
        if near is not None and not any(pt.has_scope(p, near) for p in found["paths"]):
            return None
        seconds = sum(sec for path, sec in found[what].items()
                      if near is None or pt.has_scope(path, near))
    else:
        seconds = found[what]
    return 100.0 * seconds / facts["trace"]["busy_s"]


# --- by hand: the round's map --------------------------------------------------

# Scopes that only wrap a whole round: the map looks through them.
WRAPPERS = ("runner.general_arm", "round", "round.damped", "round.linked")


def round_map(facts: dict, sections=()) -> Dict[str, object]:
    """What `PERF.md` §5 tabulates, in % of busy: `classes` (the five,
    summing to 100), `by_scope` (the scoped class by the outermost catalogue
    scope of each op's name stack, the WRAPPERS looked through: an op under
    nothing but wrappers goes to the innermost of them) and `copies_near` /
    `fusions_near` (those two classes by each scope of `sections`)."""
    found = parts(facts)
    busy = facts["trace"]["busy_s"]
    known = catalogue()
    by_scope: Dict[str, float] = {}

    def row(path: str) -> str:
        hit = [p for p in path.rstrip(":").split("/") if p in known]
        inner = [p for p in hit if p not in WRAPPERS]
        return inner[0] if inner else hit[-1]

    cap = facts["capture"]
    lo, hi = pt.window(cap)
    planes = pt.planes(cap)
    for plane in planes:
        for path, (sec, _n) in trace.self_seconds(pt.path_events(cap, plane), lo, hi).items():
            if path and classify("", path, known) == "scoped":
                by_scope[row(path)] = by_scope.get(row(path), 0.0) + sec / len(planes)
    pct = lambda s: 100.0 * s / busy  # noqa: E731
    classes = {k: pct(sum(v.values()) if isinstance(v, dict) else v)
               for k, v in found.items() if k != "paths"}
    return {
        "busy_s": busy,
        "classes": classes,
        "by_scope": {k: pct(v) for k, v in sorted(by_scope.items(), key=lambda kv: -kv[1])},
        **{f"{what}_near": {s: read(facts, {"what": what, "near": s}) for s in sections}
           for what in NEARABLE},
    }


if __name__ == "__main__":
    # python3 -m benchmark.reducers.unnamed_share [trace_dir] [section ...]
    import json
    import sys

    cap = pt.load(*sys.argv[1:2])
    json.dump(round_map(pt.facts_of(cap), sys.argv[2:]), sys.stdout, indent=1)
