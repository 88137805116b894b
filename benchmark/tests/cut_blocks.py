"""Cut a small chip recording out of a traced run of a split cell under a
chaos plan (`fleet-100k-r3.load-restart`), in `program_trace.export`'s format:

    python3 benchmark/tests/cut_blocks.py <out.json> [head [trace_dir]]

`program_trace.py export` keeps the HEAD of the first traced segment, which in
this cell is one general block (eight rounds, thousands of device events)
and never reaches a fused one.  This keeps every host span and the
device events of three blocks of the first traced segment: the first block
that ran the fused kernel, whole; the first that did not, its first `head`
events and, past them, those under the scopes a chaos plan adds to a block;
and the first block of store 1's down stretch — the phase change — likewise.
Run it on the machine that traced, from the checkout's root, after
`run.py --trace 1`; `data/program_trace_stores_restart.json` is its output
with `head` 330 (PR 51, TPU v5 lite; the name sorts last, so that every older
metric file keeps the recording `test_program_trace.py` showed it on).
"""

import bisect
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import program_trace as pt  # noqa: E402

KERNEL = "raft_steady_damped"
SCOPES = ("runner.block_planes", "runner.block_guard", "runner.guard_refusals")
UP_BLOCKS = 240 // 8  # the mix's first phase, in blocks: block 30 is store 1's first down


def cut(cap: pt.Capture, head: int):
    lo, _hi = pt.window(cap)
    ops = sorted(cap.ops, key=lambda o: o.start_ns)
    starts = [o.start_ns for o in ops]
    blocks = sorted((m for m in cap.modules if m.start_ns >= lo and "block_run" in m.name),
                    key=lambda m: m.start_ns)

    def events(m):
        span = ops[bisect.bisect_left(starts, m.start_ns):bisect.bisect_left(starts, m.end_ns)]
        return [o for o in span if o.plane == m.plane]

    def fused(m):
        return any(pt.has_kernel(o.path, KERNEL) for o in events(m))

    up = blocks[:UP_BLOCKS]
    keep = []
    for module, n in ((next(m for m in up if fused(m)), None),
                      (next(m for m in up if not fused(m)), head), (blocks[UP_BLOCKS], head)):
        inside = events(module)
        n = len(inside) if n is None else n
        keep += [module] + inside[:n] + [
            o for o in inside[n:] if any(pt.has_scope(o.path, s) for s in SCOPES)]
    return sorted(keep, key=lambda o: o.start_ns)


def main(argv) -> int:
    out = argv[0]
    head = int(argv[1]) if len(argv) > 1 else 330
    cap = pt.load(*argv[2:3])
    strings = {}

    def ref(text: str) -> int:
        return strings.setdefault(text, len(strings))

    rows = [[ref(o.plane), ref(o.line), ref(o.name if " custom-call(" in o.name else o.name[:96]),
             ref(o.path), o.start_ns, o.dur_ns] for o in cut(cap, head)]
    with open(out, "w", encoding="utf-8") as f:
        json.dump({"spans": [list(s) for s in cap.spans], "strings": list(strings), "ops": rows},
                  f, separators=(",", ":"))
    print(f"{out}: {len(rows)} device events, {len(cap.spans)} host spans")
    return 0


if __name__ == "__main__":
    sys.exit(main(argv=sys.argv[1:]))
