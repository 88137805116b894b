"""The control at the cell's own size, on the chip (run by hand through the
chip tool; neither the benchmark's runs nor pytest run it):

    python3 benchmark/tests/control_on_chip.py <workload> [<weakening>] <seed> [<seed> ...]

For each seed it drives a whole run of the cell twice in this one process —
the program as it is, then the program weakened by the context manager
`<weakening>` of weaken.py (default `lease_without_quorum_gate`; for a cell
whose membership changes, `joint_commit_on_incoming_only`) — and prints every
number `correct` compared, and `correct`.  The sound program must come out
correct and the control not correct, on every seed."""

import contextlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, HERE]

import weaken  # noqa: E402
from benchmark import run  # noqa: E402


def main(argv) -> int:
    workload, rest = argv[0], argv[1:]
    weakening = rest.pop(0) if not rest[0].isdigit() else "lease_without_quorum_gate"
    seeds = [int(s) for s in rest]
    bench = run.load_json(ROOT, "BENCHMARK.json")
    bad = 0
    for seed in seeds:
        verdicts = {}
        for weak in (False, True):
            lines = []
            ctx = getattr(weaken, weakening)() if weak else contextlib.nullcontext()
            with ctx:
                text = run.run_cell(bench, workload, seed, seconds=1.0, traced=False,
                                    say=lines.append)
            verdicts[weak] = json.loads(text)["correct"]
            for l in lines:
                if l.startswith("check "):
                    print(f"seed {seed} {'CONTROL' if weak else 'sound  '} {l[:260]}", flush=True)
            print(f"seed {seed} {'CONTROL' if weak else 'sound  '} correct={verdicts[weak]}", flush=True)
        bad += verdicts[False] is not True or verdicts[True] is not False
    print("control holds on every seed" if not bad else f"{bad} seed(s) failed")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
