"""The rehearsal to run before spending chip time: every cell of
BENCHMARK.json end to end on the pinned CPU at G = 64, control flow only.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py [workload ...]

The untraced path must print a line that line.validate accepts; the traced
path must FAIL in the trace reduction, because a CPU has no device plane
and nothing may invent `busy_s`.  Its numbers describe no device and are
never recorded."""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import line, run, trace  # noqa: E402

G = 64


def main(argv) -> int:
    import jax

    if jax.devices()[0].platform != "cpu":
        sys.exit("rehearse.py is for the pinned CPU; use run.py on the chip")
    bench = run.load_json(ROOT, "BENCHMARK.json")
    names = argv or [w["name"] for w in bench["workloads"]]
    bad = 0
    for name in names:
        lines = []
        text = run.run_cell(bench, name, seed=2**31 + 5, seconds=1.0, traced=False,
                            say=lines.append, n_groups=G, devices=jax.devices())
        problems = [
            p for p in line.validate("\n".join(lines + [text]) + "\n", bench, name, False)
            if not p.startswith("device.memory_peak_bytes")  # a CPU reports none
        ]
        for info in lines:
            print(f"  | {info[:600]}")
        correct = json.loads(text)["correct"]
        print(f"{name} --trace 0: correct={correct} problems={problems}")
        print(f"  {text}")
        bad += bool(problems) or not correct
        try:
            run.run_cell(bench, name, seed=7, seconds=0.2, traced=True,
                         say=lambda _t: None, n_groups=G, devices=jax.devices())
        except trace.TraceError as e:
            print(f"{name} --trace 1: refused as it must be on a CPU: {e}")
        else:
            print(f"{name} --trace 1: produced a line on a CPU — it must not")
            bad += 1
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
