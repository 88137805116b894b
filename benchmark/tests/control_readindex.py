"""The control of a deployment whose reads are ReadIndex rounds (raft-rs's
default Config: `fleet-100k-r5-stock`): the nearest weaker guarantee that
would tempt a later PR — answer a ReadIndex read without waiting for the
acknowledging majority (it saves the ctx heartbeat round: a [P, P, G] ack
plane and two quorum counts every round).  Without check-quorum nothing
deposes a leader that is cut off but alive, so with the majority gone that
leader goes on answering at its own commit index while the rest of its
group elects a successor and commits past it: reads stop being linearizable.

`readindex_without_ack_quorum` patches the PROGRAM (never the benchmark) and
restores it: every alive leader that has committed in its own term passes the
ReadIndex gate.  `test_control_readindex.py` holds the control at G = 64; on
the chip at the cell's own size (run by hand through the chip tool; neither
the benchmark's runs nor pytest run this):

    python3 benchmark/tests/control_readindex.py <workload> <seed> [<seed> ...]

drives a whole run of the cell twice in one process — the program as it is,
then weakened — and prints every number `correct` compared, and `correct`.
The sound program must come out correct and the control not correct (by the
device's linearizability audit: `stale_read`), on every seed.  On a program
that has no `sim.read_index_holders` (one from before PR 35) there is
nothing to patch and the script says so and exits 3."""

import contextlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, HERE]


@contextlib.contextmanager
def readindex_without_ack_quorum():
    from raft_tpu.multiraft import kernels, sim

    real = sim.read_index_holders

    def weak(cfg, st, crashed, link=None):
        return (
            (st.state == kernels.ROLE_LEADER)
            & ~crashed
            & (st.commit >= st.term_start_index)
        )

    sim.read_index_holders = weak
    try:
        yield
    finally:
        sim.read_index_holders = real


def main(argv) -> int:
    from benchmark import run
    from raft_tpu.multiraft import sim

    if not hasattr(sim, "read_index_holders"):
        print("this program has no sim.read_index_holders: nothing to weaken")
        return 3
    workload, seeds = argv[0], [int(s) for s in argv[1:]]
    bench = run.load_json(ROOT, "BENCHMARK.json")
    bad = 0
    for seed in seeds:
        verdicts = {}
        for weak in (False, True):
            lines = []
            ctx = readindex_without_ack_quorum() if weak else contextlib.nullcontext()
            with ctx:
                text = run.run_cell(bench, workload, seed, seconds=1.0, traced=False,
                                    say=lines.append)
            out = json.loads(text)
            verdicts[weak] = out["correct"]
            tag = "CONTROL" if weak else "sound  "
            for l in lines:
                if l.startswith("check "):
                    print(f"seed {seed} {tag} {l[:260]}", flush=True)
            print(f"seed {seed} {tag} correct={out['correct']} attempted={out['attempted']} "
                  f"failed={out['failed']}", flush=True)
        bad += verdicts[False] is not True or verdicts[True] is not False
    print("control holds on every seed" if not bad else f"{bad} seed(s) failed")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
