"""The control of a deployment with damping on whose reads are ReadIndex
rounds (`fleet-100k-r5-readindex`: check-quorum, pre-vote,
`ReadOnlyOption::Safe`): the nearest weaker guarantee that would tempt a
later PR — answer a ReadIndex read without waiting for the acknowledging
majority (it saves the ctx heartbeat's response stream: a `[P, P, G]` plane
and a P-step loop every round).  A store that is cut off but alive keeps its
leaderships until its check-quorum boundary, up to two election timeouts,
while the rest of each group elects a successor and commits past it; the
ack quorum, counted strictly before the first higher-term member's nudge, is
all that keeps such a leader from answering.

`readindex_without_ack_quorum` patches the PROGRAM (never the benchmark) and
restores it: the ONE gate function, `sim._acks_before_nudge`, says yes, so
every alive leader that has committed in its own term passes — at the acting
leader (the probe, what a client is told) and at every other peer (the mask
the audit holds) alike.  `test_control_readindex_damped.py` holds the control
at G = 64; on the chip at the cell's own size (run by hand through the chip
tool; neither the benchmark's runs nor pytest run this):

    python3 benchmark/tests/control_readindex_damped.py <workload> <seed> [<seed> ...]

drives a whole run of the cell twice in one process — the program as it is,
then weakened — and prints every number `correct` compared, and `correct`.
The sound program must come out correct and the control not correct (by the
device's linearizability audit: `stale_read` / `dual_lease`), on every seed.
On a program that has no `sim._acks_before_nudge` (one from before PR 40:
its damped ReadIndex round was audited by nothing) there is nothing to patch
and the script says so and exits 3."""

import contextlib
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, HERE]

GATE = "_acks_before_nudge"


@contextlib.contextmanager
def readindex_without_ack_quorum():
    import jax.numpy as jnp

    from raft_tpu.multiraft import sim

    real = getattr(sim, GATE)

    def weak(st, ack_v, ndg_v, cnt_i, cnt_o, h):
        return jnp.ones(cnt_i.shape, bool)

    setattr(sim, GATE, weak)
    try:
        yield
    finally:
        setattr(sim, GATE, real)


def main(argv) -> int:
    """control_readindex.py's driver (sound, then weakened, every compared
    number printed) with this file's weakening in place of its own."""
    import control_readindex as stock
    from raft_tpu.multiraft import sim

    if not hasattr(sim, GATE):
        print(f"this program has no sim.{GATE}: nothing to weaken")
        return 3
    stock.readindex_without_ack_quorum = readindex_without_ack_quorum
    return stock.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
