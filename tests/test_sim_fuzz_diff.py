"""Randomized DIFFERENTIAL fuzz: one full `sim.step` vs the scalar oracle
on random op sequences — hypothesis-free (seeded numpy RandomState), closing
the gap between the golden corpora (fixed schedules someone thought of) and
the parity proofs (graftcheck GC010's obligations say WHAT must match; this
drives unforeseen interleavings of crash flips, targeted leader kills, mass
recoveries, and bursty appends to check that it DOES).

Differs from tests/test_sim_fuzz.py (regression seeds + native engine) by
fuzzing the OP MIX per round — including the health planes riding along —
rather than replaying historical divergence schedules.

Tier-1 cost: the cheap cases run G=4 on the CPU backend (<5s each; the
plain case dropped 64 -> 48 rounds when a timing audit caught it creeping
past ~5s); the larger joint/learner configs are marked slow (long cases;
tier-1 takes 247 s of its 1470 s limit under xdist -n 6 at PR 32)."""

import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.multiraft import (
    ClusterSim,
    HealthOracle,
    ScalarCluster,
    SimConfig,
)

FIELDS = ("term", "state", "commit", "last_index", "last_term")


def _masks(P, G, voters, outgoing, learners):
    vm = np.zeros((P, G), bool)
    om = np.zeros((P, G), bool)
    lm = np.zeros((P, G), bool)
    for id in voters:
        vm[id - 1] = True
    for id in outgoing:
        om[id - 1] = True
    for id in learners:
        lm[id - 1] = True
    return jnp.asarray(vm), jnp.asarray(om), jnp.asarray(lm)


def run_diff(seed, G, P, rounds, config="plain", window=8):
    """One fuzz run: random per-round ops, exact per-round state AND
    health-plane parity."""
    if config == "joint":
        voters, outgoing, learners = [1, 2, 3], [3, 4, 5], []
    elif config == "learners":
        voters, outgoing, learners = list(range(1, P)), [], [P]
    else:
        voters, outgoing, learners = list(range(1, P + 1)), [], []
    kwargs = {"voters": voters}
    if outgoing:
        kwargs["voters_outgoing"] = outgoing
    if learners:
        kwargs["learners"] = learners
    scalar = ScalarCluster(G, P, **kwargs)
    oracle = HealthOracle(scalar, window=window)
    vm, om, lm = _masks(P, G, voters, outgoing, learners)
    sim = ClusterSim(
        SimConfig(
            n_groups=G, n_peers=P, collect_health=True, health_window=window
        ),
        vm,
        om,
        lm,
    )
    rng = np.random.RandomState(seed)
    crashed = np.zeros((G, P), bool)
    for r in range(rounds):
        # Random op mix per round: bit flips, targeted leader kills, mass
        # recovery, bursty appends.  A full-group outage is allowed for
        # VOTERS (commit stalls are part of the contract) but at least one
        # peer recovers when everyone is down, so runs terminate with some
        # traffic.
        for g in range(G):
            roll = rng.rand()
            if roll < 0.10:
                p = rng.randint(P)
                crashed[g, p] = not crashed[g, p]
            elif roll < 0.13:
                snap_state = [
                    int(scalar.networks[g].peers[p + 1].raft.state)
                    for p in range(P)
                ]
                leaders = [p for p, s in enumerate(snap_state) if s == 2]
                if leaders:
                    crashed[g, leaders[0]] = True
            elif roll < 0.16:
                crashed[g, :] = False
            if crashed[g].all():
                crashed[g, rng.randint(P)] = False
        burst = rng.rand() < 0.2
        append = rng.randint(0, 5 if burst else 2, size=G).astype(np.int64)

        oracle.round(crashed, append)  # drives scalar.round internally
        sim.run_round(
            jnp.asarray(crashed.T), jnp.asarray(append, dtype=jnp.int32)
        )

        want = scalar.snapshot()
        for f in FIELDS:
            got = np.asarray(getattr(sim.state, f), dtype=np.int64).T
            if not np.array_equal(want[f], got):
                bad = np.argwhere(want[f] != got)[0]
                raise AssertionError(
                    f"seed {seed} config {config} round {r}: field {f} "
                    f"group {bad[0]} peer {bad[1]}: "
                    f"scalar={want[f][bad[0], bad[1]]} "
                    f"device={got[bad[0], bad[1]]}"
                )
        got_planes = np.asarray(sim._health.planes)
        if not np.array_equal(got_planes, oracle.planes):
            bad = np.argwhere(got_planes != oracle.planes)[0]
            raise AssertionError(
                f"seed {seed} config {config} round {r}: health plane "
                f"{bad[0]} group {bad[1]}: oracle="
                f"{oracle.planes[bad[0], bad[1]]} "
                f"device={got_planes[bad[0], bad[1]]}"
            )


def test_diff_fuzz_plain_small():
    run_diff(0, G=4, P=3, rounds=48, config="plain")


def test_diff_fuzz_learners_small():
    run_diff(7, G=4, P=3, rounds=64, config="learners")


@pytest.mark.slow  # lockstep scalar sim at G=16/P=5: over the tier-1 budget
def test_diff_fuzz_joint_large():
    for seed in (11, 12):
        run_diff(seed, G=16, P=5, rounds=200, config="joint")


@pytest.mark.slow
def test_diff_fuzz_plain_large():
    for seed in (21, 22, 23):
        run_diff(seed, G=16, P=5, rounds=200, config="plain")
