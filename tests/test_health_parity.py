"""Device health-plane parity: the [N_HEALTH_PLANES, G] int32 planes
maintained inside the jitted sim step must equal the scalar HealthOracle's
planes after every round of an identical seeded schedule — the fleet-health
face of the bit-identical-trajectory claim (tests/test_sim_parity.py).

Also: unit coverage for the health kernels (zero_health, update_health,
health_summary) including the lax.top_k worst-offender extraction against a
host-side stable argsort.

Tier-1 cases stay at G <= 8 on the CPU backend; the G=64 staggered
partition-stall scenario is marked slow (a long case;
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
from raft_tpu.multiraft.kernels import (
    HEALTH_COUNT_NAMES,
    HEALTH_PLANE_NAMES,
    HP_LEADERLESS,
    HP_SINCE_COMMIT,
    HP_TERM_BUMPS,
    HP_VOTE_SPLITS,
    N_HEALTH_COUNTS,
    N_HEALTH_PLANES,
    health_summary,
    update_health,
    zero_health,
)


def run_parity(G, P, rounds, schedule, window=8, seed_note=""):
    """Drive the same schedule through ClusterSim(collect_health) and the
    scalar HealthOracle; assert exact plane equality after every round."""
    oracle = HealthOracle(ScalarCluster(G, P), window=window)
    sim = ClusterSim(
        SimConfig(
            n_groups=G, n_peers=P, collect_health=True, health_window=window
        )
    )
    for r in range(rounds):
        crashed, append = schedule(r)
        oracle.round(crashed, append)
        sim.run_round(
            jnp.asarray(crashed.T), jnp.asarray(append, dtype=jnp.int32)
        )
        got = np.asarray(sim._health.planes)
        want = oracle.planes
        if not np.array_equal(got, want):
            bad = np.argwhere(got != want)
            pl, g = bad[0]
            raise AssertionError(
                f"{seed_note} round {r}: health plane "
                f"{HEALTH_PLANE_NAMES[pl]} mismatch at group {g}: "
                f"oracle={want[pl, g]} device={got[pl, g]}\n"
                f"oracle planes:\n{want}\ndevice planes:\n{got}"
            )


def test_health_plane_names_cover_planes():
    assert len(HEALTH_PLANE_NAMES) == N_HEALTH_PLANES
    assert len(HEALTH_COUNT_NAMES) == N_HEALTH_COUNTS


def test_health_disabled_by_default():
    # No run_round: the disabled accessors must raise before any jit work,
    # so this test never pays a compile.
    sim = ClusterSim(SimConfig(n_groups=8, n_peers=3))
    with pytest.raises(RuntimeError):
        sim.health()
    with pytest.raises(RuntimeError):
        sim.explain(0)


def test_parity_elections_stall_recovery_g8():
    """The tier-1 parity case: cold-start election storm, then a majority
    partition (leaderless + vote-split churn + commit stall), then
    recovery — every plane moves."""
    G, P = 8, 3

    def schedule(r):
        crashed = np.zeros((G, P), bool)
        if 20 <= r < 45:
            crashed[:, [0, 1]] = True  # majority down
        append = np.full(G, r % 2, np.int64)
        return crashed, append

    run_parity(G, P, 60, schedule)


@pytest.mark.slow  # second lockstep scalar sim + a fresh 5-peer jit graph
def test_parity_minority_crash_5_peers():
    G, P = 4, 5

    def schedule(r):
        crashed = np.zeros((G, P), bool)
        if 15 <= r < 30:
            crashed[:, 0] = True  # minority: commits keep flowing
        append = np.array([1, 0, 2, 0], np.int64)
        return crashed, append

    run_parity(G, P, 40, schedule)


@pytest.mark.slow  # lockstep scalar sim at G=64: far over the tier-1 budget
def test_parity_g64_staggered_partition_stall():
    """G=64 staggered partitions: group blocks lose their majority in
    overlapping windows, so at any time some groups are stalled, some are
    churning, and some are healthy — the summary's threshold counts and
    the worst-offender extraction see a mixed fleet."""
    G, P = 64, 3

    def schedule(r):
        crashed = np.zeros((G, P), bool)
        for block in range(4):
            lo = 20 + 10 * block
            if lo <= r < lo + 25:
                crashed[block * 16 : (block + 1) * 16, [0, 1]] = True
        append = np.full(G, 1, np.int64)
        return crashed, append

    run_parity(G, P, 80, schedule, window=16)

    # And the end-state summary reflects a genuinely mixed fleet.
    oracle = HealthOracle(ScalarCluster(G, P), window=16)
    sim = ClusterSim(
        SimConfig(
            n_groups=G,
            n_peers=P,
            collect_health=True,
            health_window=16,
            leaderless_stall_ticks=8,
        )
    )
    for r in range(70):
        crashed, append = schedule(r)
        sim.run_round(
            jnp.asarray(crashed.T), jnp.asarray(append, dtype=jnp.int32)
        )
    s = sim.health()
    assert s["counts"]["stalled_leaderless"] > 0
    assert s["counts"]["leaderless"] >= s["counts"]["stalled_leaderless"]
    assert s["worst"][0]["score"] > 0
    assert sum(s["lag_hist"]) == G


# --- kernel unit coverage (GC006: every public kernel exercised) ---


def test_zero_health_shape():
    z = np.asarray(zero_health(5))
    assert z.shape == (N_HEALTH_PLANES, 5)
    assert z.dtype == np.int32
    assert not z.any()


def test_update_health_fold_rules():
    planes = zero_health(3)
    # Round 1 (window_pos 0): no leader anywhere, no commits, a split.
    planes, pos = update_health(
        planes,
        jnp.int32(0),
        4,
        jnp.asarray([False, False, False]),
        jnp.asarray([False, False, False]),
        jnp.asarray([1, 0, 0], jnp.int32),
        jnp.asarray([True, False, False]),
    )
    np.testing.assert_array_equal(
        np.asarray(planes),
        [[1, 1, 1], [1, 1, 1], [1, 0, 0], [1, 0, 0]],
    )
    assert int(pos) == 1
    # Round 2: group 0 gets a leader + commit; bumps accumulate in-window.
    planes, pos = update_health(
        planes,
        pos,
        4,
        jnp.asarray([True, False, False]),
        jnp.asarray([True, False, False]),
        jnp.asarray([0, 2, 0], jnp.int32),
        jnp.asarray([False, False, False]),
    )
    np.testing.assert_array_equal(
        np.asarray(planes),
        [[0, 2, 2], [0, 2, 2], [1, 2, 0], [1, 0, 0]],
    )
    assert int(pos) == 2


def test_update_health_window_reset():
    planes = zero_health(1)
    pos = jnp.int32(0)
    for r in range(5):  # window 4: round 4 starts a fresh window
        planes, pos = update_health(
            planes,
            pos,
            4,
            jnp.asarray([True]),
            jnp.asarray([True]),
            jnp.asarray([1], jnp.int32),
            jnp.asarray([False]),
        )
    # rounds 0-3 accumulate 4 bumps, round 4 resets then adds 1.
    assert int(np.asarray(planes)[HP_TERM_BUMPS][0]) == 1
    assert int(pos) == 1


def test_health_summary_counts_and_hist():
    G = 6
    planes = np.zeros((N_HEALTH_PLANES, G), np.int32)
    planes[HP_LEADERLESS] = [0, 1, 5, 16, 0, 0]
    planes[HP_SINCE_COMMIT] = [0, 0, 3, 40, 64, 7]
    planes[HP_TERM_BUMPS] = [0, 4, 0, 9, 0, 0]
    planes[HP_VOTE_SPLITS] = [0, 2, 0, 5, 0, 0]
    counts, hist, ids, scores = health_summary(
        jnp.asarray(planes), 16, 32, 4, 3
    )
    counts = dict(zip(HEALTH_COUNT_NAMES, np.asarray(counts)))
    assert counts == {
        "leaderless": 3,
        "stalled_leaderless": 1,
        "commit_stalled": 2,
        "churning": 2,
    }
    # lag 0,0 -> bucket 0; 3 -> [2,4); 7 -> [4,8); 40 -> [32,64); 64 -> last
    np.testing.assert_array_equal(
        np.asarray(hist), [2, 0, 1, 1, 0, 0, 1, 1]
    )
    np.testing.assert_array_equal(np.asarray(ids), [4, 3, 5])
    np.testing.assert_array_equal(np.asarray(scores), [64, 40, 7])
    assert int(np.asarray(hist).sum()) == G


def test_topk_matches_host_argsort():
    """lax.top_k worst-offender IDs == a stable host argsort of -score,
    ties and all."""
    rng = np.random.RandomState(7)
    G, k = 50, 8
    planes = np.zeros((N_HEALTH_PLANES, G), np.int32)
    planes[HP_LEADERLESS] = rng.randint(0, 5, G)
    planes[HP_SINCE_COMMIT] = rng.randint(0, 5, G)  # many ties
    _, _, ids, scores = health_summary(jnp.asarray(planes), 16, 32, 4, k)
    score = np.maximum(planes[HP_SINCE_COMMIT], planes[HP_LEADERLESS])
    want = np.argsort(-score, kind="stable")[:k]
    np.testing.assert_array_equal(np.asarray(ids), want)
    np.testing.assert_array_equal(np.asarray(scores), score[want])


def test_explain_matches_planes():
    # Same (G, P, collect_health) shape as the parity case: jit-cache hit.
    G, P = 8, 3
    cfg = SimConfig(n_groups=G, n_peers=P, collect_health=True, health_window=8)
    sim = ClusterSim(cfg)
    crashed = np.zeros((P, G), bool)
    crashed[:2, 2] = True  # group 2 loses its majority
    for _ in range(30):
        sim.run_round(jnp.asarray(crashed), jnp.ones((G,), jnp.int32))
    info = sim.explain(2)
    planes = np.asarray(sim._health.planes)
    assert info["group"] == 2
    for i, name in enumerate(HEALTH_PLANE_NAMES):
        assert info["health"][name] == planes[i, 2]
    assert len(info["peers"]["term"]) == P
    assert info["health"]["ticks_since_commit"] > 0


# --- GC010 parity obligations (tools/graftcheck/parity_obligations.json) ---


def test_health_obligations_exercised():
    """Every obligation assigned to this suite (the health kernels) must be
    exercised HERE: the run_parity harness drives zero_health/update_health
    through ClusterSim(collect_health=True) every round, and the unit tests
    above call all three kernels directly.  A new health kernel fails this
    until the suite covers it."""
    import json
    from pathlib import Path

    base = Path(__file__).resolve().parent.parent
    doc = json.loads(
        (base / "tools" / "graftcheck" / "parity_obligations.json").read_text(
            encoding="utf-8"
        )
    )
    mine = {
        o["kernel"]
        for o in doc["obligations"]
        if o["parity_suite"].endswith("test_health_parity.py")
    }
    assert mine == {"zero_health", "update_health", "health_summary"}
    for o in doc["obligations"]:
        if o["parity_suite"].endswith("test_health_parity.py"):
            assert "tests/test_health_parity.py" in o["tests"], (
                f"obligation {o['kernel']} is assigned to this suite but "
                "not exercised by it"
            )
