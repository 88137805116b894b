"""Compiled client-workload tests (ISSUE 13; raft_tpu/multiraft/workload).

Layers:
  * schedule compilation: CompiledClient vs HostClientSchedule bit-equality
    (one `_compile_arrays` walk feeds both, incl. the seeded Zipf draws);
  * latency_percentiles vs the profiling.py nearest-rank rule on raw
    sample lists;
  * end-to-end read accounting: the jitted workload scan's read stats +
    latency histogram + receipts vs a host replay driving
    simref.ReadOracle through the identical schedules (the retry/drop
    protocol mirrored in plain python);
  * the golden chaos corpus + the reconfig corpus replayed WITH reads
    through the workload runner: zero safety violations, including the
    new linearizability slots, damped and undamped.
"""

import json
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from raft_tpu.multiraft import ClusterSim, ScalarCluster, SimConfig, sim
from raft_tpu.multiraft import chaos, kernels, reconfig, workload
from raft_tpu.multiraft import runner as runner_mod
from raft_tpu.multiraft.simref import ReadOracle

TESTDATA = os.path.join(os.path.dirname(__file__), "testdata")


def load_corpus(kind):
    with open(os.path.join(TESTDATA, kind, "plans.json")) as f:
        return json.load(f)


def mixed_read_plan(n_peers, rounds, seed=5, settle=16):
    """A read/write mix spanning `rounds`: settle, then interleaved
    lease/safe read phases with Zipf writes."""
    body = rounds - settle
    a = body // 2
    return workload.ClientPlan(
        name="mixed",
        n_peers=n_peers,
        phases=[
            workload.ClientPhase(rounds=settle, append=1),
            workload.ClientPhase(
                rounds=a, write_zipf=1.9, write_max=4, read_every=2,
                read_mode="lease",
            ),
            workload.ClientPhase(
                rounds=body - a, append=1, read_every=1, read_mode="safe"
            ),
        ],
        seed=seed,
    )


# --- schedule compilation twins ------------------------------------------


def test_compiled_client_matches_host_schedule():
    plan = mixed_read_plan(3, 48)
    G = 11  # awkward width: packing pads to 32
    compiled = workload.compile_plan(plan, G)
    host = workload.HostClientSchedule(plan, G)
    assert compiled.n_rounds == host.n_rounds == plan.n_rounds
    fire_dev = np.asarray(
        kernels.unpack_bits_g(compiled.read_fire_packed, G)
    )
    assert np.array_equal(fire_dev, host.read_fire)
    assert np.array_equal(np.asarray(compiled.read_mode), host.read_mode)
    assert np.array_equal(np.asarray(compiled.append), host.append)
    # Zipf draws are seeded: recompiling reproduces them bit-for-bit.
    again = workload.compile_plan(plan, G)
    assert np.array_equal(
        np.asarray(again.append), np.asarray(compiled.append)
    )
    # ...and a different seed moves them.
    plan2 = mixed_read_plan(3, 48, seed=6)
    other = workload.compile_plan(plan2, G)
    assert not np.array_equal(
        np.asarray(other.append), np.asarray(compiled.append)
    )


def test_plan_json_round_trip():
    doc = {
        "name": "zm",
        "peers": 5,
        "seed": 7,
        "phases": [
            {"rounds": 8, "append": 1},
            {"rounds": 8, "write_zipf": 1.8, "read_every": 2,
             "read_mode": "lease", "groups": {"mod": 2, "eq": 1}},
        ],
    }
    plan = workload.plan_from_dict(doc)
    assert plan.n_rounds == 16
    assert plan.phases[1].read_mode == "lease"
    c = workload.compile_plan(plan, 6)
    modes = np.asarray(c.read_mode)
    assert set(np.unique(modes[1])) == {0, sim.READ_LEASE}
    with pytest.raises(ValueError, match="read_mode"):
        workload.plan_from_dict(
            {"name": "x", "peers": 3,
             "phases": [{"rounds": 4, "read_mode": "stale"}]}
        )


def test_latency_percentiles_nearest_rank():
    rng = np.random.RandomState(0)
    for _ in range(16):
        n = rng.randint(0, 200)
        samples = rng.randint(
            0, workload.N_LAT_BUCKETS + 8, size=n
        )  # incl. overflow past the cap
        clipped = np.minimum(samples, workload.LAT_CAP)
        hist = np.bincount(clipped, minlength=workload.N_LAT_BUCKETS)
        got = np.asarray(
            workload.latency_percentiles(jnp.asarray(hist, jnp.int32))
        )
        for i, q in enumerate((50, 90, 99)):
            want = workload.host_latency_percentile(clipped, q)
            assert got[i] == want, (n, q, got[i], want)
    # Empty histogram: -1 sentinel everywhere.
    empty = np.asarray(
        workload.latency_percentiles(
            jnp.zeros((workload.N_LAT_BUCKETS,), jnp.int32)
        )
    )
    assert (empty == -1).all()


# --- end-to-end: workload scan vs oracle-driven host replay ---------------


def host_replay(cfg, client_plan, chaos_plan=None, each_round=None):
    """Mirror the workload runner's retry/drop protocol in plain python,
    driving simref.ReadOracle (real scalar pumps on throwaway copies) for
    every receipt; returns (read stats, latency hist, oracle).
    `each_round(cluster, crashed[P, G], pending[G])` is called at every
    round's end (`pending`: the mode of each group's read in flight)."""
    G, P = cfg.n_groups, cfg.n_peers
    cl = ScalarCluster(
        G, P, election_tick=cfg.election_tick,
        heartbeat_tick=cfg.heartbeat_tick,
        check_quorum=cfg.check_quorum, pre_vote=cfg.pre_vote,
    )
    oracle = ReadOracle(
        cl, election_tick=cfg.election_tick, lease_read=cfg.lease_read
    )
    csched = workload.HostClientSchedule(client_plan, G)
    hsched = (
        chaos.HostSchedule(chaos_plan, G) if chaos_plan is not None else None
    )
    pending = np.zeros(G, np.int32)
    since = np.zeros(G, np.int32)
    stats = np.zeros(workload.N_READ_STATS, np.int64)
    hist = np.zeros(workload.N_LAT_BUCKETS, np.int64)
    for r in range(csched.n_rounds):
        fire, mode_row, capp = csched.masks(r)
        if hsched is not None:
            link, crashed, app = hsched.masks(r)
            app = app + capp
        else:
            link = None
            crashed = np.zeros((P, G), bool)
            app = capp
        fire = fire & (mode_row > 0)
        fresh = fire & (pending == 0)
        dropped = fire & (pending > 0)
        pending = np.where(fresh, mode_row, pending)
        since = np.where(fresh, r, since)
        oracle.round(
            crashed.T, app, link, read_propose=pending
        )
        rec = oracle.last_receipts
        served = np.array([i >= 0 for i, _, _ in rec]) & (pending > 0)
        lease = np.array([l for _, l, _ in rec])
        deg = np.array([d for _, _, d in rec])
        stats[workload.RS_ISSUED] += fresh.sum()
        stats[workload.RS_SERVED_LEASE] += (served & lease).sum()
        stats[workload.RS_SERVED_QUORUM] += (served & ~lease).sum()
        stats[workload.RS_DEGRADED_SERVES] += (served & deg).sum()
        stats[workload.RS_RETRY_ROUNDS] += ((pending > 0) & ~served).sum()
        stats[workload.RS_DROPPED_FIRES] += dropped.sum()
        for g in np.where(served)[0]:
            hist[min(r - since[g], workload.LAT_CAP)] += 1
        pending = np.where(served, 0, pending)
        since = np.where(served, 0, since)
        if each_round is not None:
            each_round(cl, crashed, pending)
    return stats, hist, oracle


def run_workload_vs_replay(cfg, client_plan, chaos_plan=None):
    cs = ClusterSim(cfg)
    compiled_chaos = (
        chaos.compile_plan(chaos_plan, cfg.n_groups)
        if chaos_plan is not None
        else None
    )
    compiled = workload.compile_plan(client_plan, cfg.n_groups)
    runner = runner_mod.make_runner(cfg, (compiled, compiled_chaos))
    rst = reconfig.init_reconfig_state(cs.state)
    rcar = workload.init_read_carry(cfg.n_groups)
    out = runner(cs.state, cs._health, rst, rcar)
    st, hl, _rst, stats, rstats, safety, rcarf, rdstats, lat_hist = out
    want_stats, want_hist, oracle = host_replay(
        cfg, client_plan, chaos_plan
    )
    got_stats = np.asarray(rdstats)
    got_hist = np.asarray(lat_hist)
    assert np.array_equal(got_stats, want_stats), (
        f"read stats diverged: device {got_stats} != host {want_stats}"
    )
    assert np.array_equal(got_hist, want_hist), "latency hist diverged"
    # The lockstep state parity composes (receipts came from copies).
    snap = oracle.cluster.snapshot()
    for key in ("term", "state", "commit", "last_index"):
        assert np.array_equal(
            np.asarray(getattr(st, key)).T, snap[key]
        ), f"{key} diverged"
    return np.asarray(safety), np.asarray(rdstats)


def test_workload_scan_matches_host_replay_undamped():
    cfg = SimConfig(
        n_groups=6, n_peers=3, collect_health=True
    )
    safety, rdstats = run_workload_vs_replay(cfg, mixed_read_plan(3, 56))
    assert (safety == 0).all(), safety
    assert rdstats[workload.RS_ISSUED] > 0
    # Undamped: every lease request degrades; nothing serves by lease.
    assert rdstats[workload.RS_SERVED_LEASE] == 0
    assert rdstats[workload.RS_DEGRADED_SERVES] > 0


@pytest.mark.slow  # its own damped scan compile; tier-1 keeps the
# undamped replay (same accounting code path) and per-round cq receipt
# parity lives tier-1 in tests/test_read_lease.py (the budget ceiling)
def test_workload_scan_matches_host_replay_cq():
    cfg = SimConfig(
        n_groups=6, n_peers=3, collect_health=True, check_quorum=True,
        lease_read=True,
    )
    safety, rdstats = run_workload_vs_replay(cfg, mixed_read_plan(3, 56))
    assert (safety == 0).all(), safety
    assert rdstats[workload.RS_SERVED_LEASE] > 0


@pytest.mark.slow  # a third damped compile (cq+pv) + chaos composition
def test_workload_scan_matches_host_replay_chaos_cq_pv():
    cfg = SimConfig(
        n_groups=4, n_peers=3, collect_health=True, check_quorum=True,
        pre_vote=True, lease_read=True,
    )
    cplan = chaos.ChaosPlan(
        name="wl-chaos",
        n_peers=3,
        phases=[
            chaos.ChaosPhase(rounds=16, append=1),
            chaos.ChaosPhase(
                rounds=24, partition=[[1], [2, 3]], loss_all=0.05,
                append=1,
            ),
            chaos.ChaosPhase(rounds=16, append=1),
        ],
    )
    safety, rdstats = run_workload_vs_replay(
        cfg, mixed_read_plan(3, 56), cplan
    )
    assert (safety == 0).all(), safety
    # The partition forces retries/stalls somewhere.
    assert rdstats[workload.RS_RETRY_ROUNDS] > 0


# --- golden corpora with reads: the linearizability slots stay zero -------


def read_overlay_for(n_rounds, n_peers, mode="lease"):
    """Reads every round across the whole scenario (the harshest overlay:
    a lease serve is attempted at every round of every fault window)."""
    return workload.ClientPlan(
        name="overlay",
        n_peers=n_peers,
        phases=[
            workload.ClientPhase(
                rounds=n_rounds, read_every=1, read_mode=mode
            )
        ],
    )


def replay_corpus_with_reads(damped: bool, mode: str, names=None):
    plans = load_corpus("chaos")
    for doc in plans:
        plan = chaos.plan_from_dict(doc)
        if names is not None and plan.name not in names:
            continue
        cfg = SimConfig(
            n_groups=8, n_peers=plan.n_peers, collect_health=True,
            check_quorum=damped, pre_vote=damped, lease_read=damped,
        )
        cs = ClusterSim(cfg)
        report = cs.run_reads(
            read_overlay_for(plan.n_rounds, plan.n_peers, mode),
            chaos_plan=plan,
        )
        assert not any(report["safety"].values()), (
            f"{plan.name} damped={damped} mode={mode}: "
            f"{report['safety']}"
        )
        assert report["reads_issued"] > 0


def test_golden_chaos_corpus_with_lease_reads_undamped_head():
    # Tier-1 keeps the first scenario; the full sweep is slow below.
    plans = load_corpus("chaos")
    replay_corpus_with_reads(False, "lease", names={plans[0]["name"]})


@pytest.mark.slow  # full corpus x {damped, undamped}; every scenario is
# its own scan compile, so the safe-mode sweep stays with the storm suite
def test_golden_chaos_corpus_with_reads_full():
    replay_corpus_with_reads(False, "lease")
    replay_corpus_with_reads(True, "lease")


@pytest.mark.slow  # reconfig corpus composed with an every-round read mix
def test_reconfig_corpus_with_reads():
    plans = load_corpus("reconfig")
    for doc in plans:
        rdoc = doc.get("reconfig", doc)
        rplan = reconfig.plan_from_dict(rdoc)
        cdoc = doc.get("chaos")
        cplan = chaos.plan_from_dict(cdoc) if cdoc else None
        cfg = SimConfig(
            n_groups=8, n_peers=rplan.n_peers, collect_health=True,
            check_quorum=True, lease_read=True,
        )
        cs = ClusterSim(
            cfg, *reconfig.initial_masks(rplan, 8)
        )
        report = cs.run_reads(
            read_overlay_for(rplan.n_rounds, rplan.n_peers, "lease"),
            chaos_plan=cplan,
            reconfig_plan=rplan,
        )
        assert not any(report["safety"].values()), (
            f"{rplan.name}: {report['safety']}"
        )


# --- the fused split runner (pallas): bit-parity + honest rejection -------


_SETTLED = {}


def _settled_state(cfg, rounds=None):
    """Settle a fresh sim; memoized per (cfg, rounds) so the split-parity
    and rejection-arm tests share ONE damped settle compile (the tier-1
    budget discipline).  Callers must not mutate the returned state."""
    import functools

    key = (cfg, rounds)
    if key in _SETTLED:
        return _SETTLED[key]
    step_fn = jax.jit(functools.partial(sim.step, cfg))
    st = sim.init_state(cfg)
    crashed = jnp.zeros((cfg.n_peers, cfg.n_groups), bool)
    app = jnp.ones((cfg.n_groups,), jnp.int32)
    for _ in range(rounds or 3 * cfg.election_tick):
        st = step_fn(st, crashed, app)
    _SETTLED[key] = st
    return st


def split_plan_fixture():
    """Settle-free plan run on a pre-settled sim: a pure-lease phase
    (fusable), a safe phase (every block rejects), a quiet tail."""
    return workload.ClientPlan(
        name="split",
        n_peers=3,
        phases=[
            workload.ClientPhase(rounds=24, append=1, read_every=2,
                                 read_mode="lease"),
            workload.ClientPhase(rounds=16, append=1, read_every=4,
                                 read_mode="safe"),
            workload.ClientPhase(rounds=8, append=1),
        ],
    )


def test_split_runner_bit_identical_and_fuses():
    """The workload split runner vs the scan runner from one settled state:
    every output — end state, health planes, op carry, stats, safety,
    read stats, latency histogram — bit-identical, with the pure-lease
    phase FUSED (lease serves fold closed-form) and every safe-read
    block honestly rejected."""
    cfg = SimConfig(
        n_groups=8, n_peers=3, election_tick=16, collect_health=True,
        check_quorum=True, lease_read=True,
    )
    st0 = _settled_state(cfg)
    plan = split_plan_fixture()
    compiled = workload.compile_plan(plan, cfg.n_groups)
    k = 8
    general = runner_mod.make_runner(cfg, (compiled,))
    split = runner_mod.make_runner(cfg, (compiled,), split=True, k=k)

    def fresh():
        return (
            jax.tree.map(jnp.copy, st0),
            sim.init_health(cfg),
            reconfig.init_reconfig_state(st0),
            workload.init_read_carry(cfg.n_groups),
        )

    out_g = general(*fresh())
    out_s = split(*fresh())
    fused = int(np.asarray(out_s[-1]))
    names = (
        "state", "health", "rstate", "stats", "rstats", "safety",
        "read_carry", "read_stats", "lat_hist",
    )
    for name, a, b in zip(names, out_g[:9], out_s[:9]):
        for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            assert np.array_equal(np.asarray(la), np.asarray(lb)), (
                f"split-vs-general diverged in {name}"
            )
    total = plan.n_rounds * cfg.n_groups
    # The pure-lease phase fused (3 blocks of k=8 at least); the safe
    # phase's blocks all fell back.
    assert fused >= 2 * k * cfg.n_groups, fused
    assert fused < total, fused
    rd = np.asarray(out_s[7])
    assert rd[workload.RS_SERVED_LEASE] > 0
    assert rd[workload.RS_SERVED_QUORUM] > 0


@pytest.mark.parametrize("damping", [{}, {"check_quorum": True, "pre_vote": True}],
                         ids=["undamped", "cq-pv"])
def test_run_reads_split_equals_sequential_steps(damping):
    """ClusterSim.run_reads(split=True) from a COLD boot under a write load,
    held to one sim.step a round: the boot storm's blocks take the general
    arm (elections in progress reject the predicate), the settled ones the
    fused kernel, and the fleet ends where k sequential general rounds leave
    it (the full-schedule parity the removed pallas_step dispatchers' tests
    held, ISSUE 50)."""
    G, P, rounds, k = 8, 3, 96, 4
    cfg = SimConfig(n_groups=G, n_peers=P, collect_health=True, **damping)
    plan = workload.ClientPlan(
        name="cold-boot", n_peers=P, phases=[workload.ClientPhase(rounds=rounds, append=1)],
    )
    cs = ClusterSim(cfg)
    report = cs.run_reads(plan, split=True, split_k=k)
    assert 0 < report["fused_frac"] < 1, report["fused_frac"]
    assert not any(report["safety"].values())

    step_fn = jax.jit(lambda st, hl: sim.step(
        cfg, st, jnp.zeros((P, G), bool), jnp.ones((G,), jnp.int32), health=hl))
    st, hl = sim.init_state(cfg), sim.init_health(cfg)
    for _ in range(rounds):
        st, hl = step_fn(st, hl)
    for f in st._fields:
        if getattr(st, f) is not None:
            np.testing.assert_array_equal(
                np.asarray(getattr(st, f)), np.asarray(getattr(cs.state, f)), err_msg=f)
    np.testing.assert_array_equal(np.asarray(hl.planes), np.asarray(cs._health.planes))


def test_steady_mask_read_pending_rejects():
    """The read_pending rejection arm: a settled steady batch accepts the
    horizon, and the same batch with read_pending set rejects exactly the
    flagged groups."""
    from raft_tpu.multiraft import pallas_step

    cfg = SimConfig(
        n_groups=8, n_peers=3, election_tick=16, collect_health=True,
        check_quorum=True, lease_read=True,
    )
    st = _settled_state(cfg)  # the split-parity test's settle, shared
    crashed = jnp.zeros((3, 8), bool)
    base = np.asarray(
        pallas_step.steady_mask(cfg, st, crashed, horizon=4)
    )
    assert base.all(), "settled batch must be steady"
    pend = jnp.asarray(np.tile([True, False], 4))
    got = np.asarray(
        pallas_step.steady_mask(
            cfg, st, crashed, horizon=4, read_pending=pend
        )
    )
    assert np.array_equal(got, ~np.asarray(pend))


def test_reads_pending_in_horizon():
    """An outstanding read (any mode) or an in-horizon SAFE fire is
    pending; pure lease fires are not."""
    plan = workload.ClientPlan(
        name="ph",
        n_peers=3,
        phases=[
            workload.ClientPhase(rounds=8, read_every=1,
                                 read_mode="lease", stagger=False),
            workload.ClientPhase(rounds=8, read_every=1,
                                 read_mode="safe", stagger=False),
        ],
    )
    G = 3
    compiled = workload.compile_plan(plan, G)
    idle = workload.init_read_carry(G)
    # Horizon fully inside the lease phase: nothing pending.
    got = np.asarray(
        workload.reads_pending_in_horizon(compiled, idle, jnp.int32(0), 4)
    )
    assert not got.any()
    # Horizon touching the safe phase: pending everywhere.
    got = np.asarray(
        workload.reads_pending_in_horizon(compiled, idle, jnp.int32(6), 4)
    )
    assert got.any()
    # An outstanding read pends regardless of the schedule.
    stuck = idle._replace(
        pending_mode=jnp.asarray(np.array([2, 0, 0], np.int32)),
    )
    got = np.asarray(
        workload.reads_pending_in_horizon(compiled, stuck, jnp.int32(0), 4)
    )
    assert got[0] and not got[1] and not got[2]
    # Closed-form lease counting matches the schedule.
    n, any_l = workload.lease_fires_in_block(compiled, jnp.int32(0), 4)
    assert (np.asarray(n) == 4).all()
    assert np.asarray(any_l).all()


# --- seeded fuzz: reads over random link chaos, receipts vs oracle --------


def fuzz_read_chaos(seed, damped, pre_vote=False, rounds=48, G=4, P=3):
    rng = np.random.RandomState(seed)
    phases = [chaos.ChaosPhase(rounds=12, append=1)]
    left = rounds - 12
    while left > 0:
        n = int(rng.randint(6, 14))
        n = min(n, left)
        kind = rng.randint(3)
        if kind == 0:
            cells = [[1], [2, 3]] if rng.rand() < 0.5 else [[1, 2], [3]]
            phases.append(
                chaos.ChaosPhase(rounds=n, partition=cells, append=1)
            )
        elif kind == 1:
            phases.append(
                chaos.ChaosPhase(
                    rounds=n, loss_all=float(rng.rand() * 0.3), append=1
                )
            )
        else:
            phases.append(chaos.ChaosPhase(rounds=n, append=1))
        left -= n
    cplan = chaos.ChaosPlan(name=f"fuzz-{seed}", n_peers=P, phases=phases)
    cfg = SimConfig(
        n_groups=G, n_peers=P, collect_health=True,
        check_quorum=damped, pre_vote=pre_vote,
        lease_read=damped,
    )
    client = workload.ClientPlan(
        name=f"fuzz-client-{seed}",
        n_peers=P,
        phases=[
            workload.ClientPhase(rounds=rounds // 2, read_every=2,
                                 read_mode="lease", write_zipf=1.9),
            workload.ClientPhase(rounds=rounds - rounds // 2,
                                 read_every=1, read_mode="safe",
                                 append=1),
        ],
        seed=seed,
    )
    safety, rdstats = run_workload_vs_replay(cfg, client, cplan)
    assert (safety == 0).all(), (seed, damped, safety)


@pytest.mark.slow  # each seeded phase layout is its own scan compile;
# tier-1 keeps the fixed-shape replay parity above (the tier-1 budget)
def test_fuzz_reads_under_chaos_undamped():
    fuzz_read_chaos(101, damped=False)


@pytest.mark.slow  # see above
def test_fuzz_reads_under_chaos_cq():
    fuzz_read_chaos(202, damped=True)


@pytest.mark.slow  # 6+ seeded configs, damped and undamped
def test_fuzz_reads_under_chaos_matrix():
    fuzz_read_chaos(303, damped=True, pre_vote=True)
    fuzz_read_chaos(404, damped=False, rounds=64)
    fuzz_read_chaos(505, damped=True, rounds=64)
    fuzz_read_chaos(606, damped=True, pre_vote=True, rounds=64, G=6)
    fuzz_read_chaos(707, damped=False, G=6)
    fuzz_read_chaos(808, damped=True, G=6)
