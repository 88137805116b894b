"""Split-horizon reconfig execution (ISSUE 11).

Four claims are pinned here:

  1. the split-point planner (`reconfig.plan_split_points` /
     `reconfig.split_plan`) tiles the horizon exactly, opens general
     windows at op starts (merging back-to-back ops, extending
     joint-entering ops to their leave), cuts fused spans at schedule
     phase starts, degrades remainders to general rounds, and yields ONE
     full fused segment for an op-free horizon;
  2. the reconfig split runner (`runner.make_runner(split=True)`) is
     bit-identical to the unsplit scan — state, health planes,
     op-protocol carry, and every stats/safety accumulator — while
     actually engaging the fused
     kernel (fused_rounds > 0) on the steady stretches between ops;
  3. the ClusterSim.run_reconfig(split=True) wiring reports the measured
     fused fraction;
  4. the split runner — the one fused / general dispatcher (ISSUE 50) —
     equals one sim.step a round on schedules with elections, crashes,
     lossy and faulted links, both arms taken in every run.

Tier-1 keeps the planner battery (pure host, no compiles), ONE undamped
G=8 split-vs-unsplit parity case and claim 4's five G=8 scenarios; the G=32 production composition (health +
counters + chaos + cq + pv) and the ClusterSim wiring case are
@pytest.mark.slow (long cases; tier-1 takes 247 s of its 1470 s limit under
xdist -n 6 at PR 32; tests/test_example_plans.py runs the shipped production
plan through ClusterSim.run_reconfig(split=True) in tier-1).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.multiraft import ClusterSim, SimConfig
from raft_tpu.multiraft import chaos, kernels, reconfig
from raft_tpu.multiraft import runner as runner_mod
from raft_tpu.multiraft import sim as sim_mod


def seg(start, rounds, fused):
    return reconfig.HorizonSegment(start, rounds, fused)


# --- claim 1: the split-point planner ---------------------------------------


def test_planner_empty_plan_one_full_fused_segment():
    assert reconfig.plan_split_points(64, [], (), k=8) == [seg(0, 64, True)]
    # A non-multiple horizon degrades only its remainder to general.
    assert reconfig.plan_split_points(60, [], (), k=8) == [
        seg(0, 56, True), seg(56, 4, False),
    ]


def test_planner_op_at_round_zero():
    assert reconfig.plan_split_points(64, [(0, 4)], (), k=4) == [
        seg(0, 4, False), seg(4, 60, True),
    ]


def test_planner_back_to_back_ops_merge():
    # Adjacent/overlapping op windows coalesce into one general segment.
    assert reconfig.plan_split_points(32, [(8, 12), (12, 16)], (), k=4) == [
        seg(0, 8, True), seg(8, 8, False), seg(16, 16, True),
    ]
    assert reconfig.plan_split_points(32, [(8, 14), (10, 16)], (), k=4) == [
        seg(0, 8, True), seg(8, 8, False), seg(16, 16, True),
    ]


def test_planner_op_in_final_round():
    # The window clips at the horizon end; the sub-k fused tail and the
    # window coalesce into one trailing general segment.
    assert reconfig.plan_split_points(32, [(31, 35)], (), k=4) == [
        seg(0, 28, True), seg(28, 4, False),
    ]


def test_planner_cuts_subdivide_fused_spans():
    # A schedule-phase start inside a fused span splits it; sub-k pieces
    # degrade to general rounds.
    assert reconfig.plan_split_points(32, [], (10,), k=4) == [
        seg(0, 8, True), seg(8, 2, False), seg(10, 20, True),
        seg(30, 2, False),
    ]


def test_planner_tiles_exactly():
    rng = np.random.RandomState(7)
    for _ in range(50):
        R = int(rng.randint(1, 200))
        wins = [
            (int(a), int(a + rng.randint(1, 9)))
            for a in rng.randint(0, max(1, R), size=rng.randint(0, 4))
        ]
        cuts = [int(c) for c in rng.randint(1, max(2, R), size=3)]
        k = int(rng.choice([2, 4, 8]))
        segs = reconfig.plan_split_points(R, wins, cuts, k=k)
        assert segs[0].start == 0
        assert sum(s.rounds for s in segs) == R
        for a, b in zip(segs, segs[1:]):
            assert a.start + a.rounds == b.start
        for s in segs:
            if s.fused:
                assert s.rounds % k == 0 and s.rounds > 0


def _joint_plan(extra_settle=16):
    return reconfig.ReconfigPlan(
        name="split-joint", n_peers=3, voters=[1, 2], learners=[3],
        phases=[
            reconfig.ReconfigPhase(rounds=16, append=1),
            reconfig.ReconfigPhase(
                rounds=8, append=1, op={"enter_joint": [{"add": 3}]}
            ),
            reconfig.ReconfigPhase(
                rounds=8, append=1, op={"leave_joint": True}
            ),
            reconfig.ReconfigPhase(rounds=extra_settle, append=1),
        ],
    )


def test_split_plan_joint_window_extends_to_leave():
    compiled = reconfig.compile_plan(_joint_plan(), 4)
    segs = reconfig.split_plan(compiled, k=4, window=4)
    # enter_joint at 16 must stay general until the leave (24) + window,
    # in ONE general segment — planning the joint interval fused would
    # only buy steady-rejected blocks.
    assert seg(16, 12, False) in segs
    assert sum(s.rounds for s in segs) == compiled.n_rounds
    # ...and a joint-entering op with NO leave extends to the horizon end.
    tail = reconfig.ReconfigPlan(
        name="split-joint-tail", n_peers=3, voters=[1, 2],
        phases=[
            reconfig.ReconfigPhase(rounds=16, append=1),
            reconfig.ReconfigPhase(
                rounds=16, append=1, op={"enter_joint": [{"add": 3}]}
            ),
        ],
    )
    segs = reconfig.split_plan(reconfig.compile_plan(tail, 4), k=4)
    assert segs[-1] == seg(16, 16, False)


def test_split_plan_simple_op_window_only():
    plan = reconfig.ReconfigPlan(
        name="split-simple", n_peers=3, voters=[1, 2], learners=[3],
        phases=[
            reconfig.ReconfigPhase(rounds=16, append=1),
            reconfig.ReconfigPhase(
                rounds=16, append=1, op={"promote_learner": 3}
            ),
        ],
    )
    segs = reconfig.split_plan(reconfig.compile_plan(plan, 4), k=4, window=4)
    assert segs == [
        seg(0, 16, True), seg(16, 4, False), seg(20, 12, True),
    ]


# --- claim 2: split-vs-unsplit parity ---------------------------------------


FIELDS = tuple(sim_mod.SimState._fields)


def _assert_run_equal(out1, out2, note):
    st1, hl1, rst1, stats1, rstats1, safety1 = out1[:6]
    st2, hl2, rst2, stats2, rstats2, safety2 = out2[:6]
    for f in FIELDS:
        a, b = getattr(st1, f), getattr(st2, f)
        if a is None and b is None:
            continue
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=f"{note}: state {f}"
        )
    np.testing.assert_array_equal(
        np.asarray(hl1.planes), np.asarray(hl2.planes),
        err_msg=f"{note}: health planes",
    )
    assert int(hl1.window_pos) == int(hl2.window_pos), note
    for f in reconfig.ReconfigState._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(rst1, f)), np.asarray(getattr(rst2, f)),
            err_msg=f"{note}: rstate {f}",
        )
    for name, a, b in (
        ("chaos stats", stats1, stats2),
        ("rstats", rstats1, rstats2),
        ("safety", safety1, safety2),
    ):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=f"{note}: {name}"
        )


def test_split_runner_matches_unsplit_g8():
    """The tier-1 split-vs-unsplit parity case: an undamped G=8 plan with
    a mid-horizon promote op — elections settle inside the horizon (the
    early blocks honestly reject), then the fused blocks engage; every
    output of the split runner must equal the unsplit scan's, and the
    fused accumulator must show real (partial) fused coverage."""
    G = 8
    plan = reconfig.ReconfigPlan(
        name="tier1-split", n_peers=3, voters=[1, 2], learners=[3],
        phases=[
            reconfig.ReconfigPhase(rounds=24, append=1),
            reconfig.ReconfigPhase(
                rounds=8, append=1, op={"promote_learner": 3}
            ),
            reconfig.ReconfigPhase(rounds=32, append=1),
        ],
    )
    cfg = SimConfig(n_groups=G, n_peers=3, collect_health=True)
    compiled = reconfig.compile_plan(plan, G)

    def fresh():
        st = sim_mod.init_state(cfg, *reconfig.initial_masks(plan, G))
        return st, sim_mod.init_health(cfg), reconfig.init_reconfig_state(st)

    out1 = runner_mod.make_runner(cfg, (compiled,))(*fresh())
    runner = runner_mod.make_runner(
        cfg, (compiled,), split=True, k=4, window=4
    )
    out2 = runner(*fresh())
    _assert_run_equal(out1, out2, "g8-split")
    fused = int(out2[6])
    total = plan.n_rounds * G
    # Real fused engagement, real honest fallback: the boot storm and the
    # op window cannot fuse, the settled stretches must.
    assert 0 < fused < total, (fused, total)
    assert not np.asarray(out2[5]).any(), "safety violations"
    # The op applied everywhere despite the split.
    assert (np.asarray(out2[2].op_ptr) == 1).all()


@pytest.mark.slow
def test_split_runner_prod_composition_g32():
    """The production composition at G=32: health + counters + chaos
    overlay + check-quorum + pre-vote + a 3-op plan through the split
    runner — bit-identical to the unsplit scan (which cannot thread
    counters; those are cross-checked against the stepped with_counters
    body), with real fused coverage."""
    G = 32
    plan = reconfig.ReconfigPlan(
        name="slow-split-prod", n_peers=3, voters=[1, 2], learners=[3],
        phases=[
            # Damped elections at G=32 need ~70 rounds to fully settle
            # (the last straggler group gates the whole-batch predicate).
            reconfig.ReconfigPhase(rounds=80, append=1),
            reconfig.ReconfigPhase(
                rounds=8, append=1, op={"promote_learner": 3}
            ),
            reconfig.ReconfigPhase(
                rounds=8, append=1, op={"enter_joint": [{"remove": 2}]}
            ),
            reconfig.ReconfigPhase(
                rounds=8, append=1, op={"leave_joint": True}
            ),
            reconfig.ReconfigPhase(rounds=24, append=1),
        ],
    )
    cplan = chaos.ChaosPlan(
        name="slow-split-chaos", n_peers=3,
        phases=[
            chaos.ChaosPhase(rounds=104),
            chaos.ChaosPhase(rounds=16, loss_all=0.03),
            chaos.ChaosPhase(rounds=8),
        ],
    )
    cfg = SimConfig(
        n_groups=G, n_peers=3, collect_health=True, collect_counters=True,
        check_quorum=True, pre_vote=True, election_tick=16,
    )
    compiled = reconfig.compile_plan(plan, G)
    ccompiled = chaos.compile_plan(cplan, G)

    def fresh():
        st = sim_mod.init_state(cfg, *reconfig.initial_masks(plan, G))
        return st, sim_mod.init_health(cfg), reconfig.init_reconfig_state(st)

    out1 = runner_mod.make_runner(cfg, (compiled, ccompiled))(*fresh())
    runner = runner_mod.make_runner(
        cfg, (compiled, ccompiled), split=True, k=4, window=4,
        with_counters=True,
    )
    st0, hl0, rst0 = fresh()
    out2 = runner(st0, hl0, rst0, kernels.zero_counters())
    _assert_run_equal(out1, out2, "g32-prod")
    fused, ctrs = int(out2[6]), out2[7]
    assert 0 < fused < plan.n_rounds * G
    # Counters: exact vs the per-round with_counters body, stepped.
    body = runner_mod._runner_body(cfg, compiled, ccompiled, with_counters=True)
    st0, hl0, rst0 = fresh()
    carry = (
        st0, hl0, rst0,
        jnp.zeros((chaos.N_CHAOS_STATS,), jnp.int32),
        jnp.zeros((reconfig.N_RECONFIG_STATS,), jnp.int32),
        jnp.zeros((kernels.N_SAFETY,), jnp.int32),
        kernels.zero_counters(),
    )
    stepped = jax.jit(lambda c, r: body(c, r)[0])
    for r in range(plan.n_rounds):
        carry = stepped(carry, jnp.int32(r))
    np.testing.assert_array_equal(
        np.asarray(carry[6]), np.asarray(ctrs), err_msg="counters"
    )


@pytest.mark.slow
def test_cluster_sim_run_reconfig_split_report():
    """ClusterSim.run_reconfig(split=True) wiring: same report shape as
    the unsplit path plus the measured fused fields, zero safety, all ops
    applied — and the counter plane threaded through the split run is
    DRAINED into the host totals afterwards (the window must not sit
    loaded under a zeroed _rounds_since_drain, or the next run_round
    window would stack past the GC008 cap)."""
    G = 8
    plan = reconfig.ReconfigPlan(
        name="cs-split", n_peers=3, voters=[1, 2], learners=[3],
        phases=[
            reconfig.ReconfigPhase(rounds=24, append=1),
            reconfig.ReconfigPhase(
                rounds=8, append=1, op={"promote_learner": 3}
            ),
            reconfig.ReconfigPhase(rounds=16, append=1),
        ],
    )
    cfg = SimConfig(
        n_groups=G, n_peers=3, collect_health=True, collect_counters=True
    )
    cs = ClusterSim(cfg, *reconfig.initial_masks(plan, G))
    report = cs.run_reconfig(plan, split=True, split_k=4)
    assert report["total_rounds"] == plan.n_rounds * G
    assert 0 < report["fused_rounds"] < report["total_rounds"]
    assert report["fused_frac"] == round(
        report["fused_rounds"] / report["total_rounds"], 4
    )
    assert not any(report["safety"].values())
    assert report["ops_applied"] == G
    # The split run's counter window landed in the host totals, the
    # device plane is settled, and the drain bookkeeping is clean.
    assert sum(cs._host_counters) > 0
    assert int(np.asarray(cs._counters).sum()) == 0
    assert cs._rounds_since_drain == 0
    totals = cs.counters()
    assert totals["heartbeats"] > 0 and totals["commit_entries"] > 0


# --- claim 4: the one dispatcher against k sequential sim.steps -------------
#
# The scenarios the removed pallas_step dispatchers' tests ran (ISSUE 50), on
# the dispatcher that stays: `make_runner(..., split=True)` with the no-op
# membership schedule over a chaos plan, held — state, health planes, counter
# plane — to one jitted `sim.step` a round on `chaos.HostSchedule`'s masks.

SEQ_K = 4


def _phases(*docs):
    return chaos.plan_from_dict({"name": "seq", "peers": 3, "phases": list(docs)})


SEQ_LOSSY = _phases({"rounds": 72, "append": 1, "loss": [
    {"from": 1, "to": 2, "rate": 0.3}, {"from": 2, "to": 1, "rate": 0.5},
    {"from": 3, "to": 2, "rate": 0.7}]})
SEQ_DAMPED = dict(election_tick=60, check_quorum=True, pre_vote=True, health_window=8)
# name -> (SimConfig flags, rounds settled before the plan, plan, counters threaded)
SEQ_SCENARIOS = {
    # The boot storm (no block can fuse), settled blocks, store 1 lost in the
    # even groups (those it led elect again), its return and the re-sync: an
    # election, a crash and a recovery across block boundaries, both arms.
    "election-crash-recovery": ({}, 0, _phases(
        {"rounds": 24, "append": 1},
        {"rounds": 16, "append": 1, "crash": [1], "groups": {"mod": 2, "eq": 0}},
        {"rounds": 24, "append": 1}), False),
    # The counter plane's closed form on the fused arm, its per-round fold on
    # the general arm (the boot storm's campaigns and wins), in one run.
    "counters-both-arms": ({}, 0, _phases({"rounds": 40, "append": 1}), True),
    # The damped chaos kernel drawing real loss, cq alone and cq + pre-vote:
    # 72 rounds cross the leaders' check-quorum boundary window at tick 60,
    # so the lossy bound sends some blocks to the general arm.
    "damped-lossy-link-cq": (dict(SEQ_DAMPED, pre_vote=False), 150, SEQ_LOSSY, False),
    "damped-lossy-link-cq-pv": (SEQ_DAMPED, 150, SEQ_LOSSY, True),
    # A link that is down is never fused over: general while it is faulted,
    # the damped kernel again once it heals.
    "damped-faulted-link": (SEQ_DAMPED, 150, _phases(
        {"rounds": 16, "append": 1},
        {"rounds": 16, "append": 1, "links": [{"from": 1, "to": 2, "up": False}]},
        {"rounds": 24, "append": 1}), False),
}


@pytest.mark.parametrize("scenario", sorted(SEQ_SCENARIOS))
def test_split_runner_equals_sequential_steps(scenario):
    flags, settle, cplan, with_counters = SEQ_SCENARIOS[scenario]
    G, P = 8, 3
    cfg = SimConfig(n_groups=G, n_peers=P, collect_health=True, **flags)
    st = sim_mod.init_state(cfg)
    if settle:
        cs = ClusterSim(cfg)
        cs.run_compiled(settle, append_n=jnp.ones((G,), jnp.int32))
        st = jax.tree.map(jnp.copy, cs.state)
    zero_ctrs = kernels.zero_counters() if with_counters else None

    @jax.jit
    def one_round(st, hl, ctrs, link, crashed, append):
        return sim_mod.step(cfg, st, crashed, append, counters=ctrs, health=hl, link=link)

    host = chaos.HostSchedule(cplan, G)
    want_st, want_hl, want_ctrs = jax.tree.map(jnp.copy, st), sim_mod.init_health(cfg), zero_ctrs
    for r in range(cplan.n_rounds):
        link, crashed, append = host.masks(r)
        out = one_round(want_st, want_hl, want_ctrs, jnp.asarray(link),
                        jnp.asarray(crashed), jnp.asarray(append, jnp.int32))
        if with_counters:
            want_st, want_ctrs, want_hl = out
        else:
            want_st, want_hl = out

    runner = runner_mod.make_runner(
        cfg, (reconfig.empty_reconfig_schedule(cplan.n_rounds, P, G),
              chaos.compile_plan(cplan, G)),
        split=True, k=SEQ_K, with_counters=with_counters,
    )
    extra = (zero_ctrs,) if with_counters else ()
    out = runner(st, sim_mod.init_health(cfg), reconfig.init_reconfig_state(st), *extra)
    for f in FIELDS:
        a, b = getattr(want_st, f), getattr(out[0], f)
        if a is not None:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=f"state {f}")
    np.testing.assert_array_equal(np.asarray(want_hl.planes), np.asarray(out[1].planes))
    assert int(want_hl.window_pos) == int(out[1].window_pos)
    if with_counters:
        np.testing.assert_array_equal(np.asarray(want_ctrs), np.asarray(out[7]))
    assert not np.asarray(out[5]).any(), "safety violations"
    # Both arms in the one run.
    assert 0 < int(out[6]) < cplan.n_rounds * G, int(out[6])
