"""Reconfig-engine parity: the membership-churn correctness claims.

Five claims are pinned here (ISSUE 10 acceptance criteria):

  1. reconfig-off is free: `sim.step(..., reconfig_propose=None)` traces
     to the SAME jaxpr as never passing it — no existing graph changes;
  2. per-round state AND health-plane AND op-protocol parity of the
     compiled reconfig round (the exact make_runner body, stepped) against
     simref.ReconfigOracle — real Raft state machines with the identical
     propose/gate/retry rules and the scalar surgery mirror of
     kernels.apply_confchange — across multi-phase schedules composed
     with link chaos, undamped AND damped (cq+pv), plus a seeded fuzz;
  3. the one-shot compiled scan (runner.make_runner) ends
     bit-identical to stepping the same schedule round by round;
  4. zero joint-window safety violations on every correct schedule, and
     each joint-window invariant CAN fire (negative tests per slot);
  5. kernels.apply_confchange's apply-time reactions (step-down, fresh
     tracker rows, recent_active grace, quorum-shrink pickup) match the
     reference semantics on handcrafted planes.

Tier-1 cost: the reconfig round body jit is the link-path step plus the
gate/apply tail (~10-15s on CPU), so tier-1 keeps ONE undamped composed
schedule and ONE damped (cq+pv) schedule at G=8; the seeded fuzz battery,
the G=32 corpus replays, and the 5-peer cases are marked slow (ROADMAP.md's
standing constraint; tier-1 takes 247 s of its 1470 s limit under xdist -n 6
at PR 32).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.multiraft import (
    ClusterSim,
    ReconfigOracle,
    ScalarCluster,
    SimConfig,
)
from raft_tpu.multiraft import chaos, kernels, reconfig
from raft_tpu.multiraft import runner as runner_mod
from raft_tpu.multiraft import sim as sim_mod

FIELDS = ("term", "state", "commit", "last_index", "last_term")

G, P, WINDOW = 8, 3, 8


# --- the stepped runner body (bit-identical to make_runner's scan) ----------


def make_round_fn(cfg, compiled, ccompiled):
    """One jitted round of exactly the make_runner body (the scan body
    lifted out so parity can compare EVERY round, not just the end)."""

    def round_fn(st, hl, rst, stats, rstats, safety, r):
        ph = compiled.phase_of_round[r]
        append = compiled.append[ph]
        if ccompiled is not None:
            link, crashed, capp = chaos.schedule_masks(ccompiled, r)
            append = append + capp
        else:
            link = None
            crashed = jnp.zeros((cfg.n_peers, cfg.n_groups), bool)
        start = reconfig._gather_op(compiled.op_start, rst.op_ptr)
        active = (rst.op_ptr < compiled.n_ops) & (r >= start)
        want_prop = active & (rst.stage == 0)
        prev_leaderless = hl.planes[kernels.HP_LEADERLESS]
        offered = append + want_prop.astype(jnp.int32)
        st2, hl2, prop = sim_mod.step(
            cfg, st, crashed, offered,
            health=hl, link=link, reconfig_propose=want_prop,
        )
        got = want_prop & (prop.owner > 0)
        stage = jnp.where(got, 1, rst.stage)
        powner = jnp.where(got, prop.owner, rst.prop_owner)
        pindex = jnp.where(got, prop.index, rst.prop_index)
        pterm = jnp.where(got, prop.term, rst.prop_term)
        own_lead = (
            (reconfig._gather_peer(st2.state, powner)
             == kernels.ROLE_LEADER)
            & (reconfig._gather_peer(st2.term, powner) == pterm)
            & ~reconfig._gather_peer(crashed, powner)
        )
        committed = reconfig._gather_peer(st2.commit, powner) >= pindex
        apply_mask = (stage == 1) & own_lead & committed
        retry = (stage == 1) & ~own_lead
        stage = jnp.where(apply_mask | retry, 0, stage)
        safety = safety + kernels.check_safety(
            st2.state, st2.term, st2.commit, st2.last_index, st2.agree,
            st.commit, voter_mask=st2.voter_mask,
            outgoing_mask=st2.outgoing_mask, matched=st2.matched,
            crashed=crashed, prev_voter_mask=rst.prev_voter,
            prev_outgoing_mask=rst.prev_outgoing,
        )
        (state3, leader3, commit3, matched3, vm3, om3, lm3, ra3, tr3) = (
            kernels.apply_confchange(
                st2.state, st2.leader_id, st2.commit,
                st2.term_start_index, st2.matched, st2.voter_mask,
                st2.outgoing_mask, st2.learner_mask,
                reconfig._gather_op(compiled.tgt_voter, rst.op_ptr),
                reconfig._gather_op(compiled.tgt_outgoing, rst.op_ptr),
                reconfig._gather_op(compiled.tgt_learner, rst.op_ptr),
                reconfig._gather_op(compiled.added, rst.op_ptr),
                reconfig._gather_op(compiled.removed, rst.op_ptr),
                apply_mask, st2.recent_active,
            )
        )
        st3 = st2._replace(
            state=state3, leader_id=leader3, commit=commit3,
            matched=matched3, voter_mask=vm3, outgoing_mask=om3,
            learner_mask=lm3, recent_active=ra3,
        )
        stats = chaos.update_chaos_stats(
            stats, prev_leaderless, hl2.planes[kernels.HP_LEADERLESS],
            offered=offered > 0, dropped=prop.dropped,
        )
        rstats = rstats + jnp.stack([
            jnp.sum(got, dtype=jnp.int32),
            jnp.sum(apply_mask, dtype=jnp.int32),
            jnp.sum(retry, dtype=jnp.int32),
            jnp.sum(jnp.any(om3, axis=0), dtype=jnp.int32),
        ])
        rst2 = reconfig.ReconfigState(
            stage=stage,
            op_ptr=jnp.where(apply_mask, rst.op_ptr + 1, rst.op_ptr),
            prop_owner=powner, prop_index=pindex, prop_term=pterm,
            prev_voter=st2.voter_mask, prev_outgoing=st2.outgoing_mask,
        )
        return st3, hl2, rst2, stats, rstats, safety

    return jax.jit(round_fn)


def drive_parity(plan_doc, n_groups, chaos_doc=None, check_quorum=False,
                 pre_vote=False, election_tick=10, note=""):
    """Step the compiled schedule against the oracle, asserting per-round
    state + health-plane + op-protocol parity; returns the final device
    tuple for end-state assertions."""
    plan = reconfig.plan_from_dict(plan_doc)
    n_peers = plan.n_peers
    cfg = SimConfig(
        n_groups=n_groups, n_peers=n_peers, collect_health=True,
        health_window=WINDOW, election_tick=election_tick,
        check_quorum=check_quorum, pre_vote=pre_vote,
    )
    compiled = reconfig.compile_plan(plan, n_groups)
    sched = reconfig.HostReconfigSchedule(plan, n_groups)
    ccompiled = csched = None
    if chaos_doc is not None:
        cplan = chaos.plan_from_dict(chaos_doc)
        ccompiled = chaos.compile_plan(cplan, n_groups)
        csched = chaos.HostSchedule(cplan, n_groups)
    vm, om, lm = reconfig.initial_masks(plan, n_groups)
    st = sim_mod.init_state(cfg, vm, om, lm)
    hl = sim_mod.init_health(cfg)
    rst = reconfig.init_reconfig_state(st)
    stats = jnp.zeros((chaos.N_CHAOS_STATS,), jnp.int32)
    rstats = jnp.zeros((reconfig.N_RECONFIG_STATS,), jnp.int32)
    safety = jnp.zeros((kernels.N_SAFETY,), jnp.int32)
    cluster = ScalarCluster(
        n_groups, n_peers, election_tick=election_tick,
        voters=plan.voters, learners=plan.learners,
        check_quorum=check_quorum, pre_vote=pre_vote,
    )
    oracle = ReconfigOracle(
        cluster, sched, chaos_schedule=csched, window=WINDOW
    )
    round_fn = make_round_fn(cfg, compiled, ccompiled)
    for r in range(plan.n_rounds):
        st, hl, rst, stats, rstats, safety = round_fn(
            st, hl, rst, stats, rstats, safety, jnp.int32(r)
        )
        oracle.scheduled_round()
        snap = oracle.cluster.snapshot()
        for f in FIELDS:
            got = np.asarray(getattr(st, f), dtype=np.int64).T
            if not np.array_equal(snap[f], got):
                bad = np.argwhere(snap[f] != got)[0]
                raise AssertionError(
                    f"{note} round {r}: {f} mismatch group {bad[0]} peer "
                    f"{bad[1]}: scalar={snap[f][bad[0], bad[1]]} "
                    f"device={got[bad[0], bad[1]]}"
                )
        got_h = np.asarray(hl.planes)
        if not np.array_equal(got_h, oracle.planes):
            bad = np.argwhere(got_h != oracle.planes)[0]
            raise AssertionError(
                f"{note} round {r}: health plane {bad[0]} group "
                f"{bad[1]}: oracle={oracle.planes[bad[0], bad[1]]} "
                f"device={got_h[bad[0], bad[1]]}"
            )
        assert np.array_equal(np.asarray(rst.stage), oracle.stage), (
            f"{note} round {r}: stage mismatch"
        )
        assert np.array_equal(np.asarray(rst.op_ptr), oracle.op_ptr), (
            f"{note} round {r}: op_ptr mismatch"
        )
    sv = np.asarray(safety)
    assert not sv.any(), (
        f"{note}: joint-window safety violations "
        f"{dict(zip(kernels.SAFETY_NAMES, sv.tolist()))}"
    )
    return st, hl, rst, stats, rstats, safety


# --- claim 1: the reconfig-off graph is bit-identical -----------------------


def test_reconfig_off_graph_identical():
    cfg = SimConfig(n_groups=4, n_peers=3)
    st = sim_mod.init_state(cfg)
    crashed = jnp.zeros((3, 4), bool)
    app = jnp.zeros((4,), jnp.int32)
    base = jax.make_jaxpr(functools.partial(sim_mod.step, cfg))(
        st, crashed, app
    )
    with_none = jax.make_jaxpr(
        lambda s, c, a: sim_mod.step(cfg, s, c, a, reconfig_propose=None)
    )(st, crashed, app)
    assert str(base) == str(with_none)
    # steady_mask's rejection arm is equally free when unused.
    from raft_tpu.multiraft import pallas_step

    j1 = jax.make_jaxpr(
        lambda s, c: pallas_step.steady_mask(cfg, s, c, 4)
    )(st, crashed)
    j2 = jax.make_jaxpr(
        lambda s, c: pallas_step.steady_mask(
            cfg, s, c, 4, None, reconfig_pending=None
        )
    )(st, crashed)
    assert str(j1) == str(j2)


# --- tier-1 parity: one undamped + one damped composed schedule -------------


def mix_plan():
    """Joint-entry during a symmetric split, exit after heal, then a
    simple add — every op kind class crossed with a fault phase."""
    return (
        {
            "name": "tier1-mix", "peers": P, "voters": [1, 2],
            "learners": [3],
            "phases": [
                {"rounds": 16, "append": 1},
                {"rounds": 18, "op": {"enter_joint": [{"add": 3}]},
                 "append": 1},
                {"rounds": 16, "op": {"leave_joint": True}, "append": 1},
                {"rounds": 30, "op": {"remove_voter": 1},
                 "groups": {"mod": 2, "eq": 0}, "append": 1},
            ],
        },
        {
            "name": "tier1-mix-chaos", "peers": P,
            "phases": [
                {"rounds": 16},
                {"rounds": 18, "partition": [[1, 2], [3]]},
                {"rounds": 16, "links": [{"from": 1, "to": 2,
                                          "up": False}]},
                {"rounds": 30, "heal": True},
            ],
        },
    )


def test_parity_reconfig_during_chaos():
    plan_doc, chaos_doc = mix_plan()
    st, hl, rst, stats, rstats, safety = drive_parity(
        plan_doc, G, chaos_doc, note="mix"
    )
    rs = np.asarray(rstats)
    assert rs[reconfig.RC_APPLIED] > 0
    assert rs[reconfig.RC_JOINT_ROUNDS] > 0
    # mod-selected groups chain 3 ops, the rest 2; every group makes
    # progress and most complete (an undamped joint election CAN
    # split-vote-livelock through the tail — the PR 7 pathology — so a
    # straggler or two is legitimate, and exactly mirrored by the oracle).
    want_ops = np.where(np.arange(G) % 2 == 0, 3, 2)
    ptr = np.asarray(rst.op_ptr)
    assert (ptr >= 1).all() and (ptr <= want_ops).all()
    assert (ptr == want_ops).sum() >= G - 2


def test_parity_damped_cq_pv():
    """The production configuration (check-quorum + pre-vote) under a
    reconfig-during-partition schedule with an owner crash (the retry
    arm), per-round exact."""
    plan_doc = {
        "name": "tier1-damped", "peers": P, "voters": [1, 2, 3],
        "phases": [
            {"rounds": 18, "append": 1},
            {"rounds": 22, "op": {"enter_joint": [{"remove": 2}]},
             "append": 1},
            {"rounds": 22, "op": {"leave_joint": True}, "append": 1},
            {"rounds": 14, "op": {"add_voter": 2}, "append": 1},
        ],
    }
    chaos_doc = {
        "name": "tier1-damped-chaos", "peers": P,
        "phases": [
            {"rounds": 18},
            {"rounds": 22, "partition": [[1, 2], [3]]},
            {"rounds": 22, "crash": [2]},
            {"rounds": 14, "heal": True},
        ],
    }
    st, hl, rst, stats, rstats, safety = drive_parity(
        plan_doc, G, chaos_doc, check_quorum=True, pre_vote=True,
        note="damped",
    )
    assert np.asarray(rstats)[reconfig.RC_APPLIED] >= 3 * G


# --- claim 3: the one-shot compiled scan == stepping ------------------------


def test_run_plan_matches_stepping():
    plan_doc, chaos_doc = mix_plan()
    plan = reconfig.plan_from_dict(plan_doc)
    cplan = chaos.plan_from_dict(chaos_doc)
    cfg = SimConfig(
        n_groups=G, n_peers=P, collect_health=True, health_window=WINDOW
    )
    compiled = reconfig.compile_plan(plan, G)
    ccompiled = chaos.compile_plan(cplan, G)
    vm, om, lm = reconfig.initial_masks(plan, G)

    # stepped (shares the claim-2 body; re-jit is the price of the
    # stepped view)
    st = sim_mod.init_state(cfg, vm, om, lm)
    hl = sim_mod.init_health(cfg)
    rst = reconfig.init_reconfig_state(st)
    stats = jnp.zeros((chaos.N_CHAOS_STATS,), jnp.int32)
    rstats = jnp.zeros((reconfig.N_RECONFIG_STATS,), jnp.int32)
    safety = jnp.zeros((kernels.N_SAFETY,), jnp.int32)
    round_fn = make_round_fn(cfg, compiled, ccompiled)
    for r in range(plan.n_rounds):
        st, hl, rst, stats, rstats, safety = round_fn(
            st, hl, rst, stats, rstats, safety, jnp.int32(r)
        )
    # the scan body folds the tail audit after the loop
    safety = safety + kernels.check_safety(
        st.state, st.term, st.commit, st.last_index, st.agree, st.commit,
        voter_mask=st.voter_mask, outgoing_mask=st.outgoing_mask,
        matched=st.matched, prev_voter_mask=rst.prev_voter,
        prev_outgoing_mask=rst.prev_outgoing,
    )

    # one-shot compiled scan
    st2 = sim_mod.init_state(cfg, vm, om, lm)
    out = runner_mod.make_runner(cfg, (compiled, ccompiled))(
        st2, sim_mod.init_health(cfg), reconfig.init_reconfig_state(st2)
    )
    stf, hlf, rstf, stats_f, rstats_f, safety_f = out
    for f in sim_mod.SimState._fields:
        a, b = getattr(st, f), getattr(stf, f)
        if a is None:
            assert b is None
            continue
        assert np.array_equal(np.asarray(a), np.asarray(b)), f
    assert np.array_equal(np.asarray(hl.planes), np.asarray(hlf.planes))
    for f in reconfig.ReconfigState._fields:
        assert np.array_equal(
            np.asarray(getattr(rst, f)), np.asarray(getattr(rstf, f))
        ), f
    assert np.array_equal(np.asarray(stats), np.asarray(stats_f))
    assert np.array_equal(np.asarray(rstats), np.asarray(rstats_f))
    assert np.array_equal(np.asarray(safety), np.asarray(safety_f))
    assert not np.asarray(safety_f).any()


# --- claim 4: each joint-window invariant can fire --------------------------


def _planes(v, g=4):
    return jnp.full((2, g), v, jnp.int32)


def test_joint_safety_slots_fire():
    g = 4
    vm = jnp.ones((2, g), bool)
    om = jnp.zeros((2, g), bool)
    matched = jnp.zeros((2, 2, g), jnp.int32)
    # a leader outside voter|outgoing
    out = kernels.check_safety(
        state=jnp.asarray([[2] * g, [0] * g], jnp.int32),
        term=_planes(3), commit=_planes(5), last_index=_planes(7),
        agree=jnp.full((2, 2, g), 6, jnp.int32), prev_commit=_planes(5),
        voter_mask=jnp.asarray([[False] * g, [True] * g]),
        outgoing_mask=om, matched=matched,
    )
    assert int(np.asarray(out)[kernels.SV_LEADER_NOT_IN_CONFIG]) == g
    # a commit advance with no quorum behind it: leader's own tracker
    # rows are all zero yet its commit moved past the round high-water
    out = kernels.check_safety(
        state=jnp.asarray([[2] * g, [0] * g], jnp.int32),
        term=_planes(3),
        commit=jnp.asarray([[6] * g, [5] * g], jnp.int32),
        last_index=_planes(7),
        agree=jnp.full((2, 2, g), 6, jnp.int32),
        prev_commit=_planes(5),
        voter_mask=vm, outgoing_mask=om, matched=matched,
    )
    assert int(np.asarray(out)[kernels.SV_COMMIT_NO_QUORUM]) == g
    # ...and the same advance IS legal when the tracker rows back it
    backed = jnp.full((2, 2, g), 6, jnp.int32)
    out = kernels.check_safety(
        state=jnp.asarray([[2] * g, [0] * g], jnp.int32),
        term=_planes(3),
        commit=jnp.asarray([[6] * g, [5] * g], jnp.int32),
        last_index=_planes(7),
        agree=jnp.full((2, 2, g), 6, jnp.int32),
        prev_commit=_planes(5),
        voter_mask=vm, outgoing_mask=om, matched=backed,
    )
    assert int(np.asarray(out)[kernels.SV_COMMIT_NO_QUORUM]) == 0
    # single-step double-membership change: both voters flipped
    out = kernels.check_safety(
        state=jnp.zeros((2, g), jnp.int32),
        term=_planes(3), commit=_planes(5), last_index=_planes(7),
        agree=jnp.full((2, 2, g), 6, jnp.int32), prev_commit=_planes(5),
        voter_mask=jnp.asarray([[True] * g, [False] * g]),
        outgoing_mask=om, matched=matched,
        prev_voter_mask=jnp.asarray([[False] * g, [True] * g]),
        prev_outgoing_mask=om,
    )
    assert int(np.asarray(out)[kernels.SV_CONF_DOUBLE_CHANGE]) == g
    # joint-entry whose outgoing is NOT the old incoming
    out = kernels.check_safety(
        state=jnp.zeros((2, g), jnp.int32),
        term=_planes(3), commit=_planes(5), last_index=_planes(7),
        agree=jnp.full((2, 2, g), 6, jnp.int32), prev_commit=_planes(5),
        voter_mask=vm,
        outgoing_mask=jnp.asarray([[True] * g, [False] * g]),
        matched=matched,
        prev_voter_mask=vm, prev_outgoing_mask=om,
    )
    assert int(np.asarray(out)[kernels.SV_CONF_DOUBLE_CHANGE]) == g
    # a LEGAL joint entry (outgoing == old incoming) does not fire
    out = kernels.check_safety(
        state=jnp.zeros((2, g), jnp.int32),
        term=_planes(3), commit=_planes(5), last_index=_planes(7),
        agree=jnp.full((2, 2, g), 6, jnp.int32), prev_commit=_planes(5),
        voter_mask=vm, outgoing_mask=vm, matched=matched,
        prev_voter_mask=vm, prev_outgoing_mask=om,
    )
    assert int(np.asarray(out)[kernels.SV_CONF_DOUBLE_CHANGE]) == 0
    # masks moving WHILE joint
    out = kernels.check_safety(
        state=jnp.zeros((2, g), jnp.int32),
        term=_planes(3), commit=_planes(5), last_index=_planes(7),
        agree=jnp.full((2, 2, g), 6, jnp.int32), prev_commit=_planes(5),
        voter_mask=jnp.asarray([[True] * g, [False] * g]),
        outgoing_mask=vm, matched=matched,
        prev_voter_mask=vm, prev_outgoing_mask=vm,
    )
    assert int(np.asarray(out)[kernels.SV_CONF_DOUBLE_CHANGE]) == g


def test_check_safety_arg_validation():
    with pytest.raises(ValueError, match="voter_mask"):
        kernels.check_safety(
            state=jnp.zeros((2, 4), jnp.int32), term=_planes(3),
            commit=_planes(5), last_index=_planes(7),
            agree=jnp.full((2, 2, 4), 6, jnp.int32),
            prev_commit=_planes(5),
            voter_mask=jnp.ones((2, 4), bool),
        )
    with pytest.raises(ValueError, match="double-change"):
        kernels.check_safety(
            state=jnp.zeros((2, 4), jnp.int32), term=_planes(3),
            commit=_planes(5), last_index=_planes(7),
            agree=jnp.full((2, 2, 4), 6, jnp.int32),
            prev_commit=_planes(5),
            prev_voter_mask=jnp.ones((2, 4), bool),
        )


# --- claim 5: apply_confchange reactions on handcrafted planes --------------


def test_apply_confchange_reactions():
    g = 4
    vm = jnp.asarray([[True] * g, [True] * g, [False] * g])
    om = jnp.zeros((3, g), bool)
    lm = jnp.zeros((3, g), bool)
    state = jnp.asarray([[2] * g, [0] * g, [0] * g], jnp.int32)  # 1 leads
    leader_id = jnp.ones((3, g), jnp.int32)
    commit = jnp.asarray([[5] * g, [5] * g, [0] * g], jnp.int32)
    ts = jnp.asarray([[4] * g, [0] * g, [0] * g], jnp.int32)
    matched = jnp.zeros((3, 3, g), jnp.int32)
    matched = matched.at[0, 0].set(8).at[0, 1].set(7).at[0, 2].set(6)
    ra = jnp.zeros((3, 3, g), bool).at[0, 1].set(True)
    apply_mask = jnp.asarray([True, True, False, False])

    # joint-entry removing the LEADER: incoming {2}, outgoing {1, 2}
    tgt_v = jnp.asarray([[False] * g, [True] * g, [False] * g])
    tgt_o = jnp.asarray([[True] * g, [True] * g, [False] * g])
    no = jnp.zeros((3, g), bool)
    st2, ld2, c2, m2, vm2, om2, lm2, ra2, _ = kernels.apply_confchange(
        state, leader_id, commit, ts, matched, vm, om, lm,
        tgt_v, tgt_o, no, no, no, apply_mask, ra,
    )
    # leader still in outgoing -> keeps leading; masks swapped only where
    # applied
    assert np.asarray(st2)[0, 0] == 2 and np.asarray(st2)[0, 2] == 2
    assert np.asarray(vm2)[:, 0].tolist() == [False, True, False]
    assert np.asarray(vm2)[:, 2].tolist() == [True, True, False]
    # quorum-shrink pickup: joint mci = min(maj{2}=7, maj{1,2}=7) = 7
    # >= ts(4) -> leader's commit advances to 7 in applied groups
    assert np.asarray(c2)[0, 0] == 7 and np.asarray(c2)[0, 2] == 5

    # joint-exit that drops the leader entirely: incoming {2}, outgoing {}
    st3, ld3, c3, m3, vm3, om3, lm3, ra3, _ = kernels.apply_confchange(
        state, leader_id, commit, ts, matched, tgt_v, tgt_o, lm,
        tgt_v, no, no, no,
        jnp.asarray([[True] * g, [False] * g, [False] * g]),  # removed: 1
        apply_mask, ra,
    )
    # step-down: ex-leader becomes follower with leader_id cleared
    assert np.asarray(st3)[0, 0] == 0 and np.asarray(ld3)[0, 0] == 0
    assert np.asarray(st3)[0, 2] == 2  # unapplied group untouched
    # removed member's tracker rows cleared across every owner
    assert np.asarray(m3)[0, 0, 0] == 0 and np.asarray(m3)[0, 1, 0] == 7

    # add a fresh member 3: rows zeroed, recent_active granted
    tgt_v3 = jnp.asarray([[True] * g, [True] * g, [True] * g])
    st4, ld4, c4, m4, vm4, om4, lm4, ra4, _ = kernels.apply_confchange(
        state, leader_id, commit, ts, matched, vm, om, lm,
        tgt_v3, no, no,
        jnp.asarray([[False] * g, [False] * g, [True] * g]),  # added: 3
        no, apply_mask, ra,
    )
    assert np.asarray(m4)[0, 2, 0] == 0  # fresh row
    assert np.asarray(m4)[0, 2, 2] == 6  # unapplied group keeps it
    assert bool(np.asarray(ra4)[0, 2, 0]) and bool(np.asarray(ra4)[1, 2, 0])
    assert not bool(np.asarray(ra4)[0, 2, 2])
    # undamped pytree passes through None
    out = kernels.apply_confchange(
        state, leader_id, commit, ts, matched, vm, om, lm,
        tgt_v3, no, no, no, no, apply_mask, None,
    )
    assert out[-1] is None


# --- sim.step proposal extra (plain path, cheap) ----------------------------


def test_step_reports_proposal_plain():
    cfg = SimConfig(n_groups=4, n_peers=3)
    st = sim_mod.init_state(cfg)
    crashed = jnp.zeros((3, 4), bool)
    rp = jnp.asarray([True, True, False, False])
    step = jax.jit(functools.partial(sim_mod.step, cfg),
                   static_argnames=())
    for r in range(12):
        st, prop = sim_mod.step(
            cfg, st, crashed, jnp.ones((4,), jnp.int32) + rp.astype(
                jnp.int32), reconfig_propose=rp,
        )
    own = np.asarray(prop.owner)
    # settled groups propose at their leader; non-proposing groups report 0
    assert (own[:2] > 0).all() and (own[2:] == 0).all()
    lead_last = np.asarray(st.last_index).max(axis=0)
    assert np.array_equal(np.asarray(prop.index)[:2], lead_last[:2])


# --- plan compilation: validation + schedule shapes -------------------------


def test_plan_validation_errors():
    def plan(phases, voters=None, learners=None, peers=3):
        return reconfig.plan_from_dict(
            {"name": "x", "peers": peers, "phases": phases,
             **({"voters": voters} if voters else {}),
             **({"learners": learners} if learners else {})}
        )

    with pytest.raises(ValueError, match="not currently a learner"):
        reconfig.compile_plan(
            plan([{"rounds": 5, "op": {"promote_learner": 2}}]), 2
        )
    with pytest.raises(ValueError, match="already a voter"):
        reconfig.compile_plan(
            plan([{"rounds": 5, "op": {"add_voter": 2}}]), 2
        )
    with pytest.raises(Exception, match="joint"):
        reconfig.compile_plan(
            plan([{"rounds": 5, "op": {"leave_joint": True}}]), 2
        )
    with pytest.raises(Exception, match="joint config"):
        # a simple change while joint is the Changer's own guard
        reconfig.compile_plan(
            plan([{"rounds": 5,
                   "op": {"enter_joint": [{"remove": 1}]}},
                  {"rounds": 5, "op": {"add_voter": 1}}]), 2
        )
    with pytest.raises(ValueError, match="out of range"):
        reconfig.compile_plan(
            plan([{"rounds": 5, "op": {"add_voter": 9}}],
                 voters=[1, 2]), 2
        )
    with pytest.raises(ValueError, match="no reconfig ops"):
        reconfig.compile_plan(plan([{"rounds": 5}]), 2)
    with pytest.raises(ValueError, match="2\\*\\*31"):
        reconfig.compile_plan(
            plan([{"rounds": 1 << 21, "op": {"remove_voter": 3}}]),
            1 << 10,
        )
    with pytest.raises(ValueError, match="exactly one kind"):
        reconfig.compile_plan(
            plan([{"rounds": 5, "op": {"add_voter": 1,
                                       "remove_voter": 2}}]), 2
        )


def test_compiled_schedule_shapes_and_selectors():
    plan = reconfig.plan_from_dict({
        "name": "sel", "peers": 3, "voters": [1, 2, 3],
        "phases": [
            {"rounds": 4},
            {"rounds": 6, "op": {"remove_voter": 3},
             "groups": {"mod": 2, "eq": 0}},
            {"rounds": 8, "op": {"enter_joint": [{"add": 3}]},
             "groups": [1]},
        ],
    })
    c = reconfig.compile_plan(plan, 4)
    assert c.n_rounds == 18
    n_ops = np.asarray(c.n_ops)
    assert n_ops.tolist() == [1, 1, 1, 0]
    starts = np.asarray(c.op_start)
    assert starts[0, 0] == 4 and starts[0, 1] == 10
    assert starts[0, 3] == reconfig.NO_ROUND
    # group 1's joint-entry targets: outgoing == old incoming
    assert np.asarray(c.tgt_outgoing)[0, :, 1].tolist() == [
        True, True, True
    ]
    host = reconfig.HostReconfigSchedule(plan, 4)
    slot = host.slot(1, 0)
    assert slot.voters_out == frozenset({1, 2, 3})
    with pytest.raises(ValueError, match="rounds"):
        runner_mod.make_runner(
            SimConfig(n_groups=4, n_peers=3, collect_health=True),
            (c, chaos.compile_plan(
                chaos.plan_from_dict(
                    {"name": "x", "peers": 3,
                     "phases": [{"rounds": 5}]}
                ), 4,
            )),
        )


def test_pending_in_horizon():
    plan = reconfig.plan_from_dict({
        "name": "p", "peers": 3,
        "phases": [{"rounds": 10},
                   {"rounds": 10, "op": {"remove_voter": 3}}],
    })
    c = reconfig.compile_plan(plan, 4)
    st = sim_mod.init_state(SimConfig(n_groups=4, n_peers=3))
    rst = reconfig.init_reconfig_state(st)
    # op starts at round 10: a horizon ending before it is clean...
    clean = reconfig.pending_in_horizon(c, rst, jnp.int32(5), 4)
    assert not np.asarray(clean).any()
    # ...one that reaches it is pending everywhere
    pend = reconfig.pending_in_horizon(c, rst, jnp.int32(7), 4)
    assert np.asarray(pend).all()
    # an in-flight entry pends regardless of schedule position
    rst2 = rst._replace(stage=jnp.ones((4,), jnp.int32))
    pend2 = reconfig.pending_in_horizon(c, rst2, jnp.int32(0), 1)
    assert np.asarray(pend2).all()
    # all ops applied -> never pending again
    rst3 = rst._replace(op_ptr=jnp.asarray(np.asarray(c.n_ops)))
    done = reconfig.pending_in_horizon(c, rst3, jnp.int32(25), 4)
    assert not np.asarray(done).any()


def test_steady_mask_rejects_pending_reconfig():
    """The rejection arm on a genuinely steady fleet: settle, verify the
    predicate accepts, then flag a pending reconfig and watch every
    flagged group fall back to the general path."""
    from raft_tpu.multiraft import pallas_step

    cfg = SimConfig(n_groups=4, n_peers=3, election_tick=10)
    sim = ClusterSim(cfg)
    crashed = jnp.zeros((3, 4), bool)
    for _ in range(40):
        sim.run_round(crashed, jnp.ones((4,), jnp.int32))
    base = pallas_step.steady_mask(cfg, sim.state, crashed, horizon=4)
    assert np.asarray(base).all()  # settled: every group fuses
    pend = jnp.asarray([True, False, True, False])
    rej = pallas_step.steady_mask(
        cfg, sim.state, crashed, horizon=4, reconfig_pending=pend
    )
    assert np.asarray(rej).tolist() == [False, True, False, True]


# --- checkpoint + sharding threading ----------------------------------------


def test_reconfig_checkpoint_roundtrip(tmp_path):
    from raft_tpu.multiraft import checkpoint

    st = sim_mod.init_state(SimConfig(n_groups=5, n_peers=3))
    rst = reconfig.init_reconfig_state(st)._replace(
        stage=jnp.asarray([1, 0, 1, 0, 0], jnp.int32),
        prop_index=jnp.asarray([7, 0, 9, 0, 0], jnp.int32),
    )
    path = str(tmp_path / "rst.npz")
    checkpoint.save_reconfig_state(rst, path)
    back = checkpoint.load_reconfig_state(path)
    for f in reconfig.ReconfigState._fields:
        assert np.array_equal(
            np.asarray(getattr(rst, f)), np.asarray(getattr(back, f))
        ), f
    # a SimState checkpoint must be rejected loudly
    spath = str(tmp_path / "st.npz")
    checkpoint.save_state(st, spath)
    with pytest.raises(ValueError, match="not a reconfig-state"):
        checkpoint.load_reconfig_state(spath)


def test_reconfig_sharding_placement():
    from raft_tpu.multiraft import sharding

    plan = reconfig.plan_from_dict({
        "name": "s", "peers": 3,
        "phases": [{"rounds": 4, "op": {"remove_voter": 3}}],
    })
    c = reconfig.compile_plan(plan, 8)
    st = sim_mod.init_state(SimConfig(n_groups=8, n_peers=3))
    rst = reconfig.init_reconfig_state(st)
    mesh = sharding.make_mesh(devices=jax.devices("cpu"))
    ps, pr = sharding.shard_reconfig(c, rst, mesh)
    assert ps.n_peers == 3
    assert "groups" in str(pr.stage.sharding.spec)
    assert np.array_equal(np.asarray(ps.op_start), np.asarray(c.op_start))


# --- slow tier: seeded fuzz + 5-peer + G=32 ---------------------------------


def _rand_op(rng, n_peers):
    kind = rng.choice(
        ["add_voter", "remove_voter", "add_learner", "promote_learner",
         "enter_joint", "leave_joint"],
        p=[0.15, 0.15, 0.1, 0.1, 0.3, 0.2],
    )
    if kind == "leave_joint":
        return {"leave_joint": True}
    if kind == "enter_joint":
        chs = []
        for _ in range(rng.randint(1, 3)):
            what = str(rng.choice(["add", "remove", "learner"]))
            chs.append({what: int(rng.randint(1, n_peers + 1))})
        return {"enter_joint": chs}
    return {str(kind): int(rng.randint(1, n_peers + 1))}


def fuzz_plan(rng, n_peers, n_phases, two_lanes):
    """Random valid op sequence(s): rejection-sample each op against a
    real Changer chain walk, per selector lane."""
    voters = sorted(
        rng.choice(np.arange(1, n_peers + 1),
                   size=rng.randint(1, n_peers + 1),
                   replace=False).tolist()
    )
    rest = [p for p in range(1, n_peers + 1) if p not in voters]
    learners = (
        sorted(rng.choice(rest, size=rng.randint(0, len(rest) + 1),
                          replace=False).tolist()) if rest else []
    )
    lanes = 2 if two_lanes else 1
    shadow = [
        reconfig.ReconfigPlan("s", n_peers, [], list(voters),
                              list(learners))
        for _ in range(lanes)
    ]
    phases = []
    for i in range(n_phases):
        lane = i % lanes
        sp = shadow[lane]
        op = None
        for _ in range(30):
            cand = _rand_op(rng, n_peers)
            trial = reconfig.ReconfigPlan(
                "s", n_peers,
                list(sp.phases) + [reconfig.ReconfigPhase(1, cand)],
                list(voters), list(learners),
            )
            try:
                reconfig._walk_chain(
                    trial,
                    tuple(j for j, ph in enumerate(trial.phases)
                          if ph.op is not None),
                )
            except Exception:
                continue
            op = cand
            sp.phases.append(reconfig.ReconfigPhase(1, cand))
            break
        ph = {"rounds": int(rng.randint(8, 22)),
              "append": int(rng.randint(0, 3))}
        if op is not None:
            ph["op"] = op
            if two_lanes:
                ph["groups"] = {"mod": 2, "eq": lane}
        phases.append(ph)
    return {"name": "fuzz", "peers": n_peers, "voters": voters,
            "learners": learners, "phases": phases}


def fuzz_chaos(rng, n_peers, phases):
    cphases = []
    for ph in phases:
        c = {"rounds": ph["rounds"]}
        mode = rng.choice(["none", "part", "link", "loss", "crash"],
                          p=[0.3, 0.2, 0.15, 0.2, 0.15])
        if mode == "part":
            ids = list(rng.permutation(np.arange(1, n_peers + 1)))
            cut = rng.randint(1, n_peers)
            c["partition"] = [[int(x) for x in ids[:cut]],
                              [int(x) for x in ids[cut:]]]
        elif mode == "link":
            c["links"] = [{"from": int(rng.randint(1, n_peers + 1)),
                           "to": int(rng.randint(1, n_peers + 1)),
                           "up": False}]
        elif mode == "loss":
            c["loss_all"] = float(rng.choice([0.2, 0.4]))
        elif mode == "crash":
            c["crash"] = [int(rng.randint(1, n_peers + 1))]
        cphases.append(c)
    return {"name": "fuzz-chaos", "peers": n_peers, "phases": cphases}


# Seeds chosen to cover: 3/5 peers, one/two selector lanes, and the
# damped (cq+pv) configuration — the ISSUE's >= 6 configs.
FUZZ_SEEDS = [0, 1, 2, 3, 4, 5]


@pytest.mark.slow
@pytest.mark.parametrize("seed", FUZZ_SEEDS)
def test_fuzz_reconfig_chaos_parity(seed):
    rng = np.random.RandomState(seed)
    n_peers = int(rng.choice([3, 3, 5]))
    two = bool(rng.randint(0, 2))
    damped = seed % 3 == 2
    plan_doc = fuzz_plan(rng, n_peers, int(rng.randint(4, 7)), two)
    chaos_doc = fuzz_chaos(rng, n_peers, plan_doc["phases"])
    drive_parity(
        plan_doc, 6, chaos_doc, check_quorum=damped, pre_vote=damped,
        note=f"fuzz{seed}",
    )


@pytest.mark.slow
def test_parity_mix_g32():
    plan_doc, chaos_doc = mix_plan()
    drive_parity(plan_doc, 32, chaos_doc, note="mix-g32")


@pytest.mark.slow
def test_parity_damped_mix_g32():
    plan_doc, chaos_doc = mix_plan()
    drive_parity(
        plan_doc, 32, chaos_doc, check_quorum=True, pre_vote=True,
        note="damped-mix-g32",
    )
