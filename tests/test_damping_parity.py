"""Device-side election damping parity (ISSUE 7): check-quorum, the
pre-vote / low-term nudge, and leader leases in the jitted wave path.

Claims pinned here:

  1. damping-off is free: SimConfig flags default False, the traced step
     is bit-identical to a trace with both flags passed explicitly False,
     and the undamped SimState carries NO recent_active plane (the pytree
     is unchanged — same pin pattern as PR 5's `link=None` claim);
  2. per-round state AND health-plane parity of the damped device round
     (sim._damped_linked_step) against ScalarCluster(check_quorum=...,
     pre_vote=...) — real Rafts with the reference damping — across
     scheduled multi-phase chaos and seeded link fuzz, plus leader-row
     recent_active parity against the scalar Progress flags;
  3. the before/after churn collapse: the PR 5 asymmetric-partition
     pathology (terms inflating without bound) is DAMPED once
     check_quorum is on — the disturbed groups' term growth and
     term_bumps_in_window stay under a pinned ceiling, with zero safety
     violations;
  4. the fused steady path accepts damping-on configs ONLY under the
     ISSUE 8 damping conditions (pallas_step.steady_mask: free-running
     timer bound + provable check-quorum boundaries via
     kernels.cq_boundary_safe) — boot states and damped states that
     cannot prove the boundary outcome are still rejected, so the fused
     path can never silently diverge (the fused-damped parity matrix
     itself lives in tests/test_pallas_step.py);
  5. sim.read_index is link-aware: acks need BOTH directions of the
     leader<->member link, parity-tested against the scalar cluster's
     real MsgReadIndex pump under per-edge drops.

Tier-1 cost: the damped wave path is its own compile, so the tier-1
cases share ONE module-scoped ClusterSim per flag configuration (G=8,
short schedules).  The 4-group 90-round link fuzz per flag configuration
and the 5-peer / joint / learner configurations are tier-1 since PR 32 (the
damped wave path is what every benchmark cell runs); G=32 stays slow
(tier-1 takes 247 s of its 1470 s limit under xdist -n 6 at PR 32).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.eraftpb import Entry, Message, MessageType
from raft_tpu.multiraft import (
    ChaosOracle,
    ClusterSim,
    ScalarCluster,
    SimConfig,
)
from raft_tpu.multiraft import chaos, kernels, pallas_step
from raft_tpu.multiraft import sim as sim_mod

FIELDS = ("term", "state", "commit", "last_index", "last_term")

G, P, WINDOW = 8, 3, 8


def damped_cfg(**flags):
    return SimConfig(
        n_groups=G, n_peers=P, collect_health=True, health_window=WINDOW,
        **flags,
    )


@pytest.fixture(scope="module")
def cq_sim():
    """One check-quorum ClusterSim — and ONE damped-wave-path compile —
    for every tier-1 check-quorum case; cases reset its state/health."""
    return ClusterSim(damped_cfg(check_quorum=True))


@pytest.fixture(scope="module")
def pv_sim():
    """The fully damped configuration (check_quorum AND pre_vote)."""
    return ClusterSim(damped_cfg(check_quorum=True, pre_vote=True))


def reset(sim):
    sim.state = sim_mod.init_state(sim.cfg)
    sim.reset_health()
    return sim


def assert_parity(scalar, sim, r, note=""):
    want = scalar.snapshot()
    for f in FIELDS:
        got = np.asarray(getattr(sim.state, f), dtype=np.int64).T
        if not np.array_equal(want[f], got):
            bad = np.argwhere(want[f] != got)[0]
            raise AssertionError(
                f"{note} round {r}: {f} mismatch group {bad[0]} peer "
                f"{bad[1]}: scalar={want[f][bad[0], bad[1]]} "
                f"device={got[bad[0], bad[1]]}\n"
                f"scalar row: { {k: v[bad[0]].tolist() for k, v in want.items()} }"
            )


def assert_health_parity(oracle, sim, r, note=""):
    got = np.asarray(sim._health.planes)
    if not np.array_equal(got, oracle.planes):
        bad = np.argwhere(got != oracle.planes)[0]
        raise AssertionError(
            f"{note} round {r}: health plane {bad[0]} group {bad[1]}: "
            f"oracle={oracle.planes[bad[0], bad[1]]} "
            f"device={got[bad[0], bad[1]]}"
        )


def assert_leader_ra_parity(scalar, sim, r, note=""):
    """Device recent_active rows of CURRENT leaders == the scalar
    Progress.recent_active flags.  Only leader rows are comparable: the
    scalar clears a peer's tracker on every role transition, the device
    only at become_leader / the boundary — rows of non-leaders are never
    read by either side."""
    ra = np.asarray(sim.state.recent_active)
    state = np.asarray(sim.state.state)
    for g in range(scalar.n_groups):
        for p in range(scalar.n_peers):
            raft = scalar.networks[g].peers[p + 1].raft
            if int(raft.state) != kernels.ROLE_LEADER:
                continue
            assert state[p, g] == kernels.ROLE_LEADER
            for v in range(scalar.n_peers):
                if v == p:
                    continue  # self is unconditionally active
                pr = raft.prs.progress.get(v + 1)
                if pr is None:
                    continue
                assert bool(ra[p, v, g]) == pr.recent_active, (
                    f"{note} round {r}: recent_active[{p},{v}] group {g}: "
                    f"scalar={pr.recent_active} device={bool(ra[p, v, g])}"
                )


# --- claim 1: the damping-off graph is bit-identical ------------------------


def test_damping_off_graph_identical():
    cfg = SimConfig(n_groups=4, n_peers=3)
    cfg_explicit = SimConfig(
        n_groups=4, n_peers=3, check_quorum=False, pre_vote=False
    )
    st = sim_mod.init_state(cfg)
    assert st.recent_active is None  # no extra plane in the undamped tree
    crashed = jnp.zeros((3, 4), bool)
    app = jnp.zeros((4,), jnp.int32)
    base = jax.make_jaxpr(functools.partial(sim_mod.step, cfg))(
        st, crashed, app
    )
    explicit = jax.make_jaxpr(
        functools.partial(sim_mod.step, cfg_explicit)
    )(st, crashed, app)
    assert str(base) == str(explicit)
    # The damped state DOES carry the plane, all-False at boot.
    dcfg = SimConfig(n_groups=4, n_peers=3, check_quorum=True)
    dst = sim_mod.init_state(dcfg)
    assert dst.recent_active is not None
    assert dst.recent_active.dtype == jnp.bool_
    assert not np.asarray(dst.recent_active).any()
    # And an undamped state fed to a damped config fails LOUDLY (e.g. an
    # undamped checkpoint loaded into a damped sim), not deep in tracing.
    with pytest.raises(ValueError, match="recent_active plane"):
        sim_mod.step(dcfg, st, crashed, app)


def test_steady_mask_damped_gate():
    """Since ISSUE 8 damping-on configs CAN ride the fused path, but only
    under the damping conditions: a boot state (no leaders, empty
    recent_active rows) is still rejected for every flag combination, and
    a degenerate heartbeat_tick >= election_tick config is rejected
    wholesale (the boundary re-saturation argument needs a full heartbeat
    interval inside each boundary window).  The acceptance side — settled
    damped states fusing bit-identically — is pinned in
    tests/test_pallas_step.py."""
    for flags in (
        dict(check_quorum=True),
        dict(pre_vote=True),
        dict(check_quorum=True, pre_vote=True),
    ):
        cfg = SimConfig(n_groups=4, n_peers=3, **flags)
        st = sim_mod.init_state(cfg)
        crashed = jnp.zeros((3, 4), bool)
        mask = pallas_step.steady_mask(cfg, st, crashed)
        assert not np.asarray(mask).any(), flags
        assert not bool(
            pallas_step.steady_predicate(cfg, st, crashed)
        ), flags
    degen = SimConfig(
        n_groups=4, n_peers=3, check_quorum=True,
        election_tick=2, heartbeat_tick=2,
    )
    st = sim_mod.init_state(degen)
    assert not np.asarray(
        pallas_step.steady_mask(degen, st, jnp.zeros((3, 4), bool))
    ).any()


def test_check_quorum_active_kernel():
    """Direct unit vs the scalar quorum_recently_active semantics: self
    always counts, joint needs both halves, learners don't count."""
    g = 3
    ra = np.zeros((3, 3, g), bool)
    vm = np.ones((3, g), bool)
    om = np.zeros((3, g), bool)
    # owner 0: no flags -> only self active -> 1 of 3 < quorum
    qa = np.asarray(kernels.check_quorum_active(
        jnp.asarray(ra), jnp.asarray(vm), jnp.asarray(om)
    ))
    assert not qa.any()
    ra[0, 1, :] = True  # one ack -> 2 of 3 >= quorum for owner 0 only
    qa = np.asarray(kernels.check_quorum_active(
        jnp.asarray(ra), jnp.asarray(vm), jnp.asarray(om)
    ))
    assert qa[0].all() and not qa[1:].any()
    # joint: incoming {1,2} active-quorate, outgoing {2,3} not
    vm2 = np.zeros((3, g), bool)
    vm2[:2] = True
    om2 = np.zeros((3, g), bool)
    om2[1:] = True
    qa = np.asarray(kernels.check_quorum_active(
        jnp.asarray(ra), jnp.asarray(vm2), jnp.asarray(om2)
    ))
    assert not qa[0].any()  # outgoing half {2,3} has only... 0 active
    ra[0, 2, :] = True
    qa = np.asarray(kernels.check_quorum_active(
        jnp.asarray(ra), jnp.asarray(vm2), jnp.asarray(om2)
    ))
    assert qa[0].all()


# --- claim 2, tier-1: scheduled parity on the shared sims -------------------


def damped_plan():
    """The tier-1 damped schedule: settle, symmetric split (the isolated
    leader must cq-step-down), asymmetric one-way link (the lease must
    block the disruptor), loss, heal."""
    return chaos.plan_from_dict(
        {
            "name": "tier1-damped-mix",
            "peers": P,
            "phases": [
                {"rounds": 16, "append": 1},
                {"rounds": 14, "partition": [[1, 2], [3]], "append": 1},
                {
                    "rounds": 12,
                    "links": [{"from": 1, "to": 3, "up": False}],
                    "loss": [{"from": 2, "to": 3, "rate": 0.5}],
                    "append": 2,
                },
                {"rounds": 12, "heal": True, "append": 1},
            ],
        }
    )


def run_scheduled(sim, cq, pv, note):
    plan = damped_plan()
    sched = chaos.HostSchedule(plan, G)
    scalar = ScalarCluster(G, P, check_quorum=cq, pre_vote=pv)
    oracle = ChaosOracle(scalar, schedule=sched, window=WINDOW)
    for r in range(plan.n_rounds):
        link, crashed, append = sched.masks(r)
        oracle.scheduled_round()
        sim.run_round(
            jnp.asarray(crashed),
            jnp.asarray(append, dtype=jnp.int32),
            link=jnp.asarray(link),
        )
        assert_parity(scalar, sim, r, note)
        assert_health_parity(oracle, sim, r, note)
        assert_leader_ra_parity(scalar, sim, r, note)


def test_check_quorum_scheduled_parity_g8(cq_sim):
    run_scheduled(reset(cq_sim), cq=True, pv=False, note="cq-scheduled")


def test_pre_vote_scheduled_parity_g8(pv_sim):
    run_scheduled(reset(pv_sim), cq=True, pv=True, note="cq+pv-scheduled")


# --- claim 3, tier-1: the churn collapse (the PR 5 pathology, damped) -------


def _run_disruptor_scenario(sim, rounds=80):
    """The PR 5 asymmetric-partition pathology: one follower per
    disturbed group receives nothing (column cut) but sends everything.
    Returns (leader_row, base_term, base_commit, peak_bumps, term_now,
    commit_now, end_state, safety, leader_deposed_rounds)."""
    settle = jnp.ones((G,), jnp.int32)
    sim.run(30)  # settle leaders, links all-up
    leader_row = np.argmax(
        np.asarray(sim.state.state) == kernels.ROLE_LEADER, axis=0
    )
    link = np.ones((P, P, G), bool)
    for g in range(4):
        link[:, (leader_row[g] + 1) % P, g] = False  # disturb groups 0-3
    base_term = np.asarray(sim.state.term).max(axis=0)
    base_commit = np.asarray(sim.state.commit).max(axis=0)
    sim.reset_health()
    peak_bumps = np.zeros(G, np.int64)
    jl = jnp.asarray(link)
    prev_commit = np.asarray(sim.state.commit)
    safety = np.zeros(kernels.N_SAFETY, np.int64)
    deposed = np.zeros(G, np.int64)
    for r in range(rounds):
        sim.run_round(append_n=settle, link=jl)
        peak_bumps = np.maximum(
            peak_bumps,
            np.asarray(sim._health.planes)[kernels.HP_TERM_BUMPS],
        )
        st = sim.state
        state_np = np.asarray(st.state)
        deposed += (
            state_np[leader_row, np.arange(G)] != kernels.ROLE_LEADER
        )
        safety += np.asarray(
            kernels.check_safety(
                st.state, st.term, st.commit, st.last_index, st.agree,
                jnp.asarray(prev_commit),
            )
        )
        prev_commit = np.asarray(st.commit)
    return (
        leader_row, base_term, base_commit, peak_bumps,
        np.asarray(sim.state.term).max(axis=0),
        np.asarray(sim.state.commit).max(axis=0),
        np.asarray(sim.state.state), safety, deposed,
    )


def test_damped_asymmetric_partition_churn_collapse(cq_sim, pv_sim):
    """The before/after demo pinned as a regression.  UNDAMPED (the PR 5
    pin, tests/test_chaos_parity.py): every disruptor campaign deposes
    the sitting leader — >= 3 fleet term bumps in 80 rounds, vote splits,
    commit stalls.  DAMPED:

      * check-quorum leases alone: every disruptor request lands inside
        a voter's lease and is IGNORED — the sitting leader is NEVER
        deposed and commits flow every round; only the disruptor's own
        term self-inflates (~1 per randomized timeout), so the fleet
        max-term ceiling is pinned at <= 6 over 80 rounds with the churn
        plane never above 1 bump per window;
      * pre-vote on top: the disruptor pre-campaigns WITHOUT bumping
        anything and never gets a pre-quorum — terms freeze entirely.
    """
    # --- check-quorum only: leader protected, disruptor-local inflation.
    (lr, base_term, base_commit, peak, term_now, commit_now, _state,
     safety, deposed) = _run_disruptor_scenario(reset(cq_sim))
    assert (deposed == 0).all(), deposed  # the lease holds: zero churn
    assert (term_now[:4] - base_term[:4] <= 6).all(), term_now - base_term
    assert (term_now[4:] == base_term[4:]).all()
    assert (peak <= 1).all(), peak  # <= one self-bump per churn window
    assert (commit_now - base_commit >= 60).all(), commit_now - base_commit
    assert not safety.any(), dict(zip(kernels.SAFETY_NAMES, safety))

    # --- pre-vote + check-quorum: the full freeze.
    (lr, base_term, base_commit, peak, term_now, commit_now, _state,
     safety, deposed) = _run_disruptor_scenario(reset(pv_sim))
    assert (deposed == 0).all(), deposed
    assert (term_now == base_term).all(), term_now - base_term
    assert (peak == 0).all(), peak
    assert (commit_now - base_commit >= 60).all(), commit_now - base_commit
    assert not safety.any(), dict(zip(kernels.SAFETY_NAMES, safety))


def test_check_quorum_isolated_leader_steps_down(cq_sim):
    """The other half of the damping story: a leader whose links are ALL
    cut steps itself down within one election_tick (check-quorum reads an
    empty recent_active row), instead of ruling a ghost partition."""
    sim = reset(cq_sim)
    sim.run(30)
    leader_row = np.argmax(
        np.asarray(sim.state.state) == kernels.ROLE_LEADER, axis=0
    )
    link = np.ones((P, P, G), bool)
    for g in range(G):
        link[leader_row[g], :, g] = False
        link[:, leader_row[g], g] = False
    jl = jnp.asarray(link)
    for r in range(2 * sim.cfg.election_tick + 1):
        sim.run_round(link=jl)
    state = np.asarray(sim.state.state)
    for g in range(G):
        assert state[leader_row[g], g] != kernels.ROLE_LEADER, (
            f"group {g}: isolated leader still leading after "
            f"2*election_tick rounds"
        )


# --- claim 5, tier-1: link-aware ReadIndex ----------------------------------


def scalar_read_probe(cluster, g, crashed_row, link_row=None):
    """Issue a real Safe-mode read at group g's acting leader and pump
    under per-edge drops.  Returns the read index or -1."""
    net = cluster.networks[g]
    cluster._apply_crash_mask(net, crashed_row, link_row)
    lead = cluster.acting_leader(g, crashed_row)
    if lead is None:
        return -1
    iface = net.peers[lead]
    before = len(iface.raft.read_states)
    net.send([
        Message(
            msg_type=MessageType.MsgReadIndex,
            from_=lead,
            to=lead,
            entries=[Entry(data=b"probe")],
        )
    ])
    rs = iface.raft.read_states
    if len(rs) > before:
        return rs[-1].index
    return -1


def test_read_index_link_aware():
    """Device read_index under a link plane == the scalar cluster's real
    MsgReadIndex pump under the same per-edge drops: a two-way healthy
    quorum serves, a one-way-cut majority (acks cannot return) fails the
    barrier even though heartbeats still reach everyone, and the
    crash-mask graph is untouched by link=None."""
    n_groups = 4
    scalar = ScalarCluster(n_groups, P)
    sim = ClusterSim(SimConfig(n_groups=n_groups, n_peers=P))
    app = jnp.ones((n_groups,), jnp.int32)
    crashed = np.zeros((n_groups, P), bool)
    for _ in range(20):
        scalar.round(crashed, np.ones(n_groups, np.int64))
        sim.run_round(append_n=app)
    assert_parity(scalar, sim, 19, "read-index-settle")
    leader_row = np.argmax(
        np.asarray(sim.state.state) == kernels.ROLE_LEADER, axis=0
    )
    link = np.ones((P, P, n_groups), bool)
    # group 1: cut every ack path back to the leader (one-way out only)
    link[:, leader_row[1], 1] = False
    # group 2: cut the leader's outbound links (heartbeats never land)
    link[leader_row[2], :, 2] = False
    # group 3: cut one member both ways; quorum = 2 of 3 still holds
    link[(leader_row[3] + 1) % P, :, 3] = False
    link[:, (leader_row[3] + 1) % P, 3] = False
    got = np.asarray(sim.read_index(link=jnp.asarray(link)))
    for g in range(n_groups):
        want = scalar_read_probe(scalar, g, crashed[g], link[:, :, g])
        assert got[g] == want, f"group {g}: device={got[g]} scalar={want}"
    assert got[0] >= 0 and got[3] >= 0
    assert got[1] == -1 and got[2] == -1
    # link=None keeps the crash-mask-only result (and its traced graph).
    base = jax.make_jaxpr(
        functools.partial(sim_mod.read_index, sim.cfg)
    )(sim.state, jnp.asarray(crashed.T))
    with_none = jax.make_jaxpr(
        lambda s, c: sim_mod.read_index(sim.cfg, s, c, link=None)
    )(sim.state, jnp.asarray(crashed.T))
    assert str(base) == str(with_none)


# --- claim 2 at scale: seeded damped link fuzz (slow tier) ------------------


def run_damped_link_fuzz(seed, n_groups, n_peers, rounds, cq, pv,
                         flip=0.08, crashp=0.03, voters=None,
                         outgoing=None, learners=None):
    kwargs = {}
    if voters:
        kwargs["voters"] = voters
        if outgoing:
            kwargs["voters_outgoing"] = outgoing
        if learners:
            kwargs["learners"] = learners
    scalar = ScalarCluster(n_groups, n_peers, check_quorum=cq, pre_vote=pv,
                           **kwargs)
    oracle = ChaosOracle(scalar, window=WINDOW)
    vm = om = lm = None
    if voters:
        vm_np = np.zeros((n_peers, n_groups), bool)
        om_np = np.zeros((n_peers, n_groups), bool)
        lm_np = np.zeros((n_peers, n_groups), bool)
        for i in voters:
            vm_np[i - 1] = True
        for i in (outgoing or []):
            om_np[i - 1] = True
        for i in (learners or []):
            lm_np[i - 1] = True
        vm, om, lm = map(jnp.asarray, (vm_np, om_np, lm_np))
    sim = ClusterSim(
        SimConfig(n_groups=n_groups, n_peers=n_peers, collect_health=True,
                  health_window=WINDOW, check_quorum=cq, pre_vote=pv),
        vm, om, lm,
    )
    rng = np.random.RandomState(seed)
    link = np.ones((n_peers, n_peers, n_groups), bool)
    crash = np.zeros((n_groups, n_peers), bool)
    prev_commit = np.asarray(sim.state.commit)
    note = f"damped-fuzz seed {seed} cq={cq} pv={pv}"
    for r in range(rounds):
        for g in range(n_groups):
            for _ in range(2):
                if rng.rand() < flip:
                    a, b = rng.randint(n_peers), rng.randint(n_peers)
                    if a != b:
                        link[a, b, g] ^= True
            if rng.rand() < crashp:
                crash[g, rng.randint(n_peers)] ^= True
            if rng.rand() < 0.05:
                link[:, :, g] = True
                crash[g, :] = False
        app = rng.randint(0, 3, size=n_groups).astype(np.int64)
        oracle.round(crash, app, link)
        sim.run_round(jnp.asarray(crash.T.copy()),
                      jnp.asarray(app, dtype=jnp.int32),
                      link=jnp.asarray(link.copy()))
        assert_parity(scalar, sim, r, note)
        assert_health_parity(oracle, sim, r, note)
        assert_leader_ra_parity(scalar, sim, r, note)
        st = sim.state
        counts = np.asarray(
            kernels.check_safety(
                st.state, st.term, st.commit, st.last_index, st.agree,
                jnp.asarray(prev_commit),
            )
        )
        prev_commit = np.asarray(st.commit)
        assert not counts.any(), (
            f"{note} round {r}: safety violations "
            f"{dict(zip(kernels.SAFETY_NAMES, counts.tolist()))}"
        )


def test_damped_link_fuzz_check_quorum():
    for seed in range(3):
        run_damped_link_fuzz(seed, 4, 3, 90, cq=True, pv=False)


def test_damped_link_fuzz_pre_vote():
    for seed in range(3):
        run_damped_link_fuzz(seed, 4, 3, 90, cq=False, pv=True)


def test_damped_link_fuzz_both_flags():
    for seed in range(3):
        run_damped_link_fuzz(seed, 4, 3, 90, cq=True, pv=True)


def test_damped_link_fuzz_5peers_and_configs():
    run_damped_link_fuzz(20, 3, 5, 70, cq=True, pv=True)
    run_damped_link_fuzz(30, 3, 5, 70, cq=True, pv=True,
                         voters=[1, 2, 3], outgoing=[3, 4, 5])
    run_damped_link_fuzz(40, 3, 4, 70, cq=True, pv=False,
                         voters=[1, 2, 3], learners=[4])
    run_damped_link_fuzz(41, 3, 4, 70, cq=False, pv=True,
                         voters=[1, 2, 3], learners=[4])


@pytest.mark.slow
def test_damped_link_fuzz_at_scale_g32():
    run_damped_link_fuzz(3, 32, 3, 90, cq=True, pv=True, flip=0.05)


@pytest.mark.slow  # golden corpus at G=32, damped, oracle in lockstep
def test_damped_golden_corpus_parity_g32():
    """All six golden-corpus scenarios (tests/testdata/chaos) replayed
    under the fully damped configuration with exact oracle parity — the
    acceptance-criteria sweep."""
    import json
    import os

    path = os.path.join(
        os.path.dirname(__file__), "testdata", "chaos", "plans.json"
    )
    with open(path, "r", encoding="utf-8") as f:
        docs = json.load(f)
    assert len(docs) >= 6
    for doc in docs:
        plan = chaos.plan_from_dict(doc)
        n_groups = 32
        sched = chaos.HostSchedule(plan, n_groups)
        scalar = ScalarCluster(n_groups, plan.n_peers, check_quorum=True,
                               pre_vote=True)
        oracle = ChaosOracle(scalar, schedule=sched, window=WINDOW)
        sim = ClusterSim(
            SimConfig(n_groups=n_groups, n_peers=plan.n_peers,
                      collect_health=True, health_window=WINDOW,
                      check_quorum=True, pre_vote=True)
        )
        for r in range(plan.n_rounds):
            link, crashed, append = sched.masks(r)
            oracle.scheduled_round()
            sim.run_round(
                jnp.asarray(crashed),
                jnp.asarray(append, dtype=jnp.int32),
                link=jnp.asarray(link),
            )
            assert_parity(scalar, sim, r, plan.name)
            assert_health_parity(oracle, sim, r, plan.name)
