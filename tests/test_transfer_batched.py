"""Batched leader transfer (ISSUE 12): exact per-round parity vs the
scalar RawNode::transfer_leader pump (simref.TransferOracle) plus the
scalar suite's corner cases (tests/test_leader_transfer_extra.py)
replayed through the batched paths — transfer to lagging/crashed/removed
targets, abort on timeout, transferee wins mid-partition, second
transfer overriding the first — and the campaign-kick action.

Tier-1 runs G=8 schedules with ONE jitted step per configuration
(module-level cache); the G>=32 and >=100-round fuzz sweeps are
@pytest.mark.slow (ROADMAP.md's standing constraint;
tier-1 takes 247 s of its 1470 s limit under xdist -n 6 at PR 32)."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from raft_tpu.multiraft import kernels
from raft_tpu.multiraft import sim
from raft_tpu.multiraft.sim import SimConfig
from raft_tpu.multiraft.simref import ScalarCluster, TransferOracle, clone_cluster

G, P = 8, 3

_STEP_CACHE = {}

# Every tier-1/fuzz schedule in this module is null (no transfer, kick,
# link, or crash) through its leader-election warmup, so run_parity
# replays rounds [0, WARM_ROUNDS) ONCE per configuration and hands each
# test a memo-seeded clone of the warmed oracle (simref.clone_cluster —
# ROADMAP's standing constraint: share the ~16s deepcopies
# module-scoped) plus the immutable device state/health pytrees.
WARM_ROUNDS = 14
_WARM_CACHE = {}


def _step_for(cfg: SimConfig):
    key = (cfg.n_groups, cfg.n_peers, cfg.check_quorum, cfg.pre_vote)
    fn = _STEP_CACHE.get(key)
    if fn is None:
        fn = jax.jit(functools.partial(sim.step, cfg))
        _STEP_CACHE[key] = fn
    return fn


def _parity_round(step, st, hl, orc, r, schedule, check_transferee, g, p):
    """One lockstep round + the per-round parity asserts.  The device
    call always passes concrete transfer_propose/kick/link planes (the
    module's ONE canonical traced signature per configuration: None and
    the neutral plane are behavior-identical — step() substitutes zeros
    for None itself — but each None/array combination is its own jit
    trace, and the retraces used to dominate this suite's tier-1 bill)."""
    crashed_h = np.zeros((g, p), bool)
    tp, kick, link, crashed_h = schedule(r, st, crashed_h)
    append_h = np.ones((g,), np.int64)
    st, hl = step(
        st,
        jnp.asarray(crashed_h.T),
        jnp.asarray(append_h, dtype=jnp.int32),
        health=hl,
        transfer_propose=jnp.zeros((g,), jnp.int32)
        if tp is None else jnp.asarray(tp),
        campaign_kick=jnp.zeros((p, g), bool)
        if kick is None else jnp.asarray(kick.T),
        link=jnp.ones((p, p, g), bool)
        if link is None else jnp.asarray(link),
    )
    orc.round(
        crashed=crashed_h, append_n=append_h, link=link,
        transfer_propose=tp, kick=kick,
    )
    snap = orc.cluster.snapshot()
    for k in ("term", "state", "commit", "last_index", "last_term"):
        dev = np.asarray(getattr(st, k)).T
        assert np.array_equal(dev, snap[k]), (
            f"round {r}: {k} diverged\ndev=\n{dev}\norc=\n{snap[k]}"
        )
    if check_transferee:
        assert np.array_equal(
            np.asarray(st.transferee).T, orc.pending()
        ), f"round {r}: lead_transferee diverged"
    assert np.array_equal(
        np.asarray(orc.planes), np.asarray(hl.planes)
    ), f"round {r}: health planes diverged"
    return st, hl


def _null_schedule(r, st, crashed_h):
    return None, None, None, crashed_h


def _fresh_pair(g, p, damped, voters, learners):
    cfg = SimConfig(
        n_groups=g, n_peers=p, collect_health=True, transfer=True,
        check_quorum=damped, pre_vote=damped,
    )
    vm = lm = None
    if voters is not None:
        v = np.zeros((p, g), bool)
        l = np.zeros((p, g), bool)
        for pid in voters:
            v[pid - 1] = True
        for pid in learners or []:
            l[pid - 1] = True
        vm, lm = jnp.asarray(v), jnp.asarray(l)
    st = sim.init_state(cfg, vm, None, lm)
    hl = sim.init_health(cfg)
    cl = ScalarCluster(
        g, p, check_quorum=damped, pre_vote=damped,
        voters=voters, learners=learners,
    )
    orc = TransferOracle(cl, window=cfg.health_window)
    return st, hl, orc, _step_for(cfg)


def run_parity(
    schedule,
    rounds,
    g=G,
    p=P,
    damped=False,
    voters=None,
    learners=None,
    check_transferee=True,
):
    """Drive identical schedules through the transfer-enabled device step
    and the TransferOracle; assert exact per-round state + health (+
    lead_transferee) parity.  `schedule(r, st, crashed_h)` returns
    (transfer_propose[G] | None, kick[G, P] | None, link[P, P, G] | None,
    crashed[G, P]); it MUST be null before WARM_ROUNDS — the warmup is
    replayed once per configuration and shared (parity asserted while
    the master is built, skipped on cache hits)."""
    key = (
        g, p, damped,
        tuple(voters or ()), tuple(learners or ()), check_transferee,
    )
    assert rounds >= WARM_ROUNDS, "schedules must be null pre-warmup"
    warm = _WARM_CACHE.get(key)
    if warm is None:
        st, hl, orc, step = _fresh_pair(g, p, damped, voters, learners)
        for r in range(WARM_ROUNDS):
            st, hl = _parity_round(
                step, st, hl, orc, r, _null_schedule, check_transferee,
                g, p,
            )
        warm = _WARM_CACHE[key] = (st, hl, orc, step)
    st, hl, master_orc, step = warm
    orc = clone_cluster(master_orc)
    for r in range(WARM_ROUNDS, rounds):
        st, hl = _parity_round(
            step, st, hl, orc, r, schedule, check_transferee, g, p
        )
    return st, orc.cluster, orc


def _targets_for(st, swap=(2, 1)):
    """Per-group transfer targets: groups led by peer 1 -> swap[0], the
    rest -> swap[1]."""
    lead = np.asarray(st.leader_id).max(axis=0)
    return np.where(lead == 1, swap[0], swap[1]).astype(np.int32)


# --- tier-1: the plain path (one compiled graph shared by all cases) -------


def test_transfer_basic_and_leadership_moves():
    """A healthy-fleet transfer completes within its round: the target
    campaigns with CAMPAIGN_TRANSFER, wins, commits its noop — and the
    workload keeps flowing at the new leader."""
    captured = {}

    def schedule(r, st, crashed_h):
        tp = None
        if r == 22:
            tp = _targets_for(st)
            captured["targets"] = tp
        return tp, None, None, crashed_h

    st, cl, orc = run_parity(schedule, 28)
    lead = np.asarray(st.leader_id).max(axis=0)
    assert np.array_equal(lead, captured["targets"]), (
        "leadership did not land on the requested targets"
    )
    # completed transfers leave no pending state
    assert not np.asarray(st.transferee).any()


def test_transfer_to_lagging_target_catches_up_first():
    """The scalar suite's lagging-target case (reference:
    test_raft.rs:3443-3476's shape, sans snapshot): the target is crashed
    long enough to fall behind; the transfer's catch-up append brings it
    to the leader's log before MsgTimeoutNow fires."""

    def schedule(r, st, crashed_h):
        tp = None
        if 14 <= r < 20:
            crashed_h[:, 2] = True  # peer 3 lags
        if r == 22:
            lead = np.asarray(st.leader_id).max(axis=0)
            tp = np.where(lead == 3, 0, 3).astype(np.int32)
        return tp, None, None, crashed_h

    st, cl, orc = run_parity(schedule, 30)
    lead = np.asarray(st.leader_id).max(axis=0)
    assert (lead == 3).any(), "no group's leadership reached the ex-laggard"


def test_transfer_to_crashed_target_pends_blocks_then_aborts():
    """Transfer to an unreachable target: lead_transferee stays pending,
    proposals are DROPPED at the leader (the scalar
    test_leader_transfer_ignore_proposal rule), and the transfer clock
    expiring at the leader's election-timeout boundary abandons it."""
    seen = {}

    def schedule(r, st, crashed_h):
        tp = None
        if 20 <= r < 40:
            crashed_h[:, 2] = True
        if r == 21:
            lead = np.asarray(st.leader_id).max(axis=0)
            tp = np.where(lead == 3, 0, 3).astype(np.int32)
        if r == 24:
            seen["pending"] = np.asarray(st.transferee).sum()
            seen["last_at_pending"] = np.asarray(st.last_index).max(axis=0)
        if r == 28:
            # proposals blocked while pending: the log did not grow
            seen["last_later"] = np.asarray(st.last_index).max(axis=0)
        return tp, None, None, crashed_h

    st, cl, orc = run_parity(schedule, 40)
    assert seen["pending"] > 0, "transfer never went pending"
    blocked = seen["last_later"] - seen["last_at_pending"]
    assert (blocked == 0).any(), (
        "a pending transfer failed to block proposals"
    )
    # the election-timeout abort cleared every pending transfer
    assert not np.asarray(st.transferee).any()


def test_second_transfer_overrides_first():
    """reference: test_raft.rs:3633-3651 — a second command to a
    DIFFERENT target aborts the pending transfer and starts over."""

    def schedule(r, st, crashed_h):
        tp = None
        link = None
        if 20 <= r < 32:
            link = np.ones((P, P, G), bool)
            link[:, 2, :] = False
            link[2, :, :] = False  # peer 3 unreachable
            lead = np.asarray(st.leader_id).max(axis=0)
            if r == 21:
                tp = np.where(lead == 3, 0, 3).astype(np.int32)
            if r == 25:
                tp = np.where(
                    lead == 1, 2, np.where(lead == 2, 1, 0)
                ).astype(np.int32)
        return tp, None, link, crashed_h

    run_parity(schedule, 36)


def test_transfer_to_learner_refused():
    """reference: handle_transfer_leader's learner check — the command is
    ignored; nothing pends, nothing blocks.  Voters {1, 2} + learner 3
    keeps the shape on the shared P=3 compile."""

    def schedule(r, st, crashed_h):
        tp = np.full(G, 3, np.int32) if r == 20 else None
        return tp, None, None, crashed_h

    st, _, _ = run_parity(
        schedule, 26, voters=[1, 2], learners=[3]
    )
    assert not np.asarray(st.transferee).any()


def test_transferee_wins_mid_partition():
    """The linked path: leadership moves between the two connected peers
    while the third is fully partitioned away — the transfer election
    resolves inside the majority component."""

    def schedule(r, st, crashed_h):
        tp = None
        link = None
        if 20 <= r < 32:
            link = np.ones((P, P, G), bool)
            link[0, 2, :] = link[2, 0, :] = False
            link[1, 2, :] = link[2, 1, :] = False
            if r == 21:
                tp = _targets_for(st)
        return tp, None, link, crashed_h

    run_parity(schedule, 36)


def test_one_way_ack_cut_withholds_timeout_now():
    """A one-way target->leader cut delivers the catch-up append but
    never the ack: MsgTimeoutNow is withheld and the transfer pends (the
    raft-rs pause discipline, including the fresh winner's paused-probe
    commit re-broadcast)."""

    def schedule(r, st, crashed_h):
        tp = None
        link = None
        if 20 <= r < 30:
            link = np.ones((P, P, G), bool)
            link[1, 0, :] = False  # 2 -> 1 down
            if r == 21:
                tp = _targets_for(st)
        return tp, None, link, crashed_h

    run_parity(schedule, 34)


def test_campaign_kick_heals_leaderless_groups():
    """The autopilot's kick action: MsgHup at a chosen follower ends a
    crash-induced leaderless episode immediately instead of waiting out
    the randomized timeout."""
    seen = {}

    def schedule(r, st, crashed_h):
        kick = None
        if 20 <= r < 34:
            crashed_h[:, 0] = True
        if r == 22:
            lead = np.asarray(st.leader_id).max(axis=0)
            seen.setdefault("leaderless", (lead == 0).sum())
            kick = np.zeros((G, P), bool)
            kick[:, 1] = True
        return None, kick, None, crashed_h

    st, cl, orc = run_parity(schedule, 38)


# --- tier-1: the damped path (one compiled graph) --------------------------


def test_transfer_damped_with_kick():
    """check_quorum + pre_vote: the transfer campaign skips the pre-vote
    probe and forces through leases (CAMPAIGN_TRANSFER), while a kick
    goes through the ordinary pre-vote machinery."""

    def schedule(r, st, crashed_h):
        tp = kick = None
        if r == 22:
            tp = _targets_for(st)
        if 26 <= r < 36:
            crashed_h[:, 0] = True
        if r == 29:
            kick = np.zeros((G, P), bool)
            kick[:, 1] = True
        return tp, kick, None, crashed_h

    run_parity(schedule, 40, damped=True)


# --- kernel units (GC006) --------------------------------------------------


def test_apply_transfer_validation_rules():
    """Batched handle_transfer_leader: member/learner/self checks, the
    same-target early return, the different-target override, and the
    abort-on-self-command ordering quirk."""
    g = 6
    p = 4
    # acting leader = peer 1 everywhere
    acting = jnp.asarray(
        np.tile(np.array([[True], [False], [False], [False]]), (1, g))
    )
    member = np.ones((p, g), bool)
    member[3] = False  # peer 4 outside every config
    learner = np.zeros((p, g), bool)
    learner[2] = True  # peer 3 is a learner
    transferee = np.zeros((p, g), np.int32)
    transferee[0, 4] = 2  # group 4 already transferring to 2
    transferee[0, 5] = 2  # group 5 pending too
    ee = np.full((p, g), 7, np.int32)
    #          g0: valid  g1: learner  g2: self  g3: non-member
    #          g4: same target (no-op)  g5: leader-self aborts pending
    propose = np.asarray([2, 3, 1, 4, 2, 1], np.int32)
    t2, ee2, accepted = kernels.apply_transfer(
        jnp.asarray(transferee), jnp.asarray(ee), acting,
        jnp.asarray(propose), jnp.asarray(member), jnp.asarray(learner),
    )
    t2, ee2, accepted = map(np.asarray, (t2, ee2, accepted))
    assert accepted.tolist() == [True, False, False, False, False, False]
    assert t2[0].tolist() == [2, 0, 0, 0, 2, 0]  # g5's pending aborted
    assert ee2[0].tolist() == [0, 7, 7, 7, 7, 7]  # clock reset on accept


def test_acting_leader_id_matches_scalar():
    cl = ScalarCluster(4, 3)
    crashed = np.zeros((4, 3), bool)
    for r in range(24):
        cl.round(crashed, np.ones((4,), np.int64))
    snap = cl.snapshot()
    state = jnp.asarray(snap["state"].T.astype(np.int32))
    term = jnp.asarray(snap["term"].T.astype(np.int32))
    crashed_j = jnp.zeros((3, 4), bool)
    got = np.asarray(kernels.acting_leader_id(state, term, crashed_j))
    want = [cl.acting_leader(g, crashed[g]) or 0 for g in range(4)]
    assert got.tolist() == want
    # crashing the leader removes it from the answer
    crashed2 = np.zeros((3, 4), bool)
    for g, lead in enumerate(want):
        crashed2[lead - 1, g] = True
    got2 = np.asarray(
        kernels.acting_leader_id(state, term, jnp.asarray(crashed2))
    )
    assert not any(a == b for a, b in zip(got2.tolist(), want))


def test_apply_confchange_aborts_removed_transferee():
    """reference: raft.rs:1356 / test_raft.rs:3590-3612 — removing the
    pending target from the (joint) voter set aborts the transfer, as
    does the owner being stepped down by the change."""
    g = 3
    state = jnp.asarray(np.tile([[2], [0], [0]], (1, g)), dtype=jnp.int32)
    leader_id = jnp.asarray(np.tile([[1], [1], [1]], (1, g)), dtype=jnp.int32)
    commit = jnp.full((3, g), 5, jnp.int32)
    ts = jnp.full((3, g), 4, jnp.int32)
    matched = jnp.full((3, 3, g), 5, jnp.int32)
    vm = jnp.ones((3, g), bool)
    om = jnp.zeros((3, g), bool)
    lm = jnp.zeros((3, g), bool)
    transferee = np.zeros((3, g), np.int32)
    transferee[0, :] = 3  # leader 1 transferring to 3 everywhere
    # target config drops peer 3 from the voters
    tgt_v = jnp.asarray(np.tile([[True], [True], [False]], (1, g)))
    no = jnp.zeros((3, g), bool)
    removed = jnp.asarray(np.tile([[False], [False], [True]], (1, g)))
    apply_mask = jnp.asarray([True, False, True])
    *_, tr = kernels.apply_confchange(
        state, leader_id, commit, ts, matched, vm, om, lm,
        tgt_v, no, no, no, removed, apply_mask, None,
        jnp.asarray(transferee),
    )
    tr = np.asarray(tr)
    assert tr[0].tolist() == [0, 3, 0]  # applied groups aborted


def test_transfer_off_graphs_pinned():
    """SimConfig(transfer=False) keeps the pytree (and so the traced
    graphs) bit-identical to the pre-transfer build, and transfer
    commands without the plane fail loudly."""
    cfg = SimConfig(n_groups=4, n_peers=3)
    st = sim.init_state(cfg)
    assert st.transferee is None
    out = sim.step(
        cfg, st, jnp.zeros((3, 4), bool), jnp.ones((4,), jnp.int32)
    )
    assert out.transferee is None
    with pytest.raises(ValueError, match="SimConfig\\(transfer=True\\)"):
        sim.step(
            cfg, st, jnp.zeros((3, 4), bool), jnp.ones((4,), jnp.int32),
            transfer_propose=jnp.zeros((4,), jnp.int32),
        )


def test_steady_mask_rejects_pending_transfer():
    from raft_tpu.multiraft import pallas_step

    cfg = SimConfig(n_groups=4, n_peers=3, transfer=True)
    st = sim.init_state(cfg)
    step = jax.jit(functools.partial(sim.step, cfg))
    crashed = jnp.zeros((3, 4), bool)
    append = jnp.ones((4,), jnp.int32)
    for _ in range(40):
        st = step(st, crashed, append)
    base = np.asarray(pallas_step.steady_mask(cfg, st, crashed, horizon=1))
    assert base.all(), "settled fleet should be steady"
    tr = np.zeros((3, 4), np.int32)
    tr[0, 1] = 2  # group 1 carries a pending transfer
    st2 = st._replace(transferee=jnp.asarray(tr))
    masked = np.asarray(
        pallas_step.steady_mask(cfg, st2, crashed, horizon=1)
    )
    assert masked.tolist() == [True, False, True, True]


# (The transferee checkpoint round-trip moved to the registry-driven
# tests/test_planes_registry.py, which covers every persisted plane.)


# --- slow: fuzz + scale ----------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("damped", [False, True])
@pytest.mark.parametrize("seed", [7, 23])
def test_transfer_fuzz_parity(seed, damped):
    """Randomized transfers/kicks/links/crashes over 100+ rounds: exact
    per-round parity of state, health planes, and lead_transferee."""
    rng = np.random.RandomState(seed)

    def schedule(r, st, crashed_h):
        tp = kick = link = None
        if r >= 20:
            if rng.rand() < 0.3:
                link = np.ones((P, P, G), bool)
                for _ in range(rng.randint(1, 4)):
                    link[
                        rng.randint(P), rng.randint(P), rng.randint(G)
                    ] = False
            if rng.rand() < 0.2:
                crashed_h[rng.randint(G), rng.randint(P)] = True
            if rng.rand() < 0.4:
                tp = rng.randint(0, P + 1, size=G).astype(np.int32)
                tp[rng.rand(G) < 0.5] = 0
            if rng.rand() < 0.2:
                kick = rng.rand(G, P) < 0.2
        return tp, kick, link, crashed_h

    run_parity(schedule, 110, damped=damped)


@pytest.mark.slow
def test_transfer_parity_g64():
    """Wide-batch parity: staggered transfers across a G=64 fleet."""
    def schedule(r, st, crashed_h):
        tp = None
        if r in (22, 30, 38):
            lead = np.asarray(st.leader_id).max(axis=0)
            tp = np.where(lead == 1 + (r // 8) % 3, 2, 0).astype(np.int32)
            tp[::2] = 0  # half the groups per wave
        return tp, None, None, crashed_h

    run_parity(schedule, 60, g=64)
