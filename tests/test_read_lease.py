"""Lease-based linearizable read parity (ISSUE 13).

`sim.step(read_propose=)` receipts — index, lease-vs-degraded decision,
serve round — must match simref.ReadOracle driving the REAL scalar read
pumps (`ReadOnlyOption::LeaseBased` for lease serves, `Safe` for the
fallback arm) per round.  The scalar probe perturbs, so the oracle runs
each probe on a throwaway deepcopy of the group's Network; the lockstep
state parity composes unchanged and is asserted alongside.

The negative tests inject the classic stale-read trap — a
deposed-but-unaware leader with a paused clock serving lease reads across
a partition while the new majority commits — and prove the
kernels.check_safety linearizability slots (SV_STALE_READ /
SV_DUAL_LEASE) fire on it and stay zero without the clock pause.
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from raft_tpu.multiraft import ScalarCluster, SimConfig, kernels, sim
from raft_tpu.multiraft.simref import ReadOracle, clone_cluster


def _masks(G, P, voters, outgoing, learners):
    if voters is None:
        return None, None, None
    vm = np.zeros((P, G), bool)
    om = np.zeros((P, G), bool)
    lm = np.zeros((P, G), bool)
    for id in voters:
        vm[id - 1] = True
    for id in outgoing or []:
        om[id - 1] = True
    for id in learners or []:
        lm[id - 1] = True
    return jnp.asarray(vm), jnp.asarray(om), jnp.asarray(lm)


_STEP_CACHE = {}


def _step_for(cfg):
    """ONE jitted step per SimConfig, shared across every test in this
    module — the damped wave-path compile is the whole cost of this
    suite, so tier-1 cases reuse one compile per configuration (the
    tier-1 budget discipline; heavy shape/flag variations are
    slow-marked)."""
    fn = _STEP_CACHE.get(cfg)
    if fn is None:
        fn = jax.jit(functools.partial(sim.step, cfg))
        _STEP_CACHE[cfg] = fn
    return fn


def build_pair(
    G, P, check_quorum=False, pre_vote=False, lease=None, transfer=False,
    voters=None, outgoing=None, learners=None, election_tick=10,
):
    """(oracle, cfg, state, jitted step) in the plan's configuration.
    `lease` defaults to check_quorum (LeaseBased requires check_quorum —
    the Config.validate rule both sides enforce)."""
    if lease is None:
        lease = check_quorum
    cfg = SimConfig(
        n_groups=G, n_peers=P, election_tick=election_tick,
        check_quorum=check_quorum, pre_vote=pre_vote,
        lease_read=lease, transfer=transfer,
    )
    kwargs = {}
    if voters is not None:
        kwargs = dict(
            voters=voters, voters_outgoing=outgoing or [],
            learners=learners or [],
        )
    scalar = ScalarCluster(
        G, P, election_tick=election_tick, check_quorum=check_quorum,
        pre_vote=pre_vote, **kwargs,
    )
    oracle = ReadOracle(
        scalar, election_tick=election_tick, lease_read=lease
    )
    vm, om, lm = _masks(G, P, voters, outgoing, learners)
    st = sim.init_state(cfg, vm, om, lm)
    return oracle, cfg, st, _step_for(cfg)


def full_link(G, P):
    return jnp.ones((P, P, G), bool)


def assert_receipts(receipt, want, tag):
    got = (
        np.asarray(receipt.index),
        np.asarray(receipt.lease),
        np.asarray(receipt.degraded),
    )
    for g, (w_idx, w_lease, w_deg) in enumerate(want):
        assert got[0][g] == w_idx, (
            f"{tag} group {g}: index {got[0][g]} != scalar {w_idx}"
        )
        assert bool(got[1][g]) == w_lease, (
            f"{tag} group {g}: lease {bool(got[1][g])} != scalar {w_lease}"
        )
        assert bool(got[2][g]) == w_deg, (
            f"{tag} group {g}: degraded {bool(got[2][g])} != {w_deg}"
        )


def assert_state_parity(oracle, st, tag):
    snap = oracle.cluster.snapshot()
    for key in ("term", "state", "commit", "last_index", "last_term"):
        dev = np.asarray(getattr(st, key)).T
        assert np.array_equal(dev, snap[key]), f"{tag}: {key} diverged"


def run_read_storm(
    seed, G, P, rounds, check_quorum=False, pre_vote=False,
    transfer=False, voters=None, outgoing=None, learners=None,
):
    """The probe-schedule storm of test_read_index_batch, with reads of a
    seeded mode mix issued EVERY round and receipt parity asserted per
    round (the oracle probes deep copies, so the lockstep run proceeds
    unperturbed on both sides)."""
    oracle, cfg, st, step_fn = build_pair(
        G, P, check_quorum=check_quorum, pre_vote=pre_vote,
        transfer=transfer, voters=voters, outgoing=outgoing,
        learners=learners,
    )
    rng = np.random.RandomState(seed)
    crashed = np.zeros((G, P), bool)
    for r in range(rounds):
        for g in range(G):
            roll = rng.rand()
            if roll < 0.10:
                crashed[g, rng.randint(P)] ^= True
            elif roll < 0.14:
                snap = oracle.cluster.snapshot()
                leaders = np.where(snap["state"][g] == 2)[0]
                if len(leaders):
                    crashed[g, leaders[0]] = True
            elif roll < 0.16:
                crashed[g, :] = False
            if crashed[g].sum() == P:
                crashed[g, rng.randint(P)] = False
        append = rng.randint(0, 3, size=G).astype(np.int64)
        modes = rng.randint(0, 3, size=G).astype(np.int32)
        kw = {}
        if check_quorum or pre_vote:
            # The module's canonical damped signature (explicit all-up
            # link): every damped test shares one traced graph per cfg.
            kw["link"] = full_link(G, P)
        st, receipt = step_fn(
            st, jnp.asarray(crashed.T), jnp.asarray(append, jnp.int32),
            read_propose=jnp.asarray(modes), **kw,
        )
        oracle.round(crashed, append, read_propose=modes)
        assert_receipts(
            receipt, oracle.last_receipts, f"seed {seed} round {r}"
        )
    assert_state_parity(oracle, st, f"seed {seed} end")


def receipt_digest(step_fn, st, G, P, rounds=60, seed=40):
    """sha1 over every round's ReadReceipt (index, lease, degraded, and the
    audit's mask where the fleet has one) of a seeded storm of crashes,
    directed link cuts and read modes — device only, integers only, so the
    digest is a constant of the PROGRAM: pinned below and in
    tests/test_stock_fleet.py from the tree before ISSUE 40 touched
    sim._read_phase, to show it changed one branch only."""
    import hashlib

    rng = np.random.RandomState(seed)
    h = hashlib.sha1()
    served = held = 0
    crashed = np.zeros((P, G), bool)
    up = np.ones((P, P, G), bool)
    for r in range(rounds):
        flip = rng.rand(P, G) < 0.04
        crashed ^= flip
        crashed[:, crashed.all(axis=0)] = False
        cut = up & ~(rng.rand(P, P, G) < 0.06)
        modes = rng.randint(0, 3, size=G).astype(np.int32)
        st, receipt = step_fn(
            st, jnp.asarray(crashed), jnp.asarray(rng.randint(0, 3, size=G), jnp.int32),
            link=jnp.asarray(cut), read_propose=jnp.asarray(modes),
        )
        for part in (receipt.index, receipt.lease, receipt.degraded):
            h.update(np.asarray(part).astype(np.int32).tobytes())
        h.update(b"no mask" if receipt.holders is None
                 else np.asarray(receipt.holders).astype(np.int8).tobytes())
        served += int((np.asarray(receipt.index) >= 0).sum())
        held += 0 if receipt.holders is None else int(np.asarray(receipt.holders).sum())
    h.update(np.asarray(st.commit).astype(np.int32).tobytes())
    return h.hexdigest(), served, held


# --- steady + edge cases (tier-1: small G, one jitted step per config) ---


def settle(oracle, st, step_fn, G, P, rounds=25, append=1, damped=True):
    """Lockstep settle.  Damped configs call the ONE canonical traced
    graph this module uses everywhere — explicit all-up link plane +
    read_propose (zeros here) — so the whole tier-1 file pays a single
    damped wave-path compile (the tier-1 budget discipline)."""
    crashed = np.zeros((G, P), bool)
    app = np.full(G, append, np.int64)
    zeros = jnp.zeros((G,), jnp.int32)
    for _ in range(rounds):
        if damped:
            st, _ = step_fn(
                st, jnp.zeros((P, G), bool), jnp.asarray(app, jnp.int32),
                link=full_link(G, P), read_propose=zeros,
            )
        else:
            st = step_fn(
                st, jnp.zeros((P, G), bool), jnp.asarray(app, jnp.int32)
            )
        oracle.round(crashed, app)
    return st, crashed


_SETTLED = {}


def settled_pair(G, P, rounds=25, damped=True, **build_kw):
    """Settle ONE master (oracle, state) per configuration, cached
    module-scoped; each caller gets the (immutable) settled device state
    plus a throwaway memo-seeded clone of the oracle
    (simref.clone_cluster — ROADMAP's standing constraint prices the
    naive re-settle/deepcopy alternative at ~16s each).  The master
    itself is never handed out, so no test can perturb another's
    starting point."""
    key = (
        G, P, rounds, damped,
        tuple(sorted(
            (k, tuple(v) if isinstance(v, list) else v)
            for k, v in build_kw.items()
        )),
    )
    hit = _SETTLED.get(key)
    if hit is None:
        oracle, cfg, st, step_fn = build_pair(G, P, **build_kw)
        st, _ = settle(
            oracle, st, step_fn, G, P, rounds=rounds, damped=damped
        )
        hit = _SETTLED[key] = (oracle, cfg, st, step_fn)
    oracle, cfg, st, step_fn = hit
    return clone_cluster(oracle), cfg, st, step_fn


def test_lease_serves_locally_steady():
    """Settled check-quorum cluster: every lease read serves at the
    leader's commit with zero message rounds; Safe reads return the same
    index through the quorum round; parity incl. the receipts' flags."""
    G, P = 2, 3
    oracle, cfg, st, step_fn = settled_pair(G, P, check_quorum=True)
    crashed = np.zeros((G, P), bool)
    app = np.ones(G, np.int64)
    for mode in (sim.READ_LEASE, sim.READ_SAFE):
        modes = np.full(G, mode, np.int32)
        st2, receipt = step_fn(
            st, jnp.zeros((P, G), bool), jnp.asarray(app, jnp.int32),
            link=full_link(G, P), read_propose=jnp.asarray(modes),
        )
        oracle.round(crashed, app, read_propose=modes)
        assert_receipts(receipt, oracle.last_receipts, f"mode {mode}")
        if mode == sim.READ_LEASE:
            assert bool(np.asarray(receipt.lease).all())
            assert (np.asarray(receipt.index) >= 0).all()
        st = st2
    assert_state_parity(oracle, st, "steady end")


def test_lease_survives_crashed_quorum_until_boundary():
    """Crash every follower: the lease keeps serving — correctly, nothing
    else can commit — until the leader's check-quorum boundary deposes
    it, then reads return -1.  Safe reads fail immediately (no ack
    quorum).  Receipt parity every round across the flip."""
    G, P = 2, 3
    oracle, cfg, st, step_fn = settled_pair(G, P, check_quorum=True)
    crashed = np.zeros((G, P), bool)
    snap = oracle.cluster.snapshot()
    for g in range(G):
        lead = int(snap["state"][g].argmax())
        for p in range(P):
            if p != lead:
                crashed[g, p] = True
    app = np.zeros(G, np.int64)
    served_rounds = 0
    stalled_rounds = 0
    for r in range(2 * cfg.election_tick + 2):
        modes = np.full(G, sim.READ_LEASE, np.int32)
        st, receipt = step_fn(
            st, jnp.asarray(crashed.T), jnp.asarray(app, jnp.int32),
            link=full_link(G, P), read_propose=jnp.asarray(modes),
        )
        oracle.round(crashed, app, read_propose=modes)
        assert_receipts(receipt, oracle.last_receipts, f"round {r}")
        idx = np.asarray(receipt.index)
        if (idx >= 0).all():
            served_rounds += 1
            assert bool(np.asarray(receipt.lease).all())
        elif (idx < 0).all():
            stalled_rounds += 1
    # The lease window served for a while, then the boundary killed it.
    assert served_rounds > 0
    assert stalled_rounds > 0


@pytest.mark.slow  # transfer=True is its own damped wave compile
def test_transfer_pending_degrades_lease():
    """A pending leader transfer rejects the lease (MsgTimeoutNow's
    forced election bypasses leases, so the hardened gate degrades to
    ReadIndex): crash the transfer target so the command stays pending,
    then read in lease mode — receipt must be degraded=True and served
    through the quorum round, matching the oracle's Safe pump."""
    G, P = 2, 3
    oracle, cfg, st, step_fn = settled_pair(
        G, P, check_quorum=True, transfer=True
    )
    crashed = np.zeros((G, P), bool)
    snap = oracle.cluster.snapshot()
    app = np.zeros(G, np.int64)
    # Pick a target and crash it, so the catch-up/TimeoutNow never lands.
    tgt = np.zeros(G, np.int32)
    for g in range(G):
        lead = int(snap["state"][g].argmax())
        t = (lead + 1) % P
        tgt[g] = t + 1
        crashed[g, t] = True
    st, receipt = step_fn(
        st, jnp.asarray(crashed.T), jnp.asarray(app, jnp.int32),
        transfer_propose=jnp.asarray(tgt),
        read_propose=jnp.asarray(np.full(G, sim.READ_LEASE, np.int32)),
    )
    oracle.round(
        crashed, app, transfer_propose=tgt,
        read_propose=np.full(G, sim.READ_LEASE, np.int32),
    )
    # Round 1: the command steps AFTER the read phase — the entry state
    # had no pending transfer, so this round still lease-serves.
    assert_receipts(receipt, oracle.last_receipts, "command round")
    assert bool(np.asarray(receipt.lease).all())
    # Round 2: the transfer is pending at round entry -> degraded, served
    # through the ack quorum (the two live peers are a majority of 3).
    modes = np.full(G, sim.READ_LEASE, np.int32)
    st, receipt = step_fn(
        st, jnp.asarray(crashed.T), jnp.asarray(app, jnp.int32),
        read_propose=jnp.asarray(modes),
    )
    oracle.round(crashed, app, read_propose=modes)
    assert_receipts(receipt, oracle.last_receipts, "pending round")
    assert bool(np.asarray(receipt.degraded).all())
    assert (np.asarray(receipt.index) >= 0).all()
    assert (np.asarray(st.transferee) > 0).any()


@pytest.mark.slow  # the (G=2, P=2) joint shape is its own damped compile
def test_joint_self_quorum_lease_serves_where_safe_hangs():
    """A joint config whose quorum is the leader alone (incoming ==
    outgoing == {2}) hangs Safe reads forever (the ack quorum is only
    evaluated on receiving a response and there is nobody to respond) —
    but the LEASE serves: LeaseBased never waits for acks.  The batched
    gate and the scalar pump must agree on both arms."""
    G, P = 2, 2
    oracle, cfg, st, step_fn = settled_pair(
        G, P, rounds=30, check_quorum=True, voters=[2], outgoing=[2]
    )
    crashed = np.zeros((G, P), bool)
    app = np.ones(G, np.int64)
    for mode, want_served in ((sim.READ_SAFE, False), (sim.READ_LEASE, True)):
        modes = np.full(G, mode, np.int32)
        st, receipt = step_fn(
            st, jnp.zeros((P, G), bool), jnp.asarray(app, jnp.int32),
            read_propose=jnp.asarray(modes),
        )
        oracle.round(crashed, app, read_propose=modes)
        assert_receipts(receipt, oracle.last_receipts, f"joint mode {mode}")
        assert (np.asarray(receipt.index) >= 0).all() == want_served


def test_undamped_lease_request_degrades():
    """check_quorum off: there is no lease (the reference rejects the
    configuration outright); every READ_LEASE request degrades to the
    ReadIndex round, bit-identically on both sides."""
    G, P = 2, 3
    oracle, cfg, st, step_fn = settled_pair(
        G, P, damped=False, check_quorum=False
    )
    crashed = np.zeros((G, P), bool)
    app = np.ones(G, np.int64)
    modes = np.full(G, sim.READ_LEASE, np.int32)
    st, receipt = step_fn(
        st, jnp.zeros((P, G), bool), jnp.asarray(app, jnp.int32),
        read_propose=jnp.asarray(modes),
    )
    oracle.round(crashed, app, read_propose=modes)
    assert_receipts(receipt, oracle.last_receipts, "undamped")
    assert bool(np.asarray(receipt.degraded).all())
    assert not bool(np.asarray(receipt.lease).any())
    assert (np.asarray(receipt.index) >= 0).all()


def test_lease_read_requires_check_quorum():
    """SimConfig(lease_read=True) without check_quorum is the reference's
    rejected configuration (Config.validate) — step() must refuse it."""
    cfg = SimConfig(n_groups=2, n_peers=3, lease_read=True)
    st = sim.init_state(cfg)
    with pytest.raises(ValueError, match="check_quorum"):
        sim.step(
            cfg, st, jnp.zeros((3, 2), bool), jnp.zeros((2,), jnp.int32)
        )


# --- the stale-read trap (the safety net's negative test) -----------------


@pytest.mark.parametrize("flags, want", [
    (dict(check_quorum=True), "765efbe2013061d4f6c76b719bf5e47ea8718473"),
    pytest.param(dict(check_quorum=True, pre_vote=True),
                 "f2cc0501801f28e31053c8bd8ea5155590817d65",
                 marks=pytest.mark.slow),  # cq+pv is a third damped wave compile
])
def test_lease_fleet_receipts_are_what_they_were_before_issue_40(flags, want):
    """ISSUE 40 gave `_read_phase` a third branch (damping on, lease reads
    off: the per-peer ReadIndex mask).  With lease reads on every receipt
    of a seeded storm is bit-equal to the tree before it (digests taken at
    0586a2e), and carries no mask: the lease holders stay the audit's."""
    G, P = 2, 3
    _oracle, _cfg, st, step_fn = build_pair(G, P, **flags)
    digest, served, held = receipt_digest(step_fn, st, G, P)
    assert served > 0 and held == 0
    assert digest == want


def _inject_trap(freeze_clock: bool):
    """Drive the stale-read-under-partition trap: partition the leader
    with its lease running, (optionally) pause its clock so the
    check-quorum boundary never fires, let the majority elect and commit,
    then force a lease serve.  Returns (safety_counts, receipt)."""
    G, P = 2, 3
    cfg = SimConfig(
        n_groups=G, n_peers=P, election_tick=10, check_quorum=True,
        lease_read=True,
    )
    st = sim.init_state(cfg)
    step_fn = _step_for(cfg)
    app = jnp.ones((G,), jnp.int32)
    none = jnp.zeros((P, G), bool)
    zeros = jnp.zeros((G,), jnp.int32)
    for _ in range(30):
        st, _ = step_fn(
            st, none, app, link=full_link(G, P), read_propose=zeros
        )
    state_h = np.asarray(st.state)
    leads = state_h.argmax(axis=0)  # [G]
    # Partition: the leader alone on one side, everyone else on the other.
    link = np.ones((P, P, G), bool)
    for g in range(G):
        for p in range(P):
            if p != leads[g]:
                link[leads[g], p, g] = False
                link[p, leads[g], g] = False
    link_j = jnp.asarray(link)
    lead_mask = jnp.asarray(
        np.arange(P)[:, None] == leads[None, :]
    )  # [P, G]
    safety = jnp.zeros((kernels.N_SAFETY,), jnp.int32)
    receipt = None
    for r in range(3 * cfg.election_tick):
        if freeze_clock:
            # The clock pause: the deposed-but-unaware leader's election
            # clock never reaches its check-quorum boundary — raft-rs's
            # own LeaseBased caveat (unbounded clock drift) injected
            # surgically; without it the boundary deposes the old leader
            # before the other side's lease-expiry election can finish.
            st = st._replace(
                election_elapsed=jnp.where(
                    lead_mask & (st.state == kernels.ROLE_LEADER),
                    0,
                    st.election_elapsed,
                )
            )
        fire = r == 3 * cfg.election_tick - 1
        modes = jnp.full((G,), sim.READ_LEASE if fire else 0, jnp.int32)
        holder, _, _ = kernels.lease_read(
            st.state, st.term, st.leader_id, st.election_elapsed,
            st.commit, st.term_start_index, none, cfg.election_tick,
            True, st.transferee,
            st.recent_active, st.voter_mask, st.outgoing_mask,
        )
        prev_commit = st.commit
        st2, receipt = step_fn(
            st, none, app, link=link_j, read_propose=modes
        )
        safety = safety + kernels.check_safety(
            st2.state, st2.term, st2.commit, st2.last_index, st2.agree,
            prev_commit,
            lease_holder=holder,
            lease_fire=modes > 0,
        )
        st = st2
    return np.asarray(safety), receipt


def test_stale_read_trap_caught_by_safety_net():
    """The injected trap MUST fire both linearizability slots: the paused
    old leader holds a 'live' lease while the new majority committed past
    it (SV_STALE_READ on the forced serve round) and two leaders hold
    leases at once (SV_DUAL_LEASE)."""
    safety, receipt = _inject_trap(freeze_clock=True)
    assert safety[kernels.SV_STALE_READ] > 0, safety
    assert safety[kernels.SV_DUAL_LEASE] > 0, safety
    # Every legacy slot stays clean — the trap is a READ problem, not a
    # replication one (the partitioned old regime never commits).
    assert safety[kernels.SV_DUAL_LEADER] == 0


def test_no_trap_without_clock_drift():
    """Same partition schedule WITHOUT the clock pause: the check-quorum
    boundary deposes the cut-off leader before the majority's election
    finishes, so the linearizability slots stay zero — the lease is safe
    under synchronized clocks, which is exactly raft-rs's LeaseBased
    contract."""
    safety, receipt = _inject_trap(freeze_clock=False)
    assert safety[kernels.SV_STALE_READ] == 0, safety
    assert safety[kernels.SV_DUAL_LEASE] == 0, safety


# --- storms: per-round receipt parity under crash churn -------------------


def test_read_storm_undamped():
    run_read_storm(11, 2, 3, 40)


def test_read_storm_cq():
    run_read_storm(23, 2, 3, 40, check_quorum=True)


@pytest.mark.slow  # cq+pv is a third damped wave compile
def test_read_storm_cq_pv():
    run_read_storm(37, 2, 3, 40, check_quorum=True, pre_vote=True)


@pytest.mark.slow  # ~6 configs x 60 rounds of per-round deepcopy probes
def test_read_storm_fuzz_matrix():
    run_read_storm(41, 3, 5, 60)
    run_read_storm(53, 3, 5, 60, check_quorum=True)
    run_read_storm(61, 3, 5, 60, check_quorum=True, pre_vote=True)
    run_read_storm(71, 3, 4, 60, check_quorum=True, transfer=True)
    run_read_storm(
        83, 3, 5, 60, check_quorum=True,
        voters=[1, 2, 3], outgoing=[3, 4, 5],
    )
    run_read_storm(
        97, 2, 6, 60, check_quorum=True, pre_vote=True,
        voters=[1, 2, 3, 4], learners=[5, 6],
    )


@pytest.mark.slow  # joint/learner shapes on the undamped path
def test_read_storm_fuzz_configs_undamped():
    run_read_storm(103, 3, 5, 60, voters=[1, 2, 3], outgoing=[3, 4, 5])
    run_read_storm(211, 3, 5, 60, voters=[1, 2, 3, 4], learners=[5])
