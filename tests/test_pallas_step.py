"""The fused Pallas steady round must be bit-identical to the general XLA
step whenever the steady predicate holds.  Which of the two runs is the split
runners' business (runner.make_runner(..., split=True)): full schedules with
elections, crashes and lossy links through both arms are held to sequential
sim.steps in tests/test_reconfig_split.py and tests/test_workload.py.

Runs in interpret mode on the pinned CPU (raft_tpu.platform decides);
the Mosaic compile path is exercised on the chip by chip_smoke.py."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from raft_tpu.multiraft import ClusterSim, SimConfig
from raft_tpu.multiraft import pallas_step, sim


def settle(cfg, rounds=30):
    s = ClusterSim(cfg)
    append = jnp.ones((cfg.n_groups,), jnp.int32)
    s.run(rounds, None, append)
    return s.state


def test_steady_round_matches_xla():
    cfg = SimConfig(n_groups=16, n_peers=5)
    st = settle(cfg)
    crashed = jnp.zeros((cfg.n_peers, cfg.n_groups), bool)
    append = jnp.ones((cfg.n_groups,), jnp.int32)

    assert bool(pallas_step.steady_predicate(cfg, st, crashed))

    fast = pallas_step.steady_round(cfg)
    for r in range(3):
        want = sim.step(cfg, st, crashed, append)
        got = fast(st, crashed, append)
        for f in st._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(want, f)),
                np.asarray(getattr(got, f)),
                err_msg=f"round {r} field {f}",
            )
        st = want


def test_steady_round_with_crashed_follower():
    cfg = SimConfig(n_groups=8, n_peers=5)
    st = settle(cfg)
    crashed = np.zeros((cfg.n_peers, cfg.n_groups), bool)
    # crash one non-leader peer per group
    leaders = np.asarray(st.state).argmax(axis=0)
    for g in range(cfg.n_groups):
        crashed[(leaders[g] + 1) % cfg.n_peers, g] = True
    crashed = jnp.asarray(crashed)
    append = jnp.ones((cfg.n_groups,), jnp.int32)

    assert bool(pallas_step.steady_predicate(cfg, st, crashed))
    fast = pallas_step.steady_round(cfg)
    want = sim.step(cfg, st, crashed, append)
    got = fast(st, crashed, append)
    for f in st._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(want, f)), np.asarray(getattr(got, f)), err_msg=f
        )


def test_predicate_rejects_non_steady():
    cfg = SimConfig(n_groups=8, n_peers=3)
    fresh = sim.init_state(cfg)  # nobody elected yet
    crashed = jnp.zeros((3, 8), bool)
    assert not bool(pallas_step.steady_predicate(cfg, fresh, crashed))

    st = settle(cfg)
    # crash every leader: not steady
    leaders = np.asarray(st.state) == 2
    assert not bool(
        pallas_step.steady_predicate(cfg, st, jnp.asarray(leaders))
    )


def test_multi_round_kernel_matches_k_steps():
    """k fused rounds == k sequential general steps from a steady state."""
    cfg = SimConfig(n_groups=8, n_peers=3)
    k = 4
    st = settle(cfg)
    crashed = jnp.zeros((cfg.n_peers, cfg.n_groups), bool)
    append = jnp.ones((cfg.n_groups,), jnp.int32)
    assert bool(pallas_step.steady_predicate(cfg, st, crashed, horizon=k))

    fused = pallas_step.steady_round(cfg, rounds=k)
    want = st
    for _ in range(k):
        want = sim.step(cfg, want, crashed, append)
    got = fused(st, crashed, append)
    for f in st._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(want, f)), np.asarray(getattr(got, f)), err_msg=f
        )


@pytest.mark.slow  # ~8s of interpret-mode compile
def test_steady_round_health_matches_general_steps():
    """The fused health fold (in-kernel ticks_since_commit + closed-form
    window math) must be bit-identical to threading sim.step's health
    extra through the same k rounds — including a window boundary inside
    the horizon and junk pre-state in every plane."""
    cfg = SimConfig(n_groups=8, n_peers=3, collect_health=True, health_window=8)
    k = 2
    st = settle(cfg)
    crashed = jnp.zeros((cfg.n_peers, cfg.n_groups), bool)
    append = jnp.ones((cfg.n_groups,), jnp.int32)
    assert bool(pallas_step.steady_predicate(cfg, st, crashed, horizon=k))

    h0 = sim.init_health(cfg)
    # Junk pre-state: term bumps + splits survive or reset per the rules.
    h0 = h0._replace(
        planes=h0.planes.at[2].set(3).at[3].set(5),
        window_pos=jnp.int32(7),  # boundary inside the 2-round horizon
    )
    want_st, want_h = st, h0
    for _ in range(k):
        want_st, want_h = sim.step(cfg, want_st, crashed, append, health=want_h)

    fused = pallas_step.steady_round(cfg, rounds=k, with_health=True)
    got_st, got_h = fused(st, crashed, append, h0)
    for f in st._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(want_st, f)),
            np.asarray(getattr(got_st, f)),
            err_msg=f,
        )
    np.testing.assert_array_equal(
        np.asarray(want_h.planes), np.asarray(got_h.planes)
    )
    assert int(want_h.window_pos) == int(got_h.window_pos)


# --- chaos-on (link + loss) fused coverage ----------------------------------


def _chaos_cfg(G=8, P=3, **kw):
    # election_tick must clear the fused horizon: the chaos path uses the
    # conservative free-running timer bound (loss can drop any heartbeat).
    return SimConfig(n_groups=G, n_peers=P, election_tick=60, **kw)


def _loss_plane(G, P, seed=0):
    del seed  # layouts are fixed; the arg keeps call sites self-describing
    loss = np.zeros((P, P, G), np.int32)
    # heavy loss on a few directed links, zero elsewhere
    loss[0, 1, :] = 3000
    loss[1, 0, ::2] = 5000
    loss[(P - 1) % P, P // 2, 1::3] = 7000
    return jnp.asarray(loss)


def _make_general_linked(cfg, crashed, append, has_c=False, has_h=False):
    """Jitted one-round general stepper over link & ~loss_draw — the
    contract the fused chaos kernel must match bit-for-bit.  Built ONCE
    per test (one link-path compile) and driven per round."""
    from raft_tpu.multiraft import kernels

    @jax.jit
    def stepper(st, link, loss, r, *extras):
        kw = {}
        i = 0
        if has_c:
            kw["counters"] = extras[i]
            i += 1
        if has_h:
            kw["health"] = extras[i]
        eff = link & ~kernels.link_loss_draw(r, loss)
        res = sim.step(cfg, st, crashed, append, link=eff, **kw)
        if not (has_c or has_h):
            res = (res,)
        return res

    def run_k(st, link, loss, rb, k, counters=None, health=None):
        for r in range(k):
            extras = ()
            if has_c:
                extras = extras + (counters,)
            if has_h:
                extras = extras + (health,)
            res = stepper(st, link, loss, jnp.int32(rb + r), *extras)
            st = res[0]
            i = 1
            if has_c:
                counters = res[i]
                i += 1
            if has_h:
                health = res[i]
        return st, counters, health

    return run_k


def test_steady_chaos_kernel_matches_linked_steps():
    """The loss-gated fused kernel == k general sim.step(link=) rounds,
    across consecutive blocks with the PRNG round_base advancing (lagging
    followers heal through the catch-up wave mid-stream)."""
    cfg = _chaos_cfg()
    G, P = cfg.n_groups, cfg.n_peers
    st = settle(cfg, rounds=150)
    crashed = jnp.zeros((P, G), bool)
    append = jnp.ones((G,), jnp.int32)
    link = jnp.ones((P, P, G), bool)
    loss = _loss_plane(G, P)
    k = 4
    assert bool(pallas_step.steady_predicate(cfg, st, crashed, k, link))

    fused = jax.jit(pallas_step.steady_round(cfg, rounds=k, with_chaos=True))
    general = _make_general_linked(cfg, crashed, append)
    a = b = st
    rb = 150
    for blk in range(5):
        a, _, _ = general(a, link, loss, rb, k)
        b = fused(b, crashed, append, loss, jnp.int32(rb))
        for f in st._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(a, f)),
                np.asarray(getattr(b, f)),
                err_msg=f"block {blk} field {f}",
            )
        rb += k


def test_steady_chaos_kernel_with_crashed_follower():
    cfg = _chaos_cfg()
    G, P = cfg.n_groups, cfg.n_peers
    st = settle(cfg, rounds=150)
    crashed = np.zeros((P, G), bool)
    leaders = np.asarray(st.state).argmax(axis=0)
    for g in range(G):
        crashed[(leaders[g] + 1) % P, g] = True
    crashed = jnp.asarray(crashed)
    append = jnp.ones((G,), jnp.int32)
    link = jnp.ones((P, P, G), bool)
    loss = _loss_plane(G, P, seed=1)
    k = 3
    assert bool(pallas_step.steady_predicate(cfg, st, crashed, k, link))
    fused = jax.jit(pallas_step.steady_round(cfg, rounds=k, with_chaos=True))
    general = _make_general_linked(cfg, crashed, append)
    want, _, _ = general(st, link, loss, 40, k)
    got = fused(st, crashed, append, loss, jnp.int32(40))
    for f in st._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(want, f)), np.asarray(getattr(got, f)),
            err_msg=f,
        )


def test_steady_counters_closed_form():
    """with_counters: the closed-form CTR_* fold == threading the counter
    plane through k general steps — plain AND chaos variants."""
    from raft_tpu.multiraft import kernels

    cfg = SimConfig(n_groups=8, n_peers=3)
    G, P = cfg.n_groups, cfg.n_peers
    st = settle(cfg)
    crashed = jnp.zeros((P, G), bool)
    append = jnp.ones((G,), jnp.int32)
    k = 4
    assert bool(pallas_step.steady_predicate(cfg, st, crashed, horizon=k))
    fused = jax.jit(
        pallas_step.steady_round(cfg, rounds=k, with_counters=True)
    )
    step_c = jax.jit(
        lambda s, c: sim.step(cfg, s, crashed, append, counters=c)
    )
    want_st, want_c = st, kernels.zero_counters()
    for _ in range(k):
        want_st, want_c = step_c(want_st, want_c)
    got_st, got_c = fused(st, crashed, append, kernels.zero_counters())
    np.testing.assert_array_equal(np.asarray(want_c), np.asarray(got_c))
    for f in st._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(want_st, f)), np.asarray(getattr(got_st, f)),
            err_msg=f,
        )

    # chaos variant: counters + loss draws in one fused call
    ccfg = _chaos_cfg()
    st2 = settle(ccfg, rounds=150)
    link = jnp.ones((P, P, G), bool)
    loss = _loss_plane(G, P, seed=2)
    fused_c = jax.jit(
        pallas_step.steady_round(
            ccfg, rounds=k, with_chaos=True, with_counters=True
        )
    )
    general = _make_general_linked(ccfg, crashed, append, has_c=True)
    want_st, want_c, _ = general(
        st2, link, loss, 200, k, counters=kernels.zero_counters()
    )
    got_st, got_c = fused_c(
        st2, crashed, append, loss, jnp.int32(200), kernels.zero_counters()
    )
    np.testing.assert_array_equal(np.asarray(want_c), np.asarray(got_c))
    for f in st2._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(want_st, f)), np.asarray(getattr(got_st, f)),
            err_msg=f,
        )


def test_plain_jaxpr_unchanged_by_new_flags():
    """The chaos/counters machinery must not perturb the flag-off graph:
    steady_round traces identically with the new flags defaulted and
    explicitly off (the packed/donated-path extension of the PR 5 chaos-off
    jaxpr pin)."""
    cfg = SimConfig(n_groups=4, n_peers=3)
    st = sim.init_state(cfg)
    crashed = jnp.zeros((3, 4), bool)
    append = jnp.zeros((4,), jnp.int32)

    base = jax.make_jaxpr(pallas_step.steady_round(cfg, rounds=2))(
        st, crashed, append
    )
    flagged = jax.make_jaxpr(
        pallas_step.steady_round(
            cfg, rounds=2, with_chaos=False, with_counters=False
        )
    )(st, crashed, append)
    assert str(base) == str(flagged)


# --- fused election damping (ISSUE 8) ---------------------------------------
#
# The damped kernel family (_steady_damped_kernel) must be bit-identical —
# per-round state AND health planes AND the recent_active plane — to k
# general damped wave rounds (sim._damped_linked_step) per configuration:
# plain / health / counters / chaos, each under cq and cq+pv — the bodies
# every benchmark cell runs, so the whole matrix is tier-1 (which takes
# 247 s of its 1470 s under xdist -n 6, /root/TESTS_LAST_RUN.json at PR 32).

DK = 4  # fused horizon for the damped cases


def _snapshot(st):
    """Host copy of a SimState (donation-safe restore point)."""
    return tuple(
        None if v is None else np.asarray(v) for v in st
    )


def _restore(snap):
    return sim.SimState(
        *(None if v is None else jnp.asarray(v) for v in snap)
    )


@pytest.fixture(scope="module")
def cq_settled():
    """One check-quorum ClusterSim + settled-state snapshot: every cq case
    (tier-1 and slow) shares this sim's damped-wave compile."""
    cfg = SimConfig(n_groups=8, n_peers=3, check_quorum=True)
    s = ClusterSim(cfg)
    append = jnp.ones((cfg.n_groups,), jnp.int32)
    s.run(30, None, append)
    return s, _snapshot(s.state)


@pytest.fixture(scope="module")
def cq_pv_settled():
    """The fully damped configuration (cq + pre-vote) with health planes."""
    cfg = SimConfig(
        n_groups=8, n_peers=3, check_quorum=True, pre_vote=True,
        collect_health=True, health_window=8,
    )
    s = ClusterSim(cfg)
    append = jnp.ones((cfg.n_groups,), jnp.int32)
    s.run(30, None, append)
    return s, _snapshot(s.state)


def _assert_state_equal(want, got, note):
    for f in want._fields:
        va, vb = getattr(want, f), getattr(got, f)
        np.testing.assert_array_equal(
            np.asarray(va), np.asarray(vb), err_msg=f"{note} field {f}"
        )


def _general_blocks(s, st0, crashed, append, blocks, k):
    """Drive `blocks` k-round blocks through the module sim's own jitted
    damped step (no extra compile); returns the per-block states."""
    s.state = st0
    out = []
    for _ in range(blocks):
        for _ in range(k):
            s.run_round(crashed, append)
        out.append(_snapshot(s.state))
    return [_restore(x) for x in out]


def test_damped_fused_parity_cq_plain(cq_settled):
    """plain × cq: 5 fused blocks from a settled state — the horizon
    crosses the leader's election-timeout boundary (election_tick=10,
    20 rounds), so the in-kernel recent_active read-and-clear cycle is
    exercised, not just ack accumulation."""
    s, snap = cq_settled
    cfg = s.cfg
    st = _restore(snap)
    crashed = jnp.zeros((cfg.n_peers, cfg.n_groups), bool)
    append = jnp.ones((cfg.n_groups,), jnp.int32)
    fused = jax.jit(pallas_step.steady_round(cfg, rounds=DK))
    want = _general_blocks(s, _restore(snap), crashed, append, 5, DK)
    got = st
    for blk in range(5):
        assert bool(
            pallas_step.steady_predicate(cfg, got, crashed, horizon=DK)
        ), f"block {blk}"
        got = fused(got, crashed, append)
        _assert_state_equal(want[blk], got, f"cq-plain block {blk}")


def test_damped_fused_parity_cq_pv_health(cq_pv_settled):
    """health × cq+pv with a crashed follower per group: the fused health
    fold (in-kernel ticks_since_commit + closed-form window math, with a
    window boundary inside the horizon) and the recent_active plane must
    both match the general damped rounds exactly."""
    s, snap = cq_pv_settled
    cfg = s.cfg
    st = _restore(snap)
    crashed_np = np.zeros((cfg.n_peers, cfg.n_groups), bool)
    leaders = np.asarray(st.state).argmax(axis=0)
    for g in range(cfg.n_groups):
        crashed_np[(leaders[g] + 1) % cfg.n_peers, g] = True
    crashed = jnp.asarray(crashed_np)
    append = jnp.ones((cfg.n_groups,), jnp.int32)
    assert bool(
        pallas_step.steady_predicate(cfg, st, crashed, horizon=DK)
    )
    def make_h0():  # fresh arrays: the module sim's step DONATES health
        return sim.init_health(cfg)._replace(
            planes=sim.init_health(cfg).planes.at[2].set(3).at[3].set(5),
            window_pos=jnp.int32(7),  # boundary inside the horizon
        )

    # General side reuses the module sim's health-threaded compile.
    s.state = _restore(snap)
    s._health = make_h0()
    for _ in range(DK):
        s.run_round(crashed, append)
    want_st, want_h = s.state, s._health
    fused = jax.jit(
        pallas_step.steady_round(cfg, rounds=DK, with_health=True)
    )
    got_st, got_h = fused(st, crashed, append, make_h0())
    _assert_state_equal(want_st, got_st, "cq+pv-health")
    np.testing.assert_array_equal(
        np.asarray(want_h.planes), np.asarray(got_h.planes)
    )
    assert int(want_h.window_pos) == int(got_h.window_pos)


def test_damped_steady_mask_rejection_conditions(cq_settled):
    """The damping-specific rejection arms (docs/PERF.md): a boot state
    (no leaders), a leader whose recent_active row lacks an active quorum
    (fresh become_leader, no acks yet), a crashed stale leader near its
    cq boundary, and — on the lossy branch — ANY role-leader near its
    boundary."""
    s, snap = cq_settled
    cfg = s.cfg
    st = _restore(snap)
    G, P = cfg.n_groups, cfg.n_peers
    crashed = jnp.zeros((P, G), bool)
    # boot: nobody elected
    assert not np.asarray(
        pallas_step.steady_mask(cfg, sim.init_state(cfg), crashed)
    ).any()
    # a leader with a cleared recent_active row (as become_leader leaves
    # it) must be rejected until acks re-saturate it
    bare = st._replace(
        recent_active=jnp.zeros((P, P, G), bool)
    )
    assert not np.asarray(
        pallas_step.steady_mask(cfg, bare, crashed)
    ).any()
    # crashed stale leader whose free-running timer reaches the boundary
    # inside the horizon: group 0 rejected, others still steady
    leaders = np.asarray(st.state).argmax(axis=0)
    stale_np = np.zeros((P, G), bool)
    stale_np[(leaders[0] + 1) % P, 0] = True
    st_np = np.asarray(st.state).copy()
    ee_np = np.asarray(st.election_elapsed).copy()
    st_np[(leaders[0] + 1) % P, 0] = 2  # ROLE_LEADER
    ee_np[(leaders[0] + 1) % P, 0] = cfg.election_tick - 1
    staled = st._replace(
        state=jnp.asarray(st_np), election_elapsed=jnp.asarray(ee_np)
    )
    mask = np.asarray(
        pallas_step.steady_mask(
            cfg, staled, jnp.asarray(stale_np), horizon=DK
        )
    )
    assert not mask[0] and mask[1:].all()
    # lossy branch: the ACTING leader near its boundary rejects too (the
    # lossless branch accepts it via the qa proof).  Every leader's timer
    # is first moved clear of the boundary, then group 0's right onto it.
    link = jnp.ones((P, P, G), bool)
    ee2 = np.asarray(st.election_elapsed).copy()
    ee2[leaders, np.arange(G)] = 2
    ee2[leaders[0], 0] = cfg.election_tick - 1
    near = st._replace(election_elapsed=jnp.asarray(ee2))
    m_lossy = np.asarray(
        pallas_step.steady_mask(cfg, near, crashed, horizon=DK, link=link)
    )
    m_lossless = np.asarray(
        pallas_step.steady_mask(cfg, near, crashed, horizon=DK)
    )
    assert not m_lossy[0] and m_lossy[1:].all()
    assert m_lossless[0]


def test_damped_build_leaves_undamped_graphs_unchanged():
    """The damped kernel family must not perturb the undamped traces: a
    config with the damping flags explicitly False builds a byte-identical
    steady_round jaxpr (the ISSUE 8 extension of the flags-off pin)."""
    cfg = SimConfig(n_groups=4, n_peers=3)
    cfg_explicit = SimConfig(
        n_groups=4, n_peers=3, check_quorum=False, pre_vote=False
    )
    st = sim.init_state(cfg)
    crashed = jnp.zeros((3, 4), bool)
    append = jnp.zeros((4,), jnp.int32)
    base, explicit = (
        jax.make_jaxpr(pallas_step.steady_round(c, rounds=2))(st, crashed, append)
        for c in (cfg, cfg_explicit)
    )
    assert str(base) == str(explicit)


def test_damped_fused_parity_matrix_plain_health(cq_settled, cq_pv_settled):
    """health × cq and plain × cq+pv — the other half of the
    plain/health matrix, off the shared settles."""
    # health × cq (health extra threads through a cfg without
    # collect_health — with_health is a build flag, like sim.step's kw)
    s, snap = cq_settled
    cfg = s.cfg
    crashed = jnp.zeros((cfg.n_peers, cfg.n_groups), bool)
    append = jnp.ones((cfg.n_groups,), jnp.int32)
    h0 = sim.init_health(cfg)._replace(window_pos=jnp.int32(3))
    step_h = jax.jit(
        lambda s_, h: sim.step(cfg, s_, crashed, append, health=h)
    )
    want_st, want_h = _restore(snap), h0
    for _ in range(DK):
        want_st, want_h = step_h(want_st, want_h)
    fused = jax.jit(
        pallas_step.steady_round(cfg, rounds=DK, with_health=True)
    )
    got_st, got_h = fused(_restore(snap), crashed, append, h0)
    _assert_state_equal(want_st, got_st, "health-cq")
    np.testing.assert_array_equal(
        np.asarray(want_h.planes), np.asarray(got_h.planes)
    )
    # plain × cq+pv off the cq+pv settle
    s2, snap2 = cq_pv_settled
    cfg2 = s2.cfg
    fused2 = jax.jit(pallas_step.steady_round(cfg2, rounds=DK))
    step2 = jax.jit(lambda s_: sim.step(cfg2, s_, crashed, append))
    want = _restore(snap2)
    for _ in range(DK):
        want = step2(want)
    got = fused2(_restore(snap2), crashed, append)
    _assert_state_equal(want, got, "plain-cq+pv")


def test_damped_fused_parity_pv_only():
    """plain × pre-vote-only: SimConfig(pre_vote=True) alone routes to
    _steady_damped_kernel(with_cq=False) in production (steady_mask's
    damped arm skips the cq-specific conditions), so the never-cleared
    recent_active accumulation arm needs its own parity pin — the cq
    cases above always cross a read-and-clear boundary."""
    cfg = SimConfig(n_groups=8, n_peers=3, pre_vote=True)
    s = ClusterSim(cfg)
    append = jnp.ones((cfg.n_groups,), jnp.int32)
    s.run(30, None, append)
    snap = _snapshot(s.state)
    crashed = jnp.zeros((cfg.n_peers, cfg.n_groups), bool)
    fused = jax.jit(pallas_step.steady_round(cfg, rounds=DK))
    want = _general_blocks(s, _restore(snap), crashed, append, 5, DK)
    got = _restore(snap)
    for blk in range(5):
        assert bool(
            pallas_step.steady_predicate(cfg, got, crashed, horizon=DK)
        ), f"block {blk}"
        got = fused(got, crashed, append)
        _assert_state_equal(want[blk], got, f"pv-only block {blk}")


def test_damped_fused_counters_closed_form(cq_settled, cq_pv_settled):
    """counters × cq and counters × cq+pv: the closed-form CTR_* fold
    (campaigns/wins provably 0, heartbeat fires arithmetic — incl. any
    crashed role-leader's free-running timer, commit deltas telescoping)
    == threading the plane through k damped wave rounds."""
    from raft_tpu.multiraft import kernels

    for fixture in (cq_settled, cq_pv_settled):
        s, snap = fixture
        cfg = s.cfg
        crashed = jnp.zeros((cfg.n_peers, cfg.n_groups), bool)
        append = jnp.ones((cfg.n_groups,), jnp.int32)
        step_c = jax.jit(
            lambda s_, c, cfg=cfg, crashed=crashed: sim.step(
                cfg, s_, crashed, append, counters=c
            )
        )
        want_st, want_c = _restore(snap), kernels.zero_counters()
        for _ in range(DK):
            want_st, want_c = step_c(want_st, want_c)
        fused = jax.jit(
            pallas_step.steady_round(cfg, rounds=DK, with_counters=True)
        )
        got_st, got_c = fused(
            _restore(snap), crashed, append, kernels.zero_counters()
        )
        note = f"counters cq={cfg.check_quorum} pv={cfg.pre_vote}"
        np.testing.assert_array_equal(
            np.asarray(want_c), np.asarray(got_c), err_msg=note
        )
        _assert_state_equal(want_st, got_st, note)


# --- ISSUE 11: the per-group lossy check-quorum bound ------------------------


def test_cq_boundary_safe_per_group_lossy_bound():
    """kernels.cq_boundary_safe(lossy=): the boundary condition is PER
    GROUP — a lossy group with an in-horizon boundary rejects while a
    loss-free group with the same timer phase keeps the saturation proof,
    and lossy=None reproduces the historical all-lossless behavior."""
    from raft_tpu.multiraft import kernels

    P, G = 3, 4
    state = jnp.zeros((P, G), jnp.int32).at[0].set(kernels.ROLE_LEADER)
    voter = jnp.ones((P, G), bool)
    outgoing = jnp.zeros((P, G), bool)
    crashed = jnp.zeros((P, G), bool)
    # Leader row fully active (acks from everyone) in every group.
    ra = jnp.zeros((P, P, G), bool).at[0].set(True)
    # Leaders of groups 1 and 3 hit their boundary inside horizon=4.
    ee = jnp.zeros((P, G), jnp.int32).at[0, 1].set(8).at[0, 3].set(8)
    args = (ra, voter, outgoing, state, crashed, ee, 4, 10)
    np.testing.assert_array_equal(
        np.asarray(kernels.cq_boundary_safe(*args)),
        [True, True, True, True],  # lossless proof covers boundaries
    )
    lossy = jnp.asarray([False, True, True, False])
    np.testing.assert_array_equal(
        np.asarray(kernels.cq_boundary_safe(*args, lossy=lossy)),
        # group 1: lossy + boundary in horizon -> rejected; group 2:
        # lossy but no boundary -> free-running bound passes; group 3:
        # boundary in horizon but loss-free -> saturation proof holds.
        [True, False, True, True],
    )
    # A crashed stale leader reaching its boundary rejects either way.
    crashed2 = crashed.at[0, 0].set(True)
    got = kernels.cq_boundary_safe(
        ra, voter, outgoing, state, crashed2,
        ee.at[0, 0].set(9), 4, 10,
    )
    assert not bool(got[0])


def test_steady_mask_loss_rate_per_group(cq_settled):
    """steady_mask(loss_rate=): only groups with a nonzero rate keep the
    conservative no-boundary bound; zero-rate groups fuse through their
    check-quorum boundary exactly like the lossless branch."""
    from raft_tpu.multiraft import kernels

    s, snap = cq_settled
    cfg = s.cfg
    st = _restore(snap)
    G, P = cfg.n_groups, cfg.n_peers
    crashed = jnp.zeros((P, G), bool)
    link = jnp.ones((P, P, G), bool)
    k = 4
    # Force every leader's boundary inside the horizon.
    lead = st.state == 2
    st = st._replace(
        election_elapsed=jnp.where(
            lead, jnp.int32(cfg.election_tick - 2), st.election_elapsed
        )
    )
    lossless = pallas_step.steady_mask(cfg, st, crashed, k)
    rate = jnp.where(jnp.arange(G) % 2 == 0, 25, 0)
    rate = jnp.broadcast_to(rate[None, None, :], (P, P, G)).astype(jnp.int32)
    got = np.asarray(
        pallas_step.steady_mask(
            cfg, st, crashed, k, link=link, loss_rate=rate
        )
    )
    # Lossy groups (even): boundary in horizon -> rejected.  Loss-free
    # groups (odd): same steadiness the lossless branch proves.
    assert not got[::2].any()
    np.testing.assert_array_equal(got[1::2], np.asarray(lossless)[1::2])
    # Without loss_rate the historical all-groups conservative form
    # rejects everything (boundary everywhere).
    old = np.asarray(
        pallas_step.steady_mask(cfg, st, crashed, k, link=link)
    )
    assert not old.any()
