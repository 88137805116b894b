"""Chaos-engine parity: the link-fault correctness claims.

Four claims are pinned here (ISSUE 5 acceptance criteria):

  1. chaos-off is free: `sim.step(..., link=None)` traces to the SAME
     jaxpr as never passing `link` — the fast path's graph is untouched;
  2. whole-peer crash is the special case
     `link[p, :, g] = link[:, p, g] = False`: the link path driven with a
     crash-shaped plane matches the scalar oracle on crash-only schedules;
  3. per-round state AND health-plane parity of the link-gated device
     round (sim._linked_step) against simref.ChaosOracle — real Raft
     state machines behind the harness Network's per-edge drops — across
     compiled multi-phase schedules with loss and a seeded link fuzz;
  4. the device loss PRNG (kernels.link_loss_draw) is bit-identical to
     the numpy twin (chaos.host_loss_draw), so every schedule replays.

Tier-1 cost: the link-path jit is ~9s on CPU, so the tier-1 cases share
ONE module-scoped ClusterSim (G=8 short schedules); everything at G>=32
or >=100 rounds is marked slow (ROADMAP.md's standing constraint; time is
not scarce: tier-1 takes 247 s of its 1470 s limit under xdist -n 6 at PR 32).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.multiraft import (
    ChaosOracle,
    ClusterSim,
    ScalarCluster,
    SimConfig,
)
from raft_tpu.multiraft import chaos, kernels
from raft_tpu.multiraft import sim as sim_mod

FIELDS = ("term", "state", "commit", "last_index", "last_term")

G, P, WINDOW = 8, 3, 8


@pytest.fixture(scope="module")
def shared_sim():
    """One ClusterSim — and ONE ~9s link-path compile — for every tier-1
    case in this file; cases reset its state/health planes."""
    return ClusterSim(
        SimConfig(
            n_groups=G, n_peers=P, collect_health=True, health_window=WINDOW
        )
    )


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


# --- claim 1: the chaos-off graph is bit-identical --------------------------


def test_chaos_off_graph_identical():
    cfg = SimConfig(n_groups=4, n_peers=3)
    st = sim_mod.init_state(cfg)
    crashed = jnp.zeros((3, 4), bool)
    app = jnp.zeros((4,), jnp.int32)
    base = jax.make_jaxpr(functools.partial(sim_mod.step, cfg))(
        st, crashed, app
    )
    with_none = jax.make_jaxpr(
        lambda s, c, a: sim_mod.step(cfg, s, c, a, link=None)
    )(st, crashed, app)
    assert str(base) == str(with_none)

    # The donated multi-round runner (ClusterSim.run_compiled) scans the
    # same step: with link/counters/health all None the per-round graph
    # inside the scan is bit-identical to scanning the bare step — the
    # packed/donated paths cannot leak into the chaos-off graph.
    def scan_plain(s, c, a):
        def body(x, _):
            return sim_mod.step(cfg, x, c, a), ()

        return jax.lax.scan(body, s, None, length=3)[0]

    def scan_none(s, c, a):
        def body(x, _):
            return (
                sim_mod.step(
                    cfg, x, c, a, group_ids=None, counters=None,
                    health=None, link=None,
                ),
                (),
            )

        return jax.lax.scan(body, s, None, length=3)[0]

    assert str(jax.make_jaxpr(scan_plain)(st, crashed, app)) == str(
        jax.make_jaxpr(scan_none)(st, crashed, app)
    )


def test_run_compiled_matches_stepping(shared_sim):
    """ClusterSim.run_compiled (ONE donated lax.scan, double-buffered
    carry) == the run_round python loop on the same constant masks —
    state AND health planes, with a one-way link cut in the plane."""
    sim = reset(shared_sim)
    link_np = np.ones((P, P, G), bool)
    link_np[0, 1, ::2] = False  # one-way cut on even groups
    link = jnp.asarray(link_np)
    app = jnp.ones((G,), jnp.int32)
    for _ in range(12):
        sim.run_round(append_n=app, link=link)
    want = {f: np.asarray(getattr(sim.state, f)) for f in sim.state._fields}
    want_planes = np.asarray(sim._health.planes)

    sim = reset(shared_sim)
    sim.run_compiled(12, append_n=app, link=link)
    for f, w in want.items():
        assert np.array_equal(np.asarray(getattr(sim.state, f)), w), f
    assert np.array_equal(np.asarray(sim._health.planes), want_planes)


# --- claim 4: the loss PRNG twin is bit-identical ---------------------------


def test_loss_draw_matches_host_twin():
    rng = np.random.RandomState(3)
    loss = rng.randint(
        0, kernels.LOSS_SCALE + 1, size=(5, 5, 37)
    ).astype(np.int32)
    for r in (0, 1, 7, 1 << 20):
        dev = np.asarray(kernels.link_loss_draw(jnp.int32(r), jnp.asarray(loss)))
        host = chaos.host_loss_draw(r, loss)
        assert np.array_equal(dev, host), f"round {r}"
    # rate 0 never drops, LOSS_SCALE always drops
    zero = np.zeros((2, 2, 8), np.int32)
    assert not np.asarray(kernels.link_loss_draw(jnp.int32(5), jnp.asarray(zero))).any()
    full = np.full((2, 2, 8), kernels.LOSS_SCALE, np.int32)
    assert np.asarray(kernels.link_loss_draw(jnp.int32(5), jnp.asarray(full))).all()


# --- check_safety unit behavior ---------------------------------------------


def test_check_safety_flags_each_invariant():
    g = 4

    def planes(v):
        return jnp.full((2, g), v, jnp.int32)

    clean = kernels.check_safety(
        state=jnp.asarray([[2] * g, [0] * g], jnp.int32),
        term=planes(3),
        commit=planes(5),
        last_index=planes(7),
        agree=jnp.full((2, 2, g), 6, jnp.int32),
        prev_commit=planes(5),
    )
    assert np.asarray(clean).tolist() == [0] * kernels.N_SAFETY
    # two leaders in one term
    dual = kernels.check_safety(
        state=jnp.asarray([[2] * g, [2] * g], jnp.int32),
        term=planes(3),
        commit=planes(5),
        last_index=planes(7),
        agree=jnp.full((2, 2, g), 6, jnp.int32),
        prev_commit=planes(5),
    )
    assert int(np.asarray(dual)[kernels.SV_DUAL_LEADER]) == g
    # committed prefixes disagree: both committed past the common prefix
    div = kernels.check_safety(
        state=jnp.zeros((2, g), jnp.int32),
        term=planes(3),
        commit=planes(5),
        last_index=planes(7),
        agree=jnp.full((2, 2, g), 4, jnp.int32),
        prev_commit=planes(5),
    )
    assert int(np.asarray(div)[kernels.SV_COMMIT_DIVERGED]) == g
    # commit regression
    reg = kernels.check_safety(
        state=jnp.zeros((2, g), jnp.int32),
        term=planes(3),
        commit=planes(4),
        last_index=planes(7),
        agree=jnp.full((2, 2, g), 6, jnp.int32),
        prev_commit=planes(5),
    )
    assert int(np.asarray(reg)[kernels.SV_COMMIT_REGRESSED]) == g
    # cursors past the log end
    bad = kernels.check_safety(
        state=jnp.zeros((2, g), jnp.int32),
        term=planes(3),
        commit=planes(9),
        last_index=planes(7),
        agree=jnp.full((2, 2, g), 6, jnp.int32),
        prev_commit=planes(5),
    )
    assert int(np.asarray(bad)[kernels.SV_CURSOR_INVALID]) == g


# --- claims 2 + 3, tier-1: shared-sim short schedules -----------------------


def golden_plan():
    """The tier-1 schedule: settle, symmetric split, asymmetric one-way
    link with loss, heal — every fault class in ~45 rounds."""
    return chaos.plan_from_dict(
        {
            "name": "tier1-mix",
            "peers": P,
            "phases": [
                {"rounds": 16, "append": 1},
                {"rounds": 10, "partition": [[1, 2], [3]], "append": 1},
                {
                    "rounds": 9,
                    "links": [{"from": 1, "to": 3, "up": False}],
                    "loss": [{"from": 2, "to": 3, "rate": 0.5}],
                    "append": 2,
                },
                {"rounds": 10, "heal": True, "append": 1},
            ],
        }
    )


def test_chaos_parity_scheduled_g8(shared_sim):
    """Per-round state + health parity against the real scalar pump across
    the tier-1 multi-phase schedule (partition, one-way link, loss, heal)."""
    sim = reset(shared_sim)
    plan = golden_plan()
    sched = chaos.HostSchedule(plan, G)
    scalar = ScalarCluster(G, P)
    oracle = ChaosOracle(scalar, schedule=sched, window=WINDOW)
    for r in range(plan.n_rounds):
        link, crashed, append = sched.masks(r)
        oracle.scheduled_round()
        sim.run_round(
            jnp.asarray(crashed),
            jnp.asarray(append, dtype=jnp.int32),
            link=jnp.asarray(link),
        )
        assert_parity(scalar, sim, r, "scheduled-g8")
        assert_health_parity(oracle, sim, r, "scheduled-g8")


def test_crash_mask_is_link_special_case(shared_sim):
    """Driving the LINK path with crash-shaped planes (row+column down)
    reproduces the scalar oracle on a crash-only schedule — whole-peer
    crash is the promised special case of the link plane."""
    sim = reset(shared_sim)
    scalar = ScalarCluster(G, P)
    oracle = ChaosOracle(scalar, window=WINDOW)
    crash = np.zeros((G, P), bool)
    for r in range(40):
        if r == 18:
            crash[::2, 0] = True  # even groups lose peer 1
        if r == 30:
            crash[:] = False
        app = np.full(G, 1 if r % 2 else 0, np.int64)
        link = np.ones((P, P, G), bool)
        cp = crash.T  # [P, G]
        link &= ~cp[:, None, :] & ~cp[None, :, :]
        oracle.round(crash, app)  # crash-mask oracle, no link arg
        sim.run_round(
            jnp.asarray(cp.copy()),
            jnp.asarray(app, dtype=jnp.int32),
            link=jnp.asarray(link),
        )
        assert_parity(scalar, sim, r, "crash-special-case")
        assert_health_parity(oracle, sim, r, "crash-special-case")


def test_asymmetric_partition_term_inflation(shared_sim):
    """The classic check-quorum-free pathology, pinned: a deposed leader
    whose INCOMING links are cut (it can send, never receive) re-campaigns
    forever — every campaign bumps the fleet's term and deposes the
    sitting leader, so terms inflate and leadership churns without bound.
    The PR 3 term_bumps_in_window plane is the documented witness: the
    disturbed groups churn past the threshold, the control groups stay
    quiet.  (Check-quorum would damp this; it stays host-side —
    sim.py protocol scope.)"""
    sim = reset(shared_sim)
    settle = jnp.ones((G,), jnp.int32)
    sim.run(30)  # settle leaders with links all-up
    # Groups 0..3 disturbed: one FOLLOWER per group receives nothing
    # (column down) but sends everything.  (Cutting the leader's incoming
    # links instead would only stall commits — a leader never campaigns.)
    # Groups 4..7 are the control.
    leader_row = np.argmax(
        np.asarray(sim.state.state) == kernels.ROLE_LEADER, axis=0
    )
    link = np.ones((P, P, G), bool)
    for g in range(4):
        link[:, (leader_row[g] + 1) % P, g] = False
    base_term = np.asarray(sim.state.term).max(axis=0)
    sim.reset_health()
    peak_bumps = np.zeros(G, np.int64)
    jl = jnp.asarray(link)
    for r in range(80):
        sim.run_round(append_n=settle, link=jl)
        peak_bumps = np.maximum(
            peak_bumps,
            np.asarray(sim._health.planes)[kernels.HP_TERM_BUMPS],
        )
    planes = np.asarray(sim._health.planes)
    term_now = np.asarray(sim.state.term).max(axis=0)
    # Disturbed groups inflate terms (one per disturber campaign, i.e.
    # every randomized timeout in [10, 20)); control groups do not move.
    assert (term_now[:4] - base_term[:4] >= 3).all(), term_now - base_term
    assert (term_now[4:] == base_term[4:]).all()
    # The churn plane is the witness: every disturbed group shows term
    # bumps inside some churn window, no control group ever does.
    assert (peak_bumps[:4] >= 1).all(), peak_bumps
    assert (peak_bumps[4:] == 0).all()
    # The disturber never wins (no grants return), so every bump is a
    # vote split — the cumulative split plane records the churn too.
    splits = planes[kernels.HP_VOTE_SPLITS]
    assert (splits[:4] >= 3).all(), splits
    assert (splits[4:] == 0).all()


ROUND_C = 8  # deposed_candidate_rounds: 1 + 3 settle, then A, B, B2, B', C


def deposed_candidate_rounds(n_peers, one_way):
    """[(link[P, P], append_n, kicked peers)]: a JOINT fleet (incoming
    {1, 2, 3}, outgoing the last two peers at P = 3, {3, 4, 5} at P = 5) is
    led into the round in which peer 1 campaigns a term below peer 2 and
    still holds the leader's entries below the leader's commit.  The last
    peer leads.  Round A loses every ack on one-way links (entries reach
    all, nothing commits); B cuts leader -> 1 alone, so the catch-up
    commit reaches everyone but peer 1; B2 grows the leader's log past
    peer 1's with the acks lost again; B' lets peer 2 campaign unheard
    (term + 1).  In round C both are kicked: peer 2's request (term + 2)
    deposes candidate 1 (term + 1) in wave 1, and the voters reject peer
    1 — its log is short — with a commit peer 1 holds the entry of.
    `one_way` cuts 1 -> 2 in round C, so no append of the new leader's is
    adopted by peer 1 and its end-of-round commit is what the rejects
    left it.  Two settled rounds follow."""
    P = n_peers
    lead, c1, c2 = P - 1, 0, 1
    up = lambda: np.ones((P, P), bool)
    rounds = [(up(), 0, [lead])] + [(up(), 1, [])] * 3
    a = up()
    a[:, lead] = False
    b = up()
    b[lead, c1] = False
    b2 = a.copy()
    b2[lead, c1] = False
    b3 = b.copy()
    b3[c2, :] = False
    c = up()
    c[c1, c2] = not one_way
    rounds += [(a, 2, []), (b, 0, []), (b2, 1, []), (b3, 0, [c2]),
               (c, 0, [c1, c2])]
    return rounds + [(up(), 1, [])] * 2


@functools.lru_cache(maxsize=None)
def deposed_candidate_runs(n_peers):
    """{one_way: (first mismatch against the scalar oracle or None,
    commit[peer 1] before and after round C on the device)} — both variants
    through ONE jitted `sim.step` (the link plane is an argument)."""
    n_groups = 8
    voters = [1, 2, 3]
    outgoing = [2, 3] if n_peers == 3 else [3, 4, 5]
    vm = np.zeros((n_peers, n_groups), bool)
    om = np.zeros((n_peers, n_groups), bool)
    vm[[i - 1 for i in voters]] = True
    om[[i - 1 for i in outgoing]] = True
    cfg = SimConfig(n_groups=n_groups, n_peers=n_peers)
    step = jax.jit(
        lambda st, crashed, app, link, kick: sim_mod.step(
            cfg, st, crashed, app, link=link, campaign_kick=kick)
    )
    crash = np.zeros((n_groups, n_peers), bool)
    runs = {}
    for one_way in (False, True):
        scalar = ScalarCluster(
            n_groups, n_peers, voters=voters, voters_outgoing=outgoing)
        oracle = ChaosOracle(scalar, window=WINDOW)
        state = sim_mod.init_state(cfg, jnp.asarray(vm), jnp.asarray(om))
        mismatch, commits = None, []
        for r, (ln, app, kicked) in enumerate(
                deposed_candidate_rounds(n_peers, one_way)):
            link = np.repeat(ln[:, :, None], n_groups, axis=2)
            kick = np.zeros((n_groups, n_peers), bool)
            kick[:, kicked] = True
            app_n = np.full(n_groups, app, np.int64)
            oracle.round(crash, app_n, link, kick=kick)
            state = step(
                state, jnp.asarray(crash.T.copy()),
                jnp.asarray(app_n, dtype=jnp.int32), jnp.asarray(link),
                jnp.asarray(kick.T.copy()),
            )
            commits.append(np.asarray(state.commit)[0])
            want = scalar.snapshot()
            for f in FIELDS:
                got = np.asarray(getattr(state, f), dtype=np.int64).T
                if mismatch is None and not np.array_equal(want[f], got):
                    mismatch = (r, f, want[f][0].tolist(), got[0].tolist())
        runs[one_way] = (mismatch, commits[ROUND_C - 1], commits[ROUND_C])
    return runs


@pytest.mark.parametrize("n_peers", [3, 5])
def test_a_candidate_deposed_in_wave_1_matches_the_scalar_replay(n_peers):
    """The round ISSUE 49's step 1 rewrote (the link-path tally is
    `sim._real_tally` now), driven where its inputs are least plain: a
    joint configuration, one-way links on the way in, a candidate deposed
    in wave 1 whose voters reject it with a commit above its own.  Every
    link is up in round C, so the new leader's commit reaches the deposed
    candidate in the same round: every plane of every round against the
    scalar replay."""
    mismatch, before, after = deposed_candidate_runs(n_peers)[False]
    assert mismatch is None, mismatch
    assert (before == 4).all() and (after == 8).all(), (before, after)


@pytest.mark.xfail(strict=True, reason=(
    "a link-path divergence from the scalar port that PR 49 found and did "
    "not cure (ROADMAP C17): raft.rs steps a MsgRequestVoteResponse "
    "through step_candidate only, so a candidate deposed before the "
    "response arrives takes no commit from it; sim._real_tally (and the "
    "rolled tally before it) fast-forwards it all the same"))
@pytest.mark.parametrize("n_peers", [3, 5])
def test_a_deposed_candidate_takes_no_commit_from_its_rejects(n_peers):
    """The same rounds with 1 -> 2 cut in round C (a one-way link): the new
    leader's noop finds no matching probe and no reverse link at peer 1, so
    nothing but the rejects can move peer 1's commit.  The scalar port
    leaves it at 4; the device, which does not mask the tally's
    fast-forward by `active`, reads 6: (8, "commit", [4, ...], [6, ...])."""
    mismatch, _, _ = deposed_candidate_runs(n_peers)[True]
    assert mismatch is None, mismatch


def test_run_plan_matches_stepping_and_is_safe(shared_sim):
    """One-scan run_plan == round-by-round stepping (same masks, same
    PRNG), zero safety violations, and the MTTR report is well-formed."""
    sim = reset(shared_sim)
    plan = golden_plan()
    sched = chaos.HostSchedule(plan, G)
    for r in range(plan.n_rounds):
        link, crashed, append = sched.masks(r)
        sim.run_round(
            jnp.asarray(crashed),
            jnp.asarray(append, dtype=jnp.int32),
            link=jnp.asarray(link),
        )
    stepped_state = sim.state
    stepped_planes = np.asarray(sim._health.planes)

    sim2 = ClusterSim(
        SimConfig(
            n_groups=G, n_peers=P, collect_health=True, health_window=WINDOW
        ),
        chaos=plan,
    )
    report = sim2.run_plan()
    for f in FIELDS + ("matched", "agree", "term_start_index"):
        assert np.array_equal(
            np.asarray(getattr(sim2.state, f)),
            np.asarray(getattr(stepped_state, f)),
        ), f"run_plan vs stepping: {f}"
    assert np.array_equal(np.asarray(sim2._health.planes), stepped_planes)
    assert report["rounds"] == plan.n_rounds
    assert all(v == 0 for v in report["safety"].values()), report
    assert report["reelections"] >= 0
    if report["reelections"]:
        assert report["mttr_rounds"] > 0


# --- the per-round masks at their bytes (ISSUE 48) ---------------------------


def masks_plan(lossy):
    """A partition, a crash and a group selector; `lossy` adds a loss rate
    on every link of one phase and a directed loss row in another."""
    phases = [
        {"rounds": 3, "append": 1},
        {"rounds": 4, "partition": [[1, 2], [3]], "append": 2,
         "groups": {"mod": 3, "eq": 1}},
        {"rounds": 4, "crash": [2], "groups": {"mod": 2, "eq": 0}},
        {"rounds": 3, "links": [{"from": 1, "to": 3, "up": False}]},
    ]
    if lossy:
        phases[1]["loss_all"] = 0.3
        phases[3]["loss"] = [{"from": 2, "to": 3, "rate": 0.5}]
    return chaos.plan_from_dict({"name": "masks", "peers": P, "phases": phases})


LOSS = pytest.mark.parametrize("lossy", [False, True], ids=["lossless", "lossy"])


@LOSS
def test_schedule_masks_equal_the_host_schedule_round_for_round(lossy):
    """`CompiledChaos.lossless` is read off the plan, and either program of
    schedule_masks — no loss unpack and no draw, or today's — hands the
    step the oracle's masks in every round, jitted as the runners call it
    (the round a traced scalar)."""
    plan = masks_plan(lossy)
    compiled = chaos.compile_plan(plan, G)
    assert compiled.lossless is (not lossy)
    host = chaos.HostSchedule(plan, G)
    masks = jax.jit(functools.partial(chaos.schedule_masks, compiled))
    dropped = 0
    for r in range(plan.n_rounds):
        link, crashed, append = masks(jnp.int32(r))
        want_link, want_crashed, want_append = host.masks(r)
        assert link.dtype == crashed.dtype == jnp.bool_
        assert append.dtype == jnp.int32
        assert np.array_equal(np.asarray(link), want_link), r
        assert np.array_equal(np.asarray(crashed), want_crashed), r
        assert np.array_equal(np.asarray(append), want_append), r
        dropped += int((host.link[host.phase_of_round[r]] & ~want_link).sum())
    assert (dropped > 0) is lossy, "the lossy plan must drop something"


@LOSS
def test_only_a_plan_with_a_loss_rate_draws_the_loss_sample(lossy):
    """The chaos runner's jaxpr: a lossless plan's round has no `rem` (the
    draw's `% LOSS_SCALE`) under `runner.chaos_masks` and reads
    `loss_packed` nowhere — the operand stays in the jit's argument list,
    in registry order, and is dead; a plan with a loss rate has both.
    Neither stacks rows, and both hand the round its planes through one
    barrier."""
    from jax.interpreters import partial_eval as pe
    from test_round_map import leaves  # the jaxpr walk, containers included

    from raft_tpu.multiraft import runner as runner_mod
    from raft_tpu.multiraft import schedules

    cfg = SimConfig(n_groups=G, n_peers=P, collect_health=True)
    compiled = chaos.compile_plan(masks_plan(lossy), G)
    run = runner_mod.make_runner(cfg, (compiled,))
    closed = jax.make_jaxpr(run.jitted)(
        sim_mod.init_state(cfg), sim_mod.init_health(cfg), *run.schedule_args
    )
    fields = schedules.array_fields("chaos")
    assert len(run.schedule_args) == len(fields)
    _, used = pe.dce_jaxpr(closed.jaxpr, [True] * len(closed.jaxpr.outvars))
    read = dict(zip(fields, used[-len(fields):]))
    assert read == {**dict.fromkeys(fields, True), "loss_packed": lossy}
    masks = [
        prim for prim, stack, _ in leaves(closed.jaxpr)
        if "runner.chaos_masks" in stack
    ]
    assert ("rem" in masks) is lossy
    assert "concatenate" not in masks
    assert masks.count("optimization_barrier") == 1


# --- claim 3 at scale: seeded link fuzz (slow tier) -------------------------


def run_link_fuzz(seed, n_groups, n_peers, rounds, flip=0.08, crashp=0.03):
    """Random directed link flips + crash flips + periodic heal-all, with
    exact per-round state and health parity."""
    scalar = ScalarCluster(n_groups, n_peers)
    oracle = ChaosOracle(scalar, window=WINDOW)
    sim = ClusterSim(
        SimConfig(
            n_groups=n_groups,
            n_peers=n_peers,
            collect_health=True,
            health_window=WINDOW,
        )
    )
    rng = np.random.RandomState(seed)
    link = np.ones((n_peers, n_peers, n_groups), bool)
    crash = np.zeros((n_groups, n_peers), bool)
    prev_commit = np.asarray(sim.state.commit)
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
        sim.run_round(
            jnp.asarray(crash.T.copy()),
            jnp.asarray(app, dtype=jnp.int32),
            link=jnp.asarray(link.copy()),
        )
        assert_parity(scalar, sim, r, f"link-fuzz seed {seed}")
        assert_health_parity(oracle, sim, r, f"link-fuzz seed {seed}")
        # The device-side safety invariants must hold on every reachable
        # state — checked every fuzz round (they caught the stale-leader
        # commit-broadcast bug the state parity alone missed).
        st = sim.state
        counts = np.asarray(
            kernels.check_safety(
                st.state, st.term, st.commit, st.last_index, st.agree,
                jnp.asarray(prev_commit),
            )
        )
        prev_commit = np.asarray(st.commit)
        assert not counts.any(), (
            f"link-fuzz seed {seed} round {r}: safety violations "
            f"{dict(zip(kernels.SAFETY_NAMES, counts.tolist()))}"
        )


@pytest.mark.slow  # ~9s link-path compile per config + lockstep scalar sim
def test_link_fuzz_plain():
    for seed in range(4):
        run_link_fuzz(seed, n_groups=4, n_peers=3, rounds=100)


@pytest.mark.slow
def test_link_fuzz_5peers():
    for seed in (10, 11):
        run_link_fuzz(seed, n_groups=3, n_peers=5, rounds=100)


@pytest.mark.slow
def test_link_fuzz_at_scale_g32():
    """One order of magnitude past the tier-1 batch: cross-group
    independence of the pairwise planes (the [P, P, G] lanes) gets 32
    chances per round to break."""
    run_link_fuzz(3, n_groups=32, n_peers=3, rounds=110, flip=0.05)


@pytest.mark.slow
def test_link_fuzz_joint_and_learners():
    """Joint double-majority elections and non-voting learners under link
    faults (the config classes the crash-only fuzz already covers)."""
    for config, peers, seeds in (
        ("joint", 5, (0, 1)),
        ("learners", 4, (0, 1)),
    ):
        if config == "joint":
            voters, outgoing, learners = [1, 2, 3], [3, 4, 5], []
        else:
            voters, outgoing, learners = list(range(1, peers)), [], [peers]
        kwargs = {"voters": voters}
        if outgoing:
            kwargs["voters_outgoing"] = outgoing
        if learners:
            kwargs["learners"] = learners
        for seed in seeds:
            n_groups = 4
            scalar = ScalarCluster(n_groups, peers, **kwargs)
            oracle = ChaosOracle(scalar, window=WINDOW)
            vm = np.zeros((peers, n_groups), bool)
            om = np.zeros((peers, n_groups), bool)
            lm = np.zeros((peers, n_groups), bool)
            for i in voters:
                vm[i - 1] = True
            for i in outgoing:
                om[i - 1] = True
            for i in learners:
                lm[i - 1] = True
            sim = ClusterSim(
                SimConfig(
                    n_groups=n_groups,
                    n_peers=peers,
                    collect_health=True,
                    health_window=WINDOW,
                ),
                jnp.asarray(vm),
                jnp.asarray(om),
                jnp.asarray(lm),
            )
            rng = np.random.RandomState(seed)
            link = np.ones((peers, peers, n_groups), bool)
            crash = np.zeros((n_groups, peers), bool)
            for r in range(90):
                for g in range(n_groups):
                    for _ in range(2):
                        if rng.rand() < 0.08:
                            a, b = rng.randint(peers), rng.randint(peers)
                            if a != b:
                                link[a, b, g] ^= True
                    if rng.rand() < 0.03:
                        crash[g, rng.randint(peers)] ^= True
                    if rng.rand() < 0.05:
                        link[:, :, g] = True
                        crash[g, :] = False
                app = rng.randint(0, 3, size=n_groups).astype(np.int64)
                oracle.round(crash, app, link)
                sim.run_round(
                    jnp.asarray(crash.T.copy()),
                    jnp.asarray(app, dtype=jnp.int32),
                    link=jnp.asarray(link.copy()),
                )
                assert_parity(scalar, sim, r, f"{config} seed {seed}")
                assert_health_parity(oracle, sim, r, f"{config} seed {seed}")


@pytest.mark.slow  # golden corpus at G=32 with the scalar oracle in lockstep
def test_chaos_golden_corpus_parity_g32():
    """The six-scenario golden corpus (tests/testdata/chaos) replayed at
    G=32 with full oracle parity — the datadriven harness pins outputs,
    this pins the semantics behind them."""
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
        scalar = ScalarCluster(n_groups, plan.n_peers)
        oracle = ChaosOracle(scalar, schedule=sched, window=WINDOW)
        sim = ClusterSim(
            SimConfig(
                n_groups=n_groups,
                n_peers=plan.n_peers,
                collect_health=True,
                health_window=WINDOW,
            )
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


# --- GC010 parity obligations (tools/graftcheck/parity_obligations.json) ---

# Obligations this suite acknowledges owning: the chaos kernels' oracle is
# the ChaosOracle lockstep driven above (the loss PRNG twin directly, the
# safety checker on every fuzz/golden round via run_plan).  A new chaos
# kernel (or a retired one) changes the extracted obligations and fails
# test_parity_obligations_fresh_and_covered until this set acknowledges it.
CHAOS_SUITE_OBLIGATIONS = {"link_loss_draw", "check_safety"}


def test_parity_obligations_chaos_suite_acknowledged():
    import json
    from pathlib import Path

    base = Path(__file__).resolve().parent.parent
    committed = json.loads(
        (base / "tools" / "graftcheck" / "parity_obligations.json").read_text(
            encoding="utf-8"
        )
    )
    mine = {
        o["kernel"]
        for o in committed["obligations"]
        if o["parity_suite"].endswith("test_chaos_parity.py")
    }
    assert mine == CHAOS_SUITE_OBLIGATIONS, (
        "chaos-suite parity obligations changed; extend the schedules (or "
        "the acknowledgment set) for: "
        f"{sorted(mine ^ CHAOS_SUITE_OBLIGATIONS)}"
    )
