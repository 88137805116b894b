"""The damped round's tallies over all candidates at once (ISSUE 43,
ROADMAP A5 (a)) and without a loop over the voters (ISSUE 45).

`sim._real_tally` and `sim._pre_tally` take every candidate at once — the
candidate axis is a batch axis, because a candidate's tally reads and writes
only its own row of the planes and its own voter slab of the responses — and
hold no loop: what a walk over the voters in receipt order carries from one
response to the next is written as prefix counts and a first-event mask
along the voter axis.  This file holds both to a plain reference that does
what the round did before either — one candidate at a time, one voter at a
time, one group at a time, Python integers and no `lax` — on drawn
grant / reject / reject-term / snapshot planes under partial links, for
P in {3, 5}, with and without a non-empty outgoing half (a joint
configuration), every draw holding a group with two candidates active at
once, and on SCRIPTED response streams that walk the pre-vote tally's
first-event rule arm by arm (what a reader of raft.rs's `poll`,
`campaign` and `become_follower` would try).  Every output plane must be
equal element for element.  The round's bit-equality end to end stays the
parity suites' subject (`tests/test_damping_parity.py`, ...); the lowered
form, `tests/test_sender_loops.py`'s.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.multiraft import kernels, sim
from raft_tpu.util import deterministic_timeout

G = 8
SEEDS = [0, 1, 2]


def has_quorum(cnt_i, cnt_o, n_i, n_o):
    return (cnt_i >= n_i // 2 + 1 or n_i == 0) and (
        cnt_o >= n_o // 2 + 1 or n_o == 0
    )


def cannot_win(cnt_i, cnt_o, rec_i, rec_o, n_i, n_o):
    return (n_i > 0 and cnt_i + (n_i - rec_i) < n_i // 2 + 1) or (
        n_o > 0 and cnt_o + (n_o - rec_o) < n_o // 2 + 1
    )


class Drawn:
    """One seeded draw of everything a tally reads, as numpy arrays
    (response planes `[P_cand, P_voter, G]`)."""

    def __init__(self, P, joint, seed):
        rng = np.random.default_rng([seed, P, int(joint)])
        self.P = P
        self.voter = rng.random((P, G)) < 0.8
        self.voter[0] |= ~self.voter.any(axis=0)
        self.outgoing = np.zeros((P, G), bool)
        if joint:
            # Every second group is mid-change, with its own outgoing half.
            self.outgoing[:, ::2] = rng.random((P, G // 2)) < 0.6
            self.outgoing[1, ::2] |= ~self.outgoing[:, ::2].any(axis=0)
        # Partial links: a response reaches its candidate or it does not;
        # no peer answers itself.
        self.erev = (rng.random((P, P, G)) < 0.75) & ~np.eye(P, dtype=bool)[
            :, :, None
        ]
        self.grants = rng.random((P, P, G)) < 0.5
        self.resps = self.grants | (rng.random((P, P, G)) < 0.7)
        self.snap = rng.integers(0, 6, (P, P, G)).astype(np.int32)
        self.agree = rng.integers(0, 6, (P, P, G)).astype(np.int32)
        self.commit = rng.integers(0, 4, (P, G)).astype(np.int32)
        # Candidates: group 0 has two for certain, the rest as drawn.
        self.active = rng.random((P, G)) < 0.5
        self.active[:2, 0] = True
        # Pre-vote: pre-campaign terms, and reject terms at, under and
        # over them (a poll rejection, a stale answer, a deposition).
        self.t0 = rng.integers(1, 4, (P, G)).astype(np.int32)
        self.resp_t = (
            self.t0[:, None, :] + rng.integers(-1, 3, (P, P, G))
        ).astype(np.int32)
        self.term = self.t0.copy()
        self.vote = rng.integers(0, P + 1, (P, G)).astype(np.int32)
        self.role = np.where(
            self.active, kernels.ROLE_PRE_CANDIDATE, kernels.ROLE_FOLLOWER
        ).astype(np.int32)
        self.ee = rng.integers(0, 20, (P, G)).astype(np.int32)
        self.hb = rng.integers(0, 2, (P, G)).astype(np.int32)
        self.rt = rng.integers(20, 40, (P, G)).astype(np.int32)


def fleet_of(d):
    cfg = sim.SimConfig(
        G, d.P, election_tick=20, heartbeat_tick=2, check_quorum=True,
        pre_vote=True,
    )
    st = sim.init_state(
        cfg, jnp.asarray(d.voter), jnp.asarray(d.outgoing)
    )._replace(agree=jnp.asarray(d.agree))
    return cfg, st


def drawn_fleet(P, joint, seed):
    d = Drawn(P, joint, seed)
    assert (d.active.sum(axis=0) >= 2).any(), "no group with two candidates"
    assert (d.outgoing.any(axis=0)).any() == joint
    return (d,) + fleet_of(d)


def reference_real(d):
    """(C', won, lost): candidate by candidate, voter by voter."""
    P = d.P
    C = d.commit.copy()
    won = np.zeros((P, G), bool)
    lost = np.zeros((P, G), bool)
    for g in range(G):
        n_i, n_o = int(d.voter[:, g].sum()), int(d.outgoing[:, g].sum())
        for s in range(P):
            act = bool(d.active[s, g])
            cnt_i = rec_i = int(act and d.voter[s, g])
            cnt_o = rec_o = int(act and d.outgoing[s, g])
            ff = 0
            for v in range(P):
                grant = bool(d.grants[s, v, g] and d.erev[s, v, g])
                reject = bool(
                    d.resps[s, v, g]
                    and not d.grants[s, v, g]
                    and d.erev[s, v, g]
                )
                decided = has_quorum(cnt_i, cnt_o, n_i, n_o) or cannot_win(
                    cnt_i, cnt_o, rec_i, rec_o, n_i, n_o
                )
                if (
                    reject
                    and not decided
                    and d.snap[s, v, g] <= d.agree[s, v, g]
                ):
                    ff = max(ff, int(d.snap[s, v, g]))
                if grant or reject:
                    rec_i += int(d.voter[v, g])
                    rec_o += int(d.outgoing[v, g])
                if grant:
                    cnt_i += int(d.voter[v, g])
                    cnt_o += int(d.outgoing[v, g])
            won[s, g] = act and has_quorum(cnt_i, cnt_o, n_i, n_o)
            lost[s, g] = (
                act
                and not won[s, g]
                and cannot_win(cnt_i, cnt_o, rec_i, rec_o, n_i, n_o)
            )
            C[s, g] = max(C[s, g], ff)
    return C, won, lost


def reference_pre(d, cfg):
    """(C, T, V, St, EE, HB, RT, pre_won) after the pre-vote tally."""
    P = d.P
    C, T, V, St = d.commit.copy(), d.term.copy(), d.vote.copy(), d.role.copy()
    EE, HB, RT = d.ee.copy(), d.hb.copy(), d.rt.copy()
    pre_won = np.zeros((P, G), bool)
    for g in range(G):
        n_i, n_o = int(d.voter[:, g].sum()), int(d.outgoing[:, g].sum())
        for s in range(P):
            act = bool(d.active[s, g])
            t0 = int(d.t0[s, g])
            cnt_i = rec_i = int(act and d.voter[s, g])
            cnt_o = rec_o = int(act and d.outgoing[s, g])
            won = act and has_quorum(cnt_i, cnt_o, n_i, n_o)
            lost = deposed = False
            cur_t = t0 + 1 if won else t0
            ff = 0
            for v in range(P):
                grant = bool(d.grants[s, v, g] and d.erev[s, v, g])
                reject = bool(
                    d.resps[s, v, g]
                    and not d.grants[s, v, g]
                    and d.erev[s, v, g]
                )
                rt_v = int(d.resp_t[s, v, g])
                deposed_now = reject and rt_v > cur_t
                undecided = not (deposed or won or lost)
                rec_grant = grant and undecided
                rec_rej = reject and rt_v == t0 and undecided
                if rec_rej and d.snap[s, v, g] <= d.agree[s, v, g]:
                    ff = max(ff, int(d.snap[s, v, g]))
                if rec_grant:
                    cnt_i += int(d.voter[v, g])
                    cnt_o += int(d.outgoing[v, g])
                if rec_grant or rec_rej:
                    rec_i += int(d.voter[v, g])
                    rec_o += int(d.outgoing[v, g])
                if rec_grant and has_quorum(cnt_i, cnt_o, n_i, n_o):
                    won = True
                    cur_t = t0 + 1
                if rec_rej and cannot_win(
                    cnt_i, cnt_o, rec_i, rec_o, n_i, n_o
                ):
                    lost = True
                if deposed_now:
                    deposed = True
                    cur_t = max(cur_t, rt_v)
            won, lost, deposed = won and act, lost and act, deposed and act
            C[s, g] = max(C[s, g], ff)
            if act:
                T[s, g] = cur_t
            if won and not deposed:
                V[s, g] = s + 1
                St[s, g] = kernels.ROLE_CANDIDATE
            else:
                if deposed and act and cur_t != t0:
                    V[s, g] = 0
                if deposed or lost:
                    St[s, g] = kernels.ROLE_FOLLOWER
            if won or lost or deposed:
                EE[s, g] = 0
                HB[s, g] = 0
            if won or deposed:
                RT[s, g] = deterministic_timeout(
                    g * 2**16 + (s + 1), int(T[s, g]), cfg.min_timeout,
                    cfg.max_timeout,
                )
            pre_won[s, g] = won
    return C, T, V, St, EE, HB, RT, pre_won


def assert_planes_equal(names, got, want):
    for name, g, w in zip(names, got, want):
        np.testing.assert_array_equal(np.asarray(g), w, err_msg=name)


REAL_OUT = ("C", "won", "lost")
PRE_OUT = ("C", "T", "V", "St", "EE", "HB", "RT", "pre_won")


def real_tally(d, st):
    return sim._real_tally(
        st, sim._halves(st), jnp.asarray(d.commit), jnp.asarray(d.active),
        jnp.asarray(d.grants), jnp.asarray(d.resps), jnp.asarray(d.snap),
        st.agree, jnp.asarray(d.erev),
    )


def pre_tally(d, cfg, st):
    P = d.P
    node_key = sim._node_key(cfg)
    lo = jnp.full((P, G), cfg.min_timeout, jnp.int32)
    hi = jnp.full((P, G), cfg.max_timeout, jnp.int32)

    def draw(term):
        return kernels.timeout_draw(node_key, term.astype(jnp.uint32), lo, hi)

    planes = tuple(
        jnp.asarray(p)
        for p in (d.commit, d.term, d.vote, d.role, d.ee, d.hb, d.rt)
    )
    return sim._pre_tally(
        st, sim._halves(st), planes, jnp.asarray(d.active),
        jnp.asarray(d.t0), jnp.asarray(d.grants), jnp.asarray(d.resps),
        jnp.asarray(d.resp_t), jnp.asarray(d.snap), jnp.asarray(d.erev), draw,
    )


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("joint", [False, True], ids=["plain", "joint"])
@pytest.mark.parametrize("P", [3, 5])
def test_real_tally_is_the_per_candidate_per_voter_tally(P, joint, seed):
    d, _, st = drawn_fleet(P, joint, seed)
    assert_planes_equal(REAL_OUT, real_tally(d, st), reference_real(d))


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("joint", [False, True], ids=["plain", "joint"])
@pytest.mark.parametrize("P", [3, 5])
def test_pre_vote_tally_is_the_per_candidate_per_voter_tally(P, joint, seed):
    d, cfg, st = drawn_fleet(P, joint, seed)
    assert_planes_equal(PRE_OUT, pre_tally(d, cfg, st), reference_pre(d, cfg))


def test_the_draws_reach_every_branch():
    """Pooled over the cases above: winners, losers, a commit fast-forward
    off a reject's snapshot; pre-winners, pre-winners deposed after the win,
    candidates deposed past term + 1, poll losers."""
    seen = set()
    for P in (3, 5):
        for joint in (False, True):
            for seed in SEEDS:
                d, cfg, _ = drawn_fleet(P, joint, seed)
                C, won, lost = reference_real(d)
                reached = {
                    "won": won, "lost": lost, "ff": C > d.commit,
                }
                C, T, _, St, _, _, _, pre_won = reference_pre(d, cfg)
                follower = St == kernels.ROLE_FOLLOWER
                reached.update({
                    "pre_won": pre_won & (St == kernels.ROLE_CANDIDATE),
                    "won_then_deposed": pre_won & follower,
                    "deposed_high": T > d.t0 + 1,
                    "pre_lost": d.active & ~pre_won & follower & (T == d.t0),
                    "pre_ff": C > d.commit,
                })
                seen |= {name for name, where in reached.items() if where.any()}
    assert seen == {
        "won", "lost", "ff", "pre_won", "won_then_deposed", "deposed_high",
        "pre_lost", "pre_ff",
    }, seen


# ---- scripted response streams: the first-event rule, arm by arm.  Peer 0
# of every group is the one (pre-)candidate at pre-campaign term T0, commit
# 1, agreeing with every voter up to index 5; its voters answer in voter
# order with a grant, or with a reject at T0 + `dt` carrying the commit
# `snap`.  A reject at T0 (dt 0) is a poll rejection, at T0 + 1 deposes a
# pre-candidate and leaves a fresh candidate (at T0 + 1 itself) standing,
# above that deposes either.

T0 = 2
FOLLOWER, CANDIDATE = kernels.ROLE_FOLLOWER, kernels.ROLE_CANDIDATE


def grant(v):
    return (v, True, 1, 0)  # a pre-vote grant echoes the request's term


def reject(v, dt, snap=0):
    return (v, False, dt, snap)


class Scripted:
    """What `Drawn` holds, written out: every group the same stream."""

    def __init__(self, P, voters, outgoing, stream):
        self.P = P
        self.voter = np.zeros((P, G), bool)
        self.voter[list(voters)] = True
        self.outgoing = np.zeros((P, G), bool)
        self.outgoing[list(outgoing)] = True
        self.erev = np.zeros((P, P, G), bool)
        self.grants = np.zeros((P, P, G), bool)
        self.resps = np.zeros((P, P, G), bool)
        self.snap = np.zeros((P, P, G), np.int32)
        self.resp_t = np.zeros((P, P, G), np.int32)
        for v, granted, dt, snap in stream:
            self.erev[0, v] = self.resps[0, v] = True
            self.grants[0, v] = granted
            self.resp_t[0, v] = T0 + dt
            self.snap[0, v] = snap
        self.agree = np.full((P, P, G), 5, np.int32)
        self.commit = np.ones((P, G), np.int32)
        self.active = np.zeros((P, G), bool)
        self.active[0] = True
        self.t0 = np.full((P, G), T0, np.int32)
        self.term = self.t0.copy()
        self.vote = np.full((P, G), 2, np.int32)
        self.role = np.where(
            self.active, kernels.ROLE_PRE_CANDIDATE, FOLLOWER
        ).astype(np.int32)
        self.ee = np.full((P, G), 7, np.int32)
        self.hb = np.ones((P, G), np.int32)
        self.rt = np.full((P, G), 30, np.int32)


def everyone(P):
    return range(P)


def enough_grants(P):
    """Grants from voters 1.. that, with its own vote, make peer 0's
    majority of `everyone(P)` — the last of them is the winning grant."""
    return [grant(v) for v in range(1, P // 2 + 1)]


# A case: P -> (voters, outgoing voters, stream, what peer 0 ends as: T, V,
# St, pre_won, C, EE and HB zeroed).
SCRIPTED = []


def scripted(case):
    SCRIPTED.append(case)
    return case


@scripted
def reject_before_the_winning_grant_deposes(P):
    # The grant that would have won comes from the voter after the
    # reject's.
    wins = enough_grants(P)
    stream = wins[:-1] + [reject(len(wins), 1), grant(len(wins) + 1)]
    return everyone(P), (), stream, (T0 + 1, 0, FOLLOWER, False, 1, True)


@scripted
def the_same_reject_after_the_win_does_not(P):
    wins = enough_grants(P)
    stream = wins + [reject(len(wins) + 1, 1)]
    return everyone(P), (), stream, (T0 + 1, 1, CANDIDATE, True, 1, True)


@scripted
def a_higher_reject_after_the_win_knocks_the_candidate_down(P):
    wins = enough_grants(P)
    stream = wins + [reject(len(wins) + 1, 3)]
    return everyone(P), (), stream, (T0 + 3, 0, FOLLOWER, True, 1, True)


@scripted
def two_deposing_rejects_in_falling_order_end_at_the_first(P):
    stream = [reject(1, 3), reject(2, 2)]
    return everyone(P), (), stream, (T0 + 3, 0, FOLLOWER, False, 1, True)


@scripted
def two_deposing_rejects_in_rising_order_end_at_the_second(P):
    stream = [reject(1, 1), reject(2, 4)]
    return everyone(P), (), stream, (T0 + 4, 0, FOLLOWER, False, 1, True)


@scripted
def a_loss_then_a_deposing_reject_sets_both(P):
    # All but the last peer vote: the poll is lost once the rejects
    # leave no majority, and the last peer's reject still deposes.
    voters = range(P - 1)
    n = P - 1
    losing = [reject(v, 0) for v in range(1, n - n // 2 + 1)]
    stream = losing + [reject(P - 1, 2)]
    return voters, (), stream, (T0 + 2, 0, FOLLOWER, False, 1, True)


@scripted
def a_loss_alone_keeps_term_and_vote(P):
    voters = range(P - 1)
    n = P - 1
    stream = [reject(v, 0) for v in range(1, n - n // 2 + 1)]
    return voters, (), stream, (T0, 2, FOLLOWER, False, 1, True)


@scripted
def nothing_records_after_the_first_event(P):
    # The reject's commit (4 <= agree 5, above commit 1) would
    # fast-forward a polling pre-candidate; a candidate has stopped.
    wins = enough_grants(P)
    late = len(wins) + 1
    stream = wins + [reject(late, 0, snap=4)] + [
        grant(v) for v in range(late + 1, P)
    ]
    return everyone(P), (), stream, (T0 + 1, 1, CANDIDATE, True, 1, True)


@scripted
def the_same_reject_before_any_event_fast_forwards(P):
    stream = [reject(1, 0, snap=4)] + [grant(v) for v in range(2, P)]
    return everyone(P), (), stream, (T0 + 1, 1, CANDIDATE, True, 4, True)


@scripted
def a_singleton_has_won_before_any_voter(P):
    stream = [reject(1, 0, snap=4), reject(2, 1)]
    return (0,), (), stream, (T0 + 1, 1, CANDIDATE, True, 1, True)


@scripted
def a_singleton_is_deposed_only_above_its_new_term(P):
    stream = [reject(1, 1), reject(2, 2)]
    return (0,), (), stream, (T0 + 2, 0, FOLLOWER, True, 1, True)


@scripted
def a_joint_group_won_in_one_half_only_is_not_won(P):
    # The grants make the incoming majority; the outgoing half (the
    # last two peers) answers with one poll rejection: lost there.
    wins = enough_grants(P)
    stream = wins + [reject(max(len(wins) + 1, P - 2), 0)]
    return (
        everyone(P), (P - 2, P - 1), stream,
        (T0, 2, FOLLOWER, False, 1, True),
    )


@scripted
def a_joint_group_wins_at_the_grant_that_completes_both_halves(P):
    stream = [grant(v) for v in range(1, P)]
    return (
        everyone(P), (P - 2, P - 1), stream,
        (T0 + 1, 1, CANDIDATE, True, 1, True),
    )


@scripted
def no_response_leaves_the_pre_candidate_polling(P):
    return (
        everyone(P), (), [],
        (T0, 2, kernels.ROLE_PRE_CANDIDATE, False, 1, False),
    )


@pytest.mark.parametrize("case", SCRIPTED, ids=lambda c: c.__name__)
@pytest.mark.parametrize("P", [3, 5])
def test_pre_vote_tally_first_event_rule(P, case):
    voters, outgoing, stream, ends_as = case(P)
    d = Scripted(P, voters, outgoing, stream)
    cfg, st = fleet_of(d)
    got = pre_tally(d, cfg, st)
    assert_planes_equal(PRE_OUT, got, reference_pre(d, cfg))
    # ... and the reference itself says what raft.rs would: peer 0's row.
    C, T, V, St, EE, HB, _, pre_won = (np.asarray(p)[0] for p in got)
    t, v, role, won, commit, settled = ends_as
    for name, plane, want in (
        ("T", T, t), ("V", V, v), ("St", St, role), ("pre_won", pre_won, won),
        ("C", C, commit), ("EE", EE, 0 if settled else 7),
        ("HB", HB, 0 if settled else 1),
    ):
        assert (plane == want).all(), (name, plane, want)


@pytest.mark.parametrize(
    "decided_first", [False, True], ids=["open", "decided"]
)
@pytest.mark.parametrize("P", [3, 5])
def test_real_tally_fast_forwards_only_while_undecided(P, decided_first):
    """poll() records every response, but a reject's commit fast-forwards
    the candidate only if the election was still open when it arrived."""
    wins = enough_grants(P)
    late = reject(len(wins) + 1, 0, snap=4)
    early = reject(1, 0, snap=4)
    stream = (
        wins + [late] if decided_first
        else [early] + [grant(v) for v in range(2, P)]
    )
    d = Scripted(P, everyone(P), (), stream)
    d.role[:] = np.where(d.active, CANDIDATE, FOLLOWER)
    _, st = fleet_of(d)
    got = real_tally(d, st)
    assert_planes_equal(REAL_OUT, got, reference_real(d))
    C, won, lost = (np.asarray(p)[0] for p in got)
    assert won.all() and not lost.any()
    assert (C == (1 if decided_first else 4)).all(), C
