"""The damped round's tallies over all candidates at once (ISSUE 43,
ROADMAP A5 (a)).

`sim._real_tally` and `sim._pre_tally` walk the P voters ONCE, their carry
`[P_cand, G]` planes: the candidate axis is a batch axis, because a
candidate's tally reads and writes only its own row of the planes and its
own voter slab of the responses.  This file holds both to a plain reference
that does what the round did before — one candidate at a time, one voter at
a time, one group at a time, Python integers and no `lax` — on drawn
grant / reject / reject-term / snapshot planes under partial links, for
P in {3, 5}, with and without a non-empty outgoing half (a joint
configuration), every draw holding a group with two candidates active at
once.  Every output plane must be equal element for element.  The round's
bit-equality end to end stays the parity suites' subject
(`tests/test_damping_parity.py`, ...); the lowered form,
`tests/test_sender_loops.py`'s.
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


def drawn_fleet(P, joint, seed):
    d = Drawn(P, joint, seed)
    cfg = sim.SimConfig(
        G, P, election_tick=20, heartbeat_tick=2, check_quorum=True,
        pre_vote=True,
    )
    st = sim.init_state(
        cfg, jnp.asarray(d.voter), jnp.asarray(d.outgoing)
    )._replace(agree=jnp.asarray(d.agree))
    assert (d.active.sum(axis=0) >= 2).any(), "no group with two candidates"
    assert (d.outgoing.any(axis=0)).any() == joint
    return d, cfg, st


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


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("joint", [False, True], ids=["plain", "joint"])
@pytest.mark.parametrize("P", [3, 5])
def test_real_tally_is_the_per_candidate_per_voter_tally(P, joint, seed):
    d, _, st = drawn_fleet(P, joint, seed)
    got = sim._real_tally(
        st, sim._halves(st), jnp.asarray(d.commit), jnp.asarray(d.active),
        jnp.asarray(d.grants), jnp.asarray(d.resps), jnp.asarray(d.snap),
        st.agree, jnp.asarray(d.erev),
    )
    want = reference_real(d)
    assert_planes_equal(("C", "won", "lost"), got, want)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("joint", [False, True], ids=["plain", "joint"])
@pytest.mark.parametrize("P", [3, 5])
def test_pre_vote_tally_is_the_per_candidate_per_voter_tally(P, joint, seed):
    d, cfg, st = drawn_fleet(P, joint, seed)
    node_key = sim._node_key(cfg)
    lo = jnp.full((P, G), cfg.min_timeout, jnp.int32)
    hi = jnp.full((P, G), cfg.max_timeout, jnp.int32)

    def draw(term):
        return kernels.timeout_draw(node_key, term.astype(jnp.uint32), lo, hi)

    planes = tuple(
        jnp.asarray(p)
        for p in (d.commit, d.term, d.vote, d.role, d.ee, d.hb, d.rt)
    )
    got = sim._pre_tally(
        st, sim._halves(st), planes, jnp.asarray(d.active),
        jnp.asarray(d.t0), jnp.asarray(d.grants), jnp.asarray(d.resps),
        jnp.asarray(d.resp_t), jnp.asarray(d.snap), jnp.asarray(d.erev), draw,
    )
    want = reference_pre(d, cfg)
    assert_planes_equal(
        ("C", "T", "V", "St", "EE", "HB", "RT", "pre_won"), got, want
    )


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
