"""How the damped round's loops lower (ISSUE 41, 43, 45; ROADMAP A5).

`sim._damped_linked_step` walks the P sender rows five times a round
(wave 1, wave 3, wave 5 and the two retry passes).  Each walk is ONE `scan`
equation in the jaxpr — the per-sender body traces once, the PR 6
discipline `tools/graftcheck/jaxpr_budget.json` holds — and lowers
straight-line (`sim._sender_scan`): no `while`, and so no
`dynamic_update_slice` that rewrites a `[P, P, G]` stacked output once a
trip, which on the chip was a third of the round (PERF.md §6, PR 41).  Each
trip opens with an `optimization_barrier` on the carry: without it the
straight-line round was 19% SLOWER on the chip than the rolled one (the
compiler pooled the trips and the carried planes fell out of fast memory),
with it 26% faster — so the barrier is held here too.

The tallies (`sim._real_tally`, `sim._pre_tally`) were P x P rolled trips
on `[G]` rows — an outer loop over the candidates around an inner one over
the voters — until PR 43 made the candidate axis a batch axis, and ONE
rolled walk over the voters on `[P_cand, G]` planes until PR 45: what that
walk carried from one response to the next has a closed form along the
voter axis (prefix counts, and for the pre-vote tally a first-event mask;
the docstrings at `sim._pre_tally` and docs/PERF.md argue it), so a tally
holds no loop at all.  Under `damped.tally` there is NO `scan` equation,
and in the lowered text of one damped `sim.step` NO `stablehlo.while` (2
with pre-vote and 1 without before PR 45, 4 and 2 before PR 43, 9 at
cq + pv before PR 41).  The response planes stay candidate-major as the
waves hand them over: under `tally.*` no `transpose`, no slice by a traced
index, and still no `take`, `gather` or row rewrite indexed by a candidate.
(PR 43 measured unrolled forms of the old loop faster on the chip and could
not commit them: XLA's CPU backend took minutes to compile them in tier-1.
The closed form compiles there in about the rolled loop's time.)

Bit-equality of the rounds is the parity suites' subject
(`tests/test_damping_parity.py`, `tests/test_readindex_damped.py`, ...) and
of the tallies alone `tests/test_tally_batched.py`'s; this file holds the
FORM, on the jaxpr and the lowered text of `sim.step` under a link plane
with a read probe, at G = 8.

`sim._linked_step` (the stock fleet's body, ROADMAP A13) was the control of
those three PRs, every loop rolled — six sender loops and the tally's inner
one over the voters, 7 `stablehlo.while` a round — until PR 49: its four
walks whose trips depend on each other lower straight-line — pass 1 and
pass 2, which carry `[P, P, G]` planes, through `sim._sender_scan` like the
damped ones; wave 1 and commit stage B, which carry `[P, G]` planes only,
as a plain `unroll=True` (the barrier rule: traced on the chip, the barrier
cost those two 13% of the round) — its tally IS `sim._real_tally`, and its
stage-A commit, whose trips never read each other, is one
`kernels.committed_index` over the owners.  No `while` there either.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from raft_tpu.multiraft import sim
from test_round_map import leaves, sub_jaxprs

G = 8
# (check_quorum, pre_vote, lease_read); lease reads need check-quorum.
DAMPED = [
    pytest.param(True, False, False, id="cq"),
    pytest.param(True, False, True, id="cq-lease"),
    pytest.param(False, True, False, id="pv"),
    pytest.param(True, True, False, id="cq+pv"),
    pytest.param(True, True, True, id="cq+pv-lease"),
]
SENDER_LOOPS = 5  # wave 1, wave 3, its retry pass, wave 5, its retry pass


def faulted_step(P, cq, pv, lease):
    """(jaxpr, lowered text) of one `sim.step` round under a link plane."""
    cfg = sim.SimConfig(
        G, P, election_tick=20, heartbeat_tick=2, check_quorum=cq,
        pre_vote=pv, lease_read=lease, collect_health=True,
    )
    args = (
        sim.init_state(cfg), jnp.zeros((P, G), bool),
        jnp.ones((G,), jnp.int32), jnp.ones((P, P, G), bool),
        jnp.full((G,), sim.READ_LEASE, jnp.int32),
    )

    def one_round(st, crashed, append_n, link, reads):
        return sim.step(
            cfg, st, crashed, append_n, link=link, read_propose=reads)

    return (jax.make_jaxpr(one_round)(*args).jaxpr,
            jax.jit(one_round).lower(*args).as_text())


def names_of(eqn):
    return tuple(c for c in str(eqn.source_info.name_stack).split("/") if c)


def scans(jaxpr, depth=0, names=()):
    """[(depth, length, unroll, first equation of the body, name stack)] of
    every `scan` equation, nested ones too."""
    found = []
    for eqn in jaxpr.eqns:
        own = names + names_of(eqn)
        if eqn.primitive.name == "scan":
            found.append((depth, eqn.params["length"], eqn.params["unroll"],
                          eqn.params["jaxpr"].jaxpr.eqns[0].primitive.name, own))
        for inner in sub_jaxprs(eqn):
            found.extend(scans(inner, depth + 1, own))
    return found


def primitives_under(jaxpr, scope):
    """The primitives of every leaf equation whose name stack holds `scope`."""
    return {prim for prim, names, _ in leaves(jaxpr) if scope in names}


def updates_of_planes(text, P):
    """The lowered `dynamic_update_slice`s whose operand is a [P, P, G] plane."""
    plane = f"tensor<{P}x{P}x{G}x"
    return [ln for ln in text.splitlines()
            if "dynamic_update_slice" in ln and plane in ln]


# What a loop over candidates would index its rows with, and what a walk
# over voter-major slabs would be fed by.
BY_CANDIDATE = {"dynamic_slice", "dynamic_update_slice", "gather", "scatter"}
VOTER_MAJOR = {"transpose", "scan", "while"}


@pytest.mark.parametrize("cq, pv, lease", DAMPED)
@pytest.mark.parametrize("P", [3, 5])
def test_sender_loops_lower_straight_line(P, cq, pv, lease):
    jaxpr, text = faulted_step(P, cq, pv, lease)
    found = scans(jaxpr)
    # One `scan` equation per loop, P trips, none nested (traced once each).
    top = found[0][0]
    assert all(s[:2] == (top, P) for s in found), found
    # The sender loops: straight-line, every trip behind its barrier.
    senders = [s for s in found if "damped.tally" not in s[4]]
    assert [s[2:4] for s in senders] == [(P, "optimization_barrier")] * SENDER_LOOPS, found
    # The tallies: no loop, nothing in them indexed by a candidate or by a
    # traced voter index, the response planes never turned voter-major.
    assert [s for s in found if "damped.tally" in s[4]] == []
    assert not primitives_under(jaxpr, "damped.tally") & BY_CANDIDATE
    for tally in ["tally.pre", "tally.real"] if pv else ["tally.real"]:
        under = primitives_under(jaxpr, tally)
        assert under, tally
        assert not under & (BY_CANDIDATE | VOTER_MAJOR), (tally, under)
    # No `while` is left in a damped round (9 at cq + pv before PR 41, 4
    # before PR 43, 2 before PR 45), and no stacked [P, P, G] output is
    # rewritten a trip.
    assert len(re.findall(r"stablehlo\.while", text)) == 0
    assert updates_of_planes(text, P) == []


# wave 1, pass 1, pass 2, commit stage B: the scope each walk sits under,
# and whether its trips start behind the barrier (a `[P, P, G]` carry)
STOCK_SENDER_LOOPS = [
    ("linked.election", False), ("linked.replicate", True),
    ("linked.replicate", True), ("linked.commit", False),
]


@pytest.mark.parametrize("P", [3, 5])
def test_the_stock_fleets_loops_lower_straight_line(P):
    """`_linked_step` after PR 49: wave 1, pass 1, pass 2 and commit stage B
    are one `scan` equation each, P trips unrolled, none nested, the two
    passes' behind their barrier and the other two's without one; the tally (`tally.real`) and the stage-A commit (`quorum_commit` on
    whole planes) hold no loop; nothing is left for a `while`."""
    jaxpr, text = faulted_step(P, False, False, False)
    found = scans(jaxpr)
    top = found[0][0]
    assert all(s[:2] == (top, P) for s in found), found
    assert all(s[2] == P for s in found), found
    assert [(s[4][-1], s[3] == "optimization_barrier")
            for s in found] == STOCK_SENDER_LOOPS, found
    under = primitives_under(jaxpr, "tally.real")
    assert under and not under & (BY_CANDIDATE | VOTER_MAJOR), under
    # Stage A reads every owner's row at once; stage B's walk still commits
    # per owner, so the scope holds both forms and neither is a loop of its own.
    commits = [n for _, n, _ in leaves(jaxpr) if "quorum_commit" in n]
    assert commits and all(
        "linked.commit" in n or "linked.workload" in n for n in commits)
    # 7 `while`s before PR 49 (six sender loops, one of them around the
    # tally's inner loop over the voters); no stacked plane rewritten a trip.
    assert len(re.findall(r"stablehlo\.while", text)) == 0
    assert updates_of_planes(text, P) == []
