"""How the damped round's sender loops lower (ISSUE 41, ROADMAP A5).

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
with it 26% faster — so the barrier is held here too.  The
tallies (`_real_tally` / `_tally_inner`, the pre-vote tally / `_pre_inner`)
are P x P bodies of `[G]` rows and stay rolled: two `while`s without
pre-vote, four with it.

Bit-equality of the rounds is the parity suites' subject
(`tests/test_damping_parity.py`, `tests/test_readindex_damped.py`, ...);
this file holds the FORM, on the lowered text of `sim.step` under a link
plane with a read probe, at G = 8.  `sim._linked_step` (the stock fleet's
body, ROADMAP A13) is the control: all of its loops stay rolled.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from raft_tpu.multiraft import sim

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


def scans(jaxpr, depth=0):
    """[(depth, length, unroll, first equation of the body)] of every `scan`
    equation, nested ones too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            found.append((depth, eqn.params["length"], eqn.params["unroll"],
                          eqn.params["jaxpr"].jaxpr.eqns[0].primitive.name))
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    found.extend(scans(inner, depth + 1))
    return found


def updates_of_planes(text, P):
    """The lowered `dynamic_update_slice`s whose operand is a [P, P, G] plane."""
    plane = f"tensor<{P}x{P}x{G}x"
    return [ln for ln in text.splitlines()
            if "dynamic_update_slice" in ln and plane in ln]


@pytest.mark.parametrize("cq, pv, lease", DAMPED)
@pytest.mark.parametrize("P", [3, 5])
def test_sender_loops_lower_straight_line(P, cq, pv, lease):
    jaxpr, text = faulted_step(P, cq, pv, lease)
    found = scans(jaxpr)
    assert all(s[1] == P for s in found), found
    unrolled = [s for s in found if s[2] == P]
    rolled = [s for s in found if s[2] == 1]
    assert len(unrolled) + len(rolled) == len(found), found
    # One `scan` equation per sender loop, none nested (traced once each),
    # every trip behind its barrier.
    top = unrolled[0][0]
    assert unrolled == [(top, P, P, "optimization_barrier")] * SENDER_LOOPS, found
    # The tallies: outer over candidates, inner over voters, still rolled.
    tallies = 2 if pv else 1
    assert sorted(s[0] - top for s in rolled) == [0] * tallies + [1] * tallies, found
    assert all(s[3] != "optimization_barrier" for s in rolled), found
    # The only `while`s left are the tallies' (9 at cq + pv before PR 41).
    assert len(re.findall(r"stablehlo\.while", text)) == 2 * tallies
    assert updates_of_planes(text, P) == []


@pytest.mark.parametrize("P", [3, 5])
def test_the_stock_fleets_loops_stay_rolled(P):
    """`_linked_step` is not PR 41's: six sender loops and the tally's inner
    one, each a rolled `scan` (the control cell's program is the parent's)."""
    jaxpr, text = faulted_step(P, False, False, False)
    found = scans(jaxpr)
    assert len(found) == 7 and all(s[1:3] == (P, 1) for s in found), found
    assert len(re.findall(r"stablehlo\.while", text)) == len(found)
    assert updates_of_planes(text, P) != []
