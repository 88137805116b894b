"""Damping on, lease reads off (ISSUE 40): check-quorum and pre-vote with
`ReadOnlyOption::Safe` — what TiKV's raftstore and etcd's server hand the
Raft library.  Every read is a ReadIndex round on the damped body, and the
linearizability audit holds EVERY peer whose damped ReadIndex gate passes
(`sim.read_quorum_damped_holders` -> `ReadReceipt.holders`), not only the
acting leader a client is routed to.

  (a) the per-peer mask equals `simref.ReadOracle.read_holders` — the real
      scalar Safe pump driven at every alive role-leader on a throwaway
      copy — round for round, lockstep state parity alongside, on states
      that hold a stale leader: a leader cut off but alive before and after
      its check-quorum boundary, a crashed leader returning beside its
      successor, a higher-term follower whose nudge deposes the leader
      mid-stream (check-quorum without pre-vote), a joint configuration
      with a learner, a singleton with learners;
  (b) its acting row is `_read_quorum_damped`'s answer on every such state,
      and the mask the step hands out (the cheap form: the per-peer gate
      only in rounds with an alive role-leader beside the acting one) is
      the full gate's, round for round, over a store-loss-shaped plan;
  (c) `ClusterSim.run_reads` on a small such fleet serves every read
      through the quorum round with every safety slot 0, and the audit can
      fail: with the gate weakened the two linearizability slots trip;
  (d) the lease fleets' and the stock fleet's receipts are what they were.

Every scenario is seeded, G <= 16, under 100 rounds.
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from raft_tpu.multiraft import ClusterSim, SimConfig, chaos, kernels, sim, workload
from test_read_lease import (
    assert_receipts, assert_state_parity, build_pair, settle,
)

LEADER = kernels.ROLE_LEADER


def gates_for(cfg):
    """(the full per-peer gate, the acting leader's probe), jitted."""
    return (
        jax.jit(functools.partial(sim.read_quorum_damped_holders, cfg)),
        jax.jit(functools.partial(sim._read_quorum_damped, cfg)),
    )


def acting_row(st, crashed, holders):
    """The acting leader's commit where its row of `holders` is set, -1
    elsewhere — how a probe is read off the mask (numpy, [P, G] planes)."""
    state, term, commit = (np.asarray(x) for x in (st.state, st.term, st.commit))
    G = state.shape[1]
    out = np.full(G, -1, np.int64)
    for g in range(G):
        leads = [p for p in range(state.shape[0])
                 if state[p, g] == LEADER and not crashed[p, g]]
        if leads:
            top = max(term[p, g] for p in leads)
            act = min(p for p in leads if term[p, g] == top)
            if holders[act, g]:
                out[g] = commit[act, g]
    return out


class Replay:
    """One damped ReadIndex fleet in lockstep with its scalar twin, every
    round's per-peer mask compared three ways."""

    def __init__(self, G, P, **build_kw):
        self.G, self.P = G, P
        self.oracle, self.cfg, self.st, self.step = build_pair(
            G, P, lease=False, **build_kw)
        self.full, self.probe = gates_for(self.cfg)
        self.st, _ = settle(self.oracle, self.st, self.step, G, P)
        self.seen = {"rounds": 0, "two_leaders": 0, "stale_refused": 0,
                     "acting_refused": 0, "held": 0}

    def leaders(self):
        snap = self.oracle.cluster.snapshot()
        return [int(np.argmax(snap["state"][g] == LEADER)) for g in range(self.G)]

    def round(self, crashed, link, append=1, tag=""):
        """crashed bool[G, P], link bool[P, P, G] (numpy)."""
        G, P = self.G, self.P
        want = np.array([
            self.oracle.read_holders(g, crashed[g], link[:, :, g])
            for g in range(G)
        ]).T  # [P, G]
        st0, cr, lk = self.st, jnp.asarray(crashed.T), jnp.asarray(link)
        modes = np.full(G, sim.READ_LEASE, np.int32)
        app = np.full(G, append, np.int64)
        self.st, receipt = self.step(
            st0, cr, jnp.asarray(app, jnp.int32), link=lk,
            read_propose=jnp.asarray(modes),
        )
        self.oracle.round(crashed, app, link=link, read_propose=modes)
        got = np.asarray(receipt.holders)
        assert got.shape == (P, G) and got.dtype == bool
        assert np.array_equal(got, want), (
            f"{tag}: the step's mask differs from the scalar pump's\n"
            f"{got.astype(int)}\n{want.astype(int)}")
        assert np.array_equal(np.asarray(self.full(st0, cr, lk)), want), (
            f"{tag}: the full gate differs from the scalar pump's")
        ri = np.asarray(self.probe(st0, cr, lk))
        assert np.array_equal(acting_row(st0, crashed.T, got), ri), tag
        assert_receipts(receipt, self.oracle.last_receipts, tag)
        assert not np.asarray(receipt.lease).any()
        assert np.array_equal(np.asarray(receipt.index), ri), tag
        # What the scenario showed (a test that saw no stale leader proved
        # nothing about one).
        alive_lead = (np.asarray(st0.state) == LEADER) & ~crashed.T
        n_lead = alive_lead.sum(axis=0)
        self.seen["rounds"] += 1
        self.seen["two_leaders"] += int((n_lead >= 2).sum())
        self.seen["stale_refused"] += int(((n_lead >= 2) & (got.sum(axis=0) <= 1)).sum())
        self.seen["acting_refused"] += int(((n_lead >= 1) & (ri < 0)).sum())
        self.seen["held"] += int(got.sum())
        assert (got.sum(axis=0) <= 1).all(), "two peers would answer one group"
        return got

    def end(self, tag):
        assert_state_parity(self.oracle, self.st, tag)
        assert self.seen["rounds"] < 100
        return self.seen


def all_up(G, P):
    return np.zeros((G, P), bool), np.ones((P, P, G), bool)


def cut(link, g, p):
    link[p, :, g] = False
    link[:, p, g] = False
    link[p, p, g] = True


def test_cut_off_leader_before_and_after_its_check_quorum_boundary():
    """Every group's leader is cut off but alive: it keeps its role until
    its check-quorum boundary while the majority elects past it — the
    stretch with two alive role-leaders — and is never a holder there; the
    successor is, once it has committed in its own term."""
    G, P = 8, 5
    rp = Replay(G, P, check_quorum=True, pre_vote=True)
    crashed, link = all_up(G, P)
    for g, lead in enumerate(rp.leaders()):
        cut(link, g, lead)
    for r in range(45):
        rp.round(crashed, link, tag=f"cut round {r}")
    crashed, link = all_up(G, P)
    for r in range(15):
        rp.round(crashed, link, tag=f"healed round {r}")
    seen = rp.end("cut-off leader")
    assert seen["two_leaders"] > 0 and seen["stale_refused"] == seen["two_leaders"]
    assert seen["acting_refused"] > 0 and seen["held"] > 0


def test_crashed_leader_returning_beside_its_successor():
    """Crash each leader for a seeded 11-19 rounds (its timers run while it
    is down): where it is back before its boundary it is an alive
    role-leader at a lower term beside the new one, and its gate is held
    shut by the first higher-term member's nudge."""
    G, P = 8, 5
    rp = Replay(G, P, check_quorum=True, pre_vote=True)
    rng = np.random.RandomState(40)
    leads = rp.leaders()
    down = rng.randint(11, 20, size=G)
    _, link = all_up(G, P)
    for r in range(40):
        crashed = np.zeros((G, P), bool)
        for g in range(G):
            crashed[g, leads[g]] = r < down[g]
        rp.round(crashed, link, tag=f"return round {r}")
    seen = rp.end("returning leader")
    assert seen["two_leaders"] > 0 and seen["stale_refused"] == seen["two_leaders"]


def test_higher_term_follower_nudges_mid_stream():
    """Check-quorum WITHOUT pre-vote: a cut-off follower campaigns and
    raises its term; healed, its answer to the ctx heartbeat is the nudge
    that deposes the leader — before the ack quorum where its peer id
    comes early in the stream, after it where it comes late."""
    G, P = 8, 5
    rp = Replay(G, P, check_quorum=True, pre_vote=False)
    crashed, link = all_up(G, P)
    leads = rp.leaders()
    for g in range(G):
        followers = [p for p in range(P) if p != leads[g]]
        cut(link, g, followers[g % len(followers)])
    for r in range(28):
        rp.round(crashed, link, tag=f"follower cut round {r}")
    crashed, link = all_up(G, P)
    served = rp.round(crashed, link, tag="heal round")
    # Both outcomes of the stream order are in the sample.
    assert 0 < served.sum() < G
    # Forty rounds on, through the returned member's next campaigns: a
    # winner of wave 2 whose noop commits on the first acks and whose
    # later responses depose it still gets its commit to the followers
    # (the wave-5 re-broadcast was in flight; ROADMAP C15, closed by
    # ISSUE 44 — before it the commit plane left the scalar replay's from
    # the twelfth round after the heal).
    for r in range(40):
        rp.round(crashed, link, tag=f"after heal {r}")
        assert_state_parity(rp.oracle, rp.st, f"after heal {r}")
    rp.end("nudging follower")


def test_joint_configuration_with_a_learner():
    """Incoming {1,2,3}, outgoing {2,3,4}, learner 5: both majorities must
    acknowledge, a learner's response counts for neither, and a cut-off
    leader is refused as above."""
    G, P = 4, 5
    rp = Replay(G, P, check_quorum=True, pre_vote=True,
                voters=[1, 2, 3], outgoing=[2, 3, 4], learners=[5])
    crashed, link = all_up(G, P)
    # One half's majority gone (peers 3 and 4 down leave outgoing {2}).
    crashed[:, 2] = crashed[:, 3] = True
    for r in range(6):
        rp.round(crashed, link, tag=f"joint minority round {r}")
    crashed, link = all_up(G, P)
    for r in range(4):
        rp.round(crashed, link, tag=f"joint back round {r}")
    for g, lead in enumerate(rp.leaders()):
        cut(link, g, lead)
    for r in range(40):
        rp.round(crashed, link, tag=f"joint cut round {r}")
    seen = rp.end("joint")
    assert seen["acting_refused"] > 0 and seen["held"] > 0


def test_singleton_with_learners_answers_without_heartbeats():
    G, P = 4, 3
    rp = Replay(G, P, check_quorum=True, pre_vote=True, voters=[1], learners=[2, 3])
    crashed, link = all_up(G, P)
    for g in range(G):
        cut(link, g, 0)  # nobody hears the one voter; it needs nobody
    for r in range(12):
        got = rp.round(crashed, link, tag=f"singleton round {r}")
        assert got[0].all() and not got[1:].any()
    rp.end("singleton")


# --- the cheap form against the full form, and the served path ----------------

G16, P5 = 16, 5


def readindex_cfg(n_groups=G16, **kw):
    kw = {"check_quorum": True, "pre_vote": True, "lease_read": False, **kw}
    return SimConfig(n_groups, P5, election_tick=10, heartbeat_tick=2,
                     collect_health=True, **kw)


STORE_LOSS = {  # the benchmark's `outage` mix in small: two stores lost in
    "name": "store-loss-small", "peers": P5,  # turn, then store 3 cut off but alive
    "phases": [
        {"rounds": 5}, {"rounds": 25, "crash": [1]},
        {"rounds": 5}, {"rounds": 25, "crash": [2]},
        {"rounds": 5}, {"rounds": 30, "partition": [[3]]},
    ],
}


def client_plan(rounds, mode="lease"):
    return workload.plan_from_dict({
        "name": "reads", "peers": P5, "seed": 40,
        "phases": [{"rounds": rounds, "append": 1, "read_every": 1, "read_mode": mode}],
    })


def test_cheap_form_is_the_full_gate_round_for_round():
    """The step computes the per-peer gate only in rounds in which some
    group has an alive role-leader beside its acting leader; in every other
    round the mask is the acting leader's row of the probe.  Same mask, on
    every round of a store-loss-shaped plan — and both kinds of round are
    in the plan."""
    cfg = readindex_cfg()
    host = chaos.HostSchedule(chaos.plan_from_dict(STORE_LOSS), G16)
    step = jax.jit(functools.partial(sim.step, cfg))
    full, probe = gates_for(cfg)
    sim0 = ClusterSim(cfg)
    sim0.run_compiled(40)
    st = sim0.state
    modes = jnp.full((G16,), sim.READ_SAFE, jnp.int32)
    app = jnp.ones((G16,), jnp.int32)
    full_rounds = 0
    for r in range(host.n_rounds):
        link_r, crashed_r, _ = host.masks(r)
        crashed, link = jnp.asarray(crashed_r), jnp.asarray(link_r)
        want = np.asarray(full(st, crashed, link))
        ri = np.asarray(probe(st, crashed, link))
        alive_lead = (np.asarray(st.state) == LEADER) & ~crashed_r
        full_rounds += bool((alive_lead.sum(axis=0) >= 2).any())
        assert np.array_equal(acting_row(st, crashed_r, want), ri), f"round {r}"
        st, receipt = step(st, crashed, app, link=link, read_propose=modes)
        assert np.array_equal(np.asarray(receipt.holders), want), f"round {r}"
        assert np.array_equal(np.asarray(receipt.index), ri), f"round {r}"
    assert host.n_rounds < 100
    assert 0 < full_rounds < host.n_rounds // 2, full_rounds


@pytest.fixture(scope="module")
def served():
    cfg = readindex_cfg()
    sim0 = ClusterSim(cfg)
    sim0.run_compiled(40)
    sim0.reset_health()
    report = sim0.run_reads(client_plan(95), chaos.plan_from_dict(STORE_LOSS))
    return report


def test_run_reads_serves_every_read_through_the_quorum_round(served):
    assert served["served_lease"] == 0
    assert served["served_quorum"] > 0
    assert served["degraded_serves"] == served["served_quorum"]
    assert served["retry_group_rounds"] > 0, "a read waits while a store is lost"
    assert len(served["safety"]) == 9 and set(served["safety"].values()) == {0}


def test_a_weakened_gate_trips_the_linearizability_audit(served, monkeypatch):
    """The audit can fail: with every alive leader that has committed in its
    own term let through (the benchmark's control), the cut-off store's
    leaders answer beside their successors."""
    monkeypatch.setattr(
        sim, "_acks_before_nudge",
        lambda st, ack_v, ndg_v, cnt_i, cnt_o, h: jnp.ones(cnt_i.shape, bool))
    cfg = readindex_cfg()
    sim0 = ClusterSim(cfg)
    sim0.run_compiled(40)
    sim0.reset_health()
    # Another plan name: the sound program's runner is cached per plan.
    weak = sim0.run_reads(client_plan(95), chaos.plan_from_dict(
        {**STORE_LOSS, "name": "store-loss-small-weak"}))
    assert weak["safety"]["dual_lease"] > 0
    assert sum(weak["safety"].values()) == (
        weak["safety"]["dual_lease"] + weak["safety"]["stale_read"])
    assert weak["served_quorum"] >= served["served_quorum"]


@pytest.mark.parametrize("flags", [
    dict(check_quorum=True, pre_vote=True, lease_read=True),
    dict(check_quorum=True, pre_vote=False, lease_read=True),
])
def test_lease_fleets_receipts_carry_no_mask_and_the_old_probe(flags):
    """With lease reads on nothing changed: no `holders`, and a degraded
    read's index is `_read_quorum_damped`'s."""
    cfg = readindex_cfg(8, **flags)
    step = jax.jit(functools.partial(sim.step, cfg))
    _, probe = gates_for(cfg)
    st = sim.init_state(cfg)
    link = jnp.ones((P5, P5, 8), bool)
    crashed = jnp.zeros((P5, 8), bool)
    app = jnp.ones((8,), jnp.int32)
    for r in range(40):
        if r == 30:
            crashed = crashed.at[0].set(True)
        ri = np.asarray(probe(st, crashed, link))
        st, receipt = step(st, crashed, app, link=link,
                           read_propose=jnp.full((8,), sim.READ_SAFE, jnp.int32))
        assert receipt.holders is None
        assert np.array_equal(np.asarray(receipt.index), ri), f"round {r}"
    assert (ri >= 0).any()
