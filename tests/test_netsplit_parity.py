"""Check-quorum WITHOUT pre-vote under a rolling network split (ISSUE 44):
the deployment `fleet-100k-r5-cq` under the mix `netsplit`, at G = 64.

Each store in turn is healthy for 40 rounds, then cut off from the other
four but ALIVE for 60: it campaigns, raises its groups' terms, and on its
return its higher-term answer to the first heartbeat deposes a healthy
leader.  The whole fleet is held to `simref.ScalarCluster` (real scalar
Rafts of the port) every round, two segments with state carried over:

  (a) every plane the two sides share — term, role, vote, commit, last
      index, last term, the leaders' `recent_active` rows, the health
      planes — is equal every round;
  (b) the round the gap of ROADMAP C15 lived in is in the plan, and is
      asserted on by name: a candidate that wins in wave 2, commits its
      noop on the first acks of its wave-3 stream and is deposed by a
      higher-term member's answer later in the SAME stream has its commit
      re-broadcast in flight, and the followers take it;
  (c) the same plan on the both-flags fleet is the control: parity holds
      there too, no cut-off member's term moves, and the fleet without
      pre-vote reads strictly more term bumps — the test can tell the two
      fleets apart.

The Safe-read half (receipts and the audit's per-peer mask under this
plan) is tests/test_netsplit_reads.py, a file of its own so that the two
scalar replays run on two workers.
"""

import types

import jax.numpy as jnp
import numpy as np

from raft_tpu.multiraft import (
    ChaosOracle, ClusterSim, ScalarCluster, SimConfig, chaos, kernels,
)
from test_damping_parity import (
    assert_health_parity, assert_leader_ra_parity, assert_parity,
)

G, P = 64, 5
UP, CUT = 40, 60
ELECTION_TICK, HEARTBEAT_TICK = 20, 2  # the configuration's
SETTLE = 4 * ELECTION_TICK  # the configuration's boot_rounds
SEGMENT = P * (UP + CUT)
WINDOW = 8
# The scalar Progress windows: never filled here (a cut-off member misses 60
# appends a stretch), and 64 x 5 x 5 of the harness's default 1 << 20 are
# 13 GB of preallocated rings.
INFLIGHT = 1 << 14


def netsplit_plan(n_peers=P, segments=2, settle=SETTLE, append=1):
    """`benchmark/traffic/netsplit.json`'s chaos block as a plan of the
    program: settle, then `segments` replays of every store in turn 40 up /
    60 cut off but alive.  No crash anywhere."""
    segment = []
    for s in range(1, n_peers + 1):
        segment += [
            {"rounds": UP, "heal": True, "append": append},
            {"rounds": CUT, "partition": [[s]], "append": append},
        ]
    return chaos.plan_from_dict({
        "name": "netsplit",
        "peers": n_peers,
        "phases": [{"rounds": settle, "append": append}] + segment * segments,
    })


def cut_off_store(r):
    """The 0-based store that is cut off in round r of the plan, or None."""
    if r < SETTLE:
        return None
    s, at = divmod((r - SETTLE) % SEGMENT, UP + CUT)
    return s if at >= UP else None


def assert_vote_parity(scalar, st, r, note):
    got = np.asarray(st.vote).T
    want = np.array([
        [scalar.networks[g].peers[p + 1].raft.vote for p in range(P)]
        for g in range(G)
    ])
    assert np.array_equal(got, want), (
        f"{note} round {r}: vote differs at {np.argwhere(got != want)[:4].tolist()}")


class Lockstep:
    """One fleet on the device and its scalar twin, stepped through the
    plan with every shared plane compared every round."""

    def __init__(self, pre_vote, segments):
        self.note = f"netsplit cq{'+pv' if pre_vote else ''}"
        self.plan = netsplit_plan(segments=segments)
        self.sched = chaos.HostSchedule(self.plan, G)
        self.scalar = ScalarCluster(
            G, P, election_tick=ELECTION_TICK, heartbeat_tick=HEARTBEAT_TICK,
            check_quorum=True, pre_vote=pre_vote, max_inflight_msgs=INFLIGHT)
        self.oracle = ChaosOracle(self.scalar, schedule=self.sched, window=WINDOW)
        self.sim = ClusterSim(SimConfig(
            n_groups=G, n_peers=P, election_tick=ELECTION_TICK,
            heartbeat_tick=HEARTBEAT_TICK, check_quorum=True,
            pre_vote=pre_vote, collect_health=True, health_window=WINDOW))
        self.r = 0
        self.last = planes(self.sim.state)  # run_round donates the state

    def round(self):
        """One round on both sides, compared; returns the cursor planes
        (before, after)."""
        link, crashed, append = self.sched.masks(self.r)
        assert not crashed.any()  # the mix has no crash
        self.oracle.scheduled_round()
        self.sim.run_round(
            jnp.asarray(crashed), jnp.asarray(append, dtype=jnp.int32),
            link=jnp.asarray(link))
        assert_parity(self.scalar, self.sim, self.r, self.note)
        assert_vote_parity(self.scalar, self.sim.state, self.r, self.note)
        assert_health_parity(self.oracle, self.sim, self.r, self.note)
        assert_leader_ra_parity(self.scalar, self.sim, self.r, self.note)
        self.r += 1
        before, self.last = self.last, planes(self.sim.state)
        return before, self.last


def planes(st):
    """The cursor planes of a state on the host, [P, G] each."""
    return types.SimpleNamespace(**{
        k: np.asarray(getattr(st, k))
        for k in ("term", "state", "last_index", "last_term", "commit")})


def deposed_winners(b, a):
    """bool[P, G]: peers that won an election and lost the role again in
    ONE round and kept what they committed meanwhile — the noop at the new
    term is their last entry, committed, and they are followers at a term
    above it.  `b`, `a`: `planes` before and after the round."""
    return (
        (b.state != kernels.ROLE_LEADER)
        & (a.state == kernels.ROLE_FOLLOWER)
        & (a.last_index == b.last_index + 1)
        & (a.last_term == b.term + 1)
        & (a.term > a.last_term)
        & (a.commit == a.last_index)
    )


def test_netsplit_fleet_parity_two_segments():
    """(a) and (b): 1080 rounds in lockstep, every plane every round; the
    C15 round is met and the deposed winner's followers hold its commit."""
    ls = Lockstep(pre_vote=False, segments=2)
    term0 = None
    c15_rounds = c15_followers = 0
    cut_term_moves = 0
    for r in range(ls.plan.n_rounds):
        before, after = ls.round()
        if r == SETTLE - 1:
            term0 = after.term.max(axis=0)
        dw = deposed_winners(before, after)
        if dw.any():
            c15_rounds += 1
            commit, last = after.commit, after.last_index
            for p, g in np.argwhere(dw):
                # Whoever holds the winner's noop heard of its commit too:
                # the re-broadcast left before the deposing answer came.
                holds = (last[:, g] == last[p, g]) & (np.arange(P) != p)
                assert (commit[holds, g] == commit[p, g]).all(), (r, p, g)
                c15_followers += int(holds.sum())
        s = cut_off_store(r)
        if s is not None:
            cut_term_moves += int((after.term[s] > before.term[s]).sum())
    # The plan shows what it is for (a test that met no such round proved
    # nothing about one).
    assert c15_rounds > 0 and c15_followers > 0
    assert cut_term_moves > 0  # cut-off members campaign and raise terms
    bumps = int((np.asarray(ls.sim.state.term).max(axis=0) - term0).sum())
    # More than five terms a group a segment (12.8 on this seed); the
    # control's ceiling is two, so the two fleets' ranges are disjoint.
    assert bumps > 2 * 5 * G


def test_both_flags_control_tells_the_fleets_apart():
    """(c): the plan on the both-flags fleet, one segment — parity holds, no
    cut-off member's term moves while it is cut off, and the fleet's terms
    grow by the hand-overs alone."""
    ls = Lockstep(pre_vote=True, segments=1)
    term0 = None
    for r in range(ls.plan.n_rounds):
        before, after = ls.round()
        if r == SETTLE - 1:
            term0 = after.term.max(axis=0)
        s = cut_off_store(r)
        if s is not None:
            assert np.array_equal(after.term[s], before.term[s]), (
                f"round {r}: a cut-off member's term moved under pre-vote")
        assert not deposed_winners(before, after).any(), r
    bumps = int((np.asarray(ls.sim.state.term).max(axis=0) - term0).sum())
    # Each store's cut-off stretch costs the groups it led one hand-over
    # (1.7 terms a group on this seed); without pre-vote the test above
    # asserts more than five a group a segment: strictly higher.
    assert 0 < bumps <= 2 * G


def test_plan_is_the_mix():
    """The plan above is `netsplit.json`'s chaos block, phase for phase."""
    import json
    import os

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "benchmark", "traffic", "netsplit.json"),
              encoding="utf-8") as f:
        mix = json.load(f)
    up, cut = mix["chaos"]["for_each_peer"]
    assert set(mix["chaos"]) == {"for_each_peer"}  # no crash, no tail
    assert up == {"rounds": UP} and cut == {"rounds": CUT, "partition": [["@peer"]]}
    plan = netsplit_plan(segments=1)
    assert plan.n_rounds - SETTLE == SEGMENT == 500
    sched = chaos.HostSchedule(plan, 2)
    for r in range(SETTLE, plan.n_rounds):
        link, crashed, _ = sched.masks(r)
        s = cut_off_store(r)
        want = np.ones((P, P), bool)
        if s is not None:
            want[s, :] = want[:, s] = False
            want[s, s] = True
        assert not crashed.any()
        assert np.array_equal(link[:, :, 0] | np.eye(P, dtype=bool), want), r
