"""Learners that stay, on the DAMPED body (ISSUE 47): the deployment
`fleet-100k-r3l2` — voters {1, 2, 3} on the TiKV stores, learners {4, 5} on
the TiFlash stores, check-quorum + pre-vote as the configuration has them —
under the accepted mix `outage`, at G = 32.

The mix's chaos block is read from `benchmark/traffic/outage.json` itself and
run as a plan of the program: every store in turn 40 rounds up and 60
crashed, then store 1 cut off but alive for 60.  Two of the five store losses
take NO voter.  The whole fleet is held to `simref.ScalarCluster` (real scalar
Rafts of the port, booted from `ConfState(voters, learners)`) every round,
two segments with state carried over:

  (a) every plane the two sides share — term, role, vote, commit, last
      index, last term, the leaders' `recent_active` rows, the health
      planes — is equal every round;
  (b) asserted BY NAME, on the device's own planes: no learner is ever a
      candidate, a pre-candidate or a leader; while a learner store is down no
      group's leader or term changes and every group's commit advances in
      every round (each offers an append); a returned learner holds its
      leader's commit within CATCH_UP rounds; while a voter store is down a
      commit moves only over entries BOTH surviving voters hold;
  (c) the control that shows the test can tell: the same plan with slots 4
      and 5 as VOTERS loses leaders and stalls commits in the stretches in
      which the learner fleet loses and stalls nothing.

The lease-read half (receipts, the lease on the voters' acknowledgements
alone, `learner_behind_group_rounds` against the scalar side) is
tests/test_learner_fleet_reads.py, a file of its own so that the two scalar
replays run on two workers.
"""

import json
import os

import jax.numpy as jnp
import numpy as np

from benchmark import traffic
from raft_tpu.multiraft import (
    ChaosOracle, ClusterSim, ScalarCluster, SimConfig, chaos, kernels,
)
from test_damping_parity import (
    assert_health_parity, assert_leader_ra_parity, assert_parity,
)
from test_netsplit_parity import INFLIGHT, planes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs", "fleet-100k-r3l2.json"),
          encoding="utf-8") as _f:
    CONFIG = json.load(_f)

G, P = 32, CONFIG["n_peers"]
VOTERS, LEARNERS = CONFIG["voters"], CONFIG["learners"]
ELECTION_TICK, HEARTBEAT_TICK = CONFIG["election_tick"], CONFIG["heartbeat_tick"]
SETTLE = CONFIG["boot_rounds"]
UP, DOWN = 40, 60
SEGMENT = (P + 1) * (UP + DOWN)
WINDOW = 8
# A returned learner is caught up by the first heartbeat that reaches it (its
# answer un-pauses the leader's probe, and with no message delay the reject,
# the append and the commit follow inside that round).
CATCH_UP = HEARTBEAT_TICK


def outage_plan(n_peers=P, segments=2, settle=SETTLE, append=1, append_up=None):
    """`benchmark/traffic/outage.json`'s chaos block, through the benchmark's
    own `traffic.chaos_document`, as a plan of the program: settle, then
    `segments` replays of it, `append` entries a group a round throughout
    (`append_up` in the settle and the healthy stretches, where given)."""
    append_up = append if append_up is None else append_up
    segment = traffic.chaos_document(traffic.load_mix("outage"), n_peers, "outage")["phases"]
    return chaos.plan_from_dict({
        "name": "outage",
        "peers": n_peers,
        "phases": [{"rounds": settle, "append": append_up}]
        + [{**ph, "append": append if len(ph) > 1 else append_up} for ph in segment] * segments,
    })


def stretch(r):
    """(kind, store, rounds into it) of round r of the plan: kind is "boot",
    "up", "down" (0-based `store` crashed) or "cut" (store 0 cut off, alive)."""
    if r < SETTLE:
        return "boot", None, r
    s, at = divmod((r - SETTLE) % SEGMENT, UP + DOWN)
    if at < UP:
        return "up", s, at
    return ("down", s, at - UP) if s < P else ("cut", 0, at - UP)


def masks(voters, learners, n_groups=G, n_peers=P):
    vm = np.zeros((n_peers, n_groups), bool)
    lm = np.zeros((n_peers, n_groups), bool)
    vm[[v - 1 for v in voters]] = True
    lm[[m - 1 for m in learners]] = True
    return vm, lm


def assert_vote_parity(scalar, st, r, note):
    got = np.asarray(st.vote).T
    want = np.array([
        [net.peers[p + 1].raft.vote for p in range(scalar.n_peers)]
        for net in scalar.networks
    ])
    assert np.array_equal(got, want), (
        f"{note} round {r}: vote differs at {np.argwhere(got != want)[:4].tolist()}")


def acting_leader(pl, crashed):
    """int[G]: 0-based acting leader of each group (-1: none alive) — the
    alive leader of the highest term, `kernels.acting_leader_id` in numpy."""
    is_lead = (pl.state == kernels.ROLE_LEADER) & ~crashed
    best = np.where(is_lead, pl.term, -1)
    return np.where(is_lead.any(axis=0), best.argmax(axis=0), -1)


class Fleet:
    """The plan on the device, round by round — and, with `scalar=True`, its
    scalar twin beside it with every shared plane compared every round."""

    def __init__(self, voters, learners, segments, scalar=True):
        self.note = f"outage voters {voters} learners {learners}"
        self.plan = outage_plan(segments=segments)
        self.sched = chaos.HostSchedule(self.plan, G)
        vm, lm = masks(voters, learners)
        self.sim = ClusterSim(
            SimConfig(
                n_groups=G, n_peers=P, election_tick=ELECTION_TICK,
                heartbeat_tick=HEARTBEAT_TICK,
                check_quorum=CONFIG["check_quorum"], pre_vote=CONFIG["pre_vote"],
                collect_health=True, health_window=WINDOW),
            jnp.asarray(vm), None, jnp.asarray(lm))
        self.scalar = self.oracle = None
        if scalar:
            self.scalar = ScalarCluster(
                G, P, election_tick=ELECTION_TICK, heartbeat_tick=HEARTBEAT_TICK,
                voters=voters, learners=learners,
                check_quorum=CONFIG["check_quorum"], pre_vote=CONFIG["pre_vote"],
                max_inflight_msgs=INFLIGHT)
            self.oracle = ChaosOracle(self.scalar, schedule=self.sched, window=WINDOW)
        self.r = 0
        self.last = planes(self.sim.state)  # run_round donates the state

    def round(self):
        """One round; returns (planes before, planes after, crashed[P, G])."""
        link, crashed, append = self.sched.masks(self.r)
        self.sim.run_round(
            jnp.asarray(crashed), jnp.asarray(append, dtype=jnp.int32),
            link=jnp.asarray(link))
        if self.scalar is not None:
            self.oracle.scheduled_round()
            assert_parity(self.scalar, self.sim, self.r, self.note)
            assert_vote_parity(self.scalar, self.sim.state, self.r, self.note)
            assert_health_parity(self.oracle, self.sim, self.r, self.note)
            assert_leader_ra_parity(self.scalar, self.sim, self.r, self.note)
        self.r += 1
        before, self.last = self.last, planes(self.sim.state)
        return before, self.last, crashed


def lost_and_stalled(fleet, stores):
    """Over the rounds in which one of `stores` (0-based) is down: (groups
    whose acting leader sat on the store when it went, (group, round) pairs
    in which no member's commit advanced)."""
    lost = stalled = 0
    for r in range(fleet.plan.n_rounds):
        kind, s, at = stretch(r)
        before, after, crashed = fleet.round()
        if kind == "down" and s in stores:
            if at == 0:
                lost += int((acting_leader(before, np.zeros_like(crashed)) == s).sum())
            stalled += int((after.commit.max(axis=0) <= before.commit.max(axis=0)).sum())
    return lost, stalled


def test_learner_fleet_parity_two_segments():
    """(a) and (b): 1280 rounds in lockstep, every plane every round, and the
    learners' own guarantees on the device's planes."""
    fleet = Fleet(VOTERS, LEARNERS, segments=2)
    learner_rows = [m - 1 for m in LEARNERS]
    voter_rows = [v - 1 for v in VOTERS]
    seen = {"learner_down_rounds": 0, "returns": 0, "catch_up_max": 0,
            "two_voter_commits": 0, "elections_in_voter_stretches": 0}
    waiting = {}  # learner row -> (rounds since it came back, groups still behind)
    for r in range(fleet.plan.n_rounds):
        kind, s, at = stretch(r)
        before, after, crashed = fleet.round()
        tag = f"round {r} ({kind} store {s}, +{at})"
        # No learner is ever a candidate, a pre-candidate or a leader.
        assert (after.state[learner_rows] == kernels.ROLE_FOLLOWER).all(), tag
        lead = acting_leader(after, crashed)
        lead_commit = np.where(lead >= 0, after.commit[np.maximum(lead, 0), np.arange(G)], -1)
        if kind == "down" and s in learner_rows:
            # A TiFlash store is down: no leader moves, no term rises, and
            # every group commits the round's append.
            seen["learner_down_rounds"] += 1
            assert np.array_equal(lead, acting_leader(before, crashed)) and (lead >= 0).all(), tag
            assert np.array_equal(after.term.max(axis=0), before.term.max(axis=0)), tag
            assert (after.commit.max(axis=0) > before.commit.max(axis=0)).all(), tag
        if kind == "up" and at == 0 and s > 0 and (s - 1) in learner_rows:
            waiting[s - 1] = 0  # store s - 1 came back this round
            seen["returns"] += 1
        for m in list(waiting):
            behind = (lead >= 0) & (after.commit[m] < lead_commit)
            waiting[m] += 1
            if not behind.any():
                seen["catch_up_max"] = max(seen["catch_up_max"], waiting.pop(m))
            else:
                assert waiting[m] < CATCH_UP, (
                    f"{tag}: learner {m + 1} still behind its leader in groups "
                    f"{np.flatnonzero(behind).tolist()} {waiting[m]} rounds after its return")
        if kind == "down" and s in voter_rows:
            # Two of three voters are left: a commit moves only over entries
            # BOTH hold, whatever the learners hold.
            survivors = [v for v in voter_rows if v != s]
            moved = after.commit.max(axis=0) > before.commit.max(axis=0)
            both_hold = after.last_index[survivors].min(axis=0)
            assert (after.commit.max(axis=0)[moved] <= both_hold[moved]).all(), tag
            assert np.isin(lead[lead >= 0], survivors).all(), tag
            seen["two_voter_commits"] += int(moved.sum())
            seen["elections_in_voter_stretches"] += int(
                (after.term.max(axis=0) > before.term.max(axis=0)).sum())
    # The plan showed what it is for.
    assert seen["learner_down_rounds"] == 2 * len(LEARNERS) * DOWN
    assert seen["returns"] == 2 * len(LEARNERS) and not waiting
    assert 1 <= seen["catch_up_max"] <= CATCH_UP
    assert seen["two_voter_commits"] > 0 and seen["elections_in_voter_stretches"] > 0, seen


def test_all_voter_control_tells_the_fleets_apart():
    """(c): the same plan, one segment, device alone.  With slots 4 and 5 as
    voters a fifth of the leaders sits on each, so their stores' losses cost
    leaders and stall commits; as learners they cost nothing."""
    learner_stores = [m - 1 for m in LEARNERS]
    lost, stalled = lost_and_stalled(
        Fleet(VOTERS, LEARNERS, segments=1, scalar=False), learner_stores)
    assert (lost, stalled) == (0, 0)
    lost5, stalled5 = lost_and_stalled(
        Fleet(VOTERS + LEARNERS, [], segments=1, scalar=False), learner_stores)
    assert lost5 > 0 and stalled5 > 0


def test_plan_is_the_mix():
    """The plan above is the accepted `outage.json`'s chaos block, phase for
    phase, and the fleet is the configuration file's."""
    mix = traffic.load_mix("outage")
    up, down = mix["chaos"]["for_each_peer"]
    assert up == {"rounds": UP} and down == {"rounds": DOWN, "crash": ["@peer"]}
    assert mix["chaos"]["then"] == [{"rounds": UP}, {"rounds": DOWN, "partition": [[1]]}]
    plan = outage_plan(segments=1)
    assert plan.n_rounds - SETTLE == SEGMENT == 600
    sched = chaos.HostSchedule(plan, 2)
    for r in range(plan.n_rounds):
        link, crashed, append = sched.masks(r)
        kind, s, _at = stretch(r)
        want_crash = np.zeros(P, bool)
        want_link = np.ones((P, P), bool)
        if kind == "down":
            want_crash[s] = True
        if kind == "cut":
            want_link[0, :] = want_link[:, 0] = False
        assert np.array_equal(crashed[:, 0], want_crash), r
        up_links = link[:, :, 0] | np.eye(P, dtype=bool)
        alive = ~want_crash
        assert np.array_equal(
            up_links[np.ix_(alive, alive)],
            (want_link | np.eye(P, dtype=bool))[np.ix_(alive, alive)]), r
        assert (append == 1).all()
    assert (VOTERS, LEARNERS) == ([1, 2, 3], [4, 5])
    assert (CONFIG["check_quorum"], CONFIG["pre_vote"], CONFIG["lease_read"]) == (True, True, True)
