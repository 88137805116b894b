"""The lease-read half of tests/test_learner_fleet_parity.py (ISSUE 47):
`fleet-100k-r3l2` — voters {1, 2, 3}, learners {4, 5}, check-quorum,
pre-vote and lease reads on — under the accepted mix `outage` at G = 32 WITH
the mix's client (`benchmark.traffic.generate`: YCSB-B's seeded lease reads)
and one append a group a round while a store is down or cut off, two
segments with state carried over.

Lockstep, every round: the five cursor planes of the whole fleet equal
`simref.ScalarCluster`'s, every group's receipt (index, lease, degraded)
equals `simref.ReadOracle`'s real pumps, and by name —

  * a lease rests on the VOTERS' acknowledgements alone: whoever serves
    under a lease holds a majority of the three voters in its
    `recent_active` row, whatever the learners' flags say;
  * no learner holds a lease (`kernels.lease_read`'s mask, the audit's);
  * the cut-off leader of the last stretch stops serving at its
    check-quorum boundary, under one election timeout.

Then the SAME schedules through `ClusterSim.run_reads` — the scan the cell
runs — segment by segment: every count of its report equals the lockstep's
(the read counts by the runner's own bookkeeping replayed on the host), and
`learner_behind_group_rounds` equals the count taken off the SCALAR fleet
round by round.  A fleet that boots without learners reports no such count.

And the directed round the plan cannot show: with two of three voters gone
a leader that still hears BOTH learners has no lease, where the same five
slots as voters keep it.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import traffic
from raft_tpu.multiraft import (
    ClusterSim, ScalarCluster, SimConfig, chaos, kernels, sim, workload,
)
from raft_tpu.multiraft.simref import ReadOracle, host_unpack_bits_g
from test_learner_fleet_parity import (
    CONFIG, DOWN, ELECTION_TICK, HEARTBEAT_TICK, LEARNERS, P, SEGMENT, SETTLE,
    VOTERS, acting_leader, masks, outage_plan, planes, stretch,
)
from test_netsplit_parity import INFLIGHT
from test_read_lease import assert_receipts, assert_state_parity

G = 32
SEED = 2**31 + 47
COUNTS = ("reads_issued", "served_lease", "served_quorum", "degraded_serves",
          "retry_group_rounds", "dropped_fires", "leaderless_group_rounds",
          "learner_behind_group_rounds")


def sim_config(n_groups=G):
    return SimConfig(
        n_groups=n_groups, n_peers=P, election_tick=ELECTION_TICK,
        heartbeat_tick=HEARTBEAT_TICK, check_quorum=CONFIG["check_quorum"],
        pre_vote=CONFIG["pre_vote"], lease_read=CONFIG["lease_read"],
        collect_health=True)


@functools.lru_cache(maxsize=None)
def step_for(n_groups):
    """`sim.step` of the configuration at `n_groups`, compiled once."""
    return jax.jit(functools.partial(sim.step, sim_config(n_groups)))


def read_oracle(voters, learners, n_groups=G):
    scalar = ScalarCluster(
        n_groups, P, election_tick=ELECTION_TICK, heartbeat_tick=HEARTBEAT_TICK,
        voters=voters, learners=learners, check_quorum=CONFIG["check_quorum"],
        pre_vote=CONFIG["pre_vote"], max_inflight_msgs=INFLIGHT)
    return ReadOracle(scalar, election_tick=ELECTION_TICK, lease_read=CONFIG["lease_read"])


@jax.jit
def lease_holders(st, crashed):
    """bool[P, G]: the peers that hold a read lease now — the mask the
    runner hands the linearizability audit (runner._runner_body)."""
    return kernels.lease_read(
        st.state, st.term, st.leader_id, st.election_elapsed, st.commit,
        st.term_start_index, crashed, ELECTION_TICK, True, st.transferee,
        st.recent_active, st.voter_mask, st.outgoing_mask)[0]


def scalar_learners_behind(scalar, crashed):
    """Groups in which a learner's commit is below its acting leader's — the
    report's `learner_behind_group_rounds`, one round of it, off the port."""
    n = 0
    for g, net in enumerate(scalar.networks):
        lead = scalar.acting_leader(g, crashed[:, g])
        if lead is not None:
            commit = net.peers[lead].raft.raft_log.committed
            n += any(net.peers[m].raft.raft_log.committed < commit for m in LEARNERS)
    return n


def test_learner_fleet_lease_reads_two_segments():
    cfg = sim_config()
    seg = traffic.generate(traffic.load_mix("outage"), G, P, SEED, "outage",
                           voters=VOTERS, learners=LEARNERS)
    assert seg.n_rounds == SEGMENT and not seg.append.any()  # the mix's writes round to 0 at this G
    fires = host_unpack_bits_g(seg.read_fire_packed, G)  # bool[R, G]
    # Appends where a learner can fall behind (a store down or cut off), none
    # in between: the scalar read pumps deep-copy a group's logs.
    plan = outage_plan(segments=2, append_up=0)
    sched = chaos.HostSchedule(plan, G)
    oracle = read_oracle(VOTERS, LEARNERS)
    step = step_for(G)
    vm, lm = masks(VOTERS, LEARNERS, G)
    st = sim.init_state(cfg, jnp.asarray(vm), None, jnp.asarray(lm))
    voter_rows, learner_rows = [v - 1 for v in VOTERS], [m - 1 for m in LEARNERS]
    pending = np.zeros(G, np.int32)
    want = [dict.fromkeys(COUNTS, 0) for _ in range(2)]
    seen = {"lease_serves": 0, "cut_leader_serves": 0, "refused_with_leader": 0}
    for r in range(plan.n_rounds):
        link, crashed, append = sched.masks(r)
        kind, s, at = stretch(r)
        at_seg, in_seg = divmod(r - SETTLE, SEGMENT) if r >= SETTLE else (None, None)
        if in_seg == 0:
            pending[:] = 0  # run_reads starts a call with no read in flight
        # The runner's bookkeeping (runner._runner_body), on the host.
        fire = fires[in_seg] & (seg.read_mode[seg.phase_of_round[in_seg]] > 0) if r >= SETTLE \
            else np.zeros(G, bool)
        fresh, dropped = fire & (pending == 0), fire & (pending > 0)
        pmode = np.where(fresh, sim.READ_LEASE, pending).astype(np.int32)
        entry = planes(st)
        ra = np.asarray(st.recent_active)
        holder = lease_holders(st, jnp.asarray(crashed))
        st, receipt = step(
            st, jnp.asarray(crashed), jnp.asarray(append, jnp.int32),
            link=jnp.asarray(link), read_propose=jnp.asarray(pmode))
        oracle.round(crashed.T, append, link=link, read_propose=pmode)
        tag = f"round {r} ({kind} store {s}, +{at})"
        assert_state_parity(oracle, st, tag)
        assert_receipts(receipt, oracle.last_receipts, tag)
        index, lease = np.asarray(receipt.index), np.asarray(receipt.lease)
        served = (index >= 0) & (pmode > 0)
        # No learner holds a lease, and whoever serves under one has a
        # majority of the VOTERS in its row (itself among them).
        assert not np.asarray(holder)[learner_rows].any(), tag
        lead = acting_leader(entry, crashed)
        for g in np.flatnonzero(served & lease):
            acks = sum(bool(ra[lead[g], v, g]) or v == lead[g] for v in voter_rows)
            assert lead[g] in voter_rows and 2 * acks > len(voter_rows), (tag, g)
        seen["lease_serves"] += int((served & lease).sum())
        seen["refused_with_leader"] += int(((pmode > 0) & ~served & (lead >= 0)).sum())
        if kind == "cut":
            by_cut_leader = served & lease & (lead == 0)
            # The cut-off leader serves on the acknowledgements it had, and
            # not past its next check-quorum boundary.
            assert not by_cut_leader.any() or at < ELECTION_TICK, tag
            seen["cut_leader_serves"] += int(by_cut_leader.sum())
        if at_seg is not None:
            w = want[at_seg]
            w["reads_issued"] += int(fresh.sum())
            w["served_lease"] += int((served & lease).sum())
            w["served_quorum"] += int((served & ~lease).sum())
            w["degraded_serves"] += int((served & np.asarray(receipt.degraded)).sum())
            w["retry_group_rounds"] += int(((pmode > 0) & ~served).sum())
            w["dropped_fires"] += int(dropped.sum())
            w["leaderless_group_rounds"] += int((oracle.planes[kernels.HP_LEADERLESS] > 0).sum())
            w["learner_behind_group_rounds"] += scalar_learners_behind(oracle.cluster, crashed)
        pending = np.where(served, 0, pmode)
    assert seen["lease_serves"] > 0 and seen["cut_leader_serves"] > 0
    assert seen["refused_with_leader"] > 0
    # A learner store's 60 rounds away, twice a segment, in every group: the
    # lag the count is for.  (A returned learner is level again in the round
    # that reaches it, so the catch-up adds at most a round a return.)
    for w in want:
        assert 2 * DOWN * G <= w["learner_behind_group_rounds"] <= 2 * (DOWN + 1) * G

    # The same schedules through run_reads, the scan the cell runs.
    fleet = ClusterSim(cfg, jnp.asarray(vm), None, jnp.asarray(lm))
    fleet.run_compiled(SETTLE)
    client = workload.CompiledClient(
        phase_of_round=jnp.asarray(seg.phase_of_round, jnp.int32),
        read_fire_packed=jnp.asarray(seg.read_fire_packed, jnp.uint32),
        read_mode=jnp.asarray(seg.read_mode, jnp.int32),
        append=jnp.asarray(seg.append, jnp.int32), n_peers=P)
    segment_plan = chaos.ChaosPlan("outage", P, plan.phases[1:1 + 2 * (P + 1)])
    assert segment_plan.n_rounds == SEGMENT
    for w in want:
        report = fleet.run_reads(client, segment_plan)
        assert {k: report[k] for k in COUNTS} == w
        assert not any(report["safety"].values()), report["safety"]
    assert_state_parity(oracle, fleet.state, "after two run_reads segments")


def test_only_a_fleet_that_boots_learners_reports_the_lag():
    """Known when the fleet is built, from its boot masks: a fleet without a
    learner hands `run_reads` a plain read carry (tests/test_round_map.py
    shows that round holds no `runner.learner_lag`), and its report has no
    such count."""
    cfg = sim_config(8)
    vm, lm = masks(VOTERS, LEARNERS, 8)
    assert ClusterSim(cfg, jnp.asarray(vm), None, jnp.asarray(lm))._boots_learners
    assert not ClusterSim(cfg)._boots_learners
    assert not ClusterSim(cfg, jnp.asarray(vm), None, jnp.zeros_like(lm))._boots_learners
    zeros = lambda n: np.zeros(n, np.int32)  # noqa: E731
    stats = (zeros(workload.N_READ_STATS), zeros(3), zeros(kernels.N_SAFETY),
             zeros(chaos.N_CHAOS_STATS), 600)
    assert "learner_behind_group_rounds" not in workload.read_report(*stats)
    assert workload.read_report(*stats, learner_behind=np.int32(7))[
        "learner_behind_group_rounds"] == 7


def lease_with_two_voters_gone(voters, learners, n=16):
    """Boot, then stores 2 and 3 are down for two election timeouts and every
    group asks for a lease read every round, receipts held to the scalar
    pumps.  Over the (group, round) pairs whose acting leader sits on store 1
    and holds both store 4's and store 5's acknowledgement in its row:
    (lease serves, refusals)."""
    cfg = sim_config(n)
    oracle = read_oracle(voters, learners, n)
    step = step_for(n)
    vm, lm = masks(voters, learners, n)
    st = sim.init_state(cfg, jnp.asarray(vm), None, jnp.asarray(lm))
    link = np.ones((P, P, n), bool)
    append = np.ones(n, np.int32)
    served = refused = 0
    for r in range(SETTLE + 2 * ELECTION_TICK):
        crashed = np.zeros((P, n), bool)
        crashed[1:3] = r >= SETTLE
        modes = np.full(n, sim.READ_LEASE if r >= SETTLE else sim.READ_NONE, np.int32)
        entry, ra = planes(st), np.asarray(st.recent_active)
        st, receipt = step(st, jnp.asarray(crashed), jnp.asarray(append),
                           link=jnp.asarray(link), read_propose=jnp.asarray(modes))
        oracle.round(crashed.T, append, link=link, read_propose=modes)
        assert_state_parity(oracle, st, f"round {r}")
        assert_receipts(receipt, oracle.last_receipts, f"round {r}")
        if r >= SETTLE:
            hears_both = (acting_leader(entry, crashed) == 0) & ra[0, 3] & ra[0, 4]
            lease = np.asarray(receipt.lease) & (np.asarray(receipt.index) >= 0)
            served += int((hears_both & lease).sum())
            refused += int((hears_both & ~lease).sum())
    return served, refused


def test_a_lease_rests_on_voters_not_on_learners():
    """Stores 2 and 3 down, a leader on store 1 alive with stores 4 and 5
    answering every heartbeat.  As LEARNERS they are no part of its lease: it
    lapses at the first check-quorum boundary, and the leader is refused
    while both still answer.  As voters they ARE a majority of five with it,
    and the lease stands throughout."""
    served, refused = lease_with_two_voters_gone(VOTERS, LEARNERS)
    assert served > 0 and refused > 0
    served5, refused5 = lease_with_two_voters_gone(VOTERS + LEARNERS, [])
    assert served5 > 0 and refused5 == 0
