"""ClusterSim.run_reads under a membership-change plan, call after call.

The benchmark's `rebalance` mix (benchmark/traffic/rebalance.json: YCSB-B
under four classes of replica moves through joint consensus and one zone
split) at G = 96 — two regions of each class — replayed through
`run_reads` with the op protocol's carry kept between the calls, against
the scalar oracle (simref.ReconfigOracle on real Raft state machines) for
both regions of every class and some regions that never move:

  * term, role, commit, last index and the voter / outgoing / learner
    masks of every peer equal after each replay, the five conf counts of
    the report equal to the oracle's, no chain unfinished;
  * a segment cut short inside a joint window: the next call finishes the
    chain from the entry in flight, in order, and never proposes op 0
    again before that;
  * with no reconfig plan the report's old keys and values are what a
    fresh carry per call gives, and the five new counts are 0.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import traffic
from raft_tpu.multiraft import (
    ClusterSim,
    ReconfigOracle,
    ScalarCluster,
    SimConfig,
)
from raft_tpu.multiraft import chaos, reconfig, workload
from raft_tpu.multiraft import runner as runner_mod

G, P = 96, 5
VOTERS = [1, 2, 3]
BOOT = 48  # every timeout in [20, 40) has fired: each region has a leader
CLASS_MOD = 48  # rebalance.json's selectors are {"mod": 48, "eq": k}
# The oracle's regions: ids 0-5 and 48-53 — classes 0-3 twice over, and
# four regions no op selects.  Both stretches read the SAME 6-region
# schedule (48 = 0 mod 48); their timeout streams are their global ids.
SPANS = (0, CLASS_MOD)
SPAN = 6
CONF_KEYS = (
    "conf_proposals", "conf_applied", "conf_retries", "joint_group_rounds",
)
OLD_KEYS = {
    "rounds", "reads_issued", "served_lease", "served_quorum",
    "degraded_serves", "retry_group_rounds", "dropped_fires", "read_p50",
    "read_p90", "read_p99", "mttr_rounds", "reelections", "healed_rounds",
    "max_leaderless_streak", "leaderless_group_rounds", "appends_offered",
    "appends_dropped", "recover_hist", "recover_p50_rounds",
    "recover_p90_rounds", "recover_p99_rounds", "safety",
}


def sim_config():
    return SimConfig(
        G, P, election_tick=20, heartbeat_tick=2, check_quorum=True,
        pre_vote=True, lease_read=True, collect_health=True,
    )


def booted_sim(cfg):
    masks = reconfig.initial_masks(
        reconfig.ReconfigPlan("boot", P, [], voters=VOTERS), G
    )
    sim = ClusterSim(cfg, *masks)
    sim.run_compiled(BOOT)
    sim.reset_health()
    return sim


def client_of(seg):
    return workload.CompiledClient(
        phase_of_round=jnp.asarray(seg.phase_of_round, jnp.int32),
        read_fire_packed=jnp.asarray(seg.read_fire_packed, jnp.uint32),
        read_mode=jnp.asarray(seg.read_mode, jnp.int32),
        append=jnp.asarray(seg.append, jnp.int32),
        n_peers=seg.n_peers,
    )


class Oracles:
    """One ReconfigOracle per stretch of regions, driven as one."""

    def __init__(self, cfg, seg):
        rplan = reconfig.plan_from_dict(seg.reconfig)
        cplan = chaos.plan_from_dict(seg.chaos) if seg.chaos else None
        self.ids = np.concatenate([np.arange(b, b + SPAN) for b in SPANS])
        self.n_rounds = seg.n_rounds
        self.oracles = []
        for base in SPANS:
            cluster = ScalarCluster(
                SPAN, P, election_tick=cfg.election_tick,
                heartbeat_tick=cfg.heartbeat_tick, voters=VOTERS,
                check_quorum=cfg.check_quorum, pre_vote=cfg.pre_vote,
                timeout_seed_base=base,
            )
            for _ in range(BOOT):
                cluster.round()
            sch = reconfig.HostReconfigSchedule(rplan, SPAN)
            # The client's updates ride on the schedule's append load, round
            # by round (reads are probes: they move no state).
            mine = seg.append[:, base:base + SPAN][seg.phase_of_round]
            sch.append = sch.append[sch.phase_of_round] + mine
            sch.phase_of_round = np.arange(seg.n_rounds)
            csch = chaos.HostSchedule(cplan, SPAN) if cplan else None
            self.oracles.append(ReconfigOracle(cluster, sch, csch))

    def replay(self, resume: bool) -> None:
        for o in self.oracles:
            if resume:
                o.resume()
            for _ in range(self.n_rounds):
                o.scheduled_round()

    def counts(self) -> dict:
        rstats = sum(o.rstats for o in self.oracles)
        out = dict(zip(CONF_KEYS, (int(v) for v in rstats)))
        out["conf_unfinished"] = sum(o.unfinished() for o in self.oracles)
        return out

    def rows(self) -> dict:
        """[n, P] per-peer cursors and membership of the oracle's regions,
        each peer's membership as its own tracker has it."""
        out = {k: [] for k in ("term", "state", "commit", "last_index",
                               "voter_mask", "outgoing_mask", "learner_mask")}
        for o in self.oracles:
            snap = o.cluster.snapshot()
            for k in ("term", "state", "commit", "last_index"):
                out[k].append(snap[k])
            for k, member in (
                ("voter_mask", lambda c, p: p in c.voters.incoming.ids()),
                ("outgoing_mask", lambda c, p: p in c.voters.outgoing.ids()),
                ("learner_mask", lambda c, p: p in c.learners),
            ):
                out[k].append(np.array([
                    [member(net.peers[p].raft.prs.conf, p)
                     for p in range(1, P + 1)]
                    for net in o.cluster.networks
                ]))
        return {k: np.concatenate(v) for k, v in out.items()}


def assert_state_equal(sim, oracles: Oracles, when: str) -> None:
    want = oracles.rows()
    for key, w in want.items():
        got = np.asarray(getattr(sim.state, key))[:, oracles.ids].T
        assert np.array_equal(got, w), f"{key} differs {when}"


def conf_counts(report: dict) -> dict:
    return {k: report[k] for k in CONF_KEYS + ("conf_unfinished",)}


def movers(seg) -> np.ndarray:
    return np.asarray(reconfig.compile_plan(
        reconfig.plan_from_dict(seg.reconfig), G).n_ops) > 0


def test_rebalance_replayed_twice_matches_the_scalar_oracle():
    cfg = sim_config()
    seg = traffic.generate(
        traffic.load_mix("rebalance"), G, P, seed=29, name="rebalance",
        voters=VOTERS,
    )
    assert seg.n_rounds <= 400 and seg.chaos and movers(seg).sum() == 8
    sim = booted_sim(cfg)
    plans = (
        client_of(seg), chaos.plan_from_dict(seg.chaos),
        reconfig.plan_from_dict(seg.reconfig),
    )
    oracles = Oracles(cfg, seg)
    assert_state_equal(sim, oracles, "after boot")
    home = jax.device_get(
        (sim.state.voter_mask, sim.state.outgoing_mask,
         sim.state.learner_mask)
    )
    for replay in (1, 2):
        report = sim.run_reads(*plans)
        oracles.replay(resume=replay > 1)
        assert_state_equal(sim, oracles, f"after replay {replay}")
        # Every region outside the oracle's stretches selects no op, so
        # the oracle's counts are the fleet's.
        assert conf_counts(report) == oracles.counts(), f"replay {replay}"
        assert report["conf_unfinished"] == 0
        # 6 ops for each of the 8 regions that move, both majorities
        # needed while joint, and the zone split inside a joint window.
        assert report["conf_applied"] == 48
        assert report["joint_group_rounds"] > 0
        assert not any(report["safety"].values()), report["safety"]
        now = jax.device_get(
            (sim.state.voter_mask, sim.state.outgoing_mask,
             sim.state.learner_mask)
        )
        for a, b in zip(now, home):
            assert np.array_equal(a, b), "a replay ends where it began"
    # The carry is the sim's to checkpoint.
    assert int(jnp.sum(sim._reconfig_state.op_ptr)) == 48


CUT_SHORT = {
    # Regions 0 and 48 move a replica 1 -> 4; stores 1, 2 are cut from 3,
    # 4, 5 from round 26 to the end: incoming {2,3,4} and outgoing {1,2,3}
    # each have their majority on another side, so the leave-joint proposed
    # at round 28 is in flight, uncommitted, when the segment ends.
    "reconfig": {"phases": [
        {"rounds": 8},
        {"rounds": 8, "op": {"add_learner": 4}, "groups": {"mod": 48, "eq": 0}},
        {"rounds": 12, "op": {"enter_joint": [{"add": 4}, {"remove": 1}]},
         "groups": {"mod": 48, "eq": 0}},
        {"rounds": 4, "op": {"leave_joint": True},
         "groups": {"mod": 48, "eq": 0}},
    ]},
    "chaos": {"then": [{"rounds": 26}, {"rounds": 6, "partition": [[1, 2]]}]},
}


def test_a_chain_cut_short_is_finished_by_the_next_call_never_from_op_0():
    cfg = sim_config()
    # Built by hand: traffic.generate refuses, as it must, a schedule that
    # does not end where it began.
    mix = {k: v for k, v in traffic.load_mix("rebalance").items()
           if k != "reconfig"}
    mix["chaos"] = CUT_SHORT["chaos"]
    seg = traffic.generate(mix, G, P, seed=31, name="cut-short",
                           voters=VOTERS)
    seg = seg._replace(reconfig={
        "name": "cut-short", "peers": P, "voters": VOTERS, "learners": [],
        **CUT_SHORT["reconfig"],
    })
    moving = movers(seg)
    assert moving.sum() == 2
    sim = booted_sim(cfg)
    plans = (
        client_of(seg), chaos.plan_from_dict(seg.chaos),
        reconfig.plan_from_dict(seg.reconfig),
    )
    oracles = Oracles(cfg, seg)

    first = sim.run_reads(*plans)
    oracles.replay(resume=False)
    assert_state_equal(sim, oracles, "after the segment cut short")
    assert conf_counts(first) == oracles.counts()
    # add-learner and enter-joint landed; the leave-joint is in flight.
    assert first["conf_applied"] == 4 and first["conf_unfinished"] == 2
    rst = jax.device_get(sim._reconfig_state)
    assert (rst.op_ptr[moving] == 2).all() and (rst.stage[moving] == 1).all()
    assert np.asarray(sim.state.outgoing_mask)[:, moving].any(axis=0).all()

    second = sim.run_reads(*plans)
    oracles.replay(resume=True)
    assert_state_equal(sim, oracles, "after the call that finishes it")
    assert conf_counts(second) == oracles.counts()
    # The entry in flight is the one applied: one apply a region, and every
    # proposal of this call follows an entry given up with its owner — none
    # is op 0's (a chain restarts at a call's START only).
    assert second["conf_applied"] == 2 and second["conf_unfinished"] == 0
    assert second["conf_proposals"] == second["conf_retries"]
    rst = jax.device_get(sim._reconfig_state)
    assert (rst.op_ptr[moving] == 3).all() and (rst.stage == 0).all()
    assert not np.asarray(sim.state.outgoing_mask).any()
    # The replica has moved: voters {2, 3, 4}.
    assert np.array_equal(
        np.asarray(sim.state.voter_mask)[:, moving],
        np.repeat([[False], [True], [True], [True], [False]], 2, axis=1),
    )
    assert not any(first["safety"].values())
    assert not any(second["safety"].values())


@pytest.mark.parametrize("split", [False, True])
def test_without_a_plan_the_report_is_what_it_was(split):
    cfg = sim_config()
    mix = {k: v for k, v in traffic.load_mix("rebalance").items()
           if k not in ("reconfig", "chaos")}
    mix["segment_rounds"] = 24
    seg = traffic.generate(mix, G, P, seed=37, name="no-plan", voters=VOTERS)
    client = client_of(seg)
    carried, fresh = booted_sim(cfg), booted_sim(cfg)
    if split:
        runner = runner_mod.make_runner(
            cfg, (client,), split=True, k=seg.split_k
        )
    else:
        runner = runner_mod.make_runner(cfg, (client,))
    for call in (1, 2):
        report = carried.run_reads(client, split=split, split_k=seg.split_k)
        # The same call as it was before the carry: a fresh op-protocol
        # state every time, and the report from the old five vectors.
        out = runner(
            fresh.state, fresh._health,
            reconfig.init_reconfig_state(fresh.state),
            workload.init_read_carry(G),
        )
        fresh.state, fresh._health = out[0], out[1]
        stats, safety, rdstats, lat_hist = out[3], out[5], out[7], out[8]
        lat_p, recover_p = workload.report_percentiles(lat_hist, stats)
        old = workload.read_report(
            *jax.device_get((rdstats, lat_p, safety, stats)), seg.n_rounds,
            jax.device_get(recover_p),
        )
        shown = {k: v for k, v in report.items() if k in OLD_KEYS}
        assert shown == {k: old[k] for k in OLD_KEYS}, f"call {call}"
        extra = set(report) - OLD_KEYS
        assert extra - {"fused_rounds", "total_rounds", "fused_frac"} == set(
            CONF_KEYS + ("conf_unfinished", "leader_changes", "term_bumps"))
        assert not any(conf_counts(report).values())
        assert set(workload.report_counts(report)) >= set(
            CONF_KEYS + ("conf_unfinished",))
