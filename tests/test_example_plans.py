"""The shipped example plans, through ClusterSim, as exact counts.

One case per plan under examples/: every safety slot 0, and the floor the
plan is shipped to show — the production reconfig plan stays >= 0.8 fused
through the split-horizon runner, the serving mix and the autopilot's
closed loop >= 0.5, every scheduled membership op lands.  A count is
exact on any backend; no case here reads a clock.
"""

import json
import os

import jax.numpy as jnp
import numpy as np

from raft_tpu.multiraft import ClusterSim, SimConfig
from raft_tpu.multiraft import chaos, reconfig, workload
from raft_tpu.multiraft.autopilot import Autopilot, AutopilotConfig

EXAMPLES = os.path.join(os.path.dirname(__file__), "..", "examples")

# election_tick of the fused regime: the damped free-running timer bound
# must clear a k-round fused horizon (docs/PERF.md).
TICK_FUSED = 64


def _path(*parts):
    return os.path.join(EXAMPLES, *parts)


def _settled(cfg, append=None, **masks):
    """A ClusterSim past its boot storm (3 x election_tick rounds under
    load): the plans describe a running fleet, not its first election."""
    cs = ClusterSim(cfg, **masks)
    if append is None:
        append = jnp.ones((cfg.n_groups,), jnp.int32)
    cs.run_compiled(3 * cfg.election_tick, append_n=append)
    return cs


def _assert_safe(report):
    assert not any(report["safety"].values()), report["safety"]


def test_prod_fused_plan_stays_fused():
    """examples/reconfig/prod_fused.json — health + chaos overlay +
    check-quorum + pre-vote + a three-op ReconfigPlan over 256 rounds —
    through run_reconfig(split=True): the steady stretches between the op
    windows ride the fused kernel.  (Counters off: run_reconfig refuses a
    256-round counter window, sim._DRAIN_MAX; tests/test_reconfig_split.py
    threads the counter plane through the split runner.)"""
    with open(_path("reconfig", "prod_fused.json"), encoding="utf-8") as f:
        doc = json.load(f)
    plan = reconfig.plan_from_dict(doc["reconfig"])
    G = 256
    cfg = SimConfig(
        n_groups=G, n_peers=plan.n_peers, election_tick=TICK_FUSED,
        collect_health=True, check_quorum=True, pre_vote=True,
    )
    vm, om, lm = reconfig.initial_masks(plan, G)
    cs = _settled(cfg, voter_mask=vm, outgoing_mask=om, learner_mask=lm)
    report = cs.run_reconfig(
        plan, chaos_plan=chaos.plan_from_dict(doc["chaos"]), split=True,
        split_k=8, split_window=4,
    )
    _assert_safe(report)
    assert report["ops_applied"] == 3 * G, report
    assert report["total_rounds"] == plan.n_rounds * G
    assert report["fused_frac"] >= 0.8, report["fused_frac"]


def test_zipf_mixed_reads_fuse_their_lease_stretches():
    """examples/reads/zipf_mixed.json — Zipf write skew, lease and Safe
    read phases — on the damped lease-read configuration through
    run_reads(split=True): the pure-lease stretches ride the fused kernel
    with their receipts folded closed-form, the linearizability slots
    audited every round."""
    plan = workload.load_plan(_path("reads", "zipf_mixed.json"))
    G = 256
    cfg = SimConfig(
        n_groups=G, n_peers=plan.n_peers, election_tick=TICK_FUSED,
        collect_health=True, check_quorum=True, pre_vote=True,
        lease_read=True,
    )
    cs = _settled(cfg)
    report = cs.run_reads(plan, split=True, split_k=8)
    _assert_safe(report)
    assert report["served_lease"] > 0 and report["served_quorum"] > 0
    outstanding = int(jnp.sum(cs._read_carry.pending_mode > 0))
    assert (
        report["reads_issued"]
        == report["served_lease"] + report["served_quorum"] + outstanding
    ), report
    assert report["fused_frac"] >= 0.5, report["fused_frac"]


def test_autopilot_fused_cadence_on_a_crash_window():
    """The closed loop's default scenario — a Zipf hot-region workload, a
    32-round crash window, kick / transfer healing at cadence 16 — with
    the fused cadence segment: the healthy stretches take the fused arm,
    the crash window and every acted-on segment the general scan."""
    G, P = 256, 5
    plan = chaos.plan_from_dict({
        "name": "autopilot-crash-window",
        "peers": P,
        "phases": [
            {"rounds": 192, "append": 0},
            {"rounds": 32, "crash": [2], "append": 0},
            {"rounds": 96, "heal": True, "append": 0},
        ],
    })
    cfg = SimConfig(
        n_groups=G, n_peers=P, election_tick=TICK_FUSED,
        collect_health=True, transfer=True, commit_stall_ticks=8,
    )
    append = jnp.asarray(
        np.minimum(np.random.RandomState(0).zipf(1.8, size=G), 8),
        dtype=jnp.int32,
    )
    cs = _settled(cfg, append=append)
    cs.reset_health()
    report = Autopilot(cs, AutopilotConfig(cadence=16), fused=True).run_plan(
        plan, append=append
    )
    _assert_safe(report)
    assert sum(report["actions"].values()) > 0, report["actions"]
    fused_frac = report["fused_rounds"] / (G * plan.n_rounds)
    assert fused_frac >= 0.5, fused_frac


def test_partition_heal_plan_is_safe():
    """examples/chaos/partition_heal.json — split, asymmetric link with
    loss, a crash on half the groups, heal — from a cold fleet through
    run_plan: leaders are lost and found again, no safety slot moves."""
    plan = chaos.load_plan(_path("chaos", "partition_heal.json"))
    cfg = SimConfig(n_groups=128, n_peers=plan.n_peers, collect_health=True)
    report = ClusterSim(cfg).run_plan(plan)
    _assert_safe(report)
    assert report["rounds"] == plan.n_rounds
    assert report["reelections"] > 0


def test_joint_churn_plan_applies_every_op():
    """examples/reconfig/joint_churn.json — BASELINE config 4's shape:
    enter-joint / leave-joint / add-learner / promote under write load —
    through run_reconfig: every scheduled op lands and no group is left
    in a joint configuration."""
    plan = reconfig.load_plan(_path("reconfig", "joint_churn.json"))
    G = 256
    cfg = SimConfig(n_groups=G, n_peers=plan.n_peers, collect_health=True)
    vm, om, lm = reconfig.initial_masks(plan, G)
    cs = ClusterSim(cfg, voter_mask=vm, outgoing_mask=om, learner_mask=lm)
    compiled = reconfig.compile_plan(plan, G)
    report = cs.run_reconfig(compiled)
    _assert_safe(report)
    assert report["ops_applied"] == int(jnp.sum(compiled.n_ops)), report
    assert report["reconfig_stalled_groups"] == 0
    assert not bool(jnp.any(cs.state.outgoing_mask))
