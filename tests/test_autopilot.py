"""Autopilot (ISSUE 12): the closed-loop control plane.

Tier-1 covers the host-side policy as pure functions (no jit), one small
end-to-end healing run, and the observability wiring; the heavier claims
— cadence-runner protocol identity vs the plain chaos scan, the fused
fast path's bit-identity, evacuation through the reconfig protocol, and
the corpus report tool — are @pytest.mark.slow: the long cases (no gate is
saturated: tier-1 takes 247 s of its 1470 s limit under xdist -n 6 at PR 32)."""

import json

import numpy as np
import pytest

import jax.numpy as jnp

from raft_tpu.metrics import Metrics
from raft_tpu.multiraft import ClusterSim, SimConfig, chaos
from raft_tpu.multiraft.autopilot import Autopilot, AutopilotConfig
from raft_tpu.multiraft.health import HealthMonitor
from raft_tpu.multiraft.reconfig import NO_ROUND, empty_reconfig_schedule

CRASH_PLAN = {
    "name": "crash-heal",
    "peers": 3,
    "phases": [
        {"rounds": 14, "append": 1},
        {"rounds": 16, "crash": [1], "append": 1},
        {"rounds": 12, "heal": True, "append": 1},
    ],
}


class _FakeSim:
    """Just enough ClusterSim surface for the pure policy tests."""

    def __init__(self, explains):
        self.cfg = SimConfig(n_groups=8, n_peers=3)
        self._explains = explains

    def explain(self, g):
        return self._explains[g]


def _info(g, leaderless=0, since=0, leader=0, last=(10, 10, 10),
          commit=(9, 9, 9), voter=(True, True, True)):
    return {
        "group": g,
        "health": {
            "leaderless_ticks": leaderless,
            "ticks_since_commit": since,
            "term_bumps_in_window": 0,
            "vote_splits": 0,
        },
        "peers": {
            "term": [1, 1, 1],
            "state": [2 if p + 1 == leader else 0 for p in range(3)],
            "commit": list(commit),
            "last_index": list(last),
            "leader_id": [leader] * 3,
            "voter": list(voter),
            "learner": [not v for v in voter],
        },
    }


def _summary(worst):
    return {
        "counts": {"leaderless": 0, "stalled_leaderless": 0,
                   "commit_stalled": 0, "churning": 0},
        "lag_hist": [0] * 8,
        "worst": worst,
    }


def test_policy_kicks_leaderless_and_respects_budget():
    explains = {
        g: _info(g, leaderless=5, last=(4, 9, 7), commit=(4, 8, 7))
        for g in range(8)
    }
    ap = Autopilot(
        _FakeSim(explains),
        AutopilotConfig(max_kicks=3, kick_leaderless_ticks=2),
    )
    worst = [{"group": g, "score": 5} for g in range(8)]
    transfer, kick, inspected = ap._decide(_summary(worst), 10)
    assert kick.sum() == 3, "per-cadence kick budget not enforced"
    # the first-choice target is the best-cursor peer (peer 2 here)
    assert kick[1].sum() == 3
    assert not transfer.any()
    assert ap.actions_taken["kicks"] == 3
    # cooldown: the same groups are not re-kicked next cadence
    transfer2, kick2, _ = ap._decide(_summary(worst[:3]), 12)
    assert not kick2.any()


def test_policy_kick_rotation_across_retries():
    explains = {0: _info(0, leaderless=5, last=(9, 6, 3), commit=(9, 6, 3))}
    ap = Autopilot(_FakeSim(explains), AutopilotConfig(cooldown=0))
    worst = [{"group": 0, "score": 5}]
    targets = []
    for r in range(3):
        _, kick, _ = ap._decide(_summary(worst), r)
        targets.append(int(np.flatnonzero(kick[:, 0])[0]) + 1)
    assert targets == [1, 2, 3], "retries must rotate through the ranking"


def test_policy_transfers_off_stalled_leader():
    explains = {
        2: _info(2, since=9, leader=3, last=(8, 9, 9), commit=(5, 5, 9)),
    }
    ap = Autopilot(
        _FakeSim(explains), AutopilotConfig(transfer_stall_ticks=6)
    )
    worst = [{"group": 2, "score": 9}]
    transfer, kick, _ = ap._decide(_summary(worst), 20)
    assert not kick.any()
    # best non-leader cursor: peer 2 (last 9) over peer 1 (last 8)
    assert transfer[2] == 2
    assert ap.actions_taken["transfers"] == 1


def test_policy_transfer_skips_learners_and_rotates():
    """A learner may hold the best cursor but is never a valid target
    (apply_transfer would refuse it); retries rotate through the VOTER
    ranking so a dead best-cursor voter cannot be re-picked forever."""
    info = _info(
        0, since=9, leader=3, last=(8, 9, 7), commit=(5, 9, 5),
        voter=(True, False, True),
    )
    ap = Autopilot(
        _FakeSim({0: info}),
        AutopilotConfig(transfer_stall_ticks=6, cooldown=0),
    )
    worst = [{"group": 0, "score": 9}]
    t1, _, _ = ap._decide(_summary(worst), 0)
    assert t1[0] == 1, "the learner's best cursor must not be targeted"
    t2, _, _ = ap._decide(_summary(worst), 1)
    assert t2[0] == 1  # sole voter candidate: rotation wraps onto it


def test_policy_leader_from_role_columns_not_stale_views():
    """The acting leader comes from the per-peer role/term columns, not
    the leader_id views — a partitioned peer's stale view naming an
    ex-leader must not mis-exclude the transfer target (or worse, let
    the real leader be targeted)."""
    info = _info(0, since=9, leader=1, last=(9, 9, 8), commit=(9, 8, 5))
    info["peers"]["leader_id"] = [3, 3, 3]  # stale views everywhere
    ap = Autopilot(
        _FakeSim({0: info}), AutopilotConfig(transfer_stall_ticks=6)
    )
    t, _, _ = ap._decide(_summary([{"group": 0, "score": 9}]), 0)
    assert t[0] == 2, "must exclude the REAL leader (peer 1, by role)"


def test_balance_transfers_spread_leaders_by_weight():
    """The Zipf load-balance policy (the TiKV-style hot-region
    regime): heavy groups move off the overloaded leader peer onto their
    least-loaded voter, strictly improving the weighted load gap, within
    budget."""
    cfg = SimConfig(n_groups=8, n_peers=3, collect_health=True,
                    transfer=True)
    sim = ClusterSim(cfg)
    crashed = jnp.zeros((3, 8), bool)
    append = jnp.ones((8,), jnp.int32)
    for _ in range(40):
        sim.state = sim._step(sim.state, crashed, append, None, None,
                              None, None)
    lead = np.asarray(sim.state.leader_id).max(axis=0)
    # Skewed weights: the heaviest groups sit wherever their leaders are.
    w = np.ones(8, np.int64)
    hot_peer = int(np.bincount(lead, minlength=4)[1:].argmax()) + 1
    w[lead == hot_peer] = 10
    ap = Autopilot(
        sim, AutopilotConfig(balance=True, max_balance_transfers=2)
    )
    tp = ap.balance_transfers(weights=w, round_idx=0)
    moved = np.flatnonzero(tp)
    assert 0 < len(moved) <= 2, "budgeted balance moves expected"
    assert all(lead[g] == hot_peer for g in moved), (
        "moves must come off the most-loaded peer"
    )
    assert all(tp[g] != hot_peer for g in moved)
    assert ap.actions_taken["transfers"] == len(moved)
    # applying the commands actually moves leadership (one eager round)
    from raft_tpu.multiraft import sim as sim_mod

    st = sim_mod.step(
        cfg, sim.state, crashed, append,
        transfer_propose=jnp.asarray(tp),
    )
    lead2 = np.asarray(st.leader_id).max(axis=0)
    assert all(lead2[g] == tp[g] for g in moved)


def test_runner_memo_keeps_a_lossless_runner_from_a_lossy_plan(monkeypatch):
    """The cadence runner's memo is keyed on shapes AND on the chaos plan's
    trace-time static: two plans of one shape, one with a loss rate, build
    two runners (a lossless plan's round draws no loss sample — ISSUE 48);
    the same static hits."""
    from raft_tpu.multiraft import autopilot as autopilot_mod

    G = 8
    lossless = chaos.compile_plan(chaos.plan_from_dict(CRASH_PLAN), G)
    doc = json.loads(json.dumps(CRASH_PLAN))
    doc["phases"][1]["loss_all"] = 0.1
    lossy = chaos.compile_plan(chaos.plan_from_dict(doc), G)
    assert lossless.lossless and not lossy.lossless
    assert [a.shape for a in lossless[:5]] == [a.shape for a in lossy[:5]]
    built = []
    monkeypatch.setattr(
        autopilot_mod.runner_mod, "make_runner",
        lambda cfg, scheds, **kw: built.append(scheds[1].lossless) or len(built),
    )
    ap = Autopilot(ClusterSim(SimConfig(n_groups=G, n_peers=3, collect_health=True)))
    compiled = empty_reconfig_schedule(lossless.n_rounds, 3, G)
    got = [ap._runner_for(compiled, c, 8) for c in (lossless, lossy, lossless, lossy)]
    assert built == [True, False]
    assert got == [1, 2, 1, 2]


def test_empty_reconfig_schedule_shape():
    sched = empty_reconfig_schedule(10, 3, 4)
    assert sched.n_rounds == 10
    assert int(sched.n_ops.sum()) == 0
    assert int(sched.op_start.min()) == NO_ROUND


def test_autopilot_heals_crash_scenario_end_to_end():
    """The small end-to-end: a crashed-leader window with the loop on —
    kicks fire, the run stays safe, and the healing beats the off replay
    on leaderless group-rounds (the kicked episodes end at the cadence
    instead of the timeout)."""
    plan = chaos.plan_from_dict(CRASH_PLAN)

    def run(on):
        cfg = SimConfig(
            n_groups=8, n_peers=3, collect_health=True, transfer=True,
            commit_stall_ticks=8,
        )
        sim = ClusterSim(cfg)
        ap = Autopilot(
            sim,
            AutopilotConfig(
                cadence=5, kick=on, transfer=on, kick_leaderless_ticks=2
            ),
        )
        return ap.run_plan(plan)

    off = run(False)
    on = run(True)
    assert not any(off["safety"].values())
    assert not any(on["safety"].values())
    assert sum(off["actions"].values()) == 0
    assert sum(on["actions"].values()) > 0
    assert (
        on["leaderless_group_rounds"] < off["leaderless_group_rounds"]
    ), "the closed loop failed to shorten the leaderless episodes"
    assert on["commit_stall_group_rounds"] <= off["commit_stall_group_rounds"]


def test_monitor_and_metrics_wiring():
    records = []
    tracer_sink = []
    m = Metrics(tracer=None)
    mon = HealthMonitor(metrics=m)
    report = {
        "rounds": 10, "mttr_rounds": 2.0, "reelections": 3,
        "commit_stall_group_rounds": 7, "actions": {"kicks": 2},
        "safety": {"dual_leader": 0},
    }
    entry = mon.record_autopilot(report)
    assert entry["autopilot"] is report
    assert mon.last()["autopilot"]["actions"] == {"kicks": 2}
    # the counter/gauge families exist and accept the autopilot labels
    m.autopilot_actions.labels(kind="kicks").inc(2)
    m.health_transfer_pending.set(3)
    snap = m.registry.snapshot()
    assert snap['multiraft_autopilot_actions_total{kind="kicks"}'] == 2
    assert snap["health_groups_transfer_pending"] == 3


def test_driver_transfer_and_autopilot_report():
    from raft_tpu import Config, MemStorage
    from raft_tpu.config import HealthConfig
    from raft_tpu.multiraft.driver import MultiRaft
    from raft_tpu.raft_log import NO_LIMIT

    cfg = Config(
        id=1, election_tick=10, heartbeat_tick=3,
        max_size_per_msg=NO_LIMIT, max_inflight_msgs=256,
    )
    storages = [
        MemStorage.new_with_conf_state(([1], [])) for _ in range(2)
    ]
    mr = MultiRaft(cfg, storages, health=HealthConfig())
    mr.campaign(0)  # singleton config: wins locally
    for _ in range(3):
        mr.tick()
    rep = mr.autopilot_report()
    assert rep["transfer_pending"] == 0
    assert "mttr" in rep
    from raft_tpu import StateRole
    assert mr.node(0).raft.state == StateRole.Leader
    # a singleton's transfer-to-self is refused; pending stays 0
    mr.transfer_leader(0, 1)
    assert mr.transfer_pending() == 0


# --- slow: identity / fused / evacuation / report tool ---------------------


@pytest.mark.slow
def test_cadence_runner_identical_to_chaos_scan():
    """With every action disabled the autopilot's cadence machinery is
    protocol-identical to the plain compiled chaos scan: same end state,
    same health planes, same MTTR stats, zero safety violations."""
    plan = chaos.plan_from_dict(CRASH_PLAN)
    G = 16

    cfg_off = SimConfig(n_groups=G, n_peers=3, collect_health=True)
    base = ClusterSim(cfg_off, chaos=plan)
    base_rep = base.run_plan()

    cfg_on = SimConfig(
        n_groups=G, n_peers=3, collect_health=True, transfer=True
    )
    sim = ClusterSim(cfg_on)
    ap = Autopilot(
        sim, AutopilotConfig(cadence=7, kick=False, transfer=False)
    )
    rep = ap.run_plan(plan)
    for k in ("term", "state", "commit", "last_index", "last_term"):
        assert np.array_equal(
            np.asarray(getattr(sim.state, k)),
            np.asarray(getattr(base.state, k)),
        ), f"{k} diverged from the plain chaos scan"
    assert np.array_equal(
        np.asarray(sim._health.planes), np.asarray(base._health.planes)
    )
    for k in ("mttr_rounds", "reelections", "leaderless_group_rounds"):
        assert rep[k] == base_rep[k]
    assert not any(rep["safety"].values())


@pytest.mark.slow
def test_fused_cadence_bit_identical():
    """The fused cadence fast path (bench --autopilot) is bit-identical
    to the general scan and actually engages on healthy stretches.  The
    crash window takes out a voter MAJORITY (2 of 3) while some leaders
    stay alive: steady_mask alone would admit those stalled-commit
    horizons, so this pins the progress_ok guard — the fused path must
    fall back there or the commit-stall group-round counts diverge."""
    doc = {
        "name": "long-heal", "peers": 3,
        "phases": [
            {"rounds": 96, "append": 1},
            {"rounds": 16, "crash": [2, 3], "append": 1},
            {"rounds": 48, "heal": True, "append": 1},
        ],
    }
    plan = chaos.plan_from_dict(doc)
    G = 16

    def run(fused):
        cfg = SimConfig(
            n_groups=G, n_peers=3, collect_health=True, transfer=True,
            election_tick=64, commit_stall_ticks=8,
        )
        sim = ClusterSim(cfg)
        ap = Autopilot(sim, AutopilotConfig(cadence=16), fused=fused)
        rep = ap.run_plan(plan)
        return sim, rep

    s1, r1 = run(True)
    s2, r2 = run(False)
    assert r1.get("fused_frac", 0) > 0, "fused branch never engaged"
    for f in s1.state._fields:
        a, b = getattr(s1.state, f), getattr(s2.state, f)
        if a is None:
            assert b is None
            continue
        assert np.array_equal(np.asarray(a), np.asarray(b)), f
    assert np.array_equal(
        np.asarray(s1._health.planes), np.asarray(s2._health.planes)
    )
    for k in ("mttr_rounds", "commit_stall_group_rounds"):
        assert r1[k] == r2[k]


@pytest.mark.slow
def test_autopilot_evacuation_through_reconfig_protocol():
    """The heaviest action: a long-crashed voter gets its groups walked
    off onto a spare peer via the PR 10 propose/gate/apply protocol, in
    the same scan as the chaos — zero safety violations, and the end
    voter sets show the swap."""
    doc = {
        "name": "evac", "peers": 5,
        "phases": [
            {"rounds": 24, "append": 1},
            {"rounds": 40, "crash": [3], "append": 1},
            {"rounds": 16, "heal": True, "append": 1},
        ],
    }
    plan = chaos.plan_from_dict(doc)
    G = 16
    cfg = SimConfig(
        n_groups=G, n_peers=5, collect_health=True, transfer=True,
        commit_stall_ticks=8,
    )
    vm = np.zeros((5, G), bool)
    vm[:3] = True
    sim = ClusterSim(cfg, voter_mask=jnp.asarray(vm))
    ap = Autopilot(
        sim,
        AutopilotConfig(
            cadence=8, evacuate=True, evac_stall_ticks=8,
            evac_min_groups=2,
        ),
    )
    rep = ap.run_plan(plan)
    assert not any(rep["safety"].values())
    assert rep["actions"]["evacuations"] > 0
    vm2 = np.asarray(sim.state.voter_mask)
    evacuated = ~vm2[2] & vm2[3]
    assert evacuated.sum() == rep["actions"]["evacuations"]
    # evacuated groups left the joint config (the leave op applied)
    assert not np.asarray(sim.state.outgoing_mask)[:, evacuated].any()


@pytest.mark.slow
def test_autopilot_report_tool(tmp_path):
    """The CI gate tool on a one-scenario corpus: JSON shape, per-side
    reports, and the improvement gate arithmetic."""
    import tools.autopilot_report as art

    corpus = [
        {
            "name": "crash-heal", "peers": 3,
            "phases": CRASH_PLAN["phases"],
        }
    ]
    plans = tmp_path / "plans.json"
    plans.write_text(json.dumps(corpus))
    out = tmp_path / "report.json"
    rc = art.main.__wrapped__() if hasattr(art.main, "__wrapped__") else None
    import sys
    argv = sys.argv
    sys.argv = [
        "autopilot_report.py", "--groups", "16", "--cadence", "5",
        "--plans", str(plans), "--out", str(out),
    ]
    try:
        rc = art.main()
    finally:
        sys.argv = argv
    doc = json.loads(out.read_text())
    assert "crash-heal" in doc["plans"]
    on = doc["plans"]["crash-heal"]["on"]
    assert sum(on["actions"].values()) > 0
    assert rc == 0, "the healing gate failed on the crash corpus"
