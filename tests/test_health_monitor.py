"""Host-side fleet-health: the HealthMonitor flight recorder / metrics /
tracing bridge (raft_tpu/multiraft/health.py), the MultiRaft driver's numpy
health planes + health()/explain(), the ClusterSim monitor wiring, and the
ready-scan short-circuit satellite (dirty-set scan + skip-ratio counters).

Everything here is host-only or reuses shapes compiled elsewhere — cheap by
construction."""

import numpy as np
import pytest

from raft_tpu import ArrayStorage, Config, MemStorage
from raft_tpu.config import HealthConfig
from raft_tpu.errors import ConfigInvalid
from raft_tpu.metrics import EventTracer, Metrics
from raft_tpu.multiraft.driver import MultiRaft
from raft_tpu.multiraft.health import HealthMonitor
from raft_tpu.raft_log import NO_LIMIT


def summary(
    leaderless=0, stalled=0, commit_stalled=0, churning=0, worst=()
):
    return {
        "counts": {
            "leaderless": leaderless,
            "stalled_leaderless": stalled,
            "commit_stalled": commit_stalled,
            "churning": churning,
        },
        "lag_hist": [4, 0, 0, 0, 0, 0, 0, 0],
        "worst": list(worst),
    }


# --- HealthMonitor unit behavior ---


def test_monitor_ring_and_seq():
    mon = HealthMonitor(recorder_size=3)
    for i in range(5):
        mon.record(summary(leaderless=i))
    ring = mon.summary_ring()
    assert len(mon) == 3
    assert [e["seq"] for e in ring] == [2, 3, 4]  # oldest evicted
    assert mon.last()["summary"]["counts"]["leaderless"] == 4
    # The historical flight_recorder() alias is gone: summary_ring is
    # the one name (the flight-recorder role lives in the device black
    # box, SimConfig.blackbox / ClusterSim.forensics()).
    assert not hasattr(mon, "flight_recorder")


def test_monitor_metrics_and_traces():
    events = []
    m = Metrics(tracer=EventTracer(events))
    mon = HealthMonitor(metrics=m)
    mon.record(
        summary(
            leaderless=3,
            stalled=2,
            commit_stalled=1,
            churning=1,
            worst=[{"group": 7, "score": 40}],
        )
    )
    snap = m.registry.snapshot()
    assert snap["health_summaries_total"] == 1
    assert snap["health_groups_leaderless"] == 3
    assert snap["health_groups_stalled_leaderless"] == 2
    assert snap["health_groups_commit_stalled"] == 1
    assert snap["health_groups_churning"] == 1
    assert snap["health_worst_group_score"] == 40
    assert snap['health_commit_lag_groups{ge="0"}'] == 4
    names = [e["event"] for e in events]
    assert "health.summary" in names
    assert "health.stall" in names
    assert "health.churn" in names


def test_monitor_quiet_summary_emits_no_stall_events():
    events = []
    m = Metrics(tracer=EventTracer(events))
    HealthMonitor(metrics=m).record(summary())
    assert [e["event"] for e in events] == ["health.summary"]


def test_monitor_snapshot_hook_captures_worst_groups():
    seen = []

    def snap(g):
        seen.append(g)
        return {"group": g, "note": "snap"}

    mon = HealthMonitor(snapshot_fn=snap)
    entry = mon.record(
        summary(worst=[{"group": 3, "score": 9}, {"group": 1, "score": 0}])
    )
    assert seen == [3]  # zero-score offenders are not snapshotted
    assert entry["worst_snapshots"][3]["note"] == "snap"


def test_health_config_validate():
    HealthConfig().validate()
    with pytest.raises(ConfigInvalid):
        HealthConfig(window=0).validate()
    with pytest.raises(ConfigInvalid):
        HealthConfig(churn_bumps=0).validate()
    with pytest.raises(ConfigInvalid):
        HealthConfig(recorder_size=0).validate()


# --- MultiRaft driver integration ---


def base_config(metrics=None) -> Config:
    return Config(
        id=1,
        election_tick=10,
        heartbeat_tick=3,
        max_size_per_msg=NO_LIMIT,
        max_inflight_msgs=256,
        metrics=metrics,
    )


def singleton_driver(G=4, metrics=None, health=None, storage_cls=MemStorage):
    """G single-voter groups: leaders elect locally on the first timeout,
    no network needed — the cheapest full Ready loop."""
    stores = [
        storage_cls.new_with_conf_state(([1], [])) for _ in range(G)
    ]
    return MultiRaft(base_config(metrics), stores, health=health)


def pump(d):
    for g in d.ready_groups():
        rd = d.ready(g)
        store = d.node(g).raft.raft_log.store
        if rd.entries:
            with store.wl() as core:
                core.append(rd.entries)
        if rd.hs is not None:
            with store.wl() as core:
                core.set_hardstate(rd.hs.clone())
        d.advance(g, rd)
        d.advance_apply(g)


def test_driver_health_planes_and_summary():
    m = Metrics()
    d = singleton_driver(
        G=4, metrics=m, health=HealthConfig(window=8, leaderless_stall_ticks=4)
    )
    # Before any leader exists, leaderless grows; stall threshold trips.
    for _ in range(6):
        d.tick()
    s = d.health()
    assert s["counts"]["leaderless"] >= 0  # may have elected already
    # Run to leaders + commits everywhere.
    for _ in range(25):
        d.tick()
        pump(d)
    s = d.health()
    assert s["counts"]["leaderless"] == 0
    assert s["counts"]["stalled_leaderless"] == 0
    assert len(s["worst"]) == 4
    assert sum(s["lag_hist"]) == 4
    info = d.explain(0)
    assert info["leader_id"] == 1 and info["commit"] >= 1
    assert info["health"]["leaderless_ticks"] == 0
    # The monitor recorded through health() and published gauges.
    assert len(d.health_monitor) >= 1
    assert m.registry.snapshot()["health_groups_leaderless"] == 0


def test_driver_health_disabled_raises():
    d = singleton_driver(G=2)
    with pytest.raises(RuntimeError):
        d.health()
    with pytest.raises(RuntimeError):
        d.mttr()
    # explain still works without health (no plane row).
    assert "health" not in d.explain(0)


def test_driver_mttr_counts_reelection_episodes():
    """The host MTTR twin: singleton groups start leaderless, elect once,
    and every healed episode's length lands in the mean."""
    d = singleton_driver(G=3, health=HealthConfig(window=8))
    m0 = d.mttr()
    assert m0["reelections"] == 0 and m0["mttr_ticks"] is None
    for _ in range(25):
        d.tick()
        pump(d)
    m1 = d.mttr()
    # Every group elected itself exactly once (singleton voters).
    assert m1["reelections"] == 3
    assert m1["mttr_ticks"] is not None and m1["mttr_ticks"] >= 1
    assert m1["max_leaderless_streak"] >= 1
    assert (
        m1["leaderless_group_ticks"]
        >= m1["reelections"] * 1
    )


def test_driver_health_with_array_storage():
    """ArrayStorage is a drop-in for MemStorage under the full driver
    Ready loop (the satellite's 'behind MemStorage's interface')."""
    d = singleton_driver(G=2, health=HealthConfig(), storage_cls=ArrayStorage)
    for _ in range(25):
        d.tick()
        pump(d)
    s = d.health()
    assert s["counts"]["leaderless"] == 0
    assert d.explain(0)["commit"] >= 1


# --- ready-scan short-circuit satellite ---


def test_ready_scan_skips_idle_groups():
    m = Metrics()
    d = singleton_driver(G=8, metrics=m)
    for _ in range(25):
        d.tick()
        pump(d)
    # Quiescent: nothing pending anywhere.
    snap0 = m.registry.snapshot()
    assert d.ready_groups() == []
    snap1 = m.registry.snapshot()
    scanned = (
        snap1["multiraft_ready_scan_groups_scanned_total"]
        - snap0["multiraft_ready_scan_groups_scanned_total"]
    )
    skipped = (
        snap1["multiraft_ready_scan_groups_skipped_total"]
        - snap0["multiraft_ready_scan_groups_skipped_total"]
    )
    assert scanned == 0 and skipped == 8
    # A host interaction re-marks exactly that group.
    d.propose(3, b"", b"x")
    assert d.ready_groups() == [3]
    snap2 = m.registry.snapshot()
    assert (
        snap2["multiraft_ready_scan_groups_scanned_total"]
        - snap1["multiraft_ready_scan_groups_scanned_total"]
        == 1
    )


def test_ready_scan_equivalent_to_full_scan():
    """The dirty-set scan must return exactly what the O(G) sweep would."""
    d = singleton_driver(G=6)
    rng = np.random.RandomState(3)
    for r in range(40):
        d.tick()
        want = [g for g in range(d.G) if d.nodes[g].has_ready()]
        got = d.ready_groups()
        assert got == want, f"round {r}: {got} != {want}"
        if r % 3 == 0:
            g = int(rng.randint(d.G))
            if d.nodes[g].raft.leader_id:  # pre-election proposals drop
                d.propose(g, b"", b"y")
        pump(d)
