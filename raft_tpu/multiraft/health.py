"""HealthMonitor: the host-side consumer of fleet-health summaries.

The device planes (raft_tpu/multiraft/kernels.py HP_* rows, maintained by
sim.step) and the MultiRaft driver's numpy planes both reduce to the same
fixed-size summary dict::

    {"counts": {"leaderless": n, "stalled_leaderless": n,
                "commit_stalled": n, "churning": n},
     "lag_hist": [kernels.N_LAG_BUCKETS counts],
     "worst": [{"group": id, "score": s}, ...]}

This module is the boundary where those summaries land on the host: the
monitor converts each one into Prometheus gauges via the PR 1 registry
(raft_tpu.metrics.Metrics.on_health_summary), emits `health.*` events
through the EventTracer, and keeps a fixed-size flight-recorder ring of
recent summaries plus per-worst-group state snapshots for post-mortems
(MultiRaft.explain / ClusterSim.explain feed the snapshot hook).

Summaries must arrive as plain host dicts — this module is in graftcheck's
GC002 scope precisely so no device sync (device_get/.item()) can creep
into the record path, and in GC004's scope so every metrics call stays
behind the single enabled-check branch.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, Deque, Dict, List, Optional

__all__ = ["HealthMonitor"]


class HealthMonitor:
    """Flight recorder + metrics/tracing bridge for health summaries.

    metrics:       optional raft_tpu.metrics.Metrics; each recorded summary
                   is published through on_health_summary and traced.
    recorder_size: ring capacity (config.HealthConfig.recorder_size).
    snapshot_fn:   optional group_id -> dict hook; when set, worst-offender
                   groups with a non-zero score get a state snapshot stored
                   alongside the summary (the owners install their explain()
                   here — ClusterSim and MultiRaft both do).
    """

    def __init__(
        self,
        metrics=None,
        recorder_size: int = 64,
        snapshot_fn: Optional[Callable[[int], dict]] = None,
    ):
        self.metrics = metrics
        self.snapshot_fn = snapshot_fn
        # The host SUMMARY ring: recent fixed-size summaries and scenario
        # reports.  Deliberately distinct from the DEVICE black box
        # (sim.BlackboxState, ISSUE 15) — this ring holds what already
        # crossed to the host; the black box holds per-group round
        # deltas that never leave the device until an incident drains.
        self._summary_ring: Deque[dict] = deque(maxlen=recorder_size)
        # Per-slot cumulative offender counts already counted into the
        # incident metric (record_incident increments by the delta).
        self._incident_seen: Dict[str, int] = {}
        self._seq = 0
        self._lock = threading.Lock()

    @staticmethod
    def summary_dict(counts, lag_hist, worst_ids, worst_scores) -> dict:
        """THE summary shape (module docstring) from the four reduction
        vectors, in kernels.health_summary's return order — the single
        formatter every producer (ClusterSim, MultiRaft) goes
        through so the consumers can never see a drifted shape."""
        from .kernels import HEALTH_COUNT_NAMES

        return {
            "counts": dict(
                zip(HEALTH_COUNT_NAMES, (int(v) for v in counts))
            ),
            "lag_hist": [int(v) for v in lag_hist],
            "worst": [
                {"group": int(g), "score": int(s)}
                for g, s in zip(worst_ids, worst_scores)
            ],
        }

    def record(self, summary: dict) -> dict:
        """Fold one summary into the recorder, metrics, and trace; returns
        the flight-recorder entry (with its seq / ts / snapshots)."""
        snapshots: Dict[int, dict] = {}
        fn = self.snapshot_fn
        if fn is not None:
            for w in summary.get("worst", ()):
                if w["score"] > 0:
                    snapshots[w["group"]] = fn(w["group"])
        with self._lock:
            entry = {"seq": self._seq, "ts": time.time(), "summary": summary}
            if snapshots:
                entry["worst_snapshots"] = snapshots
            self._seq += 1
            self._summary_ring.append(entry)
        m = self.metrics
        if m is not None:
            m.on_health_summary(summary)
            counts = summary.get("counts", {})
            m.trace("health.summary", **counts)
            if counts.get("stalled_leaderless", 0) or counts.get(
                "commit_stalled", 0
            ):
                m.trace(
                    "health.stall",
                    stalled_leaderless=counts.get("stalled_leaderless", 0),
                    commit_stalled=counts.get("commit_stalled", 0),
                    worst=summary.get("worst", []),
                )
            if counts.get("churning", 0):
                m.trace("health.churn", churning=counts.get("churning", 0))
        return entry

    @staticmethod
    def chaos_report(stats, safety, rounds: int) -> dict:
        """Per-scenario chaos summary off the device accumulators.

        stats:  [chaos.N_CHAOS_STATS] int32 vector (CS_* indices) — the
                time-to-reelect facts folded from the HP_LEADERLESS
                health plane every round of the compiled run.
        safety: [kernels.N_SAFETY] int32 violation counts (SV_*
                indices); all-zero on every correct run — the chaos fuzz
                harness asserts it.
        rounds: rounds executed (python int, from the compiled plan).

        Returns the scenario-summary dict ClusterSim.run_plan returns
        (tools/chaos_churn_report.py writes it as a CI artifact)::

            {"rounds": R,
             "mttr_rounds": mean leaderless-episode length (None when no
                            episode ended),
             "reelections": episodes that ended with a leader regained,
             "max_leaderless_streak": worst streak observed anywhere,
             "leaderless_group_rounds": leaderless (group, round) pairs,
             "safety": {"dual_leader": 0, ...}}
        """
        from .chaos import (
            CS_HEALED_ROUNDS,
            CS_LEADERLESS_ROUNDS,
            CS_MAX_STREAK,
            CS_REELECTIONS,
        )
        from .kernels import SAFETY_NAMES

        reelections = int(stats[CS_REELECTIONS])
        healed = int(stats[CS_HEALED_ROUNDS])
        return {
            "rounds": int(rounds),
            "mttr_rounds": (
                round(healed / reelections, 3) if reelections else None
            ),
            "reelections": reelections,
            "max_leaderless_streak": int(stats[CS_MAX_STREAK]),
            "leaderless_group_rounds": int(stats[CS_LEADERLESS_ROUNDS]),
            "safety": {
                name: int(v) for name, v in zip(SAFETY_NAMES, safety)
            },
        }

    @staticmethod
    def reconfig_stall_groups(
        outgoing_mask, since_commit, election_tick: int,
        stall_timeouts: int = 4, topk: int = 8,
    ):
        """THE reconfig-stall rule, host-side off downloaded planes: a
        group still inside a joint config (outgoing half non-empty)
        whose commit has been flat for `stall_timeouts * election_tick`
        rounds — the existing commit-stall health plane joined with the
        joint bit, no new device plane.  Returns
        (stalled_count, worst_group_ids) with worst ranked by staleness,
        capped at `topk`."""
        import numpy as np

        # graftcheck: allow-no-host-sync-in-jit — callers pass planes
        # they already downloaded (device_get) at end of run; this whole
        # helper is deliberately host-side.
        joint = np.any(np.asarray(outgoing_mask), axis=0)
        # graftcheck: allow-no-host-sync-in-jit — same (host-side rule).
        since = np.asarray(since_commit)
        stuck = joint & (since >= stall_timeouts * election_tick)
        n_stuck = int(stuck.sum())
        order = np.argsort(np.where(stuck, since, -1))[::-1]
        return n_stuck, [int(g) for g in order[: min(n_stuck, topk)]]

    @staticmethod
    def reconfig_report(
        stats, rstats, safety, rounds: int, stalled_groups: int,
        stalled_worst=(),
    ) -> dict:
        """Per-scenario reconfig summary off the device accumulators.

        stats:   [chaos.N_CHAOS_STATS] int32 MTTR facts (same fold as the
                 chaos runner — reconfig churn rides the leaderless plane
                 too).
        rstats:  [reconfig.N_RECONFIG_STATS] int32 op-protocol counts
                 (RC_* indices: proposals / applies / retries /
                 joint-group-rounds).
        safety:  [kernels.N_SAFETY] int32 violation counts, now including
                 the joint-window slots; all-zero on every correct run.
        rounds:  rounds executed.
        stalled_groups / stalled_worst: the host-side stall detection —
                 groups sitting in a joint config (outgoing half
                 non-empty) whose commit has stalled past the threshold,
                 derived from the existing commit-stall health plane plus
                 the joint bit (no new device plane).

        Returns the scenario-summary dict ClusterSim.run_reconfig returns
        and tools/reconfig_report.py writes as a CI artifact.
        """
        from .chaos import CS_MAX_STREAK, CS_REELECTIONS, CS_HEALED_ROUNDS
        from .kernels import SAFETY_NAMES
        from .reconfig import RECONFIG_STAT_NAMES

        reelections = int(stats[CS_REELECTIONS])
        healed = int(stats[CS_HEALED_ROUNDS])
        return {
            "rounds": int(rounds),
            **{
                name: int(v)
                for name, v in zip(RECONFIG_STAT_NAMES, rstats)
            },
            "mttr_rounds": (
                round(healed / reelections, 3) if reelections else None
            ),
            "reelections": reelections,
            "max_leaderless_streak": int(stats[CS_MAX_STREAK]),
            "reconfig_stalled_groups": int(stalled_groups),
            "reconfig_stalled_worst": [int(g) for g in stalled_worst],
            "safety": {
                name: int(v) for name, v in zip(SAFETY_NAMES, safety)
            },
        }

    def record_reconfig(self, report: dict) -> dict:
        """Fold a reconfig scenario report (reconfig_report's shape) into
        the flight recorder, gauges, and trace stream; stalled groups
        raise a `health.reconfig_stall` event and safety violations a
        `reconfig.safety` event so neither can scroll by silently."""
        with self._lock:
            entry = {"seq": self._seq, "ts": time.time(),
                     "reconfig": report}
            self._seq += 1
            self._summary_ring.append(entry)
        m = self.metrics
        if m is not None:
            stalled = report.get("reconfig_stalled_groups", 0)
            m.health_reconfig_stalled.set(stalled)
            m.trace(
                "reconfig.scenario",
                rounds=report.get("rounds", 0),
                proposals=report.get("proposals", 0),
                ops_applied=report.get("ops_applied", 0),
                retries=report.get("retries", 0),
                joint_group_rounds=report.get("joint_group_rounds", 0),
            )
            if stalled:
                m.trace(
                    "health.reconfig_stall",
                    stalled=stalled,
                    worst=report.get("reconfig_stalled_worst", []),
                )
            if any(report.get("safety", {}).values()):
                m.trace("reconfig.safety", **report["safety"])
        return entry

    def record_autopilot(self, report: dict) -> dict:
        """Fold an autopilot run report (Autopilot.run_plan's shape —
        chaos_report plus commit_stall_group_rounds / end_counts /
        actions) into the flight recorder and trace stream; actions and
        safety violations each raise their own events so a healing run
        can be audited from the trace alone."""
        with self._lock:
            entry = {"seq": self._seq, "ts": time.time(),
                     "autopilot": report}
            self._seq += 1
            self._summary_ring.append(entry)
        m = self.metrics
        if m is not None:
            m.trace(
                "autopilot.scenario",
                rounds=report.get("rounds", 0),
                mttr_rounds=report.get("mttr_rounds"),
                commit_stall_group_rounds=report.get(
                    "commit_stall_group_rounds", 0
                ),
                actions=report.get("actions", {}),
            )
            if any(report.get("safety", {}).values()):
                m.trace("autopilot.safety", **report["safety"])
        return entry

    def record_reads(self, report: dict) -> dict:
        """Fold a client-read workload report (workload.read_report's
        shape) into the flight recorder and trace stream; a nonzero
        linearizability (or any safety) count raises a `reads.safety`
        event so a stale-read can never scroll by silently."""
        with self._lock:
            entry = {"seq": self._seq, "ts": time.time(), "reads": report}
            self._seq += 1
            self._summary_ring.append(entry)
        m = self.metrics
        if m is not None:
            m.trace(
                "reads.scenario",
                rounds=report.get("rounds", 0),
                reads_issued=report.get("reads_issued", 0),
                served_lease=report.get("served_lease", 0),
                served_quorum=report.get("served_quorum", 0),
                degraded_serves=report.get("degraded_serves", 0),
                read_p50=report.get("read_p50", -1),
                read_p99=report.get("read_p99", -1),
                leaderless_group_rounds=report.get(
                    "leaderless_group_rounds", 0
                ),
                appends_offered=report.get("appends_offered", 0),
                appends_dropped=report.get("appends_dropped", 0),
                recover_p50_rounds=report.get("recover_p50_rounds", -1),
                recover_p90_rounds=report.get("recover_p90_rounds", -1),
                recover_p99_rounds=report.get("recover_p99_rounds", -1),
            )
            if any(report.get("safety", {}).values()):
                m.trace("reads.safety", **report["safety"])
        return entry

    def record_scenario(self, report: dict) -> dict:
        """Fold a chaos scenario report (chaos_report's shape) into the
        flight recorder and trace stream; safety violations raise a
        `chaos.safety` trace event so they can never scroll by silently."""
        with self._lock:
            entry = {"seq": self._seq, "ts": time.time(), "chaos": report}
            self._seq += 1
            self._summary_ring.append(entry)
        m = self.metrics
        if m is not None:
            m.trace(
                "chaos.scenario",
                rounds=report.get("rounds", 0),
                mttr_rounds=report.get("mttr_rounds"),
                reelections=report.get("reelections", 0),
                max_leaderless_streak=report.get(
                    "max_leaderless_streak", 0
                ),
            )
            if any(report.get("safety", {}).values()):
                m.trace("chaos.safety", **report["safety"])
        return entry

    def record_incident(self, incident: dict) -> dict:
        """Fold a forensics incident (the ISSUE 15 device black-box
        capture: {"slot": name, "count": n, "offenders": [{"group",
        "round"}, ...]}) into the summary ring, emit the
        `forensics.incident` trace event, and bump the
        multiraft_safety_incidents_total{slot} counter by the NEW
        offender count since the slot was last reported (the caller —
        ClusterSim's drain — passes cumulative counts)."""
        with self._lock:
            entry = {"seq": self._seq, "ts": time.time(),
                     "incident": incident}
            self._seq += 1
            self._summary_ring.append(entry)
            # The seen-count read-modify-write shares the ring's lock:
            # two concurrent reporters of the same slot must not both
            # count the same offenders into the metric.
            prev = self._incident_seen.get(incident["slot"], 0)
            delta = max(0, incident.get("count", 0) - prev)
            self._incident_seen[incident["slot"]] = max(
                prev, incident.get("count", 0)
            )
        m = self.metrics
        if m is not None:
            if delta:
                m.safety_incidents.labels(slot=incident["slot"]).inc(delta)
            m.trace(
                "forensics.incident",
                slot=incident["slot"],
                count=incident.get("count", 0),
                offenders=incident.get("offenders", []),
            )
        return entry

    def incidents(self) -> List[dict]:
        """Oldest-to-newest forensics incidents recorded so far."""
        with self._lock:
            return [
                e["incident"] for e in self._summary_ring if "incident" in e
            ]

    def last(self) -> Optional[dict]:
        """Most recent summary-ring entry, or None."""
        with self._lock:
            return self._summary_ring[-1] if self._summary_ring else None

    def summary_ring(self) -> List[dict]:
        """Oldest-to-newest copy of the host summary ring."""
        with self._lock:
            return list(self._summary_ring)

    def __len__(self) -> int:
        with self._lock:
            return len(self._summary_ring)
