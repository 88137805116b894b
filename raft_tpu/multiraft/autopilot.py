"""Fleet autopilot: the closed loop that ACTS on health.

The fleet already elects, replicates, damps, reconfigures, and reports
health under chaos; this module closes the loop: a host-side
DECLARATIVE policy (`AutopilotConfig`: thresholds, per-cadence action
budgets, cooldowns) reads the device-reduced health summary at each drain
cadence and emits batched actions whose ACTUATION is device-resident:

  kick       `sim.step(campaign_kick=)` — RawNode::campaign (MsgHup) at a
             chosen healthy voter of a leaderless group, ending the
             episode at the next cadence instead of waiting out the
             randomized election timeout;
  transfer   `sim.step(transfer_propose=)` — the raft-rs
             MsgTransferLeader / MsgTimeoutNow protocol
             (sim._transfer_phase): moves leadership off an ack-starved
             leader (the asymmetric-partition commit stall that never
             self-heals undamped) and rebalances leader placement against
             skewed workloads ("Paxos vs Raft" names leadership placement
             as THE production lever);
  evacuate   an auto-generated ReconfigPlan (remove the degraded voter,
             add a spare peer) compiled through the PR 10 Changer walk
             and executed by the SAME propose/gate/apply scan as the
             chaos that triggered it — CD-Raft's move-the-group-off-the-
             degraded-site framing.

Execution shape: the chaos horizon runs as cadence-sized donated jitted
segments (`runner.make_runner(..., cadence=)` over runner._runner_body,
so the op protocol, the MTTR/safety folds, and the chaos masks are the
SAME code the reconfig runner uses); between segments the fixed-size
health summary crosses to the host, the policy decides, and the next
segment carries the action planes.  An evacuation decision swaps in the
compiled evacuation schedule for the remaining horizon — reconfig + chaos
in one scan.

Determinism/replay: the loop is fully deterministic — identical plans,
state, and policy knobs reproduce identical actions round-for-round (the
device side is the deterministic sim; the policy reads device-computed
summaries only).  `tools/autopilot_report.py` exploits this for the
before/after CI gate: the autopilot-on corpus replay must beat the
autopilot-off replay on MTTR and commit-stall with zero safety
violations.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Set, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from . import chaos as chaos_mod
from . import kernels
from . import runner as runner_mod
from .reconfig import (
    N_RECONFIG_STATS,
    ReconfigPhase,
    ReconfigPlan,
    compile_plan,
    empty_reconfig_schedule,
    init_reconfig_state,
)

__all__ = [
    "Autopilot",
    "AutopilotConfig",
]


class AutopilotConfig(NamedTuple):
    """Declarative autopilot policy: thresholds, budgets, cooldowns.

    The policy is HOST-side and pure — it maps one health summary (plus
    on-demand `explain()` columns for the worst offenders) to at most
    `max_*` actions per cadence; actuation is device-resident.
    """

    # Rounds between health reads / action batches (the drain cadence).
    cadence: int = 8
    # Campaign kick: a leaderless group whose HP_LEADERLESS plane is at or
    # over the threshold gets a MsgHup at its best-cursor voter.
    kick: bool = True
    kick_leaderless_ticks: int = 2
    max_kicks: int = 8
    # Leader transfer: a group with an alive leader whose commit has been
    # flat for the threshold gets its leadership transferred to the
    # best-cursor follower voter (the ack-starved-leader heal).
    transfer: bool = True
    transfer_stall_ticks: int = 6
    max_transfers: int = 8
    # Evacuation: when >= evac_min_groups of the inspected worst offenders
    # implicate the SAME degraded voter, those groups' configs are walked
    # off it (remove-voter + add a spare peer) through the PR 10 reconfig
    # protocol.  Off by default: it needs spare peers and is the heaviest
    # action.
    evacuate: bool = False
    evac_stall_ticks: int = 12
    evac_min_groups: int = 2
    # Leader-placement balancing against a skewed workload (the Zipf
    # hot-region regime): when on, each cadence ALSO spends up to
    # max_balance_transfers moving the
    # heaviest groups off the most-loaded leader peer onto each group's
    # least-loaded voter — "Paxos vs Raft" names leadership placement as
    # the production lever, and this is its closed-loop form.  Needs the
    # per-group workload weights (run_plan's `append` plane).
    balance: bool = False
    max_balance_transfers: int = 4
    # Rounds before the policy may act on the same group again (actions
    # take a cadence to show up in the health planes).
    cooldown: int = 8

    def validate(self) -> "AutopilotConfig":
        if self.cadence < 1:
            raise ValueError("cadence must be >= 1")
        if self.cooldown < 0:
            raise ValueError("cooldown must be >= 0")
        return self


class Autopilot:
    """The closed loop: drive a ClusterSim through a chaos plan in cadence
    segments, reading health and issuing batched heal actions between
    them.  The sim must be built with SimConfig(collect_health=True,
    transfer=True).

    `monitor` (an optional multiraft.health.HealthMonitor) receives the
    per-cadence summaries and the final report; `metrics` (an optional
    raft_tpu.metrics.Metrics) gets `autopilot.action` trace events, the
    multiraft_autopilot_actions_total{kind} counters, and the
    health_groups_transfer_pending gauge.
    """

    def __init__(
        self,
        sim,
        cfg: AutopilotConfig = AutopilotConfig(),
        monitor=None,
        metrics=None,
        fused: bool = False,
    ):
        self.sim = sim
        self.cfg = cfg.validate()
        self.monitor = (
            monitor
            if monitor is not None
            else getattr(sim, "health_monitor", None)
        )
        self.metrics = metrics
        self.fused = fused
        self._cooldown_until: Dict[int, int] = {}
        # Per-group retry counter shared by kicks AND transfers: the
        # policy cannot see liveness, so repeated attempts on the same
        # group rotate through the target ranking instead of
        # deterministically re-picking a dead best-cursor peer forever.
        self._retry_rotation: Dict[int, int] = {}
        self._evacuated: Set[int] = set()
        self._runners: Dict[Tuple, object] = {}
        self.actions_taken = {"kicks": 0, "transfers": 0, "evacuations": 0}

    # --- policy -----------------------------------------------------------

    def _emit(self, kind: str, n: int, round_idx: int, detail) -> None:
        self.actions_taken[kind] += n
        m = self.metrics
        if m is not None and n:
            m.autopilot_actions.labels(kind=kind).inc(n)
            m.trace(
                "autopilot.action", kind=kind, n=n, round=round_idx,
                detail=detail,
            )

    @staticmethod
    def _acting_leader_of(info: dict) -> int:
        """The acting leader from the per-peer role/term columns (state
        == Leader at the highest term, lowest index tie) — NOT from the
        leader_id views, which go stale on partitioned peers (a stale
        view naming an ex-leader would mis-exclude the transfer
        target)."""
        peers = info["peers"]
        best = 0
        best_term = -1
        for p, (role, term) in enumerate(
            zip(peers["state"], peers["term"])
        ):
            if role == kernels.ROLE_LEADER and term > best_term:
                best, best_term = p + 1, term
        return best

    def _ranked_target(
        self, info: dict, exclude: int = 0, attempt: int = 0
    ) -> int:
        """The healthiest-looking VOTER target: ranked by
        (last_index, commit, -peer_id) cursor over the group's voters
        (learners and removed peers are never valid transfer/kick
        targets), skipping `exclude`; `attempt` rotates through the
        ranking across retries — the policy cannot see liveness, and the
        best-looking cursor may belong to the crashed peer."""
        peers = info["peers"]
        voter = peers.get("voter", [True] * len(peers["last_index"]))
        ranked = sorted(
            (
                (-li, -c, p + 1)
                for p, (li, c) in enumerate(
                    zip(peers["last_index"], peers["commit"])
                )
                if p + 1 != exclude and voter[p]
            ),
        )
        if not ranked:
            return 0
        return ranked[attempt % len(ranked)][2]

    def _decide(
        self, summary: dict, round_idx: int
    ) -> Tuple[np.ndarray, np.ndarray, List[dict]]:
        """Map one health summary to this cadence's action planes.
        Returns (transfer[G] int32, kick[P, G] bool, inspected) where
        `inspected` carries each worst offender's explain() columns for
        the evacuation policy (which needs cross-group evidence)."""
        c = self.cfg
        G = self.sim.cfg.n_groups
        P = self.sim.cfg.n_peers
        transfer = np.zeros((G,), np.int32)
        kick = np.zeros((P, G), bool)
        kicks = transfers = 0
        inspected: List[dict] = []
        for w in summary.get("worst", ()):
            g, score = w["group"], w["score"]
            if score <= 0:
                continue
            info = self.sim.explain(g)
            inspected.append(info)
            if self._cooldown_until.get(g, -1) > round_idx:
                continue
            hp = info["health"]
            lead = self._acting_leader_of(info)
            if (
                c.kick
                and kicks < c.max_kicks
                and hp["leaderless_ticks"] >= c.kick_leaderless_ticks
            ):
                attempt = self._retry_rotation.get(g, 0)
                target = self._ranked_target(info, attempt=attempt)
                if target:
                    self._retry_rotation[g] = attempt + 1
                    kick[target - 1, g] = True
                    kicks += 1
                    self._cooldown_until[g] = round_idx + c.cooldown
            elif (
                c.transfer
                and transfers < c.max_transfers
                and lead > 0
                and hp["leaderless_ticks"] == 0
                and hp["ticks_since_commit"] >= c.transfer_stall_ticks
            ):
                attempt = self._retry_rotation.get(g, 0)
                target = self._ranked_target(
                    info, exclude=lead, attempt=attempt
                )
                if target:
                    self._retry_rotation[g] = attempt + 1
                    transfer[g] = target
                    transfers += 1
                    self._cooldown_until[g] = round_idx + c.cooldown
        self._emit("kicks", kicks, round_idx, int(kick.sum()))
        self._emit("transfers", transfers, round_idx,
                   [int(g) for g in np.flatnonzero(transfer)])
        return transfer, kick, inspected

    def balance_transfers(
        self,
        weights=None,
        budget: Optional[int] = None,
        round_idx: int = 0,
        transfer: Optional[np.ndarray] = None,
        crashed=None,
    ) -> np.ndarray:
        """Leader-placement rebalance: greedily move the heaviest groups
        off the most-loaded leader peer onto each group's least-loaded
        OTHER voter, while the move strictly improves the pairwise load
        gap.  Loads are weighted per group (`weights`, default 1s — pass
        the workload's append plane); leader placement comes from the
        device reduction kernels.acting_leader_id, downloaded once
        (int32[G]).  `crashed` (optional bool[P, G]) excludes dead peers
        from the placement read — run_plan passes the upcoming round's
        chaos crash plane so a crashed stale leader is never load-counted
        or picked as a move's src/dst.  Returns the transfer-command
        plane (int32[G]), extending `transfer` if given; budgeted and
        cooldown-aware like every other action."""
        sim = self.sim
        G, P = sim.cfg.n_groups, sim.cfg.n_peers
        budget = (
            self.cfg.max_balance_transfers if budget is None else budget
        )
        out = (
            np.zeros((G,), np.int32) if transfer is None else transfer
        )
        if budget <= 0:
            return out
        if crashed is None:
            crashed = jnp.zeros((P, G), bool)
        # graftcheck: allow-no-host-sync-in-jit — cadence-boundary policy
        # reads (one int32[G] row + the voter masks), outside every
        # jitted segment.
        lead, vm, dead = jax.device_get(
            (
                kernels.acting_leader_id(
                    sim.state.state,
                    sim.state.term,
                    jnp.asarray(crashed, dtype=bool),
                ),
                sim.state.voter_mask,
                jnp.asarray(crashed, dtype=bool),
            )
        )
        if weights is None:
            w = np.ones((G,), np.int64)
        else:
            # graftcheck: allow-no-host-sync-in-jit — host-side policy
            # input (run_plan hands the pre-downloaded workload plane).
            w = np.asarray(weights, np.int64)
        load = np.zeros((P,), np.int64)
        for p in range(P):
            load[p] = int(w[lead == p + 1].sum())
        moves = 0
        moved_groups = []
        # Heaviest groups first: one pass is enough per cadence — the
        # next cadence re-reads placement and continues.
        for g in np.argsort(-w, kind="stable"):
            if moves >= budget:
                break
            src = int(lead[g])
            if src == 0 or out[g]:
                continue
            if self._cooldown_until.get(int(g), -1) > round_idx:
                continue
            others = [
                q + 1
                for q in range(P)
                if vm[q, g] and q + 1 != src and not dead[q, g]
            ]
            if not others:
                continue
            dst = min(others, key=lambda q: (load[q - 1], q))
            # Strict improvement: moving w[g] must shrink the src/dst gap.
            if load[src - 1] - load[dst - 1] <= int(w[g]):
                continue
            out[g] = dst
            load[src - 1] -= int(w[g])
            load[dst - 1] += int(w[g])
            self._cooldown_until[int(g)] = round_idx + self.cfg.cooldown
            moved_groups.append(int(g))
            moves += 1
        self._emit("transfers", moves, round_idx, {"balance": moved_groups})
        return out

    def _decide_evacuation(
        self, inspected: List[dict], round_idx: int, horizon: int
    ) -> Optional[ReconfigPlan]:
        """Cross-group evacuation policy: when enough of the inspected
        worst offenders show the SAME voter lagging far behind its
        group's max cursor, generate the remove+add plan for the affected
        groups (each group is evacuated at most once per run — the
        Changer chain walk starts from the bootstrap config)."""
        c = self.cfg
        if not c.evacuate or round_idx + 2 >= horizon:
            return None
        sim = self.sim
        P = sim.cfg.n_peers
        # graftcheck: allow-no-host-sync-in-jit — cadence-boundary policy
        # read of two [P, G] bool masks, outside every jitted segment.
        vm, lm = jax.device_get(
            (sim.state.voter_mask, sim.state.learner_mask)
        )
        suspects: Dict[int, List[int]] = {}
        for info in inspected:
            g = info["group"]
            if g in self._evacuated:
                continue
            if info["health"]["ticks_since_commit"] < c.evac_stall_ticks:
                continue
            cursors = info["peers"]["commit"]
            hi = max(cursors)
            for p in range(P):
                if vm[p, g] and hi - cursors[p] >= c.evac_stall_ticks:
                    suspects.setdefault(p + 1, []).append(g)
        for peer, groups in sorted(suspects.items()):
            groups = [
                g for g in groups
                if not vm.T[g].all()  # a spare peer must exist
            ]
            if len(groups) < c.evac_min_groups:
                continue
            # One uniform spare for the plan: the lowest peer id outside
            # every selected group's config (bootstrap configs are
            # uniform; per-group spares would need per-group chains).
            spare = 0
            for q in range(1, P + 1):
                if all(
                    not vm[q - 1, g] and not lm[q - 1, g] for g in groups
                ):
                    spare = q
                    break
            if not spare:
                continue
            voters = [p + 1 for p in range(P) if vm[p, groups[0]]]
            learners = [p + 1 for p in range(P) if lm[p, groups[0]]]
            self._evacuated.update(groups)
            self._emit(
                "evacuations", len(groups), round_idx,
                {"peer": peer, "spare": spare, "groups": groups},
            )
            return ReconfigPlan(
                name=f"autopilot-evac-p{peer}",
                n_peers=P,
                voters=voters,
                learners=learners,
                phases=[
                    ReconfigPhase(rounds=round_idx),
                    ReconfigPhase(
                        rounds=1,
                        op={
                            "enter_joint": [
                                {"remove": peer},
                                {"add": spare},
                            ]
                        },
                        groups=groups,
                    ),
                    ReconfigPhase(
                        rounds=horizon - round_idx - 1,
                        op={"leave_joint": True},
                        groups=groups,
                    ),
                ],
            )
        return None

    # --- the loop ---------------------------------------------------------

    def _runner_for(self, compiled, chaos_compiled, rounds: int):
        # Schedule arrays enter the jit as runtime arguments (GC012), so
        # one compiled runner serves every plan with the same SHAPES and
        # the same trace-time static (CompiledChaos.lossless selects the
        # round's program: a lossless plan's runner draws no loss sample
        # and must never serve a plan with a loss rate) — the key holds
        # nothing else on purpose (an evacuation swap recompiles once,
        # later swaps with the same op count reuse it).
        key = (
            rounds,
            tuple(compiled.op_start.shape),
            tuple(compiled.append.shape),
            compiled.phase_of_round.shape[0],
            chaos_compiled.lossless,
        )
        r = self._runners.get(key)
        if r is None:
            # The fused fast path only pays off at the full cadence
            # length (a remainder segment would compile its own Pallas
            # kernel for one use).
            r = runner_mod.make_runner(
                self.sim.cfg, (compiled, chaos_compiled), cadence=rounds,
                fused=self.fused and rounds == self.cfg.cadence,
            )
            self._runners[key] = r
        return r

    def run_plan(self, chaos_plan=None, append=None) -> dict:
        """Drive the attached sim through `chaos_plan` (default: the
        sim's) with the closed loop ON; returns the autopilot report
        (HealthMonitor.autopilot_report's shape).  The sim's state and
        health planes advance in place, exactly as run_plan would move
        them — plus whatever healing the autopilot achieved.

        `append` (optional int32[G]) is a per-GROUP workload plane ADDED
        to every round's chaos-phase append (a Zipf hot-region
        workload, say); None keeps the plan's own workload only."""
        sim = self.sim
        scfg = sim.cfg
        G, P = scfg.n_groups, scfg.n_peers
        plan = chaos_plan if chaos_plan is not None else sim._chaos
        if plan is None:
            raise ValueError("no chaos plan; pass one or attach via chaos=")
        if isinstance(plan, chaos_mod.CompiledChaos):
            chaos_compiled = plan
        else:
            chaos_compiled = chaos_mod.compile_plan(plan, G)
        R = chaos_compiled.n_rounds
        compiled = empty_reconfig_schedule(R, P, G)
        append_host = None
        if append is not None:
            # graftcheck: allow-no-host-sync-in-jit — one-time host copy
            # of the caller's workload plane for the balance policy,
            # before any jitted segment runs.
            append_host = np.asarray(append, dtype=np.int64)
            append = jnp.asarray(append, dtype=jnp.int32)
            compiled = compiled._replace(
                append=compiled.append + append[None, :]
            )
        rst = init_reconfig_state(sim.state)
        hl = sim._require_health()
        stats = jnp.zeros((chaos_mod.N_CHAOS_STATS,), jnp.int32)
        rstats = jnp.zeros((N_RECONFIG_STATS,), jnp.int32)
        safety = jnp.zeros((kernels.N_SAFETY,), jnp.int32)
        csr = jnp.int32(0)
        st = sim.state
        bb = sim._blackbox
        transfer = np.zeros((G,), np.int32)
        kick = np.zeros((P, G), bool)
        done = 0
        fused_rounds = 0
        while done < R:
            seg = min(self.cfg.cadence, R - done)
            runner = self._runner_for(compiled, chaos_compiled, seg)
            # The flat runtime-arg tuple comes from the registry
            # (schedules.py via runner.schedule_args) — never hand-listed
            # (GC018).
            sched_args = runner_mod.schedule_args(compiled, chaos_compiled)
            out = runner(
                st, hl, rst, stats, rstats, safety,
                *((bb,) if bb is not None else ()),
                csr,
                jnp.int32(done),
                jnp.asarray(transfer, dtype=jnp.int32),
                jnp.asarray(kick, dtype=bool),
                *sched_args,
            )
            if bb is not None:
                (
                    st, hl, rst, stats, rstats, safety, bb, csr,
                    seg_fused,
                ) = out
                sim._blackbox = bb
            else:
                st, hl, rst, stats, rstats, safety, csr, seg_fused = out
            if self.fused:
                # graftcheck: allow-no-host-sync-in-jit — one int32
                # scalar per cadence segment, outside the jitted scans.
                fused_rounds += int(jax.device_get(seg_fused))
            sim.state, sim._health = st, hl
            done += seg
            if done >= R:
                break
            # Drain cadence: the fixed-size summary crosses to the host,
            # the policy decides the next segment's action planes.
            summary = sim._health_summary_dict()
            if self.monitor is not None:
                self.monitor.record(summary)
            transfer, kick, inspected = self._decide(summary, done)
            if self.cfg.balance:
                # The upcoming round's crash plane (gathered from the
                # compiled schedule) keeps the placement read honest: a
                # crashed stale leader is neither load-counted nor
                # eligible as a move endpoint.  schedule_planes skips the
                # loss knockout schedule_masks would draw and discard.
                _, _, crash_next, _ = chaos_mod.schedule_planes(
                    chaos_compiled, jnp.int32(done)
                )
                transfer = self.balance_transfers(
                    weights=append_host, round_idx=done,
                    transfer=transfer, crashed=crash_next,
                )
            if self.metrics is not None:
                # graftcheck: allow-no-host-sync-in-jit — one int32
                # scalar at the cadence boundary, outside the segments.
                pending = jax.device_get(
                    jnp.sum(st.transferee > 0, dtype=jnp.int32)
                )
                self.metrics.health_transfer_pending.set(int(pending))
            evac = self._decide_evacuation(inspected, done, R)
            if evac is not None:
                compiled = compile_plan(evac, G)
                if append is not None:
                    compiled = compiled._replace(
                        append=compiled.append + append[None, :]
                    )
                rst = init_reconfig_state(st)
        # Tail audit, exactly the scan runners': a final-round apply's mask
        # transition is checked one extra fold later.
        if bb is not None:
            viol = kernels.check_safety_groups(
                st.state, st.term, st.commit, st.last_index, st.agree,
                st.commit,
                voter_mask=st.voter_mask,
                outgoing_mask=st.outgoing_mask,
                matched=st.matched,
                prev_voter_mask=rst.prev_voter,
                prev_outgoing_mask=rst.prev_outgoing,
            )
            safety = safety + jnp.sum(viol, axis=1, dtype=jnp.int32)
            meta, trip = kernels.blackbox_mark(
                bb.meta, bb.trip_round, bb.round_idx, viol
            )
            sim._blackbox = bb._replace(meta=meta, trip_round=trip)
        else:
            safety = safety + kernels.check_safety(
                st.state, st.term, st.commit, st.last_index, st.agree,
                st.commit,
                voter_mask=st.voter_mask,
                outgoing_mask=st.outgoing_mask,
                matched=st.matched,
                prev_voter_mask=rst.prev_voter,
                prev_outgoing_mask=rst.prev_outgoing,
            )
        from .health import HealthMonitor

        # graftcheck: allow-no-host-sync-in-jit — end-of-run download of
        # fixed-size stat vectors, outside the jitted segments.
        stats_h, safety_h, csr_h = jax.device_get((stats, safety, csr))
        report = HealthMonitor.chaos_report(stats_h, safety_h, R)
        report["commit_stall_group_rounds"] = int(csr_h)
        end = sim._health_summary_dict()
        report["end_counts"] = end["counts"]
        report["actions"] = dict(self.actions_taken)
        if self.fused:
            total = R * G
            report["fused_rounds"] = fused_rounds
            report["total_rounds"] = total
            report["fused_frac"] = round(fused_rounds / total, 4)
        if self.monitor is not None:
            self.monitor.record_autopilot(report)
        return report
