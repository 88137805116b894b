"""ScalarCluster: the lockstep parity oracle for ClusterSim.

Runs G groups × P real scalar `Raft` instances through the harness Network's
persist-before-send pump, one protocol round at a time, with the same
(node, term)-keyed deterministic timeouts as the device sim.  A round is:
tick every peer (in peer order) → pump to quiescence → propose the round's
append workload at the acting leader → pump.

Commit-index parity between this and ClusterSim on identical crash/append
schedules is THE correctness claim of the batched backend (BASELINE.json's
"bit-identical commit indices").

The oracle family layers on top of ScalarCluster: HealthOracle folds the
numpy twin of the device health planes each round; ChaosOracle replays a
compiled fault schedule (chaos.HostSchedule) through it; TransferOracle
(ISSUE 12) drives the real RawNode::transfer_leader pump as a pre-tick
phase; ReadOracle (ISSUE 13) drives the real ReadOnlyOption::LeaseBased
and Safe read pumps on throwaway deep copies for per-round receipt
parity with sim.step(read_propose=); ReconfigOracle (ISSUE 10) walks a
compiled membership-churn schedule (reconfig.HostReconfigSchedule) —
proposing real conf entries, gating on the dual-majority commit, and
applying the Changer-computed config by scalar surgery — the exact twin
of the reconfig runner's scan (runner._runner_body).
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

from ..config import Config
from ..eraftpb import ConfState, Entry, Message, MessageType
from ..raft import StateRole
from ..raft_log import NO_LIMIT
from ..storage import MemStorage
from ..harness import Interface, Network


class ScalarCluster:
    def __init__(self, n_groups: int, n_peers: int, election_tick: int = 10,
                 heartbeat_tick: int = 1, voters=None, voters_outgoing=None,
                 learners=None, check_quorum: bool = False,
                 pre_vote: bool = False, metrics=None,
                 timeout_seed_base: int = 0,
                 max_inflight_msgs: int = 1 << 20):
        """`voters`/`voters_outgoing`/`learners` (peer-id lists) bootstrap
        every group in that (possibly joint) configuration; default: all
        peers voters.  `check_quorum`/`pre_vote` configure every Raft the
        reference way (raft.rs Config); since ISSUE 7 the device sim
        models both (SimConfig.check_quorum / pre_vote route rounds
        through the damped wave path), so damped parity schedules set the
        SAME flags on both sides (tests/test_damping_parity.py) while the
        undamped suites keep both False.  `metrics` (an optional
        raft_tpu.metrics.Metrics) is shared by every Raft in the cluster —
        the scalar side of the device counter-plane parity test.
        `timeout_seed_base` offsets every group's timeout_seed (group g
        draws from stream timeout_seed_base + g): the forensics one-group
        repro (raft_tpu/multiraft/forensics.py) replays GLOBAL group id g
        as a 1-group cluster on stream g, bit-identical to the fleet.
        `max_inflight_msgs` is every Progress's Inflights window, which the
        port preallocates: the default is effectively unbounded and costs
        8 MB a Progress (13 GB and minutes to build at G = 64, P = 5); a
        whole-fleet replay passes a window its plan cannot fill (1 << 14,
        the `_TWIN_CAP` argument below)."""
        self.n_groups = n_groups
        self.n_peers = n_peers
        self.networks: List[Network] = []
        for g in range(n_groups):
            config = Config(
                election_tick=election_tick,
                heartbeat_tick=heartbeat_tick,
                max_size_per_msg=NO_LIMIT,
                max_inflight_msgs=max_inflight_msgs,
                timeout_seed=timeout_seed_base + g,
                check_quorum=check_quorum,
                pre_vote=pre_vote,
                metrics=metrics,
            )
            if voters is None:
                peers: List[Optional[Interface]] = [None] * n_peers
                self.networks.append(Network.new_with_config(peers, config))
            else:
                from ..raft import Raft

                ifaces = []
                for id in range(1, n_peers + 1):
                    cs = ConfState(
                        voters=list(voters),
                        voters_outgoing=list(voters_outgoing or []),
                        learners=list(learners or []),
                    )
                    store = MemStorage.new_with_conf_state(cs)
                    cfg = Config(**{**config.__dict__, "id": id})
                    ifaces.append(Interface(Raft(cfg, store)))
                self.networks.append(
                    Network.new_with_config(ifaces, config)
                )

    def _apply_crash_mask(
        self,
        net: Network,
        crashed_row: Sequence[bool],
        link_row: Optional[np.ndarray] = None,
    ) -> None:
        """Install the round's faults as per-edge drops: whole-peer crashes
        (isolation) plus, when a `link_row[P, P]` reachability matrix is
        given, a 1.0 drop on every down DIRECTED link — the scalar half of
        the chaos engine's link plane (sim.step's `link=`)."""
        net.recover()
        for p, c in enumerate(crashed_row):
            if c:
                net.isolate(p + 1)
        if link_row is not None:
            for a in range(self.n_peers):
                for b in range(self.n_peers):
                    if a != b and not link_row[a, b]:
                        net.drop(a + 1, b + 1, 1.0)

    def round(self, crashed: Optional[np.ndarray] = None,
              append_n: Optional[np.ndarray] = None,
              link: Optional[np.ndarray] = None,
              conf_propose: Optional[np.ndarray] = None,
              kick: Optional[np.ndarray] = None):
        """One lockstep protocol round across all groups.

        crashed:  bool[G, P] whole-peer isolation for the round.
        append_n: int[G] workload proposed at each group's acting leader.
        link:     optional bool[P, P, G] directed reachability (peer-major
                  src/dst axes, like the device plane); a down link drops
                  every message on that edge for the whole round.
        conf_propose: optional bool[G] — groups whose pending conf-change
                  op proposes its entry this round (the scalar twin of
                  sim.step's reconfig_propose): ONE extra entry joins the
                  group's propose batch, appended LAST.  Returns a list of
                  per-group (owner, index, term) records — the acting
                  leader's id, the conf entry's log index, and the
                  leader's term at propose time, or (0, 0, 0) where no
                  alive leader acted — mirroring sim.ReconfigProposal
                  bit-for-bit.  Returns None when conf_propose is None.
        kick:     optional bool[G, P] — the autopilot campaign kick (the
                  scalar twin of sim.step's campaign_kick): a MsgHup
                  stepped at the peer right after its tick, i.e. the
                  RawNode::campaign admin call.  A kick lands only when
                  the peer's own election timer did NOT fire this tick
                  (the device ORs the two into one campaign), and MsgHup
                  itself enforces the leader/promotable gates (hup()).
        """
        if crashed is None:
            crashed = np.zeros((self.n_groups, self.n_peers), dtype=bool)
        if append_n is None:
            append_n = np.zeros((self.n_groups,), dtype=np.int64)
        props = (
            None
            if conf_propose is None
            else [(0, 0, 0)] * self.n_groups
        )
        for g, net in enumerate(self.networks):
            self._apply_crash_mask(
                net, crashed[g], None if link is None else link[:, :, g]
            )
            # Tick every peer in peer order, collecting outbound messages
            # with the pump's persist-before-send discipline.
            initial: List[Message] = []
            for p in range(1, self.n_peers + 1):
                peer = net.peers[p]
                fired = (
                    peer.raft.state != StateRole.Leader
                    and peer.raft.promotable
                    and peer.raft.election_elapsed + 1
                    >= peer.raft.randomized_election_timeout
                )
                peer.raft.tick()
                if kick is not None and bool(kick[g][p - 1]) and not fired:
                    peer.raft.step(
                        Message(msg_type=MessageType.MsgHup, from_=p, to=p)
                    )
                peer.persist()
                initial.extend(net.filter(peer.read_messages()))
            net.send(initial)
            # Propose the append workload at the acting leader (the alive
            # leader with the highest term).
            n = int(append_n[g])
            extra = conf_propose is not None and bool(conf_propose[g])
            total = n + (1 if extra else 0)
            if total > 0:
                lead = self.acting_leader(g, crashed[g])
                if lead is not None:
                    if extra:
                        # The conf entry's landing spot, captured BEFORE
                        # the propose pump (the leader appends the batch
                        # first thing; later traffic in the pump can
                        # depose it but never unappend) — matches the
                        # device extra's workload-stage snapshot.
                        r = net.peers[lead].raft
                        props[g] = (
                            lead,
                            r.raft_log.last_index() + total,
                            r.term,
                        )
                    ents = [Entry(data=b"x") for _ in range(total)]
                    net.send([
                        Message(
                            msg_type=MessageType.MsgPropose,
                            from_=lead,
                            to=lead,
                            entries=ents,
                        )
                    ])
        return props

    def acting_leader(self, g: int, crashed_row: Sequence[bool]) -> Optional[int]:
        best = None
        best_term = -1
        for p in range(1, self.n_peers + 1):
            if crashed_row[p - 1]:
                continue
            r = self.networks[g].peers[p].raft
            if r.state == StateRole.Leader and r.term > best_term:
                best, best_term = p, r.term
        return best

    # --- state extraction for parity comparison ---

    def snapshot(self) -> dict:
        G, P = self.n_groups, self.n_peers
        out = {
            k: np.zeros((G, P), dtype=np.int64)
            for k in ("term", "state", "commit", "last_index", "last_term")
        }
        for g in range(G):
            for p in range(P):
                r = self.networks[g].peers[p + 1].raft
                out["term"][g, p] = r.term
                out["state"][g, p] = r.state
                out["commit"][g, p] = r.raft_log.committed
                out["last_index"][g, p] = r.raft_log.last_index()
                out["last_term"][g, p] = r.raft_log.last_term()
        return out


def host_pack_bits_g(plane: np.ndarray) -> np.ndarray:
    """Numpy twin of kernels.pack_bits_g: pack a bool plane 32:1 along its
    LAST (group) axis into uint32 words (word w's bit j = group 32*w + j,
    zero-padded past G).  The GC010 oracle for the recent_active
    scan-carry packing — tests/test_multiraft_kernels.py asserts bit-exact
    equality with the device kernel at awkward widths."""
    plane = np.asarray(plane, dtype=bool)
    g = plane.shape[-1]
    n_words = (g + 31) // 32
    pad = n_words * 32 - g
    bits = plane.astype(np.uint32)
    if pad:
        bits = np.pad(bits, [(0, 0)] * (bits.ndim - 1) + [(0, pad)])
    bits = bits.reshape(plane.shape[:-1] + (n_words, 32))
    lanes = np.arange(32, dtype=np.uint32)
    return (bits << lanes).sum(axis=-1).astype(np.uint32)


def host_unpack_bits_g(words: np.ndarray, g: int) -> np.ndarray:
    """Numpy twin of kernels.unpack_bits_g (inverse of host_pack_bits_g)."""
    words = np.asarray(words, dtype=np.uint32)
    lanes = np.arange(32, dtype=np.uint32)
    bits = (words[..., :, None] >> lanes) & np.uint32(1)
    flat = bits.reshape(words.shape[:-1] + (words.shape[-1] * 32,))
    return flat[..., :g] != 0


# Throwaway-clone Inflights window (slots).  Real harness clusters run
# max_inflight_msgs = 1 << 20 ("effectively unbounded"); clones carry a
# rebased ring of this size instead so a clone costs microseconds, not
# a 1M-slot buffer alloc per progress — see _seed_clone_memo.
_TWIN_CAP = 1 << 14


def _seed_clone_memo(net, memo: dict) -> dict:
    """Seed a deepcopy memo for one group's Network so the copy is exact
    AND cheap: per-store RLocks (unpicklable — a naive deepcopy raises)
    are re-seeded fresh, a shared metrics registry is dropped so the
    clone's pumps can never double-count the live cluster's events, and
    each Inflights ring — its buffer a flat int list preallocated to
    max_inflight_msgs (1 << 20 in the harness config), ~10M interned
    ints per network — is seeded with a rebased twin carrying only the
    LIVE window [start, start+count): slots outside it are never read
    before being overwritten (inflights.py's ring discipline), so the
    twin is observationally exact while skipping the full-buffer copies
    that made naive clones cost seconds each."""
    import threading

    for iface in net.peers.values():
        r = iface.raft
        if r is None:
            continue
        store = getattr(r.raft_log, "store", None)
        lock = getattr(store, "_lock", None)
        if lock is not None:
            memo[id(lock)] = threading.RLock()
        if r.metrics is not None:
            memo[id(r.metrics)] = None
        for _, pr in r.prs.iter():
            ins = pr.ins
            # Rebase the twin to start=0 on a small ring: only the live
            # window is observable (slots outside [start, start+count)
            # are never read before being overwritten), and the ONLY cap
            # dependence is full() at count == cap — unreachable below
            # _TWIN_CAP for any harness schedule (≤ a few hundred
            # in-flight appends even across a 110-round fuzz run with a
            # crashed follower).  A genuine backlog falls back to the
            # real window so full()-parity can never silently change.
            tcap = min(ins.cap, _TWIN_CAP)
            if ins.count > tcap // 4:
                tcap = ins.cap
            twin = type(ins)(tcap)
            twin.count = ins.count
            for i in range(ins.count):
                twin.buffer[i] = ins.buffer[(ins.start + i) % ins.cap]
            memo[id(ins)] = twin
    return memo


def clone_cluster(obj):
    """Memo-seeded deepcopy of a ScalarCluster — or of any oracle
    holding one as `.cluster` — in milliseconds where a naive deepcopy
    costs ~16s per clone (ROADMAP's standing tier-1 constraint) or
    aborts outright on the stores' RLocks.  The parity suites use this
    to settle ONE master cluster per configuration module-scoped and
    hand every test its own throwaway copy instead of re-running the
    settle; ReadOracle's per-probe `_clone_group` is the single-network
    special case of the same memo seeding."""
    import copy

    cluster = getattr(obj, "cluster", obj)
    memo: dict = {}
    for net in cluster.networks:
        _seed_clone_memo(net, memo)
    return copy.deepcopy(obj, memo)


class HealthOracle:
    """Scalar-side oracle for the device health planes (sim.HealthState).

    Maintains the same four per-group int32 planes — leaderless_ticks,
    ticks_since_commit, term_bumps_in_window, vote_splits (row order
    kernels.HP_*) — from OBSERVABLE scalar-cluster state, with the
    bit-identical fold rules of kernels.update_health:

      * has_leader:      some alive peer ends the round as Leader;
      * commit_advanced: the group's max commit index grew this round;
      * term_bump:       growth of the group's max term this round;
      * campaigned:      some peer's election timer fires this round —
                         computed BEFORE the round from the same facts as
                         kernels.tick_kernel (not-leader & promotable &
                         election_elapsed + 1 >= randomized timeout,
                         reference: raft.rs:1037-1047);
      * won:             some peer became leader during the round (Leader
                         at round end with a new term or a non-Leader
                         pre-round role — become_leader is the only path);
      * vote_split:      campaigned and nobody won.

    tests/test_health_parity.py asserts exact per-round equality of these
    planes against ClusterSim's device-maintained HealthState.

    This class is the resolved GC010 oracle symbol for the health kernels
    (tools/graftcheck/parity_obligations.json: zero_health/update_health
    -> simref.HealthOracle); renaming it or its `round` entry point is an
    obligation change and must go through `make obligations`.
    """

    def __init__(self, cluster: ScalarCluster, window: int = 32):
        self.cluster = cluster
        G = cluster.n_groups
        self.planes = np.zeros((4, G), dtype=np.int32)
        self.window = window
        self.window_pos = 0

    def _capture(self):
        G, P = self.cluster.n_groups, self.cluster.n_peers
        from ..raft import StateRole

        state = np.zeros((G, P), dtype=np.int64)
        term = np.zeros((G, P), dtype=np.int64)
        commit = np.zeros((G, P), dtype=np.int64)
        for g in range(G):
            for p in range(P):
                r = self.cluster.networks[g].peers[p + 1].raft
                state[g, p] = int(r.state)
                term[g, p] = r.term
                commit[g, p] = r.raft_log.committed
        return state, term, commit, int(StateRole.Leader)

    def _pre_round(self, crashed, link) -> None:
        """Hook between the pre-round capture and the want_campaign read:
        the TransferOracle's pre-tick transfer pump runs here (the device
        twin, sim._transfer_phase, runs before the round's ticks, so the
        tick-time campaign facts must be read AFTER it).  No-op here."""

    def round(self, crashed=None, append_n=None, link=None,
              conf_propose=None, kick=None):
        """Drive one cluster round and fold its health facts into the
        planes (the scalar twin of sim.step's health extra).  `link` is
        the optional bool[P, P, G] chaos reachability plane,
        `conf_propose` the optional bool[G] conf-entry propose mask, and
        `kick` the optional bool[G, P] campaign-kick mask, all passed
        through to ScalarCluster.round; returns its proposal records
        (None unless conf_propose is given).  A kicked campaign joins the
        `campaigned` health fact exactly like the device fold (the kick
        IS a campaign() call)."""
        G, P = self.cluster.n_groups, self.cluster.n_peers
        if crashed is None:
            crashed = np.zeros((G, P), dtype=bool)
        pre_state, pre_term, pre_commit, leader_code = self._capture()
        self._pre_round(crashed, link)
        want_campaign = np.zeros((G, P), dtype=bool)
        for g in range(G):
            for p in range(P):
                r = self.cluster.networks[g].peers[p + 1].raft
                want_campaign[g, p] = (
                    int(r.state) != leader_code
                    and r.promotable
                    and (
                        r.election_elapsed + 1
                        >= r.randomized_election_timeout
                        or (kick is not None and bool(kick[g][p]))
                    )
                )

        props = self.cluster.round(
            crashed, append_n, link, conf_propose, kick=kick
        )

        post_state, post_term, post_commit, _ = self._capture()
        alive = ~np.asarray(crashed, dtype=bool)
        has_leader = np.any((post_state == leader_code) & alive, axis=1)
        commit_adv = post_commit.max(axis=1) > pre_commit.max(axis=1)
        term_bump = (post_term.max(axis=1) - pre_term.max(axis=1)).astype(
            np.int32
        )
        won = np.any(
            (post_state == leader_code)
            & ((pre_state != leader_code) | (post_term > pre_term)),
            axis=1,
        )
        campaigned = np.any(want_campaign, axis=1)

        leaderless, since, bumps, splits = self.planes
        leaderless = np.where(has_leader, 0, leaderless + 1)
        since = np.where(commit_adv, 0, since + 1)
        if self.window_pos == 0:
            bumps = np.zeros_like(bumps)
        bumps = bumps + term_bump
        splits = splits + (campaigned & ~won).astype(np.int32)
        self.planes = np.stack([leaderless, since, bumps, splits]).astype(
            np.int32
        )
        self.window_pos = (self.window_pos + 1) % self.window
        return props


class ChaosOracle(HealthOracle):
    """Scalar-side oracle for chaos (link-fault) schedules.

    Replays a compiled fault schedule (chaos.HostSchedule — the numpy twin
    of the device schedule arrays, including the bit-identical per-round
    loss draws) through real Raft state machines: each round installs the
    round's effective link matrix as per-edge 1.0 drops on the harness
    Network, runs the standard lockstep round, and folds the same health
    facts as HealthOracle.  tests/test_chaos_parity.py asserts exact
    per-round equality of every peer's state AND the health planes against
    ClusterSim stepping the identical schedule through the link-gated
    device path (sim.step's `link=`).

    This class is the resolved GC010 oracle symbol for the chaos kernels
    (tools/graftcheck/parity_obligations.json: link_loss_draw /
    check_safety -> simref.ChaosOracle); renaming it or its entry points
    is an obligation change and must go through `make obligations`.
    """

    def __init__(self, cluster: ScalarCluster, schedule=None, window: int = 32):
        super().__init__(cluster, window=window)
        self.schedule = schedule
        self.round_idx = 0

    def scheduled_round(self) -> None:
        """Advance one round of the attached chaos.HostSchedule."""
        if self.schedule is None:
            raise RuntimeError("no schedule attached; pass schedule= or "
                               "call round(link=...) directly")
        link, crashed, append = self.schedule.masks(self.round_idx)
        self.round_idx += 1
        # Schedule planes are peer-major [P, G]; the scalar round wants
        # [G, P] crash rows.
        self.round(crashed=crashed.T, append_n=append, link=link)


class TransferOracle(HealthOracle):
    """Scalar-side oracle for the batched leader-transfer protocol
    (ISSUE 12): drives the REAL RawNode::transfer_leader machinery —
    handle_transfer_leader's validation/abort rules, the catch-up append,
    MsgTimeoutNow, hup(true)'s CAMPAIGN_TRANSFER forced election, the
    ProposalDropped gate, and the tick-time election-timeout abort —
    through the harness pump, one drain-cadence round at a time, exactly
    as sim._transfer_phase models it:

      * a round's `transfer_propose[g]` (1-based target, 0 = none) steps
        MsgTransferLeader at the group's acting leader BEFORE the ticks
        and pumps it to quiescence — a reachable transfer completes
        within the round (catch-up, TimeoutNow, forced election, noop
        commit), an unreachable one leaves lead_transferee pending;
      * a PENDING transfer is nudged each round with an empty catch-up
        append (`_maybe_send_append(allow_empty=True)` — the effect the
        heartbeat-response chain has in the full-message system), whose
        ack re-triggers the TimeoutNow check;
      * `kick[g][p]` steps MsgHup at tick time (the RawNode::campaign
        admin call — the autopilot's re-election kick).

    tests/test_transfer_batched.py asserts exact per-round equality of
    every peer's state AND the health planes against ClusterSim stepping
    identical schedules through the transfer-enabled device paths
    (plain, linked, and damped).

    This class is the resolved GC010 oracle symbol for the transfer
    kernels (tools/graftcheck/parity_obligations.json: apply_transfer ->
    simref.TransferOracle); renaming it or its entry points is an
    obligation change and must go through `make obligations`.
    """

    def __init__(self, cluster: ScalarCluster, window: int = 32):
        super().__init__(cluster, window=window)
        self._transfer_propose = None

    def round(self, crashed=None, append_n=None, link=None,
              conf_propose=None, kick=None, transfer_propose=None):
        """One round with optional transfer commands: the pre-tick pump
        runs in the `_pre_round` hook (after the health capture, before
        the want_campaign read — where the device phase sits)."""
        self._transfer_propose = transfer_propose
        return super().round(
            crashed, append_n, link, conf_propose, kick=kick
        )

    def pending(self) -> np.ndarray:
        """int64[G, P] lead_transferee per peer (0 = none) — the scalar
        twin of SimState.transferee for parity comparison."""
        G, P = self.cluster.n_groups, self.cluster.n_peers
        out = np.zeros((G, P), dtype=np.int64)
        for g in range(G):
            for p in range(P):
                r = self.cluster.networks[g].peers[p + 1].raft
                out[g, p] = r.lead_transferee or 0
        return out

    def _pre_round(self, crashed, link) -> None:
        tp = self._transfer_propose
        self._transfer_propose = None
        cl = self.cluster
        for g, net in enumerate(cl.networks):
            # The round's faults gate the pump (the parent round
            # re-installs the same masks afterwards — idempotent).
            cl._apply_crash_mask(
                net, crashed[g], None if link is None else link[:, :, g]
            )
            lead = cl.acting_leader(g, crashed[g])
            if lead is None:
                continue
            r = net.peers[lead].raft
            want = 0 if tp is None else int(tp[g])
            if want and want != (r.lead_transferee or 0):
                # The admin command reaches the leader out-of-band (the
                # autopilot talks to it directly), so it is stepped, not
                # routed through the faulted network.  The drain-cadence
                # pump probes unconditionally (the device phase has no
                # pause state), so a paused probe is resumed first.
                pr = r.prs.get_mut(want)
                if pr is not None:
                    pr.paused = False
                r.step(
                    Message(
                        msg_type=MessageType.MsgTransferLeader,
                        from_=want,
                        to=lead,
                    )
                )
            elif r.lead_transferee is not None:
                pr = r.prs.get_mut(r.lead_transferee)
                if pr is not None:
                    pr.paused = False
                    r._maybe_send_append(
                        r.lead_transferee, pr, allow_empty=True
                    )
            else:
                continue
            net.peers[lead].persist()
            net.send(net.filter(net.peers[lead].read_messages()))


class ReadOracle(TransferOracle):
    """Scalar-side oracle for the batched client-read path (ISSUE 13):
    drives the REAL scalar read pumps — `ReadOnlyOption::LeaseBased` for
    lease serves and `Safe` for the ReadIndex fallback arm — with exact
    per-round read-response parity (index, serve round, and the
    degraded-to-ReadIndex decision) against `sim.step(read_propose=)`.

    The scalar Safe probe PERTURBS its cluster (the ctx heartbeat
    broadcast resets timers, teaches commits, and under damping its
    low-term nudge deposes stale leaders), while the device read phase is
    a pure probe on the round-entry state; per-round receipt parity
    therefore runs each probe on a THROWAWAY `copy.deepcopy` of the
    group's Network — the pump's perturbation is confined to the copy and
    the lockstep state parity composes unchanged.  The lease DECISION
    itself comes from `lease_gate`, the host twin of the hardened
    `kernels.lease_read` gate (check-quorum leader naming itself, inside
    the lease window, committed in its own term, no pending transfer, and
    lease reads enabled): when it passes the oracle drives the LeaseBased
    pump, when a READ_LEASE request finds it failed the oracle marks the
    read DEGRADED and drives the Safe pump — including the
    transfer-pending rejection, where raft-rs itself would serve (a real
    LeaseBased soundness gap: MsgTimeoutNow's forced election bypasses
    leases) and the hardened gate degrades instead.

    Subclasses TransferOracle so transfer schedules compose: probes run
    BEFORE the pre-tick transfer pump, exactly where the device's read
    phase sits.

    This class is the resolved GC010 oracle symbol for the lease-read
    kernels (tools/graftcheck/parity_obligations.json: lease_read /
    check_safety's linearizability slots -> simref.ReadOracle); renaming
    it or its entry points is an obligation change and must go through
    `make obligations`.
    """

    # sim.READ_* twins (workload schedules carry these codes).
    READ_NONE = 0
    READ_SAFE = 1
    READ_LEASE = 2

    def __init__(self, cluster: ScalarCluster, election_tick: int = 10,
                 lease_read: bool = False, window: int = 32):
        super().__init__(cluster, window=window)
        self.election_tick = election_tick
        self.lease_read = lease_read
        self.last_receipts: Optional[list] = None
        self._probe_seq = 0

    def lease_gate(self, g: int, crashed_row) -> tuple:
        """(acting_leader_id or None, gate bool): the host twin of
        kernels.lease_read's holder gate evaluated at the group's acting
        leader, from OBSERVABLE scalar state."""
        cl = self.cluster
        lead = cl.acting_leader(g, crashed_row)
        if lead is None:
            return None, False
        r = cl.networks[g].peers[lead].raft
        # Quorum-active-NOW: the non-clearing read of the same flags the
        # check-quorum boundary read-and-clears (the device gate's
        # check_quorum_active over the CURRENT recent_active row — see
        # kernels.lease_read for why boundary-only is unsound).
        active = {id for id, pr in r.prs.iter() if pr.recent_active}
        active.add(r.id)
        ok = (
            self.lease_read
            and r.check_quorum
            and r.state == StateRole.Leader
            and r.leader_id == r.id
            and r.election_elapsed < self.election_tick
            and not r.lead_transferee
            and r.commit_to_current_term()
            and r.prs.has_quorum(active)
        )
        return lead, ok

    def _clone_group(self, g: int):
        """deepcopy one group's Network for a throwaway probe: per-store
        RLocks (unpicklable) are re-seeded fresh via the deepcopy memo,
        and a shared metrics registry is dropped from the copy so the
        probe's pump can never double-count the live cluster's events."""
        import copy

        net = self.cluster.networks[g]
        memo: dict = {}
        _seed_clone_memo(net, memo)
        return copy.deepcopy(net, memo)

    def _pump_read(self, g: int, crashed_row, link_col, peer: int,
                   lease: bool) -> int:
        """Step one MsgReadIndex at `peer` of a THROWAWAY copy of group
        g's Network under the round's faults and pump it dry: the index
        its read state carries, -1 where the read did not complete."""
        from ..read_only_option import ReadOnlyOption

        net = self._clone_group(g)
        self.cluster._apply_crash_mask(net, crashed_row, link_col)
        iface = net.peers[peer]
        iface.raft.read_only.option = (
            ReadOnlyOption.LeaseBased if lease else ReadOnlyOption.Safe
        )
        self._probe_seq += 1
        ctx = b"read-%d" % self._probe_seq
        before = len(iface.raft.read_states)
        net.send([
            Message(
                msg_type=MessageType.MsgReadIndex,
                from_=peer,
                to=peer,
                entries=[Entry(data=ctx)],
            )
        ])
        rs = iface.raft.read_states
        if len(rs) > before and bytes(rs[-1].request_ctx) == ctx:
            return rs[-1].index
        return -1

    def read_probe(self, g: int, crashed_row, link_col, mode: int) -> tuple:
        """One group's read receipt for this round: (index, lease,
        degraded) — the scalar twin of sim.ReadReceipt's per-group lanes.
        Runs the real pump on a deep copy (see class docstring)."""
        if mode == self.READ_NONE:
            return -1, False, False
        lead, gate = self.lease_gate(g, crashed_row)
        lease = mode == self.READ_LEASE and gate
        degraded = mode == self.READ_LEASE and not lease
        if lead is None:
            return -1, False, degraded
        return (
            self._pump_read(g, crashed_row, link_col, lead, lease),
            lease, degraded,
        )

    def read_holders(self, g: int, crashed_row, link_col) -> list:
        """The per-peer question behind sim.ReadReceipt.holders where no
        lease exists: for every peer of group g, would a Safe ReadIndex
        read asked of IT at this round boundary complete?  Every alive
        role-leader — the acting leader and any deposed-but-unaware one
        beside it — drives the real Safe pump on a throwaway copy of its
        own; a peer that is crashed or no leader answers nothing (raft-rs
        forwards or drops its MsgReadIndex).  [bool] * P, the scalar twin
        of sim.read_index_holders / sim.read_quorum_damped_holders."""
        peers = self.cluster.networks[g].peers
        out = []
        for p in range(self.cluster.n_peers):
            lead = (
                not crashed_row[p]
                and peers[p + 1].raft.state == StateRole.Leader
            )
            out.append(
                lead
                and self._pump_read(g, crashed_row, link_col, p + 1, False)
                >= 0
            )
        return out

    def round(self, crashed=None, append_n=None, link=None,
              conf_propose=None, kick=None, transfer_propose=None,
              read_propose=None):
        """One lockstep round with optional per-group read commands
        (`read_propose[g]` in READ_* codes).  Probes run FIRST — on the
        round-entry state, before the transfer pump and the ticks, where
        the device read phase sits — and land in `self.last_receipts` as
        [(index, lease, degraded)] per group (None when read_propose is
        None)."""
        G, P = self.cluster.n_groups, self.cluster.n_peers
        if crashed is None:
            crashed = np.zeros((G, P), dtype=bool)
        if read_propose is None:
            self.last_receipts = None
        else:
            self.last_receipts = [
                self.read_probe(
                    g,
                    crashed[g],
                    None if link is None else link[:, :, g],
                    int(read_propose[g]),
                )
                for g in range(G)
            ]
        return super().round(
            crashed, append_n, link, conf_propose, kick=kick,
            transfer_propose=transfer_propose,
        )


class ReconfigOracle(HealthOracle):
    """Scalar-side oracle for compiled membership-churn schedules.

    Replays a compiled reconfig schedule (reconfig.HostReconfigSchedule —
    the numpy/python twin of the device schedule arrays, derived from the
    SAME Changer-validated chain walk), optionally composed with a chaos
    schedule (chaos.HostSchedule), through real Raft state machines:
    each round runs the standard lockstep round with the round's faults
    and the pending op's conf-entry propose (ScalarCluster.round's
    conf_propose), applies the IDENTICAL propose/gate/retry rules the
    device runner folds into its scan (runner._runner_body), and — when
    a group's gate fires — performs the scalar surgery mirror of
    kernels.apply_confchange on every peer of the group at once:
    tracker.apply_conf with the Changer-computed configuration + map
    delta (fresh rows get the added-node recent_active grace and the
    device model's paused-probe discipline), promotable refresh,
    leader-step-down for peers leaving the config (raw role/leader_id
    surgery — no become_follower timer side effects, matching the
    kernel), and the quorum-shrink commit pickup via Raft.maybe_commit
    (no broadcast — the round's ordinary traffic propagates it).

    tests/test_reconfig_parity.py asserts exact per-round equality of
    every peer's state AND the health planes against the device runner
    stepping the identical schedule.

    This class is the resolved GC010 oracle symbol for the reconfig
    kernels (tools/graftcheck/parity_obligations.json: apply_confchange /
    check_safety -> simref.ReconfigOracle); renaming it or its entry
    points is an obligation change and must go through
    `make obligations`.
    """

    def __init__(self, cluster: ScalarCluster, schedule,
                 chaos_schedule=None, window: int = 32):
        super().__init__(cluster, window=window)
        self.schedule = schedule
        self.chaos = chaos_schedule
        if chaos_schedule is not None:
            if chaos_schedule.n_rounds != schedule.n_rounds:
                raise ValueError(
                    "chaos and reconfig schedules disagree on rounds"
                )
            if chaos_schedule.n_peers != schedule.n_peers:
                raise ValueError(
                    "chaos and reconfig schedules disagree on peers"
                )
        G = cluster.n_groups
        self.round_idx = 0
        self.stage = np.zeros(G, dtype=np.int64)
        self.op_ptr = np.zeros(G, dtype=np.int64)
        self.prop_owner = np.zeros(G, dtype=np.int64)
        self.prop_index = np.zeros(G, dtype=np.int64)
        self.prop_term = np.zeros(G, dtype=np.int64)
        # The runner's rstats vector (reconfig.RC_* order: proposals,
        # applies, retries, joint group-rounds), since the last resume().
        self.rstats = np.zeros(4, dtype=np.int64)

    def resume(self) -> None:
        """Start a replay of the same schedule from the carry the last
        one ended with — the scalar twin of reconfig.resume_state: a
        group whose chain is complete starts again at op 0, a group with
        an op in flight or ops left keeps its pointer and its pending
        entry; the round index and the counts start over."""
        done = (self.op_ptr >= self.schedule.n_ops) & (self.stage == 0)
        self.op_ptr[done] = 0
        self.round_idx = 0
        self.rstats[:] = 0

    def unfinished(self) -> int:
        """Groups with ops of their chain still to apply."""
        return int((self.op_ptr < self.schedule.n_ops).sum())

    @staticmethod
    def _regime_start(raft) -> int:
        """First index of the leader's current-term regime in its own log
        (the device's term_start_index): a leader's log tail is its
        regime, so walk back while the term matches."""
        idx = raft.raft_log.last_index()
        if raft.raft_log.term_or(idx) != raft.term:
            return idx + 1  # defensive: no regime entries yet
        while idx > 1 and raft.raft_log.term_or(idx - 1) == raft.term:
            idx -= 1
        return idx

    def _apply_surgery(self, g: int, slot) -> None:
        """The scalar mirror of kernels.apply_confchange for ONE group:
        identical mask swap, tracker-row delta, step-down, and commit
        pickup on every peer simultaneously."""
        from ..confchange.changer import MapChangeType
        from ..tracker import Configuration

        net = self.cluster.networks[g]
        for p in range(1, self.cluster.n_peers + 1):
            r = net.peers[p].raft
            conf = Configuration(
                voters=slot.voters_inc, learners=slot.learners
            )
            conf.voters.outgoing.voters.update(slot.voters_out)
            conf.learners_next = set(slot.learners_next)
            changes = [
                (i, MapChangeType(ct)) for i, ct in slot.changes
            ]
            # Fresh rows start at the reference's next_idx; for an acting
            # leader the device probe model derives the first-probe prev
            # from its term-start cursor (sim.py's never-acked rule), so
            # the leader's fresh rows get next = its regime start.
            if r.state == StateRole.Leader:
                next_idx = self._regime_start(r)
            else:
                next_idx = r.raft_log.last_index() + 1
            r.prs.apply_conf(conf, changes, next_idx)
            for i, ct in changes:
                if ct == MapChangeType.Add:
                    # apply_conf granted recent_active (the added-node
                    # grace); the device additionally models the fresh
                    # row as a PAUSED probe — appends skip it until a
                    # heartbeat response resumes it.
                    r.prs.get_mut(i).paused = True
            in_config = conf.voters.contains(r.id)
            r.promotable = in_config
            if r.state != StateRole.Follower and not in_config:
                # Leader-step-down when the peer leaves the config: raw
                # role surgery exactly like the kernel — no
                # become_follower timer reset or timeout redraw.
                r.state = StateRole.Follower
                r.leader_id = 0
            elif r.state == StateRole.Leader:
                # Quorum-shrink commit pickup under the NEW config (the
                # reference's post_conf_change maybe_commit), without the
                # broadcast — the round's ordinary traffic propagates it.
                r.maybe_commit()

    def scheduled_round(self) -> None:
        """Advance one round: faults + eligibility + propose + gate +
        surgery, in exactly the device runner's order."""
        r = self.round_idx
        sch = self.schedule
        G, P = sch.n_groups, sch.n_peers
        if self.chaos is not None:
            link, crashed, capp = self.chaos.masks(r)
            append = sch.append[sch.phase_of_round[r]] + capp
        else:
            link = None
            crashed = np.zeros((P, G), dtype=bool)
            append = sch.append[sch.phase_of_round[r]]
        k = np.clip(self.op_ptr, 0, sch.op_start.shape[0] - 1)
        start = sch.op_start[k, np.arange(G)]
        active = (self.op_ptr < sch.n_ops) & (r >= start)
        want = active & (self.stage == 0)
        props = self.round(
            crashed=crashed.T, append_n=append, link=link,
            conf_propose=want,
        )
        for g in range(G):
            if want[g] and props[g][0] > 0:
                self.stage[g] = 1
                (
                    self.prop_owner[g],
                    self.prop_index[g],
                    self.prop_term[g],
                ) = props[g]
                self.rstats[0] += 1
        for g in range(G):
            if self.stage[g] != 1:
                continue
            o = int(self.prop_owner[g])
            raft = self.cluster.networks[g].peers[o].raft
            own_lead = (
                raft.state == StateRole.Leader
                and raft.term == self.prop_term[g]
                and not crashed[o - 1, g]
            )
            if own_lead and raft.raft_log.committed >= self.prop_index[g]:
                self._apply_surgery(g, sch.slot(g, int(self.op_ptr[g])))
                self.op_ptr[g] += 1
                self.stage[g] = 0
                self.rstats[1] += 1
            elif not own_lead:
                self.stage[g] = 0  # retry at the next acting leader
                self.rstats[2] += 1
        # Every peer of a group holds the same configuration (the surgery
        # is applied to all at once): peer 1's says whether it is joint.
        self.rstats[3] += sum(
            bool(net.peers[1].raft.prs.conf.voters.outgoing.ids())
            for net in self.cluster.networks
        )
        self.round_idx += 1
