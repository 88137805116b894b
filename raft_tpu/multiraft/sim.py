"""ClusterSim: closed-loop on-device simulation of G Raft groups × P peers.

This is the intra-pod co-located-groups execution mode (SURVEY.md §5.8a):
all P replicas of each group live in the same device planes, so the entire
message exchange of one protocol round — vote requests/responses, append
broadcast and acks, heartbeats, commit propagation — reduces to array
permutations and masked reductions.  One `step()` advances every group by
one tick AND settles all resulting traffic, exactly like the scalar
harness's "tick all peers, pump to quiescence" round (see
simref.ScalarCluster, the parity oracle; the native C++ twin is
cpp/multiraft_engine.cpp).

TPU layout: every plane is **peer-major [P, G]** — the group axis lands on
the 128-wide vector lanes (G is huge, P <= 8), so all elementwise work
vectorizes fully; a [G, P] layout would waste 123/128 lanes.  The quorum
"sort" is a fixed odd-even transposition network over the P rows (pure
min/max of [G] vectors — no XLA variadic sort), and the whole election
phase is gated behind a batch-level `lax.cond` so steady-state rounds pay
only tick + replication + commit.

Protocol scope (BASELINE configs 2/3/4/5 + the read barrier):
  * elections with randomized timeouts (counter PRNG keyed (node, term)),
    log-up-to-date vote checks, split votes, term inflation from isolated
    peers, stale-candidate disruption on recovery;
  * steady-state replication with per-round append workloads and quorum
    commit (term-gated, Raft §5.4.2 via the term_start_index trick);
  * joint-consensus configs (outgoing_mask: double-majority elections and
    commits) and non-voting learners (learner_mask), with conf changes
    DEVICE-RESIDENT (ISSUE 10): compiled reconfig schedules
    (raft_tpu/multiraft/reconfig.py) propose a real conf entry at the
    acting leader (`step(..., reconfig_propose=)` reports where it
    landed), gate the mask swap on its dual-majority commit, and apply
    it in-scan via kernels.apply_confchange — composable with a chaos
    plan in the same scan (`ClusterSim.run_reconfig`);
  * the linearizable read path, BOTH raft-rs modes (ISSUE 13): the
    ReadIndex barrier, Safe mode (`read_index` below, link-aware; the
    damped nudge-cutoff form in `_read_quorum_damped`), and LeaseBased
    local serves under the check-quorum leader lease
    (`kernels.lease_read`, enabled by SimConfig(lease_read=True)) —
    `step(..., read_propose=)` evaluates per-group read commands on the
    round-entry state in all three step paths and reports a ReadReceipt
    extra (index, lease-vs-degraded), with the stale-read trap
    machine-checked by kernels.check_safety's linearizability slots;
    compiled client workloads drive it at scale
    (raft_tpu/multiraft/workload.py);
  * fault injection at LINK granularity (the chaos engine,
    raft_tpu/multiraft/chaos.py): a directed reachability plane
    `link[src, dst, g]` threaded through every exchange of the round via
    `step(..., link=)` — asymmetric partitions, one-way links, seeded
    per-link message loss, and whole-peer crashes as the special case of
    a fully-down row+column.  Crash (isolation) masks remain the
    first-class fast-path input: crashed peers keep ticking and
    campaigning but exchange no messages, and with `link=None` the
    traced graph is bit-identical to the pre-chaos build.
  * election damping (ISSUE 7): SimConfig(check_quorum=True) runs the
    reference check-quorum machinery on device — per-owner recent_active
    rows read-and-cleared at the leader's election-timeout boundary, the
    low-term nudge deposing stale leaders, and leader leases ignoring
    disruptive vote requests at receipt time; pre_vote=True adds the
    two-phase pre-election.  Both flags are trace-time static: flags-off
    traces (and the flags-off SimState pytree) are bit-identical to the
    undamped build, which keeps the one-way-partition term-inflation
    pathology pinned (tests/test_chaos_parity.py) next to its damped
    collapse (tests/test_damping_parity.py).  The ReadIndex barrier is
    link-aware via read_index(link=).
  * leader transfer (ISSUE 12): SimConfig(transfer=True) carries the
    per-owner lead_transferee plane and `step(..., transfer_propose=)`
    runs the raft-rs MsgTransferLeader / MsgTimeoutNow protocol as a
    pre-tick pump (_transfer_phase, shared by all three step paths):
    validation via kernels.apply_transfer, the probe-gated catch-up
    append, the forced CAMPAIGN_TRANSFER election (no pre-vote, leases
    bypassed), ProposalDropped while pending, and the tick-time
    election-timeout abort — exact parity vs the real
    RawNode::transfer_leader pump (simref.TransferOracle).
    `step(..., campaign_kick=)` is the companion admin action (MsgHup
    at tick time — RawNode::campaign).  Both are the autopilot's
    actuation surface (raft_tpu/multiraft/autopilot.py).
  * black-box forensics (ISSUE 15): SimConfig(blackbox=True) carries the
    device flight recorder (BlackboxState) — a [W, G] bit-packed ring of
    per-group round deltas plus the [N_SAFETY, G] first-trip plane the
    compiled runners min-fold from kernels.check_safety_groups — so a
    nonzero safety count resolves to (group, round) offenders
    (ClusterSim.forensics() / incident_report()), and
    raft_tpu/multiraft/forensics.py turns a captured offender into a
    one-group scalar repro.  Flag-off pytrees and graphs are
    bit-identical, like every optional plane.
  Not modeled on device (host path handles them): snapshots and entry
  payloads (the device sees cursor effects only) and ad-hoc conf changes
  OUTSIDE a compiled plan — a manual host-side mask swap still works but
  skips the commit gate, the added-node recent_active grace, and the
  joint-window safety audit that the reconfig runner provides.

Log model: each peer's log is summarized by (last_index, last_term) plus
the pairwise agreement plane `agree[a, b]` (common-prefix length).  Logs DO
diverge — a crashed peer keeps a stale uncommitted suffix while a new
regime canonizes other entries — but replication is wholesale adoption of
the leader's log, so the live log-shapes form a tree and pairwise
agreement stays prefix-shaped and maintainable without entry contents
(the per-entry conflict scan itself stays host-side — SURVEY.md §7
hard-3).  Commit fast-forward via vote traffic (maybe_commit_by_vote)
and deposed-leader heartbeat interleavings are modeled exactly; see
tests/test_sim_fuzz.py for the schedules that originally exposed them.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from .. import profiling
from . import kernels, planes
from .kernels import ROLE_CANDIDATE, ROLE_FOLLOWER, ROLE_LEADER


class SimConfig(NamedTuple):
    """Static per-sim configuration (python ints: shapes and timeouts are
    compile-time constants for XLA)."""

    n_groups: int
    n_peers: int
    election_tick: int = 10
    heartbeat_tick: int = 1
    # Observability toggle: when True, ClusterSim carries the device-side
    # [kernels.N_COUNTERS] int32 event-counter plane, summed INSIDE the
    # jitted step (one dispatch either way) and downloaded only on demand
    # via ClusterSim.counters().  Compile-time static: the disabled graph is
    # bit-identical to pre-observability builds.
    collect_counters: bool = False
    # Fleet-health toggle: when True, ClusterSim threads the per-group
    # [kernels.N_HEALTH_PLANES, G] health planes through the jitted step
    # (kernels.update_health) and reduces them on device
    # (kernels.health_summary) so only a fixed-size summary ever crosses to
    # the host.  Compile-time static like collect_counters.
    collect_health: bool = False
    # Churn window (rounds): term_bumps_in_window covers at most this many
    # trailing rounds before resetting.
    health_window: int = 32
    # Summary thresholds (rounds / bumps-per-window): a group counts as
    # stalled/churning when the plane value is AT or OVER the threshold.
    leaderless_stall_ticks: int = 16
    commit_stall_ticks: int = 32
    churn_bumps: int = 4
    # Worst-offender extraction width (jax.lax.top_k k).
    health_topk: int = 8
    # Election damping (DESIGN.md §8, landed on device by ISSUE 7).
    # check_quorum enables all three reference mechanisms: per-owner
    # recent_active rows read-and-cleared at the leader's election-timeout
    # boundary (step down without an active quorum, suppressing that
    # round's heartbeat), the low-term nudge (receivers of lower-term
    # append/heartbeat traffic respond at their own term, deposing stale
    # leaders), and leader leases (a voter ignores higher-term vote
    # requests while it heard from a live leader within election_tick
    # ticks of receipt).  pre_vote enables the two-phase pre-election
    # (candidates probe at term+1 without bumping anything) and, like the
    # reference, also turns on the low-term nudge.  Both are trace-time
    # static: the flags-off graph is bit-identical to the undamped build
    # (damping-on rounds run the pairwise wave path, _damped_linked_step).
    check_quorum: bool = False
    pre_vote: bool = False
    # Leader transfer (ISSUE 12): when True, SimState carries the per-owner
    # lead_transferee plane (int32[P, G]) and step() accepts the
    # `transfer_propose` / `campaign_kick` autopilot actions — the batched
    # raft-rs MsgTransferLeader / MsgTimeoutNow protocol runs as a
    # pre-tick pump (_transfer_phase) in all three step paths.  Trace-time
    # static like the damping flags: the flag-off pytree and graphs are
    # bit-identical to the pre-transfer build.
    transfer: bool = False
    # Lease-based linearizable reads (ISSUE 13): when True,
    # step(..., read_propose=) may serve a LeaseBased read LOCALLY — zero
    # message rounds — under the check-quorum leader lease
    # (kernels.lease_read); when False every lease request degrades to
    # the ReadIndex quorum round.  Mirrors the reference's
    # Config.read_only_option == LeaseBased, including its validate rule:
    # lease_read=True requires check_quorum=True (step() raises
    # otherwise — without the boundary deposal the lease proves
    # nothing).  Trace-time static: read_propose=None graphs are
    # bit-identical regardless, and no new SimState plane exists (the
    # lease gate reads the ISSUE 7 planes).
    lease_read: bool = False
    # Black-box forensics (ISSUE 15): when True, ClusterSim carries the
    # device-resident flight recorder (sim.BlackboxState) — a
    # [blackbox_window, G] bit-packed ring of per-group round deltas
    # (max role, acting leader id, max term, max commit, fired safety
    # slots; kernels.blackbox_fold) plus the [N_SAFETY, G] first-trip
    # plane the compiled runners min-fold from
    # kernels.check_safety_groups — so a nonzero safety counter at fleet
    # scale resolves to the offending (group, round) pairs without
    # re-running anything.  One masked fold per round, zero host syncs;
    # only the fixed-size kernels.blackbox_capture reduction crosses at
    # the drain cadence.  Trace-time static like every plane flag: the
    # blackbox=False pytrees and graphs are bit-identical to the
    # pre-forensics build, and pallas_step.steady_mask conservatively
    # rejects blackbox-on fused horizons (v1: the fused kernel cannot
    # fold the ring), so instrumented runs ride the general path.
    blackbox: bool = False
    # Ring window W (rounds of per-group trace retained) and the
    # first-K offender capture width per safety slot (blackbox_capture).
    blackbox_window: int = 8
    blackbox_topk: int = 8
    # SPMD/mesh-friendly graphs (ISSUE 14): when True, the plain step runs
    # its election phase UNCONDITIONALLY as masked ops instead of behind
    # `lax.cond(jnp.any(want_campaign & alive))`.  The cond's scalar
    # predicate is a global reduction over the group axis, which the GSPMD
    # partitioner must lower as a per-round cross-chip all-reduce — the
    # one collective the otherwise embarrassingly-parallel steady step
    # graph would carry on a device mesh (machine-checked by graftcheck
    # GC015).  The election phase is a provable no-op when nobody
    # campaigned (every write is masked on this round's campaigners), so
    # the two forms are bit-identical — pinned by
    # tests/test_sharded_parity.py.  Off by default: single-chip graphs
    # keep the data-dependent skip (and their pinned jaxprs);
    # ClusterSim(mesh=) enables it automatically.
    spmd: bool = False

    @property
    def min_timeout(self) -> int:
        return self.election_tick

    @property
    def max_timeout(self) -> int:
        return 2 * self.election_tick


class SimState(NamedTuple):
    """Device-resident SoA state, peer-major [P, G] int32/bool (SURVEY.md §7
    phase-4 state inventory)."""

    term: jnp.ndarray  # gc: int32[P, G]
    state: jnp.ndarray  # gc: int32[P, G] — ROLE_* codes
    vote: jnp.ndarray  # gc: int32[P, G] — 0 = none, else peer id (1..P)
    leader_id: jnp.ndarray  # gc: int32[P, G] — each peer's view; 0 = none
    election_elapsed: jnp.ndarray  # gc: int32[P, G]
    heartbeat_elapsed: jnp.ndarray  # gc: int32[P, G]
    randomized_timeout: jnp.ndarray  # gc: int32[P, G]
    last_index: jnp.ndarray  # gc: int32[P, G]
    last_term: jnp.ndarray  # gc: int32[P, G]
    commit: jnp.ndarray  # gc: int32[P, G]
    # Per-OWNER leader bookkeeping.  Every peer that has ever led keeps its
    # own frozen ProgressTracker row, exactly like the scalar per-peer
    # tracker (reference: tracker.rs): when the current leader crashes and a
    # stale alive leader keeps acting, it must use ITS view of matched /
    # term-start, not the newer regime's (found by the storm parity test).
    matched: jnp.ndarray  # gc: int32[P, P, G] — per-OWNER Progress.matched
    term_start_index: jnp.ndarray  # gc: int32[P, G] — owner's noop index
    # Pairwise log-agreement lengths: agree[a, b, g] = length of the common
    # prefix of peer a's and b's logs.  Logs CAN diverge (a crashed peer
    # keeps a stale uncommitted suffix while a new regime canonizes other
    # entries), but every log is a wholesale-adopted regime log, so the
    # regime logs form a tree and pairwise agreement is prefix-shaped.
    # This is what makes maybe_commit_by_vote's "term(m.commit) ==
    # m.commit_term" check computable from cursors: the sender committed
    # m.commit, so the receiver's entry there matches iff
    # m.commit <= agree[receiver, sender] (index+term identify entries).
    agree: jnp.ndarray  # gc: int32[P, P, G]
    voter_mask: jnp.ndarray  # gc: bool[P, G] — incoming majority config
    # Outgoing majority for joint consensus (reference: joint.rs:12-15):
    # all-False = not joint; decisions then need BOTH majorities (BASELINE
    # config 4's quorum path).  Conf changes are host-side barriers that
    # swap these mask planes (SURVEY.md §7 hard-part 5).
    outgoing_mask: jnp.ndarray  # gc: bool[P, G]
    # Learners (reference: tracker.rs:40-49): replicated to, never voting,
    # never campaigning, never counted in quorums.
    learner_mask: jnp.ndarray  # gc: bool[P, G]
    # Per-OWNER check-quorum activity rows (reference: progress.rs
    # recent_active), present ONLY when SimConfig damping is on — None
    # otherwise, so the undamped pytree (and its traced graph) is
    # bit-identical to the pre-damping build.  recent_active[owner,
    # target, g] is set by sync-acks reaching `owner` while it leads and
    # read-and-cleared (to the self-only row) at the owner's
    # election-timeout boundary; cleared wholesale when `owner` wins an
    # election (become_leader's tracker reset).  bool[P, P, G] when
    # present.
    recent_active: Optional[jnp.ndarray] = None  # gc: bool[P, P, G]
    # Per-OWNER lead_transferee (reference: raft.rs Raft.lead_transferee),
    # present ONLY when SimConfig.transfer is on — None otherwise, so the
    # transfer-off pytree (and its traced graphs) is bit-identical to the
    # pre-transfer build.  transferee[owner, g] is the 1-based peer id the
    # owner is transferring its leadership to (0 = none); non-zero only
    # while the owner keeps leading at the recording term (every
    # become_* path runs reset(), which aborts the transfer), values
    # bounded by n_peers <= P (GC008 TRANSFER_PLANES registry).
    transferee: Optional[jnp.ndarray] = None  # gc: int32[P, G]


class HealthState(NamedTuple):
    """Device-resident fleet-health telemetry carried alongside SimState.

    planes:     [kernels.N_HEALTH_PLANES, G] int32 per-group planes (row
                indices kernels.HP_*); updated once per step by
                kernels.update_health, downloaded never — only the
                kernels.health_summary reduction crosses to the host.
    window_pos: int32 scalar, rounds into the current churn window; the
                term-bump plane resets when it wraps to 0.
    """

    planes: jnp.ndarray  # gc: int32[H, G]
    window_pos: jnp.ndarray  # gc: int32[]


def init_health(cfg: SimConfig) -> HealthState:
    """Fresh all-zero health state for a sim of cfg.n_groups groups."""
    return HealthState(
        planes=kernels.zero_health(cfg.n_groups),
        window_pos=jnp.int32(0),
    )


class BlackboxState(NamedTuple):
    """Device-resident black-box flight recorder (ISSUE 15), carried
    alongside SimState when SimConfig.blackbox is on.

    meta:       uint32[W, G] packed per-round record ring (W =
                SimConfig.blackbox_window; slot = round % W): group max
                role, acting leader id, and the round's fired safety-slot
                bits in one word (kernels.pack_blackbox_meta — GC008
                PACKED_PLANES `blackbox_meta`).
    term:       int32[W, G] group max term per ring slot.
    commit:     int32[W, G] group max commit per ring slot.
    trip_round: int32[kernels.N_SAFETY, G] FIRST round each safety slot
                fired for each group (kernels.INF = never): the capture
                plane kernels.blackbox_capture reduces to the fixed-size
                per-slot offender lists at the drain cadence.
    round_idx:  int32[] absolute rounds folded so far.
    """

    meta: jnp.ndarray  # gc: uint32[W, G]
    term: jnp.ndarray  # gc: int32[W, G]
    commit: jnp.ndarray  # gc: int32[W, G]
    trip_round: jnp.ndarray  # gc: int32[S, G]
    round_idx: jnp.ndarray  # gc: int32[]


def init_blackbox(cfg: SimConfig) -> BlackboxState:
    """Fresh (all-zero ring, never-tripped) black-box state."""
    return BlackboxState(*kernels.zero_blackbox(
        cfg.n_groups, cfg.blackbox_window
    ))


class ReconfigProposal(NamedTuple):
    """Where this round's conf-change entry landed, per group (the step
    extra behind `step(..., reconfig_propose=)`): owner is the acting
    leader's peer id (0 = no alive leader, nothing proposed), index the
    entry's log index (the group's append workload plus the conf entry,
    appended last), term the owner's term at propose time.  The reconfig
    runner (raft_tpu/multiraft/reconfig.py) records these as the pending
    joint log position whose commit under BOTH majorities gates the mask
    swap.  `dropped` marks the groups whose whole batch of this round
    (workload entries and conf entry alike) was offered and taken by
    nobody — no alive leader, or a transfer pending at the acting leader
    (raft-rs ProposalDropped); the runners count it
    (chaos.CS_APPENDS_DROPPED)."""

    owner: jnp.ndarray  # gc: int32[G]
    index: jnp.ndarray  # gc: int32[G]
    term: jnp.ndarray  # gc: int32[G]
    dropped: jnp.ndarray  # gc: bool[G]


# Read-request modes for step(..., read_propose=) — int32[G] per-group
# commands, matching raft_tpu.read_only_option.ReadOnlyOption + 1 (0 is
# "no read this round").
READ_NONE = 0
READ_SAFE = 1  # the ReadIndex quorum round (ReadOnlyOption::Safe)
READ_LEASE = 2  # local serve under the lease (ReadOnlyOption::LeaseBased)


class ReadReceipt(NamedTuple):
    """What this round's client reads returned, per group (the step extra
    behind `step(..., read_propose=)`): `index` is the commit index the
    group's acting leader served (-1 = the read did not complete this
    round — no alive leader, the commit_to_current_term gate, or a failed
    ack quorum — and the caller retries it next round), `lease` marks
    groups served LOCALLY under the check-quorum leader lease (zero
    message rounds — the kernels.lease_read gate), and `degraded` marks
    LeaseBased requests that fell back to the ReadIndex quorum round (the
    DECISION, recorded even when the fallback also failed to serve).
    Reads are probes: the receipt is computed on the round-ENTRY state
    and the round's protocol phases never see the read traffic, exactly
    like sim.read_index (the scalar pump's perturbation is confined to
    the ReadOracle's throwaway copy).  simref.ReadOracle reproduces
    index, serve round, and the degrade decision bit-for-bit
    (tests/test_read_lease.py).  `holders` is the audit's view of the same
    probe where no lease exists (cfg.lease_read off): EVERY peer whose
    ReadIndex gate passed (read_index_holders; under check-quorum or
    pre-vote read_quorum_damped_holders), of which `index` is the acting
    leader's row; None where lease reads are on and the lease-holder mask
    (kernels.lease_read) plays that part."""

    index: jnp.ndarray  # gc: int32[G]
    lease: jnp.ndarray  # gc: bool[G]
    degraded: jnp.ndarray  # gc: bool[G]
    holders: Optional[jnp.ndarray] = None  # gc: bool[P, G]


class _Halves(NamedTuple):
    """The two halves of every group's configuration (incoming and
    outgoing voters; joint.rs): their sizes, their majorities, and the
    singleton — one incoming voter and an EMPTY outgoing half — whose
    leader answers a read without heartbeats (raft.rs:2075-2079)."""

    n_i: jnp.ndarray  # gc: int32[G]
    n_o: jnp.ndarray  # gc: int32[G]
    singleton: jnp.ndarray  # gc: bool[G]
    q_i: jnp.ndarray  # gc: int32[G]
    q_o: jnp.ndarray  # gc: int32[G]


def _halves(st: SimState) -> _Halves:
    n_i = jnp.sum(st.voter_mask, axis=0).astype(jnp.int32)
    n_o = jnp.sum(st.outgoing_mask, axis=0).astype(jnp.int32)
    singleton = (n_i == 1) & (n_o == 0)
    return _Halves(n_i, n_o, singleton, n_i // 2 + 1, n_o // 2 + 1)


def _has_quorum(
    h: _Halves,
    cnt_i: jnp.ndarray,  # gc: int32[..., G]
    cnt_o: jnp.ndarray,  # gc: int32[..., G]
) -> jnp.ndarray:
    """The counts (grants, acks) reach the majority of BOTH halves of the
    configuration; an empty half agrees (joint.rs vote_result)."""
    return ((cnt_i >= h.q_i) | (h.n_i == 0)) & (
        (cnt_o >= h.q_o) | (h.n_o == 0)
    )


def _cannot_win(
    h: _Halves,
    cnt_i: jnp.ndarray,  # gc: int32[..., G]
    cnt_o: jnp.ndarray,  # gc: int32[..., G]
    rec_i: jnp.ndarray,  # gc: int32[..., G]
    rec_o: jnp.ndarray,  # gc: int32[..., G]
) -> jnp.ndarray:
    """Some half's grants plus its voters yet to respond fall short of its
    majority (VoteResult::Lost)."""
    return ((h.n_i > 0) & (cnt_i + (h.n_i - rec_i) < h.q_i)) | (
        (h.n_o > 0) & (cnt_o + (h.n_o - rec_o) < h.q_o)
    )


def _acks_and_nudges(
    st: SimState,
    resp: jnp.ndarray,  # gc: bool[..., P, G]
    l_term: jnp.ndarray,  # gc: int32[..., G]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Split the delivered responses `resp[..., m, :]` to a leader at term
    `l_term[..., :]`'s ctx heartbeat: a member at or under the leader's
    term acks; a HIGHER-term member answers with an empty
    MsgAppendResponse at its own term (reference: raft.rs step's
    m.term < self.term arm under check_quorum/pre_vote), which deposes
    the leader when processed — the nudge."""
    ack_v = resp & (st.term <= l_term[..., None, :])
    ndg_v = resp & (st.term > l_term[..., None, :])
    return ack_v, ndg_v


def _acks_before_nudge(
    st: SimState,
    ack_v: jnp.ndarray,  # gc: bool[..., P, G]
    ndg_v: jnp.ndarray,  # gc: bool[..., P, G]
    cnt_i: jnp.ndarray,  # gc: int32[..., G]
    cnt_o: jnp.ndarray,  # gc: int32[..., G]
    h: _Halves,
) -> jnp.ndarray:
    """THE damped ReadIndex gate: does a leader's ack quorum land STRICTLY
    BEFORE the first deposing nudge of its response stream (peer-id order
    — the harness pump's wave order)?  `ack_v` / `ndg_v` are the stream
    (_acks_and_nudges, member axis second to last), `cnt_i` / `cnt_o` the
    leader's own ack in the two halves of the configuration (add_request
    seeds acks = {self}).  A processed nudge deposes the leader, and
    become_follower's reset() WIPES the pending read queue, so every later
    ack is stepped by a follower and ignored.  Ack quorum evaluation
    happens per processed ack (handle_heartbeat_response), so the joint
    self-quorum hang and the at-least-one-responder rule fall out of the
    same loop.

    One body for one leader a group (the acting leader's planes, no
    leading axis: _acting_read_gate) and for every peer as its own leader
    (a leading [P_l] axis: read_quorum_damped_holders), so the probe and
    the audit cannot drift apart and a control that weakens this function
    weakens both.  Returns bool[..., G]."""
    served = jnp.zeros(cnt_i.shape, bool)
    dead = jnp.zeros(cnt_i.shape, bool)
    for v in range(st.term.shape[0]):
        # The nudge at stream position v deposes a leader not yet served;
        # every later response is stepped by a follower and ignored.
        dead = dead | (ndg_v[..., v, :] & ~served)
        a = ack_v[..., v, :] & ~dead
        cnt_i = cnt_i + (a & st.voter_mask[v]).astype(jnp.int32)
        cnt_o = cnt_o + (a & st.outgoing_mask[v]).astype(jnp.int32)
        quorum = _has_quorum(h, cnt_i, cnt_o)
        # has_quorum(acks) is only EVALUATED inside
        # handle_heartbeat_response — i.e. on processing ack `a` — which
        # is what makes the leader-alone joint quorum hang until some
        # other member responds (read_index's any_other rule).
        served = served | (a & quorum)
    return served


def _links_up(
    alive: jnp.ndarray,  # gc: bool[P, G]
    link: Optional[jnp.ndarray],  # gc: bool[P, P, G]
) -> jnp.ndarray:
    """E[src, dst, G]: a message from src reaches dst this round — both
    alive, two peers, the directed link up."""
    P = alive.shape[0]
    off_diag = ~jnp.eye(P, dtype=bool)[:, :, None]
    E = alive[:, None, :] & alive[None, :, :] & off_diag
    if link is not None:
        E = E & link
    return E


def _acting_read_gate(
    cfg: SimConfig,
    st: SimState,
    crashed: jnp.ndarray,  # gc: bool[P, G]
    link: Optional[jnp.ndarray],  # gc: bool[P, P, G]
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The damped ReadIndex gate at the group's ACTING leader — where the
    sim routes client reads: (is_acting bool[P, G], ok bool[G], the acting
    leader's commit index int32[G]).  `ok` is the acting leader's row of
    read_quorum_damped_holders, computed on that one leader's planes."""
    P = cfg.n_peers
    alive = ~crashed
    member = st.voter_mask | st.outgoing_mask | st.learner_mask
    is_lead = (st.state == ROLE_LEADER) & alive
    lead_term = jnp.max(jnp.where(is_lead, st.term, -1), axis=0)
    # The acting leader is THE acting_leader_id rule (alive max-term,
    # lowest index on the tie; 0 = none, matched by no peer id).
    lead_id = kernels.acting_leader_id(st.state, st.term, crashed)
    has_lead = lead_id > 0
    p_idx = jnp.arange(P, dtype=jnp.int32)[:, None]
    is_acting = (p_idx + 1) == lead_id[None, :]
    # dtype= so the probed indices stay int32 under x64 (GC007).
    lead_commit = jnp.sum(
        jnp.where(is_acting, st.commit, 0), axis=0, dtype=jnp.int32
    )
    lead_ts = jnp.sum(
        jnp.where(is_acting, st.term_start_index, 0), axis=0, dtype=jnp.int32
    )
    servable = has_lead & (lead_commit >= lead_ts)
    h = _halves(st)
    E = _links_up(alive, link)
    reach = jnp.any(E & is_acting[:, None, :], axis=0)  # [P_m, G] l -> m
    ret = jnp.any(E & is_acting[None, :, :], axis=1)  # [P_m, G] m -> l
    resp = member & reach & ret & ~is_acting  # a delivered response
    ack_v, ndg_v = _acks_and_nudges(st, resp, lead_term)
    # The leader's own ack (add_request seeds acks = {self}).
    cnt_i = jnp.sum(
        jnp.where(is_acting & st.voter_mask, 1, 0), axis=0, dtype=jnp.int32
    )
    cnt_o = jnp.sum(
        jnp.where(is_acting & st.outgoing_mask, 1, 0), axis=0,
        dtype=jnp.int32,
    )
    served = _acks_before_nudge(st, ack_v, ndg_v, cnt_i, cnt_o, h)
    return is_acting, servable & (h.singleton | served), lead_commit


def _read_quorum_damped(
    cfg: SimConfig,
    st: SimState,
    crashed: jnp.ndarray,  # gc: bool[P, G]
    link: Optional[jnp.ndarray],  # gc: bool[P, P, G]
) -> jnp.ndarray:
    """The Safe-mode ReadIndex barrier under damping (check_quorum or
    pre_vote): like sim.read_index, but with the low-term nudge cutoff
    the damped scalar pump applies — the read completes only if a quorum
    of acks lands STRICTLY BEFORE the first deposing nudge in the response
    stream (_acks_before_nudge, at the acting leader).  Pure probe, like
    read_index; returns int32[G] (-1 = not served)."""
    _, ok, lead_commit = _acting_read_gate(cfg, st, crashed, link)
    return jnp.where(ok, lead_commit, jnp.int32(-1))


def read_quorum_damped_holders(
    cfg: SimConfig,
    st: SimState,
    crashed: jnp.ndarray,  # gc: bool[P, G]
    link: Optional[jnp.ndarray] = None,  # gc: bool[P, P, G]
) -> jnp.ndarray:
    """The damped ReadIndex gate of EVERY peer (read_index_holders' twin
    under check-quorum / pre-vote): bool[P, G], true where a Safe read
    asked of peer l at this round boundary would complete — l is an alive
    role-leader, has committed in its own term, and its ack quorum (both
    halves of a joint configuration, at least one responder, the
    singleton rule) lands strictly before the first deposing nudge of ITS
    response stream: _read_quorum_damped's rules with "the acting leader"
    replaced by "peer l", one computation over a leader axis.  The acting
    leader's row is _read_quorum_damped's answer.  Pure and jittable."""
    member = st.voter_mask | st.outgoing_mask | st.learner_mask
    alive = ~crashed
    is_lead = (st.state == ROLE_LEADER) & alive  # [P_l, G]
    E = _links_up(alive, link)
    # resp[l, m]: l's ctx heartbeat reaches member m and m's response l.
    resp = member[None, :, :] & E & jnp.swapaxes(E, 0, 1)
    h = _halves(st)
    ack_v, ndg_v = _acks_and_nudges(st, resp, st.term)
    served = _acks_before_nudge(
        st, ack_v, ndg_v,
        st.voter_mask.astype(jnp.int32), st.outgoing_mask.astype(jnp.int32),
        h,
    )
    return (
        is_lead
        & (st.commit >= st.term_start_index)
        & (h.singleton[None, :] | served)
    )


def _read_holders_damped(
    cfg: SimConfig,
    st: SimState,
    crashed: jnp.ndarray,  # gc: bool[P, G]
    link: Optional[jnp.ndarray],  # gc: bool[P, P, G]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(read_quorum_damped_holders, _read_quorum_damped) of one round, the
    per-peer gate paid only in the rounds that can need it.  A peer passes
    the gate only as an alive role-leader, so in a round in which no group
    has an alive role-leader other than its acting leader the mask IS the
    acting leader's row of the probe the round computes anyway
    (docs/READINDEX_AUDIT.md); under a store-loss mix a second alive
    role-leader exists only while a cut-off store's leaders wait for
    their check-quorum boundary."""
    is_acting, ok, lead_commit = _acting_read_gate(cfg, st, crashed, link)
    is_lead = (st.state == ROLE_LEADER) & ~crashed
    stale = jnp.any(is_lead & ~is_acting)

    def full():
        with profiling.scope("damped.read_holders"):
            return read_quorum_damped_holders(cfg, st, crashed, link)

    holders = jax.lax.cond(stale, full, lambda: is_acting & ok[None, :])
    return holders, jnp.where(ok, lead_commit, jnp.int32(-1))


def _read_phase(
    cfg: SimConfig,
    st: SimState,
    crashed: jnp.ndarray,  # gc: bool[P, G]
    read_propose: jnp.ndarray,  # gc: int32[G]
    link: Optional[jnp.ndarray],  # gc: bool[P, P, G]
) -> ReadReceipt:
    """The client-read phase, shared by all three step paths: evaluate
    this round's read requests (`read_propose[g]` in READ_* modes) on the
    round-ENTRY state — before the transfer pump, the ticks, and every
    protocol phase, exactly where the scalar oracle steps MsgReadIndex at
    the acting leader.

    A READ_LEASE request serves locally when the hardened lease gate
    passes (kernels.lease_read: check-quorum leader inside its lease
    window, committed in its own term, no transfer pending) and
    cfg.lease_read is on; otherwise it DEGRADES to the ReadIndex quorum
    round — the same link-aware barrier a READ_SAFE request runs
    (read_index undamped; _read_quorum_damped's nudge-cutoff form under
    damping).  Pure: reads touch no message planes, so the round's traced
    protocol phases are byte-identical with or without them."""
    want = read_propose > READ_NONE
    lease_want = read_propose == READ_LEASE
    _, lease_served, lease_idx = kernels.lease_read(
        st.state, st.term, st.leader_id, st.election_elapsed, st.commit,
        st.term_start_index, crashed, cfg.election_tick,
        cfg.check_quorum and cfg.lease_read, st.transferee,
        st.recent_active, st.voter_mask, st.outgoing_mask,
    )
    serve_l = lease_want & lease_served
    fallback = want & ~serve_l
    if cfg.lease_read:
        # A lease exists (lease_read needs check_quorum): the audit holds
        # the lease holders (kernels.lease_read), not the fallback's gate.
        holders = None
        ri = _read_quorum_damped(cfg, st, crashed, link)
    elif cfg.check_quorum or cfg.pre_vote:
        holders, ri = _read_holders_damped(cfg, st, crashed, link)
    else:
        holders = read_index_holders(cfg, st, crashed, link)
        ri = _acting_index(st, crashed, holders)
    index = jnp.where(
        serve_l, lease_idx, jnp.where(fallback, ri, jnp.int32(-1))
    )
    return ReadReceipt(
        index=index, lease=serve_l, degraded=lease_want & ~serve_l,
        holders=holders,
    )


def _node_key(
    cfg: SimConfig, group_ids: Optional[jnp.ndarray] = None
) -> jnp.ndarray:
    """node_key[p, g] = g * 2**16 + (p + 1): matches the scalar side's
    Config.timeout_seed = g convention (util.deterministic_timeout).

    `group_ids` overrides the iota when the step runs on a slice of the
    fleet that is not groups 0..G-1 (a gathered sub-batch): the timeout
    PRNG must keep drawing from each group's GLOBAL stream."""
    if group_ids is None:
        g = jnp.arange(cfg.n_groups, dtype=jnp.uint32)[None, :]
    else:
        g = group_ids.astype(jnp.uint32)[None, :]
    p = jnp.arange(cfg.n_peers, dtype=jnp.uint32)[:, None]
    return g * jnp.uint32(1 << 16) + (p + 1)


def init_state(
    cfg: SimConfig,
    voter_mask: Optional[jnp.ndarray] = None,
    outgoing_mask: Optional[jnp.ndarray] = None,
    learner_mask: Optional[jnp.ndarray] = None,
) -> SimState:
    """All peers start as followers at term 0 with their deterministic
    timeout draw (mirrors Raft.__init__ -> become_follower(0))."""
    G, P = cfg.n_groups, cfg.n_peers
    shape = (P, G)

    def zeros():
        # Distinct buffers per field: step() donates the whole state, and
        # aliased buffers would be donated twice.
        return jnp.zeros(shape, jnp.int32)

    if voter_mask is None:
        voter_mask = jnp.ones(shape, bool)
    if outgoing_mask is None:
        outgoing_mask = jnp.zeros(shape, bool)
    if learner_mask is None:
        learner_mask = jnp.zeros(shape, bool)
    lo = jnp.full(shape, cfg.min_timeout, jnp.int32)
    hi = jnp.full(shape, cfg.max_timeout, jnp.int32)
    rt = kernels.timeout_draw(_node_key(cfg), jnp.zeros(shape, jnp.uint32), lo, hi)
    recent_active = (
        jnp.zeros((P, P, G), bool)
        if (cfg.check_quorum or cfg.pre_vote)
        else None
    )
    transferee = jnp.zeros(shape, jnp.int32) if cfg.transfer else None
    return SimState(
        recent_active=recent_active,
        transferee=transferee,
        term=zeros(),
        state=zeros(),
        vote=zeros(),
        leader_id=zeros(),
        election_elapsed=zeros(),
        heartbeat_elapsed=zeros(),
        randomized_timeout=rt,
        last_index=zeros(),
        last_term=zeros(),
        commit=zeros(),
        matched=jnp.zeros((P, P, G), jnp.int32),
        term_start_index=jnp.zeros((P, G), jnp.int32),
        agree=jnp.zeros((P, P, G), jnp.int32),
        voter_mask=voter_mask,
        outgoing_mask=outgoing_mask,
        learner_mask=learner_mask,
    )


# The plane that rides the scan carry bit-packed, from the registry
# (planes.py `packing == "bits_g"`; exactly one row today — the
# destructuring fails loudly if a second packed-carry plane lands without
# generalizing the carry to a tuple of word planes).
(_PACKED_CARRY_FIELD,) = planes.packed_carry_fields()


def pack_ra_carry(
    st: SimState,
) -> Tuple[SimState, Optional[jnp.ndarray]]:
    """Split `st` into (state-without-recent_active, packed words) for a
    scan carry: the optional `recent_active bool[P, P, G]` plane — the
    single largest plane damping added, the registry's packed-carry row —
    rides bit-packed 32:1 along the group axis (kernels.pack_bits_g,
    GC008 PACKED_PLANES `bits_g`) between rounds, so a donated
    double-buffered scan reads/writes ~32x less HBM for it per round.
    Undamped states pass through unchanged (None words), keeping the
    undamped scan graph bit-identical.  Inverse: unpack_ra_carry."""
    plane = getattr(st, _PACKED_CARRY_FIELD)
    if plane is None:
        return st, None
    return (
        st._replace(**{_PACKED_CARRY_FIELD: None}),
        kernels.pack_bits_g(plane),
    )


def unpack_ra_carry(
    st: SimState, words: Optional[jnp.ndarray]
) -> SimState:
    """Inverse of pack_ra_carry: restore the packed-carry plane from its
    scan-carry words (None words = undamped state, unchanged)."""
    if words is None:
        return st
    n_groups = st.term.shape[-1]
    return st._replace(
        **{_PACKED_CARRY_FIELD: kernels.unpack_bits_g(words, n_groups)}
    )


@profiling.scope("quorum_commit")
def _quorum_index(matched: jnp.ndarray, voter_mask: jnp.ndarray) -> jnp.ndarray:
    """Per-group majority commit index over the peer axis of [P, G] planes
    (the scalar oracle: quorum.MajorityConfig.committed_index, reference:
    majority.rs:70-124): kernels._quorum_of_rows over the P rows, the body of
    kernels.committed_index too.  Returns int32[G]."""
    P = matched.shape[0]
    return kernels._quorum_of_rows(
        [matched[p] for p in range(P)], [voter_mask[p] for p in range(P)]
    )


def _transfer_phase(
    cfg: SimConfig,
    st: SimState,
    crashed: jnp.ndarray,  # gc: bool[P, G]
    transfer_propose: Optional[jnp.ndarray],  # gc: int32[G]
    link: Optional[jnp.ndarray],  # gc: bool[P, P, G]
    group_ids: Optional[jnp.ndarray] = None,
) -> Tuple[SimState, jnp.ndarray, jnp.ndarray]:
    """The pre-tick leader-transfer pump, shared by all three step paths.

    One round of the drain-cadence transfer protocol, exactly the scalar
    pump the TransferOracle drives (simref.TransferOracle): BEFORE the
    round's ticks, each group's acting leader (1) steps this round's
    MsgTransferLeader command if `transfer_propose[g]` names a target
    (kernels.apply_transfer — the reference's validation + transfer-clock
    reset, raft.rs:1821-1889), then (2) pumps its pending transfer: a
    catch-up append to the transferee (allow_empty, so an already
    caught-up target is probed too), whose ack shows the target caught up
    and triggers MsgTimeoutNow — or MsgTimeoutNow directly when a NEW
    command finds the target already caught up (no ack round trip, so a
    one-way leader->target link suffices there).  The transferee receiving
    MsgTimeoutNow campaigns immediately with CAMPAIGN_TRANSFER (hup(true),
    raft.rs:2257-2354): no pre-vote probe, leases bypassed by the force
    context (raft.rs:1280-1348), and the whole forced election — vote
    requests, grants/rejections with the scalar response-order cutoffs,
    commit fast-forwards, the winner's noop append/broadcast/quorum-commit
    — resolves inside this same pump, like any reachable scalar transfer
    completes within one pumped round.

    Every hop is gated per DIRECTED link (the chaos plane): an
    unreachable transferee leaves the transfer pending (proposals stay
    blocked at the leader until the tick-time election-timeout abort),
    and a one-way target->leader cut delivers the catch-up append but
    never the ack, so MsgTimeoutNow is withheld — the raft-rs behavior.

    Returns (state', campaigned[G], won[G]) — the transfer-campaign and
    transfer-win facts the caller folds into counters/health (the scalar
    side counts the hup(true) campaign() call and the become_leader).
    Under damping (check_quorum/pre_vote) the catch-up append reaching a
    HIGHER-term target draws the low-term nudge, deposing the stale
    leader (and aborting the transfer) exactly like the reference.
    """
    G, P = cfg.n_groups, cfg.n_peers
    damped = cfg.check_quorum or cfg.pre_vote
    self_id = jnp.arange(P, dtype=jnp.int32)[:, None] + 1  # [P, 1]
    p_idx = jnp.arange(P, dtype=jnp.int32)[:, None]  # [P, 1]
    alive = ~crashed
    off_diag = ~jnp.eye(P, dtype=bool)[:, :, None]
    if link is None:
        E = alive[:, None, :] & alive[None, :, :] & off_diag
    else:
        E = link & alive[:, None, :] & alive[None, :, :] & off_diag
    node_key = _node_key(cfg, group_ids)
    lo = jnp.full((P, G), cfg.min_timeout, jnp.int32)
    hi = jnp.full((P, G), cfg.max_timeout, jnp.int32)

    def draw(term):
        return kernels.timeout_draw(node_key, term.astype(jnp.uint32), lo, hi)

    promotable = st.voter_mask | st.outgoing_mask
    member = promotable | st.learner_mask

    # ---- the acting leader, pre-round (the scalar pump steps the command
    # at the alive max-term leader; ties resolve to the lowest index).
    is_lead = (st.state == ROLE_LEADER) & alive
    has_lead = jnp.any(is_lead, axis=0)  # [G]
    lead_term = jnp.max(jnp.where(is_lead, st.term, -1), axis=0)  # [G]
    acting = is_lead & (st.term == lead_term[None, :])
    first_l = jnp.min(jnp.where(acting, p_idx, P), axis=0)  # [G]
    is_acting = (p_idx == first_l) & has_lead[None, :]
    acting_i = is_acting.astype(jnp.int32)

    if transfer_propose is None:
        transfer_propose = jnp.zeros((G,), jnp.int32)
    T, ee0, accepted = kernels.apply_transfer(
        st.transferee, st.election_elapsed, is_acting, transfer_propose,
        member, st.learner_mask,
    )

    # The acting leader's pending target, post-command; everything below
    # is masked on `active` so transfer-free groups are untouched.
    t_all = jnp.sum(jnp.where(is_acting, T, 0), axis=0, dtype=jnp.int32)
    active = has_lead & (t_all > 0)  # [G]
    is_tgt = (self_id == t_all[None, :]) & active[None, :]  # [P, G]

    lead_last = jnp.sum(st.last_index * acting_i, axis=0, dtype=jnp.int32)
    lead_lterm = jnp.sum(st.last_term * acting_i, axis=0, dtype=jnp.int32)
    lead_commit = jnp.sum(st.commit * acting_i, axis=0, dtype=jnp.int32)
    m_row = jnp.sum(
        st.matched * acting_i[:, None, :], axis=0, dtype=jnp.int32
    )  # [P, G]: the leader's tracker row
    agree_lead = jnp.sum(
        st.agree * acting_i[:, None, :], axis=0, dtype=jnp.int32
    )  # [P, G]: agree[leader, :]
    matched_t = jnp.sum(
        jnp.where(is_tgt, m_row, 0), axis=0, dtype=jnp.int32
    )  # [G]
    caught_pre = matched_t == lead_last
    term_t = jnp.sum(jnp.where(is_tgt, st.term, 0), axis=0, dtype=jnp.int32)

    # Directed leader<->target links.
    E_lt = jnp.any(E & is_acting[:, None, :] & is_tgt[None, :, :], axis=(0, 1))
    E_tl = jnp.any(E & is_tgt[:, None, :] & is_acting[None, :, :], axis=(0, 1))

    # ---- hop 1: MsgTimeoutNow directly (new command, target caught up —
    # reference: handle_transfer_leader's matched == last_index branch) or
    # the catch-up append (allow_empty=True: the pending-transfer nudge).
    tn_direct = active & accepted & caught_pre & E_lt
    ap_path = active & ~(accepted & caught_pre)
    del_ap = ap_path & E_lt & (term_t <= lead_term)
    # Log+commit adoption needs the probe to MATCH (the target's
    # agreement with the leader covers the append's prev entry) or a
    # live reverse link for the reject/retry chain to converge within
    # the pump — the same gate _linked_step applies to workload appends;
    # a delivered-but-rejected append still resets timers and follower
    # state (message receipt), it just adopts nothing.
    lead_ts = jnp.sum(
        st.term_start_index * acting_i, axis=0, dtype=jnp.int32
    )
    prev_t = jnp.where(matched_t == 0, lead_ts - 1, lead_last)
    agree_lt = jnp.sum(
        jnp.where(is_tgt, agree_lead, 0), axis=0, dtype=jnp.int32
    )  # [G]: agree[leader, target]
    adopt_ap = del_ap & ((agree_lt >= prev_t) | E_tl)
    sync = is_tgt & del_ap[None, :]
    adopt = is_tgt & adopt_ap[None, :]
    bump = sync & (st.term < lead_term[None, :])
    T_pl = jnp.where(sync, lead_term[None, :], st.term)
    St_pl = jnp.where(sync, ROLE_FOLLOWER, st.state)
    V_pl = jnp.where(bump, 0, st.vote)
    Ld_pl = jnp.where(sync, first_l[None, :] + 1, st.leader_id)
    EE_pl = jnp.where(sync, 0, ee0)
    HB_pl = st.heartbeat_elapsed
    RT_pl = jnp.where(bump, draw(T_pl), st.randomized_timeout)
    LI_pl = jnp.where(adopt, lead_last[None, :], st.last_index)
    LT_pl = jnp.where(adopt, lead_lterm[None, :], st.last_term)
    C_pl = jnp.where(
        adopt, jnp.maximum(st.commit, lead_commit[None, :]), st.commit
    )
    in_s = adopt | (is_acting & adopt_ap[None, :])
    agree_pl = jnp.where(
        in_s[:, None, :] & in_s[None, :, :],
        lead_last[None, None, :],
        jnp.where(
            in_s[:, None, :],
            agree_lead[None, :, :],
            jnp.where(in_s[None, :, :], agree_lead[:, None, :], st.agree),
        ),
    )
    ack = adopt_ap & E_tl
    mack = is_acting[:, None, :] & is_tgt[None, :, :] & ack[None, None, :]
    matched_pl = jnp.where(mack, lead_last[None, None, :], st.matched)
    RA = st.recent_active
    if RA is not None:
        RA = jnp.where(mack, True, RA)
    if damped:
        # The low-term nudge: the catch-up append reaching a higher-term
        # target draws an empty MsgAppendResponse at the target's term,
        # deposing the stale leader (reference: raft.rs:1280-1348's
        # m.term < self.term branch) — reset() aborts the transfer.
        ndg = ap_path & E_lt & (term_t > lead_term) & E_tl
        dep = is_acting & ndg[None, :]
        T_pl = jnp.where(dep, term_t[None, :], T_pl)
        St_pl = jnp.where(dep, ROLE_FOLLOWER, St_pl)
        V_pl = jnp.where(dep, 0, V_pl)
        Ld_pl = jnp.where(dep, 0, Ld_pl)
        EE_pl = jnp.where(dep, 0, EE_pl)
        HB_pl = jnp.where(dep, 0, HB_pl)
        RT_pl = jnp.where(dep, draw(T_pl), RT_pl)
        T = jnp.where(dep, 0, T)

    # ---- hop 2: MsgTimeoutNow at the target.  A lower-term target first
    # takes the generic become_follower(m.term) bump; then a FOLLOWER at
    # the leader's term hups — candidates and leaders at that term ignore
    # it (step_candidate/step_leader), exactly the reference dispatch.
    # The ack-triggered send fires only when the ack made PROGRESS
    # (handle_append_response early-returns on maybe_update(m.index) ==
    # false, so an already-caught-up transferee's empty-append ack never
    # re-sends a lost MsgTimeoutNow — the transfer hangs until the
    # tick-time abort, the reference behavior).
    tn = tn_direct | (ack & (matched_t < lead_last))
    tn_bump = is_tgt & tn[None, :] & (T_pl < lead_term[None, :])
    T_pl = jnp.where(tn_bump, lead_term[None, :], T_pl)
    St_pl = jnp.where(tn_bump, ROLE_FOLLOWER, St_pl)
    V_pl = jnp.where(tn_bump, 0, V_pl)
    Ld_pl = jnp.where(tn_bump, 0, Ld_pl)
    EE_pl = jnp.where(tn_bump, 0, EE_pl)
    HB_pl = jnp.where(tn_bump, 0, HB_pl)
    RT_pl = jnp.where(tn_bump, draw(T_pl), RT_pl)
    campaign_mask = (
        is_tgt
        & tn[None, :]
        & (St_pl == ROLE_FOLLOWER)
        & (T_pl == lead_term[None, :])
        & promotable
    )
    cg = jnp.any(campaign_mask, axis=0)  # [G]

    # ---- the forced campaign (CAMPAIGN_TRANSFER skips pre-vote even when
    # cfg.pre_vote is on; reference: hup raft.rs:1472-1525).
    t_star = lead_term + 1  # [G]
    T_pl = jnp.where(campaign_mask, t_star[None, :], T_pl)
    St_pl = jnp.where(campaign_mask, ROLE_CANDIDATE, St_pl)
    V_pl = jnp.where(campaign_mask, self_id, V_pl)
    Ld_pl = jnp.where(campaign_mask, 0, Ld_pl)
    EE_pl = jnp.where(campaign_mask, 0, EE_pl)
    HB_pl = jnp.where(campaign_mask, 0, HB_pl)
    RT_pl = jnp.where(campaign_mask, draw(T_pl), RT_pl)

    # ---- hop 3: the transfer election.  Vote requests reach every voter
    # over the target's outbound links; the force context bypasses leases
    # and a real request at a lower term is silently ignored by
    # higher-term voters (no nudge for real votes), so delivery reduces
    # to the masks below.  The candidate's log is its post-catch-up log.
    E_from_t = jnp.any(E & is_tgt[:, None, :], axis=0)  # [P_v, G]
    E_to_t = jnp.any(E & is_tgt[None, :, :], axis=1)  # [P_v, G]
    del_rq = cg[None, :] & promotable & ~is_tgt & E_from_t
    li_t = jnp.sum(jnp.where(is_tgt, LI_pl, 0), axis=0, dtype=jnp.int32)
    lt_t = jnp.sum(jnp.where(is_tgt, LT_pl, 0), axis=0, dtype=jnp.int32)
    c_t = jnp.sum(jnp.where(is_tgt, C_pl, 0), axis=0, dtype=jnp.int32)
    agree_t = jnp.sum(
        agree_pl * is_tgt.astype(jnp.int32)[:, None, :],
        axis=0,
        dtype=jnp.int32,
    )  # [P_v, G]: agree[target, v]
    vbump = del_rq & (T_pl < t_star[None, :])
    at = del_rq & (T_pl <= t_star[None, :])
    T_pl = jnp.where(vbump, t_star[None, :], T_pl)
    St_pl = jnp.where(vbump, ROLE_FOLLOWER, St_pl)
    V_pl = jnp.where(vbump, 0, V_pl)
    Ld_pl = jnp.where(vbump, 0, Ld_pl)
    EE_pl = jnp.where(vbump, 0, EE_pl)
    HB_pl = jnp.where(vbump, 0, HB_pl)
    RT_pl = jnp.where(vbump, draw(T_pl), RT_pl)
    up = (lt_t[None, :] > LT_pl) | (
        (lt_t[None, :] == LT_pl) & (li_t[None, :] >= LI_pl)
    )
    can = at & (((V_pl == 0) & (Ld_pl == 0)) | (V_pl == t_all[None, :]))
    grant = can & up
    rej = at & ~grant
    rej_snap = C_pl  # reject responses snapshot commit BEFORE the vff
    # Voter-side maybe_commit_by_vote off the request's commit info
    # (reference: raft.rs:2126-2164; leaders skip).
    vff = (
        rej
        & (St_pl != ROLE_LEADER)
        & (c_t[None, :] > C_pl)
        & (c_t[None, :] <= agree_t)
    )
    V_pl = jnp.where(grant, t_all[None, :], V_pl)
    EE_pl = jnp.where(grant, 0, EE_pl)
    C_pl = jnp.where(vff, c_t[None, :], C_pl)

    # ---- hop 4: responses back in voter order with the scalar win/loss
    # cutoffs (raft.rs:2184-2190 + 2236-2247), candidate-side commit
    # fast-forward included.
    n_i = jnp.sum(st.voter_mask, axis=0).astype(jnp.int32)
    n_o = jnp.sum(st.outgoing_mask, axis=0).astype(jnp.int32)
    q_i = n_i // 2 + 1
    q_o = n_o // 2 + 1
    vm_t = jnp.sum(
        jnp.where(is_tgt, st.voter_mask, False), axis=0, dtype=jnp.int32
    )
    om_t = jnp.sum(
        jnp.where(is_tgt, st.outgoing_mask, False), axis=0, dtype=jnp.int32
    )
    cnt_i = jnp.where(cg, vm_t, 0)  # the self-vote
    cnt_o = jnp.where(cg, om_t, 0)
    rec_i = cnt_i
    rec_o = cnt_o
    ff = jnp.zeros((G,), jnp.int32)
    del_g = grant & E_to_t
    del_r = rej & E_to_t
    for v in range(P):
        won_before = ((cnt_i >= q_i) | (n_i == 0)) & (
            (cnt_o >= q_o) | (n_o == 0)
        )
        lost_before = ((n_i > 0) & (cnt_i + (n_i - rec_i) < q_i)) | (
            (n_o > 0) & (cnt_o + (n_o - rec_o) < q_o)
        )
        ok = del_r[v] & ~won_before & ~lost_before & (rej_snap[v] <= agree_t[v])
        ff = jnp.where(ok, jnp.maximum(ff, rej_snap[v]), ff)
        resp_v = del_g[v] | del_r[v]
        rec_i = rec_i + (resp_v & st.voter_mask[v]).astype(jnp.int32)
        rec_o = rec_o + (resp_v & st.outgoing_mask[v]).astype(jnp.int32)
        cnt_i = cnt_i + (del_g[v] & st.voter_mask[v]).astype(jnp.int32)
        cnt_o = cnt_o + (del_g[v] & st.outgoing_mask[v]).astype(jnp.int32)
    won_t = cg & ((cnt_i >= q_i) | (n_i == 0)) & ((cnt_o >= q_o) | (n_o == 0))
    lost_t = (
        cg
        & ~won_t
        & (
            ((n_i > 0) & (cnt_i + (n_i - rec_i) < q_i))
            | ((n_o > 0) & (cnt_o + (n_o - rec_o) < q_o))
        )
    )
    C_pl = jnp.where(
        is_tgt & cg[None, :], jnp.maximum(C_pl, ff[None, :]), C_pl
    )

    # ---- hop 5: the winner's become_leader + noop append + broadcast +
    # quorum commit + commit re-broadcast; a decided loser steps down at
    # t_star (become_follower — same-term reset keeps its self-vote).
    win_mask = is_tgt & won_t[None, :]
    lose_mask = is_tgt & lost_t[None, :]
    St_pl = jnp.where(win_mask, ROLE_LEADER, St_pl)
    Ld_pl = jnp.where(win_mask, self_id, Ld_pl)
    EE_pl = jnp.where(win_mask | lose_mask, 0, EE_pl)
    HB_pl = jnp.where(win_mask | lose_mask, 0, HB_pl)
    St_pl = jnp.where(lose_mask, ROLE_FOLLOWER, St_pl)
    Ld_pl = jnp.where(lose_mask, 0, Ld_pl)
    LI_pl = LI_pl + win_mask.astype(jnp.int32)  # the noop entry
    LT_pl = jnp.where(win_mask, t_star[None, :], LT_pl)
    TS_pl = jnp.where(win_mask, LI_pl, st.term_start_index)
    matched_pl = jnp.where(win_mask[:, None, :], 0, matched_pl)
    c_t_bcast = jnp.sum(
        jnp.where(is_tgt, C_pl, 0), axis=0, dtype=jnp.int32
    )  # the noop broadcast's carried commit (pre-quorum-commit)
    noop_last = jnp.sum(
        jnp.where(win_mask, LI_pl, 0), axis=0, dtype=jnp.int32
    )
    noop_prev = noop_last - 1  # every voter synced to it pre-noop
    del_nb = (
        won_t[None, :] & member & ~is_tgt & E_from_t
        & (T_pl <= t_star[None, :])
    )
    # Probe gate (the reference's progress model): the noop append's prev
    # entry must match — voters that granted hold the caught-up log; a
    # member whose log diverges below the prev is synced by the wholesale
    # adoption model only if its agreement with the target reaches prev.
    nb_ok = del_nb & (
        (agree_t >= noop_prev[None, :]) | E_to_t
    )
    nb_bump = nb_ok & (T_pl < t_star[None, :])
    T_pl = jnp.where(nb_ok, t_star[None, :], T_pl)
    St_pl = jnp.where(nb_ok, ROLE_FOLLOWER, St_pl)
    V_pl = jnp.where(nb_bump, 0, V_pl)
    Ld_pl = jnp.where(nb_ok, t_all[None, :], Ld_pl)
    EE_pl = jnp.where(nb_ok, 0, EE_pl)
    HB_pl = jnp.where(nb_bump, 0, HB_pl)
    RT_pl = jnp.where(nb_bump, draw(T_pl), RT_pl)
    LI_pl = jnp.where(nb_ok, noop_last[None, :], LI_pl)
    LT_pl = jnp.where(nb_ok, t_star[None, :], LT_pl)
    C_pl = jnp.where(nb_ok, jnp.maximum(C_pl, c_t_bcast[None, :]), C_pl)
    in_nb = nb_ok | win_mask
    agree_row_t = agree_t  # agree[target, :] before the broadcast
    agree_pl = jnp.where(
        in_nb[:, None, :] & in_nb[None, :, :],
        noop_last[None, None, :],
        jnp.where(
            in_nb[:, None, :],
            agree_row_t[None, :, :],
            jnp.where(in_nb[None, :, :], agree_row_t[:, None, :], agree_pl),
        ),
    )
    ack_nb = nb_ok & E_to_t
    acked_m = ack_nb | win_mask  # the winner's own persisted noop
    matched_pl = jnp.where(
        is_tgt[:, None, :] & acked_m[None, :, :] & won_t[None, None, :],
        noop_last[None, None, :],
        matched_pl,
    )
    if RA is not None:
        # become_leader's wholesale tracker reset (self-only row), then
        # the noop acks mark the responders recently active.
        eye_pp = jnp.eye(P, dtype=bool)[:, :, None]
        RA = jnp.where(is_tgt[:, None, :] & won_t[None, None, :], eye_pp, RA)
        RA = jnp.where(
            is_tgt[:, None, :] & ack_nb[None, :, :] & won_t[None, None, :],
            True,
            RA,
        )
    row_t = jnp.sum(
        matched_pl * is_tgt.astype(jnp.int32)[:, None, :],
        axis=0,
        dtype=jnp.int32,
    )  # [P, G]
    mci = jnp.minimum(
        _quorum_index(row_t, st.voter_mask),
        _quorum_index(row_t, st.outgoing_mask),
    )
    commit_ok = won_t & (mci >= noop_last) & (mci < kernels.INF)
    c_t_new = jnp.where(
        commit_ok, jnp.maximum(c_t_bcast, mci), c_t_bcast
    )
    C_pl = jnp.where(is_tgt & won_t[None, :], c_t_new[None, :], C_pl)
    # The commit-advance re-broadcast is itself an append: a member whose
    # noop ack was LOST leaves its fresh probe paused (no ack since the
    # winner's tracker reset), so only acked members learn the settled
    # commit — the raft-rs pause discipline, same as the workload phase's
    # pr_ok gate.
    C_pl = jnp.where(ack_nb, jnp.maximum(C_pl, c_t_new[None, :]), C_pl)

    # reset-abort invariant: lead_transferee survives only while its
    # owner keeps leading (every become_* path runs reset(), which clears
    # it — raft.rs:942-971).
    T = jnp.where(St_pl == ROLE_LEADER, T, 0)
    out = st._replace(
        term=T_pl,
        state=St_pl,
        vote=V_pl,
        leader_id=Ld_pl,
        election_elapsed=EE_pl,
        heartbeat_elapsed=HB_pl,
        randomized_timeout=RT_pl,
        last_index=LI_pl,
        last_term=LT_pl,
        commit=C_pl,
        matched=matched_pl,
        term_start_index=TS_pl,
        agree=agree_pl,
        recent_active=RA,
        transferee=T,
    )
    return out, cg, won_t


@profiling.scope("round")
def step(
    cfg: SimConfig,
    st: SimState,
    crashed: jnp.ndarray,  # gc: bool[P, G]
    append_n: jnp.ndarray,  # gc: int32[G]
    group_ids: Optional[jnp.ndarray] = None,
    counters: Optional[jnp.ndarray] = None,  # gc: int32[N]
    health: Optional[HealthState] = None,  # gc: HealthState
    link: Optional[jnp.ndarray] = None,  # gc: bool[P, P, G]
    reconfig_propose: Optional[jnp.ndarray] = None,  # gc: bool[G]
    transfer_propose: Optional[jnp.ndarray] = None,  # gc: int32[G]
    campaign_kick: Optional[jnp.ndarray] = None,  # gc: bool[P, G]
    read_propose: Optional[jnp.ndarray] = None,  # gc: int32[G]
    blackbox: Optional[BlackboxState] = None,  # gc: BlackboxState
) -> Union[SimState, Tuple]:
    """One lockstep protocol round for every group.

    crashed:  bool[P, G] peers isolated this round (keep ticking, no I/O)
    append_n: int32[G]   entries proposed at the group's leader this round
    group_ids: optional int32[G] global group ids when st is a gathered
               sub-batch (keeps the per-(group, term) timeout PRNG global)
    counters: optional [kernels.N_COUNTERS] int32 accumulator plane; when
               given, this round's event counts (campaigns, heartbeats,
               elections won, commit entries) are folded in on-device.
    health:   optional HealthState; when given, this round's per-group
               health facts (alive-leader presence, commit advance, term
               bumps, vote splits) are folded into the planes on-device
               (kernels.update_health).
    link:     optional bool[P, P, G] directed link-reachability plane
               (link[src, dst, g]): the chaos-engine fault surface.  When
               given, every message exchange is gated per directed link and
               the round runs through the pairwise implementation
               (_linked_step); whole-peer crash is the special case
               link[p, :, g] = link[:, p, g] = False.  When None (the
               default) the original all-visible phases below run and the
               traced graph is bit-identical to the pre-chaos build — the
               choice is trace-time static, like counters/health.

    reconfig_propose: optional bool[G] — groups whose pending conf-change
    op proposes its conf entry at the acting leader this round.  The
    CALLER adds the +1 entry to `append_n`; this mask only makes the step
    REPORT where the workload landed, as a ReconfigProposal extra (owner 0
    where no alive leader acted, so the op retries next round).

    read_propose: optional int32[G] — this round's client-read commands
    (READ_* modes: 0 none, 1 Safe/ReadIndex, 2 LeaseBased), evaluated by
    the shared _read_phase on the round-ENTRY state and reported as a
    ReadReceipt extra.  Reads are pure probes: the round's protocol
    phases are unchanged by them.

    blackbox: optional BlackboxState (ISSUE 15) — this round's per-group
    deltas (max role, acting leader, max term, max commit) are folded
    into the ring on-device (kernels.blackbox_fold, computed on the
    round-EXIT state).  The step itself runs no safety audit, so the
    fired-slot bits are folded as zero here; a caller auditing between
    rounds stamps them onto the same slot with kernels.blackbox_mark,
    and the compiled runners fold bits and trace in one call instead.

    Extras are appended to the return value in (counters, health,
    blackbox, proposal, read) order for whichever are given — (state,),
    (state, counters), (state, health), (state, counters, health), each
    with the BlackboxState appended after the health extra when
    `blackbox` is given, the ReconfigProposal appended when
    reconfig_propose is given and the ReadReceipt when read_propose is
    given; bare `state` when none.  All choices are trace-time static:
    the counters=None/health=None/blackbox=None/reconfig_propose=None/
    read_propose=None graph is unchanged.

    The round = the scalar oracle's (tick all peers) + (pump to quiescence)
    + (propose at leader) + (pump), expressed as masked phases; the election
    phase is skipped wholesale when no peer campaigned this round.

    Election damping (SimConfig.check_quorum / pre_vote) always runs the
    pairwise wave path (_damped_linked_step) — lease decisions are
    receipt-order-dependent, which only the per-receiver sender-ordered
    replay expresses; with both flags False this dispatch (and the traced
    graph) is unchanged.
    """
    if blackbox is not None:
        # The black-box fold wraps whichever step path runs: the inner
        # round is traced UNCHANGED (the blackbox=None graph is the
        # pinned one) and the ring write folds on its exit state.  The
        # step runs no safety audit, so the fired-slot bits fold as
        # all-False here — kernels.blackbox_mark stamps them afterwards
        # on the ad-hoc path; compiled runners bypass this wrapper and
        # fold bits + trace in one kernels.blackbox_fold call.
        res = step(
            cfg, st, crashed, append_n, group_ids, counters, health, link,
            reconfig_propose, transfer_propose, campaign_kick,
            read_propose,
        )
        if isinstance(res, SimState):  # graftcheck: allow-no-python-branch-on-traced — pytree STRUCTURE test (trace-time static), not a value branch
            res = (res,)
        st_out = res[0]
        no_viol = jnp.zeros(
            (kernels.N_SAFETY, cfg.n_groups), bool
        )
        bb = BlackboxState(*kernels.blackbox_fold(
            blackbox.meta, blackbox.term, blackbox.commit,
            blackbox.trip_round, blackbox.round_idx,
            st_out.state, st_out.term, st_out.commit, crashed, no_viol,
        ))
        pos = (
            1
            + (1 if counters is not None else 0)
            + (1 if health is not None else 0)
        )
        return res[:pos] + (bb,) + res[pos:]
    if transfer_propose is not None and st.transferee is None:
        raise ValueError(
            "step(transfer_propose=) needs the lead_transferee plane — "
            "construct the sim with SimConfig(transfer=True) (init_state "
            "creates it); the transfer-off pytree/graphs stay pinned"
        )
    if cfg.lease_read and not cfg.check_quorum:
        # The reference's Config.validate rule verbatim: without the
        # check-quorum boundary deposal a "lease" proves nothing, so a
        # LeaseBased configuration that skipped check_quorum is a
        # misconfiguration, not a degraded mode.
        raise ValueError(
            "SimConfig(lease_read=True) requires check_quorum=True "
            "(reference: Config.validate — read_only_option == LeaseBased "
            "requires check_quorum); undamped sims serve reads through "
            "the ReadIndex quorum round only"
        )
    if cfg.check_quorum or cfg.pre_vote:
        if link is None:
            link = jnp.ones(
                (cfg.n_peers, cfg.n_peers, cfg.n_groups), bool
            )
        return _damped_linked_step(
            cfg, st, crashed, append_n, link, group_ids, counters, health,
            reconfig_propose, transfer_propose, campaign_kick,
            read_propose,
        )
    if link is not None:
        return _linked_step(
            cfg, st, crashed, append_n, link, group_ids, counters, health,
            reconfig_propose, transfer_propose, campaign_kick,
            read_propose,
        )
    G, P = cfg.n_groups, cfg.n_peers
    # Client-read phase (ISSUE 13): pure probe on the round-entry state,
    # reported as the trailing ReadReceipt extra; the protocol phases
    # below never see it.
    read_extra = (
        None
        if read_propose is None
        else _read_phase(cfg, st, crashed, read_propose, None)
    )
    # Leader-transfer pre-tick pump (ISSUE 12): runs the pending/new
    # transfer commands to quiescence BEFORE the round's ticks, exactly
    # where the scalar TransferOracle pumps them; the round's protocol
    # phases below then run on the post-transfer state while the
    # counter/health extras keep the ORIGINAL pre-round baseline (the
    # scalar facts span the whole round, transfer included).
    st_in = st
    t_extra = None
    if st.transferee is not None:
        st, t_campaigned, t_won = _transfer_phase(
            cfg, st, crashed, transfer_propose, None, group_ids
        )
        t_extra = (t_campaigned, t_won)
    self_id = jnp.arange(P, dtype=jnp.int32)[:, None] + 1  # [P, 1]
    alive = ~crashed
    node_key = _node_key(cfg, group_ids)
    lo = jnp.full((P, G), cfg.min_timeout, jnp.int32)
    hi = jnp.full((P, G), cfg.max_timeout, jnp.int32)

    def draw(term):
        return kernels.timeout_draw(node_key, term.astype(jnp.uint32), lo, hi)

    # ---- Phase A: tick every peer (crashed peers tick too — isolation cuts
    # the network, not their clock), reference: raft.rs:1024-1079.
    # promotable == voter in either half of a (possibly joint) config
    # (reference: raft.rs:2609-2610 via JointConfig::contains); members
    # (voters + learners) are who the leader replicates to.
    promotable = st.voter_mask | st.outgoing_mask
    member = promotable | st.learner_mask
    ee, hb, want_campaign, want_heartbeat, want_cq = kernels.tick_kernel(
        st.state,
        st.election_elapsed,
        st.heartbeat_elapsed,
        st.randomized_timeout,
        promotable,
        cfg.election_tick,
        cfg.heartbeat_tick,
    )
    if campaign_kick is not None:
        # Autopilot campaign kick: a MsgHup stepped at tick time (the
        # RawNode::campaign admin call) — a kicked promotable non-leader
        # campaigns NOW, through the ordinary election machinery (hup
        # resets the election clock via become_candidate's reset).
        kicked = campaign_kick & (st.state != ROLE_LEADER) & promotable
        want_campaign = want_campaign | kicked
        ee = jnp.where(kicked, 0, ee)
    transferee = st.transferee
    if transferee is not None:
        # Tick-time transfer abort (reference: raft.rs:1051-1079): the
        # transfer clock expiring at the leader's election-timeout
        # boundary abandons the pending transfer.
        transferee = jnp.where(want_cq, 0, transferee)

    # ---- Phase B: campaigners become candidates (reference:
    # raft.rs:1101-1117): term+1, vote self, redraw timeout.
    term = st.term + want_campaign.astype(jnp.int32)
    state = jnp.where(want_campaign, ROLE_CANDIDATE, st.state)
    vote = jnp.where(want_campaign, self_id, st.vote)
    leader_id = jnp.where(want_campaign, 0, st.leader_id)
    rt = jnp.where(want_campaign, draw(term), st.randomized_timeout)

    # ---- Phase C: election resolution among alive requesters.  Only this
    # round's campaigners broadcast MsgRequestVote (a pending candidate from
    # an earlier round waits for its own next timeout).  The whole phase is
    # skipped when nobody campaigned — the common steady-state case.
    req = want_campaign & alive

    def election(args):
        (
            term, state, vote, leader_id, ee, hb, rt, li, lt, matched, ts,
            commit,
        ) = args
        any_req = jnp.any(req, axis=0)  # [G]
        t_star = jnp.max(jnp.where(req, term, 0), axis=0)  # [G]
        p_idx = jnp.arange(P, dtype=jnp.int32)[:, None]  # [P, 1]

        # --- deposed-leader heartbeat interleaving.  If a live leader beat
        # this round but a higher-term campaign deposes it, its heartbeats
        # were already queued: they reach voters only if the leader's pump
        # position precedes the first campaigner's (FIFO by peer index), and
        # always reach learners (learners get no vote requests, so nothing
        # bumps them first).  Heartbeats carry commit clamped to
        # min(matched, committed) (reference: raft.rs:829-839).
        prev_leader = (state == ROLE_LEADER) & alive
        prev_has = jnp.any(prev_leader, axis=0)
        prev_lt = jnp.max(jnp.where(prev_leader, term, -1), axis=0)
        prev_acting = prev_leader & (term == prev_lt)
        prev_first = jnp.min(jnp.where(prev_acting, p_idx, P), axis=0)
        prev_is_acting = (p_idx == prev_first) & prev_has
        beat = jnp.any(want_heartbeat & prev_is_acting, axis=0)
        deposed = prev_has & (t_star > prev_lt) & any_req
        first_req = jnp.min(jnp.where(req, p_idx, P), axis=0)
        hb_first = prev_first < first_req
        prev_f = prev_is_acting.astype(jnp.int32)
        # dtype= on the masked-row sums: bare jnp.sum widens int32 to int64
        # under x64, silently turning the state planes int64 (GC007).
        prev_row = jnp.sum(
            matched * prev_f[:, None, :], axis=0, dtype=jnp.int32
        )  # [P, G]
        prev_commit = jnp.max(jnp.where(prev_is_acting, commit, 0), axis=0)
        hb_val = jnp.minimum(prev_row, prev_commit[None, :])
        apply_v = (
            deposed & beat & hb_first & alive & promotable
            & (term <= prev_lt) & ~prev_is_acting
        )
        apply_l = (
            deposed & beat & alive & st.learner_mask & (term <= prev_lt)
        )
        commit = jnp.where(
            apply_v | apply_l, jnp.maximum(commit, hb_val), commit
        )
        ee = jnp.where(apply_l, 0, ee)
        leader_id = jnp.where(apply_l, prev_first + 1, leader_id)
        # A lower-term learner receiving the heartbeat becomes a follower at
        # the (deposed) leader's term — and, unlike voters, is never
        # re-bumped by the vote requests, so the change persists
        # (reference: raft.rs:1340-1344 become_follower on higher-term
        # heartbeat).
        lrn_bump = apply_l & (term < prev_lt)
        term = jnp.where(lrn_bump, prev_lt, term)
        vote = jnp.where(lrn_bump, 0, vote)
        rt = jnp.where(lrn_bump, draw(term), rt)

        # Receiving a higher-term request makes any alive VOTER a follower
        # at that term with vote cleared (reference: raft.rs:1284-1348;
        # campaign() sends requests only to voters, raft.rs:1238).
        bump = alive & promotable & (term < t_star) & any_req
        term_c = jnp.where(bump, t_star, term)
        state_c = jnp.where(bump, ROLE_FOLLOWER, state)
        vote_c = jnp.where(bump, 0, vote)
        leader_c = jnp.where(bump, 0, leader_id)
        ee_c = jnp.where(bump, 0, ee)
        hb_c = jnp.where(bump, 0, hb)
        rt_c = jnp.where(bump, draw(term_c), rt)

        # Candidates actually contending: requesters whose (pre-bump) term
        # IS t_star; lower-term requesters just got deposed by the bump.
        cand = req & (term == t_star)  # [P, G]

        # Vote decision per alive voter v (reference: raft.rs:1418-1461):
        # can_vote (vote empty after bump) & candidate log up-to-date; ties
        # resolve to the lowest peer index (scalar pump delivery order).
        #   axes: [c, v, G]
        lt_c = lt[:, None, :]
        li_c = li[:, None, :]
        lt_v = lt[None, :, :]
        li_v = li[None, :, :]
        up_to_date = (lt_c > lt_v) | ((lt_c == lt_v) & (li_c >= li_v))
        elig = cand[:, None, :] & up_to_date

        c_idx = jnp.arange(P, dtype=jnp.int32)[:, None, None]
        first_elig = jnp.min(jnp.where(elig, c_idx, P), axis=0)  # [v, G]
        # Voters (either half of the config) respond only if alive and at
        # exactly t_star after the bump (peers with higher terms silently
        # ignore stale requests).
        responder = alive & promotable & (term_c == t_star) & any_req
        can_vote = (vote_c == 0) & responder
        grant_to = jnp.where(can_vote & (first_elig < P), first_elig, -1)
        granted_v = (grant_to[None, :, :] == c_idx) & (
            grant_to[None, :, :] >= 0
        )  # [c, v, G]

        # Joint tally: a candidate wins iff it wins BOTH majorities and
        # loses if it loses EITHER (reference: joint.rs:56-67; an empty
        # half wins by convention, majority.rs:131-136).
        def tally(mask):
            grants = jnp.sum(granted_v & mask[None, :, :], axis=1).astype(
                jnp.int32
            )
            votes_for = grants + (cand & mask).astype(jnp.int32)
            n = jnp.sum(mask, axis=0).astype(jnp.int32)  # [G]
            q = n // 2 + 1
            resp = jnp.sum(responder & mask, axis=0).astype(jnp.int32)
            missing = n - resp
            won_h = (votes_for >= q) | (n == 0)
            lost_h = (votes_for + missing < q) & (n > 0)
            return won_h, lost_h

        won_i, lost_i = tally(st.voter_mask)
        won_o, lost_o = tally(st.outgoing_mask)
        won = cand & won_i & won_o
        lost = cand & (lost_i | lost_o)

        winner_exists = jnp.any(won, axis=0)  # [G]

        # --- commit fast-forward via vote traffic (reference:
        # maybe_commit_by_vote raft.rs:2126-2164; requests carry commit info
        # raft.rs:1249-1254, reject responses raft.rs:1455-1458).  The sim's
        # logs are prefix-consistent, so the receiver's "term(m.commit) ==
        # m.commit_term" check reduces to "m.commit <= receiver.last_index".
        # Scalar pump ordering: requests processed in candidate-index order
        # (voter-side snapshots accumulate), responses in voter-index order
        # (a winner stops applying rejections once its grant quorum lands,
        # raft.rs:2184-2190 + step_leader ignoring vote responses).
        n_i = jnp.sum(st.voter_mask, axis=0).astype(jnp.int32)
        n_o = jnp.sum(st.outgoing_mask, axis=0).astype(jnp.int32)
        q_i = n_i // 2 + 1
        q_o = n_o // 2 + 1
        commit_run = commit  # running voter commits, wave-1 order
        cand_ff = jnp.zeros_like(commit)  # candidate-side fast-forwards
        for ci in range(P):
            c_active = cand[ci]  # [G]
            c_req_commit = commit[ci]  # snapshotted at campaign time
            grants_ci = granted_v[ci]  # [P_v, G]
            rej_ci = (
                responder & ~grants_ci & (p_idx != ci) & c_active[None, :]
            )
            # agree[ci] row: by symmetry, both "receiver v holds ci's
            # committed entry" and "ci holds v's committed entry" are
            # index <= agree[ci, v].
            agree_ci = st.agree[ci]  # [P_v, G]
            # candidate-side: rejections apply until the election DECIDES in
            # voter-index response order — a winner's later responses are
            # stepped by step_leader (ignored; raft.rs:2184-2190), and a
            # LOSER's later responses are stepped by step_follower (also
            # ignored: poll -> Lost -> become_follower).  The response that
            # triggers the loss itself still applies (poll runs before
            # maybe_commit_by_vote, raft.rs:2236-2247), hence the cutoffs
            # below are both STRICT prefixes.
            cnt_i = (c_active & st.voter_mask[ci]).astype(jnp.int32)
            cnt_o = (c_active & st.outgoing_mask[ci]).astype(jnp.int32)
            rec_i = cnt_i  # responses recorded so far (incl. self-vote)
            rec_o = cnt_o
            ff = jnp.zeros((G,), jnp.int32)
            for v in range(P):
                won_before = ((cnt_i >= q_i) | (n_i == 0)) & (
                    (cnt_o >= q_o) | (n_o == 0)
                )
                lost_before = (
                    (n_i > 0) & (cnt_i + (n_i - rec_i) < q_i)
                ) | ((n_o > 0) & (cnt_o + (n_o - rec_o) < q_o))
                snap = commit_run[v]
                ok = (
                    rej_ci[v]
                    & ~won_before
                    & ~lost_before
                    & (snap <= agree_ci[v])
                )
                ff = jnp.where(ok, jnp.maximum(ff, snap), ff)
                resp_v = grants_ci[v] | rej_ci[v]
                rec_i = rec_i + (resp_v & st.voter_mask[v]).astype(jnp.int32)
                rec_o = rec_o + (resp_v & st.outgoing_mask[v]).astype(
                    jnp.int32
                )
                cnt_i = cnt_i + (grants_ci[v] & st.voter_mask[v]).astype(
                    jnp.int32
                )
                cnt_o = cnt_o + (grants_ci[v] & st.outgoing_mask[v]).astype(
                    jnp.int32
                )
            cand_ff = cand_ff.at[ci].set(jnp.maximum(cand_ff[ci], ff))
            # voter-side: rejecting non-leader voters fast-forward from the
            # request's commit (leaders skip, raft.rs:2131).
            vs_apply = (
                rej_ci
                & (state_c != ROLE_LEADER)
                & (c_req_commit[None, :] > commit_run)
                & (c_req_commit[None, :] <= agree_ci)
            )
            commit_run = jnp.where(vs_apply, c_req_commit[None, :], commit_run)
        commit_c = jnp.maximum(commit_run, cand_ff)

        # Record granted votes; granting a REAL vote also resets the
        # voter's election timer (reference: raft.rs:1445-1449).
        vote_c = jnp.where(grant_to >= 0, grant_to + 1, vote_c)
        ee_c = jnp.where(grant_to >= 0, 0, ee_c)

        # Winner becomes leader and appends its noop entry (reference:
        # raft.rs:1151-1202); losers with a decided election step down.
        li_n = jnp.where(won, li + 1, li)
        lt_n = jnp.where(won, t_star, lt)
        state_c = jnp.where(won, ROLE_LEADER, state_c)
        leader_c = jnp.where(won, self_id, leader_c)
        rt_c = jnp.where(won, draw(term_c), rt_c)
        ee_c = jnp.where(won, 0, ee_c)
        hb_c = jnp.where(won, 0, hb_c)
        step_down = cand & ~won & (lost | (winner_exists & alive))
        state_c = jnp.where(step_down, ROLE_FOLLOWER, state_c)
        rt_c = jnp.where(step_down, draw(term_c), rt_c)
        ee_c = jnp.where(step_down, 0, ee_c)

        # become_leader resets the winner's OWN tracker row (matched=0; the
        # self/synced values are written in phase D) and records its noop
        # index; other owners' frozen rows are untouched
        # (reference: raft.rs:942-971, 1151-1202).
        matched_n = jnp.where(won[:, None, :], 0, matched)
        ts_n = jnp.where(won, li_n, ts)
        return (
            term_c, state_c, vote_c, leader_c, ee_c, hb_c, rt_c,
            li_n, lt_n, matched_n, ts_n, commit_c, winner_exists,
        )

    def no_election(args):
        (
            term, state, vote, leader_id, ee, hb, rt, li, lt, matched, ts,
            commit,
        ) = args
        return (
            term, state, vote, leader_id, ee, hb, rt, li, lt, matched, ts,
            commit, jnp.zeros((G,), bool),
        )

    _election_args = (
        term, state, vote, leader_id, ee, hb, rt,
        st.last_index, st.last_term, st.matched, st.term_start_index,
        st.commit,
    )
    if cfg.spmd:
        # Mesh-friendly form (ISSUE 14): the cond's `jnp.any(req)`
        # predicate is a global reduction — a per-round cross-chip
        # all-reduce under GSPMD — so the SPMD graph runs the election
        # phase unconditionally; every write inside is masked on `req`,
        # making the no-campaigner round a bit-exact no-op (pinned by
        # tests/test_sharded_parity.py, audited by GC015).
        (
            term, state, vote, leader_id, ee, hb, rt,
            new_last_index, new_last_term, matched, term_start, commit_c,
            winner_exists,
        ) = election(_election_args)
    else:
        (
            term, state, vote, leader_id, ee, hb, rt,
            new_last_index, new_last_term, matched, term_start, commit_c,
            winner_exists,
        ) = jax.lax.cond(
            jnp.any(req),
            election,
            no_election,
            _election_args,
        )

    # ---- Phase C': a campaigner that is the sole voter of both config
    # halves wins its election LOCALLY — campaign, self-vote, quorum of 1,
    # become_leader, noop append, self-commit — with no network traffic, so
    # isolation does not stop it (reference: campaign raft.rs:1217-1263,
    # where poll() after the self-vote returns Won before any message is
    # sent; found by singleton-config fuzz).  Alive solo campaigners go
    # through the normal election branch; this handles crashed ones, which
    # `req = want_campaign & alive` excludes.
    def _half_solo(mask):
        n = jnp.sum(mask, axis=0).astype(jnp.int32)  # [G]
        return (n[None, :] == 0) | ((n[None, :] == 1) & mask)

    solo_win = (
        want_campaign
        & crashed
        & _half_solo(st.voter_mask)
        & _half_solo(st.outgoing_mask)
    )
    state = jnp.where(solo_win, ROLE_LEADER, state)
    leader_id = jnp.where(solo_win, self_id, leader_id)
    new_last_index = new_last_index + solo_win.astype(jnp.int32)  # noop
    new_last_term = jnp.where(solo_win, term, new_last_term)
    term_start = jnp.where(solo_win, new_last_index, term_start)
    matched = jnp.where(solo_win[:, None, :], 0, matched)
    matched = jnp.where(
        solo_win[:, None, :]
        & (
            jnp.arange(P, dtype=jnp.int32)[None, :, None]
            == jnp.arange(P, dtype=jnp.int32)[:, None, None]
        ),
        new_last_index[:, None, :],
        matched,
    )
    commit_c = jnp.where(solo_win, new_last_index, commit_c)
    hb = jnp.where(solo_win, 0, hb)

    # ---- Phase D: replication round for groups with an alive leader.
    is_leader = (state == ROLE_LEADER) & alive
    has_leader = jnp.any(is_leader, axis=0)  # [G]
    # The acting leader is the alive leader with the highest term (a stale
    # recovered leader loses this and gets synced down below).
    lead_score = jnp.where(is_leader, term, -1)  # [P, G]
    lead_term = jnp.max(lead_score, axis=0)  # [G]
    # lowest peer index among max-term alive leaders (unique in practice)
    is_acting = is_leader & (term == lead_term)
    first_l = jnp.min(
        jnp.where(is_acting, jnp.arange(P, dtype=jnp.int32)[:, None], P), axis=0
    )  # [G]
    is_acting_leader = (jnp.arange(P, dtype=jnp.int32)[:, None] == first_l) & has_leader

    # Append workload at the leader (entries stamped with its term).
    n_app = jnp.where(has_leader, append_n, 0)  # [G]
    if transferee is not None:
        # Proposals are dropped while a transfer is pending at the acting
        # leader (reference: raft.rs:1956-2123 step_leader's
        # lead_transferee ProposalDropped).
        blocked = jnp.any(is_acting_leader & (transferee > 0), axis=0)
        n_app = jnp.where(blocked, 0, n_app)
    else:
        blocked = None
    new_last_index = new_last_index + jnp.where(is_acting_leader, n_app, 0)
    new_last_term = jnp.where(is_acting_leader, lead_term, new_last_term)

    lead_last = jnp.max(jnp.where(is_acting_leader, new_last_index, 0), axis=0)
    lead_last_term = jnp.max(
        jnp.where(is_acting_leader, new_last_term, 0), axis=0
    )

    # Did the leader send anything this round?  Heartbeats (every
    # heartbeat_tick), the election noop, or workload appends.
    lead_beat = jnp.any(want_heartbeat & is_acting_leader, axis=0)
    sent = has_leader & (lead_beat | (n_app > 0) | winner_exists)

    # Peers that sync to the leader this round: alive config members
    # (voters + learners) with reachable terms (term <= leader's —
    # higher-term peers ignore), not the leader itself (non-members are
    # outside the progress map: no traffic).
    sync = sent & alive & member & (term <= lead_term) & ~is_acting_leader
    term_bumped = sync & (term < lead_term)
    term_d = jnp.where(sync, lead_term, term)
    state_d = jnp.where(sync, ROLE_FOLLOWER, state)
    vote_d = jnp.where(term_bumped, 0, vote)
    leader_d = jnp.where(sync, first_l + 1, leader_id)
    ee = jnp.where(sync, 0, ee)
    rt = jnp.where(term_bumped, draw(term_d), rt)
    # Followers adopt the leader's log wholesale (prefix property).
    new_last_index = jnp.where(sync, lead_last, new_last_index)
    new_last_term = jnp.where(sync, lead_last_term, new_last_term)

    # Pairwise log agreement: every peer in the sync set (incl. the leader)
    # now holds exactly the leader's log, so agreement within the set is the
    # leader's last index and agreement with outsiders is the leader's
    # agreement with them (log adoption is wholesale).
    acting_f = is_acting_leader.astype(jnp.int32)  # [P, G]
    in_s = sync | is_acting_leader  # [P, G]
    agree_lead_row = jnp.sum(
        st.agree * acting_f[:, None, :], axis=0, dtype=jnp.int32
    )  # [P, G]: agree[l, b]
    agree = jnp.where(
        in_s[:, None, :] & in_s[None, :, :],
        lead_last[None, None, :],
        jnp.where(
            in_s[:, None, :],
            agree_lead_row[None, :, :],
            jnp.where(in_s[None, :, :], agree_lead_row[:, None, :], st.agree),
        ),
    )
    acting_row = jnp.sum(
        matched * acting_f[:, None, :], axis=0, dtype=jnp.int32
    )  # [P_t, G]
    acting_row = jnp.where(sync | is_acting_leader, new_last_index, acting_row)
    matched = jnp.where(
        is_acting_leader[:, None, :], acting_row[None, :, :], matched
    )
    ts_acting = jnp.sum(term_start * acting_f, axis=0, dtype=jnp.int32)  # [G]

    # Quorum commit: jointly committed = min over both majorities
    # (reference: joint.rs:47-51; an empty outgoing half returns INF so the
    # min reduces to the incoming half), gated on the entry being from the
    # leader's own term (raft_log.maybe_commit's term check; reference:
    # raft_log.rs:487-499 — mci >= the owner's term_start iff
    # term(mci) == lead_term, by log monotonicity).
    mci = jnp.minimum(
        _quorum_index(acting_row, st.voter_mask),
        _quorum_index(acting_row, st.outgoing_mask),
    )
    commit_ok = has_leader & (mci >= ts_acting) & (mci < kernels.INF)
    lead_commit_old = jnp.max(jnp.where(is_acting_leader, commit_c, 0), axis=0)
    lead_commit = jnp.where(
        commit_ok, jnp.maximum(lead_commit_old, mci), lead_commit_old
    )
    commit = jnp.where(is_acting_leader, lead_commit, commit_c)
    # Synced followers learn the leader's commit; commit_to never decreases
    # (reference: raft_log.rs:286-300), so vote-traffic fast-forwards that
    # outran a stale leader are kept.
    commit = jnp.where(sync, jnp.maximum(commit, lead_commit), commit)

    if transferee is not None:
        # reset-abort invariant: any owner that stopped leading this
        # round ran reset() on the scalar side, clearing lead_transferee.
        transferee = jnp.where(state_d == ROLE_LEADER, transferee, 0)
    out = SimState(
        term=term_d,
        state=state_d,
        vote=vote_d,
        leader_id=leader_d,
        election_elapsed=ee,
        heartbeat_elapsed=hb,
        randomized_timeout=rt,
        last_index=new_last_index,
        last_term=new_last_term,
        commit=commit,
        matched=matched,
        term_start_index=term_start,
        agree=agree,
        voter_mask=st.voter_mask,
        outgoing_mask=st.outgoing_mask,
        learner_mask=st.learner_mask,
        recent_active=st.recent_active,
        transferee=transferee,
    )
    if (
        counters is None
        and health is None
        and reconfig_propose is None
        and read_extra is None
    ):
        return out
    # A group wins at most one election per round (quorum uniqueness), and
    # the solo crashed-campaigner path is mutually exclusive with the
    # networked one, so `winner_exists | any(solo_win)` is exactly the
    # become_leader count.
    won_any = winner_exists | jnp.any(solo_win, axis=0)
    extras: Tuple = ()
    if counters is not None:
        # Device-side event counting, fused into this same dispatch; the
        # baseline is the PRE-transfer state so a transfer's commit
        # advances count, and the transfer campaign/win join the
        # campaign()/become_leader tallies like their scalar twins.
        counters = kernels.count_events(
            counters, want_campaign, want_heartbeat, won_any,
            commit - st_in.commit,
        )
        if t_extra is not None:
            counters = counters.at[kernels.CTR_CAMPAIGNS].add(
                jnp.sum(t_extra[0], dtype=jnp.int32)
            )
            counters = counters.at[kernels.CTR_ELECTIONS_WON].add(
                jnp.sum(t_extra[1], dtype=jnp.int32)
            )
        extras = extras + (counters,)
    if health is not None:
        # Device-side per-group health fold, fused into this same dispatch.
        # All facts are derived from the round's (pre, post) state pair plus
        # the in-flight election masks; the scalar oracle computes the
        # identical facts from observable scalar state
        # (simref.HealthOracle — exact parity, tests/test_health_parity.py).
        has_lead_end = jnp.any((out.state == ROLE_LEADER) & alive, axis=0)
        commit_adv = jnp.max(out.commit, axis=0) > jnp.max(
            st_in.commit, axis=0
        )
        term_bump = jnp.max(out.term, axis=0) - jnp.max(st_in.term, axis=0)
        campaigned = jnp.any(want_campaign, axis=0)
        if t_extra is None:
            won_h = won_any
        else:
            # With a transfer phase in the round, `won` is the oracle's
            # OBSERVED end-of-round fact (a transfer winner deposed by
            # the tick election later in the same round does not count) —
            # the same rule the damped path already mirrors.
            won_h = jnp.any(
                (out.state == ROLE_LEADER)
                & ((st_in.state != ROLE_LEADER) | (out.term > st_in.term)),
                axis=0,
            )
        planes, pos = kernels.update_health(
            health.planes,
            health.window_pos,
            cfg.health_window,
            has_lead_end,
            commit_adv,
            term_bump,
            campaigned & ~won_h,
        )
        extras = extras + (HealthState(planes, pos),)
    if reconfig_propose is not None:
        prop_mask = has_leader & reconfig_propose
        if blocked is not None:
            # A pending transfer drops the conf entry with the rest of
            # the batch (ProposalDropped); owner 0 makes the op retry.
            prop_mask = prop_mask & ~blocked
        extras = extras + (
            ReconfigProposal(
                owner=jnp.where(prop_mask, first_l + 1, 0),
                index=jnp.where(prop_mask, lead_last, 0),
                term=jnp.where(prop_mask, lead_term, 0),
                dropped=(append_n > 0) & (n_app == 0),
            ),
        )
    if read_extra is not None:
        extras = extras + (read_extra,)
    return (out,) + extras


@profiling.scope("round.linked")
def _linked_step(
    cfg: SimConfig,
    st: SimState,
    crashed: jnp.ndarray,  # gc: bool[P, G]
    append_n: jnp.ndarray,  # gc: int32[G]
    link: jnp.ndarray,  # gc: bool[P, P, G]
    group_ids: Optional[jnp.ndarray] = None,
    counters: Optional[jnp.ndarray] = None,  # gc: int32[N]
    health: Optional[HealthState] = None,  # gc: HealthState
    reconfig_propose: Optional[jnp.ndarray] = None,  # gc: bool[G]
    transfer_propose: Optional[jnp.ndarray] = None,  # gc: int32[G]
    campaign_kick: Optional[jnp.ndarray] = None,  # gc: bool[P, G]
    read_propose: Optional[jnp.ndarray] = None,  # gc: int32[G]
) -> Union[SimState, Tuple]:
    """The pairwise (link-gated) protocol round behind `step(..., link=)`.

    Every exchange of the round is gated per DIRECTED link: the effective
    delivery plane is `E[src, dst, g] = link & alive(src) & alive(dst)`
    (self edges excluded — self-votes and local proposals never cross the
    network).  Unlike the all-visible fast path, elections can now resolve
    per partition component (different groups of voters see different
    candidate sets at different terms), several leaders can replicate to
    disjoint reachable sets in one round, and one-way links deliver
    entries without returning acks — so the phases below mirror the scalar
    pump's wave structure directly:

      wave 1   tick-queued traffic (vote requests + leader heartbeats),
               processed per receiver in sender-index order — term bumps,
               grants/rejections, heartbeat commit learning, and the
               voter-side maybe_commit_by_vote fast-forward;
      wave 2   responses back over the reverse links: per-candidate joint
               tallies with the scalar pump's voter-index response order
               and win/loss cutoffs, candidate-side commit fast-forward;
      wave 3+  winners' noop broadcasts and heartbeat-triggered catch-up
               appends, acks over reverse links into per-owner `matched`
               rows, per-leader quorum commit, and the commit-advance
               re-broadcast that syncs one-way-reachable members;
      finally  the round's append workload at the acting leader (the
               scalar round's propose-then-pump segment).

    Semantics are identical to `step` when every link is up, and to the
    crash path when `link[p, :, g] = link[:, p, g] = False` mirrors the
    crash mask — both equivalences are pinned by tests/test_chaos_parity
    alongside per-round oracle parity (simref.ChaosOracle).
    """
    G, P = cfg.n_groups, cfg.n_peers
    st_in = st
    # The parts of the round, each under its profiling scope (the names
    # the device ops carry in a trace; they change no equation).
    sec = profiling.Sections()
    sec.at("linked.read_probe")
    # Client-read phase (ISSUE 13): pure probe on the round-entry state,
    # link-aware, reported as the trailing ReadReceipt extra.
    read_extra = (
        None
        if read_propose is None
        else _read_phase(cfg, st, crashed, read_propose, link)
    )
    sec.at("linked.tick")
    t_extra = None
    if st.transferee is not None:
        # The transfer pre-tick pump, link-gated (see _transfer_phase).
        st, t_campaigned, t_won = _transfer_phase(
            cfg, st, crashed, transfer_propose, link, group_ids
        )
        t_extra = (t_campaigned, t_won)
    self_id = jnp.arange(P, dtype=jnp.int32)[:, None] + 1  # [P, 1]
    p_idx = jnp.arange(P, dtype=jnp.int32)[:, None]  # [P, 1]
    alive = ~crashed
    off_diag = ~jnp.eye(P, dtype=bool)[:, :, None]
    E = link & alive[:, None, :] & alive[None, :, :] & off_diag
    Erev = jnp.swapaxes(E, 0, 1)  # Erev[s, v, g]: v -> s delivery
    node_key = _node_key(cfg, group_ids)
    lo = jnp.full((P, G), cfg.min_timeout, jnp.int32)
    hi = jnp.full((P, G), cfg.max_timeout, jnp.int32)

    def draw(term):
        return kernels.timeout_draw(node_key, term.astype(jnp.uint32), lo, hi)

    promotable = st.voter_mask | st.outgoing_mask
    member = promotable | st.learner_mask
    ee, hb, want_campaign, want_heartbeat, want_cq = kernels.tick_kernel(
        st.state,
        st.election_elapsed,
        st.heartbeat_elapsed,
        st.randomized_timeout,
        promotable,
        cfg.election_tick,
        cfg.heartbeat_tick,
    )

    if campaign_kick is not None:
        # Autopilot campaign kick (MsgHup at tick time; see step()).
        kicked = campaign_kick & (st.state != ROLE_LEADER) & promotable
        want_campaign = want_campaign | kicked
        ee = jnp.where(kicked, 0, ee)
    transferee = st.transferee
    if transferee is not None:
        # Tick-time transfer abort (reference: raft.rs:1051-1079).
        transferee = jnp.where(want_cq, 0, transferee)

    # ---- campaign side effects are local (reference: raft.rs:1101-1117);
    # isolation cuts the network, never the clock.
    term = st.term + want_campaign.astype(jnp.int32)
    state = jnp.where(want_campaign, ROLE_CANDIDATE, st.state)
    vote = jnp.where(want_campaign, self_id, st.vote)
    leader_id = jnp.where(want_campaign, 0, st.leader_id)
    rt = jnp.where(want_campaign, draw(term), st.randomized_timeout)

    req = want_campaign
    hb_send = want_heartbeat  # tick_kernel gates this on leadership

    sec.at("linked.election")
    # ---- wave 1: tick-queued traffic, per receiver in sender order.  The
    # running planes (T, V, Ld, ...) play each receiver's sequential
    # message processing; candidate payloads are the pre-round cursors
    # (snapshotted at campaign time, before any delivery).  The four walks
    # whose trips read what earlier senders left (wave 1, pass 1, pass 2,
    # commit stage B) are one `scan` equation each over the stacked
    # per-sender rows — the body traces ONCE, the PR 6 jaxpr discipline —
    # lowered straight-line: pass 1 and pass 2, which carry `[P, P, G]`
    # planes, behind a per-trip barrier (_sender_scan says why); wave 1 and
    # commit stage B, which carry `[P, G]` planes only, without one (on the
    # chip the barrier cost them 13% of the round, PERF.md §6 PR 49).  The
    # tally and commit stage A, whose rows never read each other, hold no
    # loop.  Same ops in the same order per sender: chaos parity stays
    # bit-exact (tests/test_chaos_parity.py).
    sender_ids = jnp.arange(P, dtype=jnp.int32)  # scan xs: the sender index

    def _wave1_body(carry, xs):
        T, V, Ld, St, EE, HB, RT, C = carry
        (d, hb_s, req_s, t_row, m_row, c_row, lt_row, li_row, agree_row,
         sid) = xs
        t_s = t_row[None, :]  # [1, G]
        # Heartbeat from s — queued at tick time, so it is delivered even
        # if s itself is deposed later this round (the FIFO interleaving
        # the all-visible path special-cases; reference: raft.rs:829-839).
        h_del = d & hb_s[None, :] & member
        h_bump = h_del & (t_s > T)
        h_acc = h_del & (t_s >= T)  # lower-term heartbeats: silent ignore
        T = jnp.where(h_bump, t_s, T)
        V = jnp.where(h_bump, 0, V)
        St = jnp.where(h_acc, ROLE_FOLLOWER, St)
        Ld = jnp.where(h_acc, sid + 1, Ld)
        EE = jnp.where(h_acc, 0, EE)
        HB = jnp.where(h_bump, 0, HB)
        RT = jnp.where(h_bump, draw(T), RT)
        hb_val = jnp.minimum(m_row, c_row[None, :])
        C = jnp.where(h_acc, jnp.maximum(C, hb_val), C)
        # Vote request from s (reference: raft.rs:1284-1348 step + the
        # can_vote check raft.rs:1418-1461 including the leader_id gate).
        r_del = d & req_s[None, :] & promotable
        r_bump = r_del & (t_s > T)
        T = jnp.where(r_bump, t_s, T)
        V = jnp.where(r_bump, 0, V)
        Ld = jnp.where(r_bump, 0, Ld)
        St = jnp.where(r_bump, ROLE_FOLLOWER, St)
        EE = jnp.where(r_bump, 0, EE)
        HB = jnp.where(r_bump, 0, HB)
        RT = jnp.where(r_bump, draw(T), RT)
        at = r_del & (T == t_s)  # higher-term receivers silently ignore
        up = (lt_row[None, :] > st.last_term) | (
            (lt_row[None, :] == st.last_term)
            & (li_row[None, :] >= st.last_index)
        )
        g = at & (V == 0) & (Ld == 0) & up
        rej = at & ~g
        snap = C  # reject responses snapshot commit BEFORE the ff
        # Voter-side maybe_commit_by_vote off the request's commit info
        # (reference: raft.rs:2126-2164; leaders skip, raft.rs:2131).
        vff = (
            rej
            & (St != ROLE_LEADER)
            & (c_row[None, :] > C)
            & (c_row[None, :] <= agree_row)
        )
        V = jnp.where(g, sid + 1, V)
        EE = jnp.where(g, 0, EE)
        C = jnp.where(vff, c_row[None, :], C)
        return (T, V, Ld, St, EE, HB, RT, C), (g, at, snap, h_acc)

    (T, V, Ld, St, EE, HB, RT, C), (grants, resps, rej_snap, hb_accs) = (
        jax.lax.scan(
            _wave1_body,
            (term, vote, leader_id, state, ee, hb, rt, st.commit),
            (
                E, hb_send, req, term, st.matched, st.commit, st.last_term,
                st.last_index, st.agree, sender_ids,
            ),
            unroll=True,
        )
    )

    # ---- wave 2: responses travel the reverse links; each candidate
    # tallies in voter-index order with the scalar cutoffs (a decided
    # election stops applying rejections — raft.rs:2184-2190 — but the
    # deciding response itself still fast-forwards, raft.rs:2236-2247):
    # every candidate at once, in closed form along the voter axis.
    C, won, lost = _real_tally(
        st, _halves(st), C, req & (St == ROLE_CANDIDATE), grants, resps,
        rej_snap, st.agree, Erev,
    )

    # Winners become leaders and append their noop (reference:
    # raft.rs:1151-1202); a crashed/cut-off singleton campaigner wins here
    # too (self-vote quorum — no solo special case needed).  Losers with a
    # decided election step down; undecided candidates wait for their next
    # timeout.
    li2 = st.last_index + won.astype(jnp.int32)
    lt2 = jnp.where(won, term, st.last_term)
    TS = jnp.where(won, li2, st.term_start_index)
    St = jnp.where(won, ROLE_LEADER, St)
    Ld = jnp.where(won, self_id, Ld)
    RT = jnp.where(won | lost, draw(T), RT)
    EE = jnp.where(won | lost, 0, EE)
    HB = jnp.where(won, 0, HB)
    St = jnp.where(lost, ROLE_FOLLOWER, St)
    eye_pp = jnp.eye(P, dtype=bool)[:, :, None]
    matched3 = jnp.where(won[:, None, :], 0, st.matched)
    matched3 = jnp.where(won[:, None, :] & eye_pp, li2[:, None, :], matched3)

    sec.at("linked.replicate")
    # ---- waves 3+: append deliveries.  Pass 1 = winner noop broadcasts
    # plus heartbeat-triggered catch-ups (the heartbeat-response path needs
    # the REVERSE link — it both resumes a paused Progress and reports the
    # lag; reference: raft.rs:1777-1819).  A delivered, term-accepted
    # append always resets the receiver's timer and leader_id
    # (step_follower MsgAppend), but the LOG is adopted only when the probe
    # matches — the receiver holds the send's prev entry, i.e.
    # `agree[s, v] >= prev` (index+term identify entries) — or the reverse
    # link is up, in which case the rejection/decr retry chain converges to
    # wholesale adoption within the pump.  Acceptance is replayed per
    # receiver in sender order so transient acks to stale leaders land in
    # their frozen matched rows exactly like the pump.
    agree_run = st.agree
    # Send-time snapshots: a leader deposed mid-wave already queued its
    # appends with ITS state (heartbeat responses are processed in wave 2,
    # before any wave-3 append can depose the processor).
    St2 = St
    C_send = C

    def _pass1_body(carry, xs):
        T, V, St, Ld, EE, RT, C, matched3, agree_run, LI, LT = carry
        (e_s, erev_s, hbacc_s, m_row, li_row, li2_row, lt2_row, st2_row,
         csend_row, won_s, t_row, sid) = xs
        res = hbacc_s & erev_s  # pr.resume() at the leader
        cu = (
            res
            & (m_row < li_row[None, :])
            & (st2_row == ROLE_LEADER)[None, :]
        )
        dmask = e_s & member & (won_s[None, :] | cu)
        msg = dmask & (t_row[None, :] >= T)
        agree_s = jax.lax.dynamic_index_in_dim(
            agree_run, sid, 0, keepdims=False
        )
        # The winner's noop probe carries prev = its pre-noop cursor (the
        # fresh-reset Progress is unpaused, so it reaches everyone).
        adopt = msg & (cu | (agree_s >= li_row[None, :]) | erev_s)
        bump = msg & (t_row[None, :] > T)
        T = jnp.where(msg, t_row[None, :], T)
        V = jnp.where(bump, 0, V)
        St = jnp.where(msg, ROLE_FOLLOWER, St)
        Ld = jnp.where(msg, sid + 1, Ld)
        EE = jnp.where(msg, 0, EE)
        RT = jnp.where(bump, draw(T), RT)
        C = jnp.where(adopt, jnp.maximum(C, csend_row[None, :]), C)
        ack = adopt & erev_s
        m3_s = jax.lax.dynamic_index_in_dim(matched3, sid, 0, keepdims=False)
        matched3 = jnp.where(
            (jnp.arange(P, dtype=jnp.int32) == sid)[:, None, None],
            jnp.where(ack, jnp.maximum(m3_s, li2_row[None, :]), m3_s)[
                None, :, :
            ],
            matched3,
        )
        sent_any = jnp.any(adopt, axis=0)  # [G]
        in_s = adopt | ((p_idx == sid) & sent_any[None, :])
        lead_row = agree_s
        agree_run = jnp.where(
            in_s[:, None, :] & in_s[None, :, :],
            li2_row[None, None, :],
            jnp.where(
                in_s[:, None, :],
                lead_row[None, :, :],
                jnp.where(in_s[None, :, :], lead_row[:, None, :], agree_run),
            ),
        )
        LI = jnp.where(adopt, li2_row[None, :], LI)
        LT = jnp.where(adopt, lt2_row[None, :], LT)
        return (T, V, St, Ld, EE, RT, C, matched3, agree_run, LI, LT), (res,)

    (
        (T, V, St, Ld, EE, RT, C, matched3, agree_run, LI, LT),
        (resumed,),
    ) = _sender_scan(
        _pass1_body,
        (T, V, St, Ld, EE, RT, C, matched3, agree_run, li2, lt2),
        (
            E, Erev, hb_accs, st.matched, st.last_index, li2, lt2, St2,
            C_send, won, term, sender_ids,
        ),
    )

    sec.at("linked.commit")
    # Stage-A quorum commit per leader off the freshly acked matched rows
    # (the term gate is raft_log.maybe_commit's own-term check).  An owner
    # reads and writes its own row only, so the owners are a batch axis.
    by_owner = jnp.swapaxes(matched3, 1, 2)  # [P_owner, G, P_peer]
    mci = jnp.minimum(
        kernels.committed_index(by_owner, st.voter_mask.T[None]),
        kernels.committed_index(by_owner, st.outgoing_mask.T[None]),
    )  # [P_owner, G]
    ok = (St == ROLE_LEADER) & (mci >= TS) & (mci < kernels.INF)
    c_new = jnp.where(ok, jnp.maximum(C, mci), C)
    adv = c_new > C
    C = c_new

    sec.at("linked.replicate")
    # Pass 2: a commit advance re-broadcasts appends to every member whose
    # Progress can still send (bcast_append on maybe_commit; reference:
    # raft.rs:893-904): Replicate members (acked since this leader's
    # election — matched > 0) and members whose heartbeat response resumed
    # a paused probe this round.  The send carries prev = the leader's
    # current last, so only in-sync members (or reverse-linked ones, via
    # the retry chain) accept it — a one-way member that missed a send
    # stays gapped until its reverse link heals.
    def _pass2_body(carry, xs):
        T, V, St, Ld, EE, RT, LI, LT, matched3, agree_run = carry
        (e_s, erev_s, adv_s, res_s, li2_row, lt2_row, t_row, sid) = xs
        m3_s = jax.lax.dynamic_index_in_dim(matched3, sid, 0, keepdims=False)
        dmask = e_s & member & adv_s[None, :] & ((m3_s > 0) | res_s)
        msg = dmask & (t_row[None, :] >= T)
        agree_s = jax.lax.dynamic_index_in_dim(
            agree_run, sid, 0, keepdims=False
        )
        adopt = msg & ((agree_s >= li2_row[None, :]) | erev_s)
        bump = msg & (t_row[None, :] > T)
        T = jnp.where(msg, t_row[None, :], T)
        V = jnp.where(bump, 0, V)
        St = jnp.where(msg, ROLE_FOLLOWER, St)
        Ld = jnp.where(msg, sid + 1, Ld)
        EE = jnp.where(msg, 0, EE)
        RT = jnp.where(bump, draw(T), RT)
        LI = jnp.where(adopt, li2_row[None, :], LI)
        LT = jnp.where(adopt, lt2_row[None, :], LT)
        ack = adopt & erev_s
        matched3 = jnp.where(
            (jnp.arange(P, dtype=jnp.int32) == sid)[:, None, None],
            jnp.where(ack, jnp.maximum(m3_s, li2_row[None, :]), m3_s)[
                None, :, :
            ],
            matched3,
        )
        sent_any = jnp.any(adopt, axis=0)
        in_s = adopt | ((p_idx == sid) & sent_any[None, :])
        lead_row = agree_s
        agree_run = jnp.where(
            in_s[:, None, :] & in_s[None, :, :],
            li2_row[None, None, :],
            jnp.where(
                in_s[:, None, :],
                lead_row[None, :, :],
                jnp.where(in_s[None, :, :], lead_row[:, None, :], agree_run),
            ),
        )
        return (T, V, St, Ld, EE, RT, LI, LT, matched3, agree_run), ()

    (T, V, St, Ld, EE, RT, LI, LT, matched3, agree_run), _ = _sender_scan(
        _pass2_body,
        (T, V, St, Ld, EE, RT, LI, LT, matched3, agree_run),
        (E, Erev, adv, resumed, li2, lt2, term, sender_ids),
    )

    sec.at("linked.commit")

    def _commit_b_body(C, xs):
        (m3_row, st_row, ts_row, e_s, erev_s, res_s, agree_s, li2_row,
         csend_row, t_row, sid) = xs
        mci = jnp.minimum(
            _quorum_index(m3_row, st.voter_mask),
            _quorum_index(m3_row, st.outgoing_mask),
        )
        c_s = jax.lax.dynamic_index_in_dim(C, sid, 0, keepdims=False)
        ok = (
            (st_row == ROLE_LEADER)
            & (mci >= ts_row)
            & (mci < kernels.INF)
        )
        c_new = jnp.where(ok, jnp.maximum(c_s, mci), c_s)
        C = jnp.where(p_idx == sid, c_new[None, :], C)
        # Commit propagation: if LEADER s's commit advanced past what its
        # append sends carried, the post-advance broadcast delivers the
        # settled value — to sendable Progresses only (paused probes miss
        # it, the same gate as pass 2) and only where the empty append's
        # probe matches or the reverse link lets the retry chain run.
        # The leadership gate matters: a stale ex-leader whose commit rose
        # this round as a RECEIVER broadcasts nothing.
        elig = (
            e_s
            & member
            & (st_row == ROLE_LEADER)[None, :]
            & (t_row[None, :] >= T)
            & ((m3_row > 0) | res_s)
            & ((agree_s >= li2_row[None, :]) | erev_s)
            & (c_new > csend_row)[None, :]
        )
        C = jnp.where(elig, jnp.maximum(C, c_new[None, :]), C)
        return C, ()

    C, _ = jax.lax.scan(
        _commit_b_body,
        C,
        (
            matched3, St, TS, E, Erev, resumed, agree_run, li2, C_send,
            term, sender_ids,
        ),
        unroll=True,
    )

    sec.at("linked.workload")
    # ---- the round's append workload at the acting leader (the scalar
    # round's propose-then-pump segment, evaluated after the tick pump
    # quiesces): link-gated port of the all-visible Phase D.
    is_leader = (St == ROLE_LEADER) & alive
    has_leader = jnp.any(is_leader, axis=0)
    lead_term = jnp.max(jnp.where(is_leader, T, -1), axis=0)
    is_acting = is_leader & (T == lead_term)
    first_l = jnp.min(jnp.where(is_acting, p_idx, P), axis=0)
    is_acting_leader = (p_idx == first_l) & has_leader
    n_app = jnp.where(has_leader, append_n, 0)
    if transferee is not None:
        # ProposalDropped while a transfer is pending at the acting
        # leader (reference: raft.rs step_leader's lead_transferee gate).
        blocked = jnp.any(is_acting_leader & (transferee > 0), axis=0)
        n_app = jnp.where(blocked, 0, n_app)
    else:
        blocked = None
    sent_b = has_leader & (n_app > 0)
    lead_pre_last = jnp.max(jnp.where(is_acting_leader, LI, 0), axis=0)
    LI = LI + jnp.where(is_acting_leader, n_app, 0)
    LT = jnp.where(is_acting_leader & (n_app > 0), lead_term, LT)
    lead_last = jnp.max(jnp.where(is_acting_leader, LI, 0), axis=0)
    lead_last_term = jnp.max(jnp.where(is_acting_leader, LT, 0), axis=0)
    reach_b = jnp.any(E & is_acting_leader[:, None, :], axis=0)  # [P_v, G]
    ack_path = jnp.any(E & is_acting_leader[None, :, :], axis=1)  # v -> l
    acting_f = is_acting_leader.astype(jnp.int32)
    acting_row0 = jnp.sum(
        matched3 * acting_f[:, None, :], axis=0, dtype=jnp.int32
    )
    resumed_act = jnp.any(
        resumed & is_acting_leader[:, None, :], axis=0
    )
    agree_act = jnp.sum(
        agree_run * acting_f[:, None, :], axis=0, dtype=jnp.int32
    )
    # The proposal broadcast skips paused probes (no ack since this
    # leader's election and no resuming heartbeat response this round);
    # delivered appends reset timers either way, but the log is adopted
    # only on a probe match or a live reverse link (retry convergence).
    pr_ok = (acting_row0 > 0) | resumed_act
    sync_msg = (
        sent_b
        & reach_b
        & member
        & (T <= lead_term)
        & ~is_acting_leader
        & pr_ok
    )
    sync_b = sync_msg & ((agree_act >= lead_pre_last[None, :]) | ack_path)
    bump_b = sync_msg & (T < lead_term)
    T = jnp.where(sync_msg, lead_term, T)
    St = jnp.where(sync_msg, ROLE_FOLLOWER, St)
    V = jnp.where(bump_b, 0, V)
    Ld = jnp.where(sync_msg, first_l + 1, Ld)
    EE = jnp.where(sync_msg, 0, EE)
    RT = jnp.where(bump_b, draw(T), RT)
    LI = jnp.where(sync_b, lead_last, LI)
    LT = jnp.where(sync_b, lead_last_term, LT)
    in_sb = sync_b | (is_acting_leader & sent_b)
    # dtype= on the masked-row sums: bare jnp.sum widens int32 to int64
    # under x64, silently turning the planes int64 (GC007).
    lead_row_b = jnp.sum(
        agree_run * acting_f[:, None, :], axis=0, dtype=jnp.int32
    )
    agree_run = jnp.where(
        in_sb[:, None, :] & in_sb[None, :, :],
        lead_last[None, None, :],
        jnp.where(
            in_sb[:, None, :],
            lead_row_b[None, :, :],
            jnp.where(in_sb[None, :, :], lead_row_b[:, None, :], agree_run),
        ),
    )
    acting_row = acting_row0
    acked_b = (sync_b & ack_path) | (is_acting_leader & sent_b)
    acting_row = jnp.where(
        acked_b, jnp.maximum(acting_row, lead_last), acting_row
    )
    matched3 = jnp.where(
        is_acting_leader[:, None, :], acting_row[None, :, :], matched3
    )
    ts_acting = jnp.sum(TS * acting_f, axis=0, dtype=jnp.int32)
    mci_b = jnp.minimum(
        _quorum_index(acting_row, st.voter_mask),
        _quorum_index(acting_row, st.outgoing_mask),
    )
    commit_ok = sent_b & (mci_b >= ts_acting) & (mci_b < kernels.INF)
    lead_commit_old = jnp.max(jnp.where(is_acting_leader, C, 0), axis=0)
    lead_commit = jnp.where(
        commit_ok, jnp.maximum(lead_commit_old, mci_b), lead_commit_old
    )
    C = jnp.where(is_acting_leader, lead_commit, C)
    C = jnp.where(sync_b, jnp.maximum(C, lead_commit), C)
    sec.end()

    if transferee is not None:
        # reset-abort invariant (see step()): only standing leaders keep
        # their lead_transferee.
        transferee = jnp.where(St == ROLE_LEADER, transferee, 0)
    out = SimState(
        term=T,
        state=St,
        vote=V,
        leader_id=Ld,
        election_elapsed=EE,
        heartbeat_elapsed=HB,
        randomized_timeout=RT,
        last_index=LI,
        last_term=LT,
        commit=C,
        matched=matched3,
        term_start_index=TS,
        agree=agree_run,
        voter_mask=st.voter_mask,
        outgoing_mask=st.outgoing_mask,
        learner_mask=st.learner_mask,
        recent_active=st.recent_active,
        transferee=transferee,
    )
    if (
        counters is None
        and health is None
        and reconfig_propose is None
        and read_extra is None
    ):
        return out
    won_any = jnp.any(won, axis=0)
    extras: Tuple = ()
    if counters is not None:
        counters = kernels.count_events(
            counters, want_campaign, want_heartbeat, won_any,
            out.commit - st_in.commit,
        )
        if t_extra is not None:
            counters = counters.at[kernels.CTR_CAMPAIGNS].add(
                jnp.sum(t_extra[0], dtype=jnp.int32)
            )
            counters = counters.at[kernels.CTR_ELECTIONS_WON].add(
                jnp.sum(t_extra[1], dtype=jnp.int32)
            )
        extras = extras + (counters,)
    if health is not None:
        has_lead_end = jnp.any((out.state == ROLE_LEADER) & alive, axis=0)
        commit_adv = jnp.max(out.commit, axis=0) > jnp.max(
            st_in.commit, axis=0
        )
        term_bump = jnp.max(out.term, axis=0) - jnp.max(st_in.term, axis=0)
        campaigned = jnp.any(want_campaign, axis=0)
        if t_extra is None:
            won_h = won_any
        else:
            # Observed end-of-round `won` when a transfer phase ran (the
            # oracle's rule; see the damped path).
            won_h = jnp.any(
                (out.state == ROLE_LEADER)
                & ((st_in.state != ROLE_LEADER) | (out.term > st_in.term)),
                axis=0,
            )
        planes, pos = kernels.update_health(
            health.planes,
            health.window_pos,
            cfg.health_window,
            has_lead_end,
            commit_adv,
            term_bump,
            campaigned & ~won_h,
        )
        extras = extras + (HealthState(planes, pos),)
    if reconfig_propose is not None:
        # Where the round's conf entry landed (lead_last is the leader's
        # post-append last index — the conf entry is appended LAST, after
        # the round's workload); owner 0 where no alive leader acted, so
        # the pending op retries next round.
        prop_mask = has_leader & reconfig_propose
        if blocked is not None:
            # A pending transfer drops the conf entry with the rest of
            # the batch (ProposalDropped); owner 0 makes the op retry.
            prop_mask = prop_mask & ~blocked
        extras = extras + (
            ReconfigProposal(
                owner=jnp.where(prop_mask, first_l + 1, 0),
                index=jnp.where(prop_mask, lead_last, 0),
                term=jnp.where(prop_mask, lead_term, 0),
                dropped=(append_n > 0) & (n_app == 0),
            ),
        )
    if read_extra is not None:
        extras = extras + (read_extra,)
    return (out,) + extras


def _sender_scan(body, carry, xs):
    """A wave's loop over the P stacked sender rows (the damped round's
    five, the stock round's two retry passes): ONE `scan` equation in the
    jaxpr (the per-sender body traces once — the PR 6 jaxpr-size
    discipline), lowered STRAIGHT-LINE (`unroll=True`: P is a static shape,
    3 or 5) with an `optimization_barrier` on the carry at the head of
    every trip.

    Rolled, the loop is an XLA `while` whose every trip cuts its `[P, G]`
    rows out of the `[P, P, G]` planes with a dynamic_slice and rewrites
    the whole stacked outputs with a dynamic_update_slice: on the chip a
    third of a damped round at 100k x 5 (PERF.md §6, PR 41).  Unrolled
    WITHOUT the barrier that bookkeeping goes and the round gets slower
    all the same: the compiler schedules the five trips as one pool of
    ops, the carried planes (`agree_run[P, P, G]` above all) fall out of
    the chip's fast memory, and selects and reduces far from the loop run
    three to six times slower.  The barrier keeps the trips apart as the
    `while` did — one trip's working set live at a time — and costs no op.
    Same ops in the same order per sender, so every output is bit-equal."""

    def trip(carry, x):
        return body(jax.lax.optimization_barrier(carry), x)

    return jax.lax.scan(trip, carry, xs, unroll=True)


# ---- the tallies (the real one is the stock round's too; the pre-vote one
# the damped round's alone).  A candidate's tally reads and writes
# only its own row of the [P, G] planes and its own [P_voter, G] slab of the
# response planes, so the candidate axis is a batch axis (PR 43); and what a
# walk over the voters in receipt order carries from one response to the
# next has a closed form along the voter axis, so neither tally holds a loop
# (PR 45): one prefix sum, elementwise planes and reductions over axis 1 of
# the [P_cand, P_voter, G] response planes as the waves hand them over —
# candidate-major, never transposed, never sliced by a traced index.  Every
# flag a later wave reads comes out of a REDUCE, where XLA's CPU backend
# stops copying a producer into its consumers' fusions (docs/PERF.md holds
# the argument and the compile times).  tests/test_tally_batched.py holds
# both tallies to a plain per-candidate, per-voter, per-group reference.


def _ended_before(
    won0: jnp.ndarray,  # gc: bool[P, G]
    event: jnp.ndarray,  # gc: bool[P, P, G]
) -> jnp.ndarray:
    """[c, v]: the poll of candidate c had ended BEFORE voter v's response
    — it was won on the candidate's own vote (`won0`), or an earlier voter's
    response was an `event`: v lies past the first one."""
    n = event.shape[1]
    voter = jnp.arange(n, dtype=jnp.int32)[None, :, None]
    first = jnp.min(jnp.where(event, voter, n), axis=1)
    return won0[:, None] | (voter > first[:, None])


def _unpack(p: jnp.ndarray) -> Tuple[jnp.ndarray, ...]:  # gc: any
    """(cnt_i, cnt_o, rec_i, rec_o) out of `_poll_counts`' packed counts."""
    return p & 0xFF, (p >> 8) & 0xFF, (p >> 16) & 0xFF, p >> 24


def _poll_counts(
    st: SimState,
    own: jnp.ndarray,  # gc: bool[P, G]
    grant: jnp.ndarray,  # gc: bool[P, P, G]
    reject: jnp.ndarray,  # gc: bool[P, P, G]
) -> Tuple[jnp.ndarray, ...]:
    """A poll's four counts — grants in the incoming and in the outgoing
    half, recorded responses in each — a byte apiece in one int32 (none
    passes P), so that ONE prefix sum along the voter axis carries all four
    -> (own, before, after, total): a candidate's own vote, counted in the
    halves it sits in [P_cand, G]; what voter v's response finds and what
    it leaves [P_cand, P_voter, G]; the poll's end [P_cand, G].  A `grant`
    counts as a grant and as a response, a recorded `reject` as a
    response."""
    half = st.voter_mask.astype(jnp.int32) + (
        st.outgoing_mask.astype(jnp.int32) << 8
    )
    w_g, w_r = half * 0x10001, half << 16
    each = jnp.where(grant, w_g[None], jnp.where(reject, w_r[None], 0))
    mine = jnp.where(own, w_g, 0)
    after = mine[:, None] + jax.lax.cumsum(each, axis=1)
    before = after - each
    return mine, before, after, mine + jnp.sum(each, axis=1, dtype=jnp.int32)


@profiling.scope("tally.real")
def _real_tally(
    st: SimState,
    h: _Halves,
    C: jnp.ndarray,  # gc: int32[P, G]
    cand_active: jnp.ndarray,  # gc: bool[P, G]
    t_grants: jnp.ndarray,  # gc: bool[P, P, G]
    t_resps: jnp.ndarray,  # gc: bool[P, P, G]
    t_snap: jnp.ndarray,  # gc: int32[P, P, G]
    agree_pl: jnp.ndarray,  # gc: int32[P, P, G]
    erev: jnp.ndarray,  # gc: bool[P, P, G]
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The real-election tally (the _linked_step wave-2 machinery) of every
    candidate at once, responses in voter order -> (C', won, lost).  The
    response planes are [P_cand, P_voter, G]; `erev` says whose response
    reaches its candidate.  A reject carries the voter's commit (`t_snap`),
    which fast-forwards an undecided candidate that agrees that far.

    poll() records every delivered response whatever the tally stands at,
    so the counts voter v's response finds are plain prefix sums; only the
    fast-forward asks whether they had decided the election by then."""
    del_g = t_grants & erev
    del_r = (t_resps & ~t_grants) & erev
    _, before, _, total = _poll_counts(st, cand_active, del_g, del_r)
    cnt = _unpack(before)
    decided = _has_quorum(h, *cnt[:2]) | _cannot_win(h, *cnt)
    ok = del_r & ~decided & (t_snap <= agree_pl)
    ff = jnp.max(jnp.where(ok, t_snap, 0), axis=1)
    total = _unpack(total)
    won = cand_active & _has_quorum(h, *total[:2])
    lost = cand_active & ~won & _cannot_win(h, *total)
    return jnp.maximum(C, ff), won, lost


@profiling.scope("tally.pre")
def _pre_tally(
    st: SimState,
    h: _Halves,
    planes: Tuple[jnp.ndarray, ...],  # gc: any
    act: jnp.ndarray,  # gc: bool[P, G]
    t_c0: jnp.ndarray,  # gc: int32[P, G]
    p_grants: jnp.ndarray,  # gc: bool[P, P, G]
    p_resps: jnp.ndarray,  # gc: bool[P, P, G]
    p_resp_t: jnp.ndarray,  # gc: int32[P, P, G]
    p_snap: jnp.ndarray,  # gc: int32[P, P, G]
    erev: jnp.ndarray,  # gc: bool[P, P, G]
    draw: Callable[[jnp.ndarray], jnp.ndarray],
) -> Tuple[jnp.ndarray, ...]:
    """The pre-vote tally of every pre-candidate (`act`, pre-campaign terms
    `t_c0`) at once, and each one's end-of-wave state -> (C, T, V, St, EE,
    HB, RT, pre_won).  Responses in voter order; a reject at a term above
    the candidate's CURRENT term deposes it (become_follower at the
    response term, chainable), a reject at exactly its pre-campaign term
    records a poll rejection, grants record while undecided; on quorum the
    pre-winner runs campaign(Election) — term+1, vote self, timers reset.
    Deposition after the win knocks the fresh candidate back down (its
    queued broadcast still delivers).  `draw(T)` is the round's timeout
    draw on whole [P, G] planes.

    The first-event rule.  A pre-candidate polls only while it IS one, and
    three responses end that, each for good: the grant that makes the
    quorum (campaign(Election)), the same-term reject that puts it out of
    reach (become_follower at its own term), a reject from a higher term
    (become_follower there).  Up to and at the first of them the poll has
    recorded every grant and every same-term reject, so its counts are the
    FREE counts — prefix sums that never ask whether it still polls — and
    its term is still `t_c0`; after it nothing records.  So the first voter
    whose response wins or loses on the free counts, or rejects above
    `t_c0`, IS the first event, and `ended` (one before this voter) is the
    walk's `~undecided`, exactly.  Deposition alone goes on afterwards:
    become_follower compares with the CURRENT term — `t_c0`, `t_c0 + 1`
    from a win on (a win is a first event: it lies before voter v iff the
    poll had ended by v and was won at all), then the highest deposing term
    so far; a reject which that running maximum hides is at or below it, so
    counting it changes neither the flag nor the final term."""
    C, T, V, St, EE, HB, RT = planes
    t0 = t_c0[:, None]
    del_g = p_grants & erev
    del_r = (p_resps & ~p_grants) & erev
    same_t = del_r & (p_resp_t == t0)
    mine, _, after, _ = _poll_counts(st, act, del_g, same_t)
    won0 = act & _has_quorum(h, *_unpack(mine)[:2])
    cnt = _unpack(after)
    won_free = del_g & _has_quorum(h, *cnt[:2])
    lost_free = same_t & _cannot_win(h, *cnt)
    ended = _ended_before(
        won0, won_free | lost_free | (del_r & (p_resp_t > t0))
    )
    won_f = won0 | jnp.any(won_free & ~ended, axis=1)
    lost_f = jnp.any(lost_free & ~ended, axis=1)
    ok = same_t & ~ended & (p_snap <= st.agree)
    ff = jnp.max(jnp.where(ok, p_snap, 0), axis=1)
    won_before = (ended & won_f[:, None]).astype(jnp.int32)
    dep = del_r & (p_resp_t > t0 + won_before)
    dep_f = jnp.any(dep, axis=1)
    cur_t = jnp.maximum(
        t_c0 + won_f.astype(jnp.int32),
        jnp.max(jnp.where(dep, p_resp_t, 0), axis=1),
    )
    won_f = won_f & act
    lost_f = lost_f & act
    dep_f = dep_f & act
    # End-of-wave state of every candidate row.
    self_id = jnp.arange(act.shape[0], dtype=jnp.int32)[:, None] + 1
    C = jnp.maximum(C, ff)
    T = jnp.where(act, cur_t, T)
    bumped = act & (cur_t != t_c0)
    V = jnp.where(won_f & ~dep_f, self_id, jnp.where(dep_f & bumped, 0, V))
    St = jnp.where(
        won_f & ~dep_f,
        ROLE_CANDIDATE,
        jnp.where(dep_f | lost_f, ROLE_FOLLOWER, St),
    )
    settled = won_f | lost_f | dep_f
    EE = jnp.where(settled, 0, EE)
    HB = jnp.where(settled, 0, HB)
    RT = jnp.where(won_f | dep_f, draw(T), RT)
    return C, T, V, St, EE, HB, RT, won_f


@profiling.scope("round.damped")
def _damped_linked_step(
    cfg: SimConfig,
    st: SimState,
    crashed: jnp.ndarray,  # gc: bool[P, G]
    append_n: jnp.ndarray,  # gc: int32[G]
    link: jnp.ndarray,  # gc: bool[P, P, G]
    group_ids: Optional[jnp.ndarray] = None,
    counters: Optional[jnp.ndarray] = None,  # gc: int32[N]
    health: Optional[HealthState] = None,  # gc: HealthState
    reconfig_propose: Optional[jnp.ndarray] = None,  # gc: bool[G]
    transfer_propose: Optional[jnp.ndarray] = None,  # gc: int32[G]
    campaign_kick: Optional[jnp.ndarray] = None,  # gc: bool[P, G]
    read_propose: Optional[jnp.ndarray] = None,  # gc: int32[G]
) -> Union[SimState, Tuple]:
    """The damped (check-quorum / pre-vote / lease) pairwise round.

    Extends _linked_step's wave replay with the three DESIGN.md §8
    mechanisms, all receipt-order exact:

      tick     each leader's election-timeout boundary reads-and-clears
               its recent_active row; without an active quorum it steps
               down AND suppresses that round's heartbeat
               (tick_heartbeat returns before MsgBeat);
      lease    a voter ignores a higher-term (pre-)vote request entirely
               while leader_id != 0 and election_elapsed < election_tick
               AT RECEIPT — the running (Ld, EE) planes of the
               per-receiver sender-ordered scan ARE receipt time, so the
               pump-position dependence (leader heartbeat before or after
               the candidate's request) falls out of the replay order;
      nudge    lower-term append/heartbeat traffic draws an empty
               MsgAppendResponse at the receiver's term; the stale leader
               processes it in response order, deposing it mid-stream —
               acks after the first deposing nudge are dropped exactly
               like the scalar step ignores them;
      pre-vote campaigners probe at term+1 without bumping anything;
               pre-winners run the REAL election two waves later, which
               is where the scalar pump puts it — real vote requests
               interleave with catch-up appends per receiver in sender
               order, so the whole election block shifts into the append
               waves when cfg.pre_vote is set.

    Both flags are trace-time static; this function is only reached when
    at least one is on, so the undamped graphs are untouched.  Parity:
    per-round state AND health planes vs ScalarCluster(check_quorum=...,
    pre_vote=...) in tests/test_damping_parity.py.
    """
    if st.recent_active is None:
        raise ValueError(
            "damped step (SimConfig.check_quorum/pre_vote) needs the "
            "recent_active plane but the state has None — this state was "
            "built for an undamped config (e.g. an undamped checkpoint "
            "loaded into a damped sim); rebuild it with init_state(cfg) "
            "or carry the plane over explicitly"
        )
    G, P = cfg.n_groups, cfg.n_peers
    st_in = st
    # The parts of the round, each under its profiling scope (the names
    # the device ops carry in a trace; they change no equation).
    sec = profiling.Sections()
    sec.at("damped.read_probe")
    # Client-read phase (ISSUE 13): pure probe on the round-entry state —
    # the lease gate plus the damped (nudge-cutoff) ReadIndex fallback —
    # BEFORE the transfer pump and the ticks, where the scalar oracle
    # steps MsgReadIndex.
    read_extra = (
        None
        if read_propose is None
        else _read_phase(cfg, st, crashed, read_propose, link)
    )
    sec.at("damped.tick")
    t_extra = None
    if st.transferee is not None:
        # The transfer pre-tick pump, link-gated and lease-exempt (the
        # CAMPAIGN_TRANSFER force context; see _transfer_phase).
        st, t_campaigned, t_won = _transfer_phase(
            cfg, st, crashed, transfer_propose, link, group_ids
        )
        t_extra = (t_campaigned, t_won)
    cq = cfg.check_quorum
    pv = cfg.pre_vote
    et = cfg.election_tick
    self_id = jnp.arange(P, dtype=jnp.int32)[:, None] + 1  # [P, 1]
    p_idx = jnp.arange(P, dtype=jnp.int32)[:, None]  # [P, 1]
    alive = ~crashed
    off_diag = ~jnp.eye(P, dtype=bool)[:, :, None]
    eye_pp = jnp.eye(P, dtype=bool)[:, :, None]
    E = link & alive[:, None, :] & alive[None, :, :] & off_diag
    Erev = jnp.swapaxes(E, 0, 1)
    node_key = _node_key(cfg, group_ids)
    lo = jnp.full((P, G), cfg.min_timeout, jnp.int32)
    hi = jnp.full((P, G), cfg.max_timeout, jnp.int32)

    def draw(term):
        return kernels.timeout_draw(node_key, term.astype(jnp.uint32), lo, hi)

    promotable = st.voter_mask | st.outgoing_mask
    member = promotable | st.learner_mask
    ee, hb, want_campaign, want_heartbeat, want_cq = kernels.tick_kernel(
        st.state,
        st.election_elapsed,
        st.heartbeat_elapsed,
        st.randomized_timeout,
        promotable,
        cfg.election_tick,
        cfg.heartbeat_tick,
    )
    RA = st.recent_active  # bool[P, P, G]
    state0, leader0 = st.state, st.leader_id

    # ---- check-quorum boundary, at tick time (reference: raft.rs
    # tick_heartbeat 1051-1079 + step_leader MsgCheckQuorum): the
    # MsgCheckQuorum step reads-and-clears the flags whenever the boundary
    # fires; without an active quorum the leader becomes a follower at its
    # OWN term (vote kept, leader_id cleared, hb zeroed by reset; the
    # (node, term)-keyed timeout redraw is idempotent) and tick_heartbeat
    # returns before MsgBeat — the boundary round's heartbeat is
    # suppressed.
    if cq:
        qa = kernels.check_quorum_active(
            RA, st.voter_mask, st.outgoing_mask
        )
        cq_dep = want_cq & ~qa
        RA = jnp.where(want_cq[:, None, :], eye_pp, RA)
        state0 = jnp.where(cq_dep, ROLE_FOLLOWER, state0)
        leader0 = jnp.where(cq_dep, 0, leader0)
        hb = jnp.where(cq_dep, 0, hb)
        want_heartbeat = want_heartbeat & ~cq_dep
    else:
        cq_dep = jnp.zeros((P, G), bool)

    if campaign_kick is not None:
        # Autopilot campaign kick (MsgHup at tick time; see step()) — a
        # kicked peer campaigns through the ordinary damped machinery
        # (pre-vote probe first when cfg.pre_vote, like hup(false)).
        kicked = campaign_kick & (st.state != ROLE_LEADER) & promotable
        want_campaign = want_campaign | kicked
        if not pv:
            # become_candidate's reset zeroes the election clock; a
            # pre-vote kick keeps it (become_pre_candidate touches only
            # role/leader_id, and the kick is a MsgHup, not a timer fire).
            ee = jnp.where(kicked, 0, ee)
    transferee = st.transferee
    if transferee is not None:
        # Tick-time transfer abort (reference: raft.rs:1051-1079): the
        # boundary fires with or without the check-quorum deposal.
        transferee = jnp.where(want_cq, 0, transferee)

    # ---- campaign local effects.  Real: become_candidate (term+1, vote
    # self, redraw).  Pre-vote: become_pre_candidate touches ONLY the role
    # and leader_id (reference: raft.rs:1124-1143) — term/vote/timeout
    # stay; the request goes out at term+1.
    if pv:
        term = st.term
        state = jnp.where(
            want_campaign, kernels.ROLE_PRE_CANDIDATE, state0
        )
        vote = st.vote
        leader_id = jnp.where(want_campaign, 0, leader0)
        rt = st.randomized_timeout
        req_term = term + want_campaign.astype(jnp.int32)
    else:
        term = st.term + want_campaign.astype(jnp.int32)
        state = jnp.where(want_campaign, ROLE_CANDIDATE, state0)
        vote = jnp.where(want_campaign, self_id, st.vote)
        leader_id = jnp.where(want_campaign, 0, leader0)
        rt = jnp.where(want_campaign, draw(term), st.randomized_timeout)
        req_term = term

    req = want_campaign
    hb_send = want_heartbeat
    sender_ids = jnp.arange(P, dtype=jnp.int32)

    def in_lease(Ld, EE):
        if not cq:  # graftcheck: allow-no-python-branch-on-traced — closes over the static SimConfig damping flag (trace-time constant)
            return jnp.zeros((P, G), bool)
        return (Ld != 0) & (EE < et)

    @profiling.scope("damped.merge_agree")
    def _merge_agree(agree_pl, in_s, new_last, lead_row):
        """Pairwise-agreement update after wholesale adoption: everyone
        in the sync set `in_s` now holds exactly the sender's log (length
        `new_last`); agreement with outsiders is the sender's own row
        `lead_row` (the shared idiom of every append wave)."""
        return jnp.where(
            in_s[:, None, :] & in_s[None, :, :],
            new_last[None, None, :],
            jnp.where(
                in_s[:, None, :],
                lead_row[None, :, :],
                jnp.where(
                    in_s[None, :, :], lead_row[:, None, :], agree_pl
                ),
            ),
        )

    @profiling.scope("damped.cut_before")
    def _cut_before(eff, axis):
        """True strictly AFTER the first effective nudge along `axis` —
        the response-stream cutoff: a deposed sender ignores everything
        later in its v-ordered stream."""
        c = jnp.cumsum(eff.astype(jnp.int32), axis=axis)
        return (c - eff.astype(jnp.int32)) > 0

    sec.at("damped.wave1")
    # ---- wave 1: heartbeats + (pre-)vote requests, per receiver in
    # sender order.  Mirrors _linked_step's wave 1 plus the damping
    # branches: lease ignores, lower-term nudges, and pre-vote's
    # no-bump/no-record grant rule.  Like every sender loop of this round
    # it is one scan equation lowered straight-line (_sender_scan).
    def _w1_body(carry, xs):
        T, V, Ld, St, EE, HB, RT, C = carry
        (d, hb_s, req_s, t_row, rqt_row, m_row, c_row, lt_row, li_row,
         agree_row, sid) = xs
        t_s = t_row[None, :]
        # Heartbeat from s.
        h_del = d & hb_s[None, :] & member
        h_bump = h_del & (t_s > T)
        h_acc = h_del & (t_s >= T)
        h_ndg = h_del & (t_s < T)  # the low-term nudge
        h_ndg_t = jnp.where(h_ndg, T, 0)
        T = jnp.where(h_bump, t_s, T)
        V = jnp.where(h_bump, 0, V)
        St = jnp.where(h_acc, ROLE_FOLLOWER, St)
        Ld = jnp.where(h_acc, sid + 1, Ld)
        EE = jnp.where(h_acc, 0, EE)
        HB = jnp.where(h_bump, 0, HB)
        RT = jnp.where(h_bump, draw(T), RT)
        hb_val = jnp.minimum(m_row, c_row[None, :])
        C = jnp.where(h_acc, jnp.maximum(C, hb_val), C)
        # (Pre-)vote request from s at rqt_row.
        rq = rqt_row[None, :]
        r_del = d & req_s[None, :] & promotable
        leased = r_del & (rq > T) & in_lease(Ld, EE)
        open_rq = r_del & ~leased
        if pv:  # graftcheck: allow-no-python-branch-on-traced — closes over the static SimConfig damping flag (trace-time constant)
            # Pre-vote: no term bump, no vote record, no timer reset.
            at_hi = open_rq & (rq > T)
            at_eq = open_rq & (rq == T)
            can = at_hi | (
                at_eq & ((V == sid + 1) | ((V == 0) & (Ld == 0)))
            )
            up = (lt_row[None, :] > st.last_term) | (
                (lt_row[None, :] == st.last_term)
                & (li_row[None, :] >= st.last_index)
            )
            g = can & up
            rej_cv = (at_hi | at_eq) & ~g  # reject w/ commit info
            rej_lo = open_rq & (rq < T)  # explicit low-term reject
            snap = jnp.where(rej_cv, C, 0)
            vff = (
                rej_cv
                & (St != ROLE_LEADER)
                & (c_row[None, :] > C)
                & (c_row[None, :] <= agree_row)
            )
            C = jnp.where(vff, c_row[None, :], C)
            resp = g | rej_cv | rej_lo
            resp_t = jnp.where(g, rq, T)
            ys = (g, resp, snap, resp_t, h_acc, h_ndg, h_ndg_t)
        else:
            bump = open_rq & (rq > T)
            T = jnp.where(bump, rq, T)
            V = jnp.where(bump, 0, V)
            Ld = jnp.where(bump, 0, Ld)
            St = jnp.where(bump, ROLE_FOLLOWER, St)
            EE = jnp.where(bump, 0, EE)
            HB = jnp.where(bump, 0, HB)
            RT = jnp.where(bump, draw(T), RT)
            at = open_rq & (T == rq)
            up = (lt_row[None, :] > st.last_term) | (
                (lt_row[None, :] == st.last_term)
                & (li_row[None, :] >= st.last_index)
            )
            g = at & (V == 0) & (Ld == 0) & up
            rej = at & ~g
            snap = C
            vff = (
                rej
                & (St != ROLE_LEADER)
                & (c_row[None, :] > C)
                & (c_row[None, :] <= agree_row)
            )
            V = jnp.where(g, sid + 1, V)
            EE = jnp.where(g, 0, EE)
            C = jnp.where(vff, c_row[None, :], C)
            ys = (g, at, snap, h_acc, h_ndg, h_ndg_t)
        return (T, V, Ld, St, EE, HB, RT, C), ys

    w1_carry, w1_ys = _sender_scan(
        _w1_body,
        (term, vote, leader_id, state, ee, hb, rt, st.commit),
        (
            E, hb_send, req, term, req_term, st.matched, st.commit,
            st.last_term, st.last_index, st.agree, sender_ids,
        ),
    )
    (T, V, Ld, St, EE, HB, RT, C) = w1_carry
    if pv:
        (p_grants, p_resps, p_snap, p_resp_t, hb_accs, hb_ndg,
         hb_ndg_t) = w1_ys
    else:
        (grants, resps, rej_snap, hb_accs, hb_ndg, hb_ndg_t) = w1_ys

    sec.at("damped.wave2")
    # ---- wave 2a: heartbeat responses + nudges back at each leader, in
    # receiver order.  Closed form: the first nudge whose term beats the
    # leader's cuts off every later response (handle_heartbeat_response
    # only runs while Leader at the response's term); the deposed leader's
    # final term is the max of the effective nudge terms.
    t_tick = term  # each sender's tick-time term (pre-wave planes)
    eff_hn = hb_ndg & Erev & (hb_ndg_t > T[:, None, :])
    resumed2 = (
        hb_accs
        & Erev
        & ~_cut_before(eff_hn, axis=1)
        & (T == t_tick)[:, None, :]
        & (St == ROLE_LEADER)[:, None, :]
    )
    RA = jnp.where(resumed2, True, RA)
    cu = resumed2 & (st.matched < st.last_index[:, None, :])
    hdep_t = jnp.max(jnp.where(eff_hn, hb_ndg_t, 0), axis=1)  # [P, G]
    hdep = jnp.any(eff_hn, axis=1)
    T = jnp.where(hdep, jnp.maximum(T, hdep_t), T)
    V = jnp.where(hdep, 0, V)
    St = jnp.where(hdep, ROLE_FOLLOWER, St)
    Ld = jnp.where(hdep, 0, Ld)
    EE = jnp.where(hdep, 0, EE)
    HB = jnp.where(hdep, 0, HB)
    RT = jnp.where(hdep, draw(T), RT)

    sec.at("damped.tally")
    halves = _halves(st)
    if not pv:
        # ---- wave 2b: the real tally now, exactly like _linked_step.
        cand_active = req & (St == ROLE_CANDIDATE)
        C, won, lost = _real_tally(
            st, halves, C, cand_active, grants, resps, rej_snap, st.agree,
            Erev,
        )
        real_req = jnp.zeros((P, G), bool)
        rqt2 = req_term  # unused senders masked off
    else:
        # ---- wave 2b: the pre-vote tally; a pre-winner's REAL vote
        # broadcast is queued for wave 3.
        t_c0 = term  # pre-campaign terms
        pre_active = req & (St == kernels.ROLE_PRE_CANDIDATE)
        C, T, V, St, EE, HB, RT, pre_won = _pre_tally(
            st, halves, (C, T, V, St, EE, HB, RT), pre_active, t_c0,
            p_grants, p_resps, p_resp_t, p_snap, Erev, draw,
        )
        real_req = pre_won  # broadcasts queued at win time
        rqt2 = t_c0 + 1

    # ---- post-election (no pre-vote) / pre-wave-3 bookkeeping.
    if not pv:
        li2 = st.last_index + won.astype(jnp.int32)
        lt2 = jnp.where(won, term, st.last_term)
        TS = jnp.where(won, li2, st.term_start_index)
        St = jnp.where(won, ROLE_LEADER, St)
        Ld = jnp.where(won, self_id, Ld)
        RT = jnp.where(won | lost, draw(T), RT)
        EE = jnp.where(won | lost, 0, EE)
        HB = jnp.where(won, 0, HB)
        St = jnp.where(lost, ROLE_FOLLOWER, St)
        matched3 = jnp.where(won[:, None, :], 0, st.matched)
        matched3 = jnp.where(
            won[:, None, :] & eye_pp, li2[:, None, :], matched3
        )
        RA = jnp.where(won[:, None, :], False, RA)
        noop_w3 = won
    else:
        li2 = st.last_index
        lt2 = st.last_term
        TS = st.term_start_index
        matched3 = st.matched
        noop_w3 = jnp.zeros((P, G), bool)
        won = jnp.zeros((P, G), bool)

    agree_run = st.agree
    LI = li2
    LT = lt2
    C_send = C  # commit snapshots for wave-3 sends

    sec.at("damped.wave3")
    # ---- wave 3: appends (winner noops + catch-ups) and — with pre-vote
    # — the REAL vote requests, per receiver in sender order.  Acks and
    # nudges are collected for the wave-4 fold; grants/rejects for the
    # wave-4 tally.
    def _w3_body(carry, xs):
        T, V, St, Ld, EE, HB, RT, C, LI, LT, agree_run = carry
        (e_s, erev_s, cu_s, noop_s, li_row, li2_row, lt2_row, csend_row,
         t_row, m0_row, ts_row, rr_s, rqt2_row, rli_row, rlt_row, rc_row,
         sid) = xs
        agree_s = jax.lax.dynamic_index_in_dim(
            agree_run, sid, 0, keepdims=False
        )
        dmask = e_s & member & (noop_s[None, :] | cu_s)
        msg = dmask & (t_row[None, :] >= T)
        ndg = dmask & (t_row[None, :] < T)
        ndg_t = jnp.where(ndg, T, 0)
        # First-probe prev: a member never acked since this owner's
        # election (matched == 0) still probes from the election noop
        # (next stuck at term_start), everyone else from the owner's
        # current last (Replicate's optimistic next).  Adoption WITHOUT a
        # probe match needs the reject/decr retry chain — deferred to the
        # post-wave retry pass, because a mid-round deposition (a nudge
        # from a receiver earlier in this very response stream, or a
        # higher-term message) kills the chain at the scalar leader.
        prev_row = jnp.where(
            m0_row == 0, ts_row[None, :] - 1, li2_row[None, :]
        )
        probe_ok = agree_s >= prev_row
        retry_cand = msg & ~probe_ok & erev_s & ~_cut_before(
            ndg & erev_s, axis=0
        )
        adopt = msg & probe_ok
        bump = msg & (t_row[None, :] > T)
        T = jnp.where(msg, t_row[None, :], T)
        V = jnp.where(bump, 0, V)
        St = jnp.where(msg, ROLE_FOLLOWER, St)
        Ld = jnp.where(msg, sid + 1, Ld)
        EE = jnp.where(msg, 0, EE)
        HB = jnp.where(bump, 0, HB)
        RT = jnp.where(bump, draw(T), RT)
        C = jnp.where(adopt, jnp.maximum(C, csend_row[None, :]), C)
        ack = adopt & erev_s
        sent_any = jnp.any(adopt, axis=0)
        in_s = adopt | ((p_idx == sid) & sent_any[None, :])
        agree_run = _merge_agree(agree_run, in_s, li2_row, agree_s)
        LI = jnp.where(adopt, li2_row[None, :], LI)
        LT = jnp.where(adopt, lt2_row[None, :], LT)
        if pv:  # graftcheck: allow-no-python-branch-on-traced — closes over the static SimConfig damping flag (trace-time constant)
            # The pre-winner's REAL vote request, after s's appends (a
            # sender is a candidate or a leader, never both; the shared
            # scan position keeps cross-sender order).
            rq = rqt2_row[None, :]
            r_del = e_s & rr_s[None, :] & promotable
            leased = r_del & (rq > T) & in_lease(Ld, EE)
            open_rq = r_del & ~leased
            rbump = open_rq & (rq > T)
            T = jnp.where(rbump, rq, T)
            V = jnp.where(rbump, 0, V)
            Ld = jnp.where(rbump, 0, Ld)
            St = jnp.where(rbump, ROLE_FOLLOWER, St)
            EE = jnp.where(rbump, 0, EE)
            HB = jnp.where(rbump, 0, HB)
            RT = jnp.where(rbump, draw(T), RT)
            at = open_rq & (T == rq)
            up = (rlt_row[None, :] > LT) | (
                (rlt_row[None, :] == LT) & (rli_row[None, :] >= LI)
            )
            g = at & (V == 0) & (Ld == 0) & up
            rej = at & ~g
            snap = C
            vff = (
                rej
                & (St != ROLE_LEADER)
                & (rc_row[None, :] > C)
                & (rc_row[None, :] <= agree_s)
            )
            V = jnp.where(g, sid + 1, V)
            EE = jnp.where(g, 0, EE)
            C = jnp.where(vff, rc_row[None, :], C)
            ys = (ack, ndg, ndg_t, retry_cand, g, at, snap)
        else:
            ys = (ack, ndg, ndg_t, retry_cand)
        return (T, V, St, Ld, EE, HB, RT, C, LI, LT, agree_run), ys

    w3_carry, w3_ys = _sender_scan(
        _w3_body,
        (T, V, St, Ld, EE, HB, RT, C, LI, LT, agree_run),
        (
            E, Erev, cu, noop_w3, st.last_index, li2, lt2, C_send, term,
            matched3, TS,
            real_req, rqt2, st.last_index, st.last_term, C_send,
            sender_ids,
        ),
    )
    (T, V, St, Ld, EE, HB, RT, C, LI, LT, agree_run) = w3_carry
    if pv:
        (ack3, ndg3, ndg3_t, retry3, r_grants, r_resps, r_snap) = w3_ys
    else:
        (ack3, ndg3, ndg3_t, retry3) = w3_ys
    # Wave-4 survival of the wave-3 retry chains: the reject is processed
    # at the sender only while it is still the same-term leader (wave-2/3
    # depositions show in the planes; same-stream nudge cutoffs are
    # already inside retry3).
    retry3_fire = (
        retry3 & ((T == term) & (St == ROLE_LEADER))[:, None, :]
    )

    # ---- generic ack/nudge stage fold (waves 4 and 6): per sender, acks
    # and nudge responses interleave in receiver order; the first
    # effective nudge deposes the sender and drops every later ack.
    @profiling.scope("damped.stage_fold")
    def _stage_fold(T, V, St, Ld, EE, HB, RT, RA, matched3, C, ack, ndg,
                    ndg_t, sent_term, sent_idx):
        eff_n = ndg & Erev & (ndg_t > T[:, None, :])
        was_lead = St == ROLE_LEADER
        ack_eff = (
            ack
            & ~_cut_before(eff_n, axis=1)
            & (T == sent_term)[:, None, :]
            & was_lead[:, None, :]
        )
        matched3 = jnp.where(
            ack_eff,
            jnp.maximum(matched3, sent_idx[:, None, :]),
            matched3,
        )
        RA = jnp.where(ack_eff, True, RA)
        dep_t = jnp.max(jnp.where(eff_n, ndg_t, 0), axis=1)
        dep = jnp.any(eff_n, axis=1)
        T = jnp.where(dep, jnp.maximum(T, dep_t), T)
        V = jnp.where(dep, 0, V)
        St = jnp.where(dep, ROLE_FOLLOWER, St)
        Ld = jnp.where(dep, 0, Ld)
        EE = jnp.where(dep, 0, EE)
        HB = jnp.where(dep, 0, HB)
        RT = jnp.where(dep, draw(T), RT)
        # Per-owner quorum commit off the cutoff rows (the term gate is
        # maybe_commit's own-term check); commits reached before a
        # mid-stream deposition stand.
        mci = jnp.minimum(
            kernels.committed_index(
                jnp.swapaxes(matched3, 1, 2),
                jnp.swapaxes(
                    jnp.broadcast_to(
                        st.voter_mask[None, :, :], (P, P, G)
                    ), 1, 2,
                ),
            ),
            kernels.committed_index(
                jnp.swapaxes(matched3, 1, 2),
                jnp.swapaxes(
                    jnp.broadcast_to(
                        st.outgoing_mask[None, :, :], (P, P, G)
                    ), 1, 2,
                ),
            ),
        )  # [P_owner, G]
        ok = was_lead & (mci >= TS) & (mci < kernels.INF)
        c_new = jnp.where(ok, jnp.maximum(C, mci), C)
        adv = c_new > C
        return T, V, St, Ld, EE, HB, RT, RA, matched3, c_new, adv

    sec.at("damped.tally")
    # ---- wave 4: with pre-vote, the REAL tally (plus its winner
    # effects); both modes run the stage fold over the wave-3 acks.
    (T, V, St, Ld, EE, HB, RT, RA, matched3, C, adv) = _stage_fold(
        T, V, St, Ld, EE, HB, RT, RA, matched3, C, ack3, ndg3, ndg3_t,
        term, li2,
    )
    if pv:
        cand_active = real_req & (St == ROLE_CANDIDATE)
        C, won, lost = _real_tally(
            st, halves, C, cand_active, r_grants, r_resps, r_snap,
            agree_run, Erev,
        )
        li2 = LI + won.astype(jnp.int32)
        lt2 = jnp.where(won, T, lt2)
        TS = jnp.where(won, li2, TS)
        St = jnp.where(won, ROLE_LEADER, St)
        Ld = jnp.where(won, self_id, Ld)
        RT = jnp.where(won | lost, draw(T), RT)
        EE = jnp.where(won | lost, 0, EE)
        HB = jnp.where(won, 0, HB)
        St = jnp.where(lost, ROLE_FOLLOWER, St)
        matched3 = jnp.where(won[:, None, :], 0, matched3)
        matched3 = jnp.where(
            won[:, None, :] & eye_pp, li2[:, None, :], matched3
        )
        RA = jnp.where(won[:, None, :], False, RA)
        LI = jnp.where(won, li2, LI)
        LT = jnp.where(won, lt2, LT)

    sec.at("damped.wave3")
    # ---- retry resends (the maybe_decr/fast-reject chain): a surviving
    # sender's resend carries prev at the receiver's conflict point, so it
    # lands as wholesale adoption one wave after the reject.  Applied
    # per sender in index order (resends of different leaders interleave
    # sender-ordered like every wave).
    def _apply_retry(fire, t_send, li_a, lt_a, csend_a, planes):
        # One scan equation over the stacked sender rows, lowered
        # straight-line like the wave loops (_sender_scan).  T is
        # read-only here: a resend is accepted only at equal term, and
        # acceptance never bumps.
        T, V, St, Ld, EE, HB, RT, C, LI, LT, agree_run = planes

        def body(carry, xs):
            St, Ld, EE, C, LI, LT, agree_run = carry
            f_s, t_row, li_row, lt_row, cs_row, sid = xs
            acc = f_s & (t_row[None, :] >= T)
            St = jnp.where(acc, ROLE_FOLLOWER, St)
            Ld = jnp.where(acc, sid + 1, Ld)
            EE = jnp.where(acc, 0, EE)
            LI = jnp.where(acc, li_row[None, :], LI)
            LT = jnp.where(acc, lt_row[None, :], LT)
            C = jnp.where(acc, jnp.maximum(C, cs_row[None, :]), C)
            sent_any = jnp.any(acc, axis=0)
            in_s = acc | ((p_idx == sid) & sent_any[None, :])
            lead_row = jax.lax.dynamic_index_in_dim(
                agree_run, sid, 0, keepdims=False
            )
            agree_run = _merge_agree(agree_run, in_s, li_row, lead_row)
            return (St, Ld, EE, C, LI, LT, agree_run), (acc,)

        (St, Ld, EE, C, LI, LT, agree_run), (acc_all,) = _sender_scan(
            body,
            (St, Ld, EE, C, LI, LT, agree_run),
            (fire, t_send, li_a, lt_a, csend_a, sender_ids),
        )
        return acc_all, (T, V, St, Ld, EE, HB, RT, C, LI, LT, agree_run)

    retry3_acc, (T, V, St, Ld, EE, HB, RT, C, LI, LT, agree_run) = (
        _apply_retry(
            retry3_fire, term, li2, lt2, C_send,
            (T, V, St, Ld, EE, HB, RT, C, LI, LT, agree_run),
        )
    )

    sec.at("damped.wave5")
    # ---- wave 5: commit-advance re-broadcasts (pass 2) and — with
    # pre-vote — the winners' noop broadcasts, one sender-ordered scan.
    C_send5 = C

    def _w5_body(carry, xs):
        T, V, St, Ld, EE, HB, RT, C, LI, LT, agree_run = carry
        (e_s, erev_s, adv_s, res_s, noop_s, m3_row, li_row, li2_row,
         lt2_row, csend_row, t_row, ts_row, sid) = xs
        agree_s = jax.lax.dynamic_index_in_dim(
            agree_run, sid, 0, keepdims=False
        )
        rb = e_s & member & adv_s[None, :] & ((m3_row > 0) | res_s)
        noop_d = e_s & member & noop_s[None, :]
        dmask = rb | noop_d
        msg = dmask & (t_row[None, :] >= T)
        ndg = dmask & (t_row[None, :] < T)
        ndg_t = jnp.where(ndg, T, 0)
        prev_row = jnp.where(
            m3_row == 0, ts_row[None, :] - 1, li_row[None, :]
        )
        probe_ok = agree_s >= prev_row
        retry_cand = msg & ~probe_ok & erev_s & ~_cut_before(
            ndg & erev_s, axis=0
        )
        adopt = msg & probe_ok
        bump = msg & (t_row[None, :] > T)
        T = jnp.where(msg, t_row[None, :], T)
        V = jnp.where(bump, 0, V)
        St = jnp.where(msg, ROLE_FOLLOWER, St)
        Ld = jnp.where(msg, sid + 1, Ld)
        EE = jnp.where(msg, 0, EE)
        HB = jnp.where(bump, 0, HB)
        RT = jnp.where(bump, draw(T), RT)
        C = jnp.where(
            adopt & noop_d, jnp.maximum(C, csend_row[None, :]), C
        )
        LI = jnp.where(adopt, li2_row[None, :], LI)
        LT = jnp.where(adopt, lt2_row[None, :], LT)
        # With pre-vote the stream hands out the acks.  Without it, WHO
        # adopted: wave 6 needs the adopters (below), and the acks are
        # theirs whose way back is up (`took5 & Erev` after the scan).
        took = adopt & erev_s if pv else adopt
        sent_any = jnp.any(adopt, axis=0)
        in_s = adopt | ((p_idx == sid) & sent_any[None, :])
        agree_run = _merge_agree(agree_run, in_s, li2_row, agree_s)
        return (T, V, St, Ld, EE, HB, RT, C, LI, LT, agree_run), (
            took, ndg, ndg_t, retry_cand,
        )

    # prev for the probe check: re-broadcasts carry prev = the leader's
    # current last (li2, the noop included for a fresh winner); a pre-vote
    # winner's noop carries prev = its pre-noop cursor.
    if pv:
        w5_prev = jnp.where(won, li2 - 1, li2)
        w5_noop = won
        sent_term5 = jnp.where(won, rqt2, term)
    else:
        w5_prev = li2
        w5_noop = jnp.zeros((P, G), bool)
        sent_term5 = term
    (T, V, St, Ld, EE, HB, RT, C, LI, LT, agree_run), (
        took5, ndg5, ndg5_t, retry5,
    ) = _sender_scan(
        _w5_body,
        (T, V, St, Ld, EE, HB, RT, C, LI, LT, agree_run),
        (
            E, Erev, adv, resumed2, w5_noop,
            matched3, w5_prev, li2, lt2, C_send5,
            sent_term5, TS, sender_ids,
        ),
    )
    ack5 = took5 if pv else took5 & Erev
    # Wave-5 retry chains: survival gate, then the resends land as
    # wholesale adoption; their acks fold into the wave-6 stage together
    # with the wave-3 chains' (the undamped path collapses the same
    # chains into its commit stages).
    retry5_fire = (
        retry5 & ((T == sent_term5) & (St == ROLE_LEADER))[:, None, :]
    )
    retry5_acc, (T, V, St, Ld, EE, HB, RT, C, LI, LT, agree_run) = (
        _apply_retry(
            retry5_fire, sent_term5, li2, lt2,
            jnp.where(w5_noop, C_send5, 0),
            (T, V, St, Ld, EE, HB, RT, C, LI, LT, agree_run),
        )
    )
    ack5 = ack5 | retry3_acc | retry5_acc

    # ---- wave 6: stage fold over the wave-5 acks, then the settled
    # commit propagated to in-sync sendable members (the _commit_b
    # approximation), whose sends draw nudges from higher-term receivers.
    (T, V, St, Ld, EE, HB, RT, RA, matched3, C, _adv6) = _stage_fold(
        T, V, St, Ld, EE, HB, RT, RA, matched3, C, ack5, ndg5, ndg5_t,
        sent_term5, li2,
    )
    is_lead6 = St == ROLE_LEADER
    # Compare against what each sender's APPEND sends carried: the wave-3
    # snapshot, except a pre-vote winner's noop which carried the wave-5
    # snapshot.
    csend6 = jnp.where(won, C_send5, C_send) if pv else C_send
    send6 = (
        E
        & member
        & is_lead6[:, None, :]
        & ((matched3 > 0) | resumed2)
        & (C > csend6)[:, None, :]
    )
    elig6 = (
        send6
        & (sent_term5[:, None, :] >= T[None, :, :])
        & ((agree_run >= li2[:, None, :]) | Erev)
    )
    heard6 = jnp.where(elig6, C[:, None, :], 0)
    if not pv:
        # A re-broadcast carries its sender's commit of wave 5 whoever the
        # sender is by now: one deposed by a nudge later in the very wave-3
        # stream whose acks advanced its commit — a wave-2 winner among
        # them — is no leader at wave 6, and its message was in flight
        # before the nudge was stepped (ROADMAP C15,
        # docs/CHECK_QUORUM_WITHOUT_PREVOTE.md).  A standing leader's
        # settled commit above is at least this.  The pre-vote arm is as it
        # was: those fleets' graphs are text-identical.
        heard6 = jnp.maximum(
            heard6, jnp.where(took5, C_send5[:, None, :], 0)
        )
    C = jnp.maximum(C, jnp.max(heard6, axis=0))
    RA = jnp.where(elig6 & Erev, True, RA)
    ndg6 = send6 & (sent_term5[:, None, :] < T[None, :, :]) & Erev
    dep6_t = jnp.max(jnp.where(ndg6, T[None, :, :], 0), axis=1)
    dep6 = jnp.any(ndg6, axis=1) & (dep6_t > T)
    T = jnp.where(dep6, dep6_t, T)
    V = jnp.where(dep6, 0, V)
    St = jnp.where(dep6, ROLE_FOLLOWER, St)
    Ld = jnp.where(dep6, 0, Ld)
    EE = jnp.where(dep6, 0, EE)
    HB = jnp.where(dep6, 0, HB)
    RT = jnp.where(dep6, draw(T), RT)

    sec.at("damped.workload")
    # ---- the round's append workload at the acting leader, with the
    # same nudge cutoffs on its ack stream.
    is_leader = (St == ROLE_LEADER) & alive
    has_leader = jnp.any(is_leader, axis=0)
    lead_term = jnp.max(jnp.where(is_leader, T, -1), axis=0)
    is_acting = is_leader & (T == lead_term)
    first_l = jnp.min(jnp.where(is_acting, p_idx, P), axis=0)
    is_acting_leader = (p_idx == first_l) & has_leader
    n_app = jnp.where(has_leader, append_n, 0)
    if transferee is not None:
        # ProposalDropped while a transfer is pending at the acting
        # leader (reference: raft.rs step_leader's lead_transferee gate).
        blocked = jnp.any(is_acting_leader & (transferee > 0), axis=0)
        n_app = jnp.where(blocked, 0, n_app)
    else:
        blocked = None
    sent_b = has_leader & (n_app > 0)
    lead_pre_last = jnp.max(jnp.where(is_acting_leader, LI, 0), axis=0)
    LI = LI + jnp.where(is_acting_leader, n_app, 0)
    LT = jnp.where(is_acting_leader & (n_app > 0), lead_term, LT)
    lead_last = jnp.max(jnp.where(is_acting_leader, LI, 0), axis=0)
    lead_last_term = jnp.max(jnp.where(is_acting_leader, LT, 0), axis=0)
    reach_b = jnp.any(E & is_acting_leader[:, None, :], axis=0)
    ack_path = jnp.any(E & is_acting_leader[None, :, :], axis=1)
    acting_f = is_acting_leader.astype(jnp.int32)
    acting_row0 = jnp.sum(
        matched3 * acting_f[:, None, :], axis=0, dtype=jnp.int32
    )
    resumed_act = jnp.any(resumed2 & is_acting_leader[:, None, :], axis=0)
    agree_act = jnp.sum(
        agree_run * acting_f[:, None, :], axis=0, dtype=jnp.int32
    )
    pr_ok = (acting_row0 > 0) | resumed_act
    ts_acting = jnp.sum(TS * acting_f, axis=0, dtype=jnp.int32)
    send_w = sent_b & reach_b & member & ~is_acting_leader & pr_ok
    sync_msg = send_w & (T <= lead_term)
    ndg_w = send_w & (T > lead_term) & ack_path
    ndg_w_t = jnp.where(ndg_w, T, 0)
    cutw = _cut_before(ndg_w, axis=0)
    # First-probe prev (never-acked members probe from the noop) or the
    # surviving retry chain — the acting leader is deposed only by these
    # very nudges, so ~cutw IS the survival gate.
    probe_w = agree_act >= jnp.where(
        acting_row0 == 0, ts_acting[None, :] - 1, lead_pre_last[None, :]
    )
    sync_b = sync_msg & (probe_w | (ack_path & ~cutw))
    bump_b = sync_msg & (T < lead_term)
    T = jnp.where(sync_msg, lead_term, T)
    St = jnp.where(sync_msg, ROLE_FOLLOWER, St)
    V = jnp.where(bump_b, 0, V)
    Ld = jnp.where(sync_msg, first_l + 1, Ld)
    EE = jnp.where(sync_msg, 0, EE)
    HB = jnp.where(bump_b, 0, HB)
    RT = jnp.where(bump_b, draw(T), RT)
    LI = jnp.where(sync_b, lead_last, LI)
    LT = jnp.where(sync_b, lead_last_term, LT)
    in_sb = sync_b | (is_acting_leader & sent_b)
    lead_row_b = jnp.sum(
        agree_run * acting_f[:, None, :], axis=0, dtype=jnp.int32
    )
    agree_run = _merge_agree(agree_run, in_sb, lead_last, lead_row_b)
    # Ack stream with nudge cutoffs (the acting leader's v-ordered
    # responses; every workload nudge carries a term above lead_term, so
    # all are effective).
    ack_w = sync_b & ack_path & ~cutw
    acting_row = jnp.where(
        ack_w | (is_acting_leader & sent_b),
        jnp.maximum(acting_row0, lead_last),
        acting_row0,
    )
    matched3 = jnp.where(
        is_acting_leader[:, None, :], acting_row[None, :, :], matched3
    )
    RA = jnp.where(
        is_acting_leader[:, None, :] & ack_w[None, :, :], True, RA
    )
    mci_b = jnp.minimum(
        _quorum_index(acting_row, st.voter_mask),
        _quorum_index(acting_row, st.outgoing_mask),
    )
    commit_ok = sent_b & (mci_b >= ts_acting) & (mci_b < kernels.INF)
    lead_commit_old = jnp.max(jnp.where(is_acting_leader, C, 0), axis=0)
    lead_commit = jnp.where(
        commit_ok, jnp.maximum(lead_commit_old, mci_b), lead_commit_old
    )
    C = jnp.where(is_acting_leader, lead_commit, C)
    C = jnp.where(sync_b, jnp.maximum(C, lead_commit), C)
    # Workload nudges depose the acting leader at round end.
    depw_t = jnp.max(ndg_w_t, axis=0)
    depw = jnp.any(ndg_w, axis=0) & (depw_t > lead_term)
    dw = is_acting_leader & depw[None, :]
    T = jnp.where(dw, depw_t[None, :], T)
    V = jnp.where(dw, 0, V)
    St = jnp.where(dw, ROLE_FOLLOWER, St)
    Ld = jnp.where(dw, 0, Ld)
    EE = jnp.where(dw, 0, EE)
    HB = jnp.where(dw, 0, HB)
    RT = jnp.where(dw, draw(T), RT)
    sec.end()

    if transferee is not None:
        # reset-abort invariant (see step()): only standing leaders keep
        # their lead_transferee.
        transferee = jnp.where(St == ROLE_LEADER, transferee, 0)
    out = SimState(
        term=T,
        state=St,
        vote=V,
        leader_id=Ld,
        election_elapsed=EE,
        heartbeat_elapsed=HB,
        randomized_timeout=RT,
        last_index=LI,
        last_term=LT,
        commit=C,
        matched=matched3,
        term_start_index=TS,
        agree=agree_run,
        voter_mask=st.voter_mask,
        outgoing_mask=st.outgoing_mask,
        learner_mask=st.learner_mask,
        recent_active=RA,
        transferee=transferee,
    )
    if (
        counters is None
        and health is None
        and reconfig_propose is None
        and read_extra is None
    ):
        return out
    extras: Tuple = ()
    if counters is not None:
        # campaign() calls: the tick-time campaigns plus, with pre-vote,
        # the pre-winners' second (real) campaign call; MsgBeat steps
        # exclude boundary-suppressed heartbeats (already folded into
        # hb_send).
        counters = kernels.count_events(
            counters, want_campaign, hb_send, jnp.any(won, axis=0),
            out.commit - st_in.commit,
        )
        if pv:
            counters = counters.at[kernels.CTR_CAMPAIGNS].add(
                jnp.sum(real_req, dtype=jnp.int32)
            )
        if t_extra is not None:
            counters = counters.at[kernels.CTR_CAMPAIGNS].add(
                jnp.sum(t_extra[0], dtype=jnp.int32)
            )
            counters = counters.at[kernels.CTR_ELECTIONS_WON].add(
                jnp.sum(t_extra[1], dtype=jnp.int32)
            )
        extras = extras + (counters,)
    if health is not None:
        # The oracle derives `won` from observable end-of-round state
        # (simref.HealthOracle): Leader at round end with a fresh term or
        # a non-Leader pre-round role — a transient winner deposed later
        # in the same round does NOT count.  Mirror that here.
        has_lead_end = jnp.any((out.state == ROLE_LEADER) & alive, axis=0)
        commit_adv = jnp.max(out.commit, axis=0) > jnp.max(
            st_in.commit, axis=0
        )
        term_bump = jnp.max(out.term, axis=0) - jnp.max(st_in.term, axis=0)
        campaigned = jnp.any(want_campaign, axis=0)
        won_end = jnp.any(
            (out.state == ROLE_LEADER)
            & ((st_in.state != ROLE_LEADER) | (out.term > st_in.term)),
            axis=0,
        )
        planes, pos = kernels.update_health(
            health.planes,
            health.window_pos,
            cfg.health_window,
            has_lead_end,
            commit_adv,
            term_bump,
            campaigned & ~won_end,
        )
        extras = extras + (HealthState(planes, pos),)
    if reconfig_propose is not None:
        # The proposal is recorded at the WORKLOAD stage (the conf entry is
        # appended there, last in the round's batch); a workload nudge that
        # deposes the acting leader afterwards does not unrecord it — the
        # entry landed, exactly like the scalar leader that appends before
        # processing its deposing ack.  The reconfig runner's gate then
        # sees the deposed owner and retries the op.
        prop_mask = has_leader & reconfig_propose
        if blocked is not None:
            # A pending transfer drops the conf entry with the rest of
            # the batch (ProposalDropped); owner 0 makes the op retry.
            prop_mask = prop_mask & ~blocked
        extras = extras + (
            ReconfigProposal(
                owner=jnp.where(prop_mask, first_l + 1, 0),
                index=jnp.where(prop_mask, lead_last, 0),
                term=jnp.where(prop_mask, lead_term, 0),
                dropped=(append_n > 0) & (n_app == 0),
            ),
        )
    if read_extra is not None:
        extras = extras + (read_extra,)
    return (out,) + extras


def read_index_holders(
    cfg: SimConfig,
    st: SimState,
    crashed: jnp.ndarray,  # gc: bool[P, G]
    link: Optional[jnp.ndarray] = None,  # gc: bool[P, P, G]
) -> jnp.ndarray:
    """The ReadIndex gate of EVERY peer (Safe mode; reference:
    read_only.rs:65-140 + raft.rs step_leader MsgReadIndex 2067-2096 +
    handle_heartbeat_response ack-quorum 1805-1818): bool[P, G], true
    where a read asked of peer p at this round boundary would complete
    and return p's commit index.  A peer holds when

      * it is an alive leader, and
      * it has committed an entry in its own term (commit >=
        term_start_index — the commit_to_current_term gate), and
      * its ack quorum stands: alive members at term <= ITS term ack the
        ctx heartbeat; members at a HIGHER term silently IGNORE it — they
        neither ack nor (for this pure probe) depose; with check_quorum on
        they would ALSO nudge-depose the stale leader, which a probing
        read must not do, so the probe models the ack set only (the
        scalar probe does perturb — parity tests probe last).  Joint
        configs need both majorities; a singleton group answers
        immediately without heartbeats (raft.rs:2075-2079).

    `link` (optional bool[P, P, G] directed reachability, the chaos
    engine's plane) makes the barrier link-aware: an ack needs the
    leader->member link for the ctx heartbeat AND the member->leader link
    for the response (a one-way reachable member heartbeats but never
    acks).  None keeps the crash-mask-only graph.

    `read_index` serves the acting leader's row of this mask; the
    workload scan hands the WHOLE mask (`ReadReceipt.holders`) to the
    safety audit (`kernels.check_safety`'s `lease_holder`), which is what
    holds a
    ReadIndex read to its acknowledging majority: a client is routed to
    the acting leader, but a leader that is cut off, alive and — without
    check-quorum — never deposed still believes it leads, and only the
    ack quorum keeps it from answering with an index the rest of its
    group has moved past.  Pure and jittable.
    """
    P = cfg.n_peers
    alive = ~crashed
    member = st.voter_mask | st.outgoing_mask | st.learner_mask
    is_lead = (st.state == ROLE_LEADER) & alive  # [P_l, G]
    own = jnp.eye(P, dtype=bool)[:, :, None]
    # ack[l, m]: member m acknowledges leader l's ctx heartbeat.
    ack = (alive & member)[None, :, :] & (
        st.term[None, :, :] <= st.term[:, None, :]
    )
    if link is not None:
        ack = ack & link & jnp.swapaxes(link, 0, 1)
    ack = ack | own  # add_request seeds acks = {self}

    def half_quorum(mask):
        # dtype= so the counts stay int32 under x64 (GC007).
        n = jnp.sum(mask, axis=0, dtype=jnp.int32)
        acks = jnp.sum(ack & mask[None, :, :], axis=1, dtype=jnp.int32)
        return (acks >= (n // 2 + 1)[None, :]) | (n == 0)[None, :]

    quorum = half_quorum(st.voter_mask) & half_quorum(st.outgoing_mask)
    n_i = jnp.sum(st.voter_mask, axis=0, dtype=jnp.int32)
    singleton = (n_i == 1) & ~jnp.any(st.outgoing_mask, axis=0)
    # The ack-quorum is only ever EVALUATED inside
    # handle_heartbeat_response (raft.rs:1805-1818), so at least one OTHER
    # alive member must actually respond — a joint config whose quorum is
    # the leader alone (e.g. incoming == outgoing == {leader}) hangs its
    # reads until leave-joint, because is_singleton() requires an EMPTY
    # outgoing half (found by randomized-config fuzz).
    any_other = jnp.any(ack & ~own, axis=1)
    return (
        is_lead
        & (st.commit >= st.term_start_index)
        & (singleton[None, :] | (quorum & any_other))
    )


def read_index(
    cfg: SimConfig,
    st: SimState,
    crashed: jnp.ndarray,  # gc: bool[P, G]
    link: Optional[jnp.ndarray] = None,  # gc: bool[P, P, G]
) -> jnp.ndarray:
    """Batched linearizable ReadIndex barrier, Safe mode: for every group,
    the index a read issued at the ACTING leader (the alive leader of the
    highest term — where the sim routes client reads) at this round
    boundary would return, or -1 when it cannot complete: no alive leader,
    or the acting leader's gate does not hold (`read_index_holders`: not
    committed in its own term yet, or no acknowledging majority).

    Pure and jittable: probing reads never mutates `st` (the scalar oracle's
    probe DOES perturb its cluster, so parity tests probe last).
    Returns int32[G].
    """
    return _acting_index(
        st, crashed, read_index_holders(cfg, st, crashed, link)
    )


def _acting_index(
    st: SimState,
    crashed: jnp.ndarray,  # gc: bool[P, G]
    holders: jnp.ndarray,  # gc: bool[P, G]
) -> jnp.ndarray:
    """The acting leader's row of `holders` (read_index_holders): its
    commit index where it holds, -1 where it does not or nobody leads."""
    is_lead = (st.state == ROLE_LEADER) & ~crashed
    lead_term = jnp.max(jnp.where(is_lead, st.term, -1), axis=0)  # [G]
    serving = is_lead & (st.term == lead_term[None, :]) & holders  # unique
    # dtype= so the probed indices stay int32 under x64 (GC007).
    index = jnp.sum(
        jnp.where(serving, st.commit, 0), axis=0, dtype=jnp.int32
    )
    return jnp.where(jnp.any(serving, axis=0), index, jnp.int32(-1))


class ClusterSim:
    """Convenience wrapper: jitted step + host-friendly runners.  Arrays are
    peer-major [P, G]."""

    def __init__(
        self,
        cfg: SimConfig,
        voter_mask: Optional[jnp.ndarray] = None,
        outgoing_mask: Optional[jnp.ndarray] = None,
        learner_mask: Optional[jnp.ndarray] = None,
        health_monitor=None,
        chaos=None,
        mesh=None,
        mesh_axis: str = "groups",
    ):
        # Multi-chip mode (ISSUE 14): with `mesh` (a 1-D jax.sharding.Mesh
        # over the group axis — sharding.make_mesh), the fleet bootstraps
        # DIRECTLY onto the mesh (sharding.sharded_init_state: the global
        # [P, P, G] planes never materialize on one host), every run_*
        # entry point places its per-round planes and compiled schedule
        # arrays with the sharding.*_sharding specs, and the existing
        # jitted runners — donated run_compiled segments, the chaos/
        # reconfig/workload scans, the split-fused runners, the
        # drain/scan overlap — execute under jit-with-shardings
        # unchanged: XLA sees the global shapes, the iota node keys stay
        # global, and every op partitions trivially along G.  The config
        # is promoted to its SPMD-friendly graph form (SimConfig.spmd),
        # which keeps the steady step graph collective-free on the mesh;
        # results are bit-identical to the single-device path
        # (tests/test_sharded_parity.py).
        if mesh is not None and not cfg.spmd:
            cfg = cfg._replace(spmd=True)
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        self.cfg = cfg
        # A fleet that BOOTS with learners (TiFlash replicas: learners for
        # good) has run_reads count their lag; known here, once, on the host.
        self._boots_learners = learner_mask is not None and bool(
            jnp.any(learner_mask)
        )
        if mesh is None:
            self.state = init_state(
                cfg, voter_mask, outgoing_mask, learner_mask
            )
        else:
            from . import sharding as sharding_mod

            self.state = sharding_mod.sharded_init_state(
                cfg, mesh, voter_mask, outgoing_mask, learner_mask,
                axis=mesh_axis,
            )
        self._step = jax.jit(functools.partial(step, cfg), donate_argnums=(0,))
        # Chaos engine attachment: a chaos.ChaosPlan or chaos.CompiledChaos
        # (plans compile lazily at this sim's batch shape).  run_plan()
        # executes it; run_round(link=...) threads ad-hoc link planes.
        # The lowered schedule and the jitted scan runner are cached per
        # attached plan so repeated run_plan() calls pay one compile, like
        # the _step* functions above.
        self._chaos = chaos
        self._chaos_compiled = None
        self._chaos_runner = None
        # Compiled multi-round scan runners (run_compiled), cached per
        # (rounds, link-threading) so repeated calls pay one compile.
        self._scan_runners: dict = {}
        self._counters: Optional[jnp.ndarray] = None
        self._step_counted = None
        self._health: Optional[HealthState] = None
        # Host-side summary consumer (multiraft.health.HealthMonitor):
        # receives the fixed-size summary dict on the drain cadence.
        self.health_monitor = health_monitor
        if (
            health_monitor is not None
            and cfg.collect_health
            and health_monitor.snapshot_fn is None
        ):
            # Flight-recorder post-mortems snapshot worst groups through us.
            health_monitor.snapshot_fn = self.explain
        self._rounds_since_drain = 0
        self._drain_every = self._DRAIN_MAX
        if cfg.collect_counters:
            self._counters = self._put_replicated(kernels.zero_counters())
            # The device plane is int32 (TPUs have no native int64), so on
            # long runs it is periodically drained into this unbounded
            # host-side accumulator: one device_get every _drain_every
            # rounds keeps the in-flight window far below 2**31 events
            # while leaving per-round dispatch untouched.  Event rates are
            # caller-controlled (append_n) and unknown here, so the cadence
            # starts at 1 round and grows toward a G-scaled cap only while
            # observed windows stay far below the int32 range (halving back
            # under pressure).  The one undetectable case left is a single
            # round accruing >= 2**31 events — a rate at which the int32
            # SimState.commit plane itself would overflow within the run.
            self._host_counters = [0] * kernels.N_COUNTERS
            self._drain_every = 1
            self._drain_cap = max(
                1, min(self._DRAIN_MAX, (1 << 31) // (256 * cfg.n_groups))
            )

            def _counted(st, crashed, append_n, ctrs, link=None):
                return step(cfg, st, crashed, append_n, counters=ctrs,
                            link=link)

            self._step_counted = jax.jit(_counted, donate_argnums=(0, 3))
        self._read_calls = 0  # run_reads' sequence number (profiling spans)
        # The op protocol's carry as the last run_reconfig / run_reads call
        # left it (checkpoint.save_reconfig_state), and the run_reads
        # runner it belongs to: only that runner's next call resumes it.
        self._reconfig_state = None
        self._reconfig_state_of = None
        # The read carry the last run_reads call ended with; its
        # `last_leader` plane seeds the next call's.
        self._read_carry = None
        if cfg.collect_health:
            self._health = init_health(cfg)
            if mesh is not None:
                from . import sharding as sharding_mod

                self._health = sharding_mod.shard_health(
                    self._health, mesh, mesh_axis
                )
            k = min(cfg.health_topk, cfg.n_groups)

            def _summarize(planes):
                return kernels.health_summary(
                    planes,
                    cfg.leaderless_stall_ticks,
                    cfg.commit_stall_ticks,
                    cfg.churn_bumps,
                    k,
                )

            self._summary_fn = jax.jit(_summarize)

            def _healthy(st, crashed, append_n, health, link=None):
                return step(cfg, st, crashed, append_n, health=health,
                            link=link)

            self._step_health = jax.jit(_healthy, donate_argnums=(0, 3))
            if cfg.collect_counters:

                def _both(st, crashed, append_n, ctrs, health, link=None):
                    return step(
                        cfg, st, crashed, append_n,
                        counters=ctrs, health=health, link=link,
                    )

                self._step_both = jax.jit(_both, donate_argnums=(0, 3, 4))
        # Black-box forensics (ISSUE 15): the device flight recorder and
        # its fixed-size drain reduction.  The blackbox-off construction
        # above is untouched — every pre-existing wrapper and its pinned
        # graph stays byte-identical.
        self._blackbox: Optional[BlackboxState] = None
        if cfg.blackbox:
            self._blackbox = init_blackbox(cfg)
            if mesh is not None:
                from . import sharding as sharding_mod

                self._blackbox = sharding_mod.shard_blackbox(
                    self._blackbox, mesh, mesh_axis
                )
            bbk = min(cfg.blackbox_topk, cfg.n_groups)
            self._bb_capture = jax.jit(
                functools.partial(kernels.blackbox_capture, k=bbk)
            )
            self._bb_mark = jax.jit(kernels.blackbox_mark)
            # Per-slot offender counts already surfaced through the
            # monitor (so a drain reports each incident once).
            self._bb_seen = [0] * kernels.N_SAFETY

            def _bb_step(st, crashed, append_n, ctrs, health, bb,
                         link=None):
                return step(
                    cfg, st, crashed, append_n, counters=ctrs,
                    health=health, link=link, blackbox=bb,
                )

            self._step_blackbox = jax.jit(
                _bb_step, donate_argnums=(0, 3, 4, 5)
            )

    _DRAIN_MAX = 128  # never let a window exceed this many rounds

    # --- mesh placement (ISSUE 14; no-ops off-mesh) ---

    def _put(self, x, *spec_axes):
        """Place `x` on the mesh with PartitionSpec(*spec_axes) — the
        trailing axis name is this sim's group mesh axis where given as
        True; None entries replicate that array axis.  Off-mesh (or for
        None planes) this is the identity, so the single-device paths are
        untouched.  device_put with an already-matching sharding is a
        no-op, so repeated run_* calls don't copy."""
        if self.mesh is None or x is None:
            return x
        from jax.sharding import NamedSharding, PartitionSpec

        spec = PartitionSpec(
            *(self.mesh_axis if a is True else None for a in spec_axes)
        )
        return jax.device_put(x, NamedSharding(self.mesh, spec))

    def _put_replicated(self, x):
        return self._put(x)

    def _put_round_planes(self, crashed, append_n, link=None):
        """Place the constant per-round planes: crashed [P, G] and link
        [P, P, G] shard on G, append_n [G] on its only axis."""
        return (
            self._put(crashed, None, True),
            self._put(append_n, True),
            self._put(link, None, None, True),
        )

    def _begin_drain(self) -> dict:
        """Start a drain WITHOUT crossing to the host (ISSUE 11 drain/scan
        overlap): capture the counter plane — swapping fresh zeros in, so
        the next donated scan segment cannot consume the buffer being
        drained — and dispatch the device-side health-summary reduction.
        `_settle_drain` finishes the host side; run_compiled calls it only
        AFTER the next segment is dispatched, so the device→host transfer
        overlaps that segment's execution instead of serializing
        consecutive scans."""
        bufs: dict = {}
        if self._counters is not None:
            bufs["counters"] = self._counters
            self._counters = self._put_replicated(kernels.zero_counters())
        if self._health is not None and self.health_monitor is not None:
            bufs["summary"] = self._summary_fn(self._health.planes)
        if self._blackbox is not None and self.health_monitor is not None:
            # The fixed-size forensics capture (counts + first-K offender
            # ids per safety slot) dispatches device-side here; the
            # incident check happens host-side in _settle_drain, so the
            # transfer overlaps the next scan segment like every drain.
            bufs["forensics"] = self._bb_capture(self._blackbox.trip_round)
        self._rounds_since_drain = 0
        return bufs

    def _settle_drain(self, bufs: dict) -> None:
        """Finish a drain started by _begin_drain: fold the captured
        counter window into the unbounded host accumulator (running the
        GC008 wrap check and the cadence adaptation) and push the health
        summary to the attached monitor."""
        from .health import HealthMonitor

        counters = bufs.get("counters")
        if counters is not None:
            # graftcheck: allow-no-host-sync-in-jit — deliberate host-side
            # drain: runs OUTSIDE the jitted step, at the adaptive cadence,
            # and (in run_compiled) only after the NEXT segment was
            # dispatched, so it overlaps device execution.
            vals = jax.device_get(counters)
            peak = 0
            for i in range(kernels.N_COUNTERS):
                v = int(vals[i])
                if v < 0:
                    raise RuntimeError(
                        "device event counter wrapped int32 within one drain "
                        "window; totals are corrupt — rerun with more frequent "
                        "ClusterSim.counters() calls or fewer events per round"
                    )
                peak = max(peak, v)
                self._host_counters[i] += v
            # Adapt the cadence to the observed event rate: stay well clear
            # of 2**31 per window, but don't sync more often than needed.
            if peak > (1 << 29) and self._drain_every > 1:
                self._drain_every //= 2
            elif peak < (1 << 26) and self._drain_every < self._drain_cap:
                self._drain_every *= 2
        summary = bufs.get("summary")
        if summary is not None:
            # graftcheck: allow-no-host-sync-in-jit — the FIXED-SIZE summary
            # download (never the [., G] planes), same overlap as above.
            counts, hist, ids, scores = jax.device_get(summary)
            self.health_monitor.record(
                HealthMonitor.summary_dict(counts, hist, ids, scores)
            )
        capture = bufs.get("forensics")
        if capture is not None:
            # graftcheck: allow-no-host-sync-in-jit — the FIXED-SIZE
            # forensics capture ([N_SAFETY] counts + [N_SAFETY, K] ids),
            # same drain overlap as the summary above.
            bcounts, bids, brounds = jax.device_get(capture)
            for s in range(kernels.N_SAFETY):
                n = int(bcounts[s])
                if n > self._bb_seen[s]:
                    self._bb_seen[s] = n
                    self.health_monitor.record_incident({
                        "slot": kernels.SAFETY_NAMES[s],
                        "count": n,
                        "offenders": [
                            {"group": int(g), "round": int(r)}
                            for g, r in zip(bids[s], brounds[s])
                            if g >= 0
                        ],
                    })

    def _drain_counters(self) -> None:
        """Blocking counter drain (run_round cadence / counters() reads)."""
        bufs = {"counters": self._counters}
        self._counters = self._put_replicated(kernels.zero_counters())
        self._rounds_since_drain = 0
        self._settle_drain(bufs)

    def _drain(self) -> None:
        """Periodic BLOCKING host boundary: counter totals fold into the
        unbounded host accumulator, and — when a monitor is attached — the
        fixed-size health summary is pushed to it.  Both ride the same
        adaptive cadence (the PR 1 drain), so health adds no extra sync
        points.  run_compiled uses the split _begin_drain/_settle_drain
        pair instead, so its drains overlap the next scan segment."""
        self._settle_drain(self._begin_drain())

    def run_round(self, crashed=None, append_n=None, link=None) -> SimState:
        """One protocol round; `link` (optional bool[P, P, G]) threads the
        chaos engine's directed reachability plane through the step (see
        sim.step) — None keeps the original all-visible graph."""
        G, P = self.cfg.n_groups, self.cfg.n_peers
        if crashed is None:
            crashed = jnp.zeros((P, G), bool)
        if append_n is None:
            append_n = jnp.zeros((G,), jnp.int32)
        crashed, append_n, link = self._put_round_planes(
            crashed, append_n, link
        )
        cc, ch = self._counters is not None, self._health is not None
        if self._blackbox is not None:
            # One wrapper covers every instrumentation combination when
            # the black box rides along (the blackbox-off wrappers below
            # keep their pinned graphs).
            out = self._step_blackbox(
                self.state, crashed, append_n, self._counters,
                self._health, self._blackbox, link,
            )
            self.state = out[0]
            i = 1
            if cc:
                self._counters = out[i]
                i += 1
            if ch:
                self._health = out[i]
                i += 1
            self._blackbox = out[i]
            if not (cc or ch or self.health_monitor is not None):
                return self.state
        elif cc and ch:
            self.state, self._counters, self._health = self._step_both(
                self.state, crashed, append_n, self._counters, self._health,
                link,
            )
        elif cc:
            self.state, self._counters = self._step_counted(
                self.state, crashed, append_n, self._counters, link
            )
        elif ch:
            self.state, self._health = self._step_health(
                self.state, crashed, append_n, self._health, link
            )
        else:
            self.state = self._step(
                self.state, crashed, append_n, None, None, None, link
            )
            return self.state
        self._rounds_since_drain += 1
        if self._rounds_since_drain >= self._drain_every:
            self._drain()
        return self.state

    def run(self, rounds: int, crashed=None, append_n=None) -> SimState:
        for _ in range(rounds):
            self.run_round(crashed, append_n)
        return self.state

    def _compiled_runner(self, rounds: int, has_link: bool):
        """Jitted `rounds`-round lax.scan with the WHOLE carry donated —
        state (and counter/health extras) double-buffer in place instead of
        paying a fresh allocation + host dispatch per round, the same shape
        the compiled scenario runners use (runner.make_runner).  Cached per
        (rounds, link-threading).

        "Donated" here is verified, not assumed: XLA can silently decline
        a donation it cannot alias, so the GC011 trace audit checks every
        donated buffer of the run_compiled@* inventory rows — including
        the packed recent_active carry — against the compiled alias map
        (tools/graftcheck/trace/inventory.py); a declined donation fails
        `make lint`.  The constant per-scan planes (crashed, append_n,
        link) are deliberately NOT donated: callers reuse them across scan
        segments."""
        key = (rounds, has_link)
        runner = self._scan_runners.get(key)
        if runner is not None:
            return runner
        cfg = self.cfg
        cc = self._counters is not None
        ch = self._health is not None
        bb = self._blackbox is not None
        n_extra = (1 if cc else 0) + (1 if ch else 0) + (1 if bb else 0)

        def run(st, crashed, append_n, *extra):
            link = extra[n_extra] if has_link else None
            # The optional recent_active plane rides the carry bit-packed
            # 32:1 along G (pack_ra_carry) and unpacks only at the step
            # boundary; for undamped states both helpers are identity
            # (None words contribute nothing to the pytree), so the
            # undamped scan graph is unchanged.
            st0, ra0 = pack_ra_carry(st)

            def body(carry, _):
                s, raw, *ex = carry
                s = unpack_ra_carry(s, raw)
                kw = {}
                j = 0
                if cc:
                    kw["counters"] = ex[j]
                    j += 1
                if ch:
                    kw["health"] = ex[j]
                    j += 1
                if bb:
                    kw["blackbox"] = ex[j]
                res = step(cfg, s, crashed, append_n, link=link, **kw)
                # SimState is itself a tuple subtype: wrap by flag.
                if not (cc or ch or bb):
                    res = (res,)
                s2, raw2 = pack_ra_carry(res[0])
                return (s2, raw2) + tuple(res[1:]), ()

            carry, _ = jax.lax.scan(
                body, (st0, ra0) + tuple(extra[:n_extra]), None,
                length=rounds,
            )
            return (unpack_ra_carry(carry[0], carry[1]),) + tuple(
                carry[2:]
            )

        runner = jax.jit(
            run, donate_argnums=(0,) + tuple(range(3, 3 + n_extra))
        )
        self._scan_runners[key] = runner
        return runner

    def run_compiled(
        self, rounds: int, crashed=None, append_n=None, link=None
    ) -> SimState:
        """Advance `rounds` lockstep rounds as donated jitted lax.scan(s):
        zero per-round host dispatches and a double-buffered carry, for
        constant crashed/append/link planes.  With
        counters enabled the scan is chunked to the GC008 drain cap (a
        residual window carried in from prior run_round calls is drained
        up front, so the undrained window provably never exceeds the cap)
        and the host drain cadence runs between chunks; with a
        HealthMonitor attached the scan is chunked to the drain cadence so
        the monitor sees the same summary stream run_round would feed it.
        Health-only with no monitor runs one scan — there is nothing to
        drain to.  Damped configs carry the optional recent_active plane
        bit-packed 32:1 along G inside the scan (pack_ra_carry), unpacked
        at each step boundary — bit-identical to the run_round loop
        (tests/test_checkpoint.py) with ~32x less per-round carry traffic
        for the plane.

        Drains never serialize consecutive segments (ISSUE 11): a due
        drain only CAPTURES its buffers at the segment boundary
        (_begin_drain — the counter plane swaps out of the donated carry
        for fresh zeros, the health summary reduction is dispatched
        device-side) and the host transfer + fold run after the NEXT
        segment is dispatched, overlapping its execution.  Totals and the
        monitor's summary stream are bit-identical to the blocking drain;
        only the ordering moved."""
        G, P = self.cfg.n_groups, self.cfg.n_peers
        if crashed is None:
            crashed = jnp.zeros((P, G), bool)
        if append_n is None:
            append_n = jnp.zeros((G,), jnp.int32)
        crashed, append_n, link = self._put_round_planes(
            crashed, append_n, link
        )
        cc = self._counters is not None
        ch = self._health is not None
        bb = self._blackbox is not None
        if cc:
            seg_max = self._drain_cap
        elif (ch or bb) and self.health_monitor is not None:
            seg_max = self._drain_every
        else:
            seg_max = rounds
        done = 0
        pending = None  # the previous segment's drain, not yet host-side
        while done < rounds:
            seg = min(seg_max, rounds - done)
            if cc and self._rounds_since_drain:
                if self._rounds_since_drain + seg > self._drain_cap:
                    # A residual run_round window plus this scan segment
                    # would stretch past the GC008-proven cap: settle it
                    # first (the drain zeroes the in-flight window).
                    if pending is not None:
                        self._settle_drain(pending)
                        pending = None
                    self._drain()
            runner = self._compiled_runner(seg, link is not None)
            args = [self.state, crashed, append_n]
            if cc:
                args.append(self._counters)
            if ch:
                args.append(self._health)
            if bb:
                args.append(self._blackbox)
            if link is not None:
                args.append(link)
            out = runner(*args)
            if pending is not None:
                # Drain/scan overlap (ISSUE 11): the previous segment's
                # drain crosses to the host only NOW — after this segment
                # was dispatched — so the device→host transfer and the
                # host fold overlap the running scan instead of
                # serializing consecutive donated segments.  The drained
                # buffers were swapped out of the carry by _begin_drain,
                # so the donation above cannot consume them.
                self._settle_drain(pending)
                pending = None
            self.state = out[0]
            i = 1
            if cc:
                self._counters = out[i]
                i += 1
            if ch:
                self._health = out[i]
                i += 1
            if bb:
                self._blackbox = out[i]
            done += seg
            if cc or ch or (bb and self.health_monitor is not None):
                self._rounds_since_drain += seg
                if self._rounds_since_drain >= self._drain_every:
                    pending = self._begin_drain()
        if pending is not None:
            self._settle_drain(pending)
        return self.state

    # --- chaos engine (see raft_tpu/multiraft/chaos.py) ---

    def _shard_chaos_schedule(self, compiled):
        """Place a compiled chaos schedule on the mesh (identity
        off-mesh); runs BEFORE make_runner so the runner's cached
        schedule_args are the placed arrays."""
        if self.mesh is None or compiled is None:
            return compiled
        from . import sharding as sharding_mod

        return sharding_mod.shard_chaos(compiled, self.mesh, self.mesh_axis)

    def _shard_reconfig_schedule(self, compiled):
        """Place a compiled reconfig schedule on the mesh (identity
        off-mesh); the op-protocol carry derives from the already-sharded
        state each run, so only the schedule needs placing."""
        if self.mesh is None or compiled is None:
            return compiled
        from . import sharding as sharding_mod

        placed, _ = sharding_mod.shard_reconfig(
            compiled, None, self.mesh, self.mesh_axis
        )
        return placed

    def _place_reconfig_state(self, rst):
        """Place a fresh op-protocol carry on the mesh (identity off-mesh):
        the [G] protocol planes shard on the group axis, the prev-mask
        copies keep the state's [P, G] spec."""
        if self.mesh is None:
            return rst
        from . import sharding as sharding_mod

        _, rstate_sh = sharding_mod.reconfig_sharding(
            self.mesh, self.mesh_axis
        )
        return jax.tree.map(jax.device_put, rst, rstate_sh)

    def _shard_client_schedule(self, compiled):
        """Place a compiled client-workload schedule on the mesh (identity
        off-mesh), including the packed read-fire words' tile-or-replicate
        fallback (sharding.shard_client); the read carry is placed
        separately per run (run_reads)."""
        if self.mesh is None or compiled is None:
            return compiled
        from . import sharding as sharding_mod

        placed, _ = sharding_mod.shard_client(
            compiled, None, self.mesh, self.mesh_axis
        )
        return placed

    def _chaos_runner_for(self, plan=None):
        """(CompiledChaos, jitted runner) for `plan` (default: the attached
        one), cached so repeated run_plan() calls reuse one scan compile."""
        from . import chaos as chaos_mod

        plan = plan if plan is not None else self._chaos
        if plan is None:
            raise RuntimeError(
                "no chaos plan; construct with chaos= or pass one"
            )
        if plan is self._chaos and self._chaos_compiled is not None:
            # The attached plan's lowered+PLACED schedule is cached
            # (mesh placement must not defeat this cache: a fresh
            # device_put namedtuple per call would invalidate the runner
            # below and retrace the whole scan every run_plan).
            compiled = self._chaos_compiled
        elif isinstance(plan, chaos_mod.CompiledChaos):
            compiled = self._shard_chaos_schedule(plan)
        else:
            compiled = self._shard_chaos_schedule(
                chaos_mod.compile_plan(plan, self.cfg.n_groups)
            )
        if plan is self._chaos:
            if self._chaos_compiled is not compiled:
                self._chaos_compiled = compiled
                self._chaos_runner = None
            if self._chaos_runner is None:
                from . import runner as runner_mod

                self._chaos_runner = runner_mod.make_runner(
                    self.cfg, (compiled,)
                )
            return compiled, self._chaos_runner
        from . import runner as runner_mod

        return compiled, runner_mod.make_runner(self.cfg, (compiled,))

    def run_plan(self, plan=None) -> dict:
        """Execute the attached (or given) chaos plan as ONE jitted
        lax.scan — zero host round trips inside the run — and return the
        scenario report (health.chaos_report: MTTR / time-to-reelect off
        the health planes, plus the per-round safety-invariant counts).

        Requires SimConfig(collect_health=True): the MTTR stats ride on
        the HP_LEADERLESS plane.  The sim's state and health planes are
        advanced in place; the attached plan's compiled schedule and scan
        are cached, so calling run_plan() repeatedly pays one compile.
        """
        from .health import HealthMonitor

        compiled, runner = self._chaos_runner_for(plan)
        health = self._require_health()
        if self._blackbox is not None:
            (
                self.state, self._health, self._blackbox, stats, safety,
            ) = runner(self.state, health, self._blackbox)
        else:
            self.state, self._health, stats, safety = runner(
                self.state, health
            )
        # graftcheck: allow-no-host-sync-in-jit — deliberate end-of-run
        # download of two fixed-size stat vectors, outside the jitted scan.
        stats_h, safety_h = jax.device_get((stats, safety))
        report = HealthMonitor.chaos_report(
            stats_h, safety_h, compiled.n_rounds
        )
        if self.health_monitor is not None:
            self.health_monitor.record_scenario(report)
        return report

    # --- reconfig engine (see raft_tpu/multiraft/reconfig.py) ---

    def run_reconfig(
        self, plan, chaos_plan=None, stall_timeouts: int = 4,
        split: bool = False, split_k: int = 8, split_window: int = 4,
    ) -> dict:
        """Execute a membership-churn plan (reconfig.ReconfigPlan or
        CompiledReconfig) as ONE jitted lax.scan — the conf-entry
        propose/gate/apply protocol, the joint-window safety fold, and
        the MTTR/op stats all fuse into the scan with zero host round
        trips — optionally composed with a chaos plan of equal length
        (reconfig DURING partition/loss/crash).  Returns the scenario
        report (health.HealthMonitor.reconfig_report).

        Requires SimConfig(collect_health=True).  The sim's state/health
        planes advance in place and the sim's config masks end in the
        plan's final configuration; the compiled schedules and scan are
        cached, so repeated calls pay one compile.  `stall_timeouts`
        drives the reconfig-stall detection: a group still in a joint
        config whose commit has been flat for `stall_timeouts *
        election_tick` rounds counts as reconfig-stalled (surfaced as the
        health.reconfig_stall event + gauge through an attached
        HealthMonitor) — no new device plane, just the existing
        commit-stall plane joined with the joint bit.

        `split=True` (ISSUE 11) executes the plan through the
        SPLIT-HORIZON runner (runner.make_runner, split=True): the steady
        stretches between ops ride the fused Pallas kernel in
        `split_k`-round blocks while the op windows (planned by
        reconfig.split_plan with `split_window` rounds around each op)
        run the general per-round body — bit-identical either way, with
        the measured fused fraction added to the report as
        `fused_frac`/`fused_rounds`/`total_rounds` (group-rounds).  With
        collect_counters on, the counter plane threads through the split
        run and drains into the host totals afterwards.
        """
        from . import chaos as chaos_mod
        from . import reconfig as reconfig_mod
        from .health import HealthMonitor

        health = self._require_health()
        fused_zero = False
        if split and self.cfg.blackbox:
            # Conservative v1 (ISSUE 15): steady_mask rejects blackbox-on
            # fused horizons (the fused kernel cannot fold the ring), so
            # the split runner would defuse every block anyway — run the
            # general scan and report the fused fraction honestly as 0.
            split = False
            fused_zero = True
        if isinstance(plan, reconfig_mod.ReconfigPlan):
            # Pre-flight: plans apply ABSOLUTE Changer-computed target
            # masks walked from the plan's bootstrap config, so the sim
            # must start in exactly that config — a mismatch (e.g.
            # re-running a plan from its own end state) would swap in
            # masks unrelated to the live membership.  The joint-window
            # safety audit catches that too, but as an end-of-run
            # violation count; fail actionably up front instead.
            import numpy as np

            want = reconfig_mod.initial_masks(plan, self.cfg.n_groups)
            # graftcheck: allow-no-host-sync-in-jit — cheap [P, G]
            # pre-flight download, before the jitted scan starts.
            cur = jax.device_get(
                (self.state.voter_mask, self.state.outgoing_mask,
                 self.state.learner_mask)
            )
            # graftcheck: allow-no-host-sync-in-jit — materializing the
            # plan's host-built masks for the host-side comparison.
            want_h = [np.asarray(w) for w in want]
            if not all(
                np.array_equal(c, w) for c, w in zip(cur, want_h)
            ):
                raise ValueError(
                    "sim state masks do not match the plan's bootstrap "
                    "config (voters/learners); start from "
                    "sim.init_state(cfg, *reconfig.initial_masks(plan, "
                    "G)) — plans apply absolute target masks, not deltas"
                )
        # Cache key holds the plan OBJECTS and compares with `is` (like
        # the chaos runner cache): an id()-based key could alias a new
        # plan at a garbage-collected plan's address and silently replay
        # the old schedule.  A cache hit also reuses the lowered
        # CompiledReconfig, so repeated calls skip the Changer chain walk
        # and schedule re-upload entirely.
        wc = split and self._counters is not None
        mode = ("split", split_k, split_window, wc) if split else "scan"
        cached = getattr(self, "_reconfig_runner", None)
        if (
            cached is None
            or cached[0] is not plan
            or cached[1] is not chaos_plan
            or cached[4] != mode
        ):
            if isinstance(plan, reconfig_mod.CompiledReconfig):
                compiled = plan
            else:
                compiled = reconfig_mod.compile_plan(
                    plan, self.cfg.n_groups
                )
            compiled = self._shard_reconfig_schedule(compiled)
            if chaos_plan is None or isinstance(
                chaos_plan, chaos_mod.CompiledChaos
            ):
                chaos_compiled = chaos_plan
            else:
                chaos_compiled = chaos_mod.compile_plan(
                    chaos_plan, self.cfg.n_groups
                )
            chaos_compiled = self._shard_chaos_schedule(chaos_compiled)
            from . import runner as runner_mod

            runner = runner_mod.make_runner(
                self.cfg, (compiled, chaos_compiled), split=split,
                k=split_k, window=split_window, with_counters=wc,
            )
            self._reconfig_runner = (
                plan, chaos_plan, compiled, runner, mode,
            )
        else:
            compiled, runner = cached[2], cached[3]
        rst = self._place_reconfig_state(
            reconfig_mod.init_reconfig_state(self.state)
        )
        self._reconfig_state_of = None  # the carry below is no run_reads'
        fused = None
        if split:
            if wc:
                # The split run threads ONE counter window across the
                # whole plan, so the GC008 wrap bound must hold for it:
                # settle any residual run_round window first, and refuse
                # plans longer than the proven per-window cap.
                if self._rounds_since_drain:
                    self._drain_counters()
                if compiled.n_rounds > self._drain_cap:
                    raise ValueError(
                        f"plan spans {compiled.n_rounds} rounds but the "
                        f"GC008 drain cap at this batch size is "
                        f"{self._drain_cap} rounds per undrained window; "
                        "run the plan through runner.make_runner with "
                        "split=True directly, managing the counter "
                        "plane yourself — or split the plan"
                    )
            out = runner(
                self.state, health, rst,
                *((self._counters,) if wc else ()),
            )
            (
                self.state, self._health, self._reconfig_state,
                stats, rstats, safety, fused,
            ) = out[:7]
            if wc:
                # Fold the run's window into the host totals (wrap check
                # included) — the plane must not sit loaded under a zeroed
                # _rounds_since_drain, or the next run_round window would
                # stack on top of it past the proven cap.
                self._counters = out[7]
                self._drain_counters()
        else:
            out = runner(
                self.state, health, rst,
                *(
                    (self._blackbox,)
                    if self._blackbox is not None
                    else ()
                ),
            )
            (
                self.state, self._health, self._reconfig_state,
                stats, rstats, safety,
            ) = out[:6]
            if self._blackbox is not None:
                self._blackbox = out[6]
        # graftcheck: allow-no-host-sync-in-jit — deliberate end-of-run
        # download of fixed-size stat vectors + two small planes,
        # outside the jitted scan.
        stats_h, rstats_h, safety_h, om_h, since_h = jax.device_get(
            (stats, rstats, safety, self.state.outgoing_mask,
             self._health.planes[kernels.HP_SINCE_COMMIT])
        )
        n_stuck, worst = HealthMonitor.reconfig_stall_groups(
            om_h, since_h, self.cfg.election_tick,
            stall_timeouts=stall_timeouts,
            topk=min(self.cfg.health_topk, self.cfg.n_groups),
        )
        report = HealthMonitor.reconfig_report(
            stats_h, rstats_h, safety_h, compiled.n_rounds,
            n_stuck, worst,
        )
        if fused is not None:
            total = compiled.n_rounds * self.cfg.n_groups
            # graftcheck: allow-no-host-sync-in-jit — one int32 scalar,
            # downloaded with the report, outside the jitted segments.
            report["fused_rounds"] = int(jax.device_get(fused))
            report["total_rounds"] = total
            report["fused_frac"] = round(
                report["fused_rounds"] / total, 4
            )
        elif fused_zero:
            report["fused_rounds"] = 0
            report["total_rounds"] = compiled.n_rounds * self.cfg.n_groups
            report["fused_frac"] = 0.0
        if self.health_monitor is not None:
            self.health_monitor.record_reconfig(report)
        return report

    # --- client-read workloads (see raft_tpu/multiraft/workload.py) ---

    def run_reads(
        self, plan, chaos_plan=None, reconfig_plan=None,
        split: bool = False, split_k: int = 8,
    ) -> dict:
        """Execute a client-read workload (workload.ClientPlan or
        CompiledClient) as ONE jitted lax.scan — read fires/retries/
        serves (lease + ReadIndex arms), the Zipf write skew, per-read
        latency folded into the on-device histogram, and the FULL safety
        audit including the linearizability slots, every round —
        optionally composed with a chaos plan and/or a reconfig plan of
        equal length in the SAME scan.  Returns the scenario report
        (workload.read_report: read counts, p50/p90/p99 latency in
        rounds, MTTR, safety).

        Requires SimConfig(collect_health=True); lease-mode phases serve
        locally only under SimConfig(lease_read=True, check_quorum=True)
        and degrade to the ReadIndex round otherwise.  The sim's state
        and health planes advance in place; the compiled schedules and
        scan are cached per plan triple, so repeated calls pay one
        compile.

        A reconfig plan replayed call after call is a cycle: the op
        protocol's carry is kept between calls of one plan triple
        (`self._reconfig_state`, what checkpoint.save_reconfig_state
        saves; reconfig.resume_state) — a group whose chain is complete
        starts again at op 0, a group with an op in flight or ops left
        finishes its chain first, never from op 0 — and its counts are in
        the report (`conf_proposals`, `conf_applied`, `conf_retries`,
        `joint_group_rounds`, `conf_unfinished`: all 0 with no reconfig
        plan).  Plans apply absolute target masks, so a plan meant to be
        replayed has to end in the configuration it starts from.

        `split=True` (the ISSUE 13 fused satellite) executes the plan
        through the workload split runner: steady stretches whose reads
        are pure lease serves ride the fused Pallas kernel in
        `split_k`-round blocks (the lease receipts fold closed-form),
        while quorum-round reads and unsteady stretches run the general
        per-round body — bit-identical either way, with the measured
        `fused_frac` added to the report.  A chaos plan composes with the
        split mode (ISSUE 51): a block is then also one chaos phase, its
        guard and its kernel take that phase's link / crash / loss planes,
        and the stretches BETWEEN a schedule's faults fuse (inside one, a
        block fuses where every group stays steady beside the fault — a
        crashed peer keeps ticking and campaigns once a timeout, so a
        large fleet's down stretch does not).  Such a run also reports
        `split_blocks`, `split_blocks_faulted` (blocks whose chaos phase
        has a crash, a cut or a loss rate), `split_blocks_healthy` and
        `split_blocks_healthy_refused` (the others, and those of them
        that did not fuse), and `guard_refusals`: the groups each guard
        term (workload.GUARD_TERMS) refused, summed over the blocks outside
        a faulted phase that did not fuse.  A reconfig plan does not
        compose with the split mode."""
        from . import reconfig as reconfig_mod
        from . import workload as workload_mod

        health = self._require_health()
        # Host spans on the trace's clock (raft_tpu/profiling.py: no-ops
        # unless a jax.profiler trace is being captured); `call` ties the
        # report span's counts to this call.
        self._read_calls += 1
        with profiling.span("raft.run_reads") as whole:
            with profiling.span("raft.run_reads.prepare") as prepare:
                fused_zero = False
                if split and self.cfg.blackbox:
                    # Conservative v1 (ISSUE 15): blackbox-on horizons
                    # never fuse (steady_mask rejects them), so run the
                    # general scan and report fused_frac 0 instead of
                    # spinning the split machinery.
                    split = False
                    fused_zero = True
                cached = getattr(self, "_read_runner", None)
                mode = ("split", split_k) if split else "scan"
                if (
                    cached is None
                    or cached[0] is not plan
                    or cached[1] is not chaos_plan
                    or cached[2] is not reconfig_plan
                    or cached[5] != mode
                ):
                    prepare.set_metadata(miss=1)
                    compiled, runner, n_ops, loss_draw = (
                        self._build_read_runner(
                            plan, chaos_plan, reconfig_plan, split, split_k
                        )
                    )
                    self._read_runner = (
                        plan, chaos_plan, reconfig_plan, compiled, runner,
                        mode, n_ops, loss_draw,
                    )
                else:
                    compiled, runner, n_ops, loss_draw = (
                        cached[3], cached[4], cached[6], cached[7]
                    )
                whole.set_metadata(
                    call=self._read_calls, rounds=compiled.n_rounds,
                    groups=self.cfg.n_groups, loss_draw=loss_draw,
                    split=int(split), chaos=int(chaos_plan is not None),
                )
                # The op protocol's carry: the one the last call of this
                # plan triple ended with (the runner resumes it: finished
                # chains start again, unfinished ones go on), else fresh.
                if self._reconfig_state_of is runner:
                    rst = self._reconfig_state
                else:
                    rst = self._place_reconfig_state(
                        reconfig_mod.init_reconfig_state(self.state)
                    )
                # Fresh reads each call, over the last acting leader the
                # previous call saw each group have.
                rcar = jax.tree.map(
                    lambda x: self._put(x, True),
                    workload_mod.init_read_carry(
                        self.cfg.n_groups,
                        None if self._read_carry is None
                        else self._read_carry.last_leader,
                    ),
                )
                # The learners' lag rides the scan beside the read carry
                # (the split runner's fused arm cannot count it).
                count_lag = self._boots_learners and not split
                if count_lag:
                    rcar = workload_mod.LearnerLagCarry(
                        rcar, self._put_replicated(jnp.int32(0))
                    )
                args = [self.state, health, rst, rcar]
                if self._blackbox is not None:
                    args.append(self._blackbox)
            with profiling.span("raft.run_reads.dispatch"):
                out = runner(*args)
            (
                self.state, self._health, self._reconfig_state, stats,
                rstats, safety, self._read_carry, rdstats, lat_hist,
            ) = out[:9]
            lag = ()
            if count_lag:
                self._read_carry, behind = self._read_carry
                lag = (behind,)
            self._reconfig_state_of = runner
            i = 9
            if self._blackbox is not None:
                self._blackbox = out[i]
                i += 1
            fused = (out[i],) if split else ()  # the fused group-rounds
            # A split run under a chaos plan: the blocks between faults
            # that did not fuse and the guard's refusals in them, by term.
            guard = out[i + 1:i + 3] if split and chaos_plan is not None else ()
            with profiling.span("raft.run_reads.report") as reporting:
                lat_p, recover_p = workload_mod.report_percentiles(
                    lat_hist, stats
                )
                # Groups with ops of their chain left (none without a plan).
                unfinished = (
                    0 if n_ops is None
                    else reconfig_mod.unfinished_groups(
                        self._reconfig_state.op_ptr, n_ops
                    )
                )
                with profiling.span("raft.run_reads.download"):
                    # graftcheck: allow-no-host-sync-in-jit — deliberate
                    # end-of-run download of fixed-size stat vectors (and
                    # the fused group-round scalar), outside the jitted
                    # scan.
                    got = jax.device_get(
                        (rdstats, lat_p, safety, stats, recover_p, rstats,
                         unfinished, *fused, *guard, *lag)
                    )
                (
                    rdstats_h, lat_p_h, safety_h, stats_h, recover_p_h,
                    rstats_h, unfinished_h,
                ) = got[:7]
                report = workload_mod.read_report(
                    rdstats_h, lat_p_h, safety_h, stats_h, compiled.n_rounds,
                    recover_p_h, rstats_h, unfinished_h,
                    learner_behind=got[-1] if count_lag else None,
                )
                if split or fused_zero:
                    total = compiled.n_rounds * self.cfg.n_groups
                    report["fused_rounds"] = int(got[7]) if split else 0
                    report["total_rounds"] = total
                    report["fused_frac"] = round(
                        report["fused_rounds"] / total, 4
                    )
                if guard:
                    report.update(workload_mod.split_chaos_report(
                        runner.n_blocks, runner.blocks_faulted, *got[8:10]
                    ))
                reporting.set_metadata(
                    call=self._read_calls, groups=self.cfg.n_groups,
                    **workload_mod.report_counts(report),
                )
        if self.health_monitor is not None:
            self.health_monitor.record_reads(report)
        return report

    def _build_read_runner(
        self, plan, chaos_plan, reconfig_plan, split: bool, split_k: int
    ):
        """(compiled client schedule, runner, the reconfig schedule's
        n_ops plane or None, loss_draw) of one run_reads plan triple:
        compile whatever is not compiled yet, place the schedules, build
        the runner (the runner-cache miss of run_reads).  loss_draw is 1
        where the runner's rounds draw the chaos plan's loss sample
        (a plan with a loss rate: chaos.CompiledChaos.lossless false),
        else 0 — the raft.run_reads span's stat of that name."""
        from . import chaos as chaos_mod
        from . import reconfig as reconfig_mod
        from . import runner as runner_mod
        from . import workload as workload_mod

        if isinstance(plan, workload_mod.CompiledClient):
            compiled = plan
        else:
            compiled = workload_mod.compile_plan(plan, self.cfg.n_groups)
        compiled = self._shard_client_schedule(compiled)
        if chaos_plan is None or isinstance(
            chaos_plan, chaos_mod.CompiledChaos
        ):
            chaos_compiled = chaos_plan
        else:
            chaos_compiled = chaos_mod.compile_plan(
                chaos_plan, self.cfg.n_groups
            )
        chaos_compiled = self._shard_chaos_schedule(chaos_compiled)
        if reconfig_plan is None or isinstance(
            reconfig_plan, reconfig_mod.CompiledReconfig
        ):
            reconfig_compiled = reconfig_plan
        else:
            reconfig_compiled = reconfig_mod.compile_plan(
                reconfig_plan, self.cfg.n_groups
            )
        reconfig_compiled = self._shard_reconfig_schedule(reconfig_compiled)
        runner = runner_mod.make_runner(
            self.cfg, (compiled, chaos_compiled, reconfig_compiled),
            split=split, k=split_k,
        )
        n_ops = None if reconfig_compiled is None else reconfig_compiled.n_ops
        loss_draw = int(
            chaos_compiled is not None and not chaos_compiled.lossless
        )
        return compiled, runner, n_ops, loss_draw

    def counters(self) -> dict:
        """Download the device event-counter plane as {name: count}.

        The device->host transfer happens HERE, on demand — never in the
        hot loop.  Requires SimConfig(collect_counters=True).
        """
        if self._counters is None:
            raise RuntimeError(
                "counters disabled; construct with "
                "SimConfig(collect_counters=True)"
            )
        # Fold the device plane into the host totals (running the wrap
        # check) rather than just peeking at it, so every user-visible read
        # is both exact and validated.
        self._drain_counters()
        return dict(zip(kernels.COUNTER_NAMES, self._host_counters))

    def reset_counters(self) -> None:
        if self._counters is not None:
            self._counters = kernels.zero_counters()
            self._host_counters = [0] * kernels.N_COUNTERS
            self._rounds_since_drain = 0

    # --- fleet health (requires SimConfig(collect_health=True)) ---

    def _require_health(self) -> HealthState:
        if self._health is None:
            raise RuntimeError(
                "health planes disabled; construct with "
                "SimConfig(collect_health=True)"
            )
        return self._health

    def _health_summary_dict(self) -> dict:
        """Reduce the device planes to the fixed-size summary and download
        it — O(topk + buckets) bytes regardless of n_groups."""
        from .health import HealthMonitor

        h = self._require_health()
        summary = self._summary_fn(h.planes)
        # graftcheck: allow-no-host-sync-in-jit — deliberate host-side
        # drain of the FIXED-SIZE summary (never the [., G] planes), on the
        # adaptive cadence / on demand, outside the jitted step.
        counts, hist, ids, scores = jax.device_get(summary)
        return HealthMonitor.summary_dict(counts, hist, ids, scores)

    def health(self) -> dict:
        """Current fleet-health summary as a plain dict:

          counts:   {leaderless, stalled_leaderless, commit_stalled,
                     churning} group counts vs the SimConfig thresholds
          lag_hist: [kernels.N_LAG_BUCKETS] commit-lag histogram
          worst:    top-k worst offenders [{group, score}, ...], score =
                    max(ticks_since_commit, leaderless_ticks)

        The reduction runs on device; only the summary is downloaded.  The
        summary is also pushed to the attached HealthMonitor (if any)."""
        summary = self._health_summary_dict()
        if self.health_monitor is not None:
            self.health_monitor.record(summary)
        return summary

    def explain(self, group_id: int) -> dict:
        """Post-mortem for ONE group: its health-plane row plus every
        peer's consensus cursors.  On-demand host download of O(P) values —
        never part of the hot loop."""
        h = self._require_health()
        # graftcheck: allow-no-host-sync-in-jit — deliberate on-demand
        # post-mortem download of one group's column, outside the step.
        planes = jax.device_get(h.planes[:, group_id])
        st = self.state
        # graftcheck: allow-no-host-sync-in-jit — same on-demand post-mortem
        # download (one [P] column per plane), outside the jitted step.
        cols = jax.device_get(
            (
                st.term[:, group_id],
                st.state[:, group_id],
                st.commit[:, group_id],
                st.last_index[:, group_id],
                st.leader_id[:, group_id],
                st.voter_mask[:, group_id] | st.outgoing_mask[:, group_id],
                st.learner_mask[:, group_id],
            )
        )
        term, role, commit, last_index, leader_id, voter, learner = cols
        return {
            "group": int(group_id),
            "health": dict(
                zip(kernels.HEALTH_PLANE_NAMES, (int(v) for v in planes))
            ),
            "peers": {
                "term": [int(v) for v in term],
                "state": [int(v) for v in role],
                "commit": [int(v) for v in commit],
                "last_index": [int(v) for v in last_index],
                "leader_id": [int(v) for v in leader_id],
                # Config membership: the autopilot's target filter (a
                # learner or removed peer is never a kick/transfer
                # target).
                "voter": [bool(v) for v in voter],
                "learner": [bool(v) for v in learner],
            },
        }

    def reset_health(self) -> None:
        if self._health is not None:
            self._health = init_health(self.cfg)

    # --- black-box forensics (requires SimConfig(blackbox=True)) ---

    def _require_blackbox(self) -> BlackboxState:
        if self._blackbox is None:
            raise RuntimeError(
                "black box disabled; construct with "
                "SimConfig(blackbox=True)"
            )
        return self._blackbox

    def record_safety(self, viol: jnp.ndarray) -> None:
        """Stamp a bool[kernels.N_SAFETY, G] violation mask onto the LAST
        stepped round's black-box record (kernels.blackbox_mark) — the
        ad-hoc stepping path: drive run_round, audit the transition
        host-side (kernels.check_safety_groups), hand the mask back here.
        The compiled runners fold trace and bits in one on-device call
        instead; nothing here runs in a hot loop."""
        bb = self._require_blackbox()
        meta, trip = self._bb_mark(
            bb.meta, bb.trip_round, bb.round_idx, viol
        )
        self._blackbox = bb._replace(meta=meta, trip_round=trip)

    def forensics(self) -> dict:
        """The fixed-size forensics capture as a plain dict: per safety
        slot, how many groups have EVER tripped it and the first-K
        offenders as [{"group": id, "round": first-trip round}, ...]
        (kernels.blackbox_capture; K = SimConfig.blackbox_topk).  The
        reduction runs on device and only O(K) bytes download — never the
        [N_SAFETY, G] trip plane."""
        bb = self._require_blackbox()
        # graftcheck: allow-no-host-sync-in-jit — deliberate on-demand
        # download of the FIXED-SIZE capture, outside the jitted scans.
        counts, ids, rounds = jax.device_get(
            self._bb_capture(bb.trip_round)
        )
        # graftcheck: allow-no-host-sync-in-jit — one int32 scalar (the
        # absolute round counter), same on-demand path.
        folded = int(jax.device_get(bb.round_idx))
        return {
            "rounds_folded": folded,
            "counts": {
                name: int(c)
                for name, c in zip(kernels.SAFETY_NAMES, counts)
            },
            "offenders": {
                kernels.SAFETY_NAMES[s]: [
                    {"group": int(g), "round": int(r)}
                    for g, r in zip(ids[s], rounds[s])
                    if g >= 0
                ]
                for s in range(kernels.N_SAFETY)
            },
        }

    def incident_report(self) -> dict:
        """The full incident JSON (forensics.build_incident): the capture
        above plus each offender group's decoded black-box window — the
        last W rounds of (role, leader, term, commit, fired slots) — the
        artifact the report tools attach on a nonzero safety count."""
        from . import forensics as forensics_mod

        return forensics_mod.build_incident(self)

    def reset_forensics(self) -> None:
        if self._blackbox is not None:
            self._blackbox = init_blackbox(self.cfg)
            if self.mesh is not None:
                from . import sharding as sharding_mod

                self._blackbox = sharding_mod.shard_blackbox(
                    self._blackbox, self.mesh, self.mesh_axis
                )
            self._bb_seen = [0] * kernels.N_SAFETY

    def read_index(self, crashed=None, link=None) -> jnp.ndarray:
        """Batched linearizable ReadIndex barrier (see sim.read_index);
        `link` threads the chaos reachability plane through the ack
        quorum."""
        if crashed is None:
            crashed = jnp.zeros(
                (self.cfg.n_peers, self.cfg.n_groups), bool
            )
        return jax.jit(functools.partial(read_index, self.cfg))(
            self.state, crashed, link
        )

    def lease_read(self, crashed=None) -> jnp.ndarray:
        """Pure LeaseBased read probe (ISSUE 13; see kernels.lease_read):
        int32[G] — the commit index each group's acting leader would
        serve LOCALLY under the check-quorum lease right now, or -1 where
        the lease gate fails (no lease-holding leader, uncommitted term,
        pending transfer, or lease reads disabled).  Requires
        SimConfig(lease_read=True, check_quorum=True) for a non-trivial
        answer; zero message rounds either way.  For the full in-round
        read path (serve + ReadIndex degrade + latency accounting) use
        step(read_propose=) / ClusterSim.run_reads."""
        if crashed is None:
            crashed = jnp.zeros(
                (self.cfg.n_peers, self.cfg.n_groups), bool
            )
        cfg = self.cfg

        def probe(st, cr):
            _, served, index = kernels.lease_read(
                st.state, st.term, st.leader_id, st.election_elapsed,
                st.commit, st.term_start_index, cr, cfg.election_tick,
                cfg.check_quorum and cfg.lease_read, st.transferee,
                st.recent_active, st.voter_mask, st.outgoing_mask,
            )
            return jnp.where(served, index, jnp.int32(-1))

        return jax.jit(probe)(self.state, crashed)
