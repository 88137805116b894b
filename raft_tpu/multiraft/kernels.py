"""Batched Raft kernels as pure jnp functions over [..., P] peer planes.

Each kernel is the vectorized equivalent of a scalar-oracle function.
This map is MACHINE-CHECKED: graftcheck GC006 fails if a public function
here is missing from it or untested under tests/.

  majority_of              <-> quorum size n//2 + 1
                               (reference: util.rs:118-120)
  committed_index          <-> quorum.MajorityConfig.committed_index
                               (reference: majority.rs:70-124)
  committed_index_grouped  <-> quorum.MajorityConfig.committed_index with
                               group-commit enabled
                               (reference: majority.rs:99-124)
  joint_committed_index    <-> quorum.JointConfig.committed_index
                               (reference: joint.rs:47-51)
  vote_result              <-> quorum.MajorityConfig.vote_result
                               (reference: majority.rs:130-154)
  joint_vote_result        <-> quorum.JointConfig.vote_result
                               (reference: joint.rs:56-67)
  timeout_draw             <-> util.deterministic_timeout (both sides use the
                               same 32-bit mixer; reference replaces
                               raft.rs:2744-2756)
  tick_kernel              <-> Raft.tick_election / tick_heartbeat
                               (reference: raft.rs:1024-1079)
  append_response_update   <-> tracker.Progress.maybe_update
                               (reference: progress.rs:138-150)
  zero_counters /          <-> the device mirror of raft_tpu.metrics event
  count_events                 counters (no reference analog; parity vs the
                               scalar counts in tests/test_counter_parity.py)
  zero_health /            <-> the device fleet-health planes (no reference
  update_health                analog; per-round parity vs the scalar
                               HealthOracle in tests/test_health_parity.py)
  health_summary           <-> on-device reduction of the health planes to a
                               fixed-size summary (threshold counts, commit-
                               lag histogram, lax.top_k worst offenders);
                               parity vs a host argsort in
                               tests/test_health_parity.py
  link_loss_draw           <-> the host-side schedule twin
                               (tests/test_chaos_parity.py asserts bit-exact
                               equality with chaos host_loss_draw, the numpy
                               half of the ChaosOracle fault schedules)
  pack_bits / unpack_bits  <-> lossless bool-plane bit packing (no reference
                               analog; exact round-trip + numpy-twin parity
                               in tests/test_multiraft_kernels.py); packs
                               the chaos schedule's bool planes 32:1 so the
                               per-round schedule gather reads words, not
                               byte-per-bool planes (GC008 PACKED_PLANES)
  pack_u16_pairs /         <-> lossless 16-bit halfword packing for values
  unpack_u16_pairs             provably < 2**16 (loss rates are <=
                               LOSS_SCALE — GC008 PACKED_PLANES); exact
                               round-trip + numpy-twin parity in
                               tests/test_multiraft_kernels.py
  pack_bits_g              <-> simref.host_pack_bits_g (the numpy twin;
                               exact round-trip + twin parity in
                               tests/test_multiraft_kernels.py): 32:1
                               GROUP-axis packing of bool planes — the
                               recent_active scan-carry form the donated
                               runners and the fused-damped bench carry
                               (GC008 PACKED_PLANES family `bits_g`)
  unpack_bits_g            <-> simref.host_unpack_bits_g (the numpy twin;
                               round-trip + twin parity in
                               tests/test_multiraft_kernels.py): the
                               inverse unpack back to bool[..., G] at the
                               step boundary
  cq_boundary_safe         <-> the check-quorum boundary outcome over a
                               steady horizon (the damping gate of
                               pallas_step.steady_mask): conservative
                               scalar twin in
                               tests/test_multiraft_kernels.py, horizon
                               behavior pinned end-to-end by the
                               fused-damped parity suite
                               tests/test_pallas_step.py
  check_safety             <-> the Raft safety arguments themselves
                               (tests/test_chaos_parity.py drives it every
                               fuzz round; ChaosOracle holds the scalar
                               state it must never flag; the joint-window
                               slots run every reconfig round against
                               simref.ReconfigOracle state in
                               tests/test_reconfig_parity.py; the
                               linearizability slots run every workload
                               round against simref.ReadOracle state in
                               tests/test_read_lease.py)
  lease_read               <-> the LeaseBased serve decision of
                               Raft.step_leader's MsgReadIndex arm under
                               the check-quorum lease (reference:
                               read_only.rs LeaseBased +
                               raft.rs:2067-2096); simref.ReadOracle
                               applies the identical host-side gate and
                               drives the REAL scalar
                               ReadOnlyOption::LeaseBased pump —
                               tests/test_read_lease.py
  apply_confchange         <-> confchange.Changer transitions + raft.rs
                               post_conf_change reactions
                               (reference: changer.rs:40-280,
                               raft.rs:2604-2673); targets are
                               Changer-validated host-side by
                               reconfig.compile_plan, and
                               simref.ReconfigOracle performs the
                               bit-identical scalar surgery —
                               tests/test_reconfig_parity.py
  select_row               <-> a per-group row look-up, plane[idx[g], ..., g]
                               (no reference twin: numpy's take_along_axis
                               is the oracle); N - 1 static selects under
                               reconfig._gather_peer / _gather_op and the
                               transfer abort of apply_confchange —
                               bit-equality in tests/test_row_selects.py
  apply_transfer           <-> Raft.handle_transfer_leader — the leader-side
                               MsgTransferLeader step (reference:
                               raft.rs:1821-1889): validate the target
                               (member, not learner, not self), abort a
                               pending transfer to another target, reset
                               the transfer clock; the catch-up append /
                               MsgTimeoutNow pump it queues is
                               sim._transfer_phase, parity vs the real
                               RawNode::transfer_leader pump
                               (simref.TransferOracle) in
                               tests/test_transfer_batched.py
  acting_leader_id         <-> ScalarCluster.acting_leader (the alive
                               max-term leader; 0 = none) — the autopilot's
                               per-group leader placement read, parity in
                               tests/test_transfer_batched.py
  check_quorum_active      <-> tracker.ProgressTracker.quorum_recently_active
                               (reference: tracker.rs:346-372); the damped
                               round reads it at each leader's
                               election-timeout boundary — per-round parity
                               vs real check-quorum Rafts in
                               tests/test_damping_parity.py
  check_safety_groups      <-> the per-GROUP form of check_safety (same
                               invariants, same optional args, on the
                               packed core `_safety_flags`): the forensics
                               trigger surface — both held to a plain
                               per-group NumPy reference in
                               tests/test_safety_audit_form.py, its
                               slot-wise group sums asserted EQUAL to
                               check_safety's counts on fuzzed and
                               trapped states in tests/test_forensics.py
  pack_blackbox_meta /     <-> the packed black-box ring word (role < 4,
  unpack_blackbox_meta         acting leader id <= n_peers < 16, N_SAFETY
                               fired-slot bits — GC008 PACKED_PLANES
                               `blackbox_meta`); exact round-trip in
                               tests/test_forensics.py
  zero_blackbox /          <-> the device black-box flight recorder
  blackbox_fold /              (ISSUE 15): a [W, G] windowed ring of
  blackbox_mark                per-group round deltas plus the
                               [N_SAFETY, G] first-trip round plane, one
                               masked fold per round; the host twin is
                               forensics.decode_window + the scalar
                               replay in tests/test_forensics.py
  blackbox_capture         <-> the drain-time reduction of the trip plane
                               to fixed-size (counts, first-K offender
                               ids, trip rounds) per safety slot —
                               lax.top_k with the same low-group-id tie
                               break as health_summary; host-argsort
                               parity in tests/test_forensics.py

TPU notes: P is tiny (<= 8 typical) and static, so the quorum position is
taken from P static slices of the peer axis, a compare-exchange network over
them (_sort_rows_desc) and P static selects (_quorum_of_rows) — elementwise
int32 work with G on the lanes, no MXU, no dynamic shapes.  It used to be a
`jnp.sort` along the last axis plus a `take_along_axis`, on the assumption
that XLA lowers that to such a network: on the v5e it does not.  The gather
ran at ~70 M elements/s and, eight calls a round, was 58 of a general
round's 98 ms at 100k x 5 (PERF.md §6, PR 26 and PR 27).  All dtypes are
int32/bool (indices < 2^31 in practice; the scalar oracle checks overflow),
so no x64 dependency.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import profiling

INF = jnp.int32(2**31 - 1)

# Vote results as int codes matching quorum.VoteResult.
VOTE_PENDING = 0
VOTE_LOST = 1
VOTE_WON = 2


def majority_of(count: jnp.ndarray) -> jnp.ndarray:  # gc: int32[...]
    """Quorum size: n // 2 + 1 (reference: util.rs:118-120)."""
    return count // 2 + 1


def _sort_rows_desc(rows: Sequence[jnp.ndarray]) -> List[jnp.ndarray]:
    """Descending odd-even transposition sorting network over P same-shaped
    rows: the TPU-friendly replacement for a variadic sort along the peer
    axis (SURVEY.md §7 kernel k2).  P is static, so the network unrolls."""
    n = len(rows)
    rows = list(rows)
    for pass_ in range(n):
        for i in range(pass_ % 2, n - 1, 2):
            hi = jnp.maximum(rows[i], rows[i + 1])
            lo = jnp.minimum(rows[i], rows[i + 1])
            rows[i], rows[i + 1] = hi, lo
    return rows


def _quorum_of_rows(
    rows: Sequence[jnp.ndarray],  # P rows of int32[...]
    masks: Sequence[jnp.ndarray],  # P rows of bool[...]
) -> jnp.ndarray:
    """The majority()-th largest of P per-peer rows among the voters the P
    masks name; INF where no peer is a voter.  The one body under both
    committed_index ([..., P] operands) and sim._quorum_index ([P, G] planes).

    Padding argument: non-voters are masked to 0.  Since matched >= 0, the
    k-th largest over (voters ∪ zero-padding) equals the k-th largest over
    voters alone for k <= |voters| — zeros can only displace other zeros.
    """
    srt = _sort_rows_desc(
        [jnp.where(m, r, 0) for r, m in zip(rows, masks)]
    )
    count = masks[0].astype(jnp.int32)
    for m in masks[1:]:
        count = count + m.astype(jnp.int32)
    qpos = count // 2  # majority_of(count) - 1, counted from the largest
    out = jnp.zeros_like(srt[0])
    for p, row in enumerate(srt):
        out = jnp.where(qpos == p, row, out)
    return jnp.where(count == 0, INF, out)


def select_row(plane: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """plane[N, ..., G], idx int32[..., G] in [0, N) -> plane[idx[g], ..., g],
    shaped as plane[0] broadcast against idx.

    N - 1 static selects over the leading axis (the form _quorum_of_rows
    picks its position by) instead of a take_along_axis: G stays on the
    lanes and every operand is read at HBM speed, where a dynamic gather of
    the same planes runs two orders slower on the TPU (PERF.md §6, PR 34).
    lax.select, not jnp.where: a jitted helper's ops drop the caller's
    name stack, and the selects are what a scope around this must name.
    """
    shape = jnp.broadcast_shapes(plane.shape[1:], idx.shape)
    out = jnp.broadcast_to(plane[0], shape)
    for n in range(1, plane.shape[0]):
        out = jax.lax.select(
            jnp.broadcast_to(idx == n, shape),
            jnp.broadcast_to(plane[n], shape),
            out,
        )
    return out


@profiling.scope("quorum_commit")
def committed_index(
    matched: jnp.ndarray,  # gc: int32[..., P]
    voter_mask: jnp.ndarray,  # gc: bool[..., P]
) -> jnp.ndarray:
    """Per-group quorum commit index over the peer axis.

    matched:    int32[..., P] acked index per peer (leader's Progress.matched)
    voter_mask: bool[..., P]  which peers are voters of this majority config

    Returns int32[...]: the majority()-th largest matched among voters; INF
    for an empty config (so joint min() ignores it), exactly the reference's
    empty-config convention (majority.rs:71-75).

    The peer axis is read with P static slices, so no operand's minor
    dimension is P and G stays on the lanes: no sort, no gather.  A
    caller's swapaxes(x[P_owner, P, G], 1, 2) is then a LAYOUT of x, not
    an op: where the compiler can hand that layout to x's producer it
    costs nothing (wave 4's sim._stage_fold at 1M x 3), where it cannot it
    is a physical owner-major copy of the plane (wave 5's; the safety
    audit's, until ISSUE 52 replaced the audit's two networks by a count —
    `tools/aot_round.py` shows which).
    """
    P = matched.shape[-1]
    return _quorum_of_rows(
        [matched[..., p] for p in range(P)],
        [voter_mask[..., p] for p in range(P)],
    )


def committed_index_grouped(
    matched: jnp.ndarray,  # gc: int32[..., P]
    group_ids: jnp.ndarray,  # gc: int32[..., P]
    voter_mask: jnp.ndarray,  # gc: bool[..., P]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Group-commit variant (reference: majority.rs:99-124): commits need
    acks from >= 2 distinct commit groups.

    matched:   int32[..., P]
    group_ids: int32[..., P] commit group per peer (0 = unassigned)
    voter_mask: bool[..., P]

    Returns (index[...], use_group_commit[...]):
      * >= 2 distinct non-zero groups among voters -> min(quorum_index,
        max matched of any voter outside the quorum group scan) — computed
        exactly as the reference does: walking the reverse-sorted list, the
        first voter whose non-zero group differs from the quorum entry's
        (first non-zero seen) group caps the result.
      * single non-zero group        -> (quorum_index, False)
      * any zero group among voters  -> falls back to min matched, False
        (unless a differing pair is found first).
    """
    p = matched.shape[-1]
    # Reverse sort by index, carrying group ids along.  Non-voters are
    # keyed -1 so they sort strictly AFTER every voter (a padded 0 must not
    # displace a genuine voter entry with matched == 0 — the group scan
    # walks exactly the first `count` sorted entries).
    masked = jnp.where(voter_mask, matched, -1)
    masked_groups = jnp.where(voter_mask, group_ids, 0)
    order = jnp.argsort(-masked, axis=-1, stable=True)
    masked = jnp.where(voter_mask, matched, 0)
    srt_idx = jnp.take_along_axis(masked, order, axis=-1)
    srt_grp = jnp.take_along_axis(masked_groups, order, axis=-1)
    count = jnp.sum(voter_mask, axis=-1).astype(jnp.int32)
    q = majority_of(count)
    qpos = jnp.clip(q - 1, 0, p - 1)
    quorum_index = jnp.take_along_axis(srt_idx, qpos[..., None], axis=-1)[..., 0]
    quorum_group = jnp.take_along_axis(srt_grp, qpos[..., None], axis=-1)[..., 0]

    # Scalar scan (majority.rs:102-123) vectorized via a P-step fori over the
    # sorted voters — P is tiny and static so this unrolls.
    def body(i, carry):
        checked_group, single_group, result, done = carry
        in_range = i < count
        g = srt_grp[..., i]
        ix = srt_idx[..., i]
        is_zero = (g == 0) & in_range
        single_group = single_group & ~is_zero
        take_group = (checked_group == 0) & (g != 0) & in_range & ~done
        differs = (
            (checked_group != 0) & (g != 0) & (g != checked_group) & in_range & ~done
        )
        result = jnp.where(differs, jnp.minimum(ix, quorum_index), result)
        done = done | differs
        checked_group = jnp.where(take_group, g, checked_group)
        return checked_group, single_group, result, done

    shape = matched.shape[:-1]
    carry = (
        quorum_group,
        jnp.ones(shape, dtype=bool),
        jnp.zeros(shape, dtype=jnp.int32),
        jnp.zeros(shape, dtype=bool),
    )
    checked_group, single_group, result, done = jax.lax.fori_loop(
        0, p, body, carry
    )
    # Smallest matched among voters (the last in-range sorted entry).
    last_pos = jnp.clip(count - 1, 0, p - 1)
    min_matched = jnp.take_along_axis(srt_idx, last_pos[..., None], axis=-1)[..., 0]
    fallback = jnp.where(single_group, quorum_index, min_matched)
    index = jnp.where(done, result, fallback)
    use_gc = done
    index = jnp.where(count == 0, INF, index)
    use_gc = jnp.where(count == 0, True, use_gc)
    return index, use_gc


def joint_committed_index(
    matched: jnp.ndarray,  # gc: int32[..., P]
    incoming_mask: jnp.ndarray,  # gc: bool[..., P]
    outgoing_mask: jnp.ndarray,  # gc: bool[..., P]
) -> jnp.ndarray:
    """Joint config: min over both majorities (reference: joint.rs:47-51).
    An empty outgoing half returns INF from committed_index, so min()
    reduces to the incoming half."""
    return jnp.minimum(
        committed_index(matched, incoming_mask),
        committed_index(matched, outgoing_mask),
    )


def vote_result(
    granted: jnp.ndarray,  # gc: bool[..., P]
    rejected: jnp.ndarray,  # gc: bool[..., P]
    voter_mask: jnp.ndarray,  # gc: bool[..., P]
) -> jnp.ndarray:
    """Vote outcome over the peer axis (reference: majority.rs:130-154).

    granted/rejected: bool[..., P] votes recorded (both False = missing)
    voter_mask:       bool[..., P]

    Returns int32[...] VOTE_{PENDING,LOST,WON}; empty configs win.
    """
    g = jnp.sum(granted & voter_mask, axis=-1).astype(jnp.int32)
    r = jnp.sum(rejected & voter_mask, axis=-1).astype(jnp.int32)
    count = jnp.sum(voter_mask, axis=-1).astype(jnp.int32)
    q = majority_of(count)
    missing = count - g - r
    won = (g >= q) | (count == 0)
    pending = (g + missing >= q) & ~won
    return jnp.where(won, VOTE_WON, jnp.where(pending, VOTE_PENDING, VOTE_LOST))


def joint_vote_result(
    granted: jnp.ndarray,  # gc: bool[..., P]
    rejected: jnp.ndarray,  # gc: bool[..., P]
    incoming_mask: jnp.ndarray,  # gc: bool[..., P]
    outgoing_mask: jnp.ndarray,  # gc: bool[..., P]
) -> jnp.ndarray:
    """reference: joint.rs:56-67"""
    i = vote_result(granted, rejected, incoming_mask)
    o = vote_result(granted, rejected, outgoing_mask)
    won = (i == VOTE_WON) & (o == VOTE_WON)
    lost = (i == VOTE_LOST) | (o == VOTE_LOST)
    return jnp.where(won, VOTE_WON, jnp.where(lost, VOTE_LOST, VOTE_PENDING))


def _mix32(x: jnp.ndarray) -> jnp.ndarray:
    """32-bit murmur3 finalizer — the shared mixer behind timeout_draw and
    link_loss_draw (the host twin is chaos.host_loss_draw's inline copy)."""
    x ^= x >> 16
    x *= jnp.uint32(0x85EBCA6B)
    x ^= x >> 13
    x *= jnp.uint32(0xC2B2AE35)
    x ^= x >> 16
    return x


LOSS_SCALE = 10_000  # loss rates are int32 fixed-point per-ten-thousand


def link_loss_draw(
    round_idx: jnp.ndarray,  # gc: int32[]
    loss_rate: jnp.ndarray,  # gc: int32[P, P, G]
    group_ids: Optional[jnp.ndarray] = None,  # gc: int32[G]
) -> jnp.ndarray:
    """Seeded per-link message-loss sample for one protocol round.

    round_idx: int32 scalar, the round number (the replay key).
    loss_rate: int32[P, P, G] per-directed-link loss probability in units
               of 1/LOSS_SCALE (0 = lossless, LOSS_SCALE = always down).
    group_ids: optional int32[G] GLOBAL group ids when loss_rate is a
               slice of the fleet that is not groups 0..G-1:
               the (round, src, dst, group) PRNG key must keep drawing
               from each group's global stream, exactly like sim.step's
               group_ids= keeps the timeout PRNG global.

    Returns bool[P, P, G]: True where the (src, dst, group) link drops all
    messages this round.  The draw is a counter PRNG keyed
    (round, src, dst, group) — no state, so any round of any schedule can
    be replayed in isolation bit-exactly; chaos.host_loss_draw is the
    numpy twin the ChaosOracle uses and must stay bit-identical
    (tests/test_chaos_parity.py).
    """
    P = loss_rate.shape[0]
    G = loss_rate.shape[2]
    if group_ids is None:
        g = jnp.arange(G, dtype=jnp.uint32)[None, None, :]
    else:
        g = group_ids.astype(jnp.uint32)[None, None, :]
    s = jnp.arange(P, dtype=jnp.uint32)[:, None, None]
    d = jnp.arange(P, dtype=jnp.uint32)[None, :, None]
    lane = s * jnp.uint32(P) + d + jnp.uint32(1)
    x = _mix32(g * jnp.uint32(0x9E3779B1) + round_idx.astype(jnp.uint32))
    x = _mix32(x ^ (lane * jnp.uint32(0x85EBCA6B)))
    return (x % jnp.uint32(LOSS_SCALE)).astype(jnp.int32) < loss_rate


def pack_bits(planes: jnp.ndarray) -> jnp.ndarray:  # gc: bool[K, ...]
    """Pack K bool planes along axis 0 into ceil(K/32) uint32 word planes.

    Word w's bit j holds plane 32*w + j.  Lossless for any K (unpack_bits
    inverts it exactly); used to shrink the chaos schedule's bool planes —
    `link[NPH, P, P, G]` stored byte-per-bool costs P*P bytes per (phase,
    group) where the packed form costs 4*ceil(P*P/32) — so the per-round
    schedule gather reads ~6x less HBM at P = 5."""
    k = planes.shape[0]
    n_words = (k + 31) // 32
    bits = planes.astype(jnp.uint32)
    words = []
    for w in range(n_words):
        acc = jnp.zeros(planes.shape[1:], jnp.uint32)
        for j in range(min(32, k - 32 * w)):
            acc = acc | (bits[32 * w + j] << j)
        words.append(acc)
    return jnp.stack(words)


def _unpack_fields(
    words: jnp.ndarray,  # gc: uint32[W, ...]
    k: int,
    width: int,
) -> jnp.ndarray:
    """The first k `width`-bit fields of a uint32 word stack, field j of
    word w at row w * (32 // width) + j: uint32[W, ...] -> uint32[k, ...].

    ONE broadcast shift over [W, 32 // width, ...] and a static slice — no
    row is built on its own and nothing is stacked, so the whole unpack is
    one elementwise kernel for any k (ISSUE 48: k stacked rows lowered to
    a `concatenate` that cost five times its bytes at k = 25)."""
    per = 32 // width
    shifts = jnp.arange(0, 32, width, dtype=jnp.uint32).reshape(
        (1, per) + (1,) * (words.ndim - 1)
    )
    fields = (words[:, None] >> shifts) & jnp.uint32((1 << width) - 1)
    return fields.reshape((words.shape[0] * per,) + words.shape[1:])[:k]


def unpack_bits(words: jnp.ndarray, k: int) -> jnp.ndarray:  # gc: uint32[W, ...]
    """Inverse of pack_bits: uint32[ceil(k/32), ...] -> bool[k, ...]."""
    return _unpack_fields(words, k, 1) != 0


def pack_u16_pairs(vals: jnp.ndarray) -> jnp.ndarray:  # gc: int32[K, ...]
    """Pack K int32 planes of values provably < 2**16 (the GC008
    PACKED_PLANES bound — loss rates are <= LOSS_SCALE) into ceil(K/2)
    uint32 planes: even indices in the low halfword, odd in the high."""
    k = vals.shape[0]
    v = vals.astype(jnp.uint32)
    words = []
    for w in range((k + 1) // 2):
        lo = v[2 * w]
        if 2 * w + 1 < k:
            words.append(lo | (v[2 * w + 1] << 16))
        else:
            words.append(lo)
    return jnp.stack(words)


def unpack_u16_pairs(words: jnp.ndarray, k: int) -> jnp.ndarray:  # gc: uint32[W, ...]
    """Inverse of pack_u16_pairs: uint32[ceil(k/2), ...] -> int32[k, ...]."""
    return _unpack_fields(words, k, 16).astype(jnp.int32)


def pack_bits_g(plane: jnp.ndarray) -> jnp.ndarray:  # gc: bool[..., G]
    """Pack a bool plane 32:1 along its LAST (group) axis: bool[..., G] ->
    uint32[..., ceil(G/32)], word w's bit j holding group 32*w + j.

    This is the scan-carry form of the `recent_active bool[P, P, G]`
    damping plane (the single largest plane ISSUE 7 added): the donated
    double-buffered runners (`ClusterSim.run_compiled`, the fused-damped
    bench loop) carry the packed words between rounds and unpack only at
    the step boundary, so the per-round carry traffic for the plane drops
    ~32x.  Packing along G (not the plane axis like `pack_bits`) keeps the
    word planes group-minor — the packed lanes stay on the TPU's 128-wide
    vector axis.  Lossless for any G (groups past G pad with zeros);
    `simref.host_pack_bits_g` is the numpy twin
    (tests/test_multiraft_kernels.py)."""
    g = plane.shape[-1]
    n_words = (g + 31) // 32
    pad = n_words * 32 - g
    bits = plane.astype(jnp.uint32)
    if pad:
        bits = jnp.pad(bits, [(0, 0)] * (bits.ndim - 1) + [(0, pad)])
    bits = bits.reshape(plane.shape[:-1] + (n_words, 32))
    lanes = jnp.arange(32, dtype=jnp.uint32)
    # Bits are disjoint, so the shifted sum is a bitwise OR; dtype= keeps
    # the reduction uint32 under x64 (GC007).
    return jnp.sum(bits << lanes, axis=-1, dtype=jnp.uint32)


def unpack_bits_g(words: jnp.ndarray, g: int) -> jnp.ndarray:  # gc: uint32[..., W]
    """Inverse of pack_bits_g: uint32[..., ceil(g/32)] -> bool[..., g]."""
    lanes = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[..., :, None] >> lanes) & jnp.uint32(1)
    flat = bits.reshape(words.shape[:-1] + (words.shape[-1] * 32,))
    return flat[..., :g] != 0


# check_safety violation-count vector indices.
SV_DUAL_LEADER = 0  # two leaders share a term in one group
SV_COMMIT_DIVERGED = 1  # two peers' committed prefixes disagree
SV_COMMIT_REGRESSED = 2  # some peer's commit index decreased
SV_CURSOR_INVALID = 3  # agree/commit cursors exceed log bounds
# Joint-window invariants (ISSUE 10): checked only when the optional mask
# args are given; the slots stay zero otherwise so every accumulator keeps
# one uniform [N_SAFETY] shape.
SV_LEADER_NOT_IN_CONFIG = 4  # a non-follower outside voter|outgoing
SV_COMMIT_NO_QUORUM = 5  # a commit advance lacking either joint majority
SV_CONF_DOUBLE_CHANGE = 6  # an illegal single-step membership transition
# Linearizability slots (ISSUE 13): checked only when the optional
# lease-read args are given (same uniform-shape rule as the joint slots).
# The "holder" is whoever would answer a read locally: a live lease under
# check-quorum lease reads; where no lease exists (lease reads off) a peer
# whose ReadIndex gate passes (sim.read_index_holders at raft-rs's default
# Config, ISSUE 35; sim.read_quorum_damped_holders under check-quorum or
# pre-vote, ISSUE 40).
SV_STALE_READ = 7  # a holder's answer older than a fleet-committed index
SV_DUAL_LEASE = 8  # two holders for one group at once
N_SAFETY = 9

SAFETY_NAMES = (
    "dual_leader",
    "commit_diverged",
    "commit_regressed",
    "cursor_invalid",
    "leader_not_in_config",
    "commit_no_quorum",
    "conf_double_change",
    "stale_read",
    "dual_lease",
)


def lease_read(
    state: jnp.ndarray,  # gc: int32[P, G]
    term: jnp.ndarray,  # gc: int32[P, G]
    leader_id: jnp.ndarray,  # gc: int32[P, G]
    election_elapsed: jnp.ndarray,  # gc: int32[P, G]
    commit: jnp.ndarray,  # gc: int32[P, G]
    term_start_index: jnp.ndarray,  # gc: int32[P, G]
    crashed: jnp.ndarray,  # gc: bool[P, G]
    election_tick: int,
    check_quorum: bool,
    transferee: Optional[jnp.ndarray] = None,  # gc: int32[P, G]
    recent_active: Optional[jnp.ndarray] = None,  # gc: bool[P, P, G]
    voter_mask: Optional[jnp.ndarray] = None,  # gc: bool[P, G]
    outgoing_mask: Optional[jnp.ndarray] = None,  # gc: bool[P, G]
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Batched LeaseBased read gate (reference: read_only.rs LeaseBased +
    raft.rs step_leader MsgReadIndex 2067-2096): which peers could serve a
    linearizable read LOCALLY — zero message rounds — under the
    check-quorum leader lease, and what the group's acting leader would
    answer.

    A peer HOLDS a live read lease when every condition of the hardened
    gate passes:

      * `check_quorum` is on (static; the reference's Config.validate
        rejects LeaseBased without it — without the boundary deposal the
        "lease" is just hope) and the peer is an uncrashed leader whose
        own `leader_id` names itself;
      * its election-elapsed sits inside the lease window
        (`election_elapsed < election_tick`): the check-quorum boundary
        read-and-clears at election_tick, so a role-leader inside the
        window is at most one interval past its last boundary.  (At
        organic round boundaries the tick reset makes this implied for
        alive leaders; it binds exactly in the clock-drift states the
        stale-read trap injects — a paused clock is how raft-rs's own
        docs say LeaseBased breaks.)
      * its CURRENT recent_active row holds an active quorum
        (check_quorum_active over the row accumulated SINCE the last
        boundary clear): every ack in the current row is younger than
        one election_tick, so a quorum of voters is still inside the
        follower-lease window that makes them IGNORE vote requests —
        by quorum intersection no higher-term leader can exist while
        the gate passes.  This is deliberately STRONGER than raft-rs,
        whose LeaseBased trusts the last boundary outcome: a boundary
        can pass on acks up to a full interval old (the pre-partition
        acks straddle the clear), stretching the effective lease to
        2*election_tick while the cut-off majority elects after ~1 —
        tests/test_read_lease.py's no-drift trap replay demonstrates
        exactly that dual-lease window and pins this gate closing it;
      * it has committed in its own term (`commit >= term_start_index` —
        the commit_to_current_term gate that drops every MsgReadIndex in
        the reference);
      * no leader transfer is pending (`transferee == 0` when the plane
        exists): MsgTimeoutNow forces a CAMPAIGN_TRANSFER election that
        BYPASSES leases, so the lease is unsound while a transfer runs —
        the reference serves anyway (a real raft-rs soundness gap); we
        degrade to the ReadIndex quorum round instead, and
        simref.ReadOracle applies the identical host-side gate before
        choosing which scalar pump to drive.

    Returns (holder bool[P, G], served bool[G], index int32[G]): the full
    holder mask (the SV_DUAL_LEASE surface — at most one holder per group
    on every reachable state), whether the group's ACTING leader (alive
    max-term, lowest peer index — where the sim routes client reads) is a
    holder, and the commit index it would serve (0 where not served; the
    caller masks on `served`).  Pure — a probe, like read_index.
    """
    P = state.shape[0]
    if not check_quorum:
        # The static no-lease arm: shapes preserved, gate constant-false
        # (the undamped configuration degrades every lease request).
        G = state.shape[1]
        return (
            jnp.zeros((P, G), bool),
            jnp.zeros((G,), bool),
            jnp.zeros((G,), jnp.int32),
        )
    if recent_active is None or voter_mask is None or outgoing_mask is None:
        raise ValueError(
            "the check-quorum lease gate needs recent_active, voter_mask "
            "and outgoing_mask (the ISSUE 7 damping planes)"
        )
    self_id = jnp.arange(P, dtype=jnp.int32)[:, None] + 1
    holder = (
        (state == ROLE_LEADER)
        & ~crashed
        & (leader_id == self_id)
        & (election_elapsed < jnp.int32(election_tick))
        & (commit >= term_start_index)
        & check_quorum_active(recent_active, voter_mask, outgoing_mask)
    )
    if transferee is not None:
        holder = holder & (transferee == 0)
    # The acting leader — where a client's read lands — is THE
    # acting_leader_id rule (alive max-term leader, lowest index on the
    # tie; 0 = none, which no self_id matches).
    is_acting = self_id == acting_leader_id(state, term, crashed)[None, :]
    served = jnp.any(is_acting & holder, axis=0)
    # dtype= keeps the served plane int32 under x64 (GC007).
    index = jnp.sum(
        jnp.where(is_acting & holder, commit, 0), axis=0, dtype=jnp.int32
    )
    return holder, served, index


@functools.lru_cache(maxsize=None)
def _combiner(ops):
    """(a, b) -> (ops[k](a[k], b[k]) ...), ONE function object per tuple of
    ops: lax.reduce caches its traced computation by the function's
    identity, and an eager caller (the CPU parity suites) would otherwise
    compile every reduction anew on every call."""
    return lambda a, b: tuple(op(x, y) for op, x, y in zip(ops, a, b))


def _reduce_each(operands, ops, axis: int, inits=None):
    """ONE variadic `lax.reduce` over `axis`: operand k folded by ops[k]
    from inits[k] (0 where not given).  A reduction is a kernel on the TPU
    and XLA fuses no reduce into a reduce, so values that leave the same
    axis leave it together."""
    return jax.lax.reduce(
        tuple(operands),
        tuple(
            jnp.int32(0 if inits is None else inits[k])
            for k in range(len(operands))
        ),
        _combiner(tuple(ops)),
        (axis,),
    )


def _pack(planes, width: int) -> jnp.ndarray:
    """bool planes -> one int32 word, plane k in the `width` bits from
    k * width.  astype and shifts by Python ints stay int32 under x64
    (GC007)."""
    return functools.reduce(
        jnp.bitwise_or,
        (p.astype(jnp.int32) << (width * k) for k, p in enumerate(planes)),
    )


def _safety_flags(
    state: jnp.ndarray,  # gc: int32[P, G]
    term: jnp.ndarray,  # gc: int32[P, G]
    commit: jnp.ndarray,  # gc: int32[P, G]
    last_index: jnp.ndarray,  # gc: int32[P, G]
    agree: jnp.ndarray,  # gc: int32[P, P, G]
    prev_commit: jnp.ndarray,  # gc: int32[P, G]
    voter_mask: Optional[jnp.ndarray],  # gc: bool[P, G]
    outgoing_mask: Optional[jnp.ndarray],  # gc: bool[P, G]
    matched: Optional[jnp.ndarray],  # gc: int32[P, P, G]
    crashed: Optional[jnp.ndarray],  # gc: bool[P, G]
    prev_voter_mask: Optional[jnp.ndarray],  # gc: bool[P, G]
    prev_outgoing_mask: Optional[jnp.ndarray],  # gc: bool[P, G]
    lease_holder: Optional[jnp.ndarray],  # gc: bool[P, G]
    lease_fire: Optional[jnp.ndarray],  # gc: bool[G]
) -> Tuple[Optional[jnp.ndarray], ...]:
    """The audit's nine per-group violation flags in SV_* order, each a
    bool[G], or None where the caller's arguments leave the slot inactive:
    the core under _check_safety_packed (which counts them) and
    check_safety_groups (which hands them on).  The invariants are in
    check_safety's docstring, the reasons for the form in
    _check_safety_packed's."""
    if voter_mask is not None and (outgoing_mask is None or matched is None):
        raise ValueError(
            "joint-window checks need voter_mask, outgoing_mask AND "
            "matched together"
        )
    if prev_voter_mask is not None and (
        voter_mask is None or prev_outgoing_mask is None
    ):
        raise ValueError(
            "the double-change check needs prev AND current masks"
        )
    if lease_fire is not None and lease_holder is None:
        raise ValueError(
            "the stale-read check needs lease_holder alongside lease_fire"
        )
    P = state.shape[0]
    if P > 0xFF:
        raise ValueError("the audit counts peers in a byte: n_peers <= 255")
    # Finished planes: behind the barrier no slot's arithmetic fuses into the
    # round's producers (None passes through: an empty pytree node).
    (
        state, term, commit, last_index, agree, prev_commit, voter_mask,
        outgoing_mask, matched, crashed, prev_voter_mask,
        prev_outgoing_mask, lease_holder, lease_fire,
    ) = jax.lax.optimization_barrier((
        state, term, commit, last_index, agree, prev_commit, voter_mask,
        outgoing_mask, matched, crashed, prev_voter_mask,
        prev_outgoing_mask, lease_holder, lease_fire,
    ))
    joint = voter_mask is not None

    # The pairwise [P, P, G] facts, a bit each of one word: any() over both
    # peer axes follows from a fold over either and the word reduce below,
    # so under the joint arguments they leave axis 1 WITH the ack count —
    # one kernel reads `agree` and `matched` together — and alone (the
    # six-argument call) the major axis, plane-wise on the TPU.
    off_diag = ~jnp.eye(P, dtype=bool)[:, :, None]
    is_lead = state == ROLE_LEADER
    cmin = jnp.minimum(commit[:, None, :], commit[None, :, :])
    lmin = jnp.minimum(last_index[:, None, :], last_index[None, :, :])
    pair = _pack(
        (
            is_lead[:, None, :]
            & is_lead[None, :, :]
            & (term[:, None, :] == term[None, :, :]),
            cmin > agree,
            agree > lmin,
        ),
        1,
    )
    pair = jnp.where(off_diag, pair, 0)
    if not joint:
        (pair,) = _reduce_each([pair], [jnp.bitwise_or], 0)
    else:
        # Per-owner joint commit bound off each leader's own tracker row
        # (reference: joint.rs:47-51 min over both majorities), as a COUNT:
        # commit exceeds the majority()-th largest acked index of a config
        # (committed_index, zero padding and all) iff the peers whose padded
        # matched reaches commit number at most half its members.  No sort
        # network, no owner-major copy of `matched`, no mask rows: one
        # reduce over the TARGET axis of [P_owner, P_target, G], which the
        # pair word rides.
        with profiling.scope("quorum_commit"):
            c3 = commit[:, None, :]
            one, two = jnp.int32(1), jnp.int32(2)

            def short(mask):
                # 2 * acked - member + 1 per target, in {0, 1, 2, 3}; the
                # select on the broadcast mask between two full-rank values
                # leaves XLA no [P_target, G] subexpression to hoist into
                # a kernel of its own.
                m = mask[None, :, :]
                acked = jnp.where(
                    jnp.where(m, matched, 0) >= c3, two, jnp.int32(0)
                )
                return jnp.where(m, acked, acked + one)

            acked = short(voter_mask) | (short(outgoing_mask) << 16)
        # The reduce itself is the audit's, not a quorum commit: it carries
        # the pair word too, and the kernel takes the reduce's name.
        pair, acks = _reduce_each(
            [pair, acked], [jnp.bitwise_or, jnp.add], 1
        )  # int32[P_owner, G]: a sum <= P iff 2 * acked <= members
    # Per-peer facts: one bit each of an int32[P, G] word, or-ed over the
    # peers below; per-peer counts: one byte each of a second word, summed.
    bits = {
        "dual": pair & 1 != 0,
        "diverged": pair & 2 != 0,
        "regressed": commit < prev_commit,
        "invalid": (pair & 4 != 0) | (commit > last_index),
    }
    counts = {}
    prev_high = None
    if joint:
        bits["outside"] = (state != ROLE_FOLLOWER) & ~(
            voter_mask | outgoing_mask
        )
        alive = ~crashed if crashed is not None else jnp.ones_like(is_lead)
        # Checked set: every crashed leader (isolation means it cannot
        # learn, so its commit is its own quorum's work) plus the
        # max-term alive leaders (a stale lower-term alive leader can
        # LEARN a settled commit via the propagation approximation).
        low = jnp.iinfo(jnp.int32).min
        prev_high, max_alive_term = _reduce_each(
            [prev_commit, jnp.where(is_lead & alive, term, -1)],
            [jnp.maximum, jnp.maximum], 0, inits=[low, low],
        )  # int32[G] each
        checked = is_lead & (~alive | (term == max_alive_term[None, :]))
        advanced = checked & (commit > prev_high[None, :])
        # acks: short of a quorum of a config that HAS members (an empty one
        # bounds nothing: committed_index's INF) — that is a per-group
        # fact, and-ed in after the reduce.
        bits["short_of_voters"] = advanced & ((acks & 0xFFFF) <= P)
        bits["short_of_outgoing"] = advanced & ((acks >> 16) <= P)
        bits["has_voters"] = voter_mask
        bits["has_outgoing"] = outgoing_mask
    elif lease_fire is not None:
        prev_high = jnp.max(prev_commit, axis=0)
    if prev_voter_mask is not None:
        bits["was_joint"] = prev_outgoing_mask
        bits["outgoing_moved"] = prev_outgoing_mask ^ outgoing_mask
        bits["entered_other"] = outgoing_mask ^ prev_voter_mask
        counts["voters_moved"] = prev_voter_mask ^ voter_mask
    if lease_holder is not None:
        counts["holders"] = lease_holder
        if lease_fire is not None:
            # prev_high is the fleet's high-water mark at serve time
            bits["stale"] = lease_holder & (prev_commit < prev_high[None, :])

    # ONE reduce over the peer axis: the bit word or-ed, the count word
    # summed.
    words, ops = [_pack(bits.values(), 1)], [jnp.bitwise_or]
    # `if counts:`, spelled off the arguments (GC003 cannot see a dict's
    # truth is static)
    if prev_voter_mask is not None or lease_holder is not None:
        words.append(_pack(counts.values(), 8))
        ops.append(jnp.add)
    folded = _reduce_each(words, ops, 0)  # int32[G] each
    any_of = {
        name: (folded[0] >> k) & 1 != 0 for k, name in enumerate(bits)
    }
    count_of = {
        name: (folded[1] >> (8 * k)) & 0xFF for k, name in enumerate(counts)
    }

    outside = unbacked = double = stale = dual_lease = None
    if joint:
        outside = any_of["outside"]
        unbacked = (any_of["short_of_voters"] & any_of["has_voters"]) | (
            any_of["short_of_outgoing"] & any_of["has_outgoing"]
        )
    if prev_voter_mask is not None:
        was_j, now_j = any_of["was_joint"], any_of["has_outgoing"]
        vm_delta = count_of["voters_moved"]
        enter_bad = (~was_j & now_j) & any_of["entered_other"]
        leave_bad = (was_j & ~now_j) & (vm_delta > 0)
        stay_bad = (was_j & now_j) & (
            (vm_delta > 0) | any_of["outgoing_moved"]
        )
        simple_bad = (~was_j & ~now_j) & (vm_delta > 1)
        double = enter_bad | leave_bad | stay_bad | simple_bad
    if lease_holder is not None:
        dual_lease = count_of["holders"] >= 2
        if lease_fire is not None:
            stale = lease_fire & any_of["stale"]
    return (
        any_of["dual"], any_of["diverged"], any_of["regressed"],
        any_of["invalid"], outside, unbacked, double, stale, dual_lease,
    )


@profiling.scope("safety_audit")
def check_safety(
    state: jnp.ndarray,  # gc: int32[P, G]
    term: jnp.ndarray,  # gc: int32[P, G]
    commit: jnp.ndarray,  # gc: int32[P, G]
    last_index: jnp.ndarray,  # gc: int32[P, G]
    agree: jnp.ndarray,  # gc: int32[P, P, G]
    prev_commit: jnp.ndarray,  # gc: int32[P, G]
    voter_mask: Optional[jnp.ndarray] = None,  # gc: bool[P, G]
    outgoing_mask: Optional[jnp.ndarray] = None,  # gc: bool[P, G]
    matched: Optional[jnp.ndarray] = None,  # gc: int32[P, P, G]
    crashed: Optional[jnp.ndarray] = None,  # gc: bool[P, G]
    prev_voter_mask: Optional[jnp.ndarray] = None,  # gc: bool[P, G]
    prev_outgoing_mask: Optional[jnp.ndarray] = None,  # gc: bool[P, G]
    lease_holder: Optional[jnp.ndarray] = None,  # gc: bool[P, G]
    lease_fire: Optional[jnp.ndarray] = None,  # gc: bool[G]
) -> jnp.ndarray:
    """Device-side Raft safety invariants over one round boundary.

    Returns int32[N_SAFETY] counts of violating groups (SV_* indices) —
    all-zero on every reachable state:

      * election safety: at most one leader per (group, term);
      * log matching at commit: any two peers' committed prefixes agree
        (min(commit_a, commit_b) <= agree[a, b] — index+term identify
        entries, so a shorter common prefix than either commit is a lost
        committed entry);
      * commit monotonicity: no peer's commit index decreases;
      * cursor sanity: commit <= last_index and
        agree[a, b] <= min(last_a, last_b).

    Joint-window invariants (the historical reconfig-bug territory; active
    only when `voter_mask`/`outgoing_mask`/`matched` are given, so legacy
    callers keep their graphs — the extra slots just stay zero):

      * election safety under dual majorities: any peer acting above
        follower must sit in at least one half of the (possibly joint)
        config — a demoted leader/candidate that failed to step down is
        exactly how a removed node keeps committing
        (SV_LEADER_NOT_IN_CONFIG; the per-term dual-leader check above
        already covers the joint window since joint elections still
        produce at most one winner per term);
      * no commit that lacks either majority: a leader's commit may only
        advance past the round's starting high-water mark when its OWN
        tracker rows reach that index under BOTH majorities
        (quorum/joint.rs min-of-halves, SV_COMMIT_NO_QUORUM).  Stale
        lower-term alive leaders are exempt: the commit-propagation
        approximation lets them LEARN a settled commit without deposing
        them, which is learning, not committing (`crashed` marks the
        peers whose isolation makes the exemption unnecessary);
      * no single-step double-membership change (SV_CONF_DOUBLE_CHANGE,
        needs `prev_voter_mask`/`prev_outgoing_mask`): outside joint at
        most one voter may change per transition; entering joint must set
        outgoing to exactly the old incoming; leaving must clear outgoing
        with incoming untouched; while joint the masks must not move.

    Linearizability slots (ISSUE 13; active only when the lease-read args
    are given — the classic stale-read-under-partition trap of
    leader-lease reads, machine-checked every round of the workload
    scan):

      * no stale lease read (SV_STALE_READ, needs `lease_holder` AND
        `lease_fire`): in a round where a LeaseBased read fired, no peer
        holding a live lease (kernels.lease_read's holder mask, computed
        on the serve-time = round-entry state) may answer with a commit
        index older than ANY index committed fleet-wide at serve time —
        `prev_commit` here is exactly the round-entry commit plane, so a
        holder with prev_commit[p] < max_p(prev_commit) would hand a
        client a linearizability violation (a deposed-but-unaware leader
        serving across a partition while the new majority committed);
      * at most one live lease per group (SV_DUAL_LEASE, needs
        `lease_holder`): two simultaneous holders means two leaders
        would BOTH serve local reads for the same group this round —
        unreachable without clock drift because the check-quorum
        boundary deposes a contactless leader before the other side's
        lease-expiry election can finish; the injected clock-pause trap
        is exactly what makes it fire.

    The chaos/reconfig fuzz harnesses fold these counts into the compiled
    schedule scan every round and assert the run total is zero.

    Form (ISSUE 52): two, chosen by the size of the pairwise planes, which
    is what decides what bounds the audit on the chip.
    `_check_safety_packed` — where `agree` is _AUDIT_PACKED_MIN_BYTES or
    more (36 MB at 1M x 3) the audit is bound by the bytes it reads: written
    a slot at a time it was 22 kernels and 7% of a round there, packed it
    is six and 1.8% (+5.6% group-rounds/s).  `_check_safety_by_slot` —
    below that (10 MB at 100k x 5) six kernels and twenty-two take the same
    0.22 ms alone, the form has nothing to gain, and every packed variant
    that was timed moved the compiler's layout of the round AROUND the
    audit by -15% .. +5% of a cell's rate, none of them inside the 2% bound
    in both `fleet-100k-r5-stock.outage` and `fleet-100k-r5.serve`: those
    fleets keep the program they had.  PERF.md section 6, PR 52 has the
    readings; between 10 and 36 MB nobody has measured.
    """
    if 4 * agree.size >= _AUDIT_PACKED_MIN_BYTES:  # graftcheck: allow-no-python-branch-on-traced — a SHAPE (trace-time static), not a value
        return _check_safety_packed(
            state, term, commit, last_index, agree, prev_commit, voter_mask,
            outgoing_mask, matched, crashed, prev_voter_mask,
            prev_outgoing_mask, lease_holder, lease_fire,
        )
    return _check_safety_by_slot(
        state, term, commit, last_index, agree, prev_commit, voter_mask,
        outgoing_mask, matched, crashed, prev_voter_mask,
        prev_outgoing_mask, lease_holder, lease_fire,
    )


# int32 bytes of the pairwise [P, P, G] plane from which check_safety packs
# its slots: between the two sizes that have a chip reading (PERF.md
# section 6, PR 52), 10 MB (100k x 5, by slot) and 36 MB (1M x 3, packed).
_AUDIT_PACKED_MIN_BYTES = 1 << 24


def _check_safety_packed(
    state, term, commit, last_index, agree, prev_commit, voter_mask,
    outgoing_mask, matched, crashed, prev_voter_mask,
    prev_outgoing_mask, lease_holder, lease_fire,
):
    """check_safety where the audit is bound by the bytes it reads.  On the
    TPU a reduction is a kernel, and XLA fuses no reduce into a reduce.  So
    (`_safety_flags`): (1) every argument passes ONE optimization_barrier —
    the audit reads FINISHED planes in kernels of its own, as
    chaos.fold_learner_lag does (ledger PR 47: 2.87% fused into the round's
    producers, 0.67% behind a barrier as four reductions, 0.43% as one);
    (2) the pairwise [P, P, G] facts are bits of one word, the joint commit
    bound is a count of acknowledgements instead of two k-th-largest
    networks (`commit > k-th largest` iff `acked(>= commit) <= members //
    2`), and both leave the target axis in ONE variadic reduce (or, add):
    `agree` and `matched` are read once, by one kernel; (3) every per-peer
    fact is a bit, every per-peer count a byte, of two int32[P, G] words
    that leave the peer axis in ONE variadic reduce (or, add); (4) the
    per-group logic reads bits of those two rows and the active slots'
    counts leave G in ONE variadic reduce.  Four reductions for
    twenty-five; bit-equal to `_check_safety_by_slot` on every int32 state,
    reachable or not (tests/test_safety_audit_form.py holds both to a plain
    per-group reference and pins this form)."""
    flags = _safety_flags(
        state, term, commit, last_index, agree, prev_commit, voter_mask,
        outgoing_mask, matched, crashed, prev_voter_mask,
        prev_outgoing_mask, lease_holder, lease_fire,
    )
    active = [f for f in flags if f is not None]
    # ONE reduce over G for every active slot's count; astype keeps the
    # counts int32 under x64 (GC007) — they feed an int32 scan accumulator.
    sums = iter(
        _reduce_each(
            [f.astype(jnp.int32) for f in active], [jnp.add] * len(active), 0
        )
    )
    return jnp.stack(
        [jnp.int32(0) if f is None else next(sums) for f in flags]
    )


def _check_safety_by_slot(
    state, term, commit, last_index, agree, prev_commit, voter_mask,
    outgoing_mask, matched, crashed, prev_voter_mask,
    prev_outgoing_mask, lease_holder, lease_fire,
):
    """check_safety a slot at a time — sixteen reductions over the peers,
    nine sums over G, two quorum networks (under `quorum_commit`) on an
    owner-major `matched`: the audit as every fleet ran it until ISSUE 52,
    and as a fleet whose planes are small still does (check_safety says
    why).  Not to be tidied toward the packed form: at these sizes what a
    form of the audit costs is what the compiler lays out around it."""
    P = state.shape[0]
    off_diag = ~jnp.eye(P, dtype=bool)[:, :, None]
    is_lead = state == ROLE_LEADER
    dual = (
        is_lead[:, None, :]
        & is_lead[None, :, :]
        & (term[:, None, :] == term[None, :, :])
        & off_diag
    )
    cmin = jnp.minimum(commit[:, None, :], commit[None, :, :])
    diverged = (cmin > agree) & off_diag
    regressed = commit < prev_commit
    lmin = jnp.minimum(last_index[:, None, :], last_index[None, :, :])
    invalid = ((agree > lmin) & off_diag) | (commit > last_index)[:, None, :]
    zero = jnp.int32(0)
    if voter_mask is not None:
        if outgoing_mask is None or matched is None:
            raise ValueError(
                "joint-window checks need voter_mask, outgoing_mask AND "
                "matched together"
            )
        non_follower = state != ROLE_FOLLOWER
        outside = non_follower & ~(voter_mask | outgoing_mask)
        # dtype= on the counts: bare bool sums widen to int64 under x64
        # (GC007) and these feed an int32 scan accumulator.
        sv_outside = jnp.sum(jnp.any(outside, axis=0), dtype=jnp.int32)
        alive = (
            ~crashed if crashed is not None else jnp.ones_like(is_lead)
        )
        # Checked set: every crashed leader (isolation means it cannot
        # learn, so its commit is its own quorum's work) plus the
        # max-term alive leaders (a stale lower-term alive leader can
        # LEARN a settled commit via the propagation approximation).
        lead_alive = is_lead & alive
        max_alive_term = jnp.max(jnp.where(lead_alive, term, -1), axis=0)
        checked = is_lead & (~alive | (term == max_alive_term[None, :]))
        # Per-owner joint commit bound off each leader's own tracker row
        # (reference: joint.rs:47-51 min over both majorities).
        owner_rows = jnp.swapaxes(matched, 1, 2)  # [P_owner, G, P_target]
        mci = jnp.minimum(
            committed_index(
                owner_rows,
                jnp.broadcast_to(
                    jnp.swapaxes(voter_mask, 0, 1)[None, :, :],
                    owner_rows.shape,
                ),
            ),
            committed_index(
                owner_rows,
                jnp.broadcast_to(
                    jnp.swapaxes(outgoing_mask, 0, 1)[None, :, :],
                    owner_rows.shape,
                ),
            ),
        )  # [P_owner, G]
        prev_high = jnp.max(prev_commit, axis=0)  # [G]
        unbacked = (
            checked & (commit > prev_high[None, :]) & (commit > mci)
        )
        sv_unbacked = jnp.sum(jnp.any(unbacked, axis=0), dtype=jnp.int32)
    else:
        sv_outside = zero
        sv_unbacked = zero
    if prev_voter_mask is not None:
        if voter_mask is None or prev_outgoing_mask is None:
            raise ValueError(
                "the double-change check needs prev AND current masks"
            )
        was_j = jnp.any(prev_outgoing_mask, axis=0)
        now_j = jnp.any(outgoing_mask, axis=0)
        vm_delta = jnp.sum(
            prev_voter_mask ^ voter_mask, axis=0, dtype=jnp.int32
        )
        om_moved = jnp.any(prev_outgoing_mask ^ outgoing_mask, axis=0)
        enter_bad = (~was_j & now_j) & jnp.any(
            outgoing_mask ^ prev_voter_mask, axis=0
        )
        leave_bad = (was_j & ~now_j) & (vm_delta > 0)
        stay_bad = (was_j & now_j) & ((vm_delta > 0) | om_moved)
        simple_bad = (~was_j & ~now_j) & (vm_delta > 1)
        sv_double = jnp.sum(
            enter_bad | leave_bad | stay_bad | simple_bad,
            dtype=jnp.int32,
        )
    else:
        sv_double = zero
    if lease_holder is not None:
        # dtype= on the counts: GC007 (bare bool sums widen under x64).
        sv_dual_lease = jnp.sum(
            jnp.sum(lease_holder, axis=0, dtype=jnp.int32) >= 2,
            dtype=jnp.int32,
        )
        if lease_fire is not None:
            fleet_high = jnp.max(prev_commit, axis=0)  # [G] at serve time
            stale = lease_holder & (prev_commit < fleet_high[None, :])
            sv_stale = jnp.sum(
                lease_fire & jnp.any(stale, axis=0), dtype=jnp.int32
            )
        else:
            sv_stale = zero
    else:
        if lease_fire is not None:
            raise ValueError(
                "the stale-read check needs lease_holder alongside "
                "lease_fire"
            )
        sv_dual_lease = zero
        sv_stale = zero
    # dtype= on the group counts: a bare bool sum widens to int64 under x64
    # (GC007), and these feed an int32 scan accumulator.
    return jnp.stack(
        [
            jnp.sum(jnp.any(dual, axis=(0, 1)), dtype=jnp.int32),
            jnp.sum(jnp.any(diverged, axis=(0, 1)), dtype=jnp.int32),
            jnp.sum(jnp.any(regressed, axis=0), dtype=jnp.int32),
            jnp.sum(jnp.any(invalid, axis=(0, 1)), dtype=jnp.int32),
            sv_outside,
            sv_unbacked,
            sv_double,
            sv_stale,
            sv_dual_lease,
        ]
    )


def check_safety_groups(
    state: jnp.ndarray,  # gc: int32[P, G]
    term: jnp.ndarray,  # gc: int32[P, G]
    commit: jnp.ndarray,  # gc: int32[P, G]
    last_index: jnp.ndarray,  # gc: int32[P, G]
    agree: jnp.ndarray,  # gc: int32[P, P, G]
    prev_commit: jnp.ndarray,  # gc: int32[P, G]
    voter_mask: Optional[jnp.ndarray] = None,  # gc: bool[P, G]
    outgoing_mask: Optional[jnp.ndarray] = None,  # gc: bool[P, G]
    matched: Optional[jnp.ndarray] = None,  # gc: int32[P, P, G]
    crashed: Optional[jnp.ndarray] = None,  # gc: bool[P, G]
    prev_voter_mask: Optional[jnp.ndarray] = None,  # gc: bool[P, G]
    prev_outgoing_mask: Optional[jnp.ndarray] = None,  # gc: bool[P, G]
    lease_holder: Optional[jnp.ndarray] = None,  # gc: bool[P, G]
    lease_fire: Optional[jnp.ndarray] = None,  # gc: bool[G]
) -> jnp.ndarray:
    """The per-GROUP form of `check_safety` (ISSUE 15): the identical
    invariants over the identical optional-argument matrix, returning the
    bool[N_SAFETY, G] violation indicators INSTEAD of their group sums —
    the black-box trigger surface, which needs to know WHICH groups
    tripped, not just how many.

    It is `_safety_flags` — the packed core, at every fleet size: no cell
    turns the black box on, so no size has a reading to choose by — with
    its nine rows stacked (an inactive slot a row of False);
    `_check_safety_packed` counts the same rows.  tests/test_forensics.py
    still asserts
    `check_safety_groups(...).sum(axis=-1) == check_safety(...)`
    slot-for-slot on fuzzed, joint, leased, and trapped states; what holds
    the shared core to the invariants is the plain per-group reference of
    tests/test_safety_audit_form.py.
    """
    flags = _safety_flags(
        state, term, commit, last_index, agree, prev_commit, voter_mask,
        outgoing_mask, matched, crashed, prev_voter_mask,
        prev_outgoing_mask, lease_holder, lease_fire,
    )
    zero_g = jnp.zeros((state.shape[1],), bool)
    return jnp.stack([zero_g if f is None else f for f in flags])


def apply_confchange(
    state: jnp.ndarray,  # gc: int32[P, G]
    leader_id: jnp.ndarray,  # gc: int32[P, G]
    commit: jnp.ndarray,  # gc: int32[P, G]
    term_start_index: jnp.ndarray,  # gc: int32[P, G]
    matched: jnp.ndarray,  # gc: int32[P, P, G]
    voter_mask: jnp.ndarray,  # gc: bool[P, G]
    outgoing_mask: jnp.ndarray,  # gc: bool[P, G]
    learner_mask: jnp.ndarray,  # gc: bool[P, G]
    new_voter: jnp.ndarray,  # gc: bool[P, G]
    new_outgoing: jnp.ndarray,  # gc: bool[P, G]
    new_learner: jnp.ndarray,  # gc: bool[P, G]
    added: jnp.ndarray,  # gc: bool[P, G]
    removed: jnp.ndarray,  # gc: bool[P, G]
    apply_mask: jnp.ndarray,  # gc: bool[G]
    recent_active: Optional[jnp.ndarray] = None,  # gc: bool[P, P, G]
    transferee: Optional[jnp.ndarray] = None,  # gc: int32[P, G]
) -> Tuple[
    jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray,
    jnp.ndarray, jnp.ndarray, Optional[jnp.ndarray], Optional[jnp.ndarray],
]:
    """Commit one validated conf change per selected group: swap the
    config mask planes and run the reference's apply-time reactions
    (reference: confchange/changer.rs for the transition shapes —
    validated host-side by `reconfig.compile_plan` driving the scalar
    `confchange.Changer` — and raft.rs:2604-2673 `post_conf_change` for
    the reactions).

    new_voter/new_outgoing/new_learner are the PRE-VALIDATED target masks
    of the op being applied (joint-entry targets carry outgoing = the old
    incoming config; joint-exit targets carry outgoing all-False with
    staged learners_next materialized).  `added`/`removed` are the member
    deltas (member = voter|outgoing|learner): like the reference's
    progress-map changes, an added member gets a FRESH tracker row —
    matched zeroed across every owner, recent_active granted (the
    added-node grace of Changer's Progress::new) — and a removed member's
    rows are cleared so a later re-add starts fresh.

    Apply-time reactions, exactly mirrored by `simref.ReconfigOracle`'s
    scalar surgery:

      * leader-step-down when the leader leaves the config: any peer
        acting above follower that lands outside voter|outgoing becomes a
        follower with leader_id cleared (the ISSUE rule; the reference's
        post_conf_change early-returns for a removed leader);
      * quorum-shrink commit pickup (post_conf_change's maybe_commit): a
        surviving leader re-evaluates its joint commit bound under the
        NEW masks — a joint-exit can commit entries that lacked the
        outgoing majority — still gated on the leader's own term
        (term_start_index, raft_log.maybe_commit's check).  No broadcast
        happens here: the round's ordinary traffic propagates it.

    Returns (state', leader_id', commit', matched', voter', outgoing',
    learner', recent_active', transferee'); recent_active/transferee pass
    through as None when absent so the legacy pytrees are unchanged.
    `transferee` (the optional lead_transferee plane, SimConfig.transfer)
    gets the reference's post_conf_change abort (raft.rs:1356): a pending
    transfer whose target leaves the joint voter set — or whose owner is
    stepped down by the change — is abandoned.
    """
    ap = apply_mask[None, :]  # [1, G]
    vm = jnp.where(ap, new_voter, voter_mask)
    om = jnp.where(ap, new_outgoing, outgoing_mask)
    lm = jnp.where(ap, new_learner, learner_mask)
    delta_t = (added | removed)[None, :, :]  # target axis
    matched2 = jnp.where(apply_mask[None, None, :] & delta_t, 0, matched)
    if recent_active is not None:
        ra = jnp.where(
            apply_mask[None, None, :] & added[None, :, :],
            True,
            jnp.where(
                apply_mask[None, None, :] & removed[None, :, :],
                False,
                recent_active,
            ),
        )
    else:
        ra = None
    step_down = ap & (state != ROLE_FOLLOWER) & ~(vm | om)
    state2 = jnp.where(step_down, ROLE_FOLLOWER, state)
    leader2 = jnp.where(step_down, 0, leader_id)
    # Quorum-shrink pickup off each surviving leader's own tracker rows
    # (joint.rs:47-51 min over both majorities under the NEW masks).
    owner_rows = jnp.swapaxes(matched2, 1, 2)  # [P_owner, G, P_target]
    mci = jnp.minimum(
        committed_index(
            owner_rows,
            jnp.broadcast_to(
                jnp.swapaxes(vm, 0, 1)[None, :, :], owner_rows.shape
            ),
        ),
        committed_index(
            owner_rows,
            jnp.broadcast_to(
                jnp.swapaxes(om, 0, 1)[None, :, :], owner_rows.shape
            ),
        ),
    )  # [P_owner, G]
    pickup = (
        ap
        & (state2 == ROLE_LEADER)
        & (mci >= term_start_index)
        & (mci < INF)
    )
    commit2 = jnp.where(pickup, jnp.maximum(commit, mci), commit)
    if transferee is not None:
        # post_conf_change's transfer abort (reference: raft.rs:1356):
        # the pending target must remain in the joint voter set, and the
        # owner must survive the change as leader.
        P = transferee.shape[0]
        joint_v = vm | om
        tgt_in = select_row(joint_v, jnp.clip(transferee - 1, 0, P - 1))
        tr = jnp.where(
            ap & ((transferee > 0) & ~tgt_in | step_down), 0, transferee
        )
    else:
        tr = None
    return state2, leader2, commit2, matched2, vm, om, lm, ra, tr


def apply_transfer(
    transferee: jnp.ndarray,  # gc: int32[P, G]
    election_elapsed: jnp.ndarray,  # gc: int32[P, G]
    acting_leader: jnp.ndarray,  # gc: bool[P, G]
    propose: jnp.ndarray,  # gc: int32[G]
    member_mask: jnp.ndarray,  # gc: bool[P, G]
    learner_mask: jnp.ndarray,  # gc: bool[P, G]
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Batched leader-side MsgTransferLeader step (reference:
    raft.rs:1821-1889 handle_transfer_leader), applied at each group's
    acting leader.

    propose[g] is the round's transfer command: the 1-based target peer id
    (0 = none).  The reference's validation runs per group: the target
    must be in the progress map (a member), must not be a learner, and
    must not be the leader itself; a pending transfer to the SAME target
    is left untouched (the retry pump nudges it), while a pending
    transfer to a DIFFERENT target is aborted and replaced.  An accepted
    command records the target in the leader's lead_transferee slot
    (`transferee[leader, g]`) and resets the leader's election_elapsed —
    the reference's "transfer should finish within one election timeout"
    clock, whose expiry aborts the transfer at tick time.

    What handle_transfer_leader QUEUES (the catch-up append when the
    target lags, MsgTimeoutNow when it is caught up) is the caller's pump
    — sim._transfer_phase models it round-by-round.

    Returns (transferee', election_elapsed', accepted) with accepted
    bool[G] marking groups whose command was newly recorded this round.
    """
    P = transferee.shape[0]
    tgt = jnp.clip(propose - 1, 0, P - 1)[None, :]  # [1, G], 0-safe
    tgt_member = jnp.take_along_axis(member_mask, tgt, axis=0)[0]
    tgt_learner = jnp.take_along_axis(learner_mask, tgt, axis=0)[0]
    # The acting leader's peer id and current lead_transferee, per group.
    p_id = jnp.arange(P, dtype=jnp.int32)[:, None] + 1
    lead_id = jnp.sum(
        jnp.where(acting_leader, p_id, 0), axis=0, dtype=jnp.int32
    )  # [G]
    cur = jnp.sum(
        jnp.where(acting_leader, transferee, 0), axis=0, dtype=jnp.int32
    )  # [G]
    checked = (propose > 0) & (lead_id > 0) & tgt_member & ~tgt_learner
    accepted = checked & (propose != lead_id) & (propose != cur)
    # Reference ordering quirk: a (member-valid) command naming the leader
    # ITSELF aborts a pending transfer to another peer before the self
    # check returns (the abort sits above it in handle_transfer_leader).
    self_abort = checked & (propose == lead_id) & (cur > 0)
    set_here = acting_leader & accepted[None, :]
    transferee2 = jnp.where(
        acting_leader & self_abort[None, :], 0, transferee
    )
    transferee2 = jnp.where(set_here, propose[None, :], transferee2)
    ee2 = jnp.where(set_here, 0, election_elapsed)
    return transferee2, ee2, accepted


def acting_leader_id(
    state: jnp.ndarray,  # gc: int32[P, G]
    term: jnp.ndarray,  # gc: int32[P, G]
    crashed: jnp.ndarray,  # gc: bool[P, G]
) -> jnp.ndarray:
    """Per-group acting-leader peer id (1-based; 0 = no alive leader) —
    the alive leader with the highest term, lowest peer index on the
    (transient) tie, exactly ScalarCluster.acting_leader.  The autopilot's
    leader-placement read: reduced on device, downloaded as one int32[G]
    row at the drain cadence, never in the hot loop."""
    P = state.shape[0]
    is_lead = (state == ROLE_LEADER) & ~crashed
    lead_term = jnp.max(jnp.where(is_lead, term, -1), axis=0)  # [G]
    acting = is_lead & (term == lead_term[None, :])
    p_idx = jnp.arange(P, dtype=jnp.int32)[:, None]
    first = jnp.min(jnp.where(acting, p_idx, P), axis=0)  # [G]
    return jnp.where(jnp.any(is_lead, axis=0), first + 1, 0)


def check_quorum_active(
    recent_active: jnp.ndarray,  # gc: bool[P, P, G]
    voter_mask: jnp.ndarray,  # gc: bool[P, G]
    outgoing_mask: jnp.ndarray,  # gc: bool[P, G]
) -> jnp.ndarray:
    """Per-owner check-quorum liveness over the recent_active rows
    (reference: tracker.rs:346-372, quorum_recently_active).

    recent_active[owner, target, g] is the owner's Progress.recent_active
    flag for `target` (set by sync-acks, read-and-cleared at the owner's
    election-timeout boundary — the caller does the clearing).  The owner
    itself always counts as active; a joint config needs BOTH majorities
    active (has_quorum over conf.voters, i.e. joint vote_result semantics).

    Returns bool[P, G]: whether owner p's view holds an active quorum.
    """
    P = recent_active.shape[0]
    active = recent_active | jnp.eye(P, dtype=bool)[:, :, None]

    def half(mask):
        # dtype= on the masked counts: a bare bool sum widens to int64
        # under x64 (GC007).
        cnt = jnp.sum(
            active & mask[None, :, :], axis=1, dtype=jnp.int32
        )  # [P_owner, G]
        n = jnp.sum(mask, axis=0, dtype=jnp.int32)[None, :]
        return (cnt >= majority_of(n)) | (n == 0)

    return half(voter_mask) & half(outgoing_mask)


def cq_boundary_safe(
    recent_active: jnp.ndarray,  # gc: bool[P, P, G]
    voter_mask: jnp.ndarray,  # gc: bool[P, G]
    outgoing_mask: jnp.ndarray,  # gc: bool[P, G]
    state: jnp.ndarray,  # gc: int32[P, G]
    crashed: jnp.ndarray,  # gc: bool[P, G]
    election_elapsed: jnp.ndarray,  # gc: int32[P, G]
    horizon: int,
    election_tick: int,
    lossy: Optional[jnp.ndarray] = None,  # gc: bool[G]
) -> jnp.ndarray:
    """bool[G]: every check-quorum boundary that CAN fire within `horizon`
    rounds provably passes — the damping half of the fused steady
    predicate (pallas_step.steady_mask).

    A boundary (tick_kernel's want_check_quorum at a role-leader's
    election-timeout) reads-and-clears the leader's recent_active row and
    steps it down without an active quorum.  On a steady all-links-up
    horizon that outcome is provable per group when:

      * every ALIVE leader's row holds an active quorum NOW
        (check_quorum_active) — recent_active only accumulates until the
        next clear, so the first in-horizon boundary passes;
      * the alive voters form a quorum of each (possibly joint) half —
        after any clear, one full heartbeat interval (the caller requires
        election_tick > heartbeat_tick) re-saturates the row with every
        alive member's ack before the NEXT boundary, so later boundaries
        pass too;
      * no CRASHED role-leader reaches its boundary at all
        (election_elapsed + horizon < election_tick; a crashed leader's
        timer runs free and its row receives no acks, so its boundary
        outcome is its carried row — conservatively excluded).

    `lossy` (optional bool[G]) marks groups whose heartbeat traffic may be
    DROPPED this horizon (a nonzero per-link loss rate anywhere in the
    group): loss breaks the re-saturation argument, so those groups fall
    back per group to the fully conservative no-boundary bound — NO
    role-leader (alive or crashed stale) may reach its election-timeout
    boundary inside the horizon at all.  None keeps the historical
    all-lossless behavior (the pre-split callers' graphs are unchanged).
    This is the PER-GROUP bound: a batch mixing lossy and loss-free
    groups no longer collapses to the weakest group's condition.
    """
    alive = ~crashed
    is_lead_alive = (state == ROLE_LEADER) & alive
    qa = check_quorum_active(recent_active, voter_mask, outgoing_mask)
    lead_ok = jnp.all(jnp.where(is_lead_alive, qa, True), axis=0)

    def half_alive(mask):
        # dtype= on the masked counts: GC007 (bare bool sums widen under
        # x64).
        cnt = jnp.sum(alive & mask, axis=0, dtype=jnp.int32)  # [G]
        n = jnp.sum(mask, axis=0, dtype=jnp.int32)
        return (cnt >= majority_of(n)) | (n == 0)

    alive_quorum = half_alive(voter_mask) & half_alive(outgoing_mask)
    stale = (state == ROLE_LEADER) & crashed
    stale_ok = jnp.all(
        jnp.where(
            stale,
            election_elapsed + jnp.int32(horizon) < jnp.int32(election_tick),
            True,
        ),
        axis=0,
    )
    lossless_ok = lead_ok & alive_quorum & stale_ok
    if lossy is None:
        return lossless_ok
    role_lead = state == ROLE_LEADER
    no_boundary = jnp.all(
        jnp.where(
            role_lead,
            election_elapsed + jnp.int32(horizon) < jnp.int32(election_tick),
            True,
        ),
        axis=0,
    )
    return jnp.where(lossy, no_boundary, lossless_ok)


def timeout_draw(
    node_key: jnp.ndarray,  # gc: uint32[...]
    epoch: jnp.ndarray,  # gc: uint32[...]
    lo: jnp.ndarray,  # gc: int32[...]
    hi: jnp.ndarray,  # gc: int32[...]
) -> jnp.ndarray:
    """Randomized election timeout in [lo, hi) — the device side of
    util.deterministic_timeout (identical 32-bit murmur3-finalizer mix)."""
    x = (
        node_key.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
        + epoch.astype(jnp.uint32)
    )
    x ^= x >> 16
    x *= jnp.uint32(0x85EBCA6B)
    x ^= x >> 13
    x *= jnp.uint32(0xC2B2AE35)
    x ^= x >> 16
    span = (hi - lo).astype(jnp.uint32)
    return (lo.astype(jnp.uint32) + x % span).astype(jnp.int32)


# State role codes matching raft.StateRole.
ROLE_FOLLOWER = 0
ROLE_CANDIDATE = 1
ROLE_LEADER = 2
ROLE_PRE_CANDIDATE = 3


# --- device-side event-counter plane (the batched observability layer) ---
#
# Indices into the [N_COUNTERS] int32 accumulator that `sim.step` sums when
# given a `counters` array: the device-resident mirror of the scalar
# metrics counters (raft_tpu.metrics), accumulated inside the jitted step so
# the hot loop's dispatch count is unchanged and downloaded only on demand
# (ClusterSim.counters()).  Parity against the scalar oracle's counts is
# asserted by tests/test_counter_parity.py.
CTR_CAMPAIGNS = 0  # election timers fired (scalar: Raft.campaign calls)
CTR_HEARTBEATS = 1  # leader heartbeat timers fired (scalar: MsgBeat steps)
CTR_ELECTIONS_WON = 2  # leaders elected (scalar: become_leader calls)
CTR_COMMIT_ENTRIES = 3  # sum of per-peer commit-index advances
N_COUNTERS = 4

COUNTER_NAMES = (
    "campaigns",
    "heartbeats",
    "elections_won",
    "commit_entries",
)


def zero_counters() -> jnp.ndarray:
    """Fresh [N_COUNTERS] int32 accumulator plane."""
    return jnp.zeros((N_COUNTERS,), jnp.int32)


def count_events(
    counters: jnp.ndarray,  # gc: int32[N]
    want_campaign: jnp.ndarray,  # gc: bool[...]
    want_heartbeat: jnp.ndarray,  # gc: bool[...]
    won: jnp.ndarray,  # gc: bool[...]
    commit_delta: jnp.ndarray,  # gc: int32[...]
) -> jnp.ndarray:
    """Fold one round's event masks into the accumulator plane.

    want_campaign/want_heartbeat/won: bool planes (any shape); commit_delta:
    int32 plane of per-peer commit-index increases this round.
    """
    # dtype= on every sum: a bare jnp.sum of bool/int32 widens to int64
    # under x64 (only there — the non-x64 suite truncates it back), which
    # would silently change the accumulator plane's dtype (GC007).
    events = jnp.stack(
        [
            jnp.sum(want_campaign, dtype=jnp.int32),
            jnp.sum(want_heartbeat, dtype=jnp.int32),
            jnp.sum(won, dtype=jnp.int32),
            jnp.sum(commit_delta, dtype=jnp.int32),
        ]
    ).astype(counters.dtype)
    return counters + events


# --- device-side fleet-health planes (the per-group observability layer) --
#
# Row indices into the [N_HEALTH_PLANES, G] int32 plane stack that
# `sim.step` maintains when given a health state: per-GROUP liveness
# telemetry (the counter plane above answers "how much happened in total";
# these answer "which groups are unhealthy right now") kept entirely on
# device so the GC002 no-host-sync invariant holds — only the fixed-size
# `health_summary` reduction ever crosses to the host.  Exact per-round
# parity against the scalar oracle (simref.HealthOracle) is asserted by
# tests/test_health_parity.py.
HP_LEADERLESS = 0  # consecutive rounds the group ended with no alive leader
HP_SINCE_COMMIT = 1  # consecutive rounds the group's max commit was flat
HP_TERM_BUMPS = 2  # max-term growth inside the current churn window
HP_VOTE_SPLITS = 3  # cumulative election rounds that elected nobody
N_HEALTH_PLANES = 4

HEALTH_PLANE_NAMES = (
    "leaderless_ticks",
    "ticks_since_commit",
    "term_bumps_in_window",
    "vote_splits",
)

# Commit-lag histogram bucket lower bounds (ticks_since_commit); bucket i
# counts groups with LAG_BUCKET_BOUNDS[i-1] <= lag < LAG_BUCKET_BOUNDS[i],
# bucket 0 is lag == 0 and the last bucket is lag >= 64.
LAG_BUCKET_BOUNDS = (1, 2, 4, 8, 16, 32, 64)
N_LAG_BUCKETS = len(LAG_BUCKET_BOUNDS) + 1

# health_summary count-vector indices.
HS_LEADERLESS = 0  # groups currently leaderless (any duration)
HS_STALLED_LEADERLESS = 1  # leaderless at/over the stall threshold
HS_COMMIT_STALLED = 2  # commit-flat at/over the stall threshold
HS_CHURNING = 3  # term bumps in window at/over the churn threshold
N_HEALTH_COUNTS = 4

HEALTH_COUNT_NAMES = (
    "leaderless",
    "stalled_leaderless",
    "commit_stalled",
    "churning",
)


def zero_health(n_groups: int) -> jnp.ndarray:
    """Fresh [N_HEALTH_PLANES, n_groups] int32 health-plane stack."""
    return jnp.zeros((N_HEALTH_PLANES, n_groups), jnp.int32)


@profiling.scope("health_fold")
def update_health(
    planes: jnp.ndarray,  # gc: int32[H, G]
    window_pos: jnp.ndarray,  # gc: int32[]
    window: int,
    has_leader: jnp.ndarray,  # gc: bool[G]
    commit_advanced: jnp.ndarray,  # gc: bool[G]
    term_bump: jnp.ndarray,  # gc: int32[G]
    vote_split: jnp.ndarray,  # gc: bool[G]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fold one protocol round into the health planes.

    planes:          [N_HEALTH_PLANES, G] int32 (see HP_* indices)
    window_pos:      int32 scalar, rounds into the current churn window
    window:          python int, churn-window length in rounds (static)
    has_leader:      bool[G]  group ended the round with an alive leader
    commit_advanced: bool[G]  group max commit index grew this round
    term_bump:       int32[G] group max term growth this round
    vote_split:      bool[G]  a campaign fired this round but nobody won

    Returns (planes', window_pos').  The churn window resets at the START
    of the round whose window_pos is 0, so `term_bumps_in_window` always
    covers the last (window_pos or window) rounds.
    """
    leaderless = jnp.where(has_leader, 0, planes[HP_LEADERLESS] + 1)
    since = jnp.where(commit_advanced, 0, planes[HP_SINCE_COMMIT] + 1)
    fresh = window_pos == 0
    bumps = jnp.where(fresh, 0, planes[HP_TERM_BUMPS]) + term_bump
    splits = planes[HP_VOTE_SPLITS] + vote_split.astype(jnp.int32)
    new_pos = (window_pos + 1) % jnp.int32(window)
    return jnp.stack([leaderless, since, bumps, splits]), new_pos


def health_summary(
    planes: jnp.ndarray,  # gc: int32[H, G]
    stall_ticks: int,
    commit_stall_ticks: int,
    churn_bumps: int,
    k: int,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """On-device reduction of the health planes to a fixed-size summary.

    Returns (counts[N_HEALTH_COUNTS], lag_hist[N_LAG_BUCKETS],
    worst_ids[k], worst_scores[k]) — all int32, O(k + buckets) bytes across
    the host boundary regardless of G.

    The worst-offender score is max(ticks_since_commit, leaderless_ticks);
    `jax.lax.top_k` breaks ties toward the LOWER group id, matching a
    stable host-side argsort of the negated score
    (tests/test_health_parity.py).
    """
    leaderless = planes[HP_LEADERLESS]
    lag = planes[HP_SINCE_COMMIT]
    bumps = planes[HP_TERM_BUMPS]
    # dtype= keeps the summary int32 under x64 too (a bare bool sum widens
    # to int64 there, changing the host-boundary buffer dtype — GC007).
    counts = jnp.stack(
        [
            jnp.sum(leaderless > 0, dtype=jnp.int32),
            jnp.sum(leaderless >= stall_ticks, dtype=jnp.int32),
            jnp.sum(lag >= commit_stall_ticks, dtype=jnp.int32),
            jnp.sum(bumps >= churn_bumps, dtype=jnp.int32),
        ]
    )
    bounds = jnp.asarray(LAG_BUCKET_BOUNDS, jnp.int32)
    bucket = jnp.sum(lag[:, None] >= bounds[None, :], axis=1, dtype=jnp.int32)
    hist = jnp.zeros((N_LAG_BUCKETS,), jnp.int32).at[bucket].add(1)
    score = jnp.maximum(lag, leaderless)
    worst_scores, worst_ids = jax.lax.top_k(score, k)
    return (
        counts,
        hist,
        worst_ids.astype(jnp.int32),
        worst_scores.astype(jnp.int32),
    )


# --- device-side black-box flight recorder (the forensics layer) ---------
#
# ISSUE 15: a bit-packed, [W, G]-windowed trace of per-group round deltas
# plus a first-trip capture plane, carried through the jitted scans behind
# SimConfig(blackbox=True) so a safety counter firing at fleet scale can
# be drilled down to the offending GROUP and ROUND without re-running
# anything.  One masked fold per round, zero host syncs; the fixed-size
# blackbox_capture reduction is the only thing that ever crosses to the
# host (the drain cadence, like health_summary).
#
# Ring word layout (GC008 PACKED_PLANES `blackbox_meta`, bound derivation
# in docs/STATIC_ANALYSIS.md "Black-box planes"):
#   bits 0-1   group max ROLE_* code (< 4)
#   bits 2-5   acting leader peer id (kernels.acting_leader_id,
#              0..n_peers <= 8 < 16)
#   bits 6-14  the N_SAFETY fired-slot indicators for the round
BB_LEADER_SHIFT = 2
BB_SAFETY_SHIFT = 6
BB_META_BITS = BB_SAFETY_SHIFT + N_SAFETY  # 15 of 32 word bits used


def pack_blackbox_meta(
    role: jnp.ndarray,  # gc: int32[...]
    leader_id: jnp.ndarray,  # gc: int32[...]
    safety_bits: jnp.ndarray,  # gc: uint32[...]
) -> jnp.ndarray:
    """Pack one black-box ring record into its uint32 word (layout above);
    all three fields are provably sub-field-width (GC008 PACKED_PLANES
    `blackbox_meta`) so the word is lossless by construction."""
    return (
        role.astype(jnp.uint32)
        | (leader_id.astype(jnp.uint32) << BB_LEADER_SHIFT)
        | (safety_bits.astype(jnp.uint32) << BB_SAFETY_SHIFT)
    )


def unpack_blackbox_meta(
    word: jnp.ndarray,  # gc: uint32[...]
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Inverse of pack_blackbox_meta: word -> (role, leader_id,
    safety_bits)."""
    role = (word & jnp.uint32(3)).astype(jnp.int32)
    leader = ((word >> BB_LEADER_SHIFT) & jnp.uint32(0xF)).astype(jnp.int32)
    bits = (word >> BB_SAFETY_SHIFT) & jnp.uint32((1 << N_SAFETY) - 1)
    return role, leader, bits


def zero_blackbox(
    n_groups: int, window: int
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fresh black-box planes: (meta uint32[W, G], term int32[W, G],
    commit int32[W, G], trip_round int32[N_SAFETY, G] at INF = never
    tripped, round_idx int32[] = 0).  sim.BlackboxState is the carried
    pytree form."""
    return (
        jnp.zeros((window, n_groups), jnp.uint32),
        jnp.zeros((window, n_groups), jnp.int32),
        jnp.zeros((window, n_groups), jnp.int32),
        jnp.full((N_SAFETY, n_groups), INF, jnp.int32),
        jnp.int32(0),
    )


def blackbox_fold(
    meta_ring: jnp.ndarray,  # gc: uint32[W, G]
    term_ring: jnp.ndarray,  # gc: int32[W, G]
    commit_ring: jnp.ndarray,  # gc: int32[W, G]
    trip_round: jnp.ndarray,  # gc: int32[S, G]
    round_idx: jnp.ndarray,  # gc: int32[]
    state: jnp.ndarray,  # gc: int32[P, G]
    term: jnp.ndarray,  # gc: int32[P, G]
    commit: jnp.ndarray,  # gc: int32[P, G]
    crashed: jnp.ndarray,  # gc: bool[P, G]
    viol: jnp.ndarray,  # gc: bool[S, G]
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fold one round's per-group deltas into the black-box ring: write
    slot round_idx % W with (packed role|leader|safety-bits word, group
    max term, group max commit) and min-fold this round into the
    first-trip plane where `viol` fired.  Purely elementwise along G plus
    one W-row dynamic write — shard-trivial on a group-sharded mesh, zero
    collectives (the GC015 steady-graph discipline).

    `viol` is kernels.check_safety_groups' output for the round; callers
    without a safety audit in the loop (the plain run_compiled trace)
    pass all-False and get the trace ring alone — `blackbox_mark` can
    stamp the bits in later from the same round index.
    """
    W = meta_ring.shape[0]
    role = jnp.max(state, axis=0)  # 2-bit ROLE_* summary (max code)
    lead = acting_leader_id(state, term, crashed)
    lanes = jnp.arange(N_SAFETY, dtype=jnp.uint32)[:, None]
    # Bits are disjoint, so the shifted sum is a bitwise OR; dtype= keeps
    # the reduction uint32 under x64 (GC007).
    bits = jnp.sum(
        viol.astype(jnp.uint32) << lanes, axis=0, dtype=jnp.uint32
    )
    word = pack_blackbox_meta(role, lead, bits)
    slot = round_idx % jnp.int32(W)
    meta_ring = meta_ring.at[slot].set(word)
    term_ring = term_ring.at[slot].set(jnp.max(term, axis=0))
    commit_ring = commit_ring.at[slot].set(jnp.max(commit, axis=0))
    trip_round = jnp.minimum(
        trip_round, jnp.where(viol, round_idx, INF)
    )
    return meta_ring, term_ring, commit_ring, trip_round, round_idx + 1


def blackbox_mark(
    meta_ring: jnp.ndarray,  # gc: uint32[W, G]
    trip_round: jnp.ndarray,  # gc: int32[S, G]
    round_idx: jnp.ndarray,  # gc: int32[]
    viol: jnp.ndarray,  # gc: bool[S, G]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Stamp a violation mask onto the LAST folded round (round_idx - 1):
    OR the fired-slot bits into its ring word and min-fold the trip
    plane.  The ad-hoc stepping path (ClusterSim.run_round + a host-side
    safety audit between rounds) uses this; the compiled runners fold
    bits and trace in one blackbox_fold call instead.  A mark on a FRESH
    recorder (round_idx == 0: no round has been folded, so there is
    nothing to attribute to) is a no-op — the mask is masked off rather
    than stamping round -1 onto ring slot W-1."""
    W = meta_ring.shape[0]
    viol = viol & (round_idx > 0)
    r = jnp.maximum(round_idx - 1, 0)
    slot = r % jnp.int32(W)
    lanes = jnp.arange(N_SAFETY, dtype=jnp.uint32)[:, None]
    bits = jnp.sum(
        viol.astype(jnp.uint32) << lanes, axis=0, dtype=jnp.uint32
    )
    meta_ring = meta_ring.at[slot].set(
        meta_ring[slot] | (bits << jnp.uint32(BB_SAFETY_SHIFT))
    )
    trip_round = jnp.minimum(trip_round, jnp.where(viol, r, INF))
    return meta_ring, trip_round


def blackbox_capture(
    trip_round: jnp.ndarray,  # gc: int32[S, G]
    k: int,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Drain-time reduction of the first-trip plane to a fixed-size
    capture: (counts int32[N_SAFETY], ids int32[N_SAFETY, k], rounds
    int32[N_SAFETY, k]) — per safety slot, how many groups ever tripped
    it and the FIRST k offenders in (trip round, group id) order
    (first-K-stable: `jax.lax.top_k` on the negated trip rounds breaks
    ties toward the LOWER group id, exactly like health_summary's
    worst-offender extraction).  Unfired lanes carry id/round -1.  O(k)
    bytes across the host boundary regardless of G; on a group-sharded
    mesh the top_k gathers per-shard candidates once per drain cadence —
    the same registered-gather shape as the sharded health drain, never
    in the hot loop."""
    fired = trip_round < INF
    # dtype= keeps the counts int32 under x64 (GC007).
    counts = jnp.sum(fired, axis=1, dtype=jnp.int32)
    neg, ids = jax.lax.top_k(-trip_round, k)
    rounds = -neg
    got = rounds < INF
    return (
        counts,
        jnp.where(got, ids.astype(jnp.int32), -1),
        jnp.where(got, rounds, -1),
    )


def tick_kernel(
    state: jnp.ndarray,  # gc: int32[...]
    election_elapsed: jnp.ndarray,  # gc: int32[...]
    heartbeat_elapsed: jnp.ndarray,  # gc: int32[...]
    randomized_timeout: jnp.ndarray,  # gc: int32[...]
    promotable: jnp.ndarray,  # gc: bool[...]
    election_timeout: int,
    heartbeat_timeout: int,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One logical-clock tick for every node in the batch
    (reference: raft.rs:1024-1079).

    All args are int32/bool arrays of one shape (any rank — [G] for a
    MultiRaft node, [G, P] for the closed-loop sim).

    Returns (election_elapsed', heartbeat_elapsed', want_campaign,
    want_heartbeat, want_check_quorum):
      * non-leaders: elapsed+1; timeout & promotable -> want_campaign with
        elapsed reset (reference: raft.rs:1037-1047)
      * leaders: heartbeat_elapsed+1 and election_elapsed+1; heartbeat
        timeout -> want_heartbeat; election timeout -> want_check_quorum
        (reference: raft.rs:1051-1079)

    The caller (driver/sim) turns the masks into MsgHup/MsgBeat/
    MsgCheckQuorum effects; timer arithmetic itself never leaves the device.
    """
    is_leader = state == ROLE_LEADER

    ee = election_elapsed + 1
    hb = jnp.where(is_leader, heartbeat_elapsed + 1, heartbeat_elapsed)

    pass_election = ee >= randomized_timeout
    want_campaign = (~is_leader) & pass_election & promotable
    ee = jnp.where(want_campaign, 0, ee)

    leader_election_timeout = is_leader & (ee >= election_timeout)
    want_check_quorum = leader_election_timeout
    ee = jnp.where(leader_election_timeout, 0, ee)

    want_heartbeat = is_leader & (hb >= heartbeat_timeout)
    hb = jnp.where(want_heartbeat, 0, hb)

    return ee, hb, want_campaign, want_heartbeat, want_check_quorum


def append_response_update(
    matched: jnp.ndarray,  # gc: int32[...]
    next_idx: jnp.ndarray,  # gc: int32[...]
    resp_index: jnp.ndarray,  # gc: int32[...]
    resp_mask: jnp.ndarray,  # gc: bool[...]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Batched Progress.maybe_update for accepted append responses
    (reference: progress.rs:138-150): matched = max(matched, index),
    next = max(next, index + 1), applied only under resp_mask."""
    new_matched = jnp.where(
        resp_mask, jnp.maximum(matched, resp_index), matched
    )
    new_next = jnp.where(
        resp_mask, jnp.maximum(next_idx, resp_index + 1), next_idx
    )
    return new_matched, new_next
