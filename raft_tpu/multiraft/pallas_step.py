"""Fused Pallas kernels for steady-state MultiRaft rounds.

In the steady state — every group has exactly one alive leader, all alive
peers share its term, and nobody's election timer can fire — a protocol
round touches only {election/heartbeat timers, log tail, matched, commit}.
The XLA expression of that path (sim.step) makes several passes over HBM;
these kernels stream each [P, block] tile through VMEM once and run **k
whole protocol rounds** on it before writing back, amortizing both HBM
traffic and per-block overhead over k rounds.

Conditions, not numbers: the design pays off only when k amortizes the
per-call traffic (at k = 1 the general XLA step's own fusion is the
competitor), the lane block is sized from each family's operand list to a
scoped-VMEM budget (`_tiling`), and whether Pallas interprets or Mosaic
compiles is raft_tpu.platform's decision, never a caller's.  Speeds come
from `python3 benchmark/run.py` on the chip (PERF.md); none is quoted here.

`steady_mask(cfg, st, crashed, horizon=k)` decides, group by group,
whether the invariant provably holds for the next k rounds
(`steady_predicate` is its reduce over the fleet); where it holds,
`steady_round(cfg, rounds=k)` is bit-identical to k sequential general
steps (tests/test_pallas_step.py asserts the parity; the crashed mask and
per-round append workload are held constant across the k rounds, which is
exactly the lockstep schedule ScalarCluster drives).  This module holds no
dispatcher: choosing between the fused kernel and the general round is
`runner.make_runner(..., split=True)`'s business (`block_run` /
`fused_block_run`, and the autopilot's cadence segment with `fused=True`).

Coverage matrix (docs/PERF.md): the INSTRUMENTED configurations ride the
fused path too — `with_health` tracks ticks_since_commit in-kernel and
folds the other planes closed-form; `with_counters` folds the CTR_* plane
closed-form (no campaigns/wins on a steady horizon, heartbeat fires and
commit deltas are arithmetic); `with_chaos` runs the loss-gated chaos
kernel (_steady_chaos_kernel): link plane healed by predicate, per-link
loss drawn IN-KERNEL with the (round, src, dst, group) counter PRNG,
bit-identical to k sequential sim.step(link=) rounds.  The chaos variants
stream packed sub-int32 operand planes (GC008 PACKED_PLANES registry).

Election damping (ISSUE 8): check_quorum/pre_vote configs — the deployed
raft-rs production configuration — ride their own fused kernel family
(_steady_damped_kernel, the same health/counters/chaos composition
surface), bit-identical to k `sim._damped_linked_step` rounds: on a
steady horizon damping has closed form — heartbeat acks saturate the
leader's recent_active row every heartbeat interval so the check-quorum
boundary provably passes (the kernel advances the boundary's
read-and-clear cycle in-kernel), leases are never tested and pre-vote is
dormant (no elections), and the low-term nudge cannot fire (uniform
terms).  steady_mask widens with the damping conditions
(kernels.cq_boundary_safe lossless; a conservative free-running bound on
the cq boundary under loss), so damped fusion needs the same
`election_tick > k` regime as chaos.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import platform, profiling
from . import kernels as kernels_mod
from . import planes
from .kernels import (
    CTR_COMMIT_ENTRIES,
    CTR_HEARTBEATS,
    HP_SINCE_COMMIT,
    HP_TERM_BUMPS,
    HP_VOTE_SPLITS,
    ROLE_FOLLOWER,
    ROLE_LEADER,
)
from .sim import HealthState, SimConfig, SimState

# Scoped VMEM a fused kernel's lane block is sized to: half of the 16 MiB
# Mosaic grants a kernel by default on a v5e, the other half being margin
# for families whose temporaries run higher than the one measured.
VMEM_BUDGET = 8 << 20
# A kernel's scoped VMEM is its double-buffered operand tiles plus the
# in-kernel temporaries and spills; on the v5e the chaos kernel took 50.0 MiB
# for 16.5 MiB of double-buffered operands at an 8192-lane block — 3x.
_SCOPED_PER_OPERAND_BYTE = 3


def _tiling(G: int, P: int, n_pg: int, n_ppg: int, n_g: int):
    """(block, grid, [P, B] spec, [P, P, B] spec, [1, B] spec) for a kernel
    streaming `n_pg` [P, B], `n_ppg` [P, P, B] and `n_g` [1, B] int32
    operand tiles (inputs + outputs) along the group axis.

    `block` — groups per grid step — is the largest power-of-two multiple
    of the 128-lane vreg width whose estimated scoped VMEM fits
    VMEM_BUDGET.  The peer axis pads to the 8-sublane tile, so a pairwise
    tile is P padded rows of it.  Besides fitting VMEM, a narrower block
    is a proportionally smaller Mosaic program (the round body is unrolled
    over the block's vregs), which is what keeps the pairwise families'
    compile time in seconds."""
    sublanes = 8 * pl.cdiv(P, 8)
    col_bytes = 4 * (n_pg * sublanes + n_ppg * P * sublanes + n_g * 8)
    lanes = VMEM_BUDGET // (2 * col_bytes * _SCOPED_PER_OPERAND_BYTE)
    block = min(max(128, 1 << (lanes.bit_length() - 1)), G)
    vmem = pltpu.VMEM
    return (
        block,
        (pl.cdiv(G, block),),
        pl.BlockSpec((P, block), lambda i: (0, i), memory_space=vmem),
        pl.BlockSpec((P, P, block), lambda i: (0, 0, i), memory_space=vmem),
        pl.BlockSpec((1, block), lambda i: (0, i), memory_space=vmem),
    )


# --- packed kernel-operand planes (GC008 "packed planes" registry) ----------
#
# The fused kernels stream every operand plane HBM -> VMEM once per call, so
# each plane dropped from the operand list is G*4 bytes of memory traffic
# saved per fused block.  Three int32 [P, G] planes whose values are provably
# sub-int32 ride in ONE word each; the bounds are registered in
# tools/graftcheck/engine/overflow.py (PACKED_PLANES) and derived in
# docs/STATIC_ANALYSIS.md:
#
#   roles word  = state | leader_id << 2 | heartbeat_elapsed << 6
#                 (state < 4 by the ROLE_* code set; leader_id <= n_peers,
#                 asserted < 16; heartbeat_elapsed <= heartbeat_tick,
#                 asserted < 2**24)
#   masks word  = voter | member << 1 | crashed << 2   (three bools)


def _pack_roles(state, leader_id, hb):
    return state + (leader_id << 2) + (hb << 6)


def _unpack_roles(word):
    return word & 3, (word >> 2) & 15, word >> 6


def _pack_masks(voter, member, crashed):
    return (
        voter.astype(jnp.int32)
        + (member.astype(jnp.int32) << 1)
        + (crashed.astype(jnp.int32) << 2)
    )


def _unpack_masks(word):
    return (word & 1) != 0, ((word >> 1) & 1) != 0, ((word >> 2) & 1) != 0


def _steady_kernel(
    # inputs: state_ref, term_ref, ee_ref, hb_ref, li_ref, lt_ref,
    # matched_ref, commit_ref, voter_ref, member_ref, crashed_ref, ts_ref,
    # app_ref [+ tsc_ref when with_health]; then the outputs: ee, hb, li,
    # lt, matched, commit [+ tsc].  Flat *refs because the health variant
    # adds one input/output pair and pallas kernels take refs positionally.
    *refs,
    P: int,
    rounds: int,
    election_tick: int,
    heartbeat_tick: int,
    with_health: bool,
):
    n_in = 14 if with_health else 13
    (
        state_ref, term_ref, ee_ref, hb_ref, li_ref, lt_ref, matched_ref,
        commit_ref, voter_ref, member_ref, crashed_ref, ts_ref, app_ref,
    ) = refs[:13]
    ee_out, hb_out, li_out, lt_out, matched_out, commit_out = refs[
        n_in : n_in + 6
    ]
    state = state_ref[...]
    term = term_ref[...]
    ee = ee_ref[...]
    hb = hb_ref[...]
    li = li_ref[...]
    lt = lt_ref[...]
    matched = matched_ref[...]
    commit = commit_ref[...]
    voter = voter_ref[...] != 0
    member = member_ref[...] != 0
    crashed = crashed_ref[...] != 0
    term_start = ts_ref[...]  # [1, BLOCK]
    app = app_ref[...]  # [1, BLOCK]
    if with_health:
        tsc = refs[13][...]  # [1, BLOCK] ticks_since_commit plane
        maxc_prev = jnp.max(commit, axis=0, keepdims=True)  # [1, BLOCK]

    alive = ~crashed
    # Timers tick by ROLE — a crashed (isolated) leader keeps ticking
    # (reference: raft.rs:1051-1079; isolation cuts the network, not the
    # clock).  Replication uses the ALIVE leader (exactly one by invariant).
    role_leader = state == ROLE_LEADER  # [P, B]
    is_leader = role_leader & alive
    has_leader = jnp.any(is_leader, axis=0, keepdims=True)  # [1, B]
    # dtype= on every sum in the kernel: a bare jnp.sum widens to int64
    # under x64 — inside a Mosaic kernel that is not even lowerable, and in
    # interpret mode it silently changes the tile dtypes (GC007).
    count = jnp.sum(voter, axis=0, keepdims=True, dtype=jnp.int32)
    qpos = count // 2
    n_app = jnp.where(has_leader, app, 0)  # [1, B]

    for _ in range(rounds):
        # --- tick (reference: raft.rs:1024-1079; no campaigns by invariant)
        ee = ee + 1
        ee = jnp.where(role_leader & (ee >= election_tick), 0, ee)
        hb = jnp.where(role_leader, hb + 1, hb)
        want_beat = role_leader & (hb >= heartbeat_tick)
        hb = jnp.where(want_beat, 0, hb)

        # --- appends at the (unique alive) leader ---
        li = li + jnp.where(is_leader, n_app, 0)
        lt = jnp.where(is_leader, term, lt)
        lead_last = jnp.sum(
            jnp.where(is_leader, li, 0), axis=0, keepdims=True,
            dtype=jnp.int32,
        )
        lead_lt = jnp.sum(
            jnp.where(is_leader, lt, 0), axis=0, keepdims=True,
            dtype=jnp.int32,
        )

        lead_beat = jnp.any(want_beat & is_leader, axis=0, keepdims=True)
        sent = has_leader & (lead_beat | (n_app > 0))  # [1, B]

        # --- instant in-round sync of alive member followers (voters +
        # learners; non-members are outside the progress map) ---
        sync = sent & alive & member & ~is_leader
        ee = jnp.where(sync, 0, ee)
        li = jnp.where(sync, lead_last, li)
        lt = jnp.where(sync, lead_lt, lt)
        matched = jnp.where(sync | (is_leader & sent), li, matched)

        # --- quorum commit via odd-even transposition network over P rows
        # (reference: majority.rs:70-124).  Rows kept 2-D [1, B].
        rows = [
            jnp.where(voter[p : p + 1, :], matched[p : p + 1, :], 0)
            for p in range(P)
        ]
        for pass_ in range(P):
            for i in range(pass_ % 2, P - 1, 2):
                hi = jnp.maximum(rows[i], rows[i + 1])
                lo = jnp.minimum(rows[i], rows[i + 1])
                rows[i], rows[i + 1] = hi, lo
        mci = jnp.zeros_like(rows[0])
        for p in range(P):
            mci = jnp.where(qpos == p, rows[p], mci)

        ok = has_leader & sent & (mci >= term_start)
        lead_commit_old = jnp.sum(
            jnp.where(is_leader, commit, 0), axis=0, keepdims=True,
            dtype=jnp.int32,
        )
        lead_commit = jnp.where(
            ok, jnp.maximum(lead_commit_old, mci), lead_commit_old
        )
        commit = jnp.where((is_leader | sync) & sent, lead_commit, commit)

        if with_health:
            # The one health plane a steady round can move: per-round
            # commit-advance tracking for ticks_since_commit (the other
            # planes are closed-form over a steady horizon — see
            # steady_round's health wrapper).
            maxc = jnp.max(commit, axis=0, keepdims=True)
            tsc = jnp.where(maxc > maxc_prev, 0, tsc + 1)
            maxc_prev = maxc

    ee_out[...] = ee
    hb_out[...] = hb
    li_out[...] = li
    lt_out[...] = lt
    matched_out[...] = matched
    commit_out[...] = commit
    if with_health:
        refs[n_in + 6][...] = tsc


def _kernel_loss_draw(round_base, r, gids, lane, loss_rate):
    """In-kernel seeded per-link loss sample: kernels.link_loss_draw
    inlined with tile-global group ids (`gids` offset by the program id)
    and the precomputed (src, dst) `lane` plane — the ONE copy both the
    chaos and damped fused kernels draw from, so the (round, src, dst,
    group) PRNG keying cannot drift between them."""
    round_u = (round_base + jnp.int32(r)).astype(jnp.uint32)  # [1, B]
    x0 = kernels_mod._mix32(gids * jnp.uint32(0x9E3779B1) + round_u)
    x = kernels_mod._mix32(
        x0[None, :, :] ^ (lane * jnp.uint32(0x85EBCA6B))
    )  # [P, P, B]
    return (x % jnp.uint32(kernels_mod.LOSS_SCALE)).astype(
        jnp.int32
    ) < loss_rate


def _agree_event(agree, in_set, value, lead_f):
    """One wholesale-adoption agreement event (sim._merge_agree with the
    acting leader as the sender): pairs inside `in_set` agree to `value`;
    pairs with one side inside inherit the leader's row.  Shared by the
    chaos and damped fused kernels."""
    lead_row = jnp.sum(
        agree * lead_f[:, None, :], axis=0, dtype=jnp.int32
    )  # [P, B] = agree[leader, :]
    return jnp.where(
        in_set[:, None, :] & in_set[None, :, :],
        value[None, :, :],
        jnp.where(
            in_set[:, None, :],
            lead_row[None, :, :],
            jnp.where(in_set[None, :, :], lead_row[:, None, :], agree),
        ),
    )


@profiling.scope("quorum_commit")
def _quorum_tile(matched, voter, qpos, P):
    """Majority index of a [P, B] matched tile over its voter rows: the
    same odd-even transposition network as the plain steady kernel (the
    in-kernel twin of sim._quorum_index for the non-joint case)."""
    rows = [
        jnp.where(voter[p : p + 1, :], matched[p : p + 1, :], 0)
        for p in range(P)
    ]
    for pass_ in range(P):
        for i in range(pass_ % 2, P - 1, 2):
            hi = jnp.maximum(rows[i], rows[i + 1])
            lo = jnp.minimum(rows[i], rows[i + 1])
            rows[i], rows[i + 1] = hi, lo
    mci = jnp.zeros_like(rows[0])
    for p in range(P):
        mci = jnp.where(qpos == p, rows[p], mci)
    return mci


def _steady_chaos_kernel(
    # inputs: roles_ref (packed state|leader_id|hb), ee, li, lt, commit,
    # matched_row, masks_ref (packed voter|member|crashed) [P, B]; agree,
    # loss_rate [P, P, B]; ts, lead_term, app, round_base [1, B]
    # [+ tsc when with_health]; outputs: roles, ee, li, lt, commit,
    # matched_row, agree [+ tsc].
    *refs,
    P: int,
    block: int,
    rounds: int,
    election_tick: int,
    heartbeat_tick: int,
    with_health: bool,
):
    n_in = 14 if with_health else 13
    (
        roles_ref, ee_ref, li_ref, lt_ref, commit_ref, matched_ref,
        masks_ref, agree_ref, loss_ref, ts_ref, ltm_ref, app_ref, rb_ref,
    ) = refs[:13]
    (
        roles_out, ee_out, li_out, lt_out, commit_out, matched_out,
        agree_out,
    ) = refs[n_in : n_in + 7]
    state, leader_id, hb = _unpack_roles(roles_ref[...])
    voter, member, crashed = _unpack_masks(masks_ref[...])
    ee = ee_ref[...]
    li = li_ref[...]
    lt = lt_ref[...]
    commit = commit_ref[...]
    matched_row = matched_ref[...]  # the acting leader's tracker row
    agree = agree_ref[...]  # [P, P, B] pairwise log agreement
    loss_rate = loss_ref[...]  # [P, P, B] fixed-point per-link loss
    ts = ts_ref[...]  # [1, B] acting leader's term_start_index
    ltm = ltm_ref[...]  # [1, B] acting leader's term
    app = app_ref[...]  # [1, B]
    round_base = rb_ref[...]  # [1, B] absolute round index of round 0
    if with_health:
        tsc = refs[13][...]
        maxc_prev = jnp.max(commit, axis=0, keepdims=True)

    alive = ~crashed
    role_leader = state == ROLE_LEADER
    is_lead = role_leader & alive  # exactly one per group by the predicate
    has_leader = jnp.any(is_lead, axis=0, keepdims=True)  # [1, B]
    lead_f = is_lead.astype(jnp.int32)
    p_iota = jax.lax.broadcasted_iota(jnp.int32, (P, 1), 0)
    # dtype= on every sum: see _steady_kernel (GC007).
    lead_id_val = jnp.sum(
        lead_f * (p_iota + 1), axis=0, keepdims=True, dtype=jnp.int32
    )
    count = jnp.sum(voter, axis=0, keepdims=True, dtype=jnp.int32)
    qpos = count // 2
    n_app = jnp.where(has_leader, app, 0)  # [1, B]
    # Global group ids for the (round, src, dst, group)-keyed loss PRNG —
    # the draw must be bit-identical to kernels.link_loss_draw on the full
    # batch, so the iota is offset by this tile's first column.
    gids = (
        jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
        + pl.program_id(0) * block
    ).astype(jnp.uint32)
    s_io = jax.lax.broadcasted_iota(jnp.uint32, (P, P, 1), 0)
    d_io = jax.lax.broadcasted_iota(jnp.uint32, (P, P, 1), 1)
    lane = s_io * jnp.uint32(P) + d_io + jnp.uint32(1)

    def lead_gather(plane):  # [P, B] -> [1, B]: the acting leader's value
        return jnp.sum(plane * lead_f, axis=0, keepdims=True, dtype=jnp.int32)

    def agree_event(agree, in_set, value):
        # sim._linked_step's triple-where, shared with the damped kernel.
        return _agree_event(agree, in_set, value, lead_f)

    for r in range(rounds):
        # --- seeded per-link loss draw (the shared in-kernel PRNG).
        drop = _kernel_loss_draw(round_base, r, gids, lane, loss_rate)
        # Forward (leader -> v) and reverse (v -> leader) delivery for this
        # round; the link plane itself is all-up among alive peers by the
        # steady predicate, so only the loss sample gates delivery.
        dfl = jnp.any(drop & is_lead[:, None, :], axis=0)  # [P, B]
        dtl = jnp.any(drop & is_lead[None, :, :], axis=1)
        fwd = ~dfl & alive & ~is_lead
        rev = ~dtl & alive & ~is_lead

        # --- tick (identical to the plain steady kernel)
        ee = ee + 1
        ee = jnp.where(role_leader & (ee >= election_tick), 0, ee)
        hb = jnp.where(role_leader, hb + 1, hb)
        want_beat = role_leader & (hb >= heartbeat_tick)
        hb = jnp.where(want_beat, 0, hb)
        beat = jnp.any(want_beat & is_lead, axis=0, keepdims=True)  # [1, B]

        # Round-start snapshots of the acting leader's cursors (the
        # wave payloads are queued before any delivery mutates them).
        c_l = lead_gather(commit)  # [1, B]
        li_l = lead_gather(li)
        lt_l = lead_gather(lt)

        # --- wave 1: heartbeat delivery (terms are all equal, so every
        # delivered heartbeat is accepted) + the reverse-link response.
        h_acc = fwd & beat & member
        state = jnp.where(h_acc, ROLE_FOLLOWER, state)
        leader_id = jnp.where(h_acc, lead_id_val, leader_id)
        ee = jnp.where(h_acc, 0, ee)
        hb_val = jnp.minimum(matched_row, c_l)
        commit = jnp.where(h_acc, jnp.maximum(commit, hb_val), commit)
        resumed = h_acc & rev  # pr.resume() at the leader

        # --- wave 3 pass 1: heartbeat-triggered catch-up appends for
        # lagging members (cu implies both links up, so the send adopts
        # and the ack lands in the leader's matched row).
        cu = resumed & (matched_row < li_l)
        commit = jnp.where(cu, jnp.maximum(commit, c_l), commit)
        matched_row = jnp.where(
            cu, jnp.maximum(matched_row, li_l), matched_row
        )
        li = jnp.where(cu, li_l, li)
        lt = jnp.where(cu, lt_l, lt)
        sent1 = jnp.any(cu, axis=0, keepdims=True)
        agree = agree_event(agree, cu | (is_lead & sent1), li_l)

        # --- stage-A quorum commit at the leader off the fresh acks.
        mci = _quorum_tile(matched_row, voter, qpos, P)
        ok_a = has_leader & (count > 0) & (mci >= ts)
        c_new = jnp.where(ok_a, jnp.maximum(c_l, mci), c_l)
        adv = c_new > c_l
        commit = jnp.where(is_lead, c_new, commit)

        # --- pass 2: a commit advance re-broadcasts to sendable members
        # (Replicate probes and freshly resumed ones).
        agree_l = jnp.sum(
            agree * lead_f[:, None, :], axis=0, dtype=jnp.int32
        )
        sendable = (matched_row > 0) | resumed
        msg2 = fwd & member & adv & sendable
        adopt2 = msg2 & ((agree_l >= li_l) | rev)
        state = jnp.where(msg2, ROLE_FOLLOWER, state)
        leader_id = jnp.where(msg2, lead_id_val, leader_id)
        ee = jnp.where(msg2, 0, ee)
        li = jnp.where(adopt2, li_l, li)
        lt = jnp.where(adopt2, lt_l, lt)
        matched_row = jnp.where(
            adopt2 & rev, jnp.maximum(matched_row, li_l), matched_row
        )
        agree = agree_event(agree, adopt2 | (is_lead & jnp.any(
            adopt2, axis=0, keepdims=True)), li_l)

        # --- stage-B commit + the post-advance commit propagation.
        mci2 = _quorum_tile(matched_row, voter, qpos, P)
        ok_b = has_leader & (count > 0) & (mci2 >= ts)
        c_new2 = jnp.where(ok_b, jnp.maximum(c_new, mci2), c_new)
        commit = jnp.where(is_lead, c_new2, commit)
        agree_l2 = jnp.sum(
            agree * lead_f[:, None, :], axis=0, dtype=jnp.int32
        )
        sendable2 = (matched_row > 0) | resumed
        elig = (
            fwd
            & member
            & sendable2
            & ((agree_l2 >= li_l) | rev)
            & (c_new2 > c_l)
        )
        commit = jnp.where(elig, jnp.maximum(commit, c_new2), commit)

        # --- the round's append workload at the leader.
        sent_b = has_leader & (n_app > 0)
        li = li + jnp.where(is_lead, n_app, 0)
        lt = jnp.where(is_lead & sent_b, ltm, lt)
        lead_last = li_l + n_app  # [1, B]
        pr_ok = (matched_row > 0) | resumed
        sync_msg = sent_b & fwd & member & ~is_lead & pr_ok
        agree_l3 = jnp.sum(
            agree * lead_f[:, None, :], axis=0, dtype=jnp.int32
        )
        sync_b = sync_msg & ((agree_l3 >= li_l) | rev)
        state = jnp.where(sync_msg, ROLE_FOLLOWER, state)
        leader_id = jnp.where(sync_msg, lead_id_val, leader_id)
        ee = jnp.where(sync_msg, 0, ee)
        li = jnp.where(sync_b, lead_last, li)
        lt = jnp.where(sync_b, ltm, lt)
        acked = (sync_b & rev) | (is_lead & sent_b)
        matched_row = jnp.where(
            acked, jnp.maximum(matched_row, lead_last), matched_row
        )
        agree = agree_event(agree, sync_b | (is_lead & sent_b), lead_last)
        mci3 = _quorum_tile(matched_row, voter, qpos, P)
        ok_c = sent_b & (count > 0) & (mci3 >= ts)
        lead_commit = jnp.where(ok_c, jnp.maximum(c_new2, mci3), c_new2)
        commit = jnp.where(is_lead, lead_commit, commit)
        commit = jnp.where(
            sync_b, jnp.maximum(commit, lead_commit), commit
        )

        if with_health:
            maxc = jnp.max(commit, axis=0, keepdims=True)
            tsc = jnp.where(maxc > maxc_prev, 0, tsc + 1)
            maxc_prev = maxc

    roles_out[...] = _pack_roles(state, leader_id, hb)
    ee_out[...] = ee
    li_out[...] = li
    lt_out[...] = lt
    commit_out[...] = commit
    matched_out[...] = matched_row
    agree_out[...] = agree
    if with_health:
        refs[n_in + 7][...] = tsc


def _fold_counters(cfg: SimConfig, k: int, st_in, st_out, counters):
    """Closed-form CTR_* fold for a steady k-round horizon: campaigns and
    elections won are 0 (the predicate forbids both), heartbeat fires per
    role-leader are (hb0 + k) // heartbeat_tick (the timer resets on every
    fire), and commit deltas telescope because commit is monotone —
    bit-identical to threading counters through k sim.steps
    (tests/test_pallas_step.py)."""
    role_leader = st_in.state == ROLE_LEADER
    fires = jnp.where(
        role_leader,
        (st_in.heartbeat_elapsed + jnp.int32(k))
        // jnp.int32(cfg.heartbeat_tick),
        0,
    )
    # dtype= on the sums: a bare jnp.sum widens to int64 under x64 (GC007).
    hb_total = jnp.sum(fires, dtype=jnp.int32)
    commit_total = jnp.sum(st_out.commit - st_in.commit, dtype=jnp.int32)
    return (
        counters.at[CTR_HEARTBEATS]
        .add(hb_total)
        .at[CTR_COMMIT_ENTRIES]
        .add(commit_total)
    )


def _steady_health_fold(cfg: SimConfig, rounds: int, health, tsc_out):
    """Closed-form health fold for a steady horizon: the churn window
    resets iff a round with window_pos == 0 falls inside [pos, pos +
    rounds), and every in-horizon bump is 0."""
    pos = health.window_pos
    window = jnp.int32(cfg.health_window)
    crossed = (pos == 0) | (pos + jnp.int32(rounds) > window)
    planes = jnp.stack(
        [
            jnp.zeros_like(tsc_out),  # leaderless: a leader held all k
            tsc_out,
            jnp.where(crossed, 0, health.planes[HP_TERM_BUMPS]),
            health.planes[HP_VOTE_SPLITS],
        ]
    )
    new_pos = (pos + jnp.int32(rounds)) % window
    return HealthState(planes, new_pos)


def steady_round(
    cfg: SimConfig,
    rounds: int = 1,
    with_health: bool = False,
    with_chaos: bool = False,
    with_counters: bool = False,
):
    """Build the pallas_call for `rounds` fused steady protocol rounds;
    returns fn(st, crashed, append_n) -> SimState (same crashed/append each
    round).

    With `with_health`, the returned fn takes a HealthState extra and
    returns it updated, bit-identical to threading sim.step's health extra
    through the same rounds.  Only ticks_since_commit needs per-round
    tracking (one extra [1, BLOCK] VMEM plane); the other planes are
    closed-form over a steady horizon — no campaigns can fire and the
    alive leader holds, so leaderless_ticks lands at 0, vote_splits is
    unchanged, term bumps are 0 and the churn window only needs its
    position advanced (with one reset if a window boundary falls inside
    the horizon).

    With `with_counters`, the fn takes/returns the [N_COUNTERS] int32
    plane; the per-round event counts are closed-form over a steady
    horizon (_fold_counters).

    With `with_chaos`, the fn signature grows (loss_rate int32[P, P, G],
    round_base int32[]) after append_n and the round runs the loss-gated
    chaos kernel (_steady_chaos_kernel): per-link loss draws are sampled
    in-kernel with the (round, src, dst, group) counter PRNG, bit-identical
    to `rounds` sequential sim.step(link=healed & ~loss_draw) calls.  The
    extras order is always (loss, round_base), counters, health —
    sim.step's extras convention.

    Damping-on configs (SimConfig.check_quorum / pre_vote) build the
    damped kernel family instead (_steady_damped_kernel) with the same
    signatures per flag combination, bit-identical to `rounds` sequential
    damped wave rounds (sim._damped_linked_step) — including the
    check-quorum boundary's recent_active read-and-clear cycle."""
    P = cfg.n_peers
    G = cfg.n_groups

    if cfg.check_quorum or cfg.pre_vote:
        # Election-damping configs route to the damped kernel family
        # (ISSUE 8): same composition surface (health/counters/chaos),
        # built separately so the undamped graphs stay byte-identical.
        return _build_damped_round(
            cfg, rounds, with_health, with_counters, with_chaos
        )

    if with_chaos:
        return _build_chaos_round(cfg, rounds, with_health, with_counters)

    kernel = functools.partial(
        _steady_kernel,
        P=P,
        rounds=rounds,
        election_tick=cfg.election_tick,
        heartbeat_tick=cfg.heartbeat_tick,
        with_health=with_health,
    )

    n_g_in = 3 if with_health else 2
    n_g_out = 1 if with_health else 0
    _, grid, pg_spec, _, g_spec = _tiling(
        G, P, 11 + 6, 0, n_g_in + n_g_out
    )
    out_shape = [jax.ShapeDtypeStruct((P, G), jnp.int32)] * 6
    out_specs = [pg_spec] * 6
    if with_health:
        out_shape = out_shape + [jax.ShapeDtypeStruct((1, G), jnp.int32)]
        out_specs = out_specs + [g_spec]

    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pg_spec] * 11 + [g_spec] * n_g_in,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=platform.pallas_interpret(),
        name=profiling.kernel("raft_steady"),
    )

    def _run(
        st: SimState,
        crashed: jnp.ndarray,
        append_n: jnp.ndarray,
        tsc_in: Optional[jnp.ndarray],
    ):
        # The acting leader is fixed for the whole steady horizon (no
        # elections, constant crash mask), so its tracker row is gathered
        # once outside the kernel and scattered back after.
        is_leader = (st.state == ROLE_LEADER) & ~crashed
        f = is_leader.astype(jnp.int32)
        # dtype= keeps the gathered tracker rows int32 under x64: these
        # feed pallas_call inputs whose BlockSpecs assume int32 (GC007).
        acting_row = jnp.sum(
            st.matched * f[:, None, :], axis=0, dtype=jnp.int32
        )  # [P, G]
        ts_acting = jnp.sum(
            st.term_start_index * f, axis=0, dtype=jnp.int32
        )  # [G]

        inputs = (
            st.state,
            st.term,
            st.election_elapsed,
            st.heartbeat_elapsed,
            st.last_index,
            st.last_term,
            acting_row,
            st.commit,
            st.voter_mask.astype(jnp.int32),
            (st.voter_mask | st.learner_mask).astype(jnp.int32),
            crashed.astype(jnp.int32),
            ts_acting[None, :],
            append_n[None, :],
        )
        if tsc_in is not None:
            inputs = inputs + (tsc_in[None, :],)
        outs = call(*inputs)
        ee, hb, li, lt, new_row, commit = outs[:6]
        tsc_out = outs[6][0] if tsc_in is not None else None
        matched = jnp.where(
            is_leader[:, None, :], new_row[None, :, :], st.matched
        )
        # Pairwise log-agreement update, applied once for the whole horizon
        # (idempotent per round: the sync set is constant while steady, and
        # only the final leader last_index matters).
        member = st.voter_mask | st.learner_mask
        in_s = (member & ~crashed) | is_leader
        lead_last = jnp.max(jnp.where(is_leader, li, 0), axis=0)  # [G]
        lead_row = jnp.sum(
            st.agree * f[:, None, :], axis=0, dtype=jnp.int32
        )  # [P, G]
        agree = jnp.where(
            in_s[:, None, :] & in_s[None, :, :],
            lead_last[None, None, :],
            jnp.where(
                in_s[:, None, :],
                lead_row[None, :, :],
                jnp.where(in_s[None, :, :], lead_row[:, None, :], st.agree),
            ),
        )
        out = st._replace(
            election_elapsed=ee,
            heartbeat_elapsed=hb,
            last_index=li,
            last_term=lt,
            matched=matched,
            commit=commit,
            agree=agree,
        )
        return out, tsc_out

    def fn(
        st: SimState, crashed: jnp.ndarray, append_n: jnp.ndarray
    ) -> SimState:
        return _run(st, crashed, append_n, None)[0]

    def fn_health(
        st: SimState,
        crashed: jnp.ndarray,
        append_n: jnp.ndarray,
        health: HealthState,
    ):
        out, tsc_out = _run(
            st, crashed, append_n, health.planes[HP_SINCE_COMMIT]
        )
        # Closed-form health fold for a steady horizon (see the docstring).
        return out, _steady_health_fold(cfg, rounds, health, tsc_out)

    if not with_counters:
        return fn_health if with_health else fn

    # Counters ride the fused path as a closed-form fold around either
    # variant above (extras order: counters before health, like sim.step).
    if with_health:

        def fn_counted_health(st, crashed, append_n, counters, health):
            out, health2 = fn_health(st, crashed, append_n, health)
            return out, _fold_counters(cfg, rounds, st, out, counters), health2

        return fn_counted_health

    def fn_counted(st, crashed, append_n, counters):
        out = fn(st, crashed, append_n)
        return out, _fold_counters(cfg, rounds, st, out, counters)

    return fn_counted


def _build_chaos_round(
    cfg: SimConfig,
    rounds: int,
    with_health: bool,
    with_counters: bool,
):
    """The chaos-on (loss-gated) fused steady round: see steady_round's
    docstring.  Separate builder so the chaos machinery cannot perturb the
    plain kernel's traced graph (pinned by jaxpr equality in
    tests/test_pallas_step.py)."""
    P = cfg.n_peers
    G = cfg.n_groups
    # The packed roles word budgets 4 bits for leader_id and the rest for
    # heartbeat_elapsed (bound: <= heartbeat_tick) — see the PACKED_PLANES
    # registry (tools/graftcheck/engine/overflow.py).
    assert P <= 15, "packed roles word budgets 4 bits for leader_id"
    assert cfg.heartbeat_tick < (1 << 24), (
        "packed roles word budgets 24 bits for heartbeat_elapsed"
    )
    n_g_in = 5 if with_health else 4
    n_g_out = 1 if with_health else 0
    block, grid, pg_spec, ppg_spec, g_spec = _tiling(
        G, P, 7 + 6, 2 + 1, n_g_in + n_g_out
    )
    kernel = functools.partial(
        _steady_chaos_kernel,
        P=P,
        block=block,
        rounds=rounds,
        election_tick=cfg.election_tick,
        heartbeat_tick=cfg.heartbeat_tick,
        with_health=with_health,
    )
    out_shape = [jax.ShapeDtypeStruct((P, G), jnp.int32)] * 6 + [
        jax.ShapeDtypeStruct((P, P, G), jnp.int32)
    ]
    out_specs = [pg_spec] * 6 + [ppg_spec]
    if with_health:
        out_shape = out_shape + [jax.ShapeDtypeStruct((1, G), jnp.int32)]
        out_specs = out_specs + [g_spec]
    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pg_spec] * 7 + [ppg_spec] * 2 + [g_spec] * n_g_in,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=platform.pallas_interpret(),
        name=profiling.kernel("raft_steady_chaos"),
    )

    def _run(
        st: SimState,
        crashed: jnp.ndarray,
        append_n: jnp.ndarray,
        loss_rate: jnp.ndarray,
        round_base: jnp.ndarray,
        tsc_in: Optional[jnp.ndarray],
    ):
        is_leader = (st.state == ROLE_LEADER) & ~crashed
        f = is_leader.astype(jnp.int32)
        # dtype= keeps the gathered rows int32 under x64 (GC007).
        acting_row = jnp.sum(
            st.matched * f[:, None, :], axis=0, dtype=jnp.int32
        )  # [P, G]
        ts_acting = jnp.sum(
            st.term_start_index * f, axis=0, dtype=jnp.int32
        )  # [G]
        lead_term = jnp.sum(st.term * f, axis=0, dtype=jnp.int32)  # [G]
        member = st.voter_mask | st.learner_mask
        rb = jnp.broadcast_to(
            jnp.reshape(round_base.astype(jnp.int32), (1, 1)), (1, G)
        )
        inputs = (
            _pack_roles(st.state, st.leader_id, st.heartbeat_elapsed),
            st.election_elapsed,
            st.last_index,
            st.last_term,
            st.commit,
            acting_row,
            _pack_masks(st.voter_mask, member, crashed),
            st.agree,
            loss_rate,
            ts_acting[None, :],
            lead_term[None, :],
            append_n[None, :],
            rb,
        )
        if tsc_in is not None:
            inputs = inputs + (tsc_in[None, :],)
        outs = call(*inputs)
        roles, ee, li, lt, commit, new_row, agree = outs[:7]
        tsc_out = outs[7][0] if tsc_in is not None else None
        state, leader_id, hb = _unpack_roles(roles)
        matched = jnp.where(
            is_leader[:, None, :], new_row[None, :, :], st.matched
        )
        out = st._replace(
            state=state,
            leader_id=leader_id,
            election_elapsed=ee,
            heartbeat_elapsed=hb,
            last_index=li,
            last_term=lt,
            matched=matched,
            commit=commit,
            agree=agree,
        )
        return out, tsc_out

    # Static extras layout, resolved at build time (counters before health,
    # sim.step's extras order); None = absent.
    idx_counters = 0 if with_counters else None
    idx_health = (1 if with_counters else 0) if with_health else None

    def fn(st, crashed, append_n, loss_rate, round_base, *extras):
        counters = None if idx_counters is None else extras[idx_counters]
        health = None if idx_health is None else extras[idx_health]
        tsc_in = None if health is None else health.planes[HP_SINCE_COMMIT]
        out, tsc_out = _run(
            st, crashed, append_n, loss_rate, round_base, tsc_in
        )
        res: tuple = (out,)
        if counters is not None:
            res = res + (_fold_counters(cfg, rounds, st, out, counters),)
        if health is not None:
            res = res + (_steady_health_fold(cfg, rounds, health, tsc_out),)
        if idx_counters is None and idx_health is None:
            return out
        return res

    return fn


def _steady_damped_kernel(
    # inputs: roles_ref (packed state|leader_id|hb), ee, li, lt, commit,
    # matched_row (acting leader's tracker row), ra (acting leader's
    # recent_active row, 0/1), masks_ref (packed voter|member|crashed)
    # [P, B]; agree [P, P, B] [+ loss_rate [P, P, B] when with_loss];
    # ts, lead_term, app [1, B] [+ round_base when with_loss, tsc when
    # with_health]; outputs: roles, ee, li, lt, commit, matched_row, ra,
    # agree [+ tsc].
    *refs,
    P: int,
    block: int,
    rounds: int,
    election_tick: int,
    heartbeat_tick: int,
    with_health: bool,
    with_cq: bool,
    with_loss: bool,
):
    """The damping-on steady round: k rounds of sim._damped_linked_step's
    wave replay specialized to the steady invariant (uniform terms among
    alive peers, one alive acting leader, all links up among alive peers,
    no campaign can fire), bit-identically — including the check-quorum
    read-and-clear `recent_active` cycle at the leader's election-timeout
    boundary (`with_cq`; the steady predicate proves every in-horizon
    boundary passes, so the boundary's only effect is the clear), the
    damped probe rule (first-probe prev from modeled cursors, retry-chain
    adoption whose acks land one stage later than probe-matched ones), and
    — `with_loss` — the chaos engine's in-kernel per-link loss draws.
    Leases and the low-term nudge are provably dormant on a steady horizon
    (no vote requests, uniform terms), so they need no carry."""
    n_in = 12 + (2 if with_loss else 0) + (1 if with_health else 0)
    i = 0
    (
        roles_ref, ee_ref, li_ref, lt_ref, commit_ref, matched_ref,
        ra_ref, masks_ref, agree_ref,
    ) = refs[:9]
    i = 9
    if with_loss:
        loss_ref = refs[i]
        i += 1
    ts_ref, ltm_ref, app_ref = refs[i : i + 3]
    i += 3
    if with_loss:
        rb_ref = refs[i]
        i += 1
    if with_health:
        tsc_ref = refs[i]
    (
        roles_out, ee_out, li_out, lt_out, commit_out, matched_out,
        ra_out, agree_out,
    ) = refs[n_in : n_in + 8]
    state, leader_id, hb = _unpack_roles(roles_ref[...])
    voter, member, crashed = _unpack_masks(masks_ref[...])
    ee = ee_ref[...]
    li = li_ref[...]
    lt = lt_ref[...]
    commit = commit_ref[...]
    matched_row = matched_ref[...]
    ra = ra_ref[...] != 0  # [P, B] the acting leader's recent_active row
    agree = agree_ref[...]
    ts = ts_ref[...]  # [1, B] acting leader's term_start_index
    ltm = ltm_ref[...]  # [1, B] acting leader's term
    app = app_ref[...]  # [1, B]
    if with_loss:
        loss_rate = loss_ref[...]  # [P, P, B]
        round_base = rb_ref[...]  # [1, B]
    if with_health:
        tsc = tsc_ref[...]
        maxc_prev = jnp.max(commit, axis=0, keepdims=True)

    alive = ~crashed
    role_leader = state == ROLE_LEADER
    is_lead = role_leader & alive  # exactly one per group by the predicate
    has_leader = jnp.any(is_lead, axis=0, keepdims=True)  # [1, B]
    lead_f = is_lead.astype(jnp.int32)
    p_iota = jax.lax.broadcasted_iota(jnp.int32, (P, 1), 0)
    # dtype= on every sum: see _steady_kernel (GC007).
    lead_id_val = jnp.sum(
        lead_f * (p_iota + 1), axis=0, keepdims=True, dtype=jnp.int32
    )
    count = jnp.sum(voter, axis=0, keepdims=True, dtype=jnp.int32)
    qpos = count // 2
    n_app = jnp.where(has_leader, app, 0)  # [1, B]
    sent_b = has_leader & (n_app > 0)
    if with_loss:
        gids = (
            jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
            + pl.program_id(0) * block
        ).astype(jnp.uint32)
        s_io = jax.lax.broadcasted_iota(jnp.uint32, (P, P, 1), 0)
        d_io = jax.lax.broadcasted_iota(jnp.uint32, (P, P, 1), 1)
        lane = s_io * jnp.uint32(P) + d_io + jnp.uint32(1)

    def lead_gather(plane):  # [P, B] -> [1, B]: the acting leader's value
        return jnp.sum(plane * lead_f, axis=0, keepdims=True, dtype=jnp.int32)

    def agree_event(agree, in_set, value):
        # sim._merge_agree with the acting leader as the sender — the
        # same shared triple-where as the chaos kernel.
        return _agree_event(agree, in_set, value, lead_f)

    def agree_lead(agree):  # [P, B]: agree[leader, :] right now
        return jnp.sum(agree * lead_f[:, None, :], axis=0, dtype=jnp.int32)

    for r in range(rounds):
        if with_loss:
            # Seeded per-link loss — the round's single delivery draw,
            # from the same shared in-kernel PRNG as the chaos kernel.
            drop = _kernel_loss_draw(round_base, r, gids, lane, loss_rate)
            dfl = jnp.any(drop & is_lead[:, None, :], axis=0)  # [P, B]
            dtl = jnp.any(drop & is_lead[None, :, :], axis=1)
            fwd = ~dfl & alive & ~is_lead
            rev = ~dtl & alive & ~is_lead
        else:
            fwd = alive & ~is_lead
            rev = fwd

        # --- tick, incl. the leader's election-timeout boundary.  With
        # check-quorum the boundary READS-AND-CLEARS the leader's
        # recent_active row; the predicate proves the read passes (and
        # that no crashed stale leader reaches its boundary), so the
        # deposition/heartbeat-suppression arms are provably dead.
        ee = ee + 1
        boundary = role_leader & (ee >= election_tick)
        ee = jnp.where(boundary, 0, ee)
        if with_cq:
            lead_bnd = jnp.any(
                boundary & is_lead, axis=0, keepdims=True
            )  # [1, B]
            # Clear to the self row.  Spelled with and/or, not jnp.where:
            # Mosaic cannot lower a select between two bool vectors
            # (i8 -> i1 truncation) on the v5e.
            ra = (lead_bnd & is_lead) | (~lead_bnd & ra)
        hb = jnp.where(role_leader, hb + 1, hb)
        want_beat = role_leader & (hb >= heartbeat_tick)
        hb = jnp.where(want_beat, 0, hb)
        beat = jnp.any(want_beat & is_lead, axis=0, keepdims=True)  # [1, B]

        # Round-start snapshots of the acting leader's cursors.
        c_l = lead_gather(commit)  # [1, B]
        li_l = lead_gather(li)
        lt_l = lead_gather(lt)

        # --- wave 1: heartbeat delivery (terms uniform: every delivered
        # heartbeat is accepted, no nudges can fire).
        h_acc = fwd & beat & member
        state = jnp.where(h_acc, ROLE_FOLLOWER, state)
        leader_id = jnp.where(h_acc, lead_id_val, leader_id)
        ee = jnp.where(h_acc, 0, ee)
        hb_val = jnp.minimum(matched_row, c_l)
        commit = jnp.where(h_acc, jnp.maximum(commit, hb_val), commit)

        # --- wave 2a: heartbeat responses resume probes and set the
        # leader's recent_active bits; lagging members trigger catch-up.
        resumed = h_acc & rev
        ra = ra | resumed
        cu = resumed & (matched_row < li_l)

        # --- wave 3: catch-up appends with the DAMPED probe rule: prev
        # comes from the modeled cursor (never-acked members probe from
        # the election noop), non-matching probes start a retry chain
        # whose wholesale adoption lands after stage A and whose ack
        # folds only at the wave-6 stage (sim._damped_linked_step).
        agree_l = agree_lead(agree)
        prev3 = jnp.where(matched_row == 0, ts - 1, li_l)
        probe3 = agree_l >= prev3
        adopt3 = cu & probe3
        retry3 = cu & ~probe3  # cu implies the reverse link is up
        commit = jnp.where(adopt3, jnp.maximum(commit, c_l), commit)
        li = jnp.where(adopt3, li_l, li)
        lt = jnp.where(adopt3, lt_l, lt)
        agree = agree_event(
            agree,
            adopt3 | (is_lead & jnp.any(adopt3, axis=0, keepdims=True)),
            li_l,
        )
        ack3 = adopt3

        # --- wave 4: stage fold over the probe-matched acks + stage-A
        # quorum commit at the leader.
        matched_row = jnp.where(
            ack3, jnp.maximum(matched_row, li_l), matched_row
        )
        ra = ra | ack3
        mci = _quorum_tile(matched_row, voter, qpos, P)
        ok_a = has_leader & (count > 0) & (mci >= ts)
        c_new = jnp.where(ok_a, jnp.maximum(c_l, mci), c_l)
        adv = c_new > c_l
        commit = jnp.where(is_lead, c_new, commit)

        # --- wave-3 retry resends (the surviving maybe_decr chain): the
        # resend lands as wholesale adoption AFTER stage A; its ack joins
        # the wave-6 fold below.
        commit = jnp.where(retry3, jnp.maximum(commit, c_l), commit)
        li = jnp.where(retry3, li_l, li)
        lt = jnp.where(retry3, lt_l, lt)
        agree = agree_event(
            agree,
            retry3 | (is_lead & jnp.any(retry3, axis=0, keepdims=True)),
            li_l,
        )

        # --- wave 5: the commit-advance re-broadcast to sendable members
        # (Replicate probes + freshly resumed ones), damped probe rule.
        agree_l2 = agree_lead(agree)
        sendable = (matched_row > 0) | resumed
        rb5 = fwd & member & adv & sendable
        prev5 = jnp.where(matched_row == 0, ts - 1, li_l)
        probe5 = agree_l2 >= prev5
        adopt5 = rb5 & probe5
        retry5 = rb5 & ~probe5 & rev
        state = jnp.where(rb5, ROLE_FOLLOWER, state)
        leader_id = jnp.where(rb5, lead_id_val, leader_id)
        ee = jnp.where(rb5, 0, ee)
        li = jnp.where(adopt5, li_l, li)
        lt = jnp.where(adopt5, lt_l, lt)
        agree = agree_event(
            agree,
            adopt5 | (is_lead & jnp.any(adopt5, axis=0, keepdims=True)),
            li_l,
        )
        li = jnp.where(retry5, li_l, li)
        lt = jnp.where(retry5, lt_l, lt)
        agree = agree_event(
            agree,
            retry5 | (is_lead & jnp.any(retry5, axis=0, keepdims=True)),
            li_l,
        )
        ack5 = (adopt5 & rev) | retry3 | retry5

        # --- wave 6: stage fold over the deferred acks + stage-B commit,
        # then the settled commit propagates to sendable members.
        matched_row = jnp.where(
            ack5, jnp.maximum(matched_row, li_l), matched_row
        )
        ra = ra | ack5
        mci2 = _quorum_tile(matched_row, voter, qpos, P)
        ok_b = has_leader & (count > 0) & (mci2 >= ts)
        c_new2 = jnp.where(ok_b, jnp.maximum(c_new, mci2), c_new)
        commit = jnp.where(is_lead, c_new2, commit)
        agree_l3 = agree_lead(agree)
        sendable2 = (matched_row > 0) | resumed
        elig6 = (
            fwd
            & member
            & sendable2
            & ((agree_l3 >= li_l) | rev)
            & (c_new2 > c_l)
        )
        commit = jnp.where(elig6, jnp.maximum(commit, c_new2), commit)
        ra = ra | (elig6 & rev)

        # --- the round's append workload at the acting leader (nudge
        # cutoffs on its ack stream are provably empty: terms uniform).
        li = li + jnp.where(is_lead, n_app, 0)
        lt = jnp.where(is_lead & sent_b, ltm, lt)
        lead_last = li_l + n_app  # [1, B]
        pr_ok = (matched_row > 0) | resumed
        send_w = sent_b & fwd & member & pr_ok
        agree_l4 = agree_lead(agree)
        probe_w = agree_l4 >= jnp.where(matched_row == 0, ts - 1, li_l)
        sync_b = send_w & (probe_w | rev)
        state = jnp.where(send_w, ROLE_FOLLOWER, state)
        leader_id = jnp.where(send_w, lead_id_val, leader_id)
        ee = jnp.where(send_w, 0, ee)
        li = jnp.where(sync_b, lead_last, li)
        lt = jnp.where(sync_b, ltm, lt)
        ack_w = sync_b & rev
        acked = ack_w | (is_lead & sent_b)
        matched_row = jnp.where(
            acked, jnp.maximum(matched_row, lead_last), matched_row
        )
        ra = ra | ack_w
        agree = agree_event(agree, sync_b | (is_lead & sent_b), lead_last)
        mci3 = _quorum_tile(matched_row, voter, qpos, P)
        ok_c = sent_b & (count > 0) & (mci3 >= ts)
        lead_commit = jnp.where(ok_c, jnp.maximum(c_new2, mci3), c_new2)
        commit = jnp.where(is_lead, lead_commit, commit)
        commit = jnp.where(
            sync_b, jnp.maximum(commit, lead_commit), commit
        )

        if with_health:
            maxc = jnp.max(commit, axis=0, keepdims=True)
            tsc = jnp.where(maxc > maxc_prev, 0, tsc + 1)
            maxc_prev = maxc

    roles_out[...] = _pack_roles(state, leader_id, hb)
    ee_out[...] = ee
    li_out[...] = li
    lt_out[...] = lt
    commit_out[...] = commit
    matched_out[...] = matched_row
    ra_out[...] = ra.astype(jnp.int32)
    agree_out[...] = agree
    if with_health:
        refs[n_in + 8][...] = tsc


def _build_damped_round(
    cfg: SimConfig,
    rounds: int,
    with_health: bool,
    with_counters: bool,
    with_chaos: bool,
):
    """The damping-on fused steady round (check_quorum/pre_vote configs):
    see steady_round's docstring.  Separate builder — like the chaos one —
    so the damped machinery cannot perturb the undamped kernels' traced
    graphs (pinned by jaxpr equality in tests/test_pallas_step.py)."""
    P = cfg.n_peers
    G = cfg.n_groups
    assert P <= 15, "packed roles word budgets 4 bits for leader_id"
    assert cfg.heartbeat_tick < (1 << 24), (
        "packed roles word budgets 24 bits for heartbeat_elapsed"
    )
    n_ppg_in = 2 if with_chaos else 1
    n_g_in = 3 + (1 if with_chaos else 0) + (1 if with_health else 0)
    n_g_out = 1 if with_health else 0
    block, grid, pg_spec, ppg_spec, g_spec = _tiling(
        G, P, 8 + 7, n_ppg_in + 1, n_g_in + n_g_out
    )
    kernel = functools.partial(
        _steady_damped_kernel,
        P=P,
        block=block,
        rounds=rounds,
        election_tick=cfg.election_tick,
        heartbeat_tick=cfg.heartbeat_tick,
        with_health=with_health,
        with_cq=cfg.check_quorum,
        with_loss=with_chaos,
    )
    out_shape = [jax.ShapeDtypeStruct((P, G), jnp.int32)] * 7 + [
        jax.ShapeDtypeStruct((P, P, G), jnp.int32)
    ]
    out_specs = [pg_spec] * 7 + [ppg_spec]
    if with_health:
        out_shape = out_shape + [jax.ShapeDtypeStruct((1, G), jnp.int32)]
        out_specs = out_specs + [g_spec]
    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pg_spec] * 8 + [ppg_spec] * n_ppg_in + [g_spec] * n_g_in,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=platform.pallas_interpret(),
        name=profiling.kernel("raft_steady_damped"),
    )

    def _run(
        st: SimState,
        crashed: jnp.ndarray,
        append_n: jnp.ndarray,
        loss_rate: Optional[jnp.ndarray],
        round_base: Optional[jnp.ndarray],
        tsc_in: Optional[jnp.ndarray],
    ):
        if st.recent_active is None:
            raise ValueError(
                "fused damped round needs the recent_active plane but the "
                "state has None — this state was built for an undamped "
                "config; rebuild it with init_state(cfg)"
            )
        is_leader = (st.state == ROLE_LEADER) & ~crashed
        f = is_leader.astype(jnp.int32)
        # dtype= keeps the gathered rows int32 under x64 (GC007).
        acting_row = jnp.sum(
            st.matched * f[:, None, :], axis=0, dtype=jnp.int32
        )  # [P, G]
        ra_row = jnp.any(
            st.recent_active & is_leader[:, None, :], axis=0
        )  # [P, G] bool
        ts_acting = jnp.sum(
            st.term_start_index * f, axis=0, dtype=jnp.int32
        )  # [G]
        lead_term = jnp.sum(st.term * f, axis=0, dtype=jnp.int32)  # [G]
        member = st.voter_mask | st.learner_mask
        # Crashed stale leaders' frozen tracker rows need no carry: the
        # damped wave path's per-round stage folds are idempotent for an
        # owner whose row receives no acks, and every state REACHABLE
        # through that path leaves each stale owner's commit already
        # settled against its frozen row at the round boundary — so k
        # fused rounds that leave them untouched are bit-identical to k
        # general rounds (pinned per configuration in
        # tests/test_pallas_step.py).
        inputs = (
            _pack_roles(st.state, st.leader_id, st.heartbeat_elapsed),
            st.election_elapsed,
            st.last_index,
            st.last_term,
            st.commit,
            acting_row,
            ra_row.astype(jnp.int32),
            _pack_masks(st.voter_mask, member, crashed),
            st.agree,
        )
        if loss_rate is not None:
            inputs = inputs + (loss_rate,)
        inputs = inputs + (
            ts_acting[None, :],
            lead_term[None, :],
            append_n[None, :],
        )
        if round_base is not None:
            rb = jnp.broadcast_to(
                jnp.reshape(round_base.astype(jnp.int32), (1, 1)), (1, G)
            )
            inputs = inputs + (rb,)
        if tsc_in is not None:
            inputs = inputs + (tsc_in[None, :],)
        outs = call(*inputs)
        roles, ee, li, lt, commit, new_row, ra_new, agree = outs[:8]
        tsc_out = outs[8][0] if tsc_in is not None else None
        state, leader_id, hb = _unpack_roles(roles)
        matched = jnp.where(
            is_leader[:, None, :], new_row[None, :, :], st.matched
        )
        recent_active = jnp.where(
            is_leader[:, None, :], (ra_new != 0)[None, :, :],
            st.recent_active,
        )
        out = st._replace(
            state=state,
            leader_id=leader_id,
            election_elapsed=ee,
            heartbeat_elapsed=hb,
            last_index=li,
            last_term=lt,
            matched=matched,
            commit=commit,
            agree=agree,
            recent_active=recent_active,
        )
        return out, tsc_out

    # Static extras layout (counters before health, sim.step's order).
    idx_counters = 0 if with_counters else None
    idx_health = (1 if with_counters else 0) if with_health else None

    def fn(st, crashed, append_n, *rest):
        if with_chaos:  # graftcheck: allow-no-python-branch-on-traced — closes over the static builder flag (trace-time constant)
            loss_rate, round_base = rest[0], rest[1]
            extras = rest[2:]
        else:
            loss_rate = round_base = None
            extras = rest
        counters = None if idx_counters is None else extras[idx_counters]
        health = None if idx_health is None else extras[idx_health]
        tsc_in = None if health is None else health.planes[HP_SINCE_COMMIT]
        out, tsc_out = _run(
            st, crashed, append_n, loss_rate, round_base, tsc_in
        )
        res: tuple = (out,)
        if counters is not None:
            res = res + (_fold_counters(cfg, rounds, st, out, counters),)
        if health is not None:
            res = res + (_steady_health_fold(cfg, rounds, health, tsc_out),)
        if idx_counters is None and idx_health is None:
            return out
        return res

    return fn


def steady_mask(
    cfg: SimConfig,
    st: SimState,
    crashed: jnp.ndarray,
    horizon: int = 1,
    link: Optional[jnp.ndarray] = None,
    reconfig_pending: Optional[jnp.ndarray] = None,
    loss_rate: Optional[jnp.ndarray] = None,
    read_pending: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """bool[G]: per-group steady invariant for the next `horizon` rounds —
    no election timer can fire, exactly one alive leader, every alive peer
    already at the leader's term, not in joint config.

    `reconfig_pending` (optional bool[G] — reconfig.pending_in_horizon:
    groups with a conf entry in flight OR a scheduled op becoming eligible
    within the horizon) is a hard rejection: the fused kernel can neither
    append the conf entry, evaluate the dual-majority commit gate, nor
    swap the mask planes mid-horizon, so any horizon containing a
    scheduled reconfig must take the general path (ISSUE 10; the joint
    window itself is already rejected by the not-joint condition below).
    None keeps every existing graph unchanged.

    With `link` (the chaos engine's bool[P, P, G] reachability plane) the
    invariant additionally requires every directed link among alive peers
    to be up (a fully-healed plane always satisfies this), and the
    election-timer bound falls back to the fully conservative free-running
    form: per-link LOSS may drop any heartbeat, so the per-round re-sync
    that lets the heartbeat_tick == 1 fast bound assume ee -> 0 cannot be
    relied on.

    Election damping (SimConfig.check_quorum / pre_vote) adds its own
    conditions (ISSUE 8; previously damping-on configs were rejected
    wholesale).  The election-timer bound is always the conservative
    free-running form (the same `election_tick > horizon` regime as
    chaos), so the dormancy of pre-vote and the low-term nudge is
    provable: nobody campaigns, terms stay uniform.  With check_quorum
    the leader's election-timeout boundary READS the recent_active row:
    the lossless branch proves every in-horizon boundary passes
    (kernels.cq_boundary_safe — the leader's row holds an active quorum
    NOW, the alive voters re-saturate it each heartbeat interval, and no
    crashed stale leader reaches its boundary); the lossy (`link=`)
    branch cannot prove re-saturation and requires that NO role-leader
    reaches its boundary at all.

    `loss_rate` (optional int32[P, P, G], only meaningful with `link`)
    makes the lossy check-quorum bound PER GROUP (ISSUE 11): a group
    whose loss rates are all zero delivers every heartbeat a healed link
    plane carries, so the LOSSLESS saturation argument
    (kernels.cq_boundary_safe) applies to it even on a chaos horizon;
    only groups with a nonzero rate anywhere keep the conservative
    no-boundary-in-horizon bound.  None preserves the historical
    all-groups conservative form byte-for-byte.

    `read_pending` (optional bool[G] — workload.reads_pending_in_horizon:
    groups with an OUTSTANDING client read, any mode, or a scheduled
    Safe-mode fire inside the horizon) is a hard rejection like
    reconfig_pending (ISSUE 13): the fused kernel can run neither arm of
    the ReadIndex quorum round (the ctx-ack accumulation and the damped
    nudge cutoff are wave logic).  Pure LEASE fires deliberately do NOT
    reject — a lease serve touches no message planes, so a steady horizon
    whose entry gate passes (kernels.lease_read, heartbeat_tick == 1)
    provably serves every in-horizon lease fire at latency 0 and the
    workload split runner folds those receipts closed-form
    (the workload split runner; fused-vs-general bit-parity in
    tests/test_workload.py).  None keeps every existing graph
    unchanged."""
    for flag in planes.steady_defuse_flags():
        # Registry-driven wholesale defuse (planes.py steady == "defuse";
        # today only `blackbox`, ISSUE 15): the fused kernel cannot fold
        # these rows' per-round wave-path writes (the black-box ring
        # trace), so configs enabling them reject every fused horizon and
        # ride the general path,
        # and graphs with every defuse flag off are untouched (this is a
        # python-level branch on static config fields).
        if getattr(cfg, flag):  # graftcheck: allow-no-python-branch-on-traced — `flag` names a static SimConfig bool (registry steady == "defuse"; GC016 pins the field's existence), so this getattr is a trace-time constant
            return jnp.zeros((cfg.n_groups,), bool)
    damped = cfg.check_quorum or cfg.pre_vote
    if damped and cfg.election_tick <= cfg.heartbeat_tick:
        # The check-quorum saturation argument needs one full heartbeat
        # interval strictly inside each boundary window; degenerate
        # configs fall back to the general damped wave path.
        return jnp.zeros((cfg.n_groups,), bool)
    alive = ~crashed
    # 1. nobody can campaign within the horizon.  With heartbeat_tick == 1
    # an alive follower under a live leader is re-synced (ee -> 0) every
    # round, so only its FIRST tick uses the current ee; crashed peers'
    # timers run free for the whole horizon.  For larger heartbeat ticks —
    # and under damping, where free-running timers are what proves
    # pre-vote/nudge dormancy — we fall back to the fully conservative
    # free-running bound.
    non_leader_voter = (st.state != ROLE_LEADER) & st.voter_mask
    if cfg.heartbeat_tick == 1 and link is None and not damped:
        may_fire = non_leader_voter & (
            jnp.where(
                alive,
                st.election_elapsed + 1,
                st.election_elapsed + horizon,
            )
            >= st.randomized_timeout
        )
        # ...and the per-round reset must keep later rounds safe too:
        # 1 tick from a reset timer can never reach rt (rt >= election_tick
        # >= 2 by Config.validate), so no extra condition is needed.
    else:
        may_fire = non_leader_voter & (
            st.election_elapsed + horizon >= st.randomized_timeout
        )
    no_campaign = ~jnp.any(may_fire, axis=0)  # [G]
    # 2. exactly one alive leader per group
    is_leader = (st.state == ROLE_LEADER) & alive
    one_leader = jnp.sum(is_leader.astype(jnp.int32), axis=0) == 1
    # 3. alive peers at the leader's term
    lead_term = jnp.max(jnp.where(is_leader, st.term, 0), axis=0)
    terms_ok = jnp.all(jnp.where(alive, st.term == lead_term, True), axis=0)
    # 4. not joint (the fused kernel computes the single-majority quorum;
    # joint groups take the general XLA path)
    not_joint = ~jnp.any(st.outgoing_mask, axis=0)
    ok = no_campaign & one_leader & terms_ok & not_joint
    if st.transferee is not None:
        # 4b'. no pending leader transfer anywhere in the group (ISSUE
        # 12): the fused kernel can neither pump the catch-up /
        # MsgTimeoutNow protocol nor enforce the transfer's
        # ProposalDropped gate, so a horizon containing one must take
        # the general path.  The transferee plane rides through a fused
        # block untouched (it is provably all-zero here); transfer-off
        # states (transferee=None) keep every existing graph unchanged.
        ok = ok & ~jnp.any(st.transferee > 0, axis=0)
    if reconfig_pending is not None:
        # 4b. no scheduled reconfig touches the horizon (see docstring).
        ok = ok & ~reconfig_pending
    if read_pending is not None:
        # 4c. no quorum-round read work touches the horizon (ISSUE 13;
        # see docstring — lease fires stay fusable and are folded by the
        # caller).
        ok = ok & ~read_pending
    if link is not None:
        # 5. every directed link among alive peers is up (crashed peers'
        # links and self-links are dead weight either way).
        eye = jnp.eye(cfg.n_peers, dtype=bool)[:, :, None]
        links_ok = jnp.all(
            link | eye | crashed[:, None, :] | crashed[None, :, :],
            axis=(0, 1),
        )
        ok = ok & links_ok
    if damped and cfg.check_quorum:
        # 6. every check-quorum boundary inside the horizon provably
        # passes.  Lossless: kernels.cq_boundary_safe (leader row holds
        # an active quorum now; alive voters re-saturate it every
        # heartbeat interval; crashed stale leaders never reach their
        # boundary).  Lossy: a dropped heartbeat breaks the saturation
        # proof, so no role-leader may reach its boundary at all (the
        # conservative free-running bound on the cq boundary).
        if st.recent_active is None:
            raise ValueError(
                "steady_mask for a check_quorum config needs the "
                "recent_active plane but the state has None — this state "
                "was built for an undamped config; rebuild it with "
                "init_state(cfg)"
            )
        if link is None:
            ok = ok & kernels_mod.cq_boundary_safe(
                st.recent_active,
                st.voter_mask,
                st.outgoing_mask,
                st.state,
                crashed,
                st.election_elapsed,
                horizon,
                cfg.election_tick,
            )
        elif loss_rate is not None:
            # Per-group lossy bound (ISSUE 11): only groups with a
            # nonzero loss rate anywhere need the conservative
            # no-boundary form; loss-free groups keep the lossless
            # saturation proof.
            ok = ok & kernels_mod.cq_boundary_safe(
                st.recent_active,
                st.voter_mask,
                st.outgoing_mask,
                st.state,
                crashed,
                st.election_elapsed,
                horizon,
                cfg.election_tick,
                lossy=jnp.any(loss_rate != 0, axis=(0, 1)),
            )
        else:
            role_lead = st.state == ROLE_LEADER
            no_boundary = jnp.all(
                jnp.where(
                    role_lead,
                    st.election_elapsed + jnp.int32(horizon)
                    < jnp.int32(cfg.election_tick),
                    True,
                ),
                axis=0,
            )
            ok = ok & no_boundary
    return ok


def steady_predicate(
    cfg: SimConfig,
    st: SimState,
    crashed: jnp.ndarray,
    horizon: int = 1,
    link: Optional[jnp.ndarray] = None,
    loss_rate: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """True iff EVERY group satisfies the steady invariant (see
    steady_mask)."""
    return jnp.all(
        steady_mask(cfg, st, crashed, horizon, link, loss_rate=loss_rate)
    )
