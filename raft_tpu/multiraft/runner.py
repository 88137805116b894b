"""The compiled-runner factory: ONE entry point, :func:`make_runner`,
builds every whole-scenario runner — chaos-only, reconfig(+chaos), client
workload, the two split-horizon variants, and the autopilot cadence
segment — from the schedule registry (schedules.py) over the shared scan
body (:func:`_runner_body`, the one general round: client traffic, chaos
masks, the op protocol, the audits and the folds).  make_runner's
docstring is the dispatch table and each variant's contract.  The split
variants are also the ONE place a fused kernel is chosen over the general
round (``block_run`` / ``fused_block_run``: pallas_step.steady_mask, one
``lax.cond``, pallas_step.steady_round or a scan of the body).  A
workload split call of a few blocks is ONE program, as the scan runners'
is (``segment_run``, exposed as ``.jitted``: ``block_run`` under an
unrolled ``lax.scan`` over the tabled block rows); a longer one, and the
reconfig split runner, dispatch a program a block from a short host loop
(_SEGMENT_MAX_BLOCKS says why).

The schedule modules (chaos, reconfig, workload) know nothing of this
one; ``ClusterSim`` and the autopilot call it.

Registry discipline (GC018): every schedule array crosses the jit
boundary as a RUNTIME argument (GC012) in its family's registry order —
:func:`flatten` / :func:`rebuild` / :func:`schedule_args` are the ONLY
way schedule tuples are assembled or rebound here, so the flat arg
order, the compiled NamedTuple field order, and the registry rows
cannot drift apart.  Hand-listing a schedule tuple or reading a
closed-over compiled schedule inside a jitted body fails the build.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import profiling
from . import chaos as chaos_mod
from . import kernels
from . import reconfig as reconfig_mod
from . import schedules as schedules_mod
from . import sim as sim_mod
from . import workload as workload_mod

__all__ = [
    "make_runner",
    "flatten",
    "rebuild",
    "rebuild_scheds",
    "schedule_args",
    "family_of",
]


# --- registry-driven schedule plumbing (the GC012/GC018 boundary) -----------

# Compiled-tuple type -> registry family; the single classification
# table the dispatcher and the flat-arg helpers share.
_FAMILY_TYPES: Tuple[Tuple[str, type], ...] = (
    ("chaos", chaos_mod.CompiledChaos),
    ("reconfig", reconfig_mod.CompiledReconfig),
    ("client", workload_mod.CompiledClient),
)


def family_of(compiled) -> str:
    """Registry family name of one compiled schedule tuple."""
    for name, typ in _FAMILY_TYPES:
        if isinstance(compiled, typ):
            return name
    raise TypeError(
        f"not a compiled schedule: {type(compiled).__name__} (expected "
        "chaos.CompiledChaos, reconfig.CompiledReconfig, or "
        "workload.CompiledClient)"
    )


def flatten(family: str, compiled) -> Tuple:
    """One compiled schedule as its flat runtime-arg tuple, in registry
    order (schedules.array_fields — GC012: these enter the jit as
    arguments, never closure consts)."""
    return tuple(
        getattr(compiled, f) for f in schedules_mod.array_fields(family)
    )


def rebuild(family: str, template, args):
    """Rebind a flat runtime-arg tuple onto its compiled template —
    the inverse of :func:`flatten`, inside the jit."""
    fields = schedules_mod.array_fields(family)
    return template._replace(**dict(zip(fields, args[: len(fields)])))


def schedule_args(*scheds) -> Tuple:
    """Flat runtime-arg tuple for several compiled schedules, each in
    its family's registry order, ``None`` entries skipped — the exact
    trailing argument list of every runner jit here."""
    out: Tuple = ()
    for s in scheds:
        if s is not None:
            out = out + flatten(family_of(s), s)
    return out


def rebuild_scheds(compiled, chaos_compiled, sched_args):
    """Rebind the runtime schedule arguments onto the compiled reconfig
    (+ optional chaos) templates (GC012) — the shared rebuild of every
    _runner_body-based runner."""
    n = len(schedules_mod.array_fields("reconfig"))
    sched = rebuild("reconfig", compiled, sched_args[:n])
    if chaos_compiled is not None:
        chaos_sched = rebuild("chaos", chaos_compiled, sched_args[n:])
    else:
        chaos_sched = None
    return sched, chaos_sched


# --- the round every variant but the chaos scan runs --------------------


def _validate_plans(
    cfg: sim_mod.SimConfig,
    compiled: reconfig_mod.CompiledReconfig,
    chaos_compiled: Optional[chaos_mod.CompiledChaos],
) -> None:
    """The runner-input compatibility checks of the reconfig scan and
    split runners: equal horizons, agreeing peer counts."""
    if chaos_compiled is not None:
        if chaos_compiled.n_rounds != compiled.n_rounds:
            raise ValueError(
                f"chaos plan spans {chaos_compiled.n_rounds} rounds but "
                f"the reconfig plan spans {compiled.n_rounds} — phases "
                "must cover the same horizon to compose in one scan"
            )
        if chaos_compiled.n_peers != compiled.n_peers:
            raise ValueError("chaos and reconfig plans disagree on peers")
    if compiled.n_peers != cfg.n_peers:
        raise ValueError(
            f"plan has {compiled.n_peers} peers but cfg.n_peers == "
            f"{cfg.n_peers}"
        )


def _runner_body(
    cfg: sim_mod.SimConfig,
    sched: reconfig_mod.CompiledReconfig,
    chaos_sched: Optional[chaos_mod.CompiledChaos],
    with_counters: bool = False,
    actions: Optional[Tuple] = None,
    client=None,
):
    """One general round of the compiled reconfig(+chaos) scenario as a
    lax.scan body over the absolute round index — the SINGLE source of the
    op propose/gate/apply protocol, shared by every runner.make_runner
    variant: the reconfig runner's whole-horizon scan, the split runners'
    general segments / fused-block fallback, the autopilot's cadence
    segment, and the client-workload runner.

    Carry: (state, health, rstate, stats, rstats, safety) with an
    [N_COUNTERS] int32 plane appended when `with_counters` (the split
    runner's production configuration threads it; the scan runner keeps
    the historical carry and graph).

    `actions` (ISSUE 12, the autopilot's device-resident actuation) is an
    optional (action_round, transfer_plane int32[G], kick_plane
    bool[P, G]) triple: at the one round whose absolute index equals
    `action_round` the transfer commands and campaign kicks are handed to
    sim.step; every other round passes the zero action.  None keeps the
    historical graphs byte-identical.

    `client` (ISSUE 13, the compiled client workload — a
    workload.CompiledClient rebuilt from runtime args) appends
    (read_carry, read_stats[workload.N_READ_STATS],
    lat_hist[workload.N_LAT_BUCKETS]) to the carry: each round gathers
    the schedule's read fires and append skew, retries outstanding reads
    through `sim.step(read_propose=)`, folds per-read latency-in-rounds
    into the on-device histogram, and runs kernels.check_safety's
    linearizability slots (lease-holder mask off the round-ENTRY state)
    alongside the joint-window audit.  None keeps every historical graph
    byte-identical.  Where the read carry arrives as a
    workload.LearnerLagCarry (ISSUE 47: ClusterSim.run_reads on a fleet
    that boots with learners) each round also folds
    chaos.fold_learner_lag into its count; a plain ReadCarry keeps the
    graph it had.

    Black-box forensics (ISSUE 15, SimConfig.blackbox): the carry gains
    a TRAILING sim.BlackboxState; each round folds
    kernels.check_safety_groups instead of check_safety — summing the
    per-group indicators into the IDENTICAL safety counts
    (tests/test_forensics.py pins the slot-for-slot equality) — and
    records the post-round trace plus the fired (group, round) pairs in
    one kernels.blackbox_fold.  blackbox=False keeps every historical
    graph byte-identical."""
    P, G = cfg.n_peers, cfg.n_groups
    with_bb = cfg.blackbox

    def body(carry, r):
        bb = None
        if with_bb:
            carry, bb = carry[:-1], carry[-1]
        rcar = rdstats = lat_hist = lag = None
        if client is not None:
            carry, (rcar, rdstats, lat_hist) = carry[:-3], carry[-3:]
            if isinstance(rcar, workload_mod.LearnerLagCarry):
                rcar, lag = rcar
        if with_counters:
            st, hl, rst, stats, rstats, safety, ctrs = carry
        else:
            st, hl, rst, stats, rstats, safety = carry
            ctrs = None
        # What the round is offered, under `runner.client` (the names the
        # device ops carry in a trace; they change no equation).
        with profiling.scope("runner.client"):
            ph = sched.phase_of_round[r]
            append = sched.append[ph]
        if chaos_sched is not None:
            link, crashed, capp = chaos_mod.schedule_masks(chaos_sched, r)
            with profiling.scope("runner.client"):
                append = append + capp
        else:
            link = None
            with profiling.scope("runner.client"):
                crashed = jnp.zeros((P, G), bool)
        if actions is not None:
            act_round, transfer_plane, kick_plane = actions
            with profiling.scope("runner.client"):
                fire = r == act_round
                transfer_propose = jnp.where(fire, transfer_plane, 0)
                campaign_kick = kick_plane & fire
        else:
            transfer_propose = None
            campaign_kick = None
        if client is not None:
            with profiling.scope("runner.client"):
                # The round's client traffic: phase append skew plus read
                # fires (packed bits along G); an outstanding read retries
                # every round until served, a fire finding one outstanding is
                # dropped (one read in flight per group).
                cph = client.phase_of_round[r]
                append = append + client.append[cph]
                fire_row = kernels.unpack_bits_g(client.read_fire_packed[r], G)
                mode_row = client.read_mode[cph]
                fire = fire_row & (mode_row > 0)
                fresh = fire & (rcar.pending_mode == 0)
                dropped = fire & (rcar.pending_mode > 0)
                pmode = jnp.where(fresh, mode_row, rcar.pending_mode)
                psince = jnp.where(fresh, r, rcar.pending_since)
                read_propose = pmode
                # The linearizability audit's inputs, off the round-ENTRY
                # (= serve-time) state: every peer that would answer a read
                # now, and the groups with such a read live this round.
                if cfg.lease_read:
                    # The full lease-holder mask and the lease-mode reads.
                    lease_holder, _, _ = kernels.lease_read(
                        st.state, st.term, st.leader_id, st.election_elapsed,
                        st.commit, st.term_start_index, crashed,
                        cfg.election_tick,
                        cfg.check_quorum and cfg.lease_read, st.transferee,
                        st.recent_active, st.voter_mask, st.outgoing_mask,
                    )
                    lease_fire = pmode == sim_mod.READ_LEASE
                else:
                    # No lease exists (raft-rs's default Config, or damping
                    # with ReadOnlyOption::Safe) and every read, whatever mode
                    # the client asked for, is a ReadIndex round — the audit
                    # holds every peer whose ReadIndex gate passes (the step's
                    # own probe: ReadReceipt.holders, below) to the same two
                    # slots: no answer older than an index committed
                    # fleet-wide, one answering peer a group.
                    lease_holder = None
                    lease_fire = pmode > sim_mod.READ_NONE
        else:
            read_propose = None
            lease_holder = None
            lease_fire = None
        # Op eligibility: the next unapplied op, once its phase starts.
        with profiling.scope("reconfig.gate"):
            start = reconfig_mod._gather_op(sched.op_start, rst.op_ptr)
            active = (rst.op_ptr < sched.n_ops) & (r >= start)
            want_prop = active & (rst.stage == 0)
        with profiling.scope("runner.stats"):
            prev_leaderless = hl.planes[kernels.HP_LEADERLESS]
        with profiling.scope("runner.client"):
            offered = append + want_prop.astype(jnp.int32)
        step_out = sim_mod.step(
            cfg, st, crashed,
            offered,
            counters=ctrs, health=hl, link=link,
            reconfig_propose=want_prop,
            transfer_propose=transfer_propose,
            campaign_kick=campaign_kick,
            read_propose=read_propose,
        )
        receipt = None
        if client is not None:
            step_out, receipt = step_out[:-1], step_out[-1]
            if lease_holder is None:
                lease_holder = receipt.holders
        if with_counters:
            st2, ctrs2, hl2, prop = step_out
        else:
            st2, hl2, prop = step_out
            ctrs2 = None
        with profiling.scope("reconfig.gate"):
            # Record where the conf entry landed (owner 0 = no alive
            # leader this round; the op stays at stage 0 and retries).
            got = want_prop & (prop.owner > 0)
            stage = jnp.where(got, 1, rst.stage)
            powner = jnp.where(got, prop.owner, rst.prop_owner)
            pindex = jnp.where(got, prop.index, rst.prop_index)
            pterm = jnp.where(got, prop.term, rst.prop_term)
            # The dual-majority commit gate, off the post-round planes:
            # the owner still leads at its propose term (its log cannot
            # have been overwritten — a leader only appends) and is not
            # crashed (a frozen isolated owner can never advance), and its
            # commit covers the entry.  Commit advancement itself already
            # required BOTH majorities of the joint config (joint.rs
            # min-of-halves in every step path), so `commit >= index` IS
            # the dual-quorum gate.
            own_lead = (
                (
                    reconfig_mod._gather_peer(st2.state, powner)
                    == kernels.ROLE_LEADER
                )
                & (reconfig_mod._gather_peer(st2.term, powner) == pterm)
                & ~reconfig_mod._gather_peer(crashed, powner)
            )
            committed = reconfig_mod._gather_peer(st2.commit, powner) >= pindex
            apply_mask = (stage == 1) & own_lead & committed
            retry = (stage == 1) & ~own_lead
            stage = jnp.where(apply_mask | retry, 0, stage)
        # Joint-window safety invariants on the post-step (pre-apply)
        # state under the masks that governed the step; the mask
        # TRANSITION pair (prev round's step masks -> this round's) audits
        # the previous round's apply.
        viol = None
        if with_bb:
            viol = kernels.check_safety_groups(
                st2.state, st2.term, st2.commit, st2.last_index, st2.agree,
                st.commit,
                voter_mask=st2.voter_mask,
                outgoing_mask=st2.outgoing_mask,
                matched=st2.matched,
                crashed=crashed,
                prev_voter_mask=rst.prev_voter,
                prev_outgoing_mask=rst.prev_outgoing,
                lease_holder=lease_holder,
                lease_fire=lease_fire,
            )
            # dtype= keeps the slot sums int32 under x64 (GC007); the
            # per-group sums equal check_safety's counts exactly.
            audit = jnp.sum(viol, axis=1, dtype=jnp.int32)
        else:
            audit = kernels.check_safety(
                st2.state, st2.term, st2.commit, st2.last_index, st2.agree,
                st.commit,
                voter_mask=st2.voter_mask,
                outgoing_mask=st2.outgoing_mask,
                matched=st2.matched,
                crashed=crashed,
                prev_voter_mask=rst.prev_voter,
                prev_outgoing_mask=rst.prev_outgoing,
                lease_holder=lease_holder,
                lease_fire=lease_fire,
            )
        with profiling.scope("runner.stats"):
            safety = safety + audit
        with profiling.scope("reconfig.apply"):
            # The gated swap: target masks of the op being applied, the
            # reference's apply-time reactions on the batched planes.
            (
                state3, leader3, commit3, matched3, vm3, om3, lm3, ra3,
                tr3,
            ) = kernels.apply_confchange(
                st2.state, st2.leader_id, st2.commit,
                st2.term_start_index,
                st2.matched, st2.voter_mask, st2.outgoing_mask,
                st2.learner_mask,
                reconfig_mod._gather_op(sched.tgt_voter, rst.op_ptr),
                reconfig_mod._gather_op(sched.tgt_outgoing, rst.op_ptr),
                reconfig_mod._gather_op(sched.tgt_learner, rst.op_ptr),
                reconfig_mod._gather_op(sched.added, rst.op_ptr),
                reconfig_mod._gather_op(sched.removed, rst.op_ptr),
                apply_mask,
                st2.recent_active,
                st2.transferee,
            )
            st3 = st2._replace(
                state=state3, leader_id=leader3, commit=commit3,
                matched=matched3, voter_mask=vm3, outgoing_mask=om3,
                learner_mask=lm3, recent_active=ra3, transferee=tr3,
            )
        with profiling.scope("runner.stats"):
            stats = chaos_mod.update_chaos_stats(
                stats, prev_leaderless, hl2.planes[kernels.HP_LEADERLESS],
                offered=offered > 0, dropped=prop.dropped,
            )
            if client is not None:
                # The round's end against the last acting leader each
                # group had, and the growth of its highest term.  Off the
                # post-step planes (st2), the ones the health fold's
                # `has_leader` read: an apply-time step-down shows from
                # the next round, and the leader compare is the fold's own
                # — off st3 the damped round was 1.2% slower on the chip
                # (PERF.md §6, PR 35).
                stats, last_leader = chaos_mod.update_leader_stats(
                    stats, rcar.last_leader, hl,
                    hl2.planes[kernels.HP_TERM_BUMPS],
                    st2.state, st2.term, crashed,
                )
        with profiling.scope("reconfig.apply"):
            # dtype= on the counts: bare bool sums widen to int64 under
            # x64 (GC007) and these feed the int32 accumulator.
            rstats = rstats + jnp.stack(
                [
                    jnp.sum(got, dtype=jnp.int32),
                    jnp.sum(apply_mask, dtype=jnp.int32),
                    jnp.sum(retry, dtype=jnp.int32),
                    jnp.sum(jnp.any(om3, axis=0), dtype=jnp.int32),
                ]
            )
            rst2 = reconfig_mod.ReconfigState(
                stage=stage,
                op_ptr=jnp.where(apply_mask, rst.op_ptr + 1, rst.op_ptr),
                prop_owner=powner,
                prop_index=pindex,
                prop_term=pterm,
                prev_voter=st2.voter_mask,
                prev_outgoing=st2.outgoing_mask,
            )
        out = (st3, hl2, rst2, stats, rstats, safety)
        if with_counters:
            out = out + (ctrs2,)
        if client is not None:
            # Serve accounting: a non-negative receipt closes the group's
            # outstanding read with latency (r - issue_round), folded into
            # the device histogram (bucket = min(latency, cap), cap =
            # N_LAT_BUCKETS - 1 derived from the carry shape).
            lat_cap = lat_hist.shape[0] - 1
            with profiling.scope("runner.client"):
                served = (receipt.index >= 0) & (pmode > 0)
                lat = jnp.clip(r - psince, 0, lat_cap)
            lat_hist = workload_mod.fold_latencies(lat_hist, served, lat)
            # dtype= on the counts: GC007 (bare bool sums widen under
            # x64) — these feed the int32 read-stats accumulator.
            with profiling.scope("runner.stats"):
                rdstats = rdstats + jnp.stack(
                    [
                        jnp.sum(fresh, dtype=jnp.int32),
                        jnp.sum(served & receipt.lease, dtype=jnp.int32),
                        jnp.sum(served & ~receipt.lease, dtype=jnp.int32),
                        jnp.sum(served & receipt.degraded, dtype=jnp.int32),
                        jnp.sum((pmode > 0) & ~served, dtype=jnp.int32),
                        jnp.sum(dropped, dtype=jnp.int32),
                    ]
                )
            with profiling.scope("runner.client"):
                rcar = type(rcar)(
                    pending_mode=jnp.where(served, 0, pmode),
                    pending_since=jnp.where(served, 0, psince),
                    last_leader=last_leader,
                )
            if lag is not None:
                # A fleet that boots with learners: the round's end, off the
                # planes the round hands on.
                lag = chaos_mod.fold_learner_lag(
                    lag, st3.state, st3.term, st3.commit, st3.learner_mask,
                    crashed,
                )
                rcar = workload_mod.LearnerLagCarry(rcar, lag)
            out = out + (rcar, rdstats, lat_hist)
        if with_bb:
            # The ring records the round-EXIT (post-apply) state; the
            # fired bits come from the audit above, so one fold covers
            # trace and trigger capture.
            bb = sim_mod.BlackboxState(*kernels.blackbox_fold(
                bb.meta, bb.term, bb.commit, bb.trip_round, bb.round_idx,
                st3.state, st3.term, st3.commit, crashed, viol,
            ))
            out = out + (bb,)
        return out, ()

    return body


# --- the runner constructors (make_runner's docstring has each contract) ----


def _tail_audit(
    stf: sim_mod.SimState, rstf: reconfig_mod.ReconfigState
) -> jnp.ndarray:
    """The one extra safety fold after a runner's last round: the scan body
    checks each apply's mask transition one round later, so a final-round
    apply needs this (prev_commit = final commit keeps the commit checks
    inert).  The scan runners fold it inside their jit; the split runners,
    whose blocks are separate dispatches, call it through a jit of their
    own — eagerly its quorum networks alone are ~110 programs a call."""
    return kernels.check_safety(
        stf.state, stf.term, stf.commit, stf.last_index, stf.agree,
        stf.commit,
        voter_mask=stf.voter_mask,
        outgoing_mask=stf.outgoing_mask,
        matched=stf.matched,
        prev_voter_mask=rstf.prev_voter,
        prev_outgoing_mask=rstf.prev_outgoing,
    )


def _make_chaos(cfg: sim_mod.SimConfig, compiled: chaos_mod.CompiledChaos):
    """The chaos-only whole-scenario runner: its own lean scan body — no
    op protocol, no read carry — so the chaos_runner@* jaxpr budgets stay
    at step + chaos gather."""
    n_rounds = compiled.n_rounds
    with_bb = cfg.blackbox

    def body(carry, r, sched):
        if with_bb:
            st, hl, bb, stats, safety = carry
        else:
            st, hl, stats, safety = carry
            bb = None
        link, crashed, append = chaos_mod.schedule_masks(sched, r)
        with profiling.scope("runner.stats"):
            prev_leaderless = hl.planes[kernels.HP_LEADERLESS]
        st2, hl2 = sim_mod.step(
            cfg, st, crashed, append, health=hl, link=link
        )
        if with_bb:
            viol = kernels.check_safety_groups(
                st2.state, st2.term, st2.commit, st2.last_index,
                st2.agree, st.commit,
            )
            # dtype= keeps the slot sums int32 under x64 (GC007); the
            # per-group sums equal check_safety's counts exactly
            # (tests/test_forensics.py pins it).
            safety = safety + jnp.sum(viol, axis=1, dtype=jnp.int32)
            bb = sim_mod.BlackboxState(*kernels.blackbox_fold(
                bb.meta, bb.term, bb.commit, bb.trip_round, bb.round_idx,
                st2.state, st2.term, st2.commit, crashed, viol,
            ))
        else:
            safety = safety + kernels.check_safety(
                st2.state, st2.term, st2.commit, st2.last_index, st2.agree,
                st.commit,
            )
        with profiling.scope("runner.stats"):
            stats = chaos_mod.update_chaos_stats(
                stats, prev_leaderless, hl2.planes[kernels.HP_LEADERLESS]
            )
        out = (
            (st2, hl2, bb, stats, safety)
            if with_bb
            else (st2, hl2, stats, safety)
        )
        return out, ()

    def run(st, hl, *args):
        if with_bb:
            bb, args = args[0], args[1:]
        sched = rebuild("chaos", compiled, args)
        stats = jnp.zeros((chaos_mod.N_CHAOS_STATS,), jnp.int32)
        safety = jnp.zeros((kernels.N_SAFETY,), jnp.int32)
        carry = (
            (st, hl, bb, stats, safety)
            if with_bb
            else (st, hl, stats, safety)
        )
        carry, _ = jax.lax.scan(
            lambda c, r: body(c, r, sched),
            carry,
            jnp.arange(n_rounds, dtype=jnp.int32),
        )
        return carry

    jitted = jax.jit(
        run, donate_argnums=(0, 1, 2) if with_bb else (0, 1)
    )
    sched_args = schedule_args(compiled)

    def runner(st, hl, *bb):
        return jitted(st, hl, *bb, *sched_args)

    runner.jitted = jitted  # type: ignore[attr-defined]
    runner.schedule_args = sched_args  # type: ignore[attr-defined]
    return runner


def _make_reconfig(
    cfg: sim_mod.SimConfig,
    compiled: reconfig_mod.CompiledReconfig,
    chaos_compiled: Optional[chaos_mod.CompiledChaos],
):
    """The reconfig(+chaos) whole-scenario runner: one scan of
    _runner_body with the tail transition audit."""
    n_rounds = compiled.n_rounds
    _validate_plans(cfg, compiled, chaos_compiled)

    with_bb = cfg.blackbox

    def body(carry, r, sched, chaos_sched):
        return _runner_body(cfg, sched, chaos_sched)(carry, r)

    def run(st, hl, rst, *args):
        if with_bb:
            bb, sched_args = args[0], args[1:]
        else:
            sched_args = args
        sched, chaos_sched = rebuild_scheds(
            compiled, chaos_compiled, sched_args
        )
        stats = jnp.zeros((chaos_mod.N_CHAOS_STATS,), jnp.int32)
        rstats = jnp.zeros((reconfig_mod.N_RECONFIG_STATS,), jnp.int32)
        safety = jnp.zeros((kernels.N_SAFETY,), jnp.int32)
        carry = (st, hl, rst, stats, rstats, safety)
        if with_bb:
            carry = carry + (bb,)
        carry, _ = jax.lax.scan(
            lambda c, r: body(c, r, sched, chaos_sched),
            carry,
            jnp.arange(n_rounds, dtype=jnp.int32),
        )
        if with_bb:
            carry, bb = carry[:-1], carry[-1]
        stf, hlf, rstf, stats, rstats, safety = carry
        # Tail audit: the scan body checks each apply's mask transition
        # one round later, so a final-round apply needs this one extra
        # fold (prev_commit = final commit keeps the commit checks inert
        # — only the transition + election-safety slots can fire).
        if with_bb:
            viol = kernels.check_safety_groups(
                stf.state, stf.term, stf.commit, stf.last_index, stf.agree,
                stf.commit,
                voter_mask=stf.voter_mask,
                outgoing_mask=stf.outgoing_mask,
                matched=stf.matched,
                prev_voter_mask=rstf.prev_voter,
                prev_outgoing_mask=rstf.prev_outgoing,
            )
            # dtype= keeps the slot sums int32 under x64 (GC007).
            safety = safety + jnp.sum(viol, axis=1, dtype=jnp.int32)
            # The tail transition belongs to the LAST real round:
            # blackbox_mark stamps slot round_idx - 1.
            meta, trip = kernels.blackbox_mark(
                bb.meta, bb.trip_round, bb.round_idx, viol
            )
            bb = bb._replace(meta=meta, trip_round=trip)
            return stf, hlf, rstf, stats, rstats, safety, bb
        safety = safety + _tail_audit(stf, rstf)
        return stf, hlf, rstf, stats, rstats, safety

    jitted = jax.jit(
        run, donate_argnums=(0, 1, 2, 3) if with_bb else (0, 1, 2)
    )
    sched_args = schedule_args(compiled, chaos_compiled)

    def runner(st, hl, rst, *bb):
        return jitted(st, hl, rst, *bb, *sched_args)

    runner.jitted = jitted  # type: ignore[attr-defined]
    runner.schedule_args = sched_args  # type: ignore[attr-defined]
    return runner


def _make_reconfig_split(
    cfg: sim_mod.SimConfig,
    compiled: reconfig_mod.CompiledReconfig,
    chaos_compiled: Optional[chaos_mod.CompiledChaos],
    k: int,
    window: int,
    with_counters: bool,
):
    """The split-horizon reconfig runner: planned general segments scan
    _runner_body; planned fused segments ride pallas_step.steady_round
    behind the steady predicate."""
    from . import pallas_step  # deferred: keeps the factory importable sans pallas

    n_rounds = compiled.n_rounds
    P, G = cfg.n_peers, cfg.n_groups
    if not cfg.collect_health:
        raise ValueError(
            "a split runner needs SimConfig(collect_health=True) — the "
            "MTTR stats and the fused block's closed-form fold ride on the "
            "health planes"
        )
    if cfg.blackbox:
        raise ValueError(
            "a split runner does not thread the black box (v1: "
            "steady_mask rejects blackbox-on horizons, so nothing would "
            "fuse) — use the unsplit runner; ClusterSim.run_reconfig"
            "(split=True) falls back automatically"
        )
    if k > cfg.health_window:
        raise ValueError(
            f"fused block k={k} exceeds health_window={cfg.health_window}: "
            "the closed-form health fold handles at most one churn-window "
            "crossing per block"
        )
    _validate_plans(cfg, compiled, chaos_compiled)
    chaos_on = chaos_compiled is not None
    segments = reconfig_mod.split_plan(compiled, k, chaos_compiled, window)
    assert segments and segments[0].start == 0 and sum(
        s.rounds for s in segments
    ) == n_rounds, "split_plan must tile the horizon exactly"
    fused_fn = pallas_step.steady_round(
        cfg, rounds=k, with_health=True, with_counters=with_counters,
        with_chaos=chaos_on,
    )
    n_carry = 7 if with_counters else 6  # ... + fused accumulator below

    def _unpack_rest(rest):
        ctrs = rest[0] if with_counters else None
        i = 1 if with_counters else 0
        return ctrs, rest[i], rest[i + 1], rest[i + 2:]  # fused, r0, sched

    def general_run(L):
        def run_gen(st, hl, rst, stats, rstats, safety, *rest):
            ctrs, fused, r0, sched_args = _unpack_rest(rest)
            sched, chaos_sched = rebuild_scheds(
                compiled, chaos_compiled, sched_args
            )
            body = _runner_body(
                cfg, sched, chaos_sched, with_counters
            )
            carry = (st, hl, rst, stats, rstats, safety)
            if with_counters:
                carry = carry + (ctrs,)
            carry, _ = jax.lax.scan(
                body, carry, r0 + jnp.arange(L, dtype=jnp.int32)
            )
            return carry + (fused,)

        return run_gen

    def fused_block_run(st, hl, rst, stats, rstats, safety, *rest):
        ctrs, fused, r0, sched_args = _unpack_rest(rest)
        sched, chaos_sched = rebuild_scheds(
            compiled, chaos_compiled, sched_args
        )
        body = _runner_body(cfg, sched, chaos_sched, with_counters)
        if chaos_on:
            link, loss, crashed, capp = chaos_mod.schedule_planes(
                chaos_sched, r0
            )
        else:
            link = loss = None
            crashed = jnp.zeros((P, G), bool)
            capp = 0
        append = sched.append[sched.phase_of_round[r0]] + capp
        pend = reconfig_mod.pending_in_horizon(sched, rst, r0, k)
        mask = pallas_step.steady_mask(
            cfg, st, crashed, horizon=k, link=link,
            reconfig_pending=pend, loss_rate=loss,
        )
        pred = jnp.all(mask)

        def fast(args):
            st, hl, rst, stats, rstats, safety, *c = args
            prev_ll = hl.planes[kernels.HP_LEADERLESS]
            fargs = (st, crashed, append)
            if chaos_on:
                fargs = fargs + (loss, r0)
            if with_counters:
                fargs = fargs + (c[0],)
            out = fused_fn(*fargs, hl)
            if with_counters:
                st2, ctrs2, hl2 = out
            else:
                st2, hl2 = out
            # One closed-form MTTR fold for the whole block: the fused
            # health fold pins HP_LEADERLESS to 0 every round (a leader
            # held), so k per-round folds telescope to this single one.
            stats2 = chaos_mod.update_chaos_stats(
                stats, prev_ll, hl2.planes[kernels.HP_LEADERLESS],
                offered=append > 0, rounds=k,
            )
            # No op proposed/gated/applied and no mask moved (predicate):
            # the op-protocol carry is unchanged except the transition-
            # audit anchors, which refresh to (unchanged -> current)
            # exactly like k general no-op rounds would leave them.
            rst2 = rst._replace(
                prev_voter=st2.voter_mask, prev_outgoing=st2.outgoing_mask
            )
            res = (st2, hl2, rst2, stats2, rstats, safety)
            if with_counters:
                res = res + (ctrs2,)
            return res

        def slow(args):
            carry, _ = jax.lax.scan(
                body, args, r0 + jnp.arange(k, dtype=jnp.int32)
            )
            return carry

        args = (st, hl, rst, stats, rstats, safety)
        if with_counters:
            args = args + (ctrs,)
        carry = jax.lax.cond(pred, fast, slow, args)
        fused = fused + jnp.where(
            pred, jnp.int32(k * G), jnp.int32(0)
        )
        return carry + (fused,)

    donate = (0, 1, 2) + ((6,) if with_counters else ())
    fused_jit = jax.jit(fused_block_run, donate_argnums=donate)
    tail_audit_jit = jax.jit(_tail_audit)
    general_jits: Dict[int, Callable] = {}
    for seg in segments:
        if not seg.fused and seg.rounds not in general_jits:
            general_jits[seg.rounds] = jax.jit(
                general_run(seg.rounds), donate_argnums=donate
            )
    sched_args = schedule_args(compiled, chaos_compiled)

    def runner(st, hl, rst, counters=None):
        if with_counters and counters is None:
            raise ValueError(
                "runner built with_counters=True needs the counters plane"
            )
        stats = jnp.zeros((chaos_mod.N_CHAOS_STATS,), jnp.int32)
        rstats = jnp.zeros((reconfig_mod.N_RECONFIG_STATS,), jnp.int32)
        safety = jnp.zeros((kernels.N_SAFETY,), jnp.int32)
        carry = (st, hl, rst, stats, rstats, safety)
        if with_counters:
            carry = carry + (counters,)
        carry = carry + (jnp.int32(0),)  # the fused group-round accumulator
        for seg in segments:
            if seg.fused:
                for b in range(seg.rounds // k):
                    carry = fused_jit(
                        *carry,
                        jnp.int32(seg.start + b * k),
                        *sched_args,
                    )
            else:
                carry = general_jits[seg.rounds](
                    *carry, jnp.int32(seg.start), *sched_args
                )
        stf, hlf, rstf, stats, rstats, safety = carry[:6]
        ctrs_f = carry[6] if with_counters else None
        fused = carry[n_carry]
        safety = safety + tail_audit_jit(stf, rstf)
        out = (stf, hlf, rstf, stats, rstats, safety, fused)
        if with_counters:
            out = out + (ctrs_f,)
        return out

    runner.segments = segments  # type: ignore[attr-defined]
    runner.fused_jit = fused_jit  # type: ignore[attr-defined]
    runner.general_jits = general_jits  # type: ignore[attr-defined]
    runner.schedule_args = sched_args  # type: ignore[attr-defined]
    return runner


def _make_workload(
    cfg: sim_mod.SimConfig,
    client: workload_mod.CompiledClient,
    chaos_compiled: Optional[chaos_mod.CompiledChaos],
    reconfig_compiled: Optional[reconfig_mod.CompiledReconfig],
):
    """The client-workload whole-scenario runner: _runner_body with the
    read protocol threaded; a missing reconfig plan runs the no-op
    schedule."""
    workload_mod._validate(cfg, client, chaos_compiled, reconfig_compiled)
    if reconfig_compiled is None:
        reconfig_compiled = reconfig_mod.empty_reconfig_schedule(
            client.n_rounds, cfg.n_peers, cfg.n_groups
        )
    n_rounds = client.n_rounds
    n_client = len(schedules_mod.array_fields("client"))

    with_bb = cfg.blackbox

    def run(st, hl, rst, rcar, *args):
        if with_bb:
            bb, sched_args = args[0], args[1:]
        else:
            sched_args = args
        csched = rebuild("client", client, sched_args)
        sched, chaos_sched = rebuild_scheds(
            reconfig_compiled, chaos_compiled, sched_args[n_client:]
        )
        # A replayed schedule is a cycle: finished chains start again,
        # unfinished ones go on (identity on a fresh carry).
        rst = reconfig_mod.resume_state(rst, sched.n_ops)
        stats = jnp.zeros((chaos_mod.N_CHAOS_STATS,), jnp.int32)
        rstats = jnp.zeros((reconfig_mod.N_RECONFIG_STATS,), jnp.int32)
        safety = jnp.zeros((kernels.N_SAFETY,), jnp.int32)
        rdstats = jnp.zeros((workload_mod.N_READ_STATS,), jnp.int32)
        lat_hist = jnp.zeros((workload_mod.N_LAT_BUCKETS,), jnp.int32)
        body = _runner_body(
            cfg, sched, chaos_sched, client=csched
        )
        carry = (
            st, hl, rst, stats, rstats, safety, rcar, rdstats, lat_hist,
        )
        if with_bb:
            carry = carry + (bb,)
        carry, _ = jax.lax.scan(
            body,
            carry,
            jnp.arange(n_rounds, dtype=jnp.int32),
        )
        if with_bb:
            carry, bb = carry[:-1], carry[-1]
        stf, hlf, rstf, stats, rstats, safety, rcarf, rdstats, lat_hist = (
            carry
        )
        # The same tail audit as the reconfig runner: a final-round
        # apply's mask transition is checked one round later, so fold
        # once more on the final state (commit checks inert).
        if with_bb:
            viol = kernels.check_safety_groups(
                stf.state, stf.term, stf.commit, stf.last_index, stf.agree,
                stf.commit,
                voter_mask=stf.voter_mask,
                outgoing_mask=stf.outgoing_mask,
                matched=stf.matched,
                prev_voter_mask=rstf.prev_voter,
                prev_outgoing_mask=rstf.prev_outgoing,
            )
            # dtype= keeps the slot sums int32 under x64 (GC007).
            safety = safety + jnp.sum(viol, axis=1, dtype=jnp.int32)
            meta, trip = kernels.blackbox_mark(
                bb.meta, bb.trip_round, bb.round_idx, viol
            )
            bb = bb._replace(meta=meta, trip_round=trip)
            return (
                stf, hlf, rstf, stats, rstats, safety, rcarf, rdstats,
                lat_hist, bb,
            )
        safety = safety + _tail_audit(stf, rstf)
        return (
            stf, hlf, rstf, stats, rstats, safety, rcarf, rdstats,
            lat_hist,
        )

    jitted = jax.jit(
        run, donate_argnums=(0, 1, 2, 3, 4) if with_bb else (0, 1, 2, 3)
    )
    sched_args = schedule_args(client, reconfig_compiled, chaos_compiled)

    def runner(st, hl, rst, rcar, *bb):
        return jitted(st, hl, rst, rcar, *bb, *sched_args)

    runner.jitted = jitted  # type: ignore[attr-defined]
    runner.schedule_args = sched_args  # type: ignore[attr-defined]
    return runner


@profiling.scope("runner.guard_refusals")
def _guard_refusals(
    cfg: sim_mod.SimConfig,
    st: sim_mod.SimState,
    crashed: jnp.ndarray,  # gc: bool[P, G]
    horizon: int,
    loss_rate: jnp.ndarray,  # gc: int32[P, P, G]
    read_pending: jnp.ndarray,  # gc: bool[G]
) -> jnp.ndarray:
    """int32[len(workload.GUARD_TERMS)]: the groups each term of a chaos
    block's guard refuses at this block's entry — pallas_step.steady_mask's
    campaign bound, its one alive leader, its uniform terms and its
    check-quorum boundary (the `link=` / `loss_rate=` forms the block's
    guard takes), and the read rejection.  A group refused by two terms
    counts under both.  It DECIDES nothing: steady_mask does, and
    tests/test_workload_split_chaos.py holds these terms' conjunction
    equal to it.  Only a block that did NOT fuse counts (the general arm
    calls it, beside k general rounds): a fused block pays nothing.

    The planes are read behind an optimization barrier and the five counts
    leave through ONE reduce over the group axis, as
    chaos.fold_learner_lag's count does (PERF.md section 6, PR 47): fused
    into the guard's producers the same arithmetic would regroup them."""
    state, term, elapsed, timeout, voter, outgoing, ra, crashed = (
        jax.lax.optimization_barrier((
            st.state, st.term, st.election_elapsed, st.randomized_timeout,
            st.voter_mask, st.outgoing_mask, st.recent_active, crashed,
        ))
    )
    alive = ~crashed
    may_fire = (state != kernels.ROLE_LEADER) & voter & (
        elapsed + horizon >= timeout
    )
    is_leader = (state == kernels.ROLE_LEADER) & alive
    lead_term = jnp.max(jnp.where(is_leader, term, 0), axis=0)
    refused = [
        jnp.any(may_fire, axis=0),
        jnp.sum(is_leader, axis=0, dtype=jnp.int32) != 1,
        ~jnp.all(jnp.where(alive, term == lead_term, True), axis=0),
        (
            ~kernels.cq_boundary_safe(
                ra, voter, outgoing, state, crashed, elapsed, horizon,
                cfg.election_tick,
                lossy=jnp.any(loss_rate != 0, axis=(0, 1)),
            )
            if cfg.check_quorum
            else jnp.zeros_like(read_pending)
        ),
        read_pending,
    ]
    counts = jax.lax.reduce(
        tuple(r.astype(jnp.int32) for r in refused),
        (jnp.int32(0),) * len(refused),
        lambda a, b: tuple(x + y for x, y in zip(a, b)),
        (0,),
    )
    return jnp.stack(counts)


# A workload split call of at most this many blocks is ONE program
# (segment_run, its scan over the blocks unrolled); a longer one dispatches
# a program a block.  On the v5e at 100k x 5 (PERF.md section 6, PR 53) the
# one program saves the host a fixed 3.6 ms a call.  With a `while` over the
# blocks it costs a general round 2.9% and a fused block 4.5-5.9%, at every
# unroll factor tried: the compiler lays the loop-carried fleet state out
# anew in both arms, and copies every plane the loop only reads once a
# call.  So a call of a few blocks gains (`serve`: 3) and one of hundreds
# loses (`load`: 250, -2.6% at best).  Unrolled there is no `while`, at
# the price of compiling the general round once a block: a few blocks.
_SEGMENT_MAX_BLOCKS = 4


def _make_workload_split(
    cfg: sim_mod.SimConfig,
    client: workload_mod.CompiledClient,
    k: int,
    chaos_compiled: Optional[chaos_mod.CompiledChaos],
    reconfig_compiled,
):
    """The fused client-workload runner: k-round blocks behind the
    steady + provably-servable-lease predicate, lease receipts folded
    closed-form on the fast arm.  With a chaos plan a block is also one
    chaos phase: its guard and its kernel take the link, loss and crash
    planes of the block's first round (chaos.schedule_planes, as the
    reconfig split runner's fused_block_run), the general arm and the tail
    scan _runner_body with the chaos schedule, and the carry ends in
    (healthy_refused, guard_refusals[len(workload.GUARD_TERMS)]) — the
    blocks outside a faulted phase that did not fuse, and what the guard
    refused in them, by term.  With none the programs are those of a bare
    client plan, equation for equation.  A reconfig plan stays refused."""
    from . import pallas_step

    if reconfig_compiled is not None:
        raise ValueError(
            "the workload split runner runs a client plan, bare or under "
            "a chaos plan; compose a reconfig schedule through the unsplit "
            "runner (or the reconfig split runner) instead"
        )
    if cfg.blackbox:
        raise ValueError(
            "a split runner does not thread the black box (v1: "
            "steady_mask rejects blackbox-on horizons, so nothing would "
            "fuse) — use the unsplit runner; ClusterSim.run_reads"
            "(split=True) falls back automatically"
        )
    if not cfg.collect_health:
        raise ValueError(
            "a split runner needs SimConfig(collect_health=True) — "
            "the MTTR stats and the fused block's closed-form fold ride "
            "on the health planes"
        )
    if k > cfg.health_window:
        raise ValueError(
            f"fused block k={k} exceeds health_window="
            f"{cfg.health_window}: the closed-form health fold handles "
            "at most one churn-window crossing per block"
        )
    workload_mod._validate(cfg, client, chaos_compiled, None)
    reconfig_sched = reconfig_mod.empty_reconfig_schedule(
        client.n_rounds, cfg.n_peers, cfg.n_groups
    )
    _validate_plans(cfg, reconfig_sched, chaos_compiled)
    chaos_on = chaos_compiled is not None
    # A plan with no loss rate keeps the kernel of a bare plan: with every
    # rate 0 the in-kernel draw knocks out nothing, so the kernel without
    # the loss operand and the draw is the same k rounds.
    lossless = chaos_on and chaos_compiled.lossless
    kernel_loss = chaos_on and not lossless
    n_rounds = client.n_rounds
    P, G = cfg.n_peers, cfg.n_groups
    n_blocks, tail = n_rounds // k, n_rounds % k
    n_client = len(schedules_mod.array_fields("client"))
    fused_fn = pallas_step.steady_round(
        cfg, rounds=k, with_health=True, with_chaos=kernel_loss
    )

    def _rebuild(sched_args):
        csched = rebuild("client", client, sched_args)
        sched, chaos_sched = rebuild_scheds(
            reconfig_sched, chaos_compiled, sched_args[n_client:]
        )
        return csched, sched, chaos_sched

    def stacked_tables(starts, *sched_args):
        """Every block's workload.BlockRows, stacked [n_blocks, ...] — the
        segment program's scanned operands — and the write load of the
        rounds `starts` (one block start per distinct load, below),
        [len(starts), G]."""
        csched, sched, chaos_sched = _rebuild(sched_args)
        append = (
            sched.append[sched.phase_of_round[starts]]
            + csched.append[csched.phase_of_round[starts]]
        )
        if chaos_on:
            append = append + chaos_sched.append[
                chaos_sched.phase_of_round[starts]
            ]
            tables = workload_mod.block_tables(csched, k, chaos_sched)
        else:
            tables = workload_mod.block_tables(csched, k)
        return tables, append

    def tables_run(starts, *sched_args):
        """stacked_tables, each plane as a list of rows: one block's
        operands of block_run (runner.block_args, below)."""
        return jax.tree.map(list, stacked_tables(starts, *sched_args))

    def block_run(
        st, hl, rst, stats, rstats, safety, rcar, rdstats, lat_hist,
        fused, *rest,
    ):
        if chaos_on:
            healthy_refused, refusals, rows, append, *sched_args = rest
        else:
            rows, append, *sched_args = rest
        csched, sched, chaos_sched = _rebuild(sched_args)
        body = _runner_body(cfg, sched, chaos_sched, client=csched)
        guard = profiling.Sections()
        if chaos_on:
            # The planes of the block's first round stand for all k: the
            # guard's `same_chaos_phase` row says whether they do.
            # Finished planes, as chaos.schedule_masks hands a round its
            # own: unbarriered the unpack is copied into every consumer.
            guard.at("runner.block_planes")
            link, loss, crashed, _ = chaos_mod.schedule_planes(
                chaos_sched, rows.r0
            )
            if lossless:
                link, crashed = jax.lax.optimization_barrier((link, crashed))
                loss = jnp.zeros_like(loss)
            else:
                link, loss, crashed = jax.lax.optimization_barrier(
                    (link, loss, crashed)
                )
        guard.at("runner.block_guard")
        if not chaos_on:
            link = loss = None
            crashed = jnp.zeros((P, G), bool)
        # The schedule's half of the guard is the block's own rows
        # (workload.BlockRows); only the fleet's half is computed here.
        read_block = (rcar.pending_mode > 0) | kernels.unpack_bits_g(
            rows.safe_fire, G
        )
        any_lease = kernels.unpack_bits_g(rows.lease_fire, G)
        _, lease_entry, _ = kernels.lease_read(
            st.state, st.term, st.leader_id, st.election_elapsed,
            st.commit, st.term_start_index, crashed, cfg.election_tick,
            cfg.check_quorum and cfg.lease_read, st.transferee,
            st.recent_active, st.voter_mask, st.outgoing_mask,
        )
        # A lease fire is provably servable across the block when the
        # gate passes at entry and the per-round heartbeat acks keep the
        # recent_active row saturated between boundary clears — which
        # needs heartbeat_tick == 1 (static); otherwise lease blocks
        # honestly fall back.
        lease_prov = ~any_lease | (
            lease_entry
            if cfg.heartbeat_tick == 1
            else jnp.zeros((G,), bool)
        )
        mask = pallas_step.steady_mask(
            cfg, st, crashed, horizon=k, link=link, loss_rate=loss,
            read_pending=read_block,
        )
        pred = jnp.all(mask & lease_prov) & rows.same_phase
        if chaos_on:
            pred = pred & rows.same_chaos_phase
            # The blocks between faults that did not fuse: the re-fuse
            # delay after a fault.
            healthy_refused = healthy_refused + (
                ~pred & ~rows.faulted
            ).astype(jnp.int32)
        guard.end()

        @profiling.scope("runner.fused_arm")
        def fast(args):
            st, hl, rst, stats, rstats, safety, rcar, rdstats, lat = args[:9]
            prev_ll = hl.planes[kernels.HP_LEADERLESS]
            if kernel_loss:
                st2, hl2 = fused_fn(st, crashed, append, loss, rows.r0, hl)
            else:
                st2, hl2 = fused_fn(st, crashed, append, hl)
            # The predicate proves a standing leader with no transfer
            # pending: every one of the k offers was taken.
            stats2 = chaos_mod.update_chaos_stats(
                stats, prev_ll, hl2.planes[kernels.HP_LEADERLESS],
                offered=append > 0, rounds=k,
            )
            # The op protocol provably never moves (no-op schedule); only
            # the transition-audit anchors refresh, like the reconfig
            # split runner's fast arm.
            rst2 = rst._replace(
                prev_voter=st2.voter_mask, prev_outgoing=st2.outgoing_mask
            )
            # Closed-form receipts: every in-block lease fire issues
            # fresh (the carry is provably empty — read_block rejected
            # otherwise) and serves the round it fires at latency 0.
            n_served = rows.n_lease
            lat = lat.at[0].add(n_served)
            rdstats2 = rdstats.at[workload_mod.RS_ISSUED].add(n_served)
            rdstats2 = rdstats2.at[workload_mod.RS_SERVED_LEASE].add(n_served)
            if chaos_on:
                # The standing leader is the last acting leader the group
                # had, as k general rounds leave it: under a plan leaders
                # do change later, and the change is counted against this
                # plane (a bare plan's fleet never has one to count).
                lead = kernels.acting_leader_id(st2.state, st2.term, crashed)
                rcar = rcar._replace(
                    last_leader=jnp.where(lead > 0, lead, rcar.last_leader)
                )
            return (
                st2, hl2, rst2, stats2, rstats, safety, rcar, rdstats2,
                lat,
            ) + args[9:]

        @profiling.scope("runner.general_arm")
        def slow(args):
            if chaos_on:
                # Why a block between faults did not fuse, by guard term.
                counts = _guard_refusals(
                    cfg, args[0], crashed, k, loss, read_block
                )
                refusals = args[9] + jnp.where(rows.faulted, 0, counts)
            carry, _ = jax.lax.scan(
                body, args[:9], rows.r0 + jnp.arange(k, dtype=jnp.int32)
            )
            return carry + ((refusals,) if chaos_on else ())

        args = (st, hl, rst, stats, rstats, safety, rcar, rdstats, lat_hist)
        if chaos_on:
            args = args + (refusals,)
        carry = jax.lax.cond(pred, fast, slow, args)
        # Named: in the segment program this runs once a scan trip.
        with profiling.scope("runner.block_guard"):
            fused = fused + jnp.where(pred, jnp.int32(k * G), jnp.int32(0))
        if chaos_on:
            return carry[:9] + (fused, healthy_refused, carry[9])
        return carry + (fused,)

    n_extra = 3 if chaos_on else 1  # fused[, healthy_refused, refusals]

    def tail_run(
        st, hl, rst, stats, rstats, safety, rcar, rdstats, lat_hist,
        *rest,
    ):
        extra, r0 = rest[:n_extra], rest[n_extra]
        sched_args = rest[n_extra + 1:]
        csched, sched, chaos_sched = _rebuild(sched_args)
        body = _runner_body(cfg, sched, chaos_sched, client=csched)
        carry, _ = jax.lax.scan(
            body,
            (st, hl, rst, stats, rstats, safety, rcar, rdstats, lat_hist),
            r0 + jnp.arange(tail, dtype=jnp.int32),
        )
        return carry + extra

    def fresh_carry(st, hl, rst, rcar):
        """A call's carry at its first block: the fleet, the read carry
        and zeroed accumulators — inside the segment program, or one eager
        fill apiece before a long call's first block."""
        stats = jnp.zeros((chaos_mod.N_CHAOS_STATS,), jnp.int32)
        rstats = jnp.zeros((reconfig_mod.N_RECONFIG_STATS,), jnp.int32)
        safety = jnp.zeros((kernels.N_SAFETY,), jnp.int32)
        rdstats = jnp.zeros((workload_mod.N_READ_STATS,), jnp.int32)
        lat_hist = jnp.zeros((workload_mod.N_LAT_BUCKETS,), jnp.int32)
        carry = (
            st, hl, rst, stats, rstats, safety, rcar, rdstats, lat_hist,
            jnp.int32(0),  # the fused group-round accumulator
        )
        if chaos_on:  # healthy_refused, refusals
            carry = carry + (
                jnp.int32(0),
                jnp.zeros((len(workload_mod.GUARD_TERMS),), jnp.int32),
            )
        return carry

    def segment_run(st, hl, rst, rcar, tables, loads, row_of, *sched_args):
        """A whole short segment, ONE program: the accumulators made here
        (as _make_workload's run makes them), block_run scanned over the
        stacked rows — block b's write load is loads[row_of[b]] — then
        tail_run and the tail audit.  The scan is unrolled: laid end to
        end the blocks compile as the block program does, where a `while`
        over them made XLA place the loop-carried fleet state anew in
        both arms (PERF.md section 6, PR 53)."""
        carry = fresh_carry(st, hl, rst, rcar)

        def block(carry, xs):
            rows, load = xs
            with profiling.scope("runner.block_guard"):
                append = jax.lax.dynamic_index_in_dim(
                    loads, load, keepdims=False
                )
            return block_run(*carry, rows, append, *sched_args), None

        if n_blocks:
            carry, _ = jax.lax.scan(
                block, carry, (tables, row_of), unroll=True
            )
        if tail:
            carry = tail_run(*carry, jnp.int32(n_blocks * k), *sched_args)
        # Inert here with the no-op schedule, kept for bit-parity with the
        # unsplit runner.
        safety = carry[5] + _tail_audit(carry[0], carry[2])
        return carry[:5] + (safety,) + carry[6:]

    donate = (0, 1, 2, 6)
    fused_jit = jax.jit(block_run, donate_argnums=donate)
    tail_audit_jit = jax.jit(_tail_audit)
    tail_jit = jax.jit(tail_run, donate_argnums=donate) if tail else None
    sched_args = schedule_args(client, reconfig_sched, chaos_compiled)
    # Blocks that start in one client phase (and, under a chaos plan, one
    # chaos phase) share one append row (the template above has a single
    # phase), so the rows never outgrow the schedules' own planes.
    starts = np.arange(n_blocks, dtype=np.int32) * k
    load_of = np.asarray(client.phase_of_round)[starts]
    if chaos_on:
        chaos_phase = np.asarray(chaos_compiled.phase_of_round)[starts]
        load_of = load_of * (int(chaos_phase.max(initial=0)) + 1) + chaos_phase
    _, first, row_of = np.unique(
        load_of, return_index=True, return_inverse=True
    )
    tables, loads = jax.jit(tables_run)(starts[first], *sched_args)
    block_args = [
        (
            jax.tree.map(
                lambda t: t[b], tables, is_leaf=lambda t: isinstance(t, list)
            ),
            loads[row_of[b]],
        )
        for b in range(n_blocks)
    ]
    tail_r0 = jnp.int32(n_blocks * k)
    blocks_faulted = (
        int(np.sum(jax.device_get(tables.faulted))) if chaos_on else 0
    )

    def blocks_span():
        return profiling.span(
            "raft.runner.blocks", blocks=n_blocks, tail=tail,
            chaos=int(chaos_on), blocks_faulted=blocks_faulted,
        )

    if n_blocks <= _SEGMENT_MAX_BLOCKS:
        jitted = jax.jit(segment_run, donate_argnums=(0, 1, 2, 3))
        segment_args = (
            *jax.jit(stacked_tables)(starts[first], *sched_args),
            jnp.asarray(row_of, jnp.int32),
            *sched_args,
        )

        def runner(st, hl, rst, rcar):
            with blocks_span():
                return jitted(st, hl, rst, rcar, *segment_args)

    else:
        jitted = segment_args = None

        def runner(st, hl, rst, rcar):
            carry = fresh_carry(st, hl, rst, rcar)
            with blocks_span():
                for block in block_args:
                    carry = fused_jit(*carry, *block, *sched_args)
            if tail_jit is not None:
                carry = tail_jit(*carry, tail_r0, *sched_args)
            # Inert here with the no-op schedule, kept for bit-parity with
            # the unsplit runner.
            safety = carry[5] + tail_audit_jit(carry[0], carry[2])
            return carry[:5] + (safety,) + carry[6:]

    runner.jitted = jitted  # type: ignore[attr-defined]
    runner.segment_args = segment_args  # type: ignore[attr-defined]
    runner.tail_jit = tail_jit  # type: ignore[attr-defined]
    runner.fused_jit = fused_jit  # type: ignore[attr-defined]
    runner.block_args = block_args  # type: ignore[attr-defined]
    runner.schedule_args = sched_args  # type: ignore[attr-defined]
    runner.n_blocks = n_blocks  # type: ignore[attr-defined]
    runner.blocks_faulted = blocks_faulted  # type: ignore[attr-defined]
    return runner


def _make_cadence(
    cfg: sim_mod.SimConfig,
    compiled: reconfig_mod.CompiledReconfig,
    chaos_compiled: Optional[chaos_mod.CompiledChaos],
    rounds: int,
    fused: bool,
):
    """One jitted autopilot cadence segment: `rounds` scan iterations of
    _runner_body with the action planes applied at the segment's first
    round, plus the commit-stall fold; `fused=True` adds the steady fast
    path behind a cond."""
    if not cfg.collect_health:
        raise ValueError("the autopilot needs SimConfig(collect_health=True)")
    if not cfg.transfer:
        raise ValueError(
            "the autopilot needs SimConfig(transfer=True) — the transfer "
            "actuation rides the lead_transferee plane"
        )
    if fused:
        from . import pallas_step

        fused_fn = pallas_step.steady_round(
            cfg, rounds=rounds, with_health=True,
            with_chaos=chaos_compiled is not None,
        )

    with_bb = cfg.blackbox

    def run(st, hl, rst, stats, rstats, safety, *rest):
        if with_bb:
            bb, csr, r0, transfer, kick, *sched_args = rest
        else:
            csr, r0, transfer, kick, *sched_args = rest
            bb = None
        sched, chaos_sched = rebuild_scheds(
            compiled, chaos_compiled, sched_args
        )
        body = _runner_body(
            cfg, sched, chaos_sched, actions=(r0, transfer, kick)
        )

        def body2(carry, r):
            inner, csr = carry[:-1], carry[-1]
            inner, _ = body(inner, r)
            hl2 = inner[1]
            csr = csr + jnp.sum(
                hl2.planes[kernels.HP_SINCE_COMMIT]
                >= jnp.int32(cfg.commit_stall_ticks),
                dtype=jnp.int32,
            )
            return inner + (csr,), ()

        def general(args):
            carry, _ = jax.lax.scan(
                body2, args, r0 + jnp.arange(rounds, dtype=jnp.int32)
            )
            return carry

        # _runner_body carries the optional BlackboxState LAST in its
        # inner tuple, so the cadence carry is (..., safety[, bb], csr).
        inner0 = (st, hl, rst, stats, rstats, safety)
        if with_bb:
            inner0 = inner0 + (bb,)

        if not fused:
            return general(inner0 + (csr,)) + (jnp.int32(0),)

        if chaos_compiled is not None:
            link, loss, crashed, capp = chaos_mod.schedule_planes(
                chaos_sched, r0
            )
        else:
            link = loss = None
            crashed = jnp.zeros((cfg.n_peers, cfg.n_groups), bool)
            capp = 0
        append = sched.append[sched.phase_of_round[r0]] + capp
        pend = reconfig_mod.pending_in_horizon(sched, rst, r0, rounds)
        mask = pallas_step.steady_mask(
            cfg, st, crashed, horizon=rounds, link=link,
            reconfig_pending=pend, loss_rate=loss,
        )
        no_action = (~jnp.any(transfer > 0)) & (~jnp.any(kick))
        # The fused kernel gathers the round-r0 masks once for the whole
        # block, so no schedule phase may change inside it (phases are
        # contiguous: endpoint equality is the whole check).
        last = r0 + jnp.int32(rounds - 1)
        same_phase = (
            sched.phase_of_round[r0] == sched.phase_of_round[last]
        )
        if chaos_compiled is not None:
            same_phase = same_phase & (
                chaos_sched.phase_of_round[r0]
                == chaos_sched.phase_of_round[last]
            )
        # The zero-commit-stall claim needs PROVABLE commit progress, not
        # just steadiness: steady_mask admits a crashed-majority horizon
        # (one alive leader, quiet timers) and lossy horizons, where
        # commits genuinely stall and the general scan would count
        # stall group-rounds.  Require an alive voter quorum in BOTH
        # halves and a loss-free horizon — then append > 0 commits every
        # round and the fold is exactly zero.
        alive_b = ~crashed

        def _half_quorum(mask):
            n = jnp.sum(mask, axis=0, dtype=jnp.int32)
            got = jnp.sum(alive_b & mask, axis=0, dtype=jnp.int32)
            return (got >= kernels.majority_of(n)) | (n == 0)

        progress_ok = jnp.all(
            _half_quorum(st.voter_mask) & _half_quorum(st.outgoing_mask)
        )
        if loss is not None:
            progress_ok = progress_ok & jnp.all(loss == 0)
        pred = (
            jnp.all(mask) & no_action & same_phase & progress_ok
            & jnp.all(append > 0)
        )

        def fast(args):
            if with_bb:
                st, hl, rst, stats, rstats, safety, bb, csr = args
            else:
                st, hl, rst, stats, rstats, safety, csr = args
                bb = None
            prev_ll = hl.planes[kernels.HP_LEADERLESS]
            fargs = (st, crashed, append)
            if chaos_compiled is not None:
                fargs = fargs + (loss, r0)
            st2, hl2 = fused_fn(*fargs, hl)
            stats2 = chaos_mod.update_chaos_stats(
                stats, prev_ll, hl2.planes[kernels.HP_LEADERLESS],
                offered=append > 0, rounds=rounds,
            )
            # No op, no action, commits flow every round (append > 0 on a
            # steady horizon): the op carry only refreshes its transition
            # anchors and the commit-stall fold is exactly zero.
            rst2 = rst._replace(
                prev_voter=st2.voter_mask, prev_outgoing=st2.outgoing_mask
            )
            out = (st2, hl2, rst2, stats2, rstats, safety)
            if with_bb:
                # Unreachable with the black box on (steady_mask rejects
                # blackbox horizons, so pred is constant-false) but the
                # cond still traces both branches: pass the recorder
                # through untouched.
                out = out + (bb,)
            return out + (csr,)

        carry = jax.lax.cond(
            pred, fast, general, inner0 + (csr,),
        )
        fused_rounds = jnp.where(
            pred, jnp.int32(rounds * cfg.n_groups), jnp.int32(0)
        )
        return carry + (fused_rounds,)

    return jax.jit(
        run,
        donate_argnums=(
            (0, 1, 2, 3, 4, 5, 6, 7) if cfg.blackbox else
            (0, 1, 2, 3, 4, 5, 6)
        ),
    )


# --- the one entry point ----------------------------------------------------


def make_runner(
    cfg: sim_mod.SimConfig,
    schedules: Sequence = (),
    *,
    split: bool = False,
    cadence: Optional[int] = None,
    k: int = 8,
    window: int = 4,
    with_counters: bool = False,
    fused: bool = False,
):
    """Build a compiled whole-scenario runner from compiled schedules.

    `schedules` is any mix of chaos.CompiledChaos,
    reconfig.CompiledReconfig and workload.CompiledClient (at most one
    each; None entries skipped, so optional schedules pass straight
    through).  What is present, plus `split` / `cadence`, picks the
    variant::

        [chaos]                               chaos scan
        [reconfig, chaos?]                    reconfig scan
        [reconfig, chaos?], split=True        reconfig split (k, window,
                                              with_counters)
        [client, chaos?, reconfig?]           workload scan
        [client, chaos?], split=True          workload split (k)
        [reconfig, chaos?], cadence=rounds    autopilot cadence segment
                                              (fused)

    Common to all: every schedule array enters the jit as a RUNTIME
    argument (GC012; only shapes specialize the compile, so one runner
    serves every plan of the same shapes), the protocol carry is donated
    and the schedule arrays are not, nothing crosses to the host inside a
    run, and each call of make_runner compiles afresh — build once, call
    repeatedly.  Schedules composed in one runner span equal rounds and
    agree on peers.  Health planes are required (the MTTR stats ride on
    HP_LEADERLESS).  Every variant but the cadence segment returns a
    wrapped callable with ``.schedule_args`` (and ``.jitted`` on the scan
    runners) exposed for the graftcheck trace audit.

    chaos scan: (state, health) -> (state', health',
    stats[N_CHAOS_STATS], safety[N_SAFETY]); one lax.scan — per-round
    masks gathered on device, the link-gated sim.step, the safety fold
    and the MTTR fold.  With SimConfig(blackbox=True) a BlackboxState
    follows `health` in and out and each round folds
    kernels.check_safety_groups (the same counts, per group).

    reconfig scan: (state, health, rstate) -> (state', health', rstate',
    stats, rstats[N_RECONFIG_STATS], safety); one scan of
    _runner_body — op eligibility, the propose/gate/apply
    protocol, the joint-window audit — with the chaos masks, when given,
    gathered exactly as the chaos scan's, so membership changes run
    during partitions; a tail audit covers a final-round apply.

    reconfig split: the same protocol, bit-identical state, health,
    op-protocol carry and accumulators, but the horizon is cut at op
    boundaries (reconfig.split_plan, `window` rounds around each op).
    Planned general segments scan _runner_body; planned fused segments
    run `k`-round blocks, each a lax.cond between
    pallas_step.steady_round (with health[, counters][, chaos loss]) and
    the same k general rounds, guarded at run time by steady_mask(
    reconfig_pending=pending_in_horizon(...), loss_rate=...) over the
    whole batch — a retry tail that outlives its window, an unsettled
    election or a lossy phase falls back.  A fused block cannot move the
    op carry, the masks, the rstats or the safety accumulator (no op is
    eligible, the config is not joint, every check_safety slot is zero
    on a steady horizon); its MTTR fold is the closed form of k leaderful
    rounds, and only prev_voter / prev_outgoing refresh.  A short host
    loop dispatches the segments (O(ops) jitted calls, carry donated end
    to end).  `with_counters` threads the [N_COUNTERS] plane through both
    arms; the caller drains it and owns the GC008 bound (n_rounds x G x
    events per group-round < 2**31 in one run).  (st, hl, rst[, ctrs]) ->
    (st', hl', rst', stats, rstats, safety, fused_rounds[, ctrs']):
    `fused_rounds` is the int32 count of fused GROUP-rounds, so
    fused_frac = fused_rounds / (n_rounds x n_groups).  Also exposes
    ``.segments``, ``.fused_jit``, ``.general_jits``.

    workload scan: (state, health, rstate, read_carry) -> (state',
    health', rstate', stats, rstats, safety, read_carry',
    read_stats[N_READ_STATS], lat_hist[N_LAT_BUCKETS]); _runner_body with
    the read protocol threaded — fires, retries, serves, the write skew,
    the latency fold and the full audit including the linearizability
    slots, every round.  A missing reconfig plan runs the no-op schedule
    (reconfig.empty_reconfig_schedule), whose op protocol never moves.

    workload split: the workload scan's outputs plus a trailing
    fused_rounds, bit-identical, run as `k`-round blocks.  A call of at
    most _SEGMENT_MAX_BLOCKS blocks is ONE program (``.jitted``, the
    segment program: the accumulators made inside it as the workload scan
    makes them, an unrolled lax.scan of the block program over the
    stacked block rows, the tail's n_rounds % k general rounds, the tail
    audit; ``runner()`` is one dispatch of it with ``.segment_args`` =
    (stacked rows, stacked loads, each block's load row,
    *schedule_args)); a longer call dispatches the block program once a
    block from a host loop, then the tail's and the audit's, and has
    ``.jitted`` None.  A block takes
    the fused kernel when, at run time, the steady invariant holds for
    the horizon (pallas_step.steady_mask, damping conditions included),
    no quorum-round read work touches it (an outstanding read of any mode
    or a scheduled Safe fire rejects: steady_mask(read_pending=
    workload.reads_pending_in_horizon(...))), and every scheduled LEASE
    fire is provably servable: the block spans one client phase, the
    acting leader passes kernels.lease_read at block entry, and
    heartbeat_tick == 1 re-saturates recent_active every round.  The
    fused arm folds the receipts closed-form (every fire served in its
    round: lat_hist[0], issued and served_lease += fires; the read carry
    stays empty; every safety slot zero).  What a block needs of the
    schedule is tabled once, when the runner is built
    (workload.block_tables; block b's operands are ``.block_args[b]``,
    the segment program scans the same rows stacked),
    so a block's cost does not follow the schedule's length; only the
    general arm reads the planes.  With a chaos plan (ISSUE 51) a block
    is also one chaos phase (tabled: BlockRows.same_chaos_phase): its
    guard and its kernel take the link / crash / loss planes of its first
    round (chaos.schedule_planes; steady_mask(link=, loss_rate=),
    kernels.lease_read(crashed)), a plan with no loss rate keeps the
    kernel without the loss operand, both arms' rounds are the chaos
    scan's, and the outputs end (..., fused_rounds, healthy_refused,
    guard_refusals[len(workload.GUARD_TERMS)]): the blocks outside a
    faulted phase that did not fuse, and the groups each guard term
    refused in them.  A reconfig plan is refused.  Also exposes
    ``.n_blocks``, ``.blocks_faulted`` and the programs a long call
    dispatches — which are also how a test walks ANY segment one block
    at a time (against the scalar cluster, a block's jaxpr, its names):
    ``.fused_jit`` (ONE block: (the ten- or twelve-piece carry, block
    b's rows, its load row, *schedule_args) -> the carry),
    ``.block_args[b]`` (those rows) and ``.tail_jit`` (the tail's
    rounds, None where k divides n_rounds).

    cadence segment: returns the bare jit (st, hl, rst, stats, rstats,
    safety[, blackbox], cs_rounds, r0, transfer_plane, kick_plane,
    *schedule_args) -> the advanced carry + a trailing fused group-rounds
    scalar, the carry donated: `cadence` scan rounds of _runner_body from
    absolute round r0 with the action planes applied at the first, plus
    the per-round commit-stall fold (group-rounds at or over
    SimConfig.commit_stall_ticks).  `fused=True` puts the whole segment
    behind a lax.cond, bit-identical to the scan when taken: the steady
    predicate over its horizon (which rejects pending transfers and
    scheduled ops) AND no action this segment AND one schedule phase AND
    an alive voter quorum on a loss-free horizon with a positive append
    everywhere (so the closed-form commit-stall fold is exactly zero).
    Needs SimConfig(collect_health=True, transfer=True) and a reconfig
    schedule (the no-op one at rest).
    """
    by_family: Dict[str, object] = {}
    for s in schedules:
        if s is None:
            continue
        fam = family_of(s)
        if fam in by_family:
            raise ValueError(f"duplicate {fam} schedule")
        by_family[fam] = s
    chaos_c = by_family.get("chaos")
    reconfig_c = by_family.get("reconfig")
    client_c = by_family.get("client")

    if cadence is not None:
        if reconfig_c is None:
            raise ValueError(
                "cadence runners need a reconfig schedule (the autopilot's "
                "no-op template at rest)"
            )
        if client_c is not None:
            raise ValueError("cadence runners do not thread a client plan")
        return _make_cadence(cfg, reconfig_c, chaos_c, cadence, fused)
    if split:
        if client_c is not None:
            return _make_workload_split(
                cfg, client_c, k, chaos_c, reconfig_c
            )
        if reconfig_c is None:
            raise ValueError(
                "split runners need a reconfig or client schedule"
            )
        return _make_reconfig_split(
            cfg, reconfig_c, chaos_c, k, window, with_counters
        )
    if client_c is not None:
        return _make_workload(cfg, client_c, chaos_c, reconfig_c)
    if reconfig_c is not None:
        return _make_reconfig(cfg, reconfig_c, chaos_c)
    if chaos_c is not None:
        return _make_chaos(cfg, chaos_c)
    raise ValueError("make_runner needs at least one compiled schedule")
