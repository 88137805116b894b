"""Profiling hooks: what the program writes into a `jax.profiler` trace, and
the one catalogue of the names it writes (SURVEY.md §5.1: the reference's
observability is structured slog logging + Criterion; the device path adds
JAX profiler traces so kernel time is inspectable in TensorBoard/Perfetto).

There is no switch.  "Tracing on" means a `jax.profiler` trace is being
captured (`device_trace` below, the benchmark's `--trace 1`); otherwise a
span is the profiler's own no-op (under 1 us) and a scope or a kernel name
costs nothing at run time: both only label the compiled program.

    from raft_tpu.profiling import device_trace

    with device_trace("/tmp/raft-trace"):      # xprof/perfetto trace
        report = sim.run_reads(plan)

Three kinds of name, each spelled in ONE place — the catalogues below —
so that code, tests, docs and the benchmark's metric files cannot drift:

  SPANS    host spans (`span`): `jax.profiler.TraceAnnotation`s on the
           trace's clock, their counts readable as the event's stats.
  SCOPES   `jax.named_scope`s (`scope`, `Sections`): every device op
           traced under one carries it in its op name
           (`jit(run)/while/body/round.damped/damped.tally/...`); a fusion
           is named after its root instruction, so it belongs to the
           scope of its root.
  KERNELS  `name=` of the `pl.pallas_call` sites (`kernel`).

A name that is not in its catalogue is a KeyError where it is used;
tests/test_profiling_spans.py proves the other direction (every catalogue
name is used).
"""

from __future__ import annotations

import contextlib
from typing import Dict, Optional

import jax

# --- the catalogue: name -> one line on what it covers -----------------------

SPANS: Dict[str, str] = {
    "raft.run_reads": (
        "one ClusterSim.run_reads call, entry to returned report; stats: "
        "call (sequence number of this sim's calls), rounds, groups, "
        "loss_draw (1 where the runner's rounds draw a chaos plan's loss "
        "sample, 0 for a plan with no loss rate or no chaos plan), split, "
        "chaos (0 / 1: the call's mode and whether it has a chaos plan)"
    ),
    "raft.run_reads.prepare": (
        "runner cache look-up (schedule compile + make_runner on a miss: "
        "stat miss=1), the op protocol's carry (fresh on a miss, else the "
        "last call's), init_read_carry, placement"
    ),
    "raft.run_reads.dispatch": (
        "runner(*args): the scan runner's ONE program; the split runner's "
        "segment program (a call of a few blocks: one program too) or its "
        "eager zero carries, block loop and tail audit (a longer call)"
    ),
    "raft.runner.blocks": (
        "the split runner's blocks, inside dispatch: ONE dispatch of the "
        "segment program (runner.jitted: the accumulators' fills, an "
        "unrolled lax.scan of the block program over the tabled rows, the "
        "tail's rounds, the tail audit) where the call has at most "
        "runner._SEGMENT_MAX_BLOCKS blocks (PR 53), else the Python loop of "
        "fused_jit dispatches; stats: blocks, tail (rounds left after the "
        "last block), "
        "chaos (1 where the runner has a chaos plan), blocks_faulted (the "
        "blocks whose chaos phase has a crash, a cut or a loss rate)"
    ),
    "raft.run_reads.report": (
        "everything dispatched for the report (latency_percentiles, "
        "unfinished_groups under a reconfig plan), the download, "
        "formatting; closed with the report's integer counts (a split call "
        "under a chaos plan: its block counts and guard_refusals.<term> too)"
    ),
    "raft.run_reads.download": (
        "the device_get of the report's vectors, inside report: the host's "
        "wait for the device to finish the call"
    ),
}

SCOPES: Dict[str, str] = {
    "round": "sim.step's undamped all-links-up round body",
    "round.linked": (
        "sim._linked_step: the undamped round under a link plane — the "
        "body of a fleet at raft-rs's default Config under faults"
    ),
    "linked.read_probe": "the round-entry read probe (the ReadIndex round)",
    "linked.tick": (
        "the transfer pre-tick pump, the delivery plane, timers, campaign "
        "local effects"
    ),
    "linked.election": (
        "waves 1-2: tick-queued heartbeats and vote requests per receiver, "
        "the responses' tallies per candidate, winners and losers"
    ),
    "linked.replicate": (
        "waves 3+: winner noops and catch-up appends (pass 1), the "
        "commit-advance re-broadcast (pass 2)"
    ),
    "linked.commit": (
        "the per-leader quorum commit off the acked rows (stages A and B) "
        "and the settled commit's propagation"
    ),
    "linked.workload": "the round's append workload at the acting leader",
    "round.damped": (
        "sim._damped_linked_step: check-quorum / pre-vote / lease round — "
        "the only body the benchmark's cells run"
    ),
    "damped.read_probe": "the round-entry read probe (lease gate, ReadIndex)",
    "damped.read_holders": (
        "sim.read_quorum_damped_holders inside damped.read_probe: the "
        "ReadIndex gate of every peer, in the rounds in which some group "
        "has an alive role-leader beside its acting leader (a fleet with "
        "damping on and lease reads off)"
    ),
    "damped.tick": "timers, the check-quorum boundary, campaign local effects",
    "damped.wave1": "heartbeats + (pre-)vote requests, per receiver",
    "damped.wave2": "heartbeat responses + nudges back at each leader",
    "damped.tally": "the (pre-)vote tallies and post-election bookkeeping",
    "tally.real": (
        "_real_tally inside damped.tally and linked.election: the real "
        "election's tally of every candidate at once, prefix counts and "
        "reductions along the voter axis, no loop (wave 2 without "
        "pre-vote, wave 4 with it)"
    ),
    "tally.pre": (
        "_pre_tally inside damped.tally: the pre-vote tally of every "
        "pre-candidate at once, prefix counts and a first-event mask along "
        "the voter axis, no loop, and the end-of-wave state on whole planes"
    ),
    "damped.wave3": "appends: winner noops, catch-ups, their retry chains",
    "damped.stage_fold": (
        "_stage_fold: the ack/nudge fold of waves 4 and 6 (inside "
        "damped.tally and damped.wave5)"
    ),
    "damped.wave5": "commit-advance re-broadcasts and their retry chains",
    "damped.workload": (
        "the round's append workload at the acting leader; dropped "
        "appends are counted here"
    ),
    "damped.merge_agree": (
        "_merge_agree: the rewrite of agree_run[P, P, G] after a wholesale "
        "adoption, once per sender trip of waves 3 and 5 and of their retry "
        "passes, once in the workload"
    ),
    "damped.cut_before": (
        "_cut_before: the response-stream cut-off after the first effective "
        "nudge (a cumulative sum), in waves 2, 3 and 5, the stage fold and "
        "the workload"
    ),
    "quorum_commit": (
        "the quorum position of the acked indexes: kernels.committed_index "
        "and sim._quorum_index (one network, kernels._quorum_of_rows) and "
        "pallas_step._quorum_tile (its twin on VMEM tiles)"
    ),
    "op_gather": (
        "reconfig._gather_peer / _gather_op: the op protocol's per-group "
        "look-ups in every _runner_body round, a plan scheduled or not. "
        "Static row selects (kernels.select_row) since PR 34, no gather; "
        "the name stays because benchmark/metrics/op_gather_share.json "
        "reads it"
    ),
    "reconfig.gate": (
        "the op protocol's first half in every _runner_body round: "
        "eligibility, the propose record, the dual-majority commit gate "
        "(its look-ups are op_gather, inside)"
    ),
    "reconfig.apply": (
        "the op protocol's second half: kernels.apply_confchange on the "
        "op's target planes (op_gather, inside), the pointer advance, the "
        "rstats fold"
    ),
    "safety_audit": "kernels.check_safety: the per-round safety slots",
    "health_fold": "kernels.update_health: the fleet-health planes",
    "read_latency": "workload.latency_percentiles over a histogram",
    "read_fold": (
        "workload.fold_latencies: the round's served reads folded into the "
        "latency histogram, in every _runner_body round with a client plan"
    ),
    "runner.chaos_masks": (
        "chaos.schedule_masks: the round's link, crash and append-skew "
        "planes cut out of the chaos schedule (the loss sample knocked out "
        "only where the plan has a loss rate), in every round of a runner "
        "with a chaos plan"
    ),
    "runner.client": (
        "what runner._runner_body offers the round: the schedules' append "
        "rows and, with a client plan, the read fires unpacked "
        "(kernels.unpack_bits_g), the pending-read bookkeeping before and "
        "after the step and the audit's inputs (kernels.lease_read's holder "
        "mask)"
    ),
    "runner.stats": (
        "chaos.update_chaos_stats / update_leader_stats and "
        "_runner_body's read-stats fold: the round's counts into the "
        "report's accumulators"
    ),
    "runner.learner_lag": (
        "chaos.fold_learner_lag: the round's groups with a learner behind "
        "its acting leader's commit, in every _runner_body round of a "
        "run_reads scan on a fleet that boots with learners "
        "(workload.LearnerLagCarry); no other fleet's round has it"
    ),
    "runner.block_planes": (
        "a split block under a chaos plan: chaos.schedule_planes of the "
        "block's first round — the link, crash and (where the plan has a "
        "rate) loss planes unpacked, finished behind a barrier, for the "
        "guard and the kernel"
    ),
    "runner.guard_refusals": (
        "runner._guard_refusals, inside runner.general_arm of a split "
        "block under a chaos plan: the groups each guard term refused in "
        "a block that did not fuse, one reduce (the report's "
        "guard_refusals)"
    ),
    "runner.block_guard": (
        "everything a split block computes outside its lax.cond's arms: "
        "its write-load row picked from the stacked loads (the segment "
        "program's scan body), its tabled schedule rows unpacked "
        "(workload.BlockRows), lease_read, steady_mask and, after the cond, "
        "the fused group-round count"
    ),
    "runner.fused_arm": "the cond's fused branch: kernel + closed-form folds",
    "runner.general_arm": "the cond's fallback: k general rounds",
}

KERNELS: Dict[str, str] = {
    "raft_steady": "pallas_step: k undamped steady rounds",
    "raft_steady_chaos": "pallas_step: k steady rounds with in-kernel link loss",
    "raft_steady_damped": (
        "pallas_step: k damped steady rounds — the kernel the benchmark's "
        ".load cell runs"
    ),
}

SPAN_PREFIX = "raft."
assert all(name.startswith(SPAN_PREFIX) for name in SPANS)


# --- host spans ---------------------------------------------------------------


def _known(catalogue: Dict[str, str], name: str) -> str:
    if name not in catalogue:
        raise KeyError(f"{name!r} is not in raft_tpu.profiling's catalogue")
    return name


def span(name: str, **counts: int) -> jax.profiler.TraceAnnotation:
    """A host span `name` (a key of SPANS) on the trace's clock, `counts`
    as its metadata; they come back as the event's stats from
    `jax.profiler.ProfileData`.  Counts known only at the end go on with
    `.set_metadata(**counts)` before the span closes.  Parentage is
    containment on one thread.  With no trace running this is the
    profiler's no-op."""
    return jax.profiler.TraceAnnotation(_known(SPANS, name), **counts)


# --- names on the device ------------------------------------------------------


def scope(name: str):
    """`jax.named_scope(name)` for a key of SCOPES: a context manager, or a
    decorator for a whole function body.  Changes no equation."""
    return jax.named_scope(_known(SCOPES, name))


class Sections:
    """Consecutive scopes over one long straight-line body, where a `with`
    per part would re-indent hundreds of lines: `at(name)` closes the part
    before it and opens `name`; `end()` closes the last.  Functions traced
    while a part is open (a scan body, a helper) land in it."""

    def __init__(self) -> None:
        self._open: Optional[contextlib.AbstractContextManager] = None

    def at(self, name: str) -> None:
        self.end()
        self._open = scope(name)
        self._open.__enter__()

    def end(self) -> None:
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None


def kernel(name: str) -> str:
    """`name` (a key of KERNELS), for `pl.pallas_call(..., name=)`."""
    return _known(KERNELS, name)


# --- capturing a trace --------------------------------------------------------


def start_trace(log_dir: str, host_profiler: bool = False) -> None:
    """Begin a JAX profiler (XLA) trace writing into `log_dir`.

    The imperative twin of `device_trace` for callers whose start/stop
    points do not nest lexically.  Must be paired with
    `stop_trace`; traces do not nest."""
    jax.profiler.start_trace(log_dir, create_perfetto_trace=host_profiler)


def stop_trace() -> None:
    """End the trace started by `start_trace` and flush it to disk."""
    jax.profiler.stop_trace()


@contextlib.contextmanager
def device_trace(log_dir: str, host_profiler: bool = False):
    """Capture a JAX profiler trace of everything inside the block; view
    with TensorBoard's profile plugin or ui.perfetto.dev."""
    start_trace(log_dir, host_profiler=host_profiler)
    try:
        yield
    finally:
        stop_trace()
