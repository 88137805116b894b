"""benchmark/run.py --workload <config>.<mix> --seed n --seconds s --trace 0|1

One cell of BENCHMARK.json on the chip: boot the fleet, build the mix's
segment from the seed, warm it up once, then replay it through
`ClusterSim.run_reads` for `--seconds`, and print one JSON line last.  See
README.md for how the pieces are found by name, and line.py for the line.

Everything a library might print goes to stderr: file descriptor 1 is
pointed at stderr for the life of the process and only `say` and the last
line write to the real stdout.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()  # set-up is counted from here

import argparse
import json
import os
import shutil
import sys
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check, line, program_trace, reducers, trace, traffic  # noqa: E402
from benchmark.reference import membership  # noqa: E402

TRACE_DIR = os.path.join(HERE, ".trace")  # fixed, inside the checkout
TRACE_SECONDS = 2.0  # a traced window closes at the first segment end after this
SAMPLE_GROUP_ROUNDS = 1500  # reference budget: sampled groups x replayed rounds


class BenchError(RuntimeError):
    """The run cannot produce a line; the reason goes to stderr."""


# --- names -> files ----------------------------------------------------------


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts), encoding="utf-8") as f:
        return json.load(f)


def find_cell(bench: dict, workload: str) -> Tuple[dict, dict, dict]:
    """(cell, configuration as run, traffic mix) of a workload name."""
    cells = [w for w in bench["workloads"] if w["name"] == workload]
    if not cells:
        names = [w["name"] for w in bench["workloads"]]
        raise BenchError(f"workload {workload!r} is not in BENCHMARK.json: {names}")
    cell = cells[0]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(ROOT, entry["file"])
    mix = traffic.load_mix(cell["traffic"])
    n = mix.get("counted_segments")
    if n is not None and (isinstance(n, bool) or not isinstance(n, int) or n < 1):
        raise BenchError(f"mix {cell['traffic']!r}: counted_segments is not a positive integer: {n!r}")
    return cell, config, mix


class Reader(NamedTuple):
    """One per-layer metric's file, loaded."""

    unit: str
    reducer: object  # a module of reducers/
    args: dict
    needs: Optional[str] = None  # a name of reducers.CONDITIONS


def metric_readers(bench: dict, workload: str) -> Dict[str, Reader]:
    """{per-layer metric of this cell: its reader}."""
    out = {}
    for name, unit in line.expected_metrics(bench, workload, traced=True).items():
        spec = load_json(HERE, "metrics", f"{name}.json")
        needs = spec.get("needs")
        if needs is not None and needs not in reducers.CONDITIONS:
            raise BenchError(f"metrics/{name}.json needs {needs!r}, which is not one of "
                             f"{sorted(reducers.CONDITIONS)}")
        out[name] = Reader(unit, reducers.load(spec["reducer"]), spec.get("args", {}), needs)
    return out


def program_names(report: dict) -> Dict[str, set]:
    """What the program under test can be asked for by name: the spans,
    scopes and kernels of `raft_tpu.profiling`'s catalogue (none, on a
    program from before it had one) and the counts its report span is
    closed with — `call`, `groups`, every integer of the report, the safety
    slots as `safety.<slot>`."""
    try:
        from raft_tpu import profiling
    except ImportError:
        profiling = None
    counts = {"call", "groups"} | {f"safety.{k}" for k in report.get("safety", {})} | {
        k for k, v in report.items() if isinstance(v, int) and not isinstance(v, bool)}
    return {
        "spans": set(getattr(profiling, "SPANS", ())),
        "scopes": set(getattr(profiling, "SCOPES", ())),
        "kernels": set(getattr(profiling, "KERNELS", ())),
        "counts": counts,
    }


def read_metrics(readers: Dict[str, Reader], facts: dict, program: Dict[str, set],
                 workload: str, say):
    """({metric: (value, unit)}, [metrics left out]).  A reader that finds
    nothing: left out where the program lacks a name the reader asks it for,
    or where the subject its file `needs` did not run in the window by the
    reports' exact counts; an error in every other case."""
    metrics, left_out = {}, []
    for name, r in readers.items():
        value = r.reducer.read(facts, r.args)
        if value is not None:
            metrics[name] = (value, r.unit)
            continue
        lacks = reducers.lacking(r.reducer, r.args, program)
        if lacks:
            why = f"the program has no {', '.join(lacks)}"
        else:
            why = reducers.not_run(r.needs, facts.get("counters", {}))
        if why is None:
            raise BenchError(
                f"per-layer metric {name!r} is listed for {workload} but its "
                "reader found nothing to read in this run"
            )
        say(f"metric {name} left out: {why}")
        left_out.append(name)
    return metrics, left_out


# --- the device --------------------------------------------------------------


def require_chips(n: int):
    """The device fields of the line, or an error when this machine does not
    hold `n` TPU chips.  A CPU, asked for or fallen back to, is no chip."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n:
        raise BenchError(
            f"needs {n} TPU chip(s); jax reports {len(devices)} x "
            f"{devices[0].platform} ({devices[0].device_kind})"
        )
    return devices


def device_fields(devices) -> dict:
    stats = [d.memory_stats() or {} for d in devices]
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0)) for s in stats),
    }


class CompileClock:
    """Seconds jax spent lowering, compiling or fetching from the persistent
    cache (copied from chip_smoke.CompileClock)."""

    EVENTS = (
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
        "/jax/compilation_cache/cache_retrieval_time_sec",
    )

    def __init__(self):
        from jax import monitoring

        self.seconds = 0.0
        self.backend_compiles = 0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, seconds, **_):
        if name in self.EVENTS:
            self.seconds += seconds
        if name == self.EVENTS[1]:
            self.backend_compiles += 1


# --- one run -----------------------------------------------------------------


class Fleet:
    """The system under test: a booted ClusterSim plus the segment's
    schedules in the program's own types, and the one call the window
    drives."""

    PLANES = {"voter": "voter_mask", "outgoing": "outgoing_mask", "learner": "learner_mask"}

    def __init__(self, config: dict, n_groups: int):
        import jax.numpy as jnp

        from raft_tpu.multiraft import ClusterSim, SimConfig

        self.config = config
        P = config["n_peers"]
        self.voters, self.learners = membership.of_config(config)
        self.home = [  # the [P, G] voter, outgoing-voter and learner planes it boots in
            membership.masks(m, P, n_groups) for m in (self.voters, [], self.learners)
        ]
        self.cfg = SimConfig(
            n_groups, config["n_peers"],
            election_tick=config["election_tick"],
            heartbeat_tick=config["heartbeat_tick"],
            check_quorum=config["check_quorum"],
            pre_vote=config["pre_vote"],
            lease_read=config["lease_read"],
            collect_health=config["collect_health"],
        )
        if len(self.voters) == P:
            self.sim = ClusterSim(self.cfg)  # every slot a voter: the program's default
        else:
            self.sim = ClusterSim(self.cfg, *(jnp.asarray(m) for m in self.home))
        self.client = None
        self.chaos = None
        self.reconfig = None
        self.seg: Optional[traffic.Segment] = None

    def boot(self) -> None:
        """The cold fleet's election storm, dispatched without waiting."""
        self.sim.run_compiled(self.config["boot_rounds"])
        self.sim.reset_health()

    def load(self, seg: traffic.Segment) -> None:
        import jax.numpy as jnp

        from raft_tpu.multiraft import chaos, reconfig, workload

        self.seg = seg
        self.client = workload.CompiledClient(
            phase_of_round=jnp.asarray(seg.phase_of_round, jnp.int32),
            read_fire_packed=jnp.asarray(seg.read_fire_packed, jnp.uint32),
            read_mode=jnp.asarray(seg.read_mode, jnp.int32),
            append=jnp.asarray(seg.append, jnp.int32),
            n_peers=seg.n_peers,
        )
        self.chaos = chaos.plan_from_dict(seg.chaos) if seg.chaos else None
        self.reconfig = reconfig.plan_from_dict(seg.reconfig) if seg.reconfig else None

    def segment(self) -> dict:
        """One replay of the segment; returns when its report is on the
        host (run_reads ends in the download)."""
        return self.sim.run_reads(
            self.client, self.chaos, self.reconfig,
            split=self.seg.split, split_k=self.seg.split_k,
        )

    def rows(self, gids) -> dict:
        """Cursor and membership rows [n, P] and the read in flight [n] of
        some groups."""
        import jax

        st = self.sim.state
        idx = np.asarray(gids)
        got = jax.device_get(
            [getattr(st, self.PLANES.get(k, k))[:, idx] for k in check.ref.FIELDS]
            + [self.sim._read_carry.pending_mode[idx]]
        )
        out = {k: v.T for k, v in zip(check.ref.FIELDS, got)}
        out["pending_mode"] = got[-1]
        return out


def summed(reports: List[dict], n_groups: int) -> Dict[str, float]:
    """Exact counts over some segments' reports."""
    keys = (
        "rounds", "reads_issued", "served_lease", "served_quorum",
        "dropped_fires", "reelections", "fused_rounds", "total_rounds",
    )
    out: Dict[str, float] = {"segments": len(reports)}
    for k in keys:
        if all(k in r for r in reports):
            out[k] = sum(int(r[k]) for r in reports)
    out["group_rounds"] = out["rounds"] * n_groups
    # Summed length of the leaderless episodes that ended (mttr x count).
    out["healed_group_rounds"] = sum(
        r["mttr_rounds"] * r["reelections"] for r in reports if r["mttr_rounds"]
    )
    return out


def median(values: List[float]) -> float:
    s = sorted(values)
    n = len(s)
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


def counted(reports: List[dict], mix: dict) -> List[dict]:
    """The timed reports the line's exact counts are taken over: the first
    `counted_segments` where the mix states it (what there is, where the
    window held fewer), every one where it does not.  A count that is exact
    per segment then does not follow how many segments a program fits into
    the window; time is taken over the whole window all the same."""
    n = mix.get("counted_segments")
    return reports if n is None else reports[:n]


def op_counts(reports: List[dict], ops_per_segment: int) -> Dict[str, int]:
    """Operations offered in some segments, and those of them that failed
    inside a segment: fires dropped behind a read in flight, and reads
    still outstanding at a segment's end."""
    dropped = sum(int(r["dropped_fires"]) for r in reports)
    outstanding = sum(
        max(0, r["reads_issued"] - r["served_lease"] - r["served_quorum"]) for r in reports
    )
    return {
        "segments": len(reports),
        "attempted": len(reports) * ops_per_segment,
        "dropped_fires": dropped,
        "outstanding_reads": outstanding,
        "failed_in_segments": dropped + outstanding,
    }


def round_counts(reports: List[dict], wanted) -> Dict[str, float]:
    """The round counts inside the two latencies: the medians over some
    segments of the read histogram's p99 and of the mean leaderless episode."""
    out = {}
    if "read_p99_ms" in wanted:
        p99 = [r["read_p99"] for r in reports]
        if min(p99) < 0:
            raise BenchError("a segment served no read: read_p99 is -1, not a latency")
        out["read_p99_rounds"] = median(p99)
    if "recover_ms" in wanted:
        mttr = [r["mttr_rounds"] for r in reports]
        if any(m is None for m in mttr):
            raise BenchError("a segment ended no leaderless episode: no mttr_rounds")
        out["mttr_rounds"] = median(mttr)
    return out


def end_to_end(reports, counts: Dict[str, float], seconds: float, setup_s: float,
               n_groups: int, wanted: Dict[str, str]) -> Dict[str, Tuple[float, str]]:
    """`reports`: every timed segment — rounds and seconds are the whole
    window's.  `counts`: `round_counts` of the counted segments."""
    rounds = sum(r["rounds"] for r in reports)
    ms_per_round = 1e3 * seconds / rounds
    values = {"setup_s": setup_s, "group_rounds_per_s": n_groups * rounds / seconds}
    if "read_p99_ms" in wanted:
        # +1: a read served in its own round is answered when the round ends.
        values["read_p99_ms"] = (counts["read_p99_rounds"] + 1) * ms_per_round
    if "recover_ms" in wanted:
        values["recover_ms"] = counts["mttr_rounds"] * ms_per_round
    missing = set(wanted) - set(values)
    if missing:
        raise BenchError(f"no code computes the end-to-end metric(s) {sorted(missing)}")
    return {k: (values[k], wanted[k]) for k in wanted}


def run_cell(bench: dict, workload: str, seed: int, seconds: float, traced: bool,
             say, n_groups: Optional[int] = None, devices=None) -> str:
    """Run one cell and return its last line.  `n_groups` overrides the
    configuration's group count and `devices` the chip check — for the CPU
    rehearsal (rehearse.py) and the tests only; the command never passes
    them."""
    import jax

    from raft_tpu import platform

    cell, config, mix = find_cell(bench, workload)
    if devices is None:
        devices = require_chips(cell["chips"])
    G = int(n_groups or config["n_groups"])
    wanted = line.expected_metrics(bench, workload, traced)
    readers = metric_readers(bench, workload) if traced else {}

    cache_dir = platform.enable_compile_cache()
    clock = CompileClock()
    t0 = time.monotonic()
    fleet = Fleet(config, G)
    fleet.boot()  # the device boots while the host draws the traffic
    t1 = time.monotonic()
    seg = traffic.generate(mix, G, config["n_peers"], seed, name=workload,
                           voters=fleet.voters, learners=fleet.learners)
    t2 = time.monotonic()
    fleet.load(seg)
    jax.block_until_ready(fleet.sim.state)
    t3 = time.monotonic()
    c0 = clock.seconds
    warm = fleet.segment()  # compiles (or fetches) every program of the window
    t4 = time.monotonic()
    n_sample = max(2, min(8, SAMPLE_GROUP_ROUNDS // (seg.n_rounds + config["boot_rounds"])))
    gids = check.pick_sample(seg, seed, n_sample)
    sample_rows = fleet.rows(gids)
    commit_start = jax.device_get(fleet.sim.state.commit)
    say(json.dumps({
        "setup": {
            "fleet_init_and_boot_dispatch_s": t1 - t0,
            "traffic_generate_s": t2 - t1,
            "upload_and_boot_wait_s": t3 - t2,
            "warmup_segment_s": t4 - t3,
            "compile_or_fetch_s_total": clock.seconds,
            "compile_or_fetch_s_in_warmup": clock.seconds - c0,
            "backend_compiles": clock.backend_compiles,
            "compile_cache": cache_dir,
        },
        "segment": {
            "rounds": seg.n_rounds, "groups": G, "peers": seg.n_peers,
            "read_fires": seg.read_fires, "read_ops": seg.read_ops,
            "update_entries": seg.update_entries, "write_batches": seg.write_batches,
            "share_of_regions_touched_per_round": seg.touched_share,
            "split": seg.split, "conf_ops": seg.conf_ops,
        },
        "warmup_report": warm,
    }))

    # --- the window ---
    reports: List[dict] = []
    compiles_before = clock.backend_compiles
    limit = min(seconds, TRACE_SECONDS) if traced else seconds
    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # host spans come from TraceAnnotation
        jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
    t_start = time.monotonic()
    setup_s = t_start - T_PROCESS
    while True:
        with jax.profiler.TraceAnnotation(trace.SEGMENT_SPAN):
            reports.append(fleet.segment())
        now = time.monotonic()
        if now - t_start >= limit:
            break
    window_s = now - t_start
    if traced:
        jax.profiler.stop_trace()
    device = device_fields(devices)
    compiled_inside = clock.backend_compiles - compiles_before

    # --- correctness, outside the window and outside set-up ---
    t_check = time.monotonic()
    st = fleet.sim.state
    commit_end, agree, voter, outgoing, learner, pending = jax.device_get(
        (st.commit, st.agree, st.voter_mask, st.outgoing_mask, st.learner_mask,
         fleet.sim._read_carry.pending_mode)
    )
    findings = [
        check.safety([warm] + reports),
        check.fires([warm] + reports, seg),
        check.reads(reports[-1], pending),
        check.monotonic(commit_start, commit_end),
        check.durability(commit_end, agree, voter, outgoing),
        check.reference(config, seg, gids, sample_rows),
    ]
    for f in findings:
        say(f"check {f.name}: {f.value} (limit {f.limit}) {'ok' if f.ok else 'FAILED'} — {f.detail}")
    rejected = sum(not f.ok for f in findings)
    say(json.dumps({"check_s": time.monotonic() - t_check,
                    "compiles_inside_window": compiled_inside}))
    if compiled_inside:
        say(f"warning: {compiled_inside} program(s) compiled inside the window")

    counters = summed(reports, G)
    # Groups that are not back in the configuration's membership: an op of
    # the last segment that did not land.
    astray = int(np.any(
        [(got != home).any(axis=0) for got, home in zip((voter, outgoing, learner), fleet.home)],
        axis=0).sum())
    # The line's exact counts are taken over the mix's stated number of
    # segments.  `rejected` (the checks read every report) and `astray` (a
    # state at the window's end) are the whole window's: 0 on a sound program.
    ops_per_segment = seg.read_fires + seg.write_batches + seg.conf_ops
    in_line = counted(reports, mix)
    ops, whole = op_counts(in_line, ops_per_segment), op_counts(reports, ops_per_segment)
    rounds_in_line = round_counts(in_line, wanted)
    attempted = ops["attempted"]
    failed = ops["failed_in_segments"] + rejected + astray
    whole_failed = whole["failed_in_segments"] + rejected + astray
    say(json.dumps({"counted": {
        "counted_segments": mix.get("counted_segments"), **ops, **rounds_in_line,
        "failed": failed, "failed_share_pct": 100.0 * failed / attempted,
        "whole_window": {**whole, "failed": whole_failed,
                         "failed_share_pct": 100.0 * whole_failed / whole["attempted"]},
    }}))
    say(json.dumps({"window": {
        "seconds": window_s, "segments": len(reports), "counters": counters,
        "read_p99_rounds": [r["read_p99"] for r in reports],
        "mttr_rounds": [r["mttr_rounds"] for r in reports],
        "max_leaderless_streak": [r["max_leaderless_streak"] for r in reports],
        "conf_ops_offered": len(reports) * seg.conf_ops,
        "groups_not_back_in_the_configuration": astray,
    }}))

    if not traced:
        metrics = end_to_end(reports, rounds_in_line, window_s, setup_s, G, wanted)
        return line.build(
            correct=rejected == 0, attempted=attempted, failed=failed,
            metrics=metrics, device=device,
        )

    # The one read of the capture: the program's spans, stats and name stacks
    # and the reduction to busy, self times and idle gaps come from it.
    capture = program_trace.read_xplane(trace.newest_xplane(TRACE_DIR))
    trace_facts = program_trace.facts_of(capture)  # a CPU has no device plane: TraceError
    peaks = load_json(HERE, "peaks.json")
    if device["kind"] not in peaks:
        raise BenchError(f"device kind {device['kind']!r} is not in peaks.json")
    facts = {
        **trace_facts,
        "counters": counters,
        "shape": {"n_groups": G, "n_peers": config["n_peers"]},
        "peaks": peaks[device["kind"]],
    }
    facts_trace = trace.TraceFacts(**facts["trace"])
    program = program_names(reports[-1])
    say(json.dumps({"trace": {
        "host_window_s": window_s, "window_s": facts_trace.window_s,
        "busy_s": facts_trace.busy_s, "chips": facts_trace.n_chips,
        "ops": sorted(
            ([trace.short_name(k), v[0], v[1]] for k, v in facts_trace.op_seconds.items()),
            key=lambda row: -row[1])[:25],
        "custom_calls": [[k[:400], v[0], v[1]] for k, v in facts_trace.op_seconds.items()
                         if "custom-call(" in k or "custom_call" in k][:8],
    }}))
    metrics, _left_out = read_metrics(readers, facts, program, workload, say)
    device["window_s"] = facts_trace.window_s
    device["busy_s"] = facts_trace.busy_s
    return line.build(
        correct=rejected == 0, attempted=attempted, failed=failed,
        metrics=metrics, device=device,
        breakdown={
            "device_ops": program_trace.top_ops(facts, program["scopes"] | program["kernels"]),
            "idle_gaps": facts_trace.idle_gaps,
        },
    )


def claim_stdout():
    """Point fd 1 at stderr for everything else in the process, and return
    (say, finish): `say` writes an information line to the real stdout,
    `finish` writes the last line and closes it."""
    sys.stdout.flush()
    real = os.fdopen(os.dup(1), "w", encoding="utf-8")
    os.dup2(2, 1)
    sys.stdout = sys.stderr

    def say(text: str) -> None:
        real.write(text.replace("\n", " ") + "\n")
        real.flush()

    def finish(text: str) -> None:
        real.write(text + "\n")
        real.flush()
        real.close()

    return say, finish


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    say, finish = claim_stdout()
    try:
        bench = load_json(ROOT, "BENCHMARK.json")
        text = run_cell(bench, args.workload, args.seed, args.seconds,
                        bool(args.trace), say)
    except (BenchError, line.LineError, trace.TraceError) as e:
        print(f"benchmark: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    finish(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
