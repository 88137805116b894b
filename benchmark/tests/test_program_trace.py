"""The one reader of a run's capture — what the program itself writes into
it, merged with `trace.py`'s reduction into the `facts` every reducer gets —
and the reducers that read the program's names: on small recordings from the
chip WITH stats and name stacks (tests/data/program_trace*.json, TPU v5
lite: `program_trace.json`, a 2048 x 3 fleet, two `run_reads(split=True)`
calls of two fused blocks and a general tail of one round, PR 26;
`program_trace_rebalance.json`, the head of one `.rebalance` segment at
2048 x 5, PR 33), and on a capture made here on the CPU for the wire format.

Which metric files read the program's names is derived from
`metrics/*.json` (a reducer with a `names` function), and each file is read
on the first recording whose program carries every name it asks for: a
later PR that adds such a file adds a recording that shows it, and edits no
list here."""

import glob
import json
import os

import pytest

from benchmark import program_trace as pt
from benchmark import reducers, run, trace

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "program_trace.json")
RECORDINGS = [RECORDED] + sorted(  # the PR 26 one first: most files read it
    set(glob.glob(os.path.join(HERE, "data", "program_trace*.json"))) - {RECORDED})
METRICS = os.path.join(os.path.dirname(HERE), "metrics")
SPECS = {
    os.path.basename(p)[:-len(".json")]: json.load(open(p, encoding="utf-8"))
    for p in glob.glob(os.path.join(METRICS, "*.json"))
}
NEW = sorted(n for n, s in SPECS.items() if hasattr(reducers.load(s["reducer"]), "names"))
KNOWN = {"runner.block_guard", "runner.fused_arm", "runner.general_arm", "round.damped",
         "quorum_commit", "op_gather", "raft_steady_damped"}


def spec_of(name):
    with open(os.path.join(METRICS, name + ".json"), encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cap():
    return pt.load_recorded(RECORDED)


def carried(cap):
    """The names a recording shows its program to carry: its spans, the
    counts they were closed with, every component of its ops' name stacks."""
    parts = {part for o in cap.ops for part in o.path.rstrip(":").split("/")}
    return {"spans": {s.name for s in cap.spans}, "scopes": parts, "kernels": parts,
            "counts": {k for s in cap.spans for k in s.stats}}


@pytest.fixture(scope="module")
def recording_of():
    """{metric file that reads the program's names: (capture, facts) of the
    first recording that carries them all}."""
    shown = []
    for path in RECORDINGS:
        cap = pt.load_recorded(path)
        shown.append((path, cap, pt.facts_of(cap), carried(cap)))
    out = {}
    for name in NEW:
        spec = SPECS[name]
        reducer = reducers.load(spec["reducer"])
        for path, cap, facts, program in shown:
            if not reducers.lacking(reducer, spec["args"], program):
                out[name] = (path, cap, facts)
                break
    return out


@pytest.fixture(scope="module")
def facts(cap):
    """What run.py would hand a reader, plus the capture."""
    return pt.facts_of(cap)


def read(name, facts):
    spec = spec_of(name)
    return reducers.load(spec["reducer"]).read(facts, spec["args"])


def test_the_loader_looks_where_run_py_traces():
    assert pt.TRACE_DIR == run.TRACE_DIR


def test_every_metric_file_that_reads_the_programs_names_has_a_recording(recording_of):
    """A reader of a name no recording carries has never been shown to read
    a number off the chip: record one (`program_trace.py export`) beside it."""
    missing = sorted(set(NEW) - set(recording_of))
    assert NEW and not missing, f"no recording under tests/data carries the names of {missing}"
    assert not any("loader" in s for s in SPECS.values())  # one way only: facts


def test_every_metric_file_is_listed_in_benchmark_json(bench):
    assert {m["name"] for m in bench["per_layer"]} == set(SPECS)


def test_metric_files_spell_names_from_the_programs_catalogue():
    from raft_tpu import profiling

    for name in NEW:
        args = spec_of(name)["args"]
        if "span" in args:
            assert args["span"] in profiling.SPANS, name
        if "scope" in args:
            assert args["scope"] in profiling.SCOPES, name
        if "kernel" in args:
            assert args["kernel"] in profiling.KERNELS, name
    assert pt.RUN_SPAN in profiling.SPANS
    assert pt.PROGRAM_PREFIX == profiling.SPAN_PREFIX


def test_window_is_reduce_events_window(cap, facts):
    lo, hi = pt.window(cap)
    assert (hi - lo) / 1e9 == facts["trace"]["window_s"]
    busy = trace.union_seconds(pt.op_intervals(cap, pt.planes(cap)[0]), lo, hi)
    assert busy == pytest.approx(facts["trace"]["busy_s"], rel=1e-12)


def test_the_recording_holds_the_span_tree_with_its_counts(cap):
    calls = pt.spans_named(cap, pt.RUN_SPAN)
    assert [c.stats["call"] for c in calls] == [2, 3]  # call 1 was the warm-up
    for c in calls:
        assert c.stats["rounds"] == 17 and c.stats["groups"] == 2048
    reports = pt.spans_named(cap, "raft.run_reads.report")
    assert [r.stats["call"] for r in reports] == [2, 3]
    for r in reports:
        assert r.stats["fused_rounds"] == 16 * 2048
        assert r.stats["total_rounds"] == 17 * 2048
        assert r.stats["appends_offered"] == 17 * 2048
        assert r.stats["appends_dropped"] == 0
        assert r.stats["safety.dual_leader"] == 0
    blocks = pt.spans_named(cap, "raft.runner.blocks")
    assert [b.stats for b in blocks] == [{"blocks": 2, "tail": 1}] * 2


def test_name_stack_rules():
    path = "jit(block_run)/cond/branch_1_fun/runner.fused_arm/raft_steady_damped/pallas_call:"
    assert pt.has_kernel(path, "raft_steady_damped")
    assert not pt.has_kernel(path, "raft_steady")
    assert pt.has_scope(path, "runner.fused_arm")
    assert not pt.has_scope(path, "runner.fused")  # whole components only
    inner = "jit(f)/while/body/round.damped/quorum_commit/jit(take_along_axis)/gather:"
    assert pt.has_scope(inner, "quorum_commit") and pt.has_scope(inner, "round.damped")
    assert not pt.has_kernel(inner, "quorum_commit")
    assert not pt.has_scope("", "round")


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_its_number_on_its_recording(name, recording_of):
    path, _cap, facts = recording_of[name]
    value = read(name, facts)
    assert isinstance(value, float)
    unit = spec_of(name)["unit"]
    if unit == "%":
        assert 0.0 <= value <= 100.0
    if path == RECORDED:  # PR 26's: nothing was lost
        if name == "recover_p99_rounds":
            assert value == -1.0  # ... so no episode ended
        elif name in ("leaderless_rounds_share", "append_drop_share"):
            assert value == 0.0
        elif name != "idle_prepare_ms":
            assert value > 0.0
    else:
        assert value >= 0.0


@pytest.mark.parametrize("name", NEW)
def test_reader_returns_none_for_a_program_without_names(name, recording_of):
    """The parent of PR 26: no `raft.` span, no scope, no kernel name."""
    _path, cap, facts = recording_of[name]
    bare = pt.Capture(
        [s for s in cap.spans if not s.name.startswith(pt.PROGRAM_PREFIX)],
        [o._replace(path="") for o in cap.ops],
        cap.modules,
    )
    assert read(name, {**facts, "capture": bare}) is None


def test_span_counter_returns_none_where_a_stat_is_missing(cap, facts):
    older = pt.Capture(
        [s._replace(stats={k: v for k, v in s.stats.items() if k != "appends_offered"})
         for s in cap.spans],
        cap.ops, cap.modules,
    )
    assert read("append_drop_share", {**facts, "capture": older}) is None
    assert read("recover_p99_rounds", {**facts, "capture": older}) is not None


def test_damped_kernel_share_is_the_custom_calls_share(facts):
    """On a capture whose only kernel is the damped one, the share found by
    the kernel's NAME equals the share found by `tpu_custom_call`."""
    by_target = reducers.load("op_share").read(facts, spec_of("fused_kernel_share")["args"])
    assert read("damped_kernel_share", facts) == pytest.approx(by_target, rel=1e-9)


def test_idle_in_spans_sums_to_the_gaps_inside_the_calls(cap, facts):
    calls = pt.spans_named(cap, pt.RUN_SPAN)
    inside_ms = 1e3 * pt.idle_seconds_in(cap, calls) / len(calls)
    parts = [read(n, facts) for n in ("idle_prepare_ms", "idle_dispatch_ms", "idle_report_ms")]
    assert sum(parts) <= inside_ms * (1 + 1e-9)
    assert sum(parts) >= 0.95 * inside_ms
    # ... and the calls' idle time is what host_ms_per_segment reads, less
    # the sliver of the benchmark's own span around each call.
    host_ms = reducers.load("host_per_segment").read(facts, {})
    assert inside_ms <= host_ms
    assert inside_ms >= 0.95 * host_ms


def test_programs_per_segment_counts_modules_inside_the_calls(cap, facts):
    value = read("programs_per_segment", facts)
    # At least the two block programs and the tail program of each call,
    # and no program of the capture counted twice.
    assert 3 <= value <= len(cap.modules) / 2
    assert value * 2 == int(value * 2)  # a mean over the two calls


def test_scopes_partition_no_more_than_busy(cap, facts):
    shares = [
        100.0 * pt.self_seconds_where(cap, lambda p, s=s: pt.has_scope(p, s))[0]
        / facts["trace"]["busy_s"]
        for s in ("runner.block_guard", "runner.fused_arm", "runner.general_arm")
    ]
    assert shares[0] > 0 and shares[1] > 0
    assert shares[2] == 0.0  # every block fused: the fallback never ran
    assert sum(shares) <= 100.0 + 1e-6


def test_read_xplane_agrees_with_profiledata(tmp_path):
    """The wire-format reader on a capture made here: the same spans, the
    same whole-ns times and the same stats as jax's own reader gives."""
    import jax
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    with jax.profiler.TraceAnnotation(trace.SEGMENT_SPAN):
        with jax.profiler.TraceAnnotation("raft.run_reads", call=7, rounds=24) as whole:
            whole.set_metadata(groups=64)
            with jax.profiler.TraceAnnotation("raft.run_reads.report", neg=-3):
                pass
        with jax.profiler.TraceAnnotation("other.span"):
            pass
    jax.profiler.stop_trace()
    path = trace.newest_xplane(str(tmp_path))
    want = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("raft.", "bench.")):
                    want.append((ev.name, ev.start_ns, ev.duration_ns, dict(ev.stats)))
    got = pt.read_xplane(path)
    assert [tuple(s) for s in got.spans] == sorted(want, key=lambda s: (s[1], -s[2]))
    assert [s.name for s in got.spans] == [
        trace.SEGMENT_SPAN, "raft.run_reads", "raft.run_reads.report"]
    assert got.spans[1].stats == {"call": 7, "rounds": 24, "groups": 64}
    assert got.spans[2].stats == {"neg": -3}
    assert got.ops == [] and got.modules == []
    for name in NEW:  # no device plane here: nothing to read
        if spec_of(name)["source"] != "program_counter":
            assert read(name, {"trace": {"busy_s": 1.0}, "capture": got}) is None


def test_export_round_trips(cap, tmp_path):
    out = tmp_path / "again.json"
    pt.export(cap, str(out), per_line=10 ** 6)
    again = pt.load_recorded(str(out))
    assert again.spans == cap.spans
    assert len(again.ops) == len([o for o in cap.ops if o.end_ns >= pt.window(cap)[0]])
    assert pt.metrics(again).keys() == set(NEW)


# --- one read, merged facts; the None rule; the named breakdown ----------------


def test_facts_hold_the_reduction_and_the_capture_from_one_read(cap, facts):
    assert set(facts) == {"trace", "capture"} and facts["capture"] is cap
    t = trace.TraceFacts(**facts["trace"])
    assert 0.0 < t.busy_s <= t.window_s and t.n_chips == 1
    # The op table is keyed by instruction text, as trace.py keys it, and the
    # capture gives each instruction its name stack.
    assert set(t.op_seconds) <= {o.name for o in cap.ops}
    assert sum(v[0] for v in t.op_seconds.values()) == pytest.approx(t.busy_s, rel=1e-6)


def test_idle_gaps_are_named_after_the_programs_spans(cap, facts):
    labels = dict(facts["trace"]["idle_gaps"])
    spans = {k.rsplit(".", 1)[0] for k in labels}
    assert "raft.run_reads.dispatch" in spans
    assert spans <= {"raft.run_reads", "raft.run_reads.prepare", "raft.run_reads.dispatch",
                     "raft.runner.blocks", "raft.run_reads.report", "raft.run_reads.download",
                     "segment.head", "segment.mid", "segment.tail", "between_segments"}
    # Naming cuts gaps up; it neither makes nor loses idle time.
    bare = [trace.Event(trace.HOST_PLANE, "", s.name, s.start_ns, s.dur_ns)
            for s in cap.spans if s.name == trace.SEGMENT_SPAN]
    bare += [trace.Event(o.plane, o.line, o.name, o.start_ns, o.dur_ns) for o in cap.ops]
    old = dict(trace.reduce_events(bare, top_gaps=100).idle_gaps)
    new = dict(trace.reduce_events(
        bare + [trace.Event(trace.HOST_PLANE, "", s.name, s.start_ns, s.dur_ns)
                for s in cap.spans if s.name.startswith(pt.PROGRAM_PREFIX)],
        top_gaps=100).idle_gaps)
    assert {k.split(".")[0] for k in old} <= {"segment", "between_segments"}
    total = lambda d: sum(v for k, v in d.items() if k.endswith(".total"))
    assert total(new) == pytest.approx(total(old), rel=1e-9)


def test_the_breakdown_names_an_op_by_its_scope_path(facts):
    rows = pt.top_ops(facts, KNOWN)
    assert len(rows) == 10 and all(sec > 0 for _l, sec in rows)
    labels = [l for l, _s in rows]
    assert "op_gather/.../gather fusion.27 pred[6144]" in labels
    assert sum(l.startswith("quorum_commit/.../gather fusion.") for l in labels) == 8
    assert all(len(l) <= 120 and "%" not in l for l in labels)
    # A program without names falls back to the old labels.
    assert [l for l, _s in pt.top_ops(facts, set())] == [
        trace.short_name(n) for n, _v in sorted(
            facts["trace"]["op_seconds"].items(), key=lambda kv: -kv[1][0])[:10]]


def test_op_label_forms():
    text = "%fusion.514 = pred[500000]{0:T(1024)S(1)} fusion(pred[5,100000]{1,0} %p), kind=kLoop"
    path = ("jit(scan_run)/while/body/runner.general_arm/op_gather/"
            "jit(take_along_axis)/gather:")
    assert pt.op_label(text, path, KNOWN) == "op_gather/.../gather fusion.514 pred[500000]"
    assert pt.op_label(text, "jit(f)/op_gather/gather:", KNOWN) == "op_gather/gather fusion.514 pred[500000]"
    assert pt.op_label(text, "jit(f)/while/body/add:", KNOWN) == "fusion.514 fusion"
    assert pt.op_label(text, "", KNOWN) == "fusion.514 fusion"
    kernel = ('%raft_steady_damped.1 = (s32[5,100000]{1,0:T(8,128)}, s32[5,100000]{1,0}) '
              'custom-call(s32[5,100000]{1,0} %p), custom_call_target="tpu_custom_call"')
    assert pt.op_label(kernel, "jit(b)/cond/runner.fused_arm/raft_steady_damped/pallas_call:",
                       KNOWN) == "raft_steady_damped/pallas_call raft_steady_damped.1 (s32[5,100000],..)"


PROGRAM = {"spans": {"raft.run_reads", "raft.run_reads.report", "raft.run_reads.dispatch"},
           "scopes": {"quorum_commit", "op_gather"}, "kernels": {"raft_steady_damped"},
           "counts": {"appends_offered", "appends_dropped"}}


def readers_of(*names):
    return {n: run.Reader(SPECS[n]["unit"], reducers.load(SPECS[n]["reducer"]), SPECS[n]["args"],
                          SPECS[n].get("needs"))
            for n in names}


# A share of a scope no op of the recording carries.
NO_SUCH = {"later_scope_share": run.Reader("%", reducers.load("scope_share"),
                                           {"scope": "later.scope"})}


def test_a_metric_whose_name_the_program_lacks_is_left_out(facts):
    """No op of the recording carries the scope `later.scope`, and the
    program's catalogue has no such scope: the metric is left out, said so,
    and the others are read."""
    said = []
    got, left_out = run.read_metrics(
        {**NO_SUCH, **readers_of("damped_kernel_share")}, facts, PROGRAM, "a.cell", said.append)
    assert left_out == ["later_scope_share"] and list(got) == ["damped_kernel_share"]
    assert said == ["metric later_scope_share left out: the program has no scopes 'later.scope'"]


def test_a_metric_whose_name_the_program_has_and_the_trace_lacks_stops_the_run(facts):
    newer = {**PROGRAM, "scopes": PROGRAM["scopes"] | {"later.scope"}}
    with pytest.raises(run.BenchError, match="later_scope_share.*found nothing to read"):
        run.read_metrics(NO_SUCH, facts, newer, "a.cell", print)
    # A reader that asks the program for no name stops the run whatever the program is.
    bare = {**facts, "trace": {**facts["trace"], "op_seconds": {}}}
    with pytest.raises(run.BenchError, match="fused_kernel_share"):
        run.read_metrics(readers_of("fused_kernel_share"), bare, {}, "a.cell", print)


def test_a_count_the_report_lacks_is_a_name_the_program_lacks(facts):
    program = {**PROGRAM, "counts": {"appends_dropped"}}
    cap = facts["capture"]
    older = pt.Capture(
        [s._replace(stats={k: v for k, v in s.stats.items() if k != "appends_offered"})
         for s in cap.spans], cap.ops, cap.modules)
    got, left_out = run.read_metrics(
        readers_of("append_drop_share"), {**facts, "capture": older}, program, "a.cell",
        lambda _t: None)
    assert got == {} and left_out == ["append_drop_share"]


# --- the third case: the reader's subject did not run in the window ---------------

SERVE = "fleet-100k-r5.serve"
NEEDY = ["general_round_ms", "quorum_commit_share", "op_gather_share"]  # BENCHMARK.json's order


def serve_facts(cap, fused_of_17):
    """What a traced `.serve` run would hand its readers, from the PR 26
    recording (two calls of 17 rounds at 2048 x 3) with the reports' counts
    grafted on: `fused_of_17` of each call's rounds ran fused."""
    G = 2048
    counters = {"segments": 2, "rounds": 34, "group_rounds": 34 * G, "total_rounds": 34 * G,
                "fused_rounds": 2 * fused_of_17 * G}
    return {**pt.facts_of(cap), "counters": counters, "shape": {"n_groups": G, "n_peers": 3},
            "peaks": run.load_json(os.path.dirname(HERE), "peaks.json")["TPU v5 lite"]}


def without_general_ops(cap):
    """The recording as a window of fused blocks alone would leave it: no
    op under a scope of the general path (here: each call's one tail round)."""
    return cap._replace(ops=[o for o in cap.ops if not any(
        pt.has_scope(o.path, s) for s in ("op_gather", "quorum_commit"))])


def test_no_general_round_in_the_window_leaves_its_readers_out_and_the_line_is_ok(bench, cap):
    facts = serve_facts(without_general_ops(cap), fused_of_17=17)
    readers = run.metric_readers(bench, SERVE)
    assert [n for n, r in readers.items() if r.needs] == NEEDY
    said = []
    metrics, left_out = run.read_metrics(readers, facts, PROGRAM, SERVE, said.append)
    assert left_out == NEEDY
    assert said == [f"metric {n} left out: the window ran no general round" for n in NEEDY]
    assert metrics["fused_frac"] == (1.0, "ratio")
    t = facts["trace"]
    text = run.line.build(
        correct=True, attempted=10, failed=0, metrics=metrics,
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 1,
                "window_s": t["window_s"], "busy_s": t["busy_s"]},
        breakdown={"device_ops": pt.top_ops(facts, KNOWN), "idle_gaps": t["idle_gaps"]})
    assert run.line.validate(text + "\n", bench, SERVE, True, left_out=left_out) == []  # "line ok"
    missing = run.line.validate(text + "\n", bench, SERVE, True)
    assert len(missing) == 3 and all("absent" in p for p in missing)  # ... only when told


def test_a_reader_that_finds_its_subject_keeps_its_value_whatever_the_counts_say(bench, cap):
    """All fused by the counts, but ops under `quorum_commit` and
    `op_gather` ran (a tail audit's, say): a number read is a number kept."""
    facts = serve_facts(cap, fused_of_17=17)
    metrics, left_out = run.read_metrics(
        run.metric_readers(bench, SERVE), facts, PROGRAM, SERVE, lambda _t: None)
    assert left_out == ["general_round_ms"]
    assert metrics["op_gather_share"][0] > 0 and metrics["quorum_commit_share"][0] > 0


@pytest.mark.parametrize("name", NEEDY)
def test_general_rounds_ran_and_the_trace_shows_nothing_stops_the_run_as_before(name, cap):
    facts = serve_facts(without_general_ops(cap), fused_of_17=16)
    if name == "general_round_ms":  # reads busy time whenever a general round ran
        assert read(name, facts) > 0
        return
    with pytest.raises(run.BenchError, match=f"{name}.*found nothing to read"):
        run.read_metrics(readers_of(name), facts, PROGRAM, SERVE, print)


def test_a_reader_without_needs_that_finds_nothing_stops_the_run_as_before(cap):
    facts = serve_facts(without_general_ops(cap), fused_of_17=17)
    bare = {**facts, "trace": {**facts["trace"], "op_seconds": {}}}
    assert "needs" not in SPECS["fused_kernel_share"]
    with pytest.raises(run.BenchError, match="fused_kernel_share.*found nothing to read"):
        run.read_metrics(readers_of("fused_kernel_share"), bare, PROGRAM, SERVE, print)
    # ... and so does one of the three with its `needs` taken away.
    plain = {"op_gather_share": readers_of("op_gather_share")["op_gather_share"]._replace(needs=None)}
    with pytest.raises(run.BenchError, match="op_gather_share.*found nothing to read"):
        run.read_metrics(plain, facts, PROGRAM, SERVE, print)


def test_a_metric_file_that_needs_an_unknown_condition_is_refused(bench, monkeypatch):
    real = run.load_json

    def load(*parts):
        doc = real(*parts)
        return {**doc, "needs": "fused_rounds"} if parts[-1] == "fused_frac.json" else doc

    monkeypatch.setattr(run, "load_json", load)
    with pytest.raises(run.BenchError, match="fused_frac.json needs 'fused_rounds'"):
        run.metric_readers(bench, SERVE)


def test_program_names_are_the_catalogue_and_the_reports_counts():
    from raft_tpu import profiling

    names = run.program_names({"rounds": 24, "read_p99": 0, "mttr_rounds": 21.5,
                               "safety": {"dual_leader": 0}, "ok": True})
    assert names["spans"] == set(profiling.SPANS) and "op_gather" in names["scopes"]
    assert names["kernels"] == set(profiling.KERNELS)
    assert names["counts"] == {"call", "groups", "rounds", "read_p99", "safety.dual_leader"}
    for spec in SPECS.values():  # today's program carries every name the files ask for
        reducer = reducers.load(spec["reducer"])
        program = {**names, "counts": names["counts"] | set(
            getattr(reducer, "names", lambda a: {})(spec["args"]).get("counts", []))}
        assert reducers.lacking(reducer, spec["args"], program) == []


def test_a_run_that_stops_over_a_listed_metric_exits_2_and_prints_no_line():
    """What `read_metrics` raises reaches the command as exit code 2 and an
    empty stdout, the reason on stderr."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from benchmark import run\n"
        "def stop(*a, **k):\n"
        "    run.read_metrics({'m': run.Reader('%', run.reducers.load('op_share'), {'pattern': 'x'})},\n"
        "                     {'trace': {'op_seconds': {}, 'busy_s': 1.0}}, {}, 'a.cell', print)\n"
        "run.run_cell = stop\n"
        "sys.exit(run.main(['--workload', 'fleet-100k-r5.serve', '--seed', '1',\n"
        "                   '--seconds', '1', '--trace', '1']))\n"
    )
    root = os.path.dirname(os.path.dirname(HERE))
    done = subprocess.run([sys.executable, "-c", code], cwd=root, capture_output=True,
                          text=True, timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 2 and done.stdout == ""
    assert "BenchError" in done.stderr and "found nothing to read" in done.stderr
