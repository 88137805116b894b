"""The trace reduction: on hand-made events where the right answer is plain,
and on a small recording of a real chip trace (tests/data/chip_trace.json,
TPU v5 lite, fleet-100k-r5.load, PR 25), where the wrong ways of adding up
give visibly wrong answers."""

import os

import pytest

from benchmark import trace
from benchmark.trace import Event

DEV, HOST = "/device:TPU:0", trace.HOST_PLANE
MS = 1e6  # ns
RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "chip_trace.json")


def op(name, start_ms, dur_ms, line=trace.OPS_LINE, plane=DEV):
    return Event(plane, line, name, start_ms * MS, dur_ms * MS)


def span(start_ms, dur_ms, name=trace.SEGMENT_SPAN):
    return Event(HOST, "python3", name, start_ms * MS, dur_ms * MS)


def test_union_not_sum_on_overlap_and_nesting():
    iv = [(0, 10), (5, 15), (20, 30), (22, 25)]
    assert trace.union_seconds(iv, 0, 100) == pytest.approx(25e-9)
    assert trace.union_seconds(iv, 8, 23) == pytest.approx((15 - 8 + 3) * 1e-9)
    assert trace.union_seconds([], 0, 10) == 0.0
    assert trace.gaps(iv, 0, 40) == [(15, 20), (30, 40)]


def test_busy_is_one_line_clipped_to_the_host_window():
    events = [
        span(10, 100),
        op("while.1 while", 0, 60),        # starts before the window: clipped
        op("fusion.1 fusion", 20, 10),     # nested in the while
        op("fusion.2 fusion", 40, 10),     # nested in the while
        op("fusion.3 fusion", 80, 10),
        op("late fusion", 105, 20),        # runs past the window: clipped
        op("jit_step(1)", 0, 125, line="XLA Modules"),  # another line: ignored
        op("step 0", 0, 125, line="Steps"),
    ]
    facts = trace.reduce_events(events)
    assert facts.window_s == pytest.approx(0.100)
    # union: [10, 60] + [80, 90] + [105, 110] = 65 ms
    assert facts.busy_s == pytest.approx(0.065)
    assert 0 < facts.busy_s <= facts.window_s
    # self time: the while is 50 ms clipped, less its 20 ms of body
    assert facts.op_seconds["while.1 while"] == [pytest.approx(0.030), 1]
    assert facts.op_seconds["fusion.1 fusion"] == [pytest.approx(0.010), 1]
    assert facts.op_seconds["late fusion"] == [pytest.approx(0.005), 1]
    assert sum(v[0] for v in facts.op_seconds.values()) == pytest.approx(facts.busy_s)
    assert facts.segments == [{"span_s": pytest.approx(0.1), "busy_s": pytest.approx(0.065)}]
    labels = dict(facts.idle_gaps)
    assert labels["segment.mid.longest"] == pytest.approx(0.020)
    assert labels["segment.mid.total"] == pytest.approx(0.035)


def test_window_spans_all_segments_and_gaps_are_labelled_by_the_host_span():
    events = [span(0, 50), span(60, 50),
              op("a fusion", 5, 40), op("a fusion", 62, 40)]
    facts = trace.reduce_events(events)
    assert facts.window_s == pytest.approx(0.110)
    assert facts.busy_s == pytest.approx(0.080)
    labels = dict(facts.idle_gaps)
    assert labels["segment.head.longest"] == pytest.approx(0.005)
    assert labels["segment.tail.longest"] == pytest.approx(0.008)
    assert labels["between_segments.total"] == pytest.approx(0.010)
    assert [s["busy_s"] for s in facts.segments] == [pytest.approx(0.04)] * 2
    assert len(facts.idle_gaps) <= 10


def test_several_chips_average():
    events = [span(0, 100), op("a", 0, 100), op("a", 0, 50, plane="/device:TPU:1")]
    facts = trace.reduce_events(events)
    assert facts.n_chips == 2 and facts.busy_s == pytest.approx(0.075)


@pytest.mark.parametrize("events,what", [
    ([span(0, 10), Event(HOST, "python3", "x", 0, 1)], "no /device:TPU"),
    ([op("a", 0, 10)], "host span"),
    ([span(0, 10), op("m", 0, 10, line="XLA Modules")], "no 'XLA Ops' line"),
    ([span(0, 10), op("a", 20, 5)], "inside the traced window"),
])
def test_a_trace_without_the_device_fails_loudly(events, what):
    with pytest.raises(trace.TraceError, match=what):
        trace.reduce_events(events)


def test_short_names():
    long = ("%fusion.552 = s32[500000]{0:T(1024)S(1)} fusion(s32[5,100000,5]{2,1,0:T(8,128)} "
            "%copy.333), kind=kCustom, calls=%fused_computation.3")
    assert trace.short_name(long) == "fusion.552 fusion"
    assert trace.short_name("%cond.3 = (s32[5]{0}, s32[2]{0}) conditional(s32[] %p)") == "cond.3 conditional"
    assert trace.short_name("jit_run(123)") == "jit_run(123)"


# --- the recorded chip trace -------------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    return trace.load_recorded(RECORDED)


def test_recorded_trace_has_the_planes_and_lines_the_reduction_expects(recorded):
    planes = {e.plane for e in recorded}
    assert planes == {"/device:TPU:0", "/host:CPU"}
    lines = {e.line for e in recorded if e.plane == "/device:TPU:0"}
    assert {"XLA Ops", "XLA Modules"} <= lines
    assert any(e.name == trace.SEGMENT_SPAN for e in recorded)


def test_recorded_trace_reduces_to_busy_inside_the_window(recorded):
    facts = trace.reduce_events(recorded)
    assert 0.0 < facts.busy_s <= facts.window_s
    ops = [e for e in recorded if e.line == trace.OPS_LINE]
    lo = min(e.start_ns for e in recorded if e.name == trace.SEGMENT_SPAN)
    hi = max(e.end_ns for e in recorded if e.name == trace.SEGMENT_SPAN)
    inside = [e for e in ops if e.start_ns >= lo and e.end_ns <= hi]
    summed = sum(e.dur_ns for e in inside) / 1e9
    # The simple ways are wrong on a real trace: containers (while,
    # conditional) hold their bodies, so durations sum to more than the
    # union, and every line together to more again.
    assert summed > 1.2 * trace.union_seconds([(e.start_ns, e.end_ns) for e in inside], lo, hi)
    device = [e for e in recorded if e.plane == "/device:TPU:0" and lo <= e.start_ns and e.end_ns <= hi]
    assert sum(e.dur_ns for e in device) / 1e9 > summed
    assert sum(v[0] for v in facts.op_seconds.values()) == pytest.approx(facts.busy_s, rel=1e-6)
    assert all(sec >= 0 for _n, sec in facts.idle_gaps) and len(facts.idle_gaps) <= 10


def test_recorded_kernel_is_found_by_the_metric_files_pattern_and_nests_in_its_cond(recorded):
    import json

    from benchmark import reducers

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "metrics", "fused_kernel_share.json"), encoding="utf-8") as f:
        pattern = json.load(f)["args"]["pattern"]
    facts = trace.reduce_events(recorded)
    seconds, calls = reducers.matching(facts.op_seconds, pattern)
    assert calls >= 1 and seconds > 0.001
    cond = [v for k, v in facts.op_seconds.items() if k.startswith("%cond.")]
    # the conditional's SELF time is what is left once the kernel inside it is taken out
    assert cond and cond[0][0] < 0.5 * seconds
    share = reducers.load("op_share").read({"trace": facts._asdict()}, {"pattern": pattern})
    assert 0 < share <= 100
    roof = reducers.load("kernel_roofline")
    assert roof.bytes_per_call({"pg": 15, "ppg": 2, "g": 5}, 100_000, 5) == 4 * 130 * 100_000
    pct = roof.read({"trace": facts._asdict(), "shape": {"n_groups": 100_000, "n_peers": 5},
                     "peaks": {"hbm_bytes_per_s": 819e9}},
                    {"pattern": pattern, "operands": {"pg": 15, "ppg": 2, "g": 5}})
    assert 0 < pct < 100


def test_recorded_gaps_keep_their_old_labels_on_a_program_without_spans(recorded):
    """chip_trace.json is PR 25's program: no `raft.` span in the capture,
    so every gap is named by its place in the segment, as before."""
    facts = trace.reduce_events(recorded)
    assert facts.idle_gaps
    assert {label.split(".")[0] for label, _s in facts.idle_gaps} <= {"segment", "between_segments"}


def test_a_gap_is_cut_at_the_edges_of_the_programs_spans():
    events = [
        span(0, 100),
        span(2, 96, name="raft.run_reads"),
        span(2, 8, name="raft.run_reads.prepare"),
        span(10, 60, name="raft.run_reads.dispatch"),
        span(20, 30, name="raft.runner.blocks"),
        span(70, 28, name="raft.run_reads.report"),
        op("a fusion", 15, 10),   # idle 0-15: 2 outside, 8 in prepare, 5 in dispatch
        op("b fusion", 40, 45),   # idle 25-40 in blocks; 85-100: 13 report, 2 outside
    ]
    labels = dict(trace.reduce_events(events, top_gaps=100).idle_gaps)
    ms = lambda key: labels[key] * 1e3
    assert ms("segment.head.total") == pytest.approx(2.0)
    assert ms("raft.run_reads.prepare.total") == pytest.approx(8.0)
    assert ms("raft.run_reads.dispatch.total") == pytest.approx(5.0)
    assert ms("raft.runner.blocks.total") == pytest.approx(15.0)  # the innermost span
    assert ms("raft.run_reads.report.total") == pytest.approx(13.0)
    assert ms("segment.tail.total") == pytest.approx(2.0)
    assert sum(v for k, v in labels.items() if k.endswith(".total")) == pytest.approx(0.045)
