"""`reducers/unnamed_share.py`: the compiler's own ops counted by kind, on a
hand-made capture (every placement rule one event) and on the chip
recording that carries the round's map
(`data/program_trace_store_loss_round_map.json`, PR 42: the head of one
`fleet-100k-r5.outage` segment, one whole round and the start of the next)."""

import glob
import json
import os

import pytest

from benchmark import program_trace as pt
from benchmark import reducers, run, trace
from benchmark.reducers import unnamed_share as u

HERE = os.path.dirname(os.path.abspath(__file__))
ROUND_MAP = os.path.join(HERE, "data", "program_trace_store_loss_round_map.json")
METRICS = os.path.join(os.path.dirname(HERE), "metrics")
PLANE = "/device:TPU:0"
BODY = "jit(run)/while/body/closed_call/"
WAVE3 = BODY + "round/round.damped/damped.wave3/select_n"
CLIENT = BODY + "runner.client/and"
BARE = BODY + "add"  # a named op under no catalogue scope


def op(name, path, start, dur):
    return pt.Op(PLANE, trace.OPS_LINE, name, path, float(start), float(dur))


def facts_of(ops, lo=0, hi=1000):
    cap = pt.Capture([pt.Span(trace.SEGMENT_SPAN, float(lo), float(hi - lo), {})], ops, [])
    return pt.facts_of(cap)


def read(facts, **args):
    return u.read(facts, args)


#   0        100       200       300       400       500       600      700
#   | copy.1 | while.2 ......................................... |
#            | fusion(wave3) | copy.3 | fusion(client) | unnamed fusion, (loop) | add | copy-start/-done
HAND = [
    op("%copy.1 = s32[5,8]{1,0} copy(s32[5,8]{0,1} %p)", "", 0, 100),  # first on its line
    op("%while.2 = (s32[], s32[5,8]{1,0}) while((s32[], s32[5,8]{1,0}) %t), body=%b", "", 100, 500),
    op("%fusion.7 = s32[5,8]{1,0} fusion(s32[5,8]{1,0} %a), kind=kLoop", WAVE3, 100, 100),
    op("%copy.3 = s32[5,8]{0,1} copy(s32[5,8]{1,0} %fusion.7)", "", 200, 100),  # wave 3's
    op("%fusion.8 = pred[8]{0} fusion(pred[8]{0} %c), kind=kLoop", CLIENT, 300, 100),
    op("%bitcast_dynamic-update-slice_fusion.5 = (s32[5,5,8]{2,1,0}, pred[5,8]{1,0}) fusion(s32[5,8]{1,0} %y)",
       "", 400, 50),  # a multi-output fusion: no root to be named after
    op("%add.9 = s32[] add(s32[] %i, s32[] %one)", BARE, 600, 40),
    op("%copy-start.4 = (s32[5,8]{1,0:S(1)}, s32[5,8]{1,0}, u32[]) copy-start(s32[5,8]{1,0} %x)",
       "", 640, 10),
    op("%fusion.10 = s32[5,8]{1,0} fusion(s32[5,8]{1,0} %d), kind=kLoop", WAVE3, 650, 30),
    op("%copy-done.4 = s32[5,8]{1,0:S(1)} copy-done((s32[5,8]{1,0:S(1)}, u32[]) %copy-start.4)",
       "", 680, 20),
]


@pytest.fixture()
def hand():
    return facts_of(HAND)


def total(found):
    return (found["scoped"] + found["containers"] + found["unscoped"]
            + sum(found["copies"].values()) + sum(found["fusions"].values()))


def test_the_five_classes_partition_busy(hand):
    found = u.parts(hand)
    busy = hand["trace"]["busy_s"]
    assert busy == pytest.approx(700e-9)
    assert total(found) == pytest.approx(busy, rel=1e-12)
    assert found["scoped"] == pytest.approx(230e-9)  # two wave-3 fusions and the client's
    assert found["unscoped"] == pytest.approx(40e-9)  # the named add under no catalogue scope
    assert read(hand, what="unscoped") == pytest.approx(100 * 40 / 700)
    # The unnamed multi-output fusion is the compiler's, placed like a copy.
    assert read(hand, what="fusions") == pytest.approx(100 * 50 / 700)
    assert read(hand, what="fusions", near="runner.client") == pytest.approx(100 * 50 / 700)
    assert read(hand, what="fusions", near="damped.wave3") == 0.0


def test_a_container_counts_its_own_time_less_the_ops_nested_in_it(hand):
    # while.2 spans 500 ns and holds three ops of 100 ns and one of 50.
    assert read(hand, what="containers") == pytest.approx(100 * 150 / 700)


def test_a_copy_belongs_to_the_named_op_before_it_on_its_line(hand):
    assert read(hand, what="copies") == pytest.approx(100 * (100 + 100 + 10 + 20) / 700)
    # copy.3 follows a wave-3 op inside the loop; copy.1 is first on the line: nobody's.
    near = u.parts(hand)["copies"]
    assert near[""] == pytest.approx(100e-9) and near[WAVE3] == pytest.approx(120e-9)
    assert read(hand, what="copies", near="damped.wave3") == pytest.approx(100 * 120 / 700)
    assert read(hand, what="copies", near="round.damped") == pytest.approx(100 * 120 / 700)


def test_a_copy_start_done_pair_is_placed_event_by_event(hand):
    """The start follows the unscoped add, the done the wave-3 op it was
    issued around: the pair is split, and the transfer between them is in
    neither (it runs beside fusion.10, whose time it shows in)."""
    near = u.parts(hand)["copies"]
    assert near[BARE] == pytest.approx(10e-9)
    assert read(hand, what="copies", near="runner.client") == 0.0  # measured: no copy follows it


def test_near_a_scope_no_op_carries_is_nothing_to_read(hand):
    assert read(hand, what="copies", near="round.linked") is None
    assert read(hand, what="copies", near="no.such.scope") is None


def test_no_copy_is_a_measured_zero_and_no_device_op_is_none():
    named_only = facts_of([o for o in HAND if o.path])
    assert read(named_only, what="copies") == 0.0
    assert read(named_only, what="containers") == 0.0
    assert read(named_only, what="fusions") == 0.0
    empty = {"trace": {"busy_s": 1.0}, "capture": pt.Capture([], [], [])}
    for what in ("copies", "containers", "fusions", "unscoped"):
        assert read(empty, what=what) is None


def test_the_pass_is_kept_with_the_capture_it_was_made_from(hand):
    first = u.parts(hand)
    assert u.parts(hand) is first
    other = facts_of([o for o in HAND if o.path])
    assert u.parts({**hand, "capture": other["capture"]}) is not first  # another capture: read anew


def test_a_program_without_a_catalogue_is_held_against_none(hand, monkeypatch):
    """The parent of PR 26 names nothing: its named ops are all unscoped,
    its copies and containers what they are."""
    monkeypatch.setattr(u, "catalogue", lambda: frozenset())
    fresh = facts_of(HAND)
    assert read(fresh, what="unscoped") == pytest.approx(100 * 270 / 700)
    assert read(fresh, what="copies") == pytest.approx(100 * 230 / 700)
    assert read(fresh, what="fusions") == pytest.approx(100 * 50 / 700)


@pytest.mark.parametrize("text,kinds", [
    ("%copy-done.66 = s32[5,5,100000]{2,1,0:T(8,128)S(1)} copy-done((s32[5,5,100000]{2,1,0:T(8,128)S(1", {"copy-done"}),
    ("%slice-done.4 = s32[5,25088]{1,0:T(8,128)S(1)} async-done(((s32[5,100000]{1,0:T(8,128)}), s32[5,", {"slice-done", "async-done"}),
    ("%slice-start.4 = ((s32[5,100000]{1,0:T(8,128)}), s32[5,25088]{1,0:T(8,128)S(1)}, s32[]{:S(2)}) a", {"slice-start"}),
    ("%while.428 = (s32[]{:T(128)}, s32[5,100000]{1,0:T(8,128)S(1)}, s32[5,100000]{1,0:T(8,128)S(1)}, ", {"while"}),
    ("%cond.2.clone.1 = (s32[65]{0:T(128)}) conditiona", {"cond"}),
    ("%cond.50 = (s32[3,2048]{1,0:T(4,128)}) conditional(s32[] %p), branch_computations={%a, %b}", {"cond", "conditional"}),
    ("%copy_bitcast_fusion.1 = s32[3,2048]{1,0:T(4,128)} fusion(s32[3,2048]{1,0} %p), kind=kLoop", {"copy_bitcast_fusion", "fusion"}),
    ("%copy_bitcast_fusion.1 = s32[3,2048]{1,0:T(4,128", {"copy_bitcast_fusion"}),
    ("%fusion.2400 = (s32[5,100000]{1,0:T(8,128)}, pred[5,100000]{1,0:T(8,128)(4,1)}) fus", {"fusion"}),
    ("%iota.155 = s32[3,2048,3]{1,2,0:T(4,128)S(1)} io", {"iota"}),
])
def test_kinds_of_an_instruction_text_whole_or_cut(text, kinds):
    assert u.kinds(text) == kinds
    want = ("copies" if kinds & u.COPIES else "containers" if kinds & u.CONTAINERS
            else "fusions" if "fusion" in text.split(" = ")[0] else "unscoped")
    assert u.classify(text, "", frozenset()) == want


def test_the_reader_asks_the_program_for_no_name():
    assert not hasattr(u, "names")
    assert reducers.lacking(u, {"what": "copies", "near": "round.damped"}, {}) == []


# --- on the chip recording ------------------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    cap = pt.load_recorded(ROUND_MAP)
    return cap, pt.facts_of(cap)


def specs(reducer=None):
    out = {}
    for path in glob.glob(os.path.join(METRICS, "*.json")):
        with open(path, encoding="utf-8") as f:
            spec = json.load(f)
        if reducer is None or spec["reducer"] == reducer:
            out[os.path.basename(path)[:-len(".json")]] = spec
    return out


def test_the_recording_carries_every_scope_a_round_of_the_cell_runs(recorded):
    from raft_tpu import profiling

    cap, _facts = recorded
    carried = {part for o in cap.ops for part in o.path.rstrip(":").split("/")}
    never_in_this_cell = {
        "round.linked", "damped.read_holders", "read_latency", "runner.block_guard",
        "runner.fused_arm", "runner.general_arm",
    } | {s for s in profiling.SCOPES if s.startswith("linked.")}
    assert set(profiling.SCOPES) - never_in_this_cell <= carried
    assert os.path.getsize(ROUND_MAP) < 300_000


def test_on_the_recording_the_classes_partition_busy(recorded):
    _cap, facts = recorded
    found = u.parts(facts)
    assert total(found) == pytest.approx(facts["trace"]["busy_s"], rel=1e-9)
    shares = {what: read(facts, what=what)
              for what in ("copies", "containers", "fusions", "unscoped")}
    assert all(v is not None and v >= 0.0 for v in shares.values())
    assert shares["copies"] > 0.0
    near = read(facts, what="copies", near="round.damped")
    assert 0.0 < near <= shares["copies"]
    # The sections are consecutive, so the copies near each add up to the
    # copies near the round.
    sections = [s for s in u.catalogue() if s.startswith("damped.")
                and s not in ("damped.stage_fold", "damped.merge_agree", "damped.cut_before",
                              "damped.read_holders")]
    by_section = [read(facts, what="copies", near=s) for s in sections]
    assert sum(v for v in by_section if v) <= near * (1 + 1e-9)


@pytest.mark.parametrize("name", sorted(specs("unnamed_share")))
def test_every_unnamed_share_file_reads_the_recording_and_a_bare_program(name, recorded):
    cap, facts = recorded
    spec = specs()[name]
    assert isinstance(u.read(facts, spec["args"]), float)
    bare = cap._replace(ops=[o._replace(path="") for o in cap.ops])
    value = u.read({**pt.facts_of(bare)}, spec["args"])
    # No name stack anywhere: nothing to be near to; the classes still read.
    assert (value is None) == ("near" in spec["args"])


def test_the_round_map_sums_to_the_whole(recorded):
    _cap, facts = recorded
    m = u.round_map(facts, ["round.damped", "damped.tally"])
    assert sum(m["classes"].values()) == pytest.approx(100.0)
    assert sum(m["by_scope"].values()) == pytest.approx(m["classes"]["scoped"])
    assert set(m["copies_near"]) == {"round.damped", "damped.tally"}
    assert not set(m["by_scope"]) & set(u.WRAPPERS) - {"round.damped", "round"}


def test_the_new_cells_lines_validate_with_and_without_the_new_names(bench, recorded):
    """`fleet-100k-r5.outage` read off the recording by every file the cell
    lists: with today's catalogue every metric is on the line; held against
    the parent's catalogue the new-scope files are left out by name and the
    three classes still read."""
    from raft_tpu import profiling

    cap, facts = recorded
    cell = "fleet-100k-r5.outage"
    G, rounds = 100000, 600  # one segment of the cell: every round a general one
    counters = {"segments": 1, "rounds": rounds, "group_rounds": rounds * G,
                "total_rounds": rounds * G, "fused_rounds": 0}
    whole = {**facts, "counters": counters, "shape": {"n_groups": G, "n_peers": 5},
             "peaks": run.load_json(os.path.dirname(HERE), "peaks.json")["TPU v5 lite"]}
    readers = {n: r for n, r in run.metric_readers(bench, cell).items()
               if getattr(r.reducer, "__name__", "").rsplit(".", 1)[-1] in ("scope_share", "unnamed_share")}
    today = run.program_names({})
    said = []
    metrics, left_out = run.read_metrics(readers, whole, today, cell, said.append)
    assert left_out == [] and set(metrics) == set(readers)
    new = {"damped.merge_agree", "damped.cut_before", "tally.real", "tally.pre",
           "runner.chaos_masks", "runner.client", "runner.stats"}
    assert new <= set(profiling.SCOPES)
    parent = {**today, "scopes": today["scopes"] - new}
    bare = cap._replace(ops=[
        o._replace(path="/".join(p for p in o.path.split("/") if p.rstrip(":") not in new))
        for o in cap.ops])
    metrics, left_out = run.read_metrics(
        readers, {**whole, **pt.facts_of(bare)}, parent, cell, said.append)
    assert sorted(left_out) == sorted(
        n for n, r in readers.items() if r.args.get("scope") in new)
    assert {"compiler_copy_share", "loop_container_share", "unnamed_fusion_share",
            "unscoped_share"} <= set(metrics)
