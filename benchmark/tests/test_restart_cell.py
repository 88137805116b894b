"""`fleet-100k-r3.load-restart` (ISSUE 51): TiKV's default three voters a
Region under the YCSB load phase while each store in turn restarts, through
`ClusterSim.run_reads(split=True)` under the chaos plan.

  * the rehearsal: the cell at G = 64 through the whole of a run prints
    `correct: true` with every compared number 0 — `check reference` replays
    the crashes — fuses blocks, and reports the blocks by kind and the
    guard's refusals by term;
  * the files are what the issue names: the configuration is
    `fleet-1m-r3.json` with `n_groups` 100 000 and nothing else the harness
    reads changed, the mix is `load.json`'s client under a rolling restart
    whose phases are multiples of the block;
  * the seven per-layer metrics the cell adds read a number off a recording
    from the chip (`data/program_trace_stores_restart.json`: every host span
    of one traced run at 100 000 x 3 and the device events of three blocks
    of its first segment — the first that fused, whole; the head of the
    first that did not; the head of the first block of store 1's down
    stretch, the phase change; cut by `cut_blocks.py` beside this file);
  * `restart_refused_block_share` goes through `span_counter`, which names
    the counts it asks the program for: a program without them (the parent,
    a bare plan) leaves the metric out instead of stopping the run.
"""

import json
import os

import pytest

from test_control import SEEDS

CELL = "fleet-100k-r3.load-restart"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RECORDING = os.path.join(ROOT, "benchmark", "tests", "data", "program_trace_stores_restart.json")
TERMS = ("no_campaign", "one_leader", "terms_ok", "cq_boundary", "read_pending")


def load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts), encoding="utf-8") as f:
        return json.load(f)


@pytest.mark.parametrize("seed", SEEDS)
def test_the_rehearsal_is_correct_fuses_and_counts_the_refusals(bench, seed):
    import jax

    from benchmark import run

    lines = []
    text = run.run_cell(bench, CELL, seed=seed, seconds=0.3, traced=False,
                        say=lines.append, n_groups=64, devices=jax.devices())
    out = json.loads(text)
    checks = [l for l in lines if l.startswith("check ")]
    assert out["correct"] is True, checks
    assert len(checks) >= 6 and all(": 0 (limit 0) ok" in c for c in checks), checks
    assert out["failed"] == 0 and out["attempted"] > 0  # no reads: nothing to fail
    assert set(out["metrics"]) == {"group_rounds_per_s", "setup_s"}
    window = next(json.loads(l)["window"] for l in lines if l.startswith('{"window"'))
    counters = window["counters"]
    assert counters["rounds"] % 960 == 0  # three stores x (240 up + 80 down)
    assert 0 < counters["fused_rounds"] < counters["total_rounds"]
    assert counters["reelections"] > 0  # a third of the Regions lose their leader a restart
    report = next(json.loads(l) for l in lines if l.startswith('{"setup"'))["warmup_report"]
    assert report["split_blocks"] == 120 and report["split_blocks_faulted"] == 30
    assert report["split_blocks_healthy"] == 90
    assert 0 < report["split_blocks_healthy_refused"] < 90
    assert set(report["guard_refusals"]) == set(TERMS)
    assert report["guard_refusals"]["read_pending"] == 0  # the mix has no read
    assert report["guard_refusals"]["terms_ok"] > 0  # a returning peer is a term behind
    assert report["appends_dropped"] > 0  # offered to a Region with no leader: counted


def test_the_configuration_is_1m_r3_at_100k_groups(bench):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("fleet-100k-r3", "load-restart", 1)
    r3, twin = load("configs", "fleet-100k-r3.json"), load("configs", "fleet-1m-r3.json")
    prose = {"name", "source", "assumed", "deployment"}
    assert set(r3) == set(twin)
    assert {k for k in r3 if k not in prose and r3[k] != twin[k]} == {"n_groups"}
    assert r3["guarantees"] == twin["guarantees"]  # word for word
    assert (r3["n_groups"], r3["n_peers"], r3["reduced"]) == (100000, 3, [])
    assert "voters" not in r3 and "learners" not in r3  # every slot a voter
    assert len(r3["source"]) <= 200 and "\n" not in r3["source"] and "  " not in r3["source"]
    assert r3["source"] != twin["source"]
    for key in ("quoted_from_memory", "ticks", "scale", "store_restart"):
        assert key in r3["assumed"], key
    assert "raft-election-timeout-ticks 10" in r3["assumed"]["ticks"]
    entry = next(c for c in bench["configs"] if c["name"] == "fleet-100k-r3")
    assert entry["source"] == r3["source"] and entry["reduced"] == []
    assert entry["file"] == "benchmark/configs/fleet-100k-r3.json"
    e2e = {m["name"] for m in bench["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]}
    assert e2e == {"group_rounds_per_s", "setup_s"}


def test_the_mix_is_the_load_phase_under_a_rolling_restart():
    from benchmark import traffic

    mix, twin = load("traffic", "load-restart.json"), load("traffic", "load.json")
    client = ("phase_rounds", "ops_per_round_per_group", "read_share", "read_mode",
              "distribution", "split")
    assert all(mix[k] == twin[k] for k in client) and mix["split"] is True
    assert "segment_rounds" not in mix and mix["counted_segments"] == 2
    assert mix["chaos"] == {"for_each_peer": [{"rounds": 240},
                                              {"rounds": 80, "crash": ["@peer"]}]}
    seg = traffic.generate(mix, 64, 3, SEEDS[1], name=CELL)
    assert seg.n_rounds == 960 and seg.split and seg.split_k == 8 and seg.read_fires == 0
    phases = seg.chaos["phases"]
    assert [ph.get("crash") for ph in phases] == [None, [1], None, [2], None, [3]]
    assert all(ph["rounds"] % seg.split_k == 0 for ph in phases)  # no block straddles a phase
    assert (seg.append == 1).all() and seg.write_batches == 960 * 64
    for key in ("restart", "no_leader_eviction", "appends_during_restart", "counted_segments"):
        assert key in mix["assumed"], key


def recording():
    from benchmark import program_trace as pt

    return pt.load_recorded(RECORDING)


NEW_METRICS = ("restart_fused_frac", "restart_round_ms", "restart_kernel_share",
               "restart_kernel_roofline", "restart_block_guard_share", "restart_idle_share",
               "restart_refused_block_share")


def test_the_seven_metrics_read_a_number_off_the_chips_recording(bench):
    from benchmark import program_trace as pt
    from benchmark import reducers

    listed = {m["name"]: m for m in bench["per_layer"] if m.get("workloads") == [CELL]}
    assert set(listed) == set(NEW_METRICS)
    cap = recording()
    reports = pt.spans_named(cap, "raft.run_reads.report")
    assert reports and all(r.stats["groups"] == 100000 and r.stats["rounds"] == 960
                           for r in reports)
    # The recording holds the three kinds of block it was cut for.
    blocks = [m for m in cap.modules if "block_run" in m.name]
    assert len(blocks) == 3 and min(m.dur_ns for m in blocks) < 5e6 < max(m.dur_ns for m in blocks)
    assert any(pt.has_kernel(o.path, "raft_steady_damped") for o in cap.ops)
    assert any(pt.has_scope(o.path, "runner.general_arm") for o in cap.ops)
    for scope in ("runner.block_planes", "runner.block_guard"):
        assert sum(pt.has_scope(o.path, scope) for o in cap.ops) >= 3, scope  # one a block
    # ... and the refusals are counted in the general arm alone.
    counted = [o for o in cap.ops if pt.has_scope(o.path, "runner.guard_refusals")]
    assert counted and all(pt.has_scope(o.path, "runner.general_arm") for o in counted)
    blocks_span = pt.spans_named(cap, "raft.runner.blocks")
    assert all(s.stats == {"blocks": 120, "tail": 0, "chaos": 1, "blocks_faulted": 30}
               for s in blocks_span) and blocks_span
    calls = pt.spans_named(cap, "raft.run_reads")
    assert all(s.stats["split"] == 1 and s.stats["chaos"] == 1 for s in calls)
    peaks = load("peaks.json")["TPU v5 lite"]
    facts = {
        **pt.facts_of(cap),
        # What run.py hands a reader beside the capture: the traced window's counts.
        "counters": {
            "fused_rounds": sum(r.stats["fused_rounds"] for r in reports),
            "total_rounds": sum(r.stats["total_rounds"] for r in reports),
            "group_rounds": sum(r.stats["rounds"] * r.stats["groups"] for r in reports),
        },
        "shape": {"n_groups": 100000, "n_peers": 3},
        "peaks": peaks,
    }
    got = {}
    for name in NEW_METRICS:
        spec = load("metrics", name + ".json")
        assert (spec["unit"], spec["moves"]) == (listed[name]["unit"], "group_rounds_per_s")
        got[name] = reducers.load(spec["reducer"]).read(facts, spec["args"])
        assert isinstance(got[name], float), name
        if spec["unit"] == "%":
            assert 0.0 <= got[name] <= 100.0, (name, got[name])
    # The exact counts are the whole traced run's: the stretches between
    # restarts fuse, a few blocks after each return do not.
    assert 0.4 < got["restart_fused_frac"] <= 0.75
    assert 0 < got["restart_refused_block_share"] < 50
    for r in reports:
        healthy = r.stats["split_blocks"] - r.stats["split_blocks_faulted"]
        assert healthy == r.stats["split_blocks_healthy"] == 90
        # ISSUE 51's spelling of the number, where no faulted block fuses.
        assert r.stats["split_blocks_healthy_refused"] == healthy - r.stats["fused_rounds"] // 800000
        assert all(f"guard_refusals.{t}" in r.stats for t in TERMS)
        assert "guard_refusals_faulted.no_campaign" not in r.stats
    spec = load("metrics", "restart_kernel_roofline.json")
    assert spec["args"]["operands"] == {"pg": 15, "ppg": 2, "g": 5}
    assert reducers.load("kernel_roofline").bytes_per_call(
        spec["args"]["operands"], 100000, 3) == 27_200_000


def test_a_report_without_the_counts_leaves_the_refused_share_out(bench):
    """On a program whose report has no `split_blocks_healthy*` — the parent
    of PR 51, or any split call of a bare plan — the reader returns nothing,
    the harness knows why (`reducers.lacking`) and leaves the metric out."""
    from benchmark import program_trace as pt
    from benchmark import reducers

    cap = recording()
    gone = ("split_blocks_healthy", "split_blocks_healthy_refused")
    older = pt.Capture(
        [s._replace(stats={k: v for k, v in s.stats.items() if k not in gone})
         for s in cap.spans],
        cap.ops, cap.modules)
    spec = load("metrics", "restart_refused_block_share.json")
    assert spec["reducer"] == "span_counter"
    reducer = reducers.load(spec["reducer"])
    assert isinstance(reducer.read(pt.facts_of(cap), spec["args"]), float)
    assert reducer.read({**pt.facts_of(cap), "capture": older}, spec["args"]) is None
    program = {"spans": {"raft.run_reads.report"}, "counts": {"rounds", "groups", "fused_rounds"}}
    assert reducers.lacking(reducer, spec["args"], program) == [
        "counts 'split_blocks_healthy_refused'", "counts 'split_blocks_healthy'"]
