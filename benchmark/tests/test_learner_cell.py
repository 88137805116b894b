"""`fleet-100k-r3l2.outage` (ISSUE 47): 3 TiKV voters + 2 TiFlash learners a
Region, for good, under the accepted store-loss mix.

  * the rehearsal: the cell at G = 64 through the whole of a run prints
    `correct: true` with every compared number 0 — the learner plane back at
    home, `check reference` on groups that boot `ConfState(voters,
    learners)` — serves every read by lease, and reports the learners' lag;
  * the files are what the issue names: the configuration is
    `fleet-100k-r3of5.json` with `learners` [4, 5] and nothing else changed,
    the mix is the accepted `outage`, and the guarantees say what a learner
    is never counted in;
  * the seven per-layer metrics the cell adds read a number off a recording
    from the chip (`data/program_trace_store_loss_learners.json`: the head
    of one traced segment of this cell at 100 000 x 5, `program_trace.py
    export`, taken while the count's fold was four reductions behind its
    barrier: the names and counts are the committed program's, PERF.md §6),
    `learner_behind_share` among them through `span_counter` —
    which names the count it asks the program for, so that a program without
    it (the parent, a fleet without learners) leaves the metric out instead
    of stopping the run.
"""

import json
import os

import pytest

from test_control import SEEDS

CELL = "fleet-100k-r3l2.outage"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RECORDING = os.path.join(ROOT, "benchmark", "tests", "data",
                         "program_trace_store_loss_learners.json")


def load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts), encoding="utf-8") as f:
        return json.load(f)


@pytest.mark.parametrize("seed", SEEDS)
def test_the_rehearsal_is_correct_and_counts_the_learners_lag(bench, seed):
    import jax

    from benchmark import run

    lines = []
    text = run.run_cell(bench, CELL, seed=seed, seconds=0.3, traced=False,
                        say=lines.append, n_groups=64, devices=jax.devices())
    out = json.loads(text)
    checks = [l for l in lines if l.startswith("check ")]
    assert out["correct"] is True, checks
    assert len(checks) >= 6 and all(": 0 (limit 0) ok" in c for c in checks), checks
    window = next(json.loads(l)["window"] for l in lines if l.startswith('{"window"'))
    counters = window["counters"]
    assert counters["served_quorum"] == 0 and counters["served_lease"] > 0
    assert window["groups_not_back_in_the_configuration"] == 0
    # A store's loss fails what is due while a Region has no leader; two of
    # the five losses take no leader with them.
    assert 0 < out["failed"] < out["attempted"] // 4
    assert counters["reelections"] > 0
    setup = next(json.loads(l) for l in lines if l.startswith('{"setup"'))
    assert "learner_behind_group_rounds" in setup["warmup_report"]


def test_the_configuration_is_r3of5_with_two_learners(bench):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("fleet-100k-r3l2", "outage", 1)
    l2, twin = load("configs", "fleet-100k-r3l2.json"), load("configs", "fleet-100k-r3of5.json")
    prose = {"name", "source", "assumed", "guarantees", "deployment"}
    assert set(l2) == set(twin)
    assert {k for k in l2 if k not in prose and l2[k] != twin[k]} == {"learners"}
    assert (l2["voters"], l2["learners"], twin["learners"]) == ([1, 2, 3], [4, 5], [])
    assert (l2["check_quorum"], l2["pre_vote"], l2["lease_read"]) == (True, True, True)
    assert (l2["n_groups"], l2["n_peers"], l2["reduced"]) == (100000, 5, [])
    assert len(l2["source"]) <= 200 and "\n" not in l2["source"] and "  " not in l2["source"]
    assert "quoted_from_memory" in l2["assumed"]
    told = " ".join(l2["guarantees"])
    for never in ("never votes", "never campaigns", "never leads", "no commit quorum",
                  "vote tally", "check-quorum round", "lease"):
        assert never in told, never
    entry = next(c for c in bench["configs"] if c["name"] == "fleet-100k-r3l2")
    assert entry["source"] == l2["source"] and entry["reduced"] == []
    assert entry["file"] == "benchmark/configs/fleet-100k-r3l2.json"


NEW_METRICS = ("learner_round_ms", "learner_behind_share", "learner_leader_change_share",
               "learner_leaderless_share", "learner_term_bumps_per_group",
               "learner_damped_round_share", "learner_recover_p99_rounds")


def test_the_seven_metrics_read_a_number_off_the_chips_recording(bench):
    from benchmark import program_trace as pt
    from benchmark import reducers

    listed = {m["name"]: m for m in bench["per_layer"] if m.get("workloads") == [CELL]}
    assert set(listed) == set(NEW_METRICS)
    cap = pt.load_recorded(RECORDING)
    reports = pt.spans_named(cap, "raft.run_reads.report")
    assert reports and all(r.stats["groups"] == 100000 and r.stats["rounds"] == 600
                           for r in reports)
    facts = {
        **pt.facts_of(cap),
        # What run.py hands a reader beside the capture: the traced window's counts.
        "counters": {"group_rounds": sum(r.stats["rounds"] * r.stats["groups"] for r in reports)},
        "shape": {"n_groups": 100000},
    }
    got = {}
    for name in NEW_METRICS:
        spec = load("metrics", name + ".json")
        assert (spec["unit"], spec["moves"]) == (listed[name]["unit"], "group_rounds_per_s")
        got[name] = reducers.load(spec["reducer"]).read(facts, spec["args"])
        assert isinstance(got[name], float), name
        if spec["unit"] == "%":
            assert 0.0 <= got[name] <= 100.0, (name, got[name])
    # The deployment shows in the counts: the lag is there and small (a
    # Region lags only while it commits, and YCSB-B writes to few), fewer
    # than three terms a group a segment, a recovery tail at the length of
    # an outage; and the damped round ran.
    assert 0 < got["learner_behind_share"] < 20
    assert 0 < got["learner_term_bumps_per_group"] < 5
    assert 0 < got["learner_leaderless_share"] < 20
    assert got["learner_recover_p99_rounds"] >= 40
    assert got["learner_round_ms"] > 0 and got["learner_damped_round_share"] > 0
    assert all(r.stats["served_quorum"] == 0 and r.stats["served_lease"] > 0 for r in reports)
    assert all(r.stats["learner_behind_group_rounds"] > 0 for r in reports)


def test_the_parents_program_leaves_the_lag_out_and_the_line_stands(bench):
    """On a program whose report has no `learner_behind_group_rounds` — the
    parent of PR 47, or any fleet without learners — the reader returns
    nothing, the harness knows why (`reducers.lacking`) and leaves the metric
    out; through `report_ratio` it would have stopped the run."""
    from benchmark import program_trace as pt
    from benchmark import reducers

    cap = pt.load_recorded(RECORDING)
    older = pt.Capture(
        [s._replace(stats={k: v for k, v in s.stats.items()
                           if k != "learner_behind_group_rounds"}) for s in cap.spans],
        cap.ops, cap.modules)
    spec = load("metrics", "learner_behind_share.json")
    reducer = reducers.load(spec["reducer"])
    assert reducer.read({**pt.facts_of(cap), "capture": older}, spec["args"]) is None
    program = {"spans": {"raft.run_reads.report"}, "counts": {"rounds", "groups"}}
    assert reducers.lacking(reducer, spec["args"], program) == [
        "counts 'learner_behind_group_rounds'"]
    assert not hasattr(reducers.load("report_ratio"), "names")
