"""`fleet-100k-r5-cq.netsplit` (ISSUE 44): check-quorum WITHOUT pre-vote
under a rolling network split.

  * the rehearsal: the cell at G = 64 through the whole of a run prints
    `correct: true` with every compared number 0, and serves every read
    through the ReadIndex round (`served_lease` 0);
  * the control under THIS cell's mix — five cut-off stretches where
    `outage` has one — and on this fleet: the program that answers a
    ReadIndex read without its acknowledging majority
    (control_readindex_damped.py) is not correct, by `safety` alone.  On
    the chip at the cell's own size:
    `python3 benchmark/tests/control_readindex_damped.py fleet-100k-r5-cq.netsplit <seed>`;
  * the six per-layer metrics the cell adds read a number off a recording
    from the chip (`data/program_trace_store_split_cq.json`: the head of one
    traced segment of this cell at 100 000 x 5, `program_trace.py export`);
  * the files are what the issue names: the configuration differs from
    `fleet-100k-r5-readindex` in ONE setting, and gives one guarantee fewer
    and says so; the mix is `outage`'s client with no crash anywhere.
"""

import json
import os

import pytest

import control_readindex_damped
from test_control import SEEDS, drive

CELL = "fleet-100k-r5-cq.netsplit"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts), encoding="utf-8") as f:
        return json.load(f)


@pytest.mark.parametrize("seed", SEEDS)
def test_the_rehearsal_is_correct_and_every_read_is_a_readindex_round(bench, seed):
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
    assert counters["served_lease"] == 0
    assert counters["served_quorum"] > 0
    # The configuration's own behaviour, not a fault: a returning member
    # deposes a healthy leader, and operations due meanwhile fail.
    assert 0 < out["failed"] < out["attempted"]
    assert counters["reelections"] > 0


@pytest.mark.parametrize("seed", SEEDS)
def test_control_readindex_without_its_majority_is_not_correct_here_too(bench, seed):
    with control_readindex_damped.readindex_without_ack_quorum():
        out, checks = drive(bench, CELL, seed)
    assert out["correct"] is False
    assert "stale_read" in checks["safety"] or "dual_lease" in checks["safety"]
    assert [name for name, c in checks.items() if "FAILED" in c] == ["safety"]


def test_the_configuration_differs_from_its_twin_in_one_setting(bench):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("fleet-100k-r5-cq", "netsplit", 1)
    cq, twin = load("configs", "fleet-100k-r5-cq.json"), load("configs", "fleet-100k-r5-readindex.json")
    prose = {"name", "source", "assumed", "guarantees", "deployment"}
    assert set(cq) == set(twin)
    assert {k for k in cq if k not in prose and cq[k] != twin[k]} == {"pre_vote"}
    assert (cq["check_quorum"], cq["pre_vote"], cq["lease_read"]) == (True, False, False)
    assert (cq["n_groups"], cq["n_peers"], cq["reduced"]) == (100000, 5, [])
    assert len(cq["source"]) <= 200
    # One guarantee fewer, said so; the others word for word.
    kept = [g for g in twin["guarantees"] if not g.startswith("pre-vote:")]
    assert cq["guarantees"][:-1] == kept and len(kept) == len(twin["guarantees"]) - 1
    assert cq["guarantees"][-1].startswith("NOT promised")
    entry = next(c for c in bench["configs"] if c["name"] == "fleet-100k-r5-cq")
    assert entry["source"] == cq["source"] and entry["reduced"] == []


def test_the_mix_is_outages_client_with_no_crash(bench):
    mix, outage = load("traffic", "netsplit.json"), load("traffic", "outage.json")
    client = ("phase_rounds", "ops_per_round_per_group", "read_share", "read_mode",
              "distribution", "split", "counted_segments")
    assert {k: mix[k] for k in client} == {k: outage[k] for k in client}
    assert mix["chaos"] == {"for_each_peer": [
        {"rounds": 40}, {"rounds": 60, "partition": [["@peer"]]}]}
    assert "crash" not in json.dumps(mix["chaos"])
    assert {"counted_segments", "netsplit", "no_crash", "failed_share"} <= set(mix["assumed"])


NEW_METRICS = ("netsplit_round_ms", "netsplit_term_bumps_per_group",
               "netsplit_leader_change_share", "netsplit_leaderless_share",
               "netsplit_tally_real_share", "netsplit_damped_round_share")


def test_the_six_metrics_read_a_number_off_the_chips_recording(bench):
    from benchmark import program_trace as pt
    from benchmark import reducers

    listed = {m["name"]: m for m in bench["per_layer"] if m.get("workloads") == [CELL]}
    assert set(listed) == set(NEW_METRICS)
    cap = pt.load_recorded(os.path.join(ROOT, "benchmark", "tests", "data",
                                        "program_trace_store_split_cq.json"))
    reports = pt.spans_named(cap, "raft.run_reads.report")
    assert reports and all(r.stats["groups"] == 100000 and r.stats["rounds"] == 500
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
    # The mechanism shows in the counts: several terms a group a segment,
    # a third of the group-rounds without a leader; and the real tally ran.
    assert got["netsplit_term_bumps_per_group"] > 5
    assert got["netsplit_leaderless_share"] > 10
    assert got["netsplit_tally_real_share"] > 0 and got["netsplit_damped_round_share"] > 0
    assert all(r.stats["served_lease"] == 0 and r.stats["served_quorum"] > 0 for r in reports)
