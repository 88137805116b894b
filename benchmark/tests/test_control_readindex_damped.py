"""`correct` holds the damped ReadIndex deployment to its guarantee: the
program that answers a ReadIndex read without its acknowledging majority
(control_readindex_damped.py) drives `fleet-100k-r5-readindex.outage` at
G = 64 through the whole of a run and the device's linearizability audit
(`stale_read` / `dual_lease`) must trip, on every seed; the sound program on
the same seeds is correct with every compared number 0.  On the chip at the
cell's own size this is `control_readindex_damped.py` itself."""

import json

import pytest

import control_readindex_damped
from test_control import SEEDS, drive

CELL = "fleet-100k-r5-readindex.outage"


@pytest.mark.parametrize("seed", SEEDS)
def test_control_readindex_without_its_majority_is_not_correct(bench, seed):
    with control_readindex_damped.readindex_without_ack_quorum():
        out, checks = drive(bench, CELL, seed)
    assert out["correct"] is False
    assert "FAILED" in checks["safety"]
    assert "stale_read" in checks["safety"] or "dual_lease" in checks["safety"]
    assert [name for name, c in checks.items() if "FAILED" in c] == ["safety"]
    assert out["failed"] >= 1


@pytest.mark.parametrize("seed", SEEDS)
def test_sound_readindex_fleet_is_correct_on_the_same_seeds(bench, seed):
    out, checks = drive(bench, CELL, seed)
    assert out["correct"] is True, checks
    assert all("FAILED" not in c for c in checks.values())


def test_the_cell_is_the_deployment_the_issue_names(bench):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["traffic"] == "outage" and cell["chips"] == 1
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(entry["file"], encoding="utf-8") as f:
        cfg = json.load(f)
    assert (cfg["check_quorum"], cfg["pre_vote"], cfg["lease_read"]) == (True, True, False)
    assert (cfg["n_groups"], cfg["n_peers"], cfg["reduced"]) == (100000, 5, [])
    assert any("ReadIndex" in g for g in cfg["guarantees"])
    assert any(g.startswith("check-quorum") for g in cfg["guarantees"])
