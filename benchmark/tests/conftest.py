"""The benchmark's own tests: `python3 -m pytest benchmark/tests -q` on the
pinned CPU.  They are not under `tests/`, so tier-1 neither runs nor counts
them."""

import copy
import json
import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CHURN_CONFIG = "fleet-64-r3of5"  # tests/data: 5 slots, voters 1-3, G = 64
CHURN_MIXES = ("churn", "churn-crash")  # tests/data: a replica move and back; the control's


@pytest.fixture(scope="session")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def with_churn_cells(bench: dict, config_file: str) -> dict:
    """`bench` plus the test-only deployment whose membership changes and a
    cell for each of its mixes — what a later PR would add as entries."""
    out = copy.deepcopy(bench)
    out["configs"].append({"name": CHURN_CONFIG, "source": "test only", "file": config_file,
                           "reduced": ["n_groups"], "why": "test only"})
    cells = [f"{CHURN_CONFIG}.{mix}" for mix in CHURN_MIXES]
    for cell, mix in zip(cells, CHURN_MIXES):
        out["workloads"].append({"name": cell, "config": CHURN_CONFIG, "traffic": mix,
                                 "chips": 1, "why": "test only"})
    serve = "fleet-100k-r5.serve"
    for m in out["end_to_end"] + out["per_layer"]:
        if serve in m.get("workloads", []):  # they report what .serve reports
            m["workloads"] += cells
    return out


@pytest.fixture
def churn_bench(bench, monkeypatch):
    from benchmark import traffic

    monkeypatch.setattr(traffic, "MIX_DIR", DATA)
    return with_churn_cells(bench, f"benchmark/tests/data/{CHURN_CONFIG}.json")
