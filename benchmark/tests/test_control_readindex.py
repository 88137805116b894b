"""`correct` holds the stock deployment to its ReadIndex guarantee: the
program that answers a ReadIndex read without its acknowledging majority
(control_readindex.py) drives `fleet-100k-r5-stock.outage` at G = 64 through
the whole of a run and the device's linearizability audit (`stale_read`)
must trip, on every seed; the sound program on the same seeds is correct.
On the chip at the cell's own size this is `control_readindex.py` itself."""

import pytest

import control_readindex
from test_control import SEEDS, drive

CELL = "fleet-100k-r5-stock.outage"


@pytest.mark.parametrize("seed", SEEDS)
def test_control_readindex_without_its_majority_is_not_correct(bench, seed):
    with control_readindex.readindex_without_ack_quorum():
        out, checks = drive(bench, CELL, seed)
    assert out["correct"] is False
    assert "FAILED" in checks["safety"] and "stale_read" in checks["safety"]
    assert out["failed"] >= 1


@pytest.mark.parametrize("seed", SEEDS)
def test_sound_stock_fleet_is_correct_on_the_same_seeds(bench, seed):
    out, checks = drive(bench, CELL, seed)
    assert out["correct"] is True, checks
    assert all("FAILED" not in c for c in checks.values())
