"""A deployment whose membership changes, through the whole of a run on the
CPU at G = 64 (tests/data: 5 slots, voters on 1-3; a replica move and its
way back for every second region, and the control's two-replica move under a
store outage): the fleet boots in the configuration's membership, the
reference replays the conf changes on its scalar machines and equals the
program cell for cell, membership columns included, and every op lands."""

import json

import numpy as np
import pytest

from benchmark import check, run
from benchmark.reference import membership
from conftest import CHURN_CONFIG


def drive(bench, cell, seed, seconds=0.3):
    import jax

    lines = []
    text = run.run_cell(bench, cell, seed=seed, seconds=seconds, traced=False,
                        say=lines.append, devices=jax.devices())
    checks = {l.split()[1].rstrip(":"): l for l in lines if l.startswith("check ")}
    window = next(json.loads(l)["window"] for l in lines if l.startswith('{"window"'))
    return json.loads(text), checks, window


@pytest.mark.parametrize("mix,seed", [("churn", 2**31 + 3), ("churn-crash", 11)])
def test_reference_equals_program_over_boot_and_a_churn_segment(churn_bench, mix, seed):
    """`churn`: no faults; `churn-crash`: composed with a chaos schedule."""
    out, checks, window = drive(churn_bench, f"{CHURN_CONFIG}.{mix}", seed)
    assert out["correct"] is True, checks
    assert all(" ok " in c and "(limit 0)" in c for c in checks.values())
    assert "check reference: 0 " in checks["reference"] and "differing []" in checks["reference"]
    applied = int(checks["reference"].split("applied ")[1].split()[0])
    assert applied >= 6 * 3  # at least half of the sample walked its whole chain
    assert window["conf_ops_offered"] > 0 and window["groups_not_back_in_the_configuration"] == 0
    per_segment = window["conf_ops_offered"] // window["segments"]
    assert per_segment == {"churn": 6 * 32, "churn-crash": 8 * 64}[mix]
    assert out["attempted"] >= window["conf_ops_offered"]


def test_the_fleet_boots_in_the_configurations_membership(churn_bench):
    cell, config, mix = run.find_cell(churn_bench, f"{CHURN_CONFIG}.churn")
    fleet = run.Fleet(config, 64)
    st = fleet.sim.state
    want = np.zeros((5, 64), bool)
    want[:3] = True
    assert np.array_equal(np.asarray(st.voter_mask), want)
    assert not np.asarray(st.outgoing_mask).any() and not np.asarray(st.learner_mask).any()
    assert [m.shape for m in fleet.home] == [(5, 64)] * 3 and np.array_equal(fleet.home[0], want)


def test_a_configuration_without_the_keys_is_all_voters(bench):
    cell, config, mix = run.find_cell(bench, "fleet-100k-r5.serve")
    assert membership.of_config(config) == ([1, 2, 3, 4, 5], [])
    with pytest.raises(ValueError, match="disjoint"):
        membership.of_config({"n_peers": 5, "voters": [1, 2], "learners": [2]})
    with pytest.raises(ValueError):
        membership.of_config({"n_peers": 3, "voters": [1, 4]})


def planes(commit, holders):
    """One group of 5 peers: peer 0 holds the highest commit index; `holders`
    are the peers whose log agrees with peer 0's up to it."""
    P = 5
    c = np.zeros((P, 1), np.int64)
    c[0, 0] = commit
    agree = np.zeros((P, P, 1), np.int64)
    for b in holders:
        agree[0, b, 0] = agree[b, 0, 0] = commit
    return c, agree


def mask(*peers):
    m = np.zeros((5, 1), bool)
    m[list(peers)] = True
    return m


@pytest.mark.parametrize("holders,voter,outgoing,short", [
    ((1, 2), mask(0, 1, 2), mask(), 0),            # today's number: no outgoing voter
    ((), mask(0, 1, 2), mask(), 1),
    ((3, 4), mask(0, 3, 4), mask(0, 1, 2), 1),     # the incoming hold it, the outgoing do not
    ((1,), mask(0, 3, 4), mask(0, 1, 2), 1),       # the other way round
    ((1, 3), mask(0, 3, 4), mask(0, 1, 2), 0),     # both majorities
])
def test_durability_needs_both_majorities_of_a_joint_configuration(holders, voter, outgoing, short):
    commit, agree = planes(9, holders)
    found = check.durability(commit, agree, voter, outgoing)
    assert found.value == short and found.limit == 0
    assert f"{int(outgoing.any())} joint" in found.detail
