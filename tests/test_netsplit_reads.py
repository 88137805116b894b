"""The Safe-read half of tests/test_netsplit_parity.py (ISSUE 44):
`fleet-100k-r5-cq` under `netsplit` at G = 64 WITH the mix's client —
every round a seeded eighth of the groups asks for a read (`read_mode`
"lease", which `lease_read: false` degrades to the ReadIndex round), a
sixteenth appends — two segments with state carried over.

Every round: the five cursor planes of the whole fleet equal
`simref.ScalarCluster`'s, every group's receipt (index, lease, degraded)
equals `simref.ReadOracle`'s real Safe pump, no receipt is a lease serve,
and the audit's per-peer mask `ReadReceipt.holders` equals
`ReadOracle.read_holders` on the groups that asked — and on EVERY group in
the heal round and the two after it, where a returning member's higher
term decides whether the leader still answers (the nudge before or after
the ack quorum, `sim._acks_before_nudge`).

And the cell itself, `fleet-100k-r5-cq.netsplit`, from BENCHMARK.json as it
is, rehearsed at G = 64 through `benchmark.run.run_cell` (tier-1's guard of
the files; the benchmark's own suite has the same in
`benchmark/tests/test_netsplit_cell.py`): `correct` true with every read a
ReadIndex round, and not correct once a ReadIndex read is answered without
its acknowledging majority (`benchmark/tests/control_readindex_damped.py`),
by `safety` alone — this mix has five cut-off stretches where `outage` has
one.

A file of its own: the scalar pumps run on throwaway deep copies, and two
such replays on one xdist worker would be the longest file of tier-1.
"""

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import line, run
from raft_tpu.multiraft import ScalarCluster, SimConfig, chaos, kernels, sim
from raft_tpu.multiraft.simref import ReadOracle
from test_netsplit_parity import (
    CUT, ELECTION_TICK, G, HEARTBEAT_TICK, INFLIGHT, P, SETTLE, UP,
    cut_off_store, netsplit_plan,
)
from test_read_lease import assert_receipts, assert_state_parity

READ_SHARE, APPEND_SHARE = 1 / 8, 1 / 16
AFTER_HEAL = 3  # rounds from a heal on in which every group's mask is compared


def test_netsplit_safe_reads_two_segments():
    cfg = SimConfig(
        n_groups=G, n_peers=P, election_tick=ELECTION_TICK,
        heartbeat_tick=HEARTBEAT_TICK, check_quorum=True, pre_vote=False,
        lease_read=False)
    scalar = ScalarCluster(
        G, P, election_tick=ELECTION_TICK, heartbeat_tick=HEARTBEAT_TICK,
        check_quorum=True, pre_vote=False, max_inflight_msgs=INFLIGHT)
    oracle = ReadOracle(scalar, election_tick=ELECTION_TICK, lease_read=False)
    step = jax.jit(functools.partial(sim.step, cfg))
    st = sim.init_state(cfg)
    plan = netsplit_plan(segments=2)
    sched = chaos.HostSchedule(plan, G)
    rng = np.random.RandomState(44)
    seen = {"asked": 0, "served": 0, "refused_with_leader": 0, "held": 0,
            "two_leaders": 0, "compared": 0}
    since_heal = AFTER_HEAL
    for r in range(plan.n_rounds):
        link, crashed, _ = sched.masks(r)  # link [P, P, G], crashed [P, G]
        if r >= SETTLE:
            since_heal = 0 if (r - SETTLE) % (UP + CUT) == 0 else since_heal + 1
        asks = (rng.rand(G) < READ_SHARE) & (r >= SETTLE)
        modes = np.where(asks, sim.READ_LEASE, sim.READ_NONE).astype(np.int32)
        app = (rng.rand(G) < APPEND_SHARE).astype(np.int64)
        compare = np.ones(G, bool) if since_heal < AFTER_HEAL else asks
        want = {
            g: oracle.read_holders(g, crashed[:, g], link[:, :, g])
            for g in np.flatnonzero(compare)
        }
        role0 = np.asarray(st.state)
        st, receipt = step(
            st, jnp.asarray(crashed), jnp.asarray(app, jnp.int32),
            link=jnp.asarray(link), read_propose=jnp.asarray(modes))
        oracle.round(crashed.T, app, link=link, read_propose=modes)
        tag = f"round {r} (cut-off store {cut_off_store(r)})"
        assert_state_parity(oracle, st, tag)
        assert_receipts(receipt, oracle.last_receipts, tag)
        assert not np.asarray(receipt.lease).any(), tag
        got = np.asarray(receipt.holders)
        assert (got.sum(axis=0) <= 1).all(), f"{tag}: two peers would answer one group"
        for g, holders in want.items():
            assert got[:, g].tolist() == holders, (
                f"{tag} group {g}: the audit's mask {got[:, g].astype(int)} "
                f"differs from the scalar pump's {np.array(holders).astype(int)}")
        index = np.asarray(receipt.index)
        leaders = (role0 == kernels.ROLE_LEADER).sum(axis=0)
        seen["asked"] += int(asks.sum())
        seen["served"] += int((asks & (index >= 0)).sum())
        seen["refused_with_leader"] += int((asks & (index < 0) & (leaders > 0)).sum())
        seen["two_leaders"] += int((compare & (leaders >= 2)).sum())
        seen["held"] += int(got[:, compare].sum())
        seen["compared"] += int(compare.sum())
    # What the plan showed: reads served and refused, leaders refused while
    # they still held the role, stale leaders beside their successors.
    assert seen["served"] > seen["asked"] // 2
    assert seen["refused_with_leader"] > 0 and seen["two_leaders"] > 0
    assert 0 < seen["held"] < seen["compared"]


# --- the cell, from BENCHMARK.json as it is -----------------------------------

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "fleet-100k-r5-cq.netsplit"


@pytest.fixture(scope="module")
def bench():
    return run.load_json(ROOT, "BENCHMARK.json")


def rehearse(bench, seed):
    lines = []
    text = run.run_cell(bench, CELL, seed=seed, seconds=0.3, traced=False,
                        say=lines.append, n_groups=G, devices=jax.devices())
    return json.loads(text), lines + [text]


def test_the_cell_rehearses(bench):
    got, lines = rehearse(bench, 2**31 + 44)
    problems = [
        p for p in line.validate("\n".join(lines) + "\n", bench, CELL, False)
        if not p.startswith("device.memory_peak_bytes")  # a CPU reports none
    ]
    assert not problems, problems
    checks = [t for t in lines if t.startswith("check ")]
    assert got["correct"] is True and all(": 0 (limit 0) ok" in c for c in checks), checks
    assert got["attempted"] > got["failed"] > 0  # the configuration's own (its guarantees)
    counters = next(json.loads(t)["window"] for t in lines if t.startswith('{"window"'))["counters"]
    assert counters["served_lease"] == 0 and counters["served_quorum"] > 0
    assert counters.get("fused_rounds", 0) == 0 and counters["reelections"] > 0


@pytest.mark.parametrize("seed", [44, 2**31 + 45])
def test_readindex_without_its_majority_is_not_correct_under_this_mix(bench, seed):
    sys.path.insert(0, os.path.join(ROOT, "benchmark", "tests"))
    try:
        import control_readindex_damped
    finally:
        sys.path.pop(0)
    with control_readindex_damped.readindex_without_ack_quorum():
        weak, lines = rehearse(bench, seed)
    failed = [t.split()[1].rstrip(":") for t in lines if t.startswith("check ") and "FAILED" in t]
    assert weak["correct"] is False and failed == ["safety"]
