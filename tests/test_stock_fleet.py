"""raft-rs's own default Config as a fleet (ISSUE 35): check-quorum off,
pre-vote off, every read a ReadIndex round (`ReadOnlyOption::Safe`) at
`election_tick 20 / heartbeat_tick 2` — the undamped bodies
(`sim._linked_step` under faults, `sim.step`'s plain body without) and the
undamped fused kernel, on the served path (`ClusterSim.run_reads`).

  (a) the benchmark's store-loss mix at P = 5 (600 rounds: five stores down
      in turn, then store 1 cut off but alive) under a seeded client of
      safe and lease fires and appends, against the scalar host replay:
      cursors per peer, the read in flight, the read counts, and the
      report's two leadership counts (`leader_changes`, `term_bumps`);
  (b) the same fleet through `split=True` without faults, bit-equal to the
      scan: a write-only client fuses, a safe fire blocks fusion;
  (c) each of the two cells the deployment's PR brought, rehearsed at
      G = 64 through `benchmark.run.run_cell` from BENCHMARK.json as it is:
      `fleet-1m-r3.outage` and `fleet-100k-r5-stock.outage`;
  (d) the guarantee the deployment adds — a ReadIndex read is served only
      after a majority acknowledged the serving leader — is one `correct`
      can see: the program that answers without the majority
      (`benchmark/tests/control_readindex.py`) trips the device's
      linearizability audit, which on this fleet looks at every peer whose
      ReadIndex gate passes (`sim.read_index_holders`), not only at the
      acting leader a client is routed to.

(a) is over 100 rounds and stays in tier-1 all the same: the mix's segment
is the subject (ROADMAP's standing rule asks a test to say so).
"""

import json
import os
import sys

import numpy as np
import jax
import pytest

from benchmark import line, run, traffic
from raft_tpu.multiraft import ClusterSim, SimConfig, chaos, workload
from test_read_lease import receipt_digest
from test_workload import host_replay

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
G, P = 8, 5


def stock_cfg(n_groups=G):
    return SimConfig(
        n_groups, P, election_tick=20, heartbeat_tick=2, check_quorum=False,
        pre_vote=False, lease_read=False, collect_health=True,
    )


def outage_chaos():
    """The chaos document the benchmark's generator makes of its `outage`
    mix at P = 5."""
    doc = traffic.chaos_document(traffic.load_mix("outage"), P, "stock-outage")
    assert sum(ph["rounds"] for ph in doc["phases"]) == 600
    return chaos.plan_from_dict(doc)


def client(phases):
    return workload.plan_from_dict(
        {"name": "stock", "peers": P, "seed": 35, "phases": phases})


def mixed_phases(rounds):
    """Settle, then lease fires under Zipf writes, then safe fires, then
    both modes side by side on the two halves of the fleet."""
    a = (rounds - 40) // 3
    return [
        {"rounds": 40, "append": 1},
        {"rounds": a, "write_zipf": 1.9, "write_max": 4, "read_every": 2,
         "read_mode": "lease"},
        {"rounds": a, "append": 1, "read_every": 1, "read_mode": "safe"},
        {"rounds": rounds - 40 - 2 * a, "write_zipf": 1.5, "write_max": 2,
         "read_every": 3, "read_mode": "lease"},
    ]


def test_store_loss_mix_equals_the_scalar_replay():
    cfg, cplan = stock_cfg(), outage_chaos()
    plan = client(mixed_phases(600))
    sim = ClusterSim(cfg)
    report = sim.run_reads(plan, cplan)

    # The scalar side of the two leadership counts, by their definitions.
    last = np.zeros(G, np.int64)
    top = np.zeros(G, np.int64)
    want = {"leader_changes": 0, "term_bumps": 0}

    in_flight = np.zeros(G, np.int32)

    def each_round(cl, crashed, pending):
        in_flight[:] = pending
        terms = cl.snapshot()["term"].max(axis=1)
        want["term_bumps"] += int((terms - top).sum())
        top[:] = terms
        for g in range(G):
            lead = cl.acting_leader(g, crashed[:, g]) or 0
            if lead and last[g] and lead != last[g]:
                want["leader_changes"] += 1
            if lead:
                last[g] = lead

    stats, hist, oracle = host_replay(cfg, plan, cplan, each_round)
    snap = oracle.cluster.snapshot()
    st = sim.state
    for key in ("term", "state", "commit", "last_index"):
        assert np.array_equal(np.asarray(getattr(st, key)).T, snap[key]), key
    names = workload.READ_STAT_NAMES
    assert {n: report[n] for n in names} == {
        n: int(v) for n, v in zip(names, stats)}
    assert report["served_lease"] == 0 and report["served_quorum"] > 0
    assert report["degraded_serves"] > 0, "lease requests degrade to ReadIndex"
    assert report["dropped_fires"] > 0, "a read waits while a store is lost"
    assert np.array_equal(np.asarray(sim._read_carry.pending_mode), in_flight)
    assert int((in_flight > 0).sum()) == (
        report["reads_issued"] - report["served_quorum"])
    assert report["leader_changes"] == want["leader_changes"] > 0
    assert report["term_bumps"] == want["term_bumps"] > 0
    assert np.array_equal(np.asarray(sim._read_carry.last_leader), last)
    assert set(report["safety"].values()) == {0} and len(report["safety"]) == 9
    # Without check-quorum nothing deposes the cut-off store's leaders:
    # the last 60 rounds leave two alive leaders in some group, at
    # different terms, which is no safety violation.
    assert report["reelections"] > 0


def test_stock_receipts_are_what_they_were_before_issue_40():
    """ISSUE 40 gave `sim._read_phase` a branch for damping with lease reads
    off; the stock branch (`read_index_holders`, its acting row the probe)
    is bit-equal to the tree before it on a seeded storm of crashes and
    link cuts (digest taken at 0586a2e), mask included."""
    import functools

    from raft_tpu.multiraft import sim

    cfg = stock_cfg()
    step = jax.jit(functools.partial(sim.step, cfg))
    digest, served, held = receipt_digest(step, sim.init_state(cfg), G, P, rounds=90)
    assert served > 0 and held >= served  # every answer is a holder's
    assert digest == "552fc786de97f02375afb8ca07fe4647a54b02b5"


@pytest.mark.parametrize("fires", ["write_only", "safe_fire"])
def test_split_equals_the_scan(fires):
    cfg = stock_cfg()
    phase = {"rounds": 64, "append": 1}
    if fires == "safe_fire":
        phase.update(read_every=1, read_mode="safe")
    plan = client([phase])
    out = {}
    for split in (False, True):
        sim = ClusterSim(cfg)
        sim.run_compiled(80)
        sim.reset_health()
        report = sim.run_reads(plan, split=split)
        out[split] = (report, jax.device_get((sim.state, sim._health)))
    scan, fused = out[False][0], dict(out[True][0])
    total = fused.pop("total_rounds")
    assert total == 64 * G and fused.pop("fused_frac") == fused["fused_rounds"] / total
    got = fused.pop("fused_rounds")
    assert (got > 0) if fires == "write_only" else (got == 0)
    assert fused == scan
    for a, b in zip(jax.tree.leaves(out[False][1]), jax.tree.leaves(out[True][1])):
        assert np.array_equal(a, b)
    assert scan["leader_changes"] == 0 == scan["term_bumps"]
    assert set(scan["safety"].values()) == {0}


STOCK = "fleet-100k-r5-stock"


@pytest.fixture(scope="module")
def bench():
    return run.load_json(ROOT, "BENCHMARK.json")


@pytest.mark.parametrize("cell", ["fleet-1m-r3.outage", f"{STOCK}.outage"])
def test_new_cell_rehearses(bench, cell):
    lines = []
    text = run.run_cell(bench, cell, seed=2**31 + 35, seconds=0.5, traced=False,
                        say=lines.append, n_groups=64, devices=jax.devices())
    problems = [
        p for p in line.validate("\n".join(lines + [text]) + "\n", bench, cell, False)
        if not p.startswith("device.memory_peak_bytes")  # a CPU reports none
    ]
    assert not problems, problems
    got = json.loads(text)
    assert got["correct"] is True and got["attempted"] > got["failed"] > 0
    window = next(json.loads(t)["window"] for t in lines if t.startswith('{"window"'))
    counters = window["counters"]
    if "stock" in cell:
        assert counters["served_lease"] == 0
        assert counters["served_quorum"] > 0
        assert counters.get("fused_rounds", 0) == 0
    else:
        assert counters["served_lease"] > 0


def rehearse(bench, seed):
    lines = []
    text = run.run_cell(bench, f"{STOCK}.outage", seed=seed, seconds=0.3, traced=False,
                        say=lines.append, n_groups=64, devices=jax.devices())
    safety = next(t for t in lines if t.startswith("check safety"))
    return json.loads(text), safety


@pytest.mark.parametrize("seed", [35, 2**31 + 36])
def test_readindex_without_its_majority_is_not_correct(bench, seed):
    sys.path.insert(0, os.path.join(ROOT, "benchmark", "tests"))
    try:
        import control_readindex
    finally:
        sys.path.pop(0)
    sound, safety = rehearse(bench, seed)
    assert sound["correct"] is True and "FAILED" not in safety
    with control_readindex.readindex_without_ack_quorum():
        weak, safety = rehearse(bench, seed)
    assert weak["correct"] is False
    assert "FAILED" in safety and "stale_read" in safety
