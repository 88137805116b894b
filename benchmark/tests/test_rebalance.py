"""`fleet-100k-r3of5.rebalance` as data: the mix's schedule against what its
`assumed` says of it, and the cell's five per-layer metrics — four read
names the program has carried since PR 29 (`reconfig.gate`,
`reconfig.apply`, the conf counts of the report span), so on the PR 26
recording they read nothing, name what the program lacks and are left out
of the line; with those names grafted onto the recording they read the
numbers computed here by hand."""

import json
import os

import numpy as np
import pytest

from benchmark import program_trace as pt
from benchmark import reducers, run, traffic
from benchmark.reference import membership

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "fleet-100k-r3of5.rebalance"
NAMED = ("joint_rounds_share", "conf_retry_share", "conf_gate_share", "conf_apply_share")
CONF_COUNTS = {"conf_proposals": 52, "conf_applied": 48, "conf_retries": 4,
               "joint_group_rounds": 170, "conf_unfinished": 0}


def spec_of(name):
    with open(os.path.join(BENCH, "metrics", name + ".json"), encoding="utf-8") as f:
        return json.load(f)


def read(name, facts):
    spec = spec_of(name)
    return reducers.load(spec["reducer"]).read(facts, spec["args"])


@pytest.fixture(scope="module")
def config():
    return run.load_json(BENCH, "configs", "fleet-100k-r3of5.json")


@pytest.fixture(scope="module")
def mix():
    return traffic.load_mix("rebalance")


# --- the deployment and its mix ------------------------------------------------


def test_the_configuration_is_baselines_and_nothing_is_reduced(config):
    assert (config["n_groups"], config["n_peers"]) == (100_000, 5)
    assert membership.of_config(config) == ([1, 2, 3], [])
    assert config["reduced"] == [] and config["chips"] == 1
    serve = run.load_json(BENCH, "configs", "fleet-100k-r5.json")
    for key in ("election_tick", "heartbeat_tick", "election_timeout_range", "check_quorum",
                "pre_vote", "lease_read", "collect_health", "message_delay_rounds",
                "boot_rounds"):
        assert config[key] == serve[key], key
    assert any("joint" in g and "outgoing" in g for g in config["guarantees"])
    assert any(g.startswith("membership:") for g in config["guarantees"])


def test_the_foreground_is_serves_ycsb_b(mix):
    serve = traffic.load_mix("serve")
    for key in ("phase_rounds", "ops_per_round_per_group", "read_share", "read_mode",
                "distribution"):
        assert mix[key] == serve[key], key
    assert mix["split"] is False


def test_four_classes_move_one_replica_there_and_back(mix, config):
    phases = mix["reconfig"]["phases"]
    G, P = config["n_groups"], config["n_peers"]
    classes = membership.classes(phases, G)
    assert len(classes) == 4
    moves = {}
    for chain, groups in classes.items():
        n = int(groups.sum())
        assert n in (2083, 2084)  # PD's region-schedule-limit 2048 at this size
        assert len(chain) == 6  # K = 6 op slots: two moves of three ops
        k = int(np.flatnonzero(groups)[0])
        assert np.array_equal(np.flatnonzero(groups) % 48, np.full(n, k))
        steps = membership.walk(phases, chain, P, [1, 2, 3], [])
        away, back = steps[2], steps[5]
        assert not away.outgoing and not back.outgoing
        assert steps[1].outgoing == frozenset({1, 2, 3})  # the joint window
        assert back.voters == frozenset({1, 2, 3}) and not back.learners
        (gone,), (came,) = {1, 2, 3} - away.voters, away.voters - {1, 2, 3}
        moves[k] = (gone, came)
        for i in chain:
            assert phases[i]["rounds"] == 8
    assert moves == {0: (1, 4), 1: (2, 5), 2: (3, 4), 3: (1, 5)}
    share = sum(int(g.sum()) for g in classes.values()) / G
    assert 0.083 < share < 0.084


def test_a_class_rests_two_election_timeouts_after_each_leave_joint(mix, config):
    phases = mix["reconfig"]["phases"]
    starts = membership.phase_starts(phases)
    total = traffic.segment_rounds(mix, config["n_peers"])
    assert total == sum(ph["rounds"] for ph in phases) <= 400
    settle = 2 * config["election_tick"]
    for chain in membership.classes(phases, config["n_groups"]):
        leaves = [i for i in chain if "leave_joint" in phases[i]["op"]]
        assert len(leaves) == 2
        first_back = chain[3]
        assert starts[first_back] >= starts[leaves[0]] + phases[leaves[0]]["rounds"] + settle
        assert total >= starts[leaves[1]] + phases[leaves[1]]["rounds"] + settle
    assert starts[1] == 8  # the lead-in


def test_the_zone_split_opens_inside_class_0s_first_joint_window(mix, config):
    phases = mix["reconfig"]["phases"]
    starts = membership.phase_starts(phases)
    seg = traffic.generate(mix, 96, config["n_peers"], 5, name=CELL, voters=[1, 2, 3])
    crashed = [ph for ph in seg.chaos["phases"] if ph.get("crash")]
    cut = [(i, ph) for i, ph in enumerate(seg.chaos["phases"]) if ph.get("partition")]
    assert not crashed and len(cut) == 1
    (i, ph), = cut
    opens = sum(p["rounds"] for p in seg.chaos["phases"][:i])
    assert ph["partition"] == [[1, 2]] and ph["rounds"] == 24
    chain0 = next(c for c, g in membership.classes(phases, 96).items() if g[0])
    enter, leave = chain0[1], chain0[2]
    assert "enter_joint" in phases[enter]["op"]
    assert opens == starts[enter] + 4 < starts[leave]
    # Incoming {2,3,4} and outgoing {1,2,3} have their majorities on
    # different sides of {1,2} | {3,4,5}: neither side may commit.
    joint = membership.walk(phases, chain0, 5, [1, 2, 3], [])[1]
    side = {1, 2}
    assert len(joint.voters & side) < 2 <= len(joint.voters - side)
    assert len(joint.outgoing - side) < 2 <= len(joint.outgoing & side)
    assert seg.conf_ops == 6 * 8  # two regions of each class at G = 96


# --- the cell's per-layer metrics ---------------------------------------------------


def test_the_cell_lists_its_five_metrics_and_no_other(bench):
    mine = {m["name"]: m for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    assert set(mine) == set(NAMED) | {"rebalance_round_ms"}
    for m in mine.values():
        assert m["workloads"] == [CELL] and m["moves"] == "group_rounds_per_s"
        assert m["better"] == "lower"
    assert set(run.line.expected_metrics(bench, CELL, False)) == {"group_rounds_per_s", "setup_s"}
    # The round's cost under the name this cell can list it by: the reader
    # and its arguments are general_round_ms's.
    a, b = spec_of("rebalance_round_ms"), spec_of("general_round_ms")
    assert (a["reducer"], a["args"]) == (b["reducer"], b["args"])


def test_the_four_named_metrics_ask_the_program_for_what_pr_29_added():
    from raft_tpu import profiling

    asked = {}
    for name in NAMED:
        spec = spec_of(name)
        for kind, names in reducers.load(spec["reducer"]).names(spec["args"]).items():
            asked.setdefault(kind, set()).update(names)
    assert asked["scopes"] == {"reconfig.gate", "reconfig.apply"} <= set(profiling.SCOPES)
    assert asked["spans"] == {"raft.run_reads.report"} <= set(profiling.SPANS)
    assert asked["counts"] == {"joint_group_rounds", "rounds", "groups", "conf_retries",
                               "conf_proposals"}


@pytest.fixture(scope="module")
def recorded():
    cap = pt.load_recorded(os.path.join(HERE, "data", "program_trace.json"))
    return cap, pt.facts_of(cap)


def test_on_a_program_without_the_names_they_are_left_out_of_the_line(recorded):
    """The PR 26 recording: a program with a catalogue but without the two
    scopes and the conf counts — the parent of PR 29."""
    cap, facts = recorded
    report = pt.spans_named(cap, "raft.run_reads.report")[-1]
    program = {"spans": {"raft.run_reads.report"}, "scopes": {"op_gather", "round.damped"},
               "kernels": set(), "counts": set(report.stats)}
    readers = {}
    for name in NAMED:
        spec = spec_of(name)
        assert read(name, facts) is None
        readers[name] = run.Reader(spec["unit"], reducers.load(spec["reducer"]), spec["args"])
    said = []
    metrics, left_out = run.read_metrics(readers, facts, program, CELL, said.append)
    assert metrics == {} and left_out == list(NAMED) and len(said) == 4
    # ... and a program that HAS the names but shows nothing stops the run.
    program["scopes"] |= {"reconfig.gate", "reconfig.apply"}
    program["counts"] |= set(CONF_COUNTS)
    with pytest.raises(run.BenchError):
        run.read_metrics(readers, facts, program, CELL, said.append)


def test_with_the_names_grafted_on_they_read_the_numbers_computed_by_hand(recorded):
    cap, facts = recorded

    def under(path):
        """The op protocol's look-ups under the half that makes them: the
        owner look-ups (jit(clip) first) in the gate, the rest in apply."""
        if "/op_gather/" not in path:
            return path
        half = "reconfig.gate" if "jit(clip)" in path else "reconfig.apply"
        return path.replace("/op_gather/", f"/{half}/op_gather/")

    grafted = pt.Capture(
        [s._replace(stats={**s.stats, **CONF_COUNTS}) if s.name == "raft.run_reads.report" else s
         for s in cap.spans],
        [o._replace(path=under(o.path)) for o in cap.ops],
        cap.modules,
    )
    facts = {**facts, "capture": grafted}
    reports = pt.spans_named(grafted, "raft.run_reads.report")
    group_rounds = sum(r.stats["rounds"] * r.stats["groups"] for r in reports)
    assert read("joint_rounds_share", facts) == pytest.approx(
        100.0 * 170 * len(reports) / group_rounds)
    assert read("conf_retry_share", facts) == pytest.approx(100.0 * 4 / 52)
    gate, apply_ = read("conf_gate_share", facts), read("conf_apply_share", facts)
    gather = read("op_gather_share", facts)
    assert gate > 0 and apply_ > 0
    assert gate + apply_ == pytest.approx(gather)  # the look-ups stay op_gather's, inside
