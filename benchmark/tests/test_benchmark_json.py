"""BENCHMARK.json against the contract's limits on names, units and files,
and against the data files the harness finds by name."""

import json
import os
import re


from benchmark import line, reducers

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51 and isinstance(bench["run_seconds"], int)
    assert len(json.dumps(bench)) < 64 * 1024
    assert bench["paths"] == ["benchmark"] and bench["command"][1].startswith("benchmark/")


def all_names(bench):
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[key]:
            yield entry["name"]
    for w in bench["workloads"]:
        yield w["config"]
        yield w["traffic"]


def test_names_and_units(bench):
    for name in all_names(bench):
        assert NAME.match(name), name
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), (m["name"], m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[key]]
        assert len(names) == len(set(names))
    metric_names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(metric_names) == len(set(metric_names))


def test_entries_have_just_their_keys(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert "\n" not in w["why"] and "\t" not in w["why"]
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])


def test_every_cell_reports_setup_another_metric_and_a_layer_metric(bench):
    cells = {w["name"] for w in bench["workloads"]}
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert {w["config"] for w in bench["workloads"]} == {c["name"] for c in bench["configs"]}
    for cell in cells:
        e2e = line.expected_metrics(bench, cell, False)
        assert "setup_s" in e2e and len(e2e) >= 2
        assert line.expected_metrics(bench, cell, True)
    e2e_names = {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", [])) <= cells
    for m in bench["per_layer"]:
        assert m["moves"] in e2e_names
        for cell in m.get("workloads", cells):
            assert m["moves"] in line.expected_metrics(bench, cell, False), (m["name"], cell)


def test_files_named_in_the_contract_exist_and_agree(bench):
    for c in bench["configs"]:
        assert c["file"].startswith("benchmark/")
        with open(os.path.join(ROOT, c["file"]), encoding="utf-8") as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"] == []
        assert cfg["election_tick"] == 20 and cfg["heartbeat_tick"] == 2
        assert cfg["check_quorum"] and cfg["pre_vote"] and cfg["lease_read"]
        assert cfg["chips"] == 1 and cfg["guarantees"] and cfg["assumed"]
        assert len(cfg["source"]) <= 200
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(ROOT, "benchmark", "traffic", w["traffic"] + ".json"))
    with open(os.path.join(ROOT, "benchmark", "peaks.json"), encoding="utf-8") as f:
        assert "source" in json.load(f)["TPU v5 lite"]


def test_every_per_layer_metric_has_a_reader_that_agrees(bench):
    for m in bench["per_layer"]:
        with open(os.path.join(ROOT, "benchmark", "metrics", m["name"] + ".json"), encoding="utf-8") as f:
            spec = json.load(f)
        for key in ("layer", "unit", "moves", "source"):
            assert spec[key] == m[key], (m["name"], key)
        assert callable(reducers.load(spec["reducer"]).read)
        if "roofline" in m["name"]:
            assert m["unit"] == "%"
        assert set(spec) <= {"layer", "unit", "moves", "source", "reducer", "args", "needs"}
        if "needs" in spec:  # the reader's subject, by a condition run.py can evaluate
            assert spec["needs"] in reducers.CONDITIONS, (m["name"], spec["needs"])


def test_conditions_are_a_predicate_on_the_counts_and_a_reason():
    mixed = {"group_rounds": 10, "fused_rounds": 8}
    for name, (ran, reason) in reducers.CONDITIONS.items():
        assert callable(ran) and isinstance(reason, str) and reason, name
        assert reducers.not_run(name, mixed) is None
    assert reducers.not_run(None, {}) is None
    all_fused = {"group_rounds": 10, "fused_rounds": 10}
    assert reducers.not_run("general_rounds", all_fused) == "the window ran no general round"
    assert reducers.not_run("general_rounds", {"group_rounds": 10}) is None  # no fused accounting


def test_counted_segments_is_a_positive_integer_where_a_mix_states_it(bench):
    for mix in sorted({w["traffic"] for w in bench["workloads"]}):
        with open(os.path.join(ROOT, "benchmark", "traffic", mix + ".json"), encoding="utf-8") as f:
            doc = json.load(f)
        if "counted_segments" in doc:
            n = doc["counted_segments"]
            assert isinstance(n, int) and not isinstance(n, bool) and n >= 1, (mix, n)
            assert "counted_segments" in doc["assumed"], mix  # and says why


def test_file_names_under_paths_use_name_characters():
    bad = []
    for base, dirs, files in os.walk(os.path.join(ROOT, "benchmark")):
        dirs[:] = [d for d in dirs if d not in ("__pycache__", ".trace", ".pytest_cache")]
        for f in files:
            if not re.match(r"^[A-Za-z0-9_.\-]+$", f):
                bad.append(os.path.join(base, f))
    assert not bad
