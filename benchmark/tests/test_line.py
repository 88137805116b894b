"""The last line: what `build` refuses to print and what `validate` refuses
to accept, in both trace modes."""

import json

import numpy as np
import pytest

from benchmark import line

CELL = "fleet-100k-r5.outage"
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
          "memory_peak_bytes": 123456789}
TRACED = {**DEVICE, "window_s": 2.5, "busy_s": 2.25}
BREAKDOWN = {"device_ops": [["fusion.1", 1.5]], "idle_gaps": [["segment.tail.total", 0.2]]}


def metrics_for(bench, traced, **override):
    out = {name: (1.5, unit)
           for name, unit in line.expected_metrics(bench, CELL, traced).items()}
    out.update(override)
    return out


def good(bench, traced):
    if traced:
        return line.build(correct=True, attempted=10, failed=1,
                          metrics=metrics_for(bench, True), device=TRACED,
                          breakdown=BREAKDOWN)
    return line.build(correct=True, attempted=10, failed=1,
                      metrics=metrics_for(bench, False), device=DEVICE)


@pytest.mark.parametrize("traced", [False, True])
def test_a_built_line_validates(bench, traced):
    text = good(bench, traced)
    assert line.validate("info\n" + text + "\n", bench, CELL, traced) == []
    obj = json.loads(text)
    assert set(obj) == set(line.TOP_KEYS) | ({"breakdown"} if traced else set())
    assert set(obj["metrics"]) == set(line.expected_metrics(bench, CELL, traced))


def test_modes_carry_different_metrics(bench):
    e2e = line.expected_metrics(bench, CELL, False)
    layer = line.expected_metrics(bench, CELL, True)
    assert "setup_s" in e2e and "recover_ms" in e2e
    assert not set(e2e) & set(layer)
    assert "recover_ms" not in line.expected_metrics(bench, "fleet-100k-r5.load", False)


@pytest.mark.parametrize("bad", [float("nan"), float("inf"), -float("inf"), None, "1.0x", True])
def test_build_refuses_what_json_cannot_say(bench, bad):
    with pytest.raises(line.LineError):
        line.build(correct=True, attempted=1, failed=0,
                   metrics=metrics_for(bench, False, setup_s=(bad, "s")), device=DEVICE)


def test_build_casts_numpy_and_jax_scalars(bench):
    import jax.numpy as jnp

    text = line.build(
        correct=np.bool_(True), attempted=np.int64(7), failed=jnp.int32(0),
        metrics=metrics_for(bench, False, setup_s=(np.float32(2.5), "s"),
                            recover_ms=(jnp.float32(3.0), "ms")),
        device={**DEVICE, "count": np.int32(1), "memory_peak_bytes": np.int64(99)},
    )
    obj = json.loads(text)
    assert obj["attempted"] == 7 and obj["metrics"]["setup_s"]["value"] == 2.5
    assert line.validate(text + "\n", bench, CELL, False) == []


@pytest.mark.parametrize("busy,window", [(0.0, 2.0), (2.5, 2.0), (-1.0, 2.0)])
def test_build_refuses_busy_outside_the_window(bench, busy, window):
    with pytest.raises(line.LineError):
        line.build(correct=True, attempted=1, failed=0, metrics=metrics_for(bench, True),
                   device={**DEVICE, "busy_s": busy, "window_s": window},
                   breakdown=BREAKDOWN)


def mutate(text, fn):
    obj = json.loads(text)
    fn(obj)
    return json.dumps(obj)


CASES = {
    "nan": lambda t: t.replace("1.5", "NaN", 1),
    "infinity": lambda t: t.replace("1.5", "Infinity", 1),
    "missing metric": lambda t: mutate(t, lambda o: o["metrics"].pop("setup_s", o["metrics"].pop("device_idle_share", None))),
    "wrong unit": lambda t: mutate(t, lambda o: next(iter(o["metrics"].values())).update(unit="furlongs")),
    "unlisted metric": lambda t: mutate(t, lambda o: o["metrics"].update(extra={"value": 1, "unit": "s"})),
    "extra top key": lambda t: mutate(t, lambda o: o.update(versions={})),
    "missing key": lambda t: mutate(t, lambda o: o.pop("failed")),
    "string value": lambda t: mutate(t, lambda o: next(iter(o["metrics"].values())).update(value="1.5")),
    "peak missing": lambda t: mutate(t, lambda o: o["device"].pop("memory_peak_bytes")),
    "peak zero": lambda t: mutate(t, lambda o: o["device"].update(memory_peak_bytes=0)),
    "trailing output": lambda t: t + "\nprofiler: session closed",
    "trailing blank": lambda t: t + "\n",
    "not an object": lambda t: "[1, 2]",
}


@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("case", sorted(CASES))
def test_validate_rejects(bench, traced, case):
    text = CASES[case](good(bench, traced))
    assert line.validate(text + "\n", bench, CELL, traced) != [], case


def test_validate_rejects_no_final_newline_and_empty(bench):
    assert line.validate(good(bench, False), bench, CELL, False) != []
    assert line.validate("", bench, CELL, False) != []


@pytest.mark.parametrize("busy,window", [(0.0, 2.0), (2.5, 2.0)])
def test_validate_rejects_busy_outside_the_window(bench, busy, window):
    text = mutate(good(bench, True), lambda o: o["device"].update(busy_s=busy, window_s=window))
    assert line.validate(text + "\n", bench, CELL, True) != []


def test_validate_rejects_traced_keys_in_an_untraced_line(bench):
    text = mutate(good(bench, False), lambda o: o["device"].update(busy_s=1.0, window_s=2.0))
    assert line.validate(text + "\n", bench, CELL, False) != []
    text = mutate(good(bench, False), lambda o: o.update(breakdown={}))
    assert line.validate(text + "\n", bench, CELL, False) != []


def test_validate_rejects_a_roofline_share_over_the_ceiling(bench):
    cell = "fleet-100k-r5.load"
    m = {n: (1.5, u) for n, u in line.expected_metrics(bench, cell, True).items()}
    m["fused_kernel_roofline"] = (140.0, "%")
    text = line.build(correct=True, attempted=1, failed=0, metrics=m, device=TRACED,
                      breakdown=BREAKDOWN)
    assert any("roofline" in p for p in line.validate(text + "\n", bench, cell, True))


def test_a_metric_the_run_left_out_may_be_absent_and_no_other(bench):
    """`run.read_metrics` leaves a per-layer metric out where the program
    lacks a name its reader asks for; the line validates when told so, and
    only for that metric, and only in a traced line."""
    metrics = metrics_for(bench, True)
    del metrics["op_gather_share"]
    text = line.build(correct=True, attempted=10, failed=1, metrics=metrics,
                      device=TRACED, breakdown=BREAKDOWN) + "\n"
    assert line.validate(text, bench, CELL, True, left_out=["op_gather_share"]) == []
    assert any("op_gather_share" in p for p in line.validate(text, bench, CELL, True))
    assert line.validate(text, bench, CELL, True, left_out=["quorum_commit_share"])
    e2e = metrics_for(bench, False)
    del e2e["recover_ms"]
    text = line.build(correct=True, attempted=10, failed=1, metrics=e2e, device=DEVICE) + "\n"
    assert line.validate(text, bench, CELL, False, left_out=["recover_ms"])
