"""The last line of standard output: one builder, one strict validator.

The driver reads that line and nothing else.  It is one JSON object with
the keys `correct`, `attempted`, `failed`, `metrics`, `device` and, in a
traced run only, `breakdown`.  `metrics` holds exactly the metrics that
`BENCHMARK.json` lists for the cell in that mode (`--trace 0`: its
end-to-end metrics; `--trace 1`: its per-layer metrics), each as
`{"value": number, "unit": unit}` with the unit `BENCHMARK.json` gives.
`device` holds `platform`, `kind`, `count`, `memory_peak_bytes` and, traced,
`window_s` and `busy_s` with 0 < busy_s <= window_s.  A per-layer metric may
be absent only where the run said it left it out (`run.read_metrics`: the
program under test does not carry a name the metric's reader asks it for, or
the subject the metric's file `needs` did not run in the window); `validate`
is told which.

`build` is the only place a value is cast for JSON, and it refuses what
JSON cannot say (NaN, infinities): a metric that cannot be computed is an
error with its reason, never a printed line.  `validate` takes the whole
captured stdout, so that anything printed after the line is seen.
"""

from __future__ import annotations

import json
import math
from typing import Dict, List, Optional, Tuple

TOP_KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")
TRACED_DEVICE_KEYS = ("window_s", "busy_s")
BREAKDOWN_KEYS = ("device_ops", "idle_gaps")
BREAKDOWN_MAX = 10
SHARE_CEILING = 105.0  # a roofline or mfu share read above this is refused


class LineError(ValueError):
    """The line cannot be built: the reason is for stderr."""


def _number(value, what: str) -> float:
    """A finite python float from a python/numpy/jax scalar."""
    if isinstance(value, bool):
        raise LineError(f"{what} is a bool, not a number")
    try:
        out = float(value)
    except (TypeError, ValueError) as e:
        raise LineError(f"{what} is not a number: {value!r}") from e
    if not math.isfinite(out):
        raise LineError(f"{what} is not finite: {out!r}")
    return out


def _count(value, what: str) -> int:
    out = _number(value, what)
    if out != int(out) or out < 0:
        raise LineError(f"{what} is not a count: {value!r}")
    return int(out)


def expected_metrics(bench: dict, workload: str, traced: bool) -> Dict[str, str]:
    """{metric name: unit} that the line of `workload` must carry."""
    def in_cell(metric: dict) -> bool:
        cells = metric.get("workloads")
        return cells is None or workload in cells

    e2e = {m["name"]: m["unit"] for m in bench["end_to_end"] if in_cell(m)}
    if not traced:
        return e2e
    return {
        m["name"]: m["unit"]
        for m in bench["per_layer"]
        if in_cell(m) and m["moves"] in e2e
    }


def build(
    *, correct, attempted, failed,
    metrics: Dict[str, Tuple[object, str]],
    device: dict,
    breakdown: Optional[dict] = None,
) -> str:
    """The line, as text without a newline."""
    traced = breakdown is not None or "busy_s" in device
    dev = {
        "platform": str(device["platform"]),
        "kind": str(device["kind"]),
        "count": _count(device["count"], "device.count"),
        "memory_peak_bytes": _count(
            device["memory_peak_bytes"], "device.memory_peak_bytes"
        ),
    }
    if traced:
        for k in TRACED_DEVICE_KEYS:
            dev[k] = _number(device[k], f"device.{k}")
        if not 0.0 < dev["busy_s"] <= dev["window_s"]:
            raise LineError(
                f"device.busy_s {dev['busy_s']!r} is not above 0 and at "
                f"most window_s {dev['window_s']!r}"
            )
    obj = {
        "correct": bool(correct),
        "attempted": _count(attempted, "attempted"),
        "failed": _count(failed, "failed"),
        "metrics": {
            str(name): {"value": _number(value, f"metric {name}"), "unit": str(unit)}
            for name, (value, unit) in metrics.items()
        },
        "device": dev,
    }
    if breakdown is not None:
        obj["breakdown"] = {
            key: [
                [str(name), _number(sec, f"breakdown.{key}[{name}]")]
                for name, sec in breakdown.get(key, [])[:BREAKDOWN_MAX]
            ]
            for key in BREAKDOWN_KEYS
        }
    return json.dumps(obj, allow_nan=False)


def _reject_constant(name: str):
    raise ValueError(f"{name} is not JSON")


def _is_number(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool) and math.isfinite(x)


def validate(stdout: str, bench: dict, workload: str, traced: bool,
             left_out=()) -> List[str]:
    """Every way the captured stdout breaks the contract; [] if none.
    `left_out`: per-layer metrics the run said it left out of the line."""
    problems: List[str] = []
    if not stdout.endswith("\n"):
        problems.append("stdout does not end in a newline")
    lines = stdout.split("\n")
    if lines and lines[-1] == "":
        lines.pop()
    if not lines or not lines[-1].strip():
        return problems + ["the last line of stdout is empty"]
    last = lines[-1]
    try:
        obj = json.loads(last, parse_constant=_reject_constant)
    except ValueError as e:
        return problems + [f"the last line is not JSON ({e}): {last[:120]!r}"]
    if not isinstance(obj, dict):
        return problems + ["the last line is not a JSON object"]

    allowed = set(TOP_KEYS) | ({"breakdown"} if traced else set())
    for k in TOP_KEYS:
        if k not in obj:
            problems.append(f"key {k!r} is missing")
    for k in obj:
        if k not in allowed:
            problems.append(f"key {k!r} is not one of {sorted(allowed)}")
    if problems:
        return problems

    if not isinstance(obj["correct"], bool):
        problems.append("correct is not a bool")
    for k in ("attempted", "failed"):
        if not isinstance(obj[k], int) or isinstance(obj[k], bool) or obj[k] < 0:
            problems.append(f"{k} is not a count: {obj[k]!r}")

    want = expected_metrics(bench, workload, traced)
    got = obj["metrics"]
    if not isinstance(got, dict):
        return problems + ["metrics is not an object"]
    for name, unit in want.items():
        if name not in got:
            if not (traced and name in left_out):
                problems.append(f"metric {name!r} is listed for {workload} but absent")
            continue
        m = got[name]
        if not isinstance(m, dict) or set(m) != {"value", "unit"}:
            problems.append(f"metric {name!r} is not {{value, unit}}: {m!r}")
            continue
        if m["unit"] != unit:
            problems.append(
                f"metric {name!r} has unit {m['unit']!r}, BENCHMARK.json says {unit!r}"
            )
        if not _is_number(m["value"]):
            problems.append(f"metric {name!r} value is not a finite number: {m['value']!r}")
        elif ("roofline" in name or "mfu" in name) and m["value"] > SHARE_CEILING:
            problems.append(f"share {name!r} reads {m['value']} > {SHARE_CEILING}")
    for name in got:
        if name not in want:
            problems.append(f"metric {name!r} is not listed for {workload} in this mode")

    dev = obj["device"]
    if not isinstance(dev, dict):
        return problems + ["device is not an object"]
    dev_keys = DEVICE_KEYS + (TRACED_DEVICE_KEYS if traced else ())
    for k in dev_keys:
        if k not in dev:
            problems.append(f"device.{k} is missing")
    for k in dev:
        if k not in dev_keys:
            problems.append(f"device.{k} is not expected in this mode")
    for k in ("platform", "kind"):
        if k in dev and not isinstance(dev[k], str):
            problems.append(f"device.{k} is not a string")
    for k in ("count", "memory_peak_bytes"):
        if k in dev and (not isinstance(dev[k], int) or isinstance(dev[k], bool) or dev[k] <= 0):
            problems.append(f"device.{k} is not a positive int: {dev[k]!r}")
    if traced and all(k in dev for k in TRACED_DEVICE_KEYS):
        b, w = dev["busy_s"], dev["window_s"]
        if not (_is_number(b) and _is_number(w)):
            problems.append(f"device.busy_s/window_s are not finite numbers: {b!r}, {w!r}")
        elif not 0.0 < b <= w:
            problems.append(f"device.busy_s {b} is not above 0 and at most window_s {w}")

    if "breakdown" in obj:
        bd = obj["breakdown"]
        if not isinstance(bd, dict) or set(bd) - set(BREAKDOWN_KEYS):
            problems.append(f"breakdown is not an object of {BREAKDOWN_KEYS}")
        else:
            for key, rows in bd.items():
                if not isinstance(rows, list) or len(rows) > BREAKDOWN_MAX:
                    problems.append(f"breakdown.{key} is not a list of at most {BREAKDOWN_MAX}")
                    continue
                for row in rows:
                    if not (isinstance(row, list) and len(row) == 2
                            and isinstance(row[0], str) and _is_number(row[1])):
                        problems.append(f"breakdown.{key} entry is not [name, seconds]: {row!r}")
    return problems


if __name__ == "__main__":
    # python3 benchmark/line.py <workload> <0|1> [metric left out ...] < captured-stdout
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        bench_json = json.load(f)
    found = validate(sys.stdin.read(), bench_json, sys.argv[1], sys.argv[2] == "1",
                     left_out=sys.argv[3:])
    for p in found:
        print(f"INVALID: {p}")
    print("line ok" if not found else f"{len(found)} problem(s)")
    sys.exit(1 if found else 0)
