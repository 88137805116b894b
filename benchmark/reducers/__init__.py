"""Per-layer metric readers, found by the `reducer` name in
`metrics/<metric>.json`.  Each module has one function

    read(facts: dict, args: dict) -> float | None

`facts` holds what a traced run gathered: `counters` (exact counts summed
over the traced segments' reports), `trace` (trace.TraceFacts as a dict),
`shape` (n_groups, n_peers) and `peaks` (this device's row of peaks.json).
A reader that finds nothing to read returns None and the harness leaves the
metric out of the line; it never invents a value.
"""

import importlib
import re
from typing import Dict, List


def load(name: str):
    return importlib.import_module(f"{__name__}.{name}")


def matching(op_seconds: Dict[str, List[float]], pattern: str):
    """(seconds, calls) of the trace's op names that match `pattern`."""
    rx = re.compile(pattern)
    hit = [v for k, v in op_seconds.items() if rx.search(k)]
    return sum(v[0] for v in hit), sum(v[1] for v in hit)
