"""Per-layer metric readers, found by the `reducer` name in
`metrics/<metric>.json`.  Each module has one function

    read(facts: dict, args: dict) -> float | None

`facts` holds what a traced run gathered, from one read of its capture:
`counters` (exact counts summed over the traced segments' reports), `trace`
(trace.TraceFacts as a dict), `capture` (program_trace.Capture: the
program's host spans with their stats, every `XLA Ops` / `XLA Modules` event
with its name stack), `shape` (n_groups, n_peers) and `peaks` (this device's
row of peaks.json).  A reader that finds nothing to read returns None; it
never invents a value.

A reader of what the PROGRAM names also has

    names(args: dict) -> {"spans" | "scopes" | "kernels" | "counts": [name, ...]}

— the names its `args` ask the program for.  `lacking` holds them against
what the program under test carries (`run.program_names`): a None from a
reader whose names the program lacks leaves the metric out of the line (an
older or newer program is no fault of the run); a None although the program
carries every name stops the run.

A metric file may also state `"needs": <condition>`: the subject its reader
reads, by a name of `CONDITIONS`.  `not_run` holds it against the window's
exact counts: a None from a reader whose subject did not run in the window
leaves the metric out too (nothing ran that it could have read); a None
although the subject ran stops the run.  No reader returns 0 for "not run".
"""

import importlib
import re
from typing import Callable, Dict, List, Optional, Tuple

# condition -> (did the subject run, by `facts["counters"]`; the reason where not)
CONDITIONS: Dict[str, Tuple[Callable[[dict], bool], str]] = {
    "general_rounds": (
        lambda c: c["group_rounds"] - c.get("fused_rounds", 0) > 0,
        "the window ran no general round",
    ),
}


def load(name: str):
    return importlib.import_module(f"{__name__}.{name}")


def lacking(reducer, args: dict, program: Dict[str, set]) -> List[str]:
    """The names `reducer` asks the program for that `program` lacks."""
    ask = getattr(reducer, "names", None)
    if ask is None:
        return []
    return [f"{kind} {name!r}" for kind, names in ask(args).items()
            for name in names if name not in program.get(kind, ())]


def not_run(needs: Optional[str], counters: dict) -> Optional[str]:
    """Why the subject a metric file `needs` did not run in the window, by
    the reports' exact counts; None where it ran, or the file states none."""
    if needs is None:
        return None
    ran, reason = CONDITIONS[needs]
    return None if ran(counters) else reason


def matching(op_seconds: Dict[str, List[float]], pattern: str):
    """(seconds, calls) of the trace's op names that match `pattern`."""
    rx = re.compile(pattern)
    hit = [v for k, v in op_seconds.items() if rx.search(k)]
    return sum(v[0] for v in hit), sum(v[1] for v in hit)
