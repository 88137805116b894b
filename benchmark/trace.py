"""Reduction of a profiler trace to the facts the per-layer metrics read.

A trace is handled as a flat list of events `(plane, line, name, start_ns,
dur_ns)`, so that the reduction can be tested on a small recorded chip trace
kept as JSON (`tests/data/`), not on an `.xplane.pb`.

What the chip's trace looks like (TPU v5 lite, jax 0.9.0, looked at by hand
in PR 25): one plane per chip named `/device:TPU:<n>`, holding several
lines — `XLA Ops` (one event per executed HLO op; a `while`, `conditional`
or `call` op is an event that CONTAINS its body's events), `XLA Modules`
(one event per executed program), `Steps`, and others; and a `/host:CPU`
plane whose lines are host threads, where this benchmark's own
`TraceAnnotation`s (names starting `bench.`) appear.  All planes share one
clock.

Rules, each there because the simple way is wrong:

- busy is the UNION of the intervals of ONE line (`XLA Ops`) of each device
  plane, clipped to the window, averaged over the device planes.  Summing
  durations counts a `while` and its body twice; summing lines counts
  every op three times.
- the window is the host span from the first `bench.segment` start to the
  last `bench.segment` end, not `--seconds` and not the whole capture.
- a name's time is its SELF time: its duration less the events nested in
  it on the same line, so that containers do not swallow their bodies.
- no device plane, no op line, or no op event inside the window is an
  error.  A CPU run has no device plane: its traced path fails here.
- an idle gap is named after the innermost host span of the PROGRAM that
  covers it (`raft.run_reads.dispatch`; a gap that straddles spans is cut at
  their edges); where the events hold no such span — an older program, or a
  loader that keeps only the `bench.` spans — by its place in the segment
  (`segment.head` / `.mid` / `.tail`), as before the program drew spans.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys
from typing import Dict, Iterable, List, NamedTuple, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
SEGMENT_SPAN = "bench.segment"


class TraceError(RuntimeError):
    """The trace does not hold what the reduction needs."""


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def newest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise TraceError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load_xplane(path: str) -> List[Event]:
    """Device-plane events and this benchmark's host spans of one capture."""
    from jax.profiler import ProfileData

    events: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if device or name.startswith(SPAN_PREFIX):
                    events.append(Event(
                        plane.name, line.name, name,
                        float(ev.start_ns), float(ev.duration_ns),
                    ))
    return events


def describe(path: str) -> dict:
    """Planes, lines and event counts of a capture: what to look at by hand
    before trusting the reduction on a new device or jax version."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            evs = list(line.events)
            lines[line.name] = {
                "events": len(evs),
                "first": [[e.name, e.start_ns, e.duration_ns] for e in evs[:3]],
            }
        out[plane.name] = lines
    return out


def union_seconds(intervals: Iterable[Tuple[float, float]],
                  lo: float, hi: float) -> float:
    """Length of the union of [start, end) intervals (ns), clipped to
    [lo, hi], in seconds."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


def gaps(intervals: Iterable[Tuple[float, float]], lo: float, hi: float):
    """The idle gaps [(start, end)] (ns) of the union inside [lo, hi]."""
    out, edge = [], lo
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if s > edge:
            out.append((edge, s))
        edge = max(edge, e)
    if hi > edge:
        out.append((edge, hi))
    return out


def self_seconds(events: Sequence[Event], lo: float, hi: float) -> Dict[str, List[float]]:
    """{name: [self seconds, calls]} of one line's events inside [lo, hi]:
    each event's clipped duration less that of the events nested in it."""
    out: Dict[str, List[float]] = {}
    stack: List[list] = []  # [event, clipped length, children's clipped length]

    def close(item):
        ev, length, child = item
        acc = out.setdefault(ev.name, [0.0, 0])
        acc[0] += max(length - child, 0.0) / 1e9
        acc[1] += 1

    for ev in sorted(events, key=lambda e: (e.start_ns, -e.dur_ns)):
        s, e = max(ev.start_ns, lo), min(ev.end_ns, hi)
        if e <= s:
            continue
        while stack and stack[-1][0].end_ns <= ev.start_ns:
            close(stack.pop())
        if stack:
            stack[-1][2] += e - s
        stack.append([ev, e - s, 0.0])
    while stack:
        close(stack.pop())
    return out


def name_gap(s: float, e: float, spans: Sequence[Event], fallback: str):
    """[(label, ns)] of the gap [s, e): cut at the edges of the host spans
    `spans`, each piece named after the innermost span that covers it
    (the one that started last), or `fallback` where none does."""
    over = [sp for sp in spans if sp.start_ns < e and sp.end_ns > s]
    if not over:
        return [(fallback, e - s)]
    edges = sorted({s, e} | {t for sp in over for t in (sp.start_ns, sp.end_ns) if s < t < e})
    out = []
    for lo, hi in zip(edges, edges[1:]):
        inside = [sp for sp in over if sp.start_ns <= lo and sp.end_ns >= hi]
        label = max(inside, key=lambda sp: (sp.start_ns, -sp.dur_ns)).name if inside else fallback
        out.append((label, hi - lo))
    return out


class TraceFacts(NamedTuple):
    window_s: float
    busy_s: float  # union of op intervals in the window, mean over chips
    n_chips: int
    op_seconds: Dict[str, List[float]]  # name -> [self seconds, calls], mean over chips
    segments: List[dict]  # per host segment span: {"span_s", "busy_s"}
    idle_gaps: List[Tuple[str, float]]  # longest gaps, labelled, seconds


def reduce_events(events: Sequence[Event], top_gaps: int = 10) -> TraceFacts:
    spans = sorted(
        (e for e in events if e.plane == HOST_PLANE and e.name == SEGMENT_SPAN),
        key=lambda e: e.start_ns,
    )
    if not spans:
        raise TraceError(f"no {SEGMENT_SPAN!r} host span in the trace")
    lo, hi = spans[0].start_ns, max(e.end_ns for e in spans)
    program_spans = [e for e in events
                     if e.plane == HOST_PLANE and not e.name.startswith(SPAN_PREFIX)]

    planes = sorted({e.plane for e in events if DEVICE_PLANE.match(e.plane)})
    if not planes:
        raise TraceError(
            "no /device:TPU:<n> plane in the trace (planes: "
            f"{sorted({e.plane for e in events})}): a traced run needs the chip"
        )
    busy = 0.0
    op_seconds: Dict[str, List[float]] = {}
    seg_busy = [0.0] * len(spans)
    gap_list: List[Tuple[str, float]] = []
    for plane in planes:
        ops = [e for e in events if e.plane == plane and e.line == OPS_LINE]
        if not ops:
            found = sorted({e.line for e in events if e.plane == plane})
            raise TraceError(f"plane {plane} has no {OPS_LINE!r} line (lines: {found})")
        intervals = [(e.start_ns, e.end_ns) for e in ops]
        plane_busy = union_seconds(intervals, lo, hi)
        if plane_busy <= 0.0:
            raise TraceError(f"no device op of {plane} ran inside the traced window")
        busy += plane_busy
        for name, (sec, calls) in self_seconds(ops, lo, hi).items():
            acc = op_seconds.setdefault(name, [0.0, 0])
            acc[0] += sec
            acc[1] += calls
        for i, sp in enumerate(spans):
            seg_busy[i] += union_seconds(intervals, sp.start_ns, sp.end_ns)
        if plane == planes[0]:
            for i, sp in enumerate(spans):
                for s, e in gaps(intervals, sp.start_ns, sp.end_ns):
                    if s == sp.start_ns:
                        where = "head"  # host work before the first device op
                    elif e == sp.end_ns:
                        where = "tail"  # report download and host work after the last
                    else:
                        where = "mid"
                    for label, ns in name_gap(s, e, program_spans, f"segment.{where}"):
                        gap_list.append((label, ns / 1e9))
                if i + 1 < len(spans) and spans[i + 1].start_ns > sp.end_ns:
                    gap_list.append(("between_segments", (spans[i + 1].start_ns - sp.end_ns) / 1e9))
    n = len(planes)
    by_label: Dict[str, float] = {}
    for label, sec in gap_list:
        longest, total = f"{label}.longest", f"{label}.total"
        by_label[longest] = max(by_label.get(longest, 0.0), sec)
        by_label[total] = by_label.get(total, 0.0) + sec
    idle = sorted(by_label.items(), key=lambda kv: -kv[1])[:top_gaps]
    return TraceFacts(
        window_s=(hi - lo) / 1e9,
        busy_s=busy / n,
        n_chips=n,
        op_seconds={k: [v[0] / n, v[1] / n] for k, v in op_seconds.items()},
        segments=[
            {"span_s": sp.dur_ns / 1e9, "busy_s": b / n}
            for sp, b in zip(spans, seg_busy)
        ],
        idle_gaps=idle,
    )


_OPCODE = re.compile(r"[\)\}\]] ([a-z][a-z0-9\-]*)\(")


def short_name(name: str) -> str:
    """`fusion.552 fusion` from the whole HLO instruction text the chip's
    trace carries as an op event's name (`%fusion.552 = s32[...] fusion(...`)."""
    head, sep, rest = name.partition(" = ")
    head = head.lstrip("%")
    if not sep:
        return head[:64]
    m = _OPCODE.search(rest)
    return f"{head} {m.group(1)}"[:64] if m else head[:64]


def export_events(xplane: str, out_json: str, per_line: int = 400) -> None:
    """Write a small recording of a capture: every host span, and the first
    `per_line` events of each device line that fall in the first segment."""
    events = load_xplane(xplane)
    spans = [e for e in events if e.plane == HOST_PLANE]
    first = min((e for e in spans if e.name == SEGMENT_SPAN), key=lambda e: e.start_ns)
    keep: List[Event] = list(spans)
    count: Dict[Tuple[str, str], int] = {}
    for e in sorted(events, key=lambda e: e.start_ns):
        if e.plane == HOST_PLANE or e.end_ns < first.start_ns:
            continue
        key = (e.plane, e.line)
        if count.get(key, 0) < per_line:
            count[key] = count.get(key, 0) + 1
            keep.append(e)
    with open(out_json, "w", encoding="utf-8") as f:
        json.dump({"events": [list(e) for e in keep]}, f)


def load_recorded(path: str) -> List[Event]:
    with open(path, encoding="utf-8") as f:
        return [Event(*row) for row in json.load(f)["events"]]


if __name__ == "__main__":
    # python3 benchmark/trace.py describe <trace_dir> | export <trace_dir> <out.json>
    cmd, trace_dir = sys.argv[1], sys.argv[2]
    if cmd == "describe":
        json.dump(describe(newest_xplane(trace_dir)), sys.stdout, indent=1)
    elif cmd == "export":
        export_events(newest_xplane(trace_dir), sys.argv[3])
    else:
        sys.exit(f"unknown command {cmd!r}")
