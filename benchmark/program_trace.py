"""What the PROGRAM wrote into the run's capture: its host spans with their
counts, and its names on the device ops.

This is the one read of a run's capture: `run.py` calls `read_xplane` on
the `.xplane.pb` it traced and hands every reader `facts_of(capture)` — the
reduction of `trace.py` (busy, self time per op, idle gaps) made from these
events, plus the capture itself under `facts["capture"]`, which the readers
of what the program names (`reducers/scope_share.py`, `idle_in_span.py`,
`modules_per_segment.py`, `span_counter.py`) work on.  `load()` is for the
command line at the foot of this file only.

Where a name lands in the chip's trace (TPU v5 lite, jax 0.9.0, looked at by
hand in PR 26 with `describe`):

- a `raft_tpu.profiling.span` is an event of a host-thread line of the
  `/host:CPU` plane, its counts the EVENT's stats (`call`, `rounds`, ...).
- an `XLA Ops` event's name is the bare HLO instruction text and its own
  stats are only `device_offset_ps`, `device_duration_ps` and a time scale:
  no stat of the event carries a scope.  The scope is in the stats of the
  event's METADATA (`XEventMetadata.stats`, one per HLO instruction of a
  program): `tf_op` holds the instruction's `op_name` — the jax name stack,
  e.g. `jit(block_run)/cond/branch_1_fun/runner.fused_arm/.../quorum_commit/
  jit(take_along_axis)/gather:` — beside `hlo_category`, `program_id`,
  `source`, `flops`, `bytes_accessed`.  `jax.profiler.ProfileData` exposes
  event stats only, so the capture is read as protobuf wire format here
  (`_fields`; the schema is tsl/profiler/protobuf/xplane.proto, the five
  messages XSpace, XPlane, XLine, XEvent, XStat and the two metadata maps).
- a fusion is ONE instruction; its `tf_op` is that of its root.  So a fusion
  belongs to the scope of its root, and an op-level split inside a fusion
  cannot be had from a trace.
- a `pl.pallas_call(name="raft_steady_damped")` shows as a `custom-call`
  instruction `%raft_steady_damped.3` whose `tf_op` ends
  `.../raft_steady_damped/pallas_call:` (`has_kernel`); the rest of the HLO
  text says only `custom_call_target="tpu_custom_call"`.
- instructions the compiler adds itself (`copy`, `copy-start/-done`, some
  `fusion`s of layout changes) have NO `tf_op`, and neither have the
  `while` / `conditional` containers: a scope's share is the self time of
  the ops that carry it and leaves such copies out.
- the `/host:metadata` plane holds each program's whole `Hlo Proto`; it is
  not needed while `tf_op` is there, and is what to join on (instruction
  name -> `metadata.op_name`) should a later jax drop the stat.

Times: an event starts at `line.timestamp_ns + offset_ps // 1000` and lasts
`duration_ps // 1000` ns, whole numbers — exactly what `ProfileData` reports
as `start_ns` and `duration_ns` — so the window taken from the
`bench.segment` spans here is `trace.reduce_events`' window to the bit.

A program that draws no `raft.` span (any commit before PR 26) gives empty
lists, and every reader returns None.
"""

from __future__ import annotations

import json
import os
import struct
import sys
from typing import Dict, List, NamedTuple, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
if os.path.dirname(HERE) not in sys.path:
    sys.path.insert(0, os.path.dirname(HERE))

from benchmark import trace  # noqa: E402

TRACE_DIR = os.path.join(HERE, ".trace")  # == run.TRACE_DIR
PROGRAM_PREFIX = "raft."
MODULES_LINE = "XLA Modules"
RUN_SPAN = "raft.run_reads"
SCOPE_STAT = "tf_op"  # the metadata stat that carries the jax name stack


class Span(NamedTuple):
    """A host span of the program or the benchmark, with its counts."""

    name: str
    start_ns: float
    dur_ns: float
    stats: Dict[str, object]

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


class Op(NamedTuple):
    """One `XLA Ops` or `XLA Modules` event of a device plane; `path` is
    the op's name stack (`tf_op`), "" where the instruction has none."""

    plane: str
    line: str
    name: str
    path: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


class Capture(NamedTuple):
    spans: List[Span]  # `raft.` and `bench.` host spans, by start
    ops: List[Op]  # XLA Ops events of every device plane
    modules: List[Op]  # XLA Modules events of every device plane


# --- protobuf wire format ------------------------------------------------------


def _fields(buf: bytes, pos: int, end: int):
    """(field number, wire type, value) of the message in buf[pos:end]:
    an int for a varint or a fixed field, (start, end) offsets into `buf`
    for a length-delimited one."""
    while pos < end:
        key = shift = 0
        while True:
            b = buf[pos]
            pos += 1
            key |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        wire = key & 7
        if wire == 0:
            value = shift = 0
            while True:
                b = buf[pos]
                pos += 1
                value |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
        elif wire == 2:
            size = shift = 0
            while True:
                b = buf[pos]
                pos += 1
                size |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            value = (pos, pos + size)
            pos += size
        elif wire == 1:
            value = int.from_bytes(buf[pos:pos + 8], "little")
            pos += 8
        elif wire == 5:
            value = int.from_bytes(buf[pos:pos + 4], "little")
            pos += 4
        else:
            raise trace.TraceError(f"wire type {wire} in the capture")
        yield key >> 3, wire, value


def _text(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _stat(buf: bytes, span: Tuple[int, int], stat_names: Dict[int, str]):
    """(name, value) of one XStat; a `ref_value` resolves to the name it
    points at (that is how strings are interned)."""
    name, value = "", None
    for no, wire, v in _fields(buf, *span):
        if no == 1:
            name = stat_names.get(v, str(v))
        elif no == 2:
            value = struct.unpack("<d", v.to_bytes(8, "little"))[0]
        elif no == 3:
            value = v
        elif no == 4:
            value = _signed(v)
        elif no == 5:
            value = _text(buf, v)
        elif no == 6:
            value = f"<{v[1] - v[0]} bytes>"
        elif no == 7:
            value = stat_names.get(v, "")
    return name, value


def _map_entry(buf: bytes, span: Tuple[int, int]):
    key, value = 0, None
    for no, _wire, v in _fields(buf, *span):
        if no == 1:
            key = v
        elif no == 2:
            value = v
    return key, value


def _plane(buf: bytes, span: Tuple[int, int]):
    """(name, line spans, {metadata id: (name, {stat: value})}, stat names)
    of one XPlane."""
    name, lines, meta_spans, stat_names = "", [], [], {}
    for no, _wire, v in _fields(buf, *span):
        if no == 2:
            name = _text(buf, v)
        elif no == 3:
            lines.append(v)
        elif no == 4:
            meta_spans.append(v)
        elif no == 5:
            _key, md = _map_entry(buf, v)
            sid, sname = 0, ""
            for n2, _w2, v2 in _fields(buf, *md):
                if n2 == 1:
                    sid = v2
                elif n2 == 2:
                    sname = _text(buf, v2)
            stat_names[sid] = sname
    metadata = {}
    for entry in meta_spans:
        key, md = _map_entry(buf, entry)
        mname, stats = "", {}
        for n2, _w2, v2 in _fields(buf, *md):
            if n2 == 2:
                mname = _text(buf, v2)
            elif n2 == 5:
                k, val = _stat(buf, v2, stat_names)
                stats[k] = val
        metadata[key] = (mname, stats)
    return name, lines, metadata, stat_names


def _line(buf: bytes, span: Tuple[int, int]):
    """(name, timestamp_ns, event spans) of one XLine."""
    name, stamp, events = "", 0, []
    for no, _wire, v in _fields(buf, *span):
        if no == 2:
            name = _text(buf, v)
        elif no == 3:
            stamp = v
        elif no == 4:
            events.append(v)
    return name, stamp, events


def _event(buf: bytes, span: Tuple[int, int]):
    """(metadata id, offset_ps, duration_ps, stat spans) of one XEvent."""
    mid = off = dur = 0
    stats = []
    for no, _wire, v in _fields(buf, *span):
        if no == 1:
            mid = v
        elif no == 2:
            off = v
        elif no == 3:
            dur = v
        elif no == 4:
            stats.append(v)
    return mid, off, dur, stats


# --- the capture ---------------------------------------------------------------


def read_xplane(path: str) -> Capture:
    """The program's and the benchmark's host spans with their stats, and
    the `XLA Ops` / `XLA Modules` events of every device plane with their
    name stacks."""
    with open(path, "rb") as f:
        buf = f.read()
    spans: List[Span] = []
    ops: List[Op] = []
    modules: List[Op] = []
    for no, _wire, v in _fields(buf, 0, len(buf)):
        if no != 1:
            continue
        pname, lines, metadata, stat_names = _plane(buf, v)
        device = bool(trace.DEVICE_PLANE.match(pname))
        if not device and pname != trace.HOST_PLANE:
            continue
        for lspan in lines:
            lname, stamp, events = _line(buf, lspan)
            if device and lname not in (trace.OPS_LINE, MODULES_LINE):
                continue
            for espan in events:
                mid, off, dur, stat_spans = _event(buf, espan)
                name, mstats = metadata.get(mid, ("", {}))
                # ProfileData's own arithmetic: whole ns, truncated.
                start, length = float(stamp + off // 1000), float(dur // 1000)
                if device:
                    op = Op(pname, lname, name, str(mstats.get(SCOPE_STAT, "")),
                            start, length)
                    (ops if lname == trace.OPS_LINE else modules).append(op)
                elif name.startswith((PROGRAM_PREFIX, trace.SPAN_PREFIX)):
                    stats = dict(_stat(buf, s, stat_names) for s in stat_spans)
                    spans.append(Span(name, start, length, stats))
    spans.sort(key=lambda s: (s.start_ns, -s.dur_ns))
    return Capture(spans, ops, modules)


def load(trace_dir: str = TRACE_DIR) -> Capture:
    """The newest capture under `trace_dir` (by hand: the foot of this file)."""
    return read_xplane(trace.newest_xplane(trace_dir))


# --- what the reducers share ---------------------------------------------------


def window(cap: Capture) -> Tuple[float, float]:
    """(lo, hi) ns: first `bench.segment` start to last end, as
    `trace.reduce_events` takes it."""
    segs = [s for s in cap.spans if s.name == trace.SEGMENT_SPAN]
    if not segs:
        raise trace.TraceError(f"no {trace.SEGMENT_SPAN!r} host span in the trace")
    return min(s.start_ns for s in segs), max(s.end_ns for s in segs)


def spans_named(cap: Capture, name: str) -> List[Span]:
    """The spans `name` that lie inside the window."""
    lo, hi = window(cap)
    return [s for s in cap.spans
            if s.name == name and s.start_ns >= lo and s.end_ns <= hi]


def planes(cap: Capture) -> List[str]:
    return sorted({op.plane for op in cap.ops})


def op_intervals(cap: Capture, plane: str) -> List[Tuple[float, float]]:
    return [(op.start_ns, op.end_ns) for op in cap.ops if op.plane == plane]


def has_scope(path: str, scope: str) -> bool:
    """Is `scope` one whole component of the name stack `path`?"""
    return scope in path.rstrip(":").split("/")


def has_kernel(path: str, kernel: str) -> bool:
    """Is the op the `pl.pallas_call` named `kernel`?  Its name stack ends
    `.../<kernel>/pallas_call`."""
    parts = path.rstrip(":").split("/")
    return len(parts) >= 2 and parts[-1] == "pallas_call" and parts[-2] == kernel


def path_events(cap: Capture, plane: str) -> List[trace.Event]:
    """One plane's `XLA Ops` events keyed by NAME STACK, for
    `trace.self_seconds`."""
    return [trace.Event(op.plane, op.line, op.path, op.start_ns, op.dur_ns)
            for op in cap.ops if op.plane == plane]


def facts_of(cap: Capture) -> dict:
    """The trace part of the `facts` a reader gets: `trace.py`'s reduction
    of this capture's events (idle gaps named after the program's spans,
    which are among them) and the capture itself."""
    events = [trace.Event(o.plane, o.line, o.name, o.start_ns, o.dur_ns)
              for o in cap.ops]
    events += [trace.Event(trace.HOST_PLANE, "", s.name, s.start_ns, s.dur_ns)
               for s in cap.spans]
    return {"trace": trace.reduce_events(events)._asdict(), "capture": cap}


def self_seconds_where(cap: Capture, keep) -> Tuple[float, int]:
    """(self seconds, events) over the window of the `XLA Ops` events whose
    name stack satisfies `keep`, mean over the device planes — self time as
    `trace.self_seconds` defines it, so a `while` or a `conditional` under a
    scope does not swallow the named ops nested in it."""
    lo, hi = window(cap)
    names = planes(cap)
    seconds, calls = 0.0, 0
    for plane in names:
        for path, (sec, n) in trace.self_seconds(path_events(cap, plane), lo, hi).items():
            if keep(path):
                seconds += sec
                calls += n
    return (seconds / len(names), calls) if names else (0.0, 0)


def idle_seconds_in(cap: Capture, spans: List[Span]) -> float:
    """Device-idle seconds (gaps of the op union, first device plane as in
    `trace.reduce_events`) while the host was inside `spans`."""
    names = planes(cap)
    if not names:
        return 0.0
    intervals = op_intervals(cap, names[0])
    return sum(
        (e - s) / 1e9
        for sp in spans
        for s, e in trace.gaps(intervals, sp.start_ns, sp.end_ns)
    )


# --- by hand: describe, export, metrics ---------------------------------------


def describe(cap: Capture, top: int = 40) -> dict:
    """What to look at before trusting a reader on a new device or jax: the
    program's spans with their stats, and the device's self time by the
    innermost two components of the name stack."""
    lo, hi = window(cap)
    by_tail: Dict[str, float] = {}
    for plane in planes(cap):
        for path, (sec, _n) in trace.self_seconds(path_events(cap, plane), lo, hi).items():
            tail = "/".join(path.rstrip(":").split("/")[-3:])
            by_tail[tail] = by_tail.get(tail, 0.0) + sec
    return {
        "window_s": (hi - lo) / 1e9,
        "spans": [[s.name, s.start_ns - lo, s.dur_ns, s.stats]
                  for s in cap.spans[:top]],
        "modules": len(cap.modules),
        "ops": len(cap.ops),
        "self_seconds_by_name_stack_tail": sorted(
            by_tail.items(), key=lambda kv: -kv[1])[:top],
    }


def export(cap: Capture, out_json: str, per_line: int = 400,
           name_chars: int = 96) -> None:
    """A small recording WITH stats and name stacks (`trace.export_events`
    drops both): every host span, and the first `per_line` events of each
    device line from the first segment's start on, their HLO text cut to
    `name_chars` (a custom-call's and the name stack are kept whole)."""
    lo, _hi = window(cap)
    count: Dict[Tuple[str, str], int] = {}
    keep: List[Op] = []
    for op in sorted(cap.ops + cap.modules, key=lambda o: o.start_ns):
        key = (op.plane, op.line)
        if op.end_ns >= lo and count.get(key, 0) < per_line:
            count[key] = count.get(key, 0) + 1
            whole = " custom-call(" in op.name
            keep.append(op if whole else op._replace(name=op.name[:name_chars]))
    strings: Dict[str, int] = {}

    def ref(text: str) -> int:
        return strings.setdefault(text, len(strings))

    rows = [[ref(o.plane), ref(o.line), ref(o.name), ref(o.path),
             o.start_ns, o.dur_ns] for o in keep]
    with open(out_json, "w", encoding="utf-8") as f:
        json.dump({"spans": [list(s) for s in cap.spans],
                   "strings": list(strings), "ops": rows},
                  f, separators=(",", ":"))


def load_recorded(path: str) -> Capture:
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    text = doc["strings"]
    events = [Op(text[a], text[b], text[c], text[d], start, dur)
              for a, b, c, d, start, dur in doc["ops"]]
    return Capture(
        [Span(*row) for row in doc["spans"]],
        [o for o in events if o.line == trace.OPS_LINE],
        [o for o in events if o.line == MODULES_LINE],
    )


def metrics(cap: Capture, names: Optional[List[str]] = None) -> Dict[str, Optional[float]]:
    """Every metric file whose reducer reads names of the program (it has a
    `names` function), computed on `cap` with the trace facts `run.py` would
    hand it (None: nothing to read)."""
    from benchmark import reducers

    facts = facts_of(cap)
    out = {}
    for fname in sorted(os.listdir(os.path.join(HERE, "metrics"))):
        with open(os.path.join(HERE, "metrics", fname), encoding="utf-8") as f:
            spec = json.load(f)
        name = fname[:-len(".json")]
        reducer = reducers.load(spec["reducer"])
        if hasattr(reducer, "names") and (not names or name in names):
            out[name] = reducer.read(facts, spec["args"])
    return out


def op_label(name: str, path: str, known) -> str:
    """What the breakdown calls a device op: where its name stack `path`
    passes a scope or kernel the program named (`known`), that name, the
    stack's last component, the instruction and its result type —
    `op_gather/.../gather fusion.514 pred[500000]`; else `trace.short_name`,
    the label of a program without names."""
    parts = [c for c in path.rstrip(":").split("/") if c]
    at = max((i for i, c in enumerate(parts) if c in known), default=None)
    if at is None:
        return trace.short_name(name)
    tail = parts[at:]
    where = tail[0] if len(tail) == 1 else tail[0] + ("/" if len(tail) == 2 else "/.../") + tail[-1]
    head, _sep, rest = name.partition(" = ")
    result = rest.split("{")[0].split(" ")[0][:24]
    if result.startswith("("):  # a tuple: its first element stands for it
        result = result.rstrip(",") + ",..)"
    return " ".join(x for x in (where, head.lstrip("%")[:32], result) if x)


def top_ops(facts: dict, known, n: int = 10) -> List[Tuple[str, float]]:
    """The `n` device ops with most self time, each under its `op_label`
    (an HLO instruction has one name stack, so the instruction text is the
    key)."""
    paths = {op.name: op.path for op in facts["capture"].ops}
    rows = sorted(facts["trace"]["op_seconds"].items(), key=lambda kv: -kv[1][0])
    return [(op_label(name, paths.get(name, ""), known), sec)
            for name, (sec, _calls) in rows[:n]]


if __name__ == "__main__":
    # python3 benchmark/program_trace.py describe|metrics [trace_dir]
    #                                    export <out.json> [trace_dir [per_line]]
    cmd = sys.argv[1]
    rest = sys.argv[2:]
    if cmd == "export":
        export(load(*rest[1:2]), rest[0], *(int(n) for n in rest[2:]))
    elif cmd == "describe":
        json.dump(describe(load(*rest)), sys.stdout, indent=1)
    elif cmd == "metrics":
        json.dump(metrics(load(*rest)), sys.stdout, indent=1)
    else:
        sys.exit(f"unknown command {cmd!r}")
