"""`XLA Modules` events (executed programs) that start inside a
`raft.run_reads` span, mean per call of the window: how many programs one
entry call dispatches."""

from .. import program_trace as pt


def names(args):
    return {"spans": [pt.RUN_SPAN]}


def read(facts, args):
    cap = facts["capture"]
    calls = pt.spans_named(cap, pt.RUN_SPAN)
    planes = pt.planes(cap)
    if not calls or not planes:
        return None
    starts = sorted(m.start_ns for m in cap.modules if m.plane == planes[0])
    inside = sum(
        1 for t in starts for c in calls if c.start_ns <= t < c.end_ns
    )
    return inside / len(calls)
