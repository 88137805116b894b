"""Device-idle time (gaps of the op union) while the host was inside the
program's span `args["span"]`, mean per `raft.run_reads` call of the window,
in ms: which part of the entry call the device waited for."""

from .. import program_trace as pt


def names(args):
    return {"spans": [pt.RUN_SPAN, args["span"]]}


def read(facts, args):
    cap = facts["capture"]
    calls = pt.spans_named(cap, pt.RUN_SPAN)
    spans = pt.spans_named(cap, args["span"])
    if not calls or not spans or not pt.planes(cap):
        return None
    return 1e3 * pt.idle_seconds_in(cap, spans) / len(calls)
