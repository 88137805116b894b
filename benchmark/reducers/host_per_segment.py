"""Mean over the traced segments of (the benchmark's span around the entry
call) - (device busy inside that span), in ms: what the host adds to every
segment while the device waits."""


def read(facts, args):
    segs = facts["trace"]["segments"]
    if not segs:
        return None
    return 1e3 * sum(s["span_s"] - s["busy_s"] for s in segs) / len(segs)
