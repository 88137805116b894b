"""scale * counters[num] / counters[den]: an exact count over an exact count."""


def read(facts, args):
    c = facts["counters"]
    num, den = c.get(args["num"]), c.get(args["den"])
    if num is None or not den:
        return None
    return float(args.get("scale", 1.0)) * num / den
