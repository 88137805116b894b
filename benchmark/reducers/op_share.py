"""100 * (self time of the device ops whose name matches `pattern`) / busy."""

from . import matching


def read(facts, args):
    t = facts["trace"]
    seconds, calls = matching(t["op_seconds"], args["pattern"])
    if not calls:
        return None
    return 100.0 * seconds / t["busy_s"]
