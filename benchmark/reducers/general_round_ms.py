"""(device busy - time in the fused kernels) / rounds that ran on the
general path, in ms.  General rounds = (total - fused group-rounds) / G;
where the run has no fused accounting every round is general."""

from . import matching


def read(facts, args):
    t, c = facts["trace"], facts["counters"]
    kernel_s, _ = matching(t["op_seconds"], args["pattern"])
    group_rounds = c["group_rounds"] - c.get("fused_rounds", 0)
    rounds = group_rounds / facts["shape"]["n_groups"]
    if rounds <= 0:
        return None
    return 1e3 * (t["busy_s"] - kernel_s) / rounds
