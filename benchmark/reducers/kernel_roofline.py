"""A kernel's share of its roofline, held to the BYTES bound:

    100 * (bytes per call * calls / peak HBM bytes/s) / kernel seconds

Bytes per call are those of the call's operand list, inputs and outputs,
each operand counted once, from shapes: `operands` gives how many int32
planes of each family the call streams — `pg` planes are [P, G], `ppg`
[P, P, G], `g` [1, G].  That is the least the call can move (every operand
is read or written whole, once), so the share cannot pass 100 unless the
list is wrong.  Bytes and not operations is the bound used because the
kernels do integer vector work and no int32 vector peak is published for
this chip (peaks.json); the share is therefore a floor on how close the
kernel is to any limit, not a claim that memory is what limits it."""

from . import matching


def bytes_per_call(operands: dict, n_groups: int, n_peers: int) -> int:
    P = n_peers
    words = operands["pg"] * P + operands["ppg"] * P * P + operands["g"]
    return 4 * words * n_groups


def read(facts, args):
    t = facts["trace"]
    seconds, calls = matching(t["op_seconds"], args["pattern"])
    if not calls or seconds <= 0.0:
        return None
    shape = facts["shape"]
    moved = bytes_per_call(args["operands"], shape["n_groups"], shape["n_peers"]) * calls
    return 100.0 * (moved / facts["peaks"]["hbm_bytes_per_s"]) / seconds
