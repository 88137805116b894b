"""100 * (self time of the device ops the program NAMED) / busy: the ops
under the `jax.named_scope` `args["scope"]`, or the `pl.pallas_call` whose
`name=` is `args["kernel"]`.  The name comes from the op's name stack
(program_trace.py says which stat carries it), never from a shape or an
instruction number."""

import functools

from .. import program_trace as pt


def names(args):
    return {"kernels": [args["kernel"]]} if "kernel" in args else {"scopes": [args["scope"]]}


def read(facts, args):
    if "kernel" in args:
        keep = functools.partial(pt.has_kernel, kernel=args["kernel"])
    else:
        keep = functools.partial(pt.has_scope, scope=args["scope"])
    seconds, calls = pt.self_seconds_where(facts["capture"], keep)
    if not calls:
        return None
    return 100.0 * seconds / facts["trace"]["busy_s"]
