"""Exact counts the program closed its span `args["span"]` with, over the
spans of the window:

    {"median": stat}               median over the spans of one stat
    {"num": stat, "den": [stats]}  scale * sum(num) / sum(product of den)

A span that lacks a stat named here (an older program) reads as nothing."""

import statistics

from .. import program_trace as pt


def _stats(args):
    return [args["median"]] if "median" in args else [args["num"], *args["den"]]


def names(args):
    return {"spans": [args["span"]], "counts": _stats(args)}


def read(facts, args):
    spans = pt.spans_named(facts["capture"], args["span"])
    wanted = _stats(args)
    if not spans or any(k not in s.stats for s in spans for k in wanted):
        return None
    if "median" in args:
        return float(statistics.median(s.stats[args["median"]] for s in spans))
    num = den = 0
    for s in spans:
        num += s.stats[args["num"]]
        product = 1
        for k in args["den"]:
            product *= s.stats[k]
        den += product
    if not den:
        return None
    return float(args.get("scale", 1.0)) * num / den
