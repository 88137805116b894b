"""scale * sum(num) / sum(product of den) over the window's
`raft.run_reads.report` spans — `span_counter`'s ratio, for the read
statistics EVERY report carries (`raft_tpu.multiraft.workload.READ_STAT_NAMES`:
they are as old as `run_reads`, and `run.summed` hands `counter_ratio` only
some of them).  It states no `names`: there is no older or newer program
that lacks these counts and would be excused for it, so a None stops the
run (README, the `None` rule)."""

from . import span_counter

SPAN = "raft.run_reads.report"


def read(facts, args):
    return span_counter.read(facts, {**args, "span": SPAN})
