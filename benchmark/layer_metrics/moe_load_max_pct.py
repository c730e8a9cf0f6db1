"""The fullest expert's share of an update's token-slots, in percent: the
program's own counter (``moe_load_max`` of the update's metrics, from the
group sizes the dispatch computes), as the argument the learner writes on
its ``rl:dispatch.fence`` spans while a profiler runs; mean over the
updates fenced in the traced window. 100 / E (1.5625 for 64 experts) at
even load, 100 / k when the router has collapsed."""

from benchmark import program_trace


def read(run):
    share = program_trace.mean_arg(run, "rl:dispatch.fence", "moe_load_max")
    return None if share is None else 100.0 * share
