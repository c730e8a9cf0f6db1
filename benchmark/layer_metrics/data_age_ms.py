"""Mean age of the data an update consumes: over the traced updates, the
``data_age_us`` argument of ``host:dispatch`` — the start of the dispatch less
the born stamp (first step of the unroll, the actor's CLOCK_MONOTONIC) of each
trajectory in its batch, averaged by the server. One host: the two clocks are
one."""

from benchmark import program_trace


def read(run):
    us = program_trace.mean_arg(run, "host:dispatch", "data_age_us")
    return None if us is None else us / 1e3
