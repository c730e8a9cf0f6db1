"""Mean time a decoded item waited in the server's ``_decoded`` queue for the
learner thread: the ``queued_us`` argument of ``rl:learner.item``. The number
that grows first when the learner saturates."""

from benchmark import program_trace


def read(run):
    us = program_trace.mean_arg(run, "rl:learner.item", "queued_us")
    return None if us is None else us / 1e3
