"""Host time per update to copy its padded trajectories into the staging
slab: the program's ``rl:batch.stack`` span (``EpochBuffer.drain``)."""

from benchmark import program_trace


def read(run):
    return program_trace.per_count_ms(run, "rl:batch.stack",
                                      "rl:batch.stack")
