"""Host time per update to pad its trajectories: the program's ``rl:batch.pad``
spans (one a trajectory, in ``EpochBuffer.add_episode``) summed over the
traced window, per batch assembled (``rl:batch.stack``)."""

from benchmark import program_trace


def read(run):
    return program_trace.per_count_ms(run, "rl:batch.pad", "rl:batch.stack")
