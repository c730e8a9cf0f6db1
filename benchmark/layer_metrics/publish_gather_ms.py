"""Publisher-thread time per landed publish in the blocking D2H gather of the snapshot:
the program's ``rl:publish.gather`` span over its ``rl:publish`` spans."""

from benchmark import program_trace


def read(run):
    return program_trace.per_count_ms(run, "rl:publish.gather", "rl:publish")
