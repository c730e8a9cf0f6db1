"""Publisher-thread time per landed publish in the socket broadcast:
the program's ``rl:publish.send`` span over its ``rl:publish`` spans."""

from benchmark import program_trace


def read(run):
    return program_trace.per_count_ms(run, "rl:publish.send", "rl:publish")
