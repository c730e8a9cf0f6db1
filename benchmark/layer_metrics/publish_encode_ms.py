"""Publisher-thread time per landed publish in the wire encode (keyframe or delta):
the program's ``rl:publish.encode`` span over its ``rl:publish`` spans."""

from benchmark import program_trace


def read(run):
    return program_trace.per_count_ms(run, "rl:publish.encode", "rl:publish")
