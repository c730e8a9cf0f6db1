"""Receive-thread time per update inside ``rl:ingest.recv``: one frame's
receive and its envelope's unpack (the poll before it is the thread's idle),
summed over the traced window, per ``host:dispatch`` inside it."""

from benchmark import program_trace


def read(run):
    return program_trace.per_count_ms(run, "rl:ingest.recv", "host:dispatch")
