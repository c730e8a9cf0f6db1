"""Receive-thread time per update inside ``rl:ingest.admit``: the server's
``on_trajectory`` whole (tag split, dedup, guardrails, the actor report's
merge, the put into the ingest queue), summed over the traced window, per
``host:dispatch`` inside it."""

from benchmark import program_trace


def read(run):
    return program_trace.per_count_ms(run, "rl:ingest.admit", "host:dispatch")
