"""Host time per update inside ``_to_device`` + the jitted call: the program's
``rl:dispatch.enqueue`` span (the part of ``dispatch_ms`` that is the
dispatch itself, without probes and the in-flight window)."""

from benchmark import program_trace


def read(run):
    return program_trace.per_count_ms(run, "rl:dispatch.enqueue",
                                      "rl:dispatch.enqueue")
