"""Device time per update of the backward dk/dv flash kernel: the operations that
carry the name the program gives it (``relayrl_flash_dkv``: ``pallas_call``
name and ``named_scope``), summed over the update modules that lie wholly
inside the traced window, per such update."""

from benchmark import program_trace


def read(run):
    return program_trace.kernel_ms_per_update(run, "relayrl_flash_dkv")
