"""Median device duration of one jitted update: the events of the module the
program names after its algorithm (``jit_impala_update``) on the device
plane's "XLA Modules" line that lie wholly inside the traced window."""

from benchmark import program_trace


def read(run):
    return program_trace.update_device_ms(run)
