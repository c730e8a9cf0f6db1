"""Device time per update of the sparse dispatch round the grouped matmuls:
the sort of the token-slots by expert and the row gathers that carry tokens
to their experts and results back, forward and backward — XLA operations
found by opcode (``benchmark/moe_trace.py``)."""

from benchmark import moe_trace


def read(run):
    return moe_trace.ms_per_update(run, moe_trace.is_dispatch)
