"""Device time per update of the expert layer's grouped matmuls, forward
and backward: the Mosaic calls the program names ``relayrl_moe_gmm_fwd`` /
``_dlhs`` / ``_drhs`` (``benchmark/moe_trace.py`` says how they are
found)."""

from benchmark import moe_trace


def read(run):
    return moe_trace.ms_per_update(run, moe_trace.is_gmm)
