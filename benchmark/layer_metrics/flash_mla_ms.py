"""Device time per update of the latent-attention layers' flash kernels,
forward and backward: the operations whose name carries a flash kernel's
name and the suffix the program gives a call whose values have a width of
their own (``relayrl_flash_fwd_mla`` / ``relayrl_flash_bwd_mla``:
``ops/flash.py``), summed over the update modules that lie wholly inside the
traced window, per such update. ``flash_fwd_ms`` matches by the shorter name
and counts the forward call too. None for a program without such
operations."""

from benchmark import moe_trace

PREFIX, SUFFIX = "relayrl_flash_", "_mla"


def is_latent_kernel(key: str) -> bool:
    name = key.split("/")[0]
    return name.startswith(PREFIX) and name.endswith(SUFFIX)


def read(run):
    return moe_trace.ms_per_update(run, is_latent_kernel)
