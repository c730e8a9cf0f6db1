"""Device time per update of the flash kernels' WINDOWED calls, forward
and backward: the operations whose name carries a flash kernel's name and
the suffix the program gives a windowed call (``relayrl_flash_fwd_win`` /
``_dq_win`` / ``_dkv_win``: ``ops/flash.py``), summed over the update
modules that lie wholly inside the traced window, per such update.
``flash_fwd_ms`` / ``_dq_ms`` / ``_dkv_ms`` match by the shorter name and
count these calls too; this reader tells the band's calls apart. None for
a program without such operations."""

from benchmark import moe_trace

PREFIX, SUFFIX = "relayrl_flash_", "_win"


def is_window_kernel(key: str) -> bool:
    name = key.split("/")[0]
    return name.startswith(PREFIX) and name.endswith(SUFFIX)


def read(run):
    return moe_trace.ms_per_update(run, is_window_kernel)
