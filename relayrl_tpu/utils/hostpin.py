"""Process-level CPU pinning for actor hosts, drills, and examples.

Actors are CPU hosts: a process that only steps environments must never
initialize the accelerator backend — the chip belongs to ONE process, the
learner, and a second process that touches it fails or hangs. The pin sets
``JAX_PLATFORMS`` for anything this process spawns and updates the live
config for jax itself, which is valid until the backend initializes — so
call it before any other jax use. This is the single shared
implementation — examples, drills, and multi-process workers all call it
instead of hand-rolling the block.
"""

from __future__ import annotations

import os


def pin_cpu(virtual_devices: int | None = None) -> None:
    """Force this process onto the CPU JAX backend.

    ``virtual_devices`` additionally requests an N-device host platform
    (``--xla_force_host_platform_device_count``) for testing sharded code
    without hardware; it must run before jax creates its backend AND
    before anything latches XLA_FLAGS, so the env mutation happens ahead
    of the jax import below.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    if virtual_devices:
        # Strip any pre-existing count and append ours: trailing flags win,
        # but relying on that is fragile and a stale smaller count from the
        # ambient environment must never shrink the requested mesh.
        import re

        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                       os.environ.get("XLA_FLAGS", ""))
        os.environ["XLA_FLAGS"] = (
            flags.strip() +
            f" --xla_force_host_platform_device_count={virtual_devices}"
        ).strip()
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        # Backend already initialized: the env vars were either respected
        # (fine) or it's too late to change platform — nothing to do.
        pass
