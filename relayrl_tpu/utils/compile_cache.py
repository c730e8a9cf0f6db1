"""Where the persistent XLA compile cache lives.

An entry point that owns a device (``chip_smoke.py``, ``benchmark/run.py``,
the learner process of the examples) calls :func:`resolve_compile_cache` once,
before its first compile. The library never calls it at import, and the
tests keep the cache off (tests/conftest.py says why).

The directory is part of the cache key, so it must not move between runs:

* ``JAX_COMPILATION_CACHE_DIR`` set — whoever launched the process placed
  the cache; JAX reads the variable itself and this code sets no other
  directory.
* unset — one fixed, git-ignored directory inside the checkout. Never a
  temp dir, a pid or a timestamp: a cache that moves never hits.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def resolve_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory."""
    import jax

    cache_dir = os.environ.get(ENV_VAR)
    if not cache_dir:
        cache_dir = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # JAX skips programs that compiled in under a second; a warm start is
    # only "compiled nothing" when those are kept too.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def announce_learner_device(tag: str) -> None:
    """For the learner process of an entry point: it runs on the backend
    JAX finds — no switch, no fallback — so say which one that is, and when
    it is an accelerator keep its compiles. (CPU runs, the tests among
    them, stay off the persistent cache.)"""
    import jax

    devices = jax.devices()
    line = (f"[{tag}] learner on {devices[0].platform} "
            f"({devices[0].device_kind} x{len(devices)})")
    if devices[0].platform != "cpu":
        line += f", compile cache {resolve_compile_cache()}"
    print(line, flush=True)
