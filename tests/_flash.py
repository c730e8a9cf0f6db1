"""What the flash kernels' test files share (``test_flash.py``: the kernels
against the dense form, the strips inside a grid step, values of a width of
their own; ``test_flash_layouts.py``: grouped k/v heads and two heads a step;
``test_flash_backward.py``: the one backward kernel, a trunk's traces, the
band kernels): the kernels through the Pallas interpreter, seeded operands,
the strip height scaled down, the kernels' caches emptied."""

import functools

import jax.numpy as jnp
import numpy as np

from relayrl_tpu.ops import flash

# ``interpret=True`` is never a default (ops/flash.py): a test asks for it
flash_attention = functools.partial(flash.flash_attention, interpret=True)


def _qkv(B=2, T=64, H=2, D=16, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    return mk(), mk(), mk()


def _sub_tile(monkeypatch, rows):
    """Scale the strip height down through the kernels' own derivation
    (the production height, 256, takes a 512-step block to engage)."""
    monkeypatch.setattr(flash, "_SUB_TILE", rows)


def _clear_kernel_caches():
    for cache in (flash._make_flash, flash._build_fwd, flash._build_bwd,
                  flash._shared):
        cache.cache_clear()


def _grad_loss(fn):
    return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v).astype(jnp.float32)))


def _grouped_qkv(B, T, H, h_kv, D=8, seed=5):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((B, T, h_kv, D)), jnp.float32)
            for _ in range(2))
    return q, k, v
