"""The operator ``"conv"``: LFM2's gated short convolution between two
projections, ``x + conv_out(C * conv(B * u))`` with ``(B, C, u) =
split3(conv_in(norm(x)))``, then the FFN. Depthwise, causal, ``conv_taps``
taps and no bias (``conv_w [taps, d]``); no positions: the operator is causal
by construction and sees ``conv_taps - 1`` rows back.

Its state is the last ``conv_taps - 1`` rows of ``B * u``, ``[B, conv_taps -
1, d]``: a cached call continues from them, and a prefill leaves the rows
before ``n_valid``, the window's count of real rows (the state has no
positions that later steps could overwrite: ``CACHE_RESTARTS = "zeroed"``).
The readout row of a window
needs its own and the ``conv_taps - 1`` rows before it and nothing else."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn

from relayrl_tpu.models.layers.block import (
    block_dense,
    block_ffn,
    block_norm,
    block_residual,
)
from relayrl_tpu.ops.scopes import OP_PROJ, SHORT_CONV_NAME

KERNELS = ()
ROW_READOUT = True
CACHE_RESTARTS = "zeroed"   # the rows have no positions


def _short_conv(bcu, w, state=None):
    """The gated short convolution between its two projections:
    ``(B, C, u) = split3(bcu)``, ``z = B * u``, ``c_t = sum_j w[j] *
    z_{t-(L-1)+j}`` (depthwise, causal, ``L = w.shape[0]`` taps, no bias),
    returns ``(C * c, z_padded)``. ``state [batch, L-1, d]`` holds the
    ``z`` rows before this call's first (zeros at a sequence's start, which
    ``None`` means). Plain XLA under one named scope: L shifted
    multiply-adds fused with the two gate products, accumulated in float32.
    ``z_padded = concat(state, z)`` is what a cache takes its next state
    from."""
    with jax.named_scope(SHORT_CONV_NAME):
        taps = w.shape[0]
        T = bcu.shape[1]
        b_gate, c_gate, u = jnp.split(bcu, 3, axis=-1)
        z = b_gate * u
        if state is None:
            zp = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
        else:
            zp = jnp.concatenate([state.astype(z.dtype), z], axis=1)
        c = sum(w[j].astype(jnp.float32) * zp[:, j:j + T].astype(jnp.float32)
                for j in range(taps))
        return c_gate * c.astype(bcu.dtype), zp


def apply(block, x, cache, t, readout_idx, n_valid):
    B, T, d = x.shape
    taps = block.cfg["conv_taps"]
    back = taps - 1
    w = block.param("conv_w", nn.initializers.lecun_normal(), (taps, d),
                    jnp.float32)

    def in_proj(rows):
        with jax.named_scope(OP_PROJ):
            h = block_norm(block, "ln_attn")(rows)
            return block_dense(block, 3 * d, "conv_in")(
                h.astype(block.compute_dtype))

    def out_proj(x, y):
        with jax.named_scope(OP_PROJ):
            return block_residual(block, x,
                                  block_dense(block, d, "conv_out")(y),
                                  "ln_attn_out")

    if readout_idx is not None:
        # the one row needs its own and the conv_taps - 1 rows before it;
        # rows before the sequence's first have z = 0
        xp = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))
        rows = jax.lax.dynamic_slice_in_dim(xp, readout_idx, back + 1,
                                            axis=1)
        real = (readout_idx - back + jnp.arange(back + 1)) >= 0
        bcu = jnp.where(real[None, :, None], in_proj(rows), 0)
        y = _short_conv(bcu, w)[0][:, back:]
        return block_ffn(block, out_proj(rows[:, back:], y), rows[:, back:])
    y, zp = _short_conv(in_proj(x), w, cache)
    out = block_ffn(block, out_proj(x, y), x)
    if cache is None:
        return out
    # zp row j is z row j - back: the state after n real rows is z rows
    # n - back .. n - 1
    n = T if n_valid is None else n_valid
    state = jax.lax.dynamic_slice_in_dim(zp, n, back, axis=1)
    return out, state.astype(cache.dtype)


def init_cache(cfg, d_model, batch, length, dtype, window):
    return jnp.zeros((batch, cfg["conv_taps"] - 1, d_model), dtype)
