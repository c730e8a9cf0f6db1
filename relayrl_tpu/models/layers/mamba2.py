"""The operator ``"mamba2"``: the Mamba-2 mixer, ``x + out(norm_g(y *
silu(z)))`` behind the layer's one norm — alone (``layer_types`` entry
``"mamba2"``: Nemotron-H keeps its FFNs in layers of their own) or with the
FFN behind a norm of its own after it (``"mamba"``: Granite 4.0-H's layer).

``[z | xBC | dt] = in(norm(x))``; ``xBC = silu(conv(xBC) + b)``, depthwise
and causal over ``mamba_conv_taps`` taps; ``(x, B, C) = split(xBC)``; ``dt =
softplus(dt + dt_bias)`` and ``A = -exp(A_log)`` in float32; ``y`` the
state-space scan of :mod:`relayrl_tpu.ops.ssd` over ``mamba_heads`` heads of
``mamba_head_dim`` with a state of ``mamba_state`` columns a head and
``mamba_groups`` groups of B and C, in chunks of ``mamba_chunk``; ``norm_g``
an RMSNorm over each group of the gated output. ``heads * head_dim`` wide
inside, not a multiple of ``d_model``.

Its state (:mod:`.recurrent`): the convolution's last ``mamba_conv_taps - 1``
rows of ``xBC`` and the ``[B, H, P, N]`` scan state in float32; prefill rows
past ``n_valid`` get ``dt = 0`` and leave the state as it is."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from relayrl_tpu.models.layers import recurrent
from relayrl_tpu.ops import ssd as ssd_ops
from relayrl_tpu.ops.scopes import MAMBA_CONV_NAME, OP_PROJ

ROW_READOUT = False
CACHE_RESTARTS = "zeroed"   # rows and state have no positions
# the one activation a layer's checkpoint keeps: the scan's output
_SSD_OUT = "relayrl_ssd_out"


def _scan_shape(x, dt, a, b, c, skip, chunk, state):
    key = (int(x.shape[1]), int(x.shape[2]), int(x.shape[3]),
           int(b.shape[3]), x.dtype.name)
    return key, ssd_ops.backend(*x.shape[1:], *b.shape[2:], chunk), (
        f"T={key[0]} heads={key[1]} head_dim={key[2]} state={key[3]} "
        f"groups={b.shape[2]} chunk={chunk} {key[4]}")


# ``Policy.scan_backends``: ``{(T, heads, head_dim, state, dtype):
# "ssd_pallas" | "ssd_xla"}``
KERNELS = (recurrent.kernel("scan", ssd_ops.ssd, _scan_shape),
           recurrent.CONV_KERNEL)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """``softplus^-1`` of step sizes log-uniform over Mamba-2's published
    range (``time_step_min`` 0.001 .. ``time_step_max`` 0.1, floor 1e-4)."""
    lo, hi = jnp.log(0.001), jnp.log(0.1)
    dt = jnp.maximum(jnp.exp(lo + (hi - lo) * jax.random.uniform(key, shape)),
                     1e-4)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _a_log_init(key, shape, dtype=jnp.float32):
    """``log`` of decay rates uniform over Mamba-2's ``A_init_range``
    (1, 16): ``A = -exp(A_log)``."""
    return jnp.log(jax.random.uniform(key, shape, minval=1.0,
                                      maxval=16.0)).astype(dtype)


def _mixer(block, shape):
    Bsz, T, d = shape
    cfg = block.cfg
    H, P = cfg["mamba_heads"], cfg["mamba_head_dim"]
    G, N = cfg["mamba_groups"], cfg["mamba_state"]
    taps, chunk = cfg["mamba_conv_taps"], cfg["mamba_chunk"]
    inner, bc = H * P, G * N
    if H % G:
        raise ValueError(f"mamba_groups {G} does not divide mamba_heads {H}")
    f32 = jnp.float32
    cd = block.compute_dtype
    scan_fn, conv_fn = block.fns["scan"], block.fns["conv"]
    lecun = nn.initializers.lecun_normal()
    weights = (
        block.param("mamba_in", lecun, (d, 2 * inner + 2 * bc + H), f32),
        block.param("mamba_conv_w", lecun, (taps, inner + 2 * bc), f32),
        block.param("mamba_conv_b", nn.initializers.normal(0.02),
                    (inner + 2 * bc,), f32),
        block.param("mamba_dt_bias", _dt_bias_init, (H,), f32),
        block.param("mamba_A_log", _a_log_init, (H,), f32),
        block.param("mamba_D", nn.initializers.ones, (H,), f32),
        block.param("mamba_norm", nn.initializers.ones, (inner,), f32),
        block.param("mamba_out", lecun, (inner, d), f32))
    eps = 1e-6 if block.norm_eps is None else float(block.norm_eps)

    def mix(h, weights, conv_rows, state, n_valid):
        w_in, conv_w, conv_b, dt_bias, a_log, skip, scale, w_out = weights
        with jax.named_scope(OP_PROJ):
            z, xbc, dt = jnp.split(
                checkpoint_name(jnp.dot(h, w_in.astype(cd)),
                                recurrent.MIXER_IN),
                [inner, 2 * inner + 2 * bc], axis=-1)
            dt = jax.nn.softplus(dt.astype(f32) + dt_bias)
            if n_valid is not None:
                dt = jnp.where(jnp.arange(T)[None, :, None] < n_valid, dt,
                               0.0)
            a = -jnp.exp(a_log)
        xbc, padded = recurrent.mixer_conv(xbc, conv_w, conv_b, conv_rows,
                                           MAMBA_CONV_NAME, conv_fn)
        with jax.named_scope(OP_PROJ):
            xs, b_in, c_in = jnp.split(xbc, [inner, inner + bc], axis=-1)
            xs = xs.reshape(Bsz, T, H, P)
            b_in, c_in = (r.reshape(Bsz, T, G, N) for r in (b_in, c_in))
        if state is not None and T == 1:
            y, state = ssd_ops.ssd_step(xs[:, 0], dt[:, 0], a, b_in[:, 0],
                                        c_in[:, 0], skip, state)
            y = y[:, None]
        else:
            y, state = scan_fn(xs, dt, a, b_in, c_in, skip, chunk, state)
        # named (and kept) with the heads side by side in the lanes, as the
        # scan's kernels write it: a [..., H, 64] view between them and the
        # norm is turned T-minor and back, 0.4 GB a layer (PERF.md section
        # 6, PR 40)
        y = checkpoint_name(y.reshape(Bsz, T, inner), _SSD_OUT)
        with jax.named_scope(OP_PROJ):
            # the gate BEFORE the norm, the norm over each group's columns:
            # a group's lane-aligned slice at a time, no [..., G, inner / G]
            # view of the rows (that view splits the lanes, and XLA copies
            # 0.27 GB in float32 to make it, three times a layer)
            g = y.astype(f32) * nn.silu(z.astype(f32))
            g = jnp.concatenate(
                [cols * jax.lax.rsqrt(
                    jnp.mean(jnp.square(cols), -1, keepdims=True) + eps)
                 for cols in jnp.split(g, G, axis=-1)], axis=-1)
            y = (g * scale).astype(cd)
            return jnp.dot(y, w_out.astype(cd)), padded, state

    return weights, mix, taps - 1


# ... and what a block checkpoint round the whole layer keeps with it
KEPT = (_SSD_OUT,)
apply = recurrent.mixer_apply(_mixer, kept=KEPT)


def init_cache(cfg, d_model, batch, length, dtype, window):
    heads, width, state = (cfg["mamba_heads"], cfg["mamba_head_dim"],
                           cfg["mamba_state"])
    xbc = heads * width + 2 * cfg["mamba_groups"] * state
    return (jnp.zeros((batch, cfg["mamba_conv_taps"] - 1, xbc), dtype),
            jnp.zeros((batch, heads, width, state), jnp.float32))
