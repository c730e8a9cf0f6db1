"""The operator ``"gdn"``: linear attention by the gated delta rule
(Qwen3-Next's Gated DeltaNet), ``x + out(norm_h(o) * silu(z))`` behind the
layer's norm, then the FFN.

``[q | k | v | z] = in_qkvz(norm(x))``, ``[b | a] = in_ba(norm(x))``; ``[q |
k | v] = silu(conv([q | k | v]))``, ``gdn_conv_taps`` taps and no bias;
``beta = sigmoid(b)`` and ``g = -exp(A_log) softplus(a + dt_bias)`` in
float32, one scalar a value head; ``q = q / |q| / sqrt(K)``, ``k = k / |k|``
a head; ``o`` the delta rule of :mod:`relayrl_tpu.ops.gdn` on a
``[gdn_key_dim, gdn_value_dim]`` matrix state a value head (``gdn_key_heads``
q/k heads under ``gdn_value_heads``), in chunks of ``gdn_chunk``; ``norm_h``
an RMSNorm over each value head's width with a plain weight (whatever
``norm_zero_centred`` says of the block's other norms), BEFORE the gate.

Its state (:mod:`.recurrent`): the convolution's last ``gdn_conv_taps - 1``
rows of ``[q | k | v]`` and the ``[B, H, K, V]`` state in float32; prefill
rows past ``n_valid`` get ``g = 0`` and ``beta = 0`` and leave the state as
it is."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from relayrl_tpu.models.layers import recurrent
from relayrl_tpu.ops import gdn as gdn_ops
from relayrl_tpu.ops.gdn import SOLVE_NAME as _GDN_SOLVE
from relayrl_tpu.ops.scopes import GDN_CONV_NAME, OP_PROJ

ROW_READOUT = False
CACHE_RESTARTS = "zeroed"   # rows and state have no positions
# what a layer's checkpoint keeps: the rule's output and, where the rule
# runs as kernels, the solve's tiles their forward wrote (67 MB a layer) —
# without those the backward would run the rule's forward a second time
_GDN_OUT = "relayrl_gdn_out"


def _rule_shape(q, k, v, g, beta, chunk, state):
    key = (int(v.shape[1]), int(v.shape[2]), int(k.shape[3]),
           int(v.shape[3]), v.dtype.name)
    return key, gdn_ops.backend(key[0], key[1], int(k.shape[2]), key[2],
                                key[3], chunk), (
        f"T={key[0]} heads={key[1]}/{k.shape[2]} key_dim={key[2]} "
        f"value_dim={key[3]} chunk={chunk} {key[4]}")


# ``Policy.gdn_backends``: ``{(T, value heads, key width, value width,
# dtype): "gdn_pallas" | "gdn_xla"}``
KERNELS = (recurrent.kernel("gdn", gdn_ops.gdn, _rule_shape),
           recurrent.CONV_KERNEL)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _normed_heads(a, heads: int, eps: float, mean: bool):
    """``a [..., heads * width]`` (float32) with each head's columns scaled
    by ``rsqrt(sum of their squares + eps)`` (``mean``: of their mean
    square): a head's lane-aligned slice at a time where a head is whole
    lane tiles, as the delta rule's kernels read and write them — no ``[...,
    heads, width]`` view of the rows (that view splits the lanes, and XLA
    copies 0.13 to 0.27 GB to make it, some thirty times a layer: PERF.md
    section 6, PR 43) —, through the view everywhere else. Jitted: a trunk's
    layers share ONE trace and one lowering of the slices (2.4 s of every
    process's start otherwise)."""
    def normed(cols):
        squares = jnp.square(cols)
        size = (jnp.mean if mean else jnp.sum)(squares, -1, keepdims=True)
        return cols * jax.lax.rsqrt(size + eps)

    width = a.shape[-1] // heads
    if width % 128:
        return normed(a.reshape(a.shape[:-1] + (heads, width))).reshape(
            a.shape)
    return jnp.concatenate(
        [normed(cols) for cols in jnp.split(a, heads, axis=-1)], axis=-1)


def _gdn_conv(qkv, w, state, conv_fn):
    """``silu(conv(qkv))`` over q, k and v together, no bias, under the
    scope ``relayrl_gdn_conv``; returns ``(out, qkv_padded)``."""
    return recurrent.mixer_conv(qkv, w, None, state, GDN_CONV_NAME, conv_fn)


def _a_log_init(key, shape, dtype=jnp.float32):
    """``log`` of decay rates uniform over (0, 16), Qwen3-Next's own: ``g =
    -exp(A_log) softplus(a + dt_bias)``."""
    return jnp.log(jax.random.uniform(key, shape, minval=1e-4,
                                      maxval=16.0)).astype(dtype)


def _mixer(block, shape):
    Bsz, T, d = shape
    cfg = block.cfg
    Hk, H = cfg["gdn_key_heads"], cfg["gdn_value_heads"]
    K, V = cfg["gdn_key_dim"], cfg["gdn_value_dim"]
    taps, chunk = cfg["gdn_conv_taps"], cfg["gdn_chunk"]
    kw, vw = Hk * K, H * V
    if H % Hk:
        raise ValueError(f"gdn_key_heads {Hk} does not divide "
                         f"gdn_value_heads {H}")
    f32 = jnp.float32
    cd = block.compute_dtype
    rule_fn, conv_fn = block.fns["gdn"], block.fns["conv"]
    lecun = nn.initializers.lecun_normal()
    weights = (
        block.param("gdn_in_qkvz", lecun, (d, 2 * kw + 2 * vw), f32),
        block.param("gdn_in_ba", lecun, (d, 2 * H), f32),
        block.param("gdn_conv_w", lecun, (taps, 2 * kw + vw), f32),
        block.param("gdn_dt_bias", nn.initializers.ones, (H,), f32),
        block.param("gdn_A_log", _a_log_init, (H,), f32),
        block.param("gdn_norm", nn.initializers.ones, (V,), f32),
        block.param("gdn_out", lecun, (vw, d), f32))
    eps = 1e-6 if block.norm_eps is None else float(block.norm_eps)

    def l2_normed(a, heads):
        # over a head's width, float32 (eps as the source's)
        return _normed_heads(a.astype(f32), heads, 1e-6, False)

    def mix(h, weights, conv_rows, state, n_valid):
        w_qkvz, w_ba, conv_w, dt_bias, a_log, scale, w_out = weights
        with jax.named_scope(OP_PROJ):
            qkv, z = jnp.split(
                checkpoint_name(jnp.dot(h, w_qkvz.astype(cd)),
                                recurrent.MIXER_IN),
                [2 * kw + vw], axis=-1)
            b_in, a_in = jnp.split(
                jnp.dot(h, w_ba.astype(cd), preferred_element_type=f32),
                2, axis=-1)
            beta = jax.nn.sigmoid(b_in)
            g = -jnp.exp(a_log) * jax.nn.softplus(a_in + dt_bias)
            if n_valid is not None:
                real = jnp.arange(T)[None, :, None] < n_valid
                beta, g = jnp.where(real, beta, 0.0), jnp.where(real, g, 0.0)
        qkv, padded = _gdn_conv(qkv, conv_w, conv_rows, conv_fn)
        with jax.named_scope(OP_PROJ):
            q, k, v = jnp.split(qkv, [kw, 2 * kw], axis=-1)
            q = (l2_normed(q, Hk) * K ** -0.5).astype(cd).reshape(
                Bsz, T, Hk, K)
            k = l2_normed(k, Hk).astype(cd).reshape(Bsz, T, Hk, K)
            v = v.reshape(Bsz, T, H, V)
        if T == 1:
            # one row is one step of the rule, from the cache's state or
            # (the row ``init`` traces) from nothing: no chunk to pad to
            if state is None:
                state = jnp.zeros((Bsz, H, K, V), f32)
            o, state = gdn_ops.gdn_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                        beta[:, 0], state)
            o = o[:, None]
        else:
            o, state = rule_fn(q, k, v, g, beta, chunk, state)
        # named (and kept) with the heads side by side in the lanes, as the
        # rule's kernels write it
        o = checkpoint_name(o.reshape(Bsz, T, vw), _GDN_OUT)
        with jax.named_scope(OP_PROJ):
            # the norm over each head's width BEFORE the gate, plain weight
            y = _normed_heads(o.astype(f32), H, eps, True) * jnp.tile(
                scale, H)
            y = y * nn.silu(z.astype(f32))
            return jnp.dot(y.astype(cd), w_out.astype(cd)), padded, state

    return weights, mix, taps - 1


# ... and what a block checkpoint round the whole layer keeps with it
KEPT = (_GDN_OUT, _GDN_SOLVE)
apply = recurrent.mixer_apply(_mixer, kept=KEPT)


def init_cache(cfg, d_model, batch, length, dtype, window):
    heads, k_dim, v_dim = (cfg["gdn_value_heads"], cfg["gdn_key_dim"],
                           cfg["gdn_value_dim"])
    qkv = 2 * cfg["gdn_key_heads"] * k_dim + heads * v_dim
    return (jnp.zeros((batch, cfg["gdn_conv_taps"] - 1, qkv), dtype),
            jnp.zeros((batch, heads, k_dim, v_dim), jnp.float32))
