"""The operator ``"kda"``: linear attention by the delta rule under a decay
a key LANE (Kimi Linear's Kimi Delta Attention), ``x + out(sigmoid(gate) *
norm_h(o))`` behind the layer's norm, then the FFN.

``[q | k | v] = in_qkv(norm(x))`` (``kda_heads`` heads of ``kda_head_dim``,
keys and values alike); ``[q | k | v] = silu(conv([q | k | v]))``,
``kda_conv_taps`` taps and no bias; ``beta = sigmoid(in_beta(norm(x)))`` a
head and ``g = -exp(A_log) softplus(f_up(f_down(norm(x))) + dt_bias)`` a key
lane (``A_log`` a head, ``dt_bias`` a lane; the low-rank path a head's
width inside, as the family's code fixes it), both float32; ``q = q / |q| /
sqrt(K)``, ``k = k / |k|`` a head; ``o`` the rule of
:mod:`relayrl_tpu.ops.kda` on a ``[K, K]`` matrix state a head, in chunks of
``kda_chunk`` (as Pallas kernels on a TPU where the shapes tile, as plain XLA
everywhere else: ``ops.kda.backend``, recorded in ``Policy.kda_backends``);
``norm_h`` an RMSNorm over each head's width with a plain weight, then the
gate ``sigmoid(g_up(g_down(norm(x))) + bias)`` (the second
low-rank path; the bias is the family's, whatever ``use_bias`` says of the
block's dense layers) and ``out``.

Its state (:mod:`.recurrent`): the convolution's last ``kda_conv_taps - 1``
rows of ``[q | k | v]`` (the three tails side by side) and the ``[B, H, K,
K]`` state in float32; prefill rows past ``n_valid`` get ``g = 0`` and
``beta = 0`` and leave the state as it is."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from relayrl_tpu.models.layers import recurrent
from relayrl_tpu.models.layers.gdn import _a_log_init, _normed_heads
from relayrl_tpu.ops import kda as kda_ops
from relayrl_tpu.ops.kda import SOLVE_NAME as _KDA_SOLVE
from relayrl_tpu.ops.scopes import KDA_CONV_NAME, OP_PROJ

ROW_READOUT = False
CACHE_RESTARTS = "zeroed"   # rows and state have no positions
# what a layer's checkpoint keeps: the rule's output and, where the rule
# runs as kernels, the solve's tiles their forward wrote (67 MB a layer) —
# without those the backward would run the rule's forward a second time
_KDA_OUT = "relayrl_kda_out"


def _rule_shape(q, k, v, g, beta, chunk, state):
    key = (int(v.shape[1]), int(v.shape[2]), int(k.shape[3]),
           int(v.shape[3]), v.dtype.name)
    return key, kda_ops.backend(*key[:4], chunk), (
        f"T={key[0]} heads={key[1]} key_dim={key[2]} value_dim={key[3]} "
        f"chunk={chunk} {key[4]}")


# ``Policy.kda_backends``: ``{(T, heads, key width, value width, dtype):
# "kda_pallas" | "kda_xla"}``
KERNELS = (recurrent.kernel("kda", kda_ops.kda, _rule_shape),
           recurrent.CONV_KERNEL)


def _mixer(block, shape):
    Bsz, T, d = shape
    cfg = block.cfg
    H, K = cfg["kda_heads"], cfg["kda_head_dim"]
    taps, chunk = cfg["kda_conv_taps"], cfg["kda_chunk"]
    width = H * K
    f32 = jnp.float32
    cd = block.compute_dtype
    rule_fn, conv_fn = block.fns["kda"], block.fns["conv"]
    lecun = nn.initializers.lecun_normal()
    weights = (
        block.param("kda_in_qkv", lecun, (d, 3 * width), f32),
        block.param("kda_in_beta", lecun, (d, H), f32),
        block.param("kda_f_down", lecun, (d, K), f32),
        block.param("kda_f_up", lecun, (K, width), f32),
        block.param("kda_g_down", lecun, (d, K), f32),
        block.param("kda_g_up", lecun, (K, width), f32),
        block.param("kda_g_bias", nn.initializers.normal(0.02), (width,),
                    f32),
        block.param("kda_conv_w", lecun, (taps, 3 * width), f32),
        block.param("kda_dt_bias", nn.initializers.ones, (width,), f32),
        block.param("kda_A_log", _a_log_init, (H,), f32),
        block.param("kda_norm", nn.initializers.ones, (K,), f32),
        block.param("kda_out", lecun, (width, d), f32))
    eps = 1e-6 if block.norm_eps is None else float(block.norm_eps)

    def low_rank(h, down, up):
        return jnp.dot(jnp.dot(h, down.astype(cd)), up.astype(cd),
                       preferred_element_type=f32)

    def mix(h, weights, conv_rows, state, n_valid):
        (w_qkv, w_beta, f_down, f_up, g_down, g_up, g_bias, conv_w, dt_bias,
         a_log, scale, w_out) = weights
        with jax.named_scope(OP_PROJ):
            qkv = checkpoint_name(jnp.dot(h, w_qkv.astype(cd)),
                                  recurrent.MIXER_IN)
            beta = jax.nn.sigmoid(
                jnp.dot(h, w_beta.astype(cd), preferred_element_type=f32))
            # a decay a key lane: A_log a head, dt_bias a lane
            g = -jnp.repeat(jnp.exp(a_log), K) * jax.nn.softplus(
                low_rank(h, f_down, f_up) + dt_bias)
            gate = low_rank(h, g_down, g_up) + g_bias
            if n_valid is not None:
                real = jnp.arange(T)[None, :, None] < n_valid
                beta, g = jnp.where(real, beta, 0.0), jnp.where(real, g, 0.0)
        qkv, padded = recurrent.mixer_conv(qkv, conv_w, None, conv_rows,
                                           KDA_CONV_NAME, conv_fn)
        with jax.named_scope(OP_PROJ):
            q, k, v = jnp.split(qkv, 3, axis=-1)
            # L2 over a head's width, float32 (eps as the source's)
            q = (_normed_heads(q.astype(f32), H, 1e-6, False)
                 * K ** -0.5).astype(cd).reshape(Bsz, T, H, K)
            k = _normed_heads(k.astype(f32), H, 1e-6, False).astype(
                cd).reshape(Bsz, T, H, K)
            v = v.reshape(Bsz, T, H, K)
            g = g.reshape(Bsz, T, H, K)
        if T == 1:
            # one row is one step of the rule, from the cache's state or
            # (the row ``init`` traces) from nothing: no chunk to pad to
            if state is None:
                state = jnp.zeros((Bsz, H, K, K), f32)
            o, state = kda_ops.kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                        beta[:, 0], state)
            o = o[:, None]
        else:
            o, state = rule_fn(q, k, v, g, beta, chunk, state)
        o = checkpoint_name(o.reshape(Bsz, T, width), _KDA_OUT)
        with jax.named_scope(OP_PROJ):
            # the norm over each head's width, plain weight, THEN the gate
            y = _normed_heads(o.astype(f32), H, eps, True) * jnp.tile(
                scale, H)
            y = y * jax.nn.sigmoid(gate)
            return jnp.dot(y.astype(cd), w_out.astype(cd)), padded, state

    return weights, mix, taps - 1


# ... and what a block checkpoint round the whole layer keeps with it
KEPT = (_KDA_OUT, _KDA_SOLVE)
apply = recurrent.mixer_apply(_mixer, kept=KEPT)


def init_cache(cfg, d_model, batch, length, dtype, window):
    heads, width = cfg["kda_heads"], cfg["kda_head_dim"]
    return (jnp.zeros((batch, cfg["kda_conv_taps"] - 1, 3 * heads * width),
                      dtype),
            jnp.zeros((batch, heads, width, width), jnp.float32))
