"""Plain reference for ``granite4h-micro-policy``: ibm-granite's
granite-4.0-h-micro (``model_type`` granitemoehybrid, 3 B dense) at its
published depth and widths as the trunk of an observation-in, action-out
policy, in float32 ``jax.numpy`` at matmul precision "highest". No kernels,
no cache, no flax, no chunked scan, no code of ``relayrl_tpu/models`` or
``relayrl_tpu/ops``; it reads the system's parameter tree as data.
(``program_kwargs``, which is no part of the forward, looks at one tuple of
names there, ``ARCH_PASSTHROUGH_KEYS``, to refuse a program that would drop
this configuration's keys.)

Every layer is ``h = h + m * mixer(rms(h))`` then ``h = h + m * mlp(rms(h))``
with ``m`` = ``residual_multiplier`` (0.22), RMSNorm at ``rms_norm_eps``, no
bias but the convolution's; the mixer by the layer's ``layer_types`` entry:

``mamba`` — Mamba-2, H = ``mamba_n_heads`` heads of P = ``mamba_d_head``
(inner = H P = ``mamba_expand`` x hidden), N = ``mamba_d_state``, ONE group
of B and C (``mamba_n_groups``):
  ``[z | xBC | dt] = u W_in`` (widths inner | inner + 2 N | H);
  ``xBC_t <- silu(w0 xBC_{t-3} + w1 xBC_{t-2} + w2 xBC_{t-1} + w3 xBC_t +
  b)``, the ``mamba_d_conv`` = 4 taps written out, rows before the
  sequence's first zero;
  ``x [H, P], B [N], C [N] = split(xBC)``; ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``;
  **the state equation one token at a time**, a ``lax.scan`` over T from a
  zero state: ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T`` (h ``[H, P,
  N]``), ``y_t = C_t h_t + D x_t``;
  ``y <- rms(y * silu(z)) * w`` over the whole inner width — the gate BEFORE
  the norm —, ``mixer = y W_out``.

``attention`` — ``num_attention_heads`` query heads of hidden / heads = 64
over ``num_key_value_heads`` k/v heads (q head j reads k/v head j // 4),
causal ``softmax(attention_multiplier * q k^T) v`` — 1/64, NOT 1/sqrt(64) —,
NO positional signal (``position_embedding_type`` nope), ``mixer =
concat(heads) W_o``.

The MLP is a SwiGLU of width ``shared_intermediate_size``
(``num_local_experts`` 0: the shared MLP is the whole FFN): ``W_down
(silu(W_gate u) * W_up u)``. The embedded observation is multiplied by
``embedding_multiplier`` (12); after the final RMSNorm the policy logits are
divided by ``logits_scaling`` (8); the value head is no logit and is not
divided. Departures from the source, each also in
``benchmark/configs/granite4h-micro-policy.json``: a Dense observation
embedding in place of the 100,352-row token table, a 16-way policy head and
a 2-layer tanh value head in place of the tied output embedding.

**It fits beside the program.** The parameters it is handed are the actor
tier's own, the matmul weights held in bfloat16 (5.97 GB of them). It walks
the layers ONE AT A TIME — two jitted programs, a ``mamba`` layer's and an
``attention`` layer's, each fenced before the next is launched — and a
layer's program up-casts that layer's weights alone (0.30 GB in float32), so
that it runs beside the rollout's 11 GB where a whole float32 tree (11.9 GB)
could not.

``forward(..., operands=<dtype>)`` rounds both operands of every matmul the
configuration computes in bfloat16 (the projections, the attention, the MLP;
the recurrence's ``x``, ``B`` and ``C``; not the norms, the step sizes, the
decays, the state, the embedding or the heads) to ``<dtype>`` and
accumulates in float32: the same reference in a lower precision.
``forward(..., wrong={...})`` computes a deliberately different model —
``carry`` (True: the recurrence and the convolution do NOT start an episode
from zero, they start from what a pass over the same observations left, a
state carried over a reset), ``residual`` (1.0: the 0.22 left out),
``attn_scale`` (0.125: 1/sqrt(64) for 1/64), ``gate`` (``"after"``: the gate
after the norm) —: what the rollout cell's limits must refuse
(``benchmark/tests/controls_granite_rollout.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.flops_granite import (  # noqa: F401  (the readers' entry)
    rollout_flops_per_step,
    ssm_step_bytes,
)

# ``layer_types`` as published -> the program's layer kinds: a Mamba-2 mixer
# and an FFN behind its own norm; attention and an FFN
KINDS = {"mamba": "mamba", "attention": "full_attention"}


def program_kwargs(cfg: dict) -> dict:
    """The configuration in the program's own hyper-parameter names. A
    program that does not take one of them (the parent of the PR that added
    this configuration) would quietly build another model: refuse."""
    for key, want in (("mamba_n_groups", 1), ("mamba_conv_bias", True),
                      ("mamba_proj_bias", False), ("attention_bias", False),
                      ("num_local_experts", 0), ("hidden_act", "silu"),
                      ("position_embedding_type", "nope"),
                      ("normalization_function", "rmsnorm")):
        if cfg[key] != want:
            raise SystemExit(
                f"benchmark: REFUSED {key} {cfg[key]!r}: the reference and "
                f"the program are written for {want!r}")
    if cfg["mamba_expand"] * cfg["hidden_size"] != (
            cfg["mamba_n_heads"] * cfg["mamba_d_head"]):
        raise SystemExit("benchmark: REFUSED mamba_expand x hidden_size is "
                         "not mamba_n_heads x mamba_d_head")
    kwargs = {
        "model_kind": "transformer_discrete",
        "d_model": cfg["hidden_size"],
        "n_layers": cfg["num_hidden_layers"],
        "layer_types": [KINDS[k] for k in cfg["layer_types"]],
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
        "max_seq_len": cfg["positions_as_run"],
        "attention": cfg["attention"],
        "norm": "rms", "norm_eps": cfg["rms_norm_eps"],
        "positions": "none", "use_bias": False,
        "ffn": "swiglu", "d_ff": cfg["shared_intermediate_size"],
        "mamba_heads": cfg["mamba_n_heads"],
        "mamba_head_dim": cfg["mamba_d_head"],
        "mamba_state": cfg["mamba_d_state"],
        "mamba_groups": cfg["mamba_n_groups"],
        "mamba_conv_taps": cfg["mamba_d_conv"],
        "mamba_chunk": cfg["mamba_chunk_size"],
        "residual_multiplier": cfg["residual_multiplier"],
        "attn_scale": cfg["attention_multiplier"],
        "embed_multiplier": cfg["embedding_multiplier"],
        "logit_divisor": cfg["logits_scaling"],
        # the actor tier's form: matmul weights at the compute type
        "held_params": True,
    }
    from relayrl_tpu.models.base import ARCH_PASSTHROUGH_KEYS

    unknown = sorted(k for k in kwargs
                     if k != "model_kind" and k not in ARCH_PASSTHROUGH_KEYS)
    if unknown:
        raise SystemExit(
            f"benchmark: REFUSED this program's models take no arch keys "
            f"{unknown}: it cannot build granite4h-micro-policy")
    return kwargs


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rounded(a, operands):
    """``operands``: a dtype's name, or None."""
    return a if operands is None else a.astype(operands).astype(jnp.float32)


def _dense(p, x):
    return x @ _f32(p["kernel"]) + _f32(p["bias"])


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)


def _rms_norm(p, x, eps):
    return _rms(x, eps) * _f32(p["scale"])


def _mamba(p, x, widths, eps, operands, carry, gate_after):
    """The Mamba-2 mixer's output, its state equation one token at a time."""
    heads, width, state = widths
    b, t, _ = x.shape
    inner = heads * width
    r = functools.partial(_rounded, operands=operands)
    u = r(_rms_norm(p["ln_attn"], x, eps))
    z, xbc, dt = jnp.split(u @ r(_f32(p["mamba_in"])),
                           [inner, 2 * inner + 2 * state], axis=-1)
    w, bias = _f32(p["mamba_conv_w"]), _f32(p["mamba_conv_b"])
    xbc = r(xbc)
    dt = jax.nn.softplus(r(dt) + _f32(p["mamba_dt_bias"]))      # [b, t, H]
    a_neg = -jnp.exp(_f32(p["mamba_A_log"]))
    skip = _f32(p["mamba_D"])

    def taps(before, rows):
        """Four explicit taps over ``rows`` behind the three rows
        ``before`` them."""
        n = rows.shape[1]
        past = jnp.concatenate([before, rows], axis=1)
        return jax.nn.silu(w[0] * past[:, 0:n] + w[1] * past[:, 1:n + 1]
                           + w[2] * past[:, 2:n + 2] + w[3] * past[:, 3:n + 3]
                           + bias)

    def recurrence(h, conv):
        xs, b_in, c_in = jnp.split(r(conv), [inner, inner + state], axis=-1)
        xs = xs.reshape(b, t, heads, width)

        def one(h, row):
            x_t, dt_t, b_t, c_t = row
            h = (jnp.exp(dt_t * a_neg)[..., None, None] * h
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :])
            return h, jnp.einsum("bhpn,bn->bhp", h, c_t) + skip[:, None] * x_t

        h, y = jax.lax.scan(one, h, tuple(
            jnp.moveaxis(a, 1, 0) for a in (xs, dt, b_in, c_in)))
        return h, jnp.moveaxis(y, 0, 1).reshape(b, t, inner)

    before = jnp.zeros((b, 3, xbc.shape[-1]), jnp.float32)
    h = jnp.zeros((b, heads, width, state), jnp.float32)
    if carry:   # the wrong reference: what a pass before this one left
        h, _ = recurrence(h, taps(before, xbc))
        before = xbc[:, -3:]
    _, y = recurrence(h, taps(before, xbc))
    gate = jax.nn.silu(r(z))
    y = _rms(y, eps) * gate if gate_after else _rms(y * gate, eps)
    return r(y * _f32(p["mamba_norm"])) @ r(_f32(p["mamba_out"]))


def _attention(p, x, n_head, n_kv, scale, eps, operands):
    """Dense causal softmax attention at ``scale``, no positions."""
    b, t, d = x.shape
    hd, group = d // n_head, n_head // n_kv
    r = functools.partial(_rounded, operands=operands)
    u = r(_rms_norm(p["ln_attn"], x, eps))
    q = (u @ r(_f32(p["q_proj"]["kernel"]))).reshape(b, t, n_kv, group, hd)
    k = (u @ r(_f32(p["k_proj"]["kernel"]))).reshape(b, t, n_kv, hd)
    v = (u @ r(_f32(p["v_proj"]["kernel"]))).reshape(b, t, n_kv, hd)
    scores = scale * jnp.einsum("bqhgd,bkhd->bhgqk", r(q), r(k))
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    attn = jnp.einsum("bhgqk,bkhd->bqhgd", r(probs), r(v))
    return r(attn.reshape(b, t, d)) @ r(_f32(p["attn_out"]["kernel"]))


def _mlp(p, x, eps, operands):
    r = functools.partial(_rounded, operands=operands)
    u = r(_rms_norm(p["ln_mlp"], x, eps))
    gated = jax.nn.silu(u @ r(_f32(p["mlp_gate"]["kernel"]))) * (
        u @ r(_f32(p["mlp_up"]["kernel"])))
    return r(gated) @ r(_f32(p["mlp_down"]["kernel"]))


@functools.partial(jax.jit, static_argnames=("kind", "shape", "as_run",
                                             "operands"))
def _layer(p, x, kind, shape, as_run, operands):
    """ONE layer: the up-cast of its held weights lives in this program."""
    mamba, heads, kv, eps = shape
    as_run = dict(as_run)
    m = as_run["residual"]
    with jax.default_matmul_precision("highest"):
        if kind == "mamba":
            x = x + m * _mamba(p, x, mamba, eps, operands, as_run["carry"],
                               as_run["gate"] == "after")
        else:
            x = x + m * _attention(p, x, heads, kv, as_run["attn_scale"],
                                   eps, operands)
        return x + m * _mlp(p, x, eps, operands)


@functools.partial(jax.jit, static_argnames=("multiplier",))
def _embed(p, obs, multiplier):
    with jax.default_matmul_precision("highest"):
        return multiplier * _dense(p, obs)


@functools.partial(jax.jit, static_argnames=("eps", "divisor"))
def _heads(p, x, eps, divisor):
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(p["ln_final"], x, eps)
        logits = _dense(p["pi_head"], x) / divisor
        v = _dense(p["vf_head"], jnp.tanh(_dense(p["vf_head_up"], x)))
        return jax.nn.log_softmax(logits, -1), v[..., 0]


def forward(params, obs, cfg: dict, operands=None, wrong=None):
    """``obs [B, T, obs_dim]`` -> (log-probabilities ``[B, T, act_dim]``,
    values ``[B, T]``). ``params``: the system's tree, its matmul weights in
    float32 or as the actor tier holds them."""
    as_run = {"carry": False, "gate": "before",
              "residual": float(cfg["residual_multiplier"]),
              "attn_scale": float(cfg["attention_multiplier"]),
              **(wrong or {})}
    shape = ((int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"]),
              int(cfg["mamba_d_state"])),
             int(cfg["num_attention_heads"]),
             int(cfg["num_key_value_heads"]), float(cfg["rms_norm_eps"]))
    operands = None if operands is None else jnp.dtype(operands).name
    p = params["params"]
    x = _embed(p["obs_embed"], _f32(obs),
               multiplier=float(cfg["embedding_multiplier"]))
    for i, kind in enumerate(cfg["layer_types"]):
        # fenced: the host would otherwise run ahead and the runtime set
        # aside every layer's float32 weights at once
        x = jax.block_until_ready(_layer(
            p[f"block_{i}"], x, kind=kind, shape=shape,
            as_run=tuple(sorted(as_run.items())), operands=operands))
    heads = {k: p[k] for k in ("ln_final", "pi_head", "vf_head",
                               "vf_head_up")}
    return _heads(heads, x, eps=shape[3],
                  divisor=float(cfg["logits_scaling"]))
