"""Plain reference for ``olmoe-policy``: OLMoE-1B-7B's decoder layer
(Muennighoff et al. 2024, arXiv:2409.02060; ``model_type`` olmoe) as the
trunk of an observation-in, action-out policy, in float32 ``jax.numpy`` at
matmul precision "highest". No kernels, no cache, no flax, no sparse
dispatch, no code of ``relayrl_tpu/models``; it reads the system's
parameter tree as data. (``program_kwargs``, which is no part of the
forward, looks at one tuple of names there, ``ARCH_PASSTHROUGH_KEYS``, to
refuse a program that would drop this configuration's keys.)

Layer, with ``h = RMSNorm(x)``:

    x += W_o . attn(rope(RMSNorm_q(W_q h)), rope(RMSNorm_k(W_k h)), W_v h)
    x += sum_{e in top8(p)} p_e . W_down,e (silu(W_gate,e h') * W_up,e h')

``h' = RMSNorm(x)``, ``p = softmax(W_r h')`` over all 64 experts, the eight
chosen probabilities NOT renormalised (``norm_topk_prob`` false); the q/k
norms run over the whole 2048-wide projection before the split into heads;
RoPE is the half-split rotation at ``theta`` 10000; no bias anywhere in the
layer; causal softmax attention scaled by 1/sqrt(head_dim). Every expert is
computed for every token, in a loop over experts, and combined with the
top-8 probabilities. Departures from the source, each also in
``benchmark/configs/olmoe-policy.json``: a Dense observation embedding
instead of the token table; a linear policy head and a 2-layer tanh value
head after the final RMSNorm instead of the untied output embedding.

``forward(..., operands=<dtype>)`` rounds both operands of every matmul the
configuration computes in bfloat16 (projections, attention, experts; not
the router, the norms, the embedding or the heads) to ``<dtype>`` and
accumulates in float32: the same reference in a lower precision, for the
two readings each limit is set from (PERF.md section 6).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import flops, flops_moe

def program_kwargs(cfg: dict) -> dict:
    """The configuration in the program's own hyper-parameter names. A
    program that does not take one of them (the parent of the PR that added
    this configuration) would quietly build another model: refuse."""
    kwargs = {
        "model_kind": "transformer_moe_discrete",
        "d_model": cfg["hidden_size"],
        "n_layers": cfg["num_hidden_layers"],
        "n_heads": cfg["num_attention_heads"],
        "max_seq_len": cfg["max_position_embeddings"],
        "attention": cfg["attention"],
        "norm": "rms", "norm_eps": cfg["rms_norm_eps"],
        "positions": "rope", "rope_theta": cfg["rope_theta"],
        "qk_norm": True, "use_bias": cfg["attention_bias"],
        "ffn": "swiglu",
        "moe_experts": cfg["num_experts"],
        "moe_top_k": cfg["num_experts_per_tok"],
        "moe_d_ff": cfg["intermediate_size"],
        "moe_norm_topk_prob": cfg["norm_topk_prob"],
    }
    from relayrl_tpu.models.base import ARCH_PASSTHROUGH_KEYS

    unknown = sorted(k for k in kwargs
                     if k != "model_kind" and k not in ARCH_PASSTHROUGH_KEYS)
    if unknown:
        raise SystemExit(
            f"benchmark: REFUSED this program's models take no arch keys "
            f"{unknown}: it cannot build olmoe-policy")
    return kwargs


def train_flops_per_sample(cfg: dict, seq_len: int) -> float:
    return flops.TRAIN_OVER_FWD * flops_moe.moe_transformer_fwd_flops(
        1, seq_len, cfg["obs_dim"], cfg["act_dim"], cfg["hidden_size"],
        cfg["num_hidden_layers"], cfg["num_experts"],
        cfg["num_experts_per_tok"], cfg["intermediate_size"])


def grouped_matmul_train_ops_bytes(cfg: dict, n_tokens: int):
    """(operations, bytes) of every expert layer's grouped matmuls in one
    update over ``n_tokens`` tokens, forward and backward."""
    ops, nbytes = flops_moe.grouped_matmul_train_ops_bytes(
        n_tokens, cfg["num_experts_per_tok"], cfg["hidden_size"],
        cfg["intermediate_size"], cfg["num_experts"])
    layers = int(cfg["num_hidden_layers"])
    return layers * ops, layers * nbytes


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rounded(a, operands):
    return a if operands is None else a.astype(operands).astype(jnp.float32)


def _dense(p, x):
    return x @ _f32(p["kernel"]) + _f32(p["bias"])


def _rms_norm(p, x, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * _f32(
            p["scale"])


def _rope(x, theta):
    """``x [B, T, H, hd]``, row j at position j: pairs (i, i + hd/2) turn by
    ``j * theta^(-2i/hd)``."""
    hd = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "theta",
                                             "operands"))
def _attention(p, x, n_head, eps, theta, operands):
    b, t, d = x.shape
    hd = d // n_head
    r = functools.partial(_rounded, operands=operands)
    qkv = r(_rms_norm(p["ln_attn"], x, eps)) @ r(_f32(p["qkv"]["kernel"]))
    q, k, v = jnp.split(qkv, 3, -1)
    q = _rms_norm(p["q_norm"], q, eps)
    k = _rms_norm(p["k_norm"], k, eps)
    q, k, v = (a.reshape(b, t, n_head, hd) for a in (q, k, v))
    q, k = _rope(q, theta), _rope(k, theta)
    scores = jnp.einsum("bqhd,bkhd->bhqk", r(q), r(k)) / jnp.sqrt(
        jnp.float32(hd))
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    attn = jnp.einsum("bhqk,bkhd->bqhd", r(jax.nn.softmax(scores, -1)),
                      r(v))
    return x + r(attn.reshape(b, t, d)) @ r(_f32(p["attn_out"]["kernel"]))


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "renorm"))
def _route(p, x, eps, top_k, renorm):
    """(h', combine weights ``[N, E]`` that are zero off the top-k)."""
    h = _rms_norm(p["ln_mlp"], x, eps).reshape(-1, x.shape[-1])
    probs = jax.nn.softmax(h @ _f32(p["moe"]["moe_gate"]["kernel"]), -1)
    kth = jax.lax.top_k(probs, top_k)[0][:, -1:]
    w = jnp.where(probs >= kth, probs, 0.0)
    if renorm:
        w = w / jnp.sum(w, -1, keepdims=True)
    return h, w


@functools.partial(jax.jit, static_argnames=("operands",))
def _experts(moe, h, w, operands):
    """Every expert on every token, one expert at a time."""
    r = functools.partial(_rounded, operands=operands)
    h = r(h)

    def one(acc, e):
        w_gate, w_up, w_down, w_e = e
        mid = jax.nn.silu(h @ r(_f32(w_gate))) * (h @ r(_f32(w_up)))
        return acc + w_e[:, None] * (r(mid) @ r(_f32(w_down))), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        moe["moe_w_gate"], moe["moe_w_up"], moe["moe_w_down"], w.T))
    return out


def forward(params, obs, cfg: dict, operands=None):
    """``obs [B, T, obs_dim]`` -> (log-probabilities ``[B, T, act_dim]``,
    values ``[B, T]``)."""
    p = params["params"]
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    n_head, top_k = int(cfg["num_attention_heads"]), int(
        cfg["num_experts_per_tok"])
    renorm = bool(cfg["norm_topk_prob"])
    with jax.default_matmul_precision("highest"):
        obs = _f32(obs)
        x = _dense(p["obs_embed"], obs)
        for i in range(int(cfg["num_hidden_layers"])):
            blk = p[f"block_{i}"]
            x = _attention(blk, x, n_head, eps, theta, operands)
            h, w = _route(blk, x, eps, top_k, renorm)
            x = x + _experts(blk["moe"], h, w, operands).reshape(x.shape)
        x = _rms_norm(p["ln_final"], x, eps)
        logits = _dense(p["pi_head"], x)
        v = _dense(p["vf_head"], jnp.tanh(_dense(p["vf_head_up"], x)))
        return jax.nn.log_softmax(logits, -1), v[..., 0]
