"""Plain reference for ``gpt2m-policy``: the GPT-2 medium decoder block as
the trunk of an observation-in, action-out policy, in float32 ``jax.numpy``
at matmul precision "highest". No kernels, no cache, no flax, nothing from
``relayrl_tpu/models``; it reads the system's parameter tree as data.

Block (Radford et al. 2019; ``openai-community/gpt2-medium``): pre-LN,
x += W_o . causal_softmax(q k^T / sqrt(head_dim)) v ; x += W_2 gelu_new(W_1
LN(x)); learned positions; biases everywhere. Departures from the source,
each also in ``benchmark/configs/gpt2m-policy.json``: a Dense observation
embedding instead of the token table; a linear policy head and a 2-layer
tanh value head after the final LN instead of the tied output embedding;
layer-norm epsilon as the configuration file states it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import flops


def program_kwargs(cfg: dict) -> dict:
    """The configuration in the program's own hyper-parameter names."""
    return {
        "model_kind": "transformer_discrete",
        "d_model": cfg["n_embd"], "n_layers": cfg["n_layer"],
        "n_heads": cfg["n_head"],
        "mlp_ratio": cfg["n_inner"] // cfg["n_embd"],
        "max_seq_len": cfg["n_positions"], "attention": cfg["attention"],
    }


def train_flops_per_sample(cfg: dict, seq_len: int) -> float:
    return flops.TRAIN_OVER_FWD * flops.transformer_fwd_flops(
        1, seq_len, cfg["obs_dim"], cfg["act_dim"], cfg["n_embd"],
        cfg["n_layer"], cfg["n_inner"] // cfg["n_embd"])


def _dense(p, x):
    return x @ p["kernel"].astype(jnp.float32) + p["bias"].astype(
        jnp.float32)


def _layer_norm(p, x, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + eps) * p["scale"]
            + p["bias"])


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("n_head", "eps"))
def _block(p, x, n_head: int, eps: float):
    b, t, d = x.shape
    hd = d // n_head
    qkv = _dense(p["qkv"], _layer_norm(p["ln_attn"], x, eps))
    q, k, v = (a.reshape(b, t, n_head, hd) for a in jnp.split(qkv, 3, -1))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.float32(hd))
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    x = x + _dense(p["attn_out"], attn.reshape(b, t, d))
    h = _gelu_new(_dense(p["mlp_up"], _layer_norm(p["ln_mlp"], x, eps)))
    return x + _dense(p["mlp_down"], h)


def forward(params, obs, cfg: dict):
    """``obs [B, T, obs_dim]`` -> (log-probabilities ``[B, T, act_dim]``,
    values ``[B, T]``)."""
    p = params["params"]
    eps = float(cfg["layer_norm_epsilon"])
    with jax.default_matmul_precision("highest"):
        obs = jnp.asarray(obs, jnp.float32)
        t = obs.shape[1]
        x = _dense(p["obs_embed"], obs) + p["pos_embed"][:t][None]
        for i in range(int(cfg["n_layer"])):
            x = _block(p[f"block_{i}"], x, n_head=int(cfg["n_head"]),
                       eps=eps)
        x = _layer_norm(p["ln_final"], x, eps)
        logits = _dense(p["pi_head"], x)
        v = _dense(p["vf_head"], jnp.tanh(_dense(p["vf_head_up"], x)))
        return jax.nn.log_softmax(logits, -1), v[..., 0]
