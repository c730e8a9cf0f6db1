"""Plain reference for ``lfm2-policy``: LFM2-24B-A2B's layers (LiquidAI,
``model_type`` lfm2_moe) as the trunk of an observation-in, action-out
policy, in float32 ``jax.numpy`` at matmul precision "highest". No kernels,
no cache, no flax, no sparse dispatch, no code of ``relayrl_tpu/models``; it
reads the system's parameter tree as data. (``program_kwargs``, which is no
part of the forward, looks at one tuple of names there,
``ARCH_PASSTHROUGH_KEYS``, to refuse a program that would drop this
configuration's keys.)

Block ``i``, all projections without bias, ``eps`` = ``norm_eps``:

    h = x + Op_i(RMSNorm(x))            Op_i by layer_types[i]
    y = h + FFN_i(RMSNorm(h))           dense for i < num_dense_layers

* ``full_attention``: ``q = W_q x`` as 32 heads of 64, ``k = W_k x``,
  ``v = W_v x`` as 8 heads of 64; q and k through an RMSNorm over the 64 of
  a head (one learned scale for all heads), then RoPE (half-split rotation,
  base ``rope_theta``) over the whole head; causal softmax(q k^T / 8) v
  with q head j reading k/v head j // 4; ``W_o``. Computed a block of
  queries at a time so that 32 heads x 8192 x 8192 never exist at once.
* ``conv``: ``(B, C, u) = split3(W_in x)``; ``z = B * u``; ``c_t = sum_j
  w[j] * z_{t-2+j}`` for j = 0..2 (depthwise, causal, zeros before the
  sequence's first row, no bias); ``W_out (C * c)``. No positions.
* dense FFN: ``W_2 (silu(W_1 x) * W_3 x)``, width 11776 (``mlp_gate`` =
  W_1, ``mlp_up`` = W_3, ``mlp_down`` = W_2).
* experts: ``s = sigmoid(W_r x)`` over all 64 (float32); chosen = top-4 of
  ``s + b`` (``b`` = ``moe_expert_bias``, in the choice only); ``w =
  s[chosen] / (sum s[chosen] + 1e-6) * routed_scaling_factor`` — normalised
  over the four chosen of all 64, held or not; output = sum over the chosen
  experts THAT ARE HELD (``held_experts_first .. + num_experts``) of ``w_e
  W_2,e (silu(W_1,e x) * W_3,e x)``. Every held expert is computed for
  every token, one at a time, and combined with those weights; what the
  absent experts would add is left out, here as in the system.

A final RMSNorm, a linear policy head and a 2-layer tanh value head.
Departures from the source, each also in
``benchmark/configs/lfm2-policy.json``: a Dense observation embedding in
place of the 65,536-row token table, the small heads in place of the
vocabulary head, 5 of 40 layers, 8 of 64 experts held.

``forward(..., operands=<dtype>)`` rounds both operands of every matmul the
configuration computes in bfloat16 (projections, attention, dense FFN,
experts; not the router, the norms, the embedding or the heads) to
``<dtype>`` and accumulates in float32: the same reference in a lower
precision. ``forward(..., wrong={...})`` computes a deliberately different
router (``top_k``, ``norm_topk_prob``, ``use_expert_bias``): the readings
the limits of the comparison are set against (PERF.md section 6).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import flops, flops_lfm2

Q_BLOCK = 512  # queries a step of the reference's attention


def program_kwargs(cfg: dict) -> dict:
    """The configuration in the program's own hyper-parameter names. A
    program that does not take one of them (the parent of the PR that added
    this configuration) would quietly build another model: refuse."""
    if cfg["routed_scaling_factor"] != 1:
        raise SystemExit(
            f"benchmark: REFUSED routed_scaling_factor "
            f"{cfg['routed_scaling_factor']}: the program's sigmoid router "
            f"has no scaling (the source's factor is 1)")
    kwargs = {
        "model_kind": "transformer_moe_discrete",
        "d_model": cfg["hidden_size"],
        "n_layers": cfg["num_hidden_layers"],
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "max_seq_len": cfg["positions_as_run"],
        "attention": cfg["attention"],
        "norm": "rms", "norm_eps": cfg["norm_eps"],
        "positions": "rope",
        "rope_theta": cfg["rope_parameters"]["rope_theta"],
        "qk_norm": "head", "use_bias": False,
        "ffn": "swiglu", "d_ff": cfg["intermediate_size"],
        "layer_types": list(cfg["layer_types"]),
        "moe_dense_layers": cfg["num_dense_layers"],
        "conv_taps": cfg["conv_L_cache"],
        "moe_experts": cfg["published"]["num_experts"],
        "moe_top_k": cfg["num_experts_per_tok"],
        "moe_d_ff": cfg["moe_intermediate_size"],
        "moe_norm_topk_prob": cfg["norm_topk_prob"],
        "moe_router": "sigmoid",
        "moe_expert_bias": cfg["use_expert_bias"],
        "moe_held": [cfg["held_experts_first"], cfg["num_experts"]],
    }
    from relayrl_tpu.models.base import ARCH_PASSTHROUGH_KEYS

    unknown = sorted(k for k in kwargs
                     if k != "model_kind" and k not in ARCH_PASSTHROUGH_KEYS)
    if unknown:
        raise SystemExit(
            f"benchmark: REFUSED this program's models take no arch keys "
            f"{unknown}: it cannot build lfm2-policy")
    return kwargs


def train_flops_per_sample(cfg: dict, seq_len: int) -> float:
    """Forward + backward operations a token. The expert layers count the
    work of the experts this chip holds at EVEN routing:
    ``num_experts_per_tok x num_experts / published.num_experts`` = 0.5
    token-slot a token and layer (``flops_lfm2.held_slots_per_token``)."""
    return flops.TRAIN_OVER_FWD * flops_lfm2.lfm2_fwd_flops_per_token(
        cfg, seq_len)


def _expert_layers(cfg: dict) -> int:
    return int(cfg["num_hidden_layers"]) - int(cfg["num_dense_layers"])


def held_grouped_matmul_train_ops_bytes(cfg: dict, held_slots: float):
    """(operations, bytes) of one update's grouped matmuls over the
    ``held_slots`` token-slots the run itself counted (all expert layers)."""
    return flops_lfm2.held_grouped_matmul_train_ops_bytes(
        held_slots, _expert_layers(cfg), int(cfg["num_experts"]),
        int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"]))


def flash_gqa_train_ops_bytes(cfg: dict, batch: int, seq_len: int):
    """(operations, bytes) of one update's flash kernels, every
    ``full_attention`` layer, forward and backward, at the scores a causal
    call needs (``T (T + 1) / 2`` a q head)."""
    heads = int(cfg["num_attention_heads"])
    ops, nbytes = flops_lfm2.flash_gqa_train_ops_bytes(
        batch, heads, int(cfg["num_key_value_heads"]), seq_len,
        int(cfg["hidden_size"]) // heads)
    layers = sum(kind == "full_attention" for kind in cfg["layer_types"])
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


@functools.partial(jax.jit, static_argnames=("n_head", "n_kv", "eps",
                                             "theta", "operands"))
def _attention(p, x, n_head, n_kv, eps, theta, operands):
    b, t, d = x.shape
    hd = d // n_head
    group = n_head // n_kv
    r = functools.partial(_rounded, operands=operands)
    h = r(_rms_norm(p["ln_attn"], x, eps))
    q = (h @ r(_f32(p["q_proj"]["kernel"]))).reshape(b, t, n_head, hd)
    k = (h @ r(_f32(p["k_proj"]["kernel"]))).reshape(b, t, n_kv, hd)
    v = (h @ r(_f32(p["v_proj"]["kernel"]))).reshape(b, t, n_kv, hd)
    q = _rope(_rms_norm(p["q_norm"], q, eps), theta)
    k = _rope(_rms_norm(p["k_norm"], k, eps), theta)
    # q head j reads k/v head j // group: [B, T, Hkv, group, hd]
    q = r(q).reshape(b, t, n_kv, group, hd)
    k, v = r(k), r(v)
    step = min(Q_BLOCK, t)
    key_pos = jnp.arange(t)

    def rows(start):
        q_blk = jax.lax.dynamic_slice_in_dim(q, start, step, axis=1)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q_blk, k) / jnp.sqrt(
            jnp.float32(hd))
        seen = (start + jnp.arange(step))[:, None] >= key_pos[None, :]
        p_blk = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", r(p_blk), v)

    attn = jax.lax.map(rows, jnp.arange(0, t, step))    # [t/step, b, step..]
    attn = jnp.moveaxis(attn, 0, 1).reshape(b, t, d)
    return x + r(attn) @ r(_f32(p["attn_out"]["kernel"]))


@functools.partial(jax.jit, static_argnames=("eps", "operands"))
def _short_conv(p, x, eps, operands):
    r = functools.partial(_rounded, operands=operands)
    bcu = r(_rms_norm(p["ln_attn"], x, eps)) @ r(_f32(p["conv_in"]["kernel"]))
    gate_b, gate_c, u = jnp.split(bcu, 3, -1)
    z = gate_b * u
    w = _f32(p["conv_w"])                       # [taps, d]
    taps, t = w.shape[0], x.shape[1]
    zp = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
    c = sum(w[j] * zp[:, j:j + t] for j in range(taps))
    return x + r(gate_c * c) @ r(_f32(p["conv_out"]["kernel"]))


@functools.partial(jax.jit, static_argnames=("eps", "operands"))
def _dense_ffn(p, x, eps, operands):
    r = functools.partial(_rounded, operands=operands)
    h = r(_rms_norm(p["ln_mlp"], x, eps))
    mid = jax.nn.silu(h @ r(_f32(p["mlp_gate"]["kernel"]))) * (
        h @ r(_f32(p["mlp_up"]["kernel"])))
    return x + r(mid) @ r(_f32(p["mlp_down"]["kernel"]))


@functools.partial(jax.jit, static_argnames=(
    "eps", "top_k", "renorm", "use_bias", "scaling", "first", "held"))
def _route(p, x, eps, top_k, renorm, use_bias, scaling, first, held):
    """(h', combine weights ``[N, held]``: zero off the top-k, and only the
    held experts' columns)."""
    h = _rms_norm(p["ln_mlp"], x, eps).reshape(-1, x.shape[-1])
    moe = p["moe"]
    s = jax.nn.sigmoid(h @ _f32(moe["moe_gate"]["kernel"]))
    biased = s + _f32(moe["moe_expert_bias"]) if use_bias else s
    kth = jax.lax.top_k(biased, top_k)[0][:, -1:]
    w = jnp.where(biased >= kth, s, 0.0)
    if renorm:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    return h, (w * scaling)[:, first:first + held]


@functools.partial(jax.jit, static_argnames=("operands",))
def _experts(moe, h, w, operands):
    """Every held expert on every token, one expert at a time."""
    r = functools.partial(_rounded, operands=operands)
    h = r(h)

    def one(acc, e):
        w_gate, w_up, w_down, w_e = e
        mid = jax.nn.silu(h @ r(_f32(w_gate))) * (h @ r(_f32(w_up)))
        return acc + w_e[:, None] * (r(mid) @ r(_f32(w_down))), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        moe["moe_w_gate"], moe["moe_w_up"], moe["moe_w_down"], w.T))
    return out


def forward(params, obs, cfg: dict, operands=None, wrong=None):
    """``obs [B, T, obs_dim]`` -> (log-probabilities ``[B, T, act_dim]``,
    values ``[B, T]``)."""
    p = params["params"]
    eps = float(cfg["norm_eps"])
    theta = float(cfg["rope_parameters"]["rope_theta"])
    router = {"top_k": int(cfg["num_experts_per_tok"]),
              "norm_topk_prob": bool(cfg["norm_topk_prob"]),
              "use_expert_bias": bool(cfg["use_expert_bias"]),
              **(wrong or {})}
    with jax.default_matmul_precision("highest"):
        x = _dense(p["obs_embed"], _f32(obs))
        for i, kind in enumerate(cfg["layer_types"]):
            blk = p[f"block_{i}"]
            if kind == "full_attention":
                x = _attention(blk, x, int(cfg["num_attention_heads"]),
                               int(cfg["num_key_value_heads"]), eps, theta,
                               operands)
            else:
                x = _short_conv(blk, x, eps, operands)
            if i < int(cfg["num_dense_layers"]):
                x = _dense_ffn(blk, x, eps, operands)
                continue
            h, w = _route(blk, x, eps, router["top_k"],
                          router["norm_topk_prob"],
                          router["use_expert_bias"],
                          float(cfg["routed_scaling_factor"]),
                          int(cfg["held_experts_first"]),
                          int(cfg["num_experts"]))
            x = x + _experts(blk["moe"], h, w, operands).reshape(x.shape)
        x = _rms_norm(p["ln_final"], x, eps)
        logits = _dense(p["pi_head"], x)
        v = _dense(p["vf_head"], jnp.tanh(_dense(p["vf_head_up"], x)))
        return jax.nn.log_softmax(logits, -1), v[..., 0]
