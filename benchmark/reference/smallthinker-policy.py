"""Plain reference for ``smallthinker-policy``: SmallThinker-21BA3B-Instruct's
decoder layers (PowerInfer, ``model_name`` smallthinker_21b_instruct) as
the trunk of an observation-in, action-out policy, in float32 ``jax.numpy``
at matmul precision "highest". No kernels, no cache, no flax, no sparse
dispatch, no code of ``relayrl_tpu/models``; it reads the system's
parameter tree as data. (``program_kwargs``, which is no part of the
forward, looks at one tuple of names there, ``ARCH_PASSTHROUGH_KEYS``, to
refuse a program that would drop this configuration's keys.)

Layer ``l``, residual stream ``x [T, 2560]``, no bias anywhere, no QK-norm,
``eps`` = ``rms_norm_eps``:

1. Router FIRST, on the layer's own input as it arrives, un-normed:
   ``r = x W_r`` over all 64 experts (float32); chosen = top-6 of ``r``;
   ``w = softmax(r[chosen])`` — over the six chosen of all 64, held or not.
2. ``h = RMSNorm(x)``; ``q = h W_q`` as 28 heads of 128 (3584 wide: the
   head width is the model's own, not 2560 / 28), ``k = h W_k``, ``v = h
   W_v`` as 4 heads of 128. Where ``rope_layout[l]`` is 1: RoPE (half-split
   rotation, base ``rope_theta``) on q and k; where 0: NO positional signal
   of any kind. Causal softmax(q k^T / sqrt(128)) v, q head j reading k/v
   head j // 7; where ``sliding_window_layout[l]`` is 1 query ``t`` sees
   keys ``s`` with ``t - sliding_window_size < s <= t``. ``y = x +
   concat(heads) W_o``. Computed a block of queries at a time, the band as
   a mask over all the keys.
3. ``u = RMSNorm(y)``; ``x' = y + sum over the chosen experts THAT ARE HELD
   (``held_experts_first .. + moe_num_primary_experts``) of ``w_e W_down,e
   (relu(W_gate,e u) * W_up,e u)`` — chosen by step 1's ``r``, from ``x``,
   not from ``u``. Every held expert is computed for every token, one at a
   time; what the absent experts would add is left out, here as in the
   system.

A final RMSNorm, a linear policy head and a 2-layer tanh value head.
Departures from the source, each also in
``benchmark/configs/smallthinker-policy.json``: a Dense observation
embedding in place of the 151,936-row token table, the small heads in place
of the vocabulary head, 4 of 52 layers, 16 of 64 experts held.

``forward(..., operands=<dtype>)`` rounds both operands of every matmul the
configuration computes in bfloat16 (projections, attention, experts; not
the router, the norms, the embedding or the heads) to ``<dtype>`` and
accumulates in float32: the same reference in a lower precision.
``forward(..., wrong={...})`` computes a deliberately different layer —
``window`` (False: full attention in the windowed layers), ``rope_global``
(True: RoPE on the NoPE layers too), ``router_input`` (``"normed"``: the
router reads ``RMSNorm(x)``, the attention's input; ``"post_attention"``:
it reads ``u``, where most models route), ``top_k``, ``activation``
(``"silu"``) —: the readings the limits of the comparison are set against
(PERF.md section 6).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import flops, flops_lfm2, flops_smallthinker

Q_BLOCK = 256  # queries a step of the reference's attention


def program_kwargs(cfg: dict) -> dict:
    """The configuration in the program's own hyper-parameter names. A
    program that does not take one of them (the parent of the PR that added
    this configuration) would quietly build another model: refuse."""
    if not cfg["moe_primary_router_apply_softmax"]:
        raise SystemExit(
            "benchmark: REFUSED moe_primary_router_apply_softmax false: "
            "the reference and the program weigh the chosen experts by a "
            "softmax over their logits")
    kwargs = {
        "model_kind": "transformer_moe_discrete",
        "d_model": cfg["hidden_size"],
        "n_layers": cfg["num_hidden_layers"],
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "max_seq_len": cfg["max_position_embeddings"],
        "attention": cfg["attention"],
        "norm": "rms", "norm_eps": cfg["rms_norm_eps"],
        "positions": "rope", "rope_theta": cfg["rope_theta"],
        "rope_layers": [bool(r) for r in cfg["rope_layout"]],
        "layer_types": ["sliding_attention" if w else "full_attention"
                        for w in cfg["sliding_window_layout"]],
        "sliding_window": cfg["sliding_window_size"],
        "use_bias": False,
        "ffn": "reglu",
        "moe_experts": cfg["published"]["moe_num_primary_experts"],
        "moe_top_k": cfg["moe_num_active_primary_experts"],
        "moe_d_ff": cfg["moe_ffn_hidden_size"],
        "moe_norm_topk_prob": cfg["norm_topk_prob"],
        "moe_router": "softmax",
        "moe_router_input": "layer",
        "moe_held": [cfg["held_experts_first"],
                     cfg["moe_num_primary_experts"]],
    }
    from relayrl_tpu.models.base import ARCH_PASSTHROUGH_KEYS

    unknown = sorted(k for k in kwargs
                     if k != "model_kind" and k not in ARCH_PASSTHROUGH_KEYS)
    if unknown:
        raise SystemExit(
            f"benchmark: REFUSED this program's models take no arch keys "
            f"{unknown}: it cannot build smallthinker-policy")
    return kwargs


def train_flops_per_sample(cfg: dict, seq_len: int) -> float:
    """Forward + backward operations a token: each layer's scores by its
    own mask (the band's in the windowed layers), the expert layers at the
    work of the experts this chip holds at EVEN routing (1.5 token-slots a
    token and layer)."""
    return (flops.TRAIN_OVER_FWD
            * flops_smallthinker.smallthinker_fwd_flops_per_token(
                cfg, seq_len))


def held_grouped_matmul_train_ops_bytes(cfg: dict, held_slots: float):
    """(operations, bytes) of one update's grouped matmuls over the
    ``held_slots`` token-slots the run itself counted (all layers)."""
    return flops_lfm2.held_grouped_matmul_train_ops_bytes(
        held_slots, int(cfg["num_hidden_layers"]),
        int(cfg["moe_num_primary_experts"]), int(cfg["hidden_size"]),
        int(cfg["moe_ffn_hidden_size"]))


def flash_gqa_train_ops_bytes(cfg: dict, batch: int, seq_len: int):
    """(operations, bytes) of one update's flash kernels, every layer,
    forward and backward, at the scores each layer's mask needs: the causal
    triangle in a global layer, the band in a windowed one."""
    return flops_smallthinker.flash_layers_train_ops_bytes(cfg, batch,
                                                           seq_len)


def flash_window_train_ops_bytes(cfg: dict, batch: int, seq_len: int):
    """...of the windowed layers alone."""
    return flops_smallthinker.flash_layers_train_ops_bytes(
        cfg, batch, seq_len, windowed_only=True)


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rounded(a, operands):
    """``operands``: a dtype's name, or None."""
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


def _attention(p, x, n_head, n_kv, hd, eps, theta, window, operands):
    """``theta`` None: no positions; ``window`` None: every key up to the
    query's own."""
    b, t, _ = x.shape
    group = n_head // n_kv
    r = functools.partial(_rounded, operands=operands)
    h = r(_rms_norm(p["ln_attn"], x, eps))
    q = (h @ r(_f32(p["q_proj"]["kernel"]))).reshape(b, t, n_head, hd)
    k = (h @ r(_f32(p["k_proj"]["kernel"]))).reshape(b, t, n_kv, hd)
    v = (h @ r(_f32(p["v_proj"]["kernel"]))).reshape(b, t, n_kv, hd)
    if theta is not None:
        q, k = _rope(q, theta), _rope(k, theta)
    # q head j reads k/v head j // group: [B, T, Hkv, group, hd]
    q = r(q).reshape(b, t, n_kv, group, hd)
    k, v = r(k), r(v)
    step = min(Q_BLOCK, t)
    key_pos = jnp.arange(t)

    def rows(start):
        q_blk = jax.lax.dynamic_slice_in_dim(q, start, step, axis=1)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q_blk, k) / jnp.sqrt(
            jnp.float32(hd))
        back = (start + jnp.arange(step))[:, None] - key_pos[None, :]
        seen = back >= 0
        if window is not None:
            seen &= back < window
        p_blk = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", r(p_blk), v)

    attn = jax.lax.map(rows, jnp.arange(0, t, step))    # [t/step, b, step..]
    attn = jnp.moveaxis(attn, 0, 1).reshape(b, t, n_head * hd)
    return x + r(attn) @ r(_f32(p["attn_out"]["kernel"]))


def _route(moe, routed, top_k, first, held):
    """Combine weights ``[N, held]`` from the rows the router reads: zero
    off the top-k, softmax over the k chosen logits of all the experts,
    and only the held experts' columns."""
    logits = routed.reshape(-1, routed.shape[-1]) @ _f32(
        moe["moe_gate"]["kernel"])
    kth = jax.lax.top_k(logits, top_k)[0][:, -1:]
    chosen = logits >= kth
    w = jax.nn.softmax(jnp.where(chosen, logits, -jnp.inf), -1)
    return w[:, first:first + held]


def _experts(p, y, w, eps, silu, operands):
    """``y + `` every held expert on every token, one expert at a time."""
    r = functools.partial(_rounded, operands=operands)
    u = r(_rms_norm(p["ln_mlp"], y, eps).reshape(-1, y.shape[-1]))
    act = jax.nn.silu if silu else jax.nn.relu
    moe = p["moe"]

    def one(acc, e):
        w_gate, w_up, w_down, w_e = e
        mid = act(u @ r(_f32(w_gate))) * (u @ r(_f32(w_up)))
        return acc + w_e[:, None] * (r(mid) @ r(_f32(w_down))), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        moe["moe_w_gate"], moe["moe_w_up"], moe["moe_w_down"], w.T))
    return y + out.reshape(y.shape)


@functools.partial(jax.jit, static_argnames=("shape", "as_run", "operands"))
def _forward(params, obs, shape, as_run, operands):
    """The whole forward as ONE program. (A program a layer part, enqueued
    from a Python loop, has the runtime allocate every part's temporaries
    and outputs as the host runs ahead of the device: 3.4 GB at once at
    T 16384 beside a learner that holds 12.8, where this program's
    temporaries are one layer's, reused.)"""
    (heads, kv, hd, eps, theta, window, rope_layout, window_layout, first,
     held) = shape
    as_run = dict(as_run)
    p = params["params"]
    with jax.default_matmul_precision("highest"):
        x = _dense(p["obs_embed"], obs)
        for i, (rope, windowed) in enumerate(zip(rope_layout, window_layout)):
            blk = p[f"block_{i}"]
            y = _attention(
                blk, x, heads, kv, hd, eps,
                theta if rope or as_run["rope_global"] else None,
                window if windowed and as_run["window"] else None, operands)
            routed = x
            if as_run["router_input"] == "normed":
                routed = _rms_norm(blk["ln_attn"], x, eps)
            elif as_run["router_input"] == "post_attention":
                routed = _rms_norm(blk["ln_mlp"], y, eps)
            elif as_run["router_input"] != "layer":
                raise ValueError(as_run["router_input"])
            w = _route(blk["moe"], routed, as_run["top_k"], first, held)
            x = _experts(blk, y, w, eps, as_run["activation"] == "silu",
                         operands)
        x = _rms_norm(p["ln_final"], x, eps)
        logits = _dense(p["pi_head"], x)
        v = _dense(p["vf_head"], jnp.tanh(_dense(p["vf_head_up"], x)))
        return jax.nn.log_softmax(logits, -1), v[..., 0]


def forward(params, obs, cfg: dict, operands=None, wrong=None):
    """``obs [B, T, obs_dim]`` -> (log-probabilities ``[B, T, act_dim]``,
    values ``[B, T]``)."""
    as_run = {"window": True, "rope_global": False, "router_input": "layer",
              "top_k": int(cfg["moe_num_active_primary_experts"]),
              "activation": "relu", **(wrong or {})}
    shape = (int(cfg["num_attention_heads"]),
             int(cfg["num_key_value_heads"]), int(cfg["head_dim"]),
             float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]),
             int(cfg["sliding_window_size"]), tuple(cfg["rope_layout"]),
             tuple(cfg["sliding_window_layout"]),
             int(cfg["held_experts_first"]),
             int(cfg["moe_num_primary_experts"]))
    return _forward(params, _f32(obs), shape, tuple(sorted(as_run.items())),
                    None if operands is None else jnp.dtype(operands).name)
