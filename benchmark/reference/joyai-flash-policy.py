"""Plain reference for ``joyai-flash-policy``: the layers of JoyAI-LLM-Flash's
``config`` (jdopensource, ``model_type`` joyai_llm_flash: the DeepSeek-V3
layer at hidden 2048) as the trunk of an observation-in, action-out policy,
in float32 ``jax.numpy`` at matmul precision "highest". No kernels, no
cache, no flax, no sparse dispatch, no code of ``relayrl_tpu/models`` or
``relayrl_tpu/ops``; it reads the system's parameter tree as data.
(``program_kwargs``, which is no part of the forward, looks at one tuple of
names there, ``ARCH_PASSTHROUGH_KEYS``, to refuse a program that would drop
this configuration's keys.)

Every layer is ``x <- x + attn(norm(x))``, ``x <- x + ffn(norm(x))``,
RMSNorm at ``rms_norm_eps``, no bias in any projection; the first
``first_k_dense_replace`` layers end in a dense SwiGLU FFN, the others in
the expert layer. With ``u = norm(x)``:

**Latent attention with a low-rank query, rotary** (``num_attention_heads``
heads): ``c_q = RMSNorm(u W_qa)`` (``q_lora_rank`` lanes), ``q = c_q W_qb`` a
head of ``[q_nope (qk_nope_head_dim) | q_pe (qk_rope_head_dim)]``; ``[c |
k_pe] = u W_kva`` (``kv_lora_rank`` + ``qk_rope_head_dim``); ``[k_nope | v] =
RMSNorm(c) W_kvb`` a head of ``qk_nope_head_dim + v_head_dim``; head h's key
is ``[k_nope_h | R_p(k_pe)]``, the rotated lanes shared by every head, its
query ``[q_nope_h | R_p(q_pe_h)]``. ``R_p`` at position p turns the PAIR OF
LANES ``(2i, 2i + 1)`` (``rope_interleave``) by ``p * rope_theta^(-2i /
qk_rope_head_dim)``: ``(a, b) -> (a cos - b sin, b cos + a sin)``, written
back to lanes ``2i`` and ``2i + 1`` — the pairing as published, nothing
de-interleaved; angles in float32; ``rope_scaling`` null: no scaling, no
``mscale``. Causal softmax(q k^T / sqrt(192)) v with k MATERIALISED a head,
a block of queries at a time; ``attn = o W_o``.

**Experts**: ``s = sigmoid(u W_r)`` over all ``published.n_routed_experts``
(``scoring_func``); the ``num_experts_per_tok`` largest of ``s + correction
bias`` (``topk_method`` noaux_tc with ``n_group`` 1: a plain top-k), weights
the UNBIASED ``s`` of the chosen over their sum (``norm_topk_prob``) times
``routed_scaling_factor``; an expert is ``W_down (silu(W_gate u) * W_up
u)``; the weighted sum over the chosen experts THAT ARE HELD
(``held_experts_first .. + n_routed_experts``), every held expert computed
for every token one at a time, plus ONE ungated shared expert of the same
form. What the absent experts would add is left out, here as in the system.

A final RMSNorm, a linear policy head and a 2-layer tanh value head.
Departures from the source, each also in
``benchmark/configs/joyai-flash-policy.json``: a Dense observation embedding
in place of the 129,280-row token table, the small heads in place of the
vocabulary head, 6 of 40 layers, 16 of 256 experts held, no multi-token
prediction layer.

``forward(..., operands=<dtype>)`` rounds both operands of every matmul the
configuration computes in bfloat16 (projections, attention, experts; not
the router, the norms, the rotation's angles, the embedding or the heads)
to ``<dtype>`` and accumulates in float32. ``forward(..., wrong={...})``
computes a deliberately different layer, the readings the limits of the
comparison are set against (PERF.md section 6): ``no_rope`` (True: no lane
turns), ``half_split`` (True: the OTHER pairing, lanes ``(i, i + 32)``),
``no_q_norm`` (True: ``(u W_qa) W_qb`` without the RMSNorm), ``scale_128``
(True: scores over ``sqrt(qk_nope_head_dim)``), ``top_k`` (7: ``top7``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import flops, flops_joyai, flops_lfm2

Q_BLOCK = 256  # queries a step of the reference's attention


def program_kwargs(cfg: dict) -> dict:
    """The configuration in the program's own hyper-parameter names. A
    program that does not take one of them (the parent of the PR that added
    this configuration) would quietly build another model: refuse."""
    for key, want in (("hidden_act", "silu"), ("norm_topk_prob", True),
                      ("scoring_func", "sigmoid"),
                      ("topk_method", "noaux_tc"), ("n_group", 1),
                      ("topk_group", 1), ("moe_layer_freq", 1),
                      ("n_shared_experts", 1), ("attention_bias", False),
                      ("rope_scaling", None), ("rope_interleave", True)):
        if cfg[key] != want:
            raise SystemExit(
                f"benchmark: REFUSED {key} {cfg[key]!r}: the reference and "
                f"the program are written for {want!r}")
    layers = int(cfg["num_hidden_layers"])
    kwargs = {
        "model_kind": "transformer_moe_discrete",
        "d_model": cfg["hidden_size"],
        "n_layers": layers,
        "layer_types": ["latent_attention"] * layers,
        "n_heads": cfg["num_attention_heads"],
        "max_seq_len": cfg["positions_as_run"],
        "attention": cfg["attention"],
        "norm": "rms", "norm_eps": cfg["rms_norm_eps"],
        "positions": "rope", "rope_theta": cfg["rope_theta"],
        "rope_interleave": cfg["rope_interleave"], "use_bias": False,
        "q_lora_rank": cfg["q_lora_rank"],
        "kv_lora_rank": cfg["kv_lora_rank"],
        "qk_nope_head_dim": cfg["qk_nope_head_dim"],
        "qk_rope_head_dim": cfg["qk_rope_head_dim"],
        "v_head_dim": cfg["v_head_dim"],
        "ffn": "swiglu", "d_ff": cfg["intermediate_size"],
        "moe_dense_layers": cfg["first_k_dense_replace"],
        "moe_experts": cfg["published"]["n_routed_experts"],
        "moe_top_k": cfg["num_experts_per_tok"],
        "moe_d_ff": cfg["moe_intermediate_size"],
        "moe_router": "sigmoid", "moe_expert_bias": True,
        "moe_norm_topk_prob": cfg["norm_topk_prob"],
        "moe_routed_scaling": cfg["routed_scaling_factor"],
        "moe_shared_d_ff": (cfg["n_shared_experts"]
                            * cfg["moe_intermediate_size"]),
        "moe_held": [cfg["held_experts_first"], cfg["n_routed_experts"]],
        "block_checkpoint": cfg["block_checkpoint"],
    }
    from relayrl_tpu.models.base import ARCH_PASSTHROUGH_KEYS

    unknown = sorted(k for k in kwargs
                     if k != "model_kind" and k not in ARCH_PASSTHROUGH_KEYS)
    if unknown:
        raise SystemExit(
            f"benchmark: REFUSED this program's models take no arch keys "
            f"{unknown}: it cannot build joyai-flash-policy")
    return kwargs


def train_flops_per_sample(cfg: dict, seq_len: int) -> float:
    """Forward + backward operations a token: every layer's latent
    projections and causal scores, the dense FFN, the expert layers at the
    work of the experts this chip holds at EVEN routing (0.5 token-slot a
    token and layer), their shared expert and router."""
    return (flops.TRAIN_OVER_FWD
            * flops_joyai.joyai_fwd_flops_per_token(cfg, seq_len))


def held_grouped_matmul_train_ops_bytes(cfg: dict, held_slots: float):
    """(operations, bytes) of one update's grouped matmuls over the
    ``held_slots`` token-slots the run itself counted (all expert layers),
    three stacks an expert."""
    return flops_lfm2.held_grouped_matmul_train_ops_bytes(
        held_slots,
        int(cfg["num_hidden_layers"]) - int(cfg["num_dense_layers"]),
        int(cfg["n_routed_experts"]), int(cfg["hidden_size"]),
        int(cfg["moe_intermediate_size"]))


def mla_flash_train_ops_bytes(cfg: dict, batch: int, seq_len: int):
    """(operations, bytes) of one update's latent-attention flash kernels
    at 192 real lanes of q / k and 128 of v, every layer."""
    return flops_joyai.mla_flash_train_ops_bytes(cfg, batch, seq_len)


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rounded(a, operands):
    """``operands``: a dtype's name, or None."""
    return a if operands is None else a.astype(operands).astype(jnp.float32)


def _dense(p, x):
    return x @ _f32(p["kernel"]) + _f32(p["bias"])


def _rms_norm(p, x, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * _f32(p["scale"])


def _rope(x, theta, interleaved):
    """``x [B, T, H, hd]``, row j at position j. ``interleaved``: the pair
    of lanes ``(2i, 2i + 1)`` turns by ``j * theta^(-2i / hd)`` and is
    written back where it was (the model's); else the pair ``(i, i + hd /
    2)`` (the wrong reference ``half_split``)."""
    hd = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    if interleaved:
        pairs = x.reshape(x.shape[:-1] + (hd // 2, 2))
        a, b = pairs[..., 0], pairs[..., 1]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         -1).reshape(x.shape)
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _latent_attention(p, x, widths, eps, theta, operands, as_run):
    heads, rank, nope, pe, vd = widths
    b, t, _ = x.shape
    r = functools.partial(_rounded, operands=operands)
    u = r(_rms_norm(p["ln_attn"], x, eps))
    c_q = u @ r(_f32(p["q_a"]["kernel"]))
    if not as_run["no_q_norm"]:
        c_q = _rms_norm(p["q_a_norm"], c_q, eps)
    q = (r(c_q) @ r(_f32(p["q_b"]["kernel"]))).reshape(b, t, heads, nope + pe)
    c, k_pe = jnp.split(u @ r(_f32(p["kv_a"]["kernel"])), [rank], axis=-1)
    kv = (r(_rms_norm(p["kv_a_norm"], c, eps))
          @ r(_f32(p["kv_b"]["kernel"]))).reshape(b, t, heads, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k_pe = k_pe[:, :, None]                        # one head, read by all
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    if not as_run["no_rope"]:
        k_pe = _rope(k_pe, theta, not as_run["half_split"])
        q_pe = _rope(q_pe, theta, not as_run["half_split"])
    # q and k materialised a head: [nope_h | R_p(pe)]
    q = jnp.concatenate([q_nope, q_pe], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, (b, t, heads, pe))], -1)
    q, k, v = r(q), r(k), r(v)
    step = min(Q_BLOCK, t)
    key_pos = jnp.arange(t)
    width = nope if as_run["scale_128"] else nope + pe

    def rows(start):
        q_blk = jax.lax.dynamic_slice_in_dim(q, start, step, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) / jnp.sqrt(
            jnp.float32(width))
        seen = (start + jnp.arange(step))[:, None] >= key_pos[None, :]
        p_blk = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", r(p_blk), v)

    attn = jax.lax.map(rows, jnp.arange(0, t, step))    # [t/step, b, step..]
    attn = jnp.moveaxis(attn, 0, 1).reshape(b, t, heads * vd)
    return x + r(attn) @ r(_f32(p["attn_out"]["kernel"]))


def _swiglu(u, w_gate, w_up, w_down, r):
    inner = jax.nn.silu(u @ r(_f32(w_gate))) * (u @ r(_f32(w_up)))
    return r(inner) @ r(_f32(w_down))


def _dense_ffn(p, x, eps, operands):
    r = functools.partial(_rounded, operands=operands)
    u = r(_rms_norm(p["ln_mlp"], x, eps))
    return x + _swiglu(u, p["mlp_gate"]["kernel"], p["mlp_up"]["kernel"],
                       p["mlp_down"]["kernel"], r)


def _route(moe, u, top_k, scaling, first, held):
    """Combine weights ``[N, held]`` from the rows the router reads: zero
    off the top-k (chosen on score + bias), the chosen experts' unbiased
    scores over their sum, times ``scaling``; the held columns only."""
    s = jax.nn.sigmoid(u @ _f32(moe["moe_gate"]["kernel"]))
    biased = s + _f32(moe["moe_expert_bias"])
    kth = jax.lax.top_k(biased, top_k)[0][:, -1:]
    w = jnp.where(biased >= kth, s, 0.0)
    w = scaling * w / jnp.sum(w, -1, keepdims=True)
    return w[:, first:first + held]


def _experts(p, x, eps, scaling, first, held, operands, as_run):
    """``x + `` every held expert on every token, one expert at a time, a
    loop over the held range, and the shared expert."""
    r = functools.partial(_rounded, operands=operands)
    u32 = _rms_norm(p["ln_mlp"], x, eps)
    u32 = u32.reshape(-1, u32.shape[-1])
    moe = p["moe"]
    w = _route(moe, u32, as_run["top_k"], scaling, first, held)  # float32
    u = r(u32)

    def one(acc, e):
        w_gate, w_up, w_down, w_e = e
        return acc + w_e[:, None] * _swiglu(u, w_gate, w_up, w_down, r), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        moe["moe_w_gate"], moe["moe_w_up"], moe["moe_w_down"], w.T))
    out = out + _swiglu(u, moe["moe_shared_gate"]["kernel"],
                        moe["moe_shared_up"]["kernel"],
                        moe["moe_shared_down"]["kernel"], r)
    return x + out.reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("shape", "as_run", "operands"))
def _forward(params, obs, shape, as_run, operands):
    """The whole forward as ONE program, computed in blocks (queries a
    block, experts one at a time): its temporaries are one layer's, reused.
    (A Python loop of jitted parts has the runtime allocate every part's
    temporaries at once as the host runs ahead of the device: PERF.md
    section 6, PR 34.)"""
    layers, dense, mla, eps, theta, scaling, first, held = shape
    as_run = dict(as_run)
    p = params["params"]
    with jax.default_matmul_precision("highest"):
        x = _dense(p["obs_embed"], obs)
        for i in range(layers):
            blk = p[f"block_{i}"]
            x = _latent_attention(blk, x, mla, eps, theta, operands, as_run)
            if i < dense:
                x = _dense_ffn(blk, x, eps, operands)
            else:
                x = _experts(blk, x, eps, scaling, first, held, operands,
                             as_run)
        x = _rms_norm(p["ln_final"], x, eps)
        logits = _dense(p["pi_head"], x)
        v = _dense(p["vf_head"], jnp.tanh(_dense(p["vf_head_up"], x)))
        return jax.nn.log_softmax(logits, -1), v[..., 0]


def forward(params, obs, cfg: dict, operands=None, wrong=None):
    """``obs [B, T, obs_dim]`` -> (log-probabilities ``[B, T, act_dim]``,
    values ``[B, T]``)."""
    as_run = {"no_rope": False, "half_split": False, "no_q_norm": False,
              "scale_128": False, "top_k": int(cfg["num_experts_per_tok"]),
              **(wrong or {})}
    mla = (int(cfg["num_attention_heads"]), int(cfg["kv_lora_rank"]),
           int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"]),
           int(cfg["v_head_dim"]))
    shape = (int(cfg["num_hidden_layers"]),
             int(cfg["first_k_dense_replace"]), mla,
             float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]),
             float(cfg["routed_scaling_factor"]),
             int(cfg["held_experts_first"]), int(cfg["n_routed_experts"]))
    return _forward(params, _f32(obs), shape, tuple(sorted(as_run.items())),
                    None if operands is None else jnp.dtype(operands).name)
