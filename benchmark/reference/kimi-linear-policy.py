"""Plain reference for ``kimi-linear-policy``: the layers of
Kimi-Linear-48B-A3B-Instruct's ``config`` (Moonshot AI, ``model_type``
kimi_linear; "Kimi Linear: An Expressive, Efficient Attention Architecture",
arXiv:2510.26692) as the trunk of an observation-in, action-out policy, in
float32 ``jax.numpy`` at matmul precision "highest". No kernels, no cache, no
flax, no chunk, no sparse dispatch, no code of ``relayrl_tpu/models`` or
``relayrl_tpu/ops``; it reads the system's parameter tree as data.
(``program_kwargs``, which is no part of the forward, looks at one tuple of
names there, ``ARCH_PASSTHROUGH_KEYS``, to refuse a program that would drop
this configuration's keys.)

Every layer is ``x <- x + mixer(norm(x))``, ``x <- x + ffn(norm(x))``,
RMSNorm at ``rms_norm_eps``, no bias in any projection. Layer ``i``
(1-based) is a KDA layer where ``linear_attn_config.kda_layers`` lists it,
a latent-attention layer where ``full_attn_layers`` does; the first
``first_k_dense_replace`` layers end in a dense SwiGLU FFN, the others in
the expert layer.

**KDA, Kimi Delta Attention** (H = ``num_heads`` heads of K =
``head_dim`` keys and as many values, u = norm(x)):
  ``[q | k | v] = u W_qkv``; ``[q | k | v] <- silu(conv([q | k | v]))``,
  depthwise, causal, ``short_conv_kernel_size`` taps, no bias;
  ``beta = sigmoid(u W_beta)`` a head;
  ``g = -exp(A_log) softplus((u W_f_down) W_f_up + dt_bias)`` a key LANE
  (``A_log`` a head, ``dt_bias`` a lane): ``alpha = exp(g)`` in (0, 1]^K;
  ``q <- q / |q| / sqrt(K)``, ``k <- k / |k|`` a head (eps 1e-6 under the
  root);
  **the state equation one token at a time**, a ``lax.scan`` over T with a
  state ``S [H, K, K]``: ``S~ = Diag(alpha_t) S`` — a decay a key lane —,
  ``S = S~ + beta_t k_t (v_t - S~^T k_t)^T`` (the rank-one correction:
  ``(I - beta_t k_t k_t^T) Diag(alpha_t) S + beta_t k_t v_t^T``), ``o_t =
  S^T q_t``;
  ``y = RMSNorm_K(o) w * sigmoid((u W_g_down) W_g_up + b_g)`` a head — the
  norm, plain weight, THEN the gate —, ``mixer = y W_out``.

**Latent attention, NoPE** (``num_attention_heads`` heads): ``q = u W_q`` a
head of ``qk_nope_head_dim + qk_rope_head_dim``; ``[c | k_pe] = u W_kva``
(``kv_lora_rank`` + ``qk_rope_head_dim``); ``[k_nope | v] = RMSNorm(c)
W_kvb`` a head of ``qk_nope_head_dim + v_head_dim``; head h's key is
``[k_nope_h | k_pe]``, ``k_pe`` shared by every head; ``mla_use_nope``: no
rotation of any lane; causal softmax(q k^T / sqrt(192)) v with k
MATERIALISED a head, a block of queries at a time; ``mixer = attn W_o``.

**Experts**: ``s = sigmoid(u W_r)`` over all ``published.num_experts``; the
``num_experts_per_token`` largest of ``s + correction bias`` (one group:
``use_grouped_topk`` with ``num_expert_group`` 1 is a plain top-k), weights
the UNBIASED ``s`` of the chosen over their sum (``moe_renormalize``) times
``routed_scaling_factor``; an expert is ``W_down (silu(W_gate u) * W_up
u)``; the weighted sum over the chosen experts THAT ARE HELD
(``held_experts_first .. + num_experts``), every held expert computed for
every token one at a time, plus ONE ungated shared expert of the same form.
What the absent experts would add is left out, here as in the system.

A final RMSNorm, a linear policy head and a 2-layer tanh value head.
Departures from the source, each also in
``benchmark/configs/kimi-linear-policy.json``: a Dense observation embedding
in place of the 163,840-row token table, the small heads in place of the
vocabulary head, 5 of 27 layers, 8 of 256 experts held; the sizes the
config does not give (the low-rank paths' inner width, the gate's bias, the
``1 / sqrt(K)`` on q) are ``assumed`` there.

``forward(..., operands=<dtype>)`` rounds both operands of every matmul the
configuration computes in bfloat16 (projections, attention, experts; the
rule's ``q``, ``k`` and ``v``; not the router, the norms, ``beta``, ``g``,
the state, the embedding or the heads) to ``<dtype>`` and accumulates in
float32. ``forward(..., wrong={...})`` computes a deliberately different
layer, the readings the limits of the comparison are set against (PERF.md
section 6): ``scalar_decay`` (True: each head's ``g`` replaced by its mean
over the K lanes — the gated delta rule in KDA's place), ``rope`` (True:
the shared ``k_pe`` lanes and q's matching lanes rotated at ``rope_theta``),
``no_latent_norm`` (True: ``c W_kvb`` without the RMSNorm), ``bf16`` (True:
the whole reference on bfloat16 operands, the rule's state, ``g`` and
``beta`` included), ``top_k``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import flops, flops_kimi_linear, flops_lfm2

Q_BLOCK = 256  # queries a step of the reference's attention


def program_kwargs(cfg: dict) -> dict:
    """The configuration in the program's own hyper-parameter names. A
    program that does not take one of them (the parent of the PR that added
    this configuration) would quietly build another model: refuse."""
    for key, want in (("hidden_act", "silu"), ("moe_renormalize", True),
                      ("moe_router_activation_func", "sigmoid"),
                      ("num_expert_group", 1), ("topk_group", 1),
                      ("moe_layer_freq", 1), ("num_shared_experts", 1),
                      ("q_lora_rank", None), ("mla_use_nope", True),
                      ("rope_scaling", None),
                      ("num_nextn_predict_layers", 0)):
        if cfg[key] != want:
            raise SystemExit(
                f"benchmark: REFUSED {key} {cfg[key]!r}: the reference and "
                f"the program are written for {want!r}")
    lin = cfg["linear_attn_config"]
    kwargs = {
        "model_kind": "transformer_moe_discrete",
        "d_model": cfg["hidden_size"],
        "n_layers": cfg["num_hidden_layers"],
        "layer_types": flops_kimi_linear.layer_kinds(cfg),
        "n_heads": cfg["num_attention_heads"],
        "max_seq_len": cfg["positions_as_run"],
        "attention": cfg["attention"],
        "norm": "rms", "norm_eps": cfg["rms_norm_eps"],
        "positions": "none", "use_bias": False,
        "kda_heads": lin["num_heads"], "kda_head_dim": lin["head_dim"],
        "kda_conv_taps": lin["short_conv_kernel_size"],
        "kda_chunk": cfg["kda_chunk"],
        "kv_lora_rank": cfg["kv_lora_rank"],
        "qk_nope_head_dim": cfg["qk_nope_head_dim"],
        "qk_rope_head_dim": cfg["qk_rope_head_dim"],
        "v_head_dim": cfg["v_head_dim"],
        "ffn": "swiglu", "d_ff": cfg["intermediate_size"],
        "moe_dense_layers": cfg["first_k_dense_replace"],
        "moe_experts": cfg["published"]["num_experts"],
        "moe_top_k": cfg["num_experts_per_token"],
        "moe_d_ff": cfg["moe_intermediate_size"],
        "moe_router": "sigmoid", "moe_expert_bias": True,
        "moe_norm_topk_prob": cfg["moe_renormalize"],
        "moe_routed_scaling": cfg["routed_scaling_factor"],
        "moe_shared_d_ff": (cfg["num_shared_experts"]
                            * cfg["moe_intermediate_size"]),
        "moe_held": [cfg["held_experts_first"], cfg["num_experts"]],
        "block_checkpoint": cfg["block_checkpoint"],
    }
    from relayrl_tpu.models.base import ARCH_PASSTHROUGH_KEYS

    unknown = sorted(k for k in kwargs
                     if k != "model_kind" and k not in ARCH_PASSTHROUGH_KEYS)
    if unknown:
        raise SystemExit(
            f"benchmark: REFUSED this program's models take no arch keys "
            f"{unknown}: it cannot build kimi-linear-policy")
    return kwargs


def train_flops_per_sample(cfg: dict, seq_len: int) -> float:
    """Forward + backward operations a token: the KDA layers' projections
    and rule, the latent-attention layer's projections and causal scores,
    the dense FFN, the expert layers at the work of the experts this chip
    holds at EVEN routing (0.25 token-slots a token and layer), their shared
    expert and router."""
    return (flops.TRAIN_OVER_FWD
            * flops_kimi_linear.kimi_linear_fwd_flops_per_token(cfg, seq_len))


def held_grouped_matmul_train_ops_bytes(cfg: dict, held_slots: float):
    """(operations, bytes) of one update's grouped matmuls over the
    ``held_slots`` token-slots the run itself counted (all expert layers),
    three stacks an expert."""
    return flops_lfm2.held_grouped_matmul_train_ops_bytes(
        held_slots,
        int(cfg["num_hidden_layers"]) - int(cfg["num_dense_layers"]),
        int(cfg["num_experts"]), int(cfg["hidden_size"]),
        int(cfg["moe_intermediate_size"]))


def kda_train_ops_bytes(cfg: dict, batch: int, seq_len: int):
    """(operations, bytes) of one update's rules, every KDA layer, forward
    and backward."""
    return flops_kimi_linear.kda_train_ops_bytes(cfg, batch, seq_len)


def mla_flash_train_ops_bytes(cfg: dict, batch: int, seq_len: int):
    """(operations, bytes) of one update's latent-attention flash kernels
    at 192 real lanes of q / k and 128 of v."""
    return flops_kimi_linear.mla_flash_train_ops_bytes(cfg, batch, seq_len)


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rounded(a, operands):
    """``operands``: a dtype's name, or None."""
    return a if operands is None else a.astype(operands).astype(jnp.float32)


def _to_bfloat16(a):
    """``a`` rounded to bfloat16's 8 exponent and 7 mantissa bits, still
    float32."""
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _dense(p, x):
    return x @ _f32(p["kernel"]) + _f32(p["bias"])


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)


def _rms_norm(p, x, eps):
    return _rms(x, eps) * _f32(p["scale"])


def _kda(p, x, widths, eps, operands, as_run):
    """The KDA mixer, its state equation one token at a time."""
    h, kd, taps = widths
    b, t, _ = x.shape
    width = h * kd
    r = functools.partial(_rounded, operands=operands)
    # the wrong reference ``bf16`` rounds what the configuration keeps in
    # float32 too: g, beta and the carried state, a token a step — through
    # ``reduce_precision``, which no compiler may drop (a pair of converts
    # back to float32 it may: XLA's excess-precision rule)
    low = _to_bfloat16 if as_run["bf16"] else (lambda a: a)
    u = r(_rms_norm(p["ln_attn"], x, eps))
    qkv = u @ r(_f32(p["kda_in_qkv"]))
    beta = low(jax.nn.sigmoid(u @ r(_f32(p["kda_in_beta"]))))    # [b, t, H]
    f = r(u @ r(_f32(p["kda_f_down"]))) @ r(_f32(p["kda_f_up"]))
    g = -jnp.repeat(jnp.exp(_f32(p["kda_A_log"])), kd) * jax.nn.softplus(
        f + _f32(p["kda_dt_bias"]))
    g = g.reshape(b, t, h, kd)                          # log alpha, a lane
    if as_run["scalar_decay"]:  # one decay a head: the gated delta rule
        g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
    g = low(g)
    gate = jax.nn.sigmoid(
        r(u @ r(_f32(p["kda_g_down"]))) @ r(_f32(p["kda_g_up"]))
        + _f32(p["kda_g_bias"]))
    # depthwise causal convolution over q, k and v together, SiLU, no bias
    w = _f32(p["kda_conv_w"])
    padded = jnp.pad(r(qkv), ((0, 0), (taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(w[j] * padded[:, j:j + t] for j in range(taps)))
    q, k, v = (a.reshape(b, t, h, kd) for a in jnp.split(r(qkv), 3, axis=-1))
    q, k = (a * jax.lax.rsqrt(jnp.sum(jnp.square(a), -1, keepdims=True)
                              + 1e-6) for a in (q, k))
    q = q / jnp.sqrt(jnp.float32(kd))
    q, k = r(q), r(k)

    def one(s, row):
        q_t, k_t, v_t, g_t, beta_t = row
        s = low(jnp.exp(g_t)[..., None] * s)            # Diag(alpha_t) S
        v_t = beta_t[..., None] * (
            v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t))
        s = low(s + k_t[..., :, None] * v_t[..., None, :])
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    _, o = jax.lax.scan(
        one, jnp.zeros((b, h, kd, kd), jnp.float32),
        tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    o = jnp.moveaxis(o, 0, 1)                                 # [b, t, H, K]
    y = _rms(o, eps) * _f32(p["kda_norm"]) * gate.reshape(b, t, h, kd)
    return x + r(y.reshape(b, t, width)) @ r(_f32(p["kda_out"]))


def _rope(x, theta):
    """``x [B, T, H, hd]``, row j at position j: pairs (i, i + hd / 2) turn
    by ``j * theta^(-2i / hd)`` (the wrong reference ``rope``)."""
    hd = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _latent_attention(p, x, widths, eps, theta, operands, as_run):
    heads, rank, nope, pe, vd = widths
    b, t, _ = x.shape
    r = functools.partial(_rounded, operands=operands)
    h = r(_rms_norm(p["ln_attn"], x, eps))
    q = (h @ r(_f32(p["q_proj"]["kernel"]))).reshape(b, t, heads, nope + pe)
    c, k_pe = jnp.split(h @ r(_f32(p["kv_a"]["kernel"])), [rank], axis=-1)
    if not as_run["no_latent_norm"]:
        c = _rms_norm(p["kv_a_norm"], c, eps)
    kv = (r(c) @ r(_f32(p["kv_b"]["kernel"]))).reshape(
        b, t, heads, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k_pe = k_pe[:, :, None]                        # one head, read by all
    if as_run["rope"]:
        k_pe = _rope(k_pe, theta)
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    # k materialised a head: [k_nope_h | k_pe]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, (b, t, heads, pe))], -1)
    q, k, v = r(q), r(k), r(v)
    step = min(Q_BLOCK, t)
    key_pos = jnp.arange(t)

    def rows(start):
        q_blk = jax.lax.dynamic_slice_in_dim(q, start, step, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) / jnp.sqrt(
            jnp.float32(nope + pe))
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
    block, experts one at a time, the rule a token a step): its temporaries
    are one layer's, reused. (A Python loop of jitted parts has the runtime
    allocate every part's temporaries at once as the host runs ahead of the
    device: PERF.md section 6, PR 34.)"""
    kinds, dense, kda, mla, eps, theta, scaling, first, held = shape
    as_run = dict(as_run)
    p = params["params"]
    with jax.default_matmul_precision("highest"):
        x = _dense(p["obs_embed"], obs)
        for i, kind in enumerate(kinds):
            blk = p[f"block_{i}"]
            if kind == "kda":
                x = _kda(blk, x, kda, eps, operands, as_run)
            else:
                x = _latent_attention(blk, x, mla, eps, theta, operands,
                                      as_run)
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
    as_run = {"scalar_decay": False, "rope": False, "no_latent_norm": False,
              "bf16": False, "top_k": int(cfg["num_experts_per_token"]),
              **(wrong or {})}
    if as_run["bf16"]:
        operands = "bfloat16"
    lin = cfg["linear_attn_config"]
    kda = (int(lin["num_heads"]), int(lin["head_dim"]),
           int(lin["short_conv_kernel_size"]))
    mla = (int(cfg["num_attention_heads"]), int(cfg["kv_lora_rank"]),
           int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"]),
           int(cfg["v_head_dim"]))
    shape = (tuple(flops_kimi_linear.layer_kinds(cfg)),
             int(cfg["first_k_dense_replace"]), kda, mla,
             float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]),
             float(cfg["routed_scaling_factor"]),
             int(cfg["held_experts_first"]), int(cfg["num_experts"]))
    return _forward(params, _f32(obs), shape, tuple(sorted(as_run.items())),
                    None if operands is None else jnp.dtype(operands).name)
