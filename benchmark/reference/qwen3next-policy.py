"""Plain reference for ``qwen3next-policy``: the layers of
Qwen3-Next-80B-A3B-Instruct's ``config`` (Qwen, ``model_type`` qwen3_next)
as the trunk of an observation-in, action-out policy, in float32
``jax.numpy`` at matmul precision "highest". No kernels, no cache, no flax,
no chunked delta rule, no sparse dispatch, no code of ``relayrl_tpu/models``
or ``relayrl_tpu/ops``; it reads the system's parameter tree as data.
(``program_kwargs``, which is no part of the forward, looks at one tuple of
names there, ``ARCH_PASSTHROUGH_KEYS``, to refuse a program that would drop
this configuration's keys.)

Every layer is ``x <- x + mixer(norm(x))``, ``x <- x + experts(norm(x))``;
every RMSNorm but the linear layers' gated one is zero-centred, ``x^ (1 +
w)`` at ``rms_norm_eps``; no bias anywhere. Layer ``i`` is full attention
where ``(i + 1) % full_attention_interval == 0``, linear attention otherwise.

**Linear attention, Gated DeltaNet** (Hk = ``linear_num_key_heads`` heads
of K = ``linear_key_head_dim`` for q and k, H = ``linear_num_value_heads``
heads of V = ``linear_value_head_dim`` for v and z, u = norm(x)):
  ``[q | k | v | z] = u W_qkvz``, ``[b | a] = u W_ba``;
  ``[q | k | v] <- silu(conv([q | k | v]))``, depthwise, causal,
  ``linear_conv_kernel_dim`` taps, no bias;
  ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``;
  ``q <- q / |q| / sqrt(K)``, ``k <- k / |k|`` a head (eps 1e-6 under the
  root); value head h reads key head ``h // (H / Hk)``;
  **the delta rule one token at a time**, a ``lax.scan`` over T with a
  state ``S [H, K, V]``: ``S~ = exp(g_t) S``, ``S = S~ + beta_t k_t (v_t -
  S~^T k_t)^T``, ``o_t = S^T q_t``;
  ``y = RMSNorm_V(o) w * silu(z)`` a head — the norm BEFORE the gate, plain
  weight —, ``mixer = y W_out``.

**Full attention, gated**: ``[q | gate] = u W_q`` a head (``head_dim`` query
lanes, then ``head_dim`` gate lanes), ``k, v = u W_k, u W_v``; q and k
RMS-normed a head (zero-centred weight); RoPE at ``rope_theta`` on the FIRST
``partial_rotary_factor * head_dim`` lanes (rotate-half within them), the
others untouched; causal softmax(q k^T / sqrt(head_dim)) v,
``num_attention_heads`` q heads over ``num_key_value_heads``; ``mixer =
(attn * sigmoid(gate)) W_o``. Computed a block of queries at a time.

**Experts**: ``p = softmax(u W_r)`` over all ``published.num_experts``; the
``num_experts_per_tok`` largest, normalised to sum 1; an expert is
``W_down (silu(W_gate u) * W_up u)``; the weighted sum over the chosen
experts THAT ARE HELD (``held_experts_first .. + num_experts``), every held
expert computed for every token one at a time, plus ONE shared expert of
the same form times ``sigmoid(u w_s)``. What the absent experts would add
is left out, here as in the system.

A final RMSNorm, a linear policy head and a 2-layer tanh value head.
Departures from the source, each also in
``benchmark/configs/qwen3next-policy.json``: a Dense observation embedding
in place of the 151,936-row token table, the small heads in place of the
vocabulary head, 4 of 48 layers, 32 of 512 experts held; multi-token
prediction is not in the ``config`` and not built.

``forward(..., operands=<dtype>)`` rounds both operands of every matmul the
configuration computes in bfloat16 (projections, attention, experts; the
rule's ``q``, ``k`` and ``v``; not the router, the norms, ``beta``, ``g``,
the state, the shared expert's gate, the embedding or the heads) to
``<dtype>`` and accumulates in float32: the same reference in a lower
precision. ``forward(..., wrong={...})`` computes a deliberately different
layer — ``carry`` (False: the state starts from zero every ``gdn_chunk``
tokens), ``beta`` (False: ``S = S~ + k v^T``, a gated linear attention with
no delta term), ``decay`` (False: ``g = 0``), ``l2`` (False: q and k not
normalised, q still over sqrt(K)), ``gate`` (``"before"``: ``RMSNorm(o *
silu(z))``), ``attn_gate`` (False), ``rope_share`` (1.0: every lane turns),
``centred`` (False: ``w`` for ``1 + w``), ``shared_gate`` (False),
``top_k`` —: the readings the limits of the comparison are set against
(PERF.md section 6).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import flops, flops_lfm2, flops_qwen3next

Q_BLOCK = 256  # queries a step of the reference's attention


def program_kwargs(cfg: dict) -> dict:
    """The configuration in the program's own hyper-parameter names. A
    program that does not take one of them (the parent of the PR that added
    this configuration) would quietly build another model: refuse."""
    for key, want in (("hidden_act", "silu"), ("norm_topk_prob", True),
                      ("decoder_sparse_step", 1), ("mlp_only_layers", []),
                      ("rope_scaling", None)):
        if cfg[key] != want:
            raise SystemExit(
                f"benchmark: REFUSED {key} {cfg[key]!r}: the reference and "
                f"the program are written for {want!r}")
    kwargs = {
        "model_kind": "transformer_moe_discrete",
        "d_model": cfg["hidden_size"],
        "n_layers": cfg["num_hidden_layers"],
        "layer_types": flops_qwen3next.layer_kinds(cfg),
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "max_seq_len": cfg["positions_as_run"],
        "attention": cfg["attention"],
        "norm": "rms", "norm_eps": cfg["rms_norm_eps"],
        "norm_zero_centred": True,
        "positions": "rope", "rope_theta": cfg["rope_theta"],
        "rope_share": cfg["partial_rotary_factor"],
        "qk_norm": "head", "attn_gate": True, "use_bias": False,
        "gdn_key_heads": cfg["linear_num_key_heads"],
        "gdn_value_heads": cfg["linear_num_value_heads"],
        "gdn_key_dim": cfg["linear_key_head_dim"],
        "gdn_value_dim": cfg["linear_value_head_dim"],
        "gdn_conv_taps": cfg["linear_conv_kernel_dim"],
        "gdn_chunk": cfg["gdn_chunk"],
        "ffn": "swiglu",
        "moe_experts": cfg["published"]["num_experts"],
        "moe_top_k": cfg["num_experts_per_tok"],
        "moe_d_ff": cfg["moe_intermediate_size"],
        "moe_norm_topk_prob": cfg["norm_topk_prob"],
        "moe_shared_d_ff": cfg["shared_expert_intermediate_size"],
        "moe_shared_expert_gate": True,
        "moe_held": [cfg["held_experts_first"], cfg["num_experts"]],
    }
    from relayrl_tpu.models.base import ARCH_PASSTHROUGH_KEYS

    unknown = sorted(k for k in kwargs
                     if k != "model_kind" and k not in ARCH_PASSTHROUGH_KEYS)
    if unknown:
        raise SystemExit(
            f"benchmark: REFUSED this program's models take no arch keys "
            f"{unknown}: it cannot build qwen3next-policy")
    return kwargs


def train_flops_per_sample(cfg: dict, seq_len: int) -> float:
    """Forward + backward operations a token: the linear-attention layers'
    projections and delta rule, the attention layer's projections and causal
    scores, the expert layers at the work of the experts this chip holds at
    EVEN routing (0.625 token-slots a token and layer), their shared expert
    and router."""
    return (flops.TRAIN_OVER_FWD
            * flops_qwen3next.qwen3next_fwd_flops_per_token(cfg, seq_len))


def held_grouped_matmul_train_ops_bytes(cfg: dict, held_slots: float):
    """(operations, bytes) of one update's grouped matmuls over the
    ``held_slots`` token-slots the run itself counted (all expert layers),
    three stacks an expert."""
    return flops_lfm2.held_grouped_matmul_train_ops_bytes(
        held_slots, int(cfg["num_hidden_layers"]), int(cfg["num_experts"]),
        int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"]))


def flash_gqa_train_ops_bytes(cfg: dict, batch: int, seq_len: int):
    """(operations, bytes) of one update's flash kernels: the full-attention
    layers' causal scores at head_dim 256, k/v at their own head count."""
    ops, nbytes = flops_lfm2.flash_gqa_train_ops_bytes(
        batch, int(cfg["num_attention_heads"]),
        int(cfg["num_key_value_heads"]), seq_len, int(cfg["head_dim"]))
    layers = flops_qwen3next.layer_kinds(cfg).count("full_attention")
    return layers * ops, layers * nbytes


def gdn_train_ops_bytes(cfg: dict, batch: int, seq_len: int):
    """(operations, bytes) of one update's delta rules, every
    linear-attention layer, forward and backward."""
    return flops_qwen3next.gdn_train_ops_bytes(cfg, batch, seq_len)


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rounded(a, operands):
    """``operands``: a dtype's name, or None."""
    return a if operands is None else a.astype(operands).astype(jnp.float32)


def _dense(p, x):
    return x @ _f32(p["kernel"]) + _f32(p["bias"])


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)


def _rms_norm(p, x, eps, centred):
    """The zero-centred norm, ``x^ (1 + w)`` (``centred`` False: the wrong
    reference's ``x^ w``)."""
    w = _f32(p["scale"])
    return _rms(x, eps) * (1.0 + w if centred else w)


def _linear_attention(p, x, widths, eps, operands, as_run):
    """The Gated DeltaNet mixer, its delta rule one token at a time."""
    hk, h, kd, vd, taps, chunk = widths
    b, t, _ = x.shape
    kw, vw = hk * kd, h * vd
    r = functools.partial(_rounded, operands=operands)
    u = r(_rms_norm(p["ln_attn"], x, eps, as_run["centred"]))
    qkv, z = jnp.split(u @ r(_f32(p["gdn_in_qkvz"])), [2 * kw + vw], axis=-1)
    b_in, a_in = jnp.split(u @ r(_f32(p["gdn_in_ba"])), 2, axis=-1)
    # depthwise causal convolution over q, k and v together, SiLU, no bias
    w = _f32(p["gdn_conv_w"])
    padded = jnp.pad(r(qkv), ((0, 0), (taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(w[j] * padded[:, j:j + t] for j in range(taps)))
    q, k, v = jnp.split(r(qkv), [kw, 2 * kw], axis=-1)
    q, k = (a.reshape(b, t, hk, kd) for a in (q, k))
    v = v.reshape(b, t, h, vd)
    if as_run["l2"]:
        q, k = (a * jax.lax.rsqrt(jnp.sum(jnp.square(a), -1, keepdims=True)
                                  + 1e-6) for a in (q, k))
    q = q / jnp.sqrt(jnp.float32(kd))
    # value head j reads key head j // (H / Hk)
    q, k = (r(jnp.repeat(a, h // hk, axis=2)) for a in (q, k))
    beta = jax.nn.sigmoid(b_in)                                   # [b, t, H]
    g = -jnp.exp(_f32(p["gdn_A_log"])) * jax.nn.softplus(
        a_in + _f32(p["gdn_dt_bias"]))
    if not as_run["decay"]:
        g = jnp.zeros_like(g)

    def one(s, row):
        i, q_t, k_t, v_t, g_t, beta_t = row
        if not as_run["carry"]:  # the wrong reference: a chunk from nothing
            s = jnp.where(i % chunk == 0, 0.0, s)
        s = jnp.exp(g_t)[..., None, None] * s
        if as_run["beta"]:
            v_t = beta_t[..., None] * (
                v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t))
        s = s + k_t[..., :, None] * v_t[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    _, o = jax.lax.scan(
        one, jnp.zeros((b, h, kd, vd), jnp.float32),
        (jnp.arange(t),) + tuple(jnp.moveaxis(a, 1, 0)
                                 for a in (q, k, v, g, beta)))
    o = jnp.moveaxis(o, 0, 1)                                 # [b, t, H, V]
    gate = jax.nn.silu(r(z)).reshape(b, t, h, vd)
    scale = _f32(p["gdn_norm"])
    y = (_rms(o * gate, eps) * scale if as_run["gate"] == "before"
         else _rms(o, eps) * scale * gate)
    return x + r(y.reshape(b, t, vw)) @ r(_f32(p["gdn_out"]))


def _rope(x, theta, share):
    """``x [B, T, H, hd]``, row j at position j: the first ``share * hd``
    lanes turn, pairs (i, i + that / 2) by ``j * theta^(-2i / that)``."""
    turned = int(x.shape[-1] * share)
    inv_freq = theta ** (-jnp.arange(0, turned, 2, dtype=jnp.float32)
                         / turned)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :turned // 2], x[..., turned // 2:turned]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., turned:]], -1)


def _attention(p, x, n_head, n_kv, hd, eps, theta, operands, as_run):
    b, t, _ = x.shape
    group = n_head // n_kv
    centred = as_run["centred"]
    r = functools.partial(_rounded, operands=operands)
    h = r(_rms_norm(p["ln_attn"], x, eps, centred))
    # a head's query lanes, then its gate lanes
    q, gate = jnp.split((h @ r(_f32(p["q_proj"]["kernel"]))).reshape(
        b, t, n_head, 2 * hd), 2, axis=-1)
    k = (h @ r(_f32(p["k_proj"]["kernel"]))).reshape(b, t, n_kv, hd)
    v = (h @ r(_f32(p["v_proj"]["kernel"]))).reshape(b, t, n_kv, hd)
    q = _rms_norm(p["q_norm"], q, eps, centred)
    k = _rms_norm(p["k_norm"], k, eps, centred)
    q, k = (_rope(a, theta, as_run["rope_share"]) for a in (q, k))
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
    attn = jnp.moveaxis(attn, 0, 1).reshape(b, t, n_head * hd)
    if as_run["attn_gate"]:
        attn = attn * jax.nn.sigmoid(gate.reshape(b, t, n_head * hd))
    return x + r(attn) @ r(_f32(p["attn_out"]["kernel"]))


def _route(moe, u, top_k, first, held):
    """Combine weights ``[N, held]`` from the rows the router reads: the
    softmax over all the experts, zero off the top-k, the chosen over their
    sum; the held columns only."""
    p = jax.nn.softmax(u @ _f32(moe["moe_gate"]["kernel"]), -1)
    kth = jax.lax.top_k(p, top_k)[0][:, -1:]
    w = jnp.where(p >= kth, p, 0.0)
    w = w / jnp.sum(w, -1, keepdims=True)
    return w[:, first:first + held]


def _experts(p, x, eps, first, held, operands, as_run):
    """``x + `` every held expert on every token, one expert at a time, and
    the gated shared expert."""
    r = functools.partial(_rounded, operands=operands)
    u32 = _rms_norm(p["ln_mlp"], x, eps, as_run["centred"])
    u32 = u32.reshape(-1, u32.shape[-1])
    moe = p["moe"]
    w = _route(moe, u32, as_run["top_k"], first, held)  # float32 router
    u = r(u32)

    def ffn(w_gate, w_up, w_down):
        inner = jax.nn.silu(u @ r(_f32(w_gate))) * (u @ r(_f32(w_up)))
        return r(inner) @ r(_f32(w_down))

    def one(acc, e):
        w_gate, w_up, w_down, w_e = e
        return acc + w_e[:, None] * ffn(w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        moe["moe_w_gate"], moe["moe_w_up"], moe["moe_w_down"], w.T))
    shared = ffn(moe["moe_shared_gate"]["kernel"],
                 moe["moe_shared_up"]["kernel"],
                 moe["moe_shared_down"]["kernel"])
    if as_run["shared_gate"]:
        shared = shared * jax.nn.sigmoid(
            u32 @ _f32(moe["moe_shared_expert_gate"]["kernel"]))
    return x + (out + shared).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("shape", "as_run", "operands"))
def _forward(params, obs, shape, as_run, operands):
    """The whole forward as ONE program, computed in blocks (queries a
    block, experts one at a time, the rule a token a step): its temporaries
    are one layer's, reused. (A Python loop of jitted parts has the runtime
    allocate every part's temporaries at once as the host runs ahead of the
    device: PERF.md section 6, PR 34.)"""
    kinds, gdn, heads, kv, hd, eps, theta, first, held = shape
    as_run = dict(as_run)
    p = params["params"]
    with jax.default_matmul_precision("highest"):
        x = _dense(p["obs_embed"], obs)
        for i, kind in enumerate(kinds):
            blk = p[f"block_{i}"]
            if kind == "linear_attention":
                x = _linear_attention(blk, x, gdn, eps, operands, as_run)
            else:
                x = _attention(blk, x, heads, kv, hd, eps, theta, operands,
                               as_run)
            x = _experts(blk, x, eps, first, held, operands, as_run)
        x = _rms_norm(p["ln_final"], x, eps, as_run["centred"])
        logits = _dense(p["pi_head"], x)
        v = _dense(p["vf_head"], jnp.tanh(_dense(p["vf_head_up"], x)))
        return jax.nn.log_softmax(logits, -1), v[..., 0]


def forward(params, obs, cfg: dict, operands=None, wrong=None):
    """``obs [B, T, obs_dim]`` -> (log-probabilities ``[B, T, act_dim]``,
    values ``[B, T]``)."""
    as_run = {"carry": True, "beta": True, "decay": True, "l2": True,
              "gate": "after", "attn_gate": True,
              "rope_share": float(cfg["partial_rotary_factor"]),
              "centred": True, "shared_gate": True,
              "top_k": int(cfg["num_experts_per_tok"]), **(wrong or {})}
    gdn = flops_qwen3next.gdn_widths(cfg) + (
        int(cfg["linear_conv_kernel_dim"]), int(cfg["gdn_chunk"]))
    shape = (tuple(flops_qwen3next.layer_kinds(cfg)), gdn,
             int(cfg["num_attention_heads"]),
             int(cfg["num_key_value_heads"]), int(cfg["head_dim"]),
             float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]),
             int(cfg["held_experts_first"]), int(cfg["num_experts"]))
    return _forward(params, _f32(obs), shape, tuple(sorted(as_run.items())),
                    None if operands is None else jnp.dtype(operands).name)
