"""Plain reference for ``nemotron3-super-policy``: the layers of
NVIDIA-Nemotron-3-Super-120B-A12B-BF16's ``config`` (nvidia, ``model_type``
nemotron_h) as the trunk of an observation-in, action-out policy, in float32
``jax.numpy`` at matmul precision "highest". No kernels, no cache, no flax,
no chunked scan, no sparse dispatch, no code of ``relayrl_tpu/models`` or
``relayrl_tpu/ops``; it reads the system's parameter tree as data.
(``program_kwargs``, which is no part of the forward, looks at one tuple of
names there, ``ARCH_PASSTHROUGH_KEYS``, to refuse a program that would drop
this configuration's keys.)

ONE CHIP'S SHARE of every layer, as the configuration's file states it: 64
chips share each layer, mixer heads over 4, experts over all 64; the
configuration's keys hold the counts HELD here (``mamba_num_heads`` 32 of
128 in ``n_groups`` 2 of 8 whole groups, ``num_attention_heads`` 8 over
``num_key_value_heads`` 1 of 32 over 2, ``n_routed_experts`` 8 of 512 from
``held_experts_first``). A mixer's heads meet only in the output
projection's sum — Mamba-2's grouped norm stays inside a group, and each
held group is whole; head h reads group h // 16 in the model and in the
share alike — so the share of an ``M`` or ``*`` layer is the same equations
at the held counts: ``part = y[:, held] W_out[held, :]``.

Every layer is ONE part behind one RMSNorm, ``x' = x + part(u)``, ``u =
RMSNorm(x)`` at ``norm_eps``, no bias but the convolution's; the part by
the layer's letter in ``hybrid_override_pattern``:

``M`` — Mamba-2, H = ``mamba_num_heads`` heads of P = ``mamba_head_dim``
(inner = H P, NOT ``expand`` x hidden), N = ``ssm_state_size``, G =
``n_groups``:
  ``[z | xBC | dt] = u W_in`` (widths inner | inner + 2 G N | H);
  ``xBC <- silu(conv(xBC) + b)``, depthwise, causal, ``conv_kernel`` taps;
  ``x [H, P], B [G, N], C [G, N] = split(xBC)``, head h reads group
  h // (H / G); ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
  **the state equation step by step**, a ``lax.scan`` over T:
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t`` (S ``[H, P, N]``),
  ``y_t = S_t C_t + D x_t``;
  ``y <- RMSNorm_G(y * silu(z))`` — the gate BEFORE the norm, the norm
  over each of the G groups of inner / G columns —, ``part = y W_out``.

``*`` — attention: ``num_attention_heads`` query heads of ``head_dim`` over
``num_key_value_heads`` k/v heads, causal softmax(q k^T / sqrt(head_dim))
v, NO positional signal of any kind (the ``nemotron_h`` attention applies
no rotary embedding), ``part = concat(heads) W_o``. Computed a block of
queries at a time over all the keys.

``E`` — experts IN A LATENT ``moe_latent_size`` wide: ``s = sigmoid(u W_r)``
over all ``published.n_routed_experts``; the ``num_experts_per_tok`` chosen
on ``s + e_score_correction_bias`` (``n_group`` 1, ``topk_group`` 1);
weights the UNBIASED ``s`` of the chosen over their sum, times
``routed_scaling_factor``; ``h = u W_dn`` (hidden -> latent); an expert is
``W_2 relu(W_1 h)^2`` (latent -> ``moe_intermediate_size`` -> latent, no
gate); ``part = (sum over the chosen experts THAT ARE HELD of w_e
expert_e(h)) W_up`` (latent -> hidden) ``+ shared(u)``, the shared expert
``W_d relu(W_u u)^2`` of width ``moe_shared_expert_intermediate_size`` on
the FULL-WIDTH ``u``, weight 1. Every held expert is computed for every
token, one at a time. What the absent experts would add is left out, here
as in the system. Assumed (the catalog row says only "experts in 1024-d
latent"; each also in the configuration file's ``assumed``): the router and
the shared expert read the full-width ``u``; the two latent projections
have no bias, norm or activation of their own.

A final RMSNorm, a linear policy head and a 2-layer tanh value head.
Departures from the source, each also in
``benchmark/configs/nemotron3-super-policy.json``: a Dense observation
embedding in place of the 131,072-row token table, the small heads in place
of the vocabulary head, 11 of 88 layers, the held heads and experts; the
multi-token-prediction module is not built.

``forward(..., operands=<dtype>)`` rounds both operands of every matmul the
configuration computes in bfloat16 (projections, attention, the latent
projections, experts; the scan's ``x``, ``B`` and ``C``; not the router,
the norms, the step sizes, the decays, the state, the embedding or the
heads) to ``<dtype>`` and accumulates in float32: the same reference in a
lower precision. ``forward(..., wrong={...})`` computes a deliberately
different layer — ``latent`` (False: the experts are fed ``u[:, :latent]``
in place of ``u W_dn``), ``scaling`` (1.0: the 5 left out), ``top_k``,
``shared`` (False: no shared expert), ``carry`` (False: the state is NOT
carried across a chunk boundary, it starts from zero every ``chunk_size``
tokens) —: the readings the limits of the comparison are set against
(``benchmark/tests/controls_nemotron3.py``, PERF.md section 6).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import flops, flops_nemotron3

Q_BLOCK = 256  # queries a step of the reference's attention


def program_kwargs(cfg: dict) -> dict:
    """The configuration in the program's own hyper-parameter names. A
    program that does not take one of them (the parent of the PR that added
    this configuration) would quietly build another model: refuse."""
    for key, want in (("n_group", 1), ("topk_group", 1),
                      ("n_shared_experts", 1), ("mlp_hidden_act", "relu2"),
                      ("mamba_hidden_act", "silu"), ("use_conv_bias", True),
                      ("norm_topk_prob", True)):
        if cfg[key] != want:
            raise SystemExit(
                f"benchmark: REFUSED {key} {cfg[key]!r}: the reference and "
                f"the program are written for {want!r}")
    kwargs = {
        "model_kind": "transformer_moe_discrete",
        "d_model": cfg["hidden_size"],
        "n_layers": cfg["num_hidden_layers"],
        "layer_types": flops_nemotron3.layer_kinds(cfg),
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "max_seq_len": cfg["positions_as_run"],
        "attention": cfg["attention"],
        "norm": "rms", "norm_eps": cfg["norm_eps"],
        "positions": "none", "use_bias": False,
        "mamba_heads": cfg["mamba_num_heads"],
        "mamba_head_dim": cfg["mamba_head_dim"],
        "mamba_state": cfg["ssm_state_size"],
        "mamba_groups": cfg["n_groups"],
        "mamba_conv_taps": cfg["conv_kernel"],
        "mamba_chunk": cfg["chunk_size"],
        "ffn": "relu2",
        "moe_experts": cfg["published"]["n_routed_experts"],
        "moe_top_k": cfg["num_experts_per_tok"],
        "moe_d_ff": cfg["moe_intermediate_size"],
        "moe_norm_topk_prob": cfg["norm_topk_prob"],
        "moe_router": "sigmoid",
        "moe_expert_bias": True,
        "moe_routed_scaling": cfg["routed_scaling_factor"],
        "moe_shared_d_ff": cfg["moe_shared_expert_intermediate_size"],
        "moe_latent": cfg["moe_latent_size"],
        "moe_held": [cfg["held_experts_first"], cfg["n_routed_experts"]],
        "block_checkpoint": bool(cfg["block_checkpoint"]),
    }
    from relayrl_tpu.models.base import ARCH_PASSTHROUGH_KEYS

    unknown = sorted(k for k in kwargs
                     if k != "model_kind" and k not in ARCH_PASSTHROUGH_KEYS)
    if unknown:
        raise SystemExit(
            f"benchmark: REFUSED this program's models take no arch keys "
            f"{unknown}: it cannot build nemotron3-super-policy")
    return kwargs


def train_flops_per_sample(cfg: dict, seq_len: int) -> float:
    """Forward + backward operations a token at the sizes as run: the
    Mamba-2 layers' projections and scan, the attention layer's causal
    scores, the expert layers' router, latent projections, the experts this
    chip holds at EVEN routing (0.34375 token-slots a token and layer) and
    their shared expert."""
    return (flops.TRAIN_OVER_FWD
            * flops_nemotron3.nemotron3_fwd_flops_per_token(cfg, seq_len))


def held_grouped_matmul_train_ops_bytes(cfg: dict, held_slots: float):
    """(operations, bytes) of one update's grouped matmuls over the
    ``held_slots`` token-slots the run itself counted (all expert layers),
    two stacks an expert, rows ``moe_latent_size`` wide."""
    return flops_nemotron3.held_grouped_matmul_train_ops_bytes(
        cfg, held_slots)


def ssd_train_ops_bytes(cfg: dict, batch: int, seq_len: int):
    """(operations, bytes) of one update's state-space scans, every Mamba-2
    layer, forward and backward, at the heads and groups held here."""
    return flops_nemotron3.ssd_train_ops_bytes(cfg, batch, seq_len)


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


def _mamba(p, x, widths, eps, operands, carry):
    """The Mamba-2 mixer, its state equation one token at a time."""
    heads, width, state, groups, taps, chunk = widths
    b, t, _ = x.shape
    inner, bc = heads * width, groups * state
    r = functools.partial(_rounded, operands=operands)
    u = r(_rms_norm(p["ln_attn"], x, eps))
    z, xbc, dt = jnp.split(u @ r(_f32(p["mamba_in"])),
                           [inner, 2 * inner + 2 * bc], axis=-1)
    # depthwise causal convolution, bias, SiLU
    w, bias = _f32(p["mamba_conv_w"]), _f32(p["mamba_conv_b"])
    padded = jnp.pad(r(xbc), ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(w[j] * padded[:, j:j + t] for j in range(taps))
                      + bias)
    xs, b_in, c_in = jnp.split(r(xbc), [inner, inner + bc], axis=-1)
    xs = xs.reshape(b, t, heads, width)
    # head h reads group h // (H / G)
    b_in, c_in = (jnp.repeat(a.reshape(b, t, groups, state),
                             heads // groups, axis=2) for a in (b_in, c_in))
    dt = jax.nn.softplus(r(dt) + _f32(p["mamba_dt_bias"]))     # [b, t, H]
    a_neg = -jnp.exp(_f32(p["mamba_A_log"]))
    skip = _f32(p["mamba_D"])

    def one(s, row):
        i, x_t, dt_t, b_t, c_t = row
        if not carry:  # the wrong reference: a chunk starts from nothing
            s = jnp.where(i % chunk == 0, 0.0, s)
        s = (jnp.exp(dt_t * a_neg)[..., None, None] * s
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return s, jnp.einsum("bhpn,bhn->bhp", s, c_t) + skip[:, None] * x_t

    _, y = jax.lax.scan(
        one, jnp.zeros((b, heads, width, state), jnp.float32),
        (jnp.arange(t),) + tuple(jnp.moveaxis(a, 1, 0)
                                 for a in (xs, dt, b_in, c_in)))
    y = jnp.moveaxis(y, 0, 1).reshape(b, t, inner)
    gate = jax.nn.silu(r(z))

    def norm_groups(a):
        return _rms(a.reshape(b, t, groups, inner // groups), eps).reshape(
            b, t, inner)

    y = norm_groups(y * gate) * _f32(p["mamba_norm"])
    return x + r(y) @ r(_f32(p["mamba_out"]))


def _attention(p, x, n_head, n_kv, hd, eps, operands):
    """Causal, grouped, NO positional signal (the model's)."""
    b, t, _ = x.shape
    group = n_head // n_kv
    r = functools.partial(_rounded, operands=operands)
    h = r(_rms_norm(p["ln_attn"], x, eps))
    q = (h @ r(_f32(p["q_proj"]["kernel"]))).reshape(b, t, n_head, hd)
    k = (h @ r(_f32(p["k_proj"]["kernel"]))).reshape(b, t, n_kv, hd)
    v = (h @ r(_f32(p["v_proj"]["kernel"]))).reshape(b, t, n_kv, hd)
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
    return x + r(attn) @ r(_f32(p["attn_out"]["kernel"]))


def _route(moe, u, top_k, scaling, first, held):
    """Combine weights ``[N, held]`` from the rows the router reads: zero
    off the top-k (chosen on score + bias), the chosen experts' unbiased
    scores over their sum, times ``scaling``; the held columns only."""
    s = jax.nn.sigmoid(u.reshape(-1, u.shape[-1]) @ _f32(
        moe["moe_gate"]["kernel"]))
    biased = s + _f32(moe["moe_expert_bias"])
    kth = jax.lax.top_k(biased, top_k)[0][:, -1:]
    w = jnp.where(biased >= kth, s, 0.0)
    w = scaling * w / jnp.sum(w, -1, keepdims=True)
    return w[:, first:first + held]


def _relu2(a):
    return jnp.square(jax.nn.relu(a))


def _experts(p, x, eps, top_k, scaling, first, held, shared, operands,
             latent=True):
    """``x + `` every held expert on every token's latent, one expert at a
    time, brought back to the stream's width, and the shared expert on the
    full-width rows. ``latent`` False (a wrong reference): the experts read
    the rows' first columns in place of their down-projection."""
    r = functools.partial(_rounded, operands=operands)
    u = _rms_norm(p["ln_mlp"], x, eps)
    moe = p["moe"]
    w = _route(moe, u, top_k, scaling, first, held)     # float32 router
    u = r(u.reshape(-1, u.shape[-1]))
    w_dn = _f32(moe["moe_latent_down"]["kernel"])
    h = r(u @ r(w_dn)) if latent else u[:, :w_dn.shape[1]]

    def ffn(rows, w_up, w_down):
        return r(_relu2(rows @ r(_f32(w_up)))) @ r(_f32(w_down))

    def one(acc, e):
        w_up, w_down, w_e = e
        return acc + w_e[:, None] * ffn(h, w_up, w_down), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        moe["moe_w_up"], moe["moe_w_down"], w.T))
    out = r(out) @ r(_f32(moe["moe_latent_up"]["kernel"]))
    if shared:
        out = out + ffn(u, moe["moe_shared_up"]["kernel"],
                        moe["moe_shared_down"]["kernel"])
    return x + out.reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("shape", "as_run", "operands"))
def _forward(params, obs, shape, as_run, operands):
    """The whole forward as ONE program, computed in blocks (queries a
    block, experts one at a time, the scan a token a step): its
    temporaries are one layer's, reused. (A Python loop of jitted parts
    has the runtime allocate every part's temporaries at once as the host
    runs ahead of the device: PERF.md section 6, PR 34.)"""
    kinds, mamba, heads, kv, hd, eps, first, held = shape
    as_run = dict(as_run)
    p = params["params"]
    with jax.default_matmul_precision("highest"):
        x = _dense(p["obs_embed"], obs)
        for i, kind in enumerate(kinds):
            blk = p[f"block_{i}"]
            if kind == "mamba2":
                x = _mamba(blk, x, mamba, eps, operands, as_run["carry"])
            elif kind == "attention":
                x = _attention(blk, x, heads, kv, hd, eps, operands)
            else:
                x = _experts(blk, x, eps, as_run["top_k"],
                             as_run["scaling"], first, held,
                             as_run["shared"], operands, as_run["latent"])
        x = _rms_norm(p["ln_final"], x, eps)
        logits = _dense(p["pi_head"], x)
        v = _dense(p["vf_head"], jnp.tanh(_dense(p["vf_head_up"], x)))
        return jax.nn.log_softmax(logits, -1), v[..., 0]


def forward(params, obs, cfg: dict, operands=None, wrong=None):
    """``obs [B, T, obs_dim]`` -> (log-probabilities ``[B, T, act_dim]``,
    values ``[B, T]``)."""
    as_run = {"carry": True, "latent": True, "shared": True,
              "scaling": float(cfg["routed_scaling_factor"]),
              "top_k": int(cfg["num_experts_per_tok"])}
    unknown = set(wrong or {}) - set(as_run)
    if unknown:
        raise ValueError(f"no wrong reference {sorted(unknown)} "
                         f"({' | '.join(sorted(as_run))})")
    as_run.update(wrong or {})
    mamba = flops_nemotron3.mamba_widths(cfg)[:4] + (
        int(cfg["conv_kernel"]), int(cfg["chunk_size"]))
    shape = (tuple(flops_nemotron3.layer_kinds(cfg)), mamba,
             int(cfg["num_attention_heads"]),
             int(cfg["num_key_value_heads"]), int(cfg["head_dim"]),
             float(cfg["norm_eps"]), int(cfg["held_experts_first"]), int(cfg["n_routed_experts"]))
    return _forward(params, _f32(obs), shape, tuple(sorted(as_run.items())),
                    None if operands is None else jnp.dtype(operands).name)
