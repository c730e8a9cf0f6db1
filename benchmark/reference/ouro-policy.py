"""Plain reference for ``ouro-policy``: Ouro-2.6B's looped decoder stack
(ByteDance, ``model_type`` ouro; "Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741) as the trunk of an observation-in,
action-out policy, in float32 ``jax.numpy`` at matmul precision "highest".
No kernels, no cache, no checkpoint, no flax, no code of
``relayrl_tpu/models``; it reads the system's parameter tree as data.
(``program_kwargs``, which is no part of the forward, looks at one tuple of
names there, ``ARCH_PASSTHROUGH_KEYS``, to refuse a program that would drop
this configuration's keys.)

With ``S = total_ut_steps`` and ``L = num_hidden_layers``, ONE set of ``L``
layers' weights, no bias anywhere, ``eps = rms_norm_eps``::

    h(0) = embed(obs)                               # [B, T, 2048]
    for s in 1..S:                                  # the same L layers
        u = h(s-1)
        for l in 0..L-1:
            n = RMS_l1(u)                           # ln_attn
            q, k, v = Wq n, Wk n, Wv n              # 16 heads of 128 each
            q, k = rope(q), rope(k)                 # theta 1e6, positions
                                                    # 0..T-1 at every s
            a = Wo softmax_causal(q k^T / sqrt(128)) v
            u = u + RMS_l2(a)                       # ln_attn_out: sandwich
            m = RMS_l3(u)                           # ln_mlp
            f = Wdown(silu(Wgate m) * Wup m)        # width 5632
            u = u + RMS_l4(f)                       # ln_mlp_out: sandwich
        h(s) = RMS_final(u)                         # the same at every s
    logits, v = heads(h(S))                         # no second norm

A linear policy head and a 2-layer tanh value head on ``h(S)``. Attention is
computed a block of queries at a time so that no ``T x T`` array stands
whole. Departures from the source, each also in
``benchmark/configs/ouro-policy.json``: a Dense observation embedding in
place of the 49,152-row token table, the small heads in place of the
vocabulary head, 8 of 48 layers, no exit gate (at ``early_exit_threshold``
1 no token leaves before the last pass, and the output does not read it).

``forward(..., operands=<dtype>)`` rounds both operands of every matmul the
configuration computes in bfloat16 (projections, attention, FFN; not the
norms, the embedding or the heads) to ``<dtype>`` and accumulates in
float32: the system's own precision. ``forward(..., wrong={...})``
computes a deliberately different model — ``passes`` (3: one pass fewer),
``sandwich`` (False: no norm on a half's output), ``precision``
(``"default"``: float32 operands without "highest", the platform's own
matmul passes), ``everywhere`` (``"bfloat16"``: parameters, residual stream,
norms, softmax and heads all in that type, the nearest precision below the
one the configuration states) —: the readings the limits of the comparison
are set against (PERF.md section 6).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import flops, flops_ouro

Q_BLOCK = 256  # queries a step of the reference's attention


def program_kwargs(cfg: dict) -> dict:
    """The configuration in the program's own hyper-parameter names. A
    program that does not take one of them (the parent of the PR that added
    this configuration) would quietly build another model: refuse."""
    kwargs = {
        "model_kind": "transformer_discrete",
        "d_model": cfg["hidden_size"],
        "n_layers": cfg["num_hidden_layers"],
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "max_seq_len": cfg["positions_as_run"],
        "attention": cfg["attention"],
        "norm": "rms", "norm_eps": cfg["rms_norm_eps"],
        "norm_sandwich": True,
        "positions": "rope", "rope_theta": cfg["rope_theta"],
        "use_bias": False,
        "ffn": "swiglu", "d_ff": cfg["intermediate_size"],
        "loop_steps": cfg["total_ut_steps"],
        "block_checkpoint": cfg["block_checkpoint"],
    }
    from relayrl_tpu.models.base import ARCH_PASSTHROUGH_KEYS

    unknown = sorted(k for k in kwargs
                     if k != "model_kind" and k not in ARCH_PASSTHROUGH_KEYS)
    if unknown:
        raise SystemExit(
            f"benchmark: REFUSED this program's models take no arch keys "
            f"{unknown}: it cannot build ouro-policy")
    return kwargs


def train_flops_per_sample(cfg: dict, seq_len: int) -> float:
    """Forward + backward operations a token: one pass's, times the passes.
    The checkpoint's second forward is the program's and does not count."""
    return flops.TRAIN_OVER_FWD * flops_ouro.ouro_fwd_flops_per_token(
        cfg, seq_len)


def flash_gqa_train_ops_bytes(cfg: dict, batch: int, seq_len: int):
    """(operations, bytes) of one update's flash kernels, every application
    of every layer, forward and backward, at the causal triangle's scores;
    k/v at their own head count (the query heads': no grouping)."""
    return flops_ouro.flash_train_ops_bytes(cfg, batch, seq_len)


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rounded(a, operands):
    """``operands``: a dtype's name, or None."""
    return a if operands is None else a.astype(operands).astype(a.dtype)


def _dense(p, x):
    return x @ p["kernel"] + p["bias"]


def _rms_norm(p, x, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * p["scale"]


def _rope(x, theta):
    """``x [B, T, H, hd]``, row j at position j: pairs (i, i + hd/2) turn by
    ``j * theta^(-2i/hd)``."""
    hd = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = (f(ang)[None, :, None].astype(x.dtype)
                for f in (jnp.cos, jnp.sin))
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(p, u, n_head, hd, eps, theta, r):
    """``Wo softmax_causal(q k^T / sqrt(hd)) v`` of the normed rows: the
    half's output, before its norm and the residual."""
    b, t, _ = u.shape
    n = r(_rms_norm(p["ln_attn"], u, eps))
    q, k, v = ((n @ r(p[name]["kernel"])).reshape(b, t, n_head, hd)
               for name in ("q_proj", "k_proj", "v_proj"))
    q, k, v = r(_rope(q, theta)), r(_rope(k, theta)), r(v)
    step = min(Q_BLOCK, t)
    key_pos = jnp.arange(t)

    def rows(start):
        q_blk = jax.lax.dynamic_slice_in_dim(q, start, step, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) / hd ** 0.5
        seen = (start + jnp.arange(step))[:, None] >= key_pos[None, :]
        p_blk = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", r(p_blk), v)

    attn = jax.lax.map(rows, jnp.arange(0, t, step))    # [t/step, b, step..]
    attn = jnp.moveaxis(attn, 0, 1).reshape(b, t, n_head * hd)
    return r(attn) @ r(p["attn_out"]["kernel"])


def _ffn(p, u, eps, r):
    m = r(_rms_norm(p["ln_mlp"], u, eps))
    mid = jax.nn.silu(m @ r(p["mlp_gate"]["kernel"])) * (
        m @ r(p["mlp_up"]["kernel"]))
    return r(mid) @ r(p["mlp_down"]["kernel"])


def _pass(p, h, shape, sandwich, r):
    """One pass of the stack, ``h(s-1) -> h(s)``, over the tree ``p``."""
    n_layers, n_head, hd, eps, theta = shape
    u = h
    for l in range(n_layers):
        blk = p[f"block_{l}"]
        a = _attention(blk, u, n_head, hd, eps, theta, r)
        if sandwich:
            a = _rms_norm(blk["ln_attn_out"], a, eps)
        u = u + a
        f = _ffn(blk, u, eps, r)
        if sandwich:
            f = _rms_norm(blk["ln_mlp_out"], f, eps)
        u = u + f
    return _rms_norm(p["ln_final"], u, eps)


def _heads(p, h):
    logits = _dense(p["pi_head"], h)
    v = _dense(p["vf_head"], jnp.tanh(_dense(p["vf_head_up"], h)))
    return jax.nn.log_softmax(logits, -1), v[..., 0]


def _cast(params, as_run):
    dtype = jnp.dtype(as_run["everywhere"])
    return dtype, jax.tree_util.tree_map(lambda a: a.astype(dtype),
                                         params["params"])


@functools.partial(jax.jit, static_argnames=("as_run",))
def _embed(params, obs, as_run):
    as_run = dict(as_run)
    dtype, p = _cast(params, as_run)
    with jax.default_matmul_precision(as_run["precision"]):
        return _dense(p["obs_embed"], obs.astype(dtype))


@functools.partial(jax.jit, static_argnames=("shape", "as_run", "operands"))
def _one_pass(params, h, shape, as_run, operands):
    """A whole pass as ONE program, run once a pass over the same tree. (A
    program a layer part, enqueued from a Python loop, has the runtime hold
    every part's temporaries at once beside a learner that fills the chip:
    PERF.md section 7. All the passes in one program are 358 MB of "highest"
    matmul code, 61 MB of a compile cache the machine caps at 192 MiB; a
    pass is 95 MB and compiles once: PERF.md section 6, PR 50.)"""
    as_run = dict(as_run)
    _dtype, p = _cast(params, as_run)
    with jax.default_matmul_precision(as_run["precision"]):
        return _pass(p, h, shape, as_run["sandwich"],
                     functools.partial(_rounded, operands=operands))


@functools.partial(jax.jit, static_argnames=("as_run",))
def _readout(params, h, as_run):
    as_run = dict(as_run)
    _dtype, p = _cast(params, as_run)
    with jax.default_matmul_precision(as_run["precision"]):
        logp, v = _heads(p, h)
        return _f32(logp), _f32(v)


def _forward(params, obs, shape, as_run, operands):
    h = _embed(params, obs, as_run)
    for _s in range(dict(as_run)["passes"]):    # the SAME tree at every s
        # fenced: the next pass's temporaries are set aside after this one's
        h = jax.block_until_ready(
            _one_pass(params, h, shape, as_run, operands))
    return _readout(params, h, as_run)


def forward(params, obs, cfg: dict, operands=None, wrong=None):
    """``obs [B, T, obs_dim]`` -> (log-probabilities ``[B, T, act_dim]``,
    values ``[B, T]``)."""
    if int(cfg["num_key_value_heads"]) != int(cfg["num_attention_heads"]):
        raise ValueError("ouro-policy's attention is plain multi-head")
    as_run = {"passes": int(cfg["total_ut_steps"]), "sandwich": True,
              "precision": "highest", "everywhere": "float32",
              **(wrong or {})}
    shape = (int(cfg["num_hidden_layers"]), int(cfg["num_attention_heads"]),
             int(cfg["head_dim"]), float(cfg["rms_norm_eps"]),
             float(cfg["rope_theta"]))
    return _forward(params, _f32(obs), shape, tuple(sorted(as_run.items())),
                    None if operands is None else jnp.dtype(operands).name)
