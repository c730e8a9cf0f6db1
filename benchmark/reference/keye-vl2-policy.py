"""Plain reference for ``keye-vl2-policy``: the decoder layers of
Keye-VL-2.0-30B-A3B's ``config`` (Kwai-Keye, ``model_type`` KeyeVL2) as the
trunk of an observation-in, action-out policy, in float32 ``jax.numpy`` at
matmul precision "highest". No kernels, no cache, no flax, no tiles of a
stage, no threshold search, no sparse dispatch, no code of
``relayrl_tpu/models`` or ``relayrl_tpu/ops``; it reads the system's
parameter tree as data. (``program_kwargs``, which is no part of the
forward, looks at one tuple of names there, ``ARCH_PASSTHROUGH_KEYS``, to
refuse a program that would drop this configuration's keys.)

Every layer is ``x <- x + attention(RMSNorm(x))``, ``x <- x +
experts(RMSNorm(x))``; RMSNorm ``x^ w`` at ``rms_norm_eps``; no bias but the
indexer's LayerNorm's. With ``u = RMSNorm(x)``:

**Main attention**: ``q = u W_q`` (``num_attention_heads`` heads of
``head_dim``), ``k = u W_k``, ``v = u W_v`` (``num_key_value_heads`` heads);
q and k RMS-normed a head; RoPE at ``rope_theta`` on every lane of q and k
(rotate-half); scores at ``1 / sqrt(head_dim)``; q head j reads k/v head
``j // (H / Hkv)``.

**Indexer** (``sa_config``; the DeepSeek-V3.2-Exp report's lightning
indexer), on ``u`` detached: ``qi = u W_qi`` (``indexer_num_heads`` heads of
``indexer_head_dim``), ``ki = LayerNorm(u W_ki)`` (ONE head), ``w = u W_w``
(a scalar a head); RoPE on all of qi's and ki's lanes; ``I[t, s] = sum_j
w[t, j] relu(qi[t, j] . ki[s]) / sqrt(Hi) / sqrt(Di)``, the DENSE ``[T, T]``
matrix, a block of rows at a time.

**Selection**: ``jax.lax.top_k`` of row t's causal scores: the ``min(t + 1,
topk)`` keys of largest ``I[t, s]``, ties to the lower index. One set a
query, shared by all heads. **Sparse attention**: a softmax over the set,
masked. No gradient passes the set.

**The indexer's loss** (:func:`index_loss`): ``p^[t, s]`` = the mean over
the heads of the attention's probabilities, detached; ``KL(p^[t, .] ||
softmax over the set of I[t, .])`` a row, summed over the layers, the mean
over the rows.

**Experts**: ``p = softmax(u W_r)`` over all ``published.num_experts``; the
``num_experts_per_tok`` largest, normalised to sum 1; an expert is ``W_down
(silu(W_gate u) * W_up u)``; the weighted sum over the chosen experts THAT
ARE HELD (``held_experts_first .. + num_experts``), every held expert
computed for every token one at a time. What the absent experts would add
is left out, here as in the system.

A final RMSNorm, a linear policy head and a 2-layer tanh value head.
Departures from the source, each also in
``benchmark/configs/keye-vl2-policy.json``: a Dense observation embedding in
place of the 151,936-row token table, the small heads in place of the
vocabulary head, 4 of 48 layers, 16 of 128 experts held; the vision tower is
not in the ``config`` and not built.

``forward(..., operands=<dtype>)`` rounds both operands of every matmul the
configuration computes in bfloat16 (projections, the indexer's, the index
scores, attention, experts; not the router, the norms, the embedding or the
heads) to ``<dtype>`` and accumulates in float32: the same reference in a
lower precision. ``forward(..., wrong={...})`` computes a deliberately
different layer — ``select`` (False: plain causal attention, no selection),
``topk``, ``relu`` (False: the indexer's ReLU left out), ``index_w`` (False:
every head weighs 1), ``per_head`` (True: query head j selects its own set,
by indexer head ``j // (H / Hi)``'s term alone), ``qk_norm`` (False),
``top_k`` (the experts a token) —: the readings the limits of the comparison
are set against (PERF.md section 6).
"""

from __future__ import annotations

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import flops, flops_keye, flops_lfm2

Q_BLOCK = 256  # queries a step of the reference's attention


def program_kwargs(cfg: dict) -> dict:
    """The configuration in the program's own hyper-parameter names. A
    program that does not take one of them (the parent of the PR that added
    this configuration) would quietly build another model: refuse."""
    for key, want in (("hidden_act", "silu"), ("norm_topk_prob", True),
                      ("decoder_sparse_step", 1), ("mlp_only_layers", []),
                      ("attention_bias", False), ("use_sliding_window", False),
                      ("sliding_window", None)):
        if cfg[key] != want:
            raise SystemExit(
                f"benchmark: REFUSED {key} {cfg[key]!r}: the reference and "
                f"the program are written for {want!r}")
    hi, di, topk = flops_keye.indexer_widths(cfg)
    kwargs = {
        "model_kind": "transformer_moe_discrete",
        "d_model": cfg["hidden_size"],
        "n_layers": cfg["num_hidden_layers"],
        "layer_types": ["sparse_attention"] * cfg["num_hidden_layers"],
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "max_seq_len": cfg["positions_as_run"],
        "norm": "rms", "norm_eps": cfg["rms_norm_eps"],
        "positions": "rope", "rope_theta": cfg["rope_theta"],
        "qk_norm": "head", "use_bias": False,
        "index_heads": hi, "index_head_dim": di, "index_topk": topk,
        "index_chunk": cfg["sa_config"]["q_chunk_size"],
        "ffn": "swiglu",
        "moe_experts": cfg["published"]["num_experts"],
        "moe_top_k": cfg["num_experts_per_tok"],
        "moe_d_ff": cfg["moe_intermediate_size"],
        "moe_norm_topk_prob": cfg["norm_topk_prob"],
        "moe_held": [cfg["held_experts_first"], cfg["num_experts"]],
    }
    from relayrl_tpu.models.base import ARCH_PASSTHROUGH_KEYS

    unknown = sorted(k for k in kwargs
                     if k != "model_kind" and k not in ARCH_PASSTHROUGH_KEYS)
    if unknown:
        raise SystemExit(
            f"benchmark: REFUSED this program's models take no arch keys "
            f"{unknown}: it cannot build keye-vl2-policy")
    return kwargs


def train_flops_per_sample(cfg: dict, seq_len: int) -> float:
    """Forward + backward operations a token: the projections, the index
    scores of the causal pairs, the attention over the KEPT pairs, the
    expert layers at the work of the experts this chip holds at EVEN
    routing (one token-slot a token and layer) and their router."""
    return (flops.TRAIN_OVER_FWD
            * flops_keye.keye_fwd_flops_per_token(cfg, seq_len))


def held_grouped_matmul_train_ops_bytes(cfg: dict, held_slots: float):
    """(operations, bytes) of one update's grouped matmuls over the
    ``held_slots`` token-slots the run itself counted (all expert layers),
    three stacks an expert."""
    return flops_lfm2.held_grouped_matmul_train_ops_bytes(
        held_slots, int(cfg["num_hidden_layers"]), int(cfg["num_experts"]),
        int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"]))


def index_train_ops_bytes(cfg: dict, batch: int, seq_len: int):
    """(operations, bytes) of one update's indexers, forward and backward."""
    return flops_keye.index_train_ops_bytes(cfg, batch, seq_len)


def sparse_attn_train_ops_bytes(cfg: dict, batch: int, seq_len: int,
                                kept_share: float | None = None):
    """(operations, bytes) of one update's attention over the kept pairs
    (``kept_share`` of the causal ones, as the run counted them)."""
    return flops_keye.sparse_attn_train_ops_bytes(cfg, batch, seq_len,
                                                  kept_share)


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


def _layer_norm(p, x, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * _f32(
            p["scale"]) + _f32(p["bias"])


def _rope(x, theta):
    """``x [B, T, H, hd]``, row j at position j: pairs (i, i + hd / 2) turn
    by ``j * theta^(-2i / hd)``."""
    hd = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _top_k_rows(scores, seen, k):
    """Bool like ``scores [..., Tq, Tk]``: each row's ``k`` largest seen
    entries by ``jax.lax.top_k`` (ties to the lower index)."""
    n_keys = scores.shape[-1]
    _, idx = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf),
                           min(k, n_keys))
    picked = jnp.vectorize(
        lambda row: jnp.zeros(n_keys, bool).at[row].set(True),
        signature="(k)->(n)")(idx)
    return picked & seen


def _attention(p, x, widths, eps, theta, operands, as_run):
    """``(x + attention, the indexer's loss a row [B, T])``."""
    n_head, n_kv, hd, hi, di = widths
    b, t, _ = x.shape
    group = n_head // n_kv
    r = functools.partial(_rounded, operands=operands)
    u = r(_rms_norm(p["ln_attn"], x, eps))
    q = (u @ r(_f32(p["q_proj"]["kernel"]))).reshape(b, t, n_head, hd)
    k = (u @ r(_f32(p["k_proj"]["kernel"]))).reshape(b, t, n_kv, hd)
    v = (u @ r(_f32(p["v_proj"]["kernel"]))).reshape(b, t, n_kv, hd)
    if as_run["qk_norm"]:
        q = _rms_norm(p["q_norm"], q, eps)
        k = _rms_norm(p["k_norm"], k, eps)
    q, k = _rope(q, theta), _rope(k, theta)
    q = r(q).reshape(b, t, n_kv, group, hd)
    k, v = r(k), r(v)
    # the indexer reads the normed rows and hands them no gradient
    ui = jax.lax.stop_gradient(u)
    qi = (ui @ r(_f32(p["index_q"]["kernel"]))).reshape(b, t, hi, di)
    ki = _layer_norm(p["index_k_norm"],
                     ui @ r(_f32(p["index_k"]["kernel"])), eps)
    w = ui @ r(_f32(p["index_w"]["kernel"]))              # [b, t, hi]
    if not as_run["index_w"]:
        w = jnp.ones_like(w)
    qi, ki = r(_rope(qi, theta)), r(_rope(ki[:, :, None], theta)[:, :, 0])
    step = min(Q_BLOCK, t)
    key_pos = jnp.arange(t)
    topk = as_run["topk"]

    def rows(start):
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, start, step, axis=1)
        seen = (start + jnp.arange(step))[:, None] >= key_pos[None, :]
        # a head's term of the index score: [b, hi, step, t]
        term = jnp.einsum("bqjd,bkd->bjqk", cut(qi), ki)
        if as_run["relu"]:
            term = jax.nn.relu(term)
        term = term * jnp.moveaxis(cut(w), 1, 2)[..., None] * (
            hi ** -0.5 * di ** -0.5)
        index = term.sum(1)                               # [b, step, t]
        if not as_run["select"]:
            keep = jnp.broadcast_to(seen, index.shape)[:, None, None]
        elif as_run["per_head"]:
            # query head j by indexer head j // (H / Hi)'s own term
            own = jnp.repeat(term, n_head // hi, axis=1)
            keep = _top_k_rows(jax.lax.stop_gradient(own), seen,
                               topk).reshape(b, n_kv, group, step, t)
        else:
            keep = _top_k_rows(jax.lax.stop_gradient(index), seen,
                               topk)[:, None, None]
        s = jnp.einsum("bqhgd,bkhd->bhgqk", cut(q), k) / jnp.sqrt(
            jnp.float32(hd))
        p_blk = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", r(p_blk), v)
        # KL(p^ || softmax over the set of the index scores), p^ detached
        shared = jnp.broadcast_to(keep, p_blk.shape)[:, 0, 0]
        p_hat = jax.lax.stop_gradient(p_blk.mean((1, 2)))
        log_pi = jax.nn.log_softmax(jnp.where(shared, index, -jnp.inf), -1)
        kl = jnp.sum(jnp.where(p_hat > 0, p_hat * (
            jnp.log(jnp.where(p_hat > 0, p_hat, 1.0))
            - jnp.where(shared, log_pi, 0.0)), 0.0), -1)
        return out, kl

    attn, kl = jax.lax.map(rows, jnp.arange(0, t, step))
    attn = jnp.moveaxis(attn, 0, 1).reshape(b, t, n_head * hd)
    kl = jnp.moveaxis(kl, 0, 1).reshape(b, t)
    return x + r(attn) @ r(_f32(p["attn_out"]["kernel"])), kl


def _route(moe, u, top_k, first, held):
    """Combine weights ``[N, held]`` from the rows the router reads: the
    softmax over all the experts, zero off the top-k, the chosen over their
    sum; the held columns only."""
    p = jax.nn.softmax(u @ _f32(moe["moe_gate"]["kernel"]), -1)
    kth = jax.lax.top_k(p, top_k)[0][:, -1:]
    w = jnp.where(p >= kth, p, 0.0)
    w = w / jnp.sum(w, -1, keepdims=True)
    return w[:, first:first + held]


def _experts(p, x, eps, first, held, operands, top_k):
    """``x + `` every held expert on every token, one expert at a time."""
    r = functools.partial(_rounded, operands=operands)
    u32 = _rms_norm(p["ln_mlp"], x, eps)
    u32 = u32.reshape(-1, u32.shape[-1])
    moe = p["moe"]
    w = _route(moe, u32, top_k, first, held)            # float32 router
    u = r(u32)

    def one(acc, e):
        w_gate, w_up, w_down, w_e = e
        inner = jax.nn.silu(u @ r(_f32(w_gate))) * (u @ r(_f32(w_up)))
        return acc + w_e[:, None] * (r(inner) @ r(_f32(w_down))), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        moe["moe_w_gate"], moe["moe_w_up"], moe["moe_w_down"], w.T))
    return x + out.reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("shape", "as_run", "operands"))
def _forward(params, obs, shape, as_run, operands):
    """The whole forward as ONE program, computed in blocks (queries a
    block, experts one at a time): its temporaries are one layer's, reused.
    Returns the log-probabilities, the values and the indexers' loss rows
    summed over the layers."""
    n_layers, widths, eps, theta, first, held = shape
    as_run = dict(as_run)
    p = params["params"]
    with jax.default_matmul_precision("highest"):
        x = _dense(p["obs_embed"], obs)
        kl = jnp.zeros(obs.shape[:2], jnp.float32)
        for i in range(n_layers):
            blk = p[f"block_{i}"]
            x, kl_i = _attention(blk, x, widths, eps, theta, operands,
                                 as_run)
            kl = kl + kl_i
            x = _experts(blk, x, eps, first, held, operands,
                         as_run["top_k"])
        x = _rms_norm(p["ln_final"], x, eps)
        logits = _dense(p["pi_head"], x)
        v = _dense(p["vf_head"], jnp.tanh(_dense(p["vf_head_up"], x)))
        return jax.nn.log_softmax(logits, -1), v[..., 0], kl


_last: dict = {}  # the newest forward: (what it was asked, its results)


def _run(params, obs, cfg, operands, wrong):
    hi, di, topk = flops_keye.indexer_widths(cfg)
    as_run = {"select": True, "topk": topk, "relu": True, "index_w": True,
              "per_head": False, "qk_norm": True,
              "top_k": int(cfg["num_experts_per_tok"]), **(wrong or {})}
    widths = (int(cfg["num_attention_heads"]),
              int(cfg["num_key_value_heads"]), int(cfg["head_dim"]), hi, di)
    shape = (int(cfg["num_hidden_layers"]), widths,
             float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]),
             int(cfg["held_experts_first"]), int(cfg["num_experts"]))
    static = (shape, tuple(sorted(as_run.items())),
              None if operands is None else jnp.dtype(operands).name)
    # A run's three comparisons (the largest difference, the quantile, the
    # indexers' loss) ask for the same forward of the same parameters and
    # observations: at 16,384 rows it is made once. Parameters by identity
    # (kept with the answer, so an identity cannot pass to another array
    # while the entry lives), observations by their bytes.
    leaves = jax.tree_util.tree_leaves(params)
    if any(isinstance(a, jax.core.Tracer) for a in leaves + [obs]):
        return _forward(params, _f32(obs), *static)
    asked = (static, tuple(id(a) for a in leaves),
             hashlib.sha1(np.asarray(obs, np.float32).tobytes()).digest())
    if _last.get("asked") != asked:
        _last.update(asked=asked, alive=leaves,
                     results=_forward(params, _f32(obs), *static))
    return _last["results"]


def forward(params, obs, cfg: dict, operands=None, wrong=None):
    """``obs [B, T, obs_dim]`` -> (log-probabilities ``[B, T, act_dim]``,
    values ``[B, T]``)."""
    return _run(params, obs, cfg, operands, wrong)[:2]


def index_loss(params, obs, cfg: dict, valid=None):
    """The indexers' loss of one forward: the KL rows summed over the
    layers, their mean over the ``valid [B, T]`` rows (None: all)."""
    kl = _run(params, obs, cfg, None, None)[2]
    if valid is None:
        return jnp.mean(kl)
    return jnp.sum(kl * valid) / jnp.maximum(jnp.sum(valid), 1.0)
