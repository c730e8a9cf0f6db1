"""The operator ``"sparse_attention"``: grouped-query causal attention over
the keys a learned indexer picks for each query (DeepSeek sparse attention).

The main attention is operator ``"attention"``'s with separate projections
(its keys ``n_kv_heads``, ``head_dim``, ``qk_norm`` false | ``"head"``,
``rope_share``; no gate, no window): ``q_proj`` / ``k_proj`` / ``v_proj`` of
the normed rows ``u``, QK-norm, RoPE, ``attn_out``. In front of it the
**indexer**, which reads ``stop_gradient(u)``: ``index_q`` (``index_heads``
heads of ``index_head_dim``), ``index_k`` (ONE head, LayerNormed:
``index_k_norm``) and ``index_w`` (a weight a head), q and k rotated as the
main ones over their whole width. Query ``t`` attends the ``index_topk``
keys ``s <= t`` of largest index score, one set for all its heads
(:mod:`relayrl_tpu.ops.sparse_attn` has the equations); no gradient reaches
the indexer through the set. It learns from a loss of its own
(:data:`OWN_LOSS`): ``KL(p^ || softmax over the set of its scores)`` a row,
``p^`` the heads' mean attention over the set, detached — sown as
``own_loss_rows`` where the caller collects ``intermediates``, beside
``index_kept`` (the pairs kept, per sequence). ``index_chunk`` is the tile
the scores are computed in and no part of the model. The attention over the
set, and the indexer's scores and selection in front of it, run as Pallas
kernels on a TPU at shapes that tile and as plain XLA everywhere else
(``ops/sparse_attn.backend`` and ``index_backend``: platform and shape
alone); :data:`KERNELS` records which, a full-mode shape at a time.

Three modes, one parameter tree:

* full (``cache=None``): the tiled form, under ``jax.checkpoint`` a tile;
* cached (``cache`` = this layer's ``(k, v, ki)``: ``[B, W, Hkv, hd]``
  twice and the indexer's key rows ``[B, W, index_head_dim]``, all rotated
  before they go in; ``t`` the write index): one position, or a prefill's
  rows from position ``t``; the queries select among the cache's rows up to
  their own;
* readout (``readout_idx`` set): k, v and the indexer's keys over every row,
  the queries, the selection, the output projection and the FFN for the ONE
  row the heads read.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from relayrl_tpu.models.layers.attention import apply_rope
from relayrl_tpu.models.layers.block import (
    block_dense,
    block_ffn,
    block_norm,
    block_residual,
    norm,
)
from relayrl_tpu.models.layers.recurrent import kernel
from relayrl_tpu.ops import sparse_attn
from relayrl_tpu.ops.scopes import INDEX, OP_PROJ

ROW_READOUT = True
# the loss the operator brings, as an update reports it
OWN_LOSS = "IndexLoss"


def _shape(q, k, v, qi, ki, w, topk, chunk, loss):
    n_rows = int(q.shape[1])
    key = (n_rows, int(q.shape[3]), int(qi.shape[2]), int(qi.shape[3]),
           int(topk), q.dtype.name)
    causal = n_rows * (n_rows + 1) // 2
    kept = sparse_attn.kept_pairs(n_rows, topk)
    tile, per_stage = sparse_attn.stages(n_rows, chunk)
    # every stage's keys are whole multiples of the first's: one answer
    attention = sparse_attn.backend(tile, per_stage, int(q.shape[2]),
                                    int(k.shape[2]), key[1])
    selection = sparse_attn.index_backend(tile, per_stage, key[2], key[3])
    return key, f"{selection}+{attention}", (
        f"T={n_rows} heads {q.shape[2]}/{k.shape[2]} head_dim={key[1]} "
        f"index {key[2]}x{key[3]} topk={topk} tile={tile} {key[5]}: "
        f"computes {100 * sparse_attn.computed_pairs(n_rows, chunk) / causal:.1f}% "
        f"of the causal pairs, keeps {100 * kept / causal:.1f}%")


# ``Policy.index_backends``: ``{(T, head_dim, index heads, index head_dim,
# topk, dtype): "select_pallas+masked_pallas" | "bisect_select+masked_xla" |
# ...}`` of the full mode's shapes: what ran the indexer's scores and
# selection (``ops/sparse_attn.index_backend``), and what the attention over it
KERNELS = (kernel("index", sparse_attn.sparse_attention, _shape),)


def apply(block, x, cache, t, readout_idx, n_valid):
    B, T, _ = x.shape
    cfg, d, cd = block.cfg, block.d_model, block.compute_dtype
    n_heads, n_kv = cfg["n_heads"], cfg["n_kv_heads"] or cfg["n_heads"]
    if cfg["attn_scale"] is not None:
        raise ValueError("sparse_attention takes no attn_scale")
    head_dim = cfg["head_dim"] or d // n_heads
    hi, di, topk = (cfg["index_heads"], cfg["index_head_dim"],
                    int(cfg["index_topk"]))
    theta, share = cfg["rope_theta"], cfg["rope_share"]
    if cfg["attn_gate"] or cfg["qk_norm"] not in (False, "head"):
        raise ValueError("sparse_attention takes no attn_gate and qk_norm "
                         "false | \"head\"")
    start = 0 if t is None else t

    def placed(a, first, share=1.0):
        return a if theta is None else apply_rope(a, first, theta, share)

    layer_in = x
    with jax.named_scope(OP_PROJ):
        h = block_norm(block, "ln_attn")(x).astype(cd)
        q = block_dense(block, n_heads * head_dim, "q_proj")(h)
        k = block_dense(block, n_kv * head_dim, "k_proj")(h)
        v = block_dense(block, n_kv * head_dim, "v_proj")(h)
        q = q.reshape(B, T, n_heads, head_dim)
        k, v = (a.reshape(B, T, n_kv, head_dim) for a in (k, v))
        if cfg["qk_norm"] == "head":
            q = block_norm(block, "q_norm", "rms")(q).astype(cd)
            k = block_norm(block, "k_norm", "rms")(k).astype(cd)
        k = placed(k, start, share)
    with jax.named_scope(INDEX):
        u = jax.lax.stop_gradient(h)
        qi = block_dense(block, hi * di, "index_q")(u).reshape(B, T, hi, di)
        ki = norm("layer", block.norm_eps, "index_k_norm")(
            block_dense(block, di, "index_k")(u)).astype(cd)
        ki = placed(ki[:, :, None], start)[:, :, 0]
        w = block_dense(block, hi, "index_w")(u)

    if cache is None and readout_idx is None:       # full: from position 0
        with jax.named_scope(OP_PROJ):
            q = placed(q, 0, share)
        with jax.named_scope(INDEX):
            qi = placed(qi, 0)
        loss = block.is_mutable_collection("intermediates")
        attn, kl, kept = block.fns["index"](
            q, k, v, qi, ki, w, topk, int(cfg["index_chunk"]), loss)
        if loss:
            block.sow("intermediates", "own_loss_rows", kl)
            block.sow("intermediates", "index_kept", kept.sum(-1))
    else:       # some rows from ``first`` against the rows ``keys`` holds
        if readout_idx is not None:
            x = layer_in = jax.lax.dynamic_slice_in_dim(x, readout_idx, 1,
                                                        axis=1)
            q, qi, w = (jax.lax.dynamic_slice_in_dim(a, readout_idx, 1,
                                                     axis=1)
                        for a in (q, qi, w))
            first, keys = readout_idx, (k, v, ki)
        else:
            cache = tuple(jax.lax.dynamic_update_slice_in_dim(
                rows, new.astype(rows.dtype), t, axis=1)
                for rows, new in zip(cache, (k, v, ki)))
            first, keys = t, cache
        with jax.named_scope(OP_PROJ):
            q = placed(q, first, share)
        with jax.named_scope(INDEX):
            qi = placed(qi, first)
        pos = first + jnp.arange(q.shape[1])
        attn, _, _ = jax.vmap(lambda q, qi, w, *keys: sparse_attn.sparse_rows(
            q, qi, w, pos, *keys, topk=topk, loss=False))(q, qi, w, *keys)
    with jax.named_scope(OP_PROJ):
        attn = attn.reshape(B, -1, n_heads * head_dim)
        x = block_residual(block, x,
                           block_dense(block, d, "attn_out")(attn),
                           "ln_attn_out")
    out = block_ffn(block, x, layer_in)
    return out if cache is None else (out, cache)


def own_loss_stats(intermediates, n_rows: int) -> dict:
    """What a forward's layers sowed, as the update's stats
    (``Policy.evaluate_stats``): ``own_loss_rows``, the loss rows summed over
    the layers ``[B, T]``, and ``index_kept_pct``, the share of the causal
    (query, key) pairs that the selections kept, over every sequence and
    such layer of the forward."""
    sown = [sub for name, sub in intermediates.items()
            if name.startswith("block_") and "own_loss_rows" in sub]
    kept = sum(sub["index_kept"][0].astype(jnp.float32).mean()
               for sub in sown)
    return {"own_loss_rows": sum(sub["own_loss_rows"][0] for sub in sown),
            "index_kept_pct": 100.0 * kept / (
                len(sown) * (n_rows * (n_rows + 1) // 2))}


def init_cache(cfg, d_model, batch, length, dtype, window):
    """Zeroed ``(k, v, ki)``: the main attention's rows ``[B, length, Hkv,
    hd]`` and the indexer's key rows ``[B, length, index_head_dim]``."""
    kv = (batch, length, cfg["n_kv_heads"] or cfg["n_heads"],
          cfg["head_dim"] or d_model // cfg["n_heads"])
    return (jnp.zeros(kv, dtype), jnp.zeros(kv, dtype),
            jnp.zeros((batch, length, cfg["index_head_dim"]), dtype))
