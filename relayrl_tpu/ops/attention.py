"""Multi-head attention ops: dense, blockwise (memory-efficient) variants.

The reference has no attention / sequence models at all (SURVEY.md §5.7:
"long-context / sequence parallelism: absent"; its largest model is a 2x128
MLP — relayrl_framework/src/native/python/algorithms/REINFORCE/
kernel.py:14-21). These ops are the TPU-first long-context building blocks
the new framework adds as first-class components: a dense softmax attention
(the correctness reference), and a blockwise online-softmax attention
(flash-attention recurrence over KV blocks via ``lax.scan``) whose
per-block combine step is shared with the ring-attention sequence-parallel
path in :mod:`relayrl_tpu.parallel.ring`.

Layout convention: ``[batch, time, heads, head_dim]`` (BTHD) everywhere;
``v`` may have a head width of its own (latent attention: q and k 192 lanes
a head, v 128), which is then the result's.
Scores are computed in float32 regardless of input dtype (bf16 trunks feed
the MXU; softmax stays f32 for stability).

Grouped-query attention: ``k`` / ``v`` may carry fewer heads than ``q``
(``H = G * Hkv``; q head ``j`` reads k/v head ``j // G``). The dense and
blockwise forms then fold the ``G`` query heads of a group into the query
time axis — ``[B, G * Tq, Hkv, D]``, each folded row keeping its own time
position for the causal mask — so k and v are used as they are, never
repeated. With ``H == Hkv`` nothing is folded and the code is what it was.

Sliding-window attention: ``window`` (causal calls) lets query ``t`` see
keys ``s`` with ``t - window < s <= t`` — ``window`` keys with its own.
Both forms take it as one more term of the mask they already build
(:func:`visible`); the blockwise scan still visits every K/V block (it is
the CPU actors' and the tests' path — the kernels of
:mod:`relayrl_tpu.ops.flash` are what skip the blocks outside the band).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Finite large-negative fill: keeps exp()/grad NaN-free where a row is fully
# masked (same rationale as the policy-logit mask fill in models/mlp.py).
_NEG_INF = -1e30


def _fold_groups(q: jax.Array, kv_heads: int) -> tuple[jax.Array, int]:
    """``q [B, T, Hkv * G, D] -> ([B, G * T, Hkv, D], G)``: folded row
    ``g * T + t`` is query head ``h * G + g`` at time ``t``."""
    B, T, H, D = q.shape
    if H % kv_heads:
        raise ValueError(f"{H} query heads do not group over {kv_heads} "
                         f"k/v heads")
    G = H // kv_heads
    q = q.reshape(B, T, kv_heads, G, D).transpose(0, 3, 1, 2, 4)
    return q.reshape(B, G * T, kv_heads, D), G


def _unfold_groups(out: jax.Array, G: int) -> jax.Array:
    """Inverse of :func:`_fold_groups` on the attention output."""
    B, GT, Hkv, D = out.shape
    out = out.reshape(B, G, GT // G, Hkv, D).transpose(0, 2, 3, 1, 4)
    return out.reshape(B, GT // G, Hkv * G, D)


def visible(q_pos: jax.Array, kv_pos: jax.Array,
            window: int | None = None) -> jax.Array:
    """Bool ``[Tq, Tk]``: the causal mask over global positions, narrowed
    to the ``window`` keys up to the query's own where one is given."""
    seen = q_pos[:, None] >= kv_pos[None, :]
    if window is not None:
        seen &= q_pos[:, None] - kv_pos[None, :] < window
    return seen


def dense_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True,
                    q_offset: int | jax.Array = 0,
                    kv_offset: int | jax.Array = 0,
                    window: int | None = None,
                    kv_positions: jax.Array | None = None) -> jax.Array:
    """Plain softmax attention on ``[B, Tq, H, D] x [B, Tk, H, D]``.

    ``q_offset``/``kv_offset`` are the global time positions of the first
    query/key — used by the blockwise and ring variants to apply a causal
    mask across blocks that live on different devices. ``kv_positions``
    ``[Tk]`` gives each key row's position outright where the rows are not
    in order (a ring cache; negative: an empty row). ``window``: see the
    module docstring.
    """
    if window is not None and not causal:
        raise ValueError("a window narrows the causal mask: causal=False "
                         "has none")
    G, Tq = 1, q.shape[1]
    if k.shape[2] != q.shape[2]:
        q, G = _fold_groups(q, k.shape[2])
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = q_offset + jnp.arange(Tq)
        if G > 1:
            q_pos = jnp.tile(q_pos, G)
        if kv_positions is None:
            seen = visible(q_pos, kv_offset + jnp.arange(k.shape[1]), window)
        else:  # a row at a negative position does not exist yet
            seen = visible(q_pos, kv_positions, window) & (kv_positions >= 0)
        s = jnp.where(seen[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    return out if G == 1 else _unfold_groups(out, G)


def cached_attention(q: jax.Array, k_rows: jax.Array, v_rows: jax.Array,
                     kv_heads: int, q_offset: int | jax.Array = 0,
                     window: int | None = None,
                     kv_positions: jax.Array | None = None) -> jax.Array:
    """Causal attention of ``q [B, Tq, H, D]`` over a cache's rows, kept
    flat: ``k_rows [B, L, Hkv * D]``, ``v_rows [B, L, Hkv * Dv]``, a row's
    heads side by side in its lanes (a head of 64 lanes as a minor axis of
    its own pads to the TPU's 128-lane tile and doubles the cache; flat,
    a row is written where it lies and read as it is). ``q_offset``,
    ``window`` and ``kv_positions`` as :func:`dense_attention` takes them.

    One query row (a decode step) never splits the lanes into heads: each
    q head is laid into the lanes of its k/v head, zeros elsewhere, so one
    matmul over whole rows gives every head's scores, and one more every
    head's values, of which a head keeps its own lanes — the same products
    summed in float32 as :func:`dense_attention`'s, beside zeros. More
    rows (a prefill) split the cache into heads once and go through
    :func:`dense_attention`."""
    B, Tq, H, D = q.shape
    L = k_rows.shape[1]
    Dv = v_rows.shape[-1] // kv_heads
    if Tq != 1:
        return dense_attention(
            q, k_rows.reshape(B, L, kv_heads, D),
            v_rows.reshape(B, L, kv_heads, Dv), causal=True,
            q_offset=q_offset, window=window, kv_positions=kv_positions)
    if H % kv_heads:
        raise ValueError(f"{H} query heads do not group over {kv_heads} "
                         f"k/v heads")
    owner = jnp.arange(H) // (H // kv_heads)        # a q head's k/v head
    own_lanes = (jnp.arange(kv_heads * D) // D)[:, None] == owner[None, :]
    q_lanes = jnp.tile(jnp.swapaxes(q[:, 0], 1, 2), (1, kv_heads, 1))
    q_lanes = jnp.where(own_lanes[None], q_lanes, 0)
    scale = 1.0 / jnp.sqrt(D).astype(jnp.float32)
    s = jnp.einsum("bkc,bch->bhk", k_rows, q_lanes,
                   preferred_element_type=jnp.float32) * scale
    q_pos = jnp.asarray(q_offset)[None]
    if kv_positions is None:
        seen = visible(q_pos, jnp.arange(L), window)
    else:  # a row at a negative position does not exist yet
        seen = visible(q_pos, kv_positions, window) & (kv_positions >= 0)
    p = jax.nn.softmax(jnp.where(seen[None], s, _NEG_INF), axis=-1)
    rows = jnp.einsum("bhk,bkc->bhc", p.astype(v_rows.dtype), v_rows)
    own = (jnp.arange(kv_heads)[None, :] == owner[:, None])[None, :, :, None]
    out = jnp.where(own, rows.reshape(B, H, kv_heads, Dv), 0).sum(axis=2)
    return out[:, None]


def attention_block_combine(carry, q, k_blk, v_blk, mask):
    """One online-softmax accumulation step (the flash-attention recurrence).

    ``carry = (o, m, l)`` with ``o [B,H,Tq,D]`` un-normalized output,
    ``m [B,H,Tq]`` running max, ``l [B,H,Tq]`` running denominator — all
    float32, ``m`` finite (init ``_NEG_INF``, never ``-inf``, so fully-masked
    blocks contribute exact zeros instead of NaNs). ``mask [Tq, Tk]`` is the
    validity of each (query, key) pair for this block.
    """
    o, m, l = carry
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask[None, None], s, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # Rows with no valid key yet keep m == _NEG_INF; exp(s - m) would be
    # exp(0) = 1 there, so zero those entries via the mask.
    p = jnp.where(mask[None, None], jnp.exp(s - m_new[..., None]), 0.0)
    correction = jnp.exp(m - m_new)
    l = l * correction + jnp.sum(p, axis=-1)
    o = o * correction[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32))
    return o, m_new, l


def finalize_attention(o: jax.Array, l: jax.Array, out_dtype) -> jax.Array:
    """Normalize the online-softmax accumulator and restore BTHD layout."""
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return jnp.einsum("bhqd->bqhd", out).astype(out_dtype)


def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        block_size: int = 128,
                        causal: bool = True,
                        window: int | None = None) -> jax.Array:
    """Memory-efficient attention: ``lax.scan`` over KV blocks.

    Peak memory is O(Tq * block_size) instead of O(Tq * Tk); numerics match
    :func:`dense_attention` (same online-softmax math flash attention uses).
    Requires ``T % block_size == 0`` (pad to fixed shapes upstream — variable
    shapes would recompile, SURVEY.md §7.4 item 3). ``window``: see the
    module docstring.
    """
    if window is not None and not causal:
        raise ValueError("a window narrows the causal mask: causal=False "
                         "has none")
    B, T, H, D = q.shape
    if T % block_size != 0:
        raise ValueError(f"seq len {T} not divisible by block {block_size}")
    n_blocks = T // block_size
    G, q_pos = 1, jnp.arange(T)
    if k.shape[2] != H:
        H = k.shape[2]
        q, G = _fold_groups(q, H)
        q_pos = jnp.tile(q_pos, G)
    Dv = v.shape[-1]            # a value head's own width (latent: 192 / 128)
    k_blocks = k.reshape(B, n_blocks, block_size, H, D)
    v_blocks = v.reshape(B, n_blocks, block_size, H, Dv)

    o = jnp.zeros((B, H, G * T, Dv), jnp.float32)
    m = jnp.full((B, H, G * T), _NEG_INF, jnp.float32)
    l = jnp.zeros((B, H, G * T), jnp.float32)

    def scan_step(carry, blk):
        k_blk, v_blk, blk_idx = blk
        kv_pos = blk_idx * block_size + jnp.arange(block_size)
        if causal:
            mask = visible(q_pos, kv_pos, window)
        else:
            mask = jnp.ones((G * T, block_size), bool)
        return attention_block_combine(carry, q, k_blk, v_blk, mask), None

    (o, m, l), _ = jax.lax.scan(
        scan_step, (o, m, l),
        (jnp.moveaxis(k_blocks, 1, 0), jnp.moveaxis(v_blocks, 1, 0),
         jnp.arange(n_blocks)),
    )
    out = finalize_attention(o, l, q.dtype)
    return out if G == 1 else _unfold_groups(out, G)
