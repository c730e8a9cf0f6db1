"""Ring attention: causal attention with the sequence sharded over ``sp``.

The reference has nothing to mirror here (SURVEY.md §5.7 — no ring
attention, no context/sequence parallelism of any kind); this is a
TPU-first component designed for the hardware: each ``sp`` device holds one
contiguous chunk of the sequence, queries stay resident, and K/V chunks
rotate around the ring via ``jax.lax.ppermute`` — neighbor exchanges that
ride the ICI torus — while an online-softmax accumulator (shared with
:func:`relayrl_tpu.ops.attention.blockwise_attention`) combines each
incoming block. HBM cost per device is O(T/sp · T/sp) scores instead of
O(T²), and no device ever materializes the full K/V.

Causality across devices falls out of global positions: device ``i`` holds
queries ``[i·C, (i+1)·C)`` and, at round ``r``, the K/V chunk of device
``(i - r) mod n`` — blocks strictly in the future are masked to exact
zeros by the combine step (finite mask fill, no NaNs), so the result is
bitwise-comparable to dense attention on the gathered sequence.

Differentiable: the rotation is a ``lax.scan`` of ``ppermute`` calls, both
of which have transpose rules, so the backward pass is itself a ring pass
in the opposite direction — no custom VJP needed for correctness.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from relayrl_tpu.ops.attention import attention_block_combine, finalize_attention

_NEG_INF = -1e30


def ring_attention_sharded(q: jax.Array, k: jax.Array, v: jax.Array,
                           axis_name: str, axis_size: int,
                           causal: bool = True) -> jax.Array:
    """Per-shard ring attention body — call INSIDE ``shard_map``.

    ``q, k, v``: local chunks ``[B, C, H, D]`` where the global sequence is
    ``n = axis_size`` chunks laid out contiguously over ``axis_name``.
    """
    B, C, H, D = q.shape
    idx = jax.lax.axis_index(axis_name)
    local_pos = jnp.arange(C)
    q_pos = idx * C + local_pos

    o = jnp.zeros((B, H, C, D), jnp.float32)
    m = jnp.full((B, H, C), _NEG_INF, jnp.float32)
    l = jnp.zeros((B, H, C), jnp.float32)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def mask_for(kv_idx):
        if not causal:
            return jnp.ones((C, C), bool)
        return q_pos[:, None] >= (kv_idx * C + local_pos)[None, :]

    # Round 0 consumes the local chunk with no communication; rounds
    # 1..n-1 rotate-then-combine, so exactly n-1 neighbor exchanges happen
    # (no dead final rotation).
    o_m_l = attention_block_combine((o, m, l), q, k, v, mask_for(idx))

    def round_step(carry, r):
        o_m_l, k_blk, v_blk = carry
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        kv_idx = (idx - r) % axis_size
        o_m_l = attention_block_combine(o_m_l, q, k_blk, v_blk, mask_for(kv_idx))
        return (o_m_l, k_blk, v_blk), None

    if axis_size > 1:
        ((o, m, l), _, _), _ = jax.lax.scan(
            round_step, (o_m_l, k, v), jnp.arange(1, axis_size))
    else:
        o, m, l = o_m_l
    return finalize_attention(o, l, q.dtype)


def make_ring_attention(mesh: Mesh, axis_name: str = "sp",
                        causal: bool = True, batch_axes=("dp", "fsdp")):
    """Global-view ring attention ``[B, T, H, D] -> [B, T, H, D]``.

    Wraps :func:`ring_attention_sharded` in ``jax.shard_map`` over ``mesh``:
    time sharded on ``axis_name``, batch on whichever of ``batch_axes`` the
    mesh actually has (>1), everything else replicated. Composable under an
    outer ``jit`` — XLA sees only ppermutes between fused compute blocks.
    """
    axis_size = mesh.shape[axis_name]
    b_axes = tuple(ax for ax in batch_axes if mesh.shape.get(ax, 1) > 1)
    spec = P(b_axes if b_axes else None, axis_name, None, None)
    body = partial(ring_attention_sharded, axis_name=axis_name,
                   axis_size=axis_size, causal=causal)
    return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)
