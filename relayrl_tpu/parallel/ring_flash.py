"""Ring attention with Pallas flash chunk kernels (TPU sp fast path).

:mod:`relayrl_tpu.parallel.ring` implements sequence-parallel causal
attention with an XLA online-softmax combine per ring round — correct
everywhere, and differentiable for free (``ppermute``/``scan`` transpose
rules). This module is the TPU-kernel tier of the same design: each
round's "attend local queries to the visiting K/V chunk" is ONE fused
Pallas kernel carrying the flash state ``(acc, m, l)`` in and out, so the
[C, C] per-round score matrix never materializes in HBM and the chunk
compute inherits the flash kernel's economics (log2-space softmax with
the scale pre-folded into q, diagonal-only masking — ops/flash.py).

The ring structure makes per-round masking *block-structured*: with the
global sequence laid out contiguously over the ``sp`` axis, the chunk a
device attends at round r is entirely in the past (full attention),
entirely in the future (skip — ``lax.cond`` passes the carry through
without even launching the kernel), or the local diagonal chunk
(standard causal masking on local positions). The kernels take that
3-way ``mode`` as an SMEM scalar, because under SPMD it is a traced
per-device value, not a Python constant.

Backward is a manual two-pass ring (no autodiff through the forward
scan): once the forward's final log2-space LSE is known, every
(q-chunk, kv-chunk) pair's gradient is independent — the same identity
the flash VJP uses (``ds = p * (dp - rowsum(do*o))``). dq accumulates
locally while K/V revisit; dk/dv accumulate on buffers that ROTATE WITH
their chunk: after n compute-then-rotate rounds each chunk's gradient
arrives back home on the device that owns it. One ``jax.custom_vjp``
wraps the whole sharded body, so nothing differentiates through
``pallas_call`` itself.

The reference has nothing to mirror here (SURVEY.md §5.7 — no sequence
parallelism of any kind); this composes two components the reference
also lacks (ring ppermute topology, flash kernels) into the TPU-first
long-context path. Parity with the scan ring and with dense attention is
tested on the CPU mesh in interpret mode (tests/test_attention.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from relayrl_tpu.ops.flash import (
    _LOG2E,
    _NEG_INF,
    _bht_to_bthd,
    _bthd_to_bht,
    _masked_scores2,
    _prescale_q,
)

# Per-round chunk relationship (SMEM scalar; traced per device).
MODE_SKIP, MODE_FULL, MODE_DIAG = 0, 1, 2


def _mode_dispatch(update, mode, q_ref, k_ref, q_start, k_start,
                   block_q: int, block_kv: int):
    """Block-class dispatch under a dynamic mode: FULL runs every block
    unmasked; DIAG runs the standard causal split on local positions
    (mask-free below the diagonal, iota/compare/select on it, skip
    above); SKIP fires neither predicate (callers lax.cond the whole
    kernel away for SKIP — this is belt-and-braces)."""
    full = mode == MODE_FULL
    diag = mode == MODE_DIAG
    live = k_start <= q_start + block_q - 1
    interior = k_start + block_kv - 1 <= q_start

    @pl.when(full | (diag & interior))
    def _unmasked():
        update(_masked_scores2(q_ref, k_ref, q_start, k_start, False,
                               block_q, block_kv))

    @pl.when(diag & live & jnp.logical_not(interior))
    def _masked():
        update(_masked_scores2(q_ref, k_ref, q_start, k_start, True,
                               block_q, block_kv))


def _chunk_fwd_kernel(mode_ref, q_ref, k_ref, v_ref, o_in_ref, m_in_ref,
                      l_in_ref, o_out_ref, m_out_ref, l_out_ref,
                      acc_ref, m_ref, l_ref, *, block_q: int, block_kv: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():  # resume the carried flash state
        acc_ref[:] = o_in_ref[0]
        m_ref[:] = m_in_ref[0]
        l_ref[:] = l_in_ref[0]

    def update(s):
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp2(s - m_new)
        corr = jnp.exp2(m_prev - m_new)
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    _mode_dispatch(update, mode_ref[0], q_ref, k_ref,
                   pl.program_id(1) * block_q, ik * block_kv,
                   block_q, block_kv)

    @pl.when(ik == pl.num_programs(2) - 1)
    def _flush():  # hand the state back to the ring carry (unfinalized)
        o_out_ref[0] = acc_ref[:]
        m_out_ref[0] = m_ref[:]
        l_out_ref[0] = l_ref[:]


def _chunk_dq_kernel(mode_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                     delta_ref, dq_in_ref, dq_out_ref, acc_ref, *,
                     block_q: int, block_kv: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[:] = dq_in_ref[0]

    def update(s):
        p = jnp.exp2(s - lse_ref[0])
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0])
        acc_ref[:] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _mode_dispatch(update, mode_ref[0], q_ref, k_ref,
                   pl.program_id(1) * block_q, ik * block_kv,
                   block_q, block_kv)

    @pl.when(ik == pl.num_programs(2) - 1)
    def _flush():  # still d/d(q.k)-space; * scale happens once, at the end
        dq_out_ref[0] = acc_ref[:]


def _chunk_dkv_kernel(mode_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                      delta_ref, dk_in_ref, dv_in_ref, dk_out_ref,
                      dv_out_ref, dk_acc, dv_acc, *, block_q: int,
                      block_kv: int):
    iq = pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = dk_in_ref[0]
        dv_acc[:] = dv_in_ref[0]

    def update(s):
        p = jnp.exp2(s - lse_ref[0])
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0])
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _mode_dispatch(update, mode_ref[0], q_ref, k_ref,
                   iq * block_q, pl.program_id(1) * block_kv,
                   block_q, block_kv)

    @pl.when(iq == pl.num_programs(2) - 1)
    def _flush():  # contracted against pre-scaled q; / log2e at the end
        dk_out_ref[0] = dk_acc[:]
        dv_out_ref[0] = dv_acc[:]


@functools.lru_cache(maxsize=None)
def _build_chunk_calls(C: int, D: int, block_q: int, block_kv: int,
                       in_dtype_name: str, interpret: bool):
    """Compile-cached pallas_calls for one [BH, C, D] chunk round.

    ``in_dtype_name`` is only an lru_cache key: every chunk output is
    deliberately float32 — the flash/gradient state must stay full
    precision across ring rounds, and the final cast happens once at the
    end of the ring.
    """
    nq, nk = C // block_q, C // block_kv
    mode_spec = pl.BlockSpec(memory_space=pltpu.SMEM)

    qi = lambda b, i, j: (b, i, 0)   # q-major rows (dq/fwd grids)
    ki = lambda b, i, j: (b, j, 0)
    qj = lambda b, j, i: (b, i, 0)   # kv-major grids (dkv)
    kj = lambda b, j, i: (b, j, 0)

    def blk(shape, imap):
        return pl.BlockSpec(shape, imap)

    fwd_kernel = functools.partial(_chunk_fwd_kernel, block_q=block_q,
                                   block_kv=block_kv)
    dq_kernel = functools.partial(_chunk_dq_kernel, block_q=block_q,
                                  block_kv=block_kv)
    dkv_kernel = functools.partial(_chunk_dkv_kernel, block_q=block_q,
                                   block_kv=block_kv)

    def fwd(mode, qs, k, v, o, m, l):
        bh = qs.shape[0]
        return pl.pallas_call(
            fwd_kernel,
            grid=(bh, nq, nk),
            in_specs=[
                mode_spec,
                blk((1, block_q, D), qi), blk((1, block_kv, D), ki),
                blk((1, block_kv, D), ki),
                blk((1, block_q, D), qi),             # o_in (f32)
                blk((1, block_q, 1), qi),             # m_in
                blk((1, block_q, 1), qi),             # l_in
            ],
            out_specs=[
                blk((1, block_q, D), qi),
                blk((1, block_q, 1), qi),
                blk((1, block_q, 1), qi),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, C, D), jnp.float32),
                jax.ShapeDtypeStruct((bh, C, 1), jnp.float32),
                jax.ShapeDtypeStruct((bh, C, 1), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, D), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
            ],
            interpret=interpret,
        )(mode, qs, k, v, o, m, l)

    def dq(mode, qs, k, v, do, lse2, delta, dq_acc):
        bh = qs.shape[0]
        return pl.pallas_call(
            dq_kernel,
            grid=(bh, nq, nk),
            in_specs=[
                mode_spec,
                blk((1, block_q, D), qi), blk((1, block_kv, D), ki),
                blk((1, block_kv, D), ki), blk((1, block_q, D), qi),
                blk((1, block_q, 1), qi), blk((1, block_q, 1), qi),
                blk((1, block_q, D), qi),             # dq_in (f32)
            ],
            out_specs=blk((1, block_q, D), qi),
            out_shape=jax.ShapeDtypeStruct((bh, C, D), jnp.float32),
            scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
            interpret=interpret,
        )(mode, qs, k, v, do, lse2, delta, dq_acc)

    def dkv(mode, qs, k, v, do, lse2, delta, dk_acc, dv_acc):
        bh = qs.shape[0]
        return pl.pallas_call(
            dkv_kernel,
            grid=(bh, nk, nq),
            in_specs=[
                mode_spec,
                blk((1, block_q, D), qj), blk((1, block_kv, D), kj),
                blk((1, block_kv, D), kj), blk((1, block_q, D), qj),
                blk((1, block_q, 1), qj), blk((1, block_q, 1), qj),
                blk((1, block_kv, D), kj),            # dk_in (f32)
                blk((1, block_kv, D), kj),            # dv_in (f32)
            ],
            out_specs=[
                blk((1, block_kv, D), kj),
                blk((1, block_kv, D), kj),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, C, D), jnp.float32),
                jax.ShapeDtypeStruct((bh, C, D), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_kv, D), jnp.float32),
                pltpu.VMEM((block_kv, D), jnp.float32),
            ],
            interpret=interpret,
        )(mode, qs, k, v, do, lse2, delta, dk_acc, dv_acc)

    return fwd, dq, dkv


def pick_chunk_block(C: int, cap: int = 1024) -> int | None:
    """Largest power-of-two divisor of the chunk length, capped; None when
    the chunk can't tile (callers fall back to the scan ring)."""
    b = 8
    if C % b:
        return None
    while b * 2 <= min(cap, C) and C % (b * 2) == 0:
        b *= 2
    return b


def _resolve_chunk_block(C: int, block: int | None) -> int:
    """Shared block-resolution/tile-validation policy for the sharded ring
    and the single-device cost model — one copy, so the cost model always
    measure the same kernels the ring runs."""
    if block is None:
        block = pick_chunk_block(C)
    if block is None or C % block:
        raise ValueError(
            f"chunk length {C} does not tile (block={block}); use the scan "
            f"ring (relayrl_tpu.parallel.ring) for this shape")
    return int(block)


def _finalize_chunk_state(o, l, out_dtype):
    """acc/l -> output chunk (the flash finalize; 1e-30 guards fully-masked
    rows, which only padding can produce). Returns (out, l_safe)."""
    l_safe = jnp.maximum(l, 1e-30)
    return (o / l_safe).astype(out_dtype), l_safe


def _round_mode(idx, r, axis_size, causal: bool):
    kv_idx = (idx - r) % axis_size
    if not causal:
        return jnp.int32(MODE_FULL), kv_idx
    mode = jnp.where(kv_idx == idx, MODE_DIAG,
                     jnp.where(kv_idx < idx, MODE_FULL, MODE_SKIP))
    return mode.astype(jnp.int32), kv_idx


@functools.lru_cache(maxsize=None)
def _make_ring_flash(axis_name: str, axis_size: int, causal: bool,
                     block: int, interpret: bool):
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def _calls(C, D, dtype):
        return _build_chunk_calls(C, D, block, block, dtype.name, interpret)

    @jax.custom_vjp
    def ring(q, k, v):
        out, _ = _fwd(q, k, v)
        return out

    def _fwd(q, k, v):
        B, C, H, D = q.shape
        fwd_call, _, _ = _calls(C, D, q.dtype)
        qs = _prescale_q(_bthd_to_bht(q))
        kb, vb = _bthd_to_bht(k), _bthd_to_bht(v)
        # Non-causal mode schedules are position-independent; an unused
        # axis_index would leave a dead partition_id op outside any manual
        # sharding annotation, which the SPMD partitioner rejects.
        idx = jax.lax.axis_index(axis_name) if causal else jnp.int32(0)
        bh = qs.shape[0]
        o = jnp.zeros((bh, C, D), jnp.float32)
        m = jnp.full((bh, C, 1), _NEG_INF, jnp.float32)
        l = jnp.zeros((bh, C, 1), jnp.float32)

        def compute(mode, kb, vb, oml):
            return jax.lax.cond(
                mode > 0,
                lambda a: tuple(fwd_call(mode[None], qs, a[0], a[1], *a[2])),
                lambda a: a[2],
                (kb, vb, tuple(oml)))

        # Round 0 on the local chunk, no communication; rounds 1..n-1
        # rotate then combine (no dead final rotation, as in ring.py).
        mode, _ = _round_mode(idx, 0, axis_size, causal)
        oml = compute(mode, kb, vb, (o, m, l))

        def round_step(carry, r):
            oml, kb, vb = carry
            kb = jax.lax.ppermute(kb, axis_name, perm)
            vb = jax.lax.ppermute(vb, axis_name, perm)
            mode, _ = _round_mode(idx, r, axis_size, causal)
            oml = compute(mode, kb, vb, oml)
            return (oml, kb, vb), None

        if axis_size > 1:
            (oml, _, _), _ = jax.lax.scan(
                round_step, (oml, kb, vb), jnp.arange(1, axis_size))
        o, m, l = oml
        out_f, l_safe = _finalize_chunk_state(o, l, q.dtype)
        out = _bht_to_bthd(out_f, B, H)
        lse2 = m + jnp.log2(l_safe)                      # [BH, C, 1], log2
        return out, lse2

    def fwd(q, k, v):
        out, lse2 = _fwd(q, k, v)
        return out, (q, k, v, out, lse2)

    def bwd(res, do):
        q, k, v, out, lse2 = res
        B, C, H, D = q.shape
        _, dq_call, dkv_call = _calls(C, D, q.dtype)
        scale = 1.0 / (D ** 0.5)
        qs = _prescale_q(_bthd_to_bht(q))
        kb, vb = _bthd_to_bht(k), _bthd_to_bht(v)
        dor, of = _bthd_to_bht(do), _bthd_to_bht(out)
        delta = jnp.sum(dor.astype(jnp.float32) * of.astype(jnp.float32),
                        axis=-1, keepdims=True)
        idx = jax.lax.axis_index(axis_name) if causal else jnp.int32(0)
        bh = qs.shape[0]
        dq_acc = jnp.zeros((bh, C, D), jnp.float32)
        dk_acc = jnp.zeros_like(dq_acc)
        dv_acc = jnp.zeros_like(dq_acc)

        def compute(r_mode, kb, vb, dq_acc, dk_acc, dv_acc):
            # One cond for both passes: the dq and dk/dv kernels share the
            # skip schedule by construction.
            return jax.lax.cond(
                r_mode > 0,
                lambda a: (dq_call(r_mode[None], qs, a[0], a[1], dor, lse2,
                                   delta, a[2]),
                           *dkv_call(r_mode[None], qs, a[0], a[1], dor,
                                     lse2, delta, a[3], a[4])),
                lambda a: (a[2], a[3], a[4]),
                (kb, vb, dq_acc, dk_acc, dv_acc))

        # Round 0 on the local chunk; rounds 1..n-1 rotate-then-compute
        # (kb/vb get no dead final rotation, mirroring the forward). dk/dv
        # accumulate on buffers that ROTATE WITH their chunk, so they need
        # one more rotation after the last compute to arrive home —
        # n rotations total for n rounds of contributions.
        mode0, _ = _round_mode(idx, 0, axis_size, causal)
        dq_acc, dk_acc, dv_acc = compute(mode0, kb, vb, dq_acc, dk_acc,
                                         dv_acc)

        def round_step(carry, r):
            dq_acc, kb, vb, dk_acc, dv_acc = carry
            kb = jax.lax.ppermute(kb, axis_name, perm)
            vb = jax.lax.ppermute(vb, axis_name, perm)
            dk_acc = jax.lax.ppermute(dk_acc, axis_name, perm)
            dv_acc = jax.lax.ppermute(dv_acc, axis_name, perm)
            mode, _ = _round_mode(idx, r, axis_size, causal)
            dq_acc, dk_acc, dv_acc = compute(mode, kb, vb, dq_acc, dk_acc,
                                             dv_acc)
            return (dq_acc, kb, vb, dk_acc, dv_acc), None

        if axis_size > 1:
            (dq_acc, _, _, dk_acc, dv_acc), _ = jax.lax.scan(
                round_step, (dq_acc, kb, vb, dk_acc, dv_acc),
                jnp.arange(1, axis_size))
        dk_acc = jax.lax.ppermute(dk_acc, axis_name, perm)
        dv_acc = jax.lax.ppermute(dv_acc, axis_name, perm)
        dq = _bht_to_bthd((dq_acc * scale).astype(q.dtype), B, H)
        dk = _bht_to_bthd((dk_acc * (1.0 / _LOG2E)).astype(k.dtype), B, H)
        dv = _bht_to_bthd(dv_acc.astype(v.dtype), B, H)
        return dq, dk, dv

    ring.defvjp(fwd, bwd)
    return ring


def ring_flash_attention_sharded(q: jax.Array, k: jax.Array, v: jax.Array,
                                 axis_name: str, axis_size: int,
                                 causal: bool = True,
                                 block: int | None = None,
                                 interpret: bool = False) -> jax.Array:
    """Per-shard flash-chunk ring attention — call INSIDE ``shard_map``.

    Same contract as :func:`relayrl_tpu.parallel.ring.ring_attention_sharded`
    (local chunks ``[B, C, H, D]``, global sequence contiguous over
    ``axis_name``); the chunk length must tile by 8 — use
    :func:`pick_chunk_block` and fall back to the scan ring when it
    returns None.
    """
    block = _resolve_chunk_block(q.shape[1], block)
    return _make_ring_flash(axis_name, axis_size, causal, block,
                            bool(interpret))(q, k, v)


def chunked_flash_local(q: jax.Array, k: jax.Array, v: jax.Array,
                        n_chunks: int, causal: bool = True,
                        block: int | None = None,
                        interpret: bool = False) -> jax.Array:
    """Single-device emulation of the ring's per-chunk kernel schedule
    (forward only) — the ring cost model without a pod.

    Runs the same flash state-carry chunk kernels the sp ring uses, but
    with every chunk local: q-chunk i visits kv-chunks 0..i (causal)
    under the same FULL/DIAG mode schedule, with the ``(acc, m, l)``
    state bounced through HBM between calls exactly as the ring carries
    it between rounds. Comparing this against the fused
    :func:`relayrl_tpu.ops.flash.flash_attention` at equal T measures
    what ring chunking costs per device (state-carry HBM traffic +
    per-call overhead) separately from ICI transfer time, which this
    deliberately excludes. No benchmark cell runs it (ROADMAP 3.6);
    tests/test_attention.py holds it to dense.
    """
    B, T, H, D = q.shape
    if T % n_chunks:
        raise ValueError(f"T={T} not divisible by n_chunks={n_chunks}")
    C = T // n_chunks
    block = _resolve_chunk_block(C, block)
    fwd_call, _, _ = _build_chunk_calls(C, D, block, block,
                                        q.dtype.name, bool(interpret))
    qs = _prescale_q(_bthd_to_bht(q))
    kr, vr = _bthd_to_bht(k), _bthd_to_bht(v)
    bh = qs.shape[0]
    outs = []
    for iq in range(n_chunks):
        qc = jax.lax.dynamic_slice_in_dim(qs, iq * C, C, axis=1)
        o = jnp.zeros((bh, C, D), jnp.float32)
        m = jnp.full((bh, C, 1), _NEG_INF, jnp.float32)
        l = jnp.zeros((bh, C, 1), jnp.float32)
        last = iq if causal else n_chunks - 1
        for kv in range(last + 1):
            mode = jnp.full((1,), MODE_DIAG if (causal and kv == iq)
                            else MODE_FULL, jnp.int32)
            kc = jax.lax.dynamic_slice_in_dim(kr, kv * C, C, axis=1)
            vc = jax.lax.dynamic_slice_in_dim(vr, kv * C, C, axis=1)
            o, m, l = fwd_call(mode, qc, kc, vc, o, m, l)
        out_f, _ = _finalize_chunk_state(o, l, q.dtype)
        outs.append(out_f)
    return _bht_to_bthd(jnp.concatenate(outs, axis=1), B, H)


def make_ring_flash_attention(mesh: Mesh, axis_name: str = "sp",
                              causal: bool = True,
                              batch_axes=("dp", "fsdp"),
                              block: int | None = None,
                              interpret: bool = False):
    """Global-view flash-chunk ring attention ``[B, T, H, D] -> same``.

    Drop-in for :func:`relayrl_tpu.parallel.ring.make_ring_attention` with
    the per-round combine running as Pallas chunk kernels.
    """
    axis_size = mesh.shape[axis_name]
    b_axes = tuple(ax for ax in batch_axes if mesh.shape.get(ax, 1) > 1)
    spec = P(b_axes if b_axes else None, axis_name, None, None)
    body = functools.partial(ring_flash_attention_sharded,
                             axis_name=axis_name, axis_size=axis_size,
                             causal=causal, block=block, interpret=interpret)
    return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)
