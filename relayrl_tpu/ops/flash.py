"""Fused flash-attention Pallas TPU kernels (forward + two-pass VJP).

The reference has no attention at all (SURVEY.md §5.7 — its largest model
is a 2x128 MLP, relayrl_framework/src/native/python/algorithms/REINFORCE/
kernel.py:14-21); :mod:`relayrl_tpu.ops.attention` adds dense and blockwise
(lax.scan online-softmax) variants. This module is the TPU-kernel tier of
the same op: one fused Pallas kernel that keeps the running-softmax state
``(acc, m, l)`` in VMEM scratch across the KV grid axis, so the [Tq, Tk]
score matrix never materializes in HBM and the two matmuls per block hit
the MXU back-to-back.

Grid layout: ``(B*H, num_q_blocks, num_kv_blocks)`` with the KV axis
innermost — TPU grids execute sequentially, so scratch initialized at
``kv == 0`` and finalized at ``kv == last`` implements the flash
recurrence without inter-kernel communication. Causal blocks strictly
above the diagonal are predicated off with ``pl.when`` (their loads still
happen — index maps are static — but the matmuls are skipped).

The backward pass is two more Pallas kernels (the standard two-pass flash
VJP — no atomics or cross-block communication): a dq pass (grid q-major,
KV innermost, accumulator in VMEM) and a dk/dv pass (grid kv-major, Q
innermost), both recomputing p from the saved log-sum-exp residual and
using the identity ``ds = p * (dp - rowsum(do * o))``. Peak memory stays
O(T * block).

VPU economy (at head_dim 64 the two block matmuls only quarter-fill the
MXU contraction depth, so the [block_q, block_kv] softmax traffic sits on
the critical path; what the two changes below buy is not measured on the
current code):

* **log2-space softmax**: ``1/sqrt(D) * log2(e)`` is folded into q OUTSIDE
  the kernel (one fused elementwise on the [BH, T, D] operand, 16x fewer
  multiplies than scaling every [block_q, block_kv] score tile), so the
  in-kernel recurrence uses ``exp2`` — faster than ``exp`` on the VPU —
  and the saved residual is the log2-space LSE. The backward finalizers
  undo the folding per output tile: ``dq = scale * acc`` and
  ``dk = acc / log2(e)`` (dk's score-recompute contracts against the
  pre-scaled q), a [block, D]-sized multiply once per block instead of a
  [block_q, block_kv] one per grid step.
* **diagonal specialization**: causal masking (two iotas, a compare and a
  select over the full score tile) runs only on blocks that straddle the
  diagonal; strictly-below blocks take a mask-free path. The separate
  underflow guard the masked path used to carry is gone: with the KV axis
  innermost the first block (k_start = 0) is live for every query row, so
  the running max is finite from step 0 and ``exp2(-1e30 - m)`` flushes
  to exactly 0 for masked entries.

Numerics: scores/softmax in float32 regardless of input dtype; the second
matmul runs in float32 against the f32 accumulator (MXU-friendly since
p is produced on-core). Outputs cast back to the input dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LOG2E = 1.4426950408889634

# The three kernels' names in a profiler trace and in the lowered program:
# each ``pallas_call`` carries its name and sits in a ``jax.named_scope`` of
# the same name, so a reduction finds it whatever flax scope called it.
FWD_NAME = "relayrl_flash_fwd"
DQ_NAME = "relayrl_flash_dq"
DKV_NAME = "relayrl_flash_dkv"


def _masked_scores2(q_ref, k_ref, q_start, k_start, masked: bool,
                    block_q: int, block_kv: int):
    """Log2-space score tile for the current block pair — the recompute
    shared by the forward and both backward kernels. q arrives pre-scaled
    by ``log2(e)/sqrt(D)`` so no per-tile multiply is needed. Inputs stay
    in their storage dtype (bf16 in production): the MXU runs
    bf16 x bf16 -> f32 at full rate, while casting to f32 first would
    quarter the matmul throughput; softmax math stays f32."""
    s = jax.lax.dot_general(
        q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if masked:
        q_pos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1)
        s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
    return s


def _dispatch(update, q_ref, k_ref, q_start, k_start, causal: bool,
              block_q: int, block_kv: int):
    """Shared block-class dispatch for all three kernels: skip blocks
    strictly above the causal diagonal, run mask-free on ``interior``
    blocks (strictly at-or-below it), and pay the iota/compare/select
    masking only on blocks that straddle the diagonal. ``live`` iff the
    block's first key comes no later than its last query. Keeping this in
    one place keeps forward and backward masking synchronized by
    construction."""
    if not causal:
        update(_masked_scores2(q_ref, k_ref, q_start, k_start, False,
                               block_q, block_kv))
        return
    live = k_start <= q_start + block_q - 1
    interior = k_start + block_kv - 1 <= q_start

    @pl.when(interior)
    def _interior():
        update(_masked_scores2(q_ref, k_ref, q_start, k_start, False,
                               block_q, block_kv))

    @pl.when(live & jnp.logical_not(interior))
    def _diagonal():
        update(_masked_scores2(q_ref, k_ref, q_start, k_start, True,
                               block_q, block_kv))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, causal: bool, block_q: int, block_kv: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_start = pl.program_id(1) * block_q
    k_start = ik * block_kv

    def update(s):
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # Masked entries carry s == _NEG_INF; with KV innermost, block
        # ik == 0 is fully live, so m_new is finite for every valid row
        # and exp2(_NEG_INF - m_new) flushes to exactly 0.
        p = jnp.exp2(s - m_new)
        corr = jnp.exp2(m_prev - m_new)
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    _dispatch(update, q_ref, k_ref, q_start, k_start, causal,
              block_q, block_kv)

    @pl.when(ik == pl.num_programs(2) - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        # log2-space LSE — the backward recomputes p = exp2(s2 - lse2).
        lse_ref[:] = (m_ref[:] + jnp.log2(l)).reshape(lse_ref.shape)


@functools.lru_cache(maxsize=None)
def _build_fwd(T: int, D: int, causal: bool, block_q: int, block_kv: int,
               in_dtype_name: str, interpret: bool):
    """Compile-cached pallas_call for a [BH, T, D] layout forward."""
    kernel = functools.partial(
        _fwd_kernel, causal=causal, block_q=block_q, block_kv=block_kv)
    grid = (None, T // block_q, T // block_kv)  # BH filled per call

    def call(qr, kr, vr):
        bh = qr.shape[0]
        fwd = pl.pallas_call(
            kernel,
            name=FWD_NAME,
            grid=(bh,) + grid[1:],
            in_specs=[
                pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_kv, D), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((1, block_kv, D), lambda b, i, j: (b, j, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
                # Trailing singleton keeps the lse block (block_q, 1)-tiled,
                # which the Mosaic layout rules accept (a bare (1, block_q)
                # block would violate the (8, 128) tile constraint).
                pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, T, D), jnp.dtype(in_dtype_name)),
                jax.ShapeDtypeStruct((bh, T, 1), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, D), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
            ],
            interpret=interpret,
        )
        with jax.named_scope(FWD_NAME):
            return fwd(qr, kr, vr)

    return call


def _bthd_to_bht(x):
    """[B,T,H,D] -> [B*H, T, D] (the kernel's flat layout)."""
    B, T, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, T, D)


def _bht_to_bthd(x, B, H):
    BH, T, D = x.shape
    return x.reshape(B, H, T, D).transpose(0, 2, 1, 3)


def _prescale_q(qr):
    """Fold softmax scale and the exp->exp2 base change into q: one fused
    elementwise over [BH, T, D] instead of a multiply on every
    [block_q, block_kv] score tile inside the kernels."""
    D = qr.shape[-1]
    c = _LOG2E / (D ** 0.5)
    return (qr.astype(jnp.float32) * c).astype(qr.dtype)


def _fwd(q, k, v, causal, block_q, block_kv, interpret):
    B, T, H, D = q.shape
    call = _build_fwd(T, D, causal, block_q, block_kv, q.dtype.name,
                      interpret)
    out, lse2 = call(_prescale_q(_bthd_to_bht(q)), _bthd_to_bht(k),
                     _bthd_to_bht(v))
    return _bht_to_bthd(out, B, H), lse2.reshape(B, H, T)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, causal: bool, block_q: int, block_kv: int,
               scale: float):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q_start = pl.program_id(1) * block_q
    k_start = ik * block_kv

    def update(s):
        p = jnp.exp2(s - lse_ref[0])                      # [bq, bk]
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0])
        acc_ref[:] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _dispatch(update, q_ref, k_ref, q_start, k_start, causal,
              block_q, block_kv)

    @pl.when(ik == pl.num_programs(2) - 1)
    def _finalize():
        # acc holds d/d(q.k) contractions; one [block_q, D] multiply undoes
        # the score scaling (ds was accumulated in natural space).
        dq_ref[0] = (acc_ref[:] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, causal: bool,
                block_q: int, block_kv: int):
    iq = pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start = iq * block_q
    k_start = pl.program_id(1) * block_kv

    def update(s):
        p = jnp.exp2(s - lse_ref[0])                      # [bq, bk]
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

    _dispatch(update, q_ref, k_ref, q_start, k_start, causal,
              block_q, block_kv)

    @pl.when(iq == pl.num_programs(2) - 1)
    def _finalize():
        # dk contracted ds against the PRE-SCALED q (scale * log2e folded
        # in), while true dk = scale * (ds^T @ q_unscaled) — so divide the
        # extra log2e back out. dv never touches scores: exact as-is.
        dk_ref[0] = (dk_acc[:] * (1.0 / _LOG2E)).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


@functools.lru_cache(maxsize=None)
def _build_bwd(T: int, D: int, causal: bool, block_q: int, block_kv: int,
               in_dtype_name: str, interpret: bool):
    """Compile-cached backward pallas_calls over the [BH, T, D] layout:
    a dq pass (grid q-major, KV innermost) and a dk/dv pass (grid kv-major,
    Q innermost) — the standard two-pass flash backward, so neither pass
    needs atomics or cross-block communication."""
    dtype = jnp.dtype(in_dtype_name)
    scale = 1.0 / (D ** 0.5)
    dq_kernel = functools.partial(_dq_kernel, causal=causal, block_q=block_q,
                                  block_kv=block_kv, scale=scale)
    dkv_kernel = functools.partial(_dkv_kernel, causal=causal,
                                   block_q=block_q, block_kv=block_kv)
    row_spec_q = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))
    row_spec_kv_inner = pl.BlockSpec((1, block_q, 1),
                                     lambda b, j, i: (b, i, 0))

    def call(qr, kr, vr, dor, lse, delta):
        bh = qr.shape[0]
        dq_call = pl.pallas_call(
            dq_kernel,
            name=DQ_NAME,
            grid=(bh, T // block_q, T // block_kv),
            in_specs=[
                pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_kv, D), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((1, block_kv, D), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
                row_spec_q,
                row_spec_q,
            ],
            out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            out_shape=jax.ShapeDtypeStruct((bh, T, D), dtype),
            scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
            interpret=interpret,
        )
        with jax.named_scope(DQ_NAME):
            dq = dq_call(qr, kr, vr, dor, lse, delta)
        dkv_call = pl.pallas_call(
            dkv_kernel,
            name=DKV_NAME,
            grid=(bh, T // block_kv, T // block_q),
            in_specs=[
                pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0)),
                pl.BlockSpec((1, block_kv, D), lambda b, j, i: (b, j, 0)),
                pl.BlockSpec((1, block_kv, D), lambda b, j, i: (b, j, 0)),
                pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0)),
                row_spec_kv_inner,
                row_spec_kv_inner,
            ],
            out_specs=[
                pl.BlockSpec((1, block_kv, D), lambda b, j, i: (b, j, 0)),
                pl.BlockSpec((1, block_kv, D), lambda b, j, i: (b, j, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, T, D), dtype),
                jax.ShapeDtypeStruct((bh, T, D), dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_kv, D), jnp.float32),
                pltpu.VMEM((block_kv, D), jnp.float32),
            ],
            interpret=interpret,
        )
        with jax.named_scope(DKV_NAME):
            dk, dv = dkv_call(qr, kr, vr, dor, lse, delta)
        return dq, dk, dv

    return call


def _bwd_pallas(q, k, v, out, lse2, do, causal, block_q, block_kv, interpret):
    B, T, H, D = q.shape
    qr, kr, vr, dor = (_bthd_to_bht(x) for x in (q, k, v, do))
    qr = _prescale_q(qr)  # the kernels recompute log2-space scores
    of = _bthd_to_bht(out)
    delta = jnp.sum(dor.astype(jnp.float32) * of.astype(jnp.float32),
                    axis=-1, keepdims=True)              # [BH, T, 1]
    lse3 = lse2.reshape(B * H, T, 1)
    call = _build_bwd(T, D, causal, block_q, block_kv, q.dtype.name,
                      interpret)
    dq, dk, dv = call(qr, kr, vr, dor, lse3, delta)
    return (_bht_to_bthd(dq, B, H), _bht_to_bthd(dk, B, H),
            _bht_to_bthd(dv, B, H))


@functools.lru_cache(maxsize=None)
def _make_flash(causal: bool, block_q: int, block_kv: int, interpret: bool):
    @jax.custom_vjp
    def flash(q, k, v):
        out, _ = _fwd(q, k, v, causal, block_q, block_kv, interpret)
        return out

    def fwd(q, k, v):
        out, lse2 = _fwd(q, k, v, causal, block_q, block_kv, interpret)
        return out, (q, k, v, out, lse2)

    def bwd(res, do):
        q, k, v, out, lse2 = res
        return _bwd_pallas(q, k, v, out, lse2, do, causal, block_q, block_kv,
                           interpret)

    flash.defvjp(fwd, bwd)
    return flash


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, block_q: int = 1024,
                    block_kv: int = 1024,
                    interpret: bool = False) -> jax.Array:
    """Fused attention on ``[B, T, H, D]`` via a Pallas TPU kernel.

    Compiled by Mosaic, so it runs on a TPU backend only; off-TPU callers
    use :func:`relayrl_tpu.ops.attention.blockwise_attention` (what the
    model-level ``attention="flash"`` config resolves to there).
    ``interpret=True`` runs the kernel body in the Pallas interpreter — a
    test-only switch that is never defaulted on, so no device process can
    reach the interpreter without saying so.
    Requires ``T`` divisible by both block sizes; callers pad or fall back.

    Default blocks are 1024 (clamped to T): fewer, larger grid steps. How
    block size trades against wall time is not measured on the current
    code; shrink blocks when VMEM pressure forces it (the in-kernel score
    tile is block_q x block_kv f32).
    """
    B, T, H, D = q.shape
    block_q = min(block_q, T)
    block_kv = min(block_kv, T)
    if T % block_q or T % block_kv:
        raise ValueError(
            f"seq len {T} not divisible by blocks ({block_q}, {block_kv})")
    return _make_flash(causal, block_q, block_kv, bool(interpret))(q, k, v)
