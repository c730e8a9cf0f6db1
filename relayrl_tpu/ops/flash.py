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

The skip also happens INSIDE a grid step. A block that straddles the
diagonal (with T = block — one 1024 x 1024 tile a head — that is the
whole grid) is walked in causal strips, static slices of the refs already
in VMEM: the forward and dq kernels take ``sub`` query rows at a time
against the keys at or before them, the dk/dv kernel ``sub`` keys at a
time against the queries at or after them. What lies above the diagonal
beyond a strip's own ``sub x sub`` corner is never computed — no matmul,
no exp2, no mask — and the mask is built for the strip, not the block.
``score_area_pct`` says how much of the T x T score matrix that leaves
(100 % -> 62.5 % at T = block = 1024; 62.5 % -> 53.1 % at T = 4096), from
the same list of strips the kernels walk. A grid of one block a head
carries no state between steps, so there each strip writes its output
rows directly: no scratch, no init, no finalize pass.

The backward pass is two more Pallas kernels (the standard two-pass flash
VJP — no atomics or cross-block communication): a dq pass (grid q-major,
KV innermost, accumulator in VMEM) and a dk/dv pass (grid kv-major, Q
innermost), both recomputing p from the saved log-sum-exp residual and
using the identity ``ds = p * (dp - rowsum(do * o))``. Peak memory stays
O(T * block). The dk/dv pass works in transposed space — scores as
``[keys, queries]``, ``k @ q^T`` — so that ``dv = p^T @ do`` and
``dk = ds^T @ q`` are plain matmuls: the MXU streams the long key axis and
nothing of score-tile size goes through a transpose.

The per-query float32 residuals (LSE out of the forward, delta =
rowsum(do * o)) live in HBM as lane-dense rows
``[BH, num_q_blocks, 1, block_q]``: as ``[BH, T, 1]`` columns the same
numbers take 128 x their bytes in tiled memory — in every kernel's DMA,
in XLA's relayout of delta (3 ms an update in ``gpt2m-policy.update``) and
in the residuals a training step keeps (1.6 GB there). The dk/dv pass
reads the rows as they are; the forward and dq kernels, which need them
down the sublanes, turn a row in VMEM.

VPU economy (at head_dim 64 the two block matmuls only half-fill the MXU
contraction depth, so the score-tile softmax traffic sits on the critical
path):

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
  select) runs only on blocks that straddle the diagonal, strip by strip;
  strictly-below blocks take a mask-free path. The separate
  underflow guard the masked path used to carry is gone: keys are visited
  in order and key 0 is live for every query row, so the running max is
  finite from the first tile and ``exp2(-1e30 - m)`` flushes to exactly 0
  for masked entries.

Measured on the v5e (PR 30, kernel-only device times, PERF.md §6): strips
of 256 against 128 and 512 at (B*H, T, D) = (128, 1024, 64) and
(64, 4096, 128) — 256 is the rule (``_SUB_TILE``); the 1024 default block
was not re-swept (a finer grid pays ~0.35 us a step, 16 x the steps).

Grouped-query attention: ``k`` / ``v`` may carry fewer heads than ``q``
(``H = G * Hkv``; q head ``j`` reads k/v head ``j // G``). k and v reach
the kernels as they are, ``[B * Hkv, T, D]`` — never repeated in HBM,
forward or backward — and only the index maps change: in the forward and
dq grids (one step a q head) the k/v blocks are those of flat head
``b // G`` (``_kv_head``); the dk/dv grid has one row a K/V head and its
innermost axis walks the ``G`` q heads of the group, all their q blocks one
after another (``_group_step``: step ``i`` is q head ``b * G + i // nq``,
q block ``i % nq``), so that dk and dv are summed over the group in the
kernel's own accumulators and written once, while the k/v block stays
where it is. With ``G == 1`` both helpers return their arguments: the
index maps, grids and kernel bodies are those of plain multi-head
attention.

Numerics: scores/softmax in float32 regardless of input dtype; p (and ds)
are cast to the operands' dtype for the second matmul, which accumulates
in float32 (bf16 x bf16 -> f32 on the MXU). Outputs cast once to the
input dtype.
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


# Rows of a causal strip: a grid step on the diagonal is walked ``_SUB_TILE``
# queries (forward, dq) or keys (dk/dv) at a time. Measured, not swept
# finer than {128, 256, 512} (PERF.md §6, PR 30). Module-level so that a
# test can scale the same derivation down to interpreter-sized blocks; not
# a knob of the program.
_SUB_TILE = 256


def _sub_tile(block_q: int, block_kv: int, causal: bool) -> int | None:
    """Strip height for a ``block_q x block_kv`` grid step, or None where
    the step stays one tile: non-causal, unequal blocks (the diagonal does
    not run corner to corner), a block that ``_SUB_TILE`` does not divide
    or that holds fewer than two strips (T = 1 / 8 / 16 ... / 1000)."""
    if not causal or block_q != block_kv:
        return None
    if block_q % _SUB_TILE or block_q < 2 * _SUB_TILE:
        return None
    return _SUB_TILE


def _strips(block: int, sub: int,
            kv_major: bool = False) -> list[tuple[int, int, int, int]]:
    """``(q0, nq, k0, nk)``: the score tiles a ``block x block`` grid step
    on the diagonal computes — query rows ``[q0, q0 + nq)`` against key
    rows ``[k0, k0 + nk)``, local to the block. Query-major: ``sub``
    queries against every key at or before them. ``kv_major``: ``sub``
    keys against every query at or after them. Either way the same
    ``sub x sub`` sub-tiles, those with ``c <= r``. The kernels walk this
    list and ``score_area_pct`` sums it."""
    if kv_major:
        return [(k0, block - k0, k0, sub) for k0 in range(0, block, sub)]
    return [(q0, sub, 0, q0 + sub) for q0 in range(0, block, sub)]


@functools.lru_cache(maxsize=None)
def score_area_pct(T: int, block_q: int, block_kv: int, sub: int | None,
                   causal: bool) -> float:
    """Share (%) of the ``T x T`` score matrix the kernels compute: all of
    a non-causal call; under the causal mask the grid blocks that are live
    (``_dispatch``'s predicates), of which a block on the diagonal counts
    its strips only. A kernel that skipped everything above the diagonal
    would read ``50 + 50 / T``."""
    if not causal:
        return 100.0
    diagonal = (block_q * block_kv if sub is None else
                sum(nq * nk for _, nq, _, nk in _strips(block_q, sub)))
    area = 0
    for q_start in range(0, T, block_q):
        for k_start in range(0, T, block_kv):
            if k_start + block_kv - 1 <= q_start:        # interior
                area += block_q * block_kv
            elif k_start <= q_start + block_q - 1:       # on the diagonal
                area += diagonal
    return 100.0 * area / (T * T)


def _causal_mask(q_start, k_start, nq: int, nk: int,
                 transposed: bool = False):
    """Bool ``[nq, nk]`` (``[nk, nq]`` transposed), true where the query
    may see the key."""
    shape, q_axis = ((nk, nq), 1) if transposed else ((nq, nk), 0)
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    return q_pos >= k_pos


def _kv_head(b, group: int):
    """Flat k/v head ``[B * Hkv]`` that flat q head ``b`` of ``[B * H]``
    reads (``H = group * Hkv``, heads of one batch row adjacent)."""
    return b if group == 1 else b // group


def _group_step(b, i, group: int, nq: int):
    """dk/dv grid: K/V head ``b``, innermost step ``i`` -> (flat q head,
    q block). The axis holds ``group * nq`` steps: the q blocks of the
    group's first q head, then its second's..."""
    return (b, i) if group == 1 else (b * group + i // nq, i % nq)


_NT = (((1,), (1,)), ((), ()))   # a @ b^T
_NN = (((1,), (0,)), ((), ()))   # a @ b


def _scores2(q_ref, k_ref, rows, cols, mask, transposed: bool = False):
    """Log2-space scores of query rows ``rows`` against key rows ``cols``
    of the current block pair (``[keys, queries]`` when transposed) — the
    recompute shared by the forward and both backward kernels. q arrives
    pre-scaled by ``log2(e)/sqrt(D)`` so no per-tile multiply is needed.
    Inputs stay in their storage dtype (bf16 in production): the MXU runs
    bf16 x bf16 -> f32 at full rate, while casting to f32 first would
    quarter the matmul throughput; softmax math stays f32. ``mask`` is
    None on the mask-free path."""
    q, k = q_ref[0, rows, :], k_ref[0, cols, :]
    s = jax.lax.dot_general(*((k, q) if transposed else (q, k)), _NT,
                            preferred_element_type=jnp.float32)
    return s if mask is None else jnp.where(mask, s, _NEG_INF)


def _masked_scores2(q_ref, k_ref, q_start, k_start, masked: bool,
                    block_q: int, block_kv: int):
    """``_scores2`` of a whole block pair under the causal mask of its
    global positions (``parallel/ring_flash.py``'s chunk kernels)."""
    mask = (_causal_mask(q_start, k_start, block_q, block_kv)
            if masked else None)
    return _scores2(q_ref, k_ref, slice(None), slice(None), mask)


def _dispatch(tile, q_start, k_start, causal: bool, block_q: int,
              block_kv: int, sub: int | None, one_block: bool,
              kv_major: bool = False):
    """Shared block-class dispatch for all three kernels: skip blocks
    strictly above the causal diagonal, run mask-free on ``interior``
    blocks (strictly at-or-below it), and pay the iota/compare/select
    masking only on blocks that straddle the diagonal. ``live`` iff the
    block's first key comes no later than its last query. Keeping this in
    one place keeps forward and backward masking synchronized by
    construction.

    ``tile(rows, cols, mask)`` is the kernel's update for query rows
    ``rows`` against key rows ``cols`` of the block pair. A diagonal block
    with a ``sub`` is walked strip by strip (``_strips``); ``kv_major``
    (the dk/dv pass) takes key strips and wants its masks transposed.
    ``one_block``: the grid has one block a head, which is the diagonal one
    — no predicate, and no dead interior body for Mosaic to compile."""
    whole = slice(None)
    if not causal:
        tile(whole, whole, None)
        return

    def diagonal():
        if sub is None:
            tile(whole, whole, _causal_mask(q_start, k_start, block_q,
                                            block_kv, kv_major))
            return
        # Equal blocks: on the diagonal q_start == k_start, so positions
        # local to the block decide the mask and it is static.
        for q0, nq, k0, nk in _strips(block_q, sub, kv_major):
            tile(pl.ds(q0, nq), pl.ds(k0, nk),
                 _causal_mask(q0, k0, nq, nk, kv_major))

    if one_block:
        diagonal()
        return
    live = k_start <= q_start + block_q - 1
    interior = k_start + block_kv - 1 <= q_start
    pl.when(interior)(lambda: tile(whole, whole, None))
    pl.when(live & jnp.logical_not(interior))(diagonal)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch, causal: bool,
                block_q: int, block_kv: int, sub: int | None,
                one_block: bool):
    q_start = pl.program_id(1) * block_q
    k_start = pl.program_id(2) * block_kv

    if one_block:
        # One block a head: a tile is all its query rows ever see, so the
        # softmax needs no carried state — the same arithmetic as the
        # recurrence below from its initial state, written out directly.
        def tile(rows, cols, mask):
            s = _scores2(q_ref, k_ref, rows, cols, mask)
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp2(s - m)
            l = jnp.sum(p, axis=-1, keepdims=True)
            acc = jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0, cols, :], _NN,
                preferred_element_type=jnp.float32)
            o_ref[0, rows, :] = (acc / l).astype(o_ref.dtype)
            lse_ref[0, 0, :, rows] = (m + jnp.log2(l)).T

        _dispatch(tile, q_start, k_start, causal, block_q, block_kv, sub,
                  one_block)
        return

    acc_ref, m_ref, l_ref = scratch
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def tile(rows, cols, mask):
        s = _scores2(q_ref, k_ref, rows, cols, mask)
        m_prev = m_ref[rows]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # Masked entries carry s == _NEG_INF; with KV innermost, block
        # ik == 0 is fully live, so m_new is finite for every valid row
        # and exp2(_NEG_INF - m_new) flushes to exactly 0.
        p = jnp.exp2(s - m_new)
        corr = jnp.exp2(m_prev - m_new)
        l_ref[rows] = l_ref[rows] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[rows] = acc_ref[rows] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, cols, :], _NN,
            preferred_element_type=jnp.float32)
        m_ref[rows] = m_new

    _dispatch(tile, q_start, k_start, causal, block_q, block_kv, sub,
              one_block)

    @pl.when(ik == pl.num_programs(2) - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        # log2-space LSE — the backward recomputes p = exp2(s2 - lse2).
        # Stored as a lane-dense row (see _build_fwd).
        lse_ref[0, 0] = (m_ref[:] + jnp.log2(l)).T


def _row_spec(block_q: int, index_map):
    """Block of a per-query float32 residual (LSE, delta) stored as
    lane-dense rows ``[BH, num_q_blocks, 1, block_q]``: one ``[1, block_q]``
    row a q block (the two trailing dims are whole, so any block_q tiles).
    As a ``[BH, T, 1]`` column the same numbers take 128 x their bytes in
    tiled HBM (module docstring). The forward and dq kernels, which need
    them down the sublanes, turn a row in VMEM (``.T``)."""
    return pl.BlockSpec((1, 1, 1, block_q), index_map)


@functools.lru_cache(maxsize=None)
def _build_fwd(T: int, D: int, causal: bool, block_q: int, block_kv: int,
               sub: int | None, in_dtype_name: str, interpret: bool,
               group: int = 1):
    """Compile-cached pallas_call for a [BH, T, D] layout forward (k and v
    ``[BH / group, T, D]``)."""
    one_block = T == block_q == block_kv
    kernel = functools.partial(
        _fwd_kernel, causal=causal, block_q=block_q, block_kv=block_kv,
        sub=sub, one_block=one_block)
    grid = (None, T // block_q, T // block_kv)  # BH filled per call

    def kv_block(b, i, j):
        return (_kv_head(b, group), j, 0)

    def call(qr, kr, vr):
        bh = qr.shape[0]
        fwd = pl.pallas_call(
            kernel,
            name=FWD_NAME,
            grid=(bh,) + grid[1:],
            in_specs=[
                pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_kv, D), kv_block),
                pl.BlockSpec((1, block_kv, D), kv_block),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
                _row_spec(block_q, lambda b, i, j: (b, i, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, T, D), jnp.dtype(in_dtype_name)),
                jax.ShapeDtypeStruct((bh, T // block_q, 1, block_q),
                                     jnp.float32),
            ],
            scratch_shapes=[] if one_block else [
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


def _fwd(q, k, v, causal, block_q, block_kv, sub, interpret):
    B, T, H, D = q.shape
    call = _build_fwd(T, D, causal, block_q, block_kv, sub, q.dtype.name,
                      interpret, H // k.shape[2])
    out, lse_row = call(_prescale_q(_bthd_to_bht(q)), _bthd_to_bht(k),
                        _bthd_to_bht(v))
    return _bht_to_bthd(out, B, H), lse_row


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               *scratch, causal: bool, block_q: int, block_kv: int,
               sub: int | None, one_block: bool, scale: float):
    q_start = pl.program_id(1) * block_q
    k_start = pl.program_id(2) * block_kv

    def dq_of(rows, cols, mask):
        s = _scores2(q_ref, k_ref, rows, cols, mask)
        p = jnp.exp2(s - lse_ref[0, 0, :, rows].T)        # [rows, cols]
        dp = jax.lax.dot_general(
            do_ref[0, rows, :], v_ref[0, cols, :], _NT,
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0, :, rows].T)
        return jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0, cols, :], _NN,
            preferred_element_type=jnp.float32)

    if one_block:  # a tile is its query rows' whole dq: no accumulator
        def tile(rows, cols, mask):
            dq_ref[0, rows, :] = (dq_of(rows, cols, mask)
                                  * scale).astype(dq_ref.dtype)

        _dispatch(tile, q_start, k_start, causal, block_q, block_kv, sub,
                  one_block)
        return

    acc_ref, = scratch
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def tile(rows, cols, mask):
        acc_ref[rows] += dq_of(rows, cols, mask)

    _dispatch(tile, q_start, k_start, causal, block_q, block_kv, sub,
              one_block)

    @pl.when(ik == pl.num_programs(2) - 1)
    def _finalize():
        # acc holds d/d(q.k) contractions; one [block_q, D] multiply undoes
        # the score scaling (ds was accumulated in natural space).
        dq_ref[0] = (acc_ref[:] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, causal: bool,
                block_q: int, block_kv: int, sub: int | None,
                one_block: bool, group: int = 1, nq: int = 1):
    step = pl.program_id(2)  # the group's q heads, nq q blocks each
    iq = _group_step(0, step, group, nq)[1]

    @pl.when(step == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start = iq * block_q
    k_start = pl.program_id(1) * block_kv

    def tile(rows, cols, mask):
        # Transposed space, [keys, queries]: lse and delta are rows.
        s_t = _scores2(q_ref, k_ref, rows, cols, mask, transposed=True)
        p_t = jnp.exp2(s_t - lse_ref[0, 0, :, rows])
        dv_acc[cols] += jax.lax.dot_general(
            p_t.astype(do_ref.dtype), do_ref[0, rows, :], _NN,
            preferred_element_type=jnp.float32)
        dp_t = jax.lax.dot_general(
            v_ref[0, cols, :], do_ref[0, rows, :], _NT,
            preferred_element_type=jnp.float32)
        ds_t = p_t * (dp_t - delta_ref[0, 0, :, rows])
        dk_acc[cols] += jax.lax.dot_general(
            ds_t.astype(q_ref.dtype), q_ref[0, rows, :], _NN,
            preferred_element_type=jnp.float32)

    _dispatch(tile, q_start, k_start, causal, block_q, block_kv, sub,
              one_block, kv_major=True)

    @pl.when(step == pl.num_programs(2) - 1)
    def _finalize():
        # dk contracted ds against the PRE-SCALED q (scale * log2e folded
        # in), while true dk = scale * (ds^T @ q_unscaled) — so divide the
        # extra log2e back out. dv never touches scores: exact as-is.
        dk_ref[0] = (dk_acc[:] * (1.0 / _LOG2E)).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


@functools.lru_cache(maxsize=None)
def _build_bwd(T: int, D: int, causal: bool, block_q: int, block_kv: int,
               sub: int | None, in_dtype_name: str, interpret: bool,
               group: int = 1):
    """Compile-cached backward pallas_calls over the [BH, T, D] layout:
    a dq pass (grid q-major, KV innermost) and a dk/dv pass (grid kv-major,
    Q innermost) — the standard two-pass flash backward, so neither pass
    needs atomics or cross-block communication. ``lse`` and ``delta``
    arrive as lane-dense rows (``_row_spec``). ``group`` q heads share a
    K/V head: dq is per q head, dk/dv per K/V head, summed over the group
    along the dk/dv grid's innermost axis."""
    dtype = jnp.dtype(in_dtype_name)
    scale = 1.0 / (D ** 0.5)
    one_block = T == block_q == block_kv
    static = dict(causal=causal, block_q=block_q, block_kv=block_kv, sub=sub,
                  one_block=one_block)
    nq = T // block_q
    dq_kernel = functools.partial(_dq_kernel, scale=scale, **static)
    dkv_kernel = functools.partial(_dkv_kernel, group=group, nq=nq, **static)
    row_spec_q = _row_spec(block_q, lambda b, i, j: (b, i, 0, 0))

    def kv_block(b, i, j):          # dq grid: q head b, kv block j
        return (_kv_head(b, group), j, 0)

    def q_block(b, j, i):           # dk/dv grid: K/V head b, step i
        return (*_group_step(b, i, group, nq), 0)

    def q_row(b, j, i):
        return (*_group_step(b, i, group, nq), 0, 0)

    row_spec_kv_inner = _row_spec(block_q, q_row)

    def call(qr, kr, vr, dor, lse, delta):
        bh, bh_kv = qr.shape[0], kr.shape[0]
        dq_call = pl.pallas_call(
            dq_kernel,
            name=DQ_NAME,
            grid=(bh, T // block_q, T // block_kv),
            in_specs=[
                pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_kv, D), kv_block),
                pl.BlockSpec((1, block_kv, D), kv_block),
                pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
                row_spec_q,
                row_spec_q,
            ],
            out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            out_shape=jax.ShapeDtypeStruct((bh, T, D), dtype),
            scratch_shapes=([] if one_block
                            else [pltpu.VMEM((block_q, D), jnp.float32)]),
            interpret=interpret,
        )
        with jax.named_scope(DQ_NAME):
            dq = dq_call(qr, kr, vr, dor, lse, delta)
        dkv_call = pl.pallas_call(
            dkv_kernel,
            name=DKV_NAME,
            grid=(bh_kv, T // block_kv, group * nq),
            in_specs=[
                pl.BlockSpec((1, block_q, D), q_block),
                pl.BlockSpec((1, block_kv, D), lambda b, j, i: (b, j, 0)),
                pl.BlockSpec((1, block_kv, D), lambda b, j, i: (b, j, 0)),
                pl.BlockSpec((1, block_q, D), q_block),
                row_spec_kv_inner,
                row_spec_kv_inner,
            ],
            out_specs=[
                pl.BlockSpec((1, block_kv, D), lambda b, j, i: (b, j, 0)),
                pl.BlockSpec((1, block_kv, D), lambda b, j, i: (b, j, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh_kv, T, D), dtype),
                jax.ShapeDtypeStruct((bh_kv, T, D), dtype),
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


def _bwd_pallas(q, k, v, out, lse_row, do, causal, block_q, block_kv, sub,
                interpret):
    B, T, H, D = q.shape
    qr, kr, vr, dor = (_bthd_to_bht(x) for x in (q, k, v, do))
    qr = _prescale_q(qr)  # the kernels recompute log2-space scores
    of = _bthd_to_bht(out)
    delta = jnp.sum(dor.astype(jnp.float32) * of.astype(jnp.float32),
                    axis=-1).reshape(lse_row.shape)      # rows, like lse
    h_kv = k.shape[2]
    call = _build_bwd(T, D, causal, block_q, block_kv, sub, q.dtype.name,
                      interpret, H // h_kv)
    dq, dk, dv = call(qr, kr, vr, dor, lse_row, delta)
    return (_bht_to_bthd(dq, B, H), _bht_to_bthd(dk, B, h_kv),
            _bht_to_bthd(dv, B, h_kv))


@functools.lru_cache(maxsize=None)
def _make_flash(causal: bool, block_q: int, block_kv: int, sub: int | None,
                interpret: bool):
    @jax.custom_vjp
    def flash(q, k, v):
        return _fwd(q, k, v, causal, block_q, block_kv, sub, interpret)[0]

    def fwd(q, k, v):
        out, lse_row = _fwd(q, k, v, causal, block_q, block_kv, sub,
                            interpret)
        return out, (q, k, v, out, lse_row)

    def bwd(res, do):
        return _bwd_pallas(*res, do, causal, block_q, block_kv, sub,
                           interpret)

    flash.defvjp(fwd, bwd)
    return flash


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, block_q: int = 1024,
                    block_kv: int = 1024,
                    interpret: bool = False) -> jax.Array:
    """Fused attention on ``q [B, T, H, D]``, ``k`` / ``v``
    ``[B, T, Hkv, D]`` (``Hkv`` divides ``H``; q head ``j`` reads k/v head
    ``j // (H / Hkv)``) via a Pallas TPU kernel.

    Compiled by Mosaic, so it runs on a TPU backend only; off-TPU callers
    use :func:`relayrl_tpu.ops.attention.blockwise_attention` (what the
    model-level ``attention="flash"`` config resolves to there).
    ``interpret=True`` runs the kernel body in the Pallas interpreter — a
    test-only switch that is never defaulted on, so no device process can
    reach the interpreter without saying so.
    Requires ``T`` divisible by both block sizes; callers pad or fall back.

    Default blocks are 1024 (clamped to T): fewer, larger grid steps, and
    what a causal call does not need of a step on the diagonal is skipped
    inside it, strip by strip (``tiling``; the module docstring has the
    measurement the strip height rests on). Shrink blocks when VMEM
    pressure forces it (an interior step's score tile is
    block_q x block_kv f32).
    """
    if k.shape != v.shape or q.shape[2] % k.shape[2]:
        raise ValueError(f"q heads {q.shape[2]} do not group over k/v "
                         f"{k.shape} / {v.shape}")
    block_q, block_kv, sub = tiling(q.shape[1], causal, block_q, block_kv)
    return _make_flash(causal, block_q, block_kv, sub,
                       bool(interpret))(q, k, v)


def tiling(T: int, causal: bool = True, block_q: int = 1024,
           block_kv: int = 1024) -> tuple[int, int, int | None]:
    """``(block_q, block_kv, sub)`` as ``flash_attention`` runs a length-T
    call: the blocks clamped to T, and the height of the causal strips a
    grid step on the diagonal is walked in (None: one tile a step).
    ``score_area_pct(T, *tiling(T, ...), causal)`` is how much of the
    score matrix the kernels then compute."""
    block_q = min(block_q, T)
    block_kv = min(block_kv, T)
    if T % block_q or T % block_kv:
        raise ValueError(
            f"seq len {T} not divisible by blocks ({block_q}, {block_kv})")
    return block_q, block_kv, _sub_tile(block_q, block_kv, causal)
