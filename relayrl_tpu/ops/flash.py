"""Fused flash-attention Pallas TPU kernels (forward + a one-kernel VJP).

The reference has no attention at all (SURVEY.md §5.7 — its largest model
is a 2x128 MLP, relayrl_framework/src/native/python/algorithms/REINFORCE/
kernel.py:14-21); :mod:`relayrl_tpu.ops.attention` adds dense and blockwise
(lax.scan online-softmax) variants. This module is the TPU-kernel tier of
the same op: one fused Pallas kernel that keeps the running-softmax state
``(acc, m, l)`` in VMEM scratch across the KV grid axis, so the [Tq, Tk]
score matrix never materializes in HBM and the two matmuls per block hit
the MXU back-to-back.

Operand layout at head_dim 64: q, k, v, do, out come in and out, dq, dk,
dv go out as the projections left them, ``[B, T, H * D]`` with the heads
side by side in the lanes (a free reshape of the block's ``[B, T, H, D]``),
in blocks ``(1, block, 128)``: TWO heads a grid step — heads ``2c`` and
``2c + 1`` at lane block ``c``. No head transpose exists round the
kernels, every block is lane-dense (a ``[BH, T, 64]`` operand is half
padding in tiled memory, in HBM and in the DMA) and a call has half the
grid steps. Two heads share a step without a lane shuffle: head ``h``'s
scores are ``(q * lanes_h) @ k^T``, contracted over all 128 lanes of
which the other head's 64 are exact zeros (the MXU passes a 64-deep
contraction pays for anyway); ``p_h @ v`` gives 128 lanes of which head
``h``'s 64 are its output, and the row strip is written as
``where(lane < 64, acc_0 / l_0, acc_1 / l_1)``. The backward the same way:
``ds_h @ k`` keeps one half, and ``p_h^T @ (do * lanes_h)``,
``ds_h^T @ (q * lanes_h)`` land in head ``h``'s lanes of dv and dk with
zeros beside them, so the heads' sums do not mix. Every added product is
``x * 0``: forward and gradients are the numbers of one head a step.
``lane_layout`` reads the layout off the operands' shape. Everywhere else
(head_dim 128, where a head-major operand is lane-dense already and the
lane blocks measured slower; ``Hkv * D`` no multiple of 128; an odd group)
the operands are turned head-major, ``[B * H, T, D]`` — one row a head,
one lane block a row — and the same kernels run one head a step.

Grid layout: ``(steps, num_q_blocks, num_kv_blocks)``, ``steps = B * H /
heads a step`` (batch row and lane block from the flat index,
``_lane_block``), with the KV axis
innermost — TPU grids execute sequentially, so scratch initialized at
``kv == 0`` and finalized at ``kv == last`` implements the flash
recurrence without inter-kernel communication. Causal blocks strictly
above the diagonal are predicated off with ``pl.when`` (their loads still
happen — index maps are static — but the matmuls are skipped).

The skip also happens INSIDE a grid step. A block that straddles the
diagonal (with T = block — one 1024 x 1024 tile a head — that is the
whole grid) is walked in causal strips, static slices of the refs already
in VMEM: the forward takes ``sub`` query rows at a time against the keys at
or before them, the backward ``sub`` keys at a time against the queries at
or after them. What lies above the diagonal
beyond a strip's own ``sub x sub`` corner is never computed — no matmul,
no exp2, no mask — and the mask is built for the strip, not the block.
``score_area_pct`` says how much of the T x T score matrix that leaves
(100 % -> 62.5 % at T = block = 1024; 62.5 % -> 53.1 % at T = 4096), from
the same list of strips the kernels walk. A grid of one block a head
carries no state between steps, so there each strip writes its output
rows directly: no scratch, no init, no finalize pass.

Sliding-window calls (``window``: query ``t`` sees keys ``s`` with ``t -
window < s <= t``) do not walk the sequence at all: **the innermost grid
axis is as long as the band**, ``window / block + 1`` steps for a window of
whole blocks (5 of 16 at T 16384, block 1024, window 4096), and the index
maps offset it by the outer block (``_band_step``) — the forward visits
K/V blocks ``i - (nband - 1) .. i`` of q block ``i``, the backward the q
blocks ``j .. j + nband - 1`` of K/V block ``j`` (for every q head of the
group).
A block outside the band is therefore neither loaded nor computed, where
the causal call's blocks above the diagonal are predicated off but still
loaded. A step that falls before block 0 (past the last, in the backward)
computes nothing and names the block its neighbour names, so Pallas skips
its DMA too. Inside the band a block is the diagonal one (causal strips, as
above), wholly inside the window (mask-free), or cut by the window's lower
edge: where that edge runs corner to corner — a window of whole blocks —
the block is walked in the mirror image of the diagonal strips (``sub``
queries against the keys AFTER them, ``_strips(lower=True)``), so what
lies below the edge beyond a strip's corner is skipped like what lies above
the diagonal; any other window masks that block as one tile, and a window
shorter than a block puts both edges into the diagonal block, one tile, one
mask. At (16384, 1024, 4096) the band needs 21.9 % of T x T, the kernels
compute 23.2 % (27.3 % without the strip walk of the cut block; the causal
call 50.8 %). The windowed kernels carry names that extend the plain ones
(``relayrl_flash_fwd_win`` ...), so a reader of all attention time finds
them and one that wants the band alone can tell them apart; ``window=None``
lowers the kernels that were there, a window of T or more is that call.
Measured on the v5e (PR 34, PERF.md section 6): a windowed layer of
``smallthinker-policy.update`` takes 0.43 of the global layer's kernel
time for 0.44 of its scores; the strip height of the cut block is the
diagonal's and was not swept on its own.

The backward pass is ONE more Pallas kernel (``relayrl_flash_bwd``): it
walks the score tiles once, in transposed space — scores as ``[keys,
queries]``, ``k @ q^T`` — recomputes ``p^T`` from the saved log-sum-exp,
makes ``ds^T = p^T * (dp^T - rowsum(do * o))`` and takes all three
gradients from the tile it holds: ``dv += p^T @ do`` and ``dk += ds^T @ q``
are plain matmuls (the MXU streams the long key axis and nothing of
score-tile size goes through a transpose) and ``dq += ds @ k`` contracts
dimension 0 of ``ds^T`` and ``k``, a turn Mosaic hides under the MXU — five
matmuls, one ``exp2`` pass and one mask a tile, where a dq pass and a dk/dv
pass made seven, two and two and walked q / k / v / do twice (PERF.md §6,
PR 53: 0.68-0.72 of the two kernels' time at every benchmark shape). Grid
``(k/v steps, q steps of a k/v step's group, K/V blocks, q blocks)``: the
sums live in float32 VMEM scratch over ALL of T — a q head's ``dq [T,
lanes]``, summed over the K/V blocks, and the k/v head's ``dk`` / ``dv [T,
lanes]``, summed over the q blocks and, the q head lying outside the K/V
blocks, over the group (a group's whole ``dq`` would not fit: 59 MB at 7
heads of T 16384) — 3 x T x lanes x 4 B, 24 MB at (16384, 128) and at
(8192, 256), under a ``vmem_limit_bytes`` of 64 MB (``_build_bwd`` refuses a
T x lanes past 4 Mi); nothing of them is ever a partial in HBM. An output
block leaves VMEM when its NAME changes, so the index maps name a block
from the step that completes it until the next one is complete
(``_dq_complete``: q block ``j`` at its diagonal step, or every q block
beside the last K/V block where no diagonal runs corner to corner; dk / dv
beside the group's last q step), and each is written once.

The per-query float32 residual (the forward's LSE) lives in HBM as
lane-dense rows ``[BH, num_q_blocks, 1, block_q]``, one row a head, the
heads of a grid step adjacent: as ``[BH, T, 1]`` columns the same numbers
take 128 x their bytes in tiled memory — in every kernel's DMA and in the
residuals a training step keeps (1.6 GB in ``gpt2m-policy.update``). The
backward reads the rows as they are; the forward, which needs them down
the sublanes, turns a row in VMEM. **delta = rowsum(do * o) never leaves
VMEM**: the step at which a q head first holds a q block
(``_first_visit``: beside K/V block 0, or where the block enters the band)
takes the out block beside the do block it holds anyway, sums each head's
lanes of ``do * o`` and keeps the row in scratch for the block's later
steps, which name the out block they were last given and so load nothing —
XLA never sees a ``[B, T, H, D]``-shaped reduction (in the lane layout it
turned the float32 product T-minor to make it: 0.78 ms of copy and 2.2 ms
of convert-multiply an update in ``gpt2m-policy.update``, PERF.md §6 PR
32), and no second kernel makes it.

VPU economy (at head_dim 64 the contraction is 128 deep with half of it
zeros — the other head's lanes — so the two block matmuls do half the
useful work of an MXU pass and the score-tile softmax traffic sits on the
critical path):

* **log2-space softmax**: ``1/sqrt(D) * log2(e)`` is folded into q OUTSIDE
  the kernel (one fused elementwise on the operand, 16x fewer
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
the kernels as they are — never repeated in HBM, forward or backward — and
only the index maps change: in the forward grid (one step a q head, or a
pair) the k/v blocks are those of flat step ``g // G`` (``_kv_head``:
``g`` itself at ``G == 1``); the backward grid has one row a k/v step, and
its second axis walks the ``G`` q steps of the group — q step ``g * G + t``
— each over all the K/V blocks, so that dk and dv are summed over the group
in the kernel's own accumulators and written once, beside the group's last
q step. With ``G == 1`` that axis has one step and the grid, index maps
and kernel body are those of plain multi-head attention. Two heads a
step and grouped (head_dim 64, ``G`` even): the q pair shares ONE k/v head,
which sits in one half of a 128-lane k/v block; ``_shared_kv`` copies that
half into both (a lane roll by 64 in VMEM, once a grid step) so that each
q head finds it in its own lanes, and the backward keeps one dk / dv
accumulator a k/v head of the block, whose two halves — the sums over the
even and the odd q heads — are added at the end.

The kernels' trace is shared by the repeats of a call (``_make_flash``). A
``pallas_call`` traces its body to a jaxpr and lowers it to Mosaic every
time it is called, two heads a step about doubles a body, and a trunk makes
the same two calls a layer: the first attention call of a trace runs the
builders' calls bare and every repeat in that trace runs them through one
inner ``jit`` each, so that a 24-layer trunk traces and lowers two bodies a
kernel, not 24, and a model with one attention layer never meets the inner
``jit`` — it would share nothing there and only move the stack depth at
which the lowering runs (PERF.md §6, PR 33, has what that cost, by phase).

Numerics: scores/softmax in float32 regardless of input dtype; p (and ds)
are cast to the operands' dtype for the second matmul, which accumulates
in float32 (bf16 x bf16 -> f32 on the MXU). Outputs cast once to the
input dtype.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from relayrl_tpu.ops.scopes import (  # noqa: F401  (re-exported)
    BWD_NAME,
    FWD_NAME,
    LATENT_SUFFIX,
    OP_PROJ,
    WINDOW_SUFFIX,
)

# what a caller's checkpoint may keep of the forward, by name: the output and
# the rows' log-sum-exp, so that its backward runs no forward kernel again
# (no ``relayrl_flash_`` prefix: that one finds the kernels)
OUT_NAME = "flash_fwd_out"
LSE_NAME = "flash_fwd_lse"

_NEG_INF = -1e30
_LOG2E = 1.4426950408889634

# The two kernels' names in a profiler trace and in the lowered program
# (``ops/scopes.py`` holds them): each ``pallas_call`` carries its name and
# sits in a ``jax.named_scope`` of the same name, so a reduction finds it
# whatever flax scope called it. What this module does round the kernels —
# the head transposes, q's pre-scaling — is the operator's glue and sits
# under ``OP_PROJ``, in the forward and in the backward rule.


# Rows of a causal strip: a grid step on the diagonal is walked ``_SUB_TILE``
# queries (forward) or keys (backward) at a time. Measured, not swept
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


def _strips(block: int, sub: int, kv_major: bool = False,
            lower: bool = False) -> list[tuple[int, int, int, int]]:
    """``(q0, nq, k0, nk)``: the score tiles a ``block x block`` grid step
    on the diagonal computes — query rows ``[q0, q0 + nq)`` against key
    rows ``[k0, k0 + nk)``, local to the block. Query-major: ``sub``
    queries against every key at or before them. ``kv_major``: ``sub``
    keys against every query at or after them. Either way the same
    ``sub x sub`` sub-tiles, those with ``c <= r``. ``lower``: the block a
    window's lower edge cuts corner to corner (a window of whole blocks:
    local key ``c`` is seen by local query ``r`` iff ``c > r``) — the
    mirror image, the sub-tiles with ``c >= r``. The kernels walk this
    list and ``score_area_pct`` sums it."""
    if lower and kv_major:
        return [(0, k0 + sub, k0, sub) for k0 in range(0, block, sub)]
    if lower:
        return [(q0, sub, q0, block - q0) for q0 in range(0, block, sub)]
    if kv_major:
        return [(k0, block - k0, k0, sub) for k0 in range(0, block, sub)]
    return [(q0, sub, 0, q0 + sub) for q0 in range(0, block, sub)]


def _band_blocks(window: int, block: int, n_blocks: int) -> int:
    """K/V blocks a q block's band touches (q blocks a K/V block's): the
    diagonal one and those the ``window`` keys before a block's first query
    reach into — ``window / block + 1`` for a window of whole blocks — at
    most all ``n_blocks``."""
    return min((window + block - 2) // block + 1, n_blocks)


@functools.lru_cache(maxsize=None)
def score_area_pct(T: int, block_q: int, block_kv: int, sub: int | None,
                   causal: bool, window: int | None = None) -> float:
    """Share (%) of the ``T x T`` score matrix the kernels compute: all of
    a non-causal call; under the causal mask the grid blocks that are live
    (``_dispatch``'s predicates), of which a block on the diagonal counts
    its strips only. A kernel that skipped everything above the diagonal
    would read ``50 + 50 / T``. With a ``window`` (< T; equal blocks) only
    the blocks of the band are visited at all: the diagonal one, those
    wholly inside the window, and the ones its lower edge cuts, which count
    their strips where they are walked in strips (a window of whole
    blocks) and whole where they are masked as one tile."""
    if not causal:
        return 100.0
    if window is not None and window < block_q:   # both edges in one block
        diagonal = block_q * block_kv
    else:
        diagonal = (block_q * block_kv if sub is None else
                    sum(nq * nk for _, nq, _, nk in _strips(block_q, sub)))
    area = 0
    if window is not None:
        cut = (diagonal if sub is not None and window % block_q == 0
               else block_q * block_kv)
        n_blocks = T // block_q
        for i in range(n_blocks):
            for d in range(min(i + 1, _band_blocks(window, block_q,
                                                   n_blocks))):
                area += (diagonal if d == 0 else block_q * block_kv
                         if d * block_q <= window - block_q else cut)
        return 100.0 * area / (T * T)
    for q_start in range(0, T, block_q):
        for k_start in range(0, T, block_kv):
            if k_start + block_kv - 1 <= q_start:        # interior
                area += block_q * block_kv
            elif k_start <= q_start + block_q - 1:       # on the diagonal
                area += diagonal
    return 100.0 * area / (T * T)


def _causal_mask(q_start, k_start, nq: int, nk: int,
                 transposed: bool = False, window: int | None = None,
                 above: bool = False):
    """Bool ``[nq, nk]`` (``[nk, nq]`` transposed), true where the query
    may see the key: the key at or before it and, with a ``window``, fewer
    than ``window`` rows before it. ``above``: the complement of the causal
    mask alone, ``k_pos > q_pos`` — what the block a whole-block window's
    lower edge cuts looks like in positions local to it."""
    shape, q_axis = ((nk, nq), 1) if transposed else ((nq, nk), 0)
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    if above:
        return k_pos > q_pos
    seen = q_pos >= k_pos
    return seen if window is None else seen & (q_pos - k_pos < window)


def _kv_head(b, group: int):
    """Flat k/v head ``[B * Hkv]`` that flat q head ``b`` of ``[B * H]``
    reads (``H = group * Hkv``, heads of one batch row adjacent)."""
    return b if group == 1 else b // group


_NT = (((1,), (1,)), ((), ()))   # a @ b^T
_NN = (((1,), (0,)), ((), ()))   # a @ b
_TN = (((0,), (0,)), ((), ()))   # a^T @ b


def _head_lanes(hps: int, D: int):
    """One entry a head of a grid step: the head's ``D`` lanes of the
    step's ``hps * D``-lane blocks as a bool ``[1, hps * D]`` mask. One
    head a step owns the whole block: ``[None]``, and the kernel bodies
    are those of the head-major layout."""
    if hps == 1:
        return [None]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, hps * D), 1)
    return [(lane >= h * D) & (lane < (h + 1) * D) for h in range(hps)]


def _head_rows(ref, rows, lanes):
    """Rows of a block with every lane but one head's zeroed: a matmul
    that contracts them over all the block's lanes contracts over that
    head's, and one that takes them as its right operand leaves the other
    heads' output lanes exact zeros."""
    x = ref[0, rows, :]
    return x if lanes is None else jnp.where(lanes, x, jnp.zeros_like(x))


def _by_head(parts, heads):
    """A block's lanes from its heads' results: head ``h``'s lanes of
    ``parts[h]`` (each as wide as the block, or a column a row)."""
    out = parts[-1]
    for part, lanes in zip(parts[-2::-1], heads[-2::-1]):
        out = jnp.where(lanes, part, out)
    return out


def _shared_kv(k_ref, v_ref, k2_ref, v2_ref, half, D: int):
    """Two q heads a step over ONE k/v head (grouped-query heads at
    head_dim 64): the k/v block holds two k/v heads and the step's q heads
    both read its head ``half``. Copy that head into both halves of the
    block (a lane roll by ``D`` in VMEM, once a grid step), so that each q
    head finds its k/v head in its own lanes; returns the refs to read."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 2 * D), 1)
    own = (lane >= D) == (half == 1)
    for src, dst in ((k_ref, k2_ref), (v_ref, v2_ref)):
        x = src[0].astype(jnp.float32)   # 32-bit lanes: the roll's own width
        dst[0] = jnp.where(own, x, pltpu.roll(x, D, 1)).astype(dst.dtype)
    return k2_ref, v2_ref


def _shares_kv(hps: int, group: int) -> bool:
    """The q heads of a grid step read one k/v head (``_shared_kv``)."""
    return hps > 1 and group > 1


def _kv_half(g, group: int, hps: int):
    """Which head of its k/v block q step ``g`` reads (``_shared_kv``):
    ``group // hps`` steps share a k/v head."""
    return (g // (group // hps)) % hps


def _scores2(q_ref, k_ref, rows, cols, mask, transposed: bool = False,
             lanes=None):
    """Log2-space scores of query rows ``rows`` against key rows ``cols``
    of the current block pair (``[keys, queries]`` when transposed) — the
    recompute shared by the forward and the backward kernel. q arrives
    pre-scaled by ``log2(e)/sqrt(D)`` so no per-tile multiply is needed.
    Inputs stay in their storage dtype (bf16 in production): the MXU runs
    bf16 x bf16 -> f32 at full rate, while casting to f32 first would
    quarter the matmul throughput; softmax math stays f32. ``mask`` is
    None on the mask-free path. ``lanes``: the head of the step (see
    ``_head_lanes``) — the other heads' q lanes enter the contraction as
    zeros."""
    q, k = _head_rows(q_ref, rows, lanes), k_ref[0, cols, :]
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
              kv_major: bool = False, window: int | None = None,
              in_grid=None):
    """Shared block-class dispatch for both kernels: skip blocks
    strictly above the causal diagonal, run mask-free on ``interior``
    blocks (strictly at-or-below it), and pay the iota/compare/select
    masking only on blocks that straddle the diagonal. ``live`` iff the
    block's first key comes no later than its last query. Keeping this in
    one place keeps forward and backward masking synchronized by
    construction.

    ``tile(rows, cols, mask)`` is the kernel's update for query rows
    ``rows`` against key rows ``cols`` of the block pair. A diagonal block
    with a ``sub`` is walked strip by strip (``_strips``); ``kv_major``
    (the backward) takes key strips and wants its masks transposed.
    ``one_block``: the grid has one block a head, which is the diagonal one
    — no predicate, and no dead interior body for Mosaic to compile.

    With a ``window`` the grid holds the blocks of the band only
    (``_band_step``; ``in_grid``: false on a step that fell off the grid's
    end and repeats its neighbour's blocks), and a block is classed by how
    many rows its queries lie after its keys: 0 — the diagonal one, as
    above; up to ``window - block`` — wholly inside, mask-free; more — cut
    by the window's lower edge, walked in the mirror image of the diagonal
    strips where the edge runs corner to corner (a window of whole blocks)
    and masked as one tile otherwise. A window shorter than a block puts
    both edges into the diagonal block: one tile, one mask."""
    whole = slice(None)
    if not causal:
        tile(whole, whole, None)
        return
    both_edges = window is not None and window < block_q

    def diagonal():
        if sub is None or both_edges:
            tile(whole, whole, _causal_mask(
                q_start, k_start, block_q, block_kv, kv_major,
                window if both_edges else None))
            return
        # Equal blocks: on the diagonal q_start == k_start, so positions
        # local to the block decide the mask and it is static.
        for q0, nq, k0, nk in _strips(block_q, sub, kv_major):
            tile(pl.ds(q0, nq), pl.ds(k0, nk),
                 _causal_mask(q0, k0, nq, nk, kv_major))

    if one_block:
        diagonal()
        return
    if window is None:
        live = k_start <= q_start + block_q - 1
        interior = k_start + block_kv - 1 <= q_start
        pl.when(interior)(lambda: tile(whole, whole, None))
        pl.when(live & jnp.logical_not(interior))(diagonal)
        return

    def cut():
        if sub is None or window % block_q:
            tile(whole, whole, _causal_mask(q_start, k_start, block_q,
                                            block_kv, kv_major, window))
            return
        for q0, nq, k0, nk in _strips(block_q, sub, kv_major, lower=True):
            tile(pl.ds(q0, nq), pl.ds(k0, nk),
                 _causal_mask(q0, k0, nq, nk, kv_major, above=True))

    rows_apart = q_start - k_start      # a multiple of the block, >= 0
    pl.when(in_grid & (rows_apart == 0))(diagonal)
    if window >= 2 * block_q:           # some block lies wholly inside
        pl.when(in_grid & (rows_apart > 0)
                & (rows_apart <= window - block_q))(
                    lambda: tile(whole, whole, None))
    pl.when(in_grid & (rows_apart > max(window - block_q, 0)))(cut)


def _band_step(outer, inner, nband: int | None, n_blocks: int = 0,
               kv_major: bool = False):
    """A windowed call's innermost grid axis is as long as the band
    (``_band_blocks``), not as the sequence: ``(block, in_grid)`` of step
    ``inner`` beside outer block ``outer``. Forward (q block outside): K/V
    blocks ``outer - (nband - 1) .. outer``, in key order; ``kv_major``
    (the backward, K/V block outside): q blocks ``outer .. outer + nband -
    1``. A step that falls before block 0 or past the last block
    computes nothing (``in_grid`` false) and names the nearest block of the
    grid, the one its neighbour names, so that nothing new is loaded for
    it. ``nband`` None — no window: the axis is the sequence, every step
    in the grid."""
    if nband is None:
        return inner, None
    if kv_major:
        block = outer + inner
        return jnp.minimum(block, n_blocks - 1), block < n_blocks
    block = outer - (nband - 1) + inner
    return jnp.maximum(block, 0), block >= 0


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch, causal: bool,
                block_q: int, block_kv: int, sub: int | None,
                one_block: bool, hps: int, D: int, group: int,
                window: int | None = None, nband: int | None = None):
    q_start = pl.program_id(1) * block_q
    kv_block, in_grid = _band_step(pl.program_id(1), pl.program_id(2), nband)
    k_start = kv_block * block_kv
    heads = _head_lanes(hps, D)
    if _shares_kv(hps, group):
        k_ref, v_ref = _shared_kv(
            k_ref, v_ref, *scratch[:2],
            _kv_half(pl.program_id(0), group, hps), D)
        scratch = scratch[2:]

    def pv(p, cols):
        # every lane of the block; head h's D lanes are p_h @ v_h
        return jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, cols, :], _NN,
            preferred_element_type=jnp.float32)

    if one_block:
        # One block a head: a tile is all its query rows ever see, so the
        # softmax needs no carried state — the same arithmetic as the
        # recurrence below from its initial state, written out directly.
        def tile(rows, cols, mask):
            outs = []
            for h, lanes in enumerate(heads):
                s = _scores2(q_ref, k_ref, rows, cols, mask, lanes=lanes)
                m = jnp.max(s, axis=-1, keepdims=True)
                p = jnp.exp2(s - m)
                l = jnp.sum(p, axis=-1, keepdims=True)
                outs.append(pv(p, cols) / l)
                lse_ref[h, 0, :, rows] = (m + jnp.log2(l)).T
            o_ref[0, rows, :] = _by_head(outs, heads).astype(o_ref.dtype)

        _dispatch(tile, q_start, k_start, causal, block_q, block_kv, sub,
                  one_block, window=window)
        return

    acc_ref, m_ref, l_ref = scratch
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def tile(rows, cols, mask):
        corrs, pvs = [], []
        for h, lanes in enumerate(heads):
            s = _scores2(q_ref, k_ref, rows, cols, mask, lanes=lanes)
            m_prev = m_ref[h, rows]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # Masked entries carry s == _NEG_INF; with KV innermost, block
            # ik == 0 is fully live, so m_new is finite for every valid row
            # and exp2(_NEG_INF - m_new) flushes to exactly 0. (A windowed
            # call's first live block is the one the window's lower edge
            # cuts, where a row may see no key yet: its m stays _NEG_INF
            # and p is all ones there, and the next block's corr =
            # exp2(_NEG_INF - m_new) = 0 wipes that from l and acc — the
            # diagonal block, the last, holds a live key for every row.)
            p = jnp.exp2(s - m_new)
            corr = jnp.exp2(m_prev - m_new)
            l_ref[h, rows] = (l_ref[h, rows] * corr
                              + jnp.sum(p, axis=-1, keepdims=True))
            m_ref[h, rows] = m_new
            corrs.append(corr)
            pvs.append(pv(p, cols))
        acc_ref[rows] = (acc_ref[rows] * _by_head(corrs, heads)
                         + _by_head(pvs, heads))

    _dispatch(tile, q_start, k_start, causal, block_q, block_kv, sub,
              one_block, window=window, in_grid=in_grid)

    @pl.when(ik == pl.num_programs(2) - 1)
    def _finalize():
        ls = [jnp.maximum(l_ref[h], 1e-30) for h in range(hps)]
        o_ref[0] = (acc_ref[:] / _by_head(ls, heads)).astype(o_ref.dtype)
        # log2-space LSE — the backward recomputes p = exp2(s2 - lse2).
        # Stored as lane-dense rows, one a head (see _build_fwd).
        for h, l in enumerate(ls):
            lse_ref[h, 0] = (m_ref[h] + jnp.log2(l)).T


def _row_spec(block_q: int, index_map, hps: int = 1):
    """Block of a per-query float32 residual (LSE, delta) stored as
    lane-dense rows ``[BH, num_q_blocks, 1, block_q]``: one ``[1, block_q]``
    row a head and q block (the two trailing dims are whole, so any block_q
    tiles), the ``hps`` heads of a grid step adjacent. As a ``[BH, T, 1]``
    column the same numbers take 128 x their bytes in tiled HBM (module
    docstring). The forward, which needs them down the sublanes, turns a
    row in VMEM (``.T``)."""
    return pl.BlockSpec((hps, 1, 1, block_q), index_map)


def _lane_block(g, nlb: int):
    """Grid step ``g`` -> (row, lane block) of a ``[rows, T, nlb * w]``
    operand, the lane blocks of a row adjacent steps. Head-major operands
    have one lane block a row: the step is the row."""
    return (g, 0) if nlb == 1 else (g // nlb, g % nlb)


def _value_lanes(hps: int, Dv: int) -> int:
    """The lanes of a v / out / do / dv block where a value head is ``Dv``
    wide and a q / k head another width: one head a step (head-major), the
    block a head's own lanes."""
    if hps != 1:
        raise ValueError("values of another width than q and k run "
                         "head-major, one head a grid step")
    return Dv


def _name_suffix(window: int | None, Dv: int | None) -> str:
    """What a call's kernels add to the plain names: ``_win`` on a
    windowed call, ``_mla`` where the values have a width of their own."""
    return ((WINDOW_SUFFIX if window is not None else "")
            + (LATENT_SUFFIX if Dv is not None else ""))


@functools.lru_cache(maxsize=None)
def _build_fwd(T: int, D: int, causal: bool, block_q: int, block_kv: int,
               sub: int | None, in_dtype_name: str, interpret: bool,
               group: int = 1, hps: int = 1, window: int | None = None,
               Dv: int | None = None):
    """Compile-cached pallas_call for a forward over ``[rows, T, lanes]``
    operands in blocks ``hps * D`` lanes wide: head-major ``[BH, T, D]``
    (k and v ``[BH / group, T, D]``), or the projections' own
    ``[B, T, H * D]`` with ``hps`` heads a grid step (``lane_layout``).
    ``window``: the K/V axis of the grid is the band (``_band_step``).
    ``Dv``: values (and the output) of another width than q and k (latent
    attention: 192 / 128), head-major operands only; None: ``D``, and the
    call is what it was."""
    one_block = T == block_q == block_kv
    w = hps * D
    wv = w if Dv is None else _value_lanes(hps, Dv)
    nq, nkv = T // block_q, T // block_kv
    nband = None if window is None else _band_blocks(window, block_kv, nkv)
    kernel = functools.partial(
        _fwd_kernel, causal=causal, block_q=block_q, block_kv=block_kv,
        sub=sub, one_block=one_block, hps=hps, D=D, group=group,
        window=window, nband=nband)
    name = FWD_NAME + _name_suffix(window, Dv)
    dtype = jnp.dtype(in_dtype_name)
    # the step's copy of its one k/v head (``_shared_kv``)
    shared_kv = [pltpu.VMEM((1, block_kv, w), dtype)] * 2 * _shares_kv(
        hps, group)

    def call(qr, kr, vr):
        nlb, nlb_kv = qr.shape[2] // w, kr.shape[2] // w
        steps = qr.shape[0] * nlb           # B * H / hps

        def q_block(g, i, j):
            row, c = _lane_block(g, nlb)
            return (row, i, c)

        def kv_block(g, i, j):
            row, c = _lane_block(_kv_head(g, group), nlb_kv)
            return (row, _band_step(i, j, nband)[0], c)

        fwd = pl.pallas_call(
            kernel,
            name=name,
            grid=(steps, nq, nband or nkv),
            in_specs=[
                pl.BlockSpec((1, block_q, w), q_block),
                pl.BlockSpec((1, block_kv, w), kv_block),
                pl.BlockSpec((1, block_kv, wv), kv_block),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, wv), q_block),
                _row_spec(block_q, lambda g, i, j: (g, i, 0, 0), hps),
            ],
            out_shape=[
                jax.ShapeDtypeStruct(
                    qr.shape if Dv is None else qr.shape[:2] + (wv,), dtype),
                jax.ShapeDtypeStruct((steps * hps, nq, 1, block_q),
                                     jnp.float32),
            ],
            scratch_shapes=shared_kv + ([] if one_block else [
                pltpu.VMEM((block_q, wv), jnp.float32),
                pltpu.VMEM((hps, block_q, 1), jnp.float32),
                pltpu.VMEM((hps, block_q, 1), jnp.float32),
            ]),
            interpret=interpret,
        )
        with jax.named_scope(name):
            return fwd(qr, kr, vr)

    return call


def _bthd_to_bht(x):
    """[B,T,H,D] -> [B*H, T, D] (the kernel's flat layout)."""
    B, T, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, T, D)


def _bht_to_bthd(x, B, H):
    BH, T, D = x.shape
    return x.reshape(B, H, T, D).transpose(0, 2, 1, 3)


def _prescale_q(qr, head_dim: int | None = None):
    """Fold softmax scale and the exp->exp2 base change into q: one fused
    elementwise over the operand instead of a multiply on every
    [block_q, block_kv] score tile inside the kernels. ``head_dim``: a
    head's width where the last axis holds several (None: it is one)."""
    D = head_dim or qr.shape[-1]
    c = _LOG2E / (D ** 0.5)
    return (qr.astype(jnp.float32) * c).astype(qr.dtype)


def lane_layout(n_heads: int, n_kv_heads: int, head_dim: int) -> int | None:
    """Heads a grid step (2) where the kernels read q, k, v, do, out and
    write out, dq, dk, dv in the projections' own ``[B, T, H * D]`` layout,
    two heads of 64 side by side in a 128-lane block — or None where the
    operands are turned head-major, ``[B * H, T, D]``, one head a step: an
    odd k/v head count (``Hkv * D`` no multiple of 128), an odd group above
    1 (the two q heads of a step would read different k/v heads), or
    another head_dim. At 128 a head-major operand is lane-dense already,
    and measured (PERF.md §6, PR 32) the kernels run 4-5 % slower on
    strided lane blocks while XLA pays more to bring a rotary-embedded
    ``[B, T, H, 128]`` back to ``[B, T, H * 128]`` than the transposes it
    saves. Read off the operands' shape alone; the kernels' builders and
    the ``[attention]`` line both ask here."""
    group = n_heads // n_kv_heads
    if head_dim == 64 and n_kv_heads % 2 == 0 and (
            group == 1 or group % 2 == 0):
        return 2
    return None


def _to_kernel(x, hps: int | None):
    """``[B, T, H, D]`` -> the kernels' operand: ``[B, T, H * D]`` as it
    is, or head-major ``[B * H, T, D]``."""
    B, T, H, D = x.shape
    return _bthd_to_bht(x) if hps is None else x.reshape(B, T, H * D)


def _from_kernel(x, B: int, H: int, hps: int | None):
    if hps is None:
        return _bht_to_bthd(x, B, H)
    return x.reshape(B, x.shape[1], H, x.shape[2] // H)


def _first_visit(j, s, nband: int | None, nq: int):
    """Backward grid, K/V block ``j``, innermost step ``s`` -> ``(first,
    block)``: whether this is the step at which the q head first holds the
    step's q block — where its ``dq`` accumulator starts and its ``delta``
    is made from ``do`` and ``out`` — and the block of ``out`` the step
    names: the q block on a first visit, and the block the last first visit
    named on every other step, so that ``out`` is loaded once a q block and
    not once a step. Without a window every q block is first held beside
    K/V block 0; in a band (``_band_step``, ``kv_major``) K/V block 0 brings
    the first ``nband`` q blocks and every later one brings one more, at its
    last step."""
    if nband is None:
        first = j == 0
        return first, jnp.where(first, s, nq - 1)
    first = (j == 0) | (s == nband - 1)
    block = jnp.where(first, j + s, j + nband - 2)
    return first & (j + s < nq), jnp.minimum(block, nq - 1)


def _dq_complete(j, iq, diagonal: bool, nkv):
    """Backward grid, K/V block ``j`` beside q block ``iq`` -> ``(done,
    block)``: whether the q block has its whole ``dq`` once the step has
    run, and the ``dq`` block the step names. The K/V blocks come in key
    order, so where the diagonal runs corner to corner (a causal call on
    equal blocks; every windowed call) q block ``j`` is complete at its
    diagonal step and the whole row of steps beside K/V block ``j`` names
    it; any other call completes every q block beside the LAST K/V block,
    and the rows before it name the block that row's first step names.
    Either way a name is left only after its block was written — Pallas
    writes a block out when the name changes."""
    if diagonal:
        return iq == j, j
    last = j == nkv - 1
    return last, jnp.where(last, iq, 0)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref, dk_ref,
                dv_ref, dq_acc, dk_acc, dv_acc, delta_ref, *scratch,
                causal: bool, block_q: int, block_kv: int, sub: int | None,
                one_block: bool, scale: float, hps: int, D: int, group: int,
                nq: int, diagonal: bool, window: int | None = None,
                nband: int | None = None):
    # grid: k/v step, q step of its group, K/V block, q block (of the band)
    t, j, step = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    iq, in_grid = _band_step(j, step, nband, nq, kv_major=True)
    heads = _head_lanes(hps, D)
    shared = _shares_kv(hps, group)
    at = lambda cols: (j, cols)      # where a tile adds to dk_acc / dv_acc
    if shared:
        # this step's q heads read k/v head ``half`` of the block, and what
        # they add to dk / dv — each in its own lanes — is that head's: one
        # accumulator a k/v head, its halves summed at the end
        half = _kv_half(t, group, hps)
        k_ref, v_ref = _shared_kv(k_ref, v_ref, *scratch, half, D)
        at = lambda cols: (j, half, cols)

    @pl.when((t == 0) & (step == 0))
    def _init():
        dk_acc[j] = jnp.zeros(dk_acc.shape[1:], jnp.float32)
        dv_acc[j] = jnp.zeros(dv_acc.shape[1:], jnp.float32)

    @pl.when(_first_visit(j, step, nband, nq)[0])
    def _first():
        # delta = rowsum(do * o) a head, kept as the lane-dense rows the
        # tiles subtract in transposed space
        dq_acc[iq] = jnp.zeros(dq_acc.shape[1:], jnp.float32)
        prod = do_ref[0].astype(jnp.float32) * o_ref[0].astype(jnp.float32)
        for h, lanes in enumerate(heads):
            delta_ref[h, iq] = jnp.sum(
                prod if lanes is None else jnp.where(lanes, prod, 0.0),
                axis=-1, keepdims=True).T

    q_start = iq * block_q
    k_start = j * block_kv

    def tile(rows, cols, mask):
        # Transposed space, [keys, queries]: lse and delta are rows. A
        # head's q and do rows are zero in the other heads' lanes, so its
        # dv and dk land in its own lanes and the heads' sums do not mix;
        # ``ds_h @ k`` fills every lane of the block, and head h's D lanes
        # of it are its dq.
        dqs = []
        for h, lanes in enumerate(heads):
            s_t = _scores2(q_ref, k_ref, rows, cols, mask, transposed=True,
                           lanes=lanes)
            p_t = jnp.exp2(s_t - lse_ref[h, 0, :, rows])
            do = _head_rows(do_ref, rows, lanes)
            dv_acc[at(cols)] += jax.lax.dot_general(
                p_t.astype(do.dtype), do, _NN,
                preferred_element_type=jnp.float32)
            dp_t = jax.lax.dot_general(
                v_ref[0, cols, :], do, _NT,
                preferred_element_type=jnp.float32)
            ds_t = (p_t * (dp_t - delta_ref[h, iq, :, rows])).astype(
                q_ref.dtype)
            dk_acc[at(cols)] += jax.lax.dot_general(
                ds_t, _head_rows(q_ref, rows, lanes), _NN,
                preferred_element_type=jnp.float32)
            dqs.append(jax.lax.dot_general(
                ds_t, k_ref[0, cols, :], _TN,
                preferred_element_type=jnp.float32))
        dq_acc[iq, rows] += _by_head(dqs, heads)

    _dispatch(tile, q_start, k_start, causal, block_q, block_kv, sub,
              one_block, kv_major=True, window=window, in_grid=in_grid)

    @pl.when(_dq_complete(j, iq, diagonal, pl.num_programs(2))[0])
    def _dq_out():
        # acc holds d/d(q.k) contractions; one [block_q, D] multiply undoes
        # the score scaling (ds was accumulated in natural space).
        dq_ref[0] = (dq_acc[iq] * scale).astype(dq_ref.dtype)

    def total(acc):
        if not shared:
            return acc
        return _by_head([acc[h] + pltpu.roll(acc[h], D, 1)
                         for h in range(hps)], heads)

    @pl.when((t == group - 1) & (step == pl.num_programs(3) - 1))
    def _dkv_out():
        # dk contracted ds against the PRE-SCALED q (scale * log2e folded
        # in), while true dk = scale * (ds^T @ q_unscaled) — so divide the
        # extra log2e back out. dv never touches scores: exact as-is.
        dk_ref[0] = (total(dk_acc[j]) * (1.0 / _LOG2E)).astype(dk_ref.dtype)
        dv_ref[0] = total(dv_acc[j]).astype(dv_ref.dtype)


# What a backward call may ask of VMEM: the accumulators below, the blocks
# in flight and the score tiles Mosaic keeps for a step (11 MiB at most as
# the compiler counts them, ``tests/test_flash_tpu_compile.py``; the
# precedent is ``ops/sparse_attn_pallas.py``'s; a v5e core has 128 MiB).
# One number for every shape: XLA keeps buffers of its own in what a kernel
# leaves, so the limit moves the glue round the kernels, and a limit that
# followed the accumulators (16 MiB + their bytes) read 3 ms an update
# better in two cells and 4 ms worse in a third (PERF.md section 6, PR 53).
_VMEM_LIMIT = 64 * 1024 * 1024
# ... of which the three float32 accumulators over all of T may take this
# much: T x lanes <= 4 Mi (16,384 x 256, 32,768 x 128)
_MAX_ACC_BYTES = 48 * 1024 * 1024


@functools.lru_cache(maxsize=None)
def _build_bwd(T: int, D: int, causal: bool, block_q: int, block_kv: int,
               sub: int | None, in_dtype_name: str, interpret: bool,
               group: int = 1, hps: int = 1, window: int | None = None,
               Dv: int | None = None):
    """Compile-cached backward pallas_call over ``_build_fwd``'s operand
    layouts: ONE kernel that walks the score tiles once, in transposed
    space, and makes dq, dk and dv from the ``ds^T`` tile it holds. Grid
    ``(k/v steps, q steps of a k/v step's group, K/V blocks, q blocks)`` —
    a windowed call's innermost axis is the band
    (``_band_step``) — with a q head's whole ``dq`` and the k/v head's whole
    ``dk`` / ``dv`` in float32 VMEM scratch over all of T: ``dq`` is summed
    over the K/V blocks, ``dk`` / ``dv`` over the q blocks and, the q head
    lying outside the K/V blocks, over the group. ``lse`` arrives as
    lane-dense rows (``_row_spec``); delta = rowsum(do * o) is made in the
    kernel where a q head first holds a q block (``_first_visit``) and never
    leaves VMEM. ``Dv``: as ``_build_fwd``'s — v, do, out and dv are then
    ``Dv`` lanes a head."""
    dtype = jnp.dtype(in_dtype_name)
    scale = 1.0 / (D ** 0.5)
    one_block = T == block_q == block_kv
    w = hps * D
    wv = w if Dv is None else _value_lanes(hps, Dv)
    nq, nkv = T // block_q, T // block_kv
    shared = _shares_kv(hps, group)
    # a q step's dq; the k/v step's dk and dv, one each a k/v head of the
    # block where q pairs share
    kv_acc = (nkv,) + (hps,) * shared + (block_kv, w)
    acc_shapes = [(nq, block_q, w), kv_acc, kv_acc[:-1] + (wv,)]
    acc_bytes = 4 * sum(math.prod(shape) for shape in acc_shapes)
    if acc_bytes > _MAX_ACC_BYTES:
        raise ValueError(
            f"flash backward: the accumulators of {T} rows x {w} lanes do "
            f"not fit VMEM ({acc_bytes} bytes of {_MAX_ACC_BYTES})")
    nband = None if window is None else _band_blocks(window, block_kv, nkv)
    diagonal = causal and block_q == block_kv
    kernel = functools.partial(
        _bwd_kernel, causal=causal, block_q=block_q, block_kv=block_kv,
        sub=sub, one_block=one_block, scale=scale, hps=hps, D=D, group=group,
        nq=nq, diagonal=diagonal, window=window, nband=nband)
    name = BWD_NAME + _name_suffix(window, Dv)

    def call(qr, kr, vr, dor, out, lse):
        nlb, nlb_kv = qr.shape[2] // w, kr.shape[2] // w
        steps_kv = kr.shape[0] * nlb_kv

        def q_of(g, t, j, s):    # -> flat q step, q block
            return (g * group + t,
                    _band_step(j, s, nband, nq, kv_major=True)[0])

        def q_block(g, t, j, s):
            gq, iq = q_of(g, t, j, s)
            row, c = _lane_block(gq, nlb)
            return (row, iq, c)

        def out_block(g, t, j, s):
            row, c = _lane_block(g * group + t, nlb)
            return (row, _first_visit(j, s, nband, nq)[1], c)

        def dq_block(g, t, j, s):
            gq, iq = q_of(g, t, j, s)
            row, c = _lane_block(gq, nlb)
            return (row, _dq_complete(j, iq, diagonal, nkv)[1], c)

        def kv_block(g, t, j, s):
            row, c = _lane_block(g, nlb_kv)
            return (row, j, c)

        def dkv_block(g, t, j, s):
            # the group's last q step completes dk / dv; the steps before
            # it name the block its first row names
            row, c = _lane_block(g, nlb_kv)
            return (row, j if group == 1 else jnp.where(t == group - 1, j, 0),
                    c)

        bwd = pl.pallas_call(
            kernel,
            name=name,
            grid=(steps_kv, group, nkv, nband or nq),
            in_specs=[
                pl.BlockSpec((1, block_q, w), q_block),
                pl.BlockSpec((1, block_kv, w), kv_block),
                pl.BlockSpec((1, block_kv, wv), kv_block),
                pl.BlockSpec((1, block_q, wv), q_block),
                pl.BlockSpec((1, block_q, wv), out_block),
                _row_spec(block_q, lambda *at: (*q_of(*at), 0, 0), hps),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, w), dq_block),
                pl.BlockSpec((1, block_kv, w), dkv_block),
                pl.BlockSpec((1, block_kv, wv), dkv_block),
            ],
            out_shape=[
                jax.ShapeDtypeStruct(qr.shape, dtype),
                jax.ShapeDtypeStruct(kr.shape, dtype),
                jax.ShapeDtypeStruct(vr.shape, dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM(shape, jnp.float32) for shape in acc_shapes
            ] + [
                pltpu.VMEM((hps, nq, 1, block_q), jnp.float32),   # delta
            ] + [pltpu.VMEM((1, block_kv, w), dtype)] * 2 * shared,
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret,
        )
        with jax.named_scope(name):
            return bwd(qr, kr, vr, dor, out, lse)

    return call


# One ``jit`` a builder's call: every repeat of the call is the same function
# to ``jit``'s own caches, which hold its jaxpr and its lowering.
_shared = functools.lru_cache(maxsize=None)(jax.jit)


@functools.lru_cache(maxsize=None)
def _make_flash(causal: bool, block_q: int, block_kv: int, sub: int | None,
                interpret: bool, D: int, group: int, hps: int | None,
                window: int | None = None, Dv: int | None = None):
    """The differentiable call over the kernels' own operands (``hps``
    None: head-major ``[BH, T, D]``; else ``[B, T, H * D]``) — what is
    kept for the backward is kept as the kernels read it.

    The first call a trace makes runs the builders' calls bare; every
    repeat in that trace runs them through one inner ``jit`` each
    (``_shared``). Every ``pallas_call`` traces its body and lowers it to
    Mosaic anew, a trunk of L attention layers makes the same two calls
    L times, and two heads a step about doubles a body
    (``gpt2m-policy.update``: 48 calls; the 72 of the three kernels it had
    cost + 7 s of warm set-up traced a layer); through the inner ``jit``
    the repeats share one jaxpr and one lowered function (eagerly —
    ``init_params`` runs every layer at T = 1 — one executable in place of
    one a layer). The first call stays bare
    so that a model with ONE attention layer traces and lowers exactly
    what it did without this: an inner ``jit`` shares nothing there, and
    it moves the depth of the Python stack at which Mosaic's lowering
    runs, which alone cost ``lfm2-policy.update`` 3 s a process (PERF.md
    §6, PR 33: CPython's frame stack grows in 16 KB chunks, and a hot call
    that straddles a chunk's end maps and unmaps one every time).
    """
    per_step = hps or 1

    def differentiable(built):
        def run_fwd(qr, kr, vr):
            T = qr.shape[1]
            call = built(_build_fwd(T, D, causal, block_q, block_kv, sub,
                                    qr.dtype.name, interpret, group,
                                    per_step, window, Dv))
            with jax.named_scope(OP_PROJ):
                qs = _prescale_q(qr, D)
            return call(qs, kr, vr)

        @jax.custom_vjp
        def flash(qr, kr, vr):
            return run_fwd(qr, kr, vr)[0]

        def fwd(qr, kr, vr):
            out, lse_row = run_fwd(qr, kr, vr)
            out = checkpoint_name(out, OUT_NAME)
            lse_row = checkpoint_name(lse_row, LSE_NAME)
            return out, (qr, kr, vr, out, lse_row)

        def bwd(res, dor):
            qr, kr, vr, out, lse_row = res
            call = built(_build_bwd(qr.shape[1], D, causal, block_q,
                                    block_kv, sub, qr.dtype.name, interpret,
                                    group, per_step, window, Dv))
            # the kernels recompute log2-space scores
            with jax.named_scope(OP_PROJ):
                qs = _prescale_q(qr, D)
            return call(qs, kr, vr, dor, out, lse_row)

        flash.defvjp(fwd, bwd)
        return flash

    bare, shared = differentiable(lambda call: call), differentiable(_shared)
    last_trace = [None]   # of the previous call; held weakly, eager is one

    def flash(qr, kr, vr):
        # asked here, in the layer's own trace: a custom_vjp that is not
        # differentiated traces its forward in a trace of its own
        trace = jax.core.get_opaque_trace_state()
        repeat, last_trace[0] = trace == last_trace[0], trace
        return (shared if repeat else bare)(qr, kr, vr)

    return flash


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, block_q: int = 1024,
                    block_kv: int = 1024,
                    interpret: bool = False,
                    window: int | None = None) -> jax.Array:
    """Fused attention on ``q [B, T, H, D]``, ``k`` / ``v``
    ``[B, T, Hkv, D]`` (``Hkv`` divides ``H``; q head ``j`` reads k/v head
    ``j // (H / Hkv)``) via a Pallas TPU kernel. ``v`` may be ``[B, T, Hkv,
    Dv]`` with a width of its own (latent attention's 192 / 128): the result
    is then ``[B, T, H, Dv]``, the operands head-major and the kernels named
    ``relayrl_flash_fwd_mla`` / ``_bwd_mla``; with ``Dv == D`` the call is
    what it was.

    Compiled by Mosaic, so it runs on a TPU backend only; off-TPU callers
    use :func:`relayrl_tpu.ops.attention.blockwise_attention` (what the
    model-level ``attention="flash"`` config resolves to there).
    ``interpret=True`` runs the kernel body in the Pallas interpreter — a
    test-only switch that is never defaulted on, so no device process can
    reach the interpreter without saying so.
    Requires ``T`` divisible by both block sizes; callers pad or fall back.

    ``window`` (causal calls): query ``t`` sees keys ``s`` with ``t -
    window < s <= t``, ``window`` keys with its own. The grids' innermost
    axes are then as long as the band and no block outside it is loaded or
    computed; a window of T or more is the causal call, the same kernels.

    Default blocks are 1024 (clamped to T): fewer, larger grid steps, and
    what a causal call does not need of a step on the diagonal is skipped
    inside it, strip by strip (``tiling``; the module docstring has the
    measurement the strip height rests on). Shrink blocks when VMEM
    pressure forces it (an interior step's score tile is
    block_q x block_kv f32).
    """
    if k.shape[:3] != v.shape[:3] or q.shape[2] % k.shape[2]:
        raise ValueError(f"q heads {q.shape[2]} do not group over k/v "
                         f"{k.shape} / {v.shape}")
    if window is not None and window >= q.shape[1]:
        window = None
    block_q, block_kv, sub = tiling(q.shape[1], causal, block_q, block_kv,
                                    window)
    B, _, H, D = q.shape
    h_kv = k.shape[2]
    hps = lane_layout(H, h_kv, D)
    own_width = v.shape[3] != D
    if own_width:
        hps = None      # a value head's lanes are not a q head's
    flash = _make_flash(causal, block_q, block_kv, sub, bool(interpret), D,
                        H // h_kv, hps, window,
                        *((int(v.shape[3]),) if own_width else ()))
    with jax.named_scope(OP_PROJ):
        operands = [_to_kernel(x, hps) for x in (q, k, v)]
    out = flash(*operands)
    with jax.named_scope(OP_PROJ):
        return _from_kernel(out, B, H, hps)


def tiling(T: int, causal: bool = True, block_q: int = 1024,
           block_kv: int = 1024, window: int | None = None
           ) -> tuple[int, int, int | None]:
    """``(block_q, block_kv, sub)`` as ``flash_attention`` runs a length-T
    call: the blocks clamped to T, and the height of the causal strips a
    grid step on the diagonal is walked in (None: one tile a step).
    ``score_area_pct(T, *tiling(T, ...), causal, window)`` is how much of
    the score matrix the kernels then compute. A ``window`` (below T)
    wants a causal call and equal blocks: the band is counted in blocks."""
    block_q = min(block_q, T)
    block_kv = min(block_kv, T)
    if T % block_q or T % block_kv:
        raise ValueError(
            f"seq len {T} not divisible by blocks ({block_q}, {block_kv})")
    if window is not None and window < T and (
            not causal or block_q != block_kv or window < 1):
        raise ValueError(
            f"window {window} needs a causal call and equal blocks, got "
            f"causal={causal}, blocks ({block_q}, {block_kv})")
    return block_q, block_kv, _sub_tile(block_q, block_kv, causal)
