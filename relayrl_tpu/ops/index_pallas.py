"""The indexer of :mod:`relayrl_tpu.ops.sparse_attn` (``index_scores`` and
``top_k_mask``) as Pallas TPU kernels: a block of queries' index scores stay
in VMEM from the matmuls that make them to the selection that reads them,
forward, in a tile's recompute and in the backward. ``ops/sparse_attn.py`` has
the equations and the rule that picks these kernels; this module is imported
only where that rule says so, as ``ops/sparse_attn_pallas.py`` is.

**Operands, as plain XLA turns them once a call** (a tile's ``qi`` is 1 MB):
``q [Hi, Tq, Di]`` the heads first, so that a kernel takes head ``h`` by the
leading index; the keys as ``kt [Di, Tk]``, so that
every score product is a plain ``[rows, Di] @ [Di, keys]`` matmul; ``w [Hi,
Tq, 1]`` float32, a head's weights a column that broadcasts along the keys;
the positions (and the thresholds) ``[Tq, 1]`` int32 columns. The grid is
``(query block, key block)``, the key blocks innermost; ``live [query
blocks]``, a prefetched scalar a query block, is the number of key blocks at
or before the largest of its positions: a step past it computes nothing and
names the block its neighbour names (``sparse_attn_pallas``'s device).

A step's scores are what ``index_scores`` computes — for each head (the
heads' bodies one after another in the kernel's code: :func:`_each_head`) the
products on the MXU with float32 accumulation, ReLU, times ``w`` and the
heads' sum **on the vector unit in float32**, head 0 first, times ``Hi^-1/2
Di^-1/2`` — and live as ``[queries, keys]``, the layout of everything the
callers read (``keep``, the scores, their cotangent), so nothing is turned.
The selection orders them as ``_ordered_bits`` does, through a signed key:
``bits ^ ((bits >> 31) & 0x7fffffff)`` is the int32 whose signed order is the
floats' total order (``-0.0`` below ``+0.0``) and equals ``_ordered_bits``
with its top bit flipped, so the bisection below walks the same 32 bits and
ends at the same threshold; an unseen entry is ``INT32_MIN``, below every
float.

* ``index_kth`` — the search: a query block's keys into VMEM scratch ``[key
  blocks, queries, block]`` as the key blocks go by, then at the last step the
  ``topk``-th largest of each row's seen scores by the 32-step bisection,
  each step one compare and count over the live blocks of the scratch (128
  rows at a time, the counts a ``[128, 128]`` float32 register tile that the
  blocks' lane tiles add into elementwise; ONE reduction across the lanes a
  step and row) -> ``kth [Tq]`` (``_ordered_bits``' uint32, carried as
  int32) and ``room [Tq]``, how many of the ties at the threshold the row
  keeps (0 where the row sees fewer than ``topk`` keys: the threshold is 0
  and every seen key lies above it).
* ``index_select`` — given the thresholds, streaming: a step makes its block
  of scores again (bit-equal: the same instructions on the same operands),
  compares once, settles the ties at the threshold in index order — the
  running count of ties along the keys is a product with an upper-triangular
  matrix of ones a lane tile at a time, exact for 0 / 1 operands and float32
  sums — and writes ``keep [Tq, Tk]`` as the int8 tile the attention's
  kernels read and, where the caller wants the loss, ``scores [Tq, Tk]``
  float32. ``select=False`` (a stage that ends at or before ``topk``): no
  thresholds, ``keep`` is the seen keys.
* ``index_bwd`` — from the cotangent ``dI [Tq, Tk]`` of the scores: each
  head's products again, ``ds[h] = dI w[:, h] (s[h] > 0) scale``, ``dqi = ds
  ki`` in VMEM scratch over the key blocks, ``dki = ds^T qi`` as ``qi^T ds``
  (``[Di, block]``, the heads summed in the step) into a block that waits in
  VMEM for all the query blocks, and ``dw[:, h] = scale rowsum(dI
  relu(s[h]))`` as 128 lane sums a row that plain XLA adds up.

**The search runs once an update.** :func:`index_select` names the
thresholds (:data:`relayrl_tpu.ops.sparse_attn.KTH_NAME`) for the caller's
checkpoint policy — 128 KB a layer beside the log-sum-exp's 2 MB — and the
search is one call, the selection another: in a tile's recompute the
thresholds are there and ``index_kth``, whose only outputs they are, is not
run again. Where nothing kept them (outside a checkpoint, another policy) it
runs: one code path, and what the code was handed decides. The price is that
the forward makes a tile's scores twice, once for the search and once for the
selection.

``jax.custom_vjp``: ``qi``, ``ki`` and ``w`` get the gradient autodiff of
``index_scores`` gives them; the positions, the thresholds and ``keep`` are
integers and get none. Every call sits under ``relayrl_index`` and under no
deeper ``relayrl_`` name (``ops/scopes.py``), so the benchmark's ``index_ms``
holds them; both rules open the scope themselves.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from relayrl_tpu.ops.scopes import INDEX
from relayrl_tpu.ops.sparse_attn import KTH_NAME

SEARCH_NAME, SELECT_NAME, BWD_NAME = "index_kth", "index_select", "index_bwd"

_VMEM_LIMIT = 64 * 1024 * 1024
_KEY_BLOCKS = (512, 256, 128)
_QUERY_BLOCKS = (512, 256, 128)
# bytes of VMEM a call's residents may take: the search's scratch (4 B a
# query of the block and key), the backward's blocks of every head (20 B a
# query, head and lane)
_MAX_RESIDENT = 32 * 1024 * 1024
_ROWS = _LANES = 128    # the search counts this many rows, a lane tile, at a time

_F32, _I32 = jnp.float32, jnp.int32
_INT_MIN = -2 ** 31


def _mm(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=_F32)


def _ordered_key(x):
    """float32 -> the int32 whose signed order is the floats' total order:
    ``sparse_attn._ordered_bits`` with the top bit flipped."""
    bits = jax.lax.bitcast_convert_type(x, _I32)
    return bits ^ ((bits >> 31) & _I32(0x7FFFFFFF))


def _each_head(n_heads: int, body, carry):
    """``body(h, carry)`` for every head, head 0 first: copies of the body
    in the kernel's code, not a loop on the device. A loop's iteration ends
    before the next one's matmul starts, and the vector unit waits for the
    MXU in turn: a tile's search, selection and backward read 1.85 ms as a
    loop and 1.27 unrolled, for 0.7 MB more of each call's code (the compiled
    update 339.7 MB serialized for 295.3, under the 393.9 it was with the
    indexer in plain XLA: PERF.md section 6, PR 49)."""
    for h in range(n_heads):
        carry = body(h, carry)
    return carry


def _block_scores(q_ref, kt_ref, w_ref, scale: float):
    """A step's ``[queries, keys]`` float32 index scores, head 0 first."""
    kt = kt_ref[...]

    def head(h, total):
        return total + jnp.maximum(_mm(q_ref[h], kt), 0.0) * w_ref[h]

    return _each_head(q_ref.shape[0], head, jnp.zeros(
        (q_ref.shape[1], kt.shape[1]), _F32)) * scale


def _seen(pos_ref, j, shape):
    """Bool ``[queries, keys]``: the keys of block ``j`` at or before each
    query's position."""
    at = j * shape[1] + jax.lax.broadcasted_iota(_I32, shape, 1)
    return pos_ref[...] >= at


def _search_kernel(live_ref, q_ref, kt_ref, w_ref, pos_ref, kth_ref, room_ref,
                   keys_ref, *, topk: int, scale: float):
    """``index_kth``: one key block's keys into the scratch; at a query
    block's last step, its rows' thresholds."""
    i, j = pl.program_id(0), pl.program_id(1)
    _, n_rows, block = keys_ref.shape

    @pl.when(j < live_ref[i])
    def _block():
        scores = _block_scores(q_ref, kt_ref, w_ref, scale)
        keys_ref[j] = jnp.where(_seen(pos_ref, j, scores.shape),
                                _ordered_key(scores), _INT_MIN)

    @pl.when(j == pl.num_programs(1) - 1)
    def _search():
        live = live_ref[i]

        def rows(r, _):
            at = pl.ds(pl.multiple_of(r * _ROWS, _ROWS), _ROWS)

            def count(test):
                """How many of each row's keys pass, ``[rows, 1]``."""
                def one_block(b, tile):
                    for c in range(block // _LANES):
                        lanes = pl.ds(c * _LANES, _LANES)
                        tile = tile + jnp.where(test(keys_ref[b, at, lanes]),
                                                1.0, 0.0)
                    return tile

                tile = jax.lax.fori_loop(0, live, one_block,
                                         jnp.zeros((_ROWS, _LANES), _F32))
                return jnp.sum(tile, axis=1, keepdims=True)

            def one_bit(bit, kth):
                # ``kth`` holds ``_ordered_bits``' unsigned pattern; the
                # scratch's keys are that with the top bit flipped
                trial = kth | (_I32(1) << (31 - bit))
                least = jnp.broadcast_to(trial ^ _INT_MIN, (_ROWS, _LANES))
                enough = count(lambda keys: keys >= least) >= topk
                return jnp.where(enough, trial, kth)

            kth = jax.lax.fori_loop(0, 32, one_bit,
                                    jnp.zeros((_ROWS, 1), _I32))
            least = jnp.broadcast_to(kth ^ _INT_MIN, (_ROWS, _LANES))
            above = count(lambda keys: keys > least).astype(_I32)
            # a threshold of 0: the row sees fewer than ``topk`` keys, all
            # of them above it, and nothing seen is tied
            room = jnp.where(kth == 0, 0, topk - above)
            kth_ref[at, :] = kth
            room_ref[at, :] = room

        jax.lax.fori_loop(0, n_rows // _ROWS, rows, None)


def _select_kernel(live_ref, q_ref, kt_ref, w_ref, pos_ref, *refs,
                   select: bool, want_scores: bool, scale: float):
    """``index_select``: one block of ``keep`` (and of the scores)."""
    refs = list(refs)
    kth_ref, room_ref = (refs.pop(0), refs.pop(0)) if select else (None, None)
    keep_ref = refs.pop(0)
    scores_ref = refs.pop(0) if want_scores else None
    ties_ref, ones_ref = refs if select else (None, None)
    i, j = pl.program_id(0), pl.program_id(1)
    shape = keep_ref.shape

    if select:
        @pl.when((i == 0) & (j == 0))
        def _ones():
            row = jax.lax.broadcasted_iota(_I32, ones_ref.shape, 0)
            col = jax.lax.broadcasted_iota(_I32, ones_ref.shape, 1)
            ones_ref[...] = jnp.where(row <= col, 1.0, 0.0).astype(
                ones_ref.dtype)

        @pl.when(j == 0)
        def _start():
            ties_ref[...] = jnp.zeros(ties_ref.shape, _F32)

    @pl.when(j < live_ref[i])
    def _block():
        scores = _block_scores(q_ref, kt_ref, w_ref, scale)
        seen = _seen(pos_ref, j, shape)
        if want_scores:
            scores_ref[...] = scores
        if not select:
            keep_ref[...] = jnp.where(seen, 1, 0).astype(keep_ref.dtype)
            return
        keys = jnp.where(seen, _ordered_key(scores), _INT_MIN)
        kth = kth_ref[...] ^ _INT_MIN
        room, before = room_ref[...].astype(_F32), ties_ref[...]
        for c in range(shape[1] // _LANES):
            lanes = slice(c * _LANES, (c + 1) * _LANES)
            tied = keys[:, lanes] == kth
            # the ties up to and with each key, this lane tile's and before
            run = before + _mm(jnp.where(tied, 1.0, 0.0).astype(
                ones_ref.dtype), ones_ref[...])
            kept = (keys[:, lanes] > kth) | (tied & (run <= room))
            keep_ref[:, lanes] = jnp.where(kept, 1, 0).astype(keep_ref.dtype)
            before = run[:, _LANES - 1:]
        ties_ref[...] = before

    @pl.when(j >= live_ref[i])
    def _above():
        keep_ref[...] = jnp.zeros(shape, keep_ref.dtype)
        if want_scores:
            scores_ref[...] = jnp.zeros(shape, _F32)


def _bwd_kernel(live_ref, q_ref, qt_ref, kt_ref, k_ref, w_ref, di_ref,
                dq_ref, dkt_ref, dw_ref, acc_ref, *, scale: float):
    """``index_bwd``: one key block's share of a query block's ``dqi`` and
    ``dw``, and the query block's share of the key block's ``dki``."""
    i, j = pl.program_id(0), pl.program_id(1)
    n_heads, block = q_ref.shape[0], kt_ref.shape[1]

    @pl.when((i == 0) & (j == 0))
    def _first():
        dkt_ref[...] = jnp.zeros(dkt_ref.shape, _F32)

    @pl.when(j == 0)
    def _start():
        acc_ref[...] = jnp.zeros(acc_ref.shape, _F32)
        dw_ref[...] = jnp.zeros(dw_ref.shape, _F32)

    @pl.when(j < live_ref[i])
    def _block():
        kt, k = kt_ref[...], k_ref[...]
        d_scaled = di_ref[...] * scale

        def head(h, dkt):
            s = _mm(q_ref[h], kt)
            ds = jnp.where(s > 0.0, d_scaled * w_ref[h], 0.0).astype(k.dtype)
            acc_ref[h] += _mm(ds, k)
            through = jnp.maximum(s, 0.0) * d_scaled
            dw_ref[h] += sum(through[:, c * _LANES:(c + 1) * _LANES]
                             for c in range(block // _LANES))
            return dkt + _mm(qt_ref[h], ds)

        dkt_ref[j] += _each_head(n_heads, head,
                                 jnp.zeros(dkt_ref.shape[1:], _F32))

    @pl.when(j == pl.num_programs(1) - 1)
    def _end():
        dq_ref[...] = acc_ref[...].astype(dq_ref.dtype)


def key_block(tk: int) -> int | None:
    """The key block the kernels walk ``tk`` keys in: the largest of
    :data:`_KEY_BLOCKS` that divides them, None where none does."""
    return next((b for b in _KEY_BLOCKS if tk % b == 0), None)


def query_block(tq: int, tk: int, n_heads: int, width: int) -> int | None:
    """The query block: the largest of :data:`_QUERY_BLOCKS` that divides
    ``tq`` and whose residents fit: the search's keys against ``tk`` keys,
    the backward's operands and sums of every head."""
    return next((b for b in _QUERY_BLOCKS if tq % b == 0
                 and 4 * b * tk <= _MAX_RESIDENT
                 and 20 * b * n_heads * max(width, _LANES) <= _MAX_RESIDENT),
                None)


def fits(tq: int, tk: int, n_heads: int, width: int) -> bool:
    """Whether the kernels take ``tq`` queries of ``n_heads`` index heads of
    ``width`` over ``tk`` keys: whole query blocks, the keys in whole blocks,
    heads of half a lane tile or whole ones (``ops/flash.lane_layout``'s
    precedent for 64), ``Hi * Di`` whole lane tiles, and a query block's
    residents — the search's keys, the backward's ``dki`` — within what a
    call may hold in VMEM."""
    return (width % 64 == 0 and (n_heads * width) % 128 == 0
            and key_block(tk) is not None
            and query_block(tq, tk, n_heads, width) is not None
            and 4 * max(width, 128) * tk <= _MAX_RESIDENT)


@functools.lru_cache(maxsize=None)
def _build(kind: str, shape: tuple, dtype_name: str, topk: int, select: bool,
           want_scores: bool, interpret: bool):
    """The ``pallas_call`` of one kernel, ``shape = (tq, tk, n_heads,
    width)``; its first operand is ``live [query blocks]`` int32."""
    tq, tk, n_heads, width = shape
    rows, block = query_block(*shape), key_block(tk)
    cd = jnp.dtype(dtype_name)
    scale = n_heads ** -0.5 * width ** -0.5

    def spec(block_shape, index):
        """``index(i, j, at)``: query block, key block, and the key block a
        step LOADS (its neighbour's past ``live``)."""
        return pl.BlockSpec(block_shape, lambda i, j, live: index(
            i, j, jnp.minimum(j, live[i] - 1)))

    heads = spec((n_heads, rows, width), lambda i, j, at: (0, i, 0))
    turned = spec((width, block), lambda i, j, at: (0, at))
    weights = spec((n_heads, rows, 1), lambda i, j, at: (0, i, 0))
    column = spec((rows, 1), lambda i, j, at: (i, 0))
    tile = spec((rows, block), lambda i, j, at: (i, j))
    S = jax.ShapeDtypeStruct
    column_s, scratch = S((tq, 1), _I32), []
    if kind == SEARCH_NAME:
        kernel = functools.partial(_search_kernel, topk=topk, scale=scale)
        in_specs = [heads, turned, weights, column]
        out_specs, out_shape = [column, column], [column_s, column_s]
        scratch = [pltpu.VMEM((tk // block, rows, block), _I32)]
    elif kind == SELECT_NAME:
        kernel = functools.partial(_select_kernel, select=select,
                                   want_scores=want_scores, scale=scale)
        in_specs = [heads, turned, weights, column] + [column] * 2 * select
        out_specs = [tile] + [tile] * want_scores
        out_shape = [S((tq, tk), jnp.int8)] + [S((tq, tk), _F32)] * want_scores
        if select:
            scratch = [pltpu.VMEM((rows, 1), _F32),
                       pltpu.VMEM((_LANES, _LANES), jnp.bfloat16)]
    else:
        kernel = functools.partial(_bwd_kernel, scale=scale)
        in_specs = [heads,
                    spec((n_heads, width, rows), lambda i, j, at: (0, 0, i)),
                    turned, spec((block, width), lambda i, j, at: (at, 0)),
                    weights, spec((rows, block), lambda i, j, at: (i, at))]
        lane_sums = spec((n_heads, rows, _LANES), lambda i, j, at: (0, i, 0))
        out_specs = [heads, spec((tk // block, width, block),
                                 lambda i, j, at: (0, 0, 0)), lane_sums]
        out_shape = [S((n_heads, tq, width), cd),
                     S((tk // block, width, block), _F32),
                     S((n_heads, tq, _LANES), _F32)]
        scratch = [pltpu.VMEM((n_heads, rows, width), _F32)]
    call = pl.pallas_call(
        kernel, name=kind,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(tq // rows, tk // block),
            in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)

    def named(*operands):
        with jax.named_scope(INDEX):
            return call(*operands)

    return named


@functools.lru_cache(maxsize=None)
def _make_rule(shape: tuple, dtype_name: str, topk: int, select: bool,
               want_scores: bool, interpret: bool):
    """``search(qi, ki, w, pos) -> kth_room [2, Tq]`` int32 and the
    differentiable ``rule(qi, ki, w, pos, kth_room) -> (keep [Tq, Tk] int8,
    scores [Tq, Tk] float32 | None)`` over ``qi [Tq, Hi, Di]``, ``ki [Tk,
    Di]``, ``w [Tq, Hi]``, ``pos [Tq]`` (``kth_room`` None unless
    ``select``)."""
    tq, tk, _, width = shape
    rows, block = query_block(*shape), key_block(tk)

    def build(kind):
        return _build(kind, shape, dtype_name, topk, select, want_scores,
                      interpret)

    def turned(qi, ki, w, pos):
        """The kernels' first five operands."""
        live = jnp.max(pos.reshape(-1, rows), axis=1) // block + 1
        return (jnp.clip(live, 1, tk // block).astype(_I32),
                qi.swapaxes(0, 1), ki.T, w.astype(_F32).T[:, :, None],
                pos.astype(_I32)[:, None])

    def search(qi, ki, w, pos):
        with jax.named_scope(INDEX):
            kth, room = build(SEARCH_NAME)(*turned(qi, ki, w, pos))
            return jnp.stack([kth[:, 0], room[:, 0]])

    def fwd(qi, ki, w, pos, kth_room):
        with jax.named_scope(INDEX):
            found = () if kth_room is None else (kth_room[0][:, None],
                                                 kth_room[1][:, None])
            out = build(SELECT_NAME)(*turned(qi, ki, w, pos), *found)
            return ((out[0], out[1] if want_scores else None),
                    (qi, ki, w, pos))

    @jax.custom_vjp
    def rule(qi, ki, w, pos, kth_room):
        return fwd(qi, ki, w, pos, kth_room)[0]

    def bwd(kept, cotangents):
        qi, ki, w, pos = kept
        if not want_scores:     # ``keep`` alone: nothing to differentiate
            return (jnp.zeros_like(qi), jnp.zeros_like(ki),
                    jnp.zeros_like(w), None, None)
        with jax.named_scope(INDEX):
            live, q, kt, wt, _ = turned(qi, ki, w, pos)
            dq, dkt, dw = build(BWD_NAME)(live, q, q.swapaxes(1, 2), kt, ki,
                                          wt, cotangents[1])
            dki = dkt.swapaxes(1, 2).reshape(tk, width).astype(ki.dtype)
            return (dq.swapaxes(0, 1), dki,
                    jnp.sum(dw, axis=-1).T.astype(w.dtype), None, None)

    rule.defvjp(fwd, bwd)
    return search, rule


def index_select(qi, ki, w, pos, topk: int, select: bool = True,
                 want_scores: bool = True, interpret: bool = False):
    """``index_scores`` and ``top_k_mask`` through the kernels, for shapes
    that :func:`fits` takes: ``qi [Tq, Hi, Di]`` at the positions ``pos
    [Tq]`` over ``ki [Tk, Di]`` under ``w [Tq, Hi]`` -> ``(keep [Tq, Tk]
    int8, scores [Tq, Tk] float32 | None)``: of each query's seen keys the
    ``topk`` of largest score, ties to the lower index (every seen key
    unless ``select``), and the scores where ``want_scores`` (zeros in the
    key blocks past the block's last query). The thresholds are searched
    without a gradient and named :data:`~relayrl_tpu.ops.sparse_attn.
    KTH_NAME`: a checkpoint that keeps that name runs no search in its
    recompute. Compiled by Mosaic: a TPU backend only; ``interpret=True``
    runs the bodies in the Pallas interpreter — a test-only switch that is
    never defaulted on."""
    (tq, n_heads, width), tk = qi.shape, ki.shape[0]
    shape = (tq, tk, n_heads, width)
    if not fits(*shape):
        raise ValueError(f"the indexer's kernels do not tile {tq} queries of "
                         f"{n_heads} x {width} over {tk} keys")
    search, rule = _make_rule(shape, qi.dtype.name, int(topk), bool(select),
                              bool(want_scores), bool(interpret))
    kth_room = None
    if select:
        kth_room = checkpoint_name(search(*(jax.lax.stop_gradient(a) for a in (
            qi, ki, w)), pos), KTH_NAME)
    return rule(qi, ki, w, pos, kth_room)
