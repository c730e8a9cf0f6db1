"""The attention over the selected keys of :mod:`relayrl_tpu.ops.sparse_attn`
(``masked_attention`` and ``p^``) as Pallas TPU kernels: a tile's scores and
probabilities stay in VMEM, forward and backward. ``ops/sparse_attn.py`` has
the equations and the rule that picks these kernels; this module is imported
only where that rule says so (importing ``jax.experimental.pallas`` costs
about a second that no other model should pay). It shares no line with
``ops/flash.py``: there the mask is a function of position and a block above
the diagonal costs nothing, here the mask is DATA — the selection — and every
score tile pays a load and a select for it.

**One call a tile of queries.** ``k, v [Tk, Hkv * D]`` as the projections
left them, the heads side by side in the lanes (a free reshape; ``D`` a whole
lane tile, so a k/v head is an aligned lane block), ``keep [Tq, Tk]`` as
int8, and ``q`` / ``do`` / ``dq`` the heads first, ``[H, Tq, D]``: plain XLA
turns the tile's 4 MB with the scale it multiplies in anyway, and a kernel
takes head ``h`` by the leading index. The grid is ``(k/v head, key
block)``: a step holds the ``H / Hkv`` query heads that read one k/v head,
ONE ``(block, D)`` block of its keys and values and ONE ``(Tq, block)`` tile
of the mask, and walks the heads — a loop on the device, not copies of its
body: see :func:`_each_head` — with the block and the mask in VMEM: k and v
are never repeated for a group. ``q`` comes scaled by ``log2(e) / sqrt(D)``,
so the kernels' exponentials are ``exp2`` and the log-sum-exp they pass each
other is in base 2; ``dq`` and ``dk`` undo the factor a ``[rows, D]`` block
at a time.

**The key blocks above the tile's last query are skipped from its
position**: ``live``, the number of key blocks at or before the largest of
the tile's positions, is a prefetched scalar; a step past it computes
nothing and names the block its neighbour names, so Pallas moves nothing
for it either (a gradient's or ``p^``'s block there is written as zeros).
Causality itself is in ``keep`` — the selection is made of seen keys — so no
kernel builds a position mask. A block inside the triangle in which the
selection kept nothing is computed like any other.

* ``sparse_attn_fwd``: the online softmax over the live key blocks in TURNED
  space — scores as ``[keys, queries]``, ``k q^T``, the mask tile and the
  values' block turned once a step — so that the running maximum and sum are
  rows ``[1, Tq]`` a head and their reductions run down the sublanes,
  elementwise over a tile's registers (across the lanes the two reductions
  were 1.4 of the kernel's 2.4 ms a tile: PERF.md section 6, PR 48); float32
  scores, maximum, sum and accumulator ``[D, Tq]`` in VMEM scratch, the
  probabilities rounded to ``v``'s dtype for ``v^T p^T``; writes ``out`` as
  ``[H, D, Tq]`` (plain XLA turns it back) and each head's log-sum-exp.
* ``sparse_attn_phat``: ``p^``, the mean over ALL the heads of the
  probabilities, ``[Tq, Tk]`` float32, from ``q``, ``k``, ``keep`` and the
  log-sum-exp: the grid is ``(key block, k/v head)`` here, the k/v head
  innermost, and the heads are summed into the output block while it stays in
  VMEM. Only where the caller wants the indexers' loss; detached (its inputs
  are cut before the call), it has no backward.
* ``sparse_attn_bwd``: the whole backward of a tile, in turned space like
  the forward: a head's ``p^T`` made again from the log-sum-exp and ``ds^T =
  p^T (v do^T - delta)`` ONCE a head and key block, and all three gradients
  from them as plain matmuls — ``dv = p^T do`` and ``dk = ds^T q``, summed
  over the group's heads in the step (a tile's ``dk`` / ``dv`` are complete
  when its call ends, and the tiles' add up through the caller's loop), and
  ``dq^T += k^T ds^T`` into a float32 ``[D, Tq]`` a head in VMEM scratch
  over the key blocks, the key block turned once a step for the group's
  heads; writes ``dq`` as ``[H, D, Tq]`` at the last step (plain XLA turns
  it back, as it turns ``out``). Five matmuls a head and block where a dq
  and a dk / dv kernel made seven and the scores, the select and the
  exponentials twice (PERF.md section 6, PR 51).

The per-query float32 scalars (log-sum-exp, delta) travel as ``[Hkv, H /
Hkv, Tq]`` rows for the kernels whose queries lie along the lanes (forward,
backward) and as ``[Hkv, Tq, H / Hkv]`` columns for ``p^``, whose queries
lie down the sublanes; plain XLA turns its 64 KB.

**Every query keeps at least one key** (``top_k_mask`` keeps ``min(t + 1,
topk) >= 1``): a row whose first blocks hold no kept key carries a running
maximum of ``-1e30`` until its first kept key, whose rescale wipes what the
masked entries added.

``jax.custom_vjp``, and what the backward needs of the forward: the
log-sum-exp, which the forward rule names
(:data:`relayrl_tpu.ops.sparse_attn.LSE_NAME`) for the caller's checkpoint
policy, and ``delta = rowsum(do * out)`` — for which the rule does NOT keep
``out``: it returns zeros ``owed [Tq, H]`` beside ``out``, whose cotangent
:func:`settle` fills with ``delta`` from the ``out`` the caller assembled.
``ops/sparse_attn._sequence`` settles once a sequence, outside the tiles'
loop, so a tile's ``jax.checkpoint`` keeps 64 KB and the backward runs
``sparse_attn_phat`` + ``sparse_attn_bwd`` and never the forward kernel a
second time; :func:`masked_attention_pallas` alone
settles its own call. ``keep`` and ``live`` are integers and get no
cotangent; the log-sum-exp's cotangent is dropped (nothing differentiates
it: ``p^`` is detached).

Names (``ops/scopes.py``): every call sits under ``relayrl_sparse_attn`` and
under no deeper ``relayrl_`` name — the kernels' own names carry no such
prefix — so the benchmark's ``sparse_attn_ms`` (device time under the exact
scope) holds them; both rules of each ``custom_vjp`` open the scope
themselves.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from relayrl_tpu.ops.attention import _NEG_INF
from relayrl_tpu.ops.scopes import SPARSE_ATTN
from relayrl_tpu.ops.sparse_attn import LSE_NAME

FWD_NAME, PHAT_NAME = "sparse_attn_fwd", "sparse_attn_phat"
BWD_NAME = "sparse_attn_bwd"

_LOG2E = math.log2(math.e)
_VMEM_LIMIT = 64 * 1024 * 1024
_KEY_BLOCKS = (512, 256, 128)
# rows x lanes of float32 a step's accumulators may hold, a head's at a time
_MAX_GROUP = 8 * 512 * 128

_NT = (((1,), (1,)), ((), ()))   # a @ b^T
_NN = (((1,), (0,)), ((), ()))   # a @ b

_F32 = jnp.float32


def _mm(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _kept(keep_ref, turned: bool = False):
    """A step's mask tile as a bool ``[Tq, block]``, or turned."""
    mask = keep_ref[...].astype(_F32)
    return (mask.T if turned else mask) > 0.0


def _turn(x):
    """A step's ``[block, D]`` block of keys or values as ``[D, block]``,
    once for all the heads of its group (in float32: a 32-bit tile is what
    the transpose unit turns)."""
    return x.astype(_F32).T.astype(x.dtype)


def _of_head(x, h, axis: int):
    """Head ``h``'s column of ``x [Tq, heads]`` (``axis`` 1) or row of ``x
    [heads, Tq]`` (``axis`` 0), for an ``h`` that a loop counts."""
    at = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    return jnp.sum(jnp.where(at == h, x, 0.0), axis=axis, keepdims=True)


def _each_head(rep: int, body, carry=None):
    """``body(h, carry)`` for the ``rep`` heads of a step's group: a loop
    on the device, not ``rep`` copies of the body in the kernel's code. A
    head's score tile is 256 vector registers an operation: unrolled, a
    kernel is 0.4 to 1.3 MB of code for 0.17 to 0.33 and an update holds 64
    of them, in an executable whose size the chip machine's compile cache
    bounds (ROADMAP 1.13 (e)); the loop costs the kernels 12 to 16% of their
    time (PERF.md section 6, PR 48 and PR 51)."""
    return jax.lax.fori_loop(0, rep, body, carry)


def _fwd_kernel(live_ref, q_ref, k_ref, v_ref, keep_ref, out_ref, lse_ref,
                m_ref, l_ref, acc_ref, *, rep: int):
    """``sparse_attn_fwd``: one key block of one k/v head's query heads, in
    turned space — scores ``[keys, queries]``, ``out`` as ``[D, queries]`` —
    so that the softmax's maximum and sum run down the sublanes (elementwise
    over a tile's registers, a row ``[1, Tq]`` a head) and not across the
    lanes: across the lanes, the two reductions were 1.4 of the kernel's
    2.4 ms a tile (my chip runs, PR 48)."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _start():
        def head(h, _):
            m_ref[h] = jnp.full(m_ref.shape[1:], _NEG_INF, _F32)
            l_ref[h] = jnp.zeros(l_ref.shape[1:], _F32)
            acc_ref[h] = jnp.zeros(acc_ref.shape[1:], _F32)

        _each_head(rep, head)

    @pl.when(j < live_ref[0])
    def _block():
        kept, k = _kept(keep_ref, turned=True), k_ref[...]
        v_t = _turn(v_ref[...])

        def head(h, _):
            s = jnp.where(kept, _mm(k, q_ref[h], _NT), _NEG_INF)
            m_prev = m_ref[h][:1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            p = jnp.exp2(s - m_new)
            alpha = jnp.exp2(m_prev - m_new)
            l_new = alpha * l_ref[h][:1] + jnp.sum(p, axis=0, keepdims=True)
            acc_ref[h] = alpha * acc_ref[h] + _mm(v_t, p.astype(v_t.dtype))
            m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])

        _each_head(rep, head)

    @pl.when(j == pl.num_programs(1) - 1)
    def _end():
        at = jax.lax.broadcasted_iota(jnp.int32, lse_ref.shape[1:], 0)

        def head(h, lse):
            total = l_ref[h][:1]
            out_ref[h] = (acc_ref[h] / total).astype(out_ref.dtype)
            return jnp.where(at == h, m_ref[h][:1] + jnp.log2(total), lse)

        lse_ref[0] = _each_head(rep, head, jnp.zeros(lse_ref.shape[1:], _F32))


def _phat_kernel(live_ref, q_ref, k_ref, keep_ref, lse_ref, p_ref, *,
                 rep: int, n_heads: int):
    """``sparse_attn_phat``: one k/v head's share of one key block of
    ``p^`` (grid ``(key block, k/v head)``: the output block waits in VMEM
    for all of them)."""
    j, g = pl.program_id(0), pl.program_id(1)

    @pl.when(g == 0)
    def _start():
        p_ref[...] = jnp.zeros(p_ref.shape, _F32)

    @pl.when(j < live_ref[0])
    def _block():
        kept, k, lse = _kept(keep_ref), k_ref[...], lse_ref[0]

        def head(h, _):
            s = jnp.where(kept, _mm(q_ref[h], k, _NT), _NEG_INF)
            p_ref[...] += jnp.exp2(s - _of_head(lse, h, 1))

        _each_head(rep, head)

    @pl.when(g == pl.num_programs(1) - 1)
    def _end():
        p_ref[...] *= 1.0 / n_heads


def _bwd_kernel(live_ref, q_ref, k_ref, v_ref, keep_ref, do_ref, lse_ref,
                delta_ref, dq_ref, dk_ref, dv_ref, acc_ref, *, rep: int):
    """``sparse_attn_bwd``: one key block's ``dk`` and ``dv`` of this tile,
    summed over the group's heads, and its share of the group's ``dq``;
    scores turned, ``[keys, queries]``, made once for the three of them, and
    ``dq`` accumulated turned, ``[D, queries]`` a head, as the forward
    accumulates ``out``."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _start():
        acc_ref[...] = jnp.zeros(acc_ref.shape, _F32)

    @pl.when(j < live_ref[0])
    def _block():
        kept, k, v = _kept(keep_ref, turned=True), k_ref[...], v_ref[...]
        k_t, lse, delta = _turn(k), lse_ref[0], delta_ref[0]

        def head(h, sums):
            q, do = q_ref[h], do_ref[h]
            s = jnp.where(kept, _mm(k, q, _NT), _NEG_INF)
            p = jnp.exp2(s - _of_head(lse, h, 0))
            ds = (p * (_mm(v, do, _NT) - _of_head(delta, h, 0))).astype(
                q.dtype)
            acc_ref[h] += _mm(k_t, ds)
            return sums[0] + _mm(ds, q), sums[1] + _mm(p.astype(do.dtype), do)

        dk, dv = _each_head(rep, head, (jnp.zeros(dk_ref.shape, _F32),
                                        jnp.zeros(dv_ref.shape, _F32)))
        # the scores were made of q log2(e) / sqrt(D): the first factor goes
        dk_ref[...] = (dk * (1.0 / _LOG2E)).astype(dk_ref.dtype)
        dv_ref[...] = dv.astype(dv_ref.dtype)

    @pl.when(j >= live_ref[0])
    def _above():
        dk_ref[...] = jnp.zeros(dk_ref.shape, dk_ref.dtype)
        dv_ref[...] = jnp.zeros(dv_ref.shape, dv_ref.dtype)

    @pl.when(j == pl.num_programs(1) - 1)
    def _end():
        # q came in scaled by log2(e) / sqrt(D); d(scores) / d(q) is the
        # second factor alone
        dq_ref[...] = (acc_ref[...] * acc_ref.shape[1] ** -0.5).astype(
            dq_ref.dtype)


def key_block(tk: int) -> int | None:
    """The key block the kernels walk ``tk`` keys in: the largest of
    :data:`_KEY_BLOCKS` that divides them, None where none does."""
    return next((b for b in _KEY_BLOCKS if tk % b == 0), None)


def fits(tq: int, tk: int, n_heads: int, n_kv: int, width: int) -> bool:
    """Whether the kernels take ``tq`` queries of ``n_heads`` heads over
    ``tk`` keys of ``n_kv``: heads of whole lane tiles, whole groups, a tile
    of queries that fills the lanes of the turned scores (and the int8
    mask's sublanes), keys in whole blocks, and a group's accumulators
    within what a step may hold in VMEM."""
    return (width % 128 == 0 and n_heads % n_kv == 0 and tq % 128 == 0
            and key_block(tk) is not None
            and tq * (n_heads // n_kv) * width <= _MAX_GROUP)


@functools.lru_cache(maxsize=None)
def _build(kind: str, shape: tuple, dtype_name: str, interpret: bool):
    """The ``pallas_call`` of one kernel, ``shape = (tq, tk, n_heads, n_kv,
    width)``; its first operand is ``live [1]`` int32."""
    tq, tk, n_heads, n_kv, width = shape
    rep, block, cd = n_heads // n_kv, key_block(tk), jnp.dtype(dtype_name)
    turned = kind == PHAT_NAME      # its grid is (key block, k/v head)

    def spec(block_shape, index):
        """``index(g, j, at)``: k/v head, key block, and the key block a
        step LOADS (its neighbour's past ``live``)."""
        def index_map(a, b, live):
            g, j = (b, a) if turned else (a, b)
            return index(g, j, jnp.minimum(j, live[0] - 1))

        return pl.BlockSpec(block_shape, index_map)

    heads = spec((rep, tq, width), lambda g, j, at: (g, 0, 0))
    heads_t = spec((rep, width, tq), lambda g, j, at: (g, 0, 0))
    keys = spec((block, width), lambda g, j, at: (at, g))
    mask = spec((tq, block), lambda g, j, at: (0, at))
    cols = spec((1, tq, rep), lambda g, j, at: (g, 0, 0))
    rows = spec((1, rep, tq), lambda g, j, at: (g, 0, 0))
    S = jax.ShapeDtypeStruct
    heads_t_s, keys_s = S((n_heads, width, tq), cd), S((tk, n_kv * width), cd)
    scratch = []
    if kind == FWD_NAME:
        kernel = functools.partial(_fwd_kernel, rep=rep)
        in_specs = [heads, keys, keys, mask]
        out_specs = [heads_t, rows]
        out_shape = [heads_t_s, S((n_kv, rep, tq), _F32)]
        scratch = [pltpu.VMEM((rep, 8, tq), _F32),
                   pltpu.VMEM((rep, 8, tq), _F32),
                   pltpu.VMEM((rep, width, tq), _F32)]
    elif kind == PHAT_NAME:
        kernel = functools.partial(_phat_kernel, rep=rep, n_heads=n_heads)
        in_specs = [heads, keys, mask, cols]
        out_specs = [spec((tq, block), lambda g, j, at: (0, j))]
        out_shape = [S((tq, tk), _F32)]
    else:
        kernel = functools.partial(_bwd_kernel, rep=rep)
        in_specs = [heads, keys, keys, mask, heads, rows, rows]
        written = spec((block, width), lambda g, j, at: (j, g))
        out_specs = [heads_t, written, written]
        out_shape = [heads_t_s, keys_s, keys_s]
        scratch = [pltpu.VMEM((rep, width, tq), _F32)]
    grid = (tk // block, n_kv) if turned else (n_kv, tk // block)
    call = pl.pallas_call(
        kernel, name=kind,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)

    def named(*operands):
        with jax.named_scope(SPARSE_ATTN):
            return call(*operands)

    return named


@functools.lru_cache(maxsize=None)
def _make_rule(shape: tuple, dtype_name: str, interpret: bool):
    """The differentiable call and ``p^`` over ``(q [Tq, H, D], k, v [Tk,
    Hkv D], keep [Tq, Tk] int8, live [1] int32)``: ``rule -> (out [Tq, H,
    D], the log-sum-exp [Hkv, H / Hkv, Tq] float32, base 2, owed [Tq, H]
    zeros)`` — ``owed``'s cotangent is where the backward is handed ``delta``
    (:func:`settle`) — and ``p_hat(q, k, keep, live, the log-sum-exp) ->
    [Tq, Tk]`` float32."""
    tq, _, n_heads, n_kv, width = shape
    rep = n_heads // n_kv

    def build(kind):
        return _build(kind, shape, dtype_name, interpret)

    def scaled(q):
        """``q log2(e) / sqrt(D)``, the heads first: ``[H, Tq, D]``."""
        return (q.astype(_F32) * (_LOG2E * width ** -0.5)).astype(
            q.dtype).swapaxes(0, 1)

    def fwd(q, k, v, keep, live):
        with jax.named_scope(SPARSE_ATTN):
            out, lse = build(FWD_NAME)(live, scaled(q), k, v, keep)
            out, lse = out.transpose(2, 0, 1), checkpoint_name(lse, LSE_NAME)
            # zeros that are a function of ``out``: under ``vmap`` a constant
            # would be one array for the whole batch and its cotangent the
            # batch's SUM of deltas
            owed = jnp.sum(out[..., :0], axis=-1, dtype=_F32)
            return (out, lse, owed), (q, k, v, keep, live, lse)

    @jax.custom_vjp
    def rule(q, k, v, keep, live):
        return fwd(q, k, v, keep, live)[0]

    def bwd(kept, cotangents):
        q, k, v, keep, live, lse = kept
        # nothing differentiates the log-sum-exp; ``owed`` brings delta
        do, _, delta = cotangents
        with jax.named_scope(SPARSE_ATTN):
            delta = delta.T.reshape(n_kv, rep, tq)
            dq, dk, dv = build(BWD_NAME)(live, scaled(q), k, v, keep,
                                         do.swapaxes(0, 1), lse, delta)
            return dq.transpose(2, 0, 1), dk, dv, None, None

    rule.defvjp(fwd, bwd)

    def p_hat(q, k, keep, live, lse):
        q, k, lse = (jax.lax.stop_gradient(a) for a in (q, k, lse))
        return build(PHAT_NAME)(live, scaled(q), k, keep,
                                lse.swapaxes(1, 2))[0]

    return rule, p_hat


def settle(out, owed):
    """``out [..., H, D]`` as it is, and in the backward ``delta =
    rowsum(do * out) [..., H]`` as the cotangent of ``owed`` (the zeros the
    kernels' rule returned beside ``out``): the one way a cotangent-shaped
    number reaches a rule's backward without the rule keeping ``out``.
    Called once over whatever the caller assembled of the tiles' ``out``
    (``ops/sparse_attn._sequence``: the whole sequence, outside the tiles'
    loop and their ``jax.checkpoint``), what it keeps for the backward is
    the array the layer keeps anyway, and a tile's backward needs its
    log-sum-exp alone of its forward — 64 KB a tile, kept by name
    (:data:`relayrl_tpu.ops.sparse_attn.LSE_NAME`) — and never the forward
    kernel a second time. Each of the two rules is the other's half: a
    caller that takes ``owed`` owes this call."""
    # kept with the heads side by side, as the layer's output projection
    # reads (and keeps) it
    return _settle(out.reshape(*owed.shape[:-1], -1), owed).reshape(out.shape)


@jax.custom_vjp
def _settle(out, owed):
    return out


def _settle_fwd(out, owed):
    # the barrier makes THIS array what both the layer and the backward below
    # read: without it XLA reads the backward's product from the tiles'
    # stacked outputs and keeps those alive beside it, 0.13 GB a layer
    # (+1.2 GB of temporaries at the benchmark's shape, ``rehearse_compile``)
    out = jax.lax.optimization_barrier(out)
    return out, (out, owed.shape)


def _settle_bwd(kept, do):
    out, heads = kept
    with jax.named_scope(SPARSE_ATTN):
        return do, jnp.sum((do.astype(_F32) * out.astype(_F32)).reshape(
            *heads, -1), axis=-1)


_settle.defvjp(_settle_fwd, _settle_bwd)


def masked_attention_pallas(q, k, v, keep, pos, want_p_hat: bool = True,
                            defer: bool = False, interpret: bool = False):
    """:func:`relayrl_tpu.ops.sparse_attn.masked_attention` through the
    kernels, for shapes that :func:`fits` takes: ``q [Tq, H, D]`` at the
    positions ``pos [Tq]`` over ``k, v [Tk, Hkv, D]`` under ``keep [Tq,
    Tk]``, which names no key after its query's position and at least one
    key a query -> ``(out [Tq, H, D], p^ [Tq, Tk] float32, detached; None
    unless ``want_p_hat``, owed)``. ``defer``: the caller settles the
    backward's ``delta`` itself (``owed [Tq, H]``: :func:`settle`, over
    whatever it assembles of several calls' ``out``); otherwise it is settled
    here and ``owed`` is None. Compiled by Mosaic: a TPU backend only;
    ``interpret=True`` runs the bodies in the Pallas interpreter — a
    test-only switch that is never defaulted on."""
    (tq, n_heads, width), (tk, n_kv, _) = q.shape, k.shape
    shape = (tq, tk, n_heads, n_kv, width)
    if not fits(*shape):
        raise ValueError(f"the sparse attention's kernels do not tile {tq} "
                         f"queries of {n_heads} x {width} over {tk} keys of "
                         f"{n_kv}")
    rule, p_hat = _make_rule(shape, v.dtype.name, bool(interpret))
    with jax.named_scope(SPARSE_ATTN):
        block = key_block(tk)
        live = jnp.clip(jnp.max(pos) // block + 1, 1, tk // block)
        live = live.astype(jnp.int32).reshape(1)
        k, v = k.reshape(tk, -1), v.reshape(tk, -1)
        keep = keep.astype(jnp.int8)
        out, lse, owed = rule(q, k, v, keep, live)
        if not defer:
            out, owed = settle(out, owed), None
        return out, (p_hat(q, k, keep, live, lse) if want_p_hat
                     else None), owed
