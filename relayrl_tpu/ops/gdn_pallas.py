"""The chunked gated delta rule of :mod:`relayrl_tpu.ops.gdn` as Pallas TPU
kernels: a chunk's tiles, its solve and the carried ``[K, V]`` state stay in
VMEM, forward and backward. ``ops/gdn.py`` has the rule, its algebra and the
rule that picks these kernels; this module is imported only where that rule
says so (importing ``jax.experimental.pallas`` costs about a second that no
other model should pay).

**Operands as the projections left them.** ``q`` / ``k`` are ``[b, T, Hk *
K]`` and ``v`` / ``o`` / their cotangents ``[b, T, H * V]``, the heads side by
side in the lanes (a free reshape): with ``K`` and ``V`` whole lane tiles head
``h`` is an aligned ``(chunk, 128)`` block, and no head transpose exists round
the calls. The state is ``[b, H, K, V]`` float32 as the caller holds it. The
per-token scalars — ``gamma``, the running sum of ``g`` inside each chunk,
``beta``, ``exp(gamma)`` and ``exp(gamma_last - gamma)`` — are made outside by
plain XLA on 4 MB arrays (``_columns``) and come as columns, the eight heads
of a grid step side by side; ``gamma`` and ``beta`` come a second time as rows
``[b, T / chunk, H, chunk]`` for the tiles' other axis. The kernels give back
``d cols`` and ``d gamma rows``, and autodiff of ``_columns`` turns them into
the gradients of ``g`` and ``beta``: no running sum is made in VMEM.

**Grid** ``(b, H / 8, T / chunk)``: eight value heads a step with their key
heads (``HEADS_A_STEP``; ``K K^T`` and ``Q K^T`` are made once a key head, and
eight heads' dependent chains interleave), the chunk axis last and sequential
— TPU grids run in order, so a float32 state in VMEM scratch, set from
``state`` at the first chunk, is the recurrence.

* ``gdn_fwd``: the masked decays, ``K K^T`` / ``Q K^T``, ``A``, the solve ``T
  = (I - A)^-1`` (the product form, float32 at precision "highest"), ``W``,
  ``U``, ``v' = U - W S``, ``o`` and the state's update; writes ``o`` and, at
  the last chunk, ``last_state``. A forward that is being differentiated also
  writes the solve's tiles in the compute dtype, as the rule rounds them
  (``[b, T / chunk, H, chunk, chunk]``: 67 MB a layer at the benchmark's
  shape, named ``relayrl_gdn_solve`` for a caller's checkpoint policy): the
  solve is most of a step's matmul passes, and the backward then makes none.
  A rule nobody differentiates writes ``o`` alone.
* ``gdn_states``: ``v'`` and the state's update from the kept solve, writing
  the float32 state each chunk STARTS from (``[b, T / chunk, H, K, V]``:
  0.54 GB a layer at the benchmark's shape, alive inside that layer's
  backward only).
* ``gdn_bwd``: the reverse sweep, the chunk axis walked from the last to the
  first, carrying the state's cotangent in VMEM; makes the tiles again, the
  score tile turned (``[j, i]``, so that ``scores^T do`` is a plain matmul),
  and writes the gradients of ``q``, ``k``, ``v``, the columns, ``gamma`` as
  rows and the initial ``state``. The solve's transpose needs no second
  inversion and not even ``dT``: with ``dW`` and ``dU`` the cotangents of
  ``W = T kb`` and ``U = T vb``, ``dA = T^T (dW kb^T + dU vb^T) T^T = (T^T
  dW) W^T + (T^T dU) U^T``, masked strictly lower — two matmuls.

**The backward makes the chunk-start states again** (``gdn_states``) and does
not keep them from the forward: under the mixer's ``jax.checkpoint`` (which
keeps the rule's output and the solve by name) the backward runs
``gdn_states`` + ``gdn_bwd`` and never the forward a second time.

Precision as ``ops/gdn._heads`` has it: the columns, the sums, the decays,
the solve and the state float32; every other matmul's operands in ``v``'s
dtype with float32 accumulation, rounded where ``_heads`` rounds them (the
solve, ``W``, ``k beta e^gamma``, ``beta v``, the scores, ``q e^gamma``, ``k
e^(gamma_C - gamma)``, ``v'``, the state for the carried products); in the
backward the cotangents that enter a matmul are rounded the same way.
Exponentials of non-positive sums only, masked BEFORE the exponential.

Names (``ops/scopes.py``): every call sits under ``relayrl_gdn`` with no
deeper ``relayrl_`` name — the kernels are ``gdn_fwd`` / ``gdn_states`` /
``gdn_bwd`` — so the benchmark's ``gdn_ms`` (device time under the exact
scope) holds them; the ``custom_vjp``'s rules open the scope themselves.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from relayrl_tpu.ops.gdn import SOLVE_NAME
from relayrl_tpu.ops.scopes import GDN_NAME

FWD_NAME, STATES_NAME, BWD_NAME = "gdn_fwd", "gdn_states", "gdn_bwd"
HEADS_A_STEP = 8

_NT = (((1,), (1,)), ((), ()))   # a @ b^T
_NN = (((1,), (0,)), ((), ()))   # a @ b

_F32 = jnp.float32


def _mm(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _mm32(a, b):
    """A float32 product of float32 operands (the solve's)."""
    return jax.lax.dot_general(a, b, _NN, preferred_element_type=_F32,
                               precision=jax.lax.Precision.HIGHEST)


def _inverse_unit_lower(tiles):
    """``(I - a)^-1`` of each of some strictly lower-triangular float32
    tiles, the product form of ``ops/gdn._inverse_unit_lower`` operation for
    operation. Tiles of half a lane tile go two at a time, side by side in
    the lanes against a block-diagonal right operand — ``[a | b] @ diag(c,
    d) = [a c | b d]``: one 128-deep pass where two 64-deep ones each fill a
    quarter of the MXU (the solve is three quarters of ``gdn_fwd``; PERF.md
    section 6, PR 43)."""
    size = tiles[0].shape[0]
    per = 2 if size * 2 == 128 and len(tiles) % 2 == 0 else 1
    inverses = []
    for at in range(0, len(tiles), per):
        a = tiles[at] if per == 1 else jnp.concatenate(
            tiles[at:at + per], axis=1)
        i = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
        j = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)

        def diagonal(x):     # [a | b] -> diag(a, b)
            if per == 1:
                return x
            return jnp.concatenate([jnp.where(j < size, x, 0.0),
                                    jnp.where(j >= size, x, 0.0)], axis=0)

        eye = (i == j) if per == 1 else (i == j) | (i + size == j)
        inv, power, covered = jnp.where(eye, 1.0, 0.0) + a, a, 2
        while covered < size:
            power = _mm32(power, diagonal(power))
            inv = inv + _mm32(power, diagonal(inv))
            covered *= 2
        inverses += [inv] if per == 1 else [inv[:, :size], inv[:, size:]]
    return inverses


def _total(x):
    """The sum of a 2-D block as ``[1, 1]``."""
    return jnp.sum(jnp.sum(x, axis=1, keepdims=True), axis=0, keepdims=True)


def _lanes(x):
    """Row sums as a column ``[rows, 1]``."""
    return jnp.sum(x, axis=1, keepdims=True)


# A step's per-token scalars as columns, ``cols [chunk, 4 * 8]``: eight
# heads' gamma | beta | exp(gamma) | exp(gamma_last - gamma) (``_columns``)
GAMMA, BETA, IN, TO_END = (n * HEADS_A_STEP for n in range(4))


class _Chunk:
    """What the kernels read of a step's per-token scalars, a head at a
    time: columns ``[chunk, 1]`` for a tile's rows, rows ``[1, chunk]`` for
    its lanes."""

    def __init__(self, cols_ref, grows_ref, brows_ref=None):
        self.cols = cols_ref[0, 0]                         # [chunk, 32]
        self.grows = grows_ref[0, 0]                       # [8, chunk]
        self.brows = None if brows_ref is None else brows_ref[0, 0]
        self.chunk = chunk = self.cols.shape[0]
        i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        self.lower, self.strict, self.upper = i >= j, i > j, i <= j

    def col(self, what: int, r: int):                      # [chunk, 1]
        return self.cols[:, what + r:what + r + 1]

    def gamma_row(self, r: int):                           # [1, chunk]
        return self.grows[r:r + 1, :]

    def beta_row(self, r: int):
        return self.brows[r:r + 1, :]

    def to_end_row(self, r: int):
        row = self.gamma_row(r)
        return jnp.exp(row[:, self.chunk - 1:] - row)

    def through(self, r: int, width: int):                 # [1, width]
        """``exp(gamma_last)`` along a row of the state (Mosaic broadcasts
        along one axis at a time: the exponential keeps the two apart)."""
        return jnp.exp(jnp.broadcast_to(
            self.col(GAMMA, r)[self.chunk - 1:], (1, width)))

    def decay(self, r: int, turned: bool = False):
        """``exp(gamma_i - gamma_j)`` on and under the diagonal, 0 above
        it, as ``[i, j]`` or turned, ``[j, i]``."""
        col, row = self.col(GAMMA, r), self.gamma_row(r)
        if turned:
            return jnp.exp(jnp.where(self.upper, row - col, -jnp.inf))
        return jnp.exp(jnp.where(self.lower, col - row, -jnp.inf))


def _head(ref, r: int, width: int):
    """Head ``r``'s lanes of a ``[1, chunk, heads * width]`` block."""
    return ref[0, :, r * width:(r + 1) * width]


def _solved(ch: _Chunk, kks, rep: int, cd):
    """The solve's tiles of a step's heads from their key heads' ``K K^T``,
    rounded as the rule rounds them."""
    tiles = [jnp.where(ch.strict, -(ch.col(BETA, r) * kks[r // rep]
                                    * ch.decay(r)), 0.0)
             for r in range(len(kks) * rep)]
    return [tile.astype(cd) for tile in _inverse_unit_lower(tiles)]


def _corrected(ch: _Chunk, r: int, solve, kf, v, start, cd):
    """``(W, U, v')`` of head ``r`` from the solve's tile and the state the
    chunk starts from: ``W`` and ``v'`` rounded for the products they enter,
    ``U`` float32."""
    beta = ch.col(BETA, r)
    w = _mm(solve, (kf * beta * ch.col(IN, r)).astype(cd)).astype(cd)
    u = _mm(solve, (v.astype(_F32) * beta).astype(cd))
    return w, u, (u - _mm(w, start.astype(cd))).astype(cd)


def _advanced(ch: _Chunk, r: int, start, k_t, v_new):
    """The state head ``r``'s chunk ends in: ``e^gamma_C S + (k e^(gamma_C -
    gamma))^T v'``, the keys already turned (``k_t [K, chunk]`` float32)."""
    return ch.through(r, start.shape[1]) * start + _mm(
        (k_t * ch.to_end_row(r)).astype(v_new.dtype), v_new)


def _fwd_kernel(*refs, K: int, V: int, rep: int, keep_solve: bool):
    """``gdn_fwd``: a chunk of eight value heads; ``keep_solve`` writes the
    solve's tiles for a backward to read."""
    (q_ref, k_ref, v_ref, cols_ref, grows_ref, s0_ref, o_ref,
     last_ref) = refs[:8]
    solve_ref = refs[8] if keep_solve else None
    state_ref = refs[-1]
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _start():
        state_ref[...] = s0_ref[0]

    ch = _Chunk(cols_ref, grows_ref)
    cd = v_ref.dtype
    keys = [_head(k_ref, kh, K) for kh in range(HEADS_A_STEP // rep)]
    solves = _solved(ch, [_mm(k, k, _NT) for k in keys], rep, cd)
    for kh, k in enumerate(keys):
        q = _head(q_ref, kh, K)
        qk = _mm(q, k, _NT)
        qf, kf = q.astype(_F32), k.astype(_F32)
        k_t = kf.T                                         # [K, chunk]
        for r in range(kh * rep, (kh + 1) * rep):
            solve = solves[r]
            if keep_solve:
                solve_ref[0, 0, r] = solve
            start = state_ref[r]
            _, _, v_new = _corrected(ch, r, solve, kf, _head(v_ref, r, V),
                                     start, cd)
            o = (_mm((qf * ch.col(IN, r)).astype(cd), start.astype(cd))
                 + _mm((qk * ch.decay(r)).astype(cd), v_new))
            o_ref[0, :, r * V:(r + 1) * V] = o.astype(cd)
            state_ref[r] = _advanced(ch, r, start, k_t, v_new)

    @pl.when(c == pl.num_programs(2) - 1)
    def _end():
        last_ref[0] = state_ref[...]


def _states_kernel(k_ref, v_ref, cols_ref, grows_ref, solve_ref, s0_ref,
                   start_ref, state_ref, *, K: int, V: int, rep: int):
    """``gdn_states``: the state's update alone, from the kept solve."""
    @pl.when(pl.program_id(2) == 0)
    def _start():
        state_ref[...] = s0_ref[0]

    ch = _Chunk(cols_ref, grows_ref)
    cd = v_ref.dtype
    for kh in range(HEADS_A_STEP // rep):
        kf = _head(k_ref, kh, K).astype(_F32)
        k_t = kf.T
        for r in range(kh * rep, (kh + 1) * rep):
            start = state_ref[r]
            start_ref[0, 0, r] = start
            _, _, v_new = _corrected(ch, r, solve_ref[0, 0, r], kf,
                                     _head(v_ref, r, V), start, cd)
            state_ref[r] = _advanced(ch, r, start, k_t, v_new)


def _bwd_kernel(q_ref, k_ref, v_ref, cols_ref, grows_ref, brows_ref,
                solve_ref, start_ref, do_ref, dlast_ref,
                dq_ref, dk_ref, dv_ref, dcols_ref, dgrows_ref, ds0_ref,
                dstate_ref, *, K: int, V: int, rep: int):
    """One chunk of the reverse sweep (grid step ``c`` is chunk ``T / chunk
    - 1 - c``): ``dstate_ref`` holds the cotangent of the state the chunk
    ENDS in. In the names of ``ops/gdn.py``, with ``kb = k beta e^gamma``,
    ``vb = beta v``, ``N = v'``, ``P`` the scores::

        dN = P^T do + (k to_end) dS'
        dS = through dS' + (q e^gamma)^T do - W^T dN
        dkb = T^T (-dN S^T)      dvb = T^T dN      dA = dkb W^T + dvb U^T
    """
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _start():
        dstate_ref[...] = dlast_ref[0]

    ch = _Chunk(cols_ref, grows_ref, brows_ref)
    chunk, cd = ch.chunk, v_ref.dtype
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, ch.cols.shape[1]), 1)
    dcols = jnp.zeros(ch.cols.shape, _F32)
    dlast = jnp.zeros((1, ch.cols.shape[1]), _F32)          # of gamma_last
    row = jax.lax.broadcasted_iota(jnp.int32, (HEADS_A_STEP, 1), 0)
    dgrows = jnp.zeros(ch.grows.shape, _F32)

    for kh in range(HEADS_A_STEP // rep):
        q, k = _head(q_ref, kh, K), _head(k_ref, kh, K)
        qf, kf = q.astype(_F32), k.astype(_F32)
        q_t, k_t = qf.T, kf.T                              # [K, chunk]
        kk, kq = _mm(k, k, _NT), _mm(k, q, _NT)            # kq turned: [j, i]
        dkq = jnp.zeros_like(kq)                           # d (Q K^T), turned
        dkk = jnp.zeros_like(kk)
        dq = jnp.zeros(qf.shape, _F32)
        dk = jnp.zeros(kf.shape, _F32)
        for r in range(kh * rep, (kh + 1) * rep):
            beta, e, to_end = ch.col(BETA, r), ch.col(IN, r), ch.col(TO_END, r)
            e_row = jnp.exp(ch.gamma_row(r))
            decay, decay_t = ch.decay(r), ch.decay(r, turned=True)
            through = ch.through(r, V)
            solve = solve_ref[0, 0, r]
            solve_t = solve.astype(_F32).T.astype(cd)
            v = _head(v_ref, r, V)
            vf = v.astype(_F32)
            do = _head(do_ref, r, V)
            start = start_ref[0, 0, r]
            start_lo = start.astype(cd)
            w, u, v_new = _corrected(ch, r, solve, kf, v, start, cd)
            dstate = dstate_ref[r]
            dstate_lo = dstate.astype(cd)

            scores_t = kq * decay_t                        # P^T, [j, i]
            dn = (_mm(scores_t.astype(cd), do)
                  + _mm((kf * to_end).astype(cd), dstate_lo)).astype(cd)
            # W^T = kb^T T^T, made turned: no transpose of a product
            w_t = _mm((k_t * (ch.beta_row(r) * e_row)).astype(cd),
                      solve_t).astype(cd)
            dstate_ref[r] = (through * dstate
                             + _mm((q_t * e_row).astype(cd), do)
                             - _mm(w_t, dn))
            dq_in = _mm(do, start_lo, _NT)                 # d (q e^gamma)
            dscores_t = _mm(v_new, do, _NT)                # [j, i]
            dk_out = _mm(v_new, dstate_lo, _NT)            # d (k to_end)
            dw = -_mm(dn, start_lo, _NT)
            dkb = _mm(solve_t, dw.astype(cd))
            dvb = _mm(solve_t, dn)
            da = jnp.where(
                ch.strict,
                _mm(dkb.astype(cd), w, _NT) + _mm(dvb.astype(cd),
                                                  u.astype(cd), _NT), 0.0)
            # A = -beta_i (K K^T)_ij decay_ij under the diagonal
            kk_decay = kk * decay
            dkk -= da * beta * decay
            dkq += dscores_t * decay_t
            dseg = da * (-beta * kk_decay)     # d (gamma_i - gamma_j)
            dseg_t = dscores_t * scores_t                  # the same, [j, i]
            dgamma = _lanes(dseg) - _lanes(dseg_t)
            dbeta = (_lanes(dvb * vf) + _lanes(dkb * kf) * e
                     - _lanes(da * kk_decay))
            de = _lanes(dq_in * qf) + _lanes(dkb * kf) * beta
            for what, column in ((GAMMA, dgamma), (BETA, dbeta), (IN, de),
                                 (TO_END, _lanes(dk_out * kf))):
                dcols = jnp.where(lane == what + r, column, dcols)
            dlast = jnp.where(lane == GAMMA + r, _total(
                through * dstate * start), dlast)
            dgrows = jnp.where(
                row == r, jnp.sum(dseg_t, axis=0, keepdims=True)
                - jnp.sum(dseg, axis=0, keepdims=True), dgrows)
            dv_ref[0, :, r * V:(r + 1) * V] = (dvb * beta).astype(cd)
            dq += dq_in * e
            dk += dkb * (beta * e) + dk_out * to_end
        dq_ref[0, :, kh * K:(kh + 1) * K] = (
            dq + _mm(dkq.T.astype(cd), k)).astype(dq_ref.dtype)
        dk_ref[0, :, kh * K:(kh + 1) * K] = (
            dk + _mm(dkq.astype(cd), q) + _mm((dkk + dkk.T).astype(cd), k)
        ).astype(dk_ref.dtype)

    dgrows_ref[0, 0] = dgrows
    dcols_ref[0, 0] = dcols
    dcols_ref[0, 0, chunk - 1:, :] += dlast

    @pl.when(c == pl.num_programs(2) - 1)
    def _end():
        ds0_ref[0] = dstate_ref[...]


def fits(H: int, Hk: int, K: int, V: int, chunk: int) -> bool:
    """Whether the kernels take a rule of these shapes (``T`` is padded to
    whole chunks first, as the plain form pads it): ``K`` and ``V`` whole
    lane tiles, eight value heads a step with whole key heads, and a chunk
    that tiles (the sublanes of a bfloat16 block, the lanes of its own
    tiles)."""
    return (K % 128 == 0 and V % 128 == 0 and H % Hk == 0
            and H % HEADS_A_STEP == 0 and HEADS_A_STEP % (H // Hk) == 0
            and chunk in (64, 128))


@functools.lru_cache(maxsize=None)
def _build(kind: str, shape: tuple, chunk: int, dtype_name: str,
           keep_solve: bool, interpret: bool):
    """The ``pallas_call`` of one kernel over ``[b, T, heads * width]``
    operands (``shape = (b, T, H, Hk, K, V)``, ``T`` whole chunks)."""
    b, T, H, Hk, K, V = shape
    hb, rep = HEADS_A_STEP, H // Hk
    steps, nc = H // hb, T // chunk
    cd = jnp.dtype(dtype_name)
    back = kind == BWD_NAME

    def at(c):                       # the chunk of grid step c
        return nc - 1 - c if back else c

    keys = pl.BlockSpec((1, chunk, hb // rep * K),
                        lambda i, s, c: (i, at(c), s))
    values = pl.BlockSpec((1, chunk, hb * V), lambda i, s, c: (i, at(c), s))
    cols = pl.BlockSpec((1, 1, chunk, 4 * hb),
                        lambda i, s, c: (i, s, at(c), 0))
    rows = pl.BlockSpec((1, 1, hb, chunk), lambda i, s, c: (i, at(c), s, 0))
    tiles = pl.BlockSpec((1, 1, hb, chunk, chunk),
                         lambda i, s, c: (i, at(c), s, 0, 0))
    state = pl.BlockSpec((1, hb, K, V), lambda i, s, c: (i, s, 0, 0))
    starts = pl.BlockSpec((1, 1, hb, K, V),
                          lambda i, s, c: (i, at(c), s, 0, 0))
    S = jax.ShapeDtypeStruct
    keys_s, values_s = S((b, T, Hk * K), cd), S((b, T, H * V), cd)
    state_s = S((b, H, K, V), _F32)
    tiles_s = S((b, nc, H, chunk, chunk), cd)
    if kind == FWD_NAME:
        kernel = functools.partial(_fwd_kernel, K=K, V=V, rep=rep,
                                   keep_solve=keep_solve)
        in_specs = [keys, keys, values, cols, rows, state]
        out_specs, out_shape = [values, state], [values_s, state_s]
        if keep_solve:
            out_specs, out_shape = out_specs + [tiles], out_shape + [tiles_s]
    elif kind == STATES_NAME:
        kernel = functools.partial(_states_kernel, K=K, V=V, rep=rep)
        in_specs = [keys, values, cols, rows, tiles, state]
        out_specs, out_shape = [starts], [S((b, nc, H, K, V), _F32)]
    else:
        kernel = functools.partial(_bwd_kernel, K=K, V=V, rep=rep)
        in_specs = [keys, keys, values, cols, rows, rows, tiles, starts,
                    values, state]
        out_specs = [keys, keys, values, cols, rows, state]
        out_shape = [keys_s, keys_s, values_s,
                     S((b, steps, T, 4 * hb), _F32),
                     S((b, nc, H, chunk), _F32), state_s]
    call = pl.pallas_call(
        kernel, name=kind, grid=(b, steps, nc), in_specs=in_specs,
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((hb, K, V), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret)

    def named(*operands):
        with jax.named_scope(GDN_NAME):
            return call(*operands)

    return named


# one jaxpr and one Mosaic lowering for the repeats of a call in a trace
# (``ops/flash._shared``, PERF.md section 6, PR 33)
_shared = functools.lru_cache(maxsize=None)(jax.jit)


@functools.lru_cache(maxsize=None)
def _make_rule(shape: tuple, chunk: int, dtype_name: str, interpret: bool):
    """The differentiable call over the kernels' own operands: ``(q, k [b,
    T, Hk K], v [b, T, H V], cols [b, H / 8, T, 32], gamma rows, beta rows
    [b, T / chunk, H, chunk] (``_columns``), state [b, H, K, V]) -> (o, last
    state)``."""

    def differentiable(built):
        def build(kind, keep_solve=False):
            return built(_build(kind, shape, chunk, dtype_name, keep_solve,
                                interpret))

        @jax.custom_vjp
        def rule(q, k, v, cols, grows, brows, state):
            return tuple(build(FWD_NAME)(q, k, v, cols, grows, state))

        def fwd(q, k, v, cols, grows, brows, state):
            o, last, solve = build(FWD_NAME, True)(q, k, v, cols, grows,
                                                   state)
            return (o, last), (q, k, v, cols, grows, brows, state,
                               checkpoint_name(solve, SOLVE_NAME))

        def bwd(kept, cotangents):
            q, k, v, cols, grows, brows, state, solve = kept
            do, dlast = cotangents
            starts, = build(STATES_NAME)(k, v, cols, grows, solve, state)
            dq, dk, dv, dcols, dgrows, dstate = build(BWD_NAME)(
                q, k, v, cols, grows, brows, solve, starts, do, dlast)
            with jax.named_scope(GDN_NAME):
                # beta's rows are read, and answered for in its columns
                return (dq, dk, dv, dcols, dgrows, jnp.zeros_like(brows),
                        dstate)

        rule.defvjp(fwd, bwd)
        return rule

    bare, shared = differentiable(lambda call: call), differentiable(_shared)
    last_trace = [None]

    def rule(*operands):
        trace = jax.core.get_opaque_trace_state()
        repeat, last_trace[0] = trace == last_trace[0], trace
        return (shared if repeat else bare)(*operands)

    return rule


def gdn_pallas(q, k, v, g, beta, chunk: int = 64, state=None,
               interpret: bool = False):
    """:func:`relayrl_tpu.ops.gdn.gdn` through the kernels, for shapes that
    :func:`fits` takes. Compiled by Mosaic: a TPU backend only;
    ``interpret=True`` runs the bodies in the Pallas interpreter — a
    test-only switch that is never defaulted on."""
    b, T, H, V = v.shape
    Hk, K = k.shape[2:]
    if not fits(H, Hk, K, V, chunk):
        raise ValueError(f"the delta rule's kernels do not tile heads "
                         f"{Hk} x {K} under {H} x {V}, chunk {chunk}")
    with jax.named_scope(GDN_NAME):
        pad = -T % chunk
        if pad:
            q, k, v, g, beta = (
                jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                for a in (q, k, v, g, beta))
        Tp = T + pad
        if state is None:
            state = jnp.zeros((b, H, K, V), _F32)
        cols, grows, brows = _columns(g.astype(_F32), beta.astype(_F32),
                                      chunk)
        rule = _make_rule((b, Tp, H, Hk, K, V), chunk, v.dtype.name,
                          bool(interpret))
        o, last = rule(q.reshape(b, Tp, Hk * K), k.reshape(b, Tp, Hk * K),
                       v.reshape(b, Tp, H * V), cols, grows, brows,
                       state.astype(_F32))
        return o.reshape(b, Tp, H, V)[:, :T], last


def _columns(g, beta, chunk: int):
    """The per-token scalars the kernels read, from ``g, beta [b, T, H]``
    (float32, ``T`` whole chunks): ``gamma``, the running sum of ``g`` inside
    each chunk; ``beta``; ``exp(gamma)``, the decay from the chunk's start;
    ``exp(gamma_last - gamma)``, the decay to its end — as columns, ``[b, H /
    8, T, 4 * 8]``, the eight heads of a grid step side by side — and
    ``gamma`` and ``beta`` again as rows ``[b, T / chunk, H, chunk]`` for the
    tiles' other axis. Plain XLA on 4 MB arrays; autodiff of these lines
    turns the kernels' ``d cols`` and ``d gamma rows`` into the gradients of
    ``g`` and ``beta``."""
    b, T, H = g.shape
    hb = HEADS_A_STEP
    beta = beta.reshape(b, T // chunk, chunk, H)
    gamma = jnp.cumsum(g.reshape(beta.shape), axis=2)
    parts = (gamma, beta, jnp.exp(gamma), jnp.exp(gamma[:, :, -1:] - gamma))
    cols = jnp.stack([a.reshape(b, T, H // hb, hb) for a in parts],
                     axis=3)                               # [b, T, S, 4, 8]
    return (cols.transpose(0, 2, 1, 3, 4).reshape(b, H // hb, T, 4 * hb),
            gamma.transpose(0, 1, 3, 2), beta.transpose(0, 1, 3, 2))
