"""The chunked delta rule of :mod:`relayrl_tpu.ops.kda` — a decay a key
LANE — as Pallas TPU kernels: a chunk's tiles, its solve and the carried ``[K,
V]`` state stay in VMEM, forward and backward. ``ops/kda.py`` has the rule,
its algebra and the rule that picks these kernels; this module is imported
only where that rule says so. It is :mod:`relayrl_tpu.ops.gdn_pallas`'s three
kernels with per-lane factors, and borrows what carries over by import.

**Operands as the projections left them.** ``q`` / ``k`` / ``v`` / ``o`` /
``g`` and their cotangents are ``[b, T, H * 128]``, the heads side by side in
the lanes (a free reshape; ``K = V = 128`` is one lane tile a head): no head
transpose exists round the calls. ``g`` stays float32 and is read once a
pass; its running sum inside a chunk, ``Gamma``, and the reverse sum that
turns ``d Gamma`` into ``dg`` are ``[chunk, chunk]`` triangular products in
float32 "highest" INSIDE the kernels — no ``[T, H, 128]`` float32 array is
written or read by XLA round a call. ``beta`` (and its cotangent) comes as
columns ``[b, H / 4, T, 4]``, a 2 MB transpose by plain XLA. The state is
``[b, H, K, V]`` float32 as the caller holds it.

**Grid** ``(b, H / 4, T / chunk)``: four heads a step (``HEADS_A_STEP``), the
chunk axis last and sequential, the heads' float32 states in VMEM scratch.

**The pair weights** ``KK_ij = sum_c k_i[c] k_j[c] e^{Gamma_i[c] -
Gamma_j[c]}`` (``QK`` likewise) are made as ``ops/kda._pair_weights`` makes
them, every exponent <= 0 and masked BEFORE the exponential: a pair in
different sub-chunks of 16 rows split at the later one's first row ``r``
(one matmul a sub-chunk, its rows of ``k`` and ``q`` together, against the
chunk's keys re-weighted to ``r``); a pair inside one sub-chunk summed lane
by lane — a DIAGONAL of the tile at a time: the pairs ``(i, i - d)`` of the
whole chunk are one sublane roll by ``d``, one exponential and one lane sum
of a ``[chunk, 128]`` block, 15 diagonals a tile and no 3-D block.

* ``kda_fwd``: ``Gamma``, ``KK`` / ``QK``, ``A``, the solve ``T = (I -
  A)^-1`` (``gdn_pallas._inverse_unit_lower``: float32 "highest", two tiles
  a pass), ``W``, ``U``, ``v' = U - W S``, ``o`` and the state's update;
  writes ``o`` and, at the last chunk, ``last_state``. A forward that is
  being differentiated also writes the solve's tiles in the compute dtype
  (``[b, T / chunk, H, chunk, chunk]``, named ``relayrl_kda_solve`` for a
  caller's checkpoint policy): ``kda_states`` then makes no pair weight and
  no solve. A rule nobody differentiates writes ``o`` alone.
* ``kda_states``: ``v'`` and the state's update from the kept solve, writing
  the float32 state each chunk STARTS from (``[b, T / chunk, H, K, V]``,
  alive inside that layer's backward only).
* ``kda_bwd``: the reverse sweep, carrying the state's cotangent in VMEM;
  makes the pair weights again and writes the cotangents of ``q``, ``k``,
  ``v``, ``g`` (a lane's own: ``[b, T, H * 128]`` float32), ``beta`` and the
  initial ``state``. The solve's transpose is ``gdn_bwd``'s (``dA = (T^T dW)
  W^T + (T^T dU) U^T``). ``Gamma`` enters every factor as ``e^{+Gamma_i}``
  beside ``q_i`` or ``k_i``, or as ``e^{-Gamma_j}`` beside ``k_j``, so its
  cotangent needs no product of its own: ``d Gamma = q (.) dq + k (.) (dk+ -
  dk-)`` with ``dk+`` / ``dk-`` the parts of ``dk`` from the first and the
  second kind of factor, plus what reaches the chunk's last row through
  ``e^{Gamma_C}``.

Precision as ``ops/kda._heads`` has it, rounded where it rounds: ``g``,
``beta``, ``Gamma``, the decays, the lane-wise sums, the solve and the state
float32; every other matmul's operands in ``v``'s dtype with float32
accumulation; in the backward the cotangents that enter a matmul are rounded
the same way.

Names (``ops/scopes.py``): every call sits under ``relayrl_kda`` with no
deeper ``relayrl_`` name — the kernels are ``kda_fwd`` / ``kda_states`` /
``kda_bwd`` — so the benchmark's ``kda_ms`` / ``kda_roofline`` hold them; the
``custom_vjp``'s rules open the scope themselves.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from relayrl_tpu.ops.gdn_pallas import (
    _F32,
    _NN,
    _NT,
    _head,
    _inverse_unit_lower,
    _lanes,
    _mm,
)
from relayrl_tpu.ops.kda import _SUB, SOLVE_NAME
from relayrl_tpu.ops.scopes import KDA_NAME

FWD_NAME, STATES_NAME, BWD_NAME = "kda_fwd", "kda_states", "kda_bwd"
HEADS_A_STEP = 4


def _mm32(a, b, dims=_NN):
    """A float32 product of float32 operands (the running sums')."""
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32,
                               precision=jax.lax.Precision.HIGHEST)


def _turned(x):
    """``x^T`` through float32, the transpose the chip has."""
    return x.astype(_F32).T.astype(x.dtype)


def _rolled(x, by: int):
    """Row ``i`` of the result is row ``i - by`` of ``x`` (a sublane roll:
    the rows that wrap are masked by their callers)."""
    return pltpu.roll(x, by % x.shape[0], 0)


class _Masks:
    """A chunk's index masks, made once a grid step."""

    def __init__(self, chunk: int, width: int):
        self.chunk = chunk
        i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        self.behind = i - j                 # the diagonal a pair sits on
        self.lower, self.strict = i >= j, i > j
        self.sum_to = jnp.where(self.lower, 1.0, 0.0)    # Gamma = sum_to @ g
        self.sum_from = jnp.where(i <= j, 1.0, 0.0)   # dg = sum_from @ dGamma
        self.row = jax.lax.broadcasted_iota(jnp.int32, (chunk, width), 0)
        self.in_sub = jnp.bitwise_and(self.row, _SUB - 1)


class _Decays:
    """What a head's chunk of log decays ``g [chunk, K]`` gives its tiles:
    ``gamma``, the running sum; ``rel``, each row's decay from its
    sub-chunk's first row; ``to_first(I)``, the decay of the rows before
    sub-chunk ``I`` up to its first row (0 from there on); ``along(d)``,
    ``e^{Gamma_i - Gamma_{i-d}}`` for the pairs of one sub-chunk (0 for the
    others). Exponents <= 0, masked before the exponential."""

    def __init__(self, m: _Masks, g):
        self.m = m
        self.gamma = gamma = _mm32(m.sum_to, g)
        first = gamma[0:1]
        for at in range(_SUB, m.chunk, _SUB):
            first = jnp.where(m.row >= at, gamma[at:at + 1], first)
        self.rel = jnp.exp(gamma - first)
        self.last = gamma[m.chunk - 1:]                     # [1, K]

    def to_first(self, sub: int):
        at = sub * _SUB
        return jnp.exp(jnp.where(self.m.row < at,
                                 self.gamma[at:at + 1] - self.gamma,
                                 -jnp.inf))

    def along(self, d: int):
        return jnp.exp(jnp.where(self.m.in_sub >= d,
                                 self.gamma - _rolled(self.gamma, d),
                                 -jnp.inf))

    def in_decay(self):
        return jnp.exp(self.gamma)

    def to_end(self):
        return jnp.exp(self.last - self.gamma)

    def through(self):
        """``e^{Gamma_C}`` down the rows of the state, ``[K, 1]``."""
        return jnp.exp(self.gamma.T[:, self.m.chunk - 1:])


def _subs(chunk: int):
    return [slice(at, at + _SUB) for at in range(_SUB, chunk, _SUB)]


def _pair_weights(dec: _Decays, qf, kf, cd):
    """``(KK, QK)``, ``[chunk, chunk]`` float32, zero above the diagonal
    (``KK`` on it too: ``A`` is strictly lower)."""
    m = dec.m
    k_rel, q_rel = (kf * dec.rel).astype(cd), (qf * dec.rel).astype(cd)
    nothing = jnp.zeros((_SUB, m.chunk), _F32)
    kk_rows, qk_rows = [nothing], [nothing]
    for sub, rows in enumerate(_subs(m.chunk), 1):
        k_to = (kf * dec.to_first(sub)).astype(cd)
        both = _mm(jnp.concatenate([k_rel[rows], q_rel[rows]], axis=0),
                   k_to, _NT)                               # [2 sub, chunk]
        kk_rows.append(both[:_SUB])
        qk_rows.append(both[_SUB:])
    kk = jnp.zeros((m.chunk, m.chunk), _F32)
    qk = jnp.where(m.behind == 0, _lanes(qf * kf), 0.0)
    for d in range(1, _SUB):
        kw = _rolled(kf, d) * dec.along(d)
        kk = jnp.where(m.behind == d, _lanes(kf * kw), kk)
        qk = jnp.where(m.behind == d, _lanes(qf * kw), qk)
    return (kk + jnp.concatenate(kk_rows, axis=0),
            qk + jnp.concatenate(qk_rows, axis=0))


def _pair_weights_bwd(dec: _Decays, qf, kf, dkk, dqk, cd):
    """The cotangents of :func:`_pair_weights`' operands from its results'
    (masked as the results are): ``(dq, dk+, dk-)``, ``dk+`` where ``k`` is
    the pair's later row (beside ``e^{+Gamma}``), ``dk-`` where it is the
    earlier one."""
    m = dec.m
    k_rel, q_rel = (kf * dec.rel).astype(cd), (qf * dec.rel).astype(cd)
    nothing = jnp.zeros((_SUB, kf.shape[1]), _F32)
    dq_rows, dk_rows = [nothing], [nothing]
    on = _lanes(jnp.where(m.behind == 0, dqk, 0.0))
    dq, dk_minus = on * kf, on * qf
    for sub, rows in enumerate(_subs(m.chunk), 1):
        to_first = dec.to_first(sub)
        both = jnp.concatenate([dkk[rows], dqk[rows]], axis=0).astype(cd)
        ahead = _mm(both, (kf * to_first).astype(cd))       # [2 sub, K]
        dk_rows.append(ahead[:_SUB])
        dq_rows.append(ahead[_SUB:])
        dk_minus += to_first * _mm(
            _turned(both), jnp.concatenate([k_rel[rows], q_rel[rows]],
                                           axis=0))
    dq += jnp.concatenate(dq_rows, axis=0) * dec.rel
    dk_plus = jnp.concatenate(dk_rows, axis=0) * dec.rel
    for d in range(1, _SUB):
        along = dec.along(d)
        kw = _rolled(kf, d) * along
        a = _lanes(jnp.where(m.behind == d, dkk, 0.0))
        b = _lanes(jnp.where(m.behind == d, dqk, 0.0))
        dq += b * kw
        dk_plus += a * kw
        dk_minus += _rolled((a * kf + b * qf) * along, -d)
    return dq, dk_plus, dk_minus


def _corrected(solve, kf, vf, beta, in_decay, start, cd):
    """``(W, U, v')`` from the solve's tile and the state the chunk starts
    from: ``W`` and ``v'`` rounded for the products they enter, ``U``
    float32."""
    w = _mm(solve, (kf * beta * in_decay).astype(cd)).astype(cd)
    u = _mm(solve, (vf * beta).astype(cd))
    return w, u, (u - _mm(w, start.astype(cd))).astype(cd)


def _advanced(dec: _Decays, start, kf, v_new):
    """The state the chunk ends in: ``Diag(e^Gamma_C) S + (k e^(Gamma_C -
    Gamma))^T v'``."""
    return dec.through() * start + _mm(
        (kf * dec.to_end()).T.astype(v_new.dtype), v_new)


def _beta(beta_ref, r: int):
    return beta_ref[0, 0][:, r:r + 1]                       # [chunk, 1]


def _fwd_kernel(*refs, K: int, V: int, keep_solve: bool):
    """``kda_fwd``: a chunk of four heads; ``keep_solve`` writes the solve's
    tiles for a backward to read."""
    (q_ref, k_ref, v_ref, g_ref, beta_ref, s0_ref, o_ref,
     last_ref) = refs[:8]
    solve_ref = refs[8] if keep_solve else None
    state_ref = refs[-1]
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _start():
        state_ref[...] = s0_ref[0]

    cd = v_ref.dtype
    m = _Masks(v_ref.shape[1], K)
    heads = []
    for r in range(HEADS_A_STEP):
        qf, kf = (_head(ref, r, K).astype(_F32) for ref in (q_ref, k_ref))
        dec = _Decays(m, _head(g_ref, r, K))
        kk, qk = _pair_weights(dec, qf, kf, cd)
        heads.append((dec, qf, kf, qk,
                      jnp.where(m.strict, -(_beta(beta_ref, r) * kk), 0.0)))
    solves = _inverse_unit_lower([head[-1] for head in heads])
    for r, (dec, qf, kf, qk, _) in enumerate(heads):
        solve = solves[r].astype(cd)
        if keep_solve:
            solve_ref[0, 0, r] = solve
        start = state_ref[r]
        in_decay = dec.in_decay()
        _, _, v_new = _corrected(solve, kf, _head(v_ref, r, V).astype(_F32),
                                 _beta(beta_ref, r), in_decay, start, cd)
        o = (_mm((qf * in_decay).astype(cd), start.astype(cd))
             + _mm(qk.astype(cd), v_new))
        o_ref[0, :, r * V:(r + 1) * V] = o.astype(cd)
        state_ref[r] = _advanced(dec, start, kf, v_new)

    @pl.when(c == pl.num_programs(2) - 1)
    def _end():
        last_ref[0] = state_ref[...]


def _states_kernel(k_ref, v_ref, g_ref, beta_ref, solve_ref, s0_ref,
                   start_ref, state_ref, *, K: int, V: int):
    """``kda_states``: the state's update alone, from the kept solve."""
    @pl.when(pl.program_id(2) == 0)
    def _start():
        state_ref[...] = s0_ref[0]

    cd = v_ref.dtype
    m = _Masks(v_ref.shape[1], K)
    for r in range(HEADS_A_STEP):
        kf = _head(k_ref, r, K).astype(_F32)
        dec = _Decays(m, _head(g_ref, r, K))
        start = state_ref[r]
        start_ref[0, 0, r] = start
        _, _, v_new = _corrected(
            solve_ref[0, 0, r], kf, _head(v_ref, r, V).astype(_F32),
            _beta(beta_ref, r), dec.in_decay(), start, cd)
        state_ref[r] = _advanced(dec, start, kf, v_new)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, solve_ref, start_ref,
                do_ref, dlast_ref, dq_ref, dk_ref, dv_ref, dg_ref,
                dbeta_ref, ds0_ref, dstate_ref, *, K: int, V: int):
    """One chunk of the reverse sweep (grid step ``c`` is chunk ``T / chunk
    - 1 - c``): ``dstate_ref`` holds the cotangent of the state the chunk
    ENDS in. In the names of ``ops/kda.py``, with ``kb = k beta e^Gamma``,
    ``vb = beta v``, ``N = v'``, ``P`` the scores::

        dN = P^T do + (k to_end) dS'
        dS = Diag(through) dS' + (q e^Gamma)^T do - W^T dN
        dkb = T^T (-dN S^T)      dvb = T^T dN      dA = dkb W^T + dvb U^T
    """
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _start():
        dstate_ref[...] = dlast_ref[0]

    cd = v_ref.dtype
    m = _Masks(v_ref.shape[1], K)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, HEADS_A_STEP), 1)
    dbetas = jnp.zeros((m.chunk, HEADS_A_STEP), _F32)
    ones = jnp.ones((8, V), _F32)
    for r in range(HEADS_A_STEP):
        qf, kf, vf = (_head(ref, r, width).astype(_F32) for ref, width in (
            (q_ref, K), (k_ref, K), (v_ref, V)))
        do = _head(do_ref, r, V)
        beta = _beta(beta_ref, r)
        dec = _Decays(m, _head(g_ref, r, K))
        in_decay, to_end, through = (dec.in_decay(), dec.to_end(),
                                     dec.through())
        kk, qk = _pair_weights(dec, qf, kf, cd)
        solve = solve_ref[0, 0, r]
        solve_t = _turned(solve)
        start = start_ref[0, 0, r]
        start_lo = start.astype(cd)
        w, u, v_new = _corrected(solve, kf, vf, beta, in_decay, start, cd)
        dstate = dstate_ref[r]
        dstate_lo = dstate.astype(cd)

        dn = (_mm(_turned(qk.astype(cd)), do)
              + _mm((kf * to_end).astype(cd), dstate_lo)).astype(cd)
        dstate_ref[r] = (through * dstate
                         + _mm((qf * in_decay).T.astype(cd), do)
                         - _mm(_turned(w), dn))
        dq_in = _mm(do, start_lo, _NT)                     # d (q e^Gamma)
        dqk = jnp.where(m.lower, _mm(do, v_new, _NT), 0.0)
        dk_out = _mm(v_new, dstate_lo, _NT) * to_end       # of k, by to_end
        dw = -_mm(dn, start_lo, _NT)
        dkb = _mm(solve_t, dw.astype(cd))
        dvb = _mm(solve_t, dn)
        da = jnp.where(
            m.strict,
            _mm(dkb.astype(cd), w, _NT) + _mm(dvb.astype(cd), u.astype(cd),
                                              _NT), 0.0)
        dq, dk_plus, dk_minus = _pair_weights_bwd(dec, qf, kf, -(beta * da),
                                                  dqk, cd)
        dq += dq_in * in_decay
        dk_plus += dkb * (beta * in_decay)
        dk_minus += dk_out
        dbetas = jnp.where(
            lane == r, _lanes(dvb * vf) + _lanes(dkb * kf * in_decay)
            - _lanes(da * kk), dbetas)
        # Gamma_C: the rows' decay to the chunk's end and the state's
        # through it; it is every row's sum, so it reaches every row of dg
        to_last = (jnp.sum(kf * dk_out, axis=0, keepdims=True)
                   + _mm32(ones, through * dstate * start, _NT)[:1])
        dg_ref[0, :, r * K:(r + 1) * K] = _mm32(
            m.sum_from, qf * dq + kf * (dk_plus - dk_minus)) + to_last
        dq_ref[0, :, r * K:(r + 1) * K] = dq.astype(dq_ref.dtype)
        dk_ref[0, :, r * K:(r + 1) * K] = (dk_plus + dk_minus).astype(
            dk_ref.dtype)
        dv_ref[0, :, r * V:(r + 1) * V] = (dvb * beta).astype(dv_ref.dtype)
    dbeta_ref[0, 0] = dbetas

    @pl.when(c == pl.num_programs(2) - 1)
    def _end():
        ds0_ref[0] = dstate_ref[...]


def fits(H: int, K: int, V: int, chunk: int) -> bool:
    """Whether the kernels take a rule of these shapes (``T`` is padded to
    whole chunks first, as the plain form pads it): keys and values of one
    lane tile a head, four heads a step, chunks of 64 (four sub-chunks; two
    of the solve's tiles a pass)."""
    return (K == 128 and V == 128 and H % HEADS_A_STEP == 0
            and chunk == 64)


@functools.lru_cache(maxsize=None)
def _build(kind: str, shape: tuple, chunk: int, dtype_name: str,
           keep_solve: bool, interpret: bool):
    """The ``pallas_call`` of one kernel over ``[b, T, H * width]`` operands
    (``shape = (b, T, H, K, V)``, ``T`` whole chunks)."""
    b, T, H, K, V = shape
    hb = HEADS_A_STEP
    steps, nc = H // hb, T // chunk
    cd = jnp.dtype(dtype_name)
    back = kind == BWD_NAME

    def at(c):                       # the chunk of grid step c
        return nc - 1 - c if back else c

    keys = pl.BlockSpec((1, chunk, hb * K), lambda i, s, c: (i, at(c), s))
    values = pl.BlockSpec((1, chunk, hb * V), lambda i, s, c: (i, at(c), s))
    betas = pl.BlockSpec((1, 1, chunk, hb), lambda i, s, c: (i, s, at(c), 0))
    tiles = pl.BlockSpec((1, 1, hb, chunk, chunk),
                         lambda i, s, c: (i, at(c), s, 0, 0))
    state = pl.BlockSpec((1, hb, K, V), lambda i, s, c: (i, s, 0, 0))
    starts = pl.BlockSpec((1, 1, hb, K, V),
                          lambda i, s, c: (i, at(c), s, 0, 0))
    S = jax.ShapeDtypeStruct
    keys_s, values_s = S((b, T, H * K), cd), S((b, T, H * V), cd)
    decays_s, betas_s = S((b, T, H * K), _F32), S((b, steps, T, hb), _F32)
    state_s = S((b, H, K, V), _F32)
    tiles_s = S((b, nc, H, chunk, chunk), cd)
    if kind == FWD_NAME:
        kernel = functools.partial(_fwd_kernel, K=K, V=V,
                                   keep_solve=keep_solve)
        in_specs = [keys, keys, values, keys, betas, state]
        out_specs, out_shape = [values, state], [values_s, state_s]
        if keep_solve:
            out_specs, out_shape = out_specs + [tiles], out_shape + [tiles_s]
    elif kind == STATES_NAME:
        kernel = functools.partial(_states_kernel, K=K, V=V)
        in_specs = [keys, values, keys, betas, tiles, state]
        out_specs, out_shape = [starts], [S((b, nc, H, K, V), _F32)]
    else:
        kernel = functools.partial(_bwd_kernel, K=K, V=V)
        in_specs = [keys, keys, values, keys, betas, tiles, starts, values,
                    state]
        out_specs = [keys, keys, values, keys, betas, state]
        out_shape = [keys_s, keys_s, values_s, decays_s, betas_s, state_s]
    call = pl.pallas_call(
        kernel, name=kind, grid=(b, steps, nc), in_specs=in_specs,
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((hb, K, V), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret)

    def named(*operands):
        with jax.named_scope(KDA_NAME):
            return call(*operands)

    return named


# one jaxpr and one Mosaic lowering for the repeats of a call in a trace
# (``ops/flash._shared``, PERF.md section 6, PR 33)
_shared = functools.lru_cache(maxsize=None)(jax.jit)


@functools.lru_cache(maxsize=None)
def _make_rule(shape: tuple, chunk: int, dtype_name: str, interpret: bool):
    """The differentiable call over the kernels' own operands: ``(q, k, v [b,
    T, H 128] in the compute dtype, g the same float32, beta's columns [b, H
    / 4, T, 4], state [b, H, K, V]) -> (o, last state)``."""

    def differentiable(built):
        def build(kind, keep_solve=False):
            return built(_build(kind, shape, chunk, dtype_name, keep_solve,
                                interpret))

        @jax.custom_vjp
        def rule(q, k, v, g, beta, state):
            return tuple(build(FWD_NAME)(q, k, v, g, beta, state))

        def fwd(q, k, v, g, beta, state):
            o, last, solve = build(FWD_NAME, True)(q, k, v, g, beta, state)
            return (o, last), (q, k, v, g, beta, state,
                               checkpoint_name(solve, SOLVE_NAME))

        def bwd(kept, cotangents):
            q, k, v, g, beta, state, solve = kept
            do, dlast = cotangents
            starts, = build(STATES_NAME)(k, v, g, beta, solve, state)
            return tuple(build(BWD_NAME)(q, k, v, g, beta, solve, starts,
                                         do, dlast))

        rule.defvjp(fwd, bwd)
        return rule

    bare, shared = differentiable(lambda call: call), differentiable(_shared)
    last_trace = [None]

    def rule(*operands):
        trace = jax.core.get_opaque_trace_state()
        repeat, last_trace[0] = trace == last_trace[0], trace
        return (shared if repeat else bare)(*operands)

    return rule


def kda_pallas(q, k, v, g, beta, chunk: int = 64, state=None,
               interpret: bool = False):
    """:func:`relayrl_tpu.ops.kda.kda` through the kernels, for shapes that
    :func:`fits` takes. Compiled by Mosaic: a TPU backend only;
    ``interpret=True`` runs the bodies in the Pallas interpreter — a
    test-only switch that is never defaulted on."""
    b, T, H, V = v.shape
    K = k.shape[3]
    if not fits(H, K, V, chunk):
        raise ValueError(f"the delta rule's kernels do not tile heads {H} x "
                         f"{K} x {V}, chunk {chunk}")
    with jax.named_scope(KDA_NAME):
        pad = -T % chunk
        if pad:
            q, k, v, g, beta = (
                jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                for a in (q, k, v, g, beta))
        Tp, hb = T + pad, HEADS_A_STEP
        if state is None:
            state = jnp.zeros((b, H, K, V), _F32)
        # beta [b, T, H] as a step's columns, [b, H / 4, T, 4]: 2 MB
        beta = beta.astype(_F32).reshape(b, Tp, H // hb, hb).swapaxes(1, 2)
        rule = _make_rule((b, Tp, H, K, V), chunk, v.dtype.name,
                          bool(interpret))
        o, last = rule(q.reshape(b, Tp, H * K), k.reshape(b, Tp, H * K),
                       v.reshape(b, Tp, H * V),
                       g.astype(_F32).reshape(b, Tp, H * K), beta,
                       state.astype(_F32))
        return o.reshape(b, Tp, H, V)[:, :T], last
