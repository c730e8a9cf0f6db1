"""The chunked Mamba-2 scan of :mod:`relayrl_tpu.ops.ssd` as Pallas TPU
kernels: a chunk's score tiles and the carried state stay in VMEM, forward
and backward. ``ops/ssd.py`` has the recurrence, the five lines of algebra
and the rule that picks these kernels; this module is imported only where
that rule says so (importing ``jax.experimental.pallas`` costs about a
second that no other model should pay).

**Operands as the projections left them.** ``x`` / ``y`` / their cotangents
are ``[b, T, H * P]``, the heads side by side in the lanes (a free reshape;
a ``[..., H, 64]`` block is half padding in the DMA, PERF.md section 6,
PR 33), ``B`` / ``C`` ``[b, T, G * N]``; no head transpose exists round the
calls. The per-token scalars — ``cs``, the running sum of ``dt A`` inside
each chunk, the step sizes, ``exp(cs)`` and ``exp(cs_last - cs)`` — are
made outside by plain XLA on 4 MB arrays (``_columns``) and come as
columns, the eight heads of a grid step side by side; ``cs`` comes a second
time as lane-dense rows ``[b, H, T]`` for the score tiles' other axis. The
kernels give back ``d cols`` and ``d cs rows``, and autodiff of
``_columns`` turns them into the gradients of ``dt`` and ``A``: no running
sum and no transpose of a row is made in VMEM (a ``[128, chunk]`` transpose
a step measured 0.2-0.3 us of a 1.3 us step, PERF.md section 6, PR 40).

**Grid** ``(b, H / 8, T / chunk)``: eight heads a step (``HEADS_A_STEP``;
a group's eight heads at the benchmark's shape, so ``C B^T`` is made once
a step), the chunk axis last and sequential — TPU grids run in order, so a
float32 state in VMEM scratch, set from ``state`` at the first chunk, is
the recurrence. The state is kept TURNED, ``[N, 8 P]``: its update ``B^T
(dt x to_end)`` and its part of the output ``C S^T`` are then plain
matmuls, and ``B`` is turned once a step (Mosaic turns the left operand of
an ``a^T @ b`` product every time it meets one). Heads narrower than a lane
tile share a 128-lane block the way the flash kernels' do at head_dim 64
(``ops/flash.py``): head ``h``'s product is taken over the whole block and
its own lanes are kept (``_by_head``), its contractions see the other
heads' lanes as exact zeros.

* ``ssd_fwd``: the masked decay and ``C B^T`` tiles in VMEM, the
  inside-chunk product a head, the carried state's part and the state's
  update a lane block; writes ``y`` and, at the last chunk, ``last_state``.
  Nothing chunk-by-chunk-shaped goes to HBM.
* ``ssd_states``: the state's update alone, writing the float32 state each
  chunk STARTS from (``[b, T / chunk, N, H * P]``: 0.27 GB a layer at the
  benchmark's shape, alive inside that layer's backward only).
* ``ssd_bwd``: the reverse sweep, the chunk axis walked from the last to
  the first, carrying the state's cotangent in VMEM; makes the score tiles
  again — turned, ``[j, i]``, so that ``scores^T dy`` is a plain matmul —
  and writes the gradients of ``x``, the columns, ``cs`` as rows, ``B``,
  ``C``, ``D`` (per lane, summed over a sequence in the resident output
  block) and the initial ``state``.

**The backward makes the chunk-start states again** (``ssd_states``) and
does not keep them from the forward: the residuals are the arguments alone,
so under the mixer's ``jax.checkpoint`` (which keeps the scan's output by
name) the backward runs ``ssd_states`` + ``ssd_bwd`` and never the forward a
second time, and a forward that nobody differentiates writes no state.

Precision as ``ops/ssd._group`` has it: columns, sums, decays and the state
float32; every matmul's operands in ``x``'s dtype with float32
accumulation, rounded where ``_group`` rounds them (``dt x``, ``dt x`` times
the decay to the chunk's end, the scores times decay, the state for the
``carried`` product — in float32 the forward is ``_group``'s bit for bit);
in the backward the cotangents that enter a matmul are rounded the same
way (the flash kernels' ``ds``). Exponentials of non-positive sums only,
masked BEFORE the exponential.

Names (``ops/scopes.py``): every call sits under ``relayrl_ssd`` with no
deeper ``relayrl_`` name — the kernels are ``ssd_fwd`` / ``ssd_states`` /
``ssd_bwd`` — so the benchmark's ``ssd_ms`` (device time under the exact
scope) holds them; the ``custom_vjp``'s rules open the scope themselves.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from relayrl_tpu.ops.scopes import SSD_NAME

FWD_NAME, STATES_NAME, BWD_NAME = "ssd_fwd", "ssd_states", "ssd_bwd"
HEADS_A_STEP = 8

_NT = (((1,), (1,)), ((), ()))   # a @ b^T
_NN = (((1,), (0,)), ((), ()))   # a @ b

_F32 = jnp.float32


def _mm(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _masks(P: int, W: int, axis: int):
    """One bool mask a head of a ``W``-wide block of heads of ``P``, along
    the lanes (``[1, W]``) or the sublanes (``[W, 1]``); ``[None]`` where
    the block is one head."""
    if W == P:
        return [None]
    at = jax.lax.broadcasted_iota(jnp.int32, (1, W) if axis else (W, 1), axis)
    return [(at >= h * P) & (at < (h + 1) * P) for h in range(W // P)]


def _by_head(parts, masks, shape):
    """A block from its heads' parts: head ``h``'s lanes (rows) of
    ``parts[h]``, each as large as the block or a column (row) of it."""
    out = jnp.broadcast_to(parts[-1], shape)
    for part, mask in zip(parts[-2::-1], masks[-2::-1]):
        out = jnp.where(mask, part, out)
    return out


def _own(x, mask):
    """``x`` with every lane (row) but one head's zeroed."""
    return x if mask is None else jnp.where(mask, x, jnp.zeros_like(x))


def _total(x):
    """The sum of a 2-D block as ``[1, 1]``."""
    return jnp.sum(jnp.sum(x, axis=1, keepdims=True), axis=0, keepdims=True)


# A step's per-token scalars as columns, ``cols [chunk, 4 * 8]``: eight
# heads' cs | dt | exp(cs) | exp(cs_last - cs), made outside (``_columns``)
CS, DT, IN, TO_END = (k * HEADS_A_STEP for k in range(4))


class _Chunk:
    """What the kernels read of a step's per-token scalars: a head's
    column, and a lane block's heads' columns spread over their lanes."""

    def __init__(self, cols_ref, P: int, W: int):
        self.cols = cols_ref[0, 0]                         # [chunk, 32]
        self.chunk = self.cols.shape[0]
        self.W, self.hpl = W, W // P
        self.lanes = _masks(P, W, 1)

    def heads(self, lb: int):
        return range(lb * self.hpl, (lb + 1) * self.hpl)

    def col(self, what: int, r: int):                      # [chunk, 1]
        return self.cols[:, what + r:what + r + 1]

    def spread(self, what: int, lb: int):                  # [chunk, W]
        return _by_head([self.col(what, r) for r in self.heads(lb)],
                        self.lanes, (self.chunk, self.W))

    def through(self, lb: int):                            # [1, W]
        """``exp(cs_last)`` of each head over its lanes of the block's
        ``[N, W]`` state."""
        return jnp.exp(_by_head(
            [self.col(CS, r)[self.chunk - 1:] for r in self.heads(lb)],
            self.lanes, (1, self.W)))

    def decay(self, r: int, cs_rows, causal, turned: bool = False):
        """``exp(cs_i - cs_j)`` on and under the diagonal, 0 above it, as
        ``[i, j]`` or turned, ``[j, i]``."""
        rows, col = cs_rows[r:r + 1, :], self.col(CS, r)
        return jnp.exp(jnp.where(
            causal, rows - col if turned else col - rows, -jnp.inf))


def _causal(chunk: int, turned: bool = False):
    i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    return i <= j if turned else i >= j


def _scan_kernel(*refs, P: int, W: int, with_y: bool):
    """``ssd_fwd`` (``with_y``) and ``ssd_states``. The state is kept
    turned, ``[N, 8 P]``: its update ``B^T (dt x to_end)`` and its part of
    the output ``C S^T`` are then plain matmuls, and ``B`` is turned once a
    step."""
    if with_y:
        (x_ref, cols_ref, cs_ref, b_ref, c_ref, d_ref, s0_ref,
         y_ref, last_ref, state_ref) = refs
    else:
        x_ref, cols_ref, b_ref, s0_ref, start_ref, state_ref = refs
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _start():
        state_ref[...] = s0_ref[0]

    ch = _Chunk(cols_ref, P, W)
    cd = x_ref.dtype
    b = b_ref[0]
    b_t = b.astype(_F32).T.astype(cd)                      # [N, chunk]
    if with_y:
        c = c_ref[0]
        cs_rows = cs_ref[0]
        cb = _mm(c, b, _NT)
        causal = _causal(ch.chunk)
    for lb in range(x_ref.shape[2] // W):
        at = slice(lb * W, (lb + 1) * W)
        x = x_ref[0, :, at].astype(_F32)
        xdt = x * ch.spread(DT, lb)                        # dt_j x_j
        start = state_ref[:, at]                           # [N, W]
        if with_y:
            u = xdt.astype(cd)
            inside = [_mm((cb * ch.decay(r, cs_rows, causal)).astype(cd), u,
                          _NN) for r in ch.heads(lb)]
            carried = _mm(c, start.astype(cd), _NN)
            y = (_by_head(inside, ch.lanes, x.shape)
                 + carried * ch.spread(IN, lb) + d_ref[:, at] * x)
            y_ref[0, :, at] = y.astype(cd)
        else:
            start_ref[0, 0, :, at] = start
        own = _mm(b_t, (xdt * ch.spread(TO_END, lb)).astype(cd), _NN)
        state_ref[:, at] = ch.through(lb) * start + own

    if with_y:
        @pl.when(k == pl.num_programs(2) - 1)
        def _end():
            last_ref[0] = state_ref[...]


def _bwd_kernel(x_ref, cols_ref, cs_ref, b_ref, c_ref, d_ref, dy_ref,
                start_ref, dlast_ref,
                dx_ref, dcols_ref, dcs_ref, db_ref, dc_ref, dd_ref, ds0_ref,
                dstate_ref, *, P: int, W: int):
    """One chunk of the reverse sweep (grid step ``k`` is chunk ``T / chunk
    - 1 - k``): ``dstate_ref`` holds the cotangent of the (turned) state
    the chunk ENDS in. The score tiles are made turned, ``[j, i]``, so that
    ``scores^T dy`` is a plain matmul; ``C`` and the summed ``d (C B^T)``
    are turned once a step."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _start():
        dstate_ref[...] = dlast_ref[0]
        dd_ref[...] = jnp.zeros_like(dd_ref)

    ch = _Chunk(cols_ref, P, W)
    cd = x_ref.dtype
    b, c = b_ref[0], c_ref[0]
    c_t = c.astype(_F32).T.astype(cd)                      # [N, chunk]
    cs_rows = cs_ref[0]
    bc = _mm(b, c, _NT)                                    # [j, i]
    causal = _causal(ch.chunk, turned=True)

    dbc = jnp.zeros_like(bc)
    db = jnp.zeros(b.shape, _F32)
    dc = jnp.zeros(c.shape, _F32)
    # what the step says of each column of ``cols``, and of cs as rows
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, ch.cols.shape[1]), 1)
    dcols = jnp.zeros(ch.cols.shape, _F32)
    dlast = jnp.zeros((1, ch.cols.shape[1]), _F32)          # of cs_last
    row = jax.lax.broadcasted_iota(jnp.int32, (HEADS_A_STEP, 1), 0)
    dcs_rows = jnp.zeros(cs_rows.shape, _F32)

    def per_head(block, h):
        """A head's lanes of a ``[chunk, W]`` block, summed: ``[chunk, 1]``."""
        return jnp.sum(_own(block, ch.lanes[h]), axis=1, keepdims=True)

    for lb in range(x_ref.shape[2] // W):
        at = slice(lb * W, (lb + 1) * W)
        x = x_ref[0, :, at].astype(_F32)
        dy_lo = dy_ref[0, :, at]
        dy = dy_lo.astype(_F32)
        dt, decay_in = ch.spread(DT, lb), ch.spread(IN, lb)
        to_end = ch.spread(TO_END, lb)
        xdt = x * dt
        u = xdt.astype(cd)
        start = start_ref[0, 0, :, at]                     # [N, W] float32
        start_lo = start.astype(cd)
        dstate = dstate_ref[:, at]
        dstate_lo = dstate.astype(cd)
        through = ch.through(lb)

        # y = ... + D x
        dd_ref[0, :, at] += jnp.sum(dy * x, axis=0, keepdims=True)
        # the carried state's part, y += exp(cs) (C S^T)
        carried = _mm(c, start_lo, _NN)                    # [chunk, W]
        dcarried = (dy * decay_in).astype(cd)
        dc += _mm(dcarried, start_lo, _NT)
        dstate_ref[:, at] = through * dstate + _mm(c_t, dcarried, _NN)
        # the state's update, S' = exp(cs_last) S + (dt x to_end)^T B
        dw = _mm(b, dstate_lo, _NN)                        # [chunk, W]
        db += _mm((xdt * to_end).astype(cd), dstate_lo, _NT)
        dxdt = dw * to_end
        of_in, of_end = dy * carried, dw * xdt
        of_last = through * dstate * start                 # [N, W]

        # inside the chunk, y_i += sum_j (C_i . B_j) decay_ij dt_j x_j
        inside = []
        for h, r in enumerate(ch.heads(lb)):
            decay = ch.decay(r, cs_rows, causal, turned=True)
            scores = bc * decay                            # [j, i]
            dscores = _mm(u, _own(dy_lo, ch.lanes[h]), _NT)
            inside.append(_mm(scores.astype(cd), dy_lo, _NN))
            dbc += dscores * decay
            dseg = dscores * scores                        # d (cs_i - cs_j)
            for what, column in (
                    (CS, -jnp.sum(dseg, axis=1, keepdims=True)),
                    (IN, per_head(of_in, h)), (TO_END, per_head(of_end, h))):
                dcols = jnp.where(lane == what + r, column, dcols)
            dlast = jnp.where(lane == CS + r,
                              _total(_own(of_last, ch.lanes[h])), dlast)
            dcs_rows = jnp.where(
                row == r, jnp.sum(dseg, axis=0, keepdims=True), dcs_rows)
        dxdt += _by_head(inside, ch.lanes, x.shape)
        for h, r in enumerate(ch.heads(lb)):
            dcols = jnp.where(lane == DT + r, per_head(dxdt * x, h), dcols)
        dx_ref[0, :, at] = (dxdt * dt + d_ref[:, at] * dy).astype(cd)

    db_ref[0] = (db + _mm(dbc.astype(cd), c, _NN)).astype(db_ref.dtype)
    dc_ref[0] = (dc + _mm(dbc.T.astype(cd), b, _NN)).astype(dc_ref.dtype)
    dcs_ref[0] = dcs_rows
    dcols_ref[0, 0] = dcols
    dcols_ref[0, 0, ch.chunk - 1:, :] += dlast

    @pl.when(k == pl.num_programs(2) - 1)
    def _end():
        ds0_ref[0] = dstate_ref[...]


def fits(H: int, P: int, G: int, N: int, chunk: int) -> bool:
    """Whether the kernels take a scan of these shapes (``T`` is padded to
    whole chunks first, as the plain form pads it): eight heads a step
    inside one group (the sublanes of the rows' float32 tile), whole heads
    a 128-lane block, and a chunk and a state that tile the lanes."""
    per_group = H // G
    return (per_group % HEADS_A_STEP == 0
            and (128 % P == 0 and P >= 8 or P % 128 == 0)
            and (HEADS_A_STEP * P) % 128 == 0
            and N % 128 == 0 and chunk in (128, 256)
            and HEADS_A_STEP * P * N * 4 <= 4 << 20)


@functools.lru_cache(maxsize=None)
def _build(kind: str, shape: tuple, chunk: int, dtype_name: str,
           interpret: bool):
    """The ``pallas_call`` of one kernel over ``[b, T, H * P]`` operands
    (``shape = (b, T, H, P, G, N)``, ``T`` whole chunks)."""
    b, T, H, P, G, N = shape
    # a lane block of whole heads: 128 lanes, or a wider head's own width
    hb, W = HEADS_A_STEP, max(P, 128)
    steps, nc, per_group = H // hb, T // chunk, H // G // hb
    cd = jnp.dtype(dtype_name)
    back = kind == BWD_NAME

    def at(k):                       # the chunk of grid step k
        return nc - 1 - k if back else k

    wide = pl.BlockSpec((1, chunk, hb * P), lambda i, s, k: (i, at(k), s))
    rows = pl.BlockSpec((1, hb, chunk), lambda i, s, k: (i, s, at(k)))
    cols = pl.BlockSpec((1, 1, chunk, 4 * hb),
                        lambda i, s, k: (i, s, at(k), 0))
    group = pl.BlockSpec((1, chunk, N),
                         lambda i, s, k: (i, at(k), s // per_group))
    per_lane = pl.BlockSpec((1, hb * P), lambda i, s, k: (0, s))
    state = pl.BlockSpec((1, N, hb * P), lambda i, s, k: (i, 0, s))
    starts = pl.BlockSpec((1, 1, N, hb * P),
                          lambda i, s, k: (i, at(k), 0, s))
    S = jax.ShapeDtypeStruct
    x_s, rows_s = S((b, T, H * P), cd), S((b, H, T), _F32)
    cols_s = S((b, steps, T, 4 * hb), _F32)
    state_s = S((b, N, H * P), _F32)
    if kind == FWD_NAME:
        kernel = functools.partial(_scan_kernel, P=P, W=W, with_y=True)
        in_specs = [wide, cols, rows, group, group, per_lane, state]
        out_specs, out_shape = [wide, state], [x_s, state_s]
    elif kind == STATES_NAME:
        kernel = functools.partial(_scan_kernel, P=P, W=W, with_y=False)
        in_specs = [wide, cols, group, state]
        out_specs, out_shape = [starts], [S((b, nc, N, H * P), _F32)]
    else:
        kernel = functools.partial(_bwd_kernel, P=P, W=W)
        in_specs = [wide, cols, rows, group, group, per_lane, wide, starts,
                    state]
        # dB and dC a step of eight heads: a group of more is summed outside
        part = pl.BlockSpec((1, chunk, N), lambda i, s, k: (i, at(k), s))
        part_s = S((b, T, steps * N), cd if per_group == 1 else _F32)
        out_specs = [wide, cols, rows, part, part,
                     pl.BlockSpec((1, 1, hb * P), lambda i, s, k: (i, 0, s)),
                     state]
        out_shape = [x_s, cols_s, rows_s, part_s, part_s,
                     S((b, 1, H * P), _F32), state_s]
    call = pl.pallas_call(
        kernel, name=kind, grid=(b, steps, nc), in_specs=in_specs,
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((N, hb * P), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret)

    def named(*operands):
        with jax.named_scope(SSD_NAME):
            return call(*operands)

    return named


# one jaxpr and one Mosaic lowering for the repeats of a call in a trace
# (``ops/flash._shared``, PERF.md section 6, PR 33)
_shared = functools.lru_cache(maxsize=None)(jax.jit)


@functools.lru_cache(maxsize=None)
def _make_scan(shape: tuple, chunk: int, dtype_name: str, interpret: bool):
    """The differentiable call over the kernels' own operands: ``(x [b, T,
    H P], cols [b, H / 8, T, 32] (``_columns``), cs rows [b, H, T], B, C
    [b, T, G N], D a lane [1, H P], the state turned [b, N, H P]) -> (y,
    last state turned)``."""
    b, T, H, P, G, N = shape
    per_group = H // G // HEADS_A_STEP

    def differentiable(built):
        def build(kind):
            return built(_build(kind, shape, chunk, dtype_name, interpret))

        @jax.custom_vjp
        def scan(x, cols, cs, B, C, D, state):
            return tuple(build(FWD_NAME)(x, cols, cs, B, C, D, state))

        def fwd(*operands):
            return scan(*operands), operands

        def bwd(operands, cotangents):
            x, cols, cs, B, C, D, state = operands
            dy, dlast = cotangents
            starts, = build(STATES_NAME)(x, cols, B, state)
            dx, dcols, dcs, dB, dC, dD, dstate = build(BWD_NAME)(
                x, cols, cs, B, C, D, dy, starts, dlast)
            with jax.named_scope(SSD_NAME):
                if per_group > 1:
                    dB, dC = (a.reshape(b, T, G, per_group, N).sum(3)
                              .reshape(b, T, G * N).astype(B.dtype)
                              for a in (dB, dC))
                return dx, dcols, dcs, dB, dC, dD.sum(0), dstate

        scan.defvjp(fwd, bwd)
        return scan

    bare, shared = differentiable(lambda call: call), differentiable(_shared)
    last_trace = [None]

    def scan(*operands):
        trace = jax.core.get_opaque_trace_state()
        repeat, last_trace[0] = trace == last_trace[0], trace
        return (shared if repeat else bare)(*operands)

    return scan


def ssd_pallas(x, dt, A, B, C, D, chunk: int = 128, state=None,
               interpret: bool = False):
    """:func:`relayrl_tpu.ops.ssd.ssd` through the kernels, for shapes that
    :func:`fits` takes. Compiled by Mosaic: a TPU backend only;
    ``interpret=True`` runs the bodies in the Pallas interpreter — a
    test-only switch that is never defaulted on."""
    b, T, H, P = x.shape
    G, N = B.shape[2:]
    if not fits(H, P, G, N, chunk):
        raise ValueError(f"the scan kernels do not tile heads {H} x {P}, "
                         f"groups {G}, state {N}, chunk {chunk}")
    with jax.named_scope(SSD_NAME):
        pad = -T % chunk
        if pad:
            x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (
                a.ndim - 2)) for a in (x, dt, B, C))
        Tp = T + pad
        if state is None:
            state = jnp.zeros((b, H, P, N), _F32)
        cols, cs_rows = _columns(dt.astype(_F32), A.astype(_F32), chunk)
        scan = _make_scan((b, Tp, H, P, G, N), chunk, x.dtype.name,
                          bool(interpret))
        y, last = scan(
            x.reshape(b, Tp, H * P), cols, cs_rows,
            B.reshape(b, Tp, G * N), C.reshape(b, Tp, G * N),
            jnp.repeat(D.astype(_F32), P)[None],
            state.astype(_F32).reshape(b, H * P, N).swapaxes(1, 2))
        return (y.reshape(b, Tp, H, P)[:, :T],
                last.swapaxes(1, 2).reshape(b, H, P, N))


def _columns(dt, A, chunk: int):
    """The per-token scalars the kernels read, from ``dt [b, T, H]`` and
    ``A [H]`` (float32, ``T`` whole chunks): ``cs``, the running sum of ``dt
    A`` inside each chunk; the step sizes; ``exp(cs)``, the decay from the
    chunk's start; ``exp(cs_last - cs)``, the decay to its end — as columns,
    ``[b, H / 8, T, 4 * 8]``, the eight heads of a grid step side by side
    (a DMA row is 128 bytes: as ``[b, T, H]`` a step's block would be 8 of
    64 lanes and no block at all) — and ``cs`` again as lane-dense rows ``[b,
    H, T]`` for the score tiles' other axis. Plain XLA on 4 MB arrays;
    autodiff of these lines turns the kernels' ``d cols`` and ``d cs rows``
    into the gradients of ``dt`` and ``A``."""
    b, T, H = dt.shape
    cs = jnp.cumsum((dt * A).reshape(b, T // chunk, chunk, H), axis=2)
    parts = (cs, dt.reshape(cs.shape), jnp.exp(cs),
             jnp.exp(cs[:, :, -1:] - cs))
    cols = jnp.stack([a.reshape(b, T, H // HEADS_A_STEP, HEADS_A_STEP)
                      for a in parts], axis=3)             # [b, T, S, 4, 8]
    return (cols.transpose(0, 2, 1, 3, 4).reshape(
        b, H // HEADS_A_STEP, T, 4 * HEADS_A_STEP),
        cs.reshape(b, T, H).transpose(0, 2, 1))
