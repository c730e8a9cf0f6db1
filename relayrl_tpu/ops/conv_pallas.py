"""The depthwise causal convolution of :mod:`relayrl_tpu.ops.conv` as two
Pallas TPU kernels: a direction reads each row of ``x [b, T, C]`` once and
writes each row once, and no shifted copy of the rows exists in HBM.
``ops/conv.py`` has the three lines of arithmetic and the rule that picks
these kernels; this module is imported only where that rule says so
(importing ``jax.experimental.pallas`` costs about a second that no other
model should pay).

**A grid step** holds a ``[rows, cols]`` tile of a sequence (``_tiles``)
and, through a second block over the same array, the 16 rows before it (a
bfloat16 row tile; zeros at a sequence's start), of which the last ``L - 1``
are the taps' reach. It walks the tile a strip of ``STRIP`` rows at a time —
a strip's float32 values stay in registers — and forms row ``t - s`` beside
row ``t`` by a sublane shift of the strip behind the 8 rows before it.

* ``conv_fwd``: the strips from the first down, the last 8 float32 rows of
  one the next one's reach; tap sum from ``j = 0`` up, bias, SiLU, one
  rounding — the plain form's arithmetic in its order.
* ``conv_bwd``: the row tiles from a sequence's last to its first and the
  strips of a tile likewise, since ``dx_t = sum_j w[j] dc_{t + L - 1 - j}``
  reaches FORWARD: the first 8 rows of ``dc`` of the strip after are carried
  (across tiles in VMEM scratch). ``c`` is made again from the rows (the
  forward keeps its input only, never the float32 pre-activation), ``dc =
  dout silu'(c)``, and ``dw[j] = sum_t dc_t x_{t - (L - 1) + j}`` and
  ``dbias = sum_t dc_t`` are summed in float32, eight partial sums a
  column (a strip's rows folded onto one sublane tile), in an output block
  that stays resident over a column tile's sequences and row tiles; the
  eight are added outside.

Names (``ops/scopes.py``): the calls are ``conv_fwd`` / ``conv_bwd`` under
the caller's scope (``relayrl_mamba_conv`` | ``relayrl_gdn_conv``), which
the ``custom_vjp``'s rules open themselves.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

FWD_NAME, BWD_NAME = "conv_fwd", "conv_bwd"
# the rows before a tile come as one bfloat16 row tile, and a strip is
# shifted behind one float32 sublane tile of them
HALO, REACH = 16, 8
# a strip: rows by lanes of float32 that the registers hold (PERF.md section
# 6, PR 44: 16 to 128 rows by 128 or 512 lanes measured up to 40 % slower)
STRIP, LANES = 64, 256

_F32 = jnp.float32


def _tiles(T: int, C: int) -> tuple[int, int] | None:
    """``(rows, cols)`` of a grid step's tile, or None where the shape does
    not tile: the largest of these that divide ``T`` and ``C`` (1024 x 512
    at the benchmark's shapes: 1 MB a block, 1 KB a row of the DMA)."""
    rows = next((r for r in (1024, 512, 256, 128, 64, 32) if T % r == 0),
                None)
    cols = next((c for c in (512, 256, 128) if C % c == 0), None)
    return None if rows is None or cols is None else (rows, cols)


def fits(T: int, C: int, taps: int) -> bool:
    """Whether the kernels take a convolution of these shapes: whole row
    tiles, columns of whole lane tiles, taps that reach no further back
    than a sublane tile."""
    return 2 <= taps <= REACH + 1 and _tiles(T, C) is not None


def _rows(ext, start: int, rows: int):
    """Rows ``start .. start + rows`` of ``ext``: a sublane roll in VMEM
    where ``start`` is off the float32 tiling, then an aligned slice."""
    at = start - start % REACH
    if at != start:
        ext = pltpu.roll(ext, (at - start) % ext.shape[0], 0)
    return ext[at:at + rows]


def _pre_activation(ext, w, bias):
    """``(the strip's rows as each tap reads them, c)`` from the strip
    behind the ``REACH`` rows before it."""
    taps, rows = len(w), ext.shape[0] - REACH
    x = [_rows(ext, REACH - (taps - 1) + j, rows) for j in range(taps)]
    c = w[0] * x[0]
    for j in range(1, taps):
        c = c + w[j] * x[j]
    return x, c if bias is None else c + bias


class _Tile:
    """What both kernels read of a grid step: the tile's refs, and a lane
    strip's taps, bias and the ``REACH`` float32 rows before the tile."""

    def __init__(self, refs, taps: int, has_bias: bool):
        self.x_ref, self.halo_ref, self.w_ref = refs[:3]
        self.b_ref = refs[3] if has_bias else None
        self.rest = refs[3 + has_bias:]
        self.taps = taps
        self.rows, cols = self.x_ref.shape[1:]
        self.strip = min(STRIP, self.rows)
        width = min(LANES, cols)
        self.lane_strips = [slice(at, at + width)
                            for at in range(0, cols, width)]

    def taps_of(self, lanes):
        return ([self.w_ref[j:j + 1, lanes] for j in range(self.taps)],
                None if self.b_ref is None else self.b_ref[:, lanes])

    def before(self, lanes, first):
        """Zeros at a sequence's start."""
        rows = self.halo_ref[0, :, lanes].astype(_F32)[HALO - REACH:]
        return jnp.where(first, jnp.zeros_like(rows), rows)

    def at(self, i):
        return pl.ds(pl.multiple_of(i * self.strip, self.strip), self.strip)


def _fwd_kernel(*refs, taps: int, has_bias: bool):
    tile = _Tile(refs, taps, has_bias)
    x_ref, (o_ref,) = tile.x_ref, tile.rest
    first = pl.program_id(1) == 0
    for lanes in tile.lane_strips:
        w, bias = tile.taps_of(lanes)

        def strip(i, before, lanes=lanes, w=w, bias=bias):
            at = tile.at(i)
            x = x_ref[0, at, lanes].astype(_F32)
            _, c = _pre_activation(jnp.concatenate([before, x], axis=0), w,
                                   bias)
            o_ref[0, at, lanes] = jax.nn.silu(c).astype(o_ref.dtype)
            return x[-REACH:]

        jax.lax.fori_loop(0, tile.rows // tile.strip, strip,
                          tile.before(lanes, first))


def _fold(a):
    """A strip's rows summed onto one sublane tile: ``[REACH, lanes]``."""
    out = a[:REACH]
    for at in range(REACH, a.shape[0], REACH):
        out = out + a[at:at + REACH]
    return out


def _bwd_kernel(*refs, taps: int, has_bias: bool):
    tile = _Tile(refs, taps, has_bias)
    x_ref = tile.x_ref
    dy_ref, dx_ref, sums_ref, xs_ref, after_ref = tile.rest
    seq, k = pl.program_id(1), pl.program_id(2)
    n, rows = tile.rows // tile.strip, tile.strip

    @pl.when((seq == 0) & (k == 0))
    def _start():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    @pl.when(k == 0)
    def _last_tile():          # nothing follows a sequence's last row
        after_ref[...] = jnp.zeros_like(after_ref)

    first = k == pl.num_programs(2) - 1
    for lanes in tile.lane_strips:
        w, bias = tile.taps_of(lanes)

        # the tile in float32 behind the rows before it
        xs_ref[:REACH, lanes] = tile.before(lanes, first)

        def widen(i, _, lanes=lanes):
            at = tile.at(i)
            xs_ref[pl.ds(REACH + at.start, rows), lanes] = x_ref[
                0, at, lanes].astype(_F32)

        jax.lax.fori_loop(0, n, widen, None)

        def strip(i, carry, lanes=lanes, w=w, bias=bias):
            after, sums = carry
            at = tile.at(n - 1 - i)
            x, c = _pre_activation(
                xs_ref[pl.ds(at.start, REACH + rows), lanes], w, bias)
            s = jax.nn.sigmoid(c)
            dc = dy_ref[0, at, lanes].astype(_F32) * (
                s * (1.0 + c * (1.0 - s)))
            # dc's reach is forward: the strip, then the rows after it
            ext = jnp.concatenate([dc, after], axis=0)
            dx = w[0] * _rows(ext, taps - 1, rows)
            for j in range(1, taps):
                dx = dx + w[j] * _rows(ext, taps - 1 - j, rows)
            dx_ref[0, at, lanes] = dx.astype(dx_ref.dtype)
            # dw[j] and, last, dbias
            parts = [dc * part for part in x] + [dc] * has_bias
            return dc[:REACH], tuple(
                total + _fold(part) for total, part in zip(sums, parts))

        after = after_ref[:, lanes]
        after, sums = jax.lax.fori_loop(
            0, n, strip,
            (after, (jnp.zeros_like(after),) * (taps + has_bias)))
        after_ref[:, lanes] = after
        for j, total in enumerate(sums):
            sums_ref[j * REACH:(j + 1) * REACH, lanes] += total


@functools.lru_cache(maxsize=None)
def _build(kind: str, shape: tuple, taps: int, has_bias: bool,
           dtype_name: str, scope: str, interpret: bool):
    """The ``pallas_call`` of one kernel over ``x [b, T, C]`` (``shape``),
    under the named scope ``scope``."""
    b, T, C = shape
    rows, cols = _tiles(T, C)
    nt, cd = T // rows, jnp.dtype(dtype_name)
    back = kind == BWD_NAME

    # grid: forward (sequence, row tile, column tile); backward (column
    # tile, sequence, row tile from the last), the sums' block resident
    # over the two inner axes
    def spec(block, index):
        if back:
            return pl.BlockSpec(block, lambda c, i, k: index(
                i, nt - 1 - k, c))
        return pl.BlockSpec(block, index)

    tile = spec((1, rows, cols), lambda i, t, c: (i, t, c))
    halo = spec((1, HALO, cols), lambda i, t, c: (
        i, jnp.maximum(t * (rows // HALO) - 1, 0), c))
    taps_spec = spec((taps, cols), lambda i, t, c: (0, c))
    lane = spec((1, cols), lambda i, t, c: (0, c))
    S = jax.ShapeDtypeStruct
    x_s = S((b, T, C), cd)
    in_specs = [tile, halo, taps_spec] + [lane] * has_bias
    params = dict(taps=taps, has_bias=has_bias)
    if back:
        n_sums = (taps + has_bias) * REACH
        call = pl.pallas_call(
            functools.partial(_bwd_kernel, **params), name=kind,
            grid=(C // cols, b, nt), in_specs=in_specs + [tile],
            out_specs=[tile, spec((n_sums, cols), lambda i, t, c: (0, c))],
            out_shape=[x_s, S((n_sums, C), _F32)],
            scratch_shapes=[pltpu.VMEM((REACH + rows, cols), _F32),
                            pltpu.VMEM((REACH, cols), _F32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary")),
            interpret=interpret)
    else:
        call = pl.pallas_call(
            functools.partial(_fwd_kernel, **params), name=kind,
            grid=(b, nt, C // cols), in_specs=in_specs, out_specs=tile,
            out_shape=x_s,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel")),
            interpret=interpret)

    def named(x, *operands):
        with jax.named_scope(scope):
            return call(x, x, *operands)

    return named


# one jaxpr and one Mosaic lowering for the repeats of a call in a trace
# (``ops/flash._shared``, PERF.md section 6, PR 33)
_shared = functools.lru_cache(maxsize=None)(jax.jit)


@functools.lru_cache(maxsize=None)
def _make_conv(shape: tuple, taps: int, has_bias: bool, dtype_name: str,
               scope: str, interpret: bool):
    """The differentiable call over the kernels' own operands: ``(x [b, T,
    C], w [taps, C] float32[, bias [1, C] float32]) -> silu(conv(x) +
    bias)``. The forward keeps its arguments and nothing else."""

    def differentiable(built):
        def build(kind):
            return built(_build(kind, shape, taps, has_bias, dtype_name,
                                scope, interpret))

        @jax.custom_vjp
        def conv(*operands):
            return build(FWD_NAME)(*operands)

        def fwd(*operands):
            return conv(*operands), operands

        def bwd(operands, dy):
            x, w = operands[:2]
            dx, sums = build(BWD_NAME)(*operands, dy)
            with jax.named_scope(scope):
                sums = sums.reshape(-1, REACH, x.shape[2]).sum(1)
                return (dx, sums[:taps]) + (
                    (sums[taps:],) if has_bias else ())

        conv.defvjp(fwd, bwd)
        return conv

    bare, shared = differentiable(lambda call: call), differentiable(_shared)
    last_trace = [None]

    def conv(*operands):
        trace = jax.core.get_opaque_trace_state()
        repeat, last_trace[0] = trace == last_trace[0], trace
        return (shared if repeat else bare)(*operands)

    return conv


def conv_pallas(x, w, bias, scope: str, interpret: bool = False):
    """:func:`relayrl_tpu.ops.conv.conv` at a sequence's start through the
    kernels, for shapes that :func:`fits` takes. Compiled by Mosaic: a TPU
    backend only; ``interpret=True`` runs the bodies in the Pallas
    interpreter — a test-only switch that is never defaulted on."""
    b, T, C = x.shape
    taps = w.shape[0]
    if not fits(T, C, taps):
        raise ValueError(f"the convolution's kernels do not tile {T} rows "
                         f"of {C} columns under {taps} taps")
    conv = _make_conv((b, T, C), taps, bias is not None, x.dtype.name, scope,
                      bool(interpret))
    with jax.named_scope(scope):
        operands = (x, w.astype(_F32)) + (
            () if bias is None else (bias.astype(_F32)[None],))
    return conv(*operands)
