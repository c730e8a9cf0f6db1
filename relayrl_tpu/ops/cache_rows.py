"""One new row a sequence into a cache of rows, where it lies.

A cached decode step writes position ``t`` of ``cache [B, L, C]`` and reads
the rest. Alone that is ``lax.dynamic_update_slice``: one small write, in
place. Under ``vmap`` over sequences that each stand at a position of their
own (the fused rollout's lanes, ``runtime/anakin.py``) JAX turns the write
into a scatter, and XLA:TPU turns the scatter into a ``while`` of one trip
a sequence, seven small operations a trip: 64 lanes x 48 caches were 3,072
trips and 10 ms of a 22 ms scan step (PERF.md section 6, PR 67).

:func:`write_row` is the same write with a rule of its own for ``vmap``
(``jax.custom_batching``): on a TPU the batch goes through ONE Pallas call,
a grid step a sequence, whose block index comes from the prefetched
positions — the tile of rows that holds row ``t`` (16 rows of bfloat16, 8
of float32) is brought into VMEM, the row replaced, the tile written back, the output aliased to the
input so every other tile stays as it is. Elsewhere, and where the shapes do
not tile, the rule is what ``vmap`` does by itself.

Name (``ops/scopes.py``): the call is ``relayrl_cache_write_row``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from relayrl_tpu.ops.scopes import CACHE_WRITE_ROW


def _rows_a_tile(dtype) -> int:
    """Rows of one packed ``(8, 128)`` tile: 8 of 32 bits, 16 of 16."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def tiles(cache_shape, dtype) -> bool:
    """Whether ``cache [..., L, C]``'s rows go through the kernel: whole
    tiles of rows, whole vectors of lanes, a 16- or 32-bit type."""
    if jnp.dtype(dtype).itemsize not in (2, 4):
        return False
    rows, lanes = cache_shape[-2:]
    return rows % _rows_a_tile(dtype) == 0 and lanes % 128 == 0


@functools.partial(jax.jit, static_argnames=("interpret",))
def write_rows_pallas(cache, rows, t, interpret: bool = False):
    """``cache [M, L, C]`` with ``rows [M, 1, C]`` at positions ``t [M]``
    (clamped into the cache, as ``dynamic_update_slice`` clamps), in place.
    ``interpret``: the Pallas interpreter, a test-only switch."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, L, C = cache.shape
    R = _rows_a_tile(cache.dtype)
    t = jnp.clip(jnp.asarray(t, jnp.int32), 0, L - 1)

    def kernel(t_ref, row_ref, tile_ref, out_ref):
        at = t_ref[pl.program_id(0)] % R
        here = lax.broadcasted_iota(jnp.int32, (1, R, C), 1) == at
        # through float32 (exact both ways): a row spread over a packed
        # tile's sublanes is plain there
        out_ref[...] = jnp.where(
            here, row_ref[...].astype(jnp.float32),
            tile_ref[...].astype(jnp.float32)).astype(out_ref.dtype)

    def tile_of(i, t_ref):
        return i, t_ref[i] // R, 0

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(M,),
            in_specs=[pl.BlockSpec((1, 1, C), lambda i, t_ref: (i, 0, 0)),
                      pl.BlockSpec((1, R, C), tile_of)],
            out_specs=pl.BlockSpec((1, R, C), tile_of)),
        out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        # operands count the prefetched positions: t 0, rows 1, cache 2
        input_output_aliases={2: 0},
        name=CACHE_WRITE_ROW, interpret=interpret,
    )(t, rows.astype(cache.dtype), cache)


def _write_row_plain(cache, row, t):
    return lax.dynamic_update_slice_in_dim(cache, row.astype(cache.dtype), t,
                                           axis=1)


@jax.custom_batching.custom_vmap
def write_row(cache, row, t):
    """``cache [B, L, C]`` with ``row [B, T, C]`` written from position
    ``t`` (a scalar) on: ``dynamic_update_slice`` along the rows, and under
    ``vmap`` the module's kernel where it applies (one row, a TPU, shapes
    that tile)."""
    return _write_row_plain(cache, row, t)


@write_row.def_vmap
def _write_row_batched(axis_size, in_batched, cache, row, t):
    B, L, C = cache.shape[-3:]
    if (jax.default_backend() == "tpu" and row.shape[-2] == 1
            and tiles(cache.shape, cache.dtype)):
        cache, row, t = (
            x if batched else jnp.broadcast_to(x, (axis_size, *jnp.shape(x)))
            for x, batched in zip((cache, row, jnp.asarray(t)), in_batched))
        out = write_rows_pallas(cache.reshape(axis_size * B, L, C),
                                row.reshape(axis_size * B, 1, C),
                                jnp.repeat(t, B))
        return out.reshape(axis_size, B, L, C), True
    # what vmap does by itself: an argument without the batch stays without
    return jax.vmap(_write_row_plain, in_axes=tuple(
        0 if batched else None for batched in in_batched),
        axis_size=axis_size)(cache, row, t), True
