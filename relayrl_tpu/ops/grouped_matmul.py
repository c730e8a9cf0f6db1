"""Grouped matmul on the TPU for the MoE expert layer (models/moe.py): the
Pallas ``megablox`` kernels that ship with jax, under this program's names.

``gmm(lhs [m, k], rhs [E, k, n], group_sizes [E]) -> [m, n]``: row block i
of ``lhs`` (``group_sizes[i]`` rows, blocks in expert order) times
``rhs[i]``, float32 accumulation, result in ``lhs``'s dtype. Forward and
both backward products are Mosaic kernels, each under a ``named_scope`` of
its own — ``relayrl_moe_gmm_fwd`` / ``_dlhs`` / ``_drhs`` — which is the
name the compiled instruction carries into a device trace (as
``relayrl_flash_*``, ops/flash.py).

k and n need not be multiples of 128: the stacks, the rows and the results
keep the model's published widths and the last tile of such an axis is
irregular (:func:`_tile`; ``chip_smoke.py`` phase E compares the three
kernels with ``lax.ragged_dot`` at 2688 x 1856 on the chip,
``tests/test_flash_tpu_compile.py`` compiles them for a described v5e).

Imported only where an arch asks for the sparse MoE dispatch on a TPU
(:func:`relayrl_tpu.models.moe.grouped_matmul`): importing
``jax.experimental.pallas`` costs about a second that no other model should
pay.
"""

from __future__ import annotations

import jax
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as _jit_gmm
from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm as _jit_tgmm

from relayrl_tpu.ops.scopes import GMM_DLHS_NAME, GMM_DRHS_NAME, GMM_FWD_NAME

# The kernels' own ``jax.jit`` wrappers would name the compiled calls
# ``gmm.N`` / ``tgmm.N``; traced inline, each call takes the name of the
# ``named_scope`` it is made under.
_gmm = _jit_gmm.__wrapped__
_tgmm = _jit_tgmm.__wrapped__

# (m, k, n) tile, at most. m = token-slots, so a group boundary costs at
# most one partly masked tile of 512 rows per expert. k and n take the
# largest multiple of 128 up to the cap that divides them: 1024 for widths
# of 1024 and 2048 (OLMoE's), 768 for LFM2's experts of 1536, 896 for
# Nemotron-H's hidden size of 2688. A width that NO multiple of 128 divides
# (Nemotron-H's experts of 1856 = 14.5 x 128) keeps its published size —
# no padded weight, no padded row — and its last tile hangs over: see _tile.
TILING = (512, 1024, 1024)


def _tile(dim: int, cap: int) -> int:
    """The tile of a k or n axis ``dim`` long: the largest multiple of 128
    up to ``cap`` that divides ``dim``. Where ``dim`` is no multiple of 128
    none does, and the tile is the multiple of 128 up to ``cap`` whose
    whole tiles overshoot ``dim`` by least, the largest of those (640 for
    1856: three tiles cover 1920): megablox takes an irregular last tile,
    zeroing its tail where the axis is contracted (k) and dropping the
    columns past the array's edge where it is the result's (n), so the
    result is the exact product at the published width. 0 under 128: no
    tile."""
    tiles = range(min(cap, dim) // 128 * 128, 0, -128)
    exact = next((t for t in tiles if dim % t == 0), 0)
    if exact or not tiles:
        return exact
    return min(tiles, key=lambda t: (-(-dim // t) * t, -t))


def fits(m: int, k: int, n: int) -> bool:
    """Whether the kernels take these shapes (else: ``lax.ragged_dot``):
    whole row tiles, and k and n of at least one 128-wide tile each — a
    multiple of 128 or not (:func:`_tile`)."""
    tm, tk, tn = TILING
    return m % tm == 0 and _tile(k, tk) > 0 and _tile(n, tn) > 0


def _tiling(k: int, n: int):
    tm, tk, tn = TILING
    return (tm, _tile(k, tk), _tile(n, tn))


@jax.custom_vjp
def gmm(lhs, rhs, group_sizes):
    return _fwd(lhs, rhs, group_sizes)[0]


def _fwd(lhs, rhs, group_sizes):
    with jax.named_scope(GMM_FWD_NAME):
        out = _gmm(lhs, rhs, group_sizes, lhs.dtype,
                   _tiling(rhs.shape[1], rhs.shape[2]))
    return out, (lhs, rhs, group_sizes)


def _bwd(res, g):
    lhs, rhs, group_sizes = res
    g = g.astype(lhs.dtype)
    with jax.named_scope(GMM_DLHS_NAME):
        d_lhs = _gmm(g, rhs, group_sizes, lhs.dtype,
                     _tiling(rhs.shape[2], rhs.shape[1]), transpose_rhs=True)
    with jax.named_scope(GMM_DRHS_NAME):
        d_rhs = _tgmm(lhs.swapaxes(0, 1), g, group_sizes, rhs.dtype,
                      _tiling(rhs.shape[1], rhs.shape[2]),
                      num_actual_groups=rhs.shape[0])
    return d_lhs, d_rhs, None


gmm.defvjp(_fwd, _bwd)
