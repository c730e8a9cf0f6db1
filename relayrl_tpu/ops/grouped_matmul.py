"""Grouped matmul on the TPU for the MoE expert layer (models/moe.py): the
Pallas ``megablox`` kernels that ship with jax, under this program's names.

``gmm(lhs [m, k], rhs [E, k, n], group_sizes [E]) -> [m, n]``: row block i
of ``lhs`` (``group_sizes[i]`` rows, blocks in expert order) times
``rhs[i]``, float32 accumulation, result in ``lhs``'s dtype. Forward and
both backward products are Mosaic kernels, each under a ``named_scope`` of
its own — ``relayrl_moe_gmm_fwd`` / ``_dlhs`` / ``_drhs`` — which is the
name the compiled instruction carries into a device trace (as
``relayrl_flash_*``, ops/flash.py).

Imported only where an arch asks for the sparse MoE dispatch on a TPU
(:func:`relayrl_tpu.models.moe.grouped_matmul`): importing
``jax.experimental.pallas`` costs about a second that no other model should
pay.
"""

from __future__ import annotations

import jax
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as _jit_gmm
from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm as _jit_tgmm

# The kernels' own ``jax.jit`` wrappers would name the compiled calls
# ``gmm.N`` / ``tgmm.N``; traced inline, each call takes the name of the
# ``named_scope`` it is made under.
_gmm = _jit_gmm.__wrapped__
_tgmm = _jit_tgmm.__wrapped__

# (m, k, n) tile. m = token-slots, so a group boundary costs at most one
# partly masked tile of 512 rows per expert.
TILING = (512, 1024, 1024)


def fits(m: int, k: int, n: int) -> bool:
    """Whether the kernels tile these shapes (else: ``lax.ragged_dot``)."""
    tm, tk, tn = TILING
    return m % tm == 0 and k % min(tk, k) == 0 and n % min(tn, n) == 0 \
        and k % 128 == 0 and n % 128 == 0


def _tiling(k: int, n: int):
    tm, tk, tn = TILING
    return (tm, min(tk, k), min(tn, n))


@jax.custom_vjp
def gmm(lhs, rhs, group_sizes):
    return _fwd(lhs, rhs, group_sizes)[0]


def _fwd(lhs, rhs, group_sizes):
    with jax.named_scope("relayrl_moe_gmm_fwd"):
        out = _gmm(lhs, rhs, group_sizes, lhs.dtype,
                   _tiling(rhs.shape[1], rhs.shape[2]))
    return out, (lhs, rhs, group_sizes)


def _bwd(res, g):
    lhs, rhs, group_sizes = res
    g = g.astype(lhs.dtype)
    with jax.named_scope("relayrl_moe_gmm_dlhs"):
        d_lhs = _gmm(g, rhs, group_sizes, lhs.dtype,
                     _tiling(rhs.shape[2], rhs.shape[1]), transpose_rhs=True)
    with jax.named_scope("relayrl_moe_gmm_drhs"):
        d_rhs = _tgmm(lhs.swapaxes(0, 1), g, group_sizes, rhs.dtype,
                      _tiling(rhs.shape[1], rhs.shape[2]),
                      num_actual_groups=rhs.shape[0])
    return d_lhs, d_rhs, None


gmm.defvjp(_fwd, _bwd)
