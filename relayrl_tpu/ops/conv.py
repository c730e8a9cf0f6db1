"""The mixers' depthwise causal convolution: ``silu(conv(x) + bias)`` over
the rows a Mamba-2 layer calls ``xBC`` and a linear-attention layer ``[q | k
| v]`` (``models/layers/recurrent.mixer_conv``, both mixers')::

    c_t = sum_j w[j] * x_{t - (L - 1) + j} (+ bias)        L = w.shape[0] taps
    out_t = silu(c_t)

one filter a column, rows before a sequence's first read as zeros (or as
``state``, the ``L - 1`` rows a cache kept). The tap sum from ``j = 0`` up,
the bias and the SiLU are float32; rows come and go in ``x``'s dtype (the
compute dtype), rounded once.

**Two forms of the same three lines, picked by what the code can observe**
(:func:`backend`; no arch key, no environment variable, no switch):

* ``conv_pallas`` — on a TPU, at a sequence's start (``state is None``: the
  learner's full mode), for columns of whole 128-lane tiles and whole row
  tiles of ``T``: the two Pallas kernels of
  :mod:`relayrl_tpu.ops.conv_pallas`. A direction reads each row once and
  writes each row once; the ``L - 1`` rows a tile needs of its neighbour are
  shifted into place in VMEM. ``nemotron-twotower-policy.update`` and
  ``qwen3next-policy.update`` run them (PERF.md section 6, PR 44: plain XLA
  materialises every shifted ``[T, C]`` slice in HBM, a row offset of 1-3
  being off the sublane tiling, and ran at 18 % of the chip's bandwidth).
* ``conv_xla`` (:func:`conv_xla`) — everywhere else (CPU actor hosts, CI,
  the cached modes, which continue from a ``state``, the one row ``init``
  traces, a shape that does not tile) and the reference the kernels' tests
  hold them to: ``L`` shifted multiply-adds, backward by autodiff.

Both sit under the caller's named scope (``relayrl_mamba_conv`` |
``relayrl_gdn_conv``, ``ops/scopes.py``): the benchmark's ``mamba_conv_ms``
/ ``gdn_conv_ms`` read the scope, whichever form runs.
``models/layers/recurrent.CONV_KERNEL`` records which form a policy's
convolutions ran as (``Policy.conv_backends``) and prints one ``[conv]``
line a shape.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# what a convolution ran as (``backend``; ``Policy.conv_backends``)
PALLAS, XLA = "conv_pallas", "conv_xla"


def padded(x, taps: int, state=None):
    """``concat(state, x)``: ``x [b, T, C]`` behind the ``taps - 1`` rows
    before its first (``state [b, taps - 1, C]``; None: zeros, a sequence's
    start) — what a cache takes its next rows from."""
    if state is None:
        return jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return jnp.concatenate([state.astype(x.dtype), x], axis=1)


def conv_xla(x, w, bias=None, state=None):
    """:func:`conv` as plain XLA: every backend takes it, and the kernels'
    tests hold them to it."""
    f32 = jnp.float32
    taps, T = w.shape[0], x.shape[1]
    xp = padded(x, taps, state)
    c = sum(w[j].astype(f32) * xp[:, j:j + T].astype(f32)
            for j in range(taps))
    if bias is not None:
        c = c + bias.astype(f32)
    return jax.nn.silu(c).astype(x.dtype)


def backend(T: int, C: int, taps: int, has_state: bool = False) -> str:
    """``"conv_pallas"`` or ``"conv_xla"``: what :func:`conv` runs a
    convolution of these shapes as on this process's platform. The kernels
    on a TPU at a sequence's start where the shape tiles
    (``conv_pallas.fits``), plain XLA everywhere else — CPU actor hosts, CI,
    a call that continues from a cache's rows, one row (the cached step; the
    row ``init`` traces), a shape that does not tile. Platform and shape
    decide, nothing else: no arch key, no environment variable."""
    if jax.default_backend() != "tpu" or has_state or T == 1:
        return XLA
    from relayrl_tpu.ops import conv_pallas

    return PALLAS if conv_pallas.fits(T, C, taps) else XLA


def conv(x, w, bias, state, scope: str):
    """``x [b, T, C]``, taps ``w [L, C]``, ``bias [C]`` or None, ``state [b,
    L - 1, C]`` or None -> ``silu(conv(x) + bias) [b, T, C]`` in ``x``'s
    dtype under the named scope ``scope``, as :func:`backend` says."""
    if backend(x.shape[1], x.shape[2], w.shape[0],
               state is not None) == PALLAS:
        from relayrl_tpu.ops.conv_pallas import conv_pallas

        return conv_pallas(x, w, bias, scope)
    with jax.named_scope(scope):
        return conv_xla(x, w, bias, state)
