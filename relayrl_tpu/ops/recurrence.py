"""Reverse first-order linear recurrence along the last axis.

``a_t = x_t + coeff_t * a_{t+1}``, ``a_T = 0`` — the one recursion under
V-trace (:mod:`relayrl_tpu.ops.vtrace`, a per-step coefficient
``gamma * c_t``) and under the discounted sums of
:mod:`relayrl_tpu.ops.gae` (a constant one). It is written once, here.

The pair combine ``(k_l, b_l) ⊕ (k_r, b_r) = (k_l * k_r, b_r + k_r * b_l)``
over reversed time is associative, so the recursion needs no T dependent
steps. It is evaluated by doubling: after step ``j`` every position holds
the recursion over its next ``2^j`` steps, one whole-array
``a += k * shift(a, 2^j)``, ``k *= shift(k, 2^j)`` a step, ceil(log2 T)
steps, T on the lanes throughout and no loop in the compiled program.
On a v5e, whole V-trace at ``[1, 16384]`` float32 (PERF.md §6, PR 38):
24.5 ms as a ``lax.scan`` of T steps, 0.147 as ``lax.associative_scan``,
0.023 as a two-level blocked scan, **0.013 in this form**, and the order
holds at every batch shape the benchmark runs. The T log2 T multiply-adds
(230 k at T 16,384) are nothing beside an instruction's launch.

Only the order of the float32 additions differs from the sequential
form: against a float64 recursion both err by about 2e-7 of the largest
entry at T 16,384.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def reverse_linear_recurrence(coeff: jax.Array, x: jax.Array,
                              sequential: bool = False) -> jax.Array:
    """``a_t = x_t + coeff_t * a_{t+1}`` along the last axis, ``a_T = 0``.

    ``coeff`` and ``x`` have one shape ``[..., T]``. The doubling form
    multiplies coefficients over spans up to T long: where they can exceed
    1 in magnitude such a product can overflow to ``inf`` and meet a zero
    (``inf * 0``) where the step-by-step recursion stays finite. A caller
    that can see that from its arguments (V-trace: ``gamma * c_bar > 1``,
    Python floats at trace time) passes ``sequential=True`` and gets the
    ``lax.scan`` of T steps instead.
    """
    if sequential:
        def step(carry, inp):
            k_t, x_t = inp
            a_t = x_t + k_t * carry
            return a_t, a_t

        _, a = jax.lax.scan(
            step, jnp.zeros(x.shape[:-1], x.dtype),
            (jnp.moveaxis(coeff, -1, 0), jnp.moveaxis(x, -1, 0)),
            reverse=True)
        return jnp.moveaxis(a, 0, -1)

    lead = [(0, 0)] * (x.ndim - 1)

    def ahead(v, d):
        # v_{t+d}, zeros past the end: a_T = 0, and a span that runs past
        # the end has nothing more to add
        return jnp.pad(v[..., d:], lead + [(0, d)])

    k, a = coeff, x
    d = 1
    while d < x.shape[-1]:
        a, k = a + k * ahead(a, d), k * ahead(k, d)
        d *= 2
    return a
