"""The gated delta rule (Gated DeltaNet's linear attention) in chunks.

Per value head ``h`` (``H`` value heads of width ``V`` over ``Hk`` query/key
heads of width ``K``, ``H / Hk`` value heads reading each: head ``h`` reads
key head ``h // (H / Hk)``) a MATRIX state ``S [K, V]`` (keys x values) that
is decayed, corrected and read a token at a time::

    S~_t = exp(g_t) S_{t-1}                               g_t <= 0
    S_t  = S~_t + beta_t k_t (v_t - S~_t^T k_t)^T         0 <= beta_t <= 1
    o_t  = S_t^T q_t

— the delta rule: what the state already answers for ``k_t`` is taken off
``v_t`` before the outer product goes in, so every token applies ``(I -
beta_t k_t k_t^T)`` to the decayed state. ``ops/ssd.py`` cannot be bent to
it: Mamba-2's state only accumulates outer products under a scalar decay,
there is no such factor there and nothing to solve inside a chunk.

:func:`gdn` evaluates it a chunk of ``chunk`` tokens at a time (Yang, Kautz
& Hatamizadeh, "Gated Delta Networks", arXiv:2412.06464, section 3.3; the
source's ``chunk_gated_delta_rule`` at chunk 64), with ``gamma_i`` the
running sum of ``g`` inside a chunk and ``decay_ij = exp(gamma_i -
gamma_j)`` on and under the diagonal:

* **inside a chunk, the solve**: ``A = -strictly_lower(K_beta K^T (.)
  decay)`` with ``K_beta = beta k``; ``T = (I - A)^-1``. ``A`` is nilpotent
  (``A^chunk = 0``), so the inverse is the finite product ``(I + A)(I +
  A^2)(I + A^4) ...`` — ``log2(chunk)`` squarings, float32 matmuls at
  precision "highest", no loop over the chunk's rows in the trace (forward
  substitution row by row is the same matrix in ``chunk`` dependent steps);
  then ``W = T (K_beta (.) e^gamma)`` and ``U = T (beta v)``;
* **across chunks**, the ``T / chunk`` chunks in order, carrying the heads'
  ``[K, V]`` states in float32 (128 steps at T 8192, the only part
  sequential in T; no Python loop over the chunks in the trace):
  ``v' = U - W S``, ``o = (Q (.) e^gamma) S + (Q K^T (.) decay (.) causal)
  v'``, ``S <- e^{gamma_C} S + (K (.) e^{gamma_C - gamma})^T v'``.

Exponentials are taken of non-positive sums only: ``gamma_i - gamma_j`` is
masked to ``-inf`` above the diagonal BEFORE the exponential. ``g``,
``beta``, the sums, the decays, the solve and the carried state are float32;
the other matmuls' operands are ``v``'s dtype (the compute dtype) with
float32 accumulation, as the flash kernels round their probabilities.

``T`` need not be a multiple of ``chunk``: the call pads on the right with
rows of ``g = 0``, ``beta = 0`` and zero ``k`` and ``v``, which leave the
state as it is, so ``last_state`` is the state after the real rows.
Right-padded episodes need nothing: the rule is causal, a real row never
sees a later one (``tests/test_gdn.py``). A caller that wants the state
after its first ``n`` rows zeroes ``g`` and ``beta`` from row ``n`` on
(``models/layers/gdn.py``'s prefill).

**Two forms of the same algebra, picked by what the code can observe**
(:func:`backend`; no arch key, no environment variable, no switch):

* ``gdn_pallas`` — on a TPU, for shapes that tile (``K`` and ``V`` whole lane
  tiles, eight value heads a grid step with whole key heads, a chunk of 64 or
  128: :func:`relayrl_tpu.ops.gdn_pallas.fits`) and at least one whole chunk
  of rows: the Pallas kernels of :mod:`relayrl_tpu.ops.gdn_pallas`, a grid
  over (sequence, eight value heads, chunk) with the chunk axis sequential, a
  chunk's tiles, its solve and the carried state in VMEM, ``q`` / ``k`` /
  ``v`` / ``o`` as the projections leave them (no head transpose), a
  hand-written backward (``jax.custom_vjp``: the chunk-start states made
  again by one kernel, the reverse sweep by another, the solve's transpose
  two matmuls and no second inversion). ``qwen3next-policy.update`` runs
  them (PERF.md section 6, PR 43: 280 ms an update of plain XLA at 3.0 % of
  its roofline before them).
* ``gdn_xla`` (:func:`gdn_xla`) — everywhere else (CPU actor hosts, CI, a
  shape that does not tile, a prompt shorter than a chunk) and the reference
  the kernels' tests hold them to: plain XLA, ``_HEADS_A_STEP`` value heads a
  step of a ``lax.map``, heads before rows inside, the chunks' states carried
  by ONE ``lax.scan``, **backward by autodiff under ``jax.checkpoint``** — a
  step's intermediates (the ``[chunk, chunk]`` tiles, ``T``, ``W``, ``U`` and
  the chunk-start states) are made again from its arguments, which is all
  the forward keeps (PERF.md section 6, PR 42, has what ``rehearse_compile``
  and the chip said).

Both sit under one named scope, ``relayrl_gdn`` (``ops/scopes.py``), and no
deeper ``relayrl_`` name: the benchmark's ``gdn_ms`` / ``gdn_roofline`` read
the exact scope. ``models/layers/gdn.KERNELS`` records which
form a policy's rules ran as (``Policy.gdn_backends``) and prints one
``[gdn]`` line a shape.

:func:`gdn_step` is the rule's one step, what a cached decode runs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from relayrl_tpu.ops.scopes import GDN_NAME

# what a rule ran as (``backend``; ``Policy.gdn_backends``)
PALLAS, XLA = "gdn_pallas", "gdn_xla"
# what a differentiated kernel forward keeps beside its arguments, by the name
# a caller's checkpoint policy saves it under (``ops/gdn_pallas.py``)
SOLVE_NAME = "relayrl_gdn_solve"

# Value heads a step of the map over heads: the chunk scan's 128 steps are
# the sequential part, so the fewer map steps the better, while a step's
# intermediates (the float32 tiles, W, U, v' and the chunk-start states the
# scan's backward keeps: some 4 KB a token and head at K = V = 128, chunk
# 64) live together in the backward. At 32 heads and 16,384 tokens the update
# compiled for a v5e holds 15.73 GB at 16 a step and 14.03 at 8 (PERF.md
# section 6, PR 42); 4 a step held more again, the scheduler's choice.
_HEADS_A_STEP = 8


def _inverse_unit_lower(a):
    """``(I - a)^-1`` for strictly lower-triangular ``a [..., C, C]``
    (nilpotent: ``a^C = 0``) as ``(I + a)(I + a^2)(I + a^4) ...``, float32
    at precision "highest"."""
    mm = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    size = a.shape[-1]
    eye = jnp.eye(size, dtype=a.dtype)
    inv, power, covered = eye + a, a, 2     # inv = sum of a^i, i < covered
    while covered < size:
        power = mm(power, power)
        inv = inv + mm(power, inv)
        covered *= 2
    return inv


@functools.partial(jax.checkpoint, static_argnums=(1,))
def _heads(args, chunk: int):
    """Some value heads over whole chunks (``T % chunk == 0``), heads before
    rows: ``q, k [b, Rk, T, K]``, ``v [b, R, T, V]``, ``g, beta [b, R, T]``
    float32, ``state [b, R, K, V]`` float32 -> ``(o [b, R, T, V], last
    state)``; value head ``r`` reads key head ``r // (R / Rk)``."""
    q, k, v, g, beta, state = args
    b, R, T, V = v.shape
    Rk, K = k.shape[1], k.shape[3]
    rep = R // Rk
    c, cd, f32 = T // chunk, v.dtype, jnp.float32
    mm = functools.partial(jnp.einsum, preferred_element_type=f32)

    def chunks(a):  # [b, heads, T, ...] -> [b, heads, c, chunk, ...]
        return a.reshape(a.shape[:2] + (c, chunk) + a.shape[3:])

    def per_value_head(a):  # [b, Rk, ...] -> [b, R, ...]
        return a if rep == 1 else jnp.repeat(a, rep, axis=1)

    qc, kc, vc = chunks(q), chunks(k), chunks(v)
    gc, bc = chunks(g), chunks(beta)                     # [b, R, c, chunk]
    gamma = jnp.cumsum(gc, axis=-1)                      # <= 0
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(
        lower, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))
    in_decay = jnp.exp(gamma)[..., None]                 # e^gamma_i
    to_end = jnp.exp(gamma[..., -1:] - gamma)[..., None]  # e^(gamma_C-gamma_i)

    # a key head's products once, then a value head's own decay and beta
    kk = per_value_head(mm("bhcik,bhcjk->bhcij", kc, kc))
    qk = per_value_head(mm("bhcik,bhcjk->bhcij", qc, kc))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    a_mat = jnp.where(strict, -(bc[..., None] * kk * decay), 0.0)
    solve = _inverse_unit_lower(a_mat).astype(cd)        # T, [b,R,c,C,C]
    kr = per_value_head(kc).astype(f32)
    k_beta = kr * bc[..., None]
    w = mm("bhcij,bhcjk->bhcik", solve,
           (k_beta * in_decay).astype(cd)).astype(cd)
    u = mm("bhcij,bhcjv->bhciv", solve,
           (vc.astype(f32) * bc[..., None]).astype(cd))
    scores = (qk * decay).astype(cd)                     # causal by decay
    q_in = (per_value_head(qc).astype(f32) * in_decay).astype(cd)
    k_out = (kr * to_end).astype(cd)
    through = jnp.exp(gamma[..., -1])                    # [b, R, c]

    def carry(s, step):
        w_c, u_c, scores_c, q_c, k_c, through_c = step
        s_cd = s.astype(cd)
        v_new = u_c - mm("bhik,bhkv->bhiv", w_c, s_cd)
        o_c = (mm("bhik,bhkv->bhiv", q_c, s_cd)
               + mm("bhij,bhjv->bhiv", scores_c, v_new.astype(cd)))
        s = (through_c[..., None, None] * s
             + mm("bhik,bhiv->bhkv", k_c, v_new.astype(cd)))
        return s, o_c

    last, o = jax.lax.scan(
        carry, state,
        tuple(jnp.moveaxis(a, 2, 0)
              for a in (w, u, scores, q_in, k_out, through)))
    # [c, b, R, chunk, V] -> [b, R, T, V]
    o = jnp.moveaxis(o, 0, 2).reshape(b, R, T, V)
    return o.astype(cd), last


def gdn_xla(q, k, v, g, beta, chunk: int = 64, state=None):
    """:func:`gdn` as plain XLA, ``_HEADS_A_STEP`` value heads a step of a
    ``lax.map``: every backend takes it, and the kernels' tests hold them to
    it."""
    with jax.named_scope(GDN_NAME):
        b, T, H, V = v.shape
        Hk, K = k.shape[2:]
        f32 = jnp.float32
        if state is None:
            state = jnp.zeros((b, H, K, V), f32)
        pad = -T % chunk
        if pad:
            q, k, v, g, beta = (
                jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                for a in (q, k, v, g, beta))
        # Heads before rows from here on: a chunk of a head is then one
        # contiguous block, and a step of the map over heads a slice of the
        # second axis. (With the rows first, [b, c, chunk, heads, width],
        # XLA re-tiled every operand twice: PERF.md section 6, PR 42.)
        q, k, v = (jnp.swapaxes(a, 1, 2) for a in (q, k, v))
        g, beta = (jnp.swapaxes(a.astype(f32), 1, 2) for a in (g, beta))
        # whole key heads a step: R value heads with their R / rep key heads
        rep = H // Hk
        R = min(H, max(_HEADS_A_STEP, rep))
        while H % R or R % rep:
            R -= 1
        n = H // R

        def by_step(a):  # [b, heads, ...] -> [n, b, heads / n, ...]
            a = a.reshape((b, n, a.shape[1] // n) + a.shape[2:])
            return jnp.moveaxis(a, 1, 0)

        args = tuple(by_step(a) for a in (q, k, v, g, beta,
                                          state.astype(f32)))
        o, last = jax.lax.map(lambda step: _heads(step, chunk), args)
        o = jnp.swapaxes(jnp.moveaxis(o, 0, 1).reshape(b, H, T + pad, V),
                         1, 2)
        return o[:, :T], jnp.moveaxis(last, 0, 1).reshape(b, H, K, V)


def backend(T: int, H: int, Hk: int, K: int, V: int, chunk: int) -> str:
    """``"gdn_pallas"`` or ``"gdn_xla"``: what :func:`gdn` runs a rule of
    these shapes as on this process's platform. The kernels on a TPU where
    the shapes tile (``gdn_pallas.fits``) and there is a whole chunk of
    rows, plain XLA everywhere else — CPU actor hosts, CI, a shape that does
    not tile, a rule shorter than a chunk (a short prompt: the kernels' grid
    would be one step of padding, and their lowering a second of every
    process's start). Platform and shape decide, nothing else: no arch key,
    no environment variable."""
    if jax.default_backend() != "tpu" or T < chunk:
        return XLA
    from relayrl_tpu.ops import gdn_pallas

    return PALLAS if gdn_pallas.fits(H, Hk, K, V, chunk) else XLA


def gdn(q, k, v, g, beta, chunk: int = 64, state=None):
    """``q, k [b, T, Hk, K]`` (as they enter the rule: normalised and scaled
    by the caller), ``v [b, T, H, V]`` with ``Hk`` dividing ``H``, ``g [b,
    T, H]`` (log decay, <= 0) and ``beta [b, T, H]`` float32, ``state [b,
    H, K, V]`` float32 (None: zeros, a sequence's start) -> ``(o [b, T, H,
    V]`` in ``v``'s dtype, ``last_state [b, H, K, V]`` float32``)``, as
    :func:`backend` says."""
    T, H, V = v.shape[1:]
    Hk, K = k.shape[2:]
    if H % Hk:
        raise ValueError(f"{Hk} key heads do not divide {H} value heads")
    if backend(T, H, Hk, K, V, chunk) == PALLAS:
        from relayrl_tpu.ops.gdn_pallas import gdn_pallas

        return gdn_pallas(q, k, v, g, beta, chunk, state)
    return gdn_xla(q, k, v, g, beta, chunk, state)


def gdn_step(q, k, v, g, beta, state):
    """One step of the rule, what :func:`gdn` computes at ``T = 1``: ``q, k
    [b, Hk, K]``, ``v [b, H, V]``, ``g, beta [b, H]``, ``state [b, H, K,
    V]`` float32 -> ``(o [b, H, V], new state)``."""
    with jax.named_scope(GDN_NAME):
        f32 = jnp.float32
        rep = v.shape[1] // k.shape[1]
        qh, kh = (jnp.repeat(a.astype(f32), rep, axis=1) for a in (q, k))
        state = jnp.exp(g.astype(f32))[..., None, None] * state
        v_new = beta.astype(f32)[..., None] * (
            v.astype(f32) - jnp.einsum("bhkv,bhk->bhv", state, kh))
        state = state + kh[..., :, None] * v_new[..., None, :]
        o = jnp.einsum("bhkv,bhk->bhv", state, qh)
        return o.astype(v.dtype), state
