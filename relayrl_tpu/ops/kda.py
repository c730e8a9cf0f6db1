"""The delta rule under a decay that differs lane by lane (Kimi Delta
Attention), in chunks.

Per head (``H`` heads, keys of width ``K``, values of width ``V``) a MATRIX
state ``S [K, V]`` (keys x values), decayed a key lane at a time, corrected
and read a token at a time::

    S~_t = Diag(exp(g_t)) S_{t-1}                         g_t [K] <= 0
    S_t  = S~_t + beta_t k_t (v_t - S~_t^T k_t)^T         0 <= beta_t <= 1
    o_t  = S_t^T q_t

— ``S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T``
with ``alpha_t = exp(g_t)`` a VECTOR a head (Kimi Linear, arXiv:2510.26692,
section 3). :mod:`relayrl_tpu.ops.gdn` is the same rule under ONE decay a
head, and its chunked algebra does not carry over: there the weight of a pair
of rows inside a chunk is ``(k_i . k_j) e^{gamma_i - gamma_j}``, one
exponential beside one product; here it is

    KK_ij = sum_c k_i[c] k_j[c] e^{Gamma_i[c] - Gamma_j[c]}      (j <= i)

(``Gamma`` the running sum of ``g`` inside the chunk, a vector a row), and
the exponential does not factor out of the contraction over the lanes. The
textbook way round — ``(k_i (.) e^{Gamma_i}) . (k_j (.) e^{-Gamma_j})`` —
overflows inside a chunk of 64 at small ``alpha`` (``e^{+589}`` at ``alpha``
1e-4 a lane). :func:`kda` therefore walks a chunk in SUB-CHUNKS of
``_SUB`` rows:

* a pair in DIFFERENT sub-chunks (row ``i`` in sub-chunk ``I``, ``j`` before
  ``I``'s first row ``r``) is split at ``r``: ``(k_i (.) e^{Gamma_i -
  Gamma_r}) . (k_j (.) e^{Gamma_r - Gamma_j})``, both exponents <= 0 — one
  matmul a sub-chunk against the chunk's keys re-weighted for it, the
  operations of one ``[C, K] x [K, C]`` product in all;
* a pair in the SAME sub-chunk is summed lane by lane, ``e^{Gamma_i -
  Gamma_j}`` taken of the masked difference (``_SUB x _SUB x K`` products a
  sub-chunk; no matmul).

``QK_ij`` (``q_i`` against ``k_j``, ``j <= i``) the same way. With every
lane's decay equal the two products are :mod:`.gdn`'s ``K K^T (.) decay`` and
``Q K^T (.) decay`` and the rule is that rule (``tests/test_kda.py``). From
there on the algebra is :mod:`.gdn`'s with per-lane factors: ``A =
-strictly_lower(beta_i KK_ij)``, ``T = (I - A)^-1`` (the nilpotent product,
float32 "highest": ``gdn._inverse_unit_lower``), ``W = T (K_beta (.)
e^Gamma)``, ``U = T (beta v)``; across the chunks in order, carrying the
heads' float32 states: ``v' = U - W S``, ``o = (Q (.) e^Gamma) S + QK v'``,
``S <- Diag(e^{Gamma_C}) S + (K (.) e^{Gamma_C - Gamma})^T v'``. Every
exponential is of a non-positive number. ``g``, ``beta``, the sums, the
decays, the lane-wise sums, the solve and the carried state are float32; the
matmuls' operands are ``v``'s dtype with float32 accumulation.

``T`` need not be a multiple of ``chunk`` (right padding of ``g = 0``,
``beta = 0``, zero ``k`` and ``v`` leaves the state as it is), and
right-padded episodes need nothing: the rule is causal.

**Two forms of the same algebra, picked by what the code can observe**
(:func:`backend`; no arch key, no environment variable, no switch):

* ``kda_pallas`` — on a TPU, for shapes that tile (keys and values of one
  lane tile a head, four heads a grid step, chunks of 64:
  :func:`relayrl_tpu.ops.kda_pallas.fits`) and at least one whole chunk of
  rows: the Pallas kernels of :mod:`relayrl_tpu.ops.kda_pallas` — ``kda_fwd``,
  and in the backward ``kda_states`` + ``kda_bwd`` under one
  ``jax.custom_vjp`` —, a grid over (sequence, four heads, chunk) with the
  chunk axis sequential, a chunk's tiles, its solve and the carried state in
  VMEM, the operands as the projections leave them (no head transpose), the
  running sums of ``g`` made inside. ``kimi-linear-policy.update`` runs them
  (PERF.md section 6, PR 56: 722 ms an update of plain XLA at 2.4 % of its
  roofline before them).
* ``kda_xla`` (:func:`kda_xla`) — everywhere else (CPU actor hosts, CI, a
  shape that does not tile, a prompt shorter than a chunk) and the reference
  the kernels' tests hold them to: plain XLA, heads before rows,
  ``_HEADS_A_STEP`` heads a step of a ``lax.map``, ONE ``lax.scan`` over the
  chunks, backward by autodiff under ``jax.checkpoint`` (a step's tiles are
  made again from its arguments).

Both sit under one named scope, ``relayrl_kda`` (``ops/scopes.py``), and no
deeper ``relayrl_`` name: the benchmark's ``kda_ms`` / ``kda_roofline`` read
the exact scope. ``models/layers/kda.KERNELS`` records which form a policy's
rules ran as (``Policy.kda_backends``) and prints one ``[kda]`` line a shape.

:func:`kda_step` is the rule's one step, what a cached decode runs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from relayrl_tpu.ops.gdn import _inverse_unit_lower
from relayrl_tpu.ops.scopes import KDA_NAME

# what a rule ran as (``backend``; ``Policy.kda_backends``)
PALLAS, XLA = "kda_pallas", "kda_xla"
# what a differentiated kernel forward keeps beside its arguments, by the name
# a caller's checkpoint policy saves it under (``ops/kda_pallas.py``)
SOLVE_NAME = "relayrl_kda_solve"

# Rows a sub-chunk: the lane-wise sums cost ``_SUB * K`` products a row and
# the split products one re-weighted copy of the chunk's keys a sub-chunk
# (``chunk / _SUB`` copies); 16 is the source's own sub-chunk.
_SUB = 16
# Heads a step of the map over heads (``gdn._HEADS_A_STEP``'s trade: fewer
# steps of the sequential chunk scan against what a step's backward holds).
_HEADS_A_STEP = 4


def _pair_weights(a, a_rel, k_to, gamma_sub, k_sub, sub_mask):
    """``sum_c a_i[c] k_j[c] e^{Gamma_i[c] - Gamma_j[c]}`` for ``j <= i``
    inside each chunk, ``[..., C, C]`` float32 (zero above the diagonal):
    the split products of rows against the sub-chunks before theirs, plus
    the lane-wise sums inside a sub-chunk. ``a [..., n, s, K]`` float32 (q
    or k by sub-chunk), ``a_rel`` the same re-weighted to its sub-chunk's
    first row (compute dtype), ``k_to [..., n, C, K]`` the chunk's keys
    re-weighted for each sub-chunk (zero from its first row on)."""
    f32 = jnp.float32
    n, s = a.shape[-3], a.shape[-2]
    across = jnp.einsum("...nsk,...njk->...nsj", a_rel, k_to,
                        preferred_element_type=f32)      # [..., n, s, C]
    diff = gamma_sub[..., :, None, :] - gamma_sub[..., None, :, :]
    weight = jnp.exp(jnp.where(sub_mask[..., None], diff, -jnp.inf))
    within = jnp.sum(a[..., :, None, :] * k_sub[..., None, :, :] * weight,
                     axis=-1)                            # [..., n, s, s]
    # sub-chunk I's own block sits at columns I*s .. (I+1)*s
    eye = jnp.eye(n, dtype=f32)
    within = jnp.einsum("...nsj,nm->...nsmj", within, eye).reshape(
        across.shape)
    out = across + within
    return out.reshape(out.shape[:-3] + (n * s, n * s))


@functools.partial(jax.checkpoint, static_argnums=(1,))
def _heads(args, chunk: int):
    """Some heads over whole chunks (``T % chunk == 0``), heads before rows:
    ``q, k, g [b, R, T, K]`` (``g`` float32), ``v [b, R, T, V]``, ``beta [b,
    R, T]`` float32, ``state [b, R, K, V]`` float32 -> ``(o [b, R, T, V],
    last state)``."""
    q, k, v, g, beta, state = args
    b, R, T, V = v.shape
    K = k.shape[3]
    sub = min(_SUB, chunk)
    c, n, cd, f32 = T // chunk, chunk // sub, v.dtype, jnp.float32
    mm = functools.partial(jnp.einsum, preferred_element_type=f32)

    def chunks(a):  # [b, R, T, ...] -> [b, R, c, chunk, ...]
        return a.reshape(a.shape[:2] + (c, chunk) + a.shape[3:])

    def subs(a):    # [b, R, c, chunk, K] -> [b, R, c, n, sub, K]
        return a.reshape(a.shape[:3] + (n, sub) + a.shape[4:])

    qc, kc, vc = (chunks(a).astype(f32) for a in (q, k, v))
    bc = chunks(beta)[..., None]                         # [b, R, c, C, 1]
    gamma = jnp.cumsum(chunks(g), axis=3)                # [b,R,c,C,K] <= 0
    gamma_sub = subs(gamma)
    first = gamma_sub[..., :1, :]                        # Gamma_r a sub-chunk
    rel = jnp.exp(gamma_sub - first)                     # e^(Gamma_i-Gamma_r)
    # the chunk's keys as sub-chunk I sees them: k_j e^(Gamma_r - Gamma_j)
    # for the rows before its first, zero from there on
    before = (jnp.arange(chunk)[None, :]
              < (jnp.arange(n) * sub)[:, None])          # [n, C]
    to_first = jnp.where(
        before[..., None],
        first[..., 0, :][..., :, None, :] - gamma[..., None, :, :], -jnp.inf)
    k_to = (kc[..., None, :, :] * jnp.exp(to_first)).astype(cd)
    k_sub, q_sub = subs(kc), subs(qc)
    lower = jnp.tril(jnp.ones((sub, sub), bool))
    kk = _pair_weights(k_sub, (k_sub * rel).astype(cd), k_to, gamma_sub,
                       k_sub, lower)
    qk = _pair_weights(q_sub, (q_sub * rel).astype(cd), k_to, gamma_sub,
                       k_sub, lower)
    in_decay = jnp.exp(gamma)                            # e^Gamma_i
    to_end = jnp.exp(gamma[..., -1:, :] - gamma)         # e^(Gamma_C-Gamma_i)
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    a_mat = jnp.where(strict, -(bc * kk), 0.0)
    solve = _inverse_unit_lower(a_mat).astype(cd)        # T, [b,R,c,C,C]
    w = mm("bhcij,bhcjk->bhcik", solve,
           (kc * bc * in_decay).astype(cd)).astype(cd)
    u = mm("bhcij,bhcjv->bhciv", solve, (vc * bc).astype(cd))
    scores = qk.astype(cd)                               # causal by its mask
    q_in = (qc * in_decay).astype(cd)
    k_out = (kc * to_end).astype(cd)
    through = in_decay[..., -1, :]                       # [b, R, c, K]

    def carry(s, step):
        w_c, u_c, scores_c, q_c, k_c, through_c = step
        s_cd = s.astype(cd)
        v_new = u_c - mm("bhik,bhkv->bhiv", w_c, s_cd)
        o_c = (mm("bhik,bhkv->bhiv", q_c, s_cd)
               + mm("bhij,bhjv->bhiv", scores_c, v_new.astype(cd)))
        s = (through_c[..., None] * s
             + mm("bhik,bhiv->bhkv", k_c, v_new.astype(cd)))
        return s, o_c

    last, o = jax.lax.scan(
        carry, state,
        tuple(jnp.moveaxis(a, 2, 0)
              for a in (w, u, scores, q_in, k_out, through)))
    o = jnp.moveaxis(o, 0, 2).reshape(b, R, T, V)
    return o.astype(cd), last


def kda_xla(q, k, v, g, beta, chunk: int = 64, state=None):
    """:func:`kda` as plain XLA, ``_HEADS_A_STEP`` heads a step of a
    ``lax.map``: every backend takes it, and the kernels' tests hold them to
    it."""
    if chunk % min(_SUB, chunk):
        raise ValueError(f"a chunk of {chunk} is no whole sub-chunks of "
                         f"{_SUB}")
    with jax.named_scope(KDA_NAME):
        b, T, H, V = v.shape
        K = k.shape[3]
        f32 = jnp.float32
        if state is None:
            state = jnp.zeros((b, H, K, V), f32)
        pad = -T % chunk
        if pad:
            q, k, v, g, beta = (
                jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                for a in (q, k, v, g, beta))
        # heads before rows, as ``gdn_xla``: a chunk of a head is then one
        # contiguous block, a step of the map a slice of the second axis
        q, k, v = (jnp.swapaxes(a, 1, 2) for a in (q, k, v))
        g, beta = (jnp.swapaxes(a.astype(f32), 1, 2) for a in (g, beta))
        R = min(H, _HEADS_A_STEP)
        while H % R:
            R -= 1
        n = H // R

        def by_step(a):  # [b, H, ...] -> [n, b, R, ...]
            return jnp.moveaxis(a.reshape((b, n, R) + a.shape[2:]), 1, 0)

        args = tuple(by_step(a) for a in (q, k, v, g, beta,
                                          state.astype(f32)))
        o, last = jax.lax.map(lambda step: _heads(step, chunk), args)
        o = jnp.swapaxes(jnp.moveaxis(o, 0, 1).reshape(b, H, T + pad, V),
                         1, 2)
        return o[:, :T], jnp.moveaxis(last, 0, 1).reshape(b, H, K, V)


def backend(T: int, H: int, K: int, V: int, chunk: int) -> str:
    """``"kda_pallas"`` or ``"kda_xla"``: what :func:`kda` runs a rule of
    these shapes as on this process's platform. The kernels on a TPU where
    the shapes tile (``kda_pallas.fits``) and there is a whole chunk of
    rows, plain XLA everywhere else — CPU actor hosts, CI, a shape that does
    not tile, a rule shorter than a chunk. Platform and shape decide, nothing
    else: no arch key, no environment variable."""
    if jax.default_backend() != "tpu" or T < chunk:
        return XLA
    from relayrl_tpu.ops import kda_pallas

    return PALLAS if kda_pallas.fits(H, K, V, chunk) else XLA


def kda(q, k, v, g, beta, chunk: int = 64, state=None):
    """``q, k [b, T, H, K]`` (as they enter the rule: normalised and scaled
    by the caller), ``v [b, T, H, V]``, ``g [b, T, H, K]`` (log decay a
    lane, <= 0) and ``beta [b, T, H]`` float32, ``state [b, H, K, V]``
    float32 (None: zeros, a sequence's start) -> ``(o [b, T, H, V]`` in
    ``v``'s dtype, ``last_state [b, H, K, V]`` float32``)``, as
    :func:`backend` says."""
    if q.shape != k.shape or g.shape != k.shape:
        raise ValueError(f"q {q.shape}, k {k.shape} and g {g.shape} are one "
                         f"shape: a decay a key lane")
    T, H, V = v.shape[1:]
    if backend(T, H, k.shape[3], V, chunk) == PALLAS:
        from relayrl_tpu.ops.kda_pallas import kda_pallas

        return kda_pallas(q, k, v, g, beta, chunk, state)
    return kda_xla(q, k, v, g, beta, chunk, state)


def kda_step(q, k, v, g, beta, state):
    """One step of the rule, what :func:`kda` computes at ``T = 1``: ``q,
    k, g [b, H, K]``, ``v [b, H, V]``, ``beta [b, H]``, ``state [b, H, K,
    V]`` float32 -> ``(o [b, H, V], new state)``."""
    with jax.named_scope(KDA_NAME):
        f32 = jnp.float32
        qh, kh = q.astype(f32), k.astype(f32)
        state = jnp.exp(g.astype(f32))[..., None] * state
        v_new = beta.astype(f32)[..., None] * (
            v.astype(f32) - jnp.einsum("bhkv,bhk->bhv", state, kh))
        state = state + kh[..., :, None] * v_new[..., None, :]
        o = jnp.einsum("bhkv,bhk->bhv", state, qh)
        return o.astype(v.dtype), state
