"""V-trace off-policy correction (IMPALA) on fixed-shape padded batches.

No counterpart in the reference (its only learner is synchronous REINFORCE —
SURVEY.md §2.5); this op is what makes the async actor fleet of the
BASELINE.json north-star configs ("IMPALA-style async A2C, 256 actors")
correct: actors run stale policies, and V-trace importance-weights their
trajectories back to the learner's current policy with clipped ratios.

Everything is element-wise over ``[B, T]`` arrays with a validity mask —
the same padded-batch discipline as :mod:`relayrl_tpu.ops.gae` (no
per-length recompilation, SURVEY.md §7.4 item 3) — but the reverse
recursion ``a_t = delta_t + gamma c_t a_{t+1}``, which is
:func:`relayrl_tpu.ops.recurrence.reverse_linear_recurrence`: ceil(log2 T)
whole-array steps along the last axis, T on the lanes, where it was a
``lax.scan`` of T dependent steps until PR 38 (16,384 of them, 35.6 ms of a
407 ms update, at one 16k-token episode a batch). A device trace shows no
loop under ``relayrl_vtrace`` any more (no ``while/while`` row of its own).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from relayrl_tpu.ops.recurrence import reverse_linear_recurrence
from relayrl_tpu.ops.scopes import VTRACE


class VTraceReturns(NamedTuple):
    vs: jax.Array       # [B, T] value targets
    pg_adv: jax.Array   # [B, T] policy-gradient advantages (rho-clipped)
    rho: jax.Array      # [B, T] clipped importance ratios (diagnostic)


def vtrace(
    behavior_logp: jax.Array,
    target_logp: jax.Array,
    rew: jax.Array,
    val: jax.Array,
    valid: jax.Array,
    gamma: float,
    last_val: jax.Array | None = None,
    rho_bar: float = 1.0,
    c_bar: float = 1.0,
) -> VTraceReturns:
    """Compute V-trace targets/advantages.

    ``behavior_logp`` is the actor-side log-prob stored at sample time
    (the ``logp_a`` aux the trajectory already carries); ``target_logp``
    the learner policy's log-prob of the same actions; ``val`` the learner
    critic's values v(x_t). With behavior == target and ``rho_bar, c_bar >=
    1`` the recursion telescopes to the on-policy n-step return.

    The recursion runs in log depth while ``gamma * c_bar <= 1`` (every
    shipped configuration: the coefficients' products over a span then only
    shrink). With ``gamma * c_bar > 1`` — both Python floats, seen at trace
    time — such a product can overflow where the step-by-step recursion
    stays finite, and the recursion is the sequential scan of T steps.
    """
    with jax.named_scope(VTRACE):
        rew = rew * valid
        val = val * valid
        if last_val is None:
            last_val = jnp.zeros(rew.shape[:-1], rew.dtype)

        log_rho = jnp.where(valid > 0, target_logp - behavior_logp, 0.0)
        ratio = jnp.exp(log_rho)
        rho = jnp.minimum(rho_bar, ratio) * valid
        c = jnp.minimum(c_bar, ratio) * valid

        # v_{t+1} with the bootstrap injected at the last valid step (same
        # construction as ops/gae.gae_advantages).
        lengths = jnp.sum(valid, axis=-1).astype(jnp.int32)
        t_idx = jnp.arange(rew.shape[-1])
        is_last = (t_idx == (lengths[..., None] - 1)) & (valid > 0)
        val_next = jnp.concatenate([val[..., 1:], last_val[..., None]],
                                   axis=-1)
        val_next = jnp.where(is_last, last_val[..., None], val_next)

        delta = rho * (rew + gamma * val_next - val) * valid

        # Reverse recursion: a_t = delta_t + gamma c_t a_{t+1}, vs = v + a
        # (delta and c are zero on padding, so a is too).
        a = reverse_linear_recurrence(gamma * c, delta,
                                      sequential=gamma * c_bar > 1.0)
        vs = (val + a) * valid

        # vs_{t+1} for the pg advantage, bootstrapping the last valid step.
        vs_next = jnp.concatenate([vs[..., 1:], last_val[..., None]], axis=-1)
        vs_next = jnp.where(is_last, last_val[..., None], vs_next)
        pg_adv = rho * (rew + gamma * vs_next - val) * valid
    return VTraceReturns(vs=vs, pg_adv=pg_adv, rho=rho)
