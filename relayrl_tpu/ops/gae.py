"""Discounted-return / GAE-λ ops on fixed-shape padded batches.

Capability parity with the reference's replay-buffer math
(reference: relayrl_framework/src/native/python/_common/_algorithms/
BaseReplayBuffer.py:6-83 ``discount_cumsum`` via scipy lfilter, and
algorithms/REINFORCE/replay_buffer.py:48-79 GAE-λ + rewards-to-go on
``finish_path``), re-designed for XLA: the reference runs scipy on Python
lists per episode; here everything is element-wise over padded ``[B, T]``
device arrays with a validity mask, the discounted sums a log-depth reverse
recurrence (:mod:`relayrl_tpu.ops.recurrence`, the one V-trace runs with a
per-step coefficient; no loop in the compiled program), so the whole
epoch's advantage computation compiles into the learner step (no host round
trip, no per-length recompilation — see SURVEY.md §7.4 item 3).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from relayrl_tpu.ops.recurrence import reverse_linear_recurrence


def discount_cumsum(x: jax.Array, discount: float, axis: int = -1) -> jax.Array:
    """Reverse discounted cumulative sum along ``axis``.

    ``out[t] = sum_k discount^k * x[t+k]`` — the scipy ``lfilter`` identity
    the reference uses, as the log-depth recurrence of
    :mod:`relayrl_tpu.ops.recurrence` with a constant coefficient.
    """
    x = jnp.moveaxis(x, axis, -1)
    out = reverse_linear_recurrence(jnp.full_like(x, discount), x)
    return jnp.moveaxis(out, -1, axis)


def rewards_to_go(rew: jax.Array, valid: jax.Array, gamma: float) -> jax.Array:
    """Masked discounted rewards-to-go over time axis -1 of ``[..., T]``.

    Padding steps (valid == 0) contribute nothing and receive 0.
    """
    rew = rew * valid
    return discount_cumsum(rew, gamma) * valid


def gae_advantages(
    rew: jax.Array,
    val: jax.Array,
    valid: jax.Array,
    gamma: float,
    lam: float,
    last_val: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """GAE-λ advantages + return targets on padded ``[..., T]`` arrays.

    ``val`` are the critic values stored at sample time (the reference keeps
    them in the action's aux dict — REINFORCE.py uses ``data['v']``).
    ``last_val`` bootstraps truncated episodes (0 for terminal, matching the
    reference's ``finish_path(last_val=0)`` on done).

    Returns ``(adv, ret)`` where ``ret`` are value-function targets
    (rewards-to-go), both zeroed on padding.
    """
    rew = rew * valid
    val = val * valid
    if last_val is None:
        last_val = jnp.zeros(rew.shape[:-1], dtype=rew.dtype)
    # v_{t+1}: shift left; the value after the last valid step is last_val.
    # Padding vals are 0, so placing last_val exactly at the episode boundary
    # is handled by adding it at the final valid index.
    val_next = jnp.concatenate(
        [val[..., 1:], last_val[..., None]], axis=-1
    )
    # At t == length-1 (final valid step), val[t+1] in the padded array is 0;
    # inject the bootstrap there instead.
    lengths = jnp.sum(valid, axis=-1).astype(jnp.int32)
    t_idx = jnp.arange(rew.shape[-1])
    is_last = (t_idx == (lengths[..., None] - 1)) & (valid > 0)
    val_next = jnp.where(is_last, last_val[..., None], val_next)

    delta = (rew + gamma * val_next - val) * valid
    adv = discount_cumsum(delta, gamma * lam) * valid
    ret = rewards_to_go(rew, valid, gamma)
    return adv, ret


def masked_mean_std(x: jax.Array, valid: jax.Array, eps: float = 1e-8):
    """Mean/std over valid entries only."""
    count = jnp.maximum(jnp.sum(valid), 1.0)
    mean = jnp.sum(x * valid) / count
    var = jnp.sum(jnp.square(x - mean) * valid) / count
    return mean, jnp.sqrt(var + eps)


def normalize_advantages(adv: jax.Array, valid: jax.Array) -> jax.Array:
    """Advantage normalization over the valid set
    (ref: replay_buffer.py:81-111 normalizes with buffer statistics)."""
    mean, std = masked_mean_std(adv, valid)
    return (adv - mean) / std * valid
