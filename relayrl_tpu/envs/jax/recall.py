"""Recall memory task as pure JAX — the on-device twin of
``envs/memory.RecallEnv``.

Integer-derived observations (cue one-hot, query flag, phase fraction), so
with ``noise=0`` (the default) the parity goldens hold this env to FULL
bitwise equality against the numpy twin — observation, reward, and flags —
whenever ``horizon`` is a power of two (the single ``t/horizon`` division
then rounds identically in float32 and float64). The optional distractor
noise draws from the state-carried PRNG key instead of a host ``Generator``
(the one necessarily PRNG-specific departure).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from relayrl_tpu.envs.jax.base import JaxEnv
from relayrl_tpu.envs.spaces import Box, Discrete


class RecallState(NamedTuple):
    cue: jnp.ndarray  # [] int32
    t: jnp.ndarray    # [] int32
    key: jnp.ndarray  # [2] uint32 — consumed only when noise > 0


class JaxRecall(JaxEnv):
    """Remember-the-cue: obs = [cue one-hot (t=0 only), is_query, t/T]."""

    def __init__(self, horizon: int = 8, n_cues: int = 2,
                 noise: float = 0.0):
        if horizon < 2:
            raise ValueError("horizon must be >= 2 (cue step + query step)")
        self.horizon = int(horizon)
        self.max_episode_steps = self.horizon
        self.n_cues = int(n_cues)
        self.noise = float(noise)
        self.observation_space = Box(-np.inf, np.inf,
                                     shape=(self.n_cues + 2,))
        self.action_space = Discrete(self.n_cues)

    def _obs(self, cue, t, noise_key) -> jnp.ndarray:
        if self.noise > 0.0:
            distractor = self.noise * jax.random.normal(
                noise_key, (self.n_cues,), jnp.float32)
        else:
            distractor = jnp.zeros((self.n_cues,), jnp.float32)
        head = jnp.where(t == 0, jax.nn.one_hot(cue, self.n_cues,
                                                dtype=jnp.float32),
                         distractor)
        is_query = (t == self.horizon - 1).astype(jnp.float32)
        phase = t.astype(jnp.float32) / self.horizon
        return jnp.concatenate([head, jnp.stack([is_query, phase])])

    def reset(self, key):
        cue_key, noise_key, carry_key = jax.random.split(key, 3)
        cue = jax.random.randint(cue_key, (), 0, self.n_cues, jnp.int32)
        state = RecallState(cue=cue, t=jnp.int32(0), key=carry_key)
        return state, self._obs(cue, state.t, noise_key)

    def step(self, state, action):
        is_query = state.t == self.horizon - 1
        reward = jnp.where(
            jnp.logical_and(
                is_query,
                jnp.asarray(action).astype(jnp.int32) == state.cue),
            jnp.float32(1.0), jnp.float32(0.0))
        t = state.t + 1
        key, noise_key = jax.random.split(state.key)
        new = RecallState(cue=state.cue, t=t, key=key)
        terminated = t >= self.horizon
        return (new, self._obs(state.cue, t, noise_key), reward,
                terminated, jnp.bool_(False))
