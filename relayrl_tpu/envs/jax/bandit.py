"""Bandit battery as pure JAX — the on-device twin of ``envs/bandit.py``.

All-integer dynamics (context draw, target-arm residue, 0/1 reward,
flags), so the parity golden holds FULL bitwise equality — observation,
reward, both flags — with no float carve-out (the GridWorld precedent).
One-step episodes make this the fastest regression signal the anakin
tier and the RLHF scheduler can run against: every scanned step crosses
an episode boundary, so autoreset, terminal folding, and credit
assignment are all exercised at the maximum possible rate.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from relayrl_tpu.envs.jax.base import JaxEnv
from relayrl_tpu.envs.spaces import Box, Discrete


class BanditState(NamedTuple):
    ctx: jnp.ndarray  # [] int32


class JaxBandit(JaxEnv):
    """One-step contextual bandit: obs = int32 one-hot context; reward
    1.0 exactly when the arm equals ``(ctx * mult + shift) % n_arms``."""

    def __init__(self, n_contexts: int = 8, n_arms: int = 4,
                 mult: int = 3, shift: int = 1):
        if n_contexts < 1 or n_arms < 2:
            raise ValueError("need n_contexts >= 1 and n_arms >= 2")
        self.n_contexts = int(n_contexts)
        self.n_arms = int(n_arms)
        self.mult = int(mult)
        self.shift = int(shift)
        self.max_episode_steps = 1
        self.observation_space = Box(0, 1, shape=(self.n_contexts,),
                                     dtype=np.int32)
        self.action_space = Discrete(self.n_arms)

    def _obs(self, ctx) -> jnp.ndarray:
        return (jnp.arange(self.n_contexts, dtype=jnp.int32)
                == ctx).astype(jnp.int32)

    def reset(self, key):
        ctx = jax.random.randint(key, (), 0, self.n_contexts, jnp.int32)
        return BanditState(ctx=ctx), self._obs(ctx)

    def step(self, state, action):
        arm = jnp.clip(jnp.asarray(action).astype(jnp.int32), 0,
                       self.n_arms - 1)
        target = (state.ctx * self.mult + self.shift) % self.n_arms
        reward = jnp.where(arm == target, jnp.float32(1.0),
                           jnp.float32(0.0))
        return (state, self._obs(state.ctx), reward, jnp.bool_(True),
                jnp.bool_(False))
