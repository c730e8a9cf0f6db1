"""Pendulum-v1 swing-up as pure JAX — the on-device twin of
``envs/classic.PendulumEnv``.

Same torque-limited dynamics in the same operation order (constants
imported from the numpy class), float32 throughout; the reward is computed
from the PRE-update angle exactly like the numpy twin. Continuous action:
anything that squeezes to a scalar (the MLP-continuous policy emits
``[1]``).
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import jax.random
import numpy as np

from relayrl_tpu.envs.classic import PendulumEnv
from relayrl_tpu.envs.jax.base import JaxEnv
from relayrl_tpu.envs.spaces import Box


class PendulumState(NamedTuple):
    theta: jnp.ndarray      # [] float32
    theta_dot: jnp.ndarray  # [] float32
    t: jnp.ndarray          # [] int32


class JaxPendulum(JaxEnv):
    """Functional pendulum swing-up, Gymnasium Pendulum-v1 semantics."""

    def __init__(self, max_steps: int | None = None):
        c = PendulumEnv
        high = np.array([1.0, 1.0, c.MAX_SPEED], np.float32)
        self.observation_space = Box(-high, high)
        self.action_space = Box(-c.MAX_TORQUE, c.MAX_TORQUE, shape=(1,))
        self.max_steps = int(max_steps or c.MAX_STEPS)
        self.max_episode_steps = self.max_steps

    def reset(self, key):
        k_theta, k_vel = jax.random.split(key)
        theta = jax.random.uniform(k_theta, (), jnp.float32, -np.pi, np.pi)
        theta_dot = jax.random.uniform(k_vel, (), jnp.float32, -1.0, 1.0)
        state = PendulumState(theta=theta, theta_dot=theta_dot,
                              t=jnp.int32(0))
        return state, self._obs(state)

    def step(self, state, action):
        c = PendulumEnv
        u = jnp.clip(
            jnp.squeeze(jnp.asarray(action, jnp.float32)),
            -c.MAX_TORQUE, c.MAX_TORQUE)
        theta, theta_dot = state.theta, state.theta_dot
        norm_theta = ((theta + np.pi) % (2 * np.pi)) - np.pi
        cost = norm_theta**2 + 0.1 * theta_dot**2 + 0.001 * u**2

        theta_dot = theta_dot + (
            3 * c.G / (2 * c.L) * jnp.sin(theta)
            + 3.0 / (c.M * c.L**2) * u
        ) * c.DT
        theta_dot = jnp.clip(theta_dot, -c.MAX_SPEED, c.MAX_SPEED)
        theta = theta + theta_dot * c.DT
        t = state.t + 1
        new = PendulumState(theta=theta, theta_dot=theta_dot, t=t)
        return (new, self._obs(new), -cost,
                jnp.bool_(False), t >= self.max_steps)

    def _obs(self, state: PendulumState) -> jnp.ndarray:
        return jnp.stack([jnp.cos(state.theta), jnp.sin(state.theta),
                          state.theta_dot])
