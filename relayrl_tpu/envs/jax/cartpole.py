"""CartPole-v1 dynamics as pure JAX — the on-device twin of
``envs/classic.CartPoleEnv``.

The step math is the same Barto-Sutton-Anderson equations in the same
operation order (the parity goldens diff the two step for step); physics
constants are imported from the numpy class so the twins can never drift
apart. Computation is float32 throughout — see the precision note in
``envs/jax/base.py``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from relayrl_tpu.envs.classic import CartPoleEnv
from relayrl_tpu.envs.jax.base import JaxEnv
from relayrl_tpu.envs.spaces import Box, Discrete

import numpy as np


class CartPoleState(NamedTuple):
    state: jnp.ndarray  # [4] float32: x, x_dot, theta, theta_dot
    t: jnp.ndarray      # [] int32 steps taken this episode


class JaxCartPole(JaxEnv):
    """Functional cart-pole, Gymnasium CartPole-v1 semantics."""

    def __init__(self, max_steps: int | None = None):
        self.observation_space = Box(-np.inf, np.inf, shape=(4,))
        self.action_space = Discrete(2)
        self.max_steps = int(max_steps or CartPoleEnv.MAX_STEPS)
        self.max_episode_steps = self.max_steps

    def reset(self, key):
        state = jax.random.uniform(key, (4,), jnp.float32, -0.05, 0.05)
        return CartPoleState(state=state, t=jnp.int32(0)), state

    def step(self, state, action):
        c = CartPoleEnv
        x, x_dot, theta, theta_dot = (state.state[0], state.state[1],
                                      state.state[2], state.state[3])
        force = jnp.where(jnp.asarray(action).astype(jnp.int32) == 1,
                          jnp.float32(c.FORCE_MAG), jnp.float32(-c.FORCE_MAG))
        cos_t, sin_t = jnp.cos(theta), jnp.sin(theta)
        total_mass = c.MASS_CART + c.MASS_POLE
        pole_ml = c.MASS_POLE * c.HALF_LENGTH

        temp = (force + pole_ml * theta_dot**2 * sin_t) / total_mass
        theta_acc = (c.GRAVITY * sin_t - cos_t * temp) / (
            c.HALF_LENGTH * (4.0 / 3.0 - c.MASS_POLE * cos_t**2 / total_mass)
        )
        x_acc = temp - pole_ml * theta_acc * cos_t / total_mass

        x = x + c.TAU * x_dot
        x_dot = x_dot + c.TAU * x_acc
        theta = theta + c.TAU * theta_dot
        theta_dot = theta_dot + c.TAU * theta_acc
        new = jnp.stack([x, x_dot, theta, theta_dot])
        t = state.t + 1

        terminated = jnp.logical_or(jnp.abs(x) > c.X_LIMIT,
                                    jnp.abs(theta) > c.THETA_LIMIT)
        # Independent flags, exactly like the numpy twin (Gymnasium allows
        # both true on the same step; terminated-beats-truncated precedence
        # is the consumer's job — flag_last_action / the anakin unstacker).
        truncated = t >= self.max_steps
        return (CartPoleState(state=new, t=t), new, jnp.float32(1.0),
                terminated, truncated)
