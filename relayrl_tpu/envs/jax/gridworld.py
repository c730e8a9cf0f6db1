"""GridWorld as pure JAX — the on-device twin of
``envs/gridworld.GridWorldEnv``.

All-integer dynamics (int32 positions, clamped moves, exactly-integral
rewards), so the parity golden holds this env to FULL bitwise equality
against the numpy twin — observation, reward, and both flags — with no
float-tolerance carve-out. The int32 ``[row, col]`` observation is the
point: under the anakin tier it rides the columnar trajectory wire as an
int32 column (types/columnar.py), exercising the non-float obs path end
to end.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from relayrl_tpu.envs.jax.base import JaxEnv
from relayrl_tpu.envs.spaces import Box, Discrete

# Same action table as the numpy twin (envs/gridworld.MOVES).
_MOVES = jnp.array([[-1, 0], [1, 0], [0, -1], [0, 1]], jnp.int32)


class GridWorldState(NamedTuple):
    pos: jnp.ndarray  # [2] int32
    t: jnp.ndarray    # [] int32


class JaxGridWorld(JaxEnv):
    """Reach the corner: obs = int32 ``[row, col]``; actions
    up/down/left/right; reward 1.0 exactly at the goal."""

    def __init__(self, size: int = 5, max_steps: int = 50):
        if size < 2:
            raise ValueError("size must be >= 2 (start and goal differ)")
        self.size = int(size)
        self.max_steps = int(max_steps)
        self.max_episode_steps = self.max_steps
        self.observation_space = Box(0, self.size - 1, shape=(2,),
                                     dtype=np.int32)
        self.action_space = Discrete(4)

    def reset(self, key):
        # Uniform over the non-goal cells (the goal owns the last linear
        # index) — the same distribution the numpy twin draws from.
        idx = jax.random.randint(key, (), 0, self.size * self.size - 1,
                                 jnp.int32)
        pos = jnp.stack([idx // self.size, idx % self.size])
        state = GridWorldState(pos=pos.astype(jnp.int32), t=jnp.int32(0))
        return state, state.pos

    def step(self, state, action):
        move = _MOVES[jnp.asarray(action).astype(jnp.int32)]
        pos = jnp.clip(state.pos + move, 0, self.size - 1)
        t = state.t + 1
        terminated = jnp.all(pos == self.size - 1)
        reward = jnp.where(terminated, jnp.float32(1.0), jnp.float32(0.0))
        truncated = t >= self.max_steps
        return (GridWorldState(pos=pos, t=t), pos, reward,
                terminated, truncated)
