"""The on-device environment ABI: pure functions over pytree state.

The host-bound built-ins (``envs/classic.py``, ``envs/memory.py``) step one
Python call at a time — ~30k env-steps/s end to end even behind the vector
actor host, because ``SyncVectorEnv`` batches the *policy* dispatch while
each env lane remains a numpy loop. The Podracer Anakin pattern
(arXiv:2104.06272) and Jumanji (arXiv:2306.09884) move the env itself onto
the device: dynamics become jittable pure functions, whole trajectory
windows fuse into one ``jit(vmap(lax.scan(policy ∘ env.step)))`` dispatch
(``runtime/anakin.py``), and lanes never leave the chip mid-window.

ABI (functional, Jumanji/gymnax-shaped, Gymnasium field semantics)::

    reset(key)          -> (state, obs)
    step(state, action) -> (state, obs, reward, terminated, truncated)

* ``state`` is a NamedTuple of arrays (lax.scan-able: fixed shapes/dtypes,
  no Python objects). ``step`` is deterministic given ``state`` — all
  stochasticity enters through ``reset(key)`` (and, for envs with
  observation noise, a key field carried *inside* the state).
* ``reward``/``terminated``/``truncated`` follow the numpy built-ins'
  Gymnasium step contract exactly, field for field — the dynamics-parity
  goldens (tests/test_jax_envs.py) hold each JAX env against its numpy
  twin step for step.
* Dtypes are pinned: float32 observations/rewards, int32 counters, bool
  flags. The numpy built-ins compute in float64 and round at the obs
  boundary; XLA also contracts mul+add chains into FMAs — so continuous
  observations agree to a few float32 ulp per step (measured ≤2 ulp on
  this backend, asserted by the goldens), while every discrete field
  (rewards where integral, flags, counters, Recall's whole observation)
  is exactly equal. Within the JAX path itself, same seed + same compiled
  program ⇒ byte-identical trajectories across processes.

``step_autoreset`` is the in-scan episode-boundary composition: a done
lane resets *inside the same scan iteration* via ``jnp.where`` masking
(under ``vmap``, ``lax.cond`` lowers to select anyway — computing the
cheap reset unconditionally keeps one fused program), so lanes never
leave the device between episodes. It mirrors ``SyncVectorEnv``'s
autoreset surface: the returned ``obs`` is already the next episode's
first observation and the pre-reset observation rides alongside for
time-limit bootstrapping.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp


class JaxEnv:
    """Base class carrying the space metadata; subclasses implement the
    functional ``reset``/``step`` pair. Instances hold only static
    configuration (horizon, physics constants) — never per-episode state —
    so one instance serves every lane of a fused rollout.

    ``max_episode_steps`` states the most ``step`` calls an episode can
    take before it reports ``terminated`` or ``truncated`` (None: no limit
    known). The fused rollout reads it to decide whether a sequence
    policy's history can outgrow its window (``runtime/anakin.py``)."""

    observation_space: Any
    action_space: Any
    max_episode_steps: int | None = None

    @property
    def obs_dim(self) -> int:
        return int(self.observation_space.shape[0])

    def reset(self, key) -> tuple[NamedTuple, jnp.ndarray]:
        raise NotImplementedError

    def step(self, state, action):
        raise NotImplementedError


def tree_where(pred, on_true, on_false):
    """Per-leaf ``jnp.where`` over two same-structure pytrees; ``pred`` is
    a scalar (or broadcastable) bool. The masking primitive the in-scan
    autoreset is built from."""
    return jax.tree.map(lambda a, b: jnp.where(pred, a, b),
                        on_true, on_false)


def step_autoreset(env: JaxEnv, key, state, action):
    """One env step with the episode boundary folded into the scan body.

    Returns ``(key, state, obs, reward, terminated, truncated,
    final_obs)`` where, for a lane that just finished, ``state``/``obs``
    are already the NEXT episode's reset state/observation (seeded from a
    fresh split of ``key`` — the per-lane key stream makes every lane's
    episode sequence reproducible from the rollout seed alone) and
    ``final_obs`` is the pre-reset observation (the ``final_observation``
    of the Gymnasium VectorEnv convention, needed for time-limit
    bootstrapping). For an unfinished lane, ``final_obs`` equals ``obs``
    and the reset branch is masked out by ``jnp.where``.

    The key splits every step, done or not: a data-dependent split count
    would make the key stream depend on episode lengths, breaking the
    fixed-seed reproducibility contract the determinism goldens pin.
    """
    stepped_state, stepped_obs, reward, terminated, truncated = env.step(
        state, action)
    done = jnp.logical_or(terminated, truncated)
    key, reset_key = jax.random.split(key)
    reset_state, reset_obs = env.reset(reset_key)
    next_state = tree_where(done, reset_state, stepped_state)
    next_obs = jnp.where(done, reset_obs, stepped_obs)
    return (key, next_state, next_obs, reward, terminated, truncated,
            stepped_obs)
