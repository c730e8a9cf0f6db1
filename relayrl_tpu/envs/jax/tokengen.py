"""TokenGen as pure JAX — the on-device twin of ``envs/tokengen.py``.

All-integer dynamics (prompt sampling, token buffer writes, flags), so
the parity goldens hold this env to FULL bitwise equality against the
numpy twin on observation/flags/counters from injected states. The
reward is paid by the pluggable scorer at the terminal step; the
built-in scorers (relayrl_tpu/envs/scorers.py) expose one jitted
implementation to both planes, so the scored reward is bit-equal too.

``scorer.score_jax(tokens, prompt_len, gen_len)`` must be traceable
(pure function of the int32 token buffer; ``prompt_len`` arrives as a
static Python int). A :class:`~relayrl_tpu.envs.scorers.
RewardModelScorer` closes over its frozen transformer params — static
per-instance configuration under the JaxEnv contract, exactly like
physics constants — so the whole episode, scoring included, fuses into
the anakin ``jit(vmap(lax.scan))`` rollout.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from relayrl_tpu.envs.jax.base import JaxEnv
from relayrl_tpu.envs.spaces import Box, Discrete
from relayrl_tpu.envs.tokengen import EOS_TOKEN, _resolve_scorer


class TokenGenState(NamedTuple):
    tokens: jnp.ndarray  # [prompt_len + max_new_tokens] int32
    t: jnp.ndarray       # [] int32 — generated-token count


class JaxTokenGen(JaxEnv):
    """One generation per episode: obs = int32 token context window,
    action = next token, terminal at EOS/max_new_tokens (both are
    ``terminated`` — the scorer pays the full return at the boundary,
    there is nothing to bootstrap through)."""

    def __init__(self, vocab_size: int = 8, prompt_len: int = 3,
                 max_new_tokens: int = 8, scorer=None):
        if vocab_size < 2:
            raise ValueError("vocab_size must be >= 2 (EOS + 1 real token)")
        if prompt_len < 1 or max_new_tokens < 1:
            raise ValueError("prompt_len and max_new_tokens must be >= 1")
        self.vocab_size = int(vocab_size)
        self.prompt_len = int(prompt_len)
        self.max_new_tokens = int(max_new_tokens)
        self.max_episode_steps = self.max_new_tokens
        self.context_len = self.prompt_len + self.max_new_tokens
        self.scorer = _resolve_scorer(scorer)
        if (self.scorer is not None
                and not callable(getattr(self.scorer, "score_jax", None))):
            raise ValueError(
                "the on-device TokenGen needs a traceable scorer "
                "(score_jax); host-only callables serve the numpy twin / "
                "the decoupled score stage (rlhf/scheduler.py)")
        self.observation_space = Box(0, self.vocab_size - 1,
                                     shape=(self.context_len,),
                                     dtype=np.int32)
        self.action_space = Discrete(self.vocab_size)

    def reset(self, key):
        prompt = jax.random.randint(key, (self.prompt_len,), 1,
                                    self.vocab_size, jnp.int32)
        tokens = jnp.zeros(self.context_len, jnp.int32)
        tokens = jax.lax.dynamic_update_slice_in_dim(tokens, prompt, 0,
                                                     axis=0)
        state = TokenGenState(tokens=tokens, t=jnp.int32(0))
        return state, tokens

    def step(self, state, action):
        token = jnp.clip(jnp.asarray(action).astype(jnp.int32), 0,
                         self.vocab_size - 1)
        tokens = state.tokens.at[self.prompt_len + state.t].set(token)
        t = state.t + 1
        terminated = jnp.logical_or(token == EOS_TOKEN,
                                    t >= self.max_new_tokens)
        if self.scorer is not None:
            reward = jnp.where(
                terminated,
                jnp.asarray(self.scorer.score_jax(tokens, self.prompt_len, t),
                            jnp.float32),
                jnp.float32(0.0))
        else:
            reward = jnp.float32(0.0)
        new = TokenGenState(tokens=tokens, t=t)
        return new, tokens, reward, terminated, jnp.bool_(False)
