"""Sequence scorers for the RLHF workload plane.

A scorer assigns the whole-generation reward paid at the episode's
terminal boundary (``envs/tokengen.py``). The interface is deliberately
dual-plane:

* ``score_np(tokens, prompt_len, gen_len) -> float`` — host-side, what
  the numpy twin env and the decoupled score stage
  (``rlhf/scheduler.py``) call;
* ``score_jax(tokens, prompt_len, gen_len) -> f32`` — traceable, what
  the pure-JAX env closes into the fused anakin rollout;
* ``score_batch_np(tokens [B, L], prompt_len, gen_lens [B]) -> [B]`` —
  the score stage's batched dispatch (ONE jitted vmap per batch of
  completed generations, the TorchBeast batching insight applied to
  scoring).

Both built-ins route every plane through ONE implementation (the numpy
paths call the same jitted function), so a generation scored on-device,
host-side, or in the decoupled stage earns bit-identical reward — the
parity goldens in tests/test_rlhf.py rely on exactly this.

Built-ins:

* ``ProgrammaticScorer`` ("programmatic") — an all-integer successor-
  pattern count: +1 for every generated non-EOS token equal to
  ``(previous token + 1) % vocab``. Cheap, deterministic, and learnable
  by construction — the CI scorer.
* ``RewardModelScorer`` ("reward_model") — a learned reward model: a
  frozen randomly-initialized transformer critic
  (``transformer_discrete``, ``has_critic=True``) over one-hot token
  sequences; the score is ``tanh(v)`` read at the last generated
  position. It holds its OWN params (never trained, never published) —
  the standard RLHF topology where the RM is a separate frozen network
  from the policy being optimized.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

EOS_TOKEN = 0


class ProgrammaticScorer:
    """Successor-pattern count: the reward-maximizing generation
    continues the prompt's token chain ``t -> (t + 1) % vocab`` for
    ``max_new_tokens`` steps without emitting EOS. Integer arithmetic
    end to end, so every plane agrees bit-for-bit."""

    name = "programmatic"

    def __init__(self, vocab_size: int = 8):
        self.vocab_size = int(vocab_size)

    def score_np(self, tokens, prompt_len: int, gen_len: int) -> float:
        tokens = np.asarray(tokens, np.int32)
        lo, hi = int(prompt_len), int(prompt_len) + int(gen_len)
        gen = tokens[lo:hi]
        prev = tokens[lo - 1:hi - 1]
        correct = (gen == (prev + 1) % self.vocab_size) & (gen != EOS_TOKEN)
        return float(np.sum(correct))

    def score_jax(self, tokens, prompt_len, gen_len):
        tokens = jnp.asarray(tokens, jnp.int32)
        idx = jnp.arange(tokens.shape[0], dtype=jnp.int32)
        in_gen = jnp.logical_and(idx >= prompt_len, idx < prompt_len + gen_len)
        prev = jnp.concatenate([jnp.zeros(1, jnp.int32), tokens[:-1]])
        correct = jnp.logical_and(
            jnp.logical_and(tokens == (prev + 1) % self.vocab_size,
                            tokens != EOS_TOKEN),
            in_gen)
        return jnp.sum(correct).astype(jnp.float32)

    def score_batch_np(self, tokens, prompt_len: int, gen_lens) -> np.ndarray:
        tokens = np.asarray(tokens, np.int32)
        gen_lens = np.asarray(gen_lens, np.int64)
        lo = int(prompt_len)
        idx = np.arange(tokens.shape[1])
        in_gen = (idx[None, :] >= lo) & (idx[None, :] < lo + gen_lens[:, None])
        prev = np.concatenate(
            [np.zeros((tokens.shape[0], 1), np.int32), tokens[:, :-1]],
            axis=1)
        correct = ((tokens == (prev + 1) % self.vocab_size)
                   & (tokens != EOS_TOKEN) & in_gen)
        return np.sum(correct, axis=1).astype(np.float32)


class RewardModelScorer:
    """Frozen transformer reward model over one-hot token sequences.

    ``score = tanh(v[prompt_len + gen_len - 1])`` — the critic head's
    value at the last generated position, squashed so the reward scale
    stays bounded for the V-trace learner regardless of the random
    init. The params are created once from ``seed`` and NEVER updated;
    two instances with the same (shape, seed) score identically, which
    is how the decoupled score stage and a self-contained env can hold
    the same RM without shipping params between them.
    """

    name = "reward_model"

    def __init__(self, vocab_size: int = 8, context_len: int = 11,
                 d_model: int = 32, n_layers: int = 1, n_heads: int = 2,
                 seed: int = 7):
        from relayrl_tpu.models import build_policy

        self.vocab_size = int(vocab_size)
        self.context_len = int(context_len)
        self.arch = {
            "kind": "transformer_discrete",
            "obs_dim": self.vocab_size,
            "act_dim": self.vocab_size,
            "d_model": int(d_model),
            "n_layers": int(n_layers),
            "n_heads": int(n_heads),
            "max_seq_len": self.context_len,
            "has_critic": True,
        }
        self._policy = build_policy(self.arch)
        self.params = self._policy.init_params(jax.random.PRNGKey(int(seed)))
        # One compiled scorer serves every plane: score_np/score_batch_np
        # call these EXACT programs, so host and device scoring can never
        # drift by a ulp (the bit-parity contract of the module docs).
        self._jit_one = jax.jit(self.score_jax)
        self._jit_batch = jax.jit(jax.vmap(self.score_jax,
                                           in_axes=(0, None, 0)))

    def score_jax(self, tokens, prompt_len, gen_len):
        tokens = jnp.asarray(tokens, jnp.int32)
        onehot = jax.nn.one_hot(tokens, self.vocab_size, dtype=jnp.float32)
        # evaluate() is the public sequence ABI: (logp, ent, v) per
        # position; the actions argument only feeds logp, which is
        # discarded — v is the RM readout.
        _logp, _ent, v = self._policy.evaluate(self.params, onehot, tokens)
        read = jnp.clip(prompt_len + gen_len - 1, 0, tokens.shape[-1] - 1)
        return jnp.tanh(v[read])

    def score_np(self, tokens, prompt_len: int, gen_len: int) -> float:
        return float(self._jit_one(np.asarray(tokens, np.int32),
                                   jnp.int32(prompt_len),
                                   jnp.int32(gen_len)))

    def score_batch_np(self, tokens, prompt_len: int, gen_lens) -> np.ndarray:
        tokens = np.ascontiguousarray(np.asarray(tokens, np.int32))
        gen_lens = np.asarray(gen_lens, np.int32)
        return np.asarray(self._jit_batch(tokens, jnp.int32(prompt_len),
                                          gen_lens))


SCORERS = {
    ProgrammaticScorer.name: ProgrammaticScorer,
    RewardModelScorer.name: RewardModelScorer,
}


def make_scorer(name: str, **kwargs):
    """Scorer by registered name (the ``rlhf.scorer`` config knob)."""
    if name not in SCORERS:
        raise ValueError(
            f"unknown scorer {name!r}; registered: {sorted(SCORERS)}")
    return SCORERS[name](**kwargs)
