"""MLP actor-critic policies (discrete masked-categorical + continuous).

Capability parity with the reference's REINFORCE kernels
(reference: relayrl_framework/src/native/python/algorithms/REINFORCE/
kernel.py — ``DiscretePolicyNetwork`` 2×128 MLP with masked logits at
:12-46, ``ContinuousPolicyNetwork`` Normal with learned log_std at :49-75,
``BaselineValueNetwork`` at :78-84, and the ``PolicyWith(out)Baseline.step``
ABI at :99-143), built as flax.linen modules with pure step/evaluate
functions instead of TorchScript exports.

Compute notes (TPU): trunks run in the configured compute dtype (bf16 by
default feeds the MXU); log-prob/entropy reductions stay in f32 for
stability; parameters are stored f32.
"""

from __future__ import annotations

from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp
from flax import linen as nn

from relayrl_tpu.models.base import Policy, mlp_sizes, register_model

_ACTIVATIONS = {"tanh": nn.tanh, "relu": nn.relu, "gelu": nn.gelu}

# Large negative fill for invalid actions. The reference uses
# ``logits + (mask - 1) * 1e8`` (kernel.py:29); `where` with a finite fill
# keeps softmax/grad NaN-free in bf16 and under XLA fusion.
_MASK_FILL = -1e9


class MLPTrunk(nn.Module):
    hidden_sizes: Sequence[int]
    activation: str = "tanh"
    compute_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        act = _ACTIVATIONS[self.activation]
        x = x.astype(self.compute_dtype)
        for i, h in enumerate(self.hidden_sizes):
            x = nn.Dense(h, dtype=self.compute_dtype, name=f"dense_{i}")(x)
            x = act(x)
        return x


class DiscreteActorCritic(nn.Module):
    """Masked-categorical policy head + optional value head."""

    act_dim: int
    hidden_sizes: Sequence[int]
    activation: str = "tanh"
    has_critic: bool = True
    compute_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, obs, mask=None):
        trunk = MLPTrunk(self.hidden_sizes, self.activation, self.compute_dtype,
                         name="pi_trunk")(obs)
        logits = nn.Dense(self.act_dim, dtype=self.compute_dtype, name="pi_head")(trunk)
        logits = logits.astype(jnp.float32)
        if mask is not None:
            logits = jnp.where(mask > 0, logits, _MASK_FILL)
        if self.has_critic:
            vtrunk = MLPTrunk(self.hidden_sizes, self.activation, self.compute_dtype,
                              name="vf_trunk")(obs)
            v = nn.Dense(1, dtype=self.compute_dtype, name="vf_head")(vtrunk)
            v = jnp.squeeze(v.astype(jnp.float32), axis=-1)
        else:
            v = jnp.zeros(logits.shape[:-1], dtype=jnp.float32)
        return logits, v


class ContinuousActorCritic(nn.Module):
    """Diagonal-Gaussian policy with learned state-independent log_std
    (ref: kernel.py:49-75) + optional value head."""

    act_dim: int
    hidden_sizes: Sequence[int]
    activation: str = "tanh"
    has_critic: bool = True
    compute_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, obs, mask=None):
        del mask  # masks are a discrete-action concept
        trunk = MLPTrunk(self.hidden_sizes, self.activation, self.compute_dtype,
                         name="pi_trunk")(obs)
        mu = nn.Dense(self.act_dim, dtype=self.compute_dtype, name="pi_head")(trunk)
        mu = mu.astype(jnp.float32)
        log_std = self.param(
            "log_std", lambda _: jnp.full((self.act_dim,), -0.5, jnp.float32)
        )
        if self.has_critic:
            vtrunk = MLPTrunk(self.hidden_sizes, self.activation, self.compute_dtype,
                              name="vf_trunk")(obs)
            v = nn.Dense(1, dtype=self.compute_dtype, name="vf_head")(vtrunk)
            v = jnp.squeeze(v.astype(jnp.float32), axis=-1)
        else:
            v = jnp.zeros(mu.shape[:-1], dtype=jnp.float32)
        return (mu, log_std), v


def _categorical_logp(logits, act):
    logp_all = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(
        logp_all, act[..., None].astype(jnp.int32), axis=-1
    ).squeeze(-1)


def _categorical_entropy(logits):
    logp_all = jax.nn.log_softmax(logits, axis=-1)
    p = jnp.exp(logp_all)
    return -jnp.sum(jnp.where(p > 0, p * logp_all, 0.0), axis=-1)


def _gaussian_logp(mu, log_std, act):
    var = jnp.exp(2 * log_std)
    return jnp.sum(
        -0.5 * (jnp.square(act - mu) / var + 2 * log_std + jnp.log(2 * jnp.pi)),
        axis=-1,
    )


def _gaussian_entropy(log_std, batch_shape):
    ent = jnp.sum(0.5 * (1.0 + jnp.log(2 * jnp.pi)) + log_std)
    return jnp.broadcast_to(ent, batch_shape)


# The gated FFN kinds (arch ``ffn``) by the activation on the gate: the
# dense FFN of models/layers/block.py and the experts of models/moe.py.
GATED_FFN = {"swiglu": nn.silu, "reglu": nn.relu}
# ... and the kinds without a gate, ``down(act(up(x)))``, by their activation
UNGATED_FFN = {"gelu": nn.gelu, "relu2": lambda x: jnp.square(nn.relu(x))}


def _compute_dtype(arch: Mapping[str, Any]):
    name = arch.get("precision", "float32")
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


@register_model("mlp_discrete")
def build_mlp_discrete(arch: Mapping[str, Any]) -> Policy:
    module = DiscreteActorCritic(
        act_dim=int(arch["act_dim"]),
        hidden_sizes=mlp_sizes(arch),
        activation=arch.get("activation", "tanh"),
        has_critic=bool(arch.get("has_critic", True)),
        compute_dtype=_compute_dtype(arch),
    )
    obs_dim = int(arch["obs_dim"])

    def init_params(rng):
        return module.init(rng, jnp.zeros((1, obs_dim), jnp.float32))

    def step(params, rng, obs, mask=None):
        logits, v = module.apply(params, obs, mask)
        act = jax.random.categorical(rng, logits, axis=-1)
        logp = _categorical_logp(logits, act)
        return act, {"logp_a": logp, "v": v}

    def evaluate(params, obs, act, mask=None):
        logits, v = module.apply(params, obs, mask)
        return _categorical_logp(logits, act), _categorical_entropy(logits), v

    def mode(params, obs, mask=None):
        logits, _ = module.apply(params, obs, mask)
        return jnp.argmax(logits, axis=-1)

    return Policy(arch=dict(arch), init_params=init_params, step=step,
                  evaluate=evaluate, mode=mode)


@register_model("mlp_continuous")
def build_mlp_continuous(arch: Mapping[str, Any]) -> Policy:
    module = ContinuousActorCritic(
        act_dim=int(arch["act_dim"]),
        hidden_sizes=mlp_sizes(arch),
        activation=arch.get("activation", "tanh"),
        has_critic=bool(arch.get("has_critic", True)),
        compute_dtype=_compute_dtype(arch),
    )
    obs_dim = int(arch["obs_dim"])

    def init_params(rng):
        return module.init(rng, jnp.zeros((1, obs_dim), jnp.float32))

    def step(params, rng, obs, mask=None):
        (mu, log_std), v = module.apply(params, obs, mask)
        act = mu + jnp.exp(log_std) * jax.random.normal(rng, mu.shape, mu.dtype)
        logp = _gaussian_logp(mu, log_std, act)
        return act, {"logp_a": logp, "v": v}

    def evaluate(params, obs, act, mask=None):
        (mu, log_std), v = module.apply(params, obs, mask)
        logp = _gaussian_logp(mu, log_std, act)
        ent = _gaussian_entropy(log_std, logp.shape)
        return logp, ent, v

    def mode(params, obs, mask=None):
        (mu, _), _ = module.apply(params, obs, mask)
        return mu

    return Policy(arch=dict(arch), init_params=init_params, step=step,
                  evaluate=evaluate, mode=mode)
