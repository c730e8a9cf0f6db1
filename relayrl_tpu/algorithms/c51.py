"""C51 (categorical distributional DQN) as a jitted XLA program.

Fills the reference's registry slot (whitelisted, never implemented —
relayrl_framework/src/sys_utils/config_loader.rs:148-159). The categorical
projection of the Bellman-updated support onto the fixed atom grid is
expressed as two one-hot matmuls (scatter-free, MXU-friendly) so the whole
update — target distribution, projection, cross-entropy, Adam, polyak —
compiles into one device program.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import optax
from flax import struct

from relayrl_tpu.algorithms.base import register_algorithm
from relayrl_tpu.algorithms.offpolicy import (
    EpsilonGreedyMixin,
    OffPolicyAlgorithm,
    polyak_update,
)
from relayrl_tpu.models import build_policy
from relayrl_tpu.models.mlp import _MASK_FILL, _compute_dtype
from relayrl_tpu.models.q_networks import DistributionalQNet


class C51State(struct.PyTreeNode):
    params: Any
    target_params: Any
    opt_state: Any
    step: jax.Array


def categorical_projection(support: jax.Array, probs: jax.Array,
                           rew: jax.Array, done: jax.Array,
                           gamma: float) -> jax.Array:
    """Project ``T z = r + gamma (1-d) z`` back onto ``support``.

    ``probs [B, N]`` is the next-state distribution of the chosen action;
    returns the projected target distribution ``[B, N]``. One-hot matmul
    formulation: each source atom j splits its mass between floor/ceil
    neighbor bins of its Bellman-updated position.
    """
    n = support.shape[0]
    v_min, v_max = support[0], support[-1]
    dz = (v_max - v_min) / (n - 1)
    tz = jnp.clip(rew[:, None] + gamma * (1.0 - done[:, None]) * support[None],
                  v_min, v_max)
    b = (tz - v_min) / dz                      # [B, N] fractional bin
    low = jnp.floor(b)
    high = jnp.ceil(b)
    # When b lands exactly on a bin (low == high) give it all mass via the
    # `low` branch: weight_low = (high - b) + (low == high).
    w_low = (high - b) + (low == high).astype(b.dtype)
    w_high = b - low
    onehot_low = jax.nn.one_hot(low.astype(jnp.int32), n, dtype=b.dtype)
    onehot_high = jax.nn.one_hot(high.astype(jnp.int32), n, dtype=b.dtype)
    # [B, N_src] x [B, N_src, N_bin] -> [B, N_bin]
    return jnp.einsum("bj,bjn->bn", probs * w_low, onehot_low) + jnp.einsum(
        "bj,bjn->bn", probs * w_high, onehot_high)


def make_c51_update(module: DistributionalQNet, support: jax.Array,
                    gamma: float, lr: float, polyak: float):
    tx = optax.adam(lr)

    def c51_update(state: C51State, batch):
        obs, act, rew = batch["obs"], batch["act"], batch["rew"]
        obs2, mask2, done = batch["obs2"], batch["mask2"], batch["done"]

        logits2 = module.apply(state.target_params, obs2)   # [B, A, N]
        probs2 = jax.nn.softmax(logits2, axis=-1)
        q2 = jnp.sum(probs2 * support, axis=-1)             # [B, A]
        a2 = jnp.argmax(jnp.where(mask2 > 0, q2, _MASK_FILL), axis=-1)
        probs2_a = jnp.take_along_axis(
            probs2, a2[:, None, None], axis=1).squeeze(1)   # [B, N]
        target_dist = categorical_projection(support, probs2_a, rew, done,
                                             gamma)

        def loss_fn(params):
            logits = module.apply(params, obs)              # [B, A, N]
            logp = jax.nn.log_softmax(logits, axis=-1)
            logp_a = jnp.take_along_axis(
                logp, act[:, None, None].astype(jnp.int32), axis=1).squeeze(1)
            loss = -jnp.mean(jnp.sum(target_dist * logp_a, axis=-1))
            q_a = jnp.sum(jnp.exp(logp_a) * support, axis=-1)
            return loss, q_a

        (loss, q_a), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params)
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        target_params = polyak_update(params, state.target_params, polyak)
        metrics = {"LossQ": loss, "QVals": jnp.mean(q_a)}
        return C51State(params=params, target_params=target_params,
                        opt_state=opt_state, step=state.step + 1), metrics

    return c51_update


@register_algorithm("C51")
class C51(EpsilonGreedyMixin, OffPolicyAlgorithm):
    ALGO_NAME = "C51"
    DEFAULT_DISCRETE = True

    def _setup(self, params: dict, learner: dict) -> None:
        eps0 = self._setup_epsilon(params)
        n_atoms = int(params.get("n_atoms", 51))
        self.arch = {
            "kind": "c51_discrete",
            "obs_dim": self.obs_dim,
            "act_dim": self.act_dim,
            "hidden_sizes": list(params.get("hidden_sizes", [128, 128])),
            "n_atoms": n_atoms,
            "v_min": float(params.get("v_min", -10.0)),
            "v_max": float(params.get("v_max", 10.0)),
            "epsilon": eps0,
            "precision": str(learner.get("precision", "float32")),
        }
        from relayrl_tpu.models.q_networks import (
            PIXEL_ARCH_KEYS,
            conv_trunk_kwargs,
        )

        for key in PIXEL_ARCH_KEYS:
            if key in params:
                self.arch[key] = params[key]
        self.policy = build_policy(self.arch)

        self._module = DistributionalQNet(
            act_dim=self.act_dim,
            n_atoms=n_atoms,
            hidden_sizes=tuple(self.arch["hidden_sizes"]),
            compute_dtype=_compute_dtype(self.arch),
            **conv_trunk_kwargs(self.arch))
        support = jnp.linspace(self.arch["v_min"], self.arch["v_max"], n_atoms)
        net_params = self.policy.init_params(self._rng_init)
        tx = optax.adam(float(params.get("lr", 1e-3)))
        self.state = C51State(
            params=net_params,
            target_params=jax.tree.map(jnp.copy, net_params),
            opt_state=tx.init(net_params),
            step=jnp.int32(0),
        )
        update = make_c51_update(
            self._module, support,
            gamma=self.gamma,
            lr=float(params.get("lr", 1e-3)),
            polyak=self.polyak,
        )
        self._update = jax.jit(update, donate_argnums=0)

    def _actor_params(self):
        return self.state.params
