"""DQN (+ double-Q) as a jitted XLA program.

Fills the reference's registry slot (whitelisted, never implemented —
relayrl_framework/src/sys_utils/config_loader.rs:148-159). One jitted
update: Huber TD loss on Q(s,a) against a (double-)Q target, Adam, and a
polyak-averaged target network — all fused into a single device program per
gradient step. Actors receive the Q-net as an epsilon-greedy
``qnet_discrete`` policy whose epsilon the learner anneals linearly per
publish (exploration rides the arch config, not actor code).
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
from relayrl_tpu.models.q_networks import DiscreteQNet


class DQNState(struct.PyTreeNode):
    params: Any
    target_params: Any
    opt_state: Any
    step: jax.Array


def make_dqn_update(module: DiscreteQNet, gamma: float, lr: float,
                    polyak: float, double_q: bool):
    tx = optax.adam(lr)

    def dqn_update(state: DQNState, batch):
        obs, act, rew = batch["obs"], batch["act"], batch["rew"]
        obs2, mask2, done = batch["obs2"], batch["mask2"], batch["done"]

        q2_target = module.apply(state.target_params, obs2)
        q2_target_masked = jnp.where(mask2 > 0, q2_target, _MASK_FILL)
        if double_q:
            q2_online = module.apply(state.params, obs2)
            a2 = jnp.argmax(jnp.where(mask2 > 0, q2_online, _MASK_FILL), -1)
            next_q = jnp.take_along_axis(
                q2_target, a2[..., None], axis=-1).squeeze(-1)
        else:
            next_q = jnp.max(q2_target_masked, axis=-1)
        target = rew + gamma * (1.0 - done) * next_q

        def loss_fn(params):
            q = module.apply(params, obs)
            q_a = jnp.take_along_axis(
                q, act[..., None].astype(jnp.int32), axis=-1).squeeze(-1)
            return jnp.mean(optax.huber_loss(q_a, target)), q_a

        (loss, q_a), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params)
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        target_params = polyak_update(params, state.target_params, polyak)
        metrics = {"LossQ": loss, "QVals": jnp.mean(q_a)}
        return DQNState(params=params, target_params=target_params,
                        opt_state=opt_state, step=state.step + 1), metrics

    return dqn_update


@register_algorithm("DQN")
class DQN(EpsilonGreedyMixin, OffPolicyAlgorithm):
    ALGO_NAME = "DQN"
    DEFAULT_DISCRETE = True

    def _setup(self, params: dict, learner: dict) -> None:
        eps0 = self._setup_epsilon(params)
        self.arch = {
            "kind": "qnet_discrete",
            "obs_dim": self.obs_dim,
            "act_dim": self.act_dim,
            "hidden_sizes": list(params.get("hidden_sizes", [128, 128])),
            "epsilon": eps0,
            "precision": str(learner.get("precision", "float32")),
        }
        # Pixel variant: obs_shape switches the q-net to the Nature conv
        # trunk (same arch keys as the cnn_discrete family).
        from relayrl_tpu.models.q_networks import (
            PIXEL_ARCH_KEYS,
            conv_trunk_kwargs,
        )

        for key in PIXEL_ARCH_KEYS:
            if key in params:
                self.arch[key] = params[key]
        self.policy = build_policy(self.arch)

        self._module = DiscreteQNet(
            act_dim=self.act_dim,
            hidden_sizes=tuple(self.arch["hidden_sizes"]),
            compute_dtype=_compute_dtype(self.arch),
            **conv_trunk_kwargs(self.arch))
        net_params = self.policy.init_params(self._rng_init)
        tx = optax.adam(float(params.get("lr", 1e-3)))
        self.state = DQNState(
            params=net_params,
            target_params=jax.tree.map(jnp.copy, net_params),
            opt_state=tx.init(net_params),
            step=jnp.int32(0),
        )
        update = make_dqn_update(
            self._module,
            gamma=self.gamma,
            lr=float(params.get("lr", 1e-3)),
            polyak=self.polyak,
            double_q=bool(params.get("double_q", True)),
        )
        self._update = jax.jit(update, donate_argnums=0)

    def _actor_params(self):
        return self.state.params
