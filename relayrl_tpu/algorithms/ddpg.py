"""DDPG as a jitted XLA program.

Fills the reference's registry slot (whitelisted, never implemented —
relayrl_framework/src/sys_utils/config_loader.rs:148-159). One jitted
update performs the critic TD step, the deterministic-policy-gradient actor
step (maximizing Q(s, mu(s)) through the critic), and both polyak target
updates. Actors receive the deterministic actor as a ``ddpg_continuous``
policy; exploration noise rides the arch config.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import optax
from flax import struct

from relayrl_tpu.algorithms.base import register_algorithm
from relayrl_tpu.algorithms.offpolicy import OffPolicyAlgorithm, polyak_update
from relayrl_tpu.models import build_policy
from relayrl_tpu.models.mlp import _compute_dtype
from relayrl_tpu.models.q_networks import DeterministicActor, QValueNet


class DDPGState(struct.PyTreeNode):
    actor_params: Any
    critic_params: Any
    target_actor_params: Any
    target_critic_params: Any
    actor_opt_state: Any
    critic_opt_state: Any
    step: jax.Array


def make_ddpg_update(actor: DeterministicActor, critic: QValueNet,
                     gamma: float, actor_lr: float, critic_lr: float,
                     polyak: float):
    actor_tx = optax.adam(actor_lr)
    critic_tx = optax.adam(critic_lr)

    def ddpg_update(state: DDPGState, batch):
        obs, act, rew = batch["obs"], batch["act"], batch["rew"]
        obs2, done = batch["obs2"], batch["done"]

        a2 = actor.apply(state.target_actor_params, obs2)
        q2 = critic.apply(state.target_critic_params, obs2, a2)
        target = rew + gamma * (1.0 - done) * q2

        def critic_loss(params):
            q = critic.apply(params, obs, act)
            return jnp.mean(jnp.square(q - target)), q

        (loss_q, q), grads = jax.value_and_grad(critic_loss, has_aux=True)(
            state.critic_params)
        updates, critic_opt_state = critic_tx.update(
            grads, state.critic_opt_state, state.critic_params)
        critic_params = optax.apply_updates(state.critic_params, updates)

        def actor_loss(params):
            a = actor.apply(params, obs)
            return -jnp.mean(critic.apply(critic_params, obs, a))

        loss_pi, grads = jax.value_and_grad(actor_loss)(state.actor_params)
        updates, actor_opt_state = actor_tx.update(
            grads, state.actor_opt_state, state.actor_params)
        actor_params = optax.apply_updates(state.actor_params, updates)

        metrics = {"LossQ": loss_q, "LossPi": loss_pi, "QVals": jnp.mean(q)}
        return DDPGState(
            actor_params=actor_params,
            critic_params=critic_params,
            target_actor_params=polyak_update(
                actor_params, state.target_actor_params, polyak),
            target_critic_params=polyak_update(
                critic_params, state.target_critic_params, polyak),
            actor_opt_state=actor_opt_state,
            critic_opt_state=critic_opt_state,
            step=state.step + 1,
        ), metrics

    return ddpg_update


@register_algorithm("DDPG")
class DDPG(OffPolicyAlgorithm):
    ALGO_NAME = "DDPG"
    DEFAULT_DISCRETE = False

    def _setup(self, params: dict, learner: dict) -> None:
        act_limit = float(params.get("act_limit", 1.0))
        self.arch = {
            "kind": "ddpg_continuous",
            "obs_dim": self.obs_dim,
            "act_dim": self.act_dim,
            "hidden_sizes": list(params.get("hidden_sizes", [128, 128])),
            "act_limit": act_limit,
            "act_noise": float(params.get("act_noise", 0.1)),
            "precision": str(learner.get("precision", "float32")),
        }
        self.policy = build_policy(self.arch)
        hidden = tuple(self.arch["hidden_sizes"])
        dtype = _compute_dtype(self.arch)
        self._actor = DeterministicActor(
            act_dim=self.act_dim, act_limit=act_limit, hidden_sizes=hidden,
            compute_dtype=dtype)
        self._critic = QValueNet(hidden_sizes=hidden, compute_dtype=dtype)

        a_rng, c_rng = jax.random.split(self._rng_init)
        obs0 = jnp.zeros((1, self.obs_dim), jnp.float32)
        act0 = jnp.zeros((1, self.act_dim), jnp.float32)
        actor_params = self._actor.init(a_rng, obs0)
        critic_params = self._critic.init(c_rng, obs0, act0)
        actor_lr = float(params.get("pi_lr", 1e-3))
        critic_lr = float(params.get("q_lr", 1e-3))
        self.state = DDPGState(
            actor_params=actor_params,
            critic_params=critic_params,
            target_actor_params=jax.tree.map(jnp.copy, actor_params),
            target_critic_params=jax.tree.map(jnp.copy, critic_params),
            actor_opt_state=optax.adam(actor_lr).init(actor_params),
            critic_opt_state=optax.adam(critic_lr).init(critic_params),
            step=jnp.int32(0),
        )
        update = make_ddpg_update(
            self._actor, self._critic, gamma=self.gamma,
            actor_lr=actor_lr, critic_lr=critic_lr, polyak=self.polyak)
        self._update = jax.jit(update, donate_argnums=0)

    def _actor_params(self):
        return self.state.actor_params

    def _metric_keys(self):
        return ("LossQ", "LossPi", "QVals")
