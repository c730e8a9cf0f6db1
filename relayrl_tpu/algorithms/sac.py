"""SAC (with automatic entropy-temperature tuning) as a jitted XLA program.

Fills the reference's registry slot (whitelisted, never implemented —
relayrl_framework/src/sys_utils/config_loader.rs:148-159). One jitted
update: twin-critic soft-Bellman TD step, reparameterized squashed-Gaussian
actor step, log-alpha temperature step toward a target entropy of
``-act_dim``, and polyak target update — a single device program per
gradient step.
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
from relayrl_tpu.models.q_networks import (
    SquashedGaussianActor,
    TwinQNet,
    squashed_gaussian_sample,
)


class SACState(struct.PyTreeNode):
    actor_params: Any
    critic_params: Any
    target_critic_params: Any
    log_alpha: jax.Array
    actor_opt_state: Any
    critic_opt_state: Any
    alpha_opt_state: Any
    rng: jax.Array
    step: jax.Array


def make_sac_update(actor: SquashedGaussianActor, critic: TwinQNet,
                    act_limit: float, gamma: float, actor_lr: float,
                    critic_lr: float, alpha_lr: float, polyak: float,
                    target_entropy: float):
    actor_tx = optax.adam(actor_lr)
    critic_tx = optax.adam(critic_lr)
    alpha_tx = optax.adam(alpha_lr)

    def sac_update(state: SACState, batch):
        obs, act, rew = batch["obs"], batch["act"], batch["rew"]
        obs2, done = batch["obs2"], batch["done"]
        rng, a2_rng, pi_rng = jax.random.split(state.rng, 3)
        alpha = jnp.exp(state.log_alpha)

        # Soft Bellman target with the fresh-policy next action.
        mu2, log_std2 = actor.apply(state.actor_params, obs2)
        a2, logp_a2 = squashed_gaussian_sample(a2_rng, mu2, log_std2,
                                               act_limit)
        q1_t, q2_t = critic.apply(state.target_critic_params, obs2, a2)
        target = rew + gamma * (1.0 - done) * (
            jnp.minimum(q1_t, q2_t) - alpha * logp_a2)

        def critic_loss(params):
            q1, q2 = critic.apply(params, obs, act)
            loss = jnp.mean(jnp.square(q1 - target)) + jnp.mean(
                jnp.square(q2 - target))
            return loss, q1

        (loss_q, q1), grads = jax.value_and_grad(critic_loss, has_aux=True)(
            state.critic_params)
        updates, critic_opt_state = critic_tx.update(
            grads, state.critic_opt_state, state.critic_params)
        critic_params = optax.apply_updates(state.critic_params, updates)

        # Reparameterized actor step through the updated critics.
        def actor_loss(params):
            mu, log_std = actor.apply(params, obs)
            a, logp_a = squashed_gaussian_sample(pi_rng, mu, log_std,
                                                 act_limit)
            q1_pi, q2_pi = critic.apply(critic_params, obs, a)
            return jnp.mean(alpha * logp_a - jnp.minimum(q1_pi, q2_pi)), logp_a

        (loss_pi, logp_a), grads = jax.value_and_grad(
            actor_loss, has_aux=True)(state.actor_params)
        updates, actor_opt_state = actor_tx.update(
            grads, state.actor_opt_state, state.actor_params)
        actor_params = optax.apply_updates(state.actor_params, updates)

        # Temperature step toward the entropy target.
        def alpha_loss(log_alpha):
            return -jnp.mean(
                jnp.exp(log_alpha)
                * (jax.lax.stop_gradient(logp_a) + target_entropy))

        loss_alpha, grad_alpha = jax.value_and_grad(alpha_loss)(
            state.log_alpha)
        updates, alpha_opt_state = alpha_tx.update(
            grad_alpha, state.alpha_opt_state, state.log_alpha)
        log_alpha = optax.apply_updates(state.log_alpha, updates)

        metrics = {
            "LossQ": loss_q,
            "LossPi": loss_pi,
            "QVals": jnp.mean(q1),
            "Alpha": alpha,
            "LogPi": jnp.mean(logp_a),
        }
        return SACState(
            actor_params=actor_params,
            critic_params=critic_params,
            target_critic_params=polyak_update(
                critic_params, state.target_critic_params, polyak),
            log_alpha=log_alpha,
            actor_opt_state=actor_opt_state,
            critic_opt_state=critic_opt_state,
            alpha_opt_state=alpha_opt_state,
            rng=rng,
            step=state.step + 1,
        ), metrics

    return sac_update


@register_algorithm("SAC")
class SAC(OffPolicyAlgorithm):
    ALGO_NAME = "SAC"
    DEFAULT_DISCRETE = False

    def _setup(self, params: dict, learner: dict) -> None:
        act_limit = float(params.get("act_limit", 1.0))
        self.arch = {
            "kind": "sac_continuous",
            "obs_dim": self.obs_dim,
            "act_dim": self.act_dim,
            "hidden_sizes": list(params.get("hidden_sizes", [128, 128])),
            "act_limit": act_limit,
            "precision": str(learner.get("precision", "float32")),
        }
        self.policy = build_policy(self.arch)
        hidden = tuple(self.arch["hidden_sizes"])
        dtype = _compute_dtype(self.arch)
        self._actor = SquashedGaussianActor(
            act_dim=self.act_dim, hidden_sizes=hidden, compute_dtype=dtype)
        self._critic = TwinQNet(hidden_sizes=hidden, compute_dtype=dtype)

        a_rng, c_rng, s_rng = jax.random.split(self._rng_init, 3)
        obs0 = jnp.zeros((1, self.obs_dim), jnp.float32)
        act0 = jnp.zeros((1, self.act_dim), jnp.float32)
        actor_params = self._actor.init(a_rng, obs0)
        critic_params = self._critic.init(c_rng, obs0, act0)
        actor_lr = float(params.get("pi_lr", 3e-4))
        critic_lr = float(params.get("q_lr", 3e-4))
        alpha_lr = float(params.get("alpha_lr", 3e-4))
        log_alpha = jnp.float32(jnp.log(float(params.get("alpha", 0.2))))
        self.state = SACState(
            actor_params=actor_params,
            critic_params=critic_params,
            target_critic_params=jax.tree.map(jnp.copy, critic_params),
            log_alpha=log_alpha,
            actor_opt_state=optax.adam(actor_lr).init(actor_params),
            critic_opt_state=optax.adam(critic_lr).init(critic_params),
            alpha_opt_state=optax.adam(alpha_lr).init(log_alpha),
            rng=s_rng,
            step=jnp.int32(0),
        )
        update = make_sac_update(
            self._actor, self._critic, act_limit=act_limit, gamma=self.gamma,
            actor_lr=actor_lr, critic_lr=critic_lr, alpha_lr=alpha_lr,
            polyak=self.polyak,
            target_entropy=float(
                params.get("target_entropy", -float(self.act_dim))))
        self._update = jax.jit(update, donate_argnums=0)

    def _actor_params(self):
        return self.state.actor_params

    def _metric_keys(self):
        return ("LossQ", "LossPi", "QVals", "Alpha", "LogPi")
