"""TD3 as a jitted XLA program.

Fills the reference's registry slot (whitelisted, never implemented —
relayrl_framework/src/sys_utils/config_loader.rs:148-159). The three TD3
mechanisms in one compiled update: clipped double-Q (twin critics, min
target), target-policy smoothing (clipped Gaussian noise on the target
action), and delayed policy updates (``lax.cond`` on ``step %
policy_delay`` gates the actor/target branch, so the delay costs no
recompilation and no host round trip).
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
from relayrl_tpu.models.q_networks import DeterministicActor, TwinQNet


class TD3State(struct.PyTreeNode):
    actor_params: Any
    critic_params: Any
    target_actor_params: Any
    target_critic_params: Any
    actor_opt_state: Any
    critic_opt_state: Any
    rng: jax.Array
    step: jax.Array


def make_td3_update(actor: DeterministicActor, critic: TwinQNet,
                    act_limit: float, gamma: float, actor_lr: float,
                    critic_lr: float, polyak: float, target_noise: float,
                    noise_clip: float, policy_delay: int):
    actor_tx = optax.adam(actor_lr)
    critic_tx = optax.adam(critic_lr)

    def td3_update(state: TD3State, batch):
        obs, act, rew = batch["obs"], batch["act"], batch["rew"]
        obs2, done = batch["obs2"], batch["done"]
        rng, noise_rng = jax.random.split(state.rng)

        # Target-policy smoothing: clipped noise on the target action.
        a2 = actor.apply(state.target_actor_params, obs2)
        noise = jnp.clip(
            target_noise * jax.random.normal(noise_rng, a2.shape, a2.dtype),
            -noise_clip, noise_clip)
        a2 = jnp.clip(a2 + noise, -act_limit, act_limit)
        q1_t, q2_t = critic.apply(state.target_critic_params, obs2, a2)
        target = rew + gamma * (1.0 - done) * jnp.minimum(q1_t, q2_t)

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

        def actor_loss(params):
            a = actor.apply(params, obs)
            q1_pi, _ = critic.apply(critic_params, obs, a)
            return -jnp.mean(q1_pi)

        def do_actor_update(_):
            loss_pi, grads = jax.value_and_grad(actor_loss)(
                state.actor_params)
            updates, actor_opt_state = actor_tx.update(
                grads, state.actor_opt_state, state.actor_params)
            actor_params = optax.apply_updates(state.actor_params, updates)
            return (actor_params, actor_opt_state,
                    polyak_update(actor_params, state.target_actor_params,
                                  polyak),
                    polyak_update(critic_params, state.target_critic_params,
                                  polyak),
                    loss_pi)

        def skip_actor_update(_):
            return (state.actor_params, state.actor_opt_state,
                    state.target_actor_params, state.target_critic_params,
                    jnp.float32(0.0))

        (actor_params, actor_opt_state, target_actor_params,
         target_critic_params, loss_pi) = jax.lax.cond(
            state.step % policy_delay == 0,
            do_actor_update, skip_actor_update, operand=None)

        metrics = {"LossQ": loss_q, "LossPi": loss_pi, "QVals": jnp.mean(q1)}
        return TD3State(
            actor_params=actor_params,
            critic_params=critic_params,
            target_actor_params=target_actor_params,
            target_critic_params=target_critic_params,
            actor_opt_state=actor_opt_state,
            critic_opt_state=critic_opt_state,
            rng=rng,
            step=state.step + 1,
        ), metrics

    return td3_update


@register_algorithm("TD3")
class TD3(OffPolicyAlgorithm):
    ALGO_NAME = "TD3"
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
        self._critic = TwinQNet(hidden_sizes=hidden, compute_dtype=dtype)

        a_rng, c_rng, s_rng = jax.random.split(self._rng_init, 3)
        obs0 = jnp.zeros((1, self.obs_dim), jnp.float32)
        act0 = jnp.zeros((1, self.act_dim), jnp.float32)
        actor_params = self._actor.init(a_rng, obs0)
        critic_params = self._critic.init(c_rng, obs0, act0)
        actor_lr = float(params.get("pi_lr", 1e-3))
        critic_lr = float(params.get("q_lr", 1e-3))
        self.state = TD3State(
            actor_params=actor_params,
            critic_params=critic_params,
            target_actor_params=jax.tree.map(jnp.copy, actor_params),
            target_critic_params=jax.tree.map(jnp.copy, critic_params),
            actor_opt_state=optax.adam(actor_lr).init(actor_params),
            critic_opt_state=optax.adam(critic_lr).init(critic_params),
            rng=s_rng,
            step=jnp.int32(0),
        )
        update = make_td3_update(
            self._actor, self._critic, act_limit=act_limit, gamma=self.gamma,
            actor_lr=actor_lr, critic_lr=critic_lr, polyak=self.polyak,
            target_noise=float(params.get("target_noise", 0.2)),
            noise_clip=float(params.get("noise_clip", 0.5)),
            policy_delay=int(params.get("policy_delay", 2)))
        self._update = jax.jit(update, donate_argnums=0)

    def _actor_params(self):
        return self.state.actor_params

    def _metric_keys(self):
        return ("LossQ", "LossPi", "QVals")
