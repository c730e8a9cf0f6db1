"""PPO (clipped surrogate) as a single jitted XLA program.

The reference whitelists PPO in its algorithm registry but never implements
it (reference: relayrl_framework/src/sys_utils/config_loader.rs:397-433 —
only REINFORCE parses to params), and the driver's north-star configs call
for PPO on Atari (BASELINE.md). This is the full algorithm, TPU-first:

* GAE-λ, advantage normalization, and **all** train iterations × minibatches
  run inside ONE jitted update on padded ``[B, T]`` batches: a
  ``lax.scan`` over shuffled trajectory-row minibatches (gather by permuted
  indices keeps shapes static — no recompilation per epoch).
* KL early stopping (stop policy updates once approx-KL exceeds
  ``1.5 × target_kl``) is a boolean carried through the scan that zeroes
  the policy update — compiler-friendly ``lax`` control flow, no Python
  branching on device values.
* Two optimizers (pi_lr / vf_lr) on the shared param tree via
  ``optax.multi_transform``; for the shared-trunk CNN family the pi/vf
  split follows top-level module names, with trunk params owned by pi.
"""

from __future__ import annotations

from typing import Any, Mapping

import jax
import jax.numpy as jnp
import optax
from flax import struct

from relayrl_tpu.algorithms.base import register_algorithm
from relayrl_tpu.algorithms.onpolicy import OnPolicyAlgorithm
from relayrl_tpu.algorithms.reinforce import make_optimizers
from relayrl_tpu.models import build_policy
from relayrl_tpu.models.base import apply_arch_overrides
from relayrl_tpu.ops import gae_advantages, masked_mean_std, normalize_advantages


class PPOState(struct.PyTreeNode):
    params: Any
    pi_opt_state: Any
    vf_opt_state: Any
    rng: jax.Array
    step: jax.Array  # i32 scalar — doubles as the model version


def make_ppo_update(
    policy,
    pi_lr: float,
    vf_lr: float,
    clip_ratio: float,
    train_iters: int,
    minibatch_count: int,
    ent_coef: float,
    vf_coef: float,
    target_kl: float,
    gamma: float,
    lam: float,
    freeze=(),
):
    """Build the pure ``(state, batch) -> (state, metrics)`` epoch update."""

    def ppo_update(state: PPOState, batch: Mapping[str, jax.Array]):
        tx_pi, tx_vf = make_optimizers(state.params, pi_lr, vf_lr, freeze)
        obs, act, act_mask = batch["obs"], batch["act"], batch["act_mask"]
        rew, val, valid = batch["rew"], batch["val"], batch["valid"]
        old_logp, last_val = batch["logp"], batch["last_val"]
        B = obs.shape[0]
        mb_rows = B // minibatch_count

        adv, ret = gae_advantages(rew, val, valid, gamma, lam, last_val)
        adv = normalize_advantages(adv, valid)

        def minibatch_loss(params, idx):
            o = jnp.take(obs, idx, axis=0)
            a = jnp.take(act, idx, axis=0)
            m = jnp.take(act_mask, idx, axis=0)
            ad = jnp.take(adv, idx, axis=0)
            rt = jnp.take(ret, idx, axis=0)
            lp_old = jnp.take(old_logp, idx, axis=0)
            vl = jnp.take(valid, idx, axis=0)
            n = jnp.maximum(jnp.sum(vl), 1.0)

            logp, ent, v = policy.evaluate(params, o, a, m)
            ratio = jnp.exp(logp - lp_old)
            clipped = jnp.clip(ratio, 1.0 - clip_ratio, 1.0 + clip_ratio)
            pi_loss = -jnp.sum(jnp.minimum(ratio * ad, clipped * ad) * vl) / n
            v_loss = jnp.sum(jnp.square(v - rt) * vl) / n
            entropy = jnp.sum(ent * vl) / n
            approx_kl = jnp.sum((lp_old - logp) * vl) / n
            clip_frac = jnp.sum(
                (jnp.abs(ratio - 1.0) > clip_ratio).astype(jnp.float32) * vl
            ) / n
            total = pi_loss + vf_coef * v_loss - ent_coef * entropy
            aux = {"pi_loss": pi_loss, "v_loss": v_loss, "entropy": entropy,
                   "kl": approx_kl, "clip_frac": clip_frac}
            return total, aux

        grad_fn = jax.value_and_grad(minibatch_loss, has_aux=True)

        def mb_step(carry, idx):
            params, pi_opt, vf_opt, stop_pi = carry
            (_, aux), grads = grad_fn(params, idx)

            # KL early stop (SpinningUp semantics): once KL > 1.5*target_kl,
            # POLICY params and pi optimizer state both freeze for the rest
            # of the epoch (select old-vs-new, branch-free; merely zeroing
            # grads would keep params moving via Adam momentum). Value
            # updates continue.
            pi_updates, pi_opt_new = tx_pi.update(grads, pi_opt, params)
            params_new = optax.apply_updates(params, pi_updates)

            def freeze(new, old):
                return jax.tree.map(
                    lambda n, o: jnp.where(stop_pi, o, n), new, old)

            params = freeze(params_new, params)
            pi_opt = freeze(pi_opt_new, pi_opt)

            vf_updates, vf_opt = tx_vf.update(grads, vf_opt, params)
            params = optax.apply_updates(params, vf_updates)

            stop_pi = jnp.logical_or(stop_pi, aux["kl"] > 1.5 * target_kl)
            return (params, pi_opt, vf_opt, stop_pi), aux

        # train_iters sweeps, each a fresh shuffle of trajectory rows.
        rng, *shuffle_rngs = jax.random.split(state.rng, train_iters + 1)
        idx_sets = jnp.stack([
            jax.random.permutation(r, B)[: mb_rows * minibatch_count].reshape(
                minibatch_count, mb_rows)
            for r in shuffle_rngs
        ]).reshape(train_iters * minibatch_count, mb_rows)

        init = (state.params, state.pi_opt_state, state.vf_opt_state,
                jnp.bool_(False))
        (params, pi_opt, vf_opt, stopped), auxes = jax.lax.scan(
            mb_step, init, idx_sets)

        adv_mean, adv_std = masked_mean_std(adv, valid)
        first = jax.tree.map(lambda x: x[0], auxes)
        last = jax.tree.map(lambda x: x[-1], auxes)
        metrics = {
            "LossPi": first["pi_loss"],
            "DeltaLossPi": last["pi_loss"] - first["pi_loss"],
            "LossV": first["v_loss"],
            "DeltaLossV": last["v_loss"] - first["v_loss"],
            "KL": last["kl"],
            "Entropy": last["entropy"],
            "ClipFrac": jnp.mean(auxes["clip_frac"]),
            "StopIter": jnp.float32(stopped),
            "AdvMean": adv_mean,
            "AdvStd": adv_std,
        }
        new_state = PPOState(params=params, pi_opt_state=pi_opt,
                             vf_opt_state=vf_opt, rng=rng,
                             step=state.step + 1)
        return new_state, metrics

    return ppo_update


@register_algorithm("PPO")
class PPO(OnPolicyAlgorithm):
    """Host-side PPO orchestration (same ctor shape as REINFORCE —
    reference REINFORCE.py:16-62 — so the training server treats all
    algorithms uniformly)."""

    ALGO_NAME = "PPO"

    def _setup(self, params: dict, learner: dict, rng: jax.Array) -> None:
        self.minibatch_count = int(params.get("minibatch_count", 4))
        if self.traj_per_epoch % self.minibatch_count:
            raise ValueError(
                f"traj_per_epoch ({self.traj_per_epoch}) must be divisible by "
                f"minibatch_count ({self.minibatch_count})")
        self.lam = float(params.get("lam", 0.95))

        obs_shape = params.get("obs_shape")
        if obs_shape is not None:
            kind = "cnn_discrete"
        else:
            kind = "mlp_discrete" if self.discrete else "mlp_continuous"
        self.arch = {
            "kind": str(params.get("model_kind", kind)),
            "obs_dim": self.obs_dim,
            "act_dim": self.act_dim,
            "hidden_sizes": list(params.get("hidden_sizes", [128, 128])),
            "activation": str(params.get("activation", "tanh")),
            "has_critic": True,
            "precision": str(learner.get("precision", "float32")),
        }
        if obs_shape is not None:
            self.arch["obs_shape"] = [int(d) for d in obs_shape]
            for key in ("conv_spec", "dense", "scale_obs"):
                if key in params:
                    self.arch[key] = params[key]
        apply_arch_overrides(self.arch, params, learner=True)
        self.policy = build_policy(self.arch)

        init_rng, state_rng = jax.random.split(rng)
        net_params = self.policy.init_params(init_rng)
        freeze = self._resolve_freeze(params, learner, net_params)
        update = make_ppo_update(
            self.policy,
            pi_lr=float(params.get("pi_lr", 3e-4)),
            vf_lr=float(params.get("vf_lr", 1e-3)),
            clip_ratio=float(params.get("clip_ratio", 0.2)),
            train_iters=int(params.get("train_iters", 4)),
            minibatch_count=self.minibatch_count,
            ent_coef=float(params.get("ent_coef", 0.0)),
            vf_coef=float(params.get("vf_coef", 0.5)),
            target_kl=float(params.get("target_kl", 0.015)),
            gamma=self.gamma,
            lam=self.lam,
            freeze=freeze,
        )
        self.update_fn = update  # undecorated — parallel layer re-jits this
        self._update = jax.jit(update, donate_argnums=0)

        tx_pi, tx_vf = make_optimizers(
            net_params, float(params.get("pi_lr", 3e-4)),
            float(params.get("vf_lr", 1e-3)), freeze)
        self.state = PPOState(
            params=net_params,
            pi_opt_state=tx_pi.init(net_params),
            vf_opt_state=tx_vf.init(net_params),
            rng=state_rng,
            step=jnp.int32(0),
        )

    def _log_keys(self):
        return ("LossPi", "DeltaLossPi", "LossV", "DeltaLossV", "KL",
                "Entropy", "ClipFrac")
