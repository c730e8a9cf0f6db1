"""REINFORCE (vanilla policy gradient) ± value baseline, as a jitted XLA
program.

Capability parity with the reference's only implemented algorithm
(reference: relayrl_framework/src/native/python/algorithms/REINFORCE/
REINFORCE.py — config-driven ctor at :16-62, ``receive_trajectory`` buffering
+ train-every-``traj_per_epoch`` at :70-95, one policy-gradient step
``-(logp*adv).mean()`` plus ``train_vf_iters`` value MSE steps with KL/entropy
diagnostics at :97-125,141-160, ``save()`` via torch.jit at :64-68).

TPU-first redesign:
* The whole epoch update — GAE-λ, advantage normalization, the policy step
  and **all** value iterations — is ONE jitted function on padded ``[B, T]``
  batches (``lax.fori_loop`` for the vf iterations). The reference loops in
  Python over scipy outputs; here a single XLA program touches HBM once.
* Two optimizers (pi_lr / vf_lr, matching the reference) act on one shared
  param tree via ``optax.multi_transform`` partitions.
* State (params + both opt states + RNG + counters) is a pytree — donate-able
  on update and fully checkpointable.
"""

from __future__ import annotations

from typing import Any, Mapping

import jax
import jax.numpy as jnp
import optax
from flax import struct

from relayrl_tpu.algorithms.base import register_algorithm
from relayrl_tpu.algorithms.onpolicy import OnPolicyAlgorithm
from relayrl_tpu.models import build_policy
from relayrl_tpu.models.base import apply_arch_overrides
from relayrl_tpu.ops import gae_advantages, masked_mean_std, normalize_advantages


class ReinforceState(struct.PyTreeNode):
    params: Any
    pi_opt_state: Any
    vf_opt_state: Any
    rng: jax.Array
    step: jax.Array  # i32 scalar — doubles as the model version


def _param_labels(params) -> Any:
    """Label each leaf 'pi' or 'vf' by its top-level module name."""

    def label_tree(tree, label):
        return jax.tree.map(lambda _: label, tree)

    inner = params["params"]
    labeled = {
        name: label_tree(sub, "vf" if name.startswith("vf") else "pi")
        for name, sub in inner.items()
    }
    return {"params": labeled}


def make_optimizers(params, pi_lr: float, vf_lr: float, freeze=()):
    """The (tx_pi, tx_vf) pair every actor-critic algorithm here uses: two
    optimizers over ONE shared param tree, partitioned by the pi/vf labels —
    the single source of truth for the partition (ctor and jitted update
    must agree or opt-state structure silently drifts).

    ``freeze`` (regex strings over leaf paths — the ``learner.freeze``
    knob, algorithms/freeze.py) adds a third partition whose leaves
    neither optimizer ever moves: frozen leaves stay bit-identical
    across updates, which is what makes them free on the wire-v2 delta
    plane. The label is only added when patterns are present, so
    freeze-less opt-state trees (and their checkpoints) are unchanged."""
    labels = _param_labels(params)
    txs_pi = {"pi": optax.adam(pi_lr), "vf": optax.set_to_zero()}
    txs_vf = {"pi": optax.set_to_zero(), "vf": optax.adam(vf_lr)}
    if freeze:
        from relayrl_tpu.algorithms.freeze import freeze_labels

        labels = freeze_labels(params, freeze, base_labels=labels)
        txs_pi["freeze"] = optax.set_to_zero()
        txs_vf["freeze"] = optax.set_to_zero()
    return (optax.multi_transform(txs_pi, labels),
            optax.multi_transform(txs_vf, labels))


def make_reinforce_update(policy, pi_lr: float, vf_lr: float,
                          train_vf_iters: int, gamma: float, lam: float,
                          with_baseline: bool, freeze=()):
    """Build the pure (state, batch) -> (state, metrics) epoch update."""

    def reinforce_update(state: ReinforceState, batch: Mapping[str, jax.Array]):
        tx_pi, tx_vf = make_optimizers(state.params, pi_lr, vf_lr, freeze)
        obs, act, act_mask = batch["obs"], batch["act"], batch["act_mask"]
        rew, val, valid = batch["rew"], batch["val"], batch["valid"]
        last_val = batch["last_val"]

        if with_baseline:
            adv, ret = gae_advantages(rew, val, valid, gamma, lam, last_val)
        else:
            # Without a baseline the advantage IS the reward-to-go
            # (ref: PolicyWithoutBaseline path).
            adv, ret = gae_advantages(rew, jnp.zeros_like(val), valid,
                                      gamma, 1.0, jnp.zeros_like(last_val))
        adv = normalize_advantages(adv, valid)
        n_valid = jnp.maximum(jnp.sum(valid), 1.0)

        # --- policy step (one, as in the reference) ---
        def pi_loss_fn(params):
            logp, ent, _ = policy.evaluate(params, obs, act, act_mask)
            loss = -jnp.sum(logp * adv * valid) / n_valid
            return loss, (logp, ent)

        (pi_loss, (logp_new, ent)), grads = jax.value_and_grad(
            pi_loss_fn, has_aux=True)(state.params)
        updates, pi_opt_state = tx_pi.update(grads, state.pi_opt_state, state.params)
        params = optax.apply_updates(state.params, updates)

        # Diagnostics (ref REINFORCE.py:141-160): approx KL vs the behavior
        # log-probs stored at sample time, mean entropy, post-update Δloss.
        old_logp = batch["logp"]
        approx_kl = jnp.sum((old_logp - logp_new) * valid) / n_valid
        entropy = jnp.sum(ent * valid) / n_valid
        pi_loss_after, _ = pi_loss_fn(params)

        # --- value steps (train_vf_iters, fori_loop on device) ---
        def vf_loss_fn(params):
            _, _, v = policy.evaluate(params, obs, act, act_mask)
            return jnp.sum(jnp.square(v - ret) * valid) / n_valid

        vf_loss_before = vf_loss_fn(params) if with_baseline else jnp.float32(0)

        def vf_body(_, carry):
            params, opt_state = carry
            grads = jax.grad(vf_loss_fn)(params)
            updates, opt_state = tx_vf.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state

        if with_baseline:
            # NOTE: loop unrolling (unroll=4/8) was measured and does NOT
            # help here — interleaved A/B on the v5e showed identical
            # steady-state throughput (~103 updates/s) for unroll 1/4/8;
            # apparent gains in sequential sweeps were ambient chip-state
            # windows (throughput drifts 100-160 up/s across minutes).
            params, vf_opt_state = jax.lax.fori_loop(
                0, train_vf_iters, vf_body, (params, state.vf_opt_state))
            vf_loss_after = vf_loss_fn(params)
        else:
            vf_opt_state = state.vf_opt_state
            vf_loss_after = jnp.float32(0)

        adv_mean, adv_std = masked_mean_std(adv, valid)
        metrics = {
            "LossPi": pi_loss,
            "DeltaLossPi": pi_loss_after - pi_loss,
            "KL": approx_kl,
            "Entropy": entropy,
            "LossV": vf_loss_before,
            "DeltaLossV": vf_loss_after - vf_loss_before,
            "AdvMean": adv_mean,
            "AdvStd": adv_std,
        }
        new_state = ReinforceState(
            params=params,
            pi_opt_state=pi_opt_state,
            vf_opt_state=vf_opt_state,
            rng=state.rng,
            step=state.step + 1,
        )
        return new_state, metrics

    return reinforce_update


@register_algorithm("REINFORCE")
class REINFORCE(OnPolicyAlgorithm):
    """Host-side REINFORCE orchestration (ctor parity with
    REINFORCE.py:16-62: ``REINFORCE(env_dir, config_path, obs_dim, act_dim,
    buf_size, **hyperparam overrides)``)."""

    ALGO_NAME = "REINFORCE"

    def _setup(self, params: dict, learner: dict, rng: jax.Array) -> None:
        self.with_baseline = bool(params.get("with_vf_baseline", False))
        self.gamma = float(params.get("gamma", 0.98))
        self.lam = float(params.get("lam", 0.97))

        self.arch = {
            "kind": str(params.get(
                "model_kind",
                "mlp_discrete" if self.discrete else "mlp_continuous")),
            "obs_dim": self.obs_dim,
            "act_dim": self.act_dim,
            "hidden_sizes": list(params.get("hidden_sizes", [128, 128])),
            "activation": "tanh",
            "has_critic": self.with_baseline,
            # learner.precision config → compute dtype (bf16 feeds the MXU);
            # actors inherit it through the arch so learner/actor agree.
            "precision": str(learner.get("precision", "float32")),
        }
        apply_arch_overrides(self.arch, params, learner=True)
        self.policy = build_policy(self.arch)

        init_rng, state_rng = jax.random.split(rng)
        net_params = self.policy.init_params(init_rng)
        freeze = self._resolve_freeze(params, learner, net_params)
        update = make_reinforce_update(
            self.policy,
            pi_lr=float(params.get("pi_lr", 3e-4)),
            vf_lr=float(params.get("vf_lr", 1e-3)),
            train_vf_iters=int(params.get("train_vf_iters", 80)),
            gamma=self.gamma,
            lam=self.lam,
            with_baseline=self.with_baseline,
            freeze=freeze,
        )
        self._update = jax.jit(update, donate_argnums=0)

        tx_pi, tx_vf = make_optimizers(
            net_params, float(params.get("pi_lr", 3e-4)),
            float(params.get("vf_lr", 1e-3)), freeze)
        self.state = ReinforceState(
            params=net_params,
            pi_opt_state=tx_pi.init(net_params),
            vf_opt_state=tx_vf.init(net_params),
            rng=state_rng,
            step=jnp.int32(0),
        )

    def _log_keys(self):
        keys = ["LossPi", "DeltaLossPi", "KL", "Entropy"]
        if self.with_baseline:
            keys += ["LossV", "DeltaLossV"]
        return keys
