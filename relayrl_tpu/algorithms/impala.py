"""IMPALA-style async A2C with V-trace, as one jitted XLA program.

Beyond-reference capability (the reference has a single synchronous learner
fed by one socket — SURVEY.md §3.3): this learner is built for a fleet of
async actors running stale policies — the BASELINE.md north-star config
"IMPALA-style async A2C, 256 actors". Each trajectory carries the behavior
policy's ``logp_a``; the update importance-weights it to the current policy
with clipped V-trace ratios, then takes one combined A2C step (policy
gradient on the rho-clipped advantage + value MSE to the vs targets +
entropy bonus) with a single optimizer.

Staleness tolerance is the whole point: ``receive_trajectory`` trains on
every ``traj_per_epoch`` batch regardless of which model version produced
it, and publishes after every update so the actor fleet continuously
hot-swaps (the version-gated swap path of runtime/policy_actor.py).
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
from relayrl_tpu.ops.gae import masked_mean_std
from relayrl_tpu.ops.scopes import LOSS, OPTIMIZER
from relayrl_tpu.ops.vtrace import vtrace


class ImpalaState(struct.PyTreeNode):
    params: Any
    opt_state: Any
    rng: jax.Array  # host-side sampling key for act(); unused by the update
    step: jax.Array


def make_impala_tx(lr: float, max_grad_norm: float, freeze=(),
                   params_template=None):
    """The single owner of IMPALA's optimizer chain (ctor opt-state init
    and the jitted update must agree or the state structure silently
    drifts): global-norm clip → adam, optionally wrapped in the
    ``learner.freeze`` multi_transform mask (algorithms/freeze.py) —
    frozen leaves never move, so they are bit-identical across updates
    and free on the wire-v2 delta plane. ``params_template`` (any tree
    with the params' structure) is required when ``freeze`` is given."""
    from relayrl_tpu.algorithms.freeze import masked_optimizer

    tx = optax.chain(
        optax.clip_by_global_norm(max_grad_norm),
        optax.adam(lr),
    )
    if freeze and params_template is None:
        raise ValueError("freeze patterns need a params_template")
    return masked_optimizer(tx, params_template, freeze)


def make_impala_update(policy, lr: float, gamma: float, vf_coef: float,
                       ent_coef: float, rho_bar: float, c_bar: float,
                       max_grad_norm: float, freeze=(),
                       params_template=None):
    tx = make_impala_tx(lr, max_grad_norm, freeze, params_template)
    # a MoE trunk reports the expert load of the same forward
    # (``moe_load_max`` / ``moe_load_min`` / ``moe_held_slots`` /
    # ``moe_row_passes`` / ``moe_sorted_slots``), a sparse-attention trunk
    # the share of the causal pairs its selections kept
    # (``index_kept_pct``); every other family reports {}
    evaluate = policy.evaluate_stats or (
        lambda *args: (*policy.evaluate(*args), {}))
    # a loss the model itself brings (``Policy.own_loss``): the name it is
    # reported under; its rows come with the stats
    own_loss = policy.own_loss

    def impala_update(state: ImpalaState, batch: Mapping[str, jax.Array]):
        obs, act, act_mask = batch["obs"], batch["act"], batch["act_mask"]
        rew, valid = batch["rew"], batch["valid"]
        behavior_logp = batch["logp"]
        last_val = batch["last_val"]
        # the update's parts carry their names onto the device
        # (ops/scopes.py): the model's are opened where its work is written,
        # V-trace's in ops/vtrace.py
        with jax.named_scope(LOSS):
            n_valid = jnp.maximum(jnp.sum(valid), 1.0)

        def loss_fn(params):
            logp, ent, v, stats = evaluate(params, obs, act, act_mask)
            vt = vtrace(behavior_logp, jax.lax.stop_gradient(logp), rew,
                        jax.lax.stop_gradient(v), valid, gamma,
                        last_val=last_val, rho_bar=rho_bar, c_bar=c_bar)
            with jax.named_scope(LOSS):
                pg_loss = -jnp.sum(logp * vt.pg_adv * valid) / n_valid
                vf_loss = jnp.sum(jnp.square(v - vt.vs) * valid) / n_valid
                ent_mean = jnp.sum(ent * valid) / n_valid
                total = pg_loss + vf_coef * vf_loss - ent_coef * ent_mean
                if own_loss:
                    stats = dict(stats)
                    stats[own_loss] = jnp.sum(
                        stats.pop("own_loss_rows") * valid) / n_valid
                    total = total + stats[own_loss]
            return total, (pg_loss, vf_loss, ent_mean, vt.rho, logp, stats)

        (total, (pg_loss, vf_loss, ent_mean, rho, logp_new, stats)), grads = (
            jax.value_and_grad(loss_fn, has_aux=True)(state.params))
        with jax.named_scope(OPTIMIZER):
            updates, opt_state = tx.update(grads, state.opt_state,
                                           state.params)
            params = optax.apply_updates(state.params, updates)

        with jax.named_scope(LOSS):
            rho_mean, _ = masked_mean_std(rho, valid)
            kl = jnp.sum((behavior_logp - logp_new) * valid) / n_valid
        metrics = {
            "LossPi": pg_loss,
            "LossV": vf_loss,
            "Entropy": ent_mean,
            "LossTotal": total,
            "RhoMean": rho_mean,
            "KL": kl,
            # moe_load_max / _min / moe_held_slots / moe_row_passes /
            # moe_sorted_slots (MoE trunks), IndexLoss / index_kept_pct
            # (sparse attention): only where the trunk has such layers
            **stats,
        }
        return ImpalaState(params=params, opt_state=opt_state, rng=state.rng,
                           step=state.step + 1), metrics

    return impala_update


@register_algorithm("IMPALA")
class IMPALA(OnPolicyAlgorithm):
    """Host orchestration: same epoch-buffer ingest as REINFORCE/PPO, but
    the update is staleness-corrected so it works with many async actors."""

    ALGO_NAME = "IMPALA"
    ADDS_OWN_LOSS = True  # make_impala_update adds ``Policy.own_loss``

    def _setup(self, params: dict, learner: dict, rng: jax.Array) -> None:
        # obs_shape implies the pixel trunk, as in PPO/DQN/C51; an explicit
        # model_kind (e.g. transformer_discrete) still wins.
        default_kind = ("cnn_discrete" if "obs_shape" in params
                        else "mlp_discrete" if self.discrete
                        else "mlp_continuous")
        kind = str(params.get("model_kind", default_kind))
        self.arch = {
            "kind": kind,
            "obs_dim": self.obs_dim,
            "act_dim": self.act_dim,
            "hidden_sizes": list(params.get("hidden_sizes", [128, 128])),
            "has_critic": True,
            "precision": str(learner.get("precision", "float32")),
        }
        if kind == "cnn_discrete" and "obs_shape" in params:
            self.arch["obs_shape"] = list(params["obs_shape"])
            # Same pixel-trunk passthrough as PPO (ppo.py): without it a
            # conv_spec="tpu"/dense override silently trains the Nature
            # trunk.
            for key in ("conv_spec", "dense", "scale_obs"):
                if key in params:
                    self.arch[key] = params[key]
        apply_arch_overrides(self.arch, params, learner=True)
        self.policy = build_policy(self.arch)

        init_rng, state_rng = jax.random.split(rng)
        net_params = self.policy.init_params(init_rng)
        lr = float(params.get("lr", 3e-4))
        max_grad_norm = float(params.get("max_grad_norm", 40.0))
        freeze = self._resolve_freeze(params, learner, net_params)
        tx = make_impala_tx(lr, max_grad_norm, freeze, net_params)
        self.state = ImpalaState(
            params=net_params,
            opt_state=tx.init(net_params),
            rng=state_rng,
            step=jnp.int32(0),
        )
        update = make_impala_update(
            self.policy, lr=lr, gamma=self.gamma,
            vf_coef=float(params.get("vf_coef", 0.5)),
            ent_coef=float(params.get("ent_coef", 0.01)),
            rho_bar=float(params.get("rho_bar", 1.0)),
            c_bar=float(params.get("c_bar", 1.0)),
            max_grad_norm=max_grad_norm, freeze=freeze,
            params_template=net_params)
        self._update = jax.jit(update, donate_argnums=0)
        if self.policy.evaluate_stats is not None:
            from relayrl_tpu import telemetry

            reg = telemetry.get_registry()
            self._metric_gauges = {
                "moe_load_max": reg.gauge(
                    "relayrl_moe_load_max",
                    "fullest expert's share of the token-slots, newest "
                    "update, max over MoE layers (1/E at even load)"),
                "moe_load_min": reg.gauge(
                    "relayrl_moe_load_min",
                    "emptiest expert's share of the token-slots, newest "
                    "update, min over MoE layers"),
                "moe_held_slots": reg.gauge(
                    "relayrl_moe_held_slots",
                    "token-slots routed to experts this device holds, "
                    "newest update, summed over MoE layers (all N*k a "
                    "layer unless the arch sets moe_held)"),
                "moe_row_passes": reg.gauge(
                    "relayrl_moe_row_passes",
                    "passes the sparse dispatch took over its row buffers, "
                    "newest update, summed over MoE layers (1 a layer "
                    "unless a held-experts layer got more rows than its "
                    "buffer has)"),
                "moe_sorted_slots": reg.gauge(
                    "relayrl_moe_sorted_slots",
                    "slots the sparse dispatch put in expert order, newest "
                    "update, summed over MoE layers (passes x the row "
                    "buffer in a held-experts layer that counts its rows, "
                    "all N*k a layer that sorts them or holds every "
                    "expert)"),
            }
            self._fence_notes = ("moe_load_max", "moe_held_slots",
                                 "moe_row_passes", "moe_sorted_slots")
        loop_steps = int(self.policy.arch.get("loop_steps", 1))
        if loop_steps > 1:
            # a looped trunk: a sample costs loop_steps passes, set once so
            # that a reader of samples/s can tell it from an un-looped one
            from relayrl_tpu import telemetry

            reg = telemetry.get_registry()
            reg.gauge("relayrl_loop_steps",
                      "passes a looped trunk makes over its one parameter "
                      "tree a forward (the arch's loop_steps)").set(loop_steps)
            reg.gauge("relayrl_layer_applications",
                      "block applications a forward: loop_steps x n_layers"
                      ).set(loop_steps * int(self.policy.arch.get("n_layers", 2)))
        # a trunk with a loss of its own: the loss as the update reports it
        # and the share of the causal pairs its selections kept
        self._own_loss_keys = ((self.policy.own_loss, "index_kept_pct")
                               if self.policy.own_loss else ())
        self._fence_notes += self._own_loss_keys

    def _log_keys(self):
        keys = ("LossPi", "LossV", "Entropy", "RhoMean", "KL")
        return keys + tuple(self._metric_gauges) + self._own_loss_keys
