"""Mesh/sharding/sharded-update tests on the virtual 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from relayrl_tpu.models import build_policy
from relayrl_tpu.parallel import (
    make_mesh,
    make_sharded_update,
    param_pspec,
    place_batch,
    place_state,
    resolve_mesh_shape,
)


class TestMeshResolve:
    def test_fill_axis(self):
        assert resolve_mesh_shape({"dp": -1}, 8) == {
            "dp": 8, "fsdp": 1, "ep": 1, "tp": 1, "sp": 1, "pp": 1}
        assert resolve_mesh_shape({"dp": -1, "tp": 2}, 8) == {
            "dp": 4, "fsdp": 1, "ep": 1, "tp": 2, "sp": 1, "pp": 1}

    def test_exact(self):
        assert resolve_mesh_shape({"dp": 2, "fsdp": 2, "tp": 2}, 8)["sp"] == 1

    def test_mismatch_rejected(self):
        with pytest.raises(ValueError):
            resolve_mesh_shape({"dp": 3}, 8)
        with pytest.raises(ValueError):
            resolve_mesh_shape({"dp": -1, "tp": -1}, 8)

    def test_make_mesh(self):
        mesh = make_mesh({"dp": 4, "tp": 2})
        assert mesh.shape["dp"] == 4 and mesh.shape["tp"] == 2
        assert mesh.devices.size == 8


class TestParamRules:
    def _params(self):
        policy = build_policy({"kind": "mlp_discrete", "obs_dim": 8, "act_dim": 4,
                               "hidden_sizes": [16, 16], "has_critic": True})
        return policy.init_params(jax.random.PRNGKey(0))

    def test_dp_replicates_params(self):
        mesh = make_mesh({"dp": -1})
        params = self._params()
        specs = jax.tree_util.tree_map_with_path(
            lambda p, l: param_pspec(p, l, mesh), params)
        for leaf in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)):
            assert leaf == P()

    def test_tp_alternates_dense_kernels(self):
        mesh = make_mesh({"dp": 4, "tp": 2})
        params = self._params()["params"]
        k0 = param_pspec(
            (jax.tree_util.DictKey("pi_trunk"), jax.tree_util.DictKey("dense_0"),
             jax.tree_util.DictKey("kernel")),
            params["pi_trunk"]["dense_0"]["kernel"], mesh)
        k1 = param_pspec(
            (jax.tree_util.DictKey("pi_trunk"), jax.tree_util.DictKey("dense_1"),
             jax.tree_util.DictKey("kernel")),
            params["pi_trunk"]["dense_1"]["kernel"], mesh)
        assert k0 == P(None, "tp")
        assert k1 == P("tp", None)

    def test_fsdp_shards_first_divisible_axis(self):
        mesh = make_mesh({"dp": 4, "fsdp": 2})
        spec = param_pspec(
            (jax.tree_util.DictKey("vf_trunk"), jax.tree_util.DictKey("dense_0"),
             jax.tree_util.DictKey("kernel")),
            jnp.zeros((8, 16)), mesh)
        assert spec == P("fsdp")


def _tiny_update(policy):
    import optax

    tx = optax.adam(1e-2)

    def update(state, batch):
        params, opt_state = state
        def loss_fn(p):
            logp, ent, v = policy.evaluate(p, batch["obs"], batch["act"],
                                           batch["act_mask"])
            return -jnp.mean(logp * batch["adv"]) + 0.5 * jnp.mean((v - batch["ret"]) ** 2)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state), {"loss": loss}

    return update, tx


@pytest.mark.parametrize("mesh_spec", [
    {"dp": -1},
    {"dp": 2, "fsdp": 2, "tp": 2},
    {"dp": 4, "tp": 2},
])
def test_sharded_update_runs_and_matches_single_device(mesh_spec):
    policy = build_policy({"kind": "mlp_discrete", "obs_dim": 8, "act_dim": 4,
                           "hidden_sizes": [16, 16], "has_critic": True})
    params = policy.init_params(jax.random.PRNGKey(0))
    update, tx = _tiny_update(policy)
    state = (params, tx.init(params))

    rng = np.random.default_rng(0)
    batch = {
        "obs": rng.standard_normal((8, 5, 8)).astype(np.float32),
        "act": rng.integers(0, 4, (8, 5)).astype(np.int32),
        "act_mask": np.ones((8, 5, 4), np.float32),
        "adv": rng.standard_normal((8, 5)).astype(np.float32),
        "ret": rng.standard_normal((8, 5)).astype(np.float32),
    }

    # single-device reference; no donation — `state` is placed on the mesh
    # below and must survive this call (the sharded side also runs
    # donate_state=False for the same reason).
    # jaxlint: disable=JAX05
    ref_state, ref_metrics = jax.jit(update)(state, {k: jnp.asarray(v) for k, v in batch.items()})

    mesh = make_mesh(mesh_spec)
    placed = place_state(state, mesh)
    sharded = make_sharded_update(update, mesh, state, donate_state=False)
    new_state, metrics = sharded(placed, place_batch(batch, mesh))

    assert float(metrics["loss"]) == pytest.approx(float(ref_metrics["loss"]), rel=1e-4)
    for ref_leaf, got_leaf in zip(jax.tree.leaves(ref_state), jax.tree.leaves(new_state)):
        np.testing.assert_allclose(np.asarray(ref_leaf), np.asarray(got_leaf),
                                   rtol=2e-4, atol=2e-5)


def test_reinforce_state_places_on_mesh(tmp_cwd):
    from relayrl_tpu.algorithms import build_algorithm

    algo = build_algorithm("REINFORCE", obs_dim=8, act_dim=4, traj_per_epoch=1,
                           with_vf_baseline=True, hidden_sizes=[16, 16],
                           logger_kwargs={"output_dir": str(tmp_cwd / "logs")})
    mesh = make_mesh({"dp": 2, "fsdp": 2, "tp": 2})
    placed = place_state(algo.state, mesh)
    # every leaf is addressable on all 8 devices
    leaves = jax.tree.leaves(placed)
    assert all(len(l.devices()) == 8 for l in leaves if hasattr(l, "devices"))


def test_virtual_cpu_devices_are_never_meshed_by_default():
    # conftest gives this process 8 virtual CPU devices; a single-process
    # server must not spread over them on its own (parallel/mesh.py).
    from relayrl_tpu.parallel import accelerator_devices

    assert len(jax.devices()) == 8 and accelerator_devices() == []


def test_dryrun_refuses_a_backend_with_too_few_devices():
    # Never a quiet switch to some other mesh: too few devices is an error
    # naming what was found.
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "graft_entry", os.path.join(os.path.dirname(__file__), "..",
                                    "__graft_entry__.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with pytest.raises(RuntimeError, match="need 64 devices, have 8 cpu"):
        mod._ensure_devices(64)
