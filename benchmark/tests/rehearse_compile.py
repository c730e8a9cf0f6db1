"""Rehearsal 3 of the on-chip-measurement guide, run by hand:

    JAX_PLATFORMS=cpu python benchmark/tests/rehearse_compile.py <cell> [traj_per_update]

Compiles the cell's real update (published widths, the traffic mix's batch)
for a DESCRIBED v5e chip — no chip attached, nothing runs — and prints
``memory_analysis()``: what the chip's compiler refuses here (a kernel it
cannot tile, a program over 16 GB) costs no chip time. The program picks
its attention backend from ``jax.default_backend()``, which is "cpu" here,
so this script (not the program) answers "tpu" while the update is traced.
A compile that passes is not a chip run.
"""

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark import harness
    from relayrl_tpu.algorithms.impala import (
        ImpalaState,
        make_impala_tx,
        make_impala_update,
    )
    from relayrl_tpu.data.batching import TrajectoryBatch
    from relayrl_tpu.models import build_policy
    from relayrl_tpu.models.base import apply_arch_overrides

    spec = harness.load_cell(sys.argv[1])
    cfg, tr = spec["config"], spec["traffic"]
    per_update = int(sys.argv[2]) if len(sys.argv) > 2 else int(
        tr["traj_per_update"])
    steps = int(tr["traj_len"])
    kwargs = harness.load_reference(spec["config_name"]).program_kwargs(cfg)
    hp = cfg["algorithm"]["hyperparams"]
    arch = {"kind": kwargs.get("model_kind", "cnn_discrete"),
            "obs_dim": cfg["obs_dim"], "act_dim": cfg["act_dim"],
            "has_critic": True, "precision": "bfloat16",
            **{k: v for k, v in kwargs.items() if k in (
                "obs_shape", "conv_spec", "dense", "scale_obs")}}
    apply_arch_overrides(arch, kwargs)

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    real_backend = jax.default_backend
    jax.default_backend = lambda: "tpu"  # steer the trace, see module doc
    try:
        policy = build_policy(arch)
        params = jax.eval_shape(policy.init_params, jax.random.PRNGKey(0))
        tx = make_impala_tx(hp["lr"], hp["max_grad_norm"])
        state = ImpalaState(
            params=params, opt_state=jax.eval_shape(tx.init, params),
            rng=jax.ShapeDtypeStruct((2,), jnp.uint32),
            step=jax.ShapeDtypeStruct((), jnp.int32))
        update = make_impala_update(
            policy, lr=hp["lr"], gamma=hp["gamma"], vf_coef=hp["vf_coef"],
            ent_coef=hp["ent_coef"], rho_bar=hp["rho_bar"],
            c_bar=hp["c_bar"], max_grad_norm=hp["max_grad_norm"])
        batch = TrajectoryBatch.zeros(per_update, steps, cfg["obs_dim"],
                                      cfg["act_dim"], True)

        def on_chip(tree):
            return jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=chip), tree)

        compiled = jax.jit(update, donate_argnums=0).lower(
            on_chip(state), on_chip(batch)).compile()
    finally:
        jax.default_backend = real_backend
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"{sys.argv[1]} B={per_update} T={steps}: {n_params / 1e6:.1f} M "
          f"parameters; arguments {mem.argument_size_in_bytes / 1e9:.2f} GB, "
          f"outputs {mem.output_size_in_bytes / 1e9:.2f} GB, aliased "
          f"{mem.alias_size_in_bytes / 1e9:.2f} GB, temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.2f} GB => {total / 1e9:.2f} GB; "
          f"Mosaic calls {compiled.as_text().count('tpu_custom_call')}; "
          f"attention {dict(policy.attention_backends or {})}")


if __name__ == "__main__":
    main()
