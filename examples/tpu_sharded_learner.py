"""TPU parallelism demo: one learner update over a dp x fsdp x tp mesh,
plus the ring-attention sequence-parallel path.

Runs over the devices JAX finds; on a host with no accelerator, ask for a
virtual CPU mesh (the standard way to exercise shardings without a pod):

    python examples/tpu_sharded_learner.py                       # real devices
    python examples/tpu_sharded_learner.py --virtual-devices 8   # CPU mesh
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def make_batch(B, T, obs_dim, act_dim):
    rng = np.random.default_rng(0)
    return {
        "obs": rng.standard_normal((B, T, obs_dim)).astype(np.float32),
        "act": rng.integers(0, act_dim, (B, T)).astype(np.int32),
        "act_mask": np.ones((B, T, act_dim), np.float32),
        "rew": np.ones((B, T), np.float32),
        "val": np.zeros((B, T), np.float32),
        "logp": np.zeros((B, T), np.float32),
        "valid": np.ones((B, T), np.float32),
        "last_val": np.zeros((B,), np.float32),
    }


def run(arch, mesh_spec, shard_time, label, B=16, T=64):
    import jax
    import jax.numpy as jnp

    from relayrl_tpu.algorithms.reinforce import (
        ReinforceState,
        make_optimizers,
        make_reinforce_update,
    )
    from relayrl_tpu.models import build_policy
    from relayrl_tpu.parallel import (
        make_mesh,
        make_sharded_update,
        place_batch,
        place_state,
    )
    from relayrl_tpu.utils import timed

    policy = build_policy(arch)
    params = policy.init_params(jax.random.PRNGKey(0))
    tx_pi, tx_vf = make_optimizers(params, 3e-4, 1e-3)
    state = ReinforceState(
        params=params, pi_opt_state=tx_pi.init(params),
        vf_opt_state=tx_vf.init(params), rng=jax.random.PRNGKey(1),
        step=jnp.int32(0))
    update = make_reinforce_update(policy, 3e-4, 1e-3, 5, 0.99, 0.95, True)
    mesh = make_mesh(mesh_spec)
    sharded = make_sharded_update(update, mesh, state, donate_state=False,
                                  shard_time=shard_time)
    batch = make_batch(B, T, arch["obs_dim"], arch["act_dim"])
    st = place_state(state, mesh)
    db = place_batch(batch, mesh, shard_time=shard_time)
    _, compile_s = timed(lambda: sharded(st, db))
    (_, metrics), step_s = timed(lambda: sharded(st, db))
    print(f"[{label}] mesh={dict(mesh.shape)} compile={compile_s:.2f}s "
          f"step={step_s * 1e3:.1f}ms LossPi={float(metrics['LossPi']):.4f}",
          flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--virtual-devices", type=int, default=0, metavar="N",
                    help="run on N virtual CPU devices instead of the "
                         "devices JAX finds")
    args = ap.parse_args()
    if args.virtual_devices:
        # Sets XLA_FLAGS for the N-device host platform BEFORE jax can
        # latch them, then forces the CPU backend.
        from relayrl_tpu.utils.hostpin import pin_cpu

        pin_cpu(virtual_devices=args.virtual_devices)

    import jax

    from relayrl_tpu.utils.compile_cache import announce_learner_device

    announce_learner_device("sharded_learner")
    n = len(jax.devices())

    # Data + fully-sharded data + tensor parallel over an MLP learner.
    run({"kind": "mlp_discrete", "obs_dim": 32, "act_dim": 8,
         "hidden_sizes": [256, 256], "has_critic": True,
         "precision": "bfloat16"},
        {"dp": -1, "fsdp": 2 if n % 2 == 0 else 1,
         "tp": 2 if n % 4 == 0 else 1, "sp": 1},
        shard_time=False, label="mlp dp/fsdp/tp")

    # Sequence parallelism: ring attention over sp for a trajectory
    # transformer — the long-context path.
    if n % 2 == 0:
        run({"kind": "transformer_discrete", "obs_dim": 32, "act_dim": 8,
             "d_model": 64, "n_layers": 2, "n_heads": 4, "max_seq_len": 64,
             "has_critic": True, "attention": "ring"},
            {"dp": -1, "fsdp": 1, "tp": 1, "sp": 2},
            shard_time=True, label="transformer ring sp")


if __name__ == "__main__":
    main()
