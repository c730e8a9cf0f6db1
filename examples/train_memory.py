"""Long-context showcase: solve a memory task with a sequence policy.

``RecallEnv`` shows a one-hot cue at t=0, hides it for the rest of the
episode, and scores only the final action: any memoryless (per-step MLP)
policy is capped at chance (1/n_cues), while the transformer sequence
policy attends back to the cue and solves it (~1.0). No equivalent exists
in the reference — its only models are per-step 2x128 MLPs
(relayrl_framework/src/native/python/algorithms/REINFORCE/kernel.py:14-21).

    python examples/train_memory.py --model transformer --epochs 50
    python examples/train_memory.py --model mlp --epochs 30   # stays ~0.5

The committed golden curve lives at examples/golden/recall_transformer/.
"""

from __future__ import annotations

import argparse
import os
import sys

# Importable when run as a script from anywhere (the script dir, not the
# cwd, lands on sys.path).
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="transformer",
                    choices=["transformer", "mlp"])
    ap.add_argument("--epochs", type=int, default=50)
    ap.add_argument("--horizon", type=int, default=8)
    ap.add_argument("--attention", default="dense",
                    choices=["dense", "blockwise", "flash"],
                    help="attention backend for the transformer policy")
    ap.add_argument("--env-dir", default="./env_memory")
    args = ap.parse_args()

    from relayrl_tpu.envs import RecallEnv
    from relayrl_tpu.runtime.local_runner import LocalRunner
    from relayrl_tpu.utils.compile_cache import announce_learner_device

    announce_learner_device("train_memory")

    bucket = max(16, 2 * args.horizon)
    hp = dict(with_vf_baseline=True, gamma=1.0, lam=0.95, traj_per_epoch=32,
              pi_lr=1e-3, vf_lr=1e-3, train_vf_iters=20,
              bucket_lengths=(bucket,))
    if args.model == "transformer":
        hp.update(model_kind="transformer_discrete", d_model=32, n_layers=1,
                  n_heads=2, max_seq_len=bucket, attention=args.attention,
                  attention_block=bucket)
    else:
        hp.update(hidden_sizes=[64, 64])

    runner = LocalRunner(RecallEnv(horizon=args.horizon), "REINFORCE",
                         env_dir=args.env_dir, seed=0, **hp)
    for block in range(0, args.epochs, 5):
        result = runner.train(epochs=min(5, args.epochs - block))
        avg = result["avg_return_last_window"]
        print(f"[memory/{args.model}] updates={runner.updates} "
              f"avg_return={avg:.2f} (chance=0.5, solved=1.0)", flush=True)
        if avg >= 0.98:
            print(f"[memory/{args.model}] solved", flush=True)
            break


if __name__ == "__main__":
    main()
