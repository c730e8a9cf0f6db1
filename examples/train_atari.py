"""Pixel-policy training: the DQN-lineage Atari pipeline + CNN learner.

North-star shapes from BASELINE.md ("PPO Atari Pong (CNN)" /
"IMPALA-style async A2C Breakout"): 84x84x4 frame-stacked grayscale
observations into the Nature-DQN trunk. The image bakes no ALE, so the
default env is the in-repo catch toy (same preprocessing, real reward
structure); pass ``--env ALE/Pong-v5`` on a machine with
``gymnasium[atari]`` and the identical pipeline drives the real game.

    python examples/train_atari.py --algo PPO --updates 30
    python examples/train_atari.py --algo IMPALA --updates 30
"""

from __future__ import annotations

import argparse

import os
import sys

# Importable as a script from anywhere. The learner runs on the backend
# JAX finds (JAX_PLATFORMS=cpu keeps it off an accelerator).
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--algo", default="PPO",
                    choices=["PPO", "IMPALA", "DQN", "C51"])
    ap.add_argument("--env", default="synthetic",
                    help='"synthetic" (in-repo catch toy) or an ALE id '
                         'like "ALE/Pong-v5" (needs gymnasium[atari])')
    ap.add_argument("--frame-size", type=int, default=84)
    ap.add_argument("--updates", type=int, default=30)
    ap.add_argument("--target", type=float, default=None)
    ap.add_argument("--lr", type=float, default=None,
                    help="override the learning rate (unset: PPO uses 1e-3 "
                         "— pixel PPO is slow at the MLP default 3e-4 — "
                         "and every other algorithm keeps its own default)")
    ap.add_argument("--seed-salt", type=int, default=None,
                    help="pin the pid seed fold-in for reproducible runs")
    ap.add_argument("--frame-skip", type=int, default=4)
    ap.add_argument("--frame-stack", type=int, default=4)
    ap.add_argument("--shaped", action="store_true",
                    help="synthetic env only: add potential-based distance "
                         "shaping (dense reward — learnable in tens of "
                         "epochs instead of the sparse catch signal)")
    ap.add_argument("--raw-size", type=int, default=64,
                    help="synthetic env only: raw board size (smaller = "
                         "bigger sprites after downsize = easier perception)")
    ap.add_argument("--balls", type=int, default=4,
                    help="synthetic env only: ball drops per episode")
    ap.add_argument("--traj-per-epoch", type=int, default=8)
    ap.add_argument("--ent-coef", type=float, default=None,
                    help="entropy bonus (PPO/IMPALA): pixel policies "
                         "collapse to a blind deterministic policy without "
                         "one — 0.01 is a good start")
    ap.add_argument("--out", default=None,
                    help="env_dir for logs/progress.txt (default: cwd)")
    ap.add_argument("--conv", default=None, choices=["nature", "tpu"],
                    help="conv trunk preset: 'nature' (reference shape) or "
                         "'tpu' (MXU-lane channel widths 64/128/128 — "
                         "higher MFU on chip; docs/parallelism.md)")
    ap.add_argument("--bytes", action="store_true",
                    help="uint8 frames end-to-end: byte-range obs from the "
                         "pipeline (4x smaller trajectories), and for "
                         "DQN/C51 a uint8 replay ring (4x smaller replay + "
                         "checkpoints); the conv trunk scales /255 "
                         "on-device either way")
    args = ap.parse_args()

    from relayrl_tpu.envs import make_atari
    from relayrl_tpu.runtime.local_runner import LocalRunner
    from relayrl_tpu.utils.compile_cache import announce_learner_device

    announce_learner_device("train_atari")

    if args.shaped and args.env != "synthetic":
        ap.error("--shaped only applies to the synthetic env")
    env_kwargs = {}
    if args.env == "synthetic":
        env_kwargs = {"shaped": args.shaped, "raw_size": args.raw_size,
                      "balls": args.balls}
    env = make_atari(args.env, frame_size=args.frame_size,
                     frame_skip=args.frame_skip,
                     frame_stack=args.frame_stack,
                     obs_dtype="uint8" if args.bytes else "float32",
                     **env_kwargs)
    h, w, c = env.obs_shape
    hp = {"obs_shape": [h, w, c], "traj_per_epoch": args.traj_per_epoch}
    if args.bytes and args.algo in ("DQN", "C51"):
        hp["obs_dtype"] = "uint8"  # byte replay ring to match
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        hp["env_dir"] = args.out
    if args.lr is not None:
        hp["pi_lr"] = args.lr
        hp["lr"] = args.lr
    elif args.algo == "PPO":
        hp["pi_lr"] = 1e-3  # pixel PPO default; see --lr help
    if args.seed_salt is not None:
        hp["seed_salt"] = args.seed_salt
    if args.ent_coef is not None:
        hp["ent_coef"] = args.ent_coef
    if args.conv is not None:
        hp["conv_spec"] = args.conv
    if args.algo in ("PPO", "IMPALA"):
        hp["model_kind"] = "cnn_discrete"  # DQN/C51 switch on obs_shape alone
    runner = LocalRunner(env, algorithm_name=args.algo, **hp)
    done_updates = 0
    while done_updates < args.updates:
        result = runner.train(epochs=min(5, args.updates - done_updates),
                              max_steps=500)
        done_updates = runner.updates
        avg = result["avg_return_last_window"]
        print(f"[atari:{args.algo}] updates={done_updates} "
              f"avg_return={avg:.2f}", flush=True)
        if args.target is not None and avg >= args.target:
            print(f"[atari:{args.algo}] target {args.target} reached",
                  flush=True)
            break
    # Deterministic probe of the final policy (nothing reaches the learner).
    eval_result = runner.evaluate(episodes=10, max_steps=500)
    print(f"[atari:{args.algo}] greedy eval over 10 episodes: "
          f"avg_return={eval_result['avg_return']:.2f}", flush=True)


if __name__ == "__main__":
    main()
