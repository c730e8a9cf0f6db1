"""Minimum end-to-end slice: in-process actor + jitted learner, no sockets.

Equivalent of the reference's single-kernel notebook loop
(reference: examples/README.md:125-152 — request_for_action -> env.step ->
flag_last_action) with the network replaced by the in-memory wire codec.

    python examples/train_local.py --algo REINFORCE --env cartpole \
        --baseline --updates 40
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
    ap.add_argument("--algo", default="REINFORCE",
                    help="any registered algorithm (REINFORCE/PPO/IMPALA/"
                         "DQN/C51 for cartpole; DDPG/TD3/SAC for pendulum)")
    ap.add_argument("--env", default="cartpole",
                    choices=["cartpole", "pendulum", "lunarlander"])
    ap.add_argument("--baseline", action="store_true",
                    help="REINFORCE: add the value baseline")
    ap.add_argument("--updates", type=int, default=40)
    ap.add_argument("--target", type=float, default=None,
                    help="stop early once the rolling avg return passes this")
    ap.add_argument("--continuous", action="store_true",
                    help="lunarlander only: the continuous-action variant "
                         "(needs Gymnasium Box2D) for the DDPG/TD3/SAC "
                         "family")
    ap.add_argument("--hp", action="append", default=[], metavar="K=V",
                    help="algorithm hyperparameter overrides, e.g. "
                         "--hp gamma=0.999 --hp ent_coef=0.01; values parse "
                         "as JSON with string fallback (parity with "
                         "train_distributed --hp)")
    ap.add_argument("--eval-episodes", type=int, default=10)
    args = ap.parse_args()

    from relayrl_tpu.envs import make
    from relayrl_tpu.runtime.local_runner import LocalRunner
    from relayrl_tpu.utils.compile_cache import announce_learner_device

    announce_learner_device("train_local")

    if args.continuous and args.env != "lunarlander":
        ap.error("--continuous only applies to --env lunarlander")
    hp = {}
    env_kwargs = {}
    if args.algo.upper() == "REINFORCE":
        hp["with_vf_baseline"] = args.baseline
    if args.env == "pendulum":
        hp.setdefault("discrete", False)
        hp.setdefault("act_limit", 2.0)
    if args.continuous:
        hp.setdefault("discrete", False)
        hp.setdefault("act_limit", 1.0)
        env_kwargs["continuous"] = True
    import json

    for kv in args.hp:
        key, sep, raw = kv.partition("=")
        if not sep:
            raise SystemExit(f"--hp expects K=V, got {kv!r}")
        try:
            hp[key] = json.loads(raw)
        except json.JSONDecodeError:
            hp[key] = raw

    env_ids = {"cartpole": "CartPole-v1", "pendulum": "Pendulum-v1",
               "lunarlander": "LunarLander-v3"}
    runner = LocalRunner(make(env_ids[args.env], **env_kwargs),
                         algorithm_name=args.algo, **hp)
    done_updates = 0
    while done_updates < args.updates:
        result = runner.train(epochs=min(5, args.updates - done_updates))
        done_updates = runner.updates
        avg = result["avg_return_last_window"]
        print(f"[local] updates={done_updates} avg_return={avg:.1f}",
              flush=True)
        if args.target is not None and avg >= args.target:
            print(f"[local] target {args.target} reached", flush=True)
            break
    # Deterministic probe of the final policy (nothing reaches the learner).
    eval_result = runner.evaluate(episodes=args.eval_episodes)
    print(f"[local] greedy eval over {args.eval_episodes} episodes: "
          f"avg_return={eval_result['avg_return']:.1f}", flush=True)


if __name__ == "__main__":
    main()
