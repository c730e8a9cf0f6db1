"""The reference's examples matrix, scripted: algorithms x transports.

The reference ships 12 notebooks (2 algorithms x 3 env families x 2
transports — reference: examples/ tree, loop at examples/README.md:125-152)
as manual end-to-end tests with committed progress.txt artifacts. This
script runs the equivalent matrix headlessly: for each (algorithm,
transport) cell it stands up a real TrainingServer + Agent over localhost
sockets, drives the gym loop until the learner has published N updates, and
leaves each cell's EpochLogger progress.txt behind as the artifact.

    python examples/run_matrix.py --updates 3 --out matrix_artifacts

Cells (12): {REINFORCE (with + without baseline), PPO, IMPALA} across
{zmq, grpc, native} on CartPole-v1 (gymnasium when installed, built-in
dynamics otherwise); the full off-policy family end-to-end — DQN
(replay/warmup/target-net, CartPole over zmq), C51 (distributional,
CartPole over grpc), and the three continuous actors SAC / TD3 / DDPG
(float action vectors on the wire, Pendulum over native/zmq/native) —
and a pixel cell (CNN policy + Atari preprocessing over zmq). Every
registered algorithm has at least one live-transport cell; `--only TAG`
refreshes individual cells without a full regen.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


_CARTPOLE = ("CartPole-v1", 4, 2)
_PENDULUM = ("Pendulum-v1", 3, 1)

# Per-cell metadata:
#   expects: "learning" — the committed golden must show an improving
#            greedy return at the golden budget; "wiring" — the cell is a
#            plumbing/e2e smoke whose budget is too small for a trend
#            (its learning evidence lives elsewhere: the offline goldens).
#   updates_scale: multiplier on the --updates budget (off-policy cells
#            need more updates than epochs to move).
CELLS = [
    ("REINFORCE", {"with_vf_baseline": True}, "zmq", _CARTPOLE,
     {"expects": "learning"}),
    ("REINFORCE", {"with_vf_baseline": False}, "grpc", _CARTPOLE,
     {"expects": "learning"}),
    # The native C++ framed-TCP core, end-to-end through the same loop
    # (skipped with a notice when the .so isn't built).
    ("REINFORCE", {"with_vf_baseline": True}, "native", _CARTPOLE,
     {"expects": "learning"}),
    ("PPO", {}, "zmq", _CARTPOLE, {"expects": "learning"}),
    ("PPO", {}, "grpc", _CARTPOLE, {"expects": "learning"}),
    # The async staleness-corrected family over the default transport.
    ("IMPALA", {}, "zmq", _CARTPOLE, {"expects": "learning"}),
    # Off-policy families:
    # replay/warmup/target-net over zmq, and continuous squashed-Gaussian
    # actions over the native wire. The DQN cell is sized to learn: the
    # epsilon schedule completes inside the cell budget and the update-
    # to-data ratio is high enough for the greedy policy to clear random
    # CartPole (a cell whose curve declines is not evidence).
    # Stability-tuned: at ratio 1.0 / lr 5e-4 / polyak 0.995 this cell
    # SOLVED CartPole then diverged (LossQ exploding to 1e5 on some runs,
    # timing-dependent). Slow targets (polyak .999), quarter update
    # ratio, and a tight per-ingest cap keep the target chase stable:
    # greedy 9 -> 200 (the cap) in ~100 s, repeatably.
    ("DQN", {"update_after": 256, "batch_size": 64, "updates_per_step": 0.25,
             "traj_per_epoch": 8, "hidden_sizes": [64, 64], "lr": 2.5e-4,
             "polyak": 0.999, "max_updates_per_ingest": 8,
             "epsilon_decay_steps": 3000, "epsilon_end": 0.05}, "zmq",
     _CARTPOLE, {"expects": "learning", "updates_scale": 40,
                 # the greedy trend is only meaningful once the epsilon
                 # schedule has completed; "updates" here counts
                 # trajectory-grain ingest events (~17+ env steps each),
                 # so 500 of them is comfortably past the 3000-env-step
                 # decay horizon
                 "trend_gate_updates": 500}),
    ("SAC", {"update_after": 64, "batch_size": 32, "updates_per_step": 0.25,
             "traj_per_epoch": 4, "hidden_sizes": [32, 32],
             "discrete": False, "act_limit": 2.0}, "native", _PENDULUM,
     {"expects": "wiring"}),  # trained SAC golden: examples/golden/sac_*
    # Remaining registered algorithms, one committed socket cell each so
    # EVERY algorithm has live-transport artifact coverage (their trained
    # curves live in the offline goldens: cartpole_c51, td3_pendulum,
    # ddpg_pendulum). Transports spread across the three planes.
    ("C51", {"update_after": 64, "batch_size": 32, "updates_per_step": 0.25,
             "traj_per_epoch": 4, "hidden_sizes": [32, 32], "n_atoms": 21,
             "epsilon_decay_steps": 1000, "epsilon_end": 0.05}, "grpc",
     _CARTPOLE, {"expects": "wiring", "updates_scale": 4}),
    ("TD3", {"update_after": 64, "batch_size": 32, "updates_per_step": 0.25,
             "traj_per_epoch": 4, "hidden_sizes": [32, 32],
             "discrete": False, "act_limit": 2.0}, "zmq", _PENDULUM,
     {"expects": "wiring"}),
    ("DDPG", {"update_after": 64, "batch_size": 32, "updates_per_step": 0.25,
              "traj_per_epoch": 4, "hidden_sizes": [32, 32],
              "discrete": False, "act_limit": 2.0}, "native", _PENDULUM,
     {"expects": "wiring"}),
    # Pixel cell: the CNN policy +
    # Atari preprocessing pipeline end-to-end over sockets — flat uint8
    # frames on the wire, Nature-trunk learner, hot-swap back.
    ("PPO", {"model_kind": "cnn_discrete", "obs_shape": [36, 36, 2],
             "pi_lr": 1e-3}, "zmq", ("pixel36", 36 * 36 * 2, 3),
     {"expects": "wiring"}),  # trained pixel golden: examples/golden/pixel_*
]


def _make_env(env_id: str):
    if env_id == "pixel36":
        from relayrl_tpu.envs import make_atari

        return make_atari("synthetic", frame_size=36, frame_stack=2,
                          frame_skip=2, raw_size=48, shaped=True)
    from relayrl_tpu.envs import make

    return make(env_id)


def cell_tag(algo: str, hp: dict, transport: str, env_spec: tuple) -> str:
    """The cell's artifact-directory tag — single definition, used by both
    run_cell and the --only filter so they can't drift."""
    env_id = env_spec[0]
    env_tag = ("" if env_id == "CartPole-v1"
               else f"_{env_id.split('-')[0].lower()}")
    return (f"{algo.lower()}"
            f"{'_baseline' if hp.get('with_vf_baseline') else ''}"
            f"{env_tag}_{transport}")


def run_cell(algo: str, hp: dict, transport: str, env_spec: tuple,
             updates: int, out_dir: str, meta: dict | None = None) -> dict:
    from relayrl_tpu.runtime.agent import Agent, greedy_episodes, run_gym_loop
    from relayrl_tpu.runtime.server import TrainingServer

    meta = meta or {}
    updates = int(updates * meta.get("updates_scale", 1))

    env_id, obs_dim, act_dim = env_spec
    tag = cell_tag(algo, hp, transport, env_spec)
    cell_dir = os.path.abspath(os.path.join(out_dir, tag))
    os.makedirs(cell_dir, exist_ok=True)
    if transport == "zmq":
        server_addrs = {
            "agent_listener_addr": f"tcp://127.0.0.1:{free_port()}",
            "trajectory_addr": f"tcp://127.0.0.1:{free_port()}",
            "model_pub_addr": f"tcp://127.0.0.1:{free_port()}",
        }
        agent_addrs = {
            "agent_listener_addr": server_addrs["agent_listener_addr"],
            "trajectory_addr": server_addrs["trajectory_addr"],
            "model_sub_addr": server_addrs["model_pub_addr"],
        }
    else:
        port = free_port()
        server_addrs = {"bind_addr": f"127.0.0.1:{port}"}
        agent_addrs = {"server_addr": f"127.0.0.1:{port}"}

    env = _make_env(env_id)
    server = TrainingServer(
        algo, obs_dim=obs_dim, act_dim=act_dim, server_type=transport,
        env_dir=cell_dir,
        hyperparams={"traj_per_epoch": 4, "hidden_sizes": [32, 32], **hp},
        **server_addrs,
    )
    t0 = time.time()
    returns: list[float] = []
    greedy_first: list[float] = []
    greedy_final: list[float] = []
    try:
        agent = Agent(server_type=transport, handshake_timeout_s=60,
                      model_path=os.path.join(cell_dir, "client_model.msgpack"),
                      seed=0, **agent_addrs)
        try:
            # Deterministic eval BEFORE training: the committed artifact
            # then shows the greedy trend, not the exploration-noised
            # sampling returns.
            greedy_first = greedy_episodes(agent.actor, _make_env(env_id),
                                           episodes=5, max_steps=200)
            while server.stats["updates"] < updates:
                returns += run_gym_loop(agent, env, episodes=2, max_steps=200)
            # Let the starved subscriber thread catch up to the server's
            # latest publish before the final eval — otherwise the greedy
            # probe scores a model many versions stale (the gym loop hogs
            # the GIL on a 1-core host).
            deadline = time.time() + 20
            while time.time() < deadline:
                if agent.model_version >= server.latest_model_version:
                    break
                time.sleep(0.1)
            greedy_final = greedy_episodes(agent.actor, _make_env(env_id),
                                           episodes=5, max_steps=200)
        finally:
            agent.disable_agent()
    finally:
        server.drain(timeout=30)
        server.disable_server()
    progress = None
    for root, _dirs, files in os.walk(cell_dir):
        if "progress.txt" in files:
            progress = os.path.join(root, "progress.txt")
    result = {
        "cell": tag, "expects": meta.get("expects", "wiring"),
        "updates": server.stats["updates"],
        "trajectories": server.stats["trajectories"],
        "dropped": server.stats["dropped"],
        "final_model_version": agent.model_version,
        "episodes": len(returns),
        "avg_return": round(sum(returns) / max(1, len(returns)), 2),
        # Greedy (deterministic) eval of the model the agent actually
        # holds, before and after training — the trend evidence.
        "greedy_return_initial": round(
            sum(greedy_first) / max(1, len(greedy_first)), 2),
        "greedy_return_final": round(
            sum(greedy_final) / max(1, len(greedy_final)), 2),
        "wall_s": round(time.time() - t0, 1),
        "progress_txt": os.path.relpath(progress, out_dir) if progress else None,
    }
    print(json.dumps(result), flush=True)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--updates", type=int, default=3,
                    help="learner updates per cell before moving on")
    ap.add_argument("--out", default="matrix_artifacts")
    ap.add_argument("--only", default=None,
                    help="run only cells whose tag contains this substring "
                         "(for adding/refreshing individual cells without "
                         "a full regen)")
    args = ap.parse_args()

    from relayrl_tpu.transport.native_backend import native_available
    from relayrl_tpu.utils.compile_cache import announce_learner_device

    # Server and agent share this process, so both run on the backend JAX
    # finds (JAX_PLATFORMS=cpu keeps the matrix off an accelerator).
    announce_learner_device("matrix")
    cells = [c for c in CELLS
             if c[2] != "native" or native_available()]
    if len(cells) < len(CELLS):  # before --only: that filter also shrinks
        print("[matrix] native .so unavailable — skipping native cells",
              flush=True)
    if args.only:
        cells = [c for c in cells
                 if args.only in cell_tag(c[0], c[1], c[2], c[3])]
        assert cells, f"--only {args.only!r} matched no cells"
    os.makedirs(args.out, exist_ok=True)
    results = [run_cell(algo, hp, transport, env_spec, args.updates,
                        args.out, meta)
               for algo, hp, transport, env_spec, meta in cells]
    # Write the artifact BEFORE the asserts: a failed trend gate must not
    # discard tens of minutes of per-cell results.
    with open(os.path.join(args.out, "summary.json"), "w") as f:
        json.dump(results, f, indent=1)
    assert all(r["dropped"] == 0 for r in results)
    assert all(r["final_model_version"] >= 1 for r in results), (
        "model hot-swap must reach the agent in every cell")
    for r, (_a, _h, _t, _e, meta) in zip(results, cells):
        if (r["expects"] == "learning"
                and r["updates"] >= meta.get("trend_gate_updates", 20)):
            assert r["greedy_return_final"] >= r["greedy_return_initial"], (
                f"{r['cell']}: committed 'learning' golden trends downward "
                f"({r['greedy_return_initial']} -> "
                f"{r['greedy_return_final']})")
    print(f"[matrix] {len(results)} cells ok -> {args.out}/summary.json",
          flush=True)


if __name__ == "__main__":
    main()
