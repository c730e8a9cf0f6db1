"""The full distributed loop: TrainingServer + N actor processes.

Covers the reference's whole example matrix (12 notebooks: REINFORCE
with/without baseline x envs x zmq/grpc — reference: examples/ tree) from
one driver, and extends it to every registered algorithm and the native
C++ transport:

    # reference cartpole_zmq.ipynb equivalent
    python examples/train_distributed.py --algo REINFORCE --baseline \
        --env cartpole --transport zmq --episodes 300

    # IMPALA-style async fleet (BASELINE.md north-star shape, scaled down)
    python examples/train_distributed.py --algo IMPALA --env cartpole \
        --actors 8 --episodes 100

    # off-policy continuous control over gRPC
    python examples/train_distributed.py --algo SAC --env pendulum \
        --transport grpc --episodes 100

Actors are OS processes (like the reference's separate agent processes),
each with its own policy copy, streaming trajectories to the one server and
hot-swapping on every publish.
"""

from __future__ import annotations

import argparse
import multiprocessing as mp
import os
import socket
import sys
import time

# Importable as a script from anywhere (parity with train_local.py /
# train_atari.py); spawn-context actor subprocesses re-execute this
# module top-level, so they get the same path fix.
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


_ENV_IDS = {"cartpole": "CartPole-v1",
            "pendulum": "Pendulum-v1",
            "lunarlander": "LunarLander-v3"}


def actor_proc(idx: int, server_type: str, agent_addrs: dict, env_id: str,
               episodes: int, max_steps: int, greedy_eval: int, queue,
               eval_barrier, num_envs: int = 1, host_mode: str = "process",
               unroll_length: int = 32):
    from relayrl_tpu.utils.hostpin import pin_cpu

    pin_cpu()  # actors are CPU hosts
    from relayrl_tpu.envs import make
    from relayrl_tpu.runtime.agent import Agent, run_eval_loop, run_gym_loop

    def _serve_actor_telemetry(tag: str) -> None:
        # telemetry.enabled in the shared config gives every actor
        # process its own registry (the Agent ctor configures it); the
        # server owns telemetry.port, so actors export on an ephemeral
        # port each. With the fleet plane on (telemetry.fleet_interval_s
        # > 0) these registries ALSO roll up to the root's /fleet pane —
        # the one URL the driver prints — so the per-process endpoint is
        # a drill-down, journaled as a telemetry_exporter event rather
        # than left to scroll away in stdout.
        from relayrl_tpu import telemetry

        if telemetry.get_registry().enabled:
            exporter = telemetry.serve(port=0)
            telemetry.emit("telemetry_exporter", proc=f"actor-{tag}",
                           url=exporter.url, tier="actor")
            print(f"[actor {tag}] telemetry at {exporter.url}", flush=True)

    if host_mode == "remote":
        # Thin-client topology (actor.host_mode="remote"): NO local
        # params, NO model subscription — every action is a round-trip
        # to the server-colocated InferenceService (the driver started
        # the server with serving=True). The trajectory plane is the
        # standard one, so run_gym_loop drives it unchanged.
        from relayrl_tpu.runtime.inference import RemoteActorClient

        client = RemoteActorClient(server_type=server_type, seed=idx,
                                   identity=f"remote-{idx}",
                                   **agent_addrs)
        _serve_actor_telemetry(f"{idx} remote")
        env = make(_ENV_IDS[env_id])
        t0 = time.time()
        returns = run_gym_loop(client, env, episodes=episodes,
                               max_steps=max_steps)
        train_s = time.time() - t0
        queue.put((idx, returns, client.model_version, [], train_s))
        client.disable_agent()
        return
    if host_mode == "anakin":
        # Fused on-device topology (actor.host_mode="anakin"): the env
        # runs as pure JAX inside the policy dispatch; each rollout()
        # produces a [num_envs, unroll_length] trajectory window. The
        # server-side view (N logical agents, N streams) is identical to
        # vector mode.
        from relayrl_tpu.runtime.agent import VectorAgent

        agent = VectorAgent(num_envs=num_envs, server_type=server_type,
                            seed=idx, host_mode="anakin",
                            jax_env=_ENV_IDS[env_id],
                            unroll_length=unroll_length, **agent_addrs)
        _serve_actor_telemetry(f"{idx} anakin")
        t0 = time.time()
        while min(len(r) for r in agent.host.episode_returns) < episodes:
            agent.rollout()
        train_s = time.time() - t0
        queue.put((idx, [ret for lane in agent.host.episode_returns
                         for ret in lane],
                   agent.model_version, [], train_s))
        agent.disable_agent()
        return
    if num_envs > 1 or host_mode == "vector":
        # Vector topology (actor.host_mode="vector" / --num-envs): this
        # process hosts num_envs logical agents behind one batched jitted
        # policy step; ``episodes`` stays the per-LANE target so rows are
        # comparable with process mode at the same actors x episodes.
        from relayrl_tpu.envs import make_vector
        from relayrl_tpu.runtime.agent import VectorAgent
        from relayrl_tpu.runtime.vector_actor import run_vector_gym_loop

        # host_mode is pinned explicitly: VectorAgent falls back to config
        # actor.host_mode, so a config saying "anakin" would otherwise
        # override the driver's resolved vector topology.
        agent = VectorAgent(num_envs=num_envs, server_type=server_type,
                            seed=idx, host_mode="vector", **agent_addrs)
        _serve_actor_telemetry(f"{idx} vector")
        venv = make_vector(_ENV_IDS[env_id], num_envs)
        t0 = time.time()
        per_lane: list[list[float]] = [[] for _ in range(num_envs)]
        while min(len(r) for r in per_lane) < episodes:
            for lane, chunk in enumerate(
                    run_vector_gym_loop(agent, venv, steps=max_steps)):
                per_lane[lane].extend(chunk)
        train_s = time.time() - t0
        # Greedy eval has no batched path (mode() is per-policy, and the
        # eval loop is deliberately unrecorded single-env); vector runs
        # report training returns only.
        queue.put((idx, [ret for lane in per_lane for ret in lane],
                   agent.model_version, [], train_s))
        agent.disable_agent()
        return
    agent = Agent(server_type=server_type, seed=idx, **agent_addrs)
    _serve_actor_telemetry(str(idx))
    env = make(_ENV_IDS[env_id])
    t0 = time.time()
    returns = run_gym_loop(agent, env, episodes=episodes, max_steps=max_steps)
    train_s = time.time() - t0
    greedy = []
    if greedy_eval > 0:
        # Rendezvous before evaluating: while any peer is still training,
        # its trajectories keep triggering publishes that would hot-swap
        # this actor's policy mid-eval and mix versions in the average.
        eval_barrier.wait(timeout=600)
        greedy = run_eval_loop(agent, env, episodes=greedy_eval,
                               max_steps=max_steps)
    queue.put((idx, returns, agent.model_version, greedy, train_s))
    agent.disable_agent()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--algo", default="REINFORCE")
    ap.add_argument("--env", default="cartpole",
                    choices=["cartpole", "pendulum", "lunarlander"],
                    help="lunarlander (the reference's committed-curve env, "
                         "examples/REINFORCE_without_baseline/box2d/"
                         "lunar_lander) needs gymnasium[box2d]")
    ap.add_argument("--transport", default="zmq",
                    choices=["zmq", "grpc", "native"])
    ap.add_argument("--actors", type=int, default=1)
    ap.add_argument("--num-envs", type=int, default=None, metavar="N",
                    help="env lanes per actor process (vector host, "
                         "runtime/vector_actor.py); default comes from "
                         "config actor.num_envs when actor.host_mode is "
                         "\"vector\" or \"anakin\", else 1 (process mode)")
    ap.add_argument("--host-mode", default=None,
                    choices=["process", "vector", "anakin", "remote"],
                    help="actor topology override: \"anakin\" fuses env + "
                         "policy into one on-device lax.scan dispatch per "
                         "[num-envs, unroll-length] window "
                         "(runtime/anakin.py; the env must be in the JAX "
                         "registry, envs.list_envs()['jax']); \"remote\" "
                         "runs thin clients against the server-colocated "
                         "batched InferenceService (runtime/inference.py "
                         "— no local params, no model subscription)")
    ap.add_argument("--unroll-length", type=int, default=None, metavar="U",
                    help="anakin mode: env steps per lane per fused "
                         "dispatch (default: config actor.unroll_length)")
    ap.add_argument("--episodes", type=int, default=200,
                    help="episodes PER actor (per lane in vector mode)")
    ap.add_argument("--max-steps", type=int, default=500)
    ap.add_argument("--baseline", action="store_true")
    ap.add_argument("--tensorboard", action="store_true")
    ap.add_argument("--greedy-eval", type=int, default=0, metavar="N",
                    help="after training, run N deterministic episodes per "
                         "actor (nothing recorded or shipped)")
    ap.add_argument("--hp", action="append", default=[], metavar="K=V",
                    help="extra algorithm hyperparameter (repeatable), e.g. "
                         "--hp ent_coef=0.05 --hp lr=1e-4; values parse as "
                         "JSON when possible, else stay strings")
    args = ap.parse_args()

    # This process is the learner: it runs on the backend JAX finds and
    # owns it. The actor children below are spawned (never forked) and pin
    # the CPU before they import jax — one process per chip.
    from relayrl_tpu.utils.compile_cache import announce_learner_device

    announce_learner_device("train_distributed")

    from relayrl_tpu.runtime.server import TrainingServer

    if args.transport == "zmq":
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

    hp: dict = {}
    if args.algo.upper() == "REINFORCE":
        hp["with_vf_baseline"] = args.baseline
    if args.env == "pendulum":
        hp["discrete"] = False
        hp["act_limit"] = 2.0
    for kv in args.hp:
        key, _, raw = kv.partition("=")
        if not _:
            raise SystemExit(f"--hp expects K=V, got {kv!r}")
        try:
            import json

            hp[key] = json.loads(raw)
        except ValueError:
            hp[key] = raw

    env_dims = {"cartpole": (4, 2), "pendulum": (3, 1),
                "lunarlander": (8, 4)}
    obs_dim, act_dim = env_dims[args.env]

    # actor.host_mode="vector" in relayrl_config.json turns every actor
    # process into a vector host of actor.num_envs lanes; --num-envs
    # overrides (and >1 implies vector mode).
    from relayrl_tpu.config import ConfigLoader

    actor_params = ConfigLoader(create_if_missing=False).get_actor_params()
    host_mode = (args.host_mode if args.host_mode is not None
                 else actor_params["host_mode"])
    num_envs = (args.num_envs if args.num_envs is not None
                else (actor_params["num_envs"]
                      if host_mode in ("vector", "anakin") else 1))
    if host_mode == "process" and num_envs > 1:
        host_mode = "vector"  # --num-envs N>1 implies the vector host
    unroll_length = (args.unroll_length if args.unroll_length is not None
                     else actor_params["unroll_length"])
    if host_mode == "anakin":
        from relayrl_tpu.envs import list_envs

        if _ENV_IDS[args.env] not in list_envs()["jax"]:
            raise SystemExit(
                f"--host-mode anakin needs an env in the JAX registry "
                f"(envs.list_envs()['jax']); {args.env!r} is host-only")
    if host_mode != "process" and args.greedy_eval > 0:
        print(f"[driver] --greedy-eval ignored in {host_mode} mode (no "
              "batched greedy path)", flush=True)
    if host_mode == "remote":
        # Thin clients need the serving plane up server-side; the zmq
        # (and native-passthrough) action channel gets its own port.
        if args.transport != "grpc":
            serving_addr = f"tcp://127.0.0.1:{free_port()}"
            server_addrs["serving_addr"] = serving_addr
            agent_addrs["serving_addr"] = serving_addr
        else:
            server_addrs["native_grpc"] = False  # GetActions is grpcio-only

    server = TrainingServer(
        args.algo, obs_dim=obs_dim, act_dim=act_dim,
        server_type=args.transport, env_dir=".",
        serving=(True if host_mode == "remote" else None),
        tensorboard=args.tensorboard, hyperparams=hp, **server_addrs)

    # ONE pane of glass for the whole run: with telemetry enabled the
    # root serves /metrics + /snapshot; with the fleet plane on
    # (telemetry.fleet_interval_s > 0) every actor's registry rolls up
    # behind /fleet too, and `telemetry.top --fleet --url <root>` is the
    # merged view — actor exporter URLs are journaled drill-downs, not
    # the discovery surface.
    from relayrl_tpu import telemetry as _telemetry

    if server._exporter is not None:
        _telemetry.emit("telemetry_exporter", proc="server",
                        url=server._exporter.url, tier="server")
        if server._fleet is not None:
            print(f"[driver] fleet telemetry at "
                  f"{server._exporter.url}/fleet "
                  f"(python -m relayrl_tpu.telemetry.top --fleet --url "
                  f"{server._exporter.url})", flush=True)
        else:
            print(f"[driver] telemetry at {server._exporter.url} (set "
                  f"telemetry.fleet_interval_s > 0 for the merged /fleet "
                  f"pane)", flush=True)

    ctx = mp.get_context("spawn")
    queue = ctx.Queue()
    eval_barrier = ctx.Barrier(args.actors)
    procs = [
        ctx.Process(target=actor_proc,
                    args=(i, args.transport, agent_addrs, args.env,
                          args.episodes, args.max_steps, args.greedy_eval,
                          queue, eval_barrier, num_envs, host_mode,
                          unroll_length))
        for i in range(args.actors)
    ]
    for p in procs:
        p.start()
    # Collect with a liveness check: an actor that dies before queue.put
    # (e.g. --env lunarlander without gymnasium[box2d]) must fail the
    # driver, not wedge it on a queue.get that will never be fed.
    results = []
    while len(results) < len(procs):
        try:
            results.append(queue.get(timeout=1.0))
        except Exception:
            reported = {r[0] for r in results}
            dead = [(i, p.exitcode) for i, p in enumerate(procs)
                    if p.exitcode is not None and i not in reported]
            if dead and len(results) + len(dead) >= len(procs):
                # every still-unreported actor is gone (any exit code —
                # a clean sys.exit(0) before reporting is just as wedging)
                server.disable_server()
                raise SystemExit(
                    f"actor(s) {dead} ((idx, exitcode)) exited before "
                    f"reporting — see the traceback above")
    for p in procs:
        p.join()
    elapsed = max(r[4] for r in results)  # training-only, excludes eval

    # Actors just finished: wait for the last episodes to arrive off the
    # sockets, then drain the learner.
    total_expected = args.actors * args.episodes * num_envs
    deadline = time.time() + 10
    while (server.stats["trajectories"] < total_expected
           and time.time() < deadline):
        time.sleep(0.05)
    server.drain()
    total_eps = sum(len(r) for _, r, _, _, _ in results)
    last = [r[-1] for _, r, _, _, _ in sorted(results)]
    print(f"\n[distributed] {args.actors} actor(s) x {args.episodes} eps in "
          f"{elapsed:.1f}s ({total_eps / elapsed:.1f} eps/s); final returns "
          f"per actor: {[round(x, 1) for x in last]}; server version "
          f"{server.algorithm.version}", flush=True)
    if args.greedy_eval > 0 and host_mode == "process":
        greedy = [g for _, _, _, gs, _ in results for g in gs]
        print(f"[distributed] greedy eval ({args.greedy_eval} eps/actor): "
              f"avg {sum(greedy) / len(greedy):.1f}  "
              f"{[round(g, 1) for g in greedy]}", flush=True)
    server.disable_server()


if __name__ == "__main__":
    main()
