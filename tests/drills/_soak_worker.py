"""Worker process of the fleet drills (``tests/drills/soak.py``).

Modes, selected by the JSON config:

* default: ``agents_per_proc`` real :class:`relayrl_tpu.runtime.Agent`
  instances in threads (each with its own DEALER/PUSH/SUB sockets — the
  socket topology the server sees is that of N separate actor processes).
  Each drives a synthetic env loop: request_for_action per step,
  flag_last_action at episode end, model hot-swap via SUB.
* ``"vector": true``: ONE :class:`relayrl_tpu.runtime.VectorAgent` hosting
  ``agents_per_proc`` logical agents — one batched policy dispatch per
  step, one transport connection, one model subscription. The server
  still sees ``agents_per_proc`` registered agents and per-lane streams.
* ``"anakin": true``: ONE VectorAgent in fused-rollout mode — the env
  (``cfg["jax_env"]``, default CartPole-v1) runs on-device inside the scan
  (runtime/anakin.py): real episodes, real terminal markers.
* ``"serving": true``: thin clients against the server's InferenceService,
  one ``RemoteActorClient`` per thread, or with ``"serving_mux": true``
  one streamed ``MultiplexedRemoteClient`` for all lanes.

Usage: _soak_worker.py <json-config>. Writes a JSON result file: one row
per LOGICAL agent (identity, steps, episodes, final model version, crash
text), so the coordinator's accounting does not depend on the topology.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time


def transport_addrs(cfg: dict) -> dict:
    return {k: cfg[k] for k in ("agent_listener_addr", "trajectory_addr",
                                "model_sub_addr")}


def start_barrier_wait(cfg: dict, ident: str, publish_ready: bool) -> None:
    """Cross-PROCESS start barrier (one ready file per worker, one go file
    from the coordinator): without it each process opens its window as
    soon as ITS agents are up, while sibling processes are still importing
    jax, and the windows barely overlap. Opt-in via cfg. The go wait must
    OUTLAST the coordinator's ready-wait (it releases at the last worker's
    readiness or its own timeout, whichever first)."""
    if not cfg.get("start_barrier"):
        return
    if publish_ready:
        with open(os.path.join(cfg["scratch"],
                               f"ready_{cfg['worker_id']}"), "w") as f:
            f.write(ident)
    go_path = os.path.join(cfg["scratch"], "go")
    go_deadline = time.time() + cfg.get("go_timeout_s", 360.0)
    while not os.path.exists(go_path) and time.time() < go_deadline:
        time.sleep(0.05)


def lane_rows(identities: list, *, steps: list, episodes: list,
              final_version: int, crashed: str | None) -> list[dict]:
    """One result row per logical lane of a batched host."""
    return [{"identity": ident, "steps": steps[lane],
             "episodes": episodes[lane], "final_version": final_version,
             "crashed": crashed}
            for lane, ident in enumerate(identities)]


def telemetry_setup(cfg: dict) -> None:
    """Install the fault plan via the env hook BEFORE any Agent is
    constructed, and a real registry whenever the result has to carry
    this process's counters (chaos accounting) or its tracer's evidence
    (``trace_rate`` > 0: the actors mint trajectory trace contexts that
    ride the envelope ids to the server, where data age is observed)."""
    if cfg.get("fault_plan"):
        from relayrl_tpu import faults

        os.environ[faults.ENV_VAR] = cfg["fault_plan"]
    rate = float(cfg.get("trace_rate") or 0.0)
    if cfg.get("chaos_telemetry") or rate > 0:
        from relayrl_tpu import telemetry

        telemetry.set_registry(telemetry.Registry(
            run_id=f"drill-worker-{cfg['worker_id']}"))
    if rate > 0:
        from relayrl_tpu.telemetry import trace

        trace.configure(rate, journal=False)


def write_result(cfg: dict, agents: list) -> None:
    result = {"worker_id": cfg["worker_id"], "agents": agents}
    if cfg.get("chaos_telemetry") or float(cfg.get("trace_rate") or 0.0) > 0:
        from relayrl_tpu import telemetry

        result["telemetry"] = telemetry.get_registry().snapshot()
    with open(cfg["result_path"], "w") as f:
        json.dump(result, f)


def chaos_finish(agent, row: dict, cfg: dict) -> None:
    """End-of-window chaos accounting for one agent row: final spool
    flush (a full replay pass — the at-least-once guarantee the server's
    dedup turns into exactly-once) and the per-agent sent-seq counts the
    coordinator reconciles against the server ledger."""
    spool = getattr(agent, "spool", None)
    if spool is None:
        return
    if cfg.get("final_replay"):
        # Convergence phase: injection STOPS (the chaos contract — the
        # window abused the system; now it must heal), then one full
        # replay pass must land so the coordinator's zero-loss accounting
        # is about recovery, not about racing a live fault.
        from relayrl_tpu import faults

        faults.deactivate()
        row["spool_flushed"] = spool.flush(deadline_s=45.0)
        # zmq's PUSH is fire-and-forget: a replay burst still sits in
        # libzmq's pipe when this thread moves on, and disable_agent's
        # linger=0 close would drop the tail — give the wire a beat.
        time.sleep(2.0)
    row["sent_counts"] = spool.sent_counts()
    row["spool_depth"] = spool.depth


def agent_loop(cfg: dict, agent_idx: int, out: dict,
               barrier: threading.Barrier):
    """One real Agent (or, with ``serving``, one RemoteActorClient: no
    local params, no model subscription, every action a round-trip to the
    InferenceService) driving the synthetic env loop."""
    import numpy as np

    from relayrl_tpu import faults

    ident = f"soak-{cfg['worker_id']}-{agent_idx}"
    seed = cfg["worker_id"] * 1000 + agent_idx
    if cfg.get("serving"):
        from relayrl_tpu.runtime.inference import RemoteActorClient

        agent = RemoteActorClient(
            config_path=cfg.get("config_path"), seed=seed,
            server_type="zmq", identity=ident,
            serving_addr=cfg.get("serving_addr"), **transport_addrs(cfg))
    else:
        from relayrl_tpu.runtime.agent import Agent

        agent = Agent(
            model_path=os.path.join(cfg["scratch"],
                                    f"model_{ident}.msgpack"),
            config_path=cfg.get("config_path"), seed=seed,
            handshake_timeout_s=cfg["handshake_timeout_s"],
            server_type="zmq", **transport_addrs(cfg))
    rng = np.random.default_rng(agent_idx)
    obs_dim, ep_len = cfg["obs_dim"], cfg["episode_len"]
    steps = episodes = 0
    # line up all agents in this process, then all processes (agent 0 of
    # each worker publishes the readiness file)
    try:
        barrier.wait(timeout=cfg["handshake_timeout_s"] + 30)
    except threading.BrokenBarrierError:
        pass  # a sibling died in construction; run solo rather than hang
    start_barrier_wait(cfg, ident, publish_ready=agent_idx == 0)
    # actor.step kill site: a plan rule {"site": "actor.step",
    # "op": "kill_process", "at": N} SIGKILLs this worker at env step N.
    # None without a plan.
    fault_step = faults.site("actor.step")
    deadline = time.time() + cfg["duration_s"]
    crashed = None
    try:
        while time.time() < deadline:
            obs = rng.standard_normal(obs_dim).astype(np.float32)
            reward = 0.0
            for _ in range(ep_len):
                if fault_step is not None and fault_step.take_kill_process():
                    import signal

                    os.kill(os.getpid(), signal.SIGKILL)
                agent.request_for_action(obs, reward=reward)
                obs = rng.standard_normal(obs_dim).astype(np.float32)
                reward = 1.0
                steps += 1
                # Deadline check INSIDE the episode: on an oversubscribed
                # host one episode can take many seconds. The cut episode
                # still terminates cleanly on the wire.
                if time.time() >= deadline:
                    break
            agent.flag_last_action(reward, terminated=True)
            episodes += 1
    except Exception as e:  # a crashed agent must still report its row
        crashed = repr(e)
    row = {"identity": ident, "steps": steps, "episodes": episodes,
           "final_version": agent.model_version, "crashed": crashed}
    chaos_finish(agent, row, cfg)
    out[agent_idx] = row
    agent.disable_agent()


def vector_host_loop(cfg: dict) -> list[dict]:
    """One VectorAgent, ``agents_per_proc`` logical lanes, one batched
    policy dispatch per env step for the whole lane set."""
    import numpy as np

    from relayrl_tpu.runtime.agent import VectorAgent

    n_lanes = cfg["agents_per_proc"]
    ident = f"soak-{cfg['worker_id']}-vec"
    agent = VectorAgent(
        num_envs=n_lanes,
        model_path=os.path.join(cfg["scratch"], f"model_{ident}.msgpack"),
        config_path=cfg.get("config_path"),
        seed=cfg["worker_id"] * 1000,
        handshake_timeout_s=cfg["handshake_timeout_s"],
        server_type="zmq", identity=ident, **transport_addrs(cfg))
    rng = np.random.default_rng(cfg["worker_id"])
    obs_dim, ep_len = cfg["obs_dim"], cfg["episode_len"]
    steps = episodes = 0  # per lane: every lane steps once per dispatch
    start_barrier_wait(cfg, ident, publish_ready=True)
    deadline = time.time() + cfg["duration_s"]
    crashed = None
    try:
        while time.time() < deadline:
            obs = rng.standard_normal((n_lanes, obs_dim)).astype(np.float32)
            rewards = None
            for _ in range(ep_len):
                agent.request_for_actions(obs, rewards=rewards)
                obs = rng.standard_normal((n_lanes, obs_dim)).astype(
                    np.float32)
                rewards = [1.0] * n_lanes
                steps += 1
                if time.time() >= deadline:
                    break  # same mid-episode cut as the threaded loop
            for lane in range(n_lanes):
                agent.flag_last_action(lane, 1.0, terminated=True)
            episodes += 1
    except Exception as e:
        crashed = repr(e)
    rows = lane_rows(agent.agent_ids, steps=[steps] * n_lanes,
                     episodes=[episodes] * n_lanes,
                     final_version=agent.model_version, crashed=crashed)
    # Chaos accounting rides the lane-0 row (ONE spool per connection
    # covering all lanes — sent_counts is keyed per lane id already).
    chaos_finish(agent, rows[0], cfg)
    agent.disable_agent()
    return rows


def anakin_host_loop(cfg: dict) -> list[dict]:
    """One VectorAgent hosting ``agents_per_proc`` lanes of an ON-DEVICE
    env, driven by fused rollout windows until the deadline."""
    from relayrl_tpu.runtime.agent import VectorAgent

    n_lanes = cfg["agents_per_proc"]
    ident = f"soak-{cfg['worker_id']}-anakin"
    agent = VectorAgent(
        num_envs=n_lanes,
        model_path=os.path.join(cfg["scratch"], f"model_{ident}.msgpack"),
        config_path=cfg.get("config_path"),
        seed=cfg["worker_id"] * 1000,
        handshake_timeout_s=cfg["handshake_timeout_s"],
        server_type="zmq", identity=ident, host_mode="anakin",
        jax_env=cfg.get("jax_env", "CartPole-v1"),
        unroll_length=cfg.get("unroll_length", 32),
        **transport_addrs(cfg))
    start_barrier_wait(cfg, ident, publish_ready=True)
    deadline = time.time() + cfg["duration_s"]
    crashed = None
    windows = 0
    try:
        while time.time() < deadline:
            agent.rollout()
            windows += 1
    except Exception as e:
        crashed = repr(e)
    # Async-emit hosts: every dispatched window must reach the wire (and
    # the episode ledgers) before the rows below read them.
    agent.host.flush_emits()
    rows = lane_rows(
        agent.agent_ids, steps=[windows * agent.unroll_length] * n_lanes,
        episodes=[len(r) for r in agent.host.episode_returns],
        final_version=agent.model_version, crashed=crashed)
    # one engine per connection: its evidence rides the lane-0 row
    rows[0]["anakin"] = {
        "windows": windows,
        "wire": "columnar" if agent.columnar_wire else "records"}
    chaos_finish(agent, rows[0], cfg)
    agent.disable_agent()
    return rows


def serving_mux_loop(cfg: dict) -> list[dict]:
    """ONE MultiplexedRemoteClient drives ``agents_per_proc`` logical env
    lanes over the pipelined serving channel — every lane's request is in
    flight before any reply is awaited (up to ``serving.stream_window``
    deep). The streaming-depth evidence (``inflight_high_water``) rides
    the lane-0 row."""
    import numpy as np

    from relayrl_tpu.runtime.inference import MultiplexedRemoteClient

    ident = f"soak-{cfg['worker_id']}"
    lanes = cfg["agents_per_proc"]
    client = MultiplexedRemoteClient(
        config_path=cfg.get("config_path"), server_type="zmq",
        lanes=lanes, seed=cfg["worker_id"] * 1000, identity=ident,
        handshake_timeout_s=cfg["handshake_timeout_s"],
        serving_addr=cfg["serving_addr"], **transport_addrs(cfg))
    rng = np.random.default_rng(cfg["worker_id"])
    obs_dim, ep_len = cfg["obs_dim"], cfg["episode_len"]
    start_barrier_wait(cfg, ident, publish_ready=True)
    steps = [0] * lanes
    episodes = [0] * lanes
    rewards = [0.0] * lanes
    ep_t = 0
    deadline = time.time() + cfg["duration_s"]
    crashed = None
    try:
        while time.time() < deadline:
            obs_batch = rng.standard_normal(
                (lanes, obs_dim)).astype(np.float32)
            client.request_for_actions(list(obs_batch), rewards=rewards)
            rewards = [1.0] * lanes
            for i in range(lanes):
                steps[i] += 1
            ep_t += 1
            if ep_t >= ep_len:
                for i in range(lanes):
                    client.flag_last_action(i, reward=1.0, terminated=True)
                    episodes[i] += 1
                rewards = [0.0] * lanes
                ep_t = 0
    except Exception as e:
        crashed = repr(e)
    rows = lane_rows(
        client._sids or [f"{ident}#L{i:03d}" for i in range(lanes)],
        steps=steps, episodes=episodes,
        final_version=client.model_version, crashed=crashed)
    rows[0]["mux"] = {"lanes": lanes,
                      "inflight_high_water": client.inflight_high_water}
    chaos_finish(client, rows[0], cfg)
    client.disable_agent()
    return rows


def main():
    import faulthandler

    faulthandler.enable()
    cfg = json.loads(sys.argv[1])
    os.environ["JAX_PLATFORMS"] = "cpu"
    telemetry_setup(cfg)

    if cfg.get("serving") and cfg.get("serving_mux"):
        rows = serving_mux_loop(cfg)
    elif cfg.get("anakin"):
        rows = anakin_host_loop(cfg)
    elif cfg.get("vector"):
        rows = vector_host_loop(cfg)
    else:
        out: dict = {}
        barrier = threading.Barrier(cfg["agents_per_proc"])
        threads = [
            threading.Thread(target=agent_loop, args=(cfg, i, out, barrier),
                             daemon=True)
            for i in range(cfg["agents_per_proc"])
        ]
        for t in threads:
            t.start()
        # The go-file wait (start_barrier) can add up to go_timeout_s
        # before the window even opens — the join bound must cover it or
        # slow agents get abandoned and silently vanish from the result.
        barrier_s = cfg.get("go_timeout_s", 360.0) if cfg.get(
            "start_barrier") else 0.0
        for t in threads:
            t.join(timeout=cfg["duration_s"] + cfg["handshake_timeout_s"]
                   + barrier_s + 120)
        rows = list(out.values())
    write_result(cfg, rows)


if __name__ == "__main__":
    main()
