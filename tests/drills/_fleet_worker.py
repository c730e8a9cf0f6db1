"""Worker process for the fleet telemetry drill
(``tests/test_fleet.py::TestLiveFleetDrill``).

One :class:`relayrl_tpu.runtime.VectorAgent` hosting
``agents_per_proc`` logical lanes drives a synthetic env loop against
whatever endpoint the config points at (the root directly, or a relay's
fan-out triple). With ``telemetry.fleet_interval_s`` > 0 in the shared
config the agent's FleetEmitter ships this process's registry snapshot
upstream every interval — plus one FINAL frame at ``disable_agent`` —
so the root's fleet table holds this life's closing totals.

The result file carries the registry snapshot taken at the moment the
env loop stopped (before teardown): every ``relayrl_actor_*`` counter
in it is frozen by then, so the root's merged totals must equal the sum
of these per-process snapshots BIT-exactly (the drill's acceptance
bar).

Usage: _fleet_worker.py <json-config>
"""

from __future__ import annotations

import json
import os
import sys
import time


def main() -> None:
    cfg = json.loads(sys.argv[1])
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np

    from relayrl_tpu.runtime.agent import VectorAgent

    n_lanes = int(cfg["agents_per_proc"])
    ident = cfg["identity"]
    agent = VectorAgent(
        num_envs=n_lanes,
        model_path=os.path.join(cfg["scratch"], f"model_{ident}.msgpack"),
        config_path=cfg["config_path"],
        seed=int(cfg.get("seed", 0)),
        handshake_timeout_s=float(cfg.get("handshake_timeout_s", 60.0)),
        server_type=cfg.get("server_type", "zmq"),
        identity=ident,
        host_mode="vector",
        agent_listener_addr=cfg["agent_listener_addr"],
        trajectory_addr=cfg["trajectory_addr"],
        model_sub_addr=cfg["model_sub_addr"],
    )
    assert agent._fleet_emitter is not None, (
        "fleet emitter did not start — telemetry.fleet_interval_s off "
        "or registry disabled in the worker config")
    with open(os.path.join(cfg["scratch"], f"ready_{ident}"), "w") as f:
        f.write(ident)

    rng = np.random.default_rng(int(cfg.get("seed", 0)))
    obs_dim = int(cfg.get("obs_dim", 4))
    ep_len = int(cfg.get("episode_len", 5))
    stop_file = cfg["stop_file"]
    deadline = time.time() + float(cfg.get("duration_s", 30.0))
    steps = episodes = 0
    while not os.path.exists(stop_file) and time.time() < deadline:
        obs = rng.standard_normal((n_lanes, obs_dim)).astype(np.float32)
        rewards = None
        for _ in range(ep_len):
            agent.request_for_actions(obs, rewards=rewards)
            obs = rng.standard_normal((n_lanes, obs_dim)).astype(np.float32)
            rewards = [1.0] * n_lanes
            steps += 1
            if os.path.exists(stop_file):
                break
        for lane in range(n_lanes):
            agent.flag_last_action(lane, 1.0, terminated=True)
        episodes += 1

    # Env loop done: every relayrl_actor_* counter is frozen NOW. This
    # snapshot is the exactness reference; the final frame shipped by
    # disable_agent below carries the same frozen actor counters.
    from relayrl_tpu import telemetry

    snapshot = telemetry.get_registry().snapshot()
    # Ship the closing frame explicitly and give the PUSH pipe a beat:
    # disable_agent's own final emit races the linger-0 socket close
    # (the chaos_finish flush-linger lesson, tests/drills/_soak_worker.py),
    # and a dropped final frame would fail the exactness check for the
    # wrong reason.
    agent._fleet_emitter.emit_now()
    time.sleep(1.0)
    agent.disable_agent()
    with open(cfg["result_path"], "w") as f:
        json.dump({
            "identity": ident,
            "lanes": n_lanes,
            "steps_per_lane": steps,
            "episodes_per_lane": episodes,
            "snapshot": snapshot,
        }, f)


if __name__ == "__main__":
    main()
