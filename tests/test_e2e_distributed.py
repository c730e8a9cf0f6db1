"""End-to-end distributed loop: TrainingServer + Agent over real sockets.

This is the test the reference never had (SURVEY.md §4 — its only
multi-process validation is criterion benches): the full loop of §3.3 —
handshake → env steps → trajectory over the wire → learner update → model
publish → actor hot-swap — on localhost ephemeral ports.
"""

import socket
import time

import numpy as np
import pytest

from relayrl_tpu.runtime.agent import Agent, run_gym_loop
from relayrl_tpu.runtime.server import TrainingServer


from _util import free_port  # noqa: E402


def _wait_for(pred, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.05)
    return False


def _zmq_addrs():
    return {
        "agent_listener_addr": f"tcp://127.0.0.1:{free_port()}",
        "trajectory_addr": f"tcp://127.0.0.1:{free_port()}",
        "model_pub_addr": f"tcp://127.0.0.1:{free_port()}",
    }


def _agent_addrs(server_addrs):
    return {
        "agent_listener_addr": server_addrs["agent_listener_addr"],
        "trajectory_addr": server_addrs["trajectory_addr"],
        "model_sub_addr": server_addrs["model_pub_addr"],
    }


class _RandomEnv:
    """Tiny deterministic env so e2e tests don't need gymnasium."""

    def __init__(self, obs_dim=4, horizon=6, seed=0):
        self._rng = np.random.default_rng(seed)
        self.obs_dim, self.horizon = obs_dim, horizon
        self._t = 0

    def reset(self, seed=None):
        self._t = 0
        return self._rng.standard_normal(self.obs_dim).astype(np.float32), {}

    def step(self, action):
        self._t += 1
        obs = self._rng.standard_normal(self.obs_dim).astype(np.float32)
        return obs, 1.0, self._t >= self.horizon, False, {}


@pytest.mark.parametrize("server_type", ["zmq", "grpc", "native"])
def test_full_loop_model_update_reaches_agent(tmp_cwd, server_type):
    if server_type == "zmq":
        server_addrs = _zmq_addrs()
        agent_addrs = _agent_addrs(server_addrs)
    else:
        if server_type == "native":
            from relayrl_tpu.transport.native_backend import native_available

            if not native_available():
                pytest.skip("native library not built")
        port = free_port()
        server_addrs = {"bind_addr": f"127.0.0.1:{port}"}
        agent_addrs = {"server_addr": f"127.0.0.1:{port}"}

    server = TrainingServer(
        "REINFORCE", obs_dim=4, act_dim=2, server_type=server_type,
        env_dir=str(tmp_cwd),
        hyperparams={"traj_per_epoch": 2, "hidden_sizes": [16],
                     "with_vf_baseline": False},
        **server_addrs,
    )
    if server_type == "grpc":
        server.transport.idle_timeout_s = 2.0
    try:
        agent = Agent(server_type=server_type, handshake_timeout_s=20,
                      seed=0, **agent_addrs)
        try:
            assert agent.model_version == 0
            env = _RandomEnv()
            run_gym_loop(agent, env, episodes=2, max_steps=10)

            assert _wait_for(lambda: server.stats["updates"] >= 1,
                             timeout=30), (
                f"learner never updated; stats={server.stats}")

            assert _wait_for(lambda: agent.model_version >= 1,
                             timeout=30), "hot-swap never happened"
            assert agent.transport.identity in server.agent_ids
        finally:
            agent.disable_agent()
    finally:
        server.disable_server()


def test_drain_then_shutdown_processes_inflight(tmp_cwd):
    """drain() must finish every already-sent trajectory (train + publish),
    and disable_server immediately after must not kill a mid-flight publish
    (the learner joins before the transport stops)."""
    server_addrs = _zmq_addrs()
    server = TrainingServer(
        "REINFORCE", obs_dim=4, act_dim=2, server_type="zmq",
        env_dir=str(tmp_cwd),
        hyperparams={"traj_per_epoch": 2, "hidden_sizes": [16],
                     "with_vf_baseline": False},
        **server_addrs,
    )
    agent = Agent(server_type="zmq", handshake_timeout_s=20, seed=0,
                  **_agent_addrs(server_addrs))
    try:
        env = _RandomEnv()
        run_gym_loop(agent, env, episodes=6, max_steps=10)
        # In-flight socket bytes are invisible to drain(): wait for arrival
        # first (6 episodes / traj_per_epoch 2 => exactly 3 updates)...
        _wait_for(lambda: server.stats["trajectories"] >= 6, timeout=60)
        # ...then drain guarantees processing/publishing has finished.
        assert server.drain(timeout=60)
        assert server.stats["updates"] == 3
        assert server.algorithm.version == 3
    finally:
        agent.disable_agent()
        server.disable_server()
    assert server.stats["dropped"] == 0


def test_multi_agent_zmq(tmp_cwd):
    """Several ZMQ agents against one server — the topology the reference's
    ZMQ plane cannot serve (SURVEY.md §2.3 socket-topology note)."""
    server_addrs = _zmq_addrs()
    server = TrainingServer(
        "REINFORCE", obs_dim=4, act_dim=2, server_type="zmq",
        env_dir=str(tmp_cwd), multiactor=True,
        hyperparams={"traj_per_epoch": 4, "hidden_sizes": [16],
                     "with_vf_baseline": False},
        **server_addrs,
    )
    agents = []
    try:
        for i in range(3):
            agents.append(Agent(server_type="zmq", handshake_timeout_s=20,
                                seed=i, **_agent_addrs(server_addrs)))
        env = _RandomEnv()
        for a in agents:
            run_gym_loop(a, env, episodes=2, max_steps=8)

        assert _wait_for(lambda: server.stats["updates"] >= 1, timeout=30)
        assert len(server.agent_ids) == 3

        for i, a in enumerate(agents):
            assert _wait_for(lambda a=a: a.model_version >= 1, timeout=30), \
                f"agent {i} never got the new model"
    finally:
        for a in agents:
            a.disable_agent()
        server.disable_server()


def test_server_checkpoint_resume(tmp_cwd):
    """Kill the server after training; a resumed server continues at the
    checkpointed version (beyond-reference capability, SURVEY.md §5.4)."""
    server_addrs = _zmq_addrs()
    hp = {"traj_per_epoch": 1, "hidden_sizes": [8], "with_vf_baseline": False,
          "checkpoint_every_epochs": 1}
    server = TrainingServer(
        "REINFORCE", obs_dim=4, act_dim=2, server_type="zmq",
        env_dir=str(tmp_cwd), hyperparams=hp, **server_addrs)
    try:
        agent = Agent(server_type="zmq", handshake_timeout_s=20, seed=0,
                      **_agent_addrs(server_addrs))
        try:
            run_gym_loop(agent, _RandomEnv(), episodes=3, max_steps=6)
            assert _wait_for(lambda: server.stats["updates"] >= 3,
                             timeout=30)
        finally:
            agent.disable_agent()
        trained_version = server.algorithm.version
        from relayrl_tpu.checkpoint import checkpoint_algorithm

        checkpoint_algorithm(server.algorithm, "checkpoints", wait=True)
    finally:
        server.disable_server()

    resumed = TrainingServer(
        "REINFORCE", obs_dim=4, act_dim=2, server_type="zmq",
        env_dir=str(tmp_cwd), hyperparams=hp, resume=True, **_zmq_addrs())
    try:
        assert resumed.algorithm.version == trained_version
    finally:
        resumed.disable_server()


def _transport_addr_pair(kind):
    """(server_addrs, agent_addrs) for any transport kind."""
    if kind == "zmq":
        srv = _zmq_addrs()
        return srv, _agent_addrs(srv)
    port = free_port()
    return ({"bind_addr": f"127.0.0.1:{port}"},
            {"server_addr": f"127.0.0.1:{port}"})


def _transports_available():
    from relayrl_tpu.transport.native_backend import native_available

    return ["zmq", "grpc"] + (["native"] if native_available() else [])


# Wall re-fit convention: zmq is the fast per-transport representative;
# the grpc/native twins exercise the same repoint path over a different
# socket and ride the slow tier.
@pytest.mark.parametrize("kind", [
    "zmq",
    pytest.param("grpc", marks=pytest.mark.slow),
    pytest.param("native", marks=pytest.mark.slow),
])
def test_agent_restart_and_repoint(tmp_cwd, kind):
    """Agent lifecycle parity (ref o3_agent.rs restart/enable/disable):
    restart against the same server keeps serving; restart with address
    overrides re-resolves to a DIFFERENT server — the reference's
    address-re-resolution semantic (training_server_wrapper.rs:69-90),
    agent side. Parametrized across all three transports: teardown +
    re-handshake is the transport-specific part."""
    if kind not in _transports_available():
        pytest.skip("native library not built (make -C native)")
    hp = {"traj_per_epoch": 1, "hidden_sizes": [8],
          "with_vf_baseline": False}
    addrs_a, ag_a = _transport_addr_pair(kind)
    srv_a = TrainingServer("REINFORCE", obs_dim=4, act_dim=2,
                           server_type=kind, env_dir=str(tmp_cwd),
                           hyperparams=hp, **addrs_a)
    try:
        agent = Agent(server_type=kind, handshake_timeout_s=20, **ag_a)
        try:
            v_a = agent.model_version
            act = agent.request_for_action(np.zeros(4, np.float32))
            assert act.get_act() is not None

            # Same-address restart: full teardown + re-handshake.
            agent.restart_agent()
            assert agent.active and agent.model_version >= v_a
            act = agent.request_for_action(np.zeros(4, np.float32))
            assert act.get_act() is not None

            # Re-point at a different server via addr overrides.
            addrs_b, ag_b = _transport_addr_pair(kind)
            srv_b = TrainingServer("REINFORCE", obs_dim=4, act_dim=2,
                                   server_type=kind,
                                   env_dir=str(tmp_cwd / "b"),
                                   hyperparams=hp, **addrs_b)
            try:
                agent.restart_agent(**ag_b)
                assert agent.active
                act = agent.request_for_action(np.zeros(4, np.float32))
                agent.flag_last_action(reward=1.0)
                assert _wait_for(lambda: srv_b.stats["trajectories"] >= 1)
                assert srv_a.stats["trajectories"] == 0, \
                    "trajectory went to the OLD server after re-point"
            finally:
                srv_b.disable_server()
        finally:
            agent.disable_agent()
    finally:
        srv_a.disable_server()


def test_server_restart(tmp_cwd):
    server_addrs = _zmq_addrs()
    server = TrainingServer(
        "REINFORCE", obs_dim=4, act_dim=2, server_type="zmq",
        env_dir=str(tmp_cwd),
        hyperparams={"traj_per_epoch": 1, "hidden_sizes": [8],
                     "with_vf_baseline": False},
        **server_addrs,
    )
    try:
        assert server.active
        server.restart_server()
        assert server.active
        # Still serves handshakes after restart.
        agent = Agent(server_type="zmq", handshake_timeout_s=20,
                      **_agent_addrs(server_addrs))
        try:
            assert agent.model_version >= 0
        finally:
            agent.disable_agent()
    finally:
        server.disable_server()


@pytest.mark.parametrize("algo,hp", [
    ("DQN", {"update_after": 8, "batch_size": 8, "updates_per_step": 0.25,
             "hidden_sizes": [16]}),
    ("IMPALA", {"traj_per_epoch": 2, "hidden_sizes": [16]}),
    ("C51", {"update_after": 8, "batch_size": 8, "updates_per_step": 0.25,
             "hidden_sizes": [16], "n_atoms": 11}),
    # Continuous actions over the wire: deterministic (DDPG/TD3) and
    # squashed-Gaussian (SAC) actors emit float vectors instead of scalar
    # ints (a different codec/actor path).
    ("SAC", {"update_after": 8, "batch_size": 8, "updates_per_step": 0.25,
             "hidden_sizes": [16], "discrete": False, "act_limit": 1.0}),
    ("DDPG", {"update_after": 8, "batch_size": 8, "updates_per_step": 0.25,
              "hidden_sizes": [16], "discrete": False, "act_limit": 1.0}),
    ("TD3", {"update_after": 8, "batch_size": 8, "updates_per_step": 0.25,
             "hidden_sizes": [16], "discrete": False, "act_limit": 1.0}),
])
def test_offpolicy_and_async_families_over_sockets(tmp_cwd, algo, hp):
    """Every non-on-policy algorithm in the registry runs the full
    distributed loop over real zmq sockets (REINFORCE/PPO are covered by
    the tests above): replay/warmup/target-net (DQN), distributional
    (C51), staleness-corrected async (IMPALA), and the three continuous
    actors (SAC/DDPG/TD3 — float action vectors on the wire)."""
    server_addrs = _zmq_addrs()
    agent_addrs = _agent_addrs(server_addrs)
    server = TrainingServer(
        algo, obs_dim=4, act_dim=2, server_type="zmq",
        env_dir=str(tmp_cwd), hyperparams=hp, **server_addrs)
    try:
        agent = Agent(server_type="zmq", handshake_timeout_s=20,
                      seed=0, **agent_addrs)
        try:
            env = _RandomEnv()
            deadline = time.monotonic() + 60
            while (server.stats["updates"] < 1
                   and time.monotonic() < deadline):
                run_gym_loop(agent, env, episodes=2, max_steps=10)
                time.sleep(0.02)
            assert server.stats["updates"] >= 1, (
                f"{algo} learner never updated; stats={server.stats}")
            assert server.stats["dropped"] == 0

            assert _wait_for(lambda: agent.model_version >= 1,
                             timeout=30), f"{algo} hot-swap never happened"
        finally:
            agent.disable_agent()
    finally:
        server.disable_server()


def test_uint8_pixel_frames_cross_the_wire_byte_sized(tmp_cwd):
    """The byte-sized pixel plane end-to-end: uint8 frames
    from the Atari pipeline stay uint8 through actor -> codec -> socket
    -> decode -> CNN learner, with per-step payload ~= obs_dim bytes
    (a float32 regression would quadruple it — exactly the silent
    upcast round 5 fixed in policy_actor.py)."""
    from relayrl_tpu.envs import make_atari

    server_addrs = _zmq_addrs()
    agent_addrs = _agent_addrs(server_addrs)
    frame, stack = 16, 2
    obs_dim = frame * frame * stack
    server = TrainingServer(
        "PPO", obs_dim=obs_dim, act_dim=3, server_type="zmq",
        env_dir=str(tmp_cwd),
        hyperparams={"model_kind": "cnn_discrete",
                     "obs_shape": [frame, frame, stack],
                     "conv_spec": [[4, 3, 2], [8, 3, 1]], "dense": 32,
                     "traj_per_epoch": 2, "minibatch_count": 1,
                     "train_iters": 1},
        **server_addrs)
    try:
        agent = Agent(server_type="zmq", handshake_timeout_s=30,
                      seed=0, **agent_addrs)
        from relayrl_tpu.utils.instrument import instrument_agent

        wire = instrument_agent(agent)
        try:
            env = make_atari("synthetic", frame_size=frame,
                             frame_stack=stack, frame_skip=2,
                             obs_dtype="uint8", raw_size=24, balls=1)
            deadline = time.monotonic() + 90
            while (server.stats["updates"] < 1
                   and time.monotonic() < deadline):
                run_gym_loop(agent, env, episodes=1, max_steps=40)
            assert server.stats["updates"] >= 1, server.stats
            assert server.stats["dropped"] == 0
            bytes_per_step = wire["bytes"] / wire["steps"]
            # obs_dim byte frame + a small fixed overhead; float32 would
            # be >= 4 * obs_dim
            assert obs_dim <= bytes_per_step < 2 * obs_dim, (
                f"pixel step costs {bytes_per_step:.0f} B on the wire "
                f"(frame is {obs_dim} B) — uint8 plane regressed")
        finally:
            agent.disable_agent()
    finally:
        server.disable_server()
