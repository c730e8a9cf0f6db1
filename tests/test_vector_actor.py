"""Vector actor host: batched-step parity, atomic multi-lane swap,
logical-agent multiplexing over one connection, and the vector-soak smoke.

The acceptance surface of the vectorized actor plane
(runtime/vector_actor.py):

* a batch-of-1 VectorActorHost is BIT-IDENTICAL to a plain PolicyActor for
  the same PRNG key (the vector host is a batching change, not a numerics
  change);
* a mid-episode model swap applies atomically across all lanes — no
  dispatch ever mixes versions;
* all three transports carry N logical agents over ONE connection: N
  distinct registry entries, per-agent trajectory attribution preserved;
* a tiny vector soak produces >= 1 trajectory per logical agent.
"""

import threading
import time

import jax
import numpy as np
import pytest

from _util import free_port


def _reinforce_bundle(scratch, obs_dim=6, act_dim=3):
    from relayrl_tpu.algorithms import build_algorithm

    algo = build_algorithm(
        "REINFORCE", env_dir=scratch, obs_dim=obs_dim, act_dim=act_dim,
        hidden_sizes=[16], traj_per_epoch=4, with_vf_baseline=True)
    return algo.bundle()


class TestBatchOf1Parity:
    def test_bit_identical_actions_and_aux(self, tmp_cwd):
        """Same key, same obs stream → the batched path and the single
        path emit bit-equal actions, logp, and v over a whole episode,
        including the reward-attachment side channel."""
        from relayrl_tpu.runtime.policy_actor import PolicyActor
        from relayrl_tpu.runtime.vector_actor import VectorActorHost

        bundle = _reinforce_bundle(str(tmp_cwd))
        sent_single, sent_vec = [], []
        single = PolicyActor(bundle, seed=11,
                             on_send=lambda p: sent_single.append(p))
        host = VectorActorHost(
            bundle, num_envs=1,
            on_send=lambda lane, p: sent_vec.append(p),
            rng_keys=np.asarray(jax.random.PRNGKey(11))[None])
        rng = np.random.default_rng(0)
        for i in range(8):
            obs = rng.standard_normal(6).astype(np.float32)
            reward = 0.0 if i == 0 else 0.5
            r1 = single.request_for_action(obs, reward=reward)
            [r2] = host.request_for_actions(obs[None], rewards=[reward])
            assert np.array_equal(np.asarray(r1.act), np.asarray(r2.act))
            for key in r1.data:
                assert np.array_equal(np.asarray(r1.data[key]),
                                      np.asarray(r2.data[key])), key
        single.flag_last_action(1.0, terminated=True)
        host.flag_last_action(0, 1.0, terminated=True)
        # The shipped episodes are byte-identical too (same records, same
        # wire codec) — lane 0 IS a single actor.
        assert sent_single == sent_vec

    def test_masked_parity(self, tmp_cwd):
        from relayrl_tpu.runtime.policy_actor import PolicyActor
        from relayrl_tpu.runtime.vector_actor import VectorActorHost

        bundle = _reinforce_bundle(str(tmp_cwd))
        single = PolicyActor(bundle, seed=3)
        host = VectorActorHost(
            bundle, num_envs=1,
            rng_keys=np.asarray(jax.random.PRNGKey(3))[None])
        rng = np.random.default_rng(1)
        mask = np.array([1.0, 0.0, 1.0], np.float32)
        for _ in range(4):
            obs = rng.standard_normal(6).astype(np.float32)
            r1 = single.request_for_action(obs, mask=mask)
            [r2] = host.request_for_actions(obs[None], masks=mask[None])
            assert np.array_equal(np.asarray(r1.act), np.asarray(r2.act))
            assert int(np.asarray(r2.act)) != 1  # mask respected

    def test_window_policy_parity(self, tmp_cwd):
        """Sequence policies: the batched padded-window path must be
        bit-identical to PolicyActor's window path for the same key,
        through window fill AND past the cap into rolling (this is the
        test that pins step_window's t = count-of-real-rows convention)."""
        from relayrl_tpu.models import build_policy
        from relayrl_tpu.runtime.policy_actor import PolicyActor
        from relayrl_tpu.runtime.vector_actor import VectorActorHost
        from relayrl_tpu.types.model_bundle import ModelBundle

        arch = {"kind": "transformer_discrete", "obs_dim": 5, "act_dim": 3,
                "d_model": 16, "n_layers": 1, "n_heads": 2,
                "max_seq_len": 8}
        policy = build_policy(arch)
        bundle = ModelBundle(version=1, arch=dict(arch),
                             params=policy.init_params(jax.random.PRNGKey(0)))
        # use_kv_cache=False pins the single actor to the window path the
        # vector host vmaps — the comparison is then exact, not
        # cache-vs-window numerics.
        single = PolicyActor(bundle, seed=9, use_kv_cache=False)
        host = VectorActorHost(
            bundle, num_envs=1,
            rng_keys=np.asarray(jax.random.PRNGKey(9))[None])
        rng = np.random.default_rng(4)
        for i in range(12):  # 8-slot window: fills at 8, rolls after
            obs = rng.standard_normal(5).astype(np.float32)
            r1 = single.request_for_action(obs)
            [r2] = host.request_for_actions(obs[None])
            assert np.array_equal(np.asarray(r1.act),
                                  np.asarray(r2.act)), f"step {i}"
            for key in r1.data:
                assert np.array_equal(np.asarray(r1.data[key]),
                                      np.asarray(r2.data[key])), (i, key)
        # episode boundary resets both window stores identically
        single.flag_last_action(1.0, terminated=True)
        host.flag_last_action(0, 1.0, terminated=True)
        obs = rng.standard_normal(5).astype(np.float32)
        r1 = single.request_for_action(obs)
        [r2] = host.request_for_actions(obs[None])
        assert np.array_equal(np.asarray(r1.act), np.asarray(r2.act))

    def test_lanes_decorrelate(self, tmp_cwd):
        """Distinct per-lane keys → lanes do not emit one shared action
        stream (the whole point of per-env key splitting)."""
        from relayrl_tpu.runtime.vector_actor import VectorActorHost

        bundle = _reinforce_bundle(str(tmp_cwd))
        host = VectorActorHost(bundle, num_envs=8, seed=0)
        rng = np.random.default_rng(2)
        obs = np.repeat(rng.standard_normal(6).astype(np.float32)[None],
                        8, axis=0)
        acts = []
        for _ in range(16):
            acts.append([int(np.asarray(r.act))
                         for r in host.request_for_actions(obs)])
        acts = np.asarray(acts)  # [steps, lanes], identical obs every lane
        assert any(len(set(acts[:, lane].tolist()))
                   != len(set(acts[:, 0].tolist()))
                   or not np.array_equal(acts[:, lane], acts[:, 0])
                   for lane in range(1, 8)), "all lanes sampled identically"


class TestAtomicSwap:
    def _versioned_bundle(self, bundle, version):
        """Params whose value head outputs exactly ``version`` for any
        obs (zero weights, bias=version): aux['v'] reveals which params
        produced each action."""
        from relayrl_tpu.types.model_bundle import ModelBundle

        params = jax.tree_util.tree_map(np.asarray, bundle.params)
        import copy

        params = copy.deepcopy(params)
        params["params"]["vf_head"]["kernel"] = np.zeros_like(
            params["params"]["vf_head"]["kernel"])
        params["params"]["vf_head"]["bias"] = np.full_like(
            params["params"]["vf_head"]["bias"], float(version))
        vt = params["params"]["vf_trunk"]
        for layer in vt.values():
            layer["bias"] = np.zeros_like(layer["bias"])
        return ModelBundle(arch=dict(bundle.arch), params=params,
                           version=version)

    def test_swap_applies_atomically_across_lanes(self, tmp_cwd):
        """A swapper thread races the stepping thread: every dispatch's
        aux['v'] must be constant across lanes (one params read per
        batch), and the final dispatches must run on the newest version."""
        from relayrl_tpu.runtime.vector_actor import VectorActorHost

        base = _reinforce_bundle(str(tmp_cwd))
        n_lanes = 8
        host = VectorActorHost(self._versioned_bundle(base, 1),
                               num_envs=n_lanes, seed=0, validate=False)
        rng = np.random.default_rng(0)
        stop = threading.Event()
        next_version = [2]

        def swapper():
            while not stop.is_set():
                host.maybe_swap(
                    self._versioned_bundle(base, next_version[0]))
                next_version[0] += 1
                time.sleep(0.002)

        t = threading.Thread(target=swapper, daemon=True)
        t.start()
        try:
            mixed = []
            for _ in range(100):
                obs = rng.standard_normal((n_lanes, 6)).astype(np.float32)
                records = host.request_for_actions(obs)
                versions = {float(np.asarray(r.data["v"])) for r in records}
                if len(versions) != 1:
                    mixed.append(versions)
        finally:
            stop.set()
            t.join(timeout=5)
        assert not mixed, f"dispatch mixed model versions: {mixed[:3]}"
        assert host.version >= 2  # swaps actually landed mid-run

    def test_stale_and_mismatched_swaps_rejected(self, tmp_cwd):
        from relayrl_tpu.runtime.vector_actor import VectorActorHost

        base = _reinforce_bundle(str(tmp_cwd))
        host = VectorActorHost(self._versioned_bundle(base, 5),
                               num_envs=2, seed=0, validate=False)
        assert not host.maybe_swap(self._versioned_bundle(base, 5))
        assert not host.maybe_swap(self._versioned_bundle(base, 4))
        assert host.maybe_swap(self._versioned_bundle(base, 6))
        assert host.version == 6


def _multiplex_roundtrip(server, make_agent, n_lanes=4):
    """N logical agents over ONE agent transport: N registry entries,
    per-agent trajectory attribution preserved."""
    received, registered = [], []
    server.get_model = lambda: (1, b"MODEL")
    server.on_trajectory = lambda aid, p: received.append((aid, p))
    server.on_register = registered.append
    server.start()
    try:
        agent = make_agent()
        try:
            assert agent.fetch_model(timeout_s=15) == (1, b"MODEL")
            lane_ids = [f"{agent.identity}.lane{k}" for k in range(n_lanes)]
            for lane_id in lane_ids:
                assert agent.register(lane_id, timeout_s=10), lane_id
            for k, lane_id in enumerate(lane_ids):
                agent.send_trajectory(b"traj-%d" % k, agent_id=lane_id)
            deadline = time.monotonic() + 10
            while len(received) < n_lanes and time.monotonic() < deadline:
                time.sleep(0.01)
            assert sorted(received) == [
                (lane_ids[k], b"traj-%d" % k) for k in range(n_lanes)]
            deadline = time.monotonic() + 10
            while (len(set(registered)) < n_lanes
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            assert set(lane_ids) <= set(registered)
        finally:
            agent.close()
    finally:
        server.stop()


class TestMultiplexedRegistration:
    def test_zmq(self, tmp_cwd):
        from relayrl_tpu.config import ConfigLoader
        from relayrl_tpu.transport import (
            make_agent_transport,
            make_server_transport,
        )

        cfg = ConfigLoader(create_if_missing=False)
        ports = [free_port() for _ in range(3)]
        server = make_server_transport(
            "zmq", cfg,
            agent_listener_addr=f"tcp://127.0.0.1:{ports[0]}",
            trajectory_addr=f"tcp://127.0.0.1:{ports[1]}",
            model_pub_addr=f"tcp://127.0.0.1:{ports[2]}")
        _multiplex_roundtrip(server, lambda: make_agent_transport(
            "zmq", cfg, probe=False,
            agent_listener_addr=f"tcp://127.0.0.1:{ports[0]}",
            trajectory_addr=f"tcp://127.0.0.1:{ports[1]}",
            model_sub_addr=f"tcp://127.0.0.1:{ports[2]}"))

    def test_grpc(self, tmp_cwd):
        from relayrl_tpu.config import ConfigLoader
        from relayrl_tpu.transport import (
            make_agent_transport,
            make_server_transport,
        )

        cfg = ConfigLoader(create_if_missing=False)
        port = free_port()
        # Pin the pure-grpcio server: the native gRPC plane is covered by
        # its own fuzz suite, and this test targets the Python servicer's
        # logical-registration path.
        server = make_server_transport("grpc", cfg,
                                       bind_addr=f"127.0.0.1:{port}",
                                       native_grpc=False)
        _multiplex_roundtrip(server, lambda: make_agent_transport(
            "grpc", cfg, probe=False, server_addr=f"127.0.0.1:{port}"))

    def test_native(self, tmp_cwd):
        from relayrl_tpu.config import ConfigLoader
        from relayrl_tpu.transport import (
            make_agent_transport,
            make_server_transport,
        )
        from relayrl_tpu.transport.native_backend import native_available

        if not native_available():
            pytest.skip("native library not built (make -C native)")
        cfg = ConfigLoader(create_if_missing=False)
        port = free_port()
        server = make_server_transport("native", cfg,
                                       bind_addr=f"127.0.0.1:{port}")
        _multiplex_roundtrip(server, lambda: make_agent_transport(
            "native", cfg, probe=False, server_addr=f"127.0.0.1:{port}"))

    def test_native_unregisters_every_lane_on_drop(self, tmp_cwd):
        """A dead vector host must reap ALL of its logical agents from
        the registry, not just the last-registered one."""
        from relayrl_tpu.config import ConfigLoader
        from relayrl_tpu.transport import (
            make_agent_transport,
            make_server_transport,
        )
        from relayrl_tpu.transport.native_backend import native_available

        if not native_available():
            pytest.skip("native library not built (make -C native)")
        cfg = ConfigLoader(create_if_missing=False)
        port = free_port()
        server = make_server_transport("native", cfg,
                                       bind_addr=f"127.0.0.1:{port}")
        server.get_model = lambda: (1, b"M")
        unregistered = []
        server.on_unregister = unregistered.append
        server.start()
        try:
            agent = make_agent_transport("native", cfg, probe=False,
                                         server_addr=f"127.0.0.1:{port}")
            agent.fetch_model(timeout_s=15)
            for k in range(3):
                assert agent.register(f"lane-{k}", timeout_s=10)
            agent.close()
            deadline = time.monotonic() + 10
            while len(unregistered) < 3 and time.monotonic() < deadline:
                time.sleep(0.05)
            assert sorted(unregistered) == ["lane-0", "lane-1", "lane-2"]
        finally:
            server.stop()


class TestSyncVectorEnv:
    def test_autoreset_preserves_final_observation(self):
        from relayrl_tpu.envs import CartPoleEnv, SyncVectorEnv

        venv = SyncVectorEnv([CartPoleEnv for _ in range(3)])
        obs, _ = venv.reset(seed=0)
        assert obs.shape == (3, 4)
        done_seen = False
        for _ in range(200):
            obs, rews, terms, truncs, infos = venv.step([1, 1, 1])
            assert obs.shape == (3, 4)
            for lane in range(3):
                if terms[lane] or truncs[lane]:
                    done_seen = True
                    final = infos[lane]["final_observation"]
                    # autoreset: the row is the NEXT episode's first obs,
                    # the pre-reset obs rides the info dict
                    assert final.shape == (4,)
                    assert not np.array_equal(obs[lane], final)
            if done_seen:
                break
        assert done_seen, "always-right CartPole never terminated?"

    def test_autoreset_preserves_reset_info_dict(self):
        """The autoreset's reset() info dict must survive under
        "reset_info" (it used to be discarded with ``obs, _ =
        env.reset()``), alongside the final_observation."""
        from relayrl_tpu.envs import SyncVectorEnv

        class InfoEnv:
            """Counts resets and echoes the seed it was reset with."""

            def __init__(self):
                from relayrl_tpu.envs import Box, Discrete

                self.observation_space = Box(-1, 1, shape=(2,))
                self.action_space = Discrete(2)
                self.resets = 0

            def reset(self, seed=None):
                self.resets += 1
                return (np.zeros(2, np.float32),
                        {"reset_seed": seed, "nth_reset": self.resets})

            def step(self, action):
                return np.ones(2, np.float32), 1.0, True, False, {}

        venv = SyncVectorEnv([InfoEnv for _ in range(2)])
        venv.reset(seed=100)
        _, _, terms, _, infos = venv.step([0, 0])
        assert terms.all()
        for lane in range(2):
            info = infos[lane]
            np.testing.assert_array_equal(info["final_observation"],
                                          np.ones(2, np.float32))
            assert info["reset_info"]["nth_reset"] == 2

    def test_autoreset_derived_seed_reproducible(self):
        """Seeded stacks stay reproducible across autoresets: episode e
        of lane k resets with ``seed + k + num_envs*e`` (episode 0 is
        exactly the documented ``seed + lane`` contract), so two
        identically-seeded stacks replay identical state streams forever,
        and distinct (lane, episode) pairs never share a seed."""
        from relayrl_tpu.envs import CartPoleEnv, SyncVectorEnv

        def run(n_steps=120):
            venv = SyncVectorEnv([CartPoleEnv for _ in range(3)])
            obs, _ = venv.reset(seed=42)
            rows, seeds = [obs], []
            for _ in range(n_steps):
                obs, _, terms, truncs, infos = venv.step([1, 1, 1])
                rows.append(obs)
                for lane in range(3):
                    if terms[lane] or truncs[lane]:
                        seeds.append(
                            infos[lane]["reset_info"].get("seed_used"))
            return np.concatenate(rows), venv._episode

        a, eps_a = run()
        b, eps_b = run()
        np.testing.assert_array_equal(a, b)
        assert eps_a == eps_b and sum(eps_a) >= 3  # boundaries crossed
        # unseeded stacks keep entropy-seeded autoresets (no determinism)
        from relayrl_tpu.envs import CartPoleEnv as CP, SyncVectorEnv as SV

        venv = SV([CP for _ in range(1)])
        venv.reset()  # no seed
        assert venv._autoreset_seed(0) is None

    def test_autoreset_seed_derivation_is_collision_free(self):
        from relayrl_tpu.envs import CartPoleEnv, SyncVectorEnv

        venv = SyncVectorEnv([CartPoleEnv for _ in range(4)])
        venv.reset(seed=7)
        seen = set()
        for lane in range(4):
            for ep in range(5):
                venv._episode[lane] = ep
                seen.add(venv._autoreset_seed(lane))
        assert len(seen) == 20  # distinct across every (lane, episode)

    def test_vector_loop_with_host(self, tmp_cwd):
        """run_vector_gym_loop end-to-end over a raw host: every lane
        ships episodes through the wire codec."""
        from relayrl_tpu.envs import CartPoleEnv, SyncVectorEnv
        from relayrl_tpu.runtime.vector_actor import (
            VectorActorHost,
            run_vector_gym_loop,
        )
        from relayrl_tpu.types.trajectory import deserialize_actions

        bundle = _reinforce_bundle(str(tmp_cwd), obs_dim=4, act_dim=2)
        sent: list[tuple[int, bytes]] = []
        host = VectorActorHost(
            bundle, num_envs=3,
            on_send=lambda lane, p: sent.append((lane, p)))
        venv = SyncVectorEnv([CartPoleEnv for _ in range(3)])
        returns = run_vector_gym_loop(host, venv, steps=120, seed=0)
        lanes_shipped = {lane for lane, _ in sent}
        assert lanes_shipped == {0, 1, 2}
        assert all(returns[lane] for lane in range(3))
        # each lane's shipped episode decodes, ending in a terminal marker
        lane0 = next(p for lane, p in sent if lane == 0)
        actions = deserialize_actions(lane0)
        assert actions[-1].done


class TestVectorSoakSmoke:
    # ISSUE 17 wall re-fit: soak smokes live in the slow tier alongside
    # tests/test_soak.py and tests/test_drills.py.
    @pytest.mark.slow
    def test_quick_vector_soak_one_traj_per_logical_agent(
            self, monkeypatch, tmp_path):
        """Tiny vector soak (tests/drills/soak.py): 4 logical agents in
        one process must each land >= 1 attributed trajectory (the CI
        gate for the vector actor plane)."""
        monkeypatch.chdir(tmp_path)
        from drills import soak

        result = soak.run_soak(
            n_actors=4, agents_per_proc=4, duration_s=3.0,
            traj_per_epoch=8, vector=True)
        assert result["agents_completed"] == 4
        assert result["agents_crashed"] == 0
        assert result["server_stats"]["dropped"] == 0
        assert result["min_episodes_per_agent"] >= 1
        assert result["distinct_traj_agents"] == 4  # per-lane attribution
