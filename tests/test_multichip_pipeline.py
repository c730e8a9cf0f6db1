"""Pipelined multichip learner (shard_map migration tentpole).

The multi-host broadcast loop now rides the same async-dispatch pieces
as the single-host learner (runtime/pipeline.py): sharded updates enter
the in-flight window unfenced, batches prefetch to the mesh via
``stage_batch``, and publishes go through the collective
``snapshot_for_publish`` gather + the latest-wins publisher thread. The
contract under test mirrors ISSUE 2's acceptance bar, lifted to a mesh:

* pipelined-vs-sync SHARDED params stay bit-identical (REINFORCE + PPO),
* ``drain()`` covers dispatched-but-unfenced sharded updates,
* the periodic checkpoint quiesces the window first, so a restore sees
  exactly the params the version counter claims.

All cells run single-process on a virtual-device CPU mesh: the broadcast
loop is driven by patching ``distributed_info`` (the broadcast helpers
no-op without a real ``jax.distributed`` init — same lockstep code path,
no subprocess fleet). The real multi-process protocol is
test_multihost_server.py's (slow) job.
"""

import json
import time

import numpy as np
import pytest

OBS_DIM, ACT_DIM = 4, 2


def _episode(n, seed=0, with_v=False):
    from relayrl_tpu.types.action import ActionRecord

    rng = np.random.default_rng(seed)
    acts = []
    for i in range(n):
        data = {"logp_a": np.float32(-0.69)}
        if with_v:
            data["v"] = np.float32(rng.standard_normal())
        acts.append(ActionRecord(
            obs=rng.standard_normal(OBS_DIM).astype(np.float32),
            act=np.int64(rng.integers(ACT_DIM)),
            rew=float(rng.random()),
            data=data,
            done=(i == n - 1),
        ))
    return acts


def _stream(episodes=8, seed0=300, with_v=False):
    lens = [6, 30, 12, 9, 5, 40, 7, 21]
    return [_episode(lens[i % len(lens)], seed=seed0 + i, with_v=with_v)
            for i in range(episodes)]


class StubTransport:
    def __init__(self, publish_delay=0.0):
        self.published = []
        self.publish_delay = publish_delay
        self.on_trajectory = None
        self.on_trajectory_decoded = None
        self.get_model = None
        self.on_register = None
        self.on_unregister = None

    def start(self):
        pass

    def stop(self):
        pass

    def publish_model(self, version, raw):
        if self.publish_delay:
            time.sleep(self.publish_delay)
        self.published.append((version, len(raw)))


def _dp2_mesh():
    import jax

    from relayrl_tpu.parallel import make_mesh

    return make_mesh({"dp": 2}, jax.devices()[:2])


@pytest.fixture
def mh_server_factory(tmp_cwd, monkeypatch):
    """TrainingServer driven through ``_learner_loop_multihost`` on a
    2-device dp mesh, single-process: ``distributed_info`` is patched to
    multi_host BEFORE enable_server picks the learner loop (the
    broadcast helpers pass batches through untouched without a real
    distributed init, so the loop runs its full lockstep body)."""
    import relayrl_tpu.runtime.server as srv_mod

    def make(algorithm="REINFORCE", publish_delay=0.0, hp=None,
             learner=None):
        stub = StubTransport(publish_delay=publish_delay)
        monkeypatch.setattr(srv_mod, "make_server_transport",
                            lambda *a, **k: stub)
        cfg = {"learner": {"checkpoint_dir": "", **(learner or {})}}
        path = tmp_cwd / "mh_config.json"
        path.write_text(json.dumps(cfg))
        hyper = {"traj_per_epoch": 2, "hidden_sizes": [16],
                 "with_vf_baseline": False, "seed_salt": 0, **(hp or {})}
        server = srv_mod.TrainingServer(
            algorithm, obs_dim=OBS_DIM, act_dim=ACT_DIM,
            env_dir=str(tmp_cwd), config_path=str(path),
            hyperparams=hyper, start=False)
        server.distributed_info = {"multi_host": True, "process_id": 0,
                                   "num_processes": 1}
        server.algorithm.enable_multihost(_dp2_mesh())
        return server, stub

    return make


def _run_stream(server, stream, timeout=120):
    server.enable_server()
    try:
        for ep in stream:
            server._decoded.put(ep)
        assert server.drain(timeout=timeout), "multihost drain timed out"
    finally:
        server.disable_server()


class TestShardedEquivalence:
    """Pipelining may not change learning semantics on a mesh: the
    async-window + prefetch + collective-gather-publish loop must
    produce params bit-identical to the synchronous escape hatch
    (max_inflight_updates=0, inline collective bundle())."""

    # Wall re-fit convention: REINFORCE is the fast per-algorithm
    # representative; the PPO twin rides the slow tier.
    @pytest.mark.parametrize("algo_name,hp,with_v", [
        ("REINFORCE", {"with_vf_baseline": True, "train_vf_iters": 2},
         True),
        pytest.param("PPO", {"train_iters": 2, "minibatch_count": 2},
                     True, marks=pytest.mark.slow),
    ])
    def test_pipelined_matches_sync_sharded_params(
            self, mh_server_factory, algo_name, hp, with_v):
        import jax

        stream = _stream(8, with_v=with_v)

        ref, _ = mh_server_factory(
            algo_name, hp={**hp, "max_inflight_updates": 0})
        ref._async_publish = False
        assert ref.algorithm.max_inflight_updates == 0
        _run_stream(ref, stream)
        ref_params = jax.device_get(ref.algorithm.state.params)
        assert ref.algorithm.version > 0, "reference never trained"

        srv, stub = mh_server_factory(algo_name, hp=hp)
        assert srv.algorithm.max_inflight_updates == 2
        _run_stream(srv, stream)
        pip_params = jax.device_get(srv.algorithm.state.params)

        flat_ref = jax.tree_util.tree_leaves(ref_params)
        flat_pip = jax.tree_util.tree_leaves(pip_params)
        assert len(flat_ref) == len(flat_pip)
        for r, p in zip(flat_ref, flat_pip):
            np.testing.assert_array_equal(np.asarray(r), np.asarray(p))
        assert srv.algorithm.version == ref.algorithm.version
        assert stub.published, "pipelined multihost server never published"
        assert stub.published[-1][0] == srv.algorithm.version

    def test_sharded_update_actually_dispatches_async(
            self, mh_server_factory):
        """The window is live on the multihost loop: updates pass
        through it (dispatch_count advances) and drain() leaves nothing
        unfenced."""
        srv, _ = mh_server_factory("REINFORCE")
        _run_stream(srv, _stream(8))
        win = srv.algorithm.inflight
        assert win.max_in_flight == 2
        assert win.dispatch_count == srv.stats["updates"] == 4
        assert win.pending == 0
        assert win.fenced_count == win.dispatch_count


class TestDrainCoversInflight:
    def test_drain_waits_for_fence_and_final_publish(
            self, mh_server_factory):
        srv, stub = mh_server_factory("REINFORCE", publish_delay=0.25)
        srv.enable_server()
        try:
            for ep in _stream(6):
                srv._decoded.put(ep)
            assert srv.drain(timeout=120)
            # Once drain returns, NOTHING is pending anywhere on the
            # multihost loop: window empty, broadcast step done, queued
            # batches gone, logs flushed, final publish landed.
            assert srv._learner_pending() == 0
            assert not srv._mh_ready and not srv._mh_busy
            assert srv.algorithm.inflight.pending == 0
            assert srv.stats["updates"] == 3
            assert stub.published
            assert stub.published[-1][0] == srv.algorithm.version
        finally:
            srv.disable_server()

    def test_disable_server_quiesces_inflight_sharded_updates(
            self, mh_server_factory):
        """STOP fences the window before the learner thread exits — no
        dispatched-but-unfenced sharded update outlives the loop."""
        srv, _ = mh_server_factory("REINFORCE")
        srv.enable_server()
        for ep in _stream(6):
            srv._decoded.put(ep)
        assert srv.drain(timeout=120)
        srv.disable_server()
        win = srv.algorithm.inflight
        assert win.pending == 0
        assert win.fenced_count == win.dispatch_count == 3


class TestCheckpointQuiesce:
    def test_periodic_checkpoint_sees_quiesced_params(
            self, mh_server_factory, tmp_cwd):
        """checkpoint_every_epochs=1 → the due-check fires on every
        update while later updates are already dispatching behind it.
        The save quiesces the window first, so restoring the final
        checkpoint yields params bit-identical to the final live state
        (a torn save would restore a params/version mismatch)."""
        import jax

        from relayrl_tpu.algorithms import build_algorithm
        from relayrl_tpu.checkpoint import restore_algorithm

        srv, _ = mh_server_factory(
            "REINFORCE",
            learner={"checkpoint_dir": "ckpts",
                     "checkpoint_every_epochs": 1})
        _run_stream(srv, _stream(6))
        assert srv.algorithm.version == 3
        srv.algorithm._ckpt_mgr.wait()
        live = jax.device_get(srv.algorithm.state.params)

        fresh = build_algorithm(
            "REINFORCE", obs_dim=OBS_DIM, act_dim=ACT_DIM,
            env_dir=str(tmp_cwd), traj_per_epoch=2, hidden_sizes=[16],
            with_vf_baseline=False, seed_salt=0)
        fresh.enable_multihost(_dp2_mesh())
        restore_algorithm(fresh, str(tmp_cwd / "ckpts"))
        assert fresh.version == 3
        restored = jax.device_get(fresh.state.params)
        flat_live = jax.tree_util.tree_leaves(live)
        flat_restored = jax.tree_util.tree_leaves(restored)
        assert len(flat_live) == len(flat_restored)
        for a, b in zip(flat_live, flat_restored):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_one_process_with_several_devices_trains_over_the_mesh(
        tmp_cwd, monkeypatch):
    """A single-process server that sees several accelerator devices
    places state and batches on learner.mesh and runs the ORDINARY learner
    loop — no second process needed to stop three chips idling. (Virtual
    CPU devices never count as accelerators, so the test says which
    devices to use.)"""
    import jax

    import relayrl_tpu.parallel as par_mod
    import relayrl_tpu.runtime.server as srv_mod

    monkeypatch.setattr(srv_mod, "make_server_transport",
                        lambda *a, **k: StubTransport())
    monkeypatch.setattr(par_mod, "accelerator_devices",
                        lambda: jax.devices()[:4])
    path = tmp_cwd / "cfg.json"
    path.write_text(json.dumps({"learner": {"checkpoint_dir": ""}}))
    server = srv_mod.TrainingServer(
        "REINFORCE", obs_dim=OBS_DIM, act_dim=ACT_DIM, env_dir=str(tmp_cwd),
        config_path=str(path), start=False,
        hyperparams={"traj_per_epoch": 4, "hidden_sizes": [16],
                     "with_vf_baseline": False, "seed_salt": 0})
    assert not server.distributed_info["multi_host"]
    assert dict(server.mesh.shape)["dp"] == 4
    leaves = jax.tree_util.tree_leaves(server.algorithm.state.params)
    assert {len(x.sharding.device_set) for x in leaves} == {4}
    for ep in _stream(4):
        server._process_one(ep)
    server._pipeline_quiesce()
    assert server.stats["updates"] == 1
    assert server.stats["learner_errors"] == 0
    assert server.algorithm.version == 1
