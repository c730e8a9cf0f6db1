"""V-trace op + IMPALA learner tests.

Key invariant: with behavior == target and rho_bar, c_bar >= 1, the V-trace
recursion telescopes to the on-policy n-step return — that anchors the op
against ops.gae.rewards_to_go. Off-policy behavior is checked via ratio
clipping and staleness tolerance (training on trajectories produced by an
older model version).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from relayrl_tpu.algorithms import IMPALA, build_algorithm, registered_algorithms
from relayrl_tpu.ops import rewards_to_go, vtrace
from relayrl_tpu.types.action import ActionRecord

B, T = 3, 12


def _batch(seed=0, lengths=(12, 7, 10)):
    rng = np.random.default_rng(seed)
    valid = np.zeros((B, T), np.float32)
    for i, n in enumerate(lengths):
        valid[i, :n] = 1.0
    return {
        "behavior_logp": rng.uniform(-2, -0.5, (B, T)).astype(np.float32) * valid,
        "rew": rng.standard_normal((B, T)).astype(np.float32) * valid,
        "val": rng.standard_normal((B, T)).astype(np.float32) * valid,
        "valid": valid,
        "last_val": rng.standard_normal(B).astype(np.float32),
    }


class TestVTrace:
    def test_on_policy_telescopes_to_nstep_return(self):
        b = _batch()
        out = vtrace(
            jnp.asarray(b["behavior_logp"]), jnp.asarray(b["behavior_logp"]),
            jnp.asarray(b["rew"]), jnp.asarray(b["val"]),
            jnp.asarray(b["valid"]), gamma=0.9,
            last_val=jnp.asarray(b["last_val"]))
        # Expected: discounted rewards-to-go + gamma^(L-t) * last_val.
        rtg = rewards_to_go(jnp.asarray(b["rew"]), jnp.asarray(b["valid"]), 0.9)
        lengths = b["valid"].sum(-1).astype(int)
        boot = np.zeros((B, T), np.float32)
        for i, L in enumerate(lengths):
            for t in range(L):
                boot[i, t] = 0.9 ** (L - t) * b["last_val"][i]
        np.testing.assert_allclose(
            np.asarray(out.vs), np.asarray(rtg) + boot, rtol=1e-4, atol=1e-5)

    def test_rho_clipped(self):
        b = _batch(1)
        target = b["behavior_logp"] + 3.0  # ratio e^3 >> rho_bar
        out = vtrace(
            jnp.asarray(b["behavior_logp"]), jnp.asarray(target),
            jnp.asarray(b["rew"]), jnp.asarray(b["val"]),
            jnp.asarray(b["valid"]), gamma=0.9, rho_bar=1.0, c_bar=1.0)
        assert float(jnp.max(out.rho)) <= 1.0 + 1e-6

    def test_zero_ratio_kills_corrections(self):
        """target far below behavior => rho ~ 0 => vs collapses to val."""
        b = _batch(2)
        target = b["behavior_logp"] - 20.0
        out = vtrace(
            jnp.asarray(b["behavior_logp"]), jnp.asarray(target),
            jnp.asarray(b["rew"]), jnp.asarray(b["val"]),
            jnp.asarray(b["valid"]), gamma=0.9)
        np.testing.assert_allclose(
            np.asarray(out.vs), b["val"] * b["valid"], atol=1e-4)
        np.testing.assert_allclose(np.asarray(out.pg_adv), 0.0, atol=1e-4)

    def test_padding_untouched(self):
        b = _batch(3)
        out = vtrace(
            jnp.asarray(b["behavior_logp"]), jnp.asarray(b["behavior_logp"]),
            jnp.asarray(b["rew"]), jnp.asarray(b["val"]),
            jnp.asarray(b["valid"]), gamma=0.95)
        pad = b["valid"] == 0
        assert np.all(np.asarray(out.vs)[pad] == 0)
        assert np.all(np.asarray(out.pg_adv)[pad] == 0)


def _episode(policy_bias, n=10, obs_dim=4, act_dim=2, seed=0):
    """Behavior data from a fake stale policy: logp reflects policy_bias."""
    rng = np.random.default_rng(seed)
    recs = []
    for i in range(n):
        act = int(rng.random() < policy_bias)
        logp = np.log(policy_bias if act == 1 else 1 - policy_bias)
        recs.append(ActionRecord(
            obs=rng.standard_normal(obs_dim).astype(np.float32),
            act=np.int64(act),
            rew=1.0 if act == 1 else 0.0,
            data={"logp_a": np.float32(logp), "v": np.float32(0.0)},
            done=(i == n - 1)))
    return recs


class TestImpala:
    def test_registered(self):
        assert "IMPALA" in registered_algorithms()

    def test_trains_and_versions(self, tmp_cwd):
        algo = build_algorithm(
            "IMPALA", obs_dim=4, act_dim=2, traj_per_epoch=2,
            hidden_sizes=[16], env_dir=str(tmp_cwd),
            logger_kwargs={"output_dir": str(tmp_cwd / "logs")})
        assert algo.receive_trajectory(_episode(0.5, seed=1)) is False
        assert algo.receive_trajectory(_episode(0.5, seed=2)) is True
        assert algo.version == 1
        for key in ("LossPi", "LossV", "RhoMean", "KL"):
            assert key in algo._last_metrics

    def test_learns_from_stale_behavior(self, tmp_cwd):
        """Trajectories from a biased stale policy (70% action 0) where
        action 1 pays: the learner must still shift toward action 1."""
        algo = build_algorithm(
            "IMPALA", obs_dim=4, act_dim=2, traj_per_epoch=4,
            hidden_sizes=[32], lr=1e-2, ent_coef=0.0, env_dir=str(tmp_cwd),
            logger_kwargs={"output_dir": str(tmp_cwd / "logs")})
        for s in range(160):
            algo.receive_trajectory(_episode(0.3, n=12, seed=s))
        obs = np.random.default_rng(5).standard_normal((16, 4)).astype(
            np.float32)
        logp, _, _ = jax.jit(algo.policy.evaluate)(
            algo.state.params, jnp.asarray(obs),
            jnp.ones((16,), jnp.int32))
        # P(action 1) should now dominate.
        assert float(jnp.exp(logp).mean()) > 0.6

    def test_rho_mean_below_one_for_stale_data(self, tmp_cwd):
        algo = build_algorithm(
            "IMPALA", obs_dim=4, act_dim=2, traj_per_epoch=2,
            hidden_sizes=[16], env_dir=str(tmp_cwd),
            logger_kwargs={"output_dir": str(tmp_cwd / "logs")})
        for s in range(4):
            algo.receive_trajectory(_episode(0.9, seed=s))
        assert 0.0 < algo._last_metrics["RhoMean"] <= 1.0 + 1e-6


def test_impala_with_sequence_policy(tmp_cwd):
    """model_kind passthrough: IMPALA trains a transformer policy (the
    async-fleet algorithm with the long-context family)."""
    import numpy as np

    from relayrl_tpu.algorithms import build_algorithm
    from relayrl_tpu.types.action import ActionRecord

    algo = build_algorithm(
        "IMPALA", obs_dim=6, act_dim=3, traj_per_epoch=4,
        model_kind="transformer_discrete", d_model=16, n_layers=1,
        n_heads=2, max_seq_len=16, bucket_lengths=(16,),
        env_dir=str(tmp_cwd), logger_kwargs={"output_dir": str(tmp_cwd)})
    assert algo.arch["kind"] == "transformer_discrete"
    rng = np.random.default_rng(0)
    for ep in range(4):
        records = [
            ActionRecord(obs=rng.standard_normal(6).astype(np.float32),
                         act=np.int64(rng.integers(3)), rew=1.0,
                         data={"logp_a": np.float32(-1.1),
                               "v": np.float32(0.2)},
                         done=(i == 7))
            for i in range(8)
        ]
        updated = algo.receive_trajectory(records)
    assert updated and algo.version == 1


_OLMOE_TINY = dict(
    model_kind="transformer_moe_discrete", d_model=16, n_layers=2, n_heads=2,
    max_seq_len=16, norm="rms", norm_eps=1e-5, positions="rope",
    rope_theta=10000.0, qk_norm=True, use_bias=False, ffn="swiglu",
    moe_experts=8, moe_top_k=2, moe_d_ff=8, moe_norm_topk_prob=False)


@pytest.mark.parametrize("precision", ["float32", "bfloat16"])
def test_impala_updates_the_olmoe_block(tmp_path, precision):
    """The update on an OLMoE-shaped trunk (tiny widths): finite losses,
    every expert stack moved, and the expert load among the metrics, in the
    epoch log's keys and, with telemetry on, in the registry."""
    import json

    from relayrl_tpu import telemetry
    from relayrl_tpu.types.columnar import DecodedTrajectory

    cfg = tmp_path / "cfg.json"
    cfg.write_text(json.dumps({"learner": {"precision": precision}}))
    telemetry.reset_for_tests()
    telemetry.set_registry(telemetry.Registry(run_id="olmoe-update"))
    try:
        algo = build_algorithm(
            "IMPALA", obs_dim=6, act_dim=3, traj_per_epoch=2,
            bucket_lengths=[16], seed_salt=0, env_dir=str(tmp_path),
            config_path=str(cfg),
            logger_kwargs={"output_dir": str(tmp_path / "logs")},
            **_OLMOE_TINY)
        assert algo.arch["positions"] == "rope"
        assert "pos_embed" not in algo.state.params["params"]
        before = jax.tree_util.tree_map(np.asarray, algo.state.params)
        rng = np.random.default_rng(0)
        metrics = None
        for i in range(2):
            n = 16
            batch = algo.accumulate(DecodedTrajectory(
                agent_id="a", n_steps=n, n_records=n, marker_truncated=False,
                columns={"o": rng.standard_normal((n, 6)).astype(np.float32),
                         "a": rng.integers(0, 3, (n,)).astype(np.int32),
                         "r": rng.random(n).astype(np.float32),
                         "t": np.array([False] * (n - 1) + [True]),
                         "u": np.zeros((n,), np.uint8),
                         "x": np.zeros((n,), np.uint8)},
                aux={"v": rng.standard_normal(n).astype(np.float32),
                     "logp_a": np.full((n,), -1.1, np.float32)}))
            if batch is not None:
                metrics = algo.train_on_batch(algo.stage_batch(batch))
        assert metrics is not None
        assert np.isfinite(metrics["LossTotal"])
        # 8 experts: the fullest holds at least 1/8 of the slots, at most
        # all of one choice (1/k); the emptiest at most 1/8
        assert 1 / 8 <= metrics["moe_load_max"] <= 1 / 2
        assert 0.0 <= metrics["moe_load_min"] <= 1 / 8
        assert {"moe_load_max", "moe_load_min"} <= set(algo._log_keys())
        # every expert held: one pass over N k rows a MoE layer, written on
        # the fence span beside the load
        n_moe = sum("moe" in block
                    for block in algo.state.params["params"].values())
        assert metrics["moe_row_passes"] == n_moe > 0
        assert "moe_row_passes" in algo._fence_notes
        # ...and one sort of all its slots: 2 x 16 tokens, top-2
        assert metrics["moe_sorted_slots"] == n_moe * 2 * 16 * 2
        assert "moe_sorted_slots" in algo._fence_notes
        snap = {m["name"]: m["value"]
                for m in telemetry.get_registry().snapshot()["metrics"]
                if m["kind"] == "gauge"}
        assert snap["relayrl_moe_load_max"] == pytest.approx(
            metrics["moe_load_max"])
        assert snap["relayrl_moe_row_passes"] == n_moe
        assert snap["relayrl_moe_sorted_slots"] == n_moe * 64
        after = algo.state.params["params"]
        for stack in ("moe_w_gate", "moe_w_up", "moe_w_down"):
            moved = np.abs(np.asarray(after["block_1"]["moe"][stack])
                           - before["params"]["block_1"]["moe"][stack])
            assert (moved.reshape(8, -1).max(axis=1) > 0).all(), stack
    finally:
        telemetry.reset_for_tests()


def test_dense_trunks_report_no_expert_load(tmp_path):
    algo = _build(tmp_path, "IMPALA", "mlp")
    assert algo._metric_gauges == {}
    assert "moe_load_max" not in algo._log_keys()


# -- byte frames stay bytes from the wire to the jitted update --------------
# (data/batching.padded_obs_dtype; the models cast on entry, on the device)

_FRAME = (12, 12, 2)
_PIX = 12 * 12 * 2
_SMALL_CONV = [[8, 4, 2], [8, 3, 1]]


def _frames_episode(n, seed, obs_dtype=np.uint8, obs_dim=_PIX, act_dim=3):
    from relayrl_tpu.types.columnar import DecodedTrajectory

    rng = np.random.default_rng(seed)
    return DecodedTrajectory(
        agent_id="a", n_steps=n, n_records=n, marker_truncated=False,
        columns={"o": rng.integers(0, 256, (n, obs_dim)).astype(obs_dtype),
                 "a": rng.integers(0, act_dim, (n,)).astype(np.int32),
                 "r": rng.random(n).astype(np.float32),
                 "t": np.array([False] * (n - 1) + [True]),
                 "u": np.zeros((n,), np.uint8),
                 "x": np.zeros((n,), np.uint8)},
        aux={"v": rng.standard_normal(n).astype(np.float32),
             "logp_a": (-1.0 - rng.random(n)).astype(np.float32)})


def _build(tmp_path, algo, model, precision="float32", **hp):
    import json

    cfg = tmp_path / "cfg.json"
    cfg.write_text(json.dumps({"learner": {"precision": precision}}))
    if model == "cnn":
        hp = {"obs_shape": list(_FRAME), "conv_spec": _SMALL_CONV,
              "dense": 16, **hp}
    else:
        hp = {"hidden_sizes": [16], **hp}
    if algo == "PPO":
        hp = {"minibatch_count": 2, "train_iters": 2, **hp}
    return build_algorithm(
        algo, obs_dim=_PIX, act_dim=3, traj_per_epoch=2,
        bucket_lengths=[8], seed_salt=0, env_dir=str(tmp_path),
        config_path=str(cfg),
        logger_kwargs={"output_dir": str(tmp_path / "logs")}, **hp)


def _full_batch(algo, obs_dtype):
    batch = None
    for i, n in enumerate((8, 5)):
        assert batch is None
        batch = algo.accumulate(_frames_episode(n, i, obs_dtype))
    return batch


class _Compiles:
    """Compile requests and backend compiles between enter and exit, from
    jax's monitoring events (as chip_smoke.CompileCounter counts them)."""

    def __enter__(self):
        from jax import monitoring

        self.requests = self.backend = 0
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)
        return self

    def __exit__(self, *exc):
        from jax import monitoring

        monitoring.unregister_event_listener(self._event)
        monitoring.unregister_event_duration_listener(self._duration)

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1

    def _duration(self, event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend += 1


class TestUint8Observations:
    @pytest.mark.parametrize("precision", ["float32", "bfloat16"])
    @pytest.mark.parametrize("algo,model", [
        ("IMPALA", "cnn"), ("IMPALA", "mlp"), ("PPO", "cnn"), ("PPO", "mlp"),
        ("REINFORCE", "mlp")])
    def test_update_is_bit_identical_to_the_float32_batch(
            self, tmp_path, algo, model, precision):
        """0..255 are exact in uint8, float32 and bfloat16, and no update
        touches ``batch["obs"]`` before the model's cast: one update on
        the uint8 batch gives the float32 batch's loss and parameters to
        the bit."""
        learner = _build(tmp_path, algo, model, precision)
        u8 = _full_batch(learner, np.uint8)
        f32 = dict(u8, obs=u8["obs"].astype(np.float32))
        assert u8["obs"].dtype == np.uint8
        outs = []
        for batch in (u8, f32):
            state = jax.tree_util.tree_map(
                lambda x: jnp.copy(x) if isinstance(x, jax.Array) else x,
                learner.state)
            outs.append(jax.device_get(
                learner._update(state, learner._to_device(batch))))
        (s_u8, m_u8), (s_f32, m_f32) = outs
        assert m_u8.keys() == m_f32.keys()
        for key in m_u8:
            assert np.asarray(m_u8[key]).tobytes() == \
                np.asarray(m_f32[key]).tobytes(), key
        before = jax.tree_util.tree_leaves(jax.device_get(
            learner.state.params))
        got, want = (jax.tree_util.tree_leaves(s.params)
                     for s in (s_u8, s_f32))
        assert len(got) == len(want)
        for a, b in zip(got, want):
            assert a.dtype == b.dtype and a.tobytes() == b.tobytes()
        assert any(a.tobytes() != b.tobytes()
                   for a, b in zip(got, before)), "the update did nothing"

    @pytest.mark.parametrize("algo,model,obs_dtype", [
        ("IMPALA", "cnn", np.uint8), ("PPO", "cnn", np.uint8),
        ("IMPALA", "mlp", np.float32), ("REINFORCE", "mlp", np.float32)])
    def test_first_real_batch_after_warmup_compiles_nothing(
            self, tmp_path, algo, model, obs_dtype):
        """Warm-up compiles the signature the stream will use: a
        ``scale_obs`` pixel learner is fed byte frames, everything else
        float32."""
        learner = _build(tmp_path, algo, model)
        assert learner.mh_zero_batch(2, 8)["obs"].dtype == obs_dtype
        assert learner.warmup() == 1
        size = learner._update._cache_size()
        batch = _full_batch(learner, obs_dtype)
        assert batch["obs"].dtype == obs_dtype
        with _Compiles() as seen:
            learner.train_on_batch(learner.stage_batch(batch))
            learner.inflight.drain()
        assert (seen.requests, seen.backend) == (0, 0)
        assert learner._update._cache_size() == size
        assert learner.version == 1

    @pytest.mark.parametrize("model,obs_dtype,hp", [
        ("cnn", np.uint8, {}),
        ("mlp", np.float32, dict(
            model_kind="transformer_discrete", d_model=16, n_layers=1,
            n_heads=2, max_seq_len=8))],
        ids=["pixel", "sequence"])
    def test_update_on_a_batch_shaped_on_the_device_is_the_same_program(
            self, tmp_path, monkeypatch, model, obs_dtype, hp):
        """``stage_batch`` shapes a flat put outside the update, so the
        update lowered on such a batch has the text of the update lowered
        on one put as it is, and running both compiles it once."""
        from relayrl_tpu.algorithms import base

        learner = _build(tmp_path, "IMPALA", model, **hp)
        if hp:
            assert learner.arch["kind"] == hp["model_kind"]
        batch = _full_batch(learner, obs_dtype)
        whole = learner.stage_batch(batch)
        monkeypatch.setattr(base, "_H2D_FLAT_BYTES", 1)
        shaped = learner.stage_batch(batch)
        assert all(isinstance(v, jax.Array) for v in shaped.values())
        on_whole, on_shaped = (
            learner._update.lower(learner.state, staged).as_text()
            for staged in (whole, shaped))
        assert on_whole == on_shaped
        learner.train_on_batch(whole)
        size = learner._update._cache_size()
        with _Compiles() as seen:
            learner.train_on_batch(shaped)
            learner.inflight.drain()
        assert (seen.requests, seen.backend) == (0, 0)
        assert learner._update._cache_size() == size
        assert learner.version == 2

    def test_scale_obs_learner_fed_float32_compiles_once_more(
            self, tmp_path):
        """envs/atari.py's default obs_dtype is float32: such a stream
        pays one more compile at its first batch, and from then on the
        placeholder follows the stream."""
        learner = _build(tmp_path, "IMPALA", "cnn")
        learner.warmup()
        size = learner._update._cache_size()
        for rnd in range(2):
            batch = None
            for i, n in enumerate((8, 5)):
                batch = learner.accumulate(
                    _frames_episode(n, 10 * rnd + i, np.float32))
            learner.train_on_batch(batch)
        assert learner._update._cache_size() == size + 1
        assert learner.mh_zero_batch(2, 8)["obs"].dtype == np.float32

    @pytest.mark.parametrize("scale_obs,obs_dtype", [
        (True, np.uint8), (False, np.float32), (None, np.uint8)])
    def test_placeholder_goes_by_scale_obs_before_any_data(
            self, tmp_path, scale_obs, obs_dtype):
        hp = {} if scale_obs is None else {"scale_obs": scale_obs}
        learner = _build(tmp_path, "IMPALA", "cnn", **hp)
        zero = learner.mh_zero_batch(2, 8)
        assert zero["obs"].dtype == obs_dtype
        assert zero["obs"].shape == (2, 8, _PIX)
        # the arch that goes out on the wire is as the user wrote it
        assert learner.arch.get("scale_obs") == scale_obs

    @pytest.mark.parametrize("model", ["cnn", "mlp"])
    def test_multihost_placeholder_and_coordinator_batch_agree(
            self, tmp_path, model):
        """Non-coordinators build ``mh_zero_batch(B, T)`` from a
        descriptor that carries only (B, T): under a mesh every batch the
        coordinator assembles is float32, whatever its actors send, and
        so is every rank's placeholder — before and after data."""
        from relayrl_tpu.parallel import make_mesh

        coordinator = _build(tmp_path, "IMPALA", model)
        other = _build(tmp_path, "IMPALA", model)
        for learner in (coordinator, other):
            learner.enable_multihost(make_mesh({"dp": 2}, jax.devices()[:2]))
        early = other.mh_zero_batch(2, 8)
        batch = _full_batch(coordinator, np.uint8)
        late = other.mh_zero_batch(*batch["obs"].shape[:2])
        for zero in (early, late, coordinator.mh_zero_batch(2, 8)):
            assert zero.keys() == batch.keys()
            for key in batch:
                assert zero[key].dtype == batch[key].dtype, key
                assert zero[key].shape == batch[key].shape, key
        assert batch["obs"].dtype == np.float32
        np.testing.assert_array_equal(
            batch["obs"][0], _frames_episode(8, 0).columns["o"])
        coordinator.train_on_batch(batch)
        coordinator.inflight.drain()
        assert coordinator.version == 1
