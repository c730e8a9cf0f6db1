"""KV-cache incremental decoding: policy-level numerics + actor behavior.

The cached path must be numerically identical to the full-window recompute
(same logits ⇒ same sampled actions for the same key), survive model
hot-swaps mid-episode (replay rebuild), and hand off to the window path
once the episode outgrows the context window.
"""

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from relayrl_tpu.models import build_policy
from relayrl_tpu.runtime.policy_actor import PolicyActor
from relayrl_tpu.types.model_bundle import ModelBundle

ARCH = {"kind": "transformer_discrete", "obs_dim": 6, "act_dim": 3,
        "d_model": 32, "n_layers": 2, "n_heads": 2, "max_seq_len": 12}


# Blocks whose positions enter q and k (RoPE) instead of the embedding: the
# cache then holds rotated keys, and the decode step rotates its query and
# key at the write index.
_MODERN = {"norm": "rms", "norm_eps": 1e-5, "positions": "rope",
           "qk_norm": True, "use_bias": False, "ffn": "swiglu", "d_ff": 48}
CACHED_ARCHS = {
    "gpt2": {},
    "rope": {"positions": "rope"},
    "rope_qknorm_rms_swiglu": _MODERN,
    "olmoe_block": {**_MODERN, "kind": "transformer_moe_discrete",
                    "moe_experts": 4, "moe_top_k": 2, "moe_d_ff": 16,
                    "moe_norm_topk_prob": False},
    # grouped-query heads alone: the cache holds 1 k/v head for 2 q heads
    "grouped_query": {**_MODERN, "n_kv_heads": 1, "qk_norm": "head"},
    # layers of several kinds (the LFM2 trunk): two kinds of state side by
    # side — a (k, v) pair of the k/v heads for the attention layer, the
    # last two rows of B * u for each conv layer — dense and expert FFNs
    "lfm2_trunk": {**_MODERN, "kind": "transformer_moe_discrete",
                   "n_layers": 4, "n_heads": 4, "n_kv_heads": 2,
                   "qk_norm": "head", "rope_theta": 1e6,
                   "layer_types": ["conv", "full_attention", "conv", "conv"],
                   "moe_dense_layers": 1, "moe_experts": 8, "moe_top_k": 2,
                   "moe_d_ff": 16, "moe_router": "sigmoid",
                   "moe_expert_bias": True, "moe_held": [2, 3]},
    # a conv layer LAST: the readout-row mode runs the operator on three
    # rows and the FFN on one
    "conv_last_dense": {**_MODERN, "n_layers": 2,
                        "layer_types": ["full_attention", "conv"]},
    # the SmallThinker trunk: a global NoPE layer's full (k, v) pair beside
    # the RINGS of the windowed RoPE layers (window 3 < the 8 rows the tests
    # decode: the ring wraps twice), 3 q heads of a width of their own over
    # 1 k/v head, the router on the layer's input, ReGLU experts
    "smallthinker_trunk": {
        "kind": "transformer_moe_discrete", "norm": "rms",
        "positions": "rope", "rope_theta": 1.5e6, "use_bias": False,
        "ffn": "reglu", "n_layers": 3, "n_heads": 3, "n_kv_heads": 1,
        "head_dim": 16,
        "layer_types": ["full_attention", "sliding_attention",
                        "sliding_attention"],
        "sliding_window": 3, "rope_layers": [0, 1, 1], "moe_experts": 8,
        "moe_top_k": 3, "moe_d_ff": 16, "moe_router_input": "layer",
        "moe_held": [2, 4]},
    # the Kimi Linear trunk: two kinds of state side by side — a KDA layer's
    # convolution tails with its [H, K, K] float32 state, and the LATENT rows
    # (c, k_pe) of the attention layer, expanded through kv_b every step —
    # the dense FFN first, sigmoid-routed experts beside a shared expert
    "kimi_linear_trunk": {
        "kind": "transformer_moe_discrete", "norm": "rms", "norm_eps": 1e-5,
        "positions": "none", "use_bias": False, "ffn": "swiglu", "d_ff": 48,
        "n_layers": 3, "n_heads": 4,
        "layer_types": ["kda", "latent_attention", "kda"],
        "kda_heads": 2, "kda_head_dim": 8, "kda_chunk": 4,
        "kv_lora_rank": 8, "qk_nope_head_dim": 4, "qk_rope_head_dim": 2,
        "v_head_dim": 4, "moe_dense_layers": 1, "moe_experts": 8,
        "moe_top_k": 3, "moe_d_ff": 16, "moe_router": "sigmoid",
        "moe_expert_bias": True, "moe_routed_scaling": 2.446,
        "moe_shared_d_ff": 16, "moe_held": [2, 4]},
    # latent attention LAST under a dense FFN, behind a layer that rotates
    # (``rope_layers``: the latent layer sees no positions): the readout-row
    # mode expands every row's latent and runs one query
    "latent_last_dense": {**_MODERN, "qk_norm": False, "n_layers": 2,
                          "n_heads": 4, "rope_layers": [1, 0],
                          "layer_types": ["full_attention",
                                          "latent_attention"],
                          "kv_lora_rank": 8, "qk_nope_head_dim": 4,
                          "qk_rope_head_dim": 2, "v_head_dim": 4},
    # a windowed layer LAST under a dense FFN: the readout-row mode masks
    # the one row's keys by the window
    "sliding_last_dense": {**_MODERN, "qk_norm": False, "n_layers": 2,
                           "layer_types": ["full_attention",
                                           "sliding_attention"],
                           "sliding_window": 3},
    # the stack run 3 times over one tree: a (k, v) pair a pass and layer,
    # sandwich norms, the final norm between passes
    "looped": {**_MODERN, "qk_norm": False, "loop_steps": 3,
               "norm_sandwich": True},
    # ... and with two kinds of state a pass: a conv layer's rows and an
    # attention layer's pair, three times each
    "looped_conv": {**_MODERN, "qk_norm": False, "loop_steps": 3,
                    "layer_types": ["conv", "full_attention"]},
}


def _policy_params(seed=0, **arch):
    policy = build_policy({**ARCH, **arch})
    return policy, policy.init_params(jax.random.PRNGKey(seed))


@functools.cache
def _trunk(name):
    """``(policy, params)`` of ``CACHED_ARCHS[name]``, built once for the
    module, the policy's three steps each ONE compiled program (the position
    traced, as the runtime calls them): stepped eagerly, every position of
    every case traced the trunk again."""
    policy, params = _policy_params(**CACHED_ARCHS[name])
    return types.SimpleNamespace(
        init_cache=policy.init_cache, evaluate=jax.jit(policy.evaluate),
        step_window=jax.jit(policy.step_window),
        step_cached=jax.jit(policy.step_cached),
        prefill_cache=jax.jit(policy.prefill_cache)), params


class TestStepCachedNumerics:
    @pytest.mark.parametrize("name", sorted(CACHED_ARCHS))
    def test_matches_step_window(self, name):
        policy, params = _trunk(name)
        rng = np.random.default_rng(0)
        W = 8
        cache = policy.init_cache(W)
        window = np.zeros((W, 6), np.float32)
        for t in range(W):
            obs = rng.standard_normal(6).astype(np.float32)
            window[t] = obs
            key = jax.random.PRNGKey(100 + t)
            a_w, aux_w = policy.step_window(params, key,
                                            jnp.asarray(window), t + 1)
            a_c, aux_c, cache = policy.step_cached(params, key, cache,
                                                   obs, t)
            assert int(a_w) == int(a_c), f"t={t}"
            np.testing.assert_allclose(float(aux_w["v"]),
                                       float(aux_c["v"]), atol=1e-4)
            np.testing.assert_allclose(float(aux_w["logp_a"]),
                                       float(aux_c["logp_a"]), atol=1e-4)

    @pytest.mark.parametrize("name", ["rope", "olmoe_block", "lfm2_trunk",
                                      "conv_last_dense",
                                      "smallthinker_trunk",
                                      "sliding_last_dense", "looped",
                                      "looped_conv", "kimi_linear_trunk"])
    def test_prefilled_cache_continues_as_the_stepped_one(self, name):
        # prefill rotates W keys at positions 0..W-1 in one dispatch; the
        # steps after it must read them as if they had been written one by
        # one
        policy, params = _trunk(name)
        rng = np.random.default_rng(5)
        W, t0 = 8, 5
        window = np.zeros((W, 6), np.float32)
        window[:t0] = rng.standard_normal((t0, 6))
        stepped = policy.init_cache(W)
        for t in range(t0):
            _, _, stepped = policy.step_cached(
                params, jax.random.PRNGKey(t), stepped, window[t], t)
        # n_valid = t0: a conv layer's state has no positions to
        # overwrite, it is taken from the rows before the real prefix's end
        filled = policy.prefill_cache(params, policy.init_cache(W),
                                      jnp.asarray(window), t0)
        obs = rng.standard_normal(6).astype(np.float32)
        key = jax.random.PRNGKey(9)
        a1, aux1, _ = policy.step_cached(params, key, stepped, obs, t0)
        a2, aux2, _ = policy.step_cached(params, key, filled, obs, t0)
        assert int(a1) == int(a2)
        np.testing.assert_allclose(float(aux1["v"]), float(aux2["v"]),
                                   atol=1e-4)

    def test_two_kinds_of_state_side_by_side(self):
        policy, _ = _policy_params(**CACHED_ARCHS["lfm2_trunk"])
        cache = policy.init_cache(8, batch_size=3)
        assert [getattr(c, "shape", None) for c in cache] == [
            (3, 2, 32), None, (3, 2, 32), (3, 2, 32)]
        k, v = cache[1]
        # flat rows of 2 k/v heads (not 4) x 8 lanes
        assert k.shape == v.shape == (3, 8, 2 * 8)

    def test_a_windowed_layer_keeps_a_ring_of_window_rows(self):
        policy, _ = _policy_params(**CACHED_ARCHS["smallthinker_trunk"])
        shapes = [[a.shape for a in pair] for pair in policy.init_cache(8, 2)]
        # the global layer: all 8 rows; the windowed ones: 3, of 1 k/v head
        # of 16 (not d_model // n_heads)
        assert shapes == [[(2, 8, 16)] * 2, [(2, 3, 16)] * 2,
                          [(2, 3, 16)] * 2]
        # a cache shorter than the window is the plain cache
        assert policy.init_cache(2)[1][0].shape == (1, 2, 16)

    @pytest.mark.parametrize("t0", [2, 3, 7])
    def test_the_ring_takes_the_real_rows_of_a_prefill_only(self, t0):
        # before, at and past the ring's first wrap; the padding rows after
        # t0 must not displace the real ones
        policy, params = _trunk("smallthinker_trunk")
        W = 8
        window = np.zeros((W, 6), np.float32)
        window[:t0] = np.random.default_rng(5).standard_normal((t0, 6))
        window[t0:] = 50.0    # padding that would be seen if it got in
        stepped = policy.init_cache(W)
        for t in range(t0):
            _, _, stepped = policy.step_cached(
                params, jax.random.PRNGKey(t), stepped, window[t], t)
        filled = policy.prefill_cache(params, policy.init_cache(W),
                                      jnp.asarray(window), t0)
        obs = np.random.default_rng(6).standard_normal(6).astype(np.float32)
        outs = [policy.step_cached(params, jax.random.PRNGKey(9), c, obs,
                                   t0)[1] for c in (stepped, filled)]
        np.testing.assert_allclose(float(outs[0]["v"]), float(outs[1]["v"]),
                                   atol=1e-4)
        np.testing.assert_allclose(float(outs[0]["logp_a"]),
                                   float(outs[1]["logp_a"]), atol=1e-4)

    @pytest.mark.parametrize("name", ["lfm2_trunk", "conv_last_dense",
                                      "grouped_query", "smallthinker_trunk",
                                      "sliding_last_dense"])
    def test_full_forward_equals_the_stepped_rows(self, name):
        # evaluate() over the whole sequence against step_cached row by row
        policy, params = _trunk(name)
        rng = np.random.default_rng(11)
        W = 8
        window = rng.standard_normal((W, 6)).astype(np.float32)
        _logp, _ent, v_full = policy.evaluate(
            params, jnp.asarray(window)[None], jnp.zeros((1, W), jnp.int32))
        cache = policy.init_cache(W)
        for t in range(W):
            _, aux, cache = policy.step_cached(
                params, jax.random.PRNGKey(t), cache, window[t], t)
            np.testing.assert_allclose(float(aux["v"]), float(v_full[0, t]),
                                       atol=1e-4, err_msg=f"t={t}")

    def test_moe_family_has_cache(self):
        moe = build_policy({**ARCH, "kind": "transformer_moe_discrete",
                            "moe_experts": 2})
        params = moe.init_params(jax.random.PRNGKey(0))
        cache = moe.init_cache(4)
        act, aux, cache = moe.step_cached(
            params, jax.random.PRNGKey(1), cache,
            np.zeros(6, np.float32), 0)
        assert np.isfinite(float(aux["logp_a"]))

    def test_mask_applies_to_readout(self):
        policy, params = _policy_params()
        cache = policy.init_cache(4)
        mask = np.array([1.0, 0.0, 0.0], np.float32)
        act, _, _ = policy.step_cached(params, jax.random.PRNGKey(0),
                                       cache, np.zeros(6, np.float32), 0,
                                       mask)
        assert int(act) == 0  # only legal action


def _actor(version=1, seed=0, use_kv_cache=True, **arch_over):
    policy, params = _policy_params()
    arch = {**ARCH, **arch_over}
    return PolicyActor(ModelBundle(arch=arch, params=params,
                                   version=version), seed=seed,
                       max_traj_length=200, use_kv_cache=use_kv_cache)


class TestActorCachedServing:
    def test_cached_equals_window_actor(self):
        # Two actors, same seed/params: one with the cache disabled.
        rng = np.random.default_rng(1)
        obs_seq = [rng.standard_normal(6).astype(np.float32)
                   for _ in range(8)]
        a_cached = _actor(seed=3)
        a_window = _actor(seed=3, use_kv_cache=False)
        assert a_cached._cached_fn is not None
        for obs in obs_seq:
            r1 = a_cached.request_for_action(obs)
            r2 = a_window.request_for_action(obs)
            assert int(np.asarray(r1.act)) == int(np.asarray(r2.act))
            np.testing.assert_allclose(
                np.asarray(r1.data["logp_a"]), np.asarray(r2.data["logp_a"]),
                atol=1e-4)

    def test_hot_swap_mid_episode_rebuilds(self):
        policy, params2 = _policy_params(seed=9)
        actor = _actor(seed=5)
        control = _actor(seed=5, use_kv_cache=False)
        rng = np.random.default_rng(2)
        obs_seq = [rng.standard_normal(6).astype(np.float32)
                   for _ in range(6)]
        for obs in obs_seq[:3]:
            actor.request_for_action(obs)
            control.request_for_action(obs)
        bundle = ModelBundle(arch=ARCH, params=params2, version=2)
        assert actor.maybe_swap(bundle) and control.maybe_swap(bundle)
        for obs in obs_seq[3:]:
            r1 = actor.request_for_action(obs)
            r2 = control.request_for_action(obs)
            assert int(np.asarray(r1.act)) == int(np.asarray(r2.act))
            np.testing.assert_allclose(
                np.asarray(r1.data["v"]), np.asarray(r2.data["v"]),
                atol=1e-4)

    def test_rolling_window_falls_back(self):
        actor = _actor(seed=7, actor_context=4)
        control = _actor(seed=7, actor_context=4, use_kv_cache=False)
        rng = np.random.default_rng(3)
        for i in range(7):  # rolls after 4 steps
            obs = rng.standard_normal(6).astype(np.float32)
            r1 = actor.request_for_action(obs)
            r2 = control.request_for_action(obs)
            assert int(np.asarray(r1.act)) == int(np.asarray(r2.act)), i
        assert actor._cache is None  # rolled -> cache dropped

    def test_episode_boundary_resets_cache(self):
        actor = _actor(seed=11)
        actor.request_for_action(np.zeros(6, np.float32))
        assert actor._cache is not None
        actor.flag_last_action(reward=1.0)
        assert actor._cache is None and actor._window_len == 0


def test_step_cached_batched():
    # init_cache(W, batch_size=B): a [B, D] obs batch is B parallel
    # episodes at the same position, NOT a time axis.
    policy, params = _policy_params()
    B, W = 4, 8
    cache = policy.init_cache(W, batch_size=B)
    rng = np.random.default_rng(4)
    obs = rng.standard_normal((B, 6)).astype(np.float32)
    act, aux, cache = policy.step_cached(params, jax.random.PRNGKey(0),
                                         cache, obs, 0)
    assert act.shape == (B,)
    assert aux["v"].shape == (B,)
    # against per-episode single decode
    for b in range(B):
        c1 = policy.init_cache(W)
        a1, aux1, _ = policy.step_cached(params, jax.random.PRNGKey(0),
                                         c1, obs[b], 0)
        np.testing.assert_allclose(float(aux1["v"]), float(aux["v"][b]),
                                   atol=1e-5)


class TestEvalHarness:
    def test_eval_does_not_ship_trajectories(self):
        # Greedy eval must neither append to the trajectory nor fire
        # on_send — the policy is probed, not trained.
        sent = []
        policy, params = _policy_params()
        actor = PolicyActor(ModelBundle(arch=ARCH, params=params, version=1),
                            seed=0, max_traj_length=100,
                            on_send=sent.append)
        for _ in range(5):
            actor.deterministic_action(np.zeros(6, np.float32))
        actor.reset_episode()
        assert sent == []
        assert len(actor.trajectory.get_actions()) == 0
        assert actor._window_len == 0 and actor._cache is None
        # and a subsequent sampling episode works from clean state
        rec = actor.request_for_action(np.zeros(6, np.float32))
        assert rec is not None and actor._window_len == 1

    def test_local_runner_evaluate(self, tmp_cwd):
        from relayrl_tpu.envs import RecallEnv
        from relayrl_tpu.runtime.local_runner import LocalRunner

        runner = LocalRunner(
            RecallEnv(horizon=4), "REINFORCE", env_dir=str(tmp_cwd), seed=0,
            seed_salt=3, with_vf_baseline=True, traj_per_epoch=4,
            bucket_lengths=(8,),
            logger_kwargs={"output_dir": str(tmp_cwd / "logs")})
        result = runner.evaluate(episodes=3, max_steps=8)
        assert result["episodes"] == 3
        assert len(result["returns"]) == 3
        # eval fed nothing into the learner
        assert runner.updates == 0
        assert len(runner.actor.trajectory.get_actions()) == 0

    def test_eval_refuses_mid_episode(self):
        from relayrl_tpu.runtime.agent import greedy_episodes

        policy, params = _policy_params()
        actor = PolicyActor(ModelBundle(arch=ARCH, params=params, version=1),
                            seed=0, max_traj_length=100)
        actor.request_for_action(np.zeros(6, np.float32))  # episode open
        import pytest as _pytest

        with _pytest.raises(RuntimeError, match="mid-episode"):
            greedy_episodes(actor, None, episodes=1)


def test_rapid_swap_churn_keeps_cached_parity():
    """Many hot-swaps interleaved with cached steps (the fleet steady
    state: a fresh bundle every few env steps) must keep the cached path
    bit-matched with the window path throughout."""
    policy, params0 = _policy_params()
    bundles = [ModelBundle(arch=ARCH,
                           params=_policy_params(seed=s)[1], version=s)
               for s in range(2, 7)]
    cached = _actor(seed=13)
    control = _actor(seed=13, use_kv_cache=False)
    rng = np.random.default_rng(6)
    swap_iter = iter(bundles)
    for t in range(10):
        obs = rng.standard_normal(6).astype(np.float32)
        r1 = cached.request_for_action(obs)
        r2 = control.request_for_action(obs)
        assert int(np.asarray(r1.act)) == int(np.asarray(r2.act)), t
        np.testing.assert_allclose(np.asarray(r1.data["v"]),
                                   np.asarray(r2.data["v"]), atol=1e-4)
        if t % 2 == 1:  # swap every other step, mid-episode
            b = next(swap_iter)
            assert cached.maybe_swap(b) and control.maybe_swap(b)
    cached.flag_last_action(reward=0.0)
    control.flag_last_action(reward=0.0)
