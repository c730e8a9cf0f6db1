"""The Ouro-shaped looped trunk against the benchmark's plain reference.

``benchmark/reference/ouro-policy.py`` is written from the model's equations
in plain ``jax.numpy`` — a Python loop over passes and layers, sandwich
norms, the final norm between passes — and reads the parameter tree as data;
it shares no code with ``relayrl_tpu/models`` (one parameter tree called
``loop_steps`` times through flax, a block checkpoint, a cache a pass and
layer). On the chip the harness compares the two at the published widths
(``benchmark/configs/ouro-policy.json``'s tolerance); here the same
comparison runs at tiny widths on the CPU: outputs, IMPALA's loss and EVERY
gradient (a tied weight's is the sum over its four uses), the checkpoint on
and off, cached decode with ``S x L`` states, the readout row.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from relayrl_tpu.models import build_policy
# the reference tests share their plumbing: a file loaded by its path, the
# system's outputs for all actions, IMPALA's loss from either side's
from test_lfm2_reference import _all_logp_v, _by_path, _impala_loss

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T = 16
S, L = 4, 2     # passes, layers: 8 block applications over 2 blocks' weights
TOP = ("obs_embed", "block_0", "block_1", "ln_final", "pi_head",
       "vf_head_up", "vf_head")


@pytest.fixture(scope="module")
def reference():
    return _by_path("benchmark/reference/ouro-policy.py")


def _published():
    with open(os.path.join(REPO, "benchmark/configs/ouro-policy.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    cfg = _published()
    # tiny widths; every mechanism of the published trunk: 4 heads of 8 with
    # k/v at the same count, a SwiGLU FFN, sandwich norms, 4 passes
    cfg.update(hidden_size=32, head_dim=8, num_attention_heads=4,
               num_key_value_heads=4, intermediate_size=48,
               num_hidden_layers=L, positions_as_run=T)
    return cfg


_BUILT: dict = {}   # a policy and its seeded parameters, built once


def _system(reference, cfg, precision="float32", seed=0, **over):
    key = (precision, seed, repr(sorted(over.items())))
    if key not in _BUILT:
        kwargs = {**reference.program_kwargs(cfg), **over}
        arch = {"kind": kwargs.pop("model_kind"), "obs_dim": cfg["obs_dim"],
                "act_dim": cfg["act_dim"], "has_critic": True,
                "precision": precision, **kwargs}
        policy = build_policy(arch)
        params = jax.jit(policy.init_params)(jax.random.PRNGKey(seed))
        # norm scales start at one: move them, so that a norm left out or
        # put in the wrong place shows
        leaves, tree = jax.tree_util.tree_flatten_with_path(params)
        rng = np.random.default_rng(seed + 7)
        params = jax.tree_util.tree_unflatten(tree, [
            leaf * jnp.asarray(rng.uniform(0.5, 1.5, leaf.shape),
                               leaf.dtype)
            if "scale" in jax.tree_util.keystr(path) else leaf
            for path, leaf in leaves])
        _BUILT[key] = policy, params
    return _BUILT[key]


def _obs(cfg, seed=1, batch=2, rows=T):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(
        (batch, rows, cfg["obs_dim"])), jnp.float32)


def _batch(cfg, seed=2):
    rng = np.random.default_rng(seed)
    shape = (2, T)
    valid = np.ones(shape, np.float32)
    valid[1, 13:] = 0.0     # one episode ends before the window does
    return {"act": jnp.asarray(rng.integers(0, cfg["act_dim"], shape)),
            "rew": jnp.asarray((rng.random(shape) < 0.2), jnp.float32),
            "valid": jnp.asarray(valid),
            "logp": jnp.full(shape, -np.log(cfg["act_dim"]), jnp.float32),
            "last_val": jnp.zeros((2,), jnp.float32)}


def _outputs(policy, params, obs, cfg):
    return jax.jit(lambda p, o: _all_logp_v(policy, p, o, cfg["act_dim"]))(
        params, obs)


def _shape(cfg):
    """What the reference's ``_pass`` takes of the tiny configuration."""
    return (L, 4, 8, float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]))


def _differs(a, b):
    return max(float(jnp.abs(a[0] - b[0]).max()),
               float(jnp.abs(a[1] - b[1]).max()))


_GRADS: dict = {}


def _loss_and_grads(reference, cfg, which):
    """IMPALA's loss and its gradient by every parameter, from the system's
    forward (``"system"``, ``"checkpointed"``) or the reference's."""
    if which not in _GRADS:
        over = {"block_checkpoint": which == "checkpointed"}
        policy, params = _system(reference, cfg, **over)
        obs, batch = _obs(cfg), _batch(cfg)
        if which == "reference":
            fwd = lambda p: reference.forward(p, obs, cfg)      # noqa: E731
        else:
            fwd = lambda p: _all_logp_v(policy, p, obs,         # noqa: E731
                                        cfg["act_dim"])
        _GRADS[which] = jax.jit(jax.value_and_grad(
            lambda p: _impala_loss(*fwd(p), batch)))(params)
    return _GRADS[which]


class TestSystemAgainstReference:
    def test_the_trunk_is_what_the_configuration_says(self, reference, cfg):
        kwargs = reference.program_kwargs(cfg)
        assert (kwargs["loop_steps"], kwargs["norm_sandwich"],
                kwargs["block_checkpoint"]) == (S, True, True)
        policy, params = _system(reference, cfg)
        tree = params["params"]
        # ONE tree of L blocks, whatever the passes; four norms a block, no
        # bias in it; one final norm for every pass
        assert sorted(tree) == sorted(TOP)
        for block in ("block_0", "block_1"):
            assert sorted(tree[block]) == [
                "attn_out", "k_proj", "ln_attn", "ln_attn_out", "ln_mlp",
                "ln_mlp_out", "mlp_down", "mlp_gate", "mlp_up", "q_proj",
                "v_proj"]
            assert all(list(leaf) in (["kernel"], ["scale"])
                       for leaf in tree[block].values())
        assert policy.arch["loop_steps"] == S

    def test_the_published_sizes(self, reference):
        pub = _published()
        d, ff = pub["hidden_size"], pub["intermediate_size"]
        assert (pub["num_attention_heads"] * pub["head_dim"]
                == pub["num_key_value_heads"] * pub["head_dim"] == d == 2048)
        layer = 4 * d * d + 3 * d * ff + 4 * d
        assert layer == 51_388_416
        assert pub["num_hidden_layers"] == 8 and pub["total_ut_steps"] == 4
        assert pub["published"]["num_hidden_layers"] == 48
        assert pub["reduced"] == ["num_hidden_layers"]
        shapes = jax.eval_shape(build_policy({
            "kind": "transformer_discrete", "obs_dim": pub["obs_dim"],
            "act_dim": pub["act_dim"], "has_critic": True,
            **{k: v for k, v in reference.program_kwargs(pub).items()
               if k != "model_kind"}}).init_params, jax.random.PRNGKey(0))
        total = sum(int(np.prod(leaf.shape))
                    for leaf in jax.tree_util.tree_leaves(shapes))
        assert total == 8 * layer + 4_272_145       # 415.4 M: 6.65 GB at 16 B

    @pytest.mark.parametrize("checkpoint", [False, True])
    def test_outputs(self, reference, cfg, checkpoint):
        policy, params = _system(reference, cfg, block_checkpoint=checkpoint)
        obs = _obs(cfg)
        got = _outputs(policy, params, obs, cfg)
        want = reference.forward(params, obs, cfg)
        assert float(jnp.max(want[0]) - jnp.min(want[0])) > 0.5
        assert _differs(got, want) < 2e-5

    @pytest.mark.parametrize("wrong", [
        {"passes": 3}, {"passes": 5}, {"sandwich": False}])
    def test_a_different_model_differs(self, reference, cfg, wrong):
        policy, params = _system(reference, cfg)
        obs = _obs(cfg)
        got = _outputs(policy, params, obs, cfg)
        assert _differs(got, reference.forward(params, obs, cfg,
                                               wrong=wrong)) > 1e-2

    def test_the_norm_between_passes_is_not_the_heads_second_norm(
            self, reference, cfg):
        """The heads read the last pass's normed rows as they are: a trunk
        that normed them once more would differ by the final norm's scale
        (moved off one here)."""
        policy, params = _system(reference, cfg)
        obs = _obs(cfg)
        h = reference._dense(params["params"]["obs_embed"], obs)
        shape = _shape(cfg)
        for _ in range(S):
            h = reference._pass(params["params"], h, shape, True,
                                lambda a: a)
        twice = reference._heads(params["params"], reference._rms_norm(
            params["params"]["ln_final"], h, float(cfg["rms_norm_eps"])))
        got = _outputs(policy, params, obs, cfg)
        assert _differs(got, reference._heads(params["params"], h)) < 2e-5
        assert _differs(got, twice) > 1e-2

    def test_the_loss(self, reference, cfg):
        loss_s, _ = _loss_and_grads(reference, cfg, "system")
        loss_r, _ = _loss_and_grads(reference, cfg, "reference")
        np.testing.assert_allclose(float(loss_s), float(loss_r), rtol=1e-5)

    @pytest.mark.parametrize("which", ["system", "checkpointed"])
    @pytest.mark.parametrize("top", TOP)
    def test_every_gradient(self, reference, cfg, top, which):
        """``jax.grad`` of the reference sums a tied weight's four uses by
        itself, as ``jax.grad`` of the system must."""
        _, gs = _loss_and_grads(reference, cfg, which)
        _, gr = _loss_and_grads(reference, cfg, "reference")
        flat_ref = dict(jax.tree_util.tree_flatten_with_path(
            gr["params"][top])[0])
        for path, g in jax.tree_util.tree_flatten_with_path(
                gs["params"][top])[0]:
            assert float(jnp.abs(g).max()) > 0, jax.tree_util.keystr(path)
            np.testing.assert_allclose(
                g, flat_ref[path], atol=2e-6, rtol=5e-4,
                err_msg=jax.tree_util.keystr(path))

    def test_checkpoint_on_and_off_are_one_function(self, reference, cfg):
        loss_off, g_off = _loss_and_grads(reference, cfg, "system")
        loss_on, g_on = _loss_and_grads(reference, cfg, "checkpointed")
        assert float(loss_on) == pytest.approx(float(loss_off), rel=1e-6)
        for a, b in zip(jax.tree_util.tree_leaves(g_on),
                        jax.tree_util.tree_leaves(g_off)):
            np.testing.assert_allclose(a, b, atol=1e-7, rtol=1e-5)

    def test_a_tied_weights_gradient_is_the_sum_of_its_passes(
            self, reference, cfg):
        """An UNTIED copy — a tree a pass, equal in value — through the
        reference's pass: the system's gradient by its one tree is the sum
        of the four trees' gradients; the embedding's comes from the first
        alone, the heads' from the last."""
        _, params = _system(reference, cfg)
        _, tied = _loss_and_grads(reference, cfg, "system")
        obs, batch = _obs(cfg), _batch(cfg)
        shape = _shape(cfg)

        def untied_loss(trees):
            with jax.default_matmul_precision("highest"):
                h = reference._dense(trees[0]["obs_embed"], obs)
                for p in trees:
                    h = reference._pass(p, h, shape, True, lambda a: a)
                return _impala_loss(*reference._heads(trees[-1], h), batch)

        per_pass = jax.jit(jax.grad(untied_loss))(
            [params["params"]] * S)
        for top in TOP:
            uses = (per_pass[:1] if top == "obs_embed" else
                    per_pass[-1:] if "head" in top else per_pass)
            summed = jax.tree_util.tree_map(
                lambda *g: sum(g), *(tree[top] for tree in uses))
            for a, b in zip(jax.tree_util.tree_leaves(tied["params"][top]),
                            jax.tree_util.tree_leaves(summed)):
                np.testing.assert_allclose(a, b, atol=2e-6, rtol=5e-4,
                                           err_msg=top)
            if top.startswith("block"):
                # ... and no single pass's is the whole of it
                one = jax.tree_util.tree_leaves(per_pass[0][top])
                whole = jax.tree_util.tree_leaves(tied["params"][top])
                assert max(float(jnp.abs(a - b).max())
                           for a, b in zip(one, whole)) > 1e-5

    def test_the_readout_row_is_the_full_forwards_row(self, reference, cfg):
        """Readout mode: every pass but the last runs all rows, the last
        pass's last layer the one row."""
        policy, params = _system(reference, cfg)
        window = np.asarray(_obs(cfg, batch=1)[0])
        logp_ref, v_ref = reference.forward(params, window[None], cfg)
        step = jax.jit(policy.step_window)
        for t in (1, 2, 9, T):
            act, aux = step(params, jax.random.PRNGKey(t),
                            jnp.asarray(window), t)
            np.testing.assert_allclose(float(aux["v"]),
                                       float(v_ref[0, t - 1]), atol=2e-5)
            np.testing.assert_allclose(
                float(aux["logp_a"]), float(logp_ref[0, t - 1, int(act)]),
                atol=2e-5)

    @pytest.mark.parametrize("prefilled", [0, 7])
    def test_cached_decode_is_the_full_forward(self, reference, cfg,
                                               prefilled):
        """A cache of ``S x L`` pairs, pass-major: pass ``s``, layer ``l``
        of position ``t`` attends what pass ``s``, layer ``l`` wrote for
        the positions up to ``t``. Stepped from empty, or after a prefill
        of the first rows, every position's value and log-probability are
        the reference's full forward's."""
        policy, params = _system(reference, cfg)
        window = np.asarray(_obs(cfg, batch=1)[0])
        logp_ref, v_ref = reference.forward(params, window[None], cfg)
        cache = policy.init_cache(T)
        assert len(cache) == S * L
        assert all(k.shape == v.shape == (1, T, 4 * 8) for k, v in cache)
        if prefilled:
            padded = np.zeros_like(window)
            padded[:prefilled] = window[:prefilled]
            cache = jax.jit(policy.prefill_cache)(
                params, cache, jnp.asarray(padded), prefilled)
        step = jax.jit(policy.step_cached)
        for t in range(prefilled, T):
            act, aux, cache = step(params, jax.random.PRNGKey(t), cache,
                                   window[t], t)
            np.testing.assert_allclose(float(aux["v"]), float(v_ref[0, t]),
                                       atol=2e-5, err_msg=f"t={t}")
            np.testing.assert_allclose(
                float(aux["logp_a"]), float(logp_ref[0, t, int(act)]),
                atol=2e-5, err_msg=f"t={t}")
        # the passes' states differ: pass 0 wrote the embedding's keys,
        # pass 1 the normed output of pass 0
        assert float(jnp.abs(cache[0][0] - cache[L][0]).max()) > 1e-3

    def test_a_cache_of_another_size_is_refused(self, reference, cfg):
        policy, params = _system(reference, cfg)
        short = policy.init_cache(T)[:L]
        with pytest.raises(ValueError, match=f"{S} passes of {L} layers"):
            policy.step_cached(params, jax.random.PRNGKey(0), short,
                               np.zeros(cfg["obs_dim"], np.float32), 0)

    def test_bfloat16_agrees_within_its_rounding(self, reference, cfg):
        policy, params = _system(reference, cfg, "bfloat16")
        obs = _obs(cfg)
        got = _outputs(policy, params, obs, cfg)
        want = reference.forward(params, obs, cfg)
        rounded = reference.forward(params, obs, cfg, operands="bfloat16")
        assert 1e-5 < _differs(got, want) < 0.3
        assert 1e-5 < _differs(rounded, want) < 0.3

    def test_a_program_without_the_keys_is_refused(self, reference, cfg,
                                                   monkeypatch):
        from relayrl_tpu.models import base

        monkeypatch.setattr(base, "ARCH_PASSTHROUGH_KEYS", tuple(
            k for k in base.ARCH_PASSTHROUGH_KEYS if k != "loop_steps"))
        with pytest.raises(SystemExit, match="loop_steps"):
            reference.program_kwargs(cfg)


class TestTheCounts:
    """``benchmark/flops_ouro.py``: a pass counted once and multiplied, the
    checkpoint's second forward not at all (``benchmark/tests/
    test_flops_ouro.py`` holds the same arithmetic outside tier-1)."""

    def test_one_application_a_token(self):
        from benchmark import flops_ouro

        pub = _published()
        # q, k, v, o: 2048 x 2048 each; the FFN's three 2048 x 5632
        matmul = 2 * (4 * 2048 * 2048 + 3 * 2048 * 5632)
        assert matmul == 2 * (51_388_416 - 4 * 2048) == 102_760_448
        scores = 4 * 2048 * (8192 * 8193 // 2) / 8192   # QK^T and PV
        assert flops_ouro.layer_fwd_flops_per_token(pub, 8192) == (
            matmul + scores)
        assert flops_ouro.applications(pub) == 32

    def test_an_update_counts_four_passes_and_no_recompute(self, reference):
        from benchmark import flops_ouro

        pub = _published()
        one = flops_ouro.layer_fwd_flops_per_token(pub, 8192)
        ends = 2 * 18 * 2048 + 2 * 2048 * 17
        assert flops_ouro.ouro_fwd_flops_per_token(pub, 8192) == (
            32 * one + ends)
        once = dict(pub, total_ut_steps=1)
        assert (flops_ouro.ouro_fwd_flops_per_token(pub, 8192) - ends) == 4 * (
            flops_ouro.ouro_fwd_flops_per_token(once, 8192) - ends)
        # forward + backward = 3 x forward: 4 x with the second forward
        # would read a third more
        per_update = 16_384 * reference.train_flops_per_sample(pub, 8192)
        assert per_update == 16_384 * 3 * (32 * one + ends)
        assert round(per_update / 1e12, 1) == 214.4     # ISSUE 50: "214"
        ops, nbytes = reference.flash_gqa_train_ops_bytes(pub, 2, 8192)
        assert ops == 32 * 6 * 2 * 2 * 16 * (8192 * 8193 // 2) * 128
        assert nbytes == 32 * 3 * 4 * 16 * (2 * 8192 * 128 * 2)


class TestTheLearner:
    KEYS = {"model_kind": "transformer_discrete", "d_model": 16,
            "n_layers": 2, "n_heads": 2, "max_seq_len": 8, "norm": "rms",
            "positions": "rope", "use_bias": False, "ffn": "swiglu",
            "norm_sandwich": True, "block_checkpoint": True}

    @pytest.mark.parametrize("loop_steps", [4, 1])
    def test_impala_builds_it_and_says_how_often_it_loops(
            self, tmp_path, loop_steps):
        """``build_policy`` -> ``build_algorithm("IMPALA")`` -> an update;
        the two gauges are set once at build, for a looped trunk alone."""
        from relayrl_tpu import telemetry
        from relayrl_tpu.algorithms import build_algorithm
        from relayrl_tpu.data.batching import TrajectoryBatch

        telemetry.reset_for_tests()
        telemetry.set_registry(telemetry.Registry(run_id="ouro-update"))
        algo = build_algorithm("IMPALA", obs_dim=4, act_dim=3,
                               env_dir=str(tmp_path), traj_per_epoch=1,
                               loop_steps=loop_steps, **self.KEYS)
        assert algo.policy.arch["loop_steps"] == loop_steps
        snap = {m["name"]: m["value"]
                for m in telemetry.get_registry().snapshot()["metrics"]
                if m["kind"] == "gauge"}
        telemetry.reset_for_tests()
        if loop_steps > 1:
            assert snap["relayrl_loop_steps"] == 4
            assert snap["relayrl_layer_applications"] == 8
        else:
            assert not [name for name in snap if "loop" in name]
        batch = TrajectoryBatch.zeros(1, 8, 4, 3, True)
        before = jax.tree_util.tree_leaves(algo.state.params)[0].copy()
        state, metrics = algo._update(algo.state, batch)
        assert np.isfinite(float(metrics["LossTotal"]))
        assert before.shape == jax.tree_util.tree_leaves(
            state.params)[0].shape
