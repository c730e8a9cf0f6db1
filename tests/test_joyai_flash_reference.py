"""The JoyAI-LLM-Flash-shaped trunk against the benchmark's plain reference.

``benchmark/reference/joyai-flash-policy.py`` is written from the model's
equations in plain ``jax.numpy`` — latent attention with a low-rank query
path, its keys materialised a head, the rotated lanes paired ``(2i, 2i + 1)``
as published — and reads the parameter tree as data; it shares no code with
``relayrl_tpu/models`` or ``ops/flash.py``. On the chip the harness compares
the two at the published widths (``benchmark/configs/joyai-flash-policy.json``'s
tolerance); here the same comparison runs at tiny widths on the CPU over a
dense layer and three expert layers, every one a rotary latent layer, a held
range that is not the first. Full, readout-row and cached modes.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from relayrl_tpu.models import build_policy
from relayrl_tpu.models.layers import mla
# the reference tests share their plumbing: a file loaded by its path, the
# system's outputs for all actions, IMPALA's loss from either side's
from test_lfm2_reference import _all_logp_v, _by_path, _impala_loss

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T = 32


@pytest.fixture(scope="module")
def reference():
    return _by_path("benchmark/reference/joyai-flash-policy.py")


def _published():
    with open(os.path.join(
            REPO, "benchmark/configs/joyai-flash-policy.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    cfg = _published()
    # tiny widths; every mechanism of the published trunk: 4 heads of 8 + 4
    # (q / k 12 wide, v 8) over a query rank of 20 and a latent row of 12;
    # theta small enough that 32 positions turn the slowest pair visibly; a
    # dense SwiGLU FFN of 40; experts 4-7 of 16 held, top-3, a shared expert
    cfg.update(hidden_size=24, num_attention_heads=4, q_lora_rank=20,
               kv_lora_rank=12, qk_nope_head_dim=8, qk_rope_head_dim=4,
               v_head_dim=8, rope_theta=100.0, intermediate_size=40,
               moe_intermediate_size=12, n_routed_experts=4,
               held_experts_first=4, num_experts_per_tok=3,
               num_hidden_layers=4, published={"n_routed_experts": 16},
               positions_as_run=T, attention="dense")
    return cfg


_BUILT = {}  # one policy (and its compiled functions) a distinct arch


def _system(reference, cfg, precision, seed=0, **over):
    key = (precision, seed, json.dumps(over, sort_keys=True))
    if key not in _BUILT:
        kwargs = {**reference.program_kwargs(cfg), **over}
        arch = {"kind": kwargs.pop("model_kind"), "obs_dim": cfg["obs_dim"],
                "act_dim": cfg["act_dim"], "has_critic": True,
                "precision": precision, **kwargs}
        policy = build_policy(arch)
        _BUILT[key] = policy, jax.jit(policy.init_params)(
            jax.random.PRNGKey(seed))
    return _BUILT[key]


def _outputs(policy, params, obs, act_dim):
    return jax.jit(lambda p, o: _all_logp_v(policy, p, o, act_dim))(params,
                                                                    obs)


def _obs(cfg, seed=1, batch=2):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(
        (batch, T, cfg["obs_dim"])), jnp.float32)


def _batch(cfg, seed=2):
    rng = np.random.default_rng(seed)
    shape = (2, T)
    return {"act": jnp.asarray(rng.integers(0, cfg["act_dim"], shape)),
            "rew": jnp.asarray((rng.random(shape) < 0.2), jnp.float32),
            "valid": jnp.ones(shape, jnp.float32),
            "logp": jnp.full(shape, -np.log(cfg["act_dim"]), jnp.float32),
            "last_val": jnp.zeros((2,), jnp.float32)}


def _differs(a, b):
    return max(float(jnp.abs(a[0] - b[0]).max()),
               float(jnp.abs(a[1] - b[1]).max()))


class TestSystemAgainstReference:
    def test_the_trunk_is_what_the_configuration_says(self, reference, cfg):
        kwargs = reference.program_kwargs(cfg)
        assert kwargs["layer_types"] == ["latent_attention"] * 4
        assert kwargs["moe_dense_layers"] == 1
        assert (kwargs["positions"], kwargs["rope_theta"],
                kwargs["rope_interleave"]) == ("rope", 100.0, True)
        _, params = _system(reference, cfg, "float32")
        p = params["params"]
        assert "pos_embed" not in p
        first = p["block_0"]
        assert set(first) == {"ln_attn", "q_a", "q_a_norm", "q_b", "kv_a",
                              "kv_a_norm", "kv_b", "attn_out", "ln_mlp",
                              "mlp_gate", "mlp_up", "mlp_down"}
        assert first["q_a"]["kernel"].shape == (24, 20)     # the low rank
        assert first["q_a_norm"]["scale"].shape == (20,)
        assert first["q_b"]["kernel"].shape == (20, 4 * 12)
        assert first["kv_a"]["kernel"].shape == (24, 12 + 4)  # latent | k_pe
        assert first["kv_a_norm"]["scale"].shape == (12,)
        assert first["kv_b"]["kernel"].shape == (12, 4 * 16)  # k_nope | v
        assert first["attn_out"]["kernel"].shape == (4 * 8, 24)
        assert first["mlp_up"]["kernel"].shape == (24, 40)
        for i in (1, 2, 3):
            assert set(p[f"block_{i}"]) == (set(first) - {
                "mlp_gate", "mlp_up", "mlp_down"}) | {"moe"}
        moe = p["block_1"]["moe"]
        assert set(moe) == {"moe_gate", "moe_expert_bias", "moe_w_gate",
                            "moe_w_up", "moe_w_down", "moe_shared_gate",
                            "moe_shared_up", "moe_shared_down"}
        assert moe["moe_w_up"].shape == (4, 24, 12)     # 4 held of 16
        assert moe["moe_gate"]["kernel"].shape == (24, 16)
        assert not [path for path, _ in
                    jax.tree_util.tree_flatten_with_path(p["block_1"])[0]
                    if jax.tree_util.keystr(path).endswith("['bias']")]

    def test_the_published_count_is_the_programs(self, reference):
        """The file's ``parameters_as_run`` is the sum of the program's own
        parameter tree at the published widths (shapes only), a layer's
        share ISSUE 62's."""
        published = _published()
        kwargs = reference.program_kwargs(published)
        arch = {"kind": kwargs.pop("model_kind"),
                "obs_dim": published["obs_dim"],
                "act_dim": published["act_dim"], "has_critic": True,
                "precision": "bfloat16", **kwargs}
        shapes = jax.eval_shape(build_policy(arch).init_params,
                                jax.random.PRNGKey(0))
        sizes = {k: sum(x.size for x in jax.tree_util.tree_leaves(v))
                 for k, v in shapes["params"].items()}
        assert sum(sizes.values()) == published["parameters_as_run"]
        latent = 26_347_520                             # ISSUE 62's count
        assert sizes["block_0"] == latent + 3 * 2048 * 7168 + 2 * 2048
        held = 2048 * 256 + 256 + 17 * 3 * 2048 * 768
        assert all(sizes[f"block_{i}"] == latent + held + 2 * 2048
                   for i in range(1, 6))

    # float32: both sides compute the same sums in another order (online
    # softmax against a dense one, the half-split rotation of de-interleaved
    # lanes against the pairs turned in place): the largest difference.
    # bfloat16: the system rounds the operands of its projections, attention
    # and experts to 8 bits of mantissa, four layers deep, and at these
    # widths a token whose 3rd and 4th scores tie within that error moves
    # its whole expert output, so the bulk of the tokens is compared.
    @pytest.mark.parametrize("precision,over_tokens,atol", [
        ("float32", jnp.max, 1e-4), ("bfloat16", jnp.median, 0.06)])
    def test_log_probabilities_and_values(self, reference, cfg, precision,
                                          over_tokens, atol):
        policy, params = _system(reference, cfg, precision)
        obs = _obs(cfg)
        logp, v = _outputs(policy, params, obs, cfg["act_dim"])
        logp_ref, v_ref = reference.forward(params, obs, cfg)
        assert float(over_tokens(jnp.abs(logp - logp_ref).max(-1))) < atol
        assert float(over_tokens(jnp.abs(v - v_ref))) < atol

    def test_the_blockwise_form_agrees(self, reference, cfg):
        """q and k 12 wide, v 8, through the blockwise form ("flash"
        resolves to it off a TPU)."""
        policy, params = _system(reference, cfg, "float32",
                                 attention="flash", attention_block=8)
        obs = _obs(cfg)
        got = _outputs(policy, params, obs, cfg["act_dim"])
        assert _differs(got, reference.forward(params, obs, cfg)) < 1e-4
        assert policy.attention_backends[(T, 12, "float32")] == "blockwise"

    @pytest.mark.parametrize("checkpoint", [False, True])
    def test_impala_loss_and_every_gradient(self, reference, cfg,
                                            checkpoint):
        """Under the block checkpoint too: the rotation and the query's
        norm are made again in the backward."""
        policy, params = _system(reference, cfg, "float32",
                                 block_checkpoint=checkpoint)
        obs, batch = _obs(cfg), _batch(cfg)
        sys_loss = lambda p: _impala_loss(
            *_all_logp_v(policy, p, obs, cfg["act_dim"]), batch)
        ref_loss = lambda p: _impala_loss(
            *reference.forward(p, obs, cfg), batch)
        (ls, gs), (lr, gr) = (jax.jit(jax.value_and_grad(f))(params)
                              for f in (sys_loss, ref_loss))
        np.testing.assert_allclose(float(ls), float(lr), atol=2e-5)
        flat_ref = dict(jax.tree_util.tree_flatten_with_path(gr)[0])
        for path, g in jax.tree_util.tree_flatten_with_path(gs)[0]:
            name = jax.tree_util.keystr(path)
            np.testing.assert_allclose(g, flat_ref[path], atol=2e-4,
                                       rtol=5e-4, err_msg=name)
            if "moe_expert_bias" not in name:   # the choice's: no gradient
                assert float(jnp.abs(g).max()) > 0, name

    def test_the_readout_row_is_the_full_forwards_row(self, reference, cfg):
        policy, params = _system(reference, cfg, "float32")
        window = np.asarray(_obs(cfg, batch=1)[0])
        logp_ref, v_ref = reference.forward(params, window[None], cfg)
        step_window = jax.jit(policy.step_window)
        for t in (9, T):
            act, aux = step_window(params, jax.random.PRNGKey(t),
                                   jnp.asarray(window), t)
            np.testing.assert_allclose(float(aux["v"]),
                                       float(v_ref[0, t - 1]), atol=3e-5)
            np.testing.assert_allclose(
                float(aux["logp_a"]), float(logp_ref[0, t - 1, int(act)]),
                atol=3e-5)

    def test_a_final_latent_layers_readout_row(self, reference, cfg):
        """A dense trunk ends in a latent layer that runs for the one row
        alone: the keys rotated over every row, ONE query at its own
        position."""
        kwargs = {k: v for k, v in reference.program_kwargs(cfg).items()
                  if not k.startswith("moe_")}
        kwargs["model_kind"] = "transformer_discrete"
        arch = {"obs_dim": cfg["obs_dim"], "act_dim": cfg["act_dim"],
                "has_critic": True, "precision": "float32", **kwargs}
        arch["kind"] = arch.pop("model_kind")
        policy = build_policy(arch)
        params = jax.jit(policy.init_params)(jax.random.PRNGKey(0))
        obs = _obs(cfg, batch=1)
        _, _, v = jax.jit(policy.evaluate)(params, obs,
                                           jnp.zeros((1, T), jnp.int32))
        step_window = jax.jit(policy.step_window)
        for t in (2, T):
            _, aux = step_window(params, jax.random.PRNGKey(t), obs[0], t)
            np.testing.assert_allclose(float(aux["v"]), float(v[0, t - 1]),
                                       atol=3e-5)

    def test_cached_decode_is_the_full_forward_at_every_step(
            self, reference, cfg):
        """32 steps through the ``(c, k_pe)`` cache, 12 + 4 numbers a token:
        ``k_pe`` goes in ALREADY rotated at its own position, so a step
        turns the new row and its query alone — every step's value and
        log-probability equal the reference's full forward at that row."""
        policy, params = _system(reference, cfg, "float32")
        window = np.asarray(_obs(cfg, batch=1)[0])
        logp_ref, v_ref = reference.forward(params, window[None], cfg)
        cache = policy.init_cache(T)
        for c in cache:
            assert [a.shape for a in c] == [(1, T, 12), (1, T, 4)]
        step = jax.jit(policy.step_cached)      # one program, 32 positions
        for t in range(T):
            act, aux, cache = step(
                params, jax.random.PRNGKey(t), cache, window[t], t)
            np.testing.assert_allclose(float(aux["v"]), float(v_ref[0, t]),
                                       atol=3e-5, err_msg=f"t={t}")
            np.testing.assert_allclose(
                float(aux["logp_a"]), float(logp_ref[0, t, int(act)]),
                atol=3e-5, err_msg=f"t={t}")

    @pytest.mark.parametrize("t0", [19])
    def test_a_prefilled_cache_continues_as_the_full_forward(
            self, reference, cfg, t0):
        """Prefill ``t0`` real rows of a zero-padded window (row j rotated
        at j), then decode (the new row at ``t``)."""
        policy, params = _system(reference, cfg, "float32")
        window = np.asarray(_obs(cfg, batch=1)[0])
        _, v_ref = reference.forward(params, window[None], cfg)
        padded = window.copy()
        padded[t0:] = 0.0
        cache = policy.prefill_cache(params, policy.init_cache(T),
                                     jnp.asarray(padded), t0)
        step = jax.jit(policy.step_cached)
        for t in range(t0, T):
            _, aux, cache = step(
                params, jax.random.PRNGKey(t), cache, window[t], t)
            np.testing.assert_allclose(float(aux["v"]), float(v_ref[0, t]),
                                       atol=3e-5, err_msg=f"t={t}")

    @pytest.mark.parametrize("wrong", [
        {"no_rope": True},              # no lane turns
        {"half_split": True},           # the other pairing
        {"no_q_norm": True},            # W_qb (W_qa u) without the RMSNorm
        {"scale_128": True},            # scores over sqrt(nope)
        {"top_k": 2},                   # one expert a token fewer
    ])
    def test_a_wrong_reference_is_told_apart(self, reference, cfg, wrong):
        policy, params = _system(reference, cfg, "float32")
        obs = _obs(cfg)
        got = _outputs(policy, params, obs, cfg["act_dim"])
        assert _differs(got, reference.forward(params, obs, cfg,
                                               wrong=wrong)) > 1e-3

    def test_the_other_pairing_is_another_function_of_the_same_tree(
            self, reference, cfg):
        """Interleaved and half-split differ on one parameter tree, and the
        program is the interleaved one where the arch says so and the
        half-split one where it does not: which runs is checked."""
        obs = _obs(cfg)
        policy, params = _system(reference, cfg, "float32")
        other, _ = _system(reference, cfg, "float32", rope_interleave=False)
        published = reference.forward(params, obs, cfg)
        halves = reference.forward(params, obs, cfg,
                                   wrong={"half_split": True})
        assert _differs(published, halves) > 1e-3
        got = _outputs(policy, params, obs, cfg["act_dim"])
        got_other = _outputs(other, params, obs, cfg["act_dim"])
        assert _differs(got, published) < 1e-4 < _differs(got, halves)
        assert _differs(got_other, halves) < 1e-4 < _differs(got_other,
                                                             published)

    @pytest.mark.parametrize("wrong", [
        {"moe_routed_scaling": 1.0}, {"rope_theta": 10000.0},
        {"positions": "none"}])
    def test_a_different_model_is_told_apart(self, reference, cfg, wrong):
        _, params = _system(reference, cfg, "float32")
        other, _ = _system(reference, cfg, "float32", **wrong)
        got = _outputs(other, params, _obs(cfg), cfg["act_dim"])
        assert _differs(got, reference.forward(params, _obs(cfg),
                                               cfg)) > 1e-3

    # ``benchmark/tests/controls_joyai.py`` is how the controls are read on
    # the chip: each wrong reference planted in the program's place and
    # handed to the two functions that decide the cell's ``correct``. Here
    # the same ``judge`` at tiny float32 widths, the limits a little above
    # what the float32 system itself reads (1e-4, above).
    @pytest.fixture(scope="class")
    def judged(self, reference, cfg):
        import types

        controls = _by_path("benchmark/tests/controls_joyai.py")
        policy, params = _system(reference, cfg, "float32")
        tight = {"logp_rel": 1e-3, "value_rel": 1e-3, "routed": {
            "quantile": 0.9, "logp_rel": 3e-4, "value_rel": 3e-4}}
        run = types.SimpleNamespace(
            config={**cfg, "tolerance": tight}, reference=reference,
            notes={}, checks={})
        run.check = lambda name, ok, detail="": run.checks.update(
            {name: bool(ok)})
        obs = np.asarray(_obs(cfg))
        from benchmark import harness
        from benchmark.drivers import update_routed

        harness.reference_check(run, policy, params, obs)
        update_routed.routed_reference_check(run, policy, params, obs)
        tiny = {**controls.CONTROLS, "top7": {"wrong": {"top_k": 2}}}
        return controls, run.checks, controls._kimi().judge(
            run, params, obs, tiny)

    def test_the_system_passes_the_limits_the_controls_are_held_to(
            self, judged):
        _, own, got = judged
        assert own == {"reference": True, "reference_routed": True}
        assert not got["exact"]["refused"]
        assert got["exact"]["reference_routed"]["rel_dlogp"] == 0.0

    @pytest.mark.parametrize("name", [
        "no_rope", "half_split", "no_q_norm", "scale_128", "top7", "bf16",
        "float8_e4m3fn", "float8_e5m2"])
    def test_a_planted_control_is_refused_by_the_cells_own_checks(
            self, judged, name):
        controls, _, got = judged
        assert set(got) == set(controls.CONTROLS)
        assert set(controls.HELD) < set(controls.CONTROLS)
        assert got[name]["refused"]
        assert not got[name]["checks"]["reference_routed"]

    def test_the_reference_is_float32_at_highest_and_imports_no_model(self):
        with open(os.path.join(
                REPO, "benchmark/reference/joyai-flash-policy.py")) as f:
            text = f.read()
        code = text.split('"""', 2)[2]
        assert "relayrl_tpu.models.transformer" not in code
        assert "relayrl_tpu.models.moe" not in code
        assert "relayrl_tpu.models.layers" not in code
        assert "relayrl_tpu.ops" not in code
        assert "flax" not in code
        assert 'jax.default_matmul_precision("highest")' in code
        assert "pairs[..., 0], pairs[..., 1]" in code   # (2i, 2i + 1)

    def test_a_program_without_the_keys_is_refused(self, reference, cfg,
                                                   monkeypatch):
        from relayrl_tpu.models import base

        monkeypatch.setattr(base, "ARCH_PASSTHROUGH_KEYS", tuple(
            k for k in base.ARCH_PASSTHROUGH_KEYS
            if k not in ("q_lora_rank", "rope_interleave")))
        with pytest.raises(SystemExit, match="REFUSED.*q_lora_rank"):
            reference.program_kwargs(cfg)


class TestTheRotation:
    """``mla._rotated`` alone: the last ``qk_rope_head_dim`` lanes of its
    rows, at their absolute positions."""

    CFG = {"rope_theta": 100.0, "qk_rope_head_dim": 8,
           "rope_interleave": True}

    def _rows(self, seed, width, heads=3, length=16):
        return jnp.asarray(np.random.default_rng(seed).standard_normal(
            (2, length, heads, width)), jnp.float32)

    @pytest.mark.parametrize("interleave", [True, False])
    @pytest.mark.parametrize("start", [1, 7, 200])
    def test_scores_depend_on_the_distance_alone(self, interleave, start):
        """``R_i q . R_j k`` is a function of ``i - j``: the same rows at
        positions ``start + j`` give the scores they give from 0."""
        cfg = {**self.CFG, "rope_interleave": interleave}
        q, k = self._rows(0, 8), self._rows(1, 8, heads=1)[:, :, 0]

        def scores(at):
            return jnp.einsum("bqhd,bkd->bhqk", mla._rotated(cfg, q, at),
                              mla._rotated(cfg, k, at))

        np.testing.assert_allclose(scores(start), scores(0), atol=1e-4)
        # and a position is seen: the unrotated rows score otherwise
        plain = jnp.einsum("bqhd,bkd->bhqk", q, k)
        assert float(jnp.abs(scores(0) - plain).max()) > 1e-2

    def test_only_the_last_lanes_turn_and_row_zero_stands(self):
        q = self._rows(2, 20)
        got = mla._rotated(self.CFG, q, 0)
        np.testing.assert_array_equal(got[..., :12], q[..., :12])
        # position 0 turns nothing: the lanes come back de-interleaved
        np.testing.assert_allclose(
            got[:, 0, :, 12:], jnp.concatenate(
                [q[:, 0, :, 12::2], q[:, 0, :, 13::2]], -1), atol=1e-6)
        assert float(jnp.abs(got[:, 1:, :, 12:]
                             - q[:, 1:, :, 12:]).max()) > 1e-2

    def test_a_layer_that_rotates_nothing_gets_its_rows_back(self):
        q = self._rows(3, 20)
        assert mla._rotated({**self.CFG, "rope_theta": None}, q, 5) is q

    def test_the_interleaved_pairs_are_the_published_ones(self, reference):
        """Against the reference's rotation in place: equal up to the ONE
        de-interleave both q and k take, so every dot product agrees."""
        q, k = self._rows(4, 8), self._rows(5, 8, heads=1)
        want = jnp.einsum("bqhd,bkd->bhqk", reference._rope(q, 100.0, True),
                          reference._rope(k, 100.0, True)[:, :, 0])
        got = jnp.einsum("bqhd,bkd->bhqk", mla._rotated(self.CFG, q, 0),
                         mla._rotated(self.CFG, k[:, :, 0], 0))
        np.testing.assert_allclose(got, want, atol=2e-5)
        halves = jnp.einsum(
            "bqhd,bkd->bhqk", reference._rope(q, 100.0, False),
            reference._rope(k, 100.0, False)[:, :, 0])
        assert float(jnp.abs(want - halves).max()) > 1e-2


class TestTheSharesAddUp:
    """Sixteen chips share a layer, experts divided: the sixteen shares'
    expert-layer outputs, the shared expert counted ONCE, sum to the UNCUT
    reference's layer output."""

    E, K, D, FF, CHIPS = 32, 8, 24, 12, 16

    def _layer(self, held):
        from relayrl_tpu.models.moe import MoEMLP

        return MoEMLP(self.D, self.FF, self.E, self.K, jnp.float32,
                      norm_topk_prob=True, ffn="swiglu", use_bias=False,
                      router="sigmoid", expert_bias=True, held=held,
                      routed_scaling=2.5, shared_d_ff=self.FF)

    @pytest.mark.parametrize("seed", [0, 1])
    def test_against_the_uncut_reference(self, reference, seed):
        x = jnp.asarray(np.random.default_rng(seed).standard_normal(
            (2, 24, self.D)), jnp.float32)
        whole = self._layer(None).init(jax.random.PRNGKey(seed), x)["params"]
        each = self.E // self.CHIPS
        # the reference's expert layer given every expert, less its
        # residual; its norm at a unit scale, so the program's layer (which
        # has none of its own) is fed the normed rows
        eps = 1e-6
        unit = {"scale": jnp.ones((self.D,), jnp.float32)}
        h = reference._rms_norm(unit, x, eps)
        blk = {"ln_mlp": unit, "moe": whole}
        as_run = {"top_k": self.K}
        with jax.default_matmul_precision("highest"):
            uncut = reference._experts(blk, x, eps, 2.5, 0, self.E, None,
                                       as_run) - x
            shared = reference._swiglu(
                h, whole["moe_shared_gate"]["kernel"],
                whole["moe_shared_up"]["kernel"],
                whole["moe_shared_down"]["kernel"], lambda a: a)
        parts = [self._layer((each * chip, each)).apply(
            {"params": {**whole, **{
                name: whole[name][each * chip:each * (chip + 1)]
                for name in ("moe_w_gate", "moe_w_up", "moe_w_down")}}}, h)
            for chip in range(self.CHIPS)]
        # every chip computes the shared expert: counted once
        total = sum(parts) - (self.CHIPS - 1) * shared
        np.testing.assert_allclose(total, uncut, atol=3e-5, rtol=1e-5)
        # and no share is the whole: the cut is real
        assert float(jnp.abs(parts[0] - uncut).max()) > 1e-3


class TestShapeArithmetic:
    def test_published_widths_in_the_configuration_file(self):
        c = _published()
        published = {
            "hidden_size": 2048, "num_attention_heads": 32,
            "num_key_value_heads": 32, "q_lora_rank": 1536,
            "kv_lora_rank": 512, "qk_nope_head_dim": 128,
            "qk_rope_head_dim": 64, "qk_head_dim": 192, "v_head_dim": 128,
            "head_dim": 64, "intermediate_size": 7168,
            "moe_intermediate_size": 768, "num_experts_per_tok": 8,
            "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
            "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
            "norm_topk_prob": True, "n_shared_experts": 1,
            "first_k_dense_replace": 1, "rope_theta": 32000000,
            "rope_interleave": True, "rope_scaling": None,
            "rms_norm_eps": 1e-6, "max_position_embeddings": 131072,
            "vocab_size": 129280, "num_nextn_predict_layers": 1,
            "model_type": "joyai_llm_flash"}
        assert {k: c[k] for k in published} == published
        assert c["reduced"] == ["num_hidden_layers", "n_routed_experts"]
        assert (c["num_hidden_layers"], c["n_routed_experts"]) == (6, 16)
        assert c["published"] == {"num_hidden_layers": 40,
                                  "n_routed_experts": 256}
        # the floors: a dense layer and at least four expert layers, at
        # least 8 held
        assert c["num_hidden_layers"] - c["first_k_dense_replace"] >= 4
        assert c["n_routed_experts"] >= 8
        assert "16 chips share each layer" in c["deployment"]
        assert "experts 0-15 of 256" in c["deployment"]
        assert "multi-token prediction" in c["departures"]
        assert {"depth", "experts", "vocabulary", "decode"} < set(
            c["departures"])

    def test_the_cell_and_its_lists(self):
        with open(os.path.join(REPO, "BENCHMARK.json")) as f:
            bench = json.load(f)
        cell = "joyai-flash-policy.update"
        assert bench["workloads"][-1] == {
            "name": cell, "config": "joyai-flash-policy",
            "traffic": "impala-seq16k-trace8-batch", "chips": 1,
            "why": bench["workloads"][-1]["why"]}
        assert bench["configs"][-1]["reduced"] == [
            "num_hidden_layers", "n_routed_experts"]
        assert bench["end_to_end"][0]["workloads"][-1] == cell
        mine = [m["name"] for m in bench["per_layer"]
                if cell in m.get("workloads", ())]
        kimi = [m["name"] for m in bench["per_layer"]
                if "kimi-linear-policy.update" in m.get("workloads", ())]
        assert mine == [n for n in kimi if not n.startswith("kda_")] + [
            "latent_rope_ms"]
        assert len(mine) == 31
        assert bench["per_layer"][-1] == {
            "name": "latent_rope_ms", "unit": "ms", "better": "lower",
            "source": "device_trace", "layer": "trunk",
            "moves": "train_samples_per_s", "workloads": [cell]}
