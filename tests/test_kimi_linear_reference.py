"""The Kimi-Linear-shaped trunk against the benchmark's plain reference.

``benchmark/reference/kimi-linear-policy.py`` is written from the model's
equations in plain ``jax.numpy`` — the KDA state equation one token at a
time, latent attention with its keys materialised a head — and reads the
parameter tree as data; it shares no code with ``relayrl_tpu/models``,
``ops/kda.py`` or ``ops/flash.py``. On the chip the harness compares the two
at the published widths (``benchmark/configs/kimi-linear-policy.json``'s
tolerance); here the same comparison runs at tiny widths on the CPU over the
published pattern's first five layers — KDA with the dense FFN, KDA, KDA, MLA,
KDA with expert layers, the rule crossing four chunks (eight sub-chunks), a
held range that is not the first. Full, readout-row and cached modes.
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
T = 32
KINDS = ["kda", "kda", "kda", "latent_attention", "kda"]


@pytest.fixture(scope="module")
def reference():
    return _by_path("benchmark/reference/kimi-linear-policy.py")


def _published():
    with open(os.path.join(
            REPO, "benchmark/configs/kimi-linear-policy.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    cfg = _published()
    # tiny widths; every mechanism of the published trunk: 4 KDA heads of 8
    # (their low-rank paths 8 wide inside), chunks of 8 (four a sequence; sub-chunks
    # of 8); 4 latent-attention heads of 8 + 4 over a latent row of 12 (q / k
    # 12 wide, v 8); a dense SwiGLU FFN of 40; experts 4-7 of 16 held,
    # top-3, an ungated shared expert
    cfg.update(hidden_size=24, num_attention_heads=4, kv_lora_rank=12,
               qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
               intermediate_size=40, moe_intermediate_size=12,
               num_experts=4, held_experts_first=4, num_experts_per_token=3,
               num_experts_per_tok=3, kda_chunk=8,
               published={"num_experts": 16}, positions_as_run=T,
               attention="dense")
    cfg["linear_attn_config"] = {**cfg["linear_attn_config"], "num_heads": 4,
                                 "head_dim": 8}
    return cfg


_BUILT = {}  # one policy (and its compiled functions) a distinct arch


def _program(reference, cfg, precision, **over):
    """The policy alone: a case that runs another program on the module's
    one tree seeds no tree of its own."""
    kwargs = {**reference.program_kwargs(cfg), **over}
    arch = {"kind": kwargs.pop("model_kind"), "obs_dim": cfg["obs_dim"],
            "act_dim": cfg["act_dim"], "has_critic": True,
            "precision": precision, **kwargs}
    return build_policy(arch)


def _system(reference, cfg, precision, seed=0, **over):
    key = (precision, seed, json.dumps(over, sort_keys=True))
    if key not in _BUILT:
        policy = _program(reference, cfg, precision, **over)
        # (one program: op by op the init costs the suite's clock a minute)
        _BUILT[key] = policy, jax.jit(policy.init_params)(
            jax.random.PRNGKey(seed))
    return _BUILT[key]


def _outputs(policy, params, obs, act_dim):
    """``_all_logp_v`` as one program (op by op it costs the suite's clock
    half a minute a call)."""
    return jax.jit(lambda p, o: _all_logp_v(policy, p, o, act_dim))(params,
                                                                    obs)

@pytest.fixture(scope="module")
def got(reference, cfg):
    """The float32 system's outputs on ``_obs(cfg)``, computed once."""
    return _outputs(*_system(reference, cfg, "float32"), _obs(cfg),
                    cfg["act_dim"])


@pytest.fixture(scope="module")
def want(reference, cfg):
    """The reference's, from the same tree and rows."""
    _, params = _system(reference, cfg, "float32")
    return reference.forward(params, _obs(cfg), cfg)


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
        assert kwargs["layer_types"] == KINDS
        assert kwargs["moe_dense_layers"] == 1
        _, params = _system(reference, cfg, "float32")
        p = params["params"]
        assert "pos_embed" not in p
        first = p["block_0"]
        assert set(first) == {
            "ln_attn", "kda_in_qkv", "kda_in_beta", "kda_f_down", "kda_f_up",
            "kda_g_down", "kda_g_up", "kda_g_bias", "kda_conv_w",
            "kda_dt_bias", "kda_A_log", "kda_norm", "kda_out", "ln_mlp",
            "mlp_gate", "mlp_up", "mlp_down"}
        assert first["kda_in_qkv"].shape == (24, 96)    # q | k | v, 32 each
        assert first["kda_in_beta"].shape == (24, 4)    # a scalar a head
        assert first["kda_f_down"].shape == (24, 8)     # a head wide inside
        assert first["kda_f_up"].shape == (8, 32)       # a decay a LANE
        assert first["kda_dt_bias"].shape == (32,)
        assert first["kda_A_log"].shape == (4,)
        assert first["kda_conv_w"].shape == (4, 96)     # q, k and v, no bias
        assert first["kda_norm"].shape == (8,)          # one head's width
        assert first["mlp_up"]["kernel"].shape == (24, 40)
        assert set(p["block_1"]) == (set(first) - {
            "mlp_gate", "mlp_up", "mlp_down"}) | {"moe"}
        mla = p["block_3"]
        assert set(mla) == {"ln_attn", "q_proj", "kv_a", "kv_a_norm",
                            "kv_b", "attn_out", "ln_mlp", "moe"}
        assert mla["q_proj"]["kernel"].shape == (24, 4 * 12)
        assert mla["kv_a"]["kernel"].shape == (24, 12 + 4)  # latent | k_pe
        assert mla["kv_a_norm"]["scale"].shape == (12,)
        assert mla["kv_b"]["kernel"].shape == (12, 4 * 16)  # k_nope | v
        assert mla["attn_out"]["kernel"].shape == (4 * 8, 24)
        moe = mla["moe"]
        assert set(moe) == {"moe_gate", "moe_expert_bias", "moe_w_gate",
                            "moe_w_up", "moe_w_down", "moe_shared_gate",
                            "moe_shared_up", "moe_shared_down"}
        assert moe["moe_w_up"].shape == (4, 24, 12)     # 4 held of 16
        assert moe["moe_gate"]["kernel"].shape == (24, 16)
        assert not [path for path, _ in
                    jax.tree_util.tree_flatten_with_path(mla)[0]
                    if jax.tree_util.keystr(path).endswith("['bias']")]

    def test_the_published_count_is_the_programs(self, reference):
        """The file's ``parameters_as_run`` is the sum of the program's own
        parameter tree at the published widths (shapes only)."""
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
        kda = sizes["block_0"] - 3 * 2304 * 9216 - 2 * 2304
        assert kda == 39_518_368                        # ISSUE 55's count
        held = 2304 * 256 + 256 + 9 * 3 * 2304 * 1024
        assert sizes["block_1"] == kda + held + 2 * 2304
        assert sizes["block_3"] == 29_114_880 + held + 2 * 2304

    # float32: both sides compute the same sums in another order (the
    # chunked rule against the step-by-step one, online softmax against a
    # dense one): the largest difference. bfloat16: the system rounds the
    # operands of its projections, rules, attention and experts to 8 bits of
    # mantissa, five layers deep, and at these widths a token whose 3rd and
    # 4th scores tie within that error moves its whole expert output, so
    # the bulk of the tokens is compared: their median.
    @pytest.mark.parametrize("precision,over_tokens,atol", [
        ("float32", jnp.max, 1e-4), ("bfloat16", jnp.median, 0.06)])
    def test_log_probabilities_and_values(self, reference, cfg, got, want,
                                          precision, over_tokens, atol):
        policy, params = _system(reference, cfg, precision)
        if precision != "float32":
            obs = _obs(cfg)
            got = _outputs(policy, params, obs, cfg["act_dim"])
            want = reference.forward(params, obs, cfg)
        (logp, v), (logp_ref, v_ref) = got, want
        assert float(over_tokens(jnp.abs(logp - logp_ref).max(-1))) < atol
        assert float(over_tokens(jnp.abs(v - v_ref))) < atol
        # the record of what the rule ran as: plain XLA off a TPU, under a
        # key of its own shape (``gdn``'s record stays empty)
        assert policy.gdn_backends == {}
        assert list(policy.kda_backends.values()) == ["kda_xla"]
        (rows, heads, key_dim, value_dim, _), = policy.kda_backends
        linear = cfg["linear_attn_config"]
        assert (rows, heads, key_dim, value_dim) == (
            T, linear["num_heads"], linear["head_dim"], linear["head_dim"])

    def test_the_blockwise_form_agrees(self, reference, cfg):
        """q and k 12 wide, v 8, through the blockwise form ("flash"
        resolves to it off a TPU)."""
        policy, params = _system(reference, cfg, "float32",
                                 attention="flash", attention_block=8)
        obs = _obs(cfg)
        got = _outputs(policy, params, obs, cfg["act_dim"])
        assert _differs(got, reference.forward(params, obs, cfg)) < 1e-4
        assert policy.attention_backends[(T, 12, "float32")] == "blockwise"

    def test_impala_loss_and_every_gradient(self, reference, cfg):
        policy, params = _system(reference, cfg, "float32")
        obs, batch = _obs(cfg), _batch(cfg)
        sys_loss = lambda p: _impala_loss(
            *_all_logp_v(policy, p, obs, cfg["act_dim"]), batch)
        ref_loss = lambda p: _impala_loss(
            *reference.forward(p, obs, cfg), batch)
        # (one program a side: op by op the five layers' backward costs the
        # suite's clock a minute and tests nothing more)
        (ls, gs), (lr, gr) = (jax.jit(jax.value_and_grad(f))(params)
                              for f in (sys_loss, ref_loss))
        np.testing.assert_allclose(float(ls), float(lr), atol=2e-5)
        flat_ref = dict(jax.tree_util.tree_flatten_with_path(gr)[0])
        for path, g in jax.tree_util.tree_flatten_with_path(gs)[0]:
            name = jax.tree_util.keystr(path)
            # float32 sums in another order, as the forward's
            np.testing.assert_allclose(g, flat_ref[path], atol=2e-4,
                                       rtol=5e-4, err_msg=name)
            if "moe_expert_bias" not in name:   # the choice's: no gradient
                assert float(jnp.abs(g).max()) > 0, name

    def test_the_readout_row_is_the_full_forwards_row(self, reference, cfg):
        """A final KDA layer's row needs the whole recurrence before it."""
        policy, params = _system(reference, cfg, "float32")
        window = np.asarray(_obs(cfg, batch=1)[0])
        logp_ref, v_ref = reference.forward(params, window[None], cfg)
        step_window = jax.jit(policy.step_window)
        for t in (9, T):                # past a chunk's end, the last row
            act, aux = step_window(params, jax.random.PRNGKey(t),
                                   jnp.asarray(window), t)
            np.testing.assert_allclose(float(aux["v"]),
                                       float(v_ref[0, t - 1]), atol=3e-5)
            np.testing.assert_allclose(
                float(aux["logp_a"]), float(logp_ref[0, t - 1, int(act)]),
                atol=3e-5)

    def test_a_final_latent_layers_readout_row(self, reference, cfg):
        """Four layers end in the latent-attention layer, as a dense trunk:
        its final layer runs for the one row alone (the latent rows over
        every row, one query)."""
        short = {**cfg, "num_hidden_layers": 4}
        kwargs = {k: v for k, v in reference.program_kwargs(short).items()
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

    def test_cached_decode_through_both_caches_is_the_full_forward(
            self, reference, cfg):
        """32 steps through the two new kinds of cache — each KDA layer's
        last three rows of ``[q | k | v]`` and its ``[H, K, K]`` state,
        whose size does not grow with the position, and the latent layer's
        ``(c, k_pe)`` rows, 12 + 4 numbers a token where the heads' keys and
        values would be 80: every step's value and log-probability equal the
        reference's full forward at that row (logits, not samples)."""
        policy, params = _system(reference, cfg, "float32")
        window = np.asarray(_obs(cfg, batch=1)[0])
        logp_ref, v_ref = reference.forward(params, window[None], cfg)
        cache = policy.init_cache(T)
        for kind, c in zip(KINDS, cache):
            if kind == "kda":
                rows, state = c
                assert rows.shape == (1, 3, 96)
                assert state.shape == (1, 4, 8, 8)
                assert state.dtype == jnp.float32
            else:
                assert [a.shape for a in c] == [(1, T, 12), (1, T, 4)]
        assert policy.init_cache(4 * T)[0][1].shape == (1, 4, 8, 8)
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
        """Prefill ``t0`` real rows of a zero-padded window, then decode:
        the padding rows enter neither the state nor the convolution's
        rows, and the latent rows past ``t0`` are overwritten in order."""
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
        {"scalar_decay": True},         # one decay a head: gdn for KDA
        {"rope": True},                 # the shared lanes rotated
        {"no_latent_norm": True},       # W_kvb c without the RMSNorm
        {"bf16": True},                 # bfloat16 throughout, state included
    ])
    def test_a_wrong_reference_is_told_apart(self, reference, cfg, got,
                                             wrong):
        _, params = _system(reference, cfg, "float32")
        assert _differs(got, reference.forward(params, _obs(cfg), cfg,
                                               wrong=wrong)) > 1e-3

    @pytest.mark.parametrize("wrong", [
        {"moe_routed_scaling": 1.0}, {"kda_conv_taps": 3}])
    def test_a_different_model_is_told_apart(self, reference, cfg, want,
                                             wrong):
        _, params = _system(reference, cfg, "float32")
        other = _program(reference, cfg, "float32", **wrong)
        if "kda_conv_taps" in wrong:    # one tap fewer: its own tree
            params = jax.tree_util.tree_map_with_path(
                lambda path, a: a[1:] if "kda_conv_w" in jax.tree_util.
                keystr(path) else a, params)
        got = _outputs(other, params, _obs(cfg), cfg["act_dim"])
        assert _differs(got, want) > 1e-3

    def test_a_trunk_that_rotates_turns_the_shared_lanes(self, reference,
                                                         cfg):
        """The model rotates no lane (``mla_use_nope``); the same tree under
        ``positions: "rope"`` runs the latent layer's rotation: the shared
        ``k_pe`` lanes and q's matching lanes in half-split pairs, which is
        the wrong reference ``rope`` — and no longer the model."""
        policy, params = _system(reference, cfg, "float32", positions="rope",
                                 rope_theta=cfg["rope_theta"])
        obs = _obs(cfg)
        got = _outputs(policy, params, obs, cfg["act_dim"])
        assert _differs(got, reference.forward(
            params, obs, cfg, wrong={"rope": True})) < 1e-4
        assert _differs(got, reference.forward(params, obs, cfg)) > 1e-3

    def test_the_latent_layers_later_keys_default_to_this_program(
            self, reference, cfg):
        """``q_lora_rank`` and ``rope_interleave`` (PR 62) at their defaults
        are the program this trunk ran before them: the keys spelled out
        lower to the text of the keys left out — one ``q_proj``, no cosine,
        no part ``relayrl_latent_rope`` — and with nothing rotated the
        pairing has nothing to pair. (The update's lowered text, tiny and
        published sizes, held to the parent's by hand: CHANGES.md, PR 62.)"""
        texts = []
        for over in ({}, {"q_lora_rank": None, "rope_interleave": False},
                     {"rope_interleave": True}):
            policy, params = _system(reference, cfg, "float32", **over)
            assert "q_proj" in params["params"]["block_3"]
            texts.append(jax.jit(policy.evaluate).lower(
                params, _obs(cfg), jnp.zeros((2, T), jnp.int32)).as_text(
                    debug_info=True))
        assert texts[0] == texts[1] == texts[2]
        assert "cosine" not in texts[0]
        assert "relayrl_latent_rope" not in texts[0]
        assert "relayrl_op_proj" in texts[0]

    # ``benchmark/tests/controls_kimi_linear.py`` is how the controls are
    # read on the chip: each wrong reference planted in the program's place
    # and handed to the two functions that decide the cell's ``correct``.
    # Here the same ``judge`` at tiny float32 widths, the limits a little
    # above what the float32 system itself reads (1e-4, above).
    @pytest.fixture(scope="class")
    def judged(self, reference, cfg):
        import types

        controls = _by_path("benchmark/tests/controls_kimi_linear.py")
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
        return controls, run.checks, controls.judge(run, params, obs)

    def test_the_system_passes_the_limits_the_controls_are_held_to(
            self, judged):
        _, own, got = judged
        assert own == {"reference": True, "reference_routed": True}
        assert not got["exact"]["refused"]
        assert got["exact"]["reference_routed"]["rel_dlogp"] == 0.0

    @pytest.mark.parametrize("name", [
        "scalar_decay", "rope", "no_latent_norm", "bf16", "float8_e4m3fn",
        "float8_e5m2"])
    def test_a_planted_control_is_refused_by_the_cells_own_checks(
            self, judged, name):
        controls, _, got = judged
        assert set(got) == set(controls.CONTROLS)
        assert got[name]["refused"]
        assert not got[name]["checks"]["reference_routed"]

    def test_the_reference_is_float32_at_highest_and_imports_no_model(self):
        with open(os.path.join(
                REPO, "benchmark/reference/kimi-linear-policy.py")) as f:
            text = f.read()
        code = text.split('"""', 2)[2]
        assert "relayrl_tpu.models.transformer" not in code
        assert "relayrl_tpu.models.moe" not in code
        assert "relayrl_tpu.ops" not in code
        assert "flax" not in code
        assert 'jax.default_matmul_precision("highest")' in code
        assert "jax.lax.scan" in code       # the state equation, by step

    def test_a_program_without_the_keys_is_refused(self, reference, cfg,
                                                   monkeypatch):
        from relayrl_tpu.models import base

        monkeypatch.setattr(base, "ARCH_PASSTHROUGH_KEYS", tuple(
            k for k in base.ARCH_PASSTHROUGH_KEYS
            if not k.startswith("kda_")))
        with pytest.raises(SystemExit, match="REFUSED"):
            reference.program_kwargs(cfg)
