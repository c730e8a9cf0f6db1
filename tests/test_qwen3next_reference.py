"""The Qwen3-Next-shaped trunk against the benchmark's plain reference.

``benchmark/reference/qwen3next-policy.py`` is written from the model's
equations in plain ``jax.numpy`` — the delta rule one token at a time — and
reads the parameter tree as data; it shares no code with
``relayrl_tpu/models`` or ``ops/gdn.py``. On the chip the harness compares
the two at the published widths (``benchmark/configs/qwen3next-policy.json``'s
tolerance); here the same comparison runs at tiny widths on the CPU over one
period of four layers — three linear-attention layers whose rule crosses four
chunks, a gated full-attention layer with a quarter-rotary and zero-centred
q/k norms, expert layers with a gated shared expert and a held range that is
not the first. Full, readout-row and cached modes.
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
KINDS = ["linear_attention"] * 3 + ["full_attention"]


@pytest.fixture(scope="module")
def reference():
    return _by_path("benchmark/reference/qwen3next-policy.py")


def _published():
    with open(os.path.join(
            REPO, "benchmark/configs/qwen3next-policy.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    cfg = _published()
    # tiny widths; every mechanism of the published trunk: 2 key heads of 8
    # under 4 value heads of 8, chunks of 8 (four a sequence); 4 q heads of
    # 16 over 1 k/v head with 4 rotary lanes (64 wide under 24); experts
    # 4-7 of 16 held, top-3, a gated shared expert
    cfg.update(hidden_size=24, linear_num_key_heads=2,
               linear_num_value_heads=4, linear_key_head_dim=8,
               linear_value_head_dim=8, gdn_chunk=8, head_dim=16,
               num_attention_heads=4, num_key_value_heads=1,
               moe_intermediate_size=12, shared_expert_intermediate_size=20,
               num_experts=4, held_experts_first=4, num_experts_per_tok=3,
               published={"num_experts": 16}, positions_as_run=T,
               attention="dense")
    return cfg


def _program(reference, cfg, precision, **over):
    """The policy alone: a case that runs another program on the module's
    one tree seeds no tree of its own."""
    kwargs = {**reference.program_kwargs(cfg), **over}
    arch = {"kind": kwargs.pop("model_kind"), "obs_dim": cfg["obs_dim"],
            "act_dim": cfg["act_dim"], "has_critic": True,
            "precision": precision, **kwargs}
    return build_policy(arch)


def _system(reference, cfg, precision, seed=0, **over):
    policy = _program(reference, cfg, precision, **over)
    return policy, policy.init_params(jax.random.PRNGKey(seed))


@pytest.fixture(scope="module")
def system(reference, cfg):
    """The float32 trunk and its parameters, built once for the module."""
    return _system(reference, cfg, "float32")


@pytest.fixture(scope="module")
def got(system, cfg):
    """The system's log-probabilities and values on ``_obs(cfg)``."""
    return _all_logp_v(*system, _obs(cfg), cfg["act_dim"])


@pytest.fixture(scope="module")
def want(reference, system, cfg):
    """The reference's, from the same parameters and rows."""
    return reference.forward(system[1], _obs(cfg), cfg)


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
    def test_the_trunk_is_what_the_configuration_says(self, reference, cfg,
                                                      system):
        kwargs = reference.program_kwargs(cfg)
        assert kwargs["layer_types"] == KINDS
        _, params = system
        p = params["params"]
        assert "pos_embed" not in p
        lin = p["block_0"]
        assert set(lin) == {"ln_attn", "gdn_in_qkvz", "gdn_in_ba",
                            "gdn_conv_w", "gdn_dt_bias", "gdn_A_log",
                            "gdn_norm", "gdn_out", "ln_mlp", "moe"}
        # [q | k | v | z]: 16 | 16 | 32 | 32; [b | a]: 4 | 4
        assert lin["gdn_in_qkvz"].shape == (24, 96)
        assert lin["gdn_in_ba"].shape == (24, 8)
        assert lin["gdn_conv_w"].shape == (4, 64)     # q, k and v, no bias
        assert lin["gdn_norm"].shape == (8,)          # one head's width
        assert lin["gdn_out"].shape == (32, 24)
        full = p["block_3"]
        assert set(full) == {"ln_attn", "q_proj", "k_proj", "v_proj",
                             "q_norm", "k_norm", "attn_out", "ln_mlp",
                             "moe"}
        assert full["q_proj"]["kernel"].shape == (24, 2 * 64)  # q and gate
        assert full["k_proj"]["kernel"].shape == (24, 16)
        assert full["q_norm"]["scale"].shape == (16,)
        assert full["attn_out"]["kernel"].shape == (64, 24)
        moe = full["moe"]
        assert set(moe) == {"moe_gate", "moe_w_gate", "moe_w_up",
                            "moe_w_down", "moe_shared_gate",
                            "moe_shared_up", "moe_shared_down",
                            "moe_shared_expert_gate"}
        assert moe["moe_w_up"].shape == (4, 24, 12)   # 4 held of 16
        assert moe["moe_gate"]["kernel"].shape == (24, 16)
        assert moe["moe_shared_expert_gate"]["kernel"].shape == (24, 1)
        assert not [path for path, _ in
                    jax.tree_util.tree_flatten_with_path(p["block_3"])[0]
                    if "bias" in jax.tree_util.keystr(path)]
        # zero-centred weights are seeded round 0, the gated norm's at 1
        assert abs(float(jnp.mean(lin["ln_attn"]["scale"]))) < 0.05
        assert float(jnp.abs(lin["ln_attn"]["scale"]).max()) > 0
        assert float(lin["gdn_norm"].min()) == 1.0

    # float32: both sides compute the same sums in another order (the
    # chunked rule against the step-by-step one): the largest difference.
    # bfloat16: the system rounds the operands of its projections, rules,
    # attention and experts to 8 bits of mantissa, four layers deep, and at
    # these widths a token whose 3rd and 4th probabilities tie within that
    # error moves its whole expert output, so the bulk of the tokens is
    # compared: their median. (float32 reads 5.2e-5 at the largest, 1e-6
    # in the median: a token whose rule's output is small is normed up.)
    @pytest.mark.parametrize("precision,over_tokens,atol", [
        ("float32", jnp.max, 1e-4), ("bfloat16", jnp.median, 0.06)])
    def test_log_probabilities_and_values(self, reference, cfg, got, want,
                                          precision, over_tokens, atol):
        if precision == "float32":
            (logp, v), (logp_ref, v_ref) = got, want
        else:
            policy, params = _system(reference, cfg, precision)
            obs = _obs(cfg)
            logp, v = _all_logp_v(policy, params, obs, cfg["act_dim"])
            logp_ref, v_ref = reference.forward(params, obs, cfg)
        assert float(over_tokens(jnp.abs(logp - logp_ref).max(-1))) < atol
        assert float(over_tokens(jnp.abs(v - v_ref))) < atol

    def test_impala_loss_and_every_gradient(self, reference, cfg, system):
        policy, params = system
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
            # float32 sums in another order, as the forward's 5e-5
            np.testing.assert_allclose(g, flat_ref[path], atol=2e-4,
                                       rtol=5e-4, err_msg=name)
            assert float(jnp.abs(g).max()) > 0, name

    def test_the_readout_row_is_the_full_forwards_row(self, reference, cfg,
                                                      system):
        policy, params = system
        window = np.asarray(_obs(cfg, batch=1)[0])
        logp_ref, v_ref = reference.forward(params, window[None], cfg)
        step = jax.jit(policy.step_window)      # one program, five rows
        for t in (1, 8, 9, 20, T):      # inside, at and past a chunk's end
            act, aux = step(params, jax.random.PRNGKey(t),
                            jnp.asarray(window), t)
            np.testing.assert_allclose(float(aux["v"]),
                                       float(v_ref[0, t - 1]), atol=3e-5)
            np.testing.assert_allclose(
                float(aux["logp_a"]), float(logp_ref[0, t - 1, int(act)]),
                atol=3e-5)

    def test_a_final_linear_layers_readout_row_too(self, reference, cfg):
        short = {**cfg, "num_hidden_layers": 3}
        policy, params = _system(reference, short, "float32")
        window = np.asarray(_obs(cfg, batch=1)[0])
        _, v_ref = reference.forward(params, window[None], short)
        step = jax.jit(policy.step_window)
        for t in (3, 17, T):
            _, aux = step(params, jax.random.PRNGKey(t), jnp.asarray(window),
                          t)
            np.testing.assert_allclose(float(aux["v"]),
                                       float(v_ref[0, t - 1]), atol=3e-5)

    def test_a_dense_trunks_gated_row_readout(self, reference, cfg):
        """The row-only path of a final gated attention layer (a trunk
        without experts takes it): the gate's row with the query's."""
        kwargs = {k: v for k, v in reference.program_kwargs(cfg).items()
                  if not k.startswith("moe_")}
        arch = {"obs_dim": cfg["obs_dim"], "act_dim": cfg["act_dim"],
                "has_critic": True, "precision": "float32", **kwargs,
                "kind": "transformer_discrete", "d_ff": 32}
        arch.pop("model_kind")
        policy = build_policy(arch)
        params = policy.init_params(jax.random.PRNGKey(0))
        obs = _obs(cfg, batch=1)
        _, _, v = policy.evaluate(params, obs, jnp.zeros((1, T), jnp.int32))
        step = jax.jit(policy.step_window)
        for t in (2, 19, T):
            _, aux = step(params, jax.random.PRNGKey(t), obs[0], t)
            np.testing.assert_allclose(float(aux["v"]), float(v[0, t - 1]),
                                       atol=3e-5)

    def test_cached_decode_through_the_state_is_the_full_forward(
            self, reference, cfg, system):
        """32 steps through the fifth kind of cache — each linear-attention
        layer's last three rows of ``[q | k | v]`` and its ``[H, K, V]``
        state, whose size does not grow with the position — beside the
        attention layer's 32-row pair of keys rotated on a quarter of their
        lanes: every step's value and log-probability equal the reference's
        full forward at that row."""
        policy, params = system
        window = np.asarray(_obs(cfg, batch=1)[0])
        logp_ref, v_ref = reference.forward(params, window[None], cfg)
        cache = policy.init_cache(T)
        for kind, c in zip(KINDS, cache):
            if kind == "linear_attention":
                rows, state = c
                assert rows.shape == (1, 3, 64)
                assert state.shape == (1, 4, 8, 8)
                assert state.dtype == jnp.float32
            else:
                assert c[0].shape == (1, T, 1 * 16)  # flat rows: heads x lanes
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

    @pytest.mark.parametrize("t0", [3, 19, T - 1])
    def test_a_prefilled_state_continues_as_the_full_forward(
            self, reference, cfg, system, t0):
        """Prefill ``t0`` real rows of a zero-padded window, then decode:
        the padding rows enter neither the state nor the convolution's
        rows."""
        policy, params = system
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
        {"carry": False},               # the state dropped at chunk ends
        {"beta": False},                # no delta term
        {"decay": False},               # g = 0
        {"l2": False},                  # q and k not normalised
        {"gate": "before"},             # the gate before the norm
        {"attn_gate": False},           # the attention's gate left out
        {"rope_share": 1.0},            # every lane turned
        {"centred": False},             # w for 1 + w
        {"shared_gate": False},         # the shared expert's gate left out
        {"top_k": 2},                   # an expert dropped per token
    ])
    def test_a_wrong_reference_is_told_apart(self, reference, cfg, system,
                                             got, wrong):
        assert _differs(got, reference.forward(system[1], _obs(cfg), cfg,
                                               wrong=wrong)) > 1e-3

    def test_the_chunk_is_no_part_of_the_model(self, reference, cfg, system,
                                               want):
        other = _program(reference, cfg, "float32", gdn_chunk=16)
        got = _all_logp_v(other, system[1], _obs(cfg), cfg["act_dim"])
        assert _differs(got, want) < 1e-4

    @pytest.mark.parametrize("wrong", [
        {"ffn": "reglu"}, {"moe_top_k": 2}, {"moe_held": [3, 4]},
        {"moe_norm_topk_prob": False}, {"norm_eps": 1e-2},
        {"rope_share": 0.5}, {"rope_theta": 10000.0},
        {"norm_zero_centred": False}])
    def test_a_different_model_is_told_apart(self, reference, cfg, system,
                                             want, wrong):
        other = _program(reference, cfg, "float32", **wrong)
        got = _all_logp_v(other, system[1], _obs(cfg), cfg["act_dim"])
        assert _differs(got, want) > 1e-3

    def test_an_8_bit_trunk_is_further_off_than_bfloat16(self, reference,
                                                         cfg, system, want):
        (_, params), obs, exact = system, _obs(cfg), want
        errs = {}
        for name, dtype in (("bf16", jnp.bfloat16),
                            ("fp8", jnp.float8_e5m2)):
            lo = reference.forward(params, obs, cfg, operands=dtype)
            # the bulk of the tokens (median), not the few that re-route
            errs[name] = float(jnp.median(jnp.abs(lo[0] - exact[0]).max(-1)))
        assert errs["bf16"] * 4 < errs["fp8"], errs

    def test_the_reference_is_float32_at_highest_and_imports_no_model(self):
        with open(os.path.join(
                REPO, "benchmark/reference/qwen3next-policy.py")) as f:
            text = f.read()
        code = text.split('"""', 2)[2]
        assert "relayrl_tpu.models.transformer" not in code
        assert "relayrl_tpu.models.moe" not in code
        assert "relayrl_tpu.ops" not in code
        assert "flax" not in code
        assert 'jax.default_matmul_precision("highest")' in code
        assert "jax.lax.scan" in code       # the delta rule, by step

    def test_a_program_without_the_keys_is_refused(self, reference, cfg,
                                                   monkeypatch):
        from relayrl_tpu.models import base

        monkeypatch.setattr(base, "ARCH_PASSTHROUGH_KEYS", tuple(
            k for k in base.ARCH_PASSTHROUGH_KEYS if k != "gdn_key_dim"))
        with pytest.raises(SystemExit, match="gdn_key_dim"):
            reference.program_kwargs(cfg)

    @pytest.mark.parametrize("key,value", [
        ("hidden_act", "gelu"), ("norm_topk_prob", False),
        ("decoder_sparse_step", 2), ("mlp_only_layers", [0])])
    def test_a_configuration_it_was_not_written_for_is_refused(
            self, reference, cfg, key, value):
        with pytest.raises(SystemExit, match=key):
            reference.program_kwargs({**cfg, key: value})


class TestTheSharesAddUp:
    """Sixteen chips share a layer, experts divided: the sixteen shares'
    ROUTED outputs and the gated shared expert, which every chip computes
    alike, counted ONCE, sum to the uncut reference's layer."""

    E, K, D, FF, SHARED, CHIPS = 512, 10, 24, 12, 20, 16

    def _layer(self, held, shared):
        from relayrl_tpu.models.moe import MoEMLP

        return MoEMLP(self.D, self.FF, self.E, self.K, jnp.float32,
                      norm_topk_prob=True, ffn="swiglu", use_bias=False,
                      held=held, shared_d_ff=self.SHARED if shared else None,
                      shared_gate=shared)

    # (slow: a second draw of the same statement; tier-1 keeps seed 0)
    @pytest.mark.parametrize("seed", [
        0, pytest.param(1, marks=pytest.mark.slow)])
    def test_against_the_uncut_reference(self, reference, seed):
        rng = np.random.default_rng(seed)
        u = jnp.asarray(rng.standard_normal((2, 24, self.D)), jnp.float32)
        # the reference's RMSNorm before the experts made the identity
        # (zero offsets on rows of unit mean square)
        u = u * jax.lax.rsqrt(jnp.mean(jnp.square(u), -1, keepdims=True))
        whole = self._layer(None, True).init(jax.random.PRNGKey(seed),
                                             u)["params"]
        per = self.E // self.CHIPS
        stacks = ("moe_w_gate", "moe_w_up", "moe_w_down")

        def share(c, shared):
            p = {**whole, **{n: whole[n][per * c:per * (c + 1)]
                             for n in stacks}}
            if not shared:
                p = {k: v for k, v in p.items() if "shared" not in k}
            return self._layer((per * c, per), shared).apply({"params": p},
                                                             u)

        routed = [share(c, False) for c in range(self.CHIPS)]
        once = share(0, True) - routed[0]   # what every chip computes alike
        as_run = {"centred": True, "top_k": self.K, "shared_gate": True}
        with jax.default_matmul_precision("highest"):
            blk = {"ln_mlp": {"scale": jnp.zeros((self.D,))}, "moe": whole}
            uncut = reference._experts(blk, u, 0.0, 0, self.E, None,
                                       as_run) - u
            ungated = reference._experts(
                blk, u, 0.0, 0, self.E, None,
                {**as_run, "shared_gate": False}) - u
        np.testing.assert_allclose(sum(routed) + once, uncut, atol=3e-5,
                                   rtol=1e-5)
        # counted sixteen times it is not the layer; no share is; and the
        # gate is part of what is counted once
        assert float(jnp.abs(sum(routed) + 16 * once - uncut).max()) > 1e-2
        assert float(jnp.abs(routed[0] + once - uncut).max()) > 1e-3
        assert float(jnp.abs(sum(routed) + once - ungated).max()) > 1e-3


class TestShapeArithmetic:
    def test_forward_operations_a_token_at_the_published_widths(self):
        flops = _by_path("benchmark/flops_qwen3next.py")
        cfg = _published()
        d, t = 2048, 8192
        gdn_proj = 2 * d * (12_288 + 64) + 2 * 4096 * d
        assert gdn_proj == 50_593_792 + 16_777_216 == 67_371_008
        rule = (16 * 256 * 31.5 + 16 * 256 * 32.5 + 32 * 2 * 63 * 62 / 6
                + 3 * 32 * 256 * 32.5 + 3 * 32 * 2 * 128 * 128)
        assert rule == flops.gdn_fwd_flops(cfg) == 4_248_256
        attn_proj = 2 * (2 * d * 4096 + 2 * d * 512) + 2 * d * 4096
        scores = 4 * 16 * 256 * (t * (t + 1) // 2) / t
        assert (attn_proj, scores) == (54_525_952, 67_117_056)
        held = 0.625 * 3 * 2 * d * 512
        shared = 3 * 2 * d * 512 + 2 * d
        router = 2 * d * 512
        assert (held, shared, router) == (3_932_160, 6_295_552, 2_097_152)
        want = (3 * (gdn_proj + rule) + attn_proj + scores
                + 4 * (router + held + shared) + 2 * 18 * d + 2 * d * 17)
        got = flops.qwen3next_fwd_flops_per_token(cfg, t)
        assert got == want == 385_943_616
        # the linear layers 56%, the attention layer 32%, the experts 13%
        assert round(3 * (gdn_proj + rule) / got, 2) == 0.56
        assert round((attn_proj + scores) / got, 2) == 0.32
        assert round(4 * (router + held + shared) / got, 2) == 0.13

    def test_published_widths_in_the_configuration_file(self):
        c = _published()
        # the source's config.json (the catalog's copy), every key but the
        # two reduced
        published = {"decoder_sparse_step": 1, "full_attention_interval": 4,
                     "head_dim": 256, "hidden_act": "silu",
                     "hidden_size": 2048, "intermediate_size": 5120,
                     "linear_conv_kernel_dim": 4,
                     "linear_key_head_dim": 128,
                     "linear_num_key_heads": 16,
                     "linear_num_value_heads": 32,
                     "linear_value_head_dim": 128,
                     "max_position_embeddings": 262144,
                     "mlp_only_layers": [], "model_type": "qwen3_next",
                     "moe_intermediate_size": 512, "norm_topk_prob": True,
                     "num_attention_heads": 16, "num_experts": 512,
                     "num_experts_per_tok": 10, "num_hidden_layers": 48,
                     "num_key_value_heads": 2,
                     "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
                     "rope_scaling": None, "rope_theta": 10000000,
                     "shared_expert_intermediate_size": 512,
                     "tie_word_embeddings": False,
                     "use_sliding_window": False, "vocab_size": 151936}
        reduced = ["num_hidden_layers", "num_experts"]
        assert c["reduced"] == reduced
        assert {k: c[k] for k in published if k not in reduced} == {
            k: v for k, v in published.items() if k not in reduced}
        assert (c["num_hidden_layers"], c["num_experts"]) == (4, 32)
        assert c["published"] == {"num_hidden_layers": 48,
                                  "num_experts": 512}
        assert "16 chips share each layer" in c["deployment"]
        assert "NOT built" in c["departures"]["multi_token_prediction"]
        # the names the unedited readers use
        assert c["n_embd"] // c["n_head"] == c["head_dim"]
        assert c["num_hidden_layers"] - c["num_dense_layers"] == 4

    def test_the_published_trunk_holds_552_million_parameters(self,
                                                              reference):
        kwargs = reference.program_kwargs(_published())
        assert kwargs["layer_types"] == KINDS
        arch = {"kind": kwargs.pop("model_kind"), "obs_dim": 18,
                "act_dim": 16, "has_critic": True, **kwargs}
        shapes = jax.eval_shape(build_policy(arch).init_params,
                                jax.random.PRNGKey(0))
        p = shapes["params"]
        count = lambda tree: sum(x.size
                                 for x in jax.tree_util.tree_leaves(tree))
        outside = 1_048_576 + 3_145_728 + 2_048 + 32 * 3_145_728 + 4_096
        assert outside == 104_863_744
        assert count(p["block_0"]) == 33_718_464 + outside      # linear
        assert count(p["block_3"]) == 27_263_488 + outside      # full
        # the stacks keep the published width: no padded weight
        assert p["block_0"]["moe"]["moe_w_up"].shape == (32, 2048, 512)
        layers = 3 * 33_718_464 + 27_263_488 + 4 * outside
        assert layers == 547_873_856
        # + embedding, final norm, policy head, the value head's two layers
        ends = 38_912 + 2_048 + 32_784 + 4_196_352 + 2_049
        assert count(shapes) == layers + ends == 552_146_001
