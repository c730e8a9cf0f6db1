"""The Nemotron-H-shaped trunk against the benchmark's plain reference.

``benchmark/reference/nemotron-twotower-policy.py`` is written from the
model's equations in plain ``jax.numpy`` — the state equation one token at
a time — and reads the parameter tree as data; it shares no code with
``relayrl_tpu/models`` or ``ops/ssd.py``. On the chip the harness compares
the two at the published widths (``benchmark/configs/
nemotron-twotower-policy.json``'s tolerance); here the same comparison runs
at tiny widths on the CPU over the published pattern's nine layers —
Mamba-2 layers whose scan crosses four chunks, an attention layer without
positions, expert layers with a ``relu^2`` shared expert, the 2.5 and a held
range that is not the first. Full, readout-row and cached modes.
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
KINDS = ["mamba2", "ffn", "mamba2", "ffn", "mamba2", "attention", "ffn",
         "mamba2", "ffn"]


@pytest.fixture(scope="module")
def reference():
    return _by_path("benchmark/reference/nemotron-twotower-policy.py")


def _published():
    with open(os.path.join(
            REPO, "benchmark/configs/nemotron-twotower-policy.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    cfg = _published()
    # tiny widths; every mechanism of the published trunk: 4 Mamba heads of
    # 8 in 2 groups, state 16, chunks of 8 (four a sequence); 4 q heads of 8
    # over 1 k/v head (32 wide under 24); experts 4-7 of 16 held, top-3
    cfg.update(hidden_size=24, mamba_num_heads=4, mamba_head_dim=8,
               ssm_state_size=16, n_groups=2, chunk_size=8, head_dim=8,
               num_attention_heads=4, num_key_value_heads=1,
               moe_intermediate_size=12,
               moe_shared_expert_intermediate_size=20, n_routed_experts=4,
               held_experts_first=4, num_experts_per_tok=3,
               published={"n_routed_experts": 16}, positions_as_run=T,
               attention="dense")
    return cfg


def _policy(reference, cfg, precision, **over):
    kwargs = {**reference.program_kwargs(cfg), **over}
    arch = {"kind": kwargs.pop("model_kind"), "obs_dim": cfg["obs_dim"],
            "act_dim": cfg["act_dim"], "has_critic": True,
            "precision": precision, **kwargs}
    return build_policy(arch)


def _system(reference, cfg, precision, seed=0, **over):
    policy = _policy(reference, cfg, precision, **over)
    # (one program, as the steps below: op by op the nine layers cost the
    # suite's clock minutes and test nothing more)
    return policy, jax.jit(policy.init_params)(jax.random.PRNGKey(seed))


def _outputs(policy, params, obs, act_dim):
    return jax.jit(lambda p, o: _all_logp_v(policy, p, o, act_dim))(params,
                                                                    obs)


@pytest.fixture(scope="module")
def system(reference, cfg):
    """The float32 trunk and its seeded weights, built once."""
    return _system(reference, cfg, "float32")


@pytest.fixture(scope="module")
def outputs(system, cfg):
    """... and its log-probabilities and values on the seeded rows."""
    policy, params = system
    return _outputs(policy, params, _obs(cfg), cfg["act_dim"])


@pytest.fixture(scope="module")
def want(reference, system, cfg):
    """The reference's, from the same weights and rows."""
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
        m = p["block_0"]                    # M: one part, one norm
        assert set(m) == {"ln_attn", "mamba_in", "mamba_conv_w",
                          "mamba_conv_b", "mamba_dt_bias", "mamba_A_log",
                          "mamba_D", "mamba_norm", "mamba_out"}
        # [z | xBC | dt]: 32 | 32 + 2 * 2 * 16 | 4
        assert m["mamba_in"].shape == (24, 32 + 96 + 4)
        assert m["mamba_conv_w"].shape == (4, 96)
        assert m["mamba_conv_b"].shape == (96,)
        assert m["mamba_out"].shape == (32, 24)
        a = p["block_5"]                    # *: attention and no FFN
        assert set(a) == {"ln_attn", "q_proj", "k_proj", "v_proj",
                          "attn_out"}
        assert a["q_proj"]["kernel"].shape == (24, 32)
        assert a["k_proj"]["kernel"].shape == (24, 8)
        e = p["block_1"]                    # E: the expert layer alone
        assert set(e) == {"ln_mlp", "moe"}
        moe = e["moe"]
        assert set(moe) == {"moe_gate", "moe_expert_bias", "moe_w_up",
                            "moe_w_down", "moe_shared_up",
                            "moe_shared_down"}      # two stacks, no gate
        assert moe["moe_w_up"].shape == (4, 24, 12)   # 4 held of 16
        assert moe["moe_gate"]["kernel"].shape == (24, 16)
        assert moe["moe_shared_up"]["kernel"].shape == (24, 20)
        assert "bias" not in moe["moe_gate"]

    # float32: both sides compute the same sums in another order (the
    # chunked scan against the step-by-step one): the largest difference.
    # bfloat16: the system rounds the operands of its projections, scans,
    # attention and experts to 8 bits of mantissa, nine layers deep, and at
    # these widths a token whose 3rd and 4th scores tie within that error
    # moves its whole expert output, times 2.5 — the largest difference
    # reads 0.08 to 2.0 by seed —, so the bulk of the tokens is compared:
    # their median reads 0.019-0.021 over four seeds, bound 0.06.
    @pytest.mark.parametrize("precision,over_tokens,atol", [
        ("float32", jnp.max, 3e-5), ("bfloat16", jnp.median, 0.06)])
    def test_log_probabilities_and_values(self, reference, cfg, outputs,
                                          want, precision, over_tokens,
                                          atol):
        if precision != "float32":
            policy, params = _system(reference, cfg, precision)
            obs = _obs(cfg)
            outputs = _outputs(policy, params, obs, cfg["act_dim"])
            want = reference.forward(params, obs, cfg)
        (logp, v), (logp_ref, v_ref) = outputs, want
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
            np.testing.assert_allclose(g, flat_ref[path], atol=3e-5,
                                       rtol=2e-4, err_msg=name)
            # the correction bias enters the choice only: never moved
            assert (float(jnp.abs(g).max()) > 0) != (
                "moe_expert_bias" in name), name

    def test_the_readout_row_is_the_full_forwards_row(self, reference, cfg,
                                                      system):
        policy, params = system
        window = np.asarray(_obs(cfg, batch=1)[0])
        logp_ref, v_ref = reference.forward(params, window[None], cfg)
        step_window = jax.jit(policy.step_window)
        for t in (1, 8, 9, 20, T):      # inside, at and past a chunk's end
            act, aux = step_window(params, jax.random.PRNGKey(t),
                                   jnp.asarray(window), t)
            np.testing.assert_allclose(float(aux["v"]),
                                       float(v_ref[0, t - 1]), atol=3e-5)
            np.testing.assert_allclose(
                float(aux["logp_a"]), float(logp_ref[0, t - 1, int(act)]),
                atol=3e-5)

    def test_a_final_mamba_layers_readout_row_too(self, reference, cfg):
        short = {**cfg, "num_hidden_layers": 8,
                 "hybrid_override_pattern": "MEMEM*EM"}
        policy, params = _system(reference, short, "float32")
        window = np.asarray(_obs(cfg, batch=1)[0])
        _, v_ref = reference.forward(params, window[None], short)
        step_window = jax.jit(policy.step_window)
        for t in (3, 17, T):
            _, aux = step_window(params, jax.random.PRNGKey(t),
                                 jnp.asarray(window), t)
            np.testing.assert_allclose(float(aux["v"]),
                                       float(v_ref[0, t - 1]), atol=3e-5)

    def test_cached_decode_through_the_state_is_the_full_forward(
            self, reference, cfg, system):
        """32 steps through the fourth kind of cache — each Mamba-2 layer's
        last three rows of ``xBC`` and its ``[H, P, N]`` state, whose size
        does not grow with the position — beside the attention layer's
        32-row pair and the expert layers' nothing: every step's value and
        log-probability equal the reference's full forward at that row."""
        policy, params = system
        window = np.asarray(_obs(cfg, batch=1)[0])
        logp_ref, v_ref = reference.forward(params, window[None], cfg)
        cache = policy.init_cache(T)
        for kind, c in zip(KINDS, cache):
            if kind == "mamba2":
                rows, state = c
                assert rows.shape == (1, 3, 96)
                assert state.shape == (1, 4, 8, 16)
                assert state.dtype == jnp.float32
            elif kind == "attention":
                assert c[0].shape == (1, T, 1 * 8)  # flat rows: heads x lanes
            else:
                assert c == ()
        assert policy.init_cache(4 * T)[0][1].shape == (1, 4, 8, 16)
        step_cached = jax.jit(policy.step_cached)
        for t in range(T):
            act, aux, cache = step_cached(
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
        cache = jax.jit(policy.prefill_cache)(
            params, policy.init_cache(T), jnp.asarray(padded), t0)
        step_cached = jax.jit(policy.step_cached)
        for t in range(t0, T):
            _, aux, cache = step_cached(
                params, jax.random.PRNGKey(t), cache, window[t], t)
            np.testing.assert_allclose(float(aux["v"]), float(v_ref[0, t]),
                                       atol=3e-5, err_msg=f"t={t}")

    @pytest.mark.parametrize("wrong", [
        {"carry": False},               # the state dropped at chunk ends
        {"gate": "after"},              # the gate after the norm
        {"activation": "silu"},         # SiLU for relu^2
        {"activation": "relu"},         # ReLU, not squared
        {"scaling": 1.0},               # the 2.5 left out
        {"shared": False},              # no shared expert
        {"rope": True},                 # rotary positions on the attention
        {"top_k": 2},                   # an expert dropped per token
    ])
    def test_a_wrong_reference_is_told_apart(self, reference, cfg, system,
                                             outputs, wrong):
        assert _differs(outputs, reference.forward(
            system[1], _obs(cfg), cfg, wrong=wrong)) > 1e-3

    def test_the_chunk_is_no_part_of_the_model(self, reference, cfg, system,
                                               want):
        other = _policy(reference, cfg, "float32", mamba_chunk=16)
        got = _outputs(other, system[1], _obs(cfg), cfg["act_dim"])
        assert _differs(got, want) < 3e-5

    @pytest.mark.parametrize("wrong", [
        {"ffn": "gelu"}, {"moe_expert_bias": False},
        {"moe_routed_scaling": 1.0}, {"moe_top_k": 2}, {"moe_held": [3, 4]},
        {"moe_router": "softmax"}, {"norm_eps": 1e-2},
        {"positions": "rope", "rope_theta": 10000.0}])
    def test_a_different_model_is_told_apart(self, reference, cfg, system,
                                             want, wrong):
        other = _policy(reference, cfg, "float32", **wrong)
        got = _outputs(other, system[1], _obs(cfg), cfg["act_dim"])
        assert _differs(got, want) > 1e-3

    def test_an_8_bit_trunk_is_further_off_than_bfloat16(self, reference,
                                                         cfg, system,
                                                         want):
        params, obs, exact = system[1], _obs(cfg), want
        errs = {}
        for name, dtype in (("bf16", jnp.bfloat16),
                            ("fp8", jnp.float8_e5m2)):
            lo = reference.forward(params, obs, cfg, operands=dtype)
            # the bulk of the tokens (median), not the few that re-route
            errs[name] = float(jnp.median(jnp.abs(lo[0] - exact[0]).max(-1)))
        assert errs["bf16"] * 4 < errs["fp8"], errs

    def test_the_reference_is_float32_at_highest_and_imports_no_model(self):
        with open(os.path.join(
                REPO,
                "benchmark/reference/nemotron-twotower-policy.py")) as f:
            text = f.read()
        code = text.split('"""', 2)[2]
        assert "relayrl_tpu.models.transformer" not in code
        assert "relayrl_tpu.models.moe" not in code
        assert "relayrl_tpu.ops" not in code and "ssd" not in code.replace(
            "ssd_train_ops_bytes", "")
        assert "flax" not in code
        assert 'jax.default_matmul_precision("highest")' in code
        assert "jax.lax.scan" in code       # the state equation, by step

    def test_a_program_without_the_keys_is_refused(self, reference, cfg,
                                                   monkeypatch):
        from relayrl_tpu.models import base

        monkeypatch.setattr(base, "ARCH_PASSTHROUGH_KEYS", tuple(
            k for k in base.ARCH_PASSTHROUGH_KEYS if k != "mamba_state"))
        with pytest.raises(SystemExit, match="mamba_state"):
            reference.program_kwargs(cfg)

    @pytest.mark.parametrize("key,value", [
        ("n_group", 8), ("topk_group", 4), ("n_shared_experts", 2),
        ("mlp_hidden_act", "silu")])
    def test_a_configuration_it_was_not_written_for_is_refused(
            self, reference, cfg, key, value):
        with pytest.raises(SystemExit, match=key):
            reference.program_kwargs({**cfg, key: value})


class TestTheSharesAddUp:
    """Sixteen chips share a layer, experts divided: the sixteen shares'
    ROUTED outputs and the shared expert, which every chip computes alike,
    counted ONCE, sum to the uncut reference's layer."""

    E, K, D, FF, SHARED, CHIPS = 128, 6, 24, 12, 20, 16

    def _layer(self, held, shared):
        from relayrl_tpu.models.moe import MoEMLP

        return MoEMLP(self.D, self.FF, self.E, self.K, jnp.float32,
                      norm_topk_prob=True, ffn="relu2", use_bias=False,
                      router="sigmoid", expert_bias=True,
                      routed_scaling=2.5, held=held,
                      shared_d_ff=self.SHARED if shared else None)

    # (slow: a second draw of the same statement; tier-1 keeps seed 0)
    @pytest.mark.parametrize("seed", [
        0, pytest.param(1, marks=pytest.mark.slow)])
    def test_against_the_uncut_reference(self, reference, seed):
        rng = np.random.default_rng(seed)
        u = jnp.asarray(rng.standard_normal((2, 24, self.D)), jnp.float32)
        # the reference's RMSNorm before the experts made the identity
        # (unit scale on rows of unit mean square)
        u = u * jax.lax.rsqrt(jnp.mean(jnp.square(u), -1, keepdims=True))
        whole = self._layer(None, True).init(jax.random.PRNGKey(seed),
                                             u)["params"]
        per = self.E // self.CHIPS
        stacks = ("moe_w_up", "moe_w_down")

        def share(c, shared):
            p = {**whole, **{n: whole[n][per * c:per * (c + 1)]
                             for n in stacks}}
            if not shared:
                p = {k: v for k, v in p.items() if "shared" not in k}
            return self._layer((per * c, per), shared).apply({"params": p},
                                                             u)

        routed = [share(c, False) for c in range(self.CHIPS)]
        with_shared = share(0, True)
        once = with_shared - routed[0]      # what every chip computes alike
        with jax.default_matmul_precision("highest"):
            blk = {"ln_mlp": {"scale": jnp.ones((self.D,))}, "moe": whole}
            uncut = reference._experts(blk, u, 0.0, self.K, 2.5, 0, self.E,
                                       "relu2", True, None) - u
            no_shared = reference._experts(blk, u, 0.0, self.K, 2.5, 0,
                                           self.E, "relu2", False, None) - u
        np.testing.assert_allclose(sum(routed) + once, uncut, atol=3e-5,
                                   rtol=1e-5)
        np.testing.assert_allclose(once, uncut - no_shared, atol=3e-5,
                                   rtol=1e-5)
        # counted sixteen times it is not the layer; and no share is
        assert float(jnp.abs(sum(routed) + 16 * once - uncut).max()) > 1e-2
        assert float(jnp.abs(routed[0] + once - uncut).max()) > 1e-3


class TestShapeArithmetic:
    def test_forward_operations_a_token_at_the_published_widths(self):
        flops = _by_path("benchmark/flops_nemotron.py")
        cfg = _published()
        d, t = 2688, 8192
        mamba_proj = 2 * d * (2 * 4096 + 2 * 8 * 128 + 64) + 2 * 4096 * d
        assert mamba_proj == 55_394_304 + 22_020_096 == 77_414_400
        scan = 8 * 128 * 129 + 64 * 64 * 129 + 2 * 2 * 64 * 64 * 128
        assert scan == flops.ssd_fwd_flops(cfg) == 2_757_632
        attn_proj = 2 * (2 * d * 4096 + 2 * d * 256)
        scores = 4 * 32 * 128 * (t * (t + 1) // 2) / t
        assert (attn_proj, scores) == (46_792_704, 67_117_056)
        held = 0.375 * 2 * 2 * d * 1856
        shared = 2 * 2 * d * 3712
        router = 2 * d * 128
        assert (held, shared, router) == (7_483_392, 39_911_424, 688_128)
        want = (4 * (mamba_proj + scan) + attn_proj + scores
                + 4 * (router + held + shared) + 2 * 18 * d + 2 * d * 17)
        got = flops.nemotron_fwd_flops_per_token(cfg, t)
        assert got == want == 627_117_824
        # ISSUE 39's shares: state-space 51%, experts 31%, attention 18%
        assert round(4 * (mamba_proj + scan) / got, 2) == 0.51
        assert round(4 * (router + held + shared) / got, 2) == 0.31
        assert round((attn_proj + scores) / got, 2) == 0.18

    def test_published_widths_in_the_configuration_file(self):
        c = _published()
        # the source's config.json, every key but the three reduced
        published = {'attention_bias': False,
                     'chunk_size': 128,
                     'conv_kernel': 4,
                     'expand': 2,
                     'head_dim': 128,
                     'hidden_size': 2688,
                     'intermediate_size': 1856,
                     'layer_norm_epsilon': 1e-05,
                     'mamba_head_dim': 64,
                     'mamba_hidden_act': 'silu',
                     'mamba_num_heads': 64,
                     'mamba_proj_bias': False,
                     'max_position_embeddings': 262144,
                     'mlp_bias': False,
                     'mlp_hidden_act': 'relu2',
                     'model_type': 'nemotron_h',
                     'moe_intermediate_size': 1856,
                     'moe_shared_expert_intermediate_size': 3712,
                     'n_group': 1,
                     'n_groups': 8,
                     'n_shared_experts': 1,
                     'norm_eps': 1e-05,
                     'norm_topk_prob': True,
                     'num_attention_heads': 32,
                     'num_experts_per_tok': 6,
                     'num_key_value_heads': 2,
                     'num_logits_to_keep': 1,
                     'partial_rotary_factor': 1,
                     'rescale_prenorm_residual': True,
                     'residual_in_fp32': False,
                     'rope_theta': 10000,
                     'routed_scaling_factor': 2.5,
                     'sliding_window': None,
                     'ssm_state_size': 128,
                     'tie_word_embeddings': False,
                     'time_step_floor': 0.0001,
                     'time_step_limit': [0, None],
                     'time_step_max': 0.1,
                     'time_step_min': 0.001,
                     'topk_group': 1,
                     'use_bias': False,
                     'use_conv_bias': True,
                     'use_mamba_kernels': True,
                     'vocab_size': 131072}
        reduced = ["num_hidden_layers", "hybrid_override_pattern",
                   "n_routed_experts"]
        assert c["reduced"] == reduced
        assert {k: c[k] for k in published if k not in reduced} == {
            k: v for k, v in published.items() if k not in reduced}
        assert (c["num_hidden_layers"], c["hybrid_override_pattern"],
                c["n_routed_experts"]) == (9, "MEMEM*EME", 8)
        assert c["published"]["n_routed_experts"] == 128
        assert c["published"]["num_hidden_layers"] == 52 == len(
            c["published"]["hybrid_override_pattern"])
        assert c["published"]["hybrid_override_pattern"].startswith(
            c["hybrid_override_pattern"])
        for letter, n in (("M", 23), ("E", 23), ("*", 6)):
            assert c["published"]["hybrid_override_pattern"].count(
                letter) == n
        assert "16 chips share each layer" in c["deployment"]
        assert "NOT built" in c["departures"]["second_tower"]
        # the names the unedited readers use
        assert c["n_embd"] // c["n_head"] == c["head_dim"]
        assert c["num_hidden_layers"] - c["num_dense_layers"] == (
            c["hybrid_override_pattern"].count("E"))

    def test_the_published_trunk_holds_586_million_parameters(self,
                                                              reference):
        kwargs = reference.program_kwargs(_published())
        arch = {"kind": kwargs.pop("model_kind"), "obs_dim": 18,
                "act_dim": 16, "has_critic": True, **kwargs}
        shapes = jax.eval_shape(build_policy(arch).init_params,
                                jax.random.PRNGKey(0))
        p = shapes["params"]
        count = lambda tree: sum(x.size
                                 for x in jax.tree_util.tree_leaves(tree))
        assert count(p["block_0"]) == 38_744_896          # M
        assert count(p["block_5"]) == 23_399_040          # *
        expert_layer = 344_192 + 8 * 9_977_856 + 19_955_712 + 2_688
        assert count(p["block_1"]) == expert_layer == 100_125_440
        # the stacks keep the published width: no padded weight
        assert p["block_1"]["moe"]["moe_w_up"].shape == (8, 2688, 1856)
        layers = 4 * 38_744_896 + 23_399_040 + 4 * expert_layer
        assert layers == 578_880_384
        # + embedding, final norm, policy head, the value head's two layers
        ends = 51_072 + 2_688 + 43_024 + 7_228_032 + 2_689
        assert count(shapes) == layers + ends == 586_207_889
