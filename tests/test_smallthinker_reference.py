"""The SmallThinker-shaped trunk against the benchmark's plain reference.

``benchmark/reference/smallthinker-policy.py`` is written from the model's
equations in plain ``jax.numpy`` and reads the parameter tree as data; it
shares no code with ``relayrl_tpu/models``. On the chip the harness compares
the two at the published widths (``benchmark/configs/
smallthinker-policy.json``'s tolerance); here the same comparison runs at
tiny widths on the CPU, for one period of the layer pattern — a global
layer without positions, then three windowed RoPE layers whose window (8)
is a quarter of the sequence — 7 q heads of a width of their own over 1 k/v
head, the router on the layer's input, ReGLU experts and a held range that
is not the first. Full, readout-row and cached modes.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from relayrl_tpu.models import build_policy
# the two reference tests share their plumbing: a file loaded by its path,
# the system's outputs for all actions, IMPALA's loss from either side's
from test_lfm2_reference import _all_logp_v, _by_path, _impala_loss

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T = 32


@pytest.fixture(scope="module")
def reference():
    return _by_path("benchmark/reference/smallthinker-policy.py")


def _published():
    with open(os.path.join(
            REPO, "benchmark/configs/smallthinker-policy.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    cfg = _published()
    # tiny widths; every mechanism of the published trunk: experts 4-7 of
    # 16 held, top-3, 7 q heads of 8 over 1 k/v head (56 wide under 32)
    cfg.update(hidden_size=32, head_dim=8, num_attention_heads=7,
               num_key_value_heads=1, moe_ffn_hidden_size=16,
               moe_num_primary_experts=4, held_experts_first=4,
               published={"moe_num_primary_experts": 16},
               moe_num_active_primary_experts=3, sliding_window_size=8,
               max_position_embeddings=T, attention="dense")
    return cfg


def _program(reference, cfg, precision, **over):
    """The policy alone: a case that runs another program on the module's
    one tree seeds no tree of its own."""
    kwargs = {**reference.program_kwargs(cfg), **over}
    arch = {"kind": kwargs.pop("model_kind"), "obs_dim": cfg["obs_dim"],
            "act_dim": cfg["act_dim"], "has_critic": True,
            "precision": precision, **kwargs}
    return build_policy(arch)


_BUILT: dict = {}   # a policy and its seeded parameters, built once


def _system(reference, cfg, precision, seed=0, **over):
    key = (precision, seed, repr(sorted(over.items())))
    if key not in _BUILT:
        policy = _program(reference, cfg, precision, **over)
        _BUILT[key] = policy, policy.init_params(jax.random.PRNGKey(seed))
    return _BUILT[key]


@pytest.fixture(scope="module")
def got(reference, cfg):
    """The float32 system's outputs on ``_obs(cfg)``, computed once."""
    return _all_logp_v(*_system(reference, cfg, "float32"), _obs(cfg),
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
        _, params = _system(reference, cfg, "float32")
        p = params["params"]
        assert "pos_embed" not in p
        blk = p["block_2"]
        assert blk["q_proj"]["kernel"].shape == (32, 56)   # 7 heads of 8
        assert blk["k_proj"]["kernel"].shape == (32, 8)    # 1 k/v head
        assert blk["attn_out"]["kernel"].shape == (56, 32)
        assert "q_norm" not in blk and "bias" not in blk["q_proj"]
        assert blk["moe"]["moe_w_gate"].shape == (4, 32, 16)  # 4 held of 16
        assert blk["moe"]["moe_gate"]["kernel"].shape == (32, 16)
        assert "bias" not in blk["moe"]["moe_gate"]

    # float32: both sides compute the same sums in another order. bfloat16:
    # the system rounds the operands of its projections, attention and
    # experts to 8 bits of mantissa, four layers deep, and at these widths
    # a token whose 3rd and 4th logits tie within that error moves its
    # whole expert output (3 of 16 experts at width 32): measured 0.22 by
    # the largest difference, bound 0.4.
    @pytest.mark.parametrize("precision,atol", [("float32", 2e-5),
                                                ("bfloat16", 0.4)])
    def test_log_probabilities_and_values(self, reference, cfg, got, want,
                                          precision, atol):
        if precision != "float32":
            policy, params = _system(reference, cfg, precision)
            obs = _obs(cfg)
            got = _all_logp_v(policy, params, obs, cfg["act_dim"])
            want = reference.forward(params, obs, cfg)
        assert _differs(got, want) < atol

    def test_the_blockwise_form_too(self, reference, cfg):
        # off-TPU "flash" resolves to blockwise: the CPU actors' path
        policy, params = _system(reference, cfg, "float32",
                                 attention="flash", attention_block=8)
        obs = _obs(cfg)
        got = _all_logp_v(policy, params, obs, cfg["act_dim"])
        assert _differs(got, reference.forward(params, obs, cfg)) < 2e-5
        assert policy.attention_backends[(T, 8, "float32")] == "blockwise"

    def test_impala_loss_and_every_gradient(self, reference, cfg):
        policy, params = _system(reference, cfg, "float32")
        obs, batch = _obs(cfg), _batch(cfg)
        sys_loss = lambda p: _impala_loss(
            *_all_logp_v(policy, p, obs, cfg["act_dim"]), batch)
        ref_loss = lambda p: _impala_loss(
            *reference.forward(p, obs, cfg), batch)
        (ls, gs), (lr, gr) = (jax.jit(jax.value_and_grad(f))(params)
                              for f in (sys_loss, ref_loss))
        np.testing.assert_allclose(float(ls), float(lr), atol=1e-5)
        flat_ref = dict(jax.tree_util.tree_flatten_with_path(gr)[0])
        for path, g in jax.tree_util.tree_flatten_with_path(gs)[0]:
            name = jax.tree_util.keystr(path)
            np.testing.assert_allclose(g, flat_ref[path], atol=2e-5,
                                       rtol=1e-4, err_msg=name)
            assert float(jnp.abs(g).max()) > 0, name

    def test_the_readout_row_is_the_full_forwards_row(self, reference, cfg):
        policy, params = _system(reference, cfg, "float32")
        window = np.asarray(_obs(cfg, batch=1)[0])
        logp_ref, v_ref = reference.forward(params, window[None], cfg)
        step = jax.jit(policy.step_window)      # one program, five rows
        for t in (1, 8, 9, 20, T):      # inside, at and past the window
            act, aux = step(params, jax.random.PRNGKey(t),
                            jnp.asarray(window), t)
            np.testing.assert_allclose(float(aux["v"]),
                                       float(v_ref[0, t - 1]), atol=2e-5)
            np.testing.assert_allclose(
                float(aux["logp_a"]), float(logp_ref[0, t - 1, int(act)]),
                atol=2e-5)

    def test_cached_decode_through_the_ring_is_the_full_forward(
            self, reference, cfg):
        """32 steps through rings of 8 rows (three wraps) beside the global
        layer's 32-row pair: every step's value and log-probability equal
        the reference's full forward at that row."""
        policy, params = _system(reference, cfg, "float32")
        window = np.asarray(_obs(cfg, batch=1)[0])
        logp_ref, v_ref = reference.forward(params, window[None], cfg)
        cache = policy.init_cache(T)
        assert [c[0].shape[1] for c in cache] == [T, 8, 8, 8]
        step = jax.jit(policy.step_cached)      # one program, 32 positions
        for t in range(T):
            act, aux, cache = step(
                params, jax.random.PRNGKey(t), cache, window[t], t)
            np.testing.assert_allclose(float(aux["v"]), float(v_ref[0, t]),
                                       atol=2e-5, err_msg=f"t={t}")
            np.testing.assert_allclose(
                float(aux["logp_a"]), float(logp_ref[0, t, int(act)]),
                atol=2e-5, err_msg=f"t={t}")

    def test_a_prefilled_ring_continues_as_the_full_forward(self, reference,
                                                            cfg):
        policy, params = _system(reference, cfg, "float32")
        window = np.asarray(_obs(cfg, batch=1)[0])
        _, v_ref = reference.forward(params, window[None], cfg)
        t0 = 19
        padded = window.copy()
        padded[t0:] = 0.0
        cache = policy.prefill_cache(params, policy.init_cache(T),
                                     jnp.asarray(padded), t0)
        step = jax.jit(policy.step_cached)
        for t in range(t0, T):
            _, aux, cache = step(
                params, jax.random.PRNGKey(t), cache, window[t], t)
            np.testing.assert_allclose(float(aux["v"]), float(v_ref[0, t]),
                                       atol=2e-5, err_msg=f"t={t}")

    @pytest.mark.parametrize("wrong", [
        {"window": False},                  # full attention everywhere
        {"rope_global": True},              # RoPE on the NoPE layer
        {"router_input": "normed"},         # the router behind the norm
        {"router_input": "post_attention"},  # ... behind the attention
        {"top_k": 2},                       # an expert dropped per token
        {"activation": "silu"},             # SwiGLU experts
    ])
    def test_a_wrong_reference_is_told_apart(self, reference, cfg, got,
                                             wrong):
        _, params = _system(reference, cfg, "float32")
        assert _differs(got, reference.forward(params, _obs(cfg), cfg,
                                               wrong=wrong)) > 1e-3

    @pytest.mark.parametrize("wrong", [
        {"sliding_window": 9}, {"rope_theta": 100.0},
        {"rope_layers": [True] * 4}, {"rope_layers": [False] * 4},
        {"layer_types": ["full_attention"] * 4},
        {"moe_router_input": "ffn"}, {"ffn": "swiglu"}, {"moe_top_k": 2},
        {"moe_held": [3, 4]}, {"norm_eps": 1e-2}])
    def test_a_different_model_is_told_apart(self, reference, cfg, want,
                                             wrong):
        _, params = _system(reference, cfg, "float32")
        other = _program(reference, cfg, "float32", **wrong)
        got = _all_logp_v(other, params, _obs(cfg), cfg["act_dim"])
        assert _differs(got, want) > 1e-3

    def test_an_8_bit_trunk_is_further_off_than_bfloat16(self, reference,
                                                         cfg, want):
        _, params = _system(reference, cfg, "float32")
        obs, exact = _obs(cfg), want
        errs = {}
        for name, dtype in (("bf16", jnp.bfloat16),
                            ("fp8", jnp.float8_e5m2)):
            lo = reference.forward(params, obs, cfg, operands=dtype)
            # the bulk of the tokens (median), not the few that re-route
            errs[name] = float(jnp.median(jnp.abs(lo[0] - exact[0]).max(-1)))
        assert errs["bf16"] * 4 < errs["fp8"], errs

    def test_the_reference_is_float32_at_highest_and_imports_no_model(self):
        with open(os.path.join(
                REPO, "benchmark/reference/smallthinker-policy.py")) as f:
            text = f.read()
        assert "relayrl_tpu.models.transformer" not in text
        assert "relayrl_tpu.models.moe" not in text
        assert "flax" not in text.split('"""', 2)[2]
        assert 'jax.default_matmul_precision("highest")' in text

    def test_a_program_without_the_keys_is_refused(self, reference, cfg,
                                                   monkeypatch):
        from relayrl_tpu.models import base

        monkeypatch.setattr(base, "ARCH_PASSTHROUGH_KEYS", tuple(
            k for k in base.ARCH_PASSTHROUGH_KEYS if k != "sliding_window"))
        with pytest.raises(SystemExit, match="sliding_window"):
            reference.program_kwargs(cfg)


class TestTheSharesAddUp:
    """Four chips share a layer, experts divided: the four shares'
    expert-layer outputs sum to the UNCUT reference's layer output, with
    the router on rows of its own and ReGLU experts."""

    E, K, D, FF = 64, 6, 32, 16

    def _layer(self, held):
        from relayrl_tpu.models.moe import MoEMLP

        return MoEMLP(self.D, self.FF, self.E, self.K, jnp.float32,
                      norm_topk_prob=True, ffn="reglu", use_bias=False,
                      held=held)

    # (slow: a second draw of the same statement; tier-1 keeps seed 0)
    @pytest.mark.parametrize("seed", [
        0, pytest.param(1, marks=pytest.mark.slow)])
    def test_against_the_uncut_reference(self, reference, seed):
        rng = np.random.default_rng(seed)
        u, x = (jnp.asarray(rng.standard_normal((2, 24, self.D)),
                            jnp.float32) for _ in range(2))
        whole = self._layer(None).init(jax.random.PRNGKey(seed), u,
                                       x)["params"]
        # the reference's router and experts, given every expert; its
        # RMSNorm before the experts made the identity (unit scale on rows
        # of unit mean square)
        u = u * jax.lax.rsqrt(jnp.mean(jnp.square(u), -1, keepdims=True))
        parts = [self._layer((16 * c, 16)).apply(
            {"params": {**whole, **{n: whole[n][16 * c:16 * c + 16]
                                    for n in ("moe_w_gate", "moe_w_up",
                                              "moe_w_down")}}}, u, x)
            for c in range(4)]
        with jax.default_matmul_precision("highest"):
            w = reference._route(whole, x, self.K, 0, self.E)
            blk = {"ln_mlp": {"scale": jnp.ones((self.D,))}, "moe": whole}
            uncut = reference._experts(blk, jnp.zeros_like(u) + u, w, 0.0,
                                       False, None) - u
        np.testing.assert_allclose(sum(parts), uncut, atol=2e-5, rtol=1e-5)
        # and no share is the whole: the cut is real
        assert float(jnp.abs(parts[0] - uncut).max()) > 1e-3


class TestShapeArithmetic:
    def test_forward_operations_a_token_at_the_published_widths(self):
        flops = _by_path("benchmark/flops_smallthinker.py")
        cfg = _published()
        d, t, w = 2560, 16384, 4096
        proj = 2 * (2 * d * 3584 + 2 * d * 512)
        assert proj == 41_943_040
        assert flops.band_scores(t, None) == t * (t + 1) // 2 == 134_225_920
        assert flops.band_scores(t, w) == w * (w + 1) // 2 + (t - w) * w == (
            58_722_304)
        assert flops.band_scores(t, t) == flops.band_scores(t, None)
        glob = 4 * 128 * 28 * 134_225_920 / t
        band = 4 * 128 * 28 * 58_722_304 / t
        held = 1.5 * 6 * d * 768 + 2 * d * 64
        want = 4 * proj + glob + 3 * band + 4 * held + 2 * 18 * d + (
            2 * d * 17)
        got = flops.smallthinker_fwd_flops_per_token(cfg, t)
        assert got == want
        assert round(got / 1e6) == 512          # ISSUE 34: "about 510"
        assert round((glob + 3 * band) / got, 2) == 0.53
        assert round(3 * band / got, 2) == 0.30
        assert round(4 * 1.5 * 6 * d * 768 / got, 2) == 0.14

    def test_published_widths_in_the_configuration_file(self):
        c = _published()
        published = {
            "hidden_size": 2560, "head_dim": 128, "num_attention_heads": 28,
            "num_key_value_heads": 4, "moe_ffn_hidden_size": 768,
            "moe_num_active_primary_experts": 6,
            "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
            "rms_norm_eps": 1e-6, "rope_theta": 1500000,
            "rope_scaling": None, "sliding_window_size": 4096,
            "max_position_embeddings": 16384, "vocab_size": 151936,
            "tie_word_embeddings": False,
            "model_name": "smallthinker_21b_instruct"}
        assert {k: c[k] for k in published} == published
        assert c["reduced"] == ["num_hidden_layers",
                                "moe_num_primary_experts"]
        assert (c["num_hidden_layers"], c["moe_num_primary_experts"]) == (
            4, 16)
        assert c["published"] == {"moe_num_primary_experts": 64,
                                  "num_hidden_layers": 52}
        assert c["rope_layout"] == c["sliding_window_layout"] == [0, 1, 1, 1]
        assert "4 chips share each layer" in c["deployment"]
        # the names drivers/update.py reads give the head's own width
        assert c["n_embd"] // c["n_head"] == c["head_dim"]

    def test_the_published_trunk_holds_469_million_parameters(self,
                                                              reference):
        kwargs = reference.program_kwargs(_published())
        arch = {"kind": kwargs.pop("model_kind"), "obs_dim": 18,
                "act_dim": 16, "has_critic": True, **kwargs}
        shapes = jax.eval_shape(build_policy(arch).init_params,
                                jax.random.PRNGKey(0))
        n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
        layer = 20_971_520 + 163_840 + 5_120 + 16 * 5_898_240
        assert layer == 115_512_320
        # + embedding, final norm, policy head, the value head's two layers
        ends = 48_640 + 2_560 + 40_976 + 6_556_160 + 2_561
        assert n == 4 * layer + ends == 468_700_177, n
