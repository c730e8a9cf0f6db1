"""The Nemotron-3-Super-shaped trunk against the benchmark's plain reference.

``benchmark/reference/nemotron3-super-policy.py`` is written from the
model's equations in plain ``jax.numpy`` — the experts in their latent one
at a time, the state equation one token at a time — and reads the parameter
tree as data; it shares no code with ``relayrl_tpu/models`` or ``ops``. On
the chip the harness compares the two at the published widths
(``benchmark/configs/nemotron3-super-policy.json``'s tolerance); here the
same comparison runs at tiny widths on the CPU over the cut's layers, two
of its five pairs (what a suite's clock has room for; the published five are
checked by shape): an expert layer whose routed experts work in a latent
narrower than the stream (beside a shared expert at the stream's width) and
a Mamba-2 layer, then an attention layer without positions. What is new
against ``tests/test_nemotron_reference.py`` is tested here — the latent and
the heads held as a chip's share; the modes the two trunks share are tested
there.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from relayrl_tpu.models import build_policy, layers
from relayrl_tpu.models import transformer as trunk
from test_lfm2_reference import _all_logp_v, _by_path, _impala_loss

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "nemotron3-super-policy"
T = 16
PAIRS = 2
KINDS = ["ffn", "mamba2"] * PAIRS + ["attention"]


@pytest.fixture(scope="module")
def reference():
    return _by_path(f"benchmark/reference/{NAME}.py")


def _published():
    with open(os.path.join(REPO, f"benchmark/configs/{NAME}.json")) as f:
        return json.load(f)


def _tiny(**over):
    cfg = _published()
    # tiny widths; every mechanism of the published trunk: experts 4-7 of 16
    # held, top-3, in a latent of 10 under a stream of 24, the 5; 4 Mamba
    # heads of 8 in 2 groups, state 8, chunks of 8 (two a sequence); 4 q
    # heads of 8 over 1 k/v head
    cfg.update(hybrid_override_pattern="EM" * PAIRS + "*",
               num_hidden_layers=2 * PAIRS + 1,
               hidden_size=24, mamba_num_heads=4, mamba_head_dim=8,
               ssm_state_size=8, n_groups=2, chunk_size=8, head_dim=8,
               num_attention_heads=4, num_key_value_heads=1,
               moe_intermediate_size=12, moe_latent_size=10,
               moe_shared_expert_intermediate_size=20, n_routed_experts=4,
               held_experts_first=4, num_experts_per_tok=3,
               published={"n_routed_experts": 16}, positions_as_run=T,
               attention="dense")
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def cfg():
    return _tiny()


def _system(reference, cfg, precision="float32", seed=0, **over):
    kwargs = {**reference.program_kwargs(cfg), **over}
    arch = {"kind": kwargs.pop("model_kind"), "obs_dim": cfg["obs_dim"],
            "act_dim": cfg["act_dim"], "has_critic": True,
            "precision": precision, **kwargs}
    policy = build_policy(arch)
    # (one program each, the init and the forwards below: op by op the
    # seven layers cost the suite's clock minutes and test nothing more)
    return policy, jax.jit(policy.init_params)(jax.random.PRNGKey(seed))


@pytest.fixture(scope="module")
def system(reference, cfg):
    """The float32 trunk and its seeded weights, built once."""
    return _system(reference, cfg)


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
    def test_the_trunk_is_what_the_configuration_says(self, reference, cfg,
                                                      system):
        kwargs = reference.program_kwargs(cfg)
        assert kwargs["layer_types"] == KINDS
        assert kwargs["moe_latent"] == 10
        assert kwargs["block_checkpoint"] is True
        p = system[1]["params"]
        assert "pos_embed" not in p
        e, m = p["block_0"], p["block_1"]
        assert set(e) == {"ln_mlp", "moe"}
        moe = e["moe"]
        assert set(moe) == {"moe_gate", "moe_expert_bias", "moe_latent_down",
                            "moe_latent_up", "moe_w_up", "moe_w_down",
                            "moe_shared_up", "moe_shared_down"}
        # the routed experts in the latent: 4 held of 16, 10 wide under 24
        assert moe["moe_w_up"].shape == (4, 10, 12)
        assert moe["moe_w_down"].shape == (4, 12, 10)
        assert moe["moe_latent_down"]["kernel"].shape == (24, 10)
        assert moe["moe_latent_up"]["kernel"].shape == (10, 24)
        assert "bias" not in moe["moe_latent_down"]
        # the router and the shared expert at the stream's width
        assert moe["moe_gate"]["kernel"].shape == (24, 16)
        assert moe["moe_shared_up"]["kernel"].shape == (24, 20)
        assert m["mamba_in"].shape == (24, 32 + 32 + 32 + 4)
        assert set(p[f"block_{2 * PAIRS}"]) == {
            "ln_attn", "q_proj", "k_proj", "v_proj", "attn_out"}

    # float32: both sides compute the same sums in another order. bfloat16:
    # at these widths a token whose 3rd and 4th scores tie within the error
    # moves its whole expert output, times 5, so the bulk of the tokens is
    # compared (their median). Held 4-7 of 16, and every expert held.
    @pytest.mark.parametrize("precision,over_tokens,atol,held", [
        ("float32", jnp.max, 3e-5, (4, 4)),
        ("float32", jnp.max, 3e-5, (0, 16)),
        ("bfloat16", jnp.median, 0.08, (4, 4))])
    def test_log_probabilities_and_values(self, reference, precision,
                                          over_tokens, atol, held):
        cfg = _tiny(held_experts_first=held[0], n_routed_experts=held[1])
        policy, params = _system(reference, cfg, precision)
        obs = _obs(cfg)
        logp, v = _outputs(policy, params, obs, cfg["act_dim"])
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
            np.testing.assert_allclose(g, flat_ref[path], atol=3e-5,
                                       rtol=2e-4, err_msg=name)
            # the correction bias enters the choice only: never moved
            assert (float(jnp.abs(g).max()) > 0) != (
                "moe_expert_bias" in name), name

    def test_cached_decode_through_the_held_state_is_the_full_forward(
            self, reference, cfg, system):
        """16 steps through the states — each held Mamba-2 share's last
        three rows of ``xBC`` and its ``[H, P, N]`` state at
        the HELD heads — beside the attention layer's pair and the expert
        layers' nothing; then the readout row of a window."""
        policy, params = system
        window = np.asarray(_obs(cfg, batch=1)[0])
        logp_ref, v_ref = reference.forward(params, window[None], cfg)
        cache = policy.init_cache(T)
        assert len(cache) == len(KINDS)
        step = jax.jit(policy.step_cached)
        for kind, c in zip(KINDS, cache):
            if kind == "mamba2":
                assert c[0].shape == (1, 3, 64)
                assert c[1].shape == (1, 4, 8, 8)
            elif kind == "ffn":
                assert c == ()
        for t in range(T):
            act, aux, cache = step(params, jax.random.PRNGKey(t), cache,
                                   window[t], t)
            np.testing.assert_allclose(float(aux["v"]), float(v_ref[0, t]),
                                       atol=3e-5, err_msg=f"t={t}")
            np.testing.assert_allclose(
                float(aux["logp_a"]), float(logp_ref[0, t, int(act)]),
                atol=3e-5, err_msg=f"t={t}")
        # a prefilled state continues likewise; a window's readout row
        t0 = 9
        padded = window.copy()
        padded[t0:] = 0.0
        cache = jax.jit(policy.prefill_cache)(
            params, policy.init_cache(T), jnp.asarray(padded), t0)
        _, aux, _ = step(params, jax.random.PRNGKey(0), cache, window[t0],
                         t0)
        np.testing.assert_allclose(float(aux["v"]), float(v_ref[0, t0]),
                                   atol=3e-5)
        _, aux = jax.jit(policy.step_window)(
            params, jax.random.PRNGKey(0), jnp.asarray(window), t0)
        np.testing.assert_allclose(float(aux["v"]), float(v_ref[0, t0 - 1]),
                                   atol=3e-5)

    @pytest.mark.parametrize("wrong", [
        {"latent": False},              # the experts fed u[:, :latent]
        {"scaling": 1.0},               # the 5 left out
        {"top_k": 2},                   # an expert dropped per token
        {"shared": False},              # no shared expert
        {"carry": False},               # the state dropped at chunk ends
    ])
    def test_a_wrong_reference_is_told_apart(self, reference, cfg, system,
                                             wrong):
        _, params = system
        obs = _obs(cfg)
        # (the right one is the system's to 3e-5: the tests above)
        assert _differs(reference.forward(params, obs, cfg),
                        reference.forward(params, obs, cfg,
                                          wrong=wrong)) > 1e-3

    def test_an_8_bit_trunk_is_further_off_than_bfloat16(self, reference,
                                                         cfg, system):
        _, params = system
        obs = _obs(cfg)
        exact = reference.forward(params, obs, cfg)
        errs = {}
        for name, dtype in (("bf16", jnp.bfloat16),
                            ("fp8", jnp.float8_e5m2)):
            lo = reference.forward(params, obs, cfg, operands=dtype)
            errs[name] = float(jnp.median(jnp.abs(lo[0] - exact[0]).max(-1)))
        assert errs["bf16"] * 4 < errs["fp8"], errs

    def test_the_reference_is_float32_at_highest_and_imports_no_model(self):
        with open(os.path.join(REPO,
                               f"benchmark/reference/{NAME}.py")) as f:
            code = f.read().split('"""', 2)[2]
        assert "relayrl_tpu.models.transformer" not in code
        assert "relayrl_tpu.models.moe" not in code
        assert "relayrl_tpu.ops" not in code and "flax" not in code
        assert 'jax.default_matmul_precision("highest")' in code
        assert "jax.lax.scan" in code       # the state equation, by step


class TestRefusals:
    @pytest.mark.parametrize("latent", [0, -8, 10.0, True])
    def test_a_latent_that_is_no_width(self, reference, cfg, latent):
        with pytest.raises(ValueError, match="moe_latent .*whole number"):
            _system(reference, cfg, moe_latent=latent)

    def test_the_pipeline_family_does_not_take_it(self):
        with pytest.raises(ValueError, match="moe_latent"):
            build_policy({"kind": "transformer_pp_discrete", "obs_dim": 4,
                          "act_dim": 2, "moe_latent": 8})

    def test_a_program_without_the_key_is_refused(self, reference, cfg,
                                                  monkeypatch):
        from relayrl_tpu.models import base

        monkeypatch.setattr(base, "ARCH_PASSTHROUGH_KEYS", tuple(
            k for k in base.ARCH_PASSTHROUGH_KEYS if k != "moe_latent"))
        with pytest.raises(SystemExit, match="moe_latent"):
            reference.program_kwargs(cfg)

    @pytest.mark.parametrize("key,value", [
        ("n_group", 8), ("topk_group", 4), ("n_shared_experts", 2),
        ("mlp_hidden_act", "silu")])
    def test_a_configuration_it_was_not_written_for_is_refused(
            self, reference, cfg, key, value):
        with pytest.raises(SystemExit, match=key):
            reference.program_kwargs({**cfg, key: value})


def _block(op, d, **arch):
    """One layer of the system alone: the operator ``op`` without an FFN."""
    arch = {"norm": "rms", "norm_eps": 1e-5, "use_bias": False, "ffn": "relu2",
            "positions": "none", "attention": "dense", **arch}
    kw = trunk._block_settings(arch)
    return trunk.TransformerBlock(
        d, 4, jnp.float32, op=op, cfg=trunk._operator_settings(arch)[op],
        fns=layers.resolve(arch, (op,))[0], has_ffn=False, **kw)


def _normal(rng, *shape, scale=1.0):
    return jnp.asarray(scale * rng.standard_normal(shape), jnp.float32)


class TestTheSharesAddUp:
    """64 chips share each layer: a mixer's heads over 4, the experts over
    all 64. The parts of a layer's result that the shares give — with what
    every chip computes alike (the shared expert) counted ONCE — add up to
    what the uncut reference gives for the whole layer, and no share alone
    is the layer."""

    D, CHIPS = 24, 4

    def _x(self, rng):
        return _normal(rng, 2, T, self.D)

    def test_the_four_head_shares_of_a_mamba2_layer(self, reference):
        rng = np.random.default_rng(0)
        H, P, N, G, taps, chunk = 8, 8, 8, 4, 4, 8
        inner, bc = H * P, G * N
        whole = {
            "ln_attn": {"scale": 1 + _normal(rng, self.D, scale=0.1)},
            "mamba_in": _normal(rng, self.D, 2 * inner + 2 * bc + H,
                                scale=0.2),
            "mamba_conv_w": _normal(rng, taps, inner + 2 * bc, scale=0.5),
            "mamba_conv_b": _normal(rng, inner + 2 * bc, scale=0.1),
            "mamba_dt_bias": _normal(rng, H),
            "mamba_A_log": _normal(rng, H, scale=0.5),
            "mamba_D": 1 + _normal(rng, H, scale=0.1),
            "mamba_norm": 1 + _normal(rng, inner, scale=0.1),
            "mamba_out": _normal(rng, inner, self.D, scale=0.2)}
        x = self._x(rng)
        with jax.default_matmul_precision("highest"):
            uncut = reference._mamba(whole, x, (H, P, N, G, taps, chunk),
                                     1e-5, None, True) - x
        hc, gc = H // self.CHIPS, G // self.CHIPS       # 2 heads, 1 group

        def cols(c):
            """Chip c's columns of ``[z | x | B | C | dt]``; without z and
            dt, shifted, its columns of the convolution's ``[x | B | C]``."""
            heads = np.arange(c * hc * P, (c + 1) * hc * P)
            groups = np.arange(c * gc * N, (c + 1) * gc * N)
            xbc = np.concatenate([inner + heads, 2 * inner + groups,
                                  2 * inner + bc + groups])
            dt = 2 * inner + 2 * bc + np.arange(c * hc, (c + 1) * hc)
            return heads, np.concatenate([heads, xbc, dt]), xbc - inner

        def share(c):
            heads, proj, conv = cols(c)
            of_heads = slice(c * hc, (c + 1) * hc)
            p = {"ln_attn": whole["ln_attn"],
                 "mamba_in": whole["mamba_in"][:, proj],
                 "mamba_conv_w": whole["mamba_conv_w"][:, conv],
                 "mamba_conv_b": whole["mamba_conv_b"][conv],
                 "mamba_dt_bias": whole["mamba_dt_bias"][of_heads],
                 "mamba_A_log": whole["mamba_A_log"][of_heads],
                 "mamba_D": whole["mamba_D"][of_heads],
                 "mamba_norm": whole["mamba_norm"][heads],
                 "mamba_out": whole["mamba_out"][heads]}
            # the program's own mixer at plain sizes: 2 heads in 1 group
            block = _block("mamba2", self.D, mamba_heads=hc,
                           mamba_head_dim=P, mamba_state=N, mamba_groups=gc,
                           mamba_conv_taps=taps, mamba_chunk=chunk)
            return block.apply({"params": p}, x) - x

        parts = [share(c) for c in range(self.CHIPS)]
        np.testing.assert_allclose(sum(parts), uncut, atol=3e-5, rtol=1e-5)
        assert float(jnp.abs(parts[0] - uncut).max()) > 1e-2

    def test_the_four_head_shares_of_an_attention_layer(self, reference):
        rng = np.random.default_rng(1)
        heads, kv, hd = 8, 2, 8
        whole = {"ln_attn": {"scale": 1 + _normal(rng, self.D, scale=0.1)},
                 **{name: {"kernel": _normal(rng, self.D, n * hd, scale=0.3)}
                    for name, n in (("q_proj", heads), ("k_proj", kv),
                                    ("v_proj", kv))},
                 "attn_out": {"kernel": _normal(rng, heads * hd, self.D,
                                                scale=0.2)}}
        x = self._x(rng)
        with jax.default_matmul_precision("highest"):
            uncut = reference._attention(whole, x, heads, kv, hd, 1e-5,
                                         None) - x
        per = heads // self.CHIPS       # 2 q heads and the k/v head they read

        def share(c):
            q = slice(c * per * hd, (c + 1) * per * hd)
            of_kv = c * per // (heads // kv)
            k = slice(of_kv * hd, (of_kv + 1) * hd)
            p = {"ln_attn": whole["ln_attn"],
                 "q_proj": {"kernel": whole["q_proj"]["kernel"][:, q]},
                 "k_proj": {"kernel": whole["k_proj"]["kernel"][:, k]},
                 "v_proj": {"kernel": whole["v_proj"]["kernel"][:, k]},
                 "attn_out": {"kernel": whole["attn_out"]["kernel"][q]}}
            block = _block("attention", self.D, n_heads=per, n_kv_heads=1,
                           head_dim=hd)
            return block.apply({"params": p}, x) - x

        parts = [share(c) for c in range(self.CHIPS)]
        np.testing.assert_allclose(sum(parts), uncut, atol=3e-5, rtol=1e-5)
        assert float(jnp.abs(parts[0] - uncut).max()) > 1e-2

    def test_the_64_expert_shares_of_a_latent_expert_layer(self, reference):
        from relayrl_tpu.models.moe import MoEMLP

        E, K, latent, ff, shared, chips = 128, 22, 10, 12, 20, 64

        def layer(held, with_shared, dispatch="dense"):
            return MoEMLP(self.D, ff, E, K, jnp.float32, norm_topk_prob=True,
                          ffn="relu2", use_bias=False, router="sigmoid",
                          expert_bias=True, routed_scaling=5.0, held=held,
                          shared_d_ff=shared if with_shared else None,
                          latent=latent, dispatch=dispatch)

        rng = np.random.default_rng(2)
        u = _normal(rng, 1, T, self.D)
        # the reference's RMSNorm before the experts made the identity
        u = u * jax.lax.rsqrt(jnp.mean(jnp.square(u), -1, keepdims=True))
        whole = layer(None, True).init(jax.random.PRNGKey(2), u)["params"]
        per = E // chips
        no_shared = {k: v for k, v in whole.items() if "shared" not in k}

        def share(c, with_shared=False, dispatch="dense"):
            p = {**(whole if with_shared else no_shared),
                 **{n: whole[n][per * c:per * (c + 1)]
                    for n in ("moe_w_up", "moe_w_down")}}
            return layer((per * c, per), with_shared, dispatch).apply(
                {"params": p}, u)

        # every expert on every token, 62 times over: the dense path, which
        # is the row buffers' sums in another order (two shares through both)
        routed = [share(c) for c in range(chips)]
        for c in (0, 37):
            np.testing.assert_allclose(share(c, dispatch="sparse"),
                                       routed[c], atol=3e-5)
        once = share(0, True) - routed[0]   # what every chip computes alike
        with jax.default_matmul_precision("highest"):
            blk = {"ln_mlp": {"scale": jnp.ones((self.D,))}, "moe": whole}
            uncut, bare = (reference._experts(
                blk, u, 0.0, K, 5.0, 0, E, s, None) - u
                for s in (True, False))
        np.testing.assert_allclose(sum(routed) + once, uncut, atol=5e-5,
                                   rtol=1e-5)
        np.testing.assert_allclose(once, uncut - bare, atol=3e-5, rtol=1e-5)
        # counted 64 times it is not the layer; and no share is
        assert float(jnp.abs(sum(routed) + chips * once - uncut).max()
                     ) > 1e-2
        assert float(jnp.abs(routed[0] + once - uncut).max()) > 1e-3


class TestTheConfigurationFile:
    def test_every_width_is_the_catalog_rows(self):
        c = _published()
        # the source's config.json, as the catalog row gives it
        published = {
            "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
            "expand": 2, "head_dim": 128, "hidden_size": 4096,
            "hybrid_override_pattern": (
                "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                "EMEMEMEMEM*EMEMEMEM*EMEMEMEME"),
            "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
            "mamba_head_dim": 64, "mamba_hidden_act": "silu",
            "mamba_num_heads": 128, "mamba_proj_bias": False,
            "max_position_embeddings": 262144, "mlp_bias": False,
            "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
            "moe_intermediate_size": 2688, "moe_latent_size": 1024,
            "moe_shared_expert_intermediate_size": 5376,
            "moe_shared_expert_overlap": False,
            "mtp_hybrid_override_pattern": "*E", "n_group": 1, "n_groups": 8,
            "n_routed_experts": 512, "n_shared_experts": 1,
            "norm_eps": 1e-05, "norm_topk_prob": True,
            "num_attention_heads": 32, "num_experts_per_tok": 22,
            "num_hidden_layers": 88, "num_key_value_heads": 2,
            "num_logits_to_keep": 1, "num_nextn_predict_layers": 1,
            "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
            "residual_in_fp32": False, "rope_theta": 10000,
            "routed_scaling_factor": 5, "sliding_window": None,
            "ssm_state_size": 128, "tie_word_embeddings": False,
            "time_step_floor": 0.0001, "time_step_max": 0.1,
            "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
            "use_conv_bias": True, "use_mamba_kernels": True,
            "vocab_size": 131072}
        reduced = ["hybrid_override_pattern", "num_hidden_layers",
                   "mamba_num_heads", "n_groups", "num_attention_heads",
                   "num_key_value_heads", "n_routed_experts"]
        assert c["reduced"] == reduced == list(c["reduced_why"])
        assert {k: c[k] for k in published if k not in reduced} == {
            k: v for k, v in published.items() if k not in reduced}
        assert c["published"] == {k: published[k] for k in reduced}
        assert [c[k] for k in reduced] == ["EMEMEMEMEM*", 11, 32, 2, 8, 1, 8]
        whole = c["published"]["hybrid_override_pattern"]
        assert len(whole) == 88
        assert [whole.count(x) for x in "ME*"] == [40, 40, 8]
        # one period in the pattern's own ratio, letters 27-37 of the 88
        assert whole[26:37] == c["hybrid_override_pattern"]
        # the held share: 4 chips a mixer's heads, each held group whole
        assert c["published"]["mamba_num_heads"] // c["mamba_num_heads"] == 4
        assert c["published"]["n_groups"] // c["n_groups"] == 4
        assert (c["published"]["num_attention_heads"]
                // c["num_attention_heads"]) == 4
        assert "64 chips share each layer" in c["deployment"]
        assert "not built" in c["departures"]["multi_token_prediction"]
        # the names the unedited readers use
        assert c["n_embd"] // c["n_head"] == c["head_dim"]
        assert c["num_hidden_layers"] - c["num_dense_layers"] == (
            c["hybrid_override_pattern"].count("E"))

    def test_parameters_as_run_are_the_programs_tree(self, reference):
        c = _published()
        kwargs = reference.program_kwargs(c)
        arch = {"kind": kwargs.pop("model_kind"), "obs_dim": c["obs_dim"],
                "act_dim": c["act_dim"], "has_critic": True, **kwargs}
        shapes = jax.eval_shape(build_policy(arch).init_params,
                                jax.random.PRNGKey(0))
        p = shapes["params"]
        count = lambda tree: sum(x.size
                                 for x in jax.tree_util.tree_leaves(tree))
        e, m = p["block_0"], p["block_1"]
        assert count(m) == 27_413_088                  # M at 32 held heads
        assert count(p["block_10"]) == 9_441_280       # * at 8 q / 1 k/v
        expert_layer = (2_097_152 + 512 + 2 * 4_194_304 + 44_040_192
                        + 8 * 5_505_024 + 4_096)
        assert count(e) == expert_layer == 98_570_752
        # the stacks keep the published widths, in the latent
        assert e["moe"]["moe_w_up"].shape == (8, 1024, 2688)
        assert e["moe"]["moe_w_down"].shape == (8, 2688, 1024)
        assert m["mamba_in"].shape == (4096, 2048 + 2048 + 512 + 32)
        layers_ = 5 * 27_413_088 + 5 * expert_layer + 9_441_280
        assert layers_ == 639_360_480
        # + embedding, final norm, policy head, the value head's two layers
        ends = 77_824 + 4_096 + 65_552 + 16_781_312 + 4_097
        assert count(shapes) == layers_ + ends == c["parameters_as_run"]
        assert c["parameters_as_run"] == 656_293_361
