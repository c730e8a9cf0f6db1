"""The LFM2-shaped trunk against the benchmark's plain reference.

``benchmark/reference/lfm2-policy.py`` is written from the model's equations
in plain ``jax.numpy`` and reads the parameter tree as data; it shares no
code with ``relayrl_tpu/models``. On the chip the harness compares the two
at the published widths (``benchmark/configs/lfm2-policy.json``'s
tolerance); here the same comparison runs at tiny widths on the CPU, for a
trunk with all three kinds of layer — dense-conv, attention-expert,
conv-expert — grouped-query heads, a non-zero ``expert_bias`` and a held
range that is not the first.
"""

import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from relayrl_tpu.models import build_policy
from relayrl_tpu.ops.vtrace import vtrace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _by_path(rel):
    sys.path.insert(0, REPO) if REPO not in sys.path else None
    path = os.path.join(REPO, rel)
    spec = importlib.util.spec_from_file_location(
        "lfm2_test_" + os.path.basename(rel).replace("-", "_")[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def reference():
    return _by_path("benchmark/reference/lfm2-policy.py")


def _published():
    with open(os.path.join(REPO, "benchmark/configs/lfm2-policy.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    cfg = _published()
    # tiny widths; every mechanism of the published trunk: experts 4-6 of
    # 8 held, top-2, 4 q heads over 2 k/v heads of 8
    cfg.update(hidden_size=32, intermediate_size=48, moe_intermediate_size=16,
               num_attention_heads=4, num_key_value_heads=2, num_experts=3,
               held_experts_first=4, published={"num_experts": 8},
               num_experts_per_tok=2, num_hidden_layers=4,
               layer_types=["conv", "full_attention", "conv", "conv"],
               positions_as_run=16, attention="dense")
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


def _all_logp_v(policy, params, obs, act_dim):
    @jax.jit
    def every_action(params, obs):
        def one(a):
            logp, _ent, v = policy.evaluate(
                params, obs, jnp.full(obs.shape[:-1], a, jnp.int32))
            return logp, v

        logp, v = jax.vmap(one)(jnp.arange(act_dim))
        return jnp.moveaxis(logp, 0, -1), v[0]

    return every_action(params, obs)


def _obs(cfg, seed=1, batch=2):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(
        (batch, 16, cfg["obs_dim"])), jnp.float32)


def _impala_loss(logp_all, v, batch):
    """IMPALA's loss (``algorithms/impala.make_impala_update``'s
    ``loss_fn``) from the log-probabilities of all actions and the values:
    what either side's forward feeds it."""
    act, rew, valid = batch["act"], batch["rew"], batch["valid"]
    logp = jnp.take_along_axis(logp_all, act[..., None], -1)[..., 0]
    ent = -jnp.sum(jnp.exp(logp_all) * logp_all, -1)
    n_valid = jnp.maximum(valid.sum(), 1.0)
    vt = vtrace(batch["logp"], jax.lax.stop_gradient(logp), rew,
                jax.lax.stop_gradient(v), valid, 0.99,
                last_val=batch["last_val"], rho_bar=1.0, c_bar=1.0)
    pg = -jnp.sum(logp * vt.pg_adv * valid) / n_valid
    vf = jnp.sum(jnp.square(v - vt.vs) * valid) / n_valid
    return pg + 0.5 * vf - 0.01 * jnp.sum(ent * valid) / n_valid


def _batch(cfg, seed=2):
    rng = np.random.default_rng(seed)
    shape = (2, 16)
    return {"act": jnp.asarray(rng.integers(0, cfg["act_dim"], shape)),
            "rew": jnp.asarray((rng.random(shape) < 0.2), jnp.float32),
            "valid": jnp.ones(shape, jnp.float32),
            "logp": jnp.full(shape, -np.log(cfg["act_dim"]), jnp.float32),
            "last_val": jnp.zeros((2,), jnp.float32)}


class TestSystemAgainstReference:
    def test_the_trunk_has_all_three_kinds_of_layer(self, reference, cfg):
        _, params = _system(reference, cfg, "float32")
        p = params["params"]
        kinds = [("conv_in" in p[f"block_{i}"], "moe" in p[f"block_{i}"])
                 for i in range(4)]
        assert kinds == [(True, False), (False, True), (True, True),
                         (True, True)]
        moe = p["block_1"]["moe"]
        assert moe["moe_w_up"].shape == (3, 32, 16)        # 3 held of 8
        assert moe["moe_gate"]["kernel"].shape == (32, 8)  # routed over 8
        assert float(jnp.abs(moe["moe_expert_bias"]).min()) > 0
        assert p["block_1"]["k_proj"]["kernel"].shape == (32, 16)
        assert p["block_1"]["q_norm"]["scale"].shape == (8,)  # per head

    # float32: both sides compute the same sums in another order. bfloat16:
    # the system rounds the operands of its projections, attention, FFN and
    # experts to 8 bits of mantissa, four layers deep, and at these widths
    # a token whose 2nd and 3rd scores tie within that error moves its
    # whole expert output: measured 0.054 / 0.022, bound 0.15.
    @pytest.mark.parametrize("precision,atol", [("float32", 2e-5),
                                                ("bfloat16", 0.15)])
    def test_log_probabilities_and_values(self, reference, cfg, got, want,
                                          precision, atol):
        if precision != "float32":
            policy, params = _system(reference, cfg, precision)
            obs = _obs(cfg)
            got = _all_logp_v(policy, params, obs, cfg["act_dim"])
            want = reference.forward(params, obs, cfg)
        (logp, v), (logp_ref, v_ref) = got, want
        assert float(jnp.abs(logp - logp_ref).max()) < atol
        assert float(jnp.abs(v - v_ref).max()) < atol

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
            if "moe_expert_bias" in name:
                # in the choice only: exactly zero, on both sides
                assert float(jnp.abs(g).max()) == 0.0
                assert float(jnp.abs(flat_ref[path]).max()) == 0.0
            else:
                assert float(jnp.abs(g).max()) > 0, name

    @pytest.mark.parametrize("wrong", [
        {"top_k": 1},                    # an expert dropped per token
        {"norm_topk_prob": False},       # un-normalised weights
        {"use_expert_bias": False},      # the bias left out of the choice
    ])
    def test_a_wrong_router_is_told_apart(self, reference, cfg, got, wrong):
        _, params = _system(reference, cfg, "float32")
        logp, v = got
        logp_w, v_w = reference.forward(params, _obs(cfg), cfg, wrong=wrong)
        assert max(float(jnp.abs(logp - logp_w).max()),
                   float(jnp.abs(v - v_w).max())) > 1e-3

    @pytest.mark.parametrize("wrong", [
        {"rope_theta": 100.0}, {"norm_eps": 1e-2}, {"qk_norm": True},
        {"layer_types": ["conv", "conv", "full_attention", "conv"]},
        {"moe_held": [3, 3]}, {"moe_norm_topk_prob": False}])
    def test_a_different_model_is_told_apart(self, reference, cfg, want,
                                             wrong):
        _, params = _system(reference, cfg, "float32")
        try:
            other = _program(reference, cfg, "float32", **wrong)
            logp, v = _all_logp_v(other, params, _obs(cfg), cfg["act_dim"])
        except Exception:  # another parameter tree altogether
            return
        logp_ref, v_ref = want
        assert max(float(jnp.abs(logp - logp_ref).max()),
                   float(jnp.abs(v - v_ref).max())) > 1e-3

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

    def test_reference_imports_nothing_of_the_models(self):
        with open(os.path.join(
                REPO, "benchmark/reference/lfm2-policy.py")) as f:
            text = f.read()
        assert "relayrl_tpu.models.transformer" not in text
        assert "relayrl_tpu.models.moe" not in text
        assert "flax" not in text.split('"""', 2)[2]


class TestTheSharesAddUp:
    """Eight chips share a layer, experts divided: the eight shares'
    expert-layer outputs sum to the UNCUT reference's layer output."""

    E, K, D, FF = 16, 4, 32, 16

    def _layer(self, held):
        from relayrl_tpu.models.moe import MoEMLP

        return MoEMLP(self.D, self.FF, self.E, self.K, jnp.float32,
                      norm_topk_prob=True, ffn="swiglu", use_bias=False,
                      router="sigmoid", expert_bias=True, held=held)

    # (slow: a second draw of the same statement; tier-1 keeps seed 0)
    @pytest.mark.parametrize("seed", [
        0, pytest.param(1, marks=pytest.mark.slow)])
    def test_against_the_uncut_reference(self, reference, seed):
        x = jnp.asarray(np.random.default_rng(seed).standard_normal(
            (2, 24, self.D)), jnp.float32)
        whole = self._layer(None).init(jax.random.PRNGKey(seed), x)["params"]
        parts = []
        for chip in range(8):
            share = dict(whole)
            for name in ("moe_w_gate", "moe_w_up", "moe_w_down"):
                share[name] = whole[name][2 * chip:2 * chip + 2]
            parts.append(self._layer((2 * chip, 2)).apply(
                {"params": share}, x))
        # the reference's expert layer, given every expert: its router
        # (the layer has no norm of its own: a unit-scale RMSNorm on
        # pre-normalised tokens is skipped by feeding h directly)
        h = x.reshape(-1, self.D)
        s = jax.nn.sigmoid(h @ whole["moe_gate"]["kernel"])
        biased = s + whole["moe_expert_bias"]
        kth = jax.lax.top_k(biased, self.K)[0][:, -1:]
        w = jnp.where(biased >= kth, s, 0.0)
        w = w / (w.sum(-1, keepdims=True) + 1e-6)
        with jax.default_matmul_precision("highest"):
            uncut = reference._experts(whole, h, w, None).reshape(x.shape)
        np.testing.assert_allclose(sum(parts), uncut, atol=2e-5, rtol=1e-5)
        # and no share is the whole: the cut is real
        assert float(jnp.abs(parts[0] - uncut).max()) > 1e-3


class TestShapeArithmetic:
    def test_forward_operations_a_token_at_the_published_widths(self):
        flops_lfm2 = _by_path("benchmark/flops_lfm2.py")
        cfg = _published()
        d, t = 2048, 8192
        attn = 2 * (2 * d * d + 2 * d * 512) + 2 * d * t
        conv = 2 * d * 3 * d + 2 * d * d
        dense = 6 * d * 11776
        held = 0.5 * 6 * d * 1536 + 2 * d * 64
        assert flops_lfm2.attention_fwd_flops(d, 32, 8, 64, t) == attn
        assert flops_lfm2.short_conv_fwd_flops(d) == conv == 33_554_432
        want = (4 * conv + attn + dense + 4 * held
                + 2 * 18 * d + 2 * d * 17)
        got = flops_lfm2.lfm2_fwd_flops_per_token(cfg, t)
        assert got == want
        assert round(got / 1e6) == 372          # ISSUE 31: "372 MFLOP"
        assert round(4 * conv / got, 2) == 0.36
        assert round(dense / got, 2) == 0.39
        assert round(attn / got, 2) == 0.15

    def test_published_widths_in_the_configuration_file(self):
        c = _published()
        published = {
            "hidden_size": 2048, "intermediate_size": 11776,
            "moe_intermediate_size": 1536, "num_attention_heads": 32,
            "num_key_value_heads": 8, "num_experts_per_tok": 4,
            "norm_topk_prob": True, "routed_scaling_factor": 1,
            "use_expert_bias": True, "conv_L_cache": 3, "conv_bias": False,
            "norm_eps": 1e-5, "max_position_embeddings": 128000,
            "vocab_size": 65536, "model_type": "lfm2_moe",
            "rope_parameters": {"rope_theta": 1000000,
                                "rope_type": "default"}}
        assert {k: c[k] for k in published} == published
        assert c["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                "num_experts"]
        assert (c["num_hidden_layers"], c["num_dense_layers"],
                c["num_experts"]) == (5, 1, 8)
        assert c["published"] == {"num_experts": 64, "num_hidden_layers": 40,
                                  "num_dense_layers": 2}
        assert c["layer_types"] == ["conv", "full_attention", "conv", "conv",
                                    "conv"]
        assert "8 chips share each layer" in c["deployment"]

    def test_the_published_trunk_holds_452_million_parameters(self,
                                                              reference):
        kwargs = reference.program_kwargs(_published())
        arch = {"kind": kwargs.pop("model_kind"), "obs_dim": 18,
                "act_dim": 16, "has_critic": True, **kwargs}
        shapes = jax.eval_shape(build_policy(arch).init_params,
                                jax.random.PRNGKey(0))
        n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
        assert 452e6 < n < 458e6, n        # + embedding, heads, norms
