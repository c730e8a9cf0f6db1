"""The OLMoE-shaped block against the benchmark's plain reference, the
defaults against the block they have always built, and the import guard.

``benchmark/reference/olmoe-policy.py`` is written from the model's
equations in plain ``jax.numpy`` and reads the parameter tree as data; it
shares no code with ``relayrl_tpu/models``. On the chip the harness compares
the two at the published widths (``benchmark/configs/olmoe-policy.json``'s
tolerance); here the same comparison runs at tiny widths on the CPU.
"""

import importlib.util
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from relayrl_tpu.models import build_policy

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _by_path(rel):
    sys.path.insert(0, REPO) if REPO not in sys.path else None
    path = os.path.join(REPO, rel)
    spec = importlib.util.spec_from_file_location(
        "olmoe_test_" + os.path.basename(rel).replace("-", "_")[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def reference():
    return _by_path("benchmark/reference/olmoe-policy.py")


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(REPO, "benchmark/configs/olmoe-policy.json")) as f:
        cfg = json.load(f)
    # tiny widths, two layers; every mechanism of the published block
    cfg.update(hidden_size=32, num_attention_heads=4, num_experts=8,
               num_experts_per_tok=2, intermediate_size=16,
               max_position_embeddings=16, num_hidden_layers=2,
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
    def one(a):
        logp, _ent, v = policy.evaluate(
            params, obs, jnp.full(obs.shape[:-1], a, jnp.int32))
        return logp, v

    logp, v = jax.vmap(one)(jnp.arange(act_dim))
    return jnp.moveaxis(logp, 0, -1), v[0]


def _obs(cfg, seed=1):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(
        (2, 16, cfg["obs_dim"])), jnp.float32)


class TestSystemAgainstReference:
    # float32: both sides compute the same sums in another order — 1e-5 on
    # log-probabilities and values of order 1. bfloat16: the system rounds
    # the operands of its projections, attention and experts to 8 bits of
    # mantissa (relative 2^-9 each), two layers deep: measured 0.014 /
    # 0.007 here, bound 0.04 — and the SAME reference with its operands
    # rounded to float8 (3 bits) must fall outside it.
    @pytest.mark.parametrize("precision,atol", [("float32", 1e-5),
                                                ("bfloat16", 0.04)])
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

    def test_an_8_bit_trunk_fails_the_bfloat16_bound(self, reference, cfg,
                                                     want):
        _, params = _system(reference, cfg, "float32")
        obs, exact = _obs(cfg), want
        errs = {}
        for name, dtype in (("bf16", jnp.bfloat16),
                            ("fp8", jnp.float8_e4m3fn)):
            lo = reference.forward(params, obs, cfg, operands=dtype)
            errs[name] = max(float(jnp.abs(lo[0] - exact[0]).max()),
                             float(jnp.abs(lo[1] - exact[1]).max()))
        assert errs["bf16"] < 0.04 < errs["fp8"], errs

    @pytest.mark.parametrize("wrong", [
        {"moe_norm_topk_prob": True},      # renormalised top-k weights
        {"moe_top_k": 1},                  # an expert dropped per token
        {"rope_theta": 100.0}, {"norm_eps": 1e-2}])
    def test_a_different_model_is_told_apart(self, reference, cfg, want,
                                             wrong):
        # the float32 comparison is tight enough to catch each departure
        _, params = _system(reference, cfg, "float32")
        other = _program(reference, cfg, "float32", **wrong)
        logp, v = _all_logp_v(other, params, _obs(cfg), cfg["act_dim"])
        logp_ref, v_ref = want
        assert float(jnp.abs(logp - logp_ref).max()) > 1e-3

    def test_reference_imports_nothing_of_the_models(self):
        with open(os.path.join(
                REPO, "benchmark/reference/olmoe-policy.py")) as f:
            text = f.read()
        assert "relayrl_tpu.models.transformer" not in text
        assert "relayrl_tpu.models.moe" not in text
        assert "flax" not in text.split('"""', 2)[2]


class TestRoutedComparison:
    """``benchmark/drivers/update_routed.py``: the cell's second comparison,
    by a statistic that a handful of tokens cannot set."""

    LIMITS = (1e-3, 1e-3)

    @pytest.fixture(scope="class")
    def driver(self):
        return _by_path("benchmark/drivers/update_routed.py")

    # tokens of 400 that differ by a whole expert's share -> whether the
    # error all but 1% of the tokens stay under is still rounding's
    @pytest.mark.parametrize("wrong,passes", [
        (0, True),          # rounding alone
        (3, True),          # a few tokens routed differently: under 1%
        (8, False),         # 2% of the tokens: no longer a handful
        (400, False),       # every token (a layer that drops an expert)
    ])
    def test_a_handful_of_tokens_cannot_set_it_and_the_bulk_can(
            self, driver, wrong, passes):
        rng = np.random.default_rng(0)
        logp = np.log(rng.dirichlet(np.ones(4), (1, 400)))
        v = rng.standard_normal((1, 400))
        rounding = rng.standard_normal(v.shape) * 1e-4
        logp_sys, v_sys = logp + rounding[..., None], v + rounding
        v_sys[0, :wrong] += 0.1
        logp_sys[0, :wrong] += 0.1
        got = driver.routed_errors(logp_sys, v_sys, logp, v, 0.99,
                                   self.LIMITS)
        assert (got["rel_dlogp"] <= self.LIMITS[0]
                and got["rel_dv"] <= self.LIMITS[1]) == passes
        assert got["tokens"] == 400 and got["flipped_tokens"] == wrong

    def test_tolerance_names_what_the_driver_reads(self):
        with open(os.path.join(
                REPO, "benchmark/configs/olmoe-policy.json")) as f:
            tol = json.load(f)["tolerance"]
        with open(os.path.join(
                REPO, "benchmark/traffic/impala-seq4k-batch.json")) as f:
            assert json.load(f)["driver"] == "update_routed"
        routed = tol["routed"]
        assert set(routed) == {"quantile", "logp_rel", "value_rel"}
        # the bulk of the tokens is held an order of magnitude tighter
        # than the one token that may route differently
        assert routed["logp_rel"] * 10 <= tol["logp_rel"]
        assert routed["value_rel"] * 10 <= tol["value_rel"]
        assert 0.95 <= routed["quantile"] < 1.0

    def test_system_against_reference_by_the_quantile(self, driver,
                                                      reference, cfg):
        # tiny widths, bfloat16 system, 4 x the usual sample so that a
        # quantile means something: the bulk of the tokens differs by
        # rounding; the same reference with every token's last expert
        # dropped, or on float8 operands, is told apart at the same limit
        policy, params = _system(reference, cfg, "bfloat16")
        obs = jnp.asarray(np.random.default_rng(3).standard_normal(
            (8, 16, cfg["obs_dim"])), jnp.float32)
        exact = reference.forward(params, obs, cfg)
        limits = (0.02, 0.02)

        def reads(outputs):
            got = driver.routed_errors(*outputs, *exact, 0.9, limits)
            return max(got["rel_dlogp"], got["rel_dv"]), got

        worst, got = reads(_all_logp_v(policy, params, obs, cfg["act_dim"]))
        assert worst < 0.02 and got["flipped_tokens"] < 13, got
        for other in (
                reference.forward(params, obs,
                                  {**cfg, "num_experts_per_tok": 1}),
                reference.forward(params, obs, cfg,
                                  operands=jnp.float8_e5m2)):
            assert reads(other)[0] > 0.02, reads(other)


class TestShapeArithmetic:
    def test_flops_per_token_at_the_published_widths(self):
        flops_moe = _by_path("benchmark/flops_moe.py")
        d, t, e, k, ff = 2048, 4096, 64, 8, 1024
        per_layer = flops_moe.moe_transformer_fwd_flops(
            1, t, 0, -1, d, 1, e, k, ff)
        assert per_layer == 8 * d * d + 2 * d * t + 2 * d * e + k * 6 * d * ff
        assert per_layer == 151_257_088          # ISSUE 27: "151 MFLOP"
        assert k * 6 * d * ff == 100_663_296     # the experts' 2/3 of it

    def test_grouped_matmul_counts(self):
        flops_moe = _by_path("benchmark/flops_moe.py")
        ops, nbytes = flops_moe.grouped_matmul_train_ops_bytes(
            16384, 8, 2048, 1024, 64)
        m = 16384 * 8
        assert ops == 9 * 2 * m * 2048 * 1024
        assert nbytes == 9 * 2 * (m * 2048 + m * 1024 + 64 * 2048 * 1024)
        # compute-bound on a v5e: operations / 197e12 over bytes / 819e9
        assert ops / 197e12 > nbytes / 819e9

    def test_published_widths_in_the_configuration_file(self):
        with open(os.path.join(
                REPO, "benchmark/configs/olmoe-policy.json")) as f:
            c = json.load(f)
        published = {
            "hidden_size": 2048, "num_attention_heads": 16,
            "num_key_value_heads": 16, "num_experts": 64,
            "num_experts_per_tok": 8, "intermediate_size": 1024,
            "hidden_act": "silu", "norm_topk_prob": False,
            "rms_norm_eps": 1e-5, "rope_theta": 10000,
            "max_position_embeddings": 4096, "attention_bias": False,
            "clip_qkv": None, "rope_scaling": None, "vocab_size": 50304,
            "tie_word_embeddings": False, "model_type": "olmoe"}
        assert {k: c[k] for k in published} == published
        assert c["reduced"] == ["num_hidden_layers"]
        assert c["num_hidden_layers"] == 1


# -- defaults: the GPT-2 shaped block, as before this family took arch keys --
# Pinned against the parent commit's code path (values computed there with
# the same seeds, PR 27): the parameter tree leaf for leaf, and outputs to
# float32 rounding.
_GPT2_ARCH = {"kind": "transformer_discrete", "obs_dim": 6, "act_dim": 3,
              "d_model": 32, "n_layers": 2, "n_heads": 4, "max_seq_len": 16}
_GPT2_LEAVES = sorted(
    [f"block_{i}/{name}/{leaf}" for i in (0, 1)
     for name in ("attn_out", "mlp_down", "mlp_up", "qkv")
     for leaf in ("bias", "kernel")]
    + [f"block_{i}/{name}/{leaf}" for i in (0, 1)
       for name in ("ln_attn", "ln_mlp") for leaf in ("bias", "scale")]
    + ["ln_final/bias", "ln_final/scale", "obs_embed/bias",
       "obs_embed/kernel", "pi_head/bias", "pi_head/kernel", "pos_embed",
       "vf_head/bias", "vf_head/kernel", "vf_head_up/bias",
       "vf_head_up/kernel"])
_PARENT = {  # precision -> (sum logp, sum v, step_window logp_a, v) at t=7
    "float32": (-55.82709884643555, 0.9691379070281982,
                -0.2446182668209076, -0.4081611931324005),
    "bfloat16": (-55.76506805419922, 0.9858989715576172,
                 -0.245941624045372, -0.40379244089126587),
}


class TestDefaultsUnchanged:
    def test_parameter_tree(self):
        params = build_policy(_GPT2_ARCH).init_params(jax.random.PRNGKey(3))
        leaves = sorted(
            "/".join(str(getattr(k, "key", k)) for k in path[1:])
            for path, _ in jax.tree_util.tree_flatten_with_path(params)[0])
        assert leaves == _GPT2_LEAVES
        assert params["params"]["block_0"]["mlp_up"]["kernel"].shape == (
            32, 128)

    @pytest.mark.parametrize("precision", sorted(_PARENT))
    def test_outputs_equal_the_parents(self, precision):
        policy = build_policy({**_GPT2_ARCH, "precision": precision})
        params = policy.init_params(jax.random.PRNGKey(3))
        obs = jnp.asarray(np.random.default_rng(0).standard_normal(
            (2, 16, 6)), jnp.float32)
        logp, _ent, v = policy.evaluate(params, obs,
                                        jnp.zeros((2, 16), jnp.int32))
        _a, aux = policy.step_window(params, jax.random.PRNGKey(1), obs[0], 7)
        got = (float(logp.sum()), float(v.sum()), float(aux["logp_a"]),
               float(aux["v"]))
        # same operations in the same order: equal to the last float32 bit
        # on the machine the pins were taken on; 1e-5 leaves room for
        # another CPU's vectorisation
        np.testing.assert_allclose(got, _PARENT[precision], rtol=1e-5,
                                   atol=1e-5)


# -- nothing of the expert layer on anyone else's path -------------------------

_GUARD = r"""
import sys
import relayrl_tpu.models
import relayrl_tpu.algorithms
from relayrl_tpu.algorithms import build_algorithm
algo = build_algorithm(
    "IMPALA", obs_dim=12 * 12 * 2, act_dim=3, obs_shape=[12, 12, 2],
    conv_spec=[[8, 4, 2], [8, 3, 1]], dense=16, traj_per_epoch=2,
    bucket_lengths=[8], env_dir=sys.argv[1],
    logger_kwargs={"output_dir": sys.argv[1]})
assert algo.arch["kind"] == "cnn_discrete"
loaded = [m for m in sys.modules
          if m.startswith("jax.experimental.pallas")
          or m in ("relayrl_tpu.models.moe", "relayrl_tpu.ops.flash",
                   "relayrl_tpu.ops.grouped_matmul")]
print("LOADED", loaded)
"""


def test_a_pixel_learner_loads_neither_pallas_nor_the_expert_layer(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    out = subprocess.run([sys.executable, "-c", _GUARD, str(tmp_path)],
                         capture_output=True, text=True, env=env,
                         cwd=str(tmp_path), timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "LOADED []" in out.stdout, out.stdout[-500:]
