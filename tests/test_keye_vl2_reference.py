"""The Keye-VL-2.0-shaped trunk against the benchmark's plain reference.

``benchmark/reference/keye-vl2-policy.py`` is written from the layer's
equations in plain ``jax.numpy`` — dense ``[T, T]`` index scores,
``lax.top_k``, a masked softmax — and reads the parameter tree as data; it
shares no code with ``relayrl_tpu/models`` or ``ops/sparse_attn.py`` (tiles
in stages, a threshold search, the loss sown row by row). On the chip the
harness compares the two at the published widths
(``benchmark/configs/keye-vl2-policy.json``'s tolerance); here the same
comparison runs at tiny widths on the CPU over two layers whose 32-token
sequences cross four tiles and select 8 of up to 32 keys. Full, readout-row
and cached modes, the indexer's loss and whose gradient moves what.
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
INDEXER = ("index_q", "index_k", "index_k_norm", "index_w")


@pytest.fixture(scope="module")
def reference():
    return _by_path("benchmark/reference/keye-vl2-policy.py")


def _published():
    with open(os.path.join(
            REPO, "benchmark/configs/keye-vl2-policy.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    cfg = _published()
    # tiny widths; every mechanism of the published trunk: 4 q heads of 8
    # over 2 k/v heads, an indexer of 2 heads of 8 over one key head that
    # keeps 8 keys a query in tiles of 8 queries, experts 4-7 of 16 held,
    # top-3
    cfg.update(hidden_size=24, head_dim=8, num_attention_heads=4,
               num_key_value_heads=2, moe_intermediate_size=12,
               num_experts=4, held_experts_first=4, num_experts_per_tok=3,
               published={"num_experts": 16}, positions_as_run=T,
               num_hidden_layers=2,
               sa_config={"indexer_head_dim": 8, "indexer_num_heads": 2,
                          "indexer_num_kv_heads": 1, "kv_chunk_size": 8,
                          "q_chunk_size": 8, "topk": 8})
    return cfg


_BUILT: dict = {}   # a policy and its seeded parameters, built once


def _program(reference, cfg, precision, **over):
    """The policy alone: a case that runs another program on the module's
    one tree seeds no tree of its own."""
    kwargs = {**reference.program_kwargs(cfg), **over}
    arch = {"kind": kwargs.pop("model_kind"), "obs_dim": cfg["obs_dim"],
            "act_dim": cfg["act_dim"], "has_critic": True,
            "precision": precision, **kwargs}
    return build_policy(arch)


def _system(reference, cfg, precision, seed=0, **over):
    key = (precision, seed, repr(sorted(over.items())))
    if key not in _BUILT:
        policy = _program(reference, cfg, precision, **over)
        _BUILT[key] = policy, jax.jit(policy.init_params)(
            jax.random.PRNGKey(seed))
    return _BUILT[key]


@pytest.fixture(scope="module")
def got(reference, cfg):
    """The float32 system's outputs on ``_obs(cfg)``, computed once."""
    return _outputs(*_system(reference, cfg, "float32"), _obs(cfg), cfg)


@pytest.fixture(scope="module")
def want(reference, cfg):
    """The reference's, from the same tree and rows."""
    _, params = _system(reference, cfg, "float32")
    return reference.forward(params, _obs(cfg), cfg)


def _obs(cfg, seed=1, batch=2, rows=T):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(
        (batch, rows, cfg["obs_dim"])), jnp.float32)


def _batch(cfg, seed=2):
    rng = np.random.default_rng(seed)
    shape = (2, T)
    valid = np.ones(shape, np.float32)
    valid[1, 27:] = 0.0     # one episode ends before the window does
    return {"act": jnp.asarray(rng.integers(0, cfg["act_dim"], shape)),
            "rew": jnp.asarray((rng.random(shape) < 0.2), jnp.float32),
            "valid": jnp.asarray(valid),
            "logp": jnp.full(shape, -np.log(cfg["act_dim"]), jnp.float32),
            "last_val": jnp.zeros((2,), jnp.float32)}


def _outputs(policy, params, obs, cfg):
    """``_all_logp_v`` as one program (the tiles' loop op by op is slow)."""
    return jax.jit(lambda p, o: _all_logp_v(policy, p, o, cfg["act_dim"]))(
        params, obs)


def _stats(policy, params, obs):
    *_, stats = jax.jit(policy.evaluate_stats)(
        params, obs, jnp.zeros(obs.shape[:2], jnp.int32))
    return stats


def _differs(a, b):
    return max(float(jnp.abs(a[0] - b[0]).max()),
               float(jnp.abs(a[1] - b[1]).max()))


def _is_indexer(path):
    return any(name in jax.tree_util.keystr(path) for name in INDEXER)


class TestSystemAgainstReference:
    def test_the_trunk_is_what_the_configuration_says(self, reference, cfg):
        kwargs = reference.program_kwargs(cfg)
        assert kwargs["layer_types"] == ["sparse_attention"] * 2
        policy, params = _system(reference, cfg, "float32")
        assert policy.own_loss == "IndexLoss"
        p = params["params"]
        assert "pos_embed" not in p
        layer = p["block_1"]
        assert set(layer) == {"ln_attn", "q_proj", "k_proj", "v_proj",
                              "q_norm", "k_norm", "attn_out", "ln_mlp",
                              "moe", *INDEXER}
        assert layer["q_proj"]["kernel"].shape == (24, 32)
        assert layer["k_proj"]["kernel"].shape == (24, 16)
        assert layer["q_norm"]["scale"].shape == (8,)
        assert layer["attn_out"]["kernel"].shape == (32, 24)
        # 2 heads of 8 over ONE LayerNormed key head, a weight a head
        assert layer["index_q"]["kernel"].shape == (24, 16)
        assert layer["index_k"]["kernel"].shape == (24, 8)
        assert set(layer["index_k_norm"]) == {"scale", "bias"}
        assert layer["index_w"]["kernel"].shape == (24, 2)
        moe = layer["moe"]
        assert set(moe) == {"moe_gate", "moe_w_gate", "moe_w_up",
                            "moe_w_down"}               # no shared expert
        assert moe["moe_w_up"].shape == (4, 24, 12)     # 4 held of 16
        assert moe["moe_gate"]["kernel"].shape == (24, 16)
        assert [jax.tree_util.keystr(path) for path, _ in
                jax.tree_util.tree_flatten_with_path(layer)[0]
                if "bias" in jax.tree_util.keystr(path)] == [
                    "['index_k_norm']['bias']"]

    # float32: both sides compute the same sums in another order, and the
    # same selection (the threshold search against lax.top_k): the largest
    # difference. bfloat16: the system rounds the operands of its
    # projections, index scores, attention and experts to 8 bits of
    # mantissa, and at these widths a token whose 3rd and 4th experts or
    # 8th and 9th keys tie within that error moves its whole output, so the
    # bulk of the tokens is compared: their median.
    @pytest.mark.parametrize("precision,over_tokens,atol", [
        ("float32", jnp.max, 1e-4), ("bfloat16", jnp.median, 0.06)])
    def test_log_probabilities_and_values(self, reference, cfg, got, want,
                                          precision, over_tokens, atol):
        if precision == "float32":
            (logp, v), (logp_ref, v_ref) = got, want
        else:
            policy, params = _system(reference, cfg, precision)
            obs = _obs(cfg)
            logp, v = _outputs(policy, params, obs, cfg)
            logp_ref, v_ref = reference.forward(params, obs, cfg)
        assert float(over_tokens(jnp.abs(logp - logp_ref).max(-1))) < atol
        assert float(over_tokens(jnp.abs(v - v_ref))) < atol

    def test_the_indexers_loss_is_the_references(self, reference, cfg):
        policy, params = _system(reference, cfg, "float32")
        obs, valid = _obs(cfg), _batch(cfg)["valid"]
        stats = _stats(policy, params, obs)
        rows = stats["own_loss_rows"]
        assert rows.shape == (2, T) and float(rows.min()) >= -1e-6
        np.testing.assert_allclose(
            float(jnp.sum(rows * valid) / valid.sum()),
            float(reference.index_loss(params, obs, cfg, valid)), atol=2e-6)
        # 8 keys a query past the first 8 rows, of 528 causal pairs, a layer
        kept = 36 + 24 * 8
        np.testing.assert_allclose(float(stats["index_kept_pct"]),
                                   100 * kept / 528, rtol=1e-6)

    def test_the_update_adds_the_loss_and_every_gradient(self, reference,
                                                         cfg):
        """``make_impala_update``'s loss — IMPALA's and the indexers' — and
        its gradient, against the reference's: the indexer's leaves move by
        the indexers' loss alone and are not zero, every other leaf by
        IMPALA's alone."""
        from relayrl_tpu.algorithms.impala import (
            ImpalaState,
            make_impala_tx,
            make_impala_update,
        )

        policy, params = _system(reference, cfg, "float32")
        obs, batch = _obs(cfg), _batch(cfg)
        ref_impala = lambda p: _impala_loss(
            *reference.forward(p, obs, cfg), batch)
        ref_index = lambda p: reference.index_loss(p, obs, cfg,
                                                   batch["valid"])
        (li, gi), (lx, gx) = (jax.jit(jax.value_and_grad(f))(params)
                              for f in (ref_impala, ref_index))
        # the system's: one plain SGD step of the real update shows its
        # gradient (lr 1, no clipping to speak of), its metrics the loss
        sys_loss = lambda p: _impala_loss(
            *_all_logp_v(policy, p, obs, cfg["act_dim"]), batch)
        gs_impala = jax.jit(jax.grad(sys_loss))(params)
        tx = make_impala_tx(1e-3, 1e9)
        update = make_impala_update(policy, 1e-3, 0.99, 0.5, 0.01, 1.0, 1.0,
                                    1e9)
        state = ImpalaState(params=params, opt_state=tx.init(params),
                            rng=jax.random.PRNGKey(0), step=jnp.int32(0))
        full = {**batch, "obs": obs,
                "act_mask": jnp.ones((2, T, cfg["act_dim"]), jnp.float32)}
        # (a copy is donated: ``params`` is read again below)
        new, metrics = jax.jit(update, donate_argnums=0)(
            jax.tree_util.tree_map(jnp.copy, state), full)
        np.testing.assert_allclose(float(metrics["IndexLoss"]), float(lx),
                                   atol=2e-5)
        np.testing.assert_allclose(float(metrics["LossTotal"]),
                                   float(li + lx), atol=2e-5)
        # Adam's first step moves a leaf by lr * sign(g) wherever g != 0
        moved = jax.tree_util.tree_map(lambda a, b: a - b, params,
                                       new.params)
        flat_i = dict(jax.tree_util.tree_flatten_with_path(gi)[0])
        flat_x = dict(jax.tree_util.tree_flatten_with_path(gx)[0])
        flat_moved = dict(jax.tree_util.tree_flatten_with_path(moved)[0])
        for path, g in jax.tree_util.tree_flatten_with_path(gs_impala)[0]:
            name = jax.tree_util.keystr(path)
            if _is_indexer(path):
                # IMPALA's loss hands the indexer nothing, on either side
                assert float(jnp.abs(g).max()) == 0.0, name
                assert float(jnp.abs(flat_i[path]).max()) == 0.0, name
                want = flat_x[path]
                assert float(jnp.abs(want).max()) > 0, name
            else:
                assert float(jnp.abs(flat_x[path]).max()) == 0.0, name
                want = flat_i[path]
                np.testing.assert_allclose(g, want, atol=2e-4, rtol=5e-4,
                                           err_msg=name)
                assert float(jnp.abs(g).max()) > 0, name
            big = jnp.abs(want) > 1e-5      # where Adam's sign is certain
            np.testing.assert_allclose(
                jnp.where(big, flat_moved[path], 0.0),
                jnp.where(big, 1e-3 * jnp.sign(want), 0.0), atol=2e-5,
                err_msg=name)

    def test_the_indexers_gradient_is_the_references(self, reference, cfg):
        policy, params = _system(reference, cfg, "float32")
        obs = _obs(cfg)

        def sys_index(p):
            *_, stats = policy.evaluate_stats(p, obs,
                                              jnp.zeros((2, T), jnp.int32))
            return stats["own_loss_rows"].mean()

        gs = jax.jit(jax.grad(sys_index))(params)
        gr = jax.jit(jax.grad(
            lambda p: reference.index_loss(p, obs, cfg)))(params)
        flat_ref = dict(jax.tree_util.tree_flatten_with_path(gr)[0])
        for path, g in jax.tree_util.tree_flatten_with_path(gs)[0]:
            np.testing.assert_allclose(
                g, flat_ref[path], atol=2e-5, rtol=5e-4,
                err_msg=jax.tree_util.keystr(path))
            assert (float(jnp.abs(g).max()) > 0) == _is_indexer(path)

    def test_the_readout_row_is_the_full_forwards_row(self, reference, cfg):
        policy, params = _system(reference, cfg, "float32")
        window = np.asarray(_obs(cfg, batch=1)[0])
        logp_ref, v_ref = reference.forward(params, window[None], cfg)
        step = jax.jit(policy.step_window)
        for t in (1, 8, 9, 20, T):      # before, at and past topk rows
            act, aux = step(params, jax.random.PRNGKey(t),
                            jnp.asarray(window), t)
            np.testing.assert_allclose(float(aux["v"]),
                                       float(v_ref[0, t - 1]), atol=3e-5)
            np.testing.assert_allclose(
                float(aux["logp_a"]), float(logp_ref[0, t - 1, int(act)]),
                atol=3e-5)

    def test_cached_decode_with_the_indexers_keys_is_the_full_forward(
            self, reference, cfg):
        """32 steps through the sixth kind of cache — each layer's ``(k,
        v)`` rows and, beside them, the indexer's key rows, all rotated
        before they go in —: every step selects among the rows up to its
        own and its value and log-probability equal the reference's full
        forward at that row."""
        policy, params = _system(reference, cfg, "float32")
        window = np.asarray(_obs(cfg, batch=1)[0])
        logp_ref, v_ref = reference.forward(params, window[None], cfg)
        cache = policy.init_cache(T)
        for k, v, ki in cache:
            assert k.shape == v.shape == (1, T, 2, 8)
            assert ki.shape == (1, T, 8)
        step = jax.jit(policy.step_cached)      # one program, 32 positions
        for t in range(T):
            act, aux, cache = step(
                params, jax.random.PRNGKey(t), cache, window[t], t)
            np.testing.assert_allclose(float(aux["v"]), float(v_ref[0, t]),
                                       atol=3e-5, err_msg=f"t={t}")
            np.testing.assert_allclose(
                float(aux["logp_a"]), float(logp_ref[0, t, int(act)]),
                atol=3e-5, err_msg=f"t={t}")
        assert float(jnp.abs(cache[0][2]).min()) > 0    # every row written

    @pytest.mark.parametrize("t0", [3, 19, T - 1])
    def test_a_prefilled_cache_continues_as_the_full_forward(
            self, reference, cfg, t0):
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

    def test_up_to_topk_rows_it_is_plain_causal_attention_bit_for_bit(
            self, reference, cfg):
        """A sequence no longer than ``topk`` selects every causal key: the
        trunk's outputs are those of the same weights under operator
        ``"attention"`` (dense), to the bit in float32."""
        policy, params = _system(reference, cfg, "float32")
        kwargs = reference.program_kwargs(cfg)
        plain = build_policy({
            "kind": kwargs.pop("model_kind"), "obs_dim": cfg["obs_dim"],
            "act_dim": cfg["act_dim"], "has_critic": True,
            "precision": "float32", **kwargs, "attention": "dense",
            "layer_types": ["full_attention"] * 2})
        less = {"params": {
            name: ({k: v for k, v in sub.items() if k not in INDEXER}
                   if name.startswith("block_") else sub)
            for name, sub in params["params"].items()}}
        assert jax.tree_util.tree_structure(less) == (
            jax.tree_util.tree_structure(
                jax.eval_shape(plain.init_params, jax.random.PRNGKey(0))))
        obs = _obs(cfg, rows=8)
        got = _outputs(policy, params, obs, cfg)
        want = _outputs(plain, less, obs, cfg)
        assert np.array_equal(got[0], want[0])
        assert np.array_equal(got[1], want[1])
        # ... and past topk rows, it is not
        obs = _obs(cfg, rows=T)
        assert _differs(_outputs(policy, params, obs, cfg),
                        _outputs(plain, less, obs, cfg)) > 1e-3

    @pytest.mark.parametrize("wrong", [
        {"select": False},              # plain causal attention
        {"topk": 4},                    # half the keys
        {"relu": False},                # the indexer's ReLU left out
        {"index_w": False},             # every head weighs 1
        {"per_head": True},             # a set a head
        {"qk_norm": False},             # q and k not normed
        {"top_k": 2},                   # an expert dropped per token
    ])
    def test_a_wrong_reference_is_told_apart(self, reference, cfg, got,
                                             wrong):
        _, params = _system(reference, cfg, "float32")
        assert _differs(got, reference.forward(params, _obs(cfg), cfg,
                                               wrong=wrong)) > 1e-3

    @pytest.mark.parametrize("chunk", [4, 16, 32, 5])
    def test_the_tile_is_no_part_of_the_model(self, reference, cfg, want,
                                              chunk):
        """``q_chunk_size`` / ``kv_chunk_size`` are read as the tile the
        scores are computed in: another tile (one that does not divide the
        sequence: one tile) gives the same outputs and the same loss."""
        _, params = _system(reference, cfg, "float32")
        other = _program(reference, cfg, "float32", index_chunk=chunk)
        obs = _obs(cfg)
        assert _differs(_outputs(other, params, obs, cfg), want) < 1e-4
        np.testing.assert_allclose(
            float(_stats(other, params, obs)["own_loss_rows"].mean()),
            float(reference.index_loss(params, obs, cfg)), atol=2e-6)

    @pytest.mark.parametrize("wrong", [
        {"index_topk": 4}, {"moe_top_k": 2}, {"moe_held": [3, 4]},
        {"moe_norm_topk_prob": False}, {"norm_eps": 1e-2},
        {"qk_norm": False}, {"rope_theta": 10000.0}])
    def test_a_different_model_is_told_apart(self, reference, cfg, want,
                                             wrong):
        _, params = _system(reference, cfg, "float32")
        other = _program(reference, cfg, "float32", **wrong)
        assert _differs(_outputs(other, params, _obs(cfg), cfg),
                        want) > 1e-3

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
                REPO, "benchmark/reference/keye-vl2-policy.py")) as f:
            text = f.read()
        code = text.split('"""', 2)[2]
        assert "relayrl_tpu.models.transformer" not in code
        assert "relayrl_tpu.models.layers" not in code
        assert "relayrl_tpu.ops" not in code
        assert "flax" not in code
        assert 'jax.default_matmul_precision("highest")' in code
        assert "jax.lax.top_k" in code      # the selection, by a sort

    def test_a_program_without_the_keys_is_refused(self, reference, cfg,
                                                   monkeypatch):
        from relayrl_tpu.models import base

        monkeypatch.setattr(base, "ARCH_PASSTHROUGH_KEYS", tuple(
            k for k in base.ARCH_PASSTHROUGH_KEYS if k != "index_topk"))
        with pytest.raises(SystemExit, match="index_topk"):
            reference.program_kwargs(cfg)

    @pytest.mark.parametrize("key,value", [
        ("hidden_act", "gelu"), ("norm_topk_prob", False),
        ("decoder_sparse_step", 2), ("mlp_only_layers", [0]),
        ("attention_bias", True), ("use_sliding_window", True)])
    def test_a_configuration_it_was_not_written_for_is_refused(
            self, reference, cfg, key, value):
        with pytest.raises(SystemExit, match=key):
            reference.program_kwargs({**cfg, key: value})


class TestTheModelsOwnLoss:
    """``Policy.own_loss`` is the one seam: IMPALA's update adds the term,
    every other algorithm refuses the policy by name, and a trunk without
    one has no such field set."""

    KEYS = {"model_kind": "transformer_moe_discrete", "d_model": 16,
            "n_layers": 1, "n_heads": 2, "max_seq_len": 8, "norm": "rms",
            "positions": "rope", "layer_types": ["sparse_attention"],
            "index_heads": 2, "index_head_dim": 4, "index_topk": 2,
            "moe_experts": 2, "moe_top_k": 1}

    @pytest.mark.parametrize("name", ["REINFORCE", "PPO"])
    def test_an_update_that_would_drop_it_refuses_the_policy(self, name,
                                                             tmp_path):
        from relayrl_tpu.algorithms import build_algorithm

        with pytest.raises(ValueError, match="IndexLoss.*would drop"):
            build_algorithm(name, obs_dim=4, act_dim=3,
                            env_dir=str(tmp_path), **self.KEYS)

    def test_impala_builds_it_and_reports_the_loss(self, tmp_path):
        from relayrl_tpu.algorithms import build_algorithm

        algo = build_algorithm("IMPALA", obs_dim=4, act_dim=3,
                               env_dir=str(tmp_path), traj_per_epoch=1,
                               **self.KEYS)
        assert algo.policy.own_loss == "IndexLoss"
        assert algo._fence_notes[-2:] == ("IndexLoss", "index_kept_pct")

    def test_a_trunk_without_one_brings_none(self):
        policy = build_policy({"kind": "transformer_moe_discrete",
                               "obs_dim": 4, "act_dim": 3, "d_model": 16,
                               "n_layers": 1, "n_heads": 2,
                               "moe_experts": 2})
        assert policy.own_loss is None and policy.index_backends == {}
        assert build_policy({"kind": "mlp_discrete", "obs_dim": 4,
                             "act_dim": 3}).own_loss is None


class TestTheSharesAddUp:
    """Eight chips share a layer, experts divided: the eight shares of 16
    held experts sum to the uncut reference's layer of 128 (no shared
    expert: nothing is counted once)."""

    E, K, D, FF, CHIPS = 128, 8, 24, 12, 8

    def _layer(self, held):
        from relayrl_tpu.models.moe import MoEMLP

        return MoEMLP(self.D, self.FF, self.E, self.K, jnp.float32,
                      norm_topk_prob=True, ffn="swiglu", use_bias=False,
                      held=held)

    # (slow: a second draw of the same statement; tier-1 keeps seed 0)
    @pytest.mark.parametrize("seed", [
        0, pytest.param(1, marks=pytest.mark.slow)])
    def test_against_the_uncut_reference(self, reference, seed):
        rng = np.random.default_rng(seed)
        u = jnp.asarray(rng.standard_normal((2, 24, self.D)), jnp.float32)
        # the reference's RMSNorm before the experts made the identity
        # (unit weights on rows of unit mean square)
        u = u * jax.lax.rsqrt(jnp.mean(jnp.square(u), -1, keepdims=True))
        whole = self._layer(None).init(jax.random.PRNGKey(seed),
                                       u)["params"]
        per = self.E // self.CHIPS
        assert per == 16
        stacks = ("moe_w_gate", "moe_w_up", "moe_w_down")

        def share(c):
            p = {**whole, **{n: whole[n][per * c:per * (c + 1)]
                             for n in stacks}}
            return self._layer((per * c, per)).apply({"params": p}, u)

        shares = [share(c) for c in range(self.CHIPS)]
        with jax.default_matmul_precision("highest"):
            blk = {"ln_mlp": {"scale": jnp.ones((self.D,))}, "moe": whole}
            uncut = reference._experts(blk, u, 0.0, 0, self.E, None,
                                       self.K) - u
        np.testing.assert_allclose(sum(shares), uncut, atol=3e-5, rtol=1e-5)
        # no share is the layer, and seven are not
        assert float(jnp.abs(shares[0] - uncut).max()) > 1e-3
        assert float(jnp.abs(sum(shares[1:]) - uncut).max()) > 1e-3


class TestShapeArithmetic:
    def test_forward_operations_at_the_published_widths(self):
        flops = _by_path("benchmark/flops_keye.py")
        cfg = _published()
        d, t = 2048, 16_384
        assert flops.causal_pairs(t) == 134_225_920
        assert flops.kept_pairs(t, 2048) == 31_458_304     # 23.4%
        assert round(100 * 31_458_304 / 134_225_920, 1) == 23.4
        assert flops.index_pair_flops(cfg) == 2 * 16 * 64 == 2_048
        assert flops.attention_pair_flops(cfg) == 4 * 32 * 128 == 16_384
        proj = 2 * (2 * d * 4096 + 2 * d * 512) + 2 * d * (1024 + 64 + 16)
        assert proj == flops.attention_proj_fwd_flops(cfg) + (
            flops.index_proj_fwd_flops(cfg)) == 37_748_736 + 4_521_984
        experts = 2 * d * 128 + 1.0 * 3 * 2 * d * 768
        assert experts == flops.experts_fwd_flops(cfg) == 9_961_472
        # a layer's forward TFLOP over one 16,384-token episode
        assert round(2_048 * 134_225_920 / 1e12, 3) == 0.275
        assert round(16_384 * 31_458_304 / 1e12, 3) == 0.515
        assert round(proj * t / 1e12, 3) == 0.693
        assert round(3 * 2 * d * 768 * t / 1e12, 3) == 0.155
        want = (4 * (proj + 2_048 * 134_225_920 / t
                     + 16_384 * 31_458_304 / t + experts)
                + 2 * 18 * d + 2 * d * 17)
        assert flops.keye_fwd_flops_per_token(cfg, t) == want

    def test_the_rooflines_count_what_the_function_needs(self):
        flops = _by_path("benchmark/flops_keye.py")
        cfg = _published()
        t = 16_384
        ops, nbytes = flops.sparse_attn_train_ops_bytes(cfg, 1, t)
        assert ops == 4 * 3 * 16_384 * 31_458_304
        assert nbytes == 4 * (5 * 4096 + 6 * 512) * t * 2
        # as the run counted the kept pairs, where it did
        assert flops.sparse_attn_train_ops_bytes(
            cfg, 1, t, 31_458_304 / 134_225_920)[0] == pytest.approx(ops)
        # a masked-dense form's causal pairs would be 4.3 times the work
        assert round(134_225_920 / 31_458_304, 1) == 4.3
        ops, nbytes = flops.index_train_ops_bytes(cfg, 1, t)
        assert ops == 4 * (3 * 4_521_984 * t + 2_048 * (
            134_225_920 + 2 * 31_458_304))
        assert nbytes == 4 * ((2048 + 5 * 1104) * t * 2 + 4 * 31_458_304)

    def test_published_widths_in_the_configuration_file(self):
        c = _published()
        # the source's config.json (the catalog's copy), every key but the
        # two reduced
        published = {
            "attention_bias": False, "decoder_sparse_step": 1,
            "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
            "intermediate_size": 6144, "max_position_embeddings": 262144,
            "max_window_layers": 48, "mlp_only_layers": [],
            "model_type": "KeyeVL2", "moe_intermediate_size": 768,
            "norm_topk_prob": True, "num_attention_heads": 32,
            "num_experts": 128, "num_experts_per_tok": 8,
            "num_hidden_layers": 48, "num_key_value_heads": 4,
            "num_local_experts": 128, "rms_norm_eps": 1e-06,
            "rope_scaling": {"mrope_section": [16, 24, 24],
                             "rope_type": "default", "type": "default"},
            "rope_theta": 10000000,
            "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                          "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                          "q_chunk_size": 512, "topk": 2048},
            "sliding_window": None, "tie_word_embeddings": False,
            "use_sliding_window": False, "vocab_size": 151936}
        reduced = ["num_hidden_layers", "num_experts"]
        assert c["reduced"] == reduced
        assert {k: c[k] for k in published if k not in reduced} == {
            k: v for k, v in published.items() if k not in reduced}
        assert (c["num_hidden_layers"], c["num_experts"]) == (4, 16)
        assert c["published"] == {"num_hidden_layers": 48,
                                  "num_experts": 128}
        assert "8 chips share each layer" in c["deployment"]
        assert "not built" in c["departures"]["vision_tower"]
        for item in ("indexer inputs", "indexer key norm", "index scale",
                     "indexer rope", "indexer loss", "qk_norm",
                     "mrope_section", "q_chunk_size, kv_chunk_size"):
            assert item in c["assumed"], item
        # the names the unedited readers use
        assert c["n_embd"] // c["n_head"] == c["head_dim"]
        assert c["num_hidden_layers"] - c["num_dense_layers"] == 4

    def test_the_published_trunk_holds_392_million_parameters(self,
                                                              reference):
        kwargs = reference.program_kwargs(_published())
        arch = {"kind": kwargs.pop("model_kind"), "obs_dim": 18,
                "act_dim": 16, "has_critic": True, **kwargs}
        shapes = jax.eval_shape(build_policy(arch).init_params,
                                jax.random.PRNGKey(0))
        p = shapes["params"]
        count = lambda tree: sum(x.size
                                 for x in jax.tree_util.tree_leaves(tree))
        attention, indexer = 18_874_624, 2_261_120
        layer = p["block_0"]
        assert count({k: layer[k] for k in INDEXER}) == indexer
        assert count({k: layer[k] for k in (
            "q_proj", "k_proj", "v_proj", "q_norm", "k_norm",
            "attn_out")}) == attention
        outside = attention + indexer + 262_144 + 4_096
        assert outside == 21_401_984
        assert count(layer) == outside + 16 * 4_718_592 == 96_899_456
        # the stacks keep the published width: no padded weight
        assert layer["moe"]["moe_w_up"].shape == (16, 2048, 768)
        # + embedding, final norm, policy head, the value head's two layers
        ends = 38_912 + 2_048 + 32_784 + 4_196_352 + 2_049
        assert count(shapes) == 4 * 96_899_456 + ends == 391_869_969
