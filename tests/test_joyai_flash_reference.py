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
from relayrl_tpu.models.layers.attention import apply_rope
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
        _BUILT[key] = policy, jax.jit(policy.init_params)(
            jax.random.PRNGKey(seed))
    return _BUILT[key]


def _outputs(policy, params, obs, act_dim):
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
    def test_a_wrong_reference_is_told_apart(self, reference, cfg, got,
                                             wrong):
        _, params = _system(reference, cfg, "float32")
        assert _differs(got, reference.forward(params, _obs(cfg), cfg,
                                               wrong=wrong)) > 1e-3

    def test_the_other_pairing_is_another_function_of_the_same_tree(
            self, reference, cfg, got, want):
        """Interleaved and half-split differ on one parameter tree, and the
        program is the interleaved one where the arch says so and the
        half-split one where it does not: which runs is checked."""
        obs = _obs(cfg)
        _, params = _system(reference, cfg, "float32")
        other = _program(reference, cfg, "float32", rope_interleave=False)
        published = want
        halves = reference.forward(params, obs, cfg,
                                   wrong={"half_split": True})
        assert _differs(published, halves) > 1e-3
        got_other = _outputs(other, params, obs, cfg["act_dim"])
        assert _differs(got, published) < 1e-4 < _differs(got, halves)
        assert _differs(got_other, halves) < 1e-4 < _differs(got_other,
                                                             published)

    @pytest.mark.parametrize("wrong", [
        {"moe_routed_scaling": 1.0}, {"rope_theta": 10000.0},
        {"positions": "none"}])
    def test_a_different_model_is_told_apart(self, reference, cfg, want,
                                             wrong):
        _, params = _system(reference, cfg, "float32")
        other = _program(reference, cfg, "float32", **wrong)
        got = _outputs(other, params, _obs(cfg), cfg["act_dim"])
        assert _differs(got, want) > 1e-3

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


def _row_form(cfg, a, start):
    """The rotation as it was written before the pairing moved to the
    weights' columns (``mla._rotated`` up to PR 62), kept here as what the
    column form has to equal: ``a [B, L, heads, w]`` (or the shared ``[B, L,
    w]``), its LAST ``qk_rope_head_dim`` lanes de-interleaved row by row and
    turned in half-split pairs, the other lanes as they came."""
    theta, pe = cfg["rope_theta"], cfg["qk_rope_head_dim"]
    if theta is None:
        return a
    shared = a.ndim == 3
    if shared:
        a = a[:, :, None]
    lanes = a[..., a.shape[-1] - pe:]
    if cfg["rope_interleave"]:
        lanes = jnp.concatenate([lanes[..., 0::2], lanes[..., 1::2]], -1)
    lanes = apply_rope(lanes, start, theta)
    if a.shape[-1] > pe:
        lanes = jnp.concatenate([a[..., :a.shape[-1] - pe], lanes], -1)
    return lanes[:, :, 0] if shared else lanes


# the widths of the tiny layer: 4 heads of 8 + 4 (q / k 12 wide, v 8) over a
# latent row of 12, the residual stream 24 wide
_D, _H, _RANK, _NOPE, _PE, _VD = 24, 4, 12, 8, 4, 8
_EPS, _THETA = 1e-6, 100.0


def _latent_layer(interleave, q_rank, use_bias=False, theta=_THETA):
    """One latent layer alone (``TransformerBlock``, no FFN) at the tiny
    widths -> ``(block, seeded params, rows [2, T, 24], cfg, records)``."""
    import flax

    from relayrl_tpu.models import layers
    from relayrl_tpu.models.transformer import TransformerBlock

    fns, records = layers.resolve({"attention": "dense"})
    cfg = {"n_heads": _H, "rope_theta": theta, "kv_lora_rank": _RANK,
           "qk_nope_head_dim": _NOPE, "qk_rope_head_dim": _PE,
           "v_head_dim": _VD, "q_lora_rank": q_rank,
           "rope_interleave": interleave}
    block = TransformerBlock(
        _D, 2, jnp.float32, op="latent_attention",
        cfg=flax.core.FrozenDict(cfg), fns=fns, has_ffn=False,
        norm="rms", norm_eps=_EPS, use_bias=use_bias)
    x = jnp.asarray(np.random.default_rng(3).standard_normal(
        (2, T, _D)), jnp.float32)
    params = block.init(jax.random.PRNGKey(0), x)
    return block, params, x, cfg, records


def _plain(reference, params, x, interleave, q_rank):
    """The reference's layer on the same tree. Without a rank of its own the
    query is ``u W_q``: the reference's ``(u I) W_qb`` with its norm left
    out."""
    p = dict(params["params"])
    if q_rank is None:
        p["q_a"] = {"kernel": jnp.eye(_D, dtype=jnp.float32)}
        p["q_b"] = p.pop("q_proj")
    as_run = {"no_rope": False, "half_split": not interleave,
              "no_q_norm": q_rank is None, "scale_128": False}
    with jax.default_matmul_precision("highest"):
        return reference._latent_attention(
            p, x, (_H, _RANK, _NOPE, _PE, _VD), _EPS, _THETA, None,
            as_run)


class TestTheRotation:
    """``mla._apart`` and ``mla._rotated`` alone: a head's last
    ``qk_rope_head_dim`` lanes taken apart as the first and the second of
    each rotated pair — of a weight's columns in the layer, of plain rows
    here, the function is one — and turned at their absolute positions."""

    CFG = {"rope_theta": 100.0, "qk_rope_head_dim": 8,
           "rope_interleave": True}

    def _rows(self, seed, width, heads=3, length=16):
        return jnp.asarray(np.random.default_rng(seed).standard_normal(
            (2, length, heads, width)), jnp.float32)

    def _turned(self, cfg, a, at, stand=0):
        """``a``'s last 8 lanes a head through the column form's two steps,
        the halves side by side behind the lanes that stand."""
        B, L, heads, width = a.shape
        still, *pair = mla._apart(a.reshape(B, L, heads * width), heads,
                                  stand, width - stand,
                                  cfg["rope_interleave"])
        pair = mla._rotated(cfg, *(h.reshape(B, L, heads, -1) for h in pair),
                            at)
        return jnp.concatenate([still.reshape(B, L, heads, stand), *pair],
                               -1)

    @pytest.mark.parametrize("interleave", [True, False])
    @pytest.mark.parametrize("start", [1, 7, 200])
    def test_scores_depend_on_the_distance_alone(self, interleave, start):
        """``R_i q . R_j k`` is a function of ``i - j``: the same rows at
        positions ``start + j`` give the scores they give from 0."""
        cfg = {**self.CFG, "rope_interleave": interleave}
        q, k = self._rows(0, 8), self._rows(1, 8, heads=1)

        def scores(at):
            return jnp.einsum("bqhd,bkd->bhqk", self._turned(cfg, q, at),
                              self._turned(cfg, k, at)[:, :, 0])

        np.testing.assert_allclose(scores(start), scores(0), atol=1e-4)
        # and a position is seen: the unrotated rows score otherwise
        plain = jnp.einsum("bqhd,bkd->bhqk", q, k[:, :, 0])
        assert float(jnp.abs(scores(0) - plain).max()) > 1e-2

    def test_only_the_last_lanes_turn_and_row_zero_stands(self):
        q = self._rows(2, 20)
        still, first, second = mla._apart(q.reshape(2, 16, 60), 3, 12, 8,
                                          True)
        np.testing.assert_array_equal(still.reshape(2, 16, 3, 12),
                                      q[..., :12])
        # the pairs (2i, 2i + 1) come apart as (i of the first, i of the
        # second)
        np.testing.assert_array_equal(first.reshape(2, 16, 3, 4),
                                      q[..., 12::2])
        np.testing.assert_array_equal(second.reshape(2, 16, 3, 4),
                                      q[..., 13::2])
        got = self._turned(self.CFG, q, 0, stand=12)
        np.testing.assert_array_equal(got[..., :12], q[..., :12])
        # position 0 turns nothing
        np.testing.assert_allclose(got[:, 0, :, 12:], jnp.concatenate(
            [q[:, 0, :, 12::2], q[:, 0, :, 13::2]], -1), atol=1e-6)
        assert float(jnp.abs(got[:, 1:, :, 12:16]
                             - q[:, 1:, :, 12::2]).max()) > 1e-2

    def test_a_layer_that_rotates_nothing_keeps_its_projections_whole(self):
        """No lane turns: ``nn.Dense`` products as before, no columns
        apart, nothing under the rotation's name."""
        block, params, x, _, records = _latent_layer(
            True, 20, theta=None)
        text = str(jax.make_jaxpr(block.apply)(params, x))
        assert text.count("dot_general") == 7   # 5 projections, q k^T, p v
        assert "relayrl_latent_rope" not in text and "cos" not in text
        assert records["latent_rope"] == {}

    def test_the_interleaved_pairs_are_the_published_ones(self, reference):
        """Against the reference's rotation in place: equal up to the ONE
        de-interleave both q and k take, so every dot product agrees."""
        q, k = self._rows(4, 8), self._rows(5, 8, heads=1)
        want = jnp.einsum("bqhd,bkd->bhqk", reference._rope(q, 100.0, True),
                          reference._rope(k, 100.0, True)[:, :, 0])
        got = jnp.einsum("bqhd,bkd->bhqk", self._turned(self.CFG, q, 0),
                         self._turned(self.CFG, k, 0)[:, :, 0])
        np.testing.assert_allclose(got, want, atol=2e-5)
        halves = jnp.einsum(
            "bqhd,bkd->bhqk", reference._rope(q, 100.0, False),
            reference._rope(k, 100.0, False)[:, :, 0])
        assert float(jnp.abs(want - halves).max()) > 1e-2

    @pytest.mark.parametrize("interleave", [True, False])
    @pytest.mark.parametrize("start", [0, 5])
    def test_the_two_steps_are_the_row_form(self, interleave, start):
        """Lanes apart, then turned: the rows the row form gave, lane for
        lane — of heads' rows and of the shared ``[B, L, pe]`` alike."""
        cfg = {**self.CFG, "rope_interleave": interleave}
        q = self._rows(6, 20)
        np.testing.assert_allclose(self._turned(cfg, q, start, stand=12),
                                   _row_form(cfg, q, start), atol=1e-6)
        k = self._rows(7, 8, heads=1)[:, :, 0]
        _, *pair = mla._apart(k, 1, 0, 8, interleave)
        np.testing.assert_allclose(
            jnp.concatenate(mla._rotated(cfg, *pair, start), -1),
            _row_form(cfg, k, start), atol=1e-6)


class TestTheColumnForm:
    """One rotary latent layer alone (``TransformerBlock``, no FFN), the
    pairing and the nope / rope split taken on the projections' columns,
    against the benchmark's plain float32 layer: full, cached (a prefill,
    then eight steps) and readout modes, both pairings, with and without a
    low rank of the query's own."""

    FORMS = [(True, 20), (True, None), (False, 20), (False, None)]

    @pytest.mark.parametrize("interleave,q_rank", FORMS)
    def test_the_full_mode(self, reference, interleave, q_rank):
        block, params, x, _, records = _latent_layer(interleave, q_rank)
        got = jax.jit(block.apply)(params, x)
        want = _plain(reference, params, x, interleave, q_rank)
        np.testing.assert_allclose(got, want, atol=2e-5)
        assert records["latent_rope"] == {
            ("latent_attention", "dense"): "columns"}
        # and the other pairing is another function of the same tree
        other = _plain(reference, params, x, not interleave, q_rank)
        assert float(jnp.abs(got - other).max()) > 1e-3

    @pytest.mark.parametrize("interleave,q_rank", FORMS)
    def test_the_readout_row(self, reference, interleave, q_rank):
        block, params, x, _, _ = _latent_layer(interleave, q_rank)
        want = _plain(reference, params, x, interleave, q_rank)
        row = jax.jit(lambda p, x, i: block.apply(p, x, readout_idx=i))
        for idx in (0, 9, T - 1):
            got = row(params, x, jnp.asarray(idx, jnp.int32))
            assert got.shape == (2, 1, _D)
            np.testing.assert_allclose(got[:, 0], want[:, idx], atol=2e-5,
                                       err_msg=f"row {idx}")

    def _decode(self, block, params, x, cache, t0):
        step = jax.jit(lambda p, row, cache, t: block.apply(
            p, row, cache=cache, t=t))
        rows = []
        for t in range(t0, T):
            out, cache = step(params, x[:, t:t + 1], cache, t)
            rows.append(out[:, 0])
        return jnp.stack(rows, 1), cache

    @pytest.mark.parametrize("interleave,q_rank", FORMS)
    def test_a_prefill_then_eight_steps(self, reference, interleave, q_rank):
        block, params, x, cfg, _ = _latent_layer(interleave, q_rank)
        want = _plain(reference, params, x, interleave, q_rank)
        t0 = T - 8
        cache = mla.init_cache(cfg, _D, 2, T, jnp.float32, None)
        out, cache = jax.jit(lambda p, rows, cache: block.apply(
            p, rows, cache=cache, t=0))(params, x[:, :t0], cache)
        np.testing.assert_allclose(out, want[:, :t0], atol=2e-5)
        got, _ = self._decode(block, params, x, cache, t0)
        np.testing.assert_allclose(got, want[:, t0:], atol=2e-5)

    @pytest.mark.parametrize("interleave,q_rank", FORMS)
    def test_a_cache_the_row_form_wrote_reads_the_same(
            self, reference, interleave, q_rank):
        """The latent rows and the shared lanes as the program up to PR 62
        wrote them — ``kv_a``'s product in the published column order, its
        last lanes turned by the row form — are the rows the column form
        writes, in the same order: a cache written before reads the same,
        and the steps from it are the full forward's rows."""
        block, params, x, cfg, _ = _latent_layer(interleave, q_rank)
        p = params["params"]
        with jax.default_matmul_precision("highest"):
            u = reference._rms_norm(p["ln_attn"], x, _EPS)
            c, k_pe = jnp.split(u @ p["kv_a"]["kernel"], [_RANK], axis=-1)
        t0 = T - 8
        old = tuple(
            jnp.zeros_like(rows).at[:, :t0].set(rows[:, :t0])
            for rows in (c, _row_form(cfg, k_pe, 0)))
        _, new = jax.jit(lambda p, rows, cache: block.apply(
            p, rows, cache=cache, t=0))(
                params, x[:, :t0],
                mla.init_cache(cfg, _D, 2, T, jnp.float32, None))
        for a, b in zip(old, new):
            np.testing.assert_allclose(a, b, atol=1e-5)
        # (the shared lanes in half-split order, not as published)
        if interleave:
            assert float(jnp.abs(
                new[1][:, 0] - k_pe[:, 0]).max()) > 1e-2
        want = _plain(reference, params, x, interleave, q_rank)
        got, _ = self._decode(block, params, x, old, t0)
        np.testing.assert_allclose(got, want[:, t0:], atol=2e-5)

    @pytest.mark.parametrize("interleave,q_rank", FORMS)
    def test_the_gradients_come_back_in_the_published_order(
            self, reference, interleave, q_rank):
        """``q_b``'s (or ``q_proj``'s) and ``kv_a``'s kernels' gradients
        and the layer input's against ``jax.grad`` of the reference, which
        reads the columns as published."""
        block, params, x, _, _ = _latent_layer(interleave, q_rank)
        w = jnp.asarray(np.random.default_rng(4).standard_normal(x.shape),
                        jnp.float32)
        loss = lambda f: (lambda p, x: jnp.sum(f(p, x) * w))
        got = jax.jit(jax.grad(loss(block.apply), (0, 1)))(params, x)
        want = jax.grad(loss(lambda p, x: _plain(
            reference, p, x, interleave, q_rank)), (0, 1))(params, x)
        np.testing.assert_allclose(got[1], want[1], atol=1e-4, rtol=1e-4)
        q_name = "q_b" if q_rank else "q_proj"
        flat_want = dict(jax.tree_util.tree_flatten_with_path(want[0])[0])
        for path, g in jax.tree_util.tree_flatten_with_path(got[0])[0]:
            np.testing.assert_allclose(
                g, flat_want[path], atol=1e-4, rtol=1e-4,
                err_msg=jax.tree_util.keystr(path))
        for name in (q_name, "kv_a"):
            g = got[0]["params"][name]["kernel"]
            assert g.shape == params["params"][name]["kernel"].shape
            assert float(jnp.abs(g).min(0).max()) > 0   # every column's

    @pytest.mark.parametrize("interleave,q_rank", FORMS)
    @pytest.mark.parametrize("use_bias", [False, True])
    def test_the_parameter_tree_is_the_one_it_was(self, interleave, q_rank,
                                                  use_bias):
        """Names, shapes and SEEDED VALUES of the tree are ``nn.Dense``'s,
        as before the columns were taken apart: the same layer built where
        no lane turns (whole projections, the code as it was) seeds the same
        numbers under the same names."""
        import flax

        block, params, x, cfg, _ = _latent_layer(interleave, q_rank, use_bias)
        whole = block.clone(cfg=flax.core.FrozenDict(
            {**cfg, "rope_theta": None}))
        was = whole.init(jax.random.PRNGKey(0), x)
        flat = {jax.tree_util.keystr(k): v for k, v in
                jax.tree_util.tree_flatten_with_path(params)[0]}
        flat_was = {jax.tree_util.keystr(k): v for k, v in
                    jax.tree_util.tree_flatten_with_path(was)[0]}
        assert list(flat) == list(flat_was)
        for name, leaf in flat.items():
            np.testing.assert_array_equal(leaf, flat_was[name], err_msg=name)
        q = ({"q_a": (_D, 20), "q_a_norm": (20,), "q_b": (20, _H * 12)}
             if q_rank else {"q_proj": (_D, _H * 12)})
        shapes = {"ln_attn": (_D,), **q, "kv_a": (_D, _RANK + _PE),
                  "kv_a_norm": (_RANK,), "kv_b": (_RANK, _H * 16),
                  "attn_out": (_H * _VD, _D)}
        p = params["params"]
        assert set(p) == set(shapes)
        for name, shape in shapes.items():
            leaf = "scale" if len(shape) == 1 else "kernel"
            assert p[name][leaf].shape == shape
            assert ("bias" in p[name]) == (use_bias and leaf == "kernel")

    @pytest.mark.parametrize("interleave", [True, False])
    def test_a_bias_is_taken_apart_with_its_columns(self, interleave):
        """``_ColumnsApart`` against ``nn.Dense`` on one tree, a bias that
        is not zero: the two products are the whole one's lanes, apart."""
        from flax import linen as nn

        x = jnp.asarray(np.random.default_rng(5).standard_normal(
            (2, 5, 7)), jnp.float32)
        apart = mla._ColumnsApart(3, 4, 6, interleave, jnp.float32, True)
        whole = nn.Dense(3 * 10, dtype=jnp.float32)
        tree = whole.init(jax.random.PRNGKey(1), x)
        np.testing.assert_array_equal(
            tree["params"]["kernel"],
            apart.init(jax.random.PRNGKey(1), x)["params"]["kernel"])
        tree = {"params": {**tree["params"], "bias": jnp.arange(30.0)}}
        still, first, second = apart.apply(tree, x)
        want = whole.apply(tree, x).reshape(2, 5, 3, 10)
        np.testing.assert_allclose(still.reshape(2, 5, 3, 4),
                                   want[..., :4], atol=1e-5)
        lanes = want[..., 4:]
        pair = ((lanes[..., 0::2], lanes[..., 1::2]) if interleave
                else (lanes[..., :3], lanes[..., 3:]))
        for got, half in zip((first, second), pair):
            np.testing.assert_allclose(got.reshape(2, 5, 3, 3), half,
                                       atol=1e-5)

    def test_the_record_and_its_line(self, reference, cfg, capsys):
        """``Policy.latent_rope`` says ``"columns"`` for both kinds of a
        six-layer trunk (one dense layer, five with experts) and the build
        prints one ``[latent_rope]`` line a kind and shape; a trunk whose
        latent layer rotates nothing has no entry."""
        policy, params = _system(reference, cfg, "float32", n_layers=6,
                                 layer_types=["latent_attention"] * 6)
        assert sorted(params["params"])[:6] == [
            f"block_{i}" for i in range(6)]
        capsys.readouterr()
        jax.jit(policy.evaluate).lower(
            params, _obs(cfg), jnp.zeros((2, T), jnp.int32))
        assert dict(policy.latent_rope) == {
            ("latent_attention", "dense"): "columns",
            ("latent_attention", "experts"): "columns"}
        lines = [line for line in capsys.readouterr().out.splitlines()
                 if line.startswith("[latent_rope]")]
        assert len(lines) == 2
        assert lines[0].startswith(
            "[latent_rope] latent_attention+dense (from block_0) T=32 "
            "heads=4 lanes 4 of 12 float32, pairs (2i, 2i + 1) -> columns")
        assert "(from block_1)" in lines[1]
        assert "MB of rows, where every row's lanes were" in lines[0]
        still, _ = _system(reference, cfg, "float32", positions="none")
        jax.jit(still.evaluate).lower(
            params, _obs(cfg), jnp.zeros((2, T), jnp.int32))
        assert dict(still.latent_rope) == {}
        assert "[latent_rope]" not in capsys.readouterr().out


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

    # (slow: a second draw of the same statement; tier-1 keeps seed 0)
    @pytest.mark.parametrize("seed", [
        0, pytest.param(1, marks=pytest.mark.slow)])
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
        # found by name, not by place: later PRs append cells, configurations
        # and metrics after this one's (PERF.md section 7, since PR 65 (c))
        entry = {w["name"]: w for w in bench["workloads"]}[cell]
        assert entry == {
            "name": cell, "config": "joyai-flash-policy",
            "traffic": "impala-seq16k-trace8-batch", "chips": 1,
            "why": entry["why"]}
        config = {c["name"]: c for c in bench["configs"]}[entry["config"]]
        assert config["reduced"] == ["num_hidden_layers", "n_routed_experts"]
        assert cell in bench["end_to_end"][0]["workloads"]
        mine = [m["name"] for m in bench["per_layer"]
                if cell in m.get("workloads", ())]
        kimi = [m["name"] for m in bench["per_layer"]
                if "kimi-linear-policy.update" in m.get("workloads", ())]
        assert mine == [n for n in kimi if not n.startswith("kda_")] + [
            "latent_rope_ms"]
        assert len(mine) == 31
        assert {m["name"]: m for m in bench["per_layer"]}[
                "latent_rope_ms"] == {
            "name": "latent_rope_ms", "unit": "ms", "better": "lower",
            "source": "device_trace", "layer": "trunk",
            "moves": "train_samples_per_s", "workloads": [cell]}
