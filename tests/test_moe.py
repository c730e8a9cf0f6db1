"""MoE per-token top-k routing + expert parallelism over the ``ep`` axis."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from relayrl_tpu.models import build_policy
from relayrl_tpu.parallel import make_mesh
from relayrl_tpu.parallel.sharding import param_pspec
from _moe import (  # noqa: F401  (the fixtures are used by name)
    _D,
    _FF,
    _N,
    ARCH,
    DISPATCHES,
    _fresh_expert_traces,
    _held_layer,
    _held_params,
    _poison_unwritten_rows,
    _policy_params,
    _row_buffer_of,
    _share_of,
)


class TestMoELayer:
    def test_expert_weights_stacked(self):
        _, params = _policy_params()
        moe = params["params"]["block_0"]["moe"]
        assert moe["moe_w_up"].shape == (4, 16, 64)
        assert moe["moe_w_down"].shape == (4, 64, 16)

    def test_forward_finite_and_batch_shaped(self):
        policy, params = _policy_params()
        obs = jnp.asarray(
            np.random.default_rng(0).standard_normal((3, 8, 6)), jnp.float32)
        logp, ent, v = policy.evaluate(params, obs,
                                       jnp.zeros((3, 8), jnp.int32))
        assert logp.shape == (3, 8)
        assert bool(jnp.isfinite(logp).all() and jnp.isfinite(v).all())

    @pytest.mark.parametrize("dispatch", DISPATCHES)
    def test_causal_routing(self, dispatch):
        # Per-token routing must keep the policy causal: logp at step t may
        # not change when FUTURE observations change (capacity-competition
        # routing schemes violate this — the reason top-k per token was
        # chosen; see models/moe.py docstring).
        policy, params = _policy_params(moe_dispatch=dispatch)
        rng = np.random.default_rng(3)
        obs = jnp.asarray(rng.standard_normal((1, 8, 6)), jnp.float32)
        act = jnp.zeros((1, 8), jnp.int32)
        obs2 = obs.at[:, 5:].set(
            jnp.asarray(rng.standard_normal((1, 3, 6)), jnp.float32))
        logp1, _, v1 = policy.evaluate(params, obs, act)
        logp2, _, v2 = policy.evaluate(params, obs2, act)
        np.testing.assert_allclose(logp1[0, :5], logp2[0, :5],
                                   atol=1e-6, rtol=1e-6)
        np.testing.assert_allclose(v1[0, :5], v2[0, :5],
                                   atol=1e-6, rtol=1e-6)

    def test_single_expert_builds(self):
        # moe_experts=1 (and init's 1-token trace) must not crash top_k.
        policy = build_policy({**ARCH, "moe_experts": 1})
        params = policy.init_params(jax.random.PRNGKey(0))
        obs = jnp.zeros((1, 8, 6), jnp.float32)
        logp, _, _ = policy.evaluate(params, obs, jnp.zeros((1, 8), jnp.int32))
        assert bool(jnp.isfinite(logp).all())

    @pytest.mark.parametrize("dispatch", DISPATCHES)
    def test_grads_reach_every_expert(self, dispatch):
        # With top-2 of 4 experts over 16 tokens, every expert receives
        # assignments at init (uniform-ish gate) — all must get gradient.
        policy, params = _policy_params(moe_dispatch=dispatch)
        obs = jnp.asarray(
            np.random.default_rng(1).standard_normal((2, 8, 6)), jnp.float32)

        def loss(p):
            logp, ent, v = policy.evaluate(p, obs,
                                           jnp.zeros((2, 8), jnp.int32))
            return logp.sum() + v.sum()

        g = jax.grad(loss)(params)
        for layer in ("block_0", "block_1"):
            mass = jnp.abs(g["params"][layer]["moe"]["moe_w_up"]).sum((1, 2))
            assert bool((mass > 0).all()), f"{layer}: dead expert {mass}"

    def test_moe_differs_from_dense_family(self):
        dense_arch = {**ARCH, "kind": "transformer_discrete"}
        dense_arch.pop("moe_experts")
        dense = build_policy(dense_arch)
        p = dense.init_params(jax.random.PRNGKey(0))
        assert "moe" not in p["params"]["block_0"]
        assert "mlp_up" in p["params"]["block_0"]


# -- sparse dispatch against the dense all-experts path ----------------------
# The layer alone, float32, eager (no compile): N = 24 tokens, d = 16,
# ff = 8. ``load`` shapes the router through its bias: "even" leaves the
# random router, "one" sends every token's first choice to expert 0 (with
# k = 1 every token-slot lands there: the whole batch in one group),
# "empty" bars the upper half of the experts (groups of size 0).
SPARSE_CASES = [
    pytest.param(e, k, norm, load, id=f"E{e}-k{k}-"
                 f"{'topk_softmax' if norm else 'softmax_topk_unnorm'}-{load}")
    for e in (4, 64) for k in (1, 2, 8) for norm in (False, True)
    for load in ("even", "one", "empty")
    # barring half the experts needs k of them left to choose from
    if not (load == "empty" and min(k, e) > e // 2)]


def _layer(e, k, norm, dispatch, ffn="swiglu"):
    from relayrl_tpu.models.moe import MoEMLP

    return MoEMLP(_D, _FF, e, k, jnp.float32, norm_topk_prob=norm, ffn=ffn,
                  dispatch=dispatch)


def _layer_params(e, k, norm, load, ffn="swiglu"):
    x = jnp.asarray(np.random.default_rng(e * 10 + k).standard_normal(
        (2, _N // 2, _D)), jnp.float32)
    params = _layer(e, k, norm, "sparse", ffn).init(jax.random.PRNGKey(e + k),
                                                    x)
    bias = np.zeros(e, np.float32)
    if load == "one":
        bias[0] = 50.0
    elif load == "empty":
        bias[e // 2:] = -50.0
    params = jax.tree_util.tree_map(lambda a: a, params)
    params["params"]["moe_gate"]["bias"] = jnp.asarray(bias)
    return params, x


class TestSparseDispatch:
    @pytest.mark.parametrize("e,k,norm,load", SPARSE_CASES)
    def test_matches_dense_forward_loss_and_every_gradient(self, e, k, norm,
                                                           load):
        """Tolerance 2e-5 absolute on values of order 1: both paths are
        float32 and compute the same products; only the order of the sums
        differs (k terms per token here, E masked terms there)."""
        params, x = _layer_params(e, k, norm, load)

        def loss(dispatch):
            def f(params, x):
                y, state = _layer(e, k, norm, dispatch).apply(
                    params, x, mutable=["intermediates"])
                return jnp.sum(jnp.sin(y) * x), (y, state)
            return f

        (ls, (ys, st)), gs = jax.value_and_grad(
            loss("sparse"), argnums=(0, 1), has_aux=True)(params, x)
        (ld, (yd, _)), gd = jax.value_and_grad(
            loss("dense"), argnums=(0, 1), has_aux=True)(params, x)
        np.testing.assert_allclose(ys, yd, atol=2e-5, rtol=1e-5)
        np.testing.assert_allclose(float(ls), float(ld), atol=2e-4,
                                   rtol=1e-5)
        flat_s = jax.tree_util.tree_flatten_with_path(gs)[0]
        flat_d = jax.tree_util.tree_leaves(gd)
        assert len(flat_s) == len(flat_d)
        for (path, a), b in zip(flat_s, flat_d):
            np.testing.assert_allclose(
                a, b, atol=2e-5, rtol=1e-4,
                err_msg=jax.tree_util.keystr(path))
        # no token-slot dropped, whatever the imbalance
        load_counts = np.asarray(st["intermediates"]["expert_load"][0])
        kk = min(k, e)
        assert load_counts.sum() == _N * kk
        if load == "one":
            assert load_counts[0] == _N
        if load == "empty":
            assert (load_counts[e // 2:] == 0).all()

    @pytest.mark.parametrize("ffn", ["gelu", "swiglu"])
    def test_expert_stacks_of_the_ffn_kind(self, ffn):
        params, _ = _layer_params(4, 2, True, "even", ffn)
        names = set(params["params"]) - {"moe_gate"}
        want = {"moe_w_up", "moe_w_down"} | (
            {"moe_w_gate"} if ffn == "swiglu" else set())
        assert names == want

    @pytest.mark.parametrize("norm", [False, True])
    def test_router_weights(self, norm):
        from relayrl_tpu.models.moe import route

        logits = jnp.asarray(np.random.default_rng(0).standard_normal(
            (5, 8)), jnp.float32)
        w, idx = route(logits, 3, norm)
        probs = jax.nn.softmax(logits, -1)
        picked = jnp.take_along_axis(probs, idx, -1)
        if norm:  # softmax over the chosen = the probabilities renormalised
            np.testing.assert_allclose(w.sum(-1), 1.0, atol=1e-6)
            np.testing.assert_allclose(
                w, picked / picked.sum(-1, keepdims=True), atol=1e-6)
        else:     # the probabilities over all experts, as they are
            np.testing.assert_allclose(w, picked, atol=1e-7)
            assert float(w.sum(-1).max()) < 1.0

    @pytest.mark.parametrize("norm", [False, True])
    def test_window_of_one_sequence_routes_as_inside_a_batch(self, norm):
        # one actor's window and the same tokens inside a training batch:
        # same experts, same outputs (the docstring's hard requirement)
        policy, params = _policy_params(moe_norm_topk_prob=norm)
        obs = jnp.asarray(np.random.default_rng(7).standard_normal(
            (3, 8, 6)), jnp.float32)
        act = jnp.zeros((3, 8), jnp.int32)
        logp_b, _, v_b = policy.evaluate(params, obs, act)
        for b in range(3):
            logp_1, _, v_1 = policy.evaluate(params, obs[b:b + 1],
                                             act[b:b + 1])
            np.testing.assert_allclose(logp_1[0], logp_b[b], atol=1e-6)
            np.testing.assert_allclose(v_1[0], v_b[b], atol=1e-6)

    def test_unknown_dispatch_refused(self):
        with pytest.raises(ValueError, match="moe_dispatch"):
            _policy_params(moe_dispatch="capacity")


class TestExpertParallel:
    @pytest.mark.parametrize("stack,shape", [
        ("moe_w_up", (4, 16, 64)), ("moe_w_gate", (4, 16, 64)),
        ("moe_w_down", (4, 64, 16))])
    def test_expert_stack_pspec(self, stack, shape):
        mesh = make_mesh({"dp": -1, "ep": 4})
        key = jax.tree_util.DictKey
        path = (key("params"), key("block_0"), key("moe"), key(stack))
        assert param_pspec(path, jnp.zeros(shape), mesh)[0] == "ep"

    def test_expert_pspec(self):
        mesh = make_mesh({"dp": -1, "ep": 4})
        key = jax.tree_util.DictKey
        path = (key("params"), key("block_0"), key("moe"), key("moe_w_up"))
        spec = param_pspec(path, jnp.zeros((4, 16, 64)), mesh)
        assert spec[0] == "ep"
        # the gate must stay replicated
        gate_path = (key("params"), key("block_0"), key("moe"),
                     key("moe_gate"), key("kernel"))
        assert param_pspec(gate_path, jnp.zeros((16, 4)), mesh) == \
            jax.sharding.PartitionSpec()

    @pytest.mark.parametrize("asked,ep,runs", [
        (None, 1, "sparse"), (None, 4, "dense"), ("dense", 1, "dense"),
        ("sparse", 1, "sparse"), ("dense", 4, "dense"),
        ("sparse", 4, "refused")])
    def test_dispatch_follows_the_ambient_mesh(self, asked, ep, runs):
        # unset, the layer takes the path GSPMD can partition where it is
        # traced under an ep mesh and the sparse one elsewhere; the sparse
        # path is told by its grouped matmul
        from relayrl_tpu.parallel import use_mesh

        over = {} if asked is None else {"moe_dispatch": asked}
        policy, params = _policy_params(**over)
        obs = jnp.zeros((2, 8, 6), jnp.float32)
        act = jnp.zeros((2, 8), jnp.int32)

        def trace():
            return str(jax.make_jaxpr(policy.evaluate)(params, obs, act))

        with use_mesh(make_mesh({"dp": -1, "ep": ep})):
            if runs == "refused":
                with pytest.raises(ValueError, match="single-device"):
                    trace()
                return
            text = trace()
        assert ("ragged_dot" in text) == (runs == "sparse")

    # ISSUE 17 wall re-fit: the heaviest compile in the fast wall (~30 s
    # on the 1-core CI host); ep-mesh stepping stays covered fast by the
    # MULTICHIP dryrun and the dp-mesh pipelined locks in
    # tests/test_multichip_pipeline.py.
    @pytest.mark.slow
    def test_sharded_update_on_ep_mesh(self):
        from relayrl_tpu.algorithms.reinforce import (
            ReinforceState,
            make_optimizers,
            make_reinforce_update,
        )
        from relayrl_tpu.parallel import (
            make_sharded_update,
            place_batch,
            place_state,
        )

        mesh = make_mesh({"dp": 2, "ep": 4})
        policy, params = _policy_params()
        tx_pi, tx_vf = make_optimizers(params, 3e-4, 1e-3)
        state = ReinforceState(params=params, pi_opt_state=tx_pi.init(params),
                               vf_opt_state=tx_vf.init(params),
                               rng=jax.random.PRNGKey(1), step=jnp.int32(0))
        update = make_reinforce_update(policy, 3e-4, 1e-3, 1, 0.99, 0.95,
                                       with_baseline=True)
        rng = np.random.default_rng(0)
        B, T = 8, 8
        batch = {
            "obs": rng.standard_normal((B, T, 6)).astype(np.float32),
            "act": rng.integers(0, 3, (B, T)).astype(np.int32),
            "act_mask": np.ones((B, T, 3), np.float32),
            "rew": np.ones((B, T), np.float32),
            "val": np.zeros((B, T), np.float32),
            "logp": np.zeros((B, T), np.float32),
            "valid": np.ones((B, T), np.float32),
            "last_val": np.zeros((B,), np.float32),
        }
        sharded = make_sharded_update(update, mesh, state, donate_state=False)
        new_state, metrics = sharded(place_state(state, mesh),
                                     place_batch(batch, mesh))
        jax.block_until_ready(new_state)
        assert int(new_state.step) == 1
        assert np.isfinite(float(metrics["LossPi"]))
        # result must match the unsharded update (same math, GSPMD layout)
        single = update(state, {k: jnp.asarray(v) for k, v in batch.items()})
        np.testing.assert_allclose(
            float(metrics["LossPi"]), float(single[1]["LossPi"]),
            atol=1e-4, rtol=1e-4)


class TestUtilizationMonitor:
    def test_update_stats_are_the_extremes_of_the_utilization(self):
        from relayrl_tpu.models.moe import expert_utilization

        policy, params = _policy_params()
        obs = np.random.default_rng(6).standard_normal((2, 8, 6)).astype(
            np.float32)
        act = jnp.zeros((2, 8), jnp.int32)
        logp, ent, v, stats = policy.evaluate_stats(params, obs, act)
        np.testing.assert_allclose(logp, policy.evaluate(params, obs, act)[0],
                                   atol=1e-6)
        util = expert_utilization(ARCH, params, obs)
        np.testing.assert_allclose(
            float(stats["moe_load_max"]),
            max(float(f.max()) for f in util.values()), atol=1e-6)
        np.testing.assert_allclose(
            float(stats["moe_load_min"]),
            min(float(f.min()) for f in util.values()), atol=1e-6)
        dense = build_policy({**ARCH, "kind": "transformer_discrete"})
        assert dense.evaluate_stats is None

    def test_fractions_sum_to_one_per_layer(self):
        from relayrl_tpu.models.moe import expert_utilization

        policy, params = _policy_params()
        obs = np.random.default_rng(5).standard_normal((2, 8, 6)).astype(
            np.float32)
        util = expert_utilization(ARCH, params, obs)
        assert set(util) == {"block_0", "block_1"}
        for layer, frac in util.items():
            assert frac.shape == (4,)
            np.testing.assert_allclose(float(frac.sum()), 1.0, atol=1e-5)
            # near-uniform at init: no expert should be collapsed-out
            assert float(frac.max()) < 0.9, (layer, frac)


# -- the sigmoid router --------------------------------------------------------

class TestSigmoidRouter:
    @pytest.mark.parametrize("norm", [False, True])
    @pytest.mark.parametrize("k", [2, 3])
    def test_weights_are_the_unbiased_scores_of_the_biased_choice(
            self, norm, k):
        from relayrl_tpu.models.moe import route

        rng = np.random.default_rng(0)
        logits = jnp.asarray(rng.standard_normal((6, 8)), jnp.float32)
        bias = jnp.asarray(rng.standard_normal(8) * 0.5, jnp.float32)
        w, idx = route(logits, k, norm, "sigmoid", bias)
        s = np.asarray(jax.nn.sigmoid(logits))
        want_idx = np.argsort(-(s + np.asarray(bias)), -1)[:, :k]
        assert (np.sort(np.asarray(idx), -1) == np.sort(want_idx, -1)).all()
        picked = np.take_along_axis(s, np.asarray(idx), -1)
        if norm:
            picked = picked / (picked.sum(-1, keepdims=True) + 1e-6)
        np.testing.assert_allclose(w, picked, atol=1e-6)
        # the bias moved a choice somewhere, and no weight
        assert (np.sort(np.argsort(-s, -1)[:, :k], -1)
                != np.sort(want_idx, -1)).any()

    def test_bias_gets_no_gradient(self):
        params, x = _held_params()
        g = jax.grad(lambda p: jnp.sum(jnp.sin(
            _held_layer(None).apply(p, x))))(params)
        assert float(jnp.abs(params["params"]["moe_expert_bias"]).max()) > 0
        assert float(jnp.abs(g["params"]["moe_expert_bias"]).max()) == 0.0
        assert float(jnp.abs(g["params"]["moe_gate"]["kernel"]).max()) > 0

    def test_unknown_router_refused(self):
        with pytest.raises(ValueError, match="moe_router"):
            _policy_params(moe_router="tanh")

def _early_layer(held, dispatch="sparse", e=64, k=6):
    from relayrl_tpu.models.moe import MoEMLP

    return MoEMLP(_D, _FF, e, k, jnp.float32, norm_topk_prob=True,
                  ffn="reglu", dispatch=dispatch, use_bias=False, held=held)


def _early_params(e=64, k=6, seed=0):
    """The whole layer's parameters, the rows its experts read and the rows
    its router reads."""
    rng = np.random.default_rng(seed)
    x, route_x = (jnp.asarray(rng.standard_normal((2, _N // 2, _D)),
                              jnp.float32) for _ in range(2))
    return _early_layer(None, e=e, k=k).init(jax.random.PRNGKey(seed), x,
                                             route_x), x, route_x


class TestRouterInputAndReGLU:
    def test_the_layer_by_hand(self):
        # top-6 of the logits of route_x, softmax over the six, every
        # chosen expert's relu(gate) * up of x
        params, x, route_x = _early_params()
        p = params["params"]
        tokens, routed = x.reshape(_N, _D), route_x.reshape(_N, _D)
        logits = routed @ p["moe_gate"]["kernel"]
        vals, idx = jax.lax.top_k(logits, 6)
        w = jax.nn.softmax(vals, -1)
        want = jnp.zeros((_N, _D))
        for j in range(6):
            e = idx[:, j]
            mid = jax.nn.relu(jnp.einsum("nd,ndf->nf", tokens,
                                         p["moe_w_gate"][e])) * jnp.einsum(
                "nd,ndf->nf", tokens, p["moe_w_up"][e])
            want += w[:, j:j + 1] * jnp.einsum("nf,nfd->nd", mid,
                                               p["moe_w_down"][e])
        got = _early_layer(None).apply(params, x, route_x)
        np.testing.assert_allclose(got.reshape(_N, _D), want, atol=2e-5,
                                   rtol=1e-5)

    def test_the_router_reads_its_own_rows(self):
        params, x, route_x = _early_params()
        layer = _early_layer(None)
        same = layer.apply(params, x, x)
        np.testing.assert_array_equal(same, layer.apply(params, x))
        assert not np.allclose(layer.apply(params, x, route_x), same,
                               atol=1e-3)

    @pytest.mark.parametrize("chips", [1, 4, 8])
    def test_the_shares_add_up_to_the_uncut_layer(self, chips):
        # 64 experts over 4 chips, 16 each (the configuration's share); the
        # router reads the layer's input and normalises over the six chosen
        # of ALL experts, so the chips' partial outputs sum to the layer's
        params, x, route_x = _early_params()
        whole = _early_layer(None).apply(params, x, route_x)
        count = 64 // chips
        parts = sum(_early_layer((c * count, count)).apply(
            _share_of(params, c * count, count), x, route_x)
            for c in range(chips))
        np.testing.assert_allclose(parts, whole, atol=2e-5, rtol=1e-5)

    @pytest.mark.parametrize("held", [None, (16, 16)])
    def test_sparse_matches_dense_forward_and_every_gradient(self, held):
        params, x, route_x = _early_params()
        share = params if held is None else _share_of(params, *held)

        def loss(dispatch):
            def f(p, x, route_x):
                y = _early_layer(held, dispatch).apply(p, x, route_x)
                return jnp.sum(jnp.sin(y) * x), y
            return f

        (_, ys), gs = jax.value_and_grad(
            loss("sparse"), (0, 1, 2), has_aux=True)(share, x, route_x)
        (_, yd), gd = jax.value_and_grad(
            loss("dense"), (0, 1, 2), has_aux=True)(share, x, route_x)
        np.testing.assert_allclose(ys, yd, atol=2e-5, rtol=1e-5)
        for (path, a), b in zip(
                jax.tree_util.tree_flatten_with_path(gs)[0],
                jax.tree_util.tree_leaves(gd)):
            np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-4,
                                       err_msg=jax.tree_util.keystr(path))
        # the router's rows get a gradient of their own (through the
        # weights), the experts' rows theirs
        assert float(jnp.abs(gs[2]).max()) > 0

    def test_the_block_hands_the_router_the_layers_input(self):
        # the same parameters under "layer" and under "ffn" are two models,
        # and a block whose attention adds nothing (attn_out zeroed) routes
        # on the un-normed rows where "ffn" routes on the normed ones
        from relayrl_tpu.models import build_policy

        arch = {"kind": "transformer_moe_discrete", "obs_dim": 6,
                "act_dim": 3, "d_model": 32, "n_layers": 1, "n_heads": 2,
                "max_seq_len": 8, "norm": "rms", "positions": "rope",
                "use_bias": False, "ffn": "reglu", "moe_experts": 8,
                "moe_top_k": 2, "moe_d_ff": 16}
        early = build_policy({**arch, "moe_router_input": "layer"})
        late = build_policy(arch)
        params = early.init_params(jax.random.PRNGKey(0))
        obs = jnp.asarray(np.random.default_rng(0).standard_normal(
            (1, 8, 6)), jnp.float32)
        act = jnp.zeros((1, 8), jnp.int32)
        assert not np.allclose(early.evaluate(params, obs, act)[2],
                               late.evaluate(params, obs, act)[2], atol=1e-4)
        with pytest.raises(ValueError, match="moe_router_input"):
            build_policy({**arch, "moe_router_input": "embedding"}
                         ).init_params(jax.random.PRNGKey(0))


# -- experts without a gate, the routed weights' factor, the shared expert ---

def _relu2_layer(held, dispatch="sparse", e=16, k=6, scaling=2.5,
                 shared=24, ffn="relu2"):
    from relayrl_tpu.models.moe import MoEMLP

    return MoEMLP(_D, _FF, e, k, jnp.float32, norm_topk_prob=True, ffn=ffn,
                  dispatch=dispatch, use_bias=False, router="sigmoid",
                  expert_bias=True, held=held, routed_scaling=scaling,
                  shared_d_ff=shared)


def _relu2_params(seed=0, **kw):
    x = jnp.asarray(np.random.default_rng(seed).standard_normal(
        (2, _N // 2, _D)), jnp.float32)
    return _relu2_layer(None, **kw).init(jax.random.PRNGKey(seed), x), x


def _relu2_share(params, first, count):
    p = dict(params["params"])
    for name in ("moe_w_up", "moe_w_down"):
        p[name] = p[name][first:first + count]
    return {"params": p}


class TestReLU2ScalingAndTheSharedExpert:
    def test_the_layer_by_hand(self):
        """``y = 2.5 sum_chosen w_e W_down,e relu(W_up,e x)^2 + W_down,s
        relu(W_up,s x)^2`` with ``w`` the chosen experts' unbiased sigmoid
        scores over their sum."""
        params, x = _relu2_params()
        p = params["params"]
        assert set(p) == {"moe_gate", "moe_expert_bias", "moe_w_up",
                          "moe_w_down", "moe_shared_up", "moe_shared_down"}
        tokens = x.reshape(-1, _D)
        s = jax.nn.sigmoid(tokens @ p["moe_gate"]["kernel"])
        idx = np.argsort(-np.asarray(s + p["moe_expert_bias"]), -1)[:, :6]
        picked = np.take_along_axis(np.asarray(s), idx, -1)
        w = 2.5 * picked / (picked.sum(-1, keepdims=True) + 1e-6)
        relu2 = lambda a: jnp.square(jax.nn.relu(a))
        want = relu2(tokens @ p["moe_shared_up"]["kernel"]) @ p[
            "moe_shared_down"]["kernel"]
        for j in range(6):
            up = jnp.einsum("nd,ndf->nf", tokens, p["moe_w_up"][idx[:, j]])
            want = want + w[:, j:j + 1] * jnp.einsum(
                "nf,nfd->nd", relu2(up), p["moe_w_down"][idx[:, j]])
        for dispatch in DISPATCHES:
            got = _relu2_layer(None, dispatch).apply(params, x)
            np.testing.assert_allclose(got.reshape(-1, _D), want, atol=2e-5,
                                       rtol=1e-5, err_msg=dispatch)

    @pytest.mark.parametrize("ffn,stacks", [
        ("relu2", {"moe_w_up", "moe_w_down"}),
        ("gelu", {"moe_w_up", "moe_w_down"}),
        ("reglu", {"moe_w_gate", "moe_w_up", "moe_w_down"})])
    def test_the_shared_expert_is_of_the_experts_kind(self, ffn, stacks):
        params, _ = _relu2_params(ffn=ffn)
        p = params["params"]
        assert {n for n in p if n.startswith("moe_w_")} == stacks
        assert ("moe_shared_gate" in p) == ("moe_w_gate" in p)
        assert p["moe_shared_up"]["kernel"].shape == (_D, 24)
        assert "bias" not in p["moe_shared_up"]

    def test_scaling_multiplies_the_routed_sum_alone(self):
        params, x = _relu2_params()
        no_shared = {"params": {k: v for k, v in params["params"].items()
                                if "shared" not in k}}
        routed = _relu2_layer(None, shared=None, scaling=1.0).apply(
            no_shared, x)
        shared = _relu2_layer(None, scaling=1.0).apply(params, x) - routed
        assert float(jnp.abs(shared).max()) > 1e-3
        np.testing.assert_allclose(
            _relu2_layer(None, scaling=2.5).apply(params, x),
            2.5 * routed + shared, atol=2e-5, rtol=1e-5)
        # the factor touches the weights alone, never the choice
        def sown(scaling):
            _, state = _relu2_layer(None, scaling=scaling).apply(
                params, x, mutable=["intermediates"])
            return np.asarray(state["intermediates"]["expert_load"][0])

        assert (sown(2.5) == sown(1.0)).all()

    @pytest.mark.parametrize("held", [None, (0, 8), (5, 3)])
    def test_sparse_matches_dense_forward_and_every_gradient(self, held):
        """relu^2 experts, the 2.5 and the shared expert under the held
        layer's own backward (its pass loop, its recomputed buffers)
        against the dense form: forward, loss and EVERY gradient — tokens,
        router, both stacks, the shared expert's two matrices."""
        params, x = _relu2_params()
        share = params if held is None else _relu2_share(params, *held)

        def loss(dispatch):
            def f(p, x):
                y = _relu2_layer(held, dispatch).apply(p, x)
                return jnp.sum(jnp.sin(y) * x), y
            return f

        (ls, ys), gs = jax.value_and_grad(
            loss("sparse"), (0, 1), has_aux=True)(share, x)
        (ld, yd), gd = jax.value_and_grad(
            loss("dense"), (0, 1), has_aux=True)(share, x)
        np.testing.assert_allclose(ys, yd, atol=2e-5, rtol=1e-5)
        for (path, a), b in zip(
                jax.tree_util.tree_flatten_with_path(gs)[0],
                jax.tree_util.tree_leaves(gd)):
            name = jax.tree_util.keystr(path)
            np.testing.assert_allclose(a, b, atol=3e-5, rtol=1e-4,
                                       err_msg=name)
            assert (float(jnp.abs(a).max()) > 0) != (
                "moe_expert_bias" in name), name

    @pytest.mark.parametrize("passes", [1, 2])
    def test_row_buffers_walked_in_passes_with_rows_never_written(
            self, monkeypatch, passes):
        """... and with the buffers a pass or two long, the rows the
        kernels leave unwritten holding NaN: ``relu(NaN)^2`` is NaN, and
        none reaches the result or a gradient."""
        held, e, k = (5, 3), 16, 6
        params, x = _relu2_params()
        share = _relu2_share(params, *held)
        _, state = _relu2_layer(held, "dense").apply(
            share, x, mutable=["intermediates"])
        live = int(state["intermediates"]["expert_load"][0].sum())
        rows = {1: live + 3, 2: -(-live // 2)}[passes]
        _row_buffer_of(monkeypatch, rows, _N * k, held[1], e)
        _poison_unwritten_rows(monkeypatch)

        def loss(dispatch):
            def f(p, x):
                y, state = _relu2_layer(held, dispatch).apply(
                    p, x, mutable=["intermediates"])
                return jnp.sum(jnp.sin(y) * x), state["intermediates"]
            return f

        (ls, sown), gs = jax.value_and_grad(
            loss("sparse"), (0, 1), has_aux=True)(share, x)
        (ld, _), gd = jax.value_and_grad(
            loss("dense"), (0, 1), has_aux=True)(share, x)
        assert int(sown["row_passes"][0]) == passes
        np.testing.assert_allclose(float(ls), float(ld), rtol=1e-5)
        for (path, a), b in zip(
                jax.tree_util.tree_flatten_with_path(gs)[0],
                jax.tree_util.tree_leaves(gd)):
            np.testing.assert_allclose(a, b, atol=3e-5, rtol=1e-4,
                                       err_msg=jax.tree_util.keystr(path))

    @pytest.mark.parametrize("chips", [2, 16])
    def test_the_shares_and_the_shared_expert_once_add_up(self, chips):
        """Every chip of a layer computes the shared expert alike: the
        chips' ROUTED shares and the shared expert counted once are the
        layer."""
        params, x = _relu2_params()
        whole = _relu2_layer(None).apply(params, x)
        per = 16 // chips
        no_shared = lambda p: {"params": {
            k: v for k, v in p["params"].items() if "shared" not in k}}
        routed = sum(_relu2_layer((c * per, per), shared=None).apply(
            no_shared(_relu2_share(params, c * per, per)), x)
            for c in range(chips))
        with_shared = _relu2_layer((0, per)).apply(
            _relu2_share(params, 0, per), x)
        once = with_shared - _relu2_layer((0, per), shared=None).apply(
            no_shared(_relu2_share(params, 0, per)), x)
        np.testing.assert_allclose(routed + once, whole, atol=3e-5,
                                   rtol=1e-5)

    @pytest.mark.parametrize("chips", [4, 16])
    def test_kimi_linears_shares_add_up_to_the_uncut_reference_layer(
            self, chips):
        """SwiGLU experts behind a sigmoid router with a correction bias,
        top-4 of 16 renormalised x 2.446, one ungated shared expert
        (``kimi-linear-policy``'s layer, tiny): the chips' routed shares —
        4 shares of 4 experts as the deployment's 32 of 8, and 16 of one —
        with the shared expert counted once are what
        ``benchmark/reference/kimi-linear-policy.py`` computes for the WHOLE
        layer, every expert held."""
        from test_lfm2_reference import _by_path

        ref = _by_path("benchmark/reference/kimi-linear-policy.py")
        kw = dict(k=4, scaling=2.446, ffn="swiglu")
        params, x = _relu2_params(**kw)
        moe_p = params["params"]
        tokens = x.reshape(-1, _D)
        plain = lambda a: a
        w = ref._route(moe_p, tokens, 4, 2.446, 0, 16)
        want = sum(w[:, e:e + 1] * ref._swiglu(
            tokens, moe_p["moe_w_gate"][e], moe_p["moe_w_up"][e],
            moe_p["moe_w_down"][e], plain) for e in range(16))
        want = want + ref._swiglu(
            tokens, moe_p["moe_shared_gate"]["kernel"],
            moe_p["moe_shared_up"]["kernel"],
            moe_p["moe_shared_down"]["kernel"], plain)
        per = 16 // chips
        no_shared = lambda p: {"params": {
            k: v for k, v in p["params"].items() if "shared" not in k}}
        routed = sum(_relu2_layer((c * per, per), shared=None, **kw).apply(
            no_shared(_share_of(params, c * per, per)), x)
            for c in range(chips))
        once = _relu2_layer((0, per), **kw).apply(
            _share_of(params, 0, per), x) - _relu2_layer(
                (0, per), shared=None, **kw).apply(
                    no_shared(_share_of(params, 0, per)), x)
        np.testing.assert_allclose((routed + once).reshape(want.shape), want,
                                   atol=3e-5, rtol=1e-5)

    def test_an_unknown_ffn_is_refused_and_the_arch_sets_the_fields(self):
        with pytest.raises(ValueError, match="unknown ffn"):
            _policy_params(ffn="relu3")
        policy, params = _policy_params(
            ffn="relu2", moe_routed_scaling=2.5, moe_shared_d_ff=24,
            moe_d_ff=12, moe_router="sigmoid", use_bias=False)
        moe = params["params"]["block_0"]["moe"]
        assert moe["moe_shared_up"]["kernel"].shape == (16, 24)
        assert "moe_w_gate" not in moe
        other, _ = _policy_params(
            ffn="relu2", moe_routed_scaling=1.0, moe_shared_d_ff=24,
            moe_d_ff=12, moe_router="sigmoid", use_bias=False)
        obs = jnp.asarray(np.random.default_rng(0).standard_normal(
            (1, 8, 6)), jnp.float32)
        act = jnp.zeros((1, 8), jnp.int32)
        assert not np.allclose(policy.evaluate(params, obs, act)[2],
                               other.evaluate(params, obs, act)[2],
                               atol=1e-4)


# -- the shared expert's gate; 32 held of 512 at top-10 (qwen3next-policy) ---

def _gated_shared_layer(held, dispatch="sparse", e=512, k=10, gate=True,
                        shared=24):
    from relayrl_tpu.models.moe import MoEMLP

    return MoEMLP(_D, _FF, e, k, jnp.float32, norm_topk_prob=True,
                  ffn="swiglu", dispatch=dispatch, use_bias=False, held=held,
                  shared_d_ff=shared, shared_gate=gate)


def _gated_shared_params(seed=0, **kw):
    x = jnp.asarray(np.random.default_rng(seed).standard_normal(
        (2, _N // 2, _D)), jnp.float32)
    return _gated_shared_layer(None, **kw).init(jax.random.PRNGKey(seed),
                                                x), x


def _swiglu_share(params, first, count):
    p = dict(params["params"])
    for name in ("moe_w_gate", "moe_w_up", "moe_w_down"):
        p[name] = p[name][first:first + count]
    return {"params": p}


class TestTheSharedExpertsGate:
    def test_the_layer_by_hand(self):
        """``y = sum_chosen w_e expert_e(x) + sigmoid(x w_s) shared(x)``
        with ``w`` the ten largest of the softmax over 512, over their
        sum."""
        params, x = _gated_shared_params()
        p = params["params"]
        assert set(p) == {"moe_gate", "moe_w_gate", "moe_w_up", "moe_w_down",
                          "moe_shared_gate", "moe_shared_up",
                          "moe_shared_down", "moe_shared_expert_gate"}
        assert p["moe_shared_expert_gate"]["kernel"].shape == (_D, 1)
        assert "bias" not in p["moe_shared_expert_gate"]
        tokens = x.reshape(-1, _D)
        probs = jax.nn.softmax(tokens @ p["moe_gate"]["kernel"], -1)
        idx = np.argsort(-np.asarray(probs), -1)[:, :10]
        picked = np.take_along_axis(np.asarray(probs), idx, -1)
        w = picked / picked.sum(-1, keepdims=True)

        def swiglu(t, gate, up, down):
            return (jax.nn.silu(t @ gate) * (t @ up)) @ down

        want = jax.nn.sigmoid(
            tokens @ p["moe_shared_expert_gate"]["kernel"]) * swiglu(
                tokens, p["moe_shared_gate"]["kernel"],
                p["moe_shared_up"]["kernel"], p["moe_shared_down"]["kernel"])
        for j in range(10):
            e = idx[:, j]
            inner = jax.nn.silu(jnp.einsum(
                "nd,ndf->nf", tokens, p["moe_w_gate"][e])) * jnp.einsum(
                    "nd,ndf->nf", tokens, p["moe_w_up"][e])
            want = want + w[:, j:j + 1] * jnp.einsum(
                "nf,nfd->nd", inner, p["moe_w_down"][e])
        for dispatch in DISPATCHES:
            got = _gated_shared_layer(None, dispatch).apply(params, x)
            np.testing.assert_allclose(got.reshape(-1, _D), want, atol=2e-5,
                                       rtol=1e-5, err_msg=dispatch)

    def test_without_the_key_the_tree_and_the_layer_are_todays(self):
        params, x = _gated_shared_params(gate=False)
        assert "moe_shared_expert_gate" not in params["params"]
        gated, _ = _gated_shared_params()
        ungated = {"params": {k: v for k, v in gated["params"].items()
                              if k != "moe_shared_expert_gate"}}
        y = _gated_shared_layer(None, gate=False).apply(ungated, x)
        assert float(jnp.abs(
            y - _gated_shared_layer(None).apply(gated, x)).max()) > 1e-3

    @pytest.mark.parametrize("held", [None, (0, 32), (37, 32)])
    def test_sparse_matches_dense_forward_and_every_gradient(self, held):
        """32 held of 512 at top-10 (choice-major slots: 8 does not divide
        10) with the gated shared expert under the held layer's own
        backward against the dense form: forward, loss and EVERY gradient
        — tokens, router, the three stacks, the shared expert's three
        matrices and its gate."""
        params, x = _gated_shared_params()
        share = params if held is None else _swiglu_share(params, *held)

        def loss(dispatch):
            def f(p, x):
                y = _gated_shared_layer(held, dispatch).apply(p, x)
                return jnp.sum(jnp.sin(y) * x), y
            return f

        (ls, ys), gs = jax.value_and_grad(
            loss("sparse"), (0, 1), has_aux=True)(share, x)
        (ld, yd), gd = jax.value_and_grad(
            loss("dense"), (0, 1), has_aux=True)(share, x)
        np.testing.assert_allclose(ys, yd, atol=2e-5, rtol=1e-5)
        for (path, a), b in zip(
                jax.tree_util.tree_flatten_with_path(gs)[0],
                jax.tree_util.tree_leaves(gd)):
            name = jax.tree_util.keystr(path)
            np.testing.assert_allclose(a, b, atol=3e-5, rtol=1e-4,
                                       err_msg=name)
            assert float(jnp.abs(a).max()) > 0, name

    @pytest.mark.parametrize("passes", [1, 2])
    def test_row_buffers_walked_in_passes_with_rows_never_written(
            self, monkeypatch, passes):
        held, e, k = (37, 32), 512, 10
        params, x = _gated_shared_params()
        share = _swiglu_share(params, *held)
        _, state = _gated_shared_layer(held, "dense").apply(
            share, x, mutable=["intermediates"])
        live = int(state["intermediates"]["expert_load"][0].sum())
        assert live > 4
        rows = {1: live + 3, 2: -(-live // 2)}[passes]
        _row_buffer_of(monkeypatch, rows, _N * k, held[1], e)
        _poison_unwritten_rows(monkeypatch)

        def loss(dispatch):
            def f(p, x):
                y, state = _gated_shared_layer(held, dispatch).apply(
                    p, x, mutable=["intermediates"])
                return jnp.sum(jnp.sin(y) * x), state["intermediates"]
            return f

        (ls, sown), gs = jax.value_and_grad(
            loss("sparse"), (0, 1), has_aux=True)(share, x)
        (ld, _), gd = jax.value_and_grad(
            loss("dense"), (0, 1), has_aux=True)(share, x)
        assert int(sown["row_passes"][0]) == passes
        np.testing.assert_allclose(float(ls), float(ld), rtol=1e-5)
        for (path, a), b in zip(
                jax.tree_util.tree_flatten_with_path(gs)[0],
                jax.tree_util.tree_leaves(gd)):
            np.testing.assert_allclose(a, b, atol=3e-5, rtol=1e-4,
                                       err_msg=jax.tree_util.keystr(path))

    def test_the_row_buffer_at_a_sixteenth(self):
        from relayrl_tpu.models.moe import row_buffer

        # qwen3next-policy.update: 16,384 tokens x 10 slots, 32 of 512 held
        assert row_buffer(163_840, 32, 512) == 20_480       # 2 x 10,240

    def test_the_arch_sets_the_field(self):
        policy, params = _policy_params(
            ffn="swiglu", moe_shared_d_ff=24, moe_shared_expert_gate=True,
            moe_d_ff=12, use_bias=False)
        moe = params["params"]["block_0"]["moe"]
        assert moe["moe_shared_expert_gate"]["kernel"].shape == (16, 1)
        _, plain = _policy_params(ffn="swiglu", moe_shared_d_ff=24,
                                  moe_d_ff=12, use_bias=False)
        assert "moe_shared_expert_gate" not in plain["params"]["block_0"][
            "moe"]
