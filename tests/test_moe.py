"""MoE per-token top-k routing + expert parallelism over the ``ep`` axis."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from relayrl_tpu.models import build_policy
from relayrl_tpu.parallel import make_mesh
from relayrl_tpu.parallel.sharding import param_pspec

ARCH = {"kind": "transformer_moe_discrete", "obs_dim": 6, "act_dim": 3,
        "d_model": 16, "n_layers": 2, "n_heads": 2, "max_seq_len": 8,
        "moe_experts": 4}


DISPATCHES = ("sparse", "dense")


def _policy_params(seed=0, **arch):
    policy = build_policy({**ARCH, **arch})
    return policy, policy.init_params(jax.random.PRNGKey(seed))


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
_N, _D, _FF = 24, 16, 8
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


# -- the sigmoid router and the layer that is told which experts it holds ----

def _held_layer(held, dispatch="sparse", e=8, k=2, bias=True):
    from relayrl_tpu.models.moe import MoEMLP

    return MoEMLP(_D, _FF, e, k, jnp.float32, norm_topk_prob=True,
                  ffn="swiglu", dispatch=dispatch, use_bias=False,
                  router="sigmoid", expert_bias=bias, held=held)


def _held_params(e=8, k=2, seed=0):
    """The whole layer's parameters (every expert held) and tokens."""
    x = jnp.asarray(np.random.default_rng(seed).standard_normal(
        (2, _N // 2, _D)), jnp.float32)
    params = _held_layer(None, e=e, k=k).init(jax.random.PRNGKey(seed), x)
    return jax.tree_util.tree_map(lambda a: a, params), x


def _share_of(params, first, count):
    """One chip's parameters of the whole layer's: its slice of the expert
    stacks; the router and its bias whole."""
    p = dict(params["params"])
    for name in ("moe_w_gate", "moe_w_up", "moe_w_down"):
        p[name] = p[name][first:first + count]
    return {"params": p}


def _row_buffer_of(monkeypatch, rows, n_slots, n_held, n_exp):
    """Make the held layer's buffers ``rows`` long at this shape — through
    the module's margin and row tile, as a router's imbalance would at the
    real ones (no arch key sets them)."""
    from relayrl_tpu.models import moe

    monkeypatch.setattr(moe, "_ROW_TILE", 1)
    monkeypatch.setattr(moe, "_ROW_MARGIN",
                        (rows - 0.5) * n_exp / (n_slots * n_held))
    assert moe.row_buffer(n_slots, n_held, n_exp) == rows


def _poison_unwritten_rows(monkeypatch):
    """The TPU kernels never write the rows past the last group, in the
    product and in its transpose to the rows (``d_lhs``), and never read
    them (the transpose to the stacks selects its groups' rows);
    ``ragged_dot`` zero-fills and multiplies by masks. This stand-in puts
    NaN where the kernels leave whatever was there: every read of such a
    row shows in the result."""
    from relayrl_tpu.models import moe

    plain = moe.grouped_matmul

    def written(rows, group_sizes):
        return (jnp.arange(rows.shape[0]) < group_sizes.sum())[:, None]

    @jax.custom_vjp
    def poisoned(lhs, rhs, group_sizes):
        return jnp.where(written(lhs, group_sizes),
                         plain(lhs, rhs, group_sizes), jnp.nan)

    def fwd(lhs, rhs, group_sizes):
        return poisoned(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)

    def bwd(res, g):
        lhs, rhs, group_sizes = res
        live = written(lhs, group_sizes)
        _, transpose = jax.vjp(
            lambda a, b: plain(a, b, group_sizes),
            jnp.where(live, lhs, 0), rhs)
        d_lhs, d_rhs = transpose(jnp.where(live, g, 0).astype(lhs.dtype))
        return jnp.where(live, d_lhs, jnp.nan), d_rhs, None

    poisoned.defvjp(fwd, bwd)
    monkeypatch.setattr(moe, "grouped_matmul", poisoned)


@pytest.fixture(autouse=True)
def _fresh_expert_traces():
    """The held layers of a process share one trace of their experts a
    shape (``moe._shared_experts``): a test that stands something in for
    what that trace calls must start, and leave, with none."""
    from relayrl_tpu.models import moe

    def clear():
        moe._shared_experts.clear_cache()
        moe._shared_experts_vjp.clear_cache()

    clear()
    yield
    clear()


@pytest.fixture(params=["sorted", "counted"])
def walk(request, monkeypatch):
    """Both walks of a held layer, each FORCED whatever the shapes' rule
    (``moe.held_form``) would pick: the sort of all N k slots with the
    absent experts' behind, and the rows counted into expert order."""
    from relayrl_tpu.models import moe

    monkeypatch.setattr(moe, "held_form", lambda *shape: request.param)
    return request.param


def _impala_update_of(policy):
    """``(update, state of shapes)``: IMPALA's update for ``policy`` as the
    learner builds it, and a state to lower or (given real parameters) run
    it with."""
    from relayrl_tpu.algorithms.impala import (
        ImpalaState, make_impala_tx, make_impala_update)

    tx = make_impala_tx(1e-4, 1.0)

    def state_of(params):
        return ImpalaState(params=params, opt_state=tx.init(params),
                           rng=jax.random.PRNGKey(0), step=jnp.int32(0))

    update = make_impala_update(
        policy, lr=1e-4, gamma=0.99, vf_coef=0.5, ent_coef=0.01,
        rho_bar=1.0, c_bar=1.0, max_grad_norm=1.0)
    return update, state_of


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


class TestHeldExperts:
    @pytest.mark.parametrize("first,count", [(0, 2), (2, 4), (5, 3), (0, 8)])
    def test_sparse_matches_dense_forward_and_every_gradient(self, first,
                                                             count, walk):
        """The held layer's sparse dispatch (absent slots sorted behind or
        the held ones counted, the tail selected away, the experts
        recomputed in the backward) against its dense form (the held
        columns of the [N, E] weight mask)."""
        params, x = _held_params()
        share = _share_of(params, first, count)

        def loss(dispatch):
            def f(p, x):
                y = _held_layer((first, count), dispatch).apply(p, x)
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
            np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-4,
                                       err_msg=jax.tree_util.keystr(path))

    @pytest.mark.parametrize("poisoned", [False, True],
                             ids=["zero_filled", "poisoned"])
    @pytest.mark.parametrize("passes", [1, 2, "max"])
    @pytest.mark.parametrize("k", [2, 4, 6, 8])
    @pytest.mark.parametrize("first,count", [(0, 8), (5, 9)])
    def test_row_buffers_walked_in_passes_match_dense(self, monkeypatch,
                                                      first, count, k,
                                                      passes, poisoned,
                                                      walk):
        """The compact layer — R-row buffers, ceil(live / R) passes, its
        own backward loop — on both of its walks against the dense form,
        whatever R: one pass with a tail of unwritten rows, two, and (every
        token routed to held experts) ceil(N k / R), at k = 2, 4, 6, 8.
        Forward, loss and EVERY gradient: tokens, the router
        (``top_w``'s only way back), the three stacks. Once on
        ``ragged_dot`` as it is, which zero-fills the rows past the groups,
        and once with NaN there, as on the chip they hold whatever they
        held: a read of one fails the case."""
        e, slots = 16, _N * k
        params, x = _held_params(e=e, k=k)
        if passes == "max":  # every choice among the held experts
            bias = np.full(e, -50.0, np.float32)
            bias[first:first + count] = 0.0
            params["params"]["moe_expert_bias"] = jnp.asarray(bias)
        share = _share_of(params, first, count)
        _, state = _held_layer((first, count), "dense", e, k).apply(
            share, x, mutable=["intermediates"])
        live = int(state["intermediates"]["expert_load"][0].sum())
        assert live == slots if passes == "max" else 2 <= live < slots
        rows = {1: live + 3, 2: -(-live // 2), "max": slots // 3 - 1}[passes]
        want = {1: 1, 2: 2, "max": 4}[passes]
        _row_buffer_of(monkeypatch, rows, slots, count, e)
        if poisoned:
            _poison_unwritten_rows(monkeypatch)

        def loss(dispatch):
            def f(p, x):
                y, state = _held_layer((first, count), dispatch, e, k).apply(
                    p, x, mutable=["intermediates"])
                return jnp.sum(jnp.sin(y) * x), (y, state["intermediates"])
            return f

        (ls, (ys, sown)), gs = jax.value_and_grad(
            loss("sparse"), (0, 1), has_aux=True)(share, x)
        (ld, (yd, _)), gd = jax.value_and_grad(
            loss("dense"), (0, 1), has_aux=True)(share, x)
        assert int(sown["row_passes"][0]) == want
        assert int(sown["row_buffer"][0]) == rows
        # what was put in expert order: a pass's rows, or all the slots
        assert int(sown["sorted_slots"][0]) == (
            want * rows if walk == "counted" else slots)
        np.testing.assert_allclose(ys, yd, atol=2e-5, rtol=1e-5)
        np.testing.assert_allclose(float(ls), float(ld), atol=2e-4,
                                   rtol=1e-5)
        for (path, a), b in zip(
                jax.tree_util.tree_flatten_with_path(gs)[0],
                jax.tree_util.tree_leaves(gd)):
            np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-4,
                                       err_msg=jax.tree_util.keystr(path))
        router = gs[0]["params"]["moe_gate"]["kernel"]
        assert float(jnp.abs(router).max()) > 0

    @pytest.mark.parametrize("k", [4, 8])
    @pytest.mark.parametrize("live,rows,want", [
        (31, 32, 1), (32, 32, 1), (33, 32, 2), (63, 32, 2), (64, 32, 2),
        (65, 32, 3), (0, 32, 0), ("empty_last", 32, 2)])
    def test_live_rows_at_the_buffer_s_edge(self, monkeypatch, live, rows,
                                            want, k, walk):
        """The crossing: a router made to send the layer exactly R - 1, R,
        R + 1, 2R - 1, 2R, 2R + 1 live rows (and none; and none to the last
        held expert), the unwritten rows poisoned: forward, trip count and
        every gradient — tokens, the router's input, the stacks — against
        the dense path."""
        from chip_smoke import forced_logits  # phase F walks them on chip
        from relayrl_tpu.models.moe import MoEMLP

        e, (first, count) = 16, (3, 8)
        empty_last = live == "empty_last"
        rng = np.random.default_rng(7)
        logits = forced_logits(rng, _N, k, e, (first, count),
                               40 if empty_last else live, empty_last)
        x = jnp.asarray(rng.standard_normal((1, _N, _D)), jnp.float32)
        # the router reads its own rows: the logits, through an identity
        route_x = jnp.asarray(logits).reshape(1, _N, e)
        assert e == _D

        def layer(dispatch):
            return MoEMLP(_D, _FF, e, k, jnp.float32, ffn="reglu",
                          dispatch=dispatch, use_bias=False,
                          held=(first, count))

        params = layer("dense").init(jax.random.PRNGKey(0), x, route_x)
        params["params"]["moe_gate"]["kernel"] = jnp.eye(e)
        _row_buffer_of(monkeypatch, rows, _N * k, count, e)
        _poison_unwritten_rows(monkeypatch)

        def loss(dispatch):
            def f(p, x, route_x):
                y, state = layer(dispatch).apply(
                    p, x, route_x, mutable=["intermediates"])
                return jnp.sum(jnp.sin(y) * x), (y, state["intermediates"])
            return jax.value_and_grad(f, (0, 1, 2), has_aux=True)

        (_, (ys, sown)), gs = loss("sparse")(params, x, route_x)
        (_, (yd, _)), gd = loss("dense")(params, x, route_x)
        load = np.asarray(sown["expert_load"][0])
        assert load.sum() == (40 if empty_last else live)
        assert not empty_last or load[-1] == 0
        assert int(sown["row_passes"][0]) == want
        np.testing.assert_allclose(ys, yd, atol=2e-5, rtol=1e-5)
        for (path, a), b in zip(
                jax.tree_util.tree_flatten_with_path(gs)[0],
                jax.tree_util.tree_leaves(gd)):
            np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-4,
                                       err_msg=jax.tree_util.keystr(path))

    @pytest.mark.parametrize("rows", [7, 16, 48])
    def test_sown_row_passes_is_the_count_the_loop_ran(self, monkeypatch,
                                                       rows, walk):
        """``row_passes`` is the loop's own count: as many as the calls a
        host callback sees the loop body make, forward and backward."""
        from relayrl_tpu.models import moe

        calls = {"fwd": 0, "bwd": 0}

        def counted(name, inner):
            def call(*args):
                jax.debug.callback(
                    lambda: calls.__setitem__(name, calls[name] + 1))
                return inner(*args)
            return call

        monkeypatch.setattr(moe, "_shared_experts",
                            counted("fwd", moe._shared_experts))
        monkeypatch.setattr(moe, "_shared_experts_vjp",
                            counted("bwd", moe._shared_experts_vjp))
        params, x = _held_params(e=16, k=4)
        share = _share_of(params, 5, 9)
        _row_buffer_of(monkeypatch, rows, _N * 4, 9, 16)

        def f(p, x):
            y, state = _held_layer((5, 9), "sparse", 16, 4).apply(
                p, x, mutable=["intermediates"])
            return jnp.sum(y), state["intermediates"]

        (_, sown), _ = jax.jit(jax.value_and_grad(f, has_aux=True))(share, x)
        jax.effects_barrier()
        live = int(sown["expert_load"][0].sum())
        assert calls == {"fwd": -(-live // rows), "bwd": -(-live // rows)}
        assert int(sown["row_passes"][0]) == calls["fwd"] >= 1

    def test_row_buffer_follows_the_held_share(self):
        from relayrl_tpu.models.moe import row_buffer

        # the two held cells of the benchmark: 8 and 16 of 64 experts held
        assert row_buffer(16384 * 4, 8, 64) == 16384
        assert row_buffer(16384 * 6, 16, 64) == 49152
        # whole row tiles, and never more rows than there are slots (a
        # decode step's handful: one pass over all of them)
        assert row_buffer(8192, 3, 64) == 1024
        assert row_buffer(2, 3, 8) == 2
        assert row_buffer(16384 * 8, 64, 64) == 16384 * 8

    def test_no_token_routed_to_held_experts_takes_no_pass(self,
                                                           monkeypatch,
                                                           walk):
        # every token to experts 2 and 3, the layer holds 4..7: no live
        # row, no pass, nothing added and nothing but zeros sent back
        _poison_unwritten_rows(monkeypatch)
        params, x = _held_params()
        bias = np.full(8, -50.0, np.float32)
        bias[2:4] = 50.0
        params["params"]["moe_expert_bias"] = jnp.asarray(bias)

        def f(p, x):
            y, state = _held_layer((4, 4)).apply(p, x,
                                                 mutable=["intermediates"])
            return jnp.sum(jnp.sin(y) * x), (y, state["intermediates"])

        (_, (y, sown)), grads = jax.value_and_grad(f, (0, 1), has_aux=True)(
            _share_of(params, 4, 4), x)
        assert int(sown["row_passes"][0]) == 0
        assert int(sown["expert_load"][0].sum()) == 0
        assert float(jnp.abs(y).max()) == 0.0
        for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
            assert float(jnp.abs(g).max()) == 0.0, jax.tree_util.keystr(path)

    def test_a_layer_that_holds_every_expert_lowers_the_plain_program(self):
        """``held`` naming all the experts is no held layer: the same
        StableHLO as ``held=None`` (the plain sparse dispatch: N k-row
        gathers, no loop), forward and backward; only a layer that holds a
        part of them walks row buffers in a loop."""
        params, x = _held_params()

        def text(held, p):
            def f(p, x):
                return jnp.sum(jnp.sin(_held_layer(held).apply(p, x)))
            return jax.jit(jax.value_and_grad(f, (0, 1))).lower(
                p, x).as_text()

        plain = text(None, params)
        assert text((0, 8), params) == plain
        assert "stablehlo.while" not in plain
        assert "stablehlo.while" in text((2, 4), _share_of(params, 2, 4))

    @pytest.mark.parametrize("chips", [1, 2, 4, 8])
    def test_the_shares_add_up_to_the_layer(self, chips, walk):
        # the router normalises over the k chosen of ALL experts, held or
        # not, so the chips' partial outputs sum to the whole layer's
        params, x = _held_params()
        whole = _held_layer(None).apply(params, x)
        count = 8 // chips
        parts = sum(_held_layer((c * count, count)).apply(
            _share_of(params, c * count, count), x) for c in range(chips))
        np.testing.assert_allclose(parts, whole, atol=2e-5, rtol=1e-5)

    @pytest.mark.parametrize("dispatch", DISPATCHES)
    def test_every_token_routed_to_held_experts_drops_nothing(self,
                                                              monkeypatch,
                                                              dispatch,
                                                              walk):
        # a bias that sends every token to experts 2 and 3: the layer that
        # holds exactly those computes the whole layer, all N*k slots
        from relayrl_tpu.models import moe

        params, x = _held_params()
        bias = np.full(8, -50.0, np.float32)
        bias[2:4] = 50.0
        params["params"]["moe_expert_bias"] = jnp.asarray(bias)
        whole = _held_layer(None).apply(params, x)
        # buffers sized for a quarter of the slots and a margin: all of
        # them arrive, and the layer walks its buffer as often as it takes
        monkeypatch.setattr(moe, "_ROW_TILE", 1)
        rows = moe.row_buffer(2 * _N, 2, 8)
        assert rows == 24
        y, state = _held_layer((2, 2), dispatch).apply(
            _share_of(params, 2, 2), x, mutable=["intermediates"])
        np.testing.assert_allclose(y, whole, atol=2e-5, rtol=1e-5)
        load = np.asarray(state["intermediates"]["expert_load"][0])
        assert load.tolist() == [_N, _N]
        assert int(state["intermediates"]["row_passes"][0]) == (
            -(-2 * _N // rows) if dispatch == "sparse" else 0)
        # and the layer that holds none of the chosen adds exactly nothing
        none = _held_layer((4, 4), dispatch).apply(
            _share_of(params, 4, 4), x)
        assert float(jnp.abs(none).max()) == 0.0

    def test_update_stats_count_the_held_slots(self):
        policy, params = _policy_params(
            moe_experts=8, moe_top_k=2, moe_router="sigmoid",
            moe_expert_bias=True, moe_held=[2, 3], moe_dense_layers=1,
            n_layers=3)
        assert "moe" not in params["params"]["block_0"]
        assert params["params"]["block_1"]["moe"]["moe_w_up"].shape[0] == 3
        obs = jnp.asarray(np.random.default_rng(2).standard_normal(
            (2, 8, 6)), jnp.float32)
        *_, stats = policy.evaluate_stats(params, obs,
                                          jnp.zeros((2, 8), jnp.int32))
        from relayrl_tpu.models.moe import expert_utilization

        util = expert_utilization(policy.arch, params, obs)
        assert sorted(util) == ["block_1", "block_2"]
        held = sum(float(u.sum()) for u in util.values()) * 16 * 2
        np.testing.assert_allclose(float(stats["moe_held_slots"]), held,
                                   rtol=1e-6)
        # shares of ALL the slots: the held experts' do not sum to 1
        assert all(float(u.sum()) < 1.0 and u.shape == (3,)
                   for u in util.values())
        np.testing.assert_allclose(
            float(stats["moe_load_max"]),
            max(float(u.max()) for u in util.values()), rtol=1e-6)

    def test_update_stats_count_the_row_passes(self, monkeypatch):
        from relayrl_tpu.data.batching import TrajectoryBatch
        from relayrl_tpu.models import moe

        def passes(**arch):
            policy, params = _policy_params(moe_experts=8, moe_top_k=2,
                                            n_layers=3, **arch)
            obs = jnp.asarray(np.random.default_rng(2).standard_normal(
                (2, 8, 6)), jnp.float32)
            *_, stats = jax.jit(policy.evaluate_stats)(
                params, obs, jnp.zeros((2, 8), jnp.int32))
            update, state_of = _impala_update_of(policy)
            batch = {name: jnp.asarray(a) for name, a in
                     TrajectoryBatch.zeros(2, 8, 6, 3, True).items()}
            _, metrics = jax.jit(update, donate_argnums=0)(state_of(params), {
                **batch, "obs": obs, "valid": jnp.ones((2, 8)),
                "act_mask": jnp.ones((2, 8, 3))})
            assert np.isfinite(float(metrics["LossTotal"]))
            assert float(metrics["moe_row_passes"]) == float(
                stats["moe_row_passes"])
            return float(stats["moe_row_passes"])

        # one pass a MoE layer: every expert held, or a share of them with
        # buffers that take what the router sends; 3 layers, then 2
        assert passes() == 3.0
        assert passes(moe_held=[2, 3], moe_dense_layers=1) == 2.0
        # buffers of 2 rows for 32 slots of which some 12 are live
        monkeypatch.setattr(moe, "_ROW_TILE", 1)
        monkeypatch.setattr(moe, "_ROW_MARGIN", 0.1)
        assert moe.row_buffer(32, 3, 8) == 2
        assert 4.0 < passes(moe_held=[2, 3], moe_dense_layers=1) <= 32.0

    # -- the counted walk: every index vector at the rows a pass holds ----

    @staticmethod
    def _routing(k, e, seed):
        rng = np.random.default_rng(seed)
        top_idx = np.stack([rng.permutation(e)[:k] for _ in range(_N)])
        return top_idx, rng.random((_N, k)).astype(np.float32)

    @staticmethod
    def _expert_order(top_idx, top_w, first, count):
        """The held slots by expert and, inside an expert, by token: (token,
        place among the token's held choices, local expert, weight) a row."""
        flat = []
        for t in range(_N):
            mine = [c for c in range(top_idx.shape[1])
                    if first <= top_idx[t, c] < first + count]
            flat += [(t, j, top_idx[t, c] - first, top_w[t, c])
                     for j, c in enumerate(mine)]
        return sorted(flat, key=lambda r: r[2])  # stable: token order

    @pytest.mark.parametrize("k,e,first,count", [
        (6, 16, 3, 2), (4, 8, 0, 8), (3, 8, 5, 3), (8, 16, 0, 5),
        (1, 4, 2, 1)])
    def test_compaction_counts_the_held_choices_into_expert_order(
            self, k, e, first, count):
        """``_compact`` against a loop over the tokens: a token's held
        choices in the order of its k — local expert, place among the k,
        weight —, the running counts each row of the order by expert and
        token is found by, WITHOUT a sort, the loads; and
        ``_at_choices`` puts a value a place back where its choice stands."""
        from relayrl_tpu.models import moe

        top_idx, top_w = self._routing(k, e, k + e)
        count_t, expert, choice, weight, running, load = (
            np.asarray(a) for a in moe._compact(
                jnp.asarray(top_idx, jnp.int32), jnp.asarray(top_w),
                (first, count)))  # _Held's fields, in their order
        h = min(k, count)
        assert expert.shape == choice.shape == weight.shape == (_N, h)
        order = self._expert_order(top_idx, top_w, first, count)
        assert load.tolist() == [sum(1 for r in order if r[2] == j)
                                 for j in range(count)]
        for t in range(_N):
            mine = [c for c in range(k)
                    if first <= top_idx[t, c] < first + count]
            assert count_t[t] == len(mine) <= h
            assert choice[t, :len(mine)].tolist() == mine
            assert (expert[t, :len(mine)] == top_idx[t, mine] - first).all()
            assert (weight[t, :len(mine)] == top_w[t, mine]).all()
            assert (expert[t, len(mine):] == -1).all()
        for at, (t, j, x, w) in enumerate(order):
            # row `at` is found at the first (expert, token) past it
            assert np.searchsorted(running, at, side="right") == x * _N + t
        assert running.shape == (count * _N,) and running[-1] == len(order)
        back = np.asarray(moe._at_choices(
            jnp.asarray(weight), jnp.asarray(count_t), jnp.asarray(choice),
            k))
        held = (top_idx >= first) & (top_idx < first + count)
        np.testing.assert_array_equal(back, np.where(held, top_w, 0))

    @pytest.mark.parametrize("rows", [5, 16, 37])
    @pytest.mark.parametrize("p", [0, 1, 2])
    def test_a_pass_finds_its_rows_by_counting(self, rows, p):
        """``_counted_pass``: rows ``[p R, (p + 1) R)`` of the order by expert
        and token — each row's token, place and weight, which are live, the
        pass-local group sizes — with no sort and no N k-sized vector."""
        from relayrl_tpu.models import moe

        k, e, first, count = 4, 8, 2, 4
        top_idx, top_w = self._routing(k, e, rows + p)
        held = moe._compact(jnp.asarray(top_idx, jnp.int32),
                            jnp.asarray(top_w), (first, count))
        token, place, w, live, sizes = (
            np.asarray(a) for a in moe._counted_pass(jnp.int32(p), rows,
                                                    held))
        order = self._expert_order(top_idx, top_w, first, count)
        mine = order[p * rows:(p + 1) * rows]
        assert live.tolist() == [True] * len(mine) + [False] * (
            rows - len(mine))
        assert sizes.tolist() == [
            sum(1 for r in mine if r[2] == j) for j in range(count)]
        assert token[:len(mine)].tolist() == [r[0] for r in mine]
        assert place[:len(mine)].tolist() == [r[1] for r in mine]
        assert w[:len(mine)].tolist() == [r[3] for r in mine]
        # the rows past the live ones point at rows that exist
        assert (0 <= token).all() and (token < _N).all()
        assert (0 <= place).all() and (place < min(k, count)).all()

    @pytest.mark.parametrize("n,block", [(24, 128), (24, 5), (300, 128),
                                         (256, 128), (7, 1)])
    def test_a_row_is_found_by_compares(self, monkeypatch, n, block):
        """``_first_past`` against ``searchsorted``: blocks that divide the
        counts and blocks that do not, runs of equal counts, rows past the
        last."""
        from relayrl_tpu.models import moe

        monkeypatch.setattr(moe, "_SEARCH_BLOCK", block)
        steps = np.random.default_rng(n).integers(0, 4, n)
        steps[n // 3: n // 2] = 0
        running = np.cumsum(steps)
        at = np.arange(running[-1] + 9)
        found = moe._first_past(jnp.asarray(running, jnp.int32),
                                jnp.asarray(at, jnp.int32))
        np.testing.assert_array_equal(
            found, np.searchsorted(running, at, side="right"))

    # the benchmark's seven held cells at 16,384 tokens: (k, held, experts)
    CELLS = {"nemotron3-super": (22, 8, 512), "kimi-linear": (8, 8, 256),
             "keye-vl2": (8, 16, 128), "qwen3next": (10, 32, 512),
             "nemotron-twotower": (6, 8, 128), "lfm2": (4, 8, 64),
             "smallthinker": (6, 16, 64)}

    @pytest.mark.parametrize("cell,form", [
        ("nemotron3-super", "counted"),                # N k = 32 R
        ("kimi-linear", "counted"),          # 8 divides k, N k = 16 R
        ("keye-vl2", "counted"),             # 8 divides k (N k = 4 R)
        ("qwen3next", "sorted"), ("nemotron-twotower", "sorted"),
        ("lfm2", "sorted"), ("smallthinker", "sorted")])
    def test_the_rule_s_pick_in_the_benchmark_s_cells(self, cell, form):
        """A held layer counts its rows where its slots would run
        token-major (8 divides k) or where N k >= 16 R, and sorts them
        elsewhere: the seven cells, by their shapes alone."""
        from relayrl_tpu.models import moe

        k, held, e = self.CELLS[cell]
        assert moe.held_form(16384, k, held, e) == form
        # at the one token a model's parameters are made at, a row a slot
        assert moe.held_form(1, k, held, e) == "sorted"
        key, said, text = moe.dispatch_form(16384, k, e, (0, held), None)
        rows = moe.row_buffer(16384 * k, held, e)
        assert (key, said) == ((16384 * k, rows, held, e, k), form)
        assert text == f"slots={16384 * k} rows={rows} held={held}/{e} k={k}"

    @pytest.mark.parametrize("n,k,held,e,form", [
        # 8 divides k, whatever the slots a row (2 here) ...
        (16384, 8, 16, 64, "counted"), (16384, 16, 16, 64, "counted"),
        # ... and either side of it at the same share
        (16384, 7, 16, 64, "sorted"), (16384, 9, 16, 64, "sorted"),
        # ... but not where the margin makes the buffers N k rows long
        (16384, 8, 32, 64, "sorted"),
        # N k = 16 R exactly (2 x 8 / 256 of the slots, whole tiles) and
        # one tile of rows more: 15.9 slots a row
        (16384, 6, 8, 256, "counted"), (16384 + 1024, 6, 8, 256, "sorted"),
        # every slot a row (a decode step: R = N k): sorted whatever k
        (1, 6, 8, 64, "sorted"), (1, 8, 8, 64, "sorted"),
        (1, 22, 8, 512, "sorted"), (7, 16, 8, 64, "sorted"),
        # ... up to the last N whose slots fill one tile of rows (keye-vl2's
        # 16 of 128 at k = 8: 64 tokens), and the first past it
        (64, 8, 16, 128, "sorted"), (65, 8, 16, 128, "counted")])
    def test_the_rule_either_side_of_its_two_conditions(self, n, k, held,
                                                        e, form):
        from relayrl_tpu.models import moe

        rows = moe.row_buffer(n * k, held, e)
        assert (rows < n * k and (k % 8 == 0 or n * k >= 16 * rows)) == (
            form == "counted")
        assert moe.held_form(n, k, held, e) == form
        # where every expert is held there is no held walk to pick
        assert moe.dispatch_form(n, k, e, None, None)[1] == "plain"
        assert moe.dispatch_form(n, k, e, (0, e), "sparse")[1] == "plain"
        assert moe.dispatch_form(n, k, e, (0, held), "dense") is None

    @pytest.mark.parametrize("held,dispatch,k,form,rows", [
        ((2, 3), None, 2, "sorted", 24), ((2, 3), None, 8, "counted", 96),
        ((2, 3), "sparse", 12, "counted", 96),   # k of the 8 there are
        (None, None, 2, "plain", 32), ((0, 8), "sparse", 2, "plain", 32),
        ((2, 3), "dense", 2, "dense", 0), (None, "dense", 2, "dense", 0)])
    def test_the_layer_takes_the_branch_its_record_says(
            self, monkeypatch, held, dispatch, k, form, rows):
        """ONE resolution (``layer_form``) for the layer and for the
        policy's record: what the layer sows — the rows of its buffers, the
        slots it put in expert order — is what ``dispatch_form`` says of
        the same arguments, on every branch."""
        from relayrl_tpu.models import moe

        monkeypatch.setattr(moe, "_ROW_TILE", 1)
        n, e = 16, 8
        said = moe.layer_form(n, k, e, held, dispatch)
        n_held = held[1] if held else e
        assert said == (form, min(k, e), held[0] if held else 0, n_held,
                        rows)
        record = moe.dispatch_form(n, k, e, held, dispatch)
        assert (record is None) == (form == "dense")
        if record is not None:
            assert record[:2] == ((n * said.k, rows, n_held, e, said.k),
                                  form)
        layer = moe.MoEMLP(_D, _FF, e, k, jnp.float32, held=held,
                           dispatch=dispatch)
        x = jnp.ones((2, n // 2, _D))
        _, state = layer.apply(layer.init(jax.random.PRNGKey(0), x), x,
                               mutable=["intermediates"])
        sown = state["intermediates"]
        assert int(sown["row_buffer"][0]) == rows
        passes = int(sown["row_passes"][0])
        assert int(sown["sorted_slots"][0]) == {
            "dense": 0, "counted": passes * rows}.get(form, n * said.k)

    @pytest.mark.parametrize("held,dispatch,said", [
        ((6, 3), None, "moe_held"), ((0, 0), None, "moe_held"),
        ((2, 3), "ragged", "unknown moe_dispatch")])
    def test_a_layer_and_its_record_refuse_alike(self, held, dispatch,
                                                 said):
        from relayrl_tpu.models import moe

        with pytest.raises(ValueError, match=said):
            moe.dispatch_form(16, 2, 8, held, dispatch)
        layer = moe.MoEMLP(_D, _FF, 8, 2, jnp.float32, held=held,
                           dispatch=dispatch)
        with pytest.raises(ValueError, match=said):
            layer.init(jax.random.PRNGKey(0), jnp.ones((2, 8, _D)))

    @pytest.mark.parametrize("poisoned", [False, True],
                             ids=["zero_filled", "poisoned"])
    @pytest.mark.parametrize("case", [
        "more_choices_than_held",   # k 6 of 16 experts, 2 held: h < k
        "sixteen_slots_a_row",      # N k / R >= 16
        "two_slots_a_row",          # N k / R = 2
        "last_experts_held",        # the held range ends at E
        "straddle",                 # a token's held rows in two passes
        "a_row_a_pass",             # R = 1: every live row a pass of its own
        "all_held",                 # every token's k: ceil(N k / R) passes
        "none_held"])               # no live row: no pass
    def test_the_counted_walk_matches_dense(self, monkeypatch, case,
                                            poisoned):
        """The layer that compacts its held choices and counts them into
        expert order (forced, whatever its shapes), its rows added back at
        their tokens, against the dense path: forward, loss, every
        gradient, the passes and ``sorted_slots`` = passes x R."""
        from relayrl_tpu.models import moe

        e, k, (first, count), rows, route_to = {
            "more_choices_than_held": (16, 6, (7, 2), 9, None),
            "sixteen_slots_a_row": (16, 16, (4, 8), 24, None),
            "two_slots_a_row": (16, 2, (0, 8), 24, None),
            "last_experts_held": (16, 4, (12, 4), 9, None),
            "straddle": (16, 4, (2, 6), 7, (2, 6)),
            "a_row_a_pass": (16, 4, (2, 6), 1, (2, 6)),
            "all_held": (16, 4, (5, 9), 13, (5, 9)),
            "none_held": (16, 4, (5, 9), 13, (0, 4)),
        }[case]
        slots = _N * k
        monkeypatch.setattr(moe, "held_form", lambda *shape: "counted")
        params, x = _held_params(e=e, k=k)
        if route_to is not None:  # every choice among these experts
            bias = np.full(e, -50.0, np.float32)
            bias[route_to[0]:route_to[0] + route_to[1]] = 0.0
            params["params"]["moe_expert_bias"] = jnp.asarray(bias)
        share = _share_of(params, first, count)
        _row_buffer_of(monkeypatch, rows, slots, count, e)
        assert slots / rows >= {"sixteen_slots_a_row": 16,
                                "two_slots_a_row": 2}.get(case, 0)
        assert moe.dispatch_form(_N, k, e, (first, count), None)[1:] == (
            "counted",
            f"slots={slots} rows={rows} held={count}/{e} k={k}")
        if poisoned:
            _poison_unwritten_rows(monkeypatch)

        def loss(dispatch):
            def f(p, x):
                y, state = _held_layer((first, count), dispatch, e, k).apply(
                    p, x, mutable=["intermediates"])
                return jnp.sum(jnp.sin(y) * x), (y, state["intermediates"])
            return f

        (ls, (ys, sown)), gs = jax.value_and_grad(
            loss("sparse"), (0, 1), has_aux=True)(share, x)
        (ld, (yd, _)), gd = jax.value_and_grad(
            loss("dense"), (0, 1), has_aux=True)(share, x)
        live = int(sown["expert_load"][0].sum())
        passes = -(-live // rows)
        assert live == {"all_held": slots, "a_row_a_pass": slots,
                        "none_held": 0}.get(case, live)
        assert int(sown["row_passes"][0]) == passes
        assert int(sown["sorted_slots"][0]) == passes * rows
        if case == "straddle":  # a token's rows on both sides of a pass
            load = np.asarray(sown["expert_load"][0])
            assert passes > 1 and (np.cumsum(load) % rows != 0).any()
        np.testing.assert_allclose(ys, yd, atol=2e-5, rtol=1e-5)
        np.testing.assert_allclose(float(ls), float(ld), atol=2e-4,
                                   rtol=1e-5)
        for (path, a), b in zip(
                jax.tree_util.tree_flatten_with_path(gs)[0],
                jax.tree_util.tree_leaves(gd)):
            np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-4,
                                       err_msg=jax.tree_util.keystr(path))

    def test_a_counted_layer_s_gradient_sorts_nothing_and_moves_no_n_k_rows(
            self, monkeypatch):
        """The jaxpr of a counted layer's gradient below the router: no
        ``sort`` at all (the expert order is counted), every gather reads R
        or N rows, and what is scattered is R rows a pass — the pass's rows
        at their tokens (forward, and the tokens' gradient) and the rows'
        weight gradients at their places. (The router's own ``top_k`` and the transpose of
        its ``take_along_axis`` are the router's: the layer is given
        ``top_w`` / ``top_idx``.)"""
        from relayrl_tpu.models import moe

        n, k, e, held, rows = 64, 6, 16, (3, 2), 24
        _row_buffer_of(monkeypatch, rows, n * k, held[1], e)
        rng = np.random.default_rng(0)
        top_idx = jnp.asarray(np.stack(
            [rng.permutation(e)[:k] for _ in range(n)]), jnp.int32)
        top_w = jnp.asarray(rng.random((n, k)), jnp.float32)
        tokens = jnp.asarray(rng.standard_normal((n, _D)), jnp.float32)
        stacks = (jnp.ones((held[1], _D, _FF)), None,
                  jnp.ones((held[1], _FF, _D)))

        def f(tokens, top_w, stacks):
            y, _ = moe._counted_experts("gelu", rows, held, tokens, top_w,
                                        top_idx, stacks)
            return jnp.sum(jnp.sin(y))

        jaxpr = jax.make_jaxpr(jax.grad(f, (0, 1, 2)))(tokens, top_w, stacks)

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                yield eqn
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from walk(sub)

        eqns = list(walk(jaxpr.jaxpr))
        assert not [eqn for eqn in eqns if eqn.primitive.name == "sort"]
        scatters = [eqn for eqn in eqns
                    if eqn.primitive.name.startswith("scatter")]
        assert [eqn.primitive.name for eqn in scatters] == [
            "scatter-add"] * 3
        for eqn in scatters:
            operand, _, updates = (v.aval.shape for v in eqn.invars)
            assert operand[0] == n and updates[0] == rows
        gathers = [eqn for eqn in eqns if eqn.primitive.name == "gather"]
        assert gathers
        for eqn in gathers:
            assert eqn.outvars[0].aval.shape[0] in (rows, n), eqn
        # ... and no gather or scatter takes N k indices (one: a slice)
        for eqn in gathers + scatters:
            indices = eqn.invars[1].aval.shape
            assert int(np.prod(indices[:-1])) in (1, rows, n), eqn

    @pytest.mark.parametrize("held,k,rows,form", [
        ([2, 3], 2, 24, "sorted"), ([2, 3], 8, 96, "counted"),
        (None, 2, 32, "plain")])
    def test_the_policy_says_once_a_shape_what_its_dispatch_is(
            self, capsys, monkeypatch, held, k, rows, form):
        """``Policy.moe_backends`` and one ``[moe]`` line a distinct layer
        shape, as ``[kda]``, ``[conv]`` and ``[index]`` say theirs: three
        layers of one shape, traced twice, say it once — from the block,
        before the layer is called (nothing of it inside the layer)."""
        from relayrl_tpu.models import moe

        monkeypatch.setattr(moe, "_ROW_TILE", 1)
        policy, params = _policy_params(
            moe_experts=8, moe_top_k=k, n_layers=3,
            **({"moe_held": held} if held else {}))
        capsys.readouterr()
        batch = (jnp.zeros((2, 8, 6)), jnp.zeros((2, 8), jnp.int32))
        for _ in range(2):
            jax.eval_shape(policy.evaluate, params, *batch)
        n_held = held[1] if held else 8
        # (beside the one-token shape its parameters were made at)
        assert policy.moe_backends[(16 * k, rows, n_held, 8, k)] == form
        assert len(policy.moe_backends) == 2
        said = [line for line in capsys.readouterr().out.splitlines()
                if line.startswith("[moe]")]
        assert said == [f"[moe] slots={16 * k} rows={rows} held={n_held}/8 "
                        f"k={k} -> {form} (platform cpu)"]
        # the dense dispatch walks no slots: nothing to say
        dense, p = _policy_params(moe_experts=8, moe_top_k=k,
                                  moe_dispatch="dense")
        jax.eval_shape(dense.evaluate, p, *batch)
        assert dict(dense.moe_backends) == {}
        assert "[moe]" not in capsys.readouterr().out

    def test_held_layers_of_a_trunk_share_one_trace_of_their_experts(self):
        """Three held layers of one shape: the lowered update holds ONE
        function for a pass's experts and ONE for their transpose, called
        from each layer's two pass loops (set-up time: the kernels are
        traced and lowered once, not once a layer and direction)."""
        from relayrl_tpu.data.batching import TrajectoryBatch

        policy = build_policy({**ARCH, "moe_experts": 8, "moe_top_k": 2,
                               "moe_held": [2, 3], "n_layers": 3})
        update, state_of = _impala_update_of(policy)
        state = jax.eval_shape(
            lambda: state_of(policy.init_params(jax.random.PRNGKey(0))))
        text = jax.jit(update, donate_argnums=0).lower(
            state, TrajectoryBatch.zeros(2, 8, 6, 3, True)).as_text()
        funcs = [ln.split("@")[1].split("(")[0] for ln in text.splitlines()
                 if "func.func private @" in ln and "experts" in ln]
        assert sorted(funcs) == ["_experts", "_shared_experts_vjp"], funcs
        assert text.count("call @_experts(") == 3
        assert text.count("call @_shared_experts_vjp(") == 3

    def test_a_range_outside_the_experts_is_refused(self):
        with pytest.raises(ValueError, match="moe_held"):
            _policy_params(moe_experts=4, moe_held=[2, 3])

    def test_the_pipeline_family_refuses_what_it_cannot_build(self):
        for key, value in (("layer_types", ["conv", "conv"]),
                           ("n_kv_heads", 1), ("moe_held", [0, 1])):
            with pytest.raises(ValueError, match="transformer_pp_discrete"):
                build_policy({**ARCH, "kind": "transformer_pp_discrete",
                              key: value})


# -- the router's input apart from the experts', and ReGLU (SmallThinker) ----

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
