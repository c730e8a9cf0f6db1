"""The held expert layer's two walks (``moe.held_form``: sorted, counted)
against its dense form, whatever the row buffer: every pass count, the
buffer's edges, rows the TPU kernels never write. Forward, loss and every
gradient. (The layer's other cases are ``tests/test_moe_held.py``.)"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _moe import (  # noqa: F401  (the fixtures are used by name)
    _D,
    _FF,
    _N,
    _fresh_expert_traces,
    _held_layer,
    _held_params,
    _poison_unwritten_rows,
    _row_buffer_of,
    _share_of,
    walk,
)


@functools.cache
def _dense_side_of_a_walk(first, count, k, all_held, e=16):
    """What ``test_row_buffers_walked_in_passes_match_dense`` compares a
    walk with, once for the eight cases (two walks, four buffers) that share
    it — the dense form knows no buffer and no walk: the share's
    parameters, the tokens, how many slots the router sends the held
    experts, and the dense layer's loss, output and every gradient."""
    params, x = _held_params(e=e, k=k)
    if all_held:    # every choice among the held experts
        bias = np.full(e, -50.0, np.float32)
        bias[first:first + count] = 0.0
        params["params"]["moe_expert_bias"] = jnp.asarray(bias)
    share = _share_of(params, first, count)

    def loss(p, x):
        y, state = _held_layer((first, count), "dense", e, k).apply(
            p, x, mutable=["intermediates"])
        return jnp.sum(jnp.sin(y) * x), (y, state["intermediates"])

    (ld, (yd, sown)), gd = jax.value_and_grad(
        loss, (0, 1), has_aux=True)(share, x)
    return share, x, int(sown["expert_load"][0].sum()), ((ld, yd), gd)


class TestHeldWalks:
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
        share, x, live, ((ld, yd), gd) = _dense_side_of_a_walk(
            first, count, k, passes == "max")
        assert live == slots if passes == "max" else 2 <= live < slots
        rows = {1: live + 3, 2: -(-live // 2), "max": slots // 3 - 1}[passes]
        want = {1: 1, 2: 2, "max": 4}[passes]
        _row_buffer_of(monkeypatch, rows, slots, count, e)
        if poisoned:
            _poison_unwritten_rows(monkeypatch)

        def loss(p, x):
            y, state = _held_layer((first, count), "sparse", e, k).apply(
                p, x, mutable=["intermediates"])
            return jnp.sum(jnp.sin(y) * x), (y, state["intermediates"])

        (ls, (ys, sown)), gs = jax.value_and_grad(
            loss, (0, 1), has_aux=True)(share, x)
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
