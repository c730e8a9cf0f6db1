"""The expert layer that is told which experts it holds (``held``): its
sparse dispatch against its dense form, the row buffer and the passes over
it, what the update's statistics count, the two ways of finding a pass's rows
and the rule that picks between them. (The walks against the dense form,
case by case, are ``tests/test_moe_walks.py``.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from relayrl_tpu.models import build_policy
from _moe import (  # noqa: F401  (the fixtures are used by name)
    _D,
    _FF,
    _N,
    ARCH,
    DISPATCHES,
    _fresh_expert_traces,
    _held_layer,
    _held_params,
    _impala_update_of,
    _poison_unwritten_rows,
    _policy_params,
    _row_buffer_of,
    _share_of,
    walk,
)


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
