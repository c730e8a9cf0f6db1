"""``ops/index_pallas.py``: the indexer's scores, the exact top-``k``
selection and the scores' backward as Pallas kernels, run by the Pallas
interpreter on the CPU and held to the plain form they stand for
(``ops/sparse_attn.index_scores``, ``top_k_mask`` and ``jax.grad`` of the
first): the set against ``lax.top_k`` on the kernel's own scores, the
scores, every gradient, the recompute with the thresholds kept, the rule
that picks them, and the tiled whole through either form."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from relayrl_tpu.models.layers import sparse_attention as layer
from relayrl_tpu.ops import index_pallas, sparse_attn, sparse_attn_pallas

HI, DI = 2, 64          # index heads of half a lane tile
N_Q, N_K = 256, 384     # two query blocks of 128 over three key blocks


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Query blocks of 128, so that ``N_Q`` queries are more than one."""
    monkeypatch.setattr(index_pallas, "_QUERY_BLOCKS", (128,))
    index_pallas._build.cache_clear()
    index_pallas._make_rule.cache_clear()
    yield
    index_pallas._build.cache_clear()
    index_pallas._make_rule.cache_clear()


def _operands(case="random", seed=0, dtype=jnp.float32, n_q=N_Q):
    """``(qi, ki, w)`` of ``n_q`` queries over ``N_K`` keys. ``case``:
    "random"; "zeros" (a ``w`` that is zero for half the rows: rows of exact
    zeros, every seen key tied); "signed_zeros" (``w`` of either sign over
    keys half of which are zero rows: scores ``-0.0`` and ``+0.0``);
    "few_values" (scores from a handful of values: more ties at the
    threshold than there is room for)."""
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return rng.standard_normal(shape).astype(np.float32)

    qi, ki, w = normal(n_q, HI, DI), normal(N_K, DI), normal(n_q, HI)
    if case == "zeros":
        w[::2] = 0.0
    elif case == "signed_zeros":
        qi, ki = np.abs(qi), -np.abs(ki)
        ki[::3] = np.abs(ki[::3])
    elif case == "few_values":
        qi = np.zeros_like(qi)
        qi[:, :, 0] = 1.0
        ki = np.zeros_like(ki)
        ki[:, 0] = rng.integers(-1, 3, N_K)
        w = np.ones_like(w)
    return tuple(jnp.asarray(a, dtype) for a in (qi, ki, w))


def _kernels(qi, ki, w, pos, topk, select=True, want_scores=True):
    return index_pallas.index_select(qi, ki, w, pos, topk, select,
                                     want_scores, interpret=True)


def _top_k_set(scores, pos, topk):
    """The set ``lax.top_k`` returns for each row's seen scores, as a bool
    ``[Tq, Tk]`` (an unseen key sorts last: ``-inf``, and is dropped)."""
    seen = np.asarray(pos)[:, None] >= np.arange(scores.shape[1])[None, :]
    _, idx = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf),
                           min(topk, scores.shape[1]))
    keep = np.zeros(scores.shape, bool)
    np.put_along_axis(keep, np.asarray(idx), True, axis=1)
    return keep & seen


class TestTheSelection:
    @pytest.mark.parametrize("first", [0, 64, 128])
    @pytest.mark.parametrize("case", ["random", "zeros", "signed_zeros",
                                      "few_values"])
    def test_keep_is_the_top_k_set_of_the_kernels_own_scores(self, case,
                                                             first):
        """Ties to the lower index, ``-0.0`` below ``+0.0``, rows that see
        fewer than ``topk`` keys (the first 48 positions), a tile whose
        first position is not 0, two query blocks over three key blocks."""
        qi, ki, w = _operands(case, seed=first)
        pos, topk = first + jnp.arange(N_Q), 48
        keep, scores = _kernels(qi, ki, w, pos, topk)
        assert keep.dtype == jnp.int8 and scores.dtype == jnp.float32
        assert keep.shape == scores.shape == (N_Q, N_K)
        want = _top_k_set(scores, pos, topk)
        assert np.array_equal(np.asarray(keep) != 0, want)
        assert np.array_equal(np.asarray(keep).sum(-1),
                              np.minimum(np.asarray(pos) + 1, topk))
        # ... which is what the plain form's bisection finds on them
        seen = pos[:, None] >= jnp.arange(N_K)[None, :]
        assert np.array_equal(sparse_attn.top_k_mask(scores, seen, topk),
                              want)
        if case == "signed_zeros":     # rows of exact zeros among the rest
            assert (np.asarray(scores)[np.asarray(seen)] == 0.0).any()
        if case == "few_values":    # the threshold's ties outnumber the room
            row = np.asarray(scores)[-1]
            kth = np.sort(row[:int(pos[-1]) + 1])[-topk]
            assert (row[:int(pos[-1]) + 1] == kth).sum() > (
                np.asarray(keep)[-1] & (row == kth)).sum() > 0

    def test_the_order_is_ordered_bits(self):
        """The kernels' signed key is ``_ordered_bits`` with the top bit
        flipped: the floats' total order, ``-0.0`` below ``+0.0``."""
        x = jnp.asarray([-jnp.inf, -3.5, -1e-45, -0.0, 0.0, 1e-45, 2.0 ** -126,
                         1.0, 3.5, jnp.inf], jnp.float32)
        key = np.asarray(index_pallas._ordered_key(x))
        assert (np.diff(key.astype(np.int64)) > 0).all()
        assert np.array_equal(key.view(np.uint32) ^ np.uint32(1 << 31),
                              sparse_attn._ordered_bits(x))

    def test_the_positions_need_no_order(self):
        qi, ki, w = _operands()
        pos, topk = 64 + jnp.arange(N_Q), 48
        order = jnp.asarray(np.random.default_rng(1).permutation(N_Q))
        keep, scores = _kernels(qi[order], ki, w[order], pos[order], topk)
        want_keep, want = _kernels(qi, ki, w, pos, topk)
        seen = (pos[:, None] >= jnp.arange(N_K)[None, :])[order]
        assert np.array_equal(keep, want_keep[order])
        assert np.array_equal(jnp.where(seen, scores, 0.0),
                              jnp.where(seen, want[order], 0.0))

    @pytest.mark.parametrize("want_scores", [True, False])
    def test_a_stage_that_does_not_select_keeps_every_seen_key(
            self, want_scores):
        qi, ki, w = _operands()
        pos = 128 + jnp.arange(N_Q)
        fn = functools.partial(_kernels, topk=N_K, select=False,
                               want_scores=want_scores)
        keep, scores = fn(qi, ki, w, pos)
        seen = pos[:, None] >= jnp.arange(N_K)[None, :]
        assert np.array_equal(np.asarray(keep) != 0, seen)
        assert (scores is None) == (not want_scores)
        text = str(jax.make_jaxpr(fn)(qi, ki, w, pos))
        assert index_pallas.SELECT_NAME in text
        assert index_pallas.SEARCH_NAME not in text     # nothing is searched

    def test_the_thresholds_are_the_plain_forms(self):
        """``kth`` is ``top_k_mask``'s own threshold: the ordered bits of
        the row's ``topk``-th largest seen score, 0 where a row sees fewer;
        ``room`` the ties it keeps."""
        qi, ki, w = _operands("few_values")
        pos, topk = 64 + jnp.arange(N_Q), 48
        search, rule = index_pallas._make_rule(
            (N_Q, N_K, HI, DI), "float32", topk, True, True, True)
        kth, room = np.asarray(search(qi, ki, w, pos))
        _, scores = rule(qi, ki, w, pos, jnp.stack([kth, room]))
        seen = np.asarray(pos)[:, None] >= np.arange(N_K)[None, :]
        bits = np.where(seen, sparse_attn._ordered_bits(scores), 0)
        for t in (0, 10, 100, N_Q - 1):
            want = np.sort(bits[t])[-topk] if seen[t].sum() >= topk else 0
            assert np.uint32(kth[t]) == want
            assert room[t] == (topk - (bits[t] > want).sum() if want else 0)


class TestTheScores:
    @pytest.mark.parametrize("first", [0, 128])
    def test_scores_are_index_scores_to_float32_rounding(self, first):
        qi, ki, w = _operands(seed=2)
        pos = first + jnp.arange(N_Q)
        _, scores = _kernels(qi, ki, w, pos, 48)
        seen = pos[:, None] >= jnp.arange(N_K)[None, :]
        want = sparse_attn.index_scores(qi, ki, w)
        # the key blocks a query block never sees are not computed: zeros
        live = (int(pos[127]) // 128 + 1) * 128
        assert float(jnp.abs(scores[:128, live:]).max(initial=0.0)) == 0.0
        np.testing.assert_allclose(jnp.where(seen, scores, 0.0),
                                   jnp.where(seen, want, 0.0), atol=2e-6,
                                   rtol=2e-6)

    def test_in_bfloat16_the_products_are_the_plain_forms(self):
        qi, ki, w = _operands(seed=3, dtype=jnp.bfloat16)
        pos = 128 + jnp.arange(N_Q)
        keep, scores = _kernels(qi, ki, w, pos, 48)
        seen = pos[:, None] >= jnp.arange(N_K)[None, :]
        want = sparse_attn.index_scores(qi, ki, w)
        np.testing.assert_allclose(jnp.where(seen, scores, 0.0),
                                   jnp.where(seen, want, 0.0), atol=1e-5,
                                   rtol=1e-5)
        assert np.array_equal(np.asarray(keep) != 0,
                              _top_k_set(scores, pos, 48))


class TestBackward:
    @staticmethod
    def _grads(scores_of, operands, weigh):
        return jax.grad(lambda *a: weigh(scores_of(*a)),
                        argnums=(0, 1, 2))(*operands)

    @pytest.mark.parametrize("first", [0, 128])
    @pytest.mark.parametrize("cotangent", ["random", "index_kl"])
    def test_dqi_dki_dw_are_autodiffs_of_index_scores(self, first, cotangent):
        operands = _operands(seed=4)
        pos, topk = first + jnp.arange(N_Q), 48
        keep = _kernels(*operands, pos, topk)[0] != 0
        if cotangent == "random":
            weight = jnp.asarray(np.random.default_rng(5).standard_normal(
                (N_Q, N_K)), jnp.float32) * (
                    pos[:, None] >= jnp.arange(N_K)[None, :])

            def weigh(scores):
                return jnp.sum(scores * weight)
        else:
            p_hat = jax.nn.softmax(jnp.where(keep, jnp.asarray(
                np.random.default_rng(6).standard_normal((N_Q, N_K)),
                jnp.float32), -1e30), axis=-1)

            def weigh(scores):
                return jnp.sum(sparse_attn.index_kl(p_hat, scores, keep))

        got = self._grads(lambda *a: _kernels(*a, pos, topk)[1], operands,
                          weigh)
        want = self._grads(sparse_attn.index_scores, operands, weigh)
        for name, a, b in zip(("qi", "ki", "w"), got, want):
            assert a.shape == b.shape and a.dtype == b.dtype
            np.testing.assert_allclose(a, b, atol=3e-5, rtol=1e-5,
                                       err_msg="d" + name)
        # the keys past the tile's last query get exact zeros
        assert float(jnp.abs(got[1][first + N_Q:]).max(initial=0.0)) == 0.0

    def test_in_bfloat16(self):
        operands = _operands(seed=7, dtype=jnp.bfloat16)
        pos = 128 + jnp.arange(N_Q)
        weight = jnp.asarray(np.random.default_rng(8).standard_normal(
            (N_Q, N_K)), jnp.float32) * (
                pos[:, None] >= jnp.arange(N_K)[None, :])
        got = self._grads(lambda *a: _kernels(*a, pos, 48)[1], operands,
                          lambda s: jnp.sum(s * weight))
        want = self._grads(sparse_attn.index_scores, operands,
                           lambda s: jnp.sum(s * weight))
        for name, a, b in zip(("qi", "ki", "w"), got, want):
            assert a.dtype == jnp.bfloat16
            np.testing.assert_allclose(
                a.astype(jnp.float32), b.astype(jnp.float32), atol=6e-2,
                rtol=2e-2, err_msg="d" + name)

    def test_no_gradient_through_the_set_or_the_thresholds(self):
        qi, ki, w = _operands()
        pos, topk = 64 + jnp.arange(N_Q), 48
        search, rule = index_pallas._make_rule(
            (N_Q, N_K, HI, DI), "float32", topk, True, True, True)
        kth_room = search(qi, ki, w, pos)
        (keep, scores), pull = jax.vjp(rule, qi, ki, w, pos, kth_room)
        dqi, dki, dw, dpos, dkth = pull((
            np.zeros(keep.shape, jax.dtypes.float0), jnp.ones_like(scores)))
        assert dpos.dtype == jax.dtypes.float0 == dkth.dtype
        assert (dqi.shape, dki.shape, dw.shape) == (qi.shape, ki.shape,
                                                    w.shape)
        # a function of ``keep`` alone moves nothing
        zero = jax.grad(lambda *a: jnp.sum(_kernels(*a, pos, topk)[0].astype(
            jnp.float32)), argnums=(0, 1, 2), allow_int=True)(qi, ki, w)
        assert all(float(jnp.abs(g).max()) == 0.0 for g in zero)


class TestTheRecompute:
    @staticmethod
    def _tile(operands, pos, topk):
        """A tile's indexer and a loss of its scores over its set, as
        ``ops/sparse_attn._sequence`` checkpoints a tile: the thresholds
        kept by name and nothing else."""
        def tile(*a):
            keep, scores = _kernels(*a, pos, topk)
            return jnp.sum(jnp.where(keep != 0, jnp.square(scores), 0.0))

        return jax.checkpoint(
            tile, policy=jax.checkpoint_policies.save_only_these_names(
                sparse_attn.LSE_NAME, sparse_attn.KTH_NAME))(*operands)

    @staticmethod
    def _calls(text, name):
        import re

        return len(re.findall(rf"name={name}\n", text))

    def test_with_the_thresholds_kept_no_search_runs_again(self):
        operands = _operands("few_values", seed=9)
        pos, topk = 64 + jnp.arange(N_Q), 48
        grad = jax.grad(lambda *a: self._tile(a, pos, topk),
                        argnums=(0, 1, 2))
        text = str(jax.make_jaxpr(grad)(*operands))
        # forward: one search, one selection; backward: the selection made
        # again from the kept thresholds, then the scores' backward
        assert self._calls(text, index_pallas.SEARCH_NAME) == 1
        assert self._calls(text, index_pallas.SELECT_NAME) == 2
        assert self._calls(text, index_pallas.BWD_NAME) == 1

        def plain(*a):
            keep, scores = _kernels(*a, pos, topk)
            return jnp.sum(jnp.where(keep != 0, jnp.square(scores), 0.0))

        # outside a checkpoint nothing keeps them: the search runs
        text = str(jax.make_jaxpr(jax.grad(plain, argnums=(0, 1, 2)))(
            *operands))
        assert self._calls(text, index_pallas.SEARCH_NAME) == 1
        assert self._calls(text, index_pallas.SELECT_NAME) == 1
        for a, b in zip(grad(*operands),
                        jax.grad(plain, argnums=(0, 1, 2))(*operands)):
            assert np.array_equal(a, b)     # the same set, bit for bit

    def test_the_selection_from_kept_thresholds_is_the_forwards_set(self):
        operands = _operands("zeros", seed=10)
        pos, topk = 128 + jnp.arange(N_Q), 48
        search, rule = index_pallas._make_rule(
            (N_Q, N_K, HI, DI), "float32", topk, True, True, True)
        kth_room = search(*operands, pos)
        keep, scores = _kernels(*operands, pos, topk)
        again, scores_again = jax.jit(rule)(*operands, pos, kth_room)
        assert np.array_equal(keep, again)
        assert np.array_equal(scores, scores_again)

    def test_the_policy_keeps_the_thresholds_and_nothing_tile_sized(self):
        from jax._src.ad_checkpoint import saved_residuals

        operands = _operands()
        pos, topk = 64 + jnp.arange(N_Q), 48
        kept = saved_residuals(lambda *a: self._tile(a, pos, topk),
                               *operands)
        made = [(aval.shape, why) for aval, why in kept
                if "from the argument" not in why
                and "from a constant" not in why]
        assert [shape for shape, _ in made] == [(2, N_Q)]
        assert sparse_attn.KTH_NAME in made[0][1]
        assert not any(aval.shape == (N_Q, N_K) for aval, _ in kept)


class TestTheRule:
    def test_a_cpu_runs_the_plain_form(self):
        assert jax.default_backend() == "cpu"
        assert sparse_attn.index_backend(512, 16_384, 16, 64) == (
            sparse_attn.SELECT_XLA)

    @pytest.mark.parametrize("shape,fits", [
        ((512, 16_384, 16, 64), True),      # keye-vl2-policy.update
        ((512, 4_096, 16, 64), True),       # its first stage
        ((256, 384, 2, 64), True),
        ((128, 384, 4, 128), True),         # heads of a whole lane tile
        ((1, 16_384, 16, 64), False),       # the cached step, the readout
        ((500, 16_384, 16, 64), False),     # no whole query blocks
        ((512, 16_390, 16, 64), False),     # keys in no whole block
        ((512, 16_384, 16, 32), False),     # a quarter of a lane tile a head
        ((512, 16_384, 1, 64), False),      # Hi * Di no whole lane tile
        ((512, 2 ** 20, 16, 64), False),    # a row of keys past the scratch
    ])
    def test_on_a_tpu_the_shapes_decide(self, monkeypatch, shape, fits):
        monkeypatch.undo()      # the real query blocks
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert index_pallas.fits(*shape) == fits
        assert sparse_attn.index_backend(*shape) == (
            sparse_attn.SELECT_PALLAS if fits else sparse_attn.SELECT_XLA)

    def test_the_blocks_are_the_largest_that_fit(self, monkeypatch):
        monkeypatch.undo()
        assert index_pallas.key_block(16_384) == 512
        assert index_pallas.key_block(384) == 128
        assert index_pallas.key_block(100) is None
        assert index_pallas.query_block(512, 16_384, 16, 64) == 512
        # a longer row of keys, or more heads, in smaller blocks of queries
        assert index_pallas.query_block(512, 32_768, 16, 64) == 256
        assert index_pallas.query_block(512, 16_384, 64, 64) == 128
        assert index_pallas.query_block(384, 384, 2, 64) == 128
        assert index_pallas.query_block(100, 384, 2, 64) is None

    def test_a_shape_that_does_not_tile_is_refused_by_the_kernels(self):
        qi, ki, w = _operands()
        with pytest.raises(ValueError, match="do not tile"):
            _kernels(qi[:100], ki, w[:100], jnp.arange(100), 48)

    def test_no_arch_key_and_no_environment_decides(self):
        import inspect

        for fn in (sparse_attn.index_backend, index_pallas.fits):
            source = inspect.getsource(fn)
            assert "environ" not in source and "cfg" not in source
        assert list(inspect.signature(
            sparse_attn.index_backend).parameters) == [
                "tq", "tk", "n_heads", "width"]

    def test_the_policy_records_what_ran(self, monkeypatch):
        monkeypatch.undo()
        S = jax.ShapeDtypeStruct
        args = (S((1, 16_384, 32, 128), jnp.bfloat16),
                S((1, 16_384, 4, 128), jnp.bfloat16),
                S((1, 16_384, 4, 128), jnp.bfloat16),
                S((1, 16_384, 16, 64), jnp.bfloat16),
                S((1, 16_384, 64), jnp.bfloat16),
                S((1, 16_384, 16), jnp.bfloat16), 2_048, 512, True)
        assert layer._shape(*args)[1] == "bisect_select+masked_xla"
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert layer._shape(*args)[1] == "select_pallas+masked_pallas"
        # index heads that do not tile keep the plain selection in front of
        # the attention's kernels: the two rules are each their own
        odd = args[:3] + (S((1, 16_384, 16, 32), jnp.bfloat16),
                          S((1, 16_384, 32), jnp.bfloat16)) + args[5:]
        assert layer._shape(*odd)[1] == "bisect_select+masked_pallas"


class TestTheTiledWhole:
    """``sparse_attention`` with both rules made to answer as on a TPU and
    every kernel interpreted, against the plain form, two sequences under
    ``vmap``."""
    T, HEADS, KV, WIDTH, TOPK = 512, 4, 2, 128, 96

    def _operands(self, seed=0):
        rng = np.random.default_rng(seed)

        def normal(*shape):
            return jnp.asarray(rng.standard_normal(shape), jnp.float32)

        B, T = 2, self.T
        return (normal(B, T, self.HEADS, self.WIDTH),
                normal(B, T, self.KV, self.WIDTH),
                normal(B, T, self.KV, self.WIDTH), normal(B, T, HI, DI),
                normal(B, T, DI), normal(B, T, HI))

    @pytest.fixture
    def kernels(self, monkeypatch):
        calls = []

        def interpreted_index(*args, **kwargs):
            calls.append(("index",) + args[0].shape[:1] + args[4:])
            return index_pallas_select(*args, **kwargs)

        index_pallas_select = functools.partial(index_pallas.index_select,
                                                interpret=True)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(index_pallas, "index_select", interpreted_index)
        monkeypatch.setattr(
            sparse_attn_pallas, "masked_attention_pallas", functools.partial(
                sparse_attn_pallas.masked_attention_pallas, interpret=True))
        return calls

    @pytest.mark.parametrize("chunk", [128, 256])
    def test_out_kl_and_kept_are_equal_across_the_backends(self, chunk,
                                                           monkeypatch,
                                                           kernels):
        operands = self._operands()
        got = sparse_attn.sparse_attention(*operands, self.TOPK, chunk)
        # a stage that ends at or before ``topk`` selects nothing
        assert kernels and {call[1] for call in kernels} == {chunk}
        assert [call[3] for call in kernels] == [
            end > self.TOPK for end in range(
                sparse_attn.stages(self.T, chunk)[1], self.T + 1,
                sparse_attn.stages(self.T, chunk)[1])]
        monkeypatch.undo()
        want = sparse_attn.sparse_attention(*operands, self.TOPK, chunk)
        np.testing.assert_allclose(got[0], want[0], atol=1e-5)
        np.testing.assert_allclose(got[1], want[1], atol=1e-5)
        assert np.array_equal(got[2], want[2])

    def test_a_first_stage_inside_topk_searches_nothing(self, monkeypatch,
                                                        kernels):
        operands = self._operands(2)
        got = sparse_attn.sparse_attention(*operands, 128, 128)
        assert [call[3] for call in kernels] == [False, True, True, True]
        monkeypatch.undo()
        want = sparse_attn.sparse_attention(*operands, 128, 128)
        np.testing.assert_allclose(got[0], want[0], atol=1e-5)
        np.testing.assert_allclose(got[1], want[1], atol=1e-5)
        assert np.array_equal(got[2], want[2])

    def test_every_gradient_is_equal_across_the_backends(self, monkeypatch,
                                                         kernels):
        operands = self._operands(1)

        def grads():
            def f(*a):
                out, kl, _ = sparse_attn.sparse_attention(*a, self.TOPK, 128)
                return jnp.sum(jnp.square(out)) + kl.sum()
            return jax.grad(f, argnums=tuple(range(6)))(*operands)

        got = grads()
        assert kernels
        monkeypatch.undo()
        for i, (a, b) in enumerate(zip(got, grads())):
            np.testing.assert_allclose(a, b, atol=5e-5, rtol=1e-4,
                                       err_msg=str(i))

    def test_a_tiles_recompute_searches_nothing(self, kernels):
        """``_sequence``'s checkpoints keep the thresholds by name: four
        tiles' searches in the forward and none in the backward, whose
        recompute selects from what was kept."""
        import re

        def f(*a):
            out, kl, _ = sparse_attn.sparse_attention(*a, self.TOPK, 128)
            return jnp.sum(jnp.square(out)) + kl.sum()

        text = str(jax.make_jaxpr(jax.grad(f, argnums=(3, 4, 5)))(
            *self._operands()))
        calls = {name: len(re.findall(rf"name={name}\n", text)) for name in (
            index_pallas.SEARCH_NAME, index_pallas.SELECT_NAME,
            index_pallas.BWD_NAME)}
        assert calls == {index_pallas.SEARCH_NAME: 4,
                         index_pallas.SELECT_NAME: 8,
                         index_pallas.BWD_NAME: 4}

    def test_without_the_loss_no_scores_leave_the_kernels(self, monkeypatch,
                                                          kernels):
        operands = self._operands(3)
        got = sparse_attn.sparse_attention(*operands, self.TOPK, 128,
                                           loss=False)
        assert kernels and not any(call[4] for call in kernels)
        monkeypatch.undo()
        want = sparse_attn.sparse_attention(*operands, self.TOPK, 128,
                                            loss=False)
        np.testing.assert_allclose(got[0], want[0], atol=1e-5)
        assert float(jnp.abs(got[1]).max()) == 0.0
        assert np.array_equal(got[2], want[2])
