"""``ops/sparse_attn.py``: the indexer's scores, the exact selection without
a sort, the attention over the selected keys and the indexer's loss — each
against a plain form (``lax.top_k``, ``ops.attention.dense_attention``,
numpy), and the tiled whole against one tile."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from relayrl_tpu.ops import sparse_attn
from relayrl_tpu.ops.attention import dense_attention


def _top_k_set(scores, seen, k):
    """The set ``lax.top_k`` picks a row (ties to the lower index)."""
    n_keys = scores.shape[-1]
    _, idx = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf), min(k, n_keys))
    picked = np.zeros(scores.shape, bool)
    np.put_along_axis(picked, np.asarray(idx), True, axis=-1)
    return picked & np.asarray(seen)


def _causal(n_q, n_k, first=0):
    return (first + jnp.arange(n_q))[:, None] >= jnp.arange(n_k)[None, :]


class TestSelection:
    @pytest.mark.parametrize("k", [1, 3, 8, 40, 64])
    @pytest.mark.parametrize("seed", [0, 1])
    def test_it_is_the_set_top_k_returns(self, k, seed):
        rng = np.random.default_rng(seed)
        scores = jnp.asarray(rng.standard_normal((24, 64)) * 10.0 ** rng.integers(
            -3, 4, (24, 1)), jnp.float32)
        seen = _causal(24, 64, first=30)
        got = sparse_attn.top_k_mask(scores, seen, k)
        assert np.array_equal(got, _top_k_set(scores, seen, k))
        assert np.array_equal(got.sum(-1), np.minimum(seen.sum(-1), k))

    @pytest.mark.parametrize("k", [2, 5, 17])
    def test_ties_go_to_the_lower_index(self, k):
        """Scores of a few distinct values: most rows tie at the k-th, the
        zeros among them of both signs (a ReLU's)."""
        rng = np.random.default_rng(k)
        scores = rng.integers(-2, 3, (32, 48)).astype(np.float32)
        scores[scores == 0] *= rng.choice([1.0, -1.0], (scores == 0).sum())
        scores = jnp.asarray(scores)
        seen = _causal(32, 48, first=16)
        got = sparse_attn.top_k_mask(scores, seen, k)
        assert np.array_equal(got, _top_k_set(scores, seen, k))
        # and by hand: a row of equal scores keeps its first k seen keys
        flat = sparse_attn.top_k_mask(jnp.zeros((1, 10)),
                                      jnp.arange(10)[None] >= 2, 3)
        assert np.flatnonzero(flat[0]).tolist() == [2, 3, 4]

    def test_a_row_that_sees_no_more_than_k_keeps_them_all(self):
        scores = jnp.asarray(np.random.default_rng(0).standard_normal(
            (8, 8)), jnp.float32)
        seen = _causal(8, 8)
        assert np.array_equal(sparse_attn.top_k_mask(scores, seen, 8), seen)
        assert np.array_equal(sparse_attn.top_k_mask(scores, seen, 100),
                              seen)

    def test_infinite_and_tiny_scores_order_as_floats(self):
        scores = jnp.asarray([[-jnp.inf, -1e-40, 0.0, 1e-40, jnp.inf, -3.0,
                               2.0, -0.0]], jnp.float32)
        seen = jnp.ones((1, 8), bool)
        for k in range(1, 9):
            assert np.array_equal(sparse_attn.top_k_mask(scores, seen, k),
                                  _top_k_set(scores, seen, k)), k

    def test_no_gradient_passes_the_selection(self):
        scores = jnp.asarray(np.random.default_rng(0).standard_normal(
            (4, 16)), jnp.float32)
        g = jax.grad(lambda s: sparse_attn.top_k_mask(
            s, jnp.ones((4, 16), bool), 3).astype(jnp.float32).sum())(scores)
        assert float(jnp.abs(g).max()) == 0.0


class TestTheParts:
    def _operands(self, seed=0, t=16, heads=4, kv=2, width=8, hi=3, di=4):
        rng = np.random.default_rng(seed)
        f = lambda *shape: jnp.asarray(rng.standard_normal(shape),
                                       jnp.float32)
        return (f(2, t, heads, width), f(2, t, kv, width),
                f(2, t, kv, width), f(2, t, hi, di), f(2, t, di),
                f(2, t, hi))

    def test_index_scores_are_the_weighted_relu_sum(self):
        _, _, _, qi, ki, w = self._operands()
        got = sparse_attn.index_scores(qi[0], ki[0], w[0])
        terms = np.einsum("qhd,kd->qhk", qi[0], ki[0])
        want = (np.maximum(terms, 0.0) * np.asarray(w[0])[:, :, None]).sum(
            1) / np.sqrt(3.0) / np.sqrt(4.0)
        np.testing.assert_allclose(got, want, atol=1e-5)

    def test_under_the_causal_mask_it_is_dense_attention(self):
        """(To the last bit or two: ``dense_attention`` folds a group's
        heads into the query axis and sums in another order; a whole trunk
        of up to ``topk`` rows IS the plain one's to the bit,
        ``tests/test_keye_vl2_reference.py``.)"""
        q, k, v, *_ = self._operands()
        out, p_hat = sparse_attn.masked_attention(q[0], k[0], v[0],
                                                  _causal(16, 16))
        want = dense_attention(q[:1], k[:1], v[:1], causal=True)[0]
        np.testing.assert_allclose(out, want, atol=1e-6)
        np.testing.assert_allclose(p_hat.sum(-1), 1.0, atol=1e-6)
        assert float(jnp.abs(jnp.triu(p_hat, 1)).max()) == 0.0

    def test_the_loss_is_a_kl_of_the_two_distributions_over_the_set(self):
        rng = np.random.default_rng(0)
        keep = jnp.asarray(rng.random((6, 12)) < 0.5).at[:, 0].set(True)
        scores = jnp.asarray(rng.standard_normal((6, 12)), jnp.float32)
        pi = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), -1)
        assert float(jnp.abs(sparse_attn.index_kl(pi, scores,
                                                  keep)).max()) < 1e-6
        other = jax.nn.softmax(jnp.where(keep, -scores, -jnp.inf), -1)
        kl = sparse_attn.index_kl(other, scores, keep)
        want = np.where(keep, other * (np.log(np.where(keep, other, 1.0))
                                       - np.log(np.where(keep, pi, 1.0))),
                        0.0).sum(-1)
        np.testing.assert_allclose(kl, want, atol=1e-5)
        assert float(kl.min()) > 0
        # its gradient reaches the kept scores only: softmax - p^
        g = jax.grad(lambda s: sparse_attn.index_kl(other, s, keep).sum())(
            scores)
        np.testing.assert_allclose(g, jnp.where(keep, pi - other, 0.0),
                                   atol=1e-6)


class TestTheTiledWhole:
    ARGS = dict(t=32, heads=4, kv=2, width=8, hi=2, di=8)

    def _run(self, chunk, topk=8, loss=True, seed=0):
        operands = TestTheParts()._operands(seed, **self.ARGS)
        return operands, sparse_attn.sparse_attention(*operands, topk, chunk,
                                                      loss)

    @pytest.mark.parametrize("chunk", [4, 8, 16, 5])
    def test_the_tile_is_no_part_of_the_function(self, chunk):
        _, (out, kl, kept) = self._run(chunk)
        _, (out1, kl1, kept1) = self._run(32)       # one tile, one stage
        np.testing.assert_allclose(out, out1, atol=2e-6)
        np.testing.assert_allclose(kl, kl1, atol=2e-6)
        assert np.array_equal(kept, kept1)
        assert kept[0].tolist() == [min(t + 1, 8) for t in range(32)]

    def test_it_is_the_masked_form_of_top_k(self):
        (q, k, v, qi, ki, w), (out, kl, _) = self._run(8)
        for b in range(2):
            scores = sparse_attn.index_scores(qi[b], ki[b], w[b])
            keep = jnp.asarray(_top_k_set(scores, _causal(32, 32), 8))
            want, p_hat = sparse_attn.masked_attention(q[b], k[b], v[b],
                                                       keep)
            np.testing.assert_allclose(out[b], want, atol=2e-6)
            np.testing.assert_allclose(
                kl[b], sparse_attn.index_kl(p_hat, scores, keep), atol=2e-6)

    def test_whose_gradient_goes_where(self):
        """The attention's output hands the indexer nothing; the loss hands
        q, k and v nothing; and the tiles' ``jax.checkpoint`` changes no
        gradient (one tile against four)."""
        operands, _ = self._run(8)

        def grads(chunk, of):
            def f(*a):
                out, kl, _ = sparse_attn.sparse_attention(*a, 8, chunk)
                return jnp.sum(jnp.square(out)) if of == "out" else kl.sum()
            return jax.grad(f, argnums=tuple(range(6)))(*operands)

        for of, zero in (("out", (3, 4, 5)), ("kl", (0, 1, 2))):
            tiled, whole = grads(8, of), grads(32, of)
            for i, (a, b) in enumerate(zip(tiled, whole)):
                np.testing.assert_allclose(a, b, atol=2e-5, err_msg=str(i))
                assert (float(jnp.abs(a).max()) == 0.0) == (i in zero), (of,
                                                                         i)

    def test_the_stages_and_the_pairs_they_compute(self):
        # keye-vl2-policy.update: 4 stages of 8 tiles of 512 queries
        assert sparse_attn.stages(16_384, 512) == (512, 4_096)
        assert sparse_attn.computed_pairs(16_384, 512) == (
            4_096 * 4_096 * 10) == 167_772_160      # 125% of the causal
        assert sparse_attn.stages(32, 8) == (8, 8)          # 4 stages
        assert sparse_attn.stages(64, 4) == (4, 16)         # of 4 tiles
        assert sparse_attn.stages(32, 5) == (32, 32)        # one tile
        assert sparse_attn.stages(1, 512) == (1, 1)
        assert sparse_attn.computed_pairs(32, 32) == 32 * 32

    def test_without_the_loss_nothing_of_it_is_computed(self):
        _, (out, kl, _) = self._run(8, loss=False)
        _, (out1, _, _) = self._run(8)
        assert np.array_equal(out, out1)
        assert float(jnp.abs(kl).max()) == 0.0
