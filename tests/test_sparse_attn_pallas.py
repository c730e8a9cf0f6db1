"""``ops/sparse_attn_pallas.py``: the attention over the selected keys as
Pallas kernels, run by the Pallas interpreter on the CPU and held to the
plain form they stand for (``ops/sparse_attn.masked_attention`` and
``jax.grad`` of it): forward, ``p^``, every gradient, the rule that picks
them, and the tiled whole through either form."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from relayrl_tpu.models.layers import sparse_attention as layer
from relayrl_tpu.ops import sparse_attn, sparse_attn_pallas

WIDTH = 128     # the kernels want heads of whole lane tiles
N_Q, N_K = 128, 384     # one tile of queries over three key blocks of 128


def _tile(first, heads, kv, mask, dtype=jnp.float32, seed=0, topk=48):
    """One tile's operands: ``N_Q`` queries from position ``first`` over
    ``N_K`` keys. ``mask``: "topk" (a random score's top-``topk`` of the
    seen keys), "causal" (every seen key) or "empty_block" (causal with key
    block 1 taken out where the row keeps anything else)."""
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    q, k, v = (normal(N_Q, heads, WIDTH), normal(N_K, kv, WIDTH),
               normal(N_K, kv, WIDTH))
    pos = first + jnp.arange(N_Q)
    seen = pos[:, None] >= jnp.arange(N_K)[None, :]
    if mask == "topk":
        keep = sparse_attn.top_k_mask(normal(N_Q, N_K).astype(jnp.float32),
                                      seen, topk)
    else:
        keep = seen
        if mask == "empty_block":
            inside = (jnp.arange(N_K) >= 128) & (jnp.arange(N_K) < 256)
            keep = seen & ~inside[None, :]
            assert first >= 256 and not bool(keep[:, 128:256].any())
    assert bool(keep.any(-1).all())
    return q, k, v, keep, pos


def _kernels(q, k, v, keep, pos, want_p_hat=True):
    out, p_hat, owed = sparse_attn_pallas.masked_attention_pallas(
        q, k, v, keep, pos, want_p_hat, interpret=True)
    assert owed is None     # settled inside: nothing is owed
    return out, p_hat


POSITIONS = [0, 128, 256]       # the first tile, one in the middle, the last
HEADS = [(32, 4), (4, 2), (2, 2)]    # groups of 8, of 2 and of ONE head


class TestForward:
    @pytest.mark.parametrize("heads,kv", HEADS)
    @pytest.mark.parametrize("first", POSITIONS)
    @pytest.mark.parametrize("mask", ["topk", "causal"])
    def test_out_and_p_hat_are_the_plain_forms(self, heads, kv, first, mask):
        q, k, v, keep, pos = _tile(first, heads, kv, mask)
        out, p_hat = _kernels(q, k, v, keep, pos)
        want, want_p = sparse_attn.masked_attention(q, k, v, keep)
        np.testing.assert_allclose(out, want, atol=1e-5)
        np.testing.assert_allclose(p_hat, want_p, atol=1e-6)
        assert p_hat.dtype == jnp.float32 and out.dtype == q.dtype
        # p^ sums to one over the kept keys and is zero everywhere else
        np.testing.assert_allclose(p_hat.sum(-1), 1.0, atol=1e-5)
        assert float(jnp.abs(jnp.where(keep, 0.0, p_hat)).max()) == 0.0

    @pytest.mark.parametrize("heads,kv", HEADS)
    def test_a_block_inside_the_triangle_that_keeps_nothing(self, heads, kv):
        """Rows whose running maximum is still ``-1e30`` after a whole
        block: the next kept key's rescale wipes what the block added."""
        q, k, v, keep, pos = _tile(256, heads, kv, "empty_block")
        out, p_hat = _kernels(q, k, v, keep, pos)
        want, want_p = sparse_attn.masked_attention(q, k, v, keep)
        np.testing.assert_allclose(out, want, atol=1e-5)
        np.testing.assert_allclose(p_hat, want_p, atol=1e-6)

    @pytest.mark.parametrize("first", POSITIONS)
    def test_in_bfloat16_as_the_plain_form_rounds(self, first):
        q, k, v, keep, pos = _tile(first, 32, 4, "topk", jnp.bfloat16)
        out, p_hat = _kernels(q, k, v, keep, pos)
        want, want_p = sparse_attn.masked_attention(q, k, v, keep)
        np.testing.assert_allclose(out.astype(jnp.float32),
                                   want.astype(jnp.float32), atol=2e-2)
        np.testing.assert_allclose(p_hat, want_p, atol=2e-3)

    def test_without_p_hat_none_is_made(self):
        q, k, v, keep, pos = _tile(128, 4, 2, "topk")
        fn = functools.partial(_kernels, want_p_hat=False)
        out, p_hat = fn(q, k, v, keep, pos)
        assert p_hat is None
        np.testing.assert_allclose(
            out, sparse_attn.masked_attention(q, k, v, keep)[0], atol=1e-5)
        text = str(jax.make_jaxpr(fn)(q, k, v, keep, pos))
        assert sparse_attn_pallas.FWD_NAME in text
        assert sparse_attn_pallas.PHAT_NAME not in text

    def test_the_positions_need_no_order(self):
        """``live`` comes from the largest position: rows in any order."""
        q, k, v, keep, pos = _tile(128, 4, 2, "topk")
        order = jnp.asarray(np.random.default_rng(1).permutation(N_Q))
        out, p_hat = _kernels(q[order], k, v, keep[order], pos[order])
        want, want_p = sparse_attn.masked_attention(q, k, v, keep)
        np.testing.assert_allclose(out, want[order], atol=1e-5)
        np.testing.assert_allclose(p_hat, want_p[order], atol=1e-6)


class TestBackward:
    @staticmethod
    def _grads(attend, q, k, v, weight):
        def loss(q, k, v):
            return jnp.sum(attend(q, k, v).astype(jnp.float32) * weight)

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    @pytest.mark.parametrize("heads,kv", HEADS)
    @pytest.mark.parametrize("first", POSITIONS)
    @pytest.mark.parametrize("mask", ["topk", "causal"])
    def test_dq_dk_dv_are_autodiffs_of_the_plain_form(self, heads, kv, first,
                                                      mask):
        q, k, v, keep, pos = _tile(first, heads, kv, mask, seed=1)
        weight = jnp.asarray(np.random.default_rng(2).standard_normal(
            q.shape), jnp.float32)
        got = self._grads(lambda *a: _kernels(*a, keep, pos)[0], q, k, v,
                          weight)
        want = self._grads(
            lambda *a: sparse_attn.masked_attention(*a, keep)[0], q, k, v,
            weight)
        for name, a, b in zip("qkv", got, want):
            np.testing.assert_allclose(a, b, atol=2e-5, err_msg="d" + name)
        # the keys past the tile's last query get exact zeros
        assert float(jnp.abs(got[1][first + N_Q:]).max(initial=0.0)) == 0.0
        assert float(jnp.abs(got[2][first + N_Q:]).max(initial=0.0)) == 0.0
        if first == 0:
            # ``live`` 1 of 3 key blocks: ``dq`` leaves its accumulator at
            # the LAST grid step, two steps after the last one that computed
            assert bool(jnp.all(jnp.any(got[0] != 0.0, axis=(1, 2))))
            assert bool(jnp.any(got[1][:N_Q] != 0.0))
            assert bool(jnp.any(got[2][:N_Q] != 0.0))

    @pytest.mark.parametrize("heads,kv", HEADS)
    def test_through_an_empty_block(self, heads, kv):
        q, k, v, keep, pos = _tile(256, heads, kv, "empty_block", seed=3)
        weight = jnp.ones(q.shape, jnp.float32)
        got = self._grads(lambda *a: _kernels(*a, keep, pos)[0], q, k, v,
                          weight)
        want = self._grads(
            lambda *a: sparse_attn.masked_attention(*a, keep)[0], q, k, v,
            weight)
        for name, a, b in zip("qkv", got, want):
            np.testing.assert_allclose(a, b, atol=2e-5, err_msg="d" + name)
        assert float(jnp.abs(got[1][128:256]).max()) == 0.0

    def test_in_bfloat16(self):
        q, k, v, keep, pos = _tile(256, 32, 4, "topk", jnp.bfloat16, seed=4)
        weight = jnp.asarray(np.random.default_rng(5).standard_normal(
            q.shape), jnp.float32)
        got = self._grads(lambda *a: _kernels(*a, keep, pos)[0], q, k, v,
                          weight)
        want = self._grads(
            lambda *a: sparse_attn.masked_attention(*a, keep)[0], q, k, v,
            weight)
        for name, a, b in zip("qkv", got, want):
            assert a.dtype == jnp.bfloat16
            np.testing.assert_allclose(
                a.astype(jnp.float32), b.astype(jnp.float32), atol=6e-2,
                rtol=2e-2, err_msg="d" + name)

    def test_keep_and_live_get_no_cotangent_and_p_hat_no_gradient(self):
        q, k, v, keep, pos = _tile(128, 4, 2, "topk")
        rule, p_hat = sparse_attn_pallas._make_rule(
            (N_Q, N_K, 4, 2, WIDTH), "float32", True)
        k2, v2 = k.reshape(N_K, -1), v.reshape(N_K, -1)
        keep8, live = keep.astype(jnp.int8), jnp.asarray([2], jnp.int32)
        (out, lse, owed), pull = jax.vjp(rule, q, k2, v2, keep8, live)
        assert owed.shape == (N_Q, 4) and float(jnp.abs(owed).max()) == 0.0
        dq, dk, dv, dkeep, dlive = pull((jnp.ones_like(out),
                                         jnp.zeros_like(lse), out.sum(-1)))
        assert dkeep.dtype == jax.dtypes.float0 == dlive.dtype
        assert (dq.shape, dk.shape, dv.shape) == (q.shape, k2.shape,
                                                  v2.shape)
        # p^ is detached: a loss of it alone moves nothing
        zero = jax.grad(lambda q, k: jnp.sum(jnp.square(
            _kernels(q, k, v, keep, pos)[1])), argnums=(0, 1))(q, k)
        assert all(float(jnp.abs(g).max()) == 0.0 for g in zero)


class TestTheRule:
    def test_a_cpu_runs_the_plain_form(self):
        assert jax.default_backend() == "cpu"
        assert sparse_attn.backend(512, 16_384, 32, 4, 128) == sparse_attn.XLA

    @pytest.mark.parametrize("shape,fits", [
        ((512, 16_384, 32, 4, 128), True),      # keye-vl2-policy.update
        ((512, 4_096, 32, 4, 128), True),       # its first stage
        ((128, 384, 4, 2, 128), True),
        ((1, 16_384, 32, 4, 128), False),       # the cached step, the readout
        ((500, 16_384, 32, 4, 128), False),     # a tile off the lanes
        ((512, 16_000, 32, 4, 128), True),      # key blocks of 128
        ((512, 16_390, 32, 4, 128), False),     # keys in no whole block
        ((512, 16_384, 32, 4, 64), False),      # half a lane tile a head
        ((512, 16_384, 32, 5, 128), False),     # no whole groups
        ((2_048, 16_384, 32, 4, 128), False),   # a group past a step's VMEM
    ])
    def test_on_a_tpu_the_shapes_decide(self, monkeypatch, shape, fits):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert sparse_attn_pallas.fits(*shape) == fits
        assert sparse_attn.backend(*shape) == (
            sparse_attn.PALLAS if fits else sparse_attn.XLA)

    def test_the_key_block_is_the_largest_that_divides(self):
        assert sparse_attn_pallas.key_block(16_384) == 512
        assert sparse_attn_pallas.key_block(4_096 + 256) == 256
        assert sparse_attn_pallas.key_block(384) == 128
        assert sparse_attn_pallas.key_block(100) is None

    def test_a_shape_that_does_not_tile_is_refused_by_the_kernels(self):
        q, k, v, keep, pos = _tile(0, 4, 2, "causal")
        with pytest.raises(ValueError, match="do not tile"):
            _kernels(q[:100], k, v, keep[:100], pos[:100])

    def test_the_policy_records_what_ran(self, monkeypatch, capsys):
        """``Policy.index_backends`` (``models/layers/sparse_attention.
        _shape`` behind ``kernel``'s record) and the ``[index]`` line: the
        plain form on a CPU, the kernels on a TPU at shapes that tile."""
        S = jax.ShapeDtypeStruct
        args = (S((1, 16_384, 32, 128), jnp.bfloat16),
                S((1, 16_384, 4, 128), jnp.bfloat16),
                S((1, 16_384, 4, 128), jnp.bfloat16),
                S((1, 16_384, 16, 64), jnp.bfloat16),
                S((1, 16_384, 64), jnp.bfloat16),
                S((1, 16_384, 16), jnp.bfloat16), 2_048, 512, True)
        key, ran, _ = layer._shape(*args)
        assert key == (16_384, 128, 16, 64, 2_048, "bfloat16")
        assert ran == "bisect_select+masked_xla"
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert layer._shape(*args)[1] == "select_pallas+masked_pallas"
        # a sequence of one row (``init``'s), a tile that does not divide
        short = tuple(S((1, 1) + a.shape[2:], a.dtype) for a in args[:6])
        assert layer._shape(*short, 2_048, 512, True)[1] == (
            "bisect_select+masked_xla")
        monkeypatch.undo()

        from relayrl_tpu.models import build_policy

        policy = build_policy({
            "kind": "transformer_moe_discrete", "obs_dim": 4, "act_dim": 3,
            "d_model": 16, "n_layers": 1, "n_heads": 2, "max_seq_len": 8,
            "norm": "rms", "positions": "rope",
            "layer_types": ["sparse_attention"], "index_heads": 2,
            "index_head_dim": 4, "index_topk": 2, "moe_experts": 2,
            "moe_top_k": 1})
        params = policy.init_params(jax.random.PRNGKey(0))
        policy.evaluate_stats(params, jnp.zeros((1, 8, 4)),
                              jnp.zeros((1, 8), jnp.int32))
        assert set(policy.index_backends.values()) == {
            "bisect_select+masked_xla"}
        assert (8, 8, 2, 4, 2, "float32") in policy.index_backends
        lines = [line for line in capsys.readouterr().out.splitlines()
                 if line.startswith("[index]")]
        assert lines and all("-> bisect_select+masked_xla (platform cpu)"
                             in line for line in lines)


class TestTheTiledWhole:
    """``sparse_attention`` through the kernels (the rule made to answer as
    on a TPU, the kernels interpreted) against the plain form."""
    T, HEADS, KV, HI, DI, TOPK = 512, 4, 2, 2, 16, 96

    def _operands(self, seed=0):
        rng = np.random.default_rng(seed)

        def normal(*shape):
            return jnp.asarray(rng.standard_normal(shape), jnp.float32)

        B, T = 2, self.T    # two sequences: the kernels under ``vmap``
        return (normal(B, T, self.HEADS, WIDTH), normal(B, T, self.KV, WIDTH),
                normal(B, T, self.KV, WIDTH), normal(B, T, self.HI, self.DI),
                normal(B, T, self.DI), normal(B, T, self.HI))

    @pytest.fixture
    def kernels(self, monkeypatch):
        calls = []

        def interpreted(*args, **kwargs):
            calls.append(args[0].shape)
            return _interpreted(*args, **kwargs)

        _interpreted = functools.partial(
            sparse_attn_pallas.masked_attention_pallas, interpret=True)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(sparse_attn_pallas, "masked_attention_pallas",
                            interpreted)
        return calls

    @pytest.mark.parametrize("chunk", [128, 256])
    def test_out_kl_and_kept_are_equal_across_the_backends(self, chunk,
                                                           monkeypatch,
                                                           kernels):
        operands = self._operands()
        got = sparse_attn.sparse_attention(*operands, self.TOPK, chunk)
        assert kernels and all(shape[0] == chunk for shape in kernels)
        monkeypatch.undo()
        want = sparse_attn.sparse_attention(*operands, self.TOPK, chunk)
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

    def test_a_chunk_that_does_not_divide_is_one_tile(self, monkeypatch,
                                                      kernels):
        """The rule is shape alone: the whole sequence as one tile tiles
        here, a sequence off the lanes does not and runs the plain form."""
        operands = self._operands()
        got = sparse_attn.sparse_attention(*operands, self.TOPK, 100)
        assert kernels == [(self.T, self.HEADS, WIDTH)]
        del kernels[:]
        short = tuple(a[:, :200] for a in operands)
        got_short = sparse_attn.sparse_attention(*short, self.TOPK, 100)
        assert not kernels
        monkeypatch.undo()
        want = sparse_attn.sparse_attention(*operands, self.TOPK, 100)
        np.testing.assert_allclose(got[0], want[0], atol=1e-5)
        np.testing.assert_allclose(got[1], want[1], atol=1e-5)
        want_short = sparse_attn.sparse_attention(*short, self.TOPK, 100)
        assert np.array_equal(got_short[0], want_short[0])
