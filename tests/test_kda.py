"""``ops/kda.py``: the delta rule under a decay a key lane, in chunks.

The chunked form (sub-chunks of 16 inside a chunk: split products across
sub-chunks, lane-wise sums inside one) against the rule one token at a time
(``kda_step``, the state equation as it is written), in float32 at matmul
precision "highest"; and against ``ops/gdn.py``, the code it was cut from:
with every lane's decay equal the two rules are one.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from relayrl_tpu.ops import gdn, kda

B, H, K, V = 2, 2, 16, 8


def _inputs(T, seed=0, g_scale=2.0):
    rng = np.random.default_rng(seed)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(rng.normal(size=(B, T, H, K))) * K ** -0.5
    k = unit(rng.normal(size=(B, T, H, K)))
    v = rng.normal(size=(B, T, H, V))
    g = -rng.uniform(0, g_scale, size=(B, T, H, K))
    beta = rng.uniform(0, 1, size=(B, T, H))
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta))


def _by_step(q, k, v, g, beta, state=None):
    """The state equation one token at a time."""
    if state is None:
        state = jnp.zeros((B, H, K, V), jnp.float32)

    def one(s, row):
        o, s = kda.kda_step(*row, s)
        return s, o

    last, o = jax.lax.scan(one, state, tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), last


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


# T under a chunk, a whole chunk, not a multiple of the chunk (the call pads
# on the right), several chunks; a chunk of one sub-chunk and of four
@pytest.mark.parametrize("T,chunk", [(5, 64), (64, 64), (150, 64),
                                     (40, 16), (96, 32)])
def test_chunks_are_the_rule_a_token_at_a_time(T, chunk):
    args = _inputs(T)
    o, last = kda.kda_xla(*args, chunk)
    o_ref, last_ref = _by_step(*args)
    np.testing.assert_allclose(o, o_ref, atol=5e-6)
    np.testing.assert_allclose(last, last_ref, atol=5e-6)


def test_a_state_carried_between_calls_is_one_call():
    args = _inputs(100)
    o, last = kda.kda_xla(*args, 32)
    head = tuple(a[:, :37] for a in args)
    tail = tuple(a[:, 37:] for a in args)
    o1, s1 = kda.kda_xla(*head, 32)
    o2, s2 = kda.kda_xla(*tail, 32, s1)
    np.testing.assert_allclose(jnp.concatenate([o1, o2], 1), o, atol=5e-6)
    np.testing.assert_allclose(s2, last, atol=5e-6)


def test_right_padded_episodes_need_nothing():
    """The rule is causal: what follows a real row does not reach it, and
    rows of ``g = 0``, ``beta = 0`` leave the state as it is."""
    q, k, v, g, beta = _inputs(80)
    n = 50
    real = (jnp.arange(80) < n)[None, :, None]
    junk = _inputs(80, seed=7)
    o, last = kda.kda_xla(q, k, v, jnp.where(real[..., None], g, 0.0),
                      jnp.where(real, beta, 0.0), 32)
    o2, _ = kda.kda_xla(*(jnp.where(real[..., None] if a.ndim == 4 else real,
                                a, b) for a, b in zip(
                                    (q, k, v, g, beta), junk)), 32)
    np.testing.assert_allclose(o[:, :n], o2[:, :n], atol=5e-6)
    _, last_n = _by_step(*(a[:, :n] for a in (q, k, v, g, beta)))
    np.testing.assert_allclose(last, last_n, atol=5e-6)


@pytest.mark.parametrize("alpha", [1e-4, 1e-2])
def test_small_decays_over_a_whole_chunk_stay_finite_and_equal(alpha):
    """``alpha`` 1e-4 a lane over 64 rows: ``e^{-Gamma}`` would be
    ``e^{+589}``; every exponential taken here is of a non-positive
    number."""
    q, k, v, _, beta = _inputs(128)
    g = jnp.full((B, 128, H, K), np.log(alpha), jnp.float32)
    # a few lanes that do not decay at all beside those that vanish
    g = g.at[..., ::5].set(0.0)
    o, last = kda.kda_xla(q, k, v, g, beta, 64)
    o_ref, last_ref = _by_step(q, k, v, g, beta)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(last).all())
    np.testing.assert_allclose(o, o_ref, atol=5e-6)
    np.testing.assert_allclose(last, last_ref, atol=5e-6)
    grads = jax.grad(lambda *a: jnp.sum(kda.kda_xla(*a, 64)[0]),
                     argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
    assert all(bool(jnp.isfinite(a).all()) for a in grads)


def test_with_every_lanes_decay_equal_kda_is_gdn():
    """The tie to the code it was cut from: one decay a head through
    ``ops/gdn.py``, the same number on every lane through ``ops/kda.py`` —
    values, state and every gradient (the decay's summed over the lanes)."""
    q, k, v, g, beta = _inputs(100)
    g1 = g[..., 0]
    wide = lambda g1: jnp.broadcast_to(g1[..., None], g.shape)
    o, last = kda.kda_xla(q, k, v, wide(g1), beta, 64)
    o_gdn, last_gdn = gdn.gdn_xla(q, k, v, g1, beta, 64)
    np.testing.assert_allclose(o, o_gdn, atol=5e-6)
    np.testing.assert_allclose(last, last_gdn, atol=5e-6)
    w = jnp.asarray(np.random.default_rng(3).normal(size=o.shape),
                    jnp.float32)
    ours = jax.grad(lambda q, k, v, g1, beta: jnp.sum(
        kda.kda_xla(q, k, v, wide(g1), beta, 64)[0] * w),
        argnums=(0, 1, 2, 3, 4))(q, k, v, g1, beta)
    theirs = jax.grad(lambda *a: jnp.sum(gdn.gdn_xla(*a, 64)[0] * w),
                      argnums=(0, 1, 2, 3, 4))(q, k, v, g1, beta)
    for a, b in zip(ours, theirs):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-4)


def test_all_five_cotangents_and_the_states():
    """Autodiff through the chunked form (under ``jax.checkpoint``) against
    autodiff through the token-by-token scan: q, k, v, the decay a LANE
    (``[B, T, H, K]``, not ``[B, T, H]``), beta and the incoming state."""
    args = _inputs(70, seed=4)
    s0 = jnp.asarray(np.random.default_rng(5).normal(size=(B, H, K, V)),
                     jnp.float32)
    rng = np.random.default_rng(6)
    w_o = jnp.asarray(rng.normal(size=(B, 70, H, V)), jnp.float32)
    w_s = jnp.asarray(rng.normal(size=(B, H, K, V)), jnp.float32)

    def loss(fn):
        def f(*a):
            o, last = fn(*a)
            return jnp.sum(o * w_o) + jnp.sum(last * w_s)
        return f

    ours = jax.grad(loss(lambda *a: kda.kda_xla(*a[:5], 32, a[5])),
                    argnums=tuple(range(6)))(*args, s0)
    theirs = jax.grad(loss(lambda *a: _by_step(*a)),
                      argnums=tuple(range(6)))(*args, s0)
    assert ours[3].shape == (B, 70, H, K)
    for a, b in zip(ours, theirs):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=1e-4)


def test_bfloat16_operands_stay_near_the_float32_rule():
    args = _inputs(96, seed=8)
    o32, _ = kda.kda_xla(*args, 32)
    q, k, v, g, beta = args
    o16, last = kda.kda_xla(*(a.astype(jnp.bfloat16) for a in (q, k, v)), g,
                        beta, 32)
    assert o16.dtype == jnp.bfloat16 and last.dtype == jnp.float32
    assert float(jnp.abs(o16.astype(jnp.float32) - o32).max()) < 0.02


def test_one_step_is_the_rules_line():
    """``kda_step`` written out: ``Diag(alpha)`` then the rank-one
    correction."""
    q, k, v, g, beta = (a[:, 0] for a in _inputs(1, seed=9))
    s0 = jnp.asarray(np.random.default_rng(10).normal(size=(B, H, K, V)),
                     jnp.float32)
    o, s1 = kda.kda_step(q, k, v, g, beta, s0)
    eye = jnp.eye(K)
    want = jnp.einsum(
        "bhij,bhjv->bhiv",
        eye - beta[..., None, None] * k[..., :, None] * k[..., None, :],
        jnp.exp(g)[..., None] * s0) + beta[..., None, None] * (
            k[..., :, None] * v[..., None, :])
    np.testing.assert_allclose(s1, want, atol=1e-5)
    np.testing.assert_allclose(o, jnp.einsum("bhkv,bhk->bhv", want, q),
                               atol=1e-5)


def test_shapes_that_are_not_the_rules_are_refused():
    q, k, v, g, beta = _inputs(16)
    with pytest.raises(ValueError, match="a decay a key lane"):
        kda.kda(q, k, v, g[..., 0], beta, 16)
    with pytest.raises(ValueError, match="sub-chunks"):
        kda.kda(q, k, v, g, beta, 24)


# the map over heads takes the largest divisor of H up to _HEADS_A_STEP
@pytest.mark.parametrize("heads", [5, 6])
def test_heads_that_are_no_multiple_of_a_maps_step(heads):
    rng = np.random.default_rng(heads)
    one = _inputs(40, seed=heads)
    args = tuple(jnp.concatenate([a] * heads, axis=2)[:, :, :heads]
                 * jnp.asarray(rng.uniform(0.5, 1.0, (1, 1, heads)
                                           + (1,) * (a.ndim - 3)),
                               jnp.float32)
                 for a in one)
    o, last = kda.kda_xla(*args, 16)
    for h in range(heads):
        head = tuple(jnp.concatenate([a[:, :, h:h + 1]] * H, axis=2)
                     for a in args)
        o_ref, last_ref = _by_step(*head)
        np.testing.assert_allclose(o[:, :, h], o_ref[:, :, 0], atol=5e-6)
        np.testing.assert_allclose(last[:, h], last_ref[:, 0], atol=5e-6)
