"""The flash kernels over grouped k/v heads (index maps only, k/v never
repeated) and over two heads a grid step (``flash.lane_layout``), through the
Pallas interpreter as ``tests/test_flash.py``: against dense attention and
against the same kernels on head-major operands."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _flash import (
    _clear_kernel_caches,
    _grouped_qkv,
    _sub_tile,
    flash_attention,
)
from relayrl_tpu.ops import flash
from relayrl_tpu.ops.attention import dense_attention


# -- grouped-query k/v: index maps only, k/v never repeated -------------------

@pytest.mark.parametrize("B,T,H,h_kv,block", [
    (2, 32, 4, 4, 16),     # group 1: plain multi-head
    (1, 32, 8, 2, 16),     # group 4, several q blocks a head
    (2, 32, 4, 1, 16),     # one k/v head for all (multi-query)
    (2, 16, 4, 2, 16),     # one block a head: the stateless kernels
])
def test_grouped_flash_matches_dense(B, T, H, h_kv, block):
    """Forward, dq, dk and dv of the grouped kernels in the interpreter
    against dense attention on the same grouped k/v (which folds the group
    into the query axis: another formulation altogether)."""
    q, k, v = _grouped_qkv(B, T, H, h_kv)
    fl = lambda q, k, v: flash_attention(q, k, v, block_q=block,
                                         block_kv=block)
    np.testing.assert_allclose(fl(q, k, v), dense_attention(q, k, v),
                               atol=2e-5, rtol=2e-5)
    loss = lambda f: lambda q, k, v: jnp.sum(jnp.sin(f(q, k, v)))
    got = jax.grad(loss(fl), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(dense_attention), (0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        assert g.shape == w.shape, name
        np.testing.assert_allclose(g, w, atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("T,block", [(32, 16), (16, 16)])
def test_grouped_flash_is_the_plain_kernels_on_each_q_head(T, block):
    """A q head of a group computes what it computes with its k/v head
    given to it alone: the grouped forward and dq are BIT-equal to the
    group-1 kernels on k/v repeated over the group (a q head's dq has an
    accumulator of its own, whatever the group); dk/dv are those kernels'
    summed over the group (another order of the same sums)."""
    q, k, v = _grouped_qkv(2, T, 4, 2)
    rep = lambda a: jnp.repeat(a, 2, axis=2)
    fl = lambda q, k, v: flash_attention(q, k, v, block_q=block,
                                         block_kv=block)
    np.testing.assert_array_equal(fl(q, k, v), fl(q, rep(k), rep(v)))
    loss = lambda q, k, v: jnp.sum(jnp.sin(fl(q, k, v)))
    dq, dk, dv = jax.grad(loss, (0, 1, 2))(q, k, v)
    wq, wk, wv = jax.grad(loss, (0, 1, 2))(q, rep(k), rep(v))
    np.testing.assert_array_equal(dq, wq)
    group = lambda a: a.reshape(2, T, 2, 2, 8).sum(3)
    np.testing.assert_allclose(dk, group(wk), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(dv, group(wv), atol=1e-5, rtol=1e-5)


def test_group_one_keeps_the_index_maps_it_had():
    # at group 1 the forward's helper hands back its argument: the same
    # index maps, grids and kernel bodies trace as before k/v could be
    # grouped
    b = object()
    assert flash._kv_head(b, 1) is b
    assert flash._kv_head(13, 4) == 3


def test_the_backward_grid_names_each_block_once():
    """The backward's three walks over a q head's blocks, as its index maps
    and its kernel read them: ``out`` is named at a q block's first visit
    and stays named between visits (one load a q block), and a ``dq`` block
    is named from the step that completes it until the next one is."""
    first = lambda *a: tuple(map(int, flash._first_visit(*a)))
    done = lambda *a: tuple(map(int, flash._dq_complete(*a)))
    # no window, 4 q blocks: all first held beside K/V block 0
    assert [first(0, s, None, 4) for s in range(4)] == [
        (1, 0), (1, 1), (1, 2), (1, 3)]
    assert [first(2, s, None, 4) for s in range(4)] == [(0, 3)] * 4
    # a band of 3 over 5 q blocks: K/V block 0 brings q blocks 0..2, each
    # later one the block at its last step, and a step past the last q block
    # is no visit at all
    assert [first(0, s, 3, 5) for s in range(3)] == [(1, 0), (1, 1), (1, 2)]
    assert [first(1, s, 3, 5) for s in range(3)] == [(0, 2), (0, 2), (1, 3)]
    assert [first(2, s, 3, 5) for s in range(3)] == [(0, 3), (0, 3), (1, 4)]
    assert [first(3, s, 3, 5) for s in range(3)] == [(0, 4), (0, 4), (0, 4)]
    assert [first(3, 0, 1, 5), first(4, 0, 1, 5)] == [(1, 3), (1, 4)]
    # the diagonal runs corner to corner: q block j is complete beside K/V
    # block j; otherwise every q block beside the last K/V block
    assert [done(2, i, True, 4) for i in range(4)] == [
        (0, 2), (0, 2), (1, 2), (0, 2)]
    assert [done(1, i, False, 4) for i in range(3)] == [(0, 0)] * 3
    assert [done(3, i, False, 4) for i in range(3)] == [
        (1, 0), (1, 1), (1, 2)]


def test_grouped_flash_refuses_heads_that_do_not_group():
    q, k, v = _grouped_qkv(1, 16, 4, 3)
    with pytest.raises(ValueError, match="do not group"):
        flash_attention(q, k, v, block_q=16, block_kv=16)


# -- the projections' own [B, T, H * D] layout: 128 lanes a grid step ---------

@pytest.mark.parametrize("heads,kv_heads,head_dim,want", [
    (16, 16, 64, 2),      # gpt2m-policy.update
    (16, 16, 128, None),  # olmoe-policy.update: lane-dense head-major
    (32, 8, 64, 2),       # lfm2-policy.update: a q pair shares its k/v head
    (2, 2, 64, 2),        # the (1, 1000, 2, 64) bucket
    (8, 2, 128, None),
    (4, 4, 256, None),    # a head_dim no cell has measured
    (4, 1, 64, None),     # Hkv * D = 64: half a lane block
    (3, 3, 64, None),     # H * D no multiple of 128
    (6, 2, 64, None),     # the q heads of a pair would read two k/v heads
    (8, 8, 32, None),     # a head_dim the lanes are not filled with
    (2, 2, 16, None),
    (16, 16, 96, None),   # 96 lanes a head: no two fill a 128-lane block
    (4, 2, 96, None),
    (12, 3, 64, None),    # an even group over an odd k/v head count
    (64, 8, 64, 2),       # group 8: four q pairs a k/v head
])
def test_lane_layout(heads, kv_heads, head_dim, want):
    assert flash.lane_layout(heads, kv_heads, head_dim) == want


def _one_ulp_bf16(got, want, name):
    """Equal to one bfloat16 unit in the last place (2^-8 of the value at
    worst), with an absolute floor for entries that cancel to near zero."""
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    np.testing.assert_allclose(got, want, rtol=2.0 ** -7,
                               atol=2.0 ** -8 * 1e-2 * np.abs(want).max(),
                               err_msg=name)


def _check_both_layouts(monkeypatch, q, k, v, causal, block, atol):
    """Forward and the three gradients of the kernels in the layout the
    shape selects: against dense attention (``atol``), and against the same
    kernels on head-major ``[BH, T, D]`` operands — the added products are
    ``x * 0``, so the two layouts give the same numbers."""
    _sub_tile(monkeypatch, 8)

    def fl(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=block,
                               block_kv=block)

    def run(fn):
        # (the output is the forward's of the gradient: not a second call)
        def loss_and_out(q, k, v):
            out = fn(q, k, v)
            return jnp.sum(jnp.sin(out.astype(jnp.float32))), out

        (_, out), grads = jax.value_and_grad(
            loss_and_out, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (out, *grads)

    names = ("out", "dq", "dk", "dv")
    got = run(fl)
    want = run(lambda q, k, v: dense_attention(q, k, v, causal=causal))
    for g, w, name in zip(got, want, names):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        np.testing.assert_allclose(
            g.astype(jnp.float32), w.astype(jnp.float32), atol=atol,
            rtol=atol, err_msg=name)
    _clear_kernel_caches()
    monkeypatch.setattr(flash, "lane_layout", lambda *a: None)
    for g, w, name in zip(got, run(fl), names):
        _one_ulp_bf16(g, w, name)
    _clear_kernel_caches()


# (slow: bfloat16 is the float32 case's twin at another tolerance; tier-1
# keeps the float32 sibling of each shape and layout)
@pytest.mark.parametrize("dtype", [
    "float32", pytest.param("bfloat16", marks=pytest.mark.slow)])
@pytest.mark.parametrize("H,h_kv,D,T,block,layout", [
    (4, 4, 64, 32, 32, 2),      # two heads a step, one block a head
    (4, 4, 64, 32, 16, 2),      # ... a 2 x 2 grid with carried state
    (2, 2, 128, 32, 16, None),  # head_dim 128 stays head-major
    (8, 2, 64, 32, 16, 2),      # grouped: a q pair shares its k/v head
    (8, 2, 64, 16, 16, 2),      # ... one block a head
    (4, 1, 64, 32, 16, None),   # Hkv * D = 64: has to fall back
    (6, 2, 64, 16, 16, None),   # an odd group: two k/v heads a q pair
])
def test_lane_layout_matches_dense_and_head_major(monkeypatch, H, h_kv, D, T,
                                                  block, layout, dtype):
    assert flash.lane_layout(H, h_kv, D) == layout
    q, k, v = (x.astype(dtype) for x in _grouped_qkv(2, T, H, h_kv, D))
    _check_both_layouts(monkeypatch, q, k, v, True, block,
                        5e-5 if dtype == "float32" else 6e-2)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("H,h_kv,T,block", [
    (4, 4, 64, 16),   # 4 x 4 blocks: interior, diagonal and skipped steps
    (8, 2, 64, 16),   # ... grouped, the q pair's k/v head rolled into place
    (4, 4, 32, 32),   # one block a head: no carried state
    (8, 2, 32, 32),
])
def test_lane_layout_grid_steps_causal_and_not(monkeypatch, H, h_kv, T,
                                               block, causal):
    """Two heads a step over every kind of grid step — below, on and
    (causal) above the diagonal — and with no mask at all."""
    assert flash.lane_layout(H, h_kv, 64) == 2
    q, k, v = _grouped_qkv(1, T, H, h_kv, 64, seed=11)
    _check_both_layouts(monkeypatch, q, k, v, causal, block, 5e-5)
