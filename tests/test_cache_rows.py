"""``ops/cache_rows``: one new row a sequence into a cache of rows.

The kernel runs here in the Pallas interpreter (its compile for the chip is
``tests/test_flash_tpu_compile.py``'s, its run ``chip_smoke.py`` phase L's
and the benchmark cell's); the rule ``write_row`` has under ``vmap`` is held
to what ``vmap`` of the plain write gives.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from relayrl_tpu.ops import cache_rows


def _case(dtype, m=5, rows=64, width=256, seed=0):
    rng = np.random.default_rng(seed)
    cache = jnp.asarray(rng.standard_normal((m, rows, width)), dtype)
    new = jnp.asarray(rng.standard_normal((m, 1, width)), dtype)
    return cache, new


def _plain(cache, new, t):
    want = np.array(cache.astype(jnp.float32))
    for i, at in enumerate(np.clip(np.asarray(t), 0, cache.shape[1] - 1)):
        want[i, at] = np.asarray(new[i, 0].astype(jnp.float32))
    return want


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_the_kernel_writes_each_row_at_its_own_position(dtype):
    """Tile edges, one tile twice, and positions off either end, which
    clamp as ``dynamic_update_slice``'s do; every other row stays."""
    cache, new = _case(dtype)
    t = jnp.asarray([0, 17, 63, 16, 15], jnp.int32)
    got = cache_rows.write_rows_pallas(cache, new, t, interpret=True)
    assert got.dtype == cache.dtype
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  _plain(cache, new, t))
    off = jnp.asarray([-3, 64, 1000, 5, 31], jnp.int32)
    got = cache_rows.write_rows_pallas(cache, new, off, interpret=True)
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  _plain(cache, new, off))


def test_what_tiles():
    assert cache_rows.tiles((64, 1, 1024, 1024), jnp.bfloat16)
    assert cache_rows.tiles((2, 8, 128), jnp.float32)
    assert not cache_rows.tiles((2, 8, 128), jnp.bfloat16)   # 16-row tiles
    assert not cache_rows.tiles((2, 16, 96), jnp.bfloat16)   # lanes
    assert not cache_rows.tiles((2, 16, 128), jnp.int8)


@pytest.mark.parametrize("batched", ["all", "cache-and-row", "position"])
def test_write_row_under_vmap_is_the_plain_write(batched):
    """Off a TPU the rule is ``vmap`` of ``dynamic_update_slice``,
    whichever arguments carry the batch; alone it is that write itself,
    also inside ``jit`` and ``scan``."""
    cache, new = _case(jnp.float32, rows=8, width=16)
    t = jnp.asarray([0, 3, 7, 7, 2], jnp.int32)
    cache, new = cache[:, None], new[:, None]        # [5, B=1, L, C]
    if batched == "all":
        got = jax.vmap(cache_rows.write_row)(cache, new, t)
        want = _plain(cache[:, 0], new[:, 0], t)
    elif batched == "cache-and-row":
        got = jax.vmap(cache_rows.write_row, in_axes=(0, 0, None))(
            cache, new, 3)
        want = _plain(cache[:, 0], new[:, 0], np.full(5, 3))
    else:
        got = jax.vmap(cache_rows.write_row, in_axes=(None, None, 0))(
            cache[0], new[0], t)
        want = _plain(jnp.repeat(cache[0], 5, 0), jnp.repeat(new[0], 5, 0),
                      t)
    np.testing.assert_array_equal(np.asarray(got[:, 0]), want)


def test_write_row_alone_and_inside_a_scan():
    cache, new = _case(jnp.float32, m=1, rows=8, width=16)
    want = _plain(cache, new, [5])
    np.testing.assert_array_equal(
        np.asarray(cache_rows.write_row(cache, new, 5)), want)

    def lane(cache, new, t):
        return jax.lax.scan(
            lambda c, _: (cache_rows.write_row(c, new, t), None), cache,
            None, length=2)[0]

    got = jax.jit(jax.vmap(lane))(cache[None], new[None], jnp.asarray([5]))
    np.testing.assert_array_equal(np.asarray(got[0]), want)
