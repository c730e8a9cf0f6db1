"""Pallas flash-attention kernel vs the dense reference (interpret mode).

The conftest pins tests to the CPU backend, so these tests ask for the
Pallas interpreter explicitly (``interpret=True`` — never a default, see
ops/flash.py) — bit-accurate TPU semantics without hardware; the compiled
kernel runs on the chip through ``chip_smoke.py`` phase B.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _util import band_attention_oracle
from relayrl_tpu.ops import flash
from relayrl_tpu.ops.attention import blockwise_attention, dense_attention

flash_attention = functools.partial(flash.flash_attention, interpret=True)


def _qkv(B=2, T=64, H=2, D=16, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_dense(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_kv=16)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_uneven_blocks():
    # block_q != block_kv exercises the cross-block causal predicate.
    q, k, v = _qkv(T=64)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_kv=16)
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_grads_match_dense():
    q, k, v = _qkv()

    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))

    got = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, block_q=16, block_kv=16)), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(dense_attention), argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(g, w, atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name}")


def test_flash_matches_blockwise_bf16():
    # bf16 inputs: the production trunk dtype; compare against blockwise at
    # a bf16-appropriate tolerance.
    q, k, v = _qkv()
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = flash_attention(qb, kb, vb, block_q=16, block_kv=16)
    ref = blockwise_attention(qb, kb, vb, block_size=16)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(out.astype(jnp.float32),
                               ref.astype(jnp.float32), atol=3e-2)


def test_flash_rejects_indivisible_seq():
    q, k, v = _qkv(T=60)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, block_q=16, block_kv=16)


def test_transformer_flash_arch_runs_off_tpu():
    # attention="flash" must be usable in the same arch config everywhere:
    # off-TPU it falls back to blockwise (models/layers/attention.resolve).
    from relayrl_tpu.models import build_policy

    arch = {"kind": "transformer_discrete", "obs_dim": 8, "act_dim": 3,
            "d_model": 32, "n_layers": 1, "n_heads": 2, "max_seq_len": 32,
            "attention": "flash", "attention_block": 16}
    policy = build_policy(arch)
    params = policy.init_params(jax.random.PRNGKey(0))
    obs = jnp.zeros((2, 32, 8), jnp.float32)
    act, aux = policy.step(params, jax.random.PRNGKey(1), obs)
    assert act.shape == (2,)
    logp, ent, v = policy.evaluate(params, obs, jnp.zeros((2, 32), jnp.int32))
    assert logp.shape == (2, 32)
    # ...and says so: the resolved backend is on the policy.
    assert policy.attention_backends[(32, 16, "float32")] == "blockwise"


def test_flash_never_defaults_to_the_interpreter():
    # Off-TPU without interpret=True the Mosaic lowering must refuse —
    # a device process can never reach the interpreter by default.
    q, k, v = _qkv(T=16)
    with pytest.raises(Exception, match="(?i)interpret|tpu|mosaic"):
        jax.block_until_ready(
            flash.flash_attention(q, k, v, block_q=16, block_kv=16))


@pytest.mark.parametrize("causal,bq,bk", [
    (True, 16, 32), (True, 32, 16), (False, 16, 32), (False, 32, 16),
])
def test_flash_grads_uneven_and_noncausal(causal, bq, bk):
    # The backward kernel walks K/V blocks outside q blocks and holds dq over
    # all of them; where the diagonal does not run corner to corner (uneven
    # blocks) or there is none (non-causal) a q block's dq is complete only
    # beside the LAST K/V block: cover both explicitly.
    q, k, v = _qkv()

    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))

    got = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, block_q=bq, block_kv=bk)),
        argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: dense_attention(
        q, k, v, causal=causal)), argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(g, w, atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name}")


# -- causal strips inside a grid step (the shape class the benchmark's
# transformer cells run: T == block, and T a few blocks) -------------------

def _sub_tile(monkeypatch, rows):
    """Scale the strip height down through the kernels' own derivation
    (the production height, 256, takes a 512-step block to engage)."""
    monkeypatch.setattr(flash, "_SUB_TILE", rows)


def _clear_kernel_caches():
    for cache in (flash._make_flash, flash._build_fwd, flash._build_bwd,
                  flash._shared):
        cache.cache_clear()


def _grad_loss(fn):
    return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v).astype(jnp.float32)))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("T,block,sub", [
    (16, 16, 8),     # T == block == 2 strips: 3 of 4 sub-tiles
    (32, 32, 8),     # T == block == 4 strips: 10 of 16
    (64, 32, 8),     # T == 2 block: diagonal and interior grid blocks
])
def test_sub_tiled_matches_dense(monkeypatch, T, block, sub, dtype):
    _sub_tile(monkeypatch, sub)
    assert flash.tiling(T, True, block, block) == (block, block, sub)
    q, k, v = (x.astype(dtype) for x in _qkv(B=1, T=T, H=2, D=16))
    atol = 5e-5 if dtype == "float32" else 6e-2

    def flash_fn(q, k, v):
        return flash_attention(q, k, v, block_q=block, block_kv=block)

    out = flash_fn(q, k, v)
    assert out.dtype == q.dtype
    np.testing.assert_allclose(
        out.astype(jnp.float32),
        dense_attention(q, k, v, causal=True).astype(jnp.float32),
        atol=atol, rtol=atol)
    got = jax.grad(_grad_loss(flash_fn), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(_grad_loss(dense_attention), argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(
            g.astype(jnp.float32), w.astype(jnp.float32), atol=atol,
            rtol=atol, err_msg=f"d{name}")


def test_sub_tiled_at_the_production_height():
    # One head of gpt2m-policy.update's shape, in the strips the chip runs.
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(B=1, T=1024, H=1, D=64))
    assert flash.tiling(1024) == (1024, 1024, flash._SUB_TILE)
    out = flash_attention(q, k, v)
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               ref.astype(jnp.float32), atol=3e-2)


@pytest.mark.parametrize("T,bq,bk,causal", [
    (1, 16, 16, True),       # build's T = 1 kernel
    (8, 16, 16, True),       # a block of one strip
    (24, 24, 24, True),      # the side does not divide the block (T = 1000)
    (32, 32, 16, True),      # unequal blocks
    (32, 16, 32, True),
    (32, 32, 32, False),     # non-causal
])
def test_fall_back_shapes_stay_one_tile(monkeypatch, T, bq, bk, causal):
    """Where the strips do not apply the kernels run the single tile they
    always ran: the same results to the bit as with no strip height at
    all."""
    q, k, v = _qkv(B=1, T=T, H=2, D=16)

    def run():
        _clear_kernel_caches()
        fn = lambda q, k, v: flash_attention(
            q, k, v, causal=causal, block_q=bq, block_kv=bk)
        return (fn(q, k, v),
                *jax.grad(_grad_loss(fn), argnums=(0, 1, 2))(q, k, v))

    _sub_tile(monkeypatch, 16)
    assert flash.tiling(T, causal, bq, bk)[2] is None
    with_strips = run()
    _sub_tile(monkeypatch, 1 << 30)
    for a, b in zip(with_strips, run()):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_allclose(
        with_strips[0], dense_attention(q, k, v, causal=causal),
        atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("T,block,sub,pct", [
    (1024, 1024, None, 100.0),   # gpt2m-policy.update before sub-tiling
    (1024, 1024, 512, 75.0),
    (1024, 1024, 256, 62.5),
    (1024, 1024, 128, 56.25),
    (4096, 1024, None, 62.5),    # olmoe-policy.update: 6 + 4 of 16 blocks
    (4096, 1024, 256, 53.125),
    (64, 16, None, 62.5),
    (1, 1, None, 100.0),
])
def test_score_area_pct(T, block, sub, pct):
    assert flash.score_area_pct(T, block, block, sub, True) == pct
    assert flash.score_area_pct(T, block, block, None, False) == 100.0


@pytest.mark.parametrize("T,block,sub", [(32, 32, 8), (32, 32, 16),
                                         (64, 32, 8), (32, 32, None)])
def test_score_area_is_what_the_kernels_visit(monkeypatch, T, block, sub):
    """The area function against the kernel bodies: sum the score tiles
    each of the two kernels (forward; backward) emits at trace time."""
    _sub_tile(monkeypatch, sub or 1 << 30)
    _clear_kernel_caches()
    visited = []
    scores2 = flash._scores2

    def counting(*args, **kw):
        s = scores2(*args, **kw)
        visited.append(s.shape)
        return s

    monkeypatch.setattr(flash, "_scores2", counting)
    q, k, v = _qkv(B=1, T=T, H=1, D=16)
    jax.grad(_grad_loss(lambda q, k, v: flash_attention(
        q, k, v, block_q=block, block_kv=block)), argnums=(0, 1, 2))(q, k, v)
    _clear_kernel_caches()  # they were built round the counting wrapper
    n_blocks = T // block
    if n_blocks > 1:
        # One whole interior tile a kernel: emitted once, run by the
        # n (n - 1) / 2 grid blocks below the diagonal.
        for _ in range(2):
            visited.remove((block, block))
    strips = sum(a * b for a, b in visited)
    assert strips % 2 == 0, visited  # fwd, bwd: the same area
    area = n_blocks * strips // 2 + (
        n_blocks * (n_blocks - 1) // 2) * block * block
    assert flash.score_area_pct(T, block, block, sub, True) == (
        100.0 * area / (T * T))
    if sub is not None:  # strips, and nothing taller or wider than needed
        n = block // sub
        assert sorted(visited) == sorted(
            [(sub, (r + 1) * sub) for r in range(n)]           # fwd
            + [(sub, (n - c) * sub) for c in range(n)])        # bwd^T


def test_policy_records_the_score_area(monkeypatch, capsys):
    # On a TPU "flash" resolves to the kernels; the policy then says how
    # much of the score matrix they compute at each traced shape.
    from relayrl_tpu.models import build_policy

    _sub_tile(monkeypatch, 8)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(flash, "flash_attention", flash_attention)
    arch = {"kind": "transformer_discrete", "obs_dim": 8, "act_dim": 3,
            "d_model": 32, "n_layers": 1, "n_heads": 2, "max_seq_len": 32,
            "attention": "flash"}
    policy = build_policy(arch)
    params = policy.init_params(jax.random.PRNGKey(0))
    policy.evaluate(params, jnp.zeros((1, 32, 8), jnp.float32),
                    jnp.zeros((1, 32), jnp.int32))
    key = (32, 16, "float32")
    assert policy.attention_backends[key] == "flash_pallas"
    assert policy.attention_score_area_pct[key] == 62.5
    assert policy.attention_score_area_pct[(1, 16, "float32")] == 100.0
    # ...and which operand layout the kernels ran: 2 heads of 16 fill no
    # 128-lane block
    assert policy.attention_layout[key] == "head-major"
    assert ("T=32 head_dim=16 float32 -> flash_pallas, score area 62.5%, "
            "layout head-major" in capsys.readouterr().out)


def test_policy_records_the_lane_layout(monkeypatch, capsys):
    # the same record at a head_dim the lanes admit: two heads a grid step
    from relayrl_tpu.models import build_policy

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(flash, "flash_attention", flash_attention)
    arch = {"kind": "transformer_discrete", "obs_dim": 8, "act_dim": 3,
            "d_model": 128, "n_layers": 1, "n_heads": 2, "max_seq_len": 16,
            "attention": "flash"}
    policy = build_policy(arch)
    params = policy.init_params(jax.random.PRNGKey(0))
    policy.evaluate(params, jnp.zeros((1, 16, 8), jnp.float32),
                    jnp.zeros((1, 16), jnp.int32))
    assert policy.attention_layout[(16, 64, "float32")] == "2 heads a step"
    assert ("T=16 head_dim=64 float32 -> flash_pallas, score area 100%, "
            "layout 2 heads a step" in capsys.readouterr().out)


# -- grouped-query k/v: index maps only, k/v never repeated -------------------

def _grouped_qkv(B, T, H, h_kv, D=8, seed=5):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((B, T, h_kv, D)), jnp.float32)
            for _ in range(2))
    return q, k, v


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
        return (fn(q, k, v),
                *jax.grad(_grad_loss(fn), argnums=(0, 1, 2))(q, k, v))

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


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
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


# -- the backward: dq, dk and dv of one score tile, one kernel a call ---------

@pytest.mark.parametrize("H,h_kv,D,T,bq,bk,window,sub", [
    (2, 2, 128, 64, 16, 16, None, 8),    # head-major at 128, plain heads
    (7, 1, 128, 64, 16, 16, None, 8),    # ... 7 q heads a k/v head
    (8, 1, 256, 32, 16, 16, None, 8),    # head_dim 256, 8 q heads a k/v head
    (4, 4, 64, 64, 16, 16, None, 8),     # two heads a step: plain pairs
    (8, 2, 64, 64, 16, 16, None, 8),     # ... 4 q heads share a k/v head
    (2, 2, 128, 32, 32, 32, None, 8),    # one block a head, in strips
    (4, 4, 64, 16, 16, 16, None, None),  # ... one tile, two heads a step
    (2, 2, 128, 64, 32, 16, None, None),  # unequal blocks: dq waits for
    (2, 1, 128, 64, 16, 32, None, None),  # the last K/V block
    (7, 1, 128, 64, 16, 16, 32, 8),      # a window of whole blocks
    (7, 1, 128, 64, 16, 16, 24, 8),      # a window that cuts a block
    (4, 2, 64, 64, 16, 16, 8, 8),        # a window shorter than a block
])
def test_fused_backward_matches_dense(monkeypatch, H, h_kv, D, T, bq, bk,
                                      window, sub):
    """dq, dk and dv of the one backward kernel against ``jax.grad`` of the
    dense form, over the layouts, groups and grids the module claims."""
    _sub_tile(monkeypatch, sub or 1 << 30)
    _clear_kernel_caches()
    assert flash.lane_layout(H, h_kv, D) == (2 if D == 64 else None)
    q, k, v = _grouped_qkv(1, T, H, h_kv, D, seed=13)
    fl = lambda q, k, v: flash_attention(q, k, v, block_q=bq, block_kv=bk,
                                         window=window)
    dense = functools.partial(dense_attention, window=window)
    text = str(jax.make_jaxpr(jax.grad(_grad_loss(fl), (0, 1, 2)))(q, k, v))
    assert text.count("pallas_call") == 2
    got = jax.grad(_grad_loss(fl), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(_grad_loss(dense), argnums=(0, 1, 2))(q, k, v)
    _clear_kernel_caches()
    for g, w, name in zip(got, want, "qkv"):
        assert g.shape == w.shape, name
        np.testing.assert_allclose(g, w, atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("causal", [True, False])
def test_the_accumulators_hold_five_blocks_and_start_from_zero(causal):
    """Five K/V blocks a head and three q heads a k/v head: a q head's dq is
    summed over all five beside dk / dv summed over five q blocks of three
    heads, with only the last q block's cotangent alive (so every K/V block's
    share of dq lands in ONE accumulator block, and dk / dv of every key
    come from one q block). A second batch row equal to the first gives the
    same bits: nothing of a head's sums is left for the next."""
    q, k, v = _grouped_qkv(1, 80, 3, 1, 16, seed=17)
    q, k, v = (jnp.concatenate([x, x]) for x in (q, k, v))
    last = (jnp.arange(80) >= 64)[None, :, None, None]

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            jnp.where(last, jnp.sin(fn(q, k, v)), 0.0))

    fl = lambda q, k, v: flash_attention(q, k, v, causal=causal, block_q=16,
                                         block_kv=16)
    dense = functools.partial(dense_attention, causal=causal)
    got = jax.grad(loss(fl), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(g, w, atol=2e-5, rtol=2e-5,
                                   err_msg=f"d{name}")
        np.testing.assert_array_equal(g[0], g[1], err_msg=f"d{name}")
    dq, dk, _ = got
    assert not dq[:, :64].any() and bool(dq[:, 64:].any())
    assert all(bool(dk[:, b * 16:(b + 1) * 16].any())
               for b in range(5))


def test_the_backward_refuses_accumulators_past_vmem():
    """A q head's dq and a k/v head's dk and dv stay in VMEM over all of T:
    the builder says so where T x lanes is past what fits, read off the
    operands' shape; the benchmark's largest (16,384 x 128 and 8,192 x 256)
    take half of it."""
    build = lambda T, D: flash._build_bwd(T, D, True, 1024, 1024, 256,
                                          "bfloat16", False)
    for T, D in ((16_384, 128), (8_192, 256), (32_768, 128)):
        assert 3 * T * D * 4 <= flash._MAX_ACC_BYTES
        build(T, D)
    with pytest.raises(ValueError, match="do not fit VMEM"):
        build(65_536, 128)
    with pytest.raises(ValueError, match="do not fit VMEM"):
        build(32_768, 256)
    flash._build_bwd.cache_clear()


# -- the kernels' trace: a call's repeats share one, its first stays bare -----

def _flash_policy(monkeypatch, n_layers, T=16):
    """A tiny ``transformer_discrete`` at head_dim 64 whose attention is the
    kernels (as on a TPU), run through the interpreter."""
    from relayrl_tpu.models import build_policy

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(flash, "flash_attention", flash_attention)
    return build_policy({
        "kind": "transformer_discrete", "obs_dim": 8, "act_dim": 3,
        "d_model": 128, "n_layers": n_layers, "n_heads": 2,
        "max_seq_len": T, "attention": "flash"})


def _count_kernel_bodies(monkeypatch):
    """Calls of the two kernel body functions = traces of a body."""
    calls = {}
    _clear_kernel_caches()
    for name in ("_fwd_kernel", "_bwd_kernel"):
        body = getattr(flash, name)

        def counted(*args, _body=body, _name=name, **kw):
            calls[_name] = calls.get(_name, 0) + 1
            return _body(*args, **kw)

        monkeypatch.setattr(flash, name, counted)
    return calls


def _lowered_update(policy, T=16):
    from relayrl_tpu.algorithms.impala import (
        ImpalaState, make_impala_tx, make_impala_update)
    from relayrl_tpu.data.batching import TrajectoryBatch

    params = jax.eval_shape(policy.init_params, jax.random.PRNGKey(0))
    tx = make_impala_tx(1e-4, 1.0)
    state = ImpalaState(
        params=params, opt_state=jax.eval_shape(tx.init, params),
        rng=jax.ShapeDtypeStruct((2,), jnp.uint32),
        step=jax.ShapeDtypeStruct((), jnp.int32))
    update = make_impala_update(policy, lr=1e-4, gamma=0.99, vf_coef=0.5,
                                ent_coef=0.01, rho_bar=1.0, c_bar=1.0,
                                max_grad_norm=1.0)
    batch = TrajectoryBatch.zeros(2, T, 8, 3, True)
    return jax.jit(update, donate_argnums=0).lower(state, batch).as_text()


@pytest.mark.parametrize("n_layers,traces,shared_funcs", [
    (4, 2, 2),  # the first layer's call bare, one inner jit for the rest
    (1, 1, 0),  # one attention layer never meets the inner jit
])
def test_a_trunk_traces_a_kernel_body_twice_at_most(monkeypatch, n_layers,
                                                    traces, shared_funcs):
    """Lowering an L-layer update traces each flash kernel body twice —
    the first call bare, its repeats through the builders' one inner
    ``jit`` (``flash._make_flash``) — where it was L times; a model with
    one attention layer traces and lowers what it did without it."""
    calls = _count_kernel_bodies(monkeypatch)
    policy = _flash_policy(monkeypatch, n_layers)
    text = _lowered_update(policy)
    _clear_kernel_caches()
    assert policy.attention_layout[(16, 64, "float32")] == "2 heads a step"
    # init ran the model at T = 1 (forward only, the custom_vjp's forward
    # in a trace of its own a layer), the update at T = 16
    assert calls == {"_fwd_kernel": 2 * traces, "_bwd_kernel": traces}
    # one lowered function a builder (forward; backward), called a layer
    assert len([ln for ln in text.splitlines()
                if "func.func private @call" in ln]) == shared_funcs


def test_eager_repeats_share_one_program(monkeypatch):
    """Eagerly — ``init_params`` runs every layer at T = 1 — the first
    call compiles the bare primitive's program, as a lone call always did
    (no ``jit(call)`` exists for it), and every repeat runs ONE
    ``jit(call)`` executable where each layer compiled its own."""
    from jax import monitoring

    compiled = []

    def on_compile(event, duration, fun_name="", **kw):
        if event.endswith("backend_compile_duration"):
            compiled.append(fun_name)

    def kernel_programs():
        found = [n for n in compiled if n in ("jit(call)", "jit(wrapped)")]
        compiled.clear()
        return found

    monitoring.register_event_duration_secs_listener(on_compile)
    try:
        calls = _count_kernel_bodies(monkeypatch)
        q, k, v = _grouped_qkv(1, 16, 2, 2, 64)
        jax.grad(_grad_loss(lambda q, k, v: flash_attention(
            q, k, v, block_q=16, block_kv=16)), argnums=(0, 1, 2))(q, k, v)
        assert calls == {"_fwd_kernel": 1, "_bwd_kernel": 1}
        assert "jit(call)" not in kernel_programs()
        calls.clear()
        policy = _flash_policy(monkeypatch, n_layers=4)
        policy.init_params(jax.random.PRNGKey(0))
        # four layers: the first bare, one trace of the shared jit
        assert calls == {"_fwd_kernel": 2}
        assert kernel_programs().count("jit(call)") == 1
    finally:
        monitoring.unregister_event_duration_listener(on_compile)
        _clear_kernel_caches()


# -- sliding-window calls: the grids' innermost axes are the band ------------

@pytest.mark.parametrize("T,block,window,H,h_kv,D,sub", [
    (64, 16, 32, 7, 1, 16, 8),     # whole blocks, group 7: cut block in strips
    (64, 16, 32, 14, 2, 16, None),  # ... masked as one tile
    (64, 16, 24, 7, 1, 16, 8),     # the edge inside a block: two blocks cut
    (64, 16, 17, 2, 1, 16, 8),     # one key past a block
    (64, 16, 8, 4, 4, 16, 8),      # shorter than a block: both edges in one
    (32, 32, 8, 2, 2, 16, 8),      # one block a head
    (64, 16, 48, 4, 2, 64, 8),     # two heads a step over a shared k/v head
    (64, 16, 16, 2, 2, 64, None),  # ... plain heads, a window of one block
])
def test_windowed_flash_matches_the_band_oracle(monkeypatch, T, block, window,
                                                H, h_kv, D, sub):
    """Forward and the three gradients of a windowed call against dense
    attention under the band mask."""
    _sub_tile(monkeypatch, sub or 1 << 30)
    _clear_kernel_caches()
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((2, T, H, D)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((2, T, h_kv, D)), jnp.float32)
            for _ in range(2))

    def fl(q, k, v):
        return flash_attention(q, k, v, block_q=block, block_kv=block,
                               window=window)

    oracle = functools.partial(band_attention_oracle, window=window)
    np.testing.assert_allclose(fl(q, k, v), oracle(q, k, v), atol=2e-5,
                               rtol=2e-5)
    np.testing.assert_allclose(
        fl(q, k, v), dense_attention(q, k, v, window=window), atol=2e-5,
        rtol=2e-5)
    got = jax.grad(_grad_loss(fl), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(_grad_loss(oracle), argnums=(0, 1, 2))(q, k, v)
    _clear_kernel_caches()
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(g, w, atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("window", [64, 100])
def test_a_window_of_the_whole_sequence_is_the_causal_call(window):
    """Bit-equal, forward and gradients: the same kernels under the same
    names."""
    q, k, v = _grouped_qkv(1, 64, 7, 1)

    def run(**kw):
        fn = lambda q, k, v: flash_attention(q, k, v, block_q=16,
                                             block_kv=16, **kw)
        return (fn(q, k, v), *jax.grad(_grad_loss(fn),
                                       argnums=(0, 1, 2))(q, k, v))

    for got, want in zip(run(window=window), run()):
        np.testing.assert_array_equal(got, want)
    lowered = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, block_q=16, block_kv=16, window=window)).lower(q, k, v)
    assert flash.WINDOW_SUFFIX not in lowered.as_text()


def test_a_windowed_call_carries_names_that_extend_the_kernels_own():
    q, k, v = _grouped_qkv(1, 64, 2, 1)
    text = jax.jit(jax.grad(_grad_loss(lambda q, k, v: flash_attention(
        q, k, v, block_q=16, block_kv=16, window=32)),
        argnums=(0, 1, 2))).lower(q, k, v).as_text(debug_info=True)
    for name in (flash.FWD_NAME, flash.BWD_NAME):
        assert name + flash.WINDOW_SUFFIX in text, name
    assert "relayrl_flash_dq" not in text and "relayrl_flash_dkv" not in text


def test_a_window_wants_a_causal_call_and_equal_blocks():
    q, k, v = _qkv(T=64)
    with pytest.raises(ValueError, match="causal call and equal blocks"):
        flash_attention(q, k, v, block_q=32, block_kv=16, window=16)
    with pytest.raises(ValueError, match="causal call and equal blocks"):
        flash_attention(q, k, v, causal=False, block_q=16, block_kv=16,
                        window=16)


@pytest.mark.parametrize("T,block,sub,window,pct", [
    # smallthinker-policy.update's windowed layers: the band needs 21.9%
    (16384, 1024, 256, 4096, 23.2421875),
    (16384, 1024, None, 4096, 27.34375),   # no strip walk at all
    (16384, 1024, 256, None, 50.78125),    # its global layer
    (64, 16, 8, 32, 100.0 * (4 * 192 + 2 * 192 + 3 * 256) / 4096),
    (64, 16, 8, 24, 100.0 * (4 * 192 + (3 + 2) * 256) / 4096),
    (64, 16, 8, 8, 100.0 * (4 + 3) * 256 / 4096),
])
def test_score_area_pct_of_a_band(T, block, sub, window, pct):
    assert flash.score_area_pct(T, block, block, sub, True, window) == pct


@pytest.mark.parametrize("T,block,sub,window", [
    (64, 16, 8, 32), (64, 16, None, 32), (64, 16, 8, 24), (64, 16, 8, 8)])
def test_band_score_area_is_what_the_kernels_visit(monkeypatch, T, block,
                                                   sub, window):
    """The area function against the kernel bodies and the grids: every
    kernel emits the diagonal's tiles, the cut block's and (where a block
    lies wholly inside the window) one interior tile; the grid runs each
    class as often as the band holds it."""
    _sub_tile(monkeypatch, sub or 1 << 30)
    _clear_kernel_caches()
    visited = []
    scores2 = flash._scores2

    def counting(*args, **kw):
        s = scores2(*args, **kw)
        visited.append(s.shape)
        return s

    monkeypatch.setattr(flash, "_scores2", counting)
    q, k, v = _qkv(B=1, T=T, H=1, D=16)
    jax.grad(_grad_loss(lambda q, k, v: flash_attention(
        q, k, v, block_q=block, block_kv=block, window=window)),
        argnums=(0, 1, 2))(q, k, v)
    _clear_kernel_caches()
    assert len(visited) % 2 == 0    # forward; backward
    n = T // block
    nband = flash._band_blocks(window, block, n)
    both_edges = window < block
    whole = window % block == 0 and sub is not None
    strips = 0 if sub is None or both_edges else block // sub
    diag = block * block if not strips else sum(
        sub * (r + 1) * sub for r in range(strips))
    cut = diag if whole else block * block
    area = 0
    for i in range(n):
        for d in range(min(i, nband - 1) + 1):
            area += (diag if d == 0 else block * block
                     if d * block <= window - block else cut)
    assert flash.score_area_pct(T, block, block, sub, True, window) == (
        100.0 * area / (T * T))
    # what a kernel's body holds: the diagonal's tiles, the cut block's,
    # and an interior tile where the window spans two blocks or more
    emitted = sum(a * b for a, b in visited) // 2
    assert emitted == diag + cut * (nband > 1) + block * block * (
        window >= 2 * block)


# -- values of another width than q and k (latent attention: 192 / 128) -------

def _latent_qkv(B, T, H, D, Dv, seed=11, h_kv=None):
    rng = np.random.default_rng(seed)
    mk = lambda h, d: jnp.asarray(rng.standard_normal((B, T, h, d)),
                                  jnp.float32)
    return mk(H, D), mk(h_kv or H, D), mk(h_kv or H, Dv)


@pytest.mark.parametrize("T,bq,bk,causal,h_kv", [
    (64, 32, 32, True, None),     # the diagonal corner to corner
    (64, 64, 64, True, None),     # one block a head: no carried softmax
    (64, 32, 16, False, 1),       # uneven, not causal, 2 q heads a k/v head
])
def test_values_of_their_own_width_match_dense(T, bq, bk, causal, h_kv):
    """q and k 24 lanes a head, v 16 (192 / 128 in small): the output is
    ``[B, T, H, 16]``, and dq, dk come back 24 wide, dv 16 — the forward and
    the one backward kernel against dense attention, and the blockwise form
    (what "flash" resolves to off a TPU) beside them."""
    q, k, v = _latent_qkv(2, T, 2, 24, 16, h_kv=h_kv)
    call = functools.partial(flash_attention, causal=causal, block_q=bq,
                             block_kv=bk)
    dense = functools.partial(dense_attention, causal=causal)
    out = call(q, k, v)
    assert out.shape == (2, T, 2, 16)
    np.testing.assert_allclose(out, dense(q, k, v), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(
        blockwise_attention(q, k, v, 16, causal=causal), dense(q, k, v),
        atol=2e-5, rtol=2e-5)
    w = jnp.asarray(np.random.default_rng(12).standard_normal(out.shape),
                    jnp.float32)
    grads = [jax.grad(lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2))(
        q, k, v) for fn in (call, dense, functools.partial(
            blockwise_attention, block_size=16, causal=causal))]
    assert [g.shape for g in grads[0]] == [q.shape, k.shape, v.shape]
    for mine, theirs in zip(grads[0], grads[1]):
        np.testing.assert_allclose(mine, theirs, atol=5e-5, rtol=5e-5)
    for mine, theirs in zip(grads[2], grads[1]):
        np.testing.assert_allclose(mine, theirs, atol=5e-5, rtol=5e-5)


def test_a_width_of_its_own_names_its_kernels_and_equal_widths_do_not():
    """``_mla`` on the kernels of a call whose values have their own width;
    a call with ``D_qk = D_v`` traces the ``pallas_call``s it traced before:
    the plain names, every block the q / k width, and the same jaxpr as a
    call that never heard of the option (the builders' cache keys hold no
    ``Dv``)."""
    q, k, v = _latent_qkv(1, 32, 2, 24, 16)
    grad = lambda *a: jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, block_q=16, block_kv=16)), argnums=(0, 1, 2))(*a)
    own = str(jax.make_jaxpr(grad)(q, k, v))
    for name in (flash.FWD_NAME, flash.BWD_NAME):
        assert name + flash.LATENT_SUFFIX in own
    flash._build_fwd.cache_clear()
    flash._build_bwd.cache_clear()
    equal = str(jax.make_jaxpr(grad)(q, k, k))
    assert flash.LATENT_SUFFIX not in equal
    assert flash.FWD_NAME in equal and flash.BWD_NAME in equal
    for built in (flash._build_fwd, flash._build_bwd):
        assert built.cache_info().currsize == 1
        # the call's key: no Dv behind the window's place
        assert built.cache_parameters()["typed"] is False
    with pytest.raises(ValueError, match="do not group"):
        flash_attention(q, k[:, :16], v)
