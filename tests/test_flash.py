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

from _flash import (
    _clear_kernel_caches,
    _grad_loss,
    _qkv,
    _sub_tile,
    flash_attention,
)
from relayrl_tpu.ops import flash
from relayrl_tpu.ops.attention import blockwise_attention, dense_attention


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

# (slow: bfloat16 is the float32 case's twin at another tolerance; tier-1
# keeps the float32 sibling of each shape, and bfloat16 in strips through
# ``test_sub_tiled_at_the_production_height``)
@pytest.mark.parametrize("dtype", [
    "float32", pytest.param("bfloat16", marks=pytest.mark.slow)])
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
