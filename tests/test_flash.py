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
    # off-TPU it falls back to blockwise (models/transformer.py resolver).
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
    # The two-pass Pallas VJP has distinct grid orderings per pass (dq is
    # q-major, dk/dv is kv-major) and per-pass live-block predicates; cover
    # uneven blocks and the non-causal branch explicitly.
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
