"""The flash kernels' one backward kernel a call, what a trunk traces of
them and the band kernels of a sliding window, through the Pallas interpreter
as ``tests/test_flash.py``."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _flash import (
    _clear_kernel_caches,
    _grad_loss,
    _grouped_qkv,
    _qkv,
    _sub_tile,
    flash_attention,
)
from _util import band_attention_oracle
from relayrl_tpu.ops import flash
from relayrl_tpu.ops.attention import dense_attention


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
