"""``ops/conv.py``: the mixers' depthwise causal convolution. The plain form
against the sum written row by row, then the Pallas kernels of
``ops/conv_pallas.py`` in the interpreter at small shapes that tile against
the plain form — the forward bit for bit, the gradients of the rows, the taps
and the bias under a written tolerance, also under ``jax.checkpoint`` —, and
the rule that picks between the two."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import checkpoint_name

from relayrl_tpu.ops import conv as cv
from relayrl_tpu.ops.scopes import GDN_CONV_NAME, MAMBA_CONV_NAME

WRT = ("x", "w", "bias")


def _inputs(T, C, taps=4, bias=True, dtype=jnp.float32, batch=2, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    return {"x": f(batch, T, C).astype(dtype), "w": 0.5 * f(taps, C),
            "bias": 0.1 * f(C) if bias else None}


# jitted: an eager call traces and compiles the interpreted kernels op by op
@functools.partial(jax.jit, static_argnames=("scope",))
def _kernels(x, w, bias, scope=MAMBA_CONV_NAME):
    from relayrl_tpu.ops.conv_pallas import conv_pallas

    return conv_pallas(x, w, bias, scope, interpret=True)


@jax.jit
def _plain(x, w, bias):
    return cv.conv_xla(x, w, bias)


# (T, C, taps, bias): one tile; several row tiles of one strip, of several
# strips and of several lane strips; C of several column tiles; three taps
SHAPES = [(64, 128, 4, True), (96, 128, 4, False), (2048, 128, 4, True),
          (128, 384, 4, False), (256, 512, 4, True), (64, 256, 3, True),
          (128, 1024, 2, False)]


@pytest.mark.parametrize("state", [False, True])
@pytest.mark.parametrize("taps", [4, 3])
def test_plain_form_is_the_sum(taps, state):
    a = _inputs(9, 5, taps)
    before = (np.asarray(_inputs(taps - 1, 5, seed=3)["x"]) if state
              else np.zeros((2, taps - 1, 5), np.float32))
    xp = np.concatenate([before, np.asarray(a["x"])], axis=1)
    c = sum(np.asarray(a["w"])[j] * xp[:, j:j + 9] for j in range(taps))
    c = c + np.asarray(a["bias"])
    got = cv.conv_xla(**a, state=jnp.asarray(before) if state else None)
    np.testing.assert_allclose(got, c / (1.0 + np.exp(-c)), atol=1e-6,
                               rtol=1e-5)
    np.testing.assert_array_equal(
        cv.padded(a["x"], taps, jnp.asarray(before) if state else None), xp)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("T,C,taps,bias", SHAPES)
def test_kernel_forward_is_the_plain_form_bit_for_bit(T, C, taps, bias,
                                                      dtype):
    a = _inputs(T, C, taps, bias, dtype, batch=1 if T > 1024 else 2)
    got, want = _kernels(**a), _plain(**a)
    assert got.dtype == want.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def _gradients(fn, a, dy):
    """The cotangents of ``dy`` at every argument that is one."""
    wrt = {k: v for k, v in a.items() if v is not None}
    out, vjp = jax.vjp(lambda wrt: fn(**{**a, **wrt}), wrt)
    return vjp(dy)[0]


@pytest.mark.parametrize("T,C,taps,bias", SHAPES)
def test_kernel_gradients(T, C, taps, bias):
    """``conv_bwd`` against autodiff of the plain form in float32: no term
    left out at a tile's, a strip's or a sequence's edge (2e-5 of a
    gradient's largest entry: the sums' order is the difference)."""
    a = _inputs(T, C, taps, bias, batch=1 if T > 1024 else 2, seed=1)
    dy = _inputs(T, C, batch=a["x"].shape[0], seed=2)["x"]
    got, want = (jax.jit(functools.partial(_gradients, fn))(a, dy)
                 for fn in (_kernels, _plain))
    assert set(got) == set(want) == {k for k in WRT if a[k] is not None}
    for wrt in got:
        assert got[wrt].shape == want[wrt].shape
        assert got[wrt].dtype == want[wrt].dtype
        scale = float(jnp.abs(want[wrt]).max())
        np.testing.assert_allclose(got[wrt], want[wrt], atol=2e-5 * scale,
                                   rtol=0, err_msg=wrt)


@pytest.mark.parametrize("bias", [True, False])
def test_kernel_gradients_in_bfloat16(bias):
    """Rows and cotangents in bfloat16: ``dx`` is rounded once from a
    float32 sum where autodiff of the plain form rounds every tap's term
    (2^-6 of the largest entry, the other kernels' limit); the taps' and the
    bias's gradients are float32 sums on both sides."""
    a = _inputs(128, 256, 4, bias, jnp.bfloat16, seed=4)
    dy = _inputs(128, 256, dtype=jnp.bfloat16, seed=5)["x"]
    got, want = (jax.jit(functools.partial(_gradients, fn))(a, dy)
                 for fn in (_kernels, _plain))
    f32 = lambda v: np.asarray(v, np.float32)
    for wrt, limit in (("x", 2.0 ** -6), ("w", 1e-4), ("bias", 1e-4)):
        if a[wrt] is None:
            continue
        assert got[wrt].dtype == want[wrt].dtype
        scale = np.abs(f32(want[wrt])).max()
        assert np.abs(f32(got[wrt]) - f32(want[wrt])).max() <= limit * scale


@pytest.mark.parametrize("scope", [MAMBA_CONV_NAME, GDN_CONV_NAME])
def test_kernels_under_a_checkpoint_that_keeps_a_name(scope):
    """As the mixers call it: inside ``jax.checkpoint`` with
    ``save_only_these_names`` of something downstream, the convolution's
    rows made again in the backward from its input."""
    a = _inputs(128, 256, 4, scope == MAMBA_CONV_NAME, seed=6)
    dy = _inputs(128, 256, seed=7)["x"]

    def mixer(fn, x, w, bias):
        y = checkpoint_name(jnp.tanh(fn(2.0 * x, w, bias)), "kept")
        return y * y

    def loss(fn, a):
        kept = jax.checkpoint(
            functools.partial(mixer, fn),
            policy=jax.checkpoint_policies.save_only_these_names("kept"))
        return jnp.sum(kept(a["x"], a["w"], a["bias"]) * dy)

    got, want = (
        jax.jit(jax.grad(functools.partial(loss, fn)))(a)
        for fn in (functools.partial(_kernels, scope=scope), _plain))
    for wrt in WRT:
        if a[wrt] is None:
            assert got[wrt] is None
            continue
        scale = float(jnp.abs(want[wrt]).max())
        np.testing.assert_allclose(got[wrt], want[wrt], atol=2e-5 * scale,
                                   rtol=0, err_msg=wrt)


@pytest.mark.parametrize("t", [0, 63, 64, 100, 127])
def test_kernels_are_causal(t):
    """A change to row ``t`` moves no output before ``t`` and no gradient's
    reach exceeds the taps': rows ``t .. t + taps - 1`` of the output alone
    (``t`` at a strip's and a tile's edges), and ``dx`` of a loss on row
    ``t`` alone reaches rows ``t - (taps - 1) .. t``."""
    a = _inputs(128, 128, batch=1, seed=8)
    moved = dict(a, x=a["x"].at[:, t].add(1.0))
    differs = np.asarray(_kernels(**a) != _kernels(**moved)).any(axis=(0, 2))
    assert not differs[:t].any() and differs[t]
    assert not differs[t + 4:].any()
    dx = jax.jit(jax.grad(lambda x: jnp.sum(_kernels(**dict(a, x=x))[:, t])))(
        a["x"])
    reached = np.asarray(dx != 0).any(axis=(0, 2))
    assert reached[max(t - 3, 0):t + 1].all()
    assert not reached[:max(t - 3, 0)].any() and not reached[t + 1:].any()


@pytest.mark.parametrize("T,C,taps,fits", [
    (8192, 6144, 4, True),      # nemotron-twotower-policy
    (8192, 8192, 4, True),      # qwen3next-policy
    (64, 128, 3, True), (32, 256, 2, True),
    (8192, 6144, 1, False),     # no reach at all: not a convolution
    (8192, 6144, 10, False),    # taps that reach past a sublane tile
    (8192, 6100, 4, False),     # columns that do not fill lane tiles
    (8, 128, 4, False), (100, 128, 4, False),   # rows that are no row tile
])
def test_the_rule_that_picks_the_kernels(monkeypatch, T, C, taps, fits):
    """Platform and shape: off a TPU every shape takes the plain form; on
    one (this process made to say so) the shapes that tile take the kernels
    at a sequence's start, and a call that continues from a cache's rows,
    the cached step's one row and ``init``'s stay plain."""
    from relayrl_tpu.ops import conv_pallas

    assert conv_pallas.fits(T, C, taps) == fits
    assert cv.backend(T, C, taps) == cv.XLA
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert cv.backend(T, C, taps) == (cv.PALLAS if fits else cv.XLA)
    assert cv.backend(T, C, taps, has_state=True) == cv.XLA
    assert cv.backend(1, C, taps) == cv.XLA


def test_conv_takes_the_plain_form_here():
    """``conv()`` on this platform is the plain form under the caller's
    scope, from a sequence's start and from a cache's rows."""
    a = _inputs(16, 128, seed=9)
    state = _inputs(3, 128, seed=10)["x"]
    np.testing.assert_array_equal(
        cv.conv(**a, state=None, scope=MAMBA_CONV_NAME), cv.conv_xla(**a))
    np.testing.assert_array_equal(
        cv.conv(**a, state=state, scope=GDN_CONV_NAME),
        cv.conv_xla(**a, state=state))
    text = jax.jit(lambda a: cv.conv(**a, state=None, scope=GDN_CONV_NAME)
                   ).lower(a).as_text(debug_info=True)
    assert GDN_CONV_NAME in text and "tpu_custom_call" not in text


@pytest.mark.parametrize("layer,key", [
    ("mamba2", (8, 4 * 8 + 2 * 2 * 8, 4, False, "float32")),
    ("linear_attention", (8, 2 * 2 * 8 + 4 * 8, 4, False, "float32")),
])
def test_the_policy_records_what_its_convolutions_ran_as(capsys, layer, key):
    from relayrl_tpu.models import build_policy

    policy = build_policy({
        "kind": "transformer_discrete", "obs_dim": 6, "act_dim": 3,
        "d_model": 16, "n_heads": 2, "max_seq_len": 8, "n_layers": 2,
        "layer_types": [layer, layer], "mamba_heads": 4,
        "mamba_head_dim": 8, "mamba_state": 8, "mamba_groups": 2,
        "mamba_chunk": 4, "gdn_key_heads": 2, "gdn_value_heads": 4,
        "gdn_key_dim": 8, "gdn_value_dim": 8, "gdn_chunk": 4,
        "norm": "rms", "positions": "none"})
    assert policy.conv_backends == {}
    params = jax.eval_shape(policy.init_params, jax.random.PRNGKey(0))
    capsys.readouterr()
    # traced, not run: the record is made where the convolution is traced
    jax.eval_shape(policy.evaluate, params, jnp.zeros((2, 8, 6)),
                   jnp.zeros((2, 8), jnp.int32), jnp.ones((2, 8, 3), bool))
    assert policy.conv_backends[key] == cv.XLA
    said = [line for line in capsys.readouterr().out.splitlines()
            if line.startswith("[conv]")]
    assert len(said) == 1 and "T=8 " in said[0]       # one line a shape
    assert f"columns={key[1]} taps=4" in said[0]
    assert said[0].endswith("from=start float32 -> conv_xla (platform cpu)")


def test_other_trunks_record_none():
    from relayrl_tpu.models import build_policy

    policy = build_policy({
        "kind": "transformer_discrete", "obs_dim": 6, "act_dim": 3,
        "d_model": 16, "n_heads": 2, "max_seq_len": 8, "n_layers": 1})
    params = jax.eval_shape(policy.init_params, jax.random.PRNGKey(0))
    jax.eval_shape(policy.evaluate, params, jnp.zeros((2, 8, 6)),
                   jnp.zeros((2, 8), jnp.int32), jnp.ones((2, 8, 3), bool))
    assert policy.conv_backends == {}
