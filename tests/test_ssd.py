"""``ops/ssd.py``: the chunked Mamba-2 scan against the recurrence written
step by step (``lax.scan`` over T of ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
(x) B_t``, ``y_t = S_t C_t + D x_t``) — outputs, the last state and the
gradients of all six arguments, at T a multiple of the chunk and not, from
a carried state, and under right padding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from relayrl_tpu.ops.ssd import ssd, ssd_step

H, P, G, N = 4, 8, 2, 16
ARGS = ("x", "dt", "A", "B", "C", "D")


def _inputs(T, seed=0, batch=2):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    return {"x": f(batch, T, H, P),
            # step sizes and decay rates over the published range: from a
            # state that forgets within a chunk to one that spans many
            "dt": jnp.asarray(rng.uniform(0.001, 0.3, (batch, T, H)),
                              jnp.float32),
            "A": -jnp.asarray(rng.uniform(1.0, 16.0, (H,)), jnp.float32),
            "B": f(batch, T, G, N), "C": f(batch, T, G, N), "D": f(H)}


def step_by_step(x, dt, A, B, C, D, state=None):
    """The recurrence as it is written, one token at a time."""
    b = x.shape[0]
    rep = H // G
    Bh, Ch = (jnp.repeat(a, rep, axis=2) for a in (B, C))     # [b, T, H, N]
    if state is None:
        state = jnp.zeros((b, H, P, N), jnp.float32)

    def one(s, row):
        x_t, dt_t, b_t, c_t = row
        s = (jnp.exp(dt_t * A)[..., None, None] * s
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None])
        return s, jnp.einsum("bhpn,bhn->bhp", s, c_t) + D[:, None] * x_t

    last, y = jax.lax.scan(one, state, tuple(
        jnp.moveaxis(a, 1, 0) for a in (x, dt, Bh, Ch)))
    return jnp.moveaxis(y, 0, 1), last


@pytest.mark.parametrize("T,chunk", [(32, 8), (24, 8), (29, 8), (5, 8),
                                     (16, 16), (128, 128)])
def test_chunked_is_the_recurrence(T, chunk):
    a = _inputs(T)
    y, last = ssd(**a, chunk=chunk)
    y_ref, last_ref = step_by_step(**a)
    np.testing.assert_allclose(y, y_ref, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(last, last_ref, atol=2e-5, rtol=1e-5)


def test_a_carried_state_continues_the_sequence():
    """Two calls, the second from the first's last state, are one call."""
    a = _inputs(40)
    whole, last = ssd(**a, chunk=8)
    cut = 19                              # inside a chunk
    head = {k: v[:, :cut] if v.ndim > 1 else v for k, v in a.items()}
    tail = {k: v[:, cut:] if v.ndim > 1 else v for k, v in a.items()}
    y0, s0 = ssd(**head, chunk=8)
    y1, s1 = ssd(**tail, chunk=8, state=s0)
    np.testing.assert_allclose(jnp.concatenate([y0, y1], 1), whole,
                               atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(s1, last, atol=2e-5, rtol=1e-5)
    ref = step_by_step(**tail, state=s0)
    np.testing.assert_allclose(y1, ref[0], atol=2e-5, rtol=1e-5)


def test_a_state_that_is_not_carried_is_told_apart():
    a = _inputs(32)
    cut = {k: v[:, 16:] if v.ndim > 1 else v for k, v in a.items()}
    assert float(jnp.abs(ssd(**a, chunk=8)[0][:, 16:]
                         - ssd(**cut, chunk=8)[0]).max()) > 1e-2


@pytest.mark.parametrize("T,chunk", [(32, 8), (21, 8)])
@pytest.mark.parametrize("wrt", ARGS)
def test_gradients_are_the_recurrences(T, chunk, wrt):
    """d loss / d each argument, the loss reading y and the last state, with
    a carried state: the chunked form's (through ``jax.checkpoint``)
    against autodiff of the step-by-step form."""
    a = _inputs(T, seed=1)
    rng = np.random.default_rng(2)
    wy = jnp.asarray(rng.standard_normal((2, T, H, P)), jnp.float32)
    ws = jnp.asarray(rng.standard_normal((2, H, P, N)), jnp.float32)
    s0 = jnp.asarray(rng.standard_normal((2, H, P, N)), jnp.float32)

    def loss(fn, value):
        y, last = fn(**{**a, wrt: value})
        return jnp.sum(wy * y) + jnp.sum(ws * last)

    got = jax.grad(lambda v: loss(
        lambda **kw: ssd(**kw, chunk=chunk, state=s0), v))(a[wrt])
    want = jax.grad(lambda v: loss(
        lambda **kw: step_by_step(**kw, state=s0), v))(a[wrt])
    scale = float(jnp.abs(want).max())
    assert scale > 0
    np.testing.assert_allclose(got, want, atol=3e-5 * max(1.0, scale),
                               rtol=1e-4)


def test_the_carried_states_gradient_too():
    a = _inputs(24, seed=3)
    s0 = jnp.asarray(np.random.default_rng(4).standard_normal(
        (2, H, P, N)), jnp.float32)
    got, want = (jax.grad(lambda s: jnp.sum(fn(s)[0]) + jnp.sum(fn(s)[1]))(s0)
                 for fn in (lambda s: ssd(**a, chunk=8, state=s),
                            lambda s: step_by_step(**a, state=s)))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


def test_right_padding_is_inert():
    """A right-padded episode needs nothing: rows past the real ones change
    no real row's output (the scan is causal), whatever they hold, and with
    ``dt = 0`` there they leave the state as the real rows left it."""
    a = _inputs(32)
    n = 21
    real = {k: v[:, :n] if v.ndim > 1 else v for k, v in a.items()}
    y_real, last_real = ssd(**real, chunk=8)
    np.testing.assert_allclose(ssd(**a, chunk=8)[0][:, :n], y_real,
                               atol=2e-6, rtol=1e-6)
    padded = dict(a, dt=a["dt"].at[:, n:].set(0.0))
    np.testing.assert_allclose(ssd(**padded, chunk=8)[1], last_real,
                               atol=2e-6, rtol=1e-6)


def test_decays_that_underflow_stay_finite():
    """``dt A`` of -50 a step: every decay inside a chunk underflows to the
    zero it stands for; nothing overflows on the way, forward or back."""
    a = _inputs(16)
    a["A"] = jnp.full((H,), -500.0)
    a["dt"] = jnp.full_like(a["dt"], 0.1)
    y, last = ssd(**a, chunk=8)
    g = jax.grad(lambda x: jnp.sum(ssd(**{**a, "x": x}, chunk=8)[0]))(a["x"])
    assert bool(jnp.isfinite(y).all() and jnp.isfinite(last).all()
                and jnp.isfinite(g).all())
    np.testing.assert_allclose(y, step_by_step(**a)[0], atol=2e-5, rtol=1e-5)


def test_bfloat16_operands_accumulate_in_float32():
    a = _inputs(32)
    lo = {k: v.astype(jnp.bfloat16) if k in ("x", "B", "C") else v
          for k, v in a.items()}
    y, last = ssd(**lo, chunk=8)
    assert y.dtype == jnp.bfloat16 and last.dtype == jnp.float32
    y_ref = step_by_step(**{k: v.astype(jnp.float32)
                            for k, v in lo.items()})[0]
    scale = float(jnp.abs(y_ref).max())
    assert float(jnp.abs(y.astype(jnp.float32) - y_ref).max()) < 0.03 * scale


def test_one_step_is_the_scan_at_one_token():
    a = _inputs(9)
    s = jnp.zeros((2, H, P, N), jnp.float32)
    for t in range(9):
        row = {k: v[:, t] if v.ndim > 1 else v for k, v in a.items()}
        y_t, s = ssd_step(**row, state=s)
    y, last = ssd(**a, chunk=4)
    np.testing.assert_allclose(y_t, y[:, -1], atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(s, last, atol=2e-5, rtol=1e-5)


def test_no_python_loop_over_the_chunks_in_the_trace():
    """64 chunks trace to as many equations as 4 do."""
    def eqns(T):
        a = _inputs(T, batch=1)
        return len(jax.make_jaxpr(lambda **kw: ssd(**kw, chunk=8))(
            **a).jaxpr.eqns)

    assert eqns(512) == eqns(32)
