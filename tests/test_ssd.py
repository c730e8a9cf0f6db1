"""``ops/ssd.py``: the chunked Mamba-2 scan against the recurrence written
step by step (``lax.scan`` over T of ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
(x) B_t``, ``y_t = S_t C_t + D x_t``) — outputs, the last state and the
gradients of all six arguments, at T a multiple of the chunk and not, from
a carried state, and under right padding. The plain form first (what every
backend but a TPU runs), then the Pallas kernels of ``ops/ssd_pallas.py`` in
the interpreter at small shapes that tile, against the recurrence and
against the plain form, and the rule that picks between the two."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from relayrl_tpu.ops import ssd as scan
from relayrl_tpu.ops.ssd import ssd, ssd_step

H, P, G, N = 4, 8, 2, 16
ARGS = ("x", "dt", "A", "B", "C", "D")


def _inputs(T, seed=0, batch=2, H=H, P=P, G=G, N=N):
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
    b, _, H, P = x.shape
    G, N = B.shape[2:]
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

    got = jax.jit(jax.grad(lambda v: loss(
        lambda **kw: ssd(**kw, chunk=chunk, state=s0), v)))(a[wrt])
    want = jax.jit(jax.grad(lambda v: loss(
        lambda **kw: step_by_step(**kw, state=s0), v)))(a[wrt])
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


# -- the Pallas kernels, in the interpreter ---------------------------------

# (heads, head width, groups): eight heads a grid step; two heads of 64 a
# 128-lane block in one group, in two, and two steps inside ONE group (dB and
# dC summed outside); a head that is a lane block; four heads of 32 a block
TILINGS = [(8, 64, 1), (16, 64, 2), (16, 64, 1), (8, 128, 1), (8, 32, 1)]
STATE = 128
WRT = ARGS + ("state",)


# jitted: an eager call traces and compiles the interpreted kernels op by op,
# three times as long
@jax.jit
def _kernels(**kw):
    from relayrl_tpu.ops.ssd_pallas import ssd_pallas

    return ssd_pallas(**kw, chunk=128, interpret=True)


@jax.jit
def _plain(**kw):
    return scan.ssd_xla(**kw, chunk=128)


def _tiled(T, tiling=TILINGS[0], seed=0, batch=1):
    heads, width, groups = tiling
    a = _inputs(T, seed, batch, H=heads, P=width, G=groups, N=STATE)
    a["state"] = jnp.asarray(np.random.default_rng(seed + 7).standard_normal(
        (batch, heads, width, STATE)), jnp.float32)
    return a


@pytest.mark.parametrize("T", [256, 200])
def test_kernels_are_the_recurrence(T):
    """From a carried state, at whole chunks and padded on the right."""
    a = _tiled(T, batch=2)
    y, last = _kernels(**a)
    y_ref, last_ref = step_by_step(**a)
    np.testing.assert_allclose(y, y_ref, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(last, last_ref, atol=1e-4, rtol=1e-4)


@pytest.fixture(scope="module")
def gradients():
    """tiling -> form -> ``((y, last state), the gradients of a loss that
    reads both with respect to all seven arguments)``; T 200 (a padded
    second chunk), made once a tiling and form."""
    made: dict = {}

    def of(tiling, form):
        if (tiling, form) not in made:
            a = _tiled(200, tiling, seed=1)
            rng = np.random.default_rng(2)
            wy = jnp.asarray(rng.standard_normal(a["x"].shape), jnp.float32)
            ws = jnp.asarray(rng.standard_normal(a["state"].shape),
                             jnp.float32)
            fn = {"kernels": _kernels, "plain": _plain,
                  "recurrence": step_by_step}[form]

            def loss(a):
                y, last = fn(**a)
                return jnp.sum(wy * y) + jnp.sum(ws * last), (y, last)

            grads, out = jax.jit(jax.grad(loss, has_aux=True))(a)
            made[tiling, form] = out, grads
        return made[tiling, form]

    return of


@pytest.mark.parametrize("against", ["recurrence", "plain"])
@pytest.mark.parametrize("wrt", WRT)
def test_kernel_gradients(gradients, wrt, against):
    """``ssd_states`` + ``ssd_bwd``: no term of any gradient left out."""
    got = gradients(TILINGS[0], "kernels")[1][wrt]
    want = gradients(TILINGS[0], against)[1][wrt]
    scale = float(jnp.abs(want).max())
    assert scale > 0
    np.testing.assert_allclose(got, want, atol=1e-4 * max(1.0, scale),
                               rtol=1e-3)


@pytest.mark.parametrize("tiling", TILINGS[1:])
def test_kernels_at_other_tilings(gradients, tiling):
    """Heads over groups and lane blocks: outputs and every gradient are
    the plain form's."""
    (out, got), (out_plain, want) = (gradients(tiling, form)
                                     for form in ("kernels", "plain"))
    for mine, plain in zip(out, out_plain):
        np.testing.assert_allclose(mine, plain, atol=1e-4, rtol=1e-4)
    for wrt in WRT:
        scale = max(1.0, float(jnp.abs(want[wrt]).max()))
        np.testing.assert_allclose(got[wrt], want[wrt], atol=1e-4 * scale,
                                   rtol=1e-3, err_msg=wrt)


def test_kernels_carry_a_state_in_and_out():
    """Two calls, the second from the first's last state, are one call."""
    a = _tiled(384)
    a.pop("state")
    whole, last = _kernels(**a)
    cut = 150                             # inside a chunk
    head = {k: v[:, :cut] if v.ndim > 1 else v for k, v in a.items()}
    tail = {k: v[:, cut:] if v.ndim > 1 else v for k, v in a.items()}
    y0, s0 = _kernels(**head)
    y1, s1 = _kernels(**tail, state=s0)
    np.testing.assert_allclose(jnp.concatenate([y0, y1], 1), whole,
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(s1, last, atol=1e-4, rtol=1e-4)


def test_kernels_right_padding_is_inert():
    a = _tiled(256)
    n = 170
    real = {k: v[:, :n] if v.ndim > 1 and k != "state" else v
            for k, v in a.items()}
    y_real, last_real = _kernels(**real)
    np.testing.assert_allclose(_kernels(**a)[0][:, :n], y_real, atol=1e-5,
                               rtol=1e-5)
    padded = dict(a, dt=a["dt"].at[:, n:].set(0.0))
    np.testing.assert_allclose(_kernels(**padded)[1], last_real, atol=1e-5,
                               rtol=1e-5)


def test_kernels_decays_that_underflow_stay_finite():
    a = _tiled(256)
    a["A"] = jnp.full_like(a["A"], -500.0)
    a["dt"] = jnp.full_like(a["dt"], 0.1)
    y, last = _kernels(**a)
    g = jax.jit(jax.grad(lambda a: jnp.sum(_kernels(**a)[0])))(a)
    assert all(bool(jnp.isfinite(v).all()) for v in (y, last, *g.values()))
    np.testing.assert_allclose(y, step_by_step(**a)[0], atol=1e-4, rtol=1e-4)


def test_kernels_bfloat16_operands_accumulate_in_float32():
    """The kernels round where the plain form rounds: in bfloat16 the two
    agree to the last place of the largest entry, forward and backward."""
    a = _tiled(256)
    lo = {k: v.astype(jnp.bfloat16) if k in ("x", "B", "C") else v
          for k, v in a.items()}
    y, last = _kernels(**lo)
    assert y.dtype == jnp.bfloat16 and last.dtype == jnp.float32
    y_ref, last_ref = _plain(**lo)
    f32 = lambda v: v.astype(jnp.float32)
    scale = float(jnp.abs(f32(y_ref)).max())
    assert float(jnp.abs(f32(y) - f32(y_ref)).max()) <= scale * 2.0 ** -7
    np.testing.assert_allclose(last, last_ref, atol=1e-3, rtol=1e-3)
    got, want = (jax.jit(jax.grad(lambda a: jnp.sum(f32(fn(**a)[0]))))(lo)
                 for fn in (_kernels, _plain))
    for wrt in WRT:
        assert got[wrt].dtype == want[wrt].dtype
        scale = float(jnp.abs(f32(want[wrt])).max())
        assert float(jnp.abs(f32(got[wrt]) - f32(want[wrt])).max()) <= (
            scale * 2.0 ** -6), wrt


@pytest.mark.parametrize("shape,fits", [
    ((64, 64, 8, 128, 128), True),     # nemotron-twotower-policy
    ((8, 128, 1, 128, 256), True),
    ((4, 8, 2, 16, 8), False),         # this file's small shapes
    ((64, 64, 16, 128, 128), False),   # four heads a group: no float32 tile
    ((64, 64, 8, 64, 128), False),     # a state of half a lane tile
    ((64, 64, 8, 128, 64), False),     # a chunk of half a lane tile
    ((64, 48, 8, 128, 128), False),    # heads that do not fill lane blocks
])
def test_the_rule_that_picks_the_kernels(monkeypatch, shape, fits):
    """Platform and shape: off a TPU every shape takes the plain form; on
    one (this process made to say so) the shapes that tile take the
    kernels from one whole chunk of rows on (``init``'s single row and a
    prompt shorter than a chunk stay plain)."""
    from relayrl_tpu.ops import ssd_pallas

    chunk = shape[-1]
    assert ssd_pallas.fits(*shape) == fits
    assert scan.backend(8192, *shape) == scan.XLA
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for T in (8192, chunk + 1, chunk):
        assert scan.backend(T, *shape) == (scan.PALLAS if fits else scan.XLA)
    for T in (chunk - 1, 1):
        assert scan.backend(T, *shape) == scan.XLA


def test_the_policy_records_what_its_scans_ran_as(capsys):
    from relayrl_tpu.models import build_policy

    policy = build_policy({
        "kind": "transformer_discrete", "obs_dim": 6, "act_dim": 3,
        "d_model": 16, "n_heads": 2, "max_seq_len": 8, "n_layers": 2,
        "layer_types": ["mamba2", "mamba2"], "mamba_heads": 4,
        "mamba_head_dim": 8, "mamba_state": 8, "mamba_groups": 2,
        "mamba_chunk": 4, "norm": "rms", "positions": "none"})
    assert policy.scan_backends == {}
    params = jax.eval_shape(policy.init_params, jax.random.PRNGKey(0))
    capsys.readouterr()
    # traced, not run: the record is made where the scan is traced
    jax.eval_shape(policy.evaluate, params, jnp.zeros((2, 8, 6)),
                   jnp.zeros((2, 8), jnp.int32), jnp.ones((2, 8, 3), bool))
    assert policy.scan_backends[(8, 4, 8, 8, "float32")] == scan.XLA
    said = [line for line in capsys.readouterr().out.splitlines()
            if line.startswith("[scan]")]
    assert len(said) == 1 and "T=8 " in said[0]       # one line a shape
    assert said[0].endswith("-> ssd_xla (platform cpu)")
