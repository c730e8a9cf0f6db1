"""``ops/gdn.py``: the gated delta rule in chunks against the rule written
step by step (``lax.scan`` over T of ``S~ = exp(g_t) S``, ``S = S~ + beta_t
k_t (v_t - S~^T k_t)^T``, ``o_t = S^T q_t``) — outputs, the last state and
the gradients of all five arguments, at T a multiple of the chunk and not,
from a carried state, and under right padding; ``beta = 0`` leaves the state
decayed only; one decode step is one step of the rule; and the record a
policy keeps of what its rules ran as. The plain form first (what every
backend but a TPU runs), then the Pallas kernels of ``ops/gdn_pallas.py`` in
the interpreter at small shapes that tile, against the rule and against the
plain form, and the rule that picks between the two."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _util import kernel_calls

from relayrl_tpu.ops import gdn as rule
from relayrl_tpu.ops.gdn import gdn, gdn_step

HK, H, K, V = 2, 4, 16, 8
ARGS = ("q", "k", "v", "g", "beta")


def _inputs(T, seed=0, batch=2, HK=HK, H=H, K=K, V=V):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)

    def unit(a):
        return a / jnp.linalg.norm(a, axis=-1, keepdims=True)

    return {"q": unit(f(batch, T, HK, K)) / np.sqrt(K),
            "k": unit(f(batch, T, HK, K)), "v": f(batch, T, H, V),
            # log decays from a state that spans many chunks to one that
            # forgets within a few tokens
            "g": -jnp.asarray(rng.uniform(0.001, 3.0, (batch, T, H)),
                              jnp.float32),
            "beta": jnp.asarray(rng.uniform(0.0, 1.0, (batch, T, H)),
                                jnp.float32)}


def step_by_step(q, k, v, g, beta, state=None):
    """The rule as it is written, one token at a time."""
    b, _, H, V = v.shape
    rep = H // k.shape[2]
    qh, kh = (jnp.repeat(a, rep, axis=2) for a in (q, k))     # [b, T, H, K]
    if state is None:
        state = jnp.zeros((b, H, k.shape[3], V), jnp.float32)

    def one(s, row):
        q_t, k_t, v_t, g_t, beta_t = row
        s = jnp.exp(g_t)[..., None, None] * s
        v_new = beta_t[..., None] * (
            v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t))
        s = s + k_t[..., :, None] * v_new[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    last, o = jax.lax.scan(one, state, tuple(
        jnp.moveaxis(a, 1, 0) for a in (qh, kh, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), last


def _state(seed=7, batch=2):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(
        (batch, H, K, V)), jnp.float32)


@pytest.mark.parametrize("T,chunk", [(32, 8), (24, 8), (29, 8), (5, 8),
                                     (16, 16), (128, 64), (70, 64)])
def test_chunked_is_the_rule(T, chunk):
    a = _inputs(T)
    o, last = gdn(**a, chunk=chunk)
    o_ref, last_ref = step_by_step(**a)
    np.testing.assert_allclose(o, o_ref, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(last, last_ref, atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("heads", [(1, 1), (3, 3), (2, 6), (16, 32)])
def test_any_grouping_of_value_heads_over_key_heads(heads):
    """One value head a key head, three a key head, and more heads than a
    step of the map over heads takes."""
    hk, h = heads
    a = _inputs(24, seed=3, batch=1, HK=hk, H=h, K=8, V=8)
    o, last = gdn(**a, chunk=8)
    o_ref, last_ref = step_by_step(**a)
    np.testing.assert_allclose(o, o_ref, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(last, last_ref, atol=2e-5, rtol=1e-5)


def test_a_carried_state_continues_the_sequence():
    a = _inputs(48)
    whole, last = gdn(**a, chunk=8)
    # the first 19 rows (no multiple of the chunk), then the rest from there
    head, state = gdn(**{n: x[:, :19] for n, x in a.items()}, chunk=8)
    tail, last2 = gdn(**{n: x[:, 19:] for n, x in a.items()}, chunk=8,
                      state=state)
    np.testing.assert_allclose(jnp.concatenate([head, tail], 1), whole,
                               atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(last2, last, atol=2e-5, rtol=1e-5)
    o, last3 = gdn(**a, chunk=8, state=_state())
    o_ref, last_ref = step_by_step(**a, state=_state())
    np.testing.assert_allclose(o, o_ref, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(last3, last_ref, atol=2e-5, rtol=1e-5)


def test_a_state_that_is_not_carried_is_told_apart():
    a = _inputs(32)
    whole, _ = gdn(**a, chunk=8)
    tail, _ = gdn(**{n: x[:, 16:] for n, x in a.items()}, chunk=8)
    assert float(jnp.abs(whole[:, 16:] - tail).max()) > 1e-2


@pytest.mark.parametrize("T,chunk", [(32, 8), (21, 8)])
@pytest.mark.parametrize("wrt", ARGS + ("state",))
def test_gradients_are_the_rules(T, chunk, wrt):
    a = {**_inputs(T, seed=1), "state": _state()}
    w_o = jnp.asarray(np.random.default_rng(5).standard_normal(
        (2, T, H, V)), jnp.float32)
    w_s = jnp.asarray(np.random.default_rng(6).standard_normal(
        (2, H, K, V)), jnp.float32)

    def loss(fn, x):
        o, last = fn(**{**a, wrt: x})
        return jnp.sum(o * w_o) + jnp.sum(last * w_s)

    got = jax.grad(lambda x: loss(
        lambda **kw: gdn(**kw, chunk=chunk), x))(a[wrt])
    want = jax.grad(lambda x: loss(step_by_step, x))(a[wrt])
    scale = float(jnp.abs(want).max())
    assert scale > 1e-3
    np.testing.assert_allclose(got, want, atol=2e-5 * max(1.0, scale),
                               rtol=2e-4)


def test_right_padding_is_inert():
    """A real row never sees a later one: an episode padded on the right
    with anything gives the same real rows, values and gradients."""
    a, junk = _inputs(19), _inputs(32, seed=9)
    padded = {n: jnp.concatenate([a[n], junk[n][:, 19:]], 1) for n in a}
    o, _ = gdn(**a, chunk=8)
    o_pad, _ = gdn(**padded, chunk=8)
    np.testing.assert_allclose(o_pad[:, :19], o, atol=1e-6, rtol=1e-6)

    def real_rows(v):
        return jnp.sum(gdn(**{**padded, "v": v}, chunk=8)[0][:, :19] ** 2)

    assert float(jnp.abs(jax.grad(real_rows)(padded["v"])[:, 19:]).max()) == 0


def test_the_calls_own_padding_leaves_the_state_as_it_is():
    a = _inputs(21)
    _, last = gdn(**a, chunk=8)            # padded to 24 inside the call
    _, last_ref = step_by_step(**a)
    np.testing.assert_allclose(last, last_ref, atol=2e-5, rtol=1e-5)


def test_beta_zero_leaves_the_state_decayed_only():
    a = _inputs(24)
    a["beta"] = jnp.zeros_like(a["beta"])
    state = _state()
    o, last = gdn(**a, chunk=8, state=state)
    through = jnp.exp(jnp.sum(a["g"], axis=1))                   # [b, H]
    np.testing.assert_allclose(last, through[..., None, None] * state,
                               atol=1e-6, rtol=1e-5)
    # and every row reads the decayed state alone
    decayed = jnp.exp(jnp.cumsum(a["g"], axis=1))[..., None, None] * state[
        :, None]
    q = jnp.repeat(a["q"], H // HK, axis=2)
    np.testing.assert_allclose(
        o, jnp.einsum("bthkv,bthk->bthv", decayed, q), atol=2e-5, rtol=1e-5)


def test_decays_that_underflow_stay_finite():
    a = _inputs(32)
    a["g"] = jnp.full_like(a["g"], -60.0)     # exp(-60 * 8) is 0 in float32
    o, last = gdn(**a, chunk=8)
    grads = jax.grad(lambda g: jnp.sum(gdn(**{**a, "g": g}, chunk=8)[0]))(
        a["g"])
    assert bool(jnp.isfinite(o).all() and jnp.isfinite(last).all()
                and jnp.isfinite(grads).all())
    o_ref, _ = step_by_step(**a)
    np.testing.assert_allclose(o, o_ref, atol=2e-5, rtol=1e-5)


def test_bfloat16_operands_accumulate_in_float32():
    a = _inputs(64)
    low = {n: (x.astype(jnp.bfloat16) if n in ("q", "k", "v") else x)
           for n, x in a.items()}
    o, last = gdn(**low, chunk=16)
    assert o.dtype == jnp.bfloat16 and last.dtype == jnp.float32
    o_ref, last_ref = step_by_step(**{
        n: x.astype(jnp.float32) for n, x in low.items()})
    assert float(jnp.abs(o.astype(jnp.float32) - o_ref).max()) < 0.05
    assert float(jnp.abs(last - last_ref).max()) < 0.05


def test_one_step_is_the_rule_at_one_token():
    a, state = _inputs(1), _state()
    o, last = gdn(**a, chunk=8, state=state)
    o1, last1 = gdn_step(*(a[n][:, 0] for n in ARGS), state)
    np.testing.assert_allclose(o[:, 0], o1, atol=1e-6, rtol=1e-5)
    np.testing.assert_allclose(last, last1, atol=1e-6, rtol=1e-5)


def test_no_python_loop_over_the_chunks_in_the_trace():
    """64 chunks trace to as many equations as 4 do."""
    def eqns(T):
        a = _inputs(T, batch=1)
        return len(jax.make_jaxpr(lambda **kw: gdn(**kw, chunk=8))(
            **a).jaxpr.eqns)

    assert eqns(512) == eqns(32)


def test_the_solve_is_the_inverse():
    rng = np.random.default_rng(0)
    a = jnp.tril(jnp.asarray(rng.standard_normal((3, 64, 64)) * 0.3,
                             jnp.float32), -1)
    inv = rule._inverse_unit_lower(a)
    np.testing.assert_allclose(
        inv @ (jnp.eye(64) - a), jnp.broadcast_to(jnp.eye(64), a.shape),
        atol=2e-4)


def test_the_policy_records_what_its_rules_ran_as(capsys):
    from relayrl_tpu.models import build_policy

    policy = build_policy({
        "kind": "transformer_discrete", "obs_dim": 6, "act_dim": 3,
        "d_model": 16, "n_heads": 2, "max_seq_len": 8, "n_layers": 2,
        "layer_types": ["linear_attention", "linear_attention"],
        "gdn_key_heads": 2, "gdn_value_heads": 4, "gdn_key_dim": 8,
        "gdn_value_dim": 8, "gdn_chunk": 4, "norm": "rms",
        "positions": "none"})
    assert policy.gdn_backends == {} and policy.scan_backends == {}
    params = jax.eval_shape(policy.init_params, jax.random.PRNGKey(0))
    capsys.readouterr()
    # traced, not run: the record is made where the rule is traced
    jax.eval_shape(policy.evaluate, params, jnp.zeros((2, 8, 6)),
                   jnp.zeros((2, 8), jnp.int32), jnp.ones((2, 8, 3), bool))
    assert policy.gdn_backends[(8, 4, 8, 8, "float32")] == rule.XLA
    said = [line for line in capsys.readouterr().out.splitlines()
            if line.startswith("[gdn]")]
    assert len(said) == 1 and "T=8 " in said[0]       # one line a shape
    assert said[0].endswith("-> gdn_xla (platform cpu)")


# -- the Pallas kernels, in the interpreter ---------------------------------

# (key heads, value heads): eight value heads a grid step over 4, 8 and 2
# key heads (2, 1 and 4 value heads a key head), and two steps of heads
GROUPINGS = [(4, 8), (8, 8), (2, 8), (8, 16)]
WIDTH, CHUNK = 128, 64
WRT = ARGS + ("state",)


# jitted: an eager call traces and compiles the interpreted kernels op by op
@jax.jit
def _kernels(**kw):
    from relayrl_tpu.ops.gdn_pallas import gdn_pallas

    return gdn_pallas(**kw, chunk=CHUNK, interpret=True)


@jax.jit
def _plain(**kw):
    return rule.gdn_xla(**kw, chunk=CHUNK)


def _tiled(T, grouping=GROUPINGS[0], seed=0, batch=1):
    hk, h = grouping
    a = _inputs(T, seed, batch, HK=hk, H=h, K=WIDTH, V=WIDTH)
    a["state"] = jnp.asarray(np.random.default_rng(seed + 7).standard_normal(
        (batch, h, WIDTH, WIDTH)), jnp.float32)
    return a


@pytest.mark.parametrize("T", [128, 150])
def test_kernels_are_the_rule(T):
    """From a carried state, at whole chunks and padded on the right; in
    float32 the forward is the plain form's to the last bits."""
    a = _tiled(T, batch=2)
    o, last = _kernels(**a)
    o_ref, last_ref = step_by_step(**a)
    np.testing.assert_allclose(o, o_ref, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(last, last_ref, atol=1e-4, rtol=1e-4)
    o_plain, last_plain = _plain(**a)
    np.testing.assert_allclose(o, o_plain, atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(last, last_plain, atol=1e-6, rtol=1e-6)


@pytest.fixture(scope="module")
def gradients():
    """grouping -> form -> ``((o, last state), the gradients of a loss that
    reads both with respect to all six arguments)``; T 150 (a padded third
    chunk), made once a grouping and form."""
    made: dict = {}

    def of(grouping, form):
        if (grouping, form) not in made:
            a = _tiled(150, grouping, seed=1)
            rng = np.random.default_rng(2)
            wo = jnp.asarray(rng.standard_normal(a["v"].shape), jnp.float32)
            ws = jnp.asarray(rng.standard_normal(a["state"].shape),
                             jnp.float32)
            fn = {"kernels": _kernels, "plain": _plain,
                  "rule": step_by_step}[form]

            def loss(a):
                o, last = fn(**a)
                return jnp.sum(wo * o) + jnp.sum(ws * last), (o, last)

            grads, out = jax.jit(jax.grad(loss, has_aux=True))(a)
            made[grouping, form] = out, grads
        return made[grouping, form]

    return of


@pytest.mark.parametrize("against", ["rule", "plain"])
@pytest.mark.parametrize("wrt", WRT)
def test_kernel_gradients(gradients, wrt, against):
    """``gdn_states`` + ``gdn_bwd``: no term of any gradient left out."""
    got = gradients(GROUPINGS[0], "kernels")[1][wrt]
    want = gradients(GROUPINGS[0], against)[1][wrt]
    scale = float(jnp.abs(want).max())
    assert scale > 0
    np.testing.assert_allclose(got, want, atol=1e-4 * max(1.0, scale),
                               rtol=1e-3)


@pytest.mark.parametrize("grouping", GROUPINGS[1:])
def test_kernels_at_other_groupings(gradients, grouping):
    """One, four and (over two grid steps) two value heads a key head:
    outputs and every gradient are the plain form's."""
    (out, got), (out_plain, want) = (gradients(grouping, form)
                                     for form in ("kernels", "plain"))
    for mine, plain in zip(out, out_plain):
        np.testing.assert_allclose(mine, plain, atol=1e-4, rtol=1e-4)
    for wrt in WRT:
        scale = max(1.0, float(jnp.abs(want[wrt]).max()))
        np.testing.assert_allclose(got[wrt], want[wrt], atol=1e-4 * scale,
                                   rtol=1e-3, err_msg=wrt)


def test_kernels_at_a_chunk_of_two_lane_halves():
    """Chunks of 128, the other size ``fits`` takes: outputs and every
    gradient are the plain form's at that chunk."""
    from relayrl_tpu.ops.gdn_pallas import gdn_pallas

    a = _tiled(200, seed=4)

    def both(fn):
        def loss(a):
            o, last = fn(**a, chunk=128)
            return jnp.sum(o) + jnp.sum(last ** 2), (o, last)
        return jax.jit(jax.grad(loss, has_aux=True))(a)

    got, out = both(lambda **kw: gdn_pallas(**kw, interpret=True))
    want, out_plain = both(rule.gdn_xla)
    for mine, plain in zip(out, out_plain):
        np.testing.assert_allclose(mine, plain, atol=1e-4, rtol=1e-4)
    for wrt in WRT:
        scale = max(1.0, float(jnp.abs(want[wrt]).max()))
        np.testing.assert_allclose(got[wrt], want[wrt], atol=1e-4 * scale,
                                   rtol=1e-3, err_msg=wrt)


def test_kernels_carry_a_state_in_and_out():
    """Two calls, the second from the first's last state, are one call."""
    a = _tiled(192)
    a.pop("state")
    whole, last = _kernels(**a)
    cut = 83                              # inside a chunk
    head, state = _kernels(**{n: x[:, :cut] for n, x in a.items()})
    tail, last2 = _kernels(**{n: x[:, cut:] for n, x in a.items()},
                           state=state)
    np.testing.assert_allclose(jnp.concatenate([head, tail], 1), whole,
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(last2, last, atol=1e-4, rtol=1e-4)


def test_kernels_right_padding_and_the_calls_own_are_inert():
    a = _tiled(128)
    n = 83
    real = {name: x[:, :n] if name != "state" else x
            for name, x in a.items()}
    o_real, last_real = _kernels(**real)     # padded to 128 inside the call
    np.testing.assert_allclose(_kernels(**a)[0][:, :n], o_real, atol=1e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(last_real, step_by_step(**real)[1],
                               atol=1e-4, rtol=1e-4)
    padded = dict(a, g=a["g"].at[:, n:].set(0.0),
                  beta=a["beta"].at[:, n:].set(0.0))
    np.testing.assert_allclose(_kernels(**padded)[1], last_real, atol=1e-5,
                               rtol=1e-5)


def test_kernels_decays_that_underflow_stay_finite():
    a = _tiled(128)
    a["g"] = jnp.full_like(a["g"], -60.0)     # exp(-60 * 64) is 0 in float32
    o, last = _kernels(**a)
    grads = jax.jit(jax.grad(lambda a: jnp.sum(_kernels(**a)[0])))(a)
    assert all(bool(jnp.isfinite(x).all())
               for x in (o, last, *grads.values()))
    np.testing.assert_allclose(o, step_by_step(**a)[0], atol=1e-4, rtol=1e-4)


def test_kernels_bfloat16_operands_accumulate_in_float32():
    """The kernels round where the plain form rounds: in bfloat16 the two
    agree to the last place of the largest entry, forward and backward."""
    a = _tiled(128)
    lo = {n: x.astype(jnp.bfloat16) if n in ("q", "k", "v") else x
          for n, x in a.items()}
    o, last = _kernels(**lo)
    assert o.dtype == jnp.bfloat16 and last.dtype == jnp.float32
    o_ref, last_ref = _plain(**lo)
    f32 = lambda x: x.astype(jnp.float32)
    scale = float(jnp.abs(f32(o_ref)).max())
    assert float(jnp.abs(f32(o) - f32(o_ref)).max()) <= scale * 2.0 ** -7
    np.testing.assert_allclose(last, last_ref, atol=1e-3, rtol=1e-3)
    got, want = (jax.jit(jax.grad(lambda a: jnp.sum(f32(fn(**a)[0]))))(lo)
                 for fn in (_kernels, _plain))
    for wrt in WRT:
        assert got[wrt].dtype == want[wrt].dtype
        scale = float(jnp.abs(f32(want[wrt])).max())
        assert float(jnp.abs(f32(got[wrt]) - f32(want[wrt])).max()) <= (
            scale * 2.0 ** -6), wrt


def test_a_rule_nobody_differentiates_writes_no_states():
    """The prefill's call is ``gdn_fwd`` alone with its two results; a
    differentiated one also writes the solve's tiles, and the chunk-start
    states are made in the backward only."""
    a = _tiled(128)
    assert kernel_calls(jax.make_jaxpr(_kernels)(**a).jaxpr) == [
        ("gdn_fwd", 2)]
    grad = jax.make_jaxpr(jax.grad(lambda a: jnp.sum(_kernels(**a)[0])))(a)
    assert sorted(kernel_calls(grad.jaxpr)) == [
        ("gdn_bwd", 6), ("gdn_fwd", 3), ("gdn_states", 1)]


def test_under_the_mixers_checkpoint_the_forward_runs_once():
    """The layer's policy (``models/layers/gdn.py``) keeps the rule's output and the solve's
    tiles by name: the backward is ``gdn_states`` + ``gdn_bwd`` and never
    ``gdn_fwd`` a second time; without the solve's name it would be."""
    from relayrl_tpu.models.layers.gdn import _GDN_OUT, _GDN_SOLVE
    from jax.ad_checkpoint import checkpoint_name

    a = _tiled(128)

    def calls(*names):
        def mixer(a):
            o, _ = _kernels(**a)
            return jnp.sum(checkpoint_name(o, _GDN_OUT) ** 2)

        kept = jax.checkpoint(
            mixer, policy=jax.checkpoint_policies.save_only_these_names(
                *names))
        found = kernel_calls(jax.make_jaxpr(jax.grad(kept))(a).jaxpr)
        return sorted(name for name, _ in found)

    assert calls(_GDN_OUT, _GDN_SOLVE) == ["gdn_bwd", "gdn_fwd",
                                           "gdn_states"]
    assert calls(_GDN_OUT).count("gdn_fwd") == 2


@pytest.mark.parametrize("shape,fits", [
    ((32, 16, 128, 128, 64), True),     # qwen3next-policy
    ((8, 8, 128, 256, 128), True),
    ((8, 2, 128, 128, 64), True),
    ((4, 2, 16, 8, 8), False),          # this file's small shapes
    ((32, 16, 64, 128, 64), False),     # keys of half a lane tile
    ((32, 16, 128, 64, 64), False),     # values of half a lane tile
    ((12, 6, 128, 128, 64), False),     # no eight value heads a step
    ((16, 1, 128, 128, 64), False),     # a key head wider than a step
    ((32, 16, 128, 128, 32), False),    # a chunk that does not tile
])
def test_the_rule_that_picks_the_kernels(monkeypatch, shape, fits):
    """Platform and shape: off a TPU every shape takes the plain form; on
    one (this process made to say so) the shapes that tile take the kernels
    from one whole chunk of rows on (``init``'s single row and a prompt
    shorter than a chunk stay plain)."""
    from relayrl_tpu.ops import gdn_pallas

    chunk = shape[-1]
    assert gdn_pallas.fits(*shape) == fits
    assert rule.backend(8192, *shape) == rule.XLA
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for T in (8192, chunk + 1, chunk):
        assert rule.backend(T, *shape) == (rule.PALLAS if fits else rule.XLA)
    for T in (chunk - 1, 1):
        assert rule.backend(T, *shape) == rule.XLA


def test_the_policy_records_the_kernels_where_they_run(monkeypatch, capsys):
    """On a TPU (this process made to say so while the policy is traced,
    nothing lowered) a shape that tiles is recorded as ``gdn_pallas``."""
    from relayrl_tpu.models import build_policy

    policy = build_policy({
        "kind": "transformer_discrete", "obs_dim": 6, "act_dim": 3,
        "d_model": 16, "n_heads": 2, "max_seq_len": 64, "n_layers": 1,
        "layer_types": ["linear_attention"], "gdn_key_heads": 4,
        "gdn_value_heads": 8, "gdn_key_dim": 128, "gdn_value_dim": 128,
        "gdn_chunk": 64, "norm": "rms", "positions": "none"})
    params = jax.eval_shape(policy.init_params, jax.random.PRNGKey(0))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    capsys.readouterr()
    jax.eval_shape(policy.evaluate, params, jnp.zeros((2, 64, 6)),
                   jnp.zeros((2, 64), jnp.int32), jnp.ones((2, 64, 3), bool))
    assert policy.gdn_backends == {(64, 8, 128, 128, "float32"): rule.PALLAS}
    said = [line for line in capsys.readouterr().out.splitlines()
            if line.startswith("[gdn]")]
    assert len(said) == 1 and said[0].endswith(
        "-> gdn_pallas (platform tpu)")
