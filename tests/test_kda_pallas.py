"""``ops/kda_pallas.py``: the delta rule under a decay a key lane as Pallas
kernels (``kda_fwd``, ``kda_states``, ``kda_bwd``), run in the Pallas
interpreter on the CPU at small shapes that tile — heads of 128 x 128, chunks
of 64 — against the plain form (``ops/kda.kda_xla``) and against the rule one
token at a time. A file of its own: ``--dist loadfile`` gives it a worker.
What the chip's compiler makes of the kernels is
``tests/test_flash_tpu_compile.py``'s; what the chip computes,
``chip_smoke.py`` phase J's.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _util import kernel_calls

from relayrl_tpu.ops import gdn
from relayrl_tpu.ops import kda as rule

WIDTH, CHUNK, HEADS = 128, 64, 4
ARGS = ("q", "k", "v", "g", "beta")
WRT = ARGS + ("state",)


def _inputs(T, seed=0, heads=HEADS, g_low=-2.0, batch=1):
    """The rule's operands as the mixer leaves them (unit keys, queries
    scaled by ``K ** -0.5``), log decays uniform over ``[g_low, 0]`` a lane,
    and a state to start from."""
    rng = np.random.default_rng(seed)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    shape = (batch, T, heads, WIDTH)
    a = {"q": unit(rng.normal(size=shape)) * WIDTH ** -0.5,
         "k": unit(rng.normal(size=shape)), "v": rng.normal(size=shape),
         "g": rng.uniform(g_low, 0.0, size=shape),
         "beta": rng.uniform(0, 1, size=shape[:3]),
         "state": rng.normal(size=(batch, heads, WIDTH, WIDTH))}
    return {name: jnp.asarray(x, jnp.float32) for name, x in a.items()}


# jitted: an eager call traces and compiles the interpreted kernels op by op
@jax.jit
def _kernels(**kw):
    from relayrl_tpu.ops.kda_pallas import kda_pallas

    return kda_pallas(**kw, chunk=CHUNK, interpret=True)


@jax.jit
def _plain(**kw):
    return rule.kda_xla(**kw, chunk=CHUNK)


@jax.jit
def step_by_step(q, k, v, g, beta, state):
    """The state equation one token at a time."""
    def one(s, row):
        o, s = rule.kda_step(*row, s)
        return s, o

    last, o = jax.lax.scan(one, state, tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), last


@pytest.mark.parametrize("T", [128, 150])
def test_kernels_are_the_rule(T):
    """From a carried state, at whole chunks and padded on the right (the
    call's own padding); in float32 the forward is the plain form's to the
    last bits."""
    a = _inputs(T)
    o, last = _kernels(**a)
    o_ref, last_ref = step_by_step(**a)
    np.testing.assert_allclose(o, o_ref, atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(last, last_ref, atol=1e-5, rtol=1e-4)
    o_plain, last_plain = _plain(**a)
    np.testing.assert_allclose(o, o_plain, atol=1e-6, rtol=1e-5)
    np.testing.assert_allclose(last, last_plain, atol=1e-6, rtol=1e-5)


def _gradients(fn, a, seed=2, rows=None):
    """``((o, last state), the gradients with respect to all six arguments
    of a loss that reads ``o`` and the last state``) — or, with ``rows``,
    those rows of ``o`` and nothing else."""
    rng = np.random.default_rng(seed)
    wo = jnp.asarray(rng.standard_normal(a["v"].shape), jnp.float32)
    ws = jnp.asarray(rng.standard_normal(a["state"].shape), jnp.float32)

    def loss(a):
        o, last = fn(**a)
        read = wo * o.astype(jnp.float32)
        if rows is not None:
            return jnp.sum(read[:, rows]), (o, last)
        return jnp.sum(read) + jnp.sum(ws * last), (o, last)

    grads, out = jax.jit(jax.grad(loss, has_aux=True))(a)
    return out, grads


@pytest.fixture(scope="module")
def gradients():
    """form -> ``_gradients`` at T 150 (a padded third chunk), made once a
    form."""
    made: dict = {}
    forms = {"kernels": _kernels, "plain": _plain, "rule": step_by_step}

    def of(form):
        if form not in made:
            made[form] = _gradients(forms[form], _inputs(150, seed=1))
        return made[form]

    return of


@pytest.mark.parametrize("against", ["rule", "plain"])
@pytest.mark.parametrize("wrt", WRT)
def test_kernel_gradients(gradients, wrt, against):
    """``kda_states`` + ``kda_bwd``: all six cotangents, no term left out —
    ``g``'s a LANE's own."""
    got, want = gradients("kernels")[1][wrt], gradients(against)[1][wrt]
    assert got.shape == want.shape and got.dtype == want.dtype
    scale = float(jnp.abs(want).max())
    assert scale > 0
    np.testing.assert_allclose(got, want, atol=1e-4 * max(1.0, scale),
                               rtol=1e-3)


@pytest.fixture(scope="module")
def near_one():
    """Five chunks over two grid steps of heads at decays near 1 (``g`` in
    [-0.05, 0], and corrected gently: ``beta`` under 0.25): the state
    carried in reaches the last chunk's rows. A loss that reads the LAST
    chunk's output alone, so that the cotangents of the earlier rows and of
    the state carried in are what travelled back through the chunks in
    between, in the state's cotangent."""
    a = _inputs(5 * CHUNK, seed=3, heads=2 * HEADS, g_low=-0.05)
    a["beta"] = 0.25 * a["beta"]
    last_chunk = slice(4 * CHUNK, None)
    return a, last_chunk, {
        form: _gradients(fn, a, rows=last_chunk)
        for form, fn in (("kernels", _kernels), ("rule", step_by_step))}


def test_a_state_is_carried_through_the_chunks(near_one):
    """The kernels' ``o`` four chunks on is the rule's to a hundredth of
    what a dropped state, or the heads' states in another order, would
    change there."""
    a, last_chunk, made = near_one
    (o, last), (o_ref, last_ref) = made["kernels"][0], made["rule"][0]
    error = float(jnp.abs(o - o_ref)[:, last_chunk].max())
    dropped, _ = step_by_step(**dict(a, state=jnp.zeros_like(a["state"])))
    swapped, _ = step_by_step(**dict(a, state=a["state"][:, ::-1]))
    for wrong in (dropped, swapped):
        changed = float(jnp.abs(wrong - o_ref)[:, last_chunk].max())
        assert changed > 1e-4 and error < 1e-2 * changed
    np.testing.assert_allclose(o, o_ref, atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(last, last_ref, atol=1e-5, rtol=1e-4)


@pytest.mark.parametrize("wrt", WRT)
def test_a_states_cotangent_is_carried_back_through_the_chunks(near_one,
                                                               wrt):
    """The first chunk's rows and the state carried in are reached through
    the state's cotangent alone, four chunks back (``q``'s not at all: a
    query reads, it does not write): the kernels' cotangents there are the
    rule's to a hundredth of their size."""
    _, _, made = near_one
    got, want = made["kernels"][1][wrt], made["rule"][1][wrt]
    first = slice(None) if wrt == "state" else (slice(None), slice(CHUNK))
    reached = float(jnp.abs(want[first]).max())
    assert (reached == 0) if wrt == "q" else (reached > 1e-4)
    assert float(jnp.abs(got[first] - want[first]).max()) <= 1e-2 * reached
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(got, want, atol=1e-4 * max(1.0, scale),
                               rtol=1e-3)


def test_kernels_carry_a_state_in_and_out():
    """Two calls, the second from the first's last state, are one call."""
    a = _inputs(128, g_low=-0.05)
    whole, last = _kernels(**a)
    cut = 83                              # inside a chunk
    parts = lambda rows: {n: x[:, rows] for n, x in a.items()
                          if n != "state"}
    head, state = _kernels(**parts(slice(cut)), state=a["state"])
    tail, last2 = _kernels(**parts(slice(cut, None)), state=state)
    np.testing.assert_allclose(jnp.concatenate([head, tail], 1), whole,
                               atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(last2, last, atol=1e-5, rtol=1e-4)


@pytest.fixture(scope="module")
def grad_of_o():
    return jax.jit(jax.grad(lambda a: jnp.sum(_kernels(**a)[0])))


@pytest.mark.parametrize("alpha", [1e-4, 1e-2])
def test_small_decays_over_a_whole_chunk_stay_finite_and_equal(alpha,
                                                               grad_of_o):
    """``alpha`` 1e-4 a lane over 64 rows: ``e^{-Gamma}`` would be
    ``e^{+589}``; every exponential the kernels take is of a non-positive
    number."""
    a = _inputs(128)
    g = jnp.full_like(a["g"], np.log(alpha))
    # a few lanes that do not decay at all beside those that vanish
    a["g"] = g.at[..., ::5].set(0.0)
    o, last = _kernels(**a)
    o_ref, last_ref = step_by_step(**a)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(last).all())
    np.testing.assert_allclose(o, o_ref, atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(last, last_ref, atol=1e-5, rtol=1e-4)
    assert all(bool(jnp.isfinite(x).all())
               for x in grad_of_o(a).values())


def test_with_every_lanes_decay_equal_the_kernels_are_gdn(grad_of_o):
    """One decay a head through ``ops/gdn.py``, the same number on every
    lane through the kernels: values, state and the decay's gradient (summed
    over the lanes)."""
    a = _inputs(128)
    a["g"] = jnp.broadcast_to(a["g"][..., :1], a["g"].shape)
    o, last = _kernels(**a)
    narrow = dict(a, g=a["g"][..., 0])
    o_gdn, last_gdn = jax.jit(
        lambda **kw: gdn.gdn_xla(**kw, chunk=CHUNK))(**narrow)
    np.testing.assert_allclose(o, o_gdn, atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(last, last_gdn, atol=1e-5, rtol=1e-4)
    want = jax.jit(jax.grad(lambda a: jnp.sum(
        gdn.gdn_xla(**a, chunk=CHUNK)[0])))(narrow)
    got = grad_of_o(a)
    scale = float(jnp.abs(want["g"]).max())
    np.testing.assert_allclose(got["g"].sum(-1), want["g"],
                               atol=1e-4 * max(1.0, scale), rtol=1e-3)
    np.testing.assert_allclose(got["beta"], want["beta"], atol=1e-4,
                               rtol=1e-3)


def test_kernels_bfloat16_operands_accumulate_in_float32():
    """The kernels round where the plain form rounds: in bfloat16 the two
    agree to the last place of the largest entry, forward and backward, and
    both stay near the float32 rule."""
    a = _inputs(128)
    lo = {n: x.astype(jnp.bfloat16) if n in ("q", "k", "v") else x
          for n, x in a.items()}
    f32 = lambda x: x.astype(jnp.float32)
    (o, last), got = _gradients(_kernels, lo)
    (o_ref, last_ref), want = _gradients(_plain, lo)
    assert o.dtype == jnp.bfloat16 and last.dtype == jnp.float32
    scale = float(jnp.abs(f32(o_ref)).max())
    assert float(jnp.abs(f32(o) - f32(o_ref)).max()) <= scale * 2.0 ** -7
    np.testing.assert_allclose(last, last_ref, atol=2e-3, rtol=2e-3)
    exact, _ = step_by_step(**{n: f32(x) for n, x in lo.items()})
    assert float(jnp.abs(f32(o) - exact).max()) <= scale * 2.0 ** -5
    for wrt in WRT:
        assert got[wrt].dtype == want[wrt].dtype
        scale = float(jnp.abs(f32(want[wrt])).max())
        assert float(jnp.abs(f32(got[wrt]) - f32(want[wrt])).max()) <= (
            scale * 2.0 ** -6), wrt


def test_a_rule_nobody_differentiates_writes_no_states():
    """The prefill's call is ``kda_fwd`` alone with its two results; a
    differentiated one also writes the solve's tiles, and the chunk-start
    states are made in the backward only."""
    a = _inputs(128)
    assert kernel_calls(jax.make_jaxpr(_kernels)(**a).jaxpr) == [
        ("kda_fwd", 2)]
    grad = jax.make_jaxpr(jax.grad(lambda a: jnp.sum(_kernels(**a)[0])))(a)
    assert sorted(kernel_calls(grad.jaxpr)) == [
        ("kda_bwd", 6), ("kda_fwd", 3), ("kda_states", 1)]


def test_under_the_mixers_checkpoint_the_forward_runs_once():
    """The layer's policy (``models/layers/kda.py``) keeps the rule's output
    and the solve's tiles by name: the backward is ``kda_states`` +
    ``kda_bwd`` and never ``kda_fwd`` a second time; without the solve's
    name it would be."""
    from jax.ad_checkpoint import checkpoint_name

    from relayrl_tpu.models.layers.kda import _KDA_OUT, _KDA_SOLVE, KEPT

    a = _inputs(128)

    def calls(*names):
        def mixer(a):
            o, _ = _kernels(**a)
            return jnp.sum(checkpoint_name(o, _KDA_OUT) ** 2)

        kept = jax.checkpoint(
            mixer, policy=jax.checkpoint_policies.save_only_these_names(
                *names))
        found = kernel_calls(jax.make_jaxpr(jax.grad(kept))(a).jaxpr)
        return sorted(name for name, _ in found)

    assert KEPT == (_KDA_OUT, _KDA_SOLVE)
    assert calls(*KEPT) == ["kda_bwd", "kda_fwd", "kda_states"]
    assert calls(_KDA_OUT).count("kda_fwd") == 2


ARCH = {"kind": "transformer_discrete", "obs_dim": 6, "act_dim": 3,
        "d_model": 16, "n_heads": 2, "max_seq_len": 64, "n_layers": 2,
        "layer_types": ["kda", "kda"], "kda_heads": 4, "kda_head_dim": 128,
        "kda_chunk": 64, "norm": "rms", "positions": "none"}
BATCH = (jnp.zeros((2, 64, 6)), jnp.zeros((2, 64), jnp.int32),
         jnp.ones((2, 64, 3), bool))


@pytest.mark.parametrize("block_checkpoint", [False, True])
def test_a_policys_gradient_runs_the_forward_once_a_layer(monkeypatch,
                                                          block_checkpoint):
    """Under the mixer's own checkpoint and under a checkpoint round the
    whole layer (``block_checkpoint``, what ``kimi-linear-policy`` runs
    under) the gradient's jaxpr holds each kernel once a layer: ``kda_fwd``
    is not run a second time by either. Traced on a TPU (this process made
    to say so), nothing lowered."""
    from relayrl_tpu.models import build_policy

    policy = build_policy({**ARCH, "block_checkpoint": block_checkpoint})
    params = jax.eval_shape(policy.init_params, jax.random.PRNGKey(0))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def loss(params):
        return jnp.sum(policy.evaluate(params, *BATCH)[0])

    found = kernel_calls(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
    mine = sorted(name for name, _ in found if name.startswith("kda_"))
    assert mine == ["kda_bwd"] * 2 + ["kda_fwd"] * 2 + ["kda_states"] * 2


@pytest.mark.parametrize("shape,fits", [
    ((32, 128, 128, 64), True),         # kimi-linear-policy
    ((4, 128, 128, 64), True),          # this file's
    ((2, 16, 8, 64), False),            # tests/test_kda.py's small shapes
    ((32, 64, 64, 64), False),          # heads of half a lane tile
    ((32, 128, 256, 64), False),        # values of two lane tiles
    ((6, 128, 128, 64), False),         # no four heads a step
    ((32, 128, 128, 32), False),        # two sub-chunks a chunk
    ((32, 128, 128, 128), False),       # eight
])
def test_the_rule_that_picks_the_kernels(monkeypatch, shape, fits):
    """Platform and shape: off a TPU every shape takes the plain form; on
    one (this process made to say so) the shapes that tile take the kernels
    from one whole chunk of rows on (``init``'s single row and a prompt
    shorter than a chunk stay plain)."""
    from relayrl_tpu.ops import kda_pallas

    chunk = shape[-1]
    assert kda_pallas.fits(*shape) == fits
    assert rule.backend(16384, *shape) == rule.XLA
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for T in (16384, chunk + 1, chunk):
        assert rule.backend(T, *shape) == (rule.PALLAS if fits else rule.XLA)
    for T in (chunk - 1, 1):
        assert rule.backend(T, *shape) == rule.XLA


@pytest.mark.parametrize("platform,ran", [("tpu", rule.PALLAS),
                                          ("cpu", rule.XLA)])
def test_the_policy_records_what_the_rule_ran_as(monkeypatch, capsys,
                                                 platform, ran):
    """On a TPU (this process made to say so while the policy is traced,
    nothing lowered) a shape that tiles is recorded as ``kda_pallas``; off
    one as ``kda_xla``: one ``[kda]`` line a shape either way."""
    from relayrl_tpu.models import build_policy

    policy = build_policy(ARCH)
    params = jax.eval_shape(policy.init_params, jax.random.PRNGKey(0))
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    capsys.readouterr()
    jax.eval_shape(policy.evaluate, params, *BATCH)
    assert policy.kda_backends == {(64, 4, 128, 128, "float32"): ran}
    said = [line for line in capsys.readouterr().out.splitlines()
            if line.startswith("[kda]")]
    assert len(said) == 1 and said[0].endswith(
        f"-> {ran} (platform {platform})")
    assert "T=64 heads=4 key_dim=128 value_dim=128 chunk=64" in said[0]
