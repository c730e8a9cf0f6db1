"""Direct unit suite for ``ops/vtrace.py`` (ISSUE 13 satellite).

V-trace is about to become the off-policy spine of the RLHF path (the
scheduler's decoupled generation runs tokens sampled N publishes behind
the learner), and until now it was covered only transitively through
the IMPALA e2e tests. This suite pins it directly:

* a GOLDEN-VALUE test against a hand-unrolled reference recursion
  (plain Python floats, written from the IMPALA paper's definition:
  ``vs_t = v_t + sum_k gamma^(k-t) (prod c) rho_k delta_k`` computed by
  the backward form ``a_t = delta_t + gamma c_t a_{t+1}``) — including
  the clipped-rho edge cases where the behavior policy was much more /
  much less confident than the target;
* the ON-POLICY IDENTITY: with behavior == target and
  ``rho_bar, c_bar >= 1`` the recursion telescopes to the n-step
  return, and ``pg_adv`` reduces to the 1-step TD advantage against
  those returns;
* masking/padding and bootstrap-injection behavior on the padded
  ``[B, T]`` batches every learner feeds it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from relayrl_tpu.ops.recurrence import reverse_linear_recurrence
from relayrl_tpu.ops.vtrace import vtrace

pytestmark = pytest.mark.rlhf


def reference_vtrace(behavior_logp, target_logp, rew, val, gamma,
                     last_val, rho_bar, c_bar):
    """Hand-unrolled single-trajectory V-trace in plain Python floats —
    the independent implementation the golden test compares against.
    Follows Espeholt et al. (2018) eq. 1 exactly, via the backward
    recursion a_t = delta_t + gamma c_t a_{t+1}, vs_t = v_t + a_t."""
    T = len(rew)
    rho = [min(rho_bar, float(np.exp(t - b)))
           for b, t in zip(behavior_logp, target_logp)]
    c = [min(c_bar, float(np.exp(t - b)))
         for b, t in zip(behavior_logp, target_logp)]
    v_next = [val[t + 1] if t + 1 < T else last_val for t in range(T)]
    delta = [rho[t] * (rew[t] + gamma * v_next[t] - val[t])
             for t in range(T)]
    a = [0.0] * (T + 1)
    for t in reversed(range(T)):
        a[t] = delta[t] + gamma * c[t] * a[t + 1]
    vs = [val[t] + a[t] for t in range(T)]
    vs_next = [vs[t + 1] if t + 1 < T else last_val for t in range(T)]
    pg_adv = [rho[t] * (rew[t] + gamma * vs_next[t] - val[t])
              for t in range(T)]
    return vs, pg_adv, rho


def run_vtrace(behavior_logp, target_logp, rew, val, gamma, last_val,
               rho_bar=1.0, c_bar=1.0, pad_to=None):
    """Single trajectory through the real op (as a [1, T] batch), with
    optional right-padding to exercise the mask path."""
    T = len(rew)
    width = pad_to or T

    def row(xs):
        out = np.zeros(width, np.float32)
        out[:T] = xs
        return jnp.asarray(out)[None]

    valid = np.zeros(width, np.float32)
    valid[:T] = 1.0
    res = vtrace(row(behavior_logp), row(target_logp), row(rew), row(val),
                 jnp.asarray(valid)[None], gamma,
                 last_val=jnp.asarray([np.float32(last_val)]),
                 rho_bar=rho_bar, c_bar=c_bar)
    return (np.asarray(res.vs)[0], np.asarray(res.pg_adv)[0],
            np.asarray(res.rho)[0])


class TestGoldenValues:
    # One fixed 4-step trajectory, moderately off-policy.
    B_LOGP = [-0.5, -1.2, -0.3, -2.0]
    T_LOGP = [-0.7, -0.4, -1.1, -0.9]
    REW = [1.0, 0.0, -0.5, 2.0]
    VAL = [0.3, -0.2, 0.8, 0.1]

    @pytest.mark.parametrize("rho_bar,c_bar", [
        (1.0, 1.0),     # standard clipping
        (0.5, 0.5),     # aggressive clipping — every ratio > 0.5 clips
        (10.0, 10.0),   # effectively unclipped (ratios here are < e^1.7)
        (1.0, 0.7),     # asymmetric rho/c bars
    ])
    def test_against_hand_recursion(self, rho_bar, c_bar):
        vs, pg, rho = run_vtrace(self.B_LOGP, self.T_LOGP, self.REW,
                                 self.VAL, 0.9, last_val=0.4,
                                 rho_bar=rho_bar, c_bar=c_bar)
        ref_vs, ref_pg, ref_rho = reference_vtrace(
            self.B_LOGP, self.T_LOGP, self.REW, self.VAL, 0.9, 0.4,
            rho_bar, c_bar)
        np.testing.assert_allclose(rho, ref_rho, rtol=1e-5)
        np.testing.assert_allclose(vs, ref_vs, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(pg, ref_pg, rtol=1e-5, atol=1e-6)

    def test_clipped_rho_edge_exact_values(self):
        """Fully hand-computed 2-step case where BOTH ratios clip:
        behavior far less confident than target → raw ratio e^2 ≈ 7.39,
        clipped to rho_bar = 1. With val=0 everywhere the recursion is
        pure reward accumulation: delta = [1*1, 1*2] (clipped rhos),
        a_1 = 2, a_0 = 1 + 0.5*1*2 = 2, vs = [2, 2]; pg_adv_0 =
        1*(1 + 0.5*vs_1 - 0) = 2, pg_adv_1 = 2."""
        vs, pg, rho = run_vtrace(
            behavior_logp=[-3.0, -3.0], target_logp=[-1.0, -1.0],
            rew=[1.0, 2.0], val=[0.0, 0.0], gamma=0.5, last_val=0.0,
            rho_bar=1.0, c_bar=1.0)
        np.testing.assert_allclose(rho, [1.0, 1.0], rtol=1e-6)
        np.testing.assert_allclose(vs, [2.0, 2.0], rtol=1e-6)
        np.testing.assert_allclose(pg, [2.0, 2.0], rtol=1e-6)

    def test_downweighted_rho_edge(self):
        """The opposite tail: behavior MORE confident than target → raw
        ratio e^-2 ≈ 0.135 passes the min() unclipped and scales both
        the targets and the advantage — stale confident tokens get tiny
        weight, the property the RLHF path leans on."""
        ratio = float(np.exp(-2.0))
        vs, pg, rho = run_vtrace(
            behavior_logp=[-1.0], target_logp=[-3.0],
            rew=[1.0], val=[0.0], gamma=0.9, last_val=0.0)
        np.testing.assert_allclose(rho, [ratio], rtol=1e-5)
        np.testing.assert_allclose(vs, [ratio], rtol=1e-5)
        np.testing.assert_allclose(pg, [ratio], rtol=1e-5)


class TestOnPolicyIdentity:
    def test_equals_nstep_return_when_on_policy(self):
        """behavior == target (every ratio exactly 1) with rho_bar,
        c_bar >= 1 must telescope to the discounted n-step return with
        bootstrap — i.e. NO correction, the identity that makes V-trace
        safe to leave always-on in a learner that is sometimes fed
        on-policy data."""
        rng = np.random.default_rng(0)
        T, gamma = 6, 0.97
        logp = rng.uniform(-2, -0.1, T).astype(np.float32)
        rew = rng.standard_normal(T).astype(np.float32)
        val = rng.standard_normal(T).astype(np.float32)
        last_val = float(rng.standard_normal())
        vs, pg, rho = run_vtrace(logp, logp, rew, val, gamma, last_val,
                                 rho_bar=1.0, c_bar=1.0)
        # n-step return: G_t = r_t + gamma G_{t+1}, G_T = last_val
        G = np.zeros(T + 1, np.float64)
        G[T] = last_val
        for t in reversed(range(T)):
            G[t] = rew[t] + gamma * G[t + 1]
        np.testing.assert_allclose(rho, np.ones(T), rtol=1e-6)
        np.testing.assert_allclose(vs, G[:T], rtol=1e-4, atol=1e-5)
        # pg advantage reduces to the TD form against those returns
        expected_pg = rew + gamma * G[1:] - val
        np.testing.assert_allclose(pg, expected_pg, rtol=1e-4, atol=1e-5)

    def test_on_policy_terminal_episode_is_reward_to_go(self):
        """Terminated episode (last_val=0), on-policy, values zero: vs
        IS the discounted reward-to-go — the degenerate case every
        from-scratch run starts in."""
        rew = [0.0, 0.0, 1.0]
        vs, pg, _ = run_vtrace([-1.0] * 3, [-1.0] * 3, rew, [0.0] * 3,
                               0.5, last_val=0.0)
        np.testing.assert_allclose(vs, [0.25, 0.5, 1.0], rtol=1e-6)
        np.testing.assert_allclose(pg, [0.25, 0.5, 1.0], rtol=1e-6)


class TestPaddedBatches:
    def test_padding_stays_zero_and_values_match_unpadded(self):
        """The [B, T] mask discipline: right-padding must neither leak
        into the valid prefix (bootstrap injects at the last VALID step,
        not the last column) nor produce nonzero outputs in the tail."""
        args = ([-0.5, -1.0, -0.8], [-0.6, -0.9, -1.1],
                [1.0, -0.3, 0.7], [0.2, 0.4, -0.1])
        vs_a, pg_a, rho_a = run_vtrace(*args, 0.9, last_val=0.33)
        vs_b, pg_b, rho_b = run_vtrace(*args, 0.9, last_val=0.33,
                                       pad_to=8)
        np.testing.assert_allclose(vs_b[:3], vs_a, rtol=1e-6)
        np.testing.assert_allclose(pg_b[:3], pg_a, rtol=1e-6)
        assert np.all(vs_b[3:] == 0) and np.all(pg_b[3:] == 0)
        assert np.all(rho_b[3:] == 0)

    def test_batch_rows_independent(self):
        """Rows of a [B, T] batch must not mix: computing two
        trajectories together equals computing them alone."""
        rng = np.random.default_rng(3)
        T = 5
        rows = []
        for _ in range(2):
            rows.append(tuple(rng.standard_normal(T).astype(np.float32)
                              for _ in range(4)))
        single = [run_vtrace(*r, 0.95, last_val=0.1) for r in rows]
        stacked = vtrace(
            jnp.asarray(np.stack([rows[0][0], rows[1][0]])),
            jnp.asarray(np.stack([rows[0][1], rows[1][1]])),
            jnp.asarray(np.stack([rows[0][2], rows[1][2]])),
            jnp.asarray(np.stack([rows[0][3], rows[1][3]])),
            jnp.ones((2, T), jnp.float32), 0.95,
            last_val=jnp.asarray([0.1, 0.1], jnp.float32))
        for b in range(2):
            np.testing.assert_allclose(np.asarray(stacked.vs)[b],
                                       single[b][0], rtol=1e-5)
            np.testing.assert_allclose(np.asarray(stacked.pg_adv)[b],
                                       single[b][1], rtol=1e-5)


# --- PR 38: the recursion in log depth (ops/recurrence.py) -----------------
#
# Everything below compares with a float64 numpy recursion, step by step
# over T — the form the op no longer has — at the two long shapes the
# benchmark's sequence cells run ([1, 16384] and [2, 8192]).


def reference_vtrace_f64(behavior_logp, target_logp, rew, val, valid, gamma,
                         last_val, rho_bar, c_bar):
    """[B, T] float64 V-trace on right-padded rows, the recursion a Python
    loop over T (vectorised over B only)."""
    b, t, rew, val, valid, last_val = (
        np.asarray(x, np.float64)
        for x in (behavior_logp, target_logp, rew, val, valid, last_val))
    B, T = rew.shape
    rew, val = rew * valid, val * valid
    ratio = np.exp(np.where(valid > 0, t - b, 0.0))
    rho = np.minimum(rho_bar, ratio) * valid
    c = np.minimum(c_bar, ratio) * valid
    lengths = valid.sum(-1).astype(int)
    rows = np.arange(B)
    has = lengths > 0

    def shifted(v):
        nxt = np.concatenate([v[:, 1:], last_val[:, None]], -1)
        nxt[rows[has], lengths[has] - 1] = last_val[has]
        return nxt

    delta = rho * (rew + gamma * shifted(val) - val) * valid
    a = np.zeros((B, T + 1))
    with np.errstate(over="ignore", invalid="ignore"):
        for i in reversed(range(T)):
            a[:, i] = delta[:, i] + gamma * c[:, i] * a[:, i + 1]
    vs = (val + a[:, :T]) * valid
    pg_adv = rho * (rew + gamma * shifted(vs) - val) * valid
    return vs, pg_adv, rho


def random_batch(shape, lengths, seed, logp_scale=0.3):
    rng = np.random.default_rng(seed)
    b = rng.normal(-1.0, logp_scale, shape).astype(np.float32)
    t = rng.normal(-1.0, logp_scale, shape).astype(np.float32)
    rew = rng.standard_normal(shape).astype(np.float32)
    val = rng.standard_normal(shape).astype(np.float32)
    valid = (np.arange(shape[1])[None, :]
             < np.asarray(lengths)[:, None]).astype(np.float32)
    last_val = rng.standard_normal(shape[0]).astype(np.float32)
    return b, t, rew, val, valid, last_val


def assert_matches_f64(args, gamma, rho_bar, c_bar):
    b, t, rew, val, valid, last_val = args
    out = vtrace(*(jnp.asarray(x) for x in (b, t, rew, val, valid)), gamma,
                 last_val=jnp.asarray(last_val), rho_bar=rho_bar,
                 c_bar=c_bar)
    ref_vs, ref_pg, ref_rho = reference_vtrace_f64(
        b, t, rew, val, valid, gamma, last_val, rho_bar, c_bar)
    for got, ref in ((out.vs, ref_vs), (out.pg_adv, ref_pg),
                     (out.rho, ref_rho)):
        got = np.asarray(got)
        assert got.dtype == np.float32
        assert np.all(got[valid == 0] == 0)
        assert np.abs(got - ref).max() <= 1e-5 * np.abs(ref).max()
    return out


LONG_SHAPES = [(1, 16384), (2, 8192)]


class TestLogDepthRecursion:
    @pytest.mark.parametrize("bar", [1.0, 0.5])
    @pytest.mark.parametrize("tail", ["0", "1", "100", "T-1"])
    @pytest.mark.parametrize("shape", LONG_SHAPES, ids=["1x16384", "2x8192"])
    def test_long_rows_against_float64(self, shape, tail, bar):
        """Random log-ratios at the cells' shapes, a padded tail of 0, 1,
        100 and T − 1 steps on the first row (the second row, where there
        is one, full)."""
        T = shape[1]
        pad = T - 1 if tail == "T-1" else int(tail)
        lengths = [T - pad] + [T] * (shape[0] - 1)
        assert_matches_f64(random_batch(shape, lengths, seed=pad + shape[0]),
                           0.99, bar, bar)

    @pytest.mark.parametrize("shape", LONG_SHAPES, ids=["1x16384", "2x8192"])
    def test_all_padding_row(self, shape):
        """A row with no valid step is all zeros, and leaves its
        neighbour alone."""
        lengths = [0] + [shape[1] - 7] * (shape[0] - 1)
        out = assert_matches_f64(random_batch(shape, lengths, seed=11),
                                 0.99, 1.0, 1.0)
        assert np.all(np.asarray(out.vs)[0] == 0)
        assert np.all(np.asarray(out.pg_adv)[0] == 0)

    @pytest.mark.parametrize("shape", LONG_SHAPES, ids=["1x16384", "2x8192"])
    def test_recursion_restarts_where_c_is_zero(self, shape):
        """A step whose ratio underflows to 0 (c_t = rho_t = 0) cuts the
        trace: nothing from later steps reaches that step or any before it
        through it, so vs there is exactly v."""
        T = shape[1]
        args = random_batch(shape, [T] * shape[0], seed=5)
        cut = T // 2 + 3
        args[1][0, cut] = args[0][0, cut] - 200.0   # exp(-200) == 0 in f32
        out = assert_matches_f64(args, 0.99, 1.0, 1.0)
        assert np.asarray(out.rho)[0, cut] == 0
        assert np.asarray(out.vs)[0, cut] == args[3][0, cut]
        # the steps before the cut see the same targets whatever follows it
        later = [x.copy() for x in args]
        later[2][0, cut + 1:] = 0.0
        out_b = vtrace(*(jnp.asarray(x) for x in later[:5]), 0.99,
                       last_val=jnp.asarray(later[5]))
        np.testing.assert_array_equal(np.asarray(out.vs)[0, :cut],
                                      np.asarray(out_b.vs)[0, :cut])

    def test_products_above_one_short(self):
        """gamma * c_bar > 1 at T 64: the coefficients' products grow
        along the row; equal to the reference."""
        args = random_batch((2, 64), [64, 40], seed=2, logp_scale=1.0)
        b, t, rew, val, valid, last_val = args
        out = vtrace(*(jnp.asarray(x) for x in args[:5]), 0.99,
                     last_val=jnp.asarray(last_val), rho_bar=2.0, c_bar=2.0)
        ref_vs, ref_pg, _ = reference_vtrace_f64(*args[:5], 0.99, last_val,
                                                 2.0, 2.0)
        np.testing.assert_allclose(np.asarray(out.vs), ref_vs, rtol=2e-5,
                                   atol=1e-5 * np.abs(ref_vs).max())
        np.testing.assert_allclose(np.asarray(out.pg_adv), ref_pg, rtol=2e-5,
                                   atol=1e-5 * np.abs(ref_pg).max())

    def test_products_above_one_long_stay_finite(self):
        """gamma * c_bar > 1 at T 16,384: 2,000 steps of ratio e^2 with no
        reward and no value (delta 0, coefficient 1.98) after a stretch of
        ordinary steps. The product of those coefficients overflows
        float32 (1.98^2000), nothing follows them, and the targets before
        them are ordinary numbers: finite wherever the reference is, and
        equal to it."""
        T = 16384
        args = random_batch((1, T), [T - 100], seed=9)
        b, t, rew, val, valid, last_val = args
        t[0, 1000:3000] = b[0, 1000:3000] + 2.0
        rew[0, 1000:] = 0.0
        val[0, 1000:] = 0.0
        last_val[:] = 0.0
        out = vtrace(*(jnp.asarray(x) for x in args[:5]), 0.99,
                     last_val=jnp.asarray(last_val), rho_bar=2.0, c_bar=2.0)
        ref_vs, ref_pg, _ = reference_vtrace_f64(*args[:5], 0.99, last_val,
                                                 2.0, 2.0)
        assert np.all(np.isfinite(ref_vs)) and np.all(np.isfinite(ref_pg))
        for got, ref in ((np.asarray(out.vs), ref_vs),
                         (np.asarray(out.pg_adv), ref_pg)):
            assert np.all(np.isfinite(got))
            assert np.abs(got - ref).max() <= 1e-5 * np.abs(ref).max()


def primitives_of(jaxpr) -> set:
    """Names of every primitive in a jaxpr, sub-jaxprs included."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names |= primitives_of(sub)
    return names


class TestNoLoopInTheProgram:
    """The mechanism's guard on the CPU: what V-trace traces to."""

    @staticmethod
    def traced(c_bar, shape=(2, 64)):
        x = jnp.zeros(shape, jnp.float32)
        return primitives_of(jax.make_jaxpr(
            lambda b, t, r, v, m, lv: vtrace(b, t, r, v, m, 0.99,
                                             last_val=lv, c_bar=c_bar))(
            x, x, x, x, x, jnp.zeros(shape[:1], jnp.float32)).jaxpr)

    def test_no_scan_or_while_at_the_shipped_bars(self):
        assert not {"scan", "while"} & self.traced(c_bar=1.0)

    def test_no_scan_or_while_at_gamma_c_bar_of_one(self):
        x = jnp.zeros((1, 16), jnp.float32)
        prims = primitives_of(jax.make_jaxpr(
            lambda d: vtrace(d, d, d, d, d, 1.0, c_bar=1.0))(x).jaxpr)
        assert not {"scan", "while"} & prims

    def test_sequential_scan_where_products_can_grow(self):
        assert "scan" in self.traced(c_bar=2.0)

    @pytest.mark.parametrize("steps", [1, 2, 3, 20, 1000])
    def test_doubling_equals_sequential(self, steps):
        """The helper's two forms on one input, any T (no power of two
        needed), against each other and a float64 loop."""
        rng = np.random.default_rng(steps)
        k = rng.uniform(0.0, 1.0, (3, steps)).astype(np.float32)
        x = rng.standard_normal((3, steps)).astype(np.float32)
        ref = np.zeros((3, steps + 1))
        for i in reversed(range(steps)):
            ref[:, i] = x[:, i] + k[:, i].astype(np.float64) * ref[:, i + 1]
        fast = np.asarray(reverse_linear_recurrence(jnp.asarray(k),
                                                    jnp.asarray(x)))
        slow = np.asarray(reverse_linear_recurrence(
            jnp.asarray(k), jnp.asarray(x), sequential=True))
        scale = np.abs(ref).max()
        assert np.abs(fast - ref[:, :steps]).max() <= 1e-6 * scale
        assert np.abs(slow - ref[:, :steps]).max() <= 1e-6 * scale
