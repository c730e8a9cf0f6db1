"""GAE/discount ops vs. straightforward numpy references (the reference's
scipy lfilter math, BaseReplayBuffer.py:6-83 / replay_buffer.py:48-79)."""

import jax.numpy as jnp
import numpy as np
import pytest

from relayrl_tpu.ops import (
    discount_cumsum,
    gae_advantages,
    masked_mean_std,
    normalize_advantages,
    rewards_to_go,
)
from relayrl_tpu.ops.recurrence import reverse_linear_recurrence


def np_discount_cumsum(x, discount):
    out = np.zeros_like(x, dtype=np.float64)
    running = 0.0
    for t in reversed(range(len(x))):
        running = x[t] + discount * running
        out[t] = running
    return out


class TestDiscountCumsum:
    @pytest.mark.parametrize("discount", [0.0, 0.5, 0.99, 1.0])
    def test_matches_reference_math(self, discount):
        rng = np.random.default_rng(0)
        x = rng.standard_normal(37).astype(np.float32)
        out = np.asarray(discount_cumsum(x, discount))
        np.testing.assert_allclose(out, np_discount_cumsum(x, discount), rtol=1e-4, atol=1e-5)

    def test_batched(self):
        rng = np.random.default_rng(1)
        x = rng.standard_normal((4, 16)).astype(np.float32)
        out = np.asarray(discount_cumsum(x, 0.9))
        for b in range(4):
            np.testing.assert_allclose(out[b], np_discount_cumsum(x[b], 0.9), rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("axis", [-1, 0])
    def test_is_the_shared_recurrence_with_a_constant_coefficient(self, axis):
        """PR 38: the repo has one reverse linear recurrence
        (ops/recurrence.py, the one V-trace runs with a per-step
        coefficient); ``discount_cumsum`` is it, bit for bit."""
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.standard_normal((5, 41)).astype(np.float32))
        rows = jnp.moveaxis(x, axis, -1)
        expected = jnp.moveaxis(
            reverse_linear_recurrence(jnp.full_like(rows, 0.97), rows),
            -1, axis)
        np.testing.assert_array_equal(
            np.asarray(discount_cumsum(x, 0.97, axis=axis)),
            np.asarray(expected))


class TestRewardsToGo:
    def test_padding_zeroed(self):
        rew = np.array([[1, 1, 1, 0, 0]], dtype=np.float32)
        valid = np.array([[1, 1, 1, 0, 0]], dtype=np.float32)
        out = np.asarray(rewards_to_go(rew, valid, 1.0))
        np.testing.assert_allclose(out[0], [3, 2, 1, 0, 0], atol=1e-6)

    def test_padding_does_not_leak(self):
        # Garbage in padded reward slots must not affect valid outputs.
        rew = np.array([[1, 1, 99, 99]], dtype=np.float32)
        valid = np.array([[1, 1, 0, 0]], dtype=np.float32)
        out = np.asarray(rewards_to_go(rew, valid, 0.9))
        np.testing.assert_allclose(out[0, :2], [1 + 0.9, 1.0], atol=1e-5)


class TestGAE:
    def test_terminal_episode_matches_reference_formula(self):
        # Hand-computed GAE on a 3-step terminal episode.
        gamma, lam = 0.9, 0.8
        rew = np.array([[1.0, 2.0, 3.0, 0.0]], dtype=np.float32)
        val = np.array([[0.5, 0.4, 0.3, 0.0]], dtype=np.float32)
        valid = np.array([[1, 1, 1, 0]], dtype=np.float32)
        adv, ret = gae_advantages(rew, val, valid, gamma, lam, np.zeros(1, np.float32))
        deltas = [
            1.0 + gamma * 0.4 - 0.5,
            2.0 + gamma * 0.3 - 0.4,
            3.0 + gamma * 0.0 - 0.3,
        ]
        expected = np_discount_cumsum(np.array(deltas), gamma * lam)
        np.testing.assert_allclose(np.asarray(adv)[0, :3], expected, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(adv)[0, 3], 0.0)
        np.testing.assert_allclose(
            np.asarray(ret)[0, :3], np_discount_cumsum(rew[0, :3], gamma), rtol=1e-4)

    def test_truncated_bootstrap(self):
        gamma, lam = 0.99, 0.95
        rew = np.array([[1.0, 1.0]], dtype=np.float32)
        val = np.array([[0.2, 0.3]], dtype=np.float32)
        valid = np.array([[1, 1]], dtype=np.float32)
        last_val = np.array([0.7], dtype=np.float32)
        adv, _ = gae_advantages(rew, val, valid, gamma, lam, last_val)
        deltas = [1.0 + gamma * 0.3 - 0.2, 1.0 + gamma * 0.7 - 0.3]
        expected = np_discount_cumsum(np.array(deltas), gamma * lam)
        np.testing.assert_allclose(np.asarray(adv)[0], expected, rtol=1e-4, atol=1e-5)

    def test_batch_of_mixed_lengths(self):
        gamma, lam = 0.95, 0.9
        rew = np.array([[1, 1, 1, 1], [2, 2, 0, 0]], dtype=np.float32)
        val = np.zeros((2, 4), dtype=np.float32)
        valid = np.array([[1, 1, 1, 1], [1, 1, 0, 0]], dtype=np.float32)
        adv, ret = gae_advantages(rew, val, valid, gamma, lam, np.zeros(2, np.float32))
        np.testing.assert_allclose(np.asarray(ret)[1, 2:], 0.0)
        np.testing.assert_allclose(
            np.asarray(ret)[1, :2], np_discount_cumsum(np.array([2.0, 2.0]), gamma), rtol=1e-4)


class TestNormalization:
    def test_masked_mean_std(self):
        x = np.array([[1.0, 2.0, 3.0, 100.0]], dtype=np.float32)
        valid = np.array([[1, 1, 1, 0]], dtype=np.float32)
        mean, std = masked_mean_std(x, valid)
        assert float(mean) == pytest.approx(2.0, abs=1e-5)
        assert float(std) == pytest.approx(np.std([1, 2, 3]), abs=1e-4)

    def test_normalize_ignores_padding(self):
        x = np.array([[1.0, 2.0, 3.0, 1e6]], dtype=np.float32)
        valid = np.array([[1, 1, 1, 0]], dtype=np.float32)
        out = np.asarray(normalize_advantages(x, valid))
        assert out[0, 3] == 0.0
        assert abs(out[0, :3].mean()) < 1e-5
