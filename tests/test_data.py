"""Batching / epoch-buffer tests (fixed-shape discipline, SURVEY.md §7.4.3)."""

import numpy as np
import pytest

from relayrl_tpu.data import EpochBuffer, pad_trajectory, pick_bucket, stack_trajectories
from relayrl_tpu.types.action import ActionRecord


def _episode(n, obs_dim=4, done=True, with_aux=True):
    acts = []
    for i in range(n):
        data = {"logp_a": np.float32(-0.5 * i), "v": np.float32(0.1 * i)} if with_aux else None
        acts.append(ActionRecord(
            obs=np.full(obs_dim, i, np.float32),
            act=np.int64(i % 2),
            rew=1.0,
            data=data,
            done=(done and i == n - 1),
        ))
    return acts


class TestPickBucket:
    def test_smallest_fit(self):
        assert pick_bucket(10, [64, 256, 1000]) == 64
        assert pick_bucket(64, [64, 256, 1000]) == 64
        assert pick_bucket(65, [64, 256, 1000]) == 256
        assert pick_bucket(5000, [64, 256, 1000]) == 1000


class TestPadTrajectory:
    def test_shapes_and_mask(self):
        padded = pad_trajectory(_episode(5), horizon=8, obs_dim=4, act_dim=2)
        assert padded.obs.shape == (8, 4)
        assert padded.act.shape == (8,)
        assert padded.valid.tolist() == [1, 1, 1, 1, 1, 0, 0, 0]
        assert padded.length == 5
        assert padded.terminated is True
        assert padded.last_val == 0.0

    def test_aux_extracted(self):
        padded = pad_trajectory(_episode(3), horizon=4, obs_dim=4, act_dim=2)
        np.testing.assert_allclose(padded.logp[:3], [0.0, -0.5, -1.0])
        np.testing.assert_allclose(padded.val[:3], [0.0, 0.1, 0.2], rtol=1e-6)

    def test_truncated_bootstraps_from_last_val(self):
        padded = pad_trajectory(_episode(3, done=False), horizon=4, obs_dim=4, act_dim=2)
        assert padded.terminated is False
        assert padded.last_val == pytest.approx(0.2, rel=1e-5)

    def test_overlong_truncates(self):
        padded = pad_trajectory(_episode(10), horizon=4, obs_dim=4, act_dim=2)
        assert padded.length == 4
        assert padded.terminated is False  # cut episodes aren't terminal

    def test_continuous_actions(self):
        acts = [ActionRecord(obs=np.zeros(3, np.float32),
                             act=np.array([0.1, 0.2], np.float32), rew=0.0)]
        padded = pad_trajectory(acts, horizon=2, obs_dim=3, act_dim=2, discrete=False)
        assert padded.act.shape == (2, 2)
        np.testing.assert_allclose(padded.act[0], [0.1, 0.2])

    def test_empty_rejected(self):
        with pytest.raises(ValueError):
            pad_trajectory([], horizon=4, obs_dim=2, act_dim=2)

    def test_terminal_marker_folds_into_last_step(self):
        # flag_last_action appends a marker with no obs/act carrying the
        # final reward; it must not become a fictitious step (review fix).
        acts = _episode(3, done=False)
        acts.append(ActionRecord(rew=5.0, done=True))
        padded = pad_trajectory(acts, horizon=8, obs_dim=4, act_dim=2)
        assert padded.length == 3
        assert padded.rew[2] == pytest.approx(1.0 + 5.0)
        assert padded.terminated is True
        assert padded.last_val == 0.0
        assert padded.valid.sum() == 3

    def test_truncation_marker_keeps_bootstrap(self):
        # A time-limit truncation (marker with truncated=True) is an
        # episode end but NOT a terminal state: last_val must bootstrap
        # from the stored value instead of zeroing.
        acts = _episode(3, done=False)
        acts.append(ActionRecord(obs=np.full(4, 9, np.float32), rew=2.0,
                                 done=True, truncated=True))
        padded = pad_trajectory(acts, horizon=8, obs_dim=4, act_dim=2)
        assert padded.length == 3
        assert padded.rew[2] == pytest.approx(1.0 + 2.0)
        assert padded.terminated is False
        assert padded.last_val == pytest.approx(0.2, rel=1e-5)

    def test_marker_only_trajectory_rejected(self):
        with pytest.raises(ValueError, match="terminal markers"):
            pad_trajectory([ActionRecord(rew=1.0, done=True)],
                           horizon=4, obs_dim=2, act_dim=2)


class TestEpochBuffer:
    def test_ready_after_traj_per_epoch(self):
        buf = EpochBuffer(obs_dim=4, act_dim=2, traj_per_epoch=3, buckets=[8, 16])
        assert buf.add_episode(_episode(5)) is False
        assert buf.add_episode(_episode(6)) is False
        assert buf.add_episode(_episode(7)) is True
        batch = buf.drain()
        assert batch.batch_size == 3
        assert batch.horizon == 8  # all fit the 8-bucket
        assert len(buf) == 0

    def test_mixed_buckets_repad(self):
        buf = EpochBuffer(obs_dim=4, act_dim=2, traj_per_epoch=2, buckets=[8, 32])
        buf.add_episode(_episode(4))
        buf.add_episode(_episode(20))  # lands in the 32-bucket
        batch = buf.drain()
        assert batch.horizon == 32
        np.testing.assert_allclose(batch.valid.sum(axis=1), [4, 20])

    def test_episode_stats(self):
        buf = EpochBuffer(obs_dim=4, act_dim=2, traj_per_epoch=2, buckets=[8])
        buf.add_episode(_episode(3))
        buf.add_episode(_episode(5))
        rets, lens = buf.pop_episode_stats()
        assert rets == [3.0, 5.0]
        assert lens == [3, 5]
        assert buf.pop_episode_stats() == ([], [])

    def test_drain_empty_raises(self):
        buf = EpochBuffer(obs_dim=4, act_dim=2, traj_per_epoch=1)
        with pytest.raises(ValueError):
            buf.drain()

    def test_stack_rejects_mixed_horizons(self):
        a = pad_trajectory(_episode(3), 4, 4, 2)
        b = pad_trajectory(_episode(3), 8, 4, 2)
        with pytest.raises(ValueError, match="mixed horizons"):
            stack_trajectories([a, b])

    def test_max_traj_length_caps_buckets(self):
        buf = EpochBuffer(obs_dim=4, act_dim=2, traj_per_epoch=1,
                          buckets=[64, 256, 1000], max_traj_length=100)
        assert buf.buckets == (64,)


def _decoded_episode(n, seed, obs_dim=6):
    from relayrl_tpu.types.columnar import DecodedTrajectory

    rng = np.random.default_rng(seed)
    return DecodedTrajectory(
        agent_id="a", n_steps=n, n_records=n, marker_truncated=False,
        columns={"o": rng.integers(0, 255, (n, obs_dim)).astype(np.uint8),
                 "a": rng.integers(0, 3, (n,)).astype(np.int32),
                 "r": rng.random(n).astype(np.float32),
                 "t": np.array([False] * (n - 1) + [seed % 2 == 0]),
                 "u": np.zeros((n,), np.uint8),
                 "x": np.zeros((n,), np.uint8)},
        aux={"v": rng.standard_normal(n).astype(np.float32),
             "logp_a": rng.standard_normal(n).astype(np.float32)})


class TestEpochBufferRecyclesEpisodes:
    @pytest.mark.parametrize("staging_slots", [3, 0])
    def test_recycled_batches_equal_fresh_ones(self, staging_slots):
        """From the second drain on, episodes are padded into the arrays of
        episodes already drained: every batch must equal what a buffer that
        has recycled nothing gives for the same episodes."""
        lens = [5, 60, 64, 17, 100, 9, 33, 64, 2, 200, 64, 41]
        kw = dict(obs_dim=6, act_dim=3, traj_per_epoch=4,
                  buckets=(64, 256), staging_slots=staging_slots)
        buf = EpochBuffer(**kw)
        drained = 0
        for i, n in enumerate(lens * 2):
            if not buf.add_episode(_decoded_episode(n, seed=i)):
                continue
            got = {k: v.copy() for k, v in buf.drain().as_dict().items()}
            fresh = EpochBuffer(**kw)
            for j in range(i - 3, i + 1):
                fresh.add_episode(_decoded_episode((lens * 2)[j], seed=j))
            want = fresh.drain().as_dict()
            assert got.keys() == want.keys()
            for key in want:
                np.testing.assert_array_equal(got[key], want[key], key)
            drained += 1
        assert drained == 6
        assert 0 < sum(len(v) for v in buf._spare.values()) <= 2 * 4
        buf.reset()
        assert not buf._spare and len(buf) == 0
