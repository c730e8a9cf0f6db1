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


def _decoded_episode(n, seed, obs_dim=6, obs_dtype=np.uint8, frac=False):
    from relayrl_tpu.types.columnar import DecodedTrajectory

    rng = np.random.default_rng(seed)
    obs = rng.integers(0, 256, (n, obs_dim))
    if frac:
        obs = obs / 7.0  # float64 -> float32 has to round
    return DecodedTrajectory(
        agent_id="a", n_steps=n, n_records=n, marker_truncated=False,
        columns={"o": obs.astype(obs_dtype),
                 "a": rng.integers(0, 3, (n,)).astype(np.int32),
                 "r": rng.random(n).astype(np.float32),
                 "t": np.array([False] * (n - 1) + [seed % 2 == 0]),
                 "u": np.zeros((n,), np.uint8),
                 "x": np.zeros((n,), np.uint8)},
        aux={"v": rng.standard_normal(n).astype(np.float32),
             "logp_a": rng.standard_normal(n).astype(np.float32)})


def _records_episode(n, seed, obs_dim=6, obs_dtype=np.uint8, frac=False):
    """The ActionRecord twin of ``_decoded_episode`` (same values)."""
    dt = _decoded_episode(n, seed, obs_dim, obs_dtype, frac)
    c, aux = dt.columns, dt.aux
    return [ActionRecord(
        obs=c["o"][i], act=np.int64(c["a"][i]), rew=float(c["r"][i]),
        data={"v": aux["v"][i], "logp_a": aux["logp_a"][i]},
        done=bool(c["t"][i])) for i in range(n)]


_MAKERS = {"DecodedTrajectory": _decoded_episode,
           "ActionRecord": _records_episode}
def _plain_batch(episodes, buckets=(64, 256, 1000), obs_dim=6, act_dim=3,
                 pin=False):
    """The batch as the tree before one-copy staging built it without a
    slab: every episode padded apart to its own bucket, grown to the
    largest bucket present, stacked into fresh arrays."""
    from relayrl_tpu.data.batching import pad_decoded
    from relayrl_tpu.types.columnar import DecodedTrajectory

    padded = []
    for ep in episodes:
        pad = (pad_decoded if isinstance(ep, DecodedTrajectory)
               else pad_trajectory)
        padded.append(pad(ep, pick_bucket(len(ep), buckets), obs_dim,
                          act_dim))
    horizon = max(t.obs.shape[0] for t in padded)
    for t in padded:
        for name in ("obs", "act", "act_mask", "rew", "val", "logp",
                     "valid"):
            arr = getattr(t, name)
            grow = [(0, horizon - arr.shape[0])] + [(0, 0)] * (arr.ndim - 1)
            setattr(t, name, np.pad(arr, grow))
    return stack_trajectories(
        padded, obs_dtype=np.float32 if pin else None).as_dict()


def _assert_batch_bytes(got, want, what=""):
    assert got.keys() == want.keys()
    for key in want:
        assert got[key].dtype == want[key].dtype, (what, key)
        assert got[key].shape == want[key].shape, (what, key)
        assert got[key].tobytes() == want[key].tobytes(), (what, key)


@pytest.fixture
def stack_spans(monkeypatch):
    """Arguments of every ``rl:batch.stack`` span opened, in order."""
    from relayrl_tpu.data import replay_buffer

    seen = []

    class recording(replay_buffer.span):
        __slots__ = ()

        def __init__(self, name, *a, **args):
            super().__init__(name, *a, **args)
            if name == "rl:batch.stack":
                seen.append(self.args)

    monkeypatch.setattr(replay_buffer, "span", recording)
    return seen


_U8, _F32 = np.uint8, np.float32
# Each script: episodes (length, obs dtype) and the calls between them, for
# a buffer of four rows and buckets (64, 256, 1000); then the rows moved a
# second time, per drained batch.
_SCRIPTS = {
    "single_bucket": ([(5, _U8), (60, _U8), (64, _U8), (17, _U8), "drain",
                       (9, _U8), (33, _U8), (64, _U8), (2, _U8), "drain"],
                      [0, 0]),
    "bucket_grows": ([(5, _U8), (9, _U8), (100, _U8), (7, _U8), "drain"],
                     [2]),
    "bucket_grows_twice": ([(5, _U8), (100, _U8), (300, _U8), (7, _U8),
                            "drain"], [3]),
    "bucket_grows_at_last_row": ([(5, _U8), (9, _U8), (7, _U8), (2000, _U8),
                                  "drain"], [3]),
    "bucket_shrinks": ([(100, _U8), (5, _U8), (9, _U8), (200, _U8),
                        "drain"], [0]),
    "uint8_then_float32": ([(5, _U8), (9, _U8), (7, _F32), (8, _U8),
                            "drain"], [2]),
    "float32_then_uint8": ([(5, _F32), (9, _U8), (7, _U8), (8, _U8),
                            "drain"], [0]),
    "grows_and_widens_at_once": ([(5, _U8), (100, _F32), (7, _U8),
                                  (64, _U8), "drain"], [1]),
    "part_filled_drain": ([(5, _U8), (100, _U8), (7, _U8), "drain",
                           (9, _U8), (33, _U8), (64, _U8), (2, _U8),
                           "drain"], [1, 0]),
    "two_batches_pending": ([(5, _U8), (60, _U8), (64, _U8), (17, _U8),
                             (100, _U8), (9, _F32), (33, _U8), (64, _U8),
                             (2, _U8), "drain", "drain", "drain"],
                            [0, 1, 0]),
    "reset_mid_batch": ([(5, _U8), (100, _F32), "reset", (9, _U8),
                         (33, _U8), (64, _U8), (2, _U8), "drain"], [0]),
    "reset_then_another_key": ([(5, _U8), (9, _U8), "reset", (100, _F32),
                                (33, _U8), (64, _U8), (2, _U8), "drain"],
                               [0]),
}


def _play(script, make, staging_slots, pin=False):
    """Run a script; returns the drained batches (copied), the episodes
    each was made of, and the buffer."""
    buf = EpochBuffer(obs_dim=6, act_dim=3, traj_per_epoch=4,
                      buckets=(64, 256, 1000), staging_slots=staging_slots)
    if pin:
        buf.pin_float32_obs()
    pending, batches, taken = [], [], []
    for i, step in enumerate(script):
        if step == "drain":
            batches.append({k: v.copy()
                            for k, v in buf.drain().as_dict().items()})
            taken.append(pending[:4])
            pending = pending[4:]
        elif step == "reset":
            buf.reset()
            pending = []
            assert len(buf) == 0 and not buf.ready
            assert buf.pop_episode_stats() == ([], [])
        else:
            n, dtype = step
            pending.append(make(n, i, obs_dtype=dtype))
            ready = buf.add_episode(pending[-1])
            assert len(buf) == len(pending)
            assert ready == buf.ready == (len(pending) >= 4)
    return batches, taken, buf


class TestOneCopyStaging:
    """Episodes are padded straight into their row of the batch slab: every
    batch must equal, byte for byte and in dtype, what a buffer without
    slabs gives and what padding apart and stacking gave."""

    @pytest.mark.parametrize("path", sorted(_MAKERS))
    @pytest.mark.parametrize("name", sorted(_SCRIPTS))
    def test_slab_rows_equal_fresh_batches(self, name, path, stack_spans):
        script, moved = _SCRIPTS[name]
        make = _MAKERS[path]
        got, taken, buf = _play(script, make, 3)
        spans = list(stack_spans)
        twin, _, _ = _play(script, make, 0)
        assert len(got) == len(twin) == len(moved)
        for k, (g, t, eps) in enumerate(zip(got, twin, taken)):
            _assert_batch_bytes(g, t, f"{name}[{k}] vs staging_slots=0")
            _assert_batch_bytes(g, _plain_batch(eps),
                                f"{name}[{k}] vs padded apart")
        assert [s["moved"] for s in spans] == moved
        for s, g, eps in zip(spans, got, taken):
            assert s["padded"] == g["valid"].size
            assert s["valid"] == int(g["valid"].sum()) == sum(
                min(len(e), 1000) for e in eps)
        assert len(buf) == 0

    @pytest.mark.parametrize("staging_slots", [3, 0])
    def test_pinned_float32_slab_is_float32_from_the_first_row(
            self, staging_slots, stack_spans):
        script = _SCRIPTS["part_filled_drain"][0]
        got, taken, buf = _play(script, _decoded_episode, staging_slots,
                                pin=True)
        for g, eps in zip(got, taken):
            assert g["obs"].dtype == np.float32
            _assert_batch_bytes(g, _plain_batch(eps, pin=True))
        # widening is not a move: only the bucket growth counts
        assert [s["moved"] for s in stack_spans] == [1, 0]

    def test_drained_batch_is_the_slab_the_rows_were_written_to(
            self, stack_spans):
        """No copy at the drain: with a ring of one, the next batch's
        first episode shows through the batch drained before."""
        buf = EpochBuffer(obs_dim=6, act_dim=3, traj_per_epoch=4,
                          buckets=(64,), staging_slots=1)
        for i in range(4):
            buf.add_episode(_decoded_episode(64, i))
        first = buf.drain()
        assert first.obs.flags["C_CONTIGUOUS"] and first.obs.shape[0] == 4
        nxt = _decoded_episode(64, 99)
        assert first.obs[0].tobytes() != nxt.columns["o"].tobytes()
        buf.add_episode(nxt)
        assert first.obs[0].tobytes() == nxt.columns["o"].tobytes()
        for i in range(3):
            buf.add_episode(_decoded_episode(64, 100 + i))
        second = buf.drain()
        assert second.obs is first.obs
        assert all(np.shares_memory(a, b) for a, b in zip(
            first.as_dict().values(), second.as_dict().values()))
        assert [s["moved"] for s in stack_spans] == [0, 0]

    def test_part_filled_drain_views_the_leading_rows(self):
        buf = EpochBuffer(obs_dim=6, act_dim=3, traj_per_epoch=4,
                          buckets=(64,))
        for i in range(3):
            buf.add_episode(_decoded_episode(9, i))
        part = buf.drain()
        assert part.batch_size == 3 and part.last_val.shape == (3,)
        assert all(v.flags["C_CONTIGUOUS"] and v.base is not None
                   for v in part.as_dict().values())

    def test_batches_pending_behind_another_never_share_a_slab(self):
        """More batches wait than the ring has slabs: each still holds its
        own episodes when its turn to drain comes."""
        buf = EpochBuffer(obs_dim=6, act_dim=3, traj_per_epoch=2,
                          buckets=(64,), staging_slots=1)
        eps = [_decoded_episode(9, i) for i in range(8)]
        for ep in eps:
            buf.add_episode(ep)
        assert len(buf) == 8
        held = [buf.drain() for _ in range(4)]
        for k, batch in enumerate(held):
            _assert_batch_bytes(batch.as_dict(),
                                _plain_batch(eps[2 * k: 2 * k + 2],
                                             buckets=(64,)))

    def test_episode_stats_are_those_of_the_episode_padded_apart(self):
        """A float32 sum depends on the length summed over: the return is
        taken over the episode's own bucket, not the slab's horizon."""
        buf = EpochBuffer(obs_dim=6, act_dim=3, traj_per_epoch=4,
                          buckets=(64, 256, 1000))
        eps = [_decoded_episode(n, i) for i, n in
               enumerate([900, 200, 63, 5])]
        for ep in eps:
            buf.add_episode(ep)
        rets, lens = buf.pop_episode_stats()
        from relayrl_tpu.data.batching import pad_decoded
        apart = [pad_decoded(ep, pick_bucket(len(ep), buf.buckets), 6, 3)
                 for ep in eps]
        assert rets == [float(t.rew.sum()) for t in apart]
        assert lens == [900, 200, 63, 5]


class TestEpochBufferBuildsBatchesInSlabRows:
    @pytest.mark.parametrize("obs_dtype", [np.uint8, np.float32])
    @pytest.mark.parametrize("staging_slots", [3, 0])
    def test_recycled_batches_equal_fresh_ones(self, staging_slots,
                                               obs_dtype):
        """From the ring's second round on, episodes are padded into rows
        that held an earlier batch: every batch must equal what a buffer
        that has built nothing yet gives for the same episodes."""
        lens = [5, 60, 64, 17, 100, 9, 33, 64, 2, 200, 64, 41]
        kw = dict(obs_dim=6, act_dim=3, traj_per_epoch=4,
                  buckets=(64, 256), staging_slots=staging_slots)
        buf = EpochBuffer(**kw)
        drained = 0
        for i, n in enumerate(lens * 4):
            if not buf.add_episode(_decoded_episode(n, i,
                                                    obs_dtype=obs_dtype)):
                continue
            got = {k: v.copy() for k, v in buf.drain().as_dict().items()}
            fresh = EpochBuffer(**kw)
            for j in range(i - 3, i + 1):
                fresh.add_episode(_decoded_episode((lens * 4)[j], j,
                                                   obs_dtype=obs_dtype))
            want = fresh.drain().as_dict()
            assert got.keys() == want.keys()
            for key in want:
                np.testing.assert_array_equal(got[key], want[key], key)
                assert got[key].dtype == want[key].dtype, key
            assert got["obs"].dtype == obs_dtype
            drained += 1
        assert drained == 12
        buf.add_episode(_decoded_episode(5, 0, obs_dtype=obs_dtype))
        buf.reset()
        assert len(buf) == 0 and not buf.ready

    def test_pad_decoded_refuses_out_of_a_narrower_obs_dtype(self):
        from relayrl_tpu.data.batching import pad_decoded

        out = pad_decoded(_decoded_episode(5, 0), 8, 6, 3)
        with pytest.raises(ValueError, match="float32"):
            pad_decoded(_decoded_episode(5, 1, obs_dtype=np.float32), 8, 6,
                        3, out=out)
        # the other way round widens exactly
        wide = pad_decoded(_decoded_episode(5, 0, obs_dtype=np.float32),
                           8, 6, 3)
        got = pad_decoded(_decoded_episode(5, 1), 8, 6, 3, out=wide)
        assert got.obs.dtype == np.float32
        np.testing.assert_array_equal(
            got.obs, pad_decoded(_decoded_episode(5, 1), 8, 6, 3).obs)


# -- observations keep their wire dtype (uint8 frames stay bytes) ----------

_LENS = [5, 60, 64, 17, 100, 9, 33, 64]


def _drain_all(make, obs_dtypes, staging_slots, pin=False, frac=False):
    """Feed ``_LENS`` episodes (obs dtype cycling through ``obs_dtypes``)
    and return every drained batch as copied dicts."""
    buf = EpochBuffer(obs_dim=6, act_dim=3, traj_per_epoch=4,
                      buckets=(64, 256), staging_slots=staging_slots)
    if pin:
        buf.pin_float32_obs()
    out = []
    for i, n in enumerate(_LENS):
        dtype = obs_dtypes[i % len(obs_dtypes)]
        if buf.add_episode(make(n, i, obs_dtype=dtype, frac=frac)):
            out.append({k: v.copy()
                        for k, v in buf.drain().as_dict().items()})
    assert len(out) == 2
    return out, buf


def _assert_same_batches(got, want, obs_dtype):
    """``want`` is the float32 batch the parent tree built: obs equal in
    value, of the dtype the rule gives; every other field byte-equal."""
    for g, w in zip(got, want):
        assert g.keys() == w.keys()
        assert w["obs"].dtype == np.float32
        assert g["obs"].dtype == obs_dtype
        np.testing.assert_array_equal(g["obs"].astype(np.float32), w["obs"])
        for key in w:
            if key != "obs":
                assert g[key].dtype == w[key].dtype, key
                assert g[key].tobytes() == w[key].tobytes(), key


class TestObsKeepsWireDtype:
    @pytest.mark.parametrize("staging_slots", [3, 0])
    @pytest.mark.parametrize("path", sorted(_MAKERS))
    @pytest.mark.parametrize("src,batch", [
        (np.uint8, np.uint8), (np.float32, np.float32),
        (np.float64, np.float32)])
    def test_batch_obs_dtype_follows_the_source(self, src, batch, path,
                                                staging_slots):
        make = _MAKERS[path]
        frac = src is not np.uint8
        got, buf = _drain_all(make, [src], staging_slots, frac=frac)
        # the float32 batch of the same values: what every stream gave
        # before observations kept their dtype
        want, _ = _drain_all(make, [np.float32], staging_slots, frac=frac)
        _assert_same_batches(got, want, batch)
        assert buf.obs_dtype == batch

    @pytest.mark.parametrize("staging_slots", [3, 0])
    @pytest.mark.parametrize("path", sorted(_MAKERS))
    def test_mixed_uint8_float32_batch_is_float32_and_exact(
            self, path, staging_slots):
        """Two fleets with different env wrappers feed one learner: a
        batch that holds any float32 episode is float32, bytes widened
        exactly at the row assignment."""
        make = _MAKERS[path]
        got, _ = _drain_all(make, [np.uint8, np.float32, np.uint8],
                            staging_slots)
        want, _ = _drain_all(make, [np.float32], staging_slots)
        _assert_same_batches(got, want, np.float32)

    @pytest.mark.parametrize("staging_slots", [3, 0])
    def test_mixed_then_pure_uint8_batches(self, staging_slots):
        """The dtype is chosen per batch, from the episodes it takes."""
        buf = EpochBuffer(obs_dim=6, act_dim=3, traj_per_epoch=2,
                          buckets=(64,), staging_slots=staging_slots)
        seen = []
        for i, dtype in enumerate([np.uint8, np.float32, np.uint8, np.uint8,
                                   np.float32, np.float32]):
            if buf.add_episode(_decoded_episode(9, i, obs_dtype=dtype)):
                seen.append(buf.drain().obs.dtype)
                assert buf.obs_dtype == seen[-1]
        assert seen == [np.float32, np.uint8, np.float32]

    @pytest.mark.parametrize("staging_slots", [3, 0])
    def test_pinned_float32_obs(self, staging_slots):
        """Multi-host: the coordinator's batches stay float32 whatever its
        actors send, because its peers build theirs from (B, T) alone."""
        got, buf = _drain_all(_decoded_episode, [np.uint8], staging_slots,
                              pin=True)
        want, _ = _drain_all(_decoded_episode, [np.float32], staging_slots)
        _assert_same_batches(got, want, np.float32)
        assert buf.obs_dtype == np.float32

    def test_obs_dtype_is_unknown_before_the_first_drain(self):
        buf = EpochBuffer(obs_dim=6, act_dim=3, traj_per_epoch=2)
        assert buf.obs_dtype is None
        buf.add_episode(_decoded_episode(5, 0))
        assert buf.obs_dtype is None
        buf.add_episode(_decoded_episode(5, 1))
        buf.drain()
        buf.reset()
        assert buf.obs_dtype == np.uint8  # the stream's, not the epoch's

    @pytest.mark.parametrize("obs,want", [
        ("uint8", np.uint8), ("float32", np.float32), ("int64", np.float32),
        ("none", np.float32), ("uint8+float32", np.float32)])
    def test_pad_trajectory_obs_dtype(self, obs, want):
        steps = _records_episode(4, 0)
        if obs == "none":
            steps = [ActionRecord(act=s.act, rew=s.rew, done=s.done)
                     for s in steps]
        elif obs == "uint8+float32":
            steps[2] = ActionRecord(obs=steps[2].obs.astype(np.float32),
                                    act=steps[2].act, rew=steps[2].rew)
        elif obs != "uint8":
            steps = [ActionRecord(obs=s.obs.astype(obs), act=s.act,
                                  rew=s.rew, done=s.done) for s in steps]
        padded = pad_trajectory(steps, 8, 6, 3)
        assert padded.obs.dtype == want
        if obs != "none":
            np.testing.assert_array_equal(
                padded.obs[:4], _decoded_episode(4, 0).columns["o"])
        assert not padded.obs[4:].any()

    def test_staging_rings_are_keyed_by_obs_dtype(self):
        from relayrl_tpu.data import BatchStaging

        st = BatchStaging(2, obs_dim=6, act_dim=3)
        u8 = [st.acquire(4, 64, np.uint8) for _ in range(3)]
        f32 = [st.acquire(4, 64) for _ in range(3)]
        assert all(s["obs"].dtype == np.uint8 for s in u8)
        assert all(s["obs"].dtype == np.float32 for s in f32)
        assert u8[2] is u8[0] and u8[1] is not u8[0]
        assert f32[2] is f32[0] and all(a is not b for a in u8 for b in f32)

    @pytest.mark.parametrize("obs_dtype", [None, np.uint8])
    def test_zeros_takes_the_obs_dtype(self, obs_dtype):
        from relayrl_tpu.data import TrajectoryBatch

        kw = {} if obs_dtype is None else {"obs_dtype": obs_dtype}
        z = TrajectoryBatch.zeros(2, 8, 6, 3, **kw)
        assert z["obs"].dtype == (obs_dtype or np.float32)
        assert z["obs"].shape == (2, 8, 6)
        assert {k: v.dtype for k, v in z.items() if k != "obs"} == {
            "act": np.int32, "act_mask": np.float32, "rew": np.float32,
            "val": np.float32, "logp": np.float32, "valid": np.float32,
            "last_val": np.float32}
