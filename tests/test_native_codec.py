"""Native columnar decoder parity: native/codec.cc vs the Python decode path.

The native decoder re-implements the trajectory wire decode + terminal-
marker folding in C++ (the reference keeps its whole ingest decode native,
training_zmq.rs:994-1011). These tests pin the two paths together: for a
wide range of trajectories, decoding natively and padding via the columnar
fast path must produce byte-identical learner inputs to deserializing in
Python and padding per-step.
"""

import numpy as np
import pytest

from relayrl_tpu.data.batching import (
    fold_trailing_markers,
    pad_decoded,
    pad_trajectory,
    pick_bucket,
)
from relayrl_tpu.data.step_buffer import StepReplayBuffer
from relayrl_tpu.transport.base import pack_trajectory_envelope
from relayrl_tpu.types.action import ActionRecord
from relayrl_tpu.types.columnar import (
    DecodedTrajectory,
    NativeDecoder,
    RawTrajectory,
    native_codec_available,
)
from relayrl_tpu.types.trajectory import deserialize_actions, serialize_actions

pytestmark = pytest.mark.skipif(
    not native_codec_available(), reason="native codec not built")


@pytest.fixture(scope="module")
def decoder():
    return NativeDecoder()


def _mk_steps(n, obs_dim=4, act_dim=2, discrete=True, with_mask=False,
              with_aux=True, seed=0):
    rng = np.random.default_rng(seed)
    steps = []
    for i in range(n):
        act = (np.int64(rng.integers(act_dim)) if discrete
               else rng.standard_normal(act_dim).astype(np.float32))
        data = None
        if with_aux:
            data = {"logp_a": np.float32(rng.standard_normal()),
                    "v": np.float32(rng.standard_normal())}
        steps.append(ActionRecord(
            obs=rng.standard_normal(obs_dim).astype(np.float32),
            act=act,
            mask=(np.ones(act_dim, np.float32) if with_mask else None),
            rew=float(rng.standard_normal()),
            data=data,
            done=(i == n - 1),
        ))
    return steps


def _assert_pad_parity(actions, decoder, obs_dim=4, act_dim=2, discrete=True,
                       horizon=None):
    payload = serialize_actions(actions)
    item = decoder.decode(payload, agent_id="parity")
    assert isinstance(item, DecodedTrajectory), f"fell back: {item!r}"
    assert item.agent_id == "parity"
    assert item.n_records == len(actions)
    folded, final_obs, truncated, final_mask = fold_trailing_markers(
        deserialize_actions(payload))
    assert item.n_steps == len(folded)
    assert item.marker_truncated == truncated
    if final_obs is None:
        assert item.final_obs is None
    else:
        np.testing.assert_array_equal(
            np.asarray(item.final_obs, np.float32), final_obs)
    if final_mask is None:
        assert item.final_mask is None
    else:
        np.testing.assert_array_equal(
            np.asarray(item.final_mask, np.float32), final_mask)

    h = horizon or pick_bucket(len(actions), (64, 256, 1000))
    want = pad_trajectory(deserialize_actions(payload), h, obs_dim, act_dim,
                          discrete)
    fields = ("obs", "act", "act_mask", "rew", "val", "logp", "valid")
    # over the arrays of an episode already drained (EpochBuffer recycles
    # them): nothing of what they held may stay
    dirty = pad_decoded(item, h, obs_dim, act_dim, discrete)
    for field in fields:
        getattr(dirty, field)[...] = 7
    dirty.length, dirty.terminated, dirty.last_val = -1, None, 7.0
    for got in (pad_decoded(item, h, obs_dim, act_dim, discrete),
                pad_decoded(item, h, obs_dim, act_dim, discrete, out=dirty)):
        for field in fields:
            np.testing.assert_array_equal(
                getattr(got, field), getattr(want, field), err_msg=field)
            assert getattr(got, field).dtype == getattr(want, field).dtype
        assert got.length == want.length
        assert got.terminated == want.terminated
        assert got.last_val == want.last_val
    assert got.obs is dirty.obs
    return item


_PIX = 6 * 6 * 2  # a small frame


def _pixel_steps(n, dtype, seed=7):
    rng = np.random.default_rng(seed)
    return [ActionRecord(
        obs=rng.integers(0, 256, _PIX).astype(dtype),
        act=np.int64(rng.integers(3)), rew=float(rng.random()),
        data={"logp_a": np.float32(-0.3), "v": np.float32(0.1)},
        done=(i == n - 1)) for i in range(n)]


class TestColumnarParity:
    def test_plain_discrete_episode(self, decoder):
        _assert_pad_parity(_mk_steps(17), decoder)

    def test_continuous_episode(self, decoder):
        _assert_pad_parity(_mk_steps(9, act_dim=3, discrete=False),
                           decoder, act_dim=3, discrete=False)

    def test_with_masks(self, decoder):
        _assert_pad_parity(_mk_steps(12, with_mask=True), decoder)

    def test_no_aux(self, decoder):
        _assert_pad_parity(_mk_steps(5, with_aux=False), decoder)

    def test_uint8_pixel_obs(self, decoder):
        """The byte-sized pixel wire (envs obs_dtype="uint8"): the C++
        columnar decoder must carry uint8 obs columns and the padded
        episode must match the Python path bit-for-bit, dtype included:
        both keep the bytes (pixels 0..255 are cast once, to the compute
        dtype, on the device, where the model casts on entry)."""
        rng = np.random.default_rng(7)
        obs_dim = 12 * 12 * 2  # small pixel-ish frame, byte range
        steps = [ActionRecord(
            obs=rng.integers(0, 256, obs_dim, dtype=np.uint8),
            act=np.int64(rng.integers(3)), rew=float(rng.random()),
            data={"logp_a": np.float32(-0.3), "v": np.float32(0.1)},
            done=(i == 7)) for i in range(8)]
        item = _assert_pad_parity(steps, decoder, obs_dim=obs_dim,
                                  act_dim=3)
        # the decoded column itself must still be bytes, not floats
        assert item.columns["o"].dtype == np.uint8
        np.testing.assert_array_equal(item.columns["o"][0], steps[0].obs)
        assert pad_decoded(item, 64, obs_dim, 3).obs.dtype == np.uint8

    @pytest.mark.parametrize("src,padded", [
        ("uint8", np.uint8), ("float32", np.float32),
        ("float64", np.float32), ("int32", np.float32)])
    def test_obs_dtype_parity(self, decoder, src, padded):
        """The wire keeps the env's dtype; both pad paths make the same of
        it: bytes stay bytes, everything else is float32."""
        steps = _pixel_steps(9, src)
        item = _assert_pad_parity(steps, decoder, obs_dim=_PIX, act_dim=3)
        assert item.columns["o"].dtype == src
        for got in (pad_decoded(item, 64, _PIX, 3),
                    pad_trajectory(steps, 64, _PIX, 3)):
            assert got.obs.dtype == padded
            np.testing.assert_array_equal(
                got.obs[:9],
                np.stack([s.obs for s in steps]).astype(np.float32))

    @pytest.mark.parametrize("staging_slots", [3, 0])
    @pytest.mark.parametrize("src", ["uint8", "float32", "float64"])
    def test_epoch_batches_equal_across_decode_paths(self, decoder, src,
                                                     staging_slots):
        """Natively decoded and Python-decoded episodes of one payload
        drain to byte-equal batches, obs dtype included."""
        from relayrl_tpu.data import EpochBuffer

        bufs = [EpochBuffer(obs_dim=_PIX, act_dim=3, traj_per_epoch=3,
                            buckets=(16, 64), staging_slots=staging_slots)
                for _ in range(2)]
        for rnd in range(2):
            for i, n in enumerate((5, 40, 16)):
                payload = serialize_actions(
                    _pixel_steps(n, src, seed=10 * rnd + i))
                bufs[0].add_episode(decoder.decode(payload, agent_id="a"))
                bufs[1].add_episode(deserialize_actions(payload))
            a, b = (buf.drain().as_dict() for buf in bufs)
            assert a["obs"].dtype == (np.uint8 if src == "uint8"
                                      else np.float32)
            for key in a:
                assert a[key].dtype == b[key].dtype, key
                assert a[key].tobytes() == b[key].tobytes(), key

    def test_terminal_marker(self, decoder):
        steps = _mk_steps(10)
        steps[-1] = ActionRecord(obs=steps[-1].obs, act=steps[-1].act,
                                 rew=steps[-1].rew, data=steps[-1].data,
                                 done=False)
        steps.append(ActionRecord(rew=2.5, done=True))  # flag_last_action
        _assert_pad_parity(steps, decoder)

    def test_truncation_marker_with_bootstrap_obs(self, decoder):
        steps = _mk_steps(8)
        steps[-1] = ActionRecord(obs=steps[-1].obs, act=steps[-1].act,
                                 rew=steps[-1].rew, data=steps[-1].data,
                                 done=False)
        steps.append(ActionRecord(
            obs=np.arange(4, dtype=np.float32), rew=0.5, done=True,
            truncated=True, mask=np.ones(2, np.float32)))
        item = _assert_pad_parity(steps, decoder)
        assert item.marker_truncated
        assert item.final_obs is not None and item.final_mask is not None

    def test_multiple_trailing_markers(self, decoder):
        steps = _mk_steps(6)
        steps.append(ActionRecord(rew=1.0, done=False))
        steps.append(ActionRecord(obs=np.full(4, 7, np.float32), rew=2.0,
                                  done=True, truncated=True))
        _assert_pad_parity(steps, decoder)

    def test_marker_only_trajectory(self, decoder):
        payload = serialize_actions([ActionRecord(rew=1.0, done=True)])
        item = decoder.decode(payload)
        assert isinstance(item, DecodedTrajectory)
        assert item.n_steps == 0 and item.n_records == 1

    def test_long_episode_truncates_to_horizon(self, decoder):
        _assert_pad_parity(_mk_steps(40), decoder, horizon=16)

    def test_envelope_decode(self, decoder):
        steps = _mk_steps(4)
        env = pack_trajectory_envelope("agent-xyz", serialize_actions(steps))
        item = decoder.decode(env, has_envelope=True)
        assert isinstance(item, DecodedTrajectory)
        assert item.agent_id == "agent-xyz"
        assert item.n_steps == 4

    def test_image_observations(self, decoder):
        # pixel policies flatten server-side; the column keeps the raw shape
        rng = np.random.default_rng(3)
        steps = [ActionRecord(obs=rng.integers(0, 255, (8, 8, 3)).astype(np.uint8),
                              act=np.int64(1), rew=1.0,
                              done=(i == 2)) for i in range(3)]
        payload = serialize_actions(steps)
        item = decoder.decode(payload)
        assert isinstance(item, DecodedTrajectory)
        assert item.columns["o"].shape == (3, 8, 8, 3)
        assert item.columns["o"].dtype == np.uint8


class TestFallbacks:
    def test_mixed_obs_shapes_fall_back(self, decoder):
        steps = _mk_steps(4)
        steps[2] = ActionRecord(obs=np.zeros(7, np.float32), act=np.int64(0),
                                rew=0.0, done=False)
        payload = serialize_actions(steps)
        item = decoder.decode(payload, agent_id="fb")
        assert isinstance(item, RawTrajectory)
        assert item.payload == payload  # Python decoder can take over
        assert deserialize_actions(item.payload)[2].obs.shape == (7,)

    def test_string_aux_falls_back(self, decoder):
        steps = _mk_steps(3)
        steps[1] = ActionRecord(obs=steps[1].obs, act=steps[1].act, rew=0.0,
                                data={"note": "hello"}, done=False)
        item = decoder.decode(serialize_actions(steps))
        assert isinstance(item, RawTrajectory)

    def test_mixed_aux_keys_fall_back(self, decoder):
        steps = _mk_steps(3)
        steps[1] = ActionRecord(obs=steps[1].obs, act=steps[1].act, rew=0.0,
                                data={"v": np.float32(1.0)}, done=False)
        item = decoder.decode(serialize_actions(steps))
        assert isinstance(item, RawTrajectory)

    def test_garbage_falls_back(self, decoder):
        item = decoder.decode(b"definitely not msgpack", agent_id="g")
        assert isinstance(item, RawTrajectory)
        assert item.payload == b"definitely not msgpack"

    def test_wrong_wire_version_falls_back(self, decoder):
        import msgpack

        payload = msgpack.packb({"v": 99, "acts": []})
        assert isinstance(decoder.decode(payload), RawTrajectory)


class TestStepBufferParity:
    def _compare(self, actions, obs_dim=4, act_dim=2, discrete=True):
        payload = serialize_actions(actions)
        dec = NativeDecoder().decode(payload)
        assert isinstance(dec, DecodedTrajectory)

        buf_py = StepReplayBuffer(obs_dim, act_dim, 128, discrete=discrete)
        n_py = buf_py.add_episode(deserialize_actions(payload))
        buf_nat = StepReplayBuffer(obs_dim, act_dim, 128, discrete=discrete)
        n_nat = buf_nat.add_episode(dec)
        assert n_nat == n_py
        for field in ("obs", "obs2", "act", "mask2", "rew", "done"):
            np.testing.assert_array_equal(
                getattr(buf_nat, field)[:n_py], getattr(buf_py, field)[:n_py],
                err_msg=field)
        assert buf_nat.ptr == buf_py.ptr and buf_nat.size == buf_py.size

    def test_terminal_episode(self):
        self._compare(_mk_steps(11))

    def test_truncated_with_bootstrap(self):
        steps = _mk_steps(7)
        steps[-1] = ActionRecord(obs=steps[-1].obs, act=steps[-1].act,
                                 rew=steps[-1].rew, data=steps[-1].data,
                                 done=False)
        steps.append(ActionRecord(obs=np.full(4, 3, np.float32), rew=1.0,
                                  done=True, truncated=True))
        self._compare(steps)

    def test_truncated_without_bootstrap_drops_last(self):
        steps = _mk_steps(5)
        steps[-1] = ActionRecord(obs=steps[-1].obs, act=steps[-1].act,
                                 rew=steps[-1].rew, data=steps[-1].data,
                                 done=False, truncated=True)
        self._compare(steps)

    def test_continuous(self):
        self._compare(_mk_steps(6, act_dim=3, discrete=False), act_dim=3,
                      discrete=False)


class TestFuzzParity:
    def test_random_trajectories(self, decoder):
        rng = np.random.default_rng(42)
        for trial in range(60):
            n = int(rng.integers(1, 24))
            obs_dim = int(rng.integers(1, 9))
            act_dim = int(rng.integers(1, 5))
            discrete = bool(rng.integers(2))
            with_mask = bool(rng.integers(2))
            with_aux = bool(rng.integers(2))
            steps = _mk_steps(n, obs_dim, act_dim, discrete, with_mask,
                              with_aux, seed=trial)
            if rng.integers(2):  # add a flag_last_action marker
                steps[-1] = ActionRecord(
                    obs=steps[-1].obs, act=steps[-1].act, rew=steps[-1].rew,
                    mask=steps[-1].mask, data=steps[-1].data, done=False)
                marker_obs = (rng.standard_normal(obs_dim).astype(np.float32)
                              if rng.integers(2) else None)
                steps.append(ActionRecord(
                    obs=marker_obs, rew=float(rng.standard_normal()),
                    done=True, truncated=bool(rng.integers(2))))
            _assert_pad_parity(steps, decoder, obs_dim=obs_dim,
                               act_dim=act_dim, discrete=discrete)
