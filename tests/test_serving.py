"""Disaggregated batched-inference serving plane (runtime/inference.py).

The acceptance surface of ISSUE 10:

* the dynamic-batching queue closes on BOTH triggers (max_batch = size,
  batch_timeout_ms = deadline) and buckets dispatch shapes via
  pick_bucket, with padded rows provably inert;
* queue-limit overload answers a typed NACK_OVERLOADED with retry-after,
  and the thin client honors it without charging its circuit breaker;
* every batch is served by exactly ONE params version even against a
  racing swapper (the single read under the shared swap gate);
* served-mode parity: a RemoteActorClient's actions are BIT-identical to
  a local PolicyActor holding the same params version and seed — and the
  shipped trajectory bytes are byte-identical — on both the zmq ROUTER
  plane and the in-band grpc GetActions RPC;
* the agent.infer fault site + a killed/restarted service heal through
  the shared RetryPolicy/breaker without wedging the env loop.
"""

import json
import os
import threading
import time

import jax
import numpy as np
import pytest

from _util import (
    assert_aux_equal,
    assert_episode_payloads_match,
    free_port,
)

pytestmark = pytest.mark.serving


@pytest.fixture
def fresh_registry():
    from relayrl_tpu import telemetry
    from relayrl_tpu.transport.retry import reset_metrics_for_tests

    reg = telemetry.Registry(run_id="serving-test")
    telemetry.set_registry(reg)
    reset_metrics_for_tests()
    yield reg
    telemetry.reset_for_tests()
    reset_metrics_for_tests()


def _reinforce_bundle(scratch, obs_dim=6, act_dim=3):
    from relayrl_tpu.algorithms import build_algorithm

    algo = build_algorithm(
        "REINFORCE", env_dir=scratch, obs_dim=obs_dim, act_dim=act_dim,
        hidden_sizes=[16], traj_per_epoch=4, with_vf_baseline=True)
    return algo.bundle()


def _versioned_bundle(bundle, version):
    """Params whose value head outputs exactly ``version`` for any obs:
    aux['v'] reveals which params produced each action (the
    test_vector_actor atomic-swap probe)."""
    import copy

    from relayrl_tpu.types.model_bundle import ModelBundle

    params = jax.tree_util.tree_map(np.asarray, bundle.params)
    params = copy.deepcopy(params)
    params["params"]["vf_head"]["kernel"] = np.zeros_like(
        params["params"]["vf_head"]["kernel"])
    params["params"]["vf_head"]["bias"] = np.full_like(
        params["params"]["vf_head"]["bias"], float(version))
    for layer in params["params"]["vf_trunk"].values():
        layer["bias"] = np.zeros_like(layer["bias"])
    return ModelBundle(arch=dict(bundle.arch), params=params,
                       version=version)


def _transformer_bundle(obs_dim=5, act_dim=3, max_seq_len=8, seed=0):
    """A tiny windowed (sequence) policy bundle — the arch the serving
    plane refused before serving v2."""
    from relayrl_tpu.models import build_policy
    from relayrl_tpu.types.model_bundle import ModelBundle

    arch = {"kind": "transformer_discrete", "obs_dim": obs_dim,
            "act_dim": act_dim, "d_model": 16, "n_layers": 1,
            "n_heads": 2, "max_seq_len": max_seq_len}
    policy = build_policy(arch)
    return ModelBundle(version=1, arch=dict(arch),
                       params=policy.init_params(jax.random.PRNGKey(seed)))


class _SessionDriver:
    """Hand-rolled serving-v2 session client against a live service:
    carries the PRNG key, the monotonic push cursor, the episode-start
    flag, and the episode mirror — the protocol RemoteActorClient speaks,
    laid bare so tests can replay/evict/desync at will."""

    def __init__(self, svc, sid, seed):
        self.svc = svc
        self.sid = sid
        self.key = np.asarray(jax.random.PRNGKey(seed))
        self.step = 0
        self.episode_start = True
        self.mirror: list = []
        self._req = 0

    def raw(self, obs, with_win=False, step=None, key=None, timeout=30):
        """One request (no client state advance): returns the decoded
        reply."""
        self._req += 1
        win = np.stack(self.mirror) if (with_win and self.mirror) else None
        done, box = _submit(
            self.svc, self.key if key is None else key, obs,
            req_id=self._req, agent_id=self.sid,
            session=self.sid, reset=self.episode_start, window=win,
            step=self.step + 1 if step is None else step)
        assert done.wait(timeout)
        return box["reply"]

    def act(self, obs):
        """One successful action with the full resync protocol: on a
        SESSION_EVICTED nack, resend with the episode window attached."""
        from relayrl_tpu.transport.base import NACK_SESSION_EVICTED

        reply = self.raw(obs)
        if reply["code"] == NACK_SESSION_EVICTED:
            reply = self.raw(obs, with_win=True)
        assert reply["code"] == 1, reply.get("error")
        self.key = np.frombuffer(reply["key"], self.key.dtype).copy()
        self.step += 1
        self.episode_start = False
        ctx = reply.get("ctx")
        assert ctx is not None
        self.mirror.append(np.asarray(obs, np.float32))
        if len(self.mirror) > ctx:
            del self.mirror[:len(self.mirror) - ctx]
        return reply

    def end_episode(self):
        self.episode_start = True
        self.mirror = []


def _submit(svc, key, obs, req_id=1, agent_id="t", mask=None,
            session=None, reset=False, window=None, step=0):
    """One decoded request against a live service; returns (event, box) —
    box['reply'] is the decoded reply once event fires. ``session`` /
    ``reset`` / ``window`` / ``step`` are the serving-v2 per-session
    fields (sequence policies)."""
    from relayrl_tpu.transport.serving import (
        pack_infer_request,
        unpack_infer_reply,
    )

    box: dict = {}
    done = threading.Event()

    def reply(b):
        box["reply"] = unpack_infer_reply(b)
        done.set()

    svc.handle_request(
        pack_infer_request(agent_id, req_id, key, obs, mask,
                           session=session, reset=reset, window=window,
                           step=step), reply)
    return done, box


class TestServingCodec:
    def test_scalar_and_array_round_trip(self):
        """0-d actions/aux must survive the wire as exact 0-d ndarrays
        (np.ascontiguousarray silently promotes them to 1-d — the shape
        is captured first)."""
        from relayrl_tpu.transport.serving import (
            pack_action_reply,
            unpack_infer_reply,
        )

        act = np.asarray(np.int32(2))
        aux = {"logp_a": np.asarray(np.float32(-1.5)),
               "vec": np.arange(3, dtype=np.float32)}
        key = np.array([1, 2], np.uint32)
        out = unpack_infer_reply(pack_action_reply(7, 3, act, key, aux))
        assert out["req"] == 7 and out["ver"] == 3
        assert out["act"].shape == () and out["act"].dtype == np.int32
        assert out["aux"]["logp_a"].shape == ()
        assert out["aux"]["logp_a"].dtype == np.float32
        assert np.array_equal(out["aux"]["vec"], aux["vec"])
        assert np.frombuffer(out["key"], np.uint32).tolist() == [1, 2]

    def test_session_fields_round_trip(self):
        """The serving-v2 wire fields (session id, reset flag, push
        cursor, resync window, reply ctx) survive the codec — and stay
        ABSENT on v1 frames so old clients and old services interop."""
        from relayrl_tpu.transport.serving import (
            pack_action_reply,
            pack_infer_request,
            unpack_infer_reply,
            unpack_infer_request,
        )

        key = np.asarray(jax.random.PRNGKey(1))
        obs = np.arange(5, dtype=np.float32)
        win = np.arange(10, dtype=np.float32).reshape(2, 5)
        out = unpack_infer_request(pack_infer_request(
            "a", 7, key, obs, None, session="a#L001", reset=True,
            window=win, step=3))
        assert out["sid"] == "a#L001" and out["rst"] is True
        assert out["stp"] == 3
        assert np.array_equal(out["win"], win)
        v1 = unpack_infer_request(pack_infer_request("a", 7, key, obs,
                                                     None))
        assert v1["sid"] is None and v1["rst"] is False
        assert v1["stp"] == 0 and v1["win"] is None
        reply = unpack_infer_reply(pack_action_reply(
            7, 3, np.asarray(np.int32(1)), np.array([1, 2], np.uint32),
            {}, ctx=8))
        assert reply["ctx"] == 8
        assert "ctx" not in unpack_infer_reply(pack_action_reply(
            7, 3, np.asarray(np.int32(1)), np.array([1, 2], np.uint32),
            {}))

    def test_request_round_trip_with_mask_and_uint8(self):
        from relayrl_tpu.transport.serving import (
            pack_infer_request,
            unpack_infer_request,
        )

        key = np.asarray(jax.random.PRNGKey(0))
        obs = np.arange(12, dtype=np.uint8).reshape(3, 4)
        mask = np.array([1.0, 0.0], np.float32)
        out = unpack_infer_request(
            pack_infer_request("agent-1", 42, key, obs, mask))
        assert out["id"] == "agent-1" and out["req"] == 42
        assert out["obs"].dtype == np.uint8 and out["obs"].shape == (3, 4)
        assert np.array_equal(out["obs"], obs)
        assert np.array_equal(out["mask"], mask)
        assert np.array_equal(out["key"], key)

    def test_wave_request_rows_match_single_wire(self):
        """Coalesced wave frames are a pure wire optimization: every
        decoded row is field-identical to the same request on the
        single-request wire (bit-exact obs/key/mask, same session
        columns)."""
        from relayrl_tpu.transport.serving import (
            pack_infer_request,
            pack_infer_wave,
            unpack_infer_any,
            unpack_infer_request,
        )

        rng = np.random.default_rng(3)
        entries = []
        for i in range(4):
            entries.append({
                "id": f"a#L{i:03d}", "req": 100 + i,
                "key": np.asarray(jax.random.PRNGKey(i)),
                "obs": rng.standard_normal(6).astype(np.float32),
                "mask": None, "sid": f"a#L{i:03d}", "stp": i + 1,
                "rst": i == 0})
        rows = unpack_infer_any(pack_infer_wave(entries))
        assert len(rows) == 4
        for e, row in zip(entries, rows):
            single = unpack_infer_request(pack_infer_request(
                e["id"], e["req"], e["key"], e["obs"], None,
                session=e["sid"], reset=e["rst"], step=e["stp"]))
            for k in ("id", "req", "sid", "rst", "stp", "win", "mask"):
                assert row[k] == single[k], k
            assert np.array_equal(row["obs"], single["obs"])
            assert row["obs"].dtype == single["obs"].dtype
            assert np.array_equal(row["key"], single["key"])
        # A single frame still decodes through the same entry point.
        assert unpack_infer_any(pack_infer_request(
            "b", 9, entries[0]["key"], entries[0]["obs"],
            None))[0]["req"] == 9

    def test_wave_reply_rows_match_single_wire(self):
        from relayrl_tpu.transport.serving import (
            pack_action_reply,
            pack_reply_wave,
            unpack_infer_reply,
            unpack_reply_any,
        )

        acts = np.asarray([2, 0, 1], np.int32)
        keys = np.asarray(jax.random.split(jax.random.PRNGKey(1), 3))
        aux = {"logp_a": np.asarray([-0.1, -0.2, -0.3], np.float32),
               "v": np.asarray([0.5, 0.6, 0.7], np.float32)}
        rows = unpack_reply_any(pack_reply_wave(
            [11, 12, 13], 5, acts, keys, aux, ctx=16))
        assert len(rows) == 3
        for i, row in enumerate(rows):
            single = unpack_infer_reply(pack_action_reply(
                11 + i, 5, acts[i, ...], keys[i],
                {k: v[i, ...] for k, v in aux.items()}, ctx=16))
            assert row["req"] == single["req"]
            assert row["code"] == single["code"] == 1
            assert row["ver"] == single["ver"] == 5
            assert row["ctx"] == single["ctx"] == 16
            assert row["key"] == single["key"]  # raw key bytes, verbatim
            assert np.array_equal(row["act"], single["act"])
            assert row["act"].dtype == single["act"].dtype
            assert row["act"].shape == single["act"].shape  # 0-d stays 0-d
            for k in aux:
                assert np.array_equal(row["aux"][k], single["aux"][k])
                assert row["aux"][k].dtype == single["aux"][k].dtype

    def test_malformed_request_answers_error(self, tmp_cwd, fresh_registry):
        from relayrl_tpu.runtime.inference import InferenceService

        bundle = _reinforce_bundle(str(tmp_cwd))
        svc = InferenceService(bundle, max_batch=2, batch_timeout_ms=1.0)
        from relayrl_tpu.transport.serving import unpack_infer_reply

        got = []
        svc.handle_request(b"\x81\xa3junk", lambda b: got.append(
            unpack_infer_reply(b)))
        assert got and got[0]["code"] == 0


class TestBatchingQueue:
    def test_size_trigger_close(self, tmp_cwd, fresh_registry):
        """max_batch requests close the batch immediately (reason
        "size"), long before the deadline."""
        from relayrl_tpu.runtime.inference import InferenceService

        bundle = _reinforce_bundle(str(tmp_cwd))
        svc = InferenceService(bundle, max_batch=4, batch_timeout_ms=5000.0)
        svc.start()
        try:
            keys = np.asarray(jax.random.split(jax.random.PRNGKey(0), 4))
            obs = np.random.default_rng(0).standard_normal(
                (4, 6)).astype(np.float32)
            # Warm the bucket-4 compile OUTSIDE the timed window (the
            # first dispatch traces + compiles; this test times the batch
            # CLOSE, not XLA).
            warm = [_submit(svc, keys[i], obs[i], req_id=100 + i)
                    for i in range(4)]
            for done, _ in warm:
                assert done.wait(60)
            t0 = time.monotonic()
            waits = [_submit(svc, keys[i], obs[i], req_id=i + 1)
                     for i in range(4)]
            for done, box in waits:
                assert done.wait(10), "size-triggered batch never closed"
                assert box["reply"]["code"] == 1
            assert time.monotonic() - t0 < 2.0, \
                "size close waited toward the deadline"
            assert svc._m_batches["size"].total() == 2
            assert svc._m_batches["deadline"].total() == 0
        finally:
            svc.stop()

    def test_deadline_trigger_close(self, tmp_cwd, fresh_registry):
        """A short batch closes batch_timeout_ms after its FIRST request
        (reason "deadline") instead of waiting for max_batch forever."""
        from relayrl_tpu.runtime.inference import InferenceService

        bundle = _reinforce_bundle(str(tmp_cwd))
        svc = InferenceService(bundle, max_batch=64, batch_timeout_ms=40.0)
        svc.start()
        try:
            key = np.asarray(jax.random.PRNGKey(1))
            obs = np.zeros(6, np.float32)
            t0 = time.monotonic()
            done, box = _submit(svc, key, obs)
            assert done.wait(10), "deadline-triggered batch never closed"
            dt = time.monotonic() - t0
            assert box["reply"]["code"] == 1
            assert dt >= 0.030, f"closed before the deadline ({dt:.3f}s)"
            assert svc._m_batches["deadline"].total() == 1
        finally:
            svc.stop()

    def test_bucket_selection_and_padding_inert(self, tmp_cwd,
                                                fresh_registry):
        """3 requests dispatch at bucket 4 (smallest bucket >= n), and
        the padded row cannot perturb the real rows: every reply carries
        the unpadded single's action and next rng key exactly, and its
        float outputs to rounding (the bucket-4 dispatch and the single
        are two XLA programs: ``_util.MODEL_OUTPUT_RTOL``)."""
        from relayrl_tpu.runtime.inference import InferenceService
        from relayrl_tpu.runtime.policy_actor import _fuse_rng

        bundle = _reinforce_bundle(str(tmp_cwd))
        svc = InferenceService(bundle, max_batch=8, batch_timeout_ms=30.0,
                               buckets=[1, 2, 4, 8])
        shapes = []
        inner = svc._batched_fn

        def spying(params, keys, obs, masks, explore):
            shapes.append(tuple(np.asarray(keys).shape))
            return inner(params, keys, obs, masks, explore)

        svc._batched_fn = spying
        svc.start()
        try:
            keys = np.asarray(jax.random.split(jax.random.PRNGKey(3), 3))
            obs = np.random.default_rng(1).standard_normal(
                (3, 6)).astype(np.float32)
            waits = [_submit(svc, keys[i], obs[i], req_id=i + 1)
                     for i in range(3)]
            single = jax.jit(_fuse_rng(svc.policy.step))
            for i, (done, box) in enumerate(waits):
                assert done.wait(10)
                reply = box["reply"]
                assert reply["code"] == 1
                act, aux, nk = single(bundle.params, keys[i], obs[i], None)
                assert np.array_equal(reply["act"], np.asarray(act))
                for k in aux:
                    assert_aux_equal(reply["aux"][k], aux[k], k)
                assert np.array_equal(
                    np.frombuffer(reply["key"], np.uint32),
                    np.asarray(nk).ravel())
            assert shapes and shapes[0][0] == 4, \
                f"expected bucket-4 dispatch, saw {shapes}"
        finally:
            svc.stop()

    def test_queue_limit_overload_nack(self, tmp_cwd, fresh_registry):
        """Beyond serving.queue_limit, submissions answer the typed
        NACK_OVERLOADED with a retry-after hint instead of queueing
        unboundedly (the worker is NOT running, so nothing drains)."""
        from relayrl_tpu.runtime.inference import InferenceService
        from relayrl_tpu.transport.base import NACK_OVERLOADED

        bundle = _reinforce_bundle(str(tmp_cwd))
        svc = InferenceService(bundle, max_batch=4, batch_timeout_ms=5.0,
                               queue_limit=2, retry_after_s=0.25)
        key = np.asarray(jax.random.PRNGKey(0))
        obs = np.zeros(6, np.float32)
        waits = [_submit(svc, key, obs, req_id=i + 1) for i in range(3)]
        done, box = waits[2]
        assert done.wait(5), "overload nack never delivered"
        assert box["reply"]["code"] == NACK_OVERLOADED
        assert box["reply"]["retry_after_s"] == pytest.approx(0.25)
        assert svc._m_rejected.total() == 1
        assert not waits[0][0].is_set() and not waits[1][0].is_set()
        # stop() answers the parked requests with a retryable nack too —
        # a restarting service must not leave clients hanging.
        svc.stop()
        for done_i, box_i in waits[:2]:
            assert done_i.wait(5)
            assert box_i["reply"]["code"] == NACK_OVERLOADED

    def test_single_params_version_per_batch_under_racing_swapper(
            self, tmp_cwd, fresh_registry):
        """A swapper thread hammers version-coded params while requests
        stream: every reply's aux['v'] must equal its reply 'ver' — no
        request is ever served params from a version other than the one
        its batch read under the gate."""
        from relayrl_tpu.runtime.inference import InferenceService

        base = _reinforce_bundle(str(tmp_cwd))
        svc = InferenceService(_versioned_bundle(base, 1), max_batch=4,
                               batch_timeout_ms=2.0)
        svc.start()
        stop = threading.Event()
        next_version = [2]

        def swapper():
            while not stop.is_set():
                svc.maybe_swap(_versioned_bundle(base, next_version[0]))
                next_version[0] += 1

        t = threading.Thread(target=swapper, daemon=True)
        t.start()
        try:
            key = np.asarray(jax.random.PRNGKey(5))
            obs = np.random.default_rng(2).standard_normal(6).astype(
                np.float32)
            mismatches = []
            for i in range(40):
                done, box = _submit(svc, key, obs, req_id=i + 1)
                assert done.wait(10)
                reply = box["reply"]
                assert reply["code"] == 1
                v = float(reply["aux"]["v"])
                if v != float(reply["ver"]):
                    mismatches.append((reply["ver"], v))
                key = np.frombuffer(reply["key"], np.uint32)
            assert not mismatches, \
                f"replies served by params of another version: {mismatches[:3]}"
            assert svc.version >= 2  # swaps actually landed mid-run
        finally:
            stop.set()
            t.join(timeout=5)
            svc.stop()

    def test_stale_requests_nacked_unserved(self, tmp_cwd,
                                            fresh_registry):
        """Ghost-work guard: requests that outlive serving.stale_after_s
        in the queue (their client timed out and retried) are answered
        with a retryable nack at batch-gather time, never dispatched —
        under backlog a retry round must not double-serve."""
        from relayrl_tpu.runtime.inference import InferenceService
        from relayrl_tpu.transport.base import NACK_OVERLOADED

        bundle = _reinforce_bundle(str(tmp_cwd))
        svc = InferenceService(bundle, max_batch=4, batch_timeout_ms=5.0,
                               stale_after_s=0.2)
        key = np.asarray(jax.random.PRNGKey(0))
        obs = np.zeros(6, np.float32)
        # Enqueue while the worker is NOT running, let them go stale,
        # then start the worker: the gather pass must nack both without
        # serving them.
        waits = [_submit(svc, key, obs, req_id=i + 1) for i in range(2)]
        time.sleep(0.4)
        svc.start()
        try:
            for done, box in waits:
                assert done.wait(10), "stale request never answered"
                assert box["reply"]["code"] == NACK_OVERLOADED
                assert "stale" in box["reply"]["error"]
            assert svc._m_stale.total() == 2
            assert (svc._m_batches["size"].total()
                    + svc._m_batches["deadline"].total()) == 0
            # fresh traffic still serves normally afterwards
            done, box = _submit(svc, key, obs, req_id=9)
            assert done.wait(30) and box["reply"]["code"] == 1
        finally:
            svc.stop()

    def test_sequence_requests_without_session_id_get_pointed_error(
            self, tmp_cwd, fresh_registry):
        """A v1 (session-less) request against a sequence policy answers
        with an error naming serving.max_sessions — the serving-v2
        replacement for the old constructor refusal."""
        from relayrl_tpu.runtime.inference import InferenceService

        svc = InferenceService(_transformer_bundle(), max_batch=1,
                               batch_timeout_ms=1.0)
        svc.start()
        try:
            key = np.asarray(jax.random.PRNGKey(0))
            obs = np.zeros(5, np.float32)
            done, box = _submit(svc, key, obs)
            assert done.wait(30)
            assert box["reply"]["code"] == 0
            assert "serving.max_sessions" in box["reply"]["error"]
        finally:
            svc.stop()

    def test_install_params_owns_memory(self, tmp_cwd, fresh_registry):
        """The colocated publish feed must copy: mutating the publisher's
        host tree after install must not change served params."""
        from relayrl_tpu.runtime.inference import InferenceService

        base = _reinforce_bundle(str(tmp_cwd))
        svc = InferenceService(_versioned_bundle(base, 1), max_batch=1,
                               batch_timeout_ms=1.0)
        svc.start()
        try:
            host_tree = jax.tree_util.tree_map(
                np.array, _versioned_bundle(base, 2).params)
            assert svc.install_params(2, base.arch, host_tree)
            host_tree["params"]["vf_head"]["bias"][:] = 777.0
            key = np.asarray(jax.random.PRNGKey(0))
            done, box = _submit(svc, key, np.zeros(6, np.float32))
            assert done.wait(10)
            assert float(box["reply"]["aux"]["v"]) == 2.0
        finally:
            svc.stop()


class _FakeServingClient:
    """Scripted reply stream for the thin client's retry loop."""

    def __init__(self, script):
        self.script = list(script)
        self.calls = 0

    def request(self, payload, req_id, timeout_s):
        self.calls += 1
        step = self.script.pop(0) if self.script else self.script_default
        if isinstance(step, Exception):
            raise step
        out = dict(step)
        out.setdefault("req", req_id)
        return out

    def close(self):
        pass


def _bare_client(fake, infer_deadline_s=5.0, request_timeout_s=0.2):
    """A RemoteActorClient wired straight to a fake serving channel —
    the retry/breaker/nack loop under test, no sockets."""
    from relayrl_tpu import telemetry
    from relayrl_tpu.runtime.inference import RemoteActorClient
    from relayrl_tpu.transport.retry import CircuitBreaker, RetryPolicy

    client = object.__new__(RemoteActorClient)
    client._serving = fake
    client._breaker = CircuitBreaker("test", failure_threshold=3,
                                     reset_timeout_s=0.2)
    client._retry = RetryPolicy(base_delay_s=0.01, max_delay_s=0.05)
    client._fault_infer = None
    client._rng = np.asarray(jax.random.PRNGKey(0))
    client._req_counter = 0
    client._request_timeout_s = request_timeout_s
    client._infer_deadline_s = infer_deadline_s
    client.version = -1
    client._session_id = "bare"
    client._session_step = 0
    client._episode_start = True
    client._mirror = []
    client._replica_addrs = None
    client._replica_idx = 0
    client._replica_fail_streak = 0
    client._serving_overrides = {}

    class _T:
        identity = "bare"

    client.transport = _T()
    reg = telemetry.get_registry()
    client._m_request_s = reg.histogram("relayrl_serving_client_request_seconds", "t")
    client._m_retries = reg.counter("relayrl_serving_client_retries_total", "t")
    client._m_nacked = reg.counter("relayrl_serving_client_nacked_total", "t")
    client._m_resyncs = reg.counter("relayrl_serving_client_resyncs_total", "t")
    client._m_reroutes = reg.counter("relayrl_serving_client_reroutes_total", "t")
    return client


def _ok_reply(act=1, ver=3):
    key = np.array([9, 9], np.uint32)
    return {"code": 1, "ver": ver, "act": np.asarray(np.int32(act)),
            "key": key.tobytes(), "aux": {"v": np.asarray(np.float32(0.5))}}


class TestClientRetry:
    def test_overload_nack_honors_retry_after_without_breaker_charge(
            self, fresh_registry):
        from relayrl_tpu.transport.base import NACK_OVERLOADED

        fake = _FakeServingClient([
            {"code": NACK_OVERLOADED, "error": "full",
             "retry_after_s": 0.15},
            _ok_reply(),
        ])
        client = _bare_client(fake)
        t0 = time.monotonic()
        act, aux = client._infer(np.zeros(4, np.float32), None)
        dt = time.monotonic() - t0
        assert int(act) == 1 and client.version == 3
        assert dt >= 0.14, f"retry-after not honored ({dt:.3f}s)"
        assert fake.calls == 2
        assert client._breaker.state == "closed"
        assert client._m_nacked.total() == 1
        assert client._m_retries.total() == 0  # nacks are not failures

    def test_timeouts_charge_breaker_then_heal(self, fresh_registry):
        fake = _FakeServingClient([
            TimeoutError("t"), TimeoutError("t"), TimeoutError("t"),
            _ok_reply(ver=7),
        ])
        client = _bare_client(fake)
        act, aux = client._infer(np.zeros(4, np.float32), None)
        assert int(act) == 1 and client.version == 7
        # 3 failures opened the breaker (threshold 3); the half-open
        # probe then healed it — the env loop waited, never wedged.
        assert client._m_retries.total() == 3
        assert client._breaker.state == "closed"

    def test_deadline_exhaustion_raises(self, fresh_registry):
        fake = _FakeServingClient([])
        fake.script_default = None

        class _AlwaysTimeout(_FakeServingClient):
            def request(self, payload, req_id, timeout_s):
                self.calls += 1
                raise TimeoutError("dead service")

        client = _bare_client(_AlwaysTimeout([]), infer_deadline_s=0.6)
        with pytest.raises(RuntimeError, match="budget"):
            client._infer(np.zeros(4, np.float32), None)

    def test_error_reply_retries(self, fresh_registry):
        """A code-0 error (corrupt request drill: the service's decode
        guard answered) is retryable, not fatal."""
        fake = _FakeServingClient([
            {"code": 0, "error": "malformed inference request"},
            _ok_reply(ver=4),
        ])
        client = _bare_client(fake)
        act, _ = client._infer(np.zeros(4, np.float32), None)
        assert int(act) == 1 and client.version == 4
        assert fake.calls == 2


def _serving_stack(tmp_path, server_type="zmq", max_batch=4,
                   batch_timeout_ms=3.0, traj_per_epoch=64,
                   spool_entries=512):
    """One TrainingServer with serving enabled + its address block, on a
    fresh set of ports."""
    from relayrl_tpu.runtime.server import TrainingServer

    scratch = str(tmp_path)
    cfg_path = os.path.join(scratch, "serving_cfg.json")
    with open(cfg_path, "w") as f:
        json.dump({"serving": {"enabled": True, "max_batch": max_batch,
                               "batch_timeout_ms": batch_timeout_ms},
                   "actor": {"spool_entries": spool_entries}}, f)
    if server_type == "grpc":
        addrs = {"bind_addr": f"127.0.0.1:{free_port()}",
                 "native_grpc": False}
        client_addrs = {"server_addr": addrs["bind_addr"], "probe": False}
    else:
        addrs = {
            "agent_listener_addr": f"tcp://127.0.0.1:{free_port()}",
            "trajectory_addr": f"tcp://127.0.0.1:{free_port()}",
            "model_pub_addr": f"tcp://127.0.0.1:{free_port()}",
            "serving_addr": f"tcp://127.0.0.1:{free_port()}",
        }
        client_addrs = {
            "agent_listener_addr": addrs["agent_listener_addr"],
            "trajectory_addr": addrs["trajectory_addr"],
            "model_sub_addr": addrs["model_pub_addr"],
            "serving_addr": addrs["serving_addr"],
            "probe": False,
        }
    server = TrainingServer(
        "REINFORCE", obs_dim=6, act_dim=3, env_dir=scratch,
        config_path=cfg_path, server_type=server_type,
        hyperparams={"traj_per_epoch": traj_per_epoch,
                     "hidden_sizes": [16], "with_vf_baseline": True},
        **addrs)
    return server, cfg_path, client_addrs


class TestServedParityE2E:
    @pytest.mark.parametrize("server_type", ["zmq", "grpc"])
    def test_bit_identical_served_vs_local(self, tmp_cwd, fresh_registry,
                                           server_type):
        """The acceptance lock: at a pinned params version, a thin
        client's action stream (and its shipped episode BYTES) are
        identical to a local PolicyActor with the same seed holding the
        same bundle — on the zmq ROUTER plane and the grpc GetActions
        RPC."""
        from relayrl_tpu.runtime.inference import RemoteActorClient
        from relayrl_tpu.runtime.policy_actor import PolicyActor
        from relayrl_tpu.types.model_bundle import ModelBundle

        server, cfg_path, client_addrs = _serving_stack(
            tmp_cwd, server_type=server_type, traj_per_epoch=10_000)
        try:
            bundle = ModelBundle(
                version=server.algorithm.version,
                arch=dict(server.algorithm.bundle().arch),
                params=server.algorithm.bundle().params)
            sent_local, sent_remote = [], []
            local = PolicyActor(bundle, seed=23,
                                on_send=lambda p: sent_local.append(p))
            client = RemoteActorClient(
                config_path=cfg_path, server_type=server_type, seed=23,
                **client_addrs)
            client.trajectory._on_send = lambda p: sent_remote.append(p)
            rng = np.random.default_rng(11)
            for i in range(10):
                obs = rng.standard_normal(6).astype(np.float32)
                reward = 0.0 if i == 0 else 0.5
                r1 = local.request_for_action(obs, reward=reward)
                r2 = client.request_for_action(obs, reward=reward)
                assert np.array_equal(np.asarray(r1.act),
                                      np.asarray(r2.act)), f"step {i}"
                assert r1.act.dtype == r2.act.dtype
                assert r1.act.shape == r2.act.shape
                for k in r1.data:
                    assert np.array_equal(np.asarray(r1.data[k]),
                                          np.asarray(r2.data[k])), (i, k)
                    assert r1.data[k].dtype == r2.data[k].dtype, (i, k)
            local.flag_last_action(1.0, terminated=True)
            client.flag_last_action(1.0, terminated=True)
            assert sent_local == sent_remote and len(sent_local) == 1, \
                "served episode bytes differ from the local actor's"
            client.disable_agent()
        finally:
            server.disable_server()

    def test_masked_served_parity(self, tmp_cwd, fresh_registry):
        from relayrl_tpu.runtime.inference import RemoteActorClient
        from relayrl_tpu.runtime.policy_actor import PolicyActor
        from relayrl_tpu.types.model_bundle import ModelBundle

        server, cfg_path, client_addrs = _serving_stack(
            tmp_cwd, traj_per_epoch=10_000)
        try:
            bundle = ModelBundle(
                version=server.algorithm.version,
                arch=dict(server.algorithm.bundle().arch),
                params=server.algorithm.bundle().params)
            local = PolicyActor(bundle, seed=4)
            client = RemoteActorClient(config_path=cfg_path, seed=4,
                                       **client_addrs)
            mask = np.array([1.0, 0.0, 1.0], np.float32)
            rng = np.random.default_rng(3)
            for _ in range(5):
                obs = rng.standard_normal(6).astype(np.float32)
                r1 = local.request_for_action(obs, mask=mask)
                r2 = client.request_for_action(obs, mask=mask)
                assert np.array_equal(np.asarray(r1.act),
                                      np.asarray(r2.act))
                assert int(np.asarray(r2.act)) != 1  # mask respected
            client.disable_agent()
        finally:
            server.disable_server()

    def test_trajectories_train_and_model_version_advances(
            self, tmp_cwd, fresh_registry):
        """The full loop: thin-client episodes reach the learner through
        the UNCHANGED trajectory plane, updates publish, and the
        colocated service starts serving the new version (visible as the
        client's model_version advancing) — with batching provably
        active (occupancy histogram saw > 1)."""
        from relayrl_tpu.runtime.inference import RemoteActorClient

        server, cfg_path, client_addrs = _serving_stack(
            tmp_cwd, traj_per_epoch=2, max_batch=4, batch_timeout_ms=4.0)
        try:
            clients = [RemoteActorClient(config_path=cfg_path, seed=s,
                                         identity=f"thin-{s}",
                                         **client_addrs)
                       for s in range(3)]
            stop = threading.Event()

            def drive(client, seed):
                rng = np.random.default_rng(seed)
                while not stop.is_set():
                    obs = rng.standard_normal(6).astype(np.float32)
                    for _ in range(8):
                        client.request_for_action(obs, reward=1.0)
                        obs = rng.standard_normal(6).astype(np.float32)
                        if stop.is_set():
                            break
                    client.flag_last_action(1.0, terminated=True)

            threads = [threading.Thread(target=drive, args=(c, i),
                                        daemon=True)
                       for i, c in enumerate(clients)]
            for t in threads:
                t.start()
            deadline = time.monotonic() + 60
            while (time.monotonic() < deadline
                   and (server.stats["updates"] < 2
                        or max(c.model_version for c in clients) < 2)):
                time.sleep(0.1)
            stop.set()
            for t in threads:
                t.join(timeout=30)
            assert server.stats["updates"] >= 2, "thin-client episodes never trained"
            assert max(c.model_version for c in clients) >= 2, \
                "the colocated service never served the published version"
            occ = server.inference._m_occupancy.totals()
            counts, total, n = occ
            assert n > 0 and total / n > 1.0, \
                f"batching never engaged (mean occupancy {total}/{n})"
            for c in clients:
                c.disable_agent()
        finally:
            server.disable_server()


class TestFaultPlaneAndHeal:
    # Wall re-fit: both single-service heal drills ride the slow tier —
    # the fast tier's serving-heal representative is now the replica
    # SIGKILL re-route drill in TestStreamingChannel (kills a live host,
    # heals through re-route + session resync).
    @pytest.mark.slow
    def test_agent_infer_fault_site_drop_and_corrupt_heal(
            self, tmp_cwd, fresh_registry):
        """agent.infer chaos: deterministic drops + corruption on the
        request plane — every action still lands (drop → timeout retry,
        corrupt → service decode-guard error reply → retry), and the
        injection ledger counted the faults."""
        from relayrl_tpu import faults
        from relayrl_tpu.faults import FaultPlan
        from relayrl_tpu.runtime.inference import (
            InferenceService,
            RemoteActorClient,
        )

        bundle = _reinforce_bundle(str(tmp_cwd))
        svc = InferenceService(bundle, max_batch=2, batch_timeout_ms=2.0)
        addr = f"tcp://127.0.0.1:{free_port()}"
        svc.bind_zmq(addr)
        svc.start()
        plan = FaultPlan.from_dict({"seed": 3, "rules": [
            {"site": "agent.infer", "op": "drop", "prob": 0.2},
            {"site": "agent.infer", "op": "corrupt", "prob": 0.2},
        ]})
        faults.install_plan(plan)
        try:
            cfg_path = os.path.join(str(tmp_cwd), "cfg.json")
            with open(cfg_path, "w") as f:
                json.dump({"actor": {"spool_entries": 0},
                           "serving": {"request_timeout_s": 0.3}}, f)
            client = RemoteActorClient(
                config_path=cfg_path, seed=1, serving_addr=addr,
                probe=False,
                agent_listener_addr=f"tcp://127.0.0.1:{free_port()}",
                trajectory_addr=f"tcp://127.0.0.1:{free_port()}",
                model_sub_addr=f"tcp://127.0.0.1:{free_port()}")
            rng = np.random.default_rng(0)
            for _ in range(30):
                client.request_for_action(
                    rng.standard_normal(6).astype(np.float32), reward=1.0)
            site = plan.site("agent.infer")
            assert site is not None and site.injected > 0, \
                "the drill injected nothing"
            client.disable_agent()
        finally:
            faults.install_plan(None)
            svc.stop()

    @pytest.mark.slow
    def test_killed_service_heals_clients_without_wedging(
            self, tmp_cwd, fresh_registry):
        """The chaos drill: the inference service dies mid-run and
        restarts; a stepping client rides the breaker/backoff through
        the outage and completes every action — the env loop never
        wedges and never loses a step."""
        from relayrl_tpu.runtime.inference import (
            InferenceService,
            RemoteActorClient,
        )

        bundle = _reinforce_bundle(str(tmp_cwd))
        addr = f"tcp://127.0.0.1:{free_port()}"
        svc = InferenceService(bundle, max_batch=2, batch_timeout_ms=2.0)
        svc.bind_zmq(addr)
        svc.start()
        cfg_path = os.path.join(str(tmp_cwd), "cfg.json")
        with open(cfg_path, "w") as f:
            json.dump({"actor": {"spool_entries": 0},
                       "serving": {"request_timeout_s": 0.25,
                                   "infer_deadline_s": 60.0}}, f)
        client = RemoteActorClient(
            config_path=cfg_path, seed=2, serving_addr=addr, probe=False,
            agent_listener_addr=f"tcp://127.0.0.1:{free_port()}",
            trajectory_addr=f"tcp://127.0.0.1:{free_port()}",
            model_sub_addr=f"tcp://127.0.0.1:{free_port()}")
        steps = []
        stop_at = 60

        def loop():
            rng = np.random.default_rng(1)
            for _ in range(stop_at):
                steps.append(client.request_for_action(
                    rng.standard_normal(6).astype(np.float32),
                    reward=1.0))

        t = threading.Thread(target=loop, daemon=True)
        t.start()
        # Let it step, kill the service, hold a real outage, restart.
        deadline = time.monotonic() + 20
        while len(steps) < 5 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert len(steps) >= 5
        svc.stop()
        time.sleep(1.0)
        svc2 = InferenceService(bundle, max_batch=2, batch_timeout_ms=2.0)
        svc2.bind_zmq(addr)
        svc2.start()
        t.join(timeout=90)
        try:
            assert not t.is_alive(), "env loop wedged through the outage"
            assert len(steps) == stop_at, \
                f"actions lost across the outage ({len(steps)}/{stop_at})"
        finally:
            client.disable_agent()
            svc2.stop()


class TestServingDisabledFailsFast:
    def test_grpc_without_serving_raises_pointed_error(self, tmp_cwd,
                                                       fresh_registry):
        """A grpc fleet whose server has serving.enabled false answers
        GetActions with the PERMANENT NACK_UNAVAILABLE — the thin client
        must fail fast with the pointed message, not retry a
        misconfiguration into a 60s deadline exhaustion."""
        from relayrl_tpu.runtime.inference import RemoteActorClient
        from relayrl_tpu.runtime.server import TrainingServer

        bind_addr = f"127.0.0.1:{free_port()}"
        server = TrainingServer(
            "REINFORCE", obs_dim=6, act_dim=3, env_dir=str(tmp_cwd),
            server_type="grpc", native_grpc=False, bind_addr=bind_addr,
            hyperparams={"traj_per_epoch": 64, "hidden_sizes": [16]})
        try:
            assert server.inference is None  # serving defaults off
            client = RemoteActorClient(
                server_type="grpc", seed=1, probe=False,
                server_addr=bind_addr)
            t0 = time.monotonic()
            with pytest.raises(RuntimeError,
                               match="serving is not enabled"):
                client.request_for_action(np.zeros(6, np.float32))
            assert time.monotonic() - t0 < 10, \
                "fail-fast path retried toward the deadline"
            client.disable_agent()
        finally:
            server.disable_server()


class TestAsyncEmitLifecycle:
    def test_close_then_restart_emitter(self, tmp_cwd, fresh_registry):
        """The emitter thread is restartable: close() (the
        disable_agent path) then start_emitter() (the enable path) must
        leave a working host — NOT a depth-2 hand-off deadlock on the
        third window — and close() must not leak the thread."""
        import jax as _jax

        from relayrl_tpu.models import build_policy
        from relayrl_tpu.runtime.anakin import AnakinActorHost
        from relayrl_tpu.types.model_bundle import ModelBundle

        arch = {"kind": "mlp_discrete", "obs_dim": 4, "act_dim": 2,
                "hidden_sizes": [16]}
        policy = build_policy(arch)
        bundle = ModelBundle(
            version=0, arch=arch,
            params=policy.init_params(_jax.random.PRNGKey(0)))
        sink = []
        host = AnakinActorHost(bundle, "CartPole-v1", num_envs=2,
                               unroll_length=8, async_emit=True,
                               on_send=lambda lane, p: sink.append(p),
                               seed=0)
        host.rollout()
        assert host.flush_emits()
        n_before = len(sink)
        assert n_before >= 0
        host.close()
        assert host._emit_thread is None
        host.start_emitter()
        for _ in range(4):  # past the depth-2 hand-off: would deadlock
            host.rollout()  # if the emitter were still stopped
        assert host.flush_emits()
        assert len(sink) > n_before
        host.close()


class TestConfig:
    def test_serving_params_defaults_and_clamps(self, tmp_cwd):
        from relayrl_tpu.config import ConfigLoader

        cfg_path = tmp_cwd / "cfg.json"
        cfg_path.write_text(json.dumps({"serving": {
            "enabled": True, "max_batch": "bogus",
            "batch_timeout_ms": -5, "buckets": [8, 2, "x"],
            "queue_limit": 0}}))
        p = ConfigLoader(None, str(cfg_path)).get_serving_params()
        assert p["enabled"] is True
        assert p["max_batch"] == 16          # malformed → default
        assert p["batch_timeout_ms"] == 0.0  # negative clamps to 0
        assert p["buckets"] is None          # malformed list → derived
        assert p["queue_limit"] == 1         # floor 1
        # serving-v2 knob defaults ride along untouched
        assert p["max_sessions"] == 4096
        assert p["session_ttl_s"] == 600.0
        assert p["stream_window"] == 32
        assert p["replicas"] is None

    def test_serving_v2_params_clamped(self, tmp_cwd):
        from relayrl_tpu.config import ConfigLoader

        cfg_path = tmp_cwd / "cfg.json"
        cfg_path.write_text(json.dumps({"serving": {
            "max_sessions": 0, "session_ttl_s": -3,
            "stream_window": "bogus",
            "replicas": ["tcp://a:1", "tcp://b:2"]}}))
        p = ConfigLoader(None, str(cfg_path)).get_serving_params()
        assert p["max_sessions"] == 1        # floor 1
        assert p["session_ttl_s"] == 0.0     # negative clamps to 0 (off)
        assert p["stream_window"] == 32      # malformed → default
        assert p["replicas"] == ["tcp://a:1", "tcp://b:2"]
        cfg_path.write_text(json.dumps({"serving": {"replicas": []}}))
        p = ConfigLoader(None, str(cfg_path)).get_serving_params()
        assert p["replicas"] is None         # empty list → single endpoint

    def test_bucket_list_covers_max_batch(self, tmp_cwd):
        from relayrl_tpu.config import ConfigLoader

        cfg_path = tmp_cwd / "cfg.json"
        cfg_path.write_text(json.dumps({"serving": {
            "max_batch": 32, "buckets": [2, 8]}}))
        p = ConfigLoader(None, str(cfg_path)).get_serving_params()
        assert p["buckets"] == [2, 8, 32]

    def test_default_buckets_powers_of_two(self):
        from relayrl_tpu.runtime.inference import default_buckets

        assert default_buckets(16) == [1, 2, 4, 8, 16]
        assert default_buckets(24) == [1, 2, 4, 8, 16, 24]
        assert default_buckets(1) == [1]

    def test_constructor_buckets_clamped_to_max_batch(self, tmp_cwd,
                                                      fresh_registry):
        """Direct construction with buckets smaller than max_batch must
        get the same cover-clamp the ConfigLoader applies — otherwise a
        size-closed full batch would pick a bucket BELOW its size and
        every full batch would fail the pad computation forever."""
        from relayrl_tpu.runtime.inference import InferenceService

        bundle = _reinforce_bundle(str(tmp_cwd))
        svc = InferenceService(bundle, max_batch=16, buckets=[4, 8])
        assert svc.buckets[-1] == 16

    def test_remote_host_mode_accepted(self, tmp_cwd):
        from relayrl_tpu.config import ConfigLoader

        cfg_path = tmp_cwd / "cfg.json"
        cfg_path.write_text(json.dumps({"actor": {"host_mode": "remote"}}))
        p = ConfigLoader(None, str(cfg_path)).get_actor_params()
        assert p["host_mode"] == "remote"


class TestServingSessions:
    """Serving v2: the server-side session table (sequence policies)."""

    def _svc(self, **kw):
        from relayrl_tpu.runtime.inference import InferenceService

        svc = InferenceService(_transformer_bundle(),
                               max_batch=kw.pop("max_batch", 1),
                               batch_timeout_ms=1.0, **kw)
        svc.start()
        return svc

    def test_sequence_parity_across_episodes(self, tmp_cwd,
                                             fresh_registry):
        """The acceptance lock extended to sequence policies: a session
        client's served action stream is bit-identical to a local
        windowed PolicyActor at the same seed — across an episode
        boundary (the server-side window must zero exactly where the
        local one does)."""
        from relayrl_tpu.runtime.policy_actor import PolicyActor

        svc = self._svc()
        try:
            local = PolicyActor(_transformer_bundle(), seed=7,
                                use_kv_cache=False)
            drv = _SessionDriver(svc, "par", seed=7)
            rng = np.random.default_rng(2)
            for episode in range(2):
                for _ in range(5):
                    obs = rng.standard_normal(5).astype(np.float32)
                    r1 = local.request_for_action(obs)
                    r2 = drv.act(obs)
                    assert np.array_equal(np.asarray(r1.act), r2["act"])
                    for k in r1.data:
                        assert np.array_equal(np.asarray(r1.data[k]),
                                              r2["aux"][k]), (episode, k)
                local.flag_last_action(1.0, terminated=True)
                drv.end_episode()
        finally:
            svc.stop()

    def test_idempotent_push_retry(self, tmp_cwd, fresh_registry):
        """An at-least-once redelivery (same ``stp``) recomputes from
        the current window WITHOUT re-pushing: identical action bytes,
        and the next step still sees a single push."""
        from relayrl_tpu.runtime.policy_actor import PolicyActor

        svc = self._svc()
        try:
            local = PolicyActor(_transformer_bundle(), seed=3,
                                use_kv_cache=False)
            drv = _SessionDriver(svc, "retry", seed=3)
            rng = np.random.default_rng(5)
            obs = rng.standard_normal(5).astype(np.float32)
            pre_key = drv.key.copy()
            pre_reset = drv.episode_start
            first = drv.act(obs)
            # Retry the ORIGINAL payload (client timed out, the reply
            # was lost): same cursor, same key, same reset flag.
            drv.episode_start = pre_reset
            replay = drv.raw(obs, step=drv.step, key=pre_key)
            drv.episode_start = False
            assert replay["code"] == 1
            assert np.array_equal(first["act"], replay["act"])
            assert replay["key"] == first["key"]
            local.request_for_action(obs)
            obs2 = rng.standard_normal(5).astype(np.float32)
            r1 = local.request_for_action(obs2)
            r2 = drv.act(obs2)
            assert np.array_equal(np.asarray(r1.act), r2["act"]), \
                "double-push corrupted the session window"
        finally:
            svc.stop()

    def test_out_of_step_cursor_nacks(self, tmp_cwd, fresh_registry):
        from relayrl_tpu.transport.base import NACK_SESSION_EVICTED

        svc = self._svc()
        try:
            drv = _SessionDriver(svc, "skew", seed=1)
            drv.act(np.zeros(5, np.float32))
            reply = drv.raw(np.ones(5, np.float32), step=drv.step + 5)
            assert reply["code"] == NACK_SESSION_EVICTED
            assert svc._m_session_nacked.total() >= 1
        finally:
            svc.stop()

    def test_lru_eviction_resync_bit_identical_continuation(
            self, tmp_cwd, fresh_registry):
        """max_sessions pressure evicts the LRU session; its client's
        next request draws NACK_SESSION_EVICTED, resends its episode
        window, and the CONTINUATION is bit-identical to an
        uninterrupted local actor — eviction is a resync, never a lost
        episode."""
        from relayrl_tpu.runtime.policy_actor import PolicyActor
        from relayrl_tpu.transport.base import NACK_SESSION_EVICTED

        svc = self._svc(max_sessions=2)
        try:
            local = PolicyActor(_transformer_bundle(), seed=11,
                                use_kv_cache=False)
            victim = _SessionDriver(svc, "victim", seed=11)
            rng = np.random.default_rng(8)
            seq = [rng.standard_normal(5).astype(np.float32)
                   for _ in range(6)]
            for obs in seq[:3]:
                r1 = local.request_for_action(obs)
                r2 = victim.act(obs)
                assert np.array_equal(np.asarray(r1.act), r2["act"])
            # Two fresher sessions push "victim" off the 2-entry table.
            for name in ("fresh-a", "fresh-b"):
                _SessionDriver(svc, name, seed=1).act(
                    np.zeros(5, np.float32))
            assert svc._m_evictions["lru"].total() >= 1
            # Mid-episode request now draws the typed resync nack...
            nack = victim.raw(seq[3])
            assert nack["code"] == NACK_SESSION_EVICTED
            # ...and the protocol-following client continues losslessly.
            for obs in seq[3:]:
                r1 = local.request_for_action(obs)
                r2 = victim.act(obs)
                assert np.array_equal(np.asarray(r1.act), r2["act"]), \
                    "post-resync continuation diverged from local"
            assert svc._m_resyncs.total() >= 1
            assert svc.accounting()["sessions"] <= 2
        finally:
            svc.stop()

    def test_ttl_expiry_reaps_idle_sessions(self, tmp_cwd,
                                            fresh_registry):
        svc = self._svc(session_ttl_s=0.05)
        try:
            idle = _SessionDriver(svc, "idle", seed=2)
            idle.act(np.zeros(5, np.float32))
            time.sleep(0.15)
            # Any later batch sweeps the expired session out.
            _SessionDriver(svc, "busy", seed=4).act(
                np.ones(5, np.float32))
            assert svc._m_evictions["ttl"].total() >= 1
            assert "idle" not in svc._sessions
        finally:
            svc.stop()


class TestStreamingChannel:
    """Serving v2: the pipelined request channel and the multiplexed
    client."""

    def test_streamed_out_of_order_matches_lockstep(self, tmp_cwd,
                                                    fresh_registry):
        """N pipelined submits collected in REVERSE order decode to the
        replies the lock-step client gets for the same payloads — actions
        and rng keys exactly; float outputs to rounding, because the
        pipelined requests batch together where the lock-step ones
        dispatch alone (two program shapes, ``_util.MODEL_OUTPUT_RTOL``).
        Out-of-order delivery is a scheduling change, not a numerics
        change."""
        from relayrl_tpu.runtime.inference import InferenceService
        from relayrl_tpu.transport.serving import (
            ZmqServingClient,
            ZmqStreamingClient,
            pack_infer_request,
        )

        svc = InferenceService(_reinforce_bundle(str(tmp_cwd)),
                               max_batch=4, batch_timeout_ms=2.0)
        addr = f"tcp://127.0.0.1:{free_port()}"
        svc.bind_zmq(addr)
        svc.start()
        stream = ZmqStreamingClient(addr)
        serial = ZmqServingClient(addr)
        try:
            rng = np.random.default_rng(6)
            payloads = []
            for i in range(8):
                key = np.asarray(jax.random.PRNGKey(100 + i))
                obs = rng.standard_normal(6).astype(np.float32)
                payloads.append(pack_infer_request(f"s{i}", i + 1, key,
                                                   obs, None))
            waiters = [stream.submit(p, i + 1)
                       for i, p in enumerate(payloads)]
            assert stream.inflight_high_water >= 2, \
                "pipelined submits never overlapped"
            streamed = [stream.wait(w, 30) for w in reversed(waiters)]
            streamed.reverse()
            lockstep = [serial.request(p, i + 1, 30)
                        for i, p in enumerate(payloads)]
            for i, (a, b) in enumerate(zip(streamed, lockstep)):
                assert a["code"] == b["code"] == 1
                assert np.array_equal(a["act"], b["act"]), i
                assert a["key"] == b["key"], i
                assert set(a["aux"]) == set(b["aux"]), i
                for k in a["aux"]:
                    assert_aux_equal(a["aux"][k], b["aux"][k], (i, k))
        finally:
            stream.close()
            serial.close()
            svc.stop()

    def test_multiplexed_client_matches_local_actors(self, tmp_cwd,
                                                     fresh_registry):
        """One MultiplexedRemoteClient process driving 4 env lanes over
        the live zmq stream channel produces, per lane, the exact action
        stream of a local PolicyActor(seed=seed+lane) — and its episodes
        ship through the standard trajectory plane with the same records
        (the float aux values to rounding: the served batch of 4 and the
        local single are two program shapes, ``_util.MODEL_OUTPUT_RTOL``)."""
        from relayrl_tpu.runtime.inference import MultiplexedRemoteClient
        from relayrl_tpu.runtime.policy_actor import PolicyActor
        from relayrl_tpu.types.model_bundle import ModelBundle

        server, cfg_path, client_addrs = _serving_stack(
            tmp_cwd, traj_per_epoch=10_000)
        mux = None
        try:
            bundle = ModelBundle(
                version=server.algorithm.version,
                arch=dict(server.algorithm.bundle().arch),
                params=server.algorithm.bundle().params)
            lanes = 4
            sent_local = [[] for _ in range(lanes)]
            sent_mux = [[] for _ in range(lanes)]
            locals_ = [PolicyActor(bundle, seed=40 + i,
                                   on_send=sent_local[i].append)
                       for i in range(lanes)]
            mux = MultiplexedRemoteClient(config_path=cfg_path,
                                          lanes=lanes, seed=40,
                                          **client_addrs)
            for i in range(lanes):
                mux.trajectories[i]._on_send = sent_mux[i].append
            rng = np.random.default_rng(17)
            for step in range(6):
                obs = [rng.standard_normal(6).astype(np.float32)
                       for _ in range(lanes)]
                rewards = None if step == 0 else [0.5] * lanes
                recs = mux.request_for_actions(obs, rewards=rewards)
                for i in range(lanes):
                    r1 = locals_[i].request_for_action(
                        obs[i], reward=0.0 if step == 0 else 0.5)
                    assert np.array_equal(np.asarray(r1.act),
                                          np.asarray(recs[i].act)), \
                        (step, i)
                    for k in r1.data:
                        assert_aux_equal(recs[i].data[k], r1.data[k],
                                         (step, i, k))
            assert mux.inflight_high_water >= 2, \
                "multiplexed lanes never overlapped in flight"
            for i in range(lanes):
                locals_[i].flag_last_action(1.0, terminated=True)
                mux.flag_last_action(i, 1.0, terminated=True)
                assert len(sent_local[i]) == len(sent_mux[i]) == 1
                assert_episode_payloads_match(
                    sent_mux[i][0], sent_local[i][0], f"lane {i}")
        finally:
            if mux is not None:
                mux.disable_agent()
            server.disable_server()

    def test_replica_reroute_and_session_resync_after_kill(
            self, tmp_cwd, fresh_registry):
        """Two sequence-policy replicas; the client's session-affine home
        replica dies mid-episode. The client rotates to the survivor,
        answers its SESSION_EVICTED nack with the episode window, and the
        action stream continues bit-identical to an uninterrupted local
        windowed actor — replica death costs a resync round-trip, never
        an episode."""
        from relayrl_tpu.runtime.inference import (
            InferenceService,
            RemoteActorClient,
        )
        from relayrl_tpu.runtime.policy_actor import PolicyActor

        server, _, client_addrs = _serving_stack(tmp_cwd)
        cfg_path = os.path.join(str(tmp_cwd), "replica_cfg.json")
        with open(cfg_path, "w") as f:
            json.dump({"serving": {"enabled": True,
                                   "request_timeout_s": 0.25,
                                   "infer_deadline_s": 30.0},
                       "actor": {"spool_entries": 64}}, f)
        addrs = [f"tcp://127.0.0.1:{free_port()}" for _ in range(2)]
        replicas = []
        for addr in addrs:
            svc = InferenceService(_transformer_bundle(), max_batch=4,
                                   batch_timeout_ms=2.0)
            svc.bind_zmq(addr)
            svc.start()
            replicas.append(svc)
        client_addrs = {k: v for k, v in client_addrs.items()
                        if k != "serving_addr"}
        client = None
        try:
            local = PolicyActor(_transformer_bundle(), seed=13,
                                use_kv_cache=False)
            client = RemoteActorClient(
                config_path=cfg_path, seed=13, identity="drill-thin",
                serving_addrs=addrs, **client_addrs)
            rng = np.random.default_rng(21)
            seq = [rng.standard_normal(5).astype(np.float32)
                   for _ in range(6)]
            for obs in seq[:3]:
                r1 = local.request_for_action(obs)
                r2 = client.request_for_action(obs)
                assert np.array_equal(np.asarray(r1.act),
                                      np.asarray(r2.act))
            home = client._replica_idx
            replicas[home].stop()  # the drill: home replica dies
            for obs in seq[3:]:
                r1 = local.request_for_action(obs)
                r2 = client.request_for_action(obs)
                assert np.array_equal(np.asarray(r1.act),
                                      np.asarray(r2.act)), \
                    "post-re-route continuation diverged from local"
            assert client._replica_idx != home
            assert client._m_reroutes.total() >= 1
            assert client._m_resyncs.total() >= 1
        finally:
            if client is not None:
                client.disable_agent()
            for svc in replicas:
                svc.stop()
            server.disable_server()
