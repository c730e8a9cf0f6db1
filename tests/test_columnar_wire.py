"""Columnar trajectory wire (ISSUE 9): frame codec, anakin emitter
parity against the native per-record decode, ingest-level parity
(byte-identical staging batches, bit-identical learner params), the
server decode path (CRC rejection, guardrails through frames), live
accounting parity on all three transports, and the crash drill with
anakin actors shipping frames.

The parity contract under test: a columnar frame decodes into EXACTLY
the :class:`DecodedTrajectory` the native msgpack decoder produces from
the per-record wire for the same rollout — same columns, same dtypes,
same bytes — so everything downstream (validation, padding, staging
slabs, the learner) is provably wire-form-agnostic.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from relayrl_tpu.models import build_policy
from relayrl_tpu.types.columnar import (
    DecodedTrajectory,
    NativeDecoder,
    encode_columnar_frame,
    is_columnar_frame,
    native_codec_available,
    parse_frame,
)
from relayrl_tpu.types.model_bundle import ModelBundle
from tests._util import free_port

pytestmark = pytest.mark.columnar

DRILLS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "drills")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

OBS_DIM, ACT_DIM = 4, 2


def _bundle(arch_over=None, seed=0, version=0):
    arch = {"kind": "mlp_discrete", "obs_dim": OBS_DIM, "act_dim": ACT_DIM,
            "hidden_sizes": [16], **(arch_over or {})}
    policy = build_policy(arch)
    return ModelBundle(version=version, arch=arch,
                       params=policy.init_params(jax.random.PRNGKey(seed)))


def _decoded(n=3, rew=1.0, obs_dtype=np.float32):
    return DecodedTrajectory(
        agent_id="lane0", n_steps=n, n_records=n + 1, marker_truncated=True,
        columns={"o": np.arange(n * OBS_DIM).reshape(n, OBS_DIM).astype(
                     obs_dtype),
                 "a": np.arange(n, dtype=np.int32),
                 "r": np.full(n, rew, np.float32),
                 "t": np.eye(1, n, n - 1, dtype=np.uint8)[0],
                 "u": np.ones(n, np.uint8),
                 "x": np.eye(1, n, n - 1, dtype=np.uint8)[0]},
        aux={"v": np.linspace(0, 1, n).astype(np.float32),
             "logp_a": np.linspace(-1, 0, n).astype(np.float32)},
        final_obs=np.arange(OBS_DIM, dtype=np.float32))


def _collect(env, arch_over, columnar, windows=3, lanes=4, unroll=64,
             seed=7, max_traj=1000, **env_kwargs):
    """Run an AnakinActorHost and return (sent payloads, host)."""
    from relayrl_tpu.runtime.anakin import AnakinActorHost

    sent: list[tuple[int, bytes]] = []
    host = AnakinActorHost(
        _bundle(arch_over), env, num_envs=lanes, unroll_length=unroll,
        max_traj_length=max_traj, columnar_wire=columnar,
        on_send=lambda lane, p: sent.append((lane, p)), seed=seed,
        **env_kwargs)
    for _ in range(windows):
        host.rollout()
    return sent, host


# ---------------------------------------------------------------------------
# frame codec
# ---------------------------------------------------------------------------
class TestFrameCodec:
    def test_round_trip_preserves_columns_dtypes_and_flags(self):
        dt = _decoded()
        buf = encode_columnar_frame(dt)
        assert is_columnar_frame(buf)
        out = parse_frame(buf)
        assert out.agent_id == "lane0"
        assert (out.n_steps, out.n_records, out.marker_truncated) == (3, 4,
                                                                      True)
        for k, col in dt.columns.items():
            assert out.columns[k].dtype == col.dtype
            assert out.columns[k].tobytes() == col.tobytes()
        for k, col in dt.aux.items():
            assert out.aux[k].tobytes() == col.tobytes()
        np.testing.assert_array_equal(out.final_obs, dt.final_obs)
        assert out.final_mask is None

    def test_int_observation_column(self):
        dt = _decoded(obs_dtype=np.int32)
        out = parse_frame(encode_columnar_frame(dt))
        assert out.columns["o"].dtype == np.int32
        assert out.columns["o"].tobytes() == dt.columns["o"].tobytes()

    def test_envelope_attribution_overrides_embedded_id(self):
        buf = encode_columnar_frame(_decoded(), agent_id="")
        assert parse_frame(buf, agent_id="fleet.lane3").agent_id == \
            "fleet.lane3"

    def test_every_corruption_is_rejected(self):
        buf = encode_columnar_frame(_decoded())
        for i in range(4, len(buf), 7):
            bad = bytearray(buf)
            bad[i] ^= 0x5A
            with pytest.raises(ValueError):
                parse_frame(bytes(bad))

    def test_truncated_and_unfooted_frames_rejected(self):
        buf = encode_columnar_frame(_decoded())
        for cut in (len(buf) - 1, len(buf) - 5, 20, 7):
            with pytest.raises(ValueError):
                parse_frame(buf[:cut])
        # a C++-drain-style blob (no CRC footer) is not a wire frame
        import relayrl_tpu.types.columnar as col_mod

        footless = bytearray(buf[:-col_mod._FOOTER.size])
        flags_off = col_mod._HDR.size + len("lane0") + 8
        footless[flags_off] &= ~col_mod.FLAG_FOOTER & 0xFF
        with pytest.raises(ValueError, match="footer"):
            parse_frame(bytes(footless))

    def test_sniff_negative_on_msgpack_payloads(self):
        from relayrl_tpu.transport.base import pack_trajectory_envelope
        from relayrl_tpu.types.action import ActionRecord
        from relayrl_tpu.types.trajectory import serialize_actions

        payload = serialize_actions(
            [ActionRecord(obs=np.zeros(4, np.float32),
                          act=np.int32(0), rew=1.0, done=True)])
        assert not is_columnar_frame(payload)
        assert not is_columnar_frame(pack_trajectory_envelope("a", payload))
        assert not is_columnar_frame(b"")


# ---------------------------------------------------------------------------
# anakin emitter parity vs the native decode of the per-record wire
# ---------------------------------------------------------------------------
@pytest.mark.anakin
@pytest.mark.skipif(not native_codec_available(),
                    reason="native codec unavailable")
class TestEmitterParity:
    CASES = {
        "cartpole": ("CartPole-v1", None, {}, 1000),
        "cartpole_chunked": ("CartPole-v1", None, {}, 17),
        "cartpole_truncating": ("CartPole-v1", None, {"max_steps": 5}, 1000),
        # Fused-sequence scan (ISSUE 20): the rolling-window carry must
        # unstack to the same frames as the per-record path — truncating
        # past W=8 so the ring rolls AND resets inside the scan.
        "cartpole_sequence": (
            "CartPole-v1",
            {"kind": "transformer_discrete", "d_model": 16, "n_layers": 1,
             "n_heads": 2, "max_seq_len": 8}, {"max_steps": 18}, 1000),
        "pendulum_continuous": (
            "Pendulum-v1",
            {"kind": "mlp_continuous", "obs_dim": 3, "act_dim": 1}, {}, 1000),
        "gridworld_int_obs": (
            "GridWorld-v0",
            {"obs_dim": 2, "act_dim": 4}, {}, 1000),
    }

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_frames_decode_identical_to_native_unstack(self, case, tmp_cwd):
        env_id, arch_over, env_kwargs, max_traj = self.CASES[case]
        windows = 8 if case == "pendulum_continuous" else 3
        frames, host_c = _collect(env_id, arch_over, True, windows=windows,
                                  max_traj=max_traj, **env_kwargs)
        records, host_r = _collect(env_id, arch_over, False, windows=windows,
                                   max_traj=max_traj, **env_kwargs)
        assert len(frames) == len(records) > 0
        assert host_c.episode_returns == host_r.episode_returns
        dec = NativeDecoder()
        for (lane_c, frame), (lane_r, payload) in zip(frames, records):
            assert lane_c == lane_r
            a = parse_frame(frame, agent_id="x")
            b = dec.decode(payload, agent_id="x")
            assert isinstance(b, DecodedTrajectory), type(b)
            assert (a.n_steps, a.n_records, a.marker_truncated) == \
                (b.n_steps, b.n_records, b.marker_truncated)
            assert set(a.columns) == set(b.columns)
            for k in a.columns:
                assert a.columns[k].dtype == b.columns[k].dtype, k
                assert a.columns[k].shape == b.columns[k].shape, k
                assert a.columns[k].tobytes() == b.columns[k].tobytes(), k
            assert set(a.aux) == set(b.aux)
            for k in a.aux:
                assert a.aux[k].dtype == b.aux[k].dtype, k
                assert a.aux[k].tobytes() == b.aux[k].tobytes(), k
            assert (a.final_obs is None) == (b.final_obs is None)
            if a.final_obs is not None:
                assert a.final_obs.dtype == b.final_obs.dtype
                assert a.final_obs.tobytes() == b.final_obs.tobytes()

    def test_padded_batches_byte_identical(self, tmp_cwd):
        """The staging-slab input: pad_decoded over both decodes of the
        same rollout yields byte-identical padded fields."""
        from relayrl_tpu.data.batching import pad_decoded

        frames, _ = _collect("CartPole-v1", None, True)
        records, _ = _collect("CartPole-v1", None, False)
        dec = NativeDecoder()
        for (_, frame), (_, payload) in zip(frames, records):
            a = pad_decoded(parse_frame(frame, agent_id="x"), 64,
                            OBS_DIM, ACT_DIM, discrete=True)
            b = pad_decoded(dec.decode(payload, agent_id="x"), 64,
                            OBS_DIM, ACT_DIM, discrete=True)
            for field in ("obs", "act", "act_mask", "rew", "val", "logp",
                          "valid"):
                assert getattr(a, field).tobytes() == \
                    getattr(b, field).tobytes(), field
            assert (a.length, a.terminated, a.last_val) == \
                (b.length, b.terminated, b.last_val)


# ---------------------------------------------------------------------------
# ingest-level parity: bit-identical learner params across wire forms
# ---------------------------------------------------------------------------
class StubTransport:
    def __init__(self):
        self.on_trajectory = None
        self.on_trajectory_decoded = None
        self.get_model = None
        self.on_register = None
        self.on_unregister = None
        self.check_ingest = None

    def start(self):
        pass

    def stop(self):
        pass

    def publish_model(self, version, raw):
        pass


@pytest.fixture
def stub_server_factory(tmp_cwd, monkeypatch):
    import relayrl_tpu.runtime.server as srv_mod
    from relayrl_tpu import telemetry

    # A live registry BEFORE the server configures (configure is
    # first-wins): the columnar decode counters must really count.
    telemetry.reset_for_tests()
    telemetry.set_registry(telemetry.Registry(run_id="columnar-test"))
    yield_registry_cleanup = telemetry.reset_for_tests

    def make(algorithm="REINFORCE", hp=None, cfg=None):
        monkeypatch.setattr(srv_mod, "make_server_transport",
                            lambda *a, **k: StubTransport())
        path = tmp_cwd / f"cfg_{len(os.listdir(tmp_cwd))}.json"
        path.write_text(json.dumps(cfg or {}))
        hyper = {"traj_per_epoch": 4, "hidden_sizes": [16],
                 "seed_salt": 0, **(hp or {})}
        return srv_mod.TrainingServer(
            algorithm, obs_dim=OBS_DIM, act_dim=ACT_DIM,
            env_dir=str(tmp_cwd), config_path=str(path), hyperparams=hyper)

    yield make
    yield_registry_cleanup()


def _feed_and_params(server, payloads, min_updates=2):
    """Feed sequence-tagged payloads through the real ingest funnel
    (transport callback → staging decode → learner), drain, return the
    final host params + accounting."""
    from relayrl_tpu.transport.base import tag_agent_seq

    server.wait_warmup(180)
    seqs: dict[str, int] = {}
    for lane, payload in payloads:
        agent_id = f"parity.lane{lane}"
        seqs[agent_id] = seqs.get(agent_id, 0) + 1
        server._on_trajectory(tag_agent_seq(agent_id, seqs[agent_id]),
                              payload)
    assert server.drain(timeout=120)
    assert server.stats["updates"] >= min_updates
    acct = server.ingest_accounting()
    params = jax.device_get(server.algorithm.bundle().params)
    return params, acct, dict(server.stats)


def _assert_trees_bit_identical(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        xa, ya = np.asarray(x), np.asarray(y)
        assert xa.dtype == ya.dtype and xa.shape == ya.shape
        assert xa.tobytes() == ya.tobytes()


@pytest.mark.parametrize("algorithm,hp", [
    ("REINFORCE", {"with_vf_baseline": False}),
    # ISSUE 17 wall re-fit: the wire-form equivalence is algorithm-agnostic;
    # REINFORCE stays fast, the PPO twin rides the slow tier.
    pytest.param("PPO", {"train_iters": 2, "minibatch_count": 2},
                 marks=pytest.mark.slow),
])
def test_learner_params_bit_identical_across_wire_forms(
        algorithm, hp, stub_server_factory, tmp_cwd):
    """THE ingest parity acceptance: the same rollout delivered as
    columnar frames vs per-record msgpack yields bit-identical learner
    params and identical accepted-step accounting."""
    frames, _ = _collect("CartPole-v1", None, True, windows=4, seed=3)
    records, _ = _collect("CartPole-v1", None, False, windows=4, seed=3)
    assert len(frames) == len(records) >= 8
    results = {}
    for label, payloads in (("columnar", frames), ("records", records)):
        server = stub_server_factory(algorithm=algorithm, hp=hp)
        try:
            results[label] = _feed_and_params(server, payloads)
        finally:
            server.disable_server()
    (p_a, acct_a, stats_a) = results["columnar"]
    (p_b, acct_b, stats_b) = results["records"]
    assert acct_a["agents"] == acct_b["agents"]
    assert stats_a["trajectories"] == stats_b["trajectories"]
    assert stats_a["updates"] == stats_b["updates"] >= 2
    _assert_trees_bit_identical(p_a, p_b)


# ---------------------------------------------------------------------------
# server decode path: CRC rejection + guardrails through frames
# ---------------------------------------------------------------------------
class TestServerColumnarPath:
    def test_crc_reject_counted_and_seq_replayable(self, stub_server_factory):
        """A corrupted frame drops with the columnar-reject counter AND
        retracts its seq from the dedup ledger, so the actor's spool
        replay can land the retained clean copy later."""
        from relayrl_tpu import telemetry
        from relayrl_tpu.transport.base import tag_agent_seq

        server = stub_server_factory()
        try:
            server.wait_warmup(180)
            frame = bytearray(encode_columnar_frame(_decoded()))
            frame[-10] ^= 0xFF  # corrupt inside the CRC-covered region
            server._on_trajectory(tag_agent_seq("crc.lane0", 1),
                                  bytes(frame))
            deadline = time.monotonic() + 30
            reg = telemetry.get_registry()

            def counter(name):
                return sum(m["value"] for m in reg.snapshot()["metrics"]
                           if m["name"] == name)

            while (counter("relayrl_server_columnar_rejects_total") < 1
                   and time.monotonic() < deadline):
                time.sleep(0.02)
            assert counter("relayrl_server_columnar_rejects_total") == 1
            assert server.stats["trajectories"] == 0
            # the retained clean copy replays under the SAME seq and is
            # accepted — the corruption burned no sequence number
            server._on_trajectory(tag_agent_seq("crc.lane0", 1),
                                  encode_columnar_frame(_decoded()))
            server.drain(timeout=60)
            row = server.ingest_accounting()["agents"]["crc.lane0"]
            assert row["accepted"] == 1 and row["contiguous"]
        finally:
            server.disable_server()

    def test_nan_poison_quarantines_through_columnar_decode(
            self, stub_server_factory):
        """Guardrails' semantic trust boundary works per-frame: NaN
        rewards inside a wire-VALID columnar frame (CRC passes) are
        rejected as nonfinite, strike the sending agent, and quarantine
        it — while a clean agent on the same funnel keeps training."""
        server = stub_server_factory(cfg={"guardrails": {
            "strike_threshold": 2, "quarantine_cooldown_s": 300.0}})
        try:
            server.wait_warmup(180)
            poison = encode_columnar_frame(_decoded(rew=float("nan")))
            clean = encode_columnar_frame(_decoded())
            server._on_trajectory("evil", poison)
            server._on_trajectory("evil", poison)  # strike 2 → quarantine
            server._on_trajectory("good", clean)
            deadline = time.monotonic() + 30
            while (server.guardrails.quarantine.quarantines_total < 1
                   and time.monotonic() < deadline):
                time.sleep(0.02)
            assert server.guardrails.quarantine.is_quarantined("evil")
            server.drain(timeout=60)
            assert server.stats["trajectories"] == 1  # only the clean one
            from relayrl_tpu import telemetry

            rejected = sum(
                m["value"]
                for m in telemetry.get_registry().snapshot()["metrics"]
                if m["name"] == "relayrl_guard_rejected_total"
                and m.get("labels", {}).get("reason") == "nonfinite")
            assert rejected >= 2
        finally:
            server.disable_server()


# ---------------------------------------------------------------------------
# live transports: accounting parity + the fast path actually taken
# ---------------------------------------------------------------------------
def _require_transport(transport: str) -> None:
    if transport == "native":
        from relayrl_tpu.transport.native_backend import native_available

        if not native_available():
            pytest.skip("native .so unavailable")
    if transport == "grpc":
        pytest.importorskip("grpc")


def _transport_addrs(transport: str) -> tuple[dict, dict]:
    if transport in ("native", "grpc"):
        port = free_port()
        return ({"bind_addr": f"127.0.0.1:{port}"},
                {"server_addr": f"127.0.0.1:{port}"})
    ports = [free_port() for _ in range(3)]
    return ({"agent_listener_addr": f"tcp://127.0.0.1:{ports[0]}",
             "trajectory_addr": f"tcp://127.0.0.1:{ports[1]}",
             "model_pub_addr": f"tcp://127.0.0.1:{ports[2]}"},
            {"agent_listener_addr": f"tcp://127.0.0.1:{ports[0]}",
             "trajectory_addr": f"tcp://127.0.0.1:{ports[1]}",
             "model_sub_addr": f"tcp://127.0.0.1:{ports[2]}"})


def _live_accounting(transport: str, columnar: bool, tmp_cwd,
                     windows: int = 4) -> tuple[dict, int]:
    """One VectorAgent(anakin) run against a live TrainingServer on
    ``transport``; returns (per-lane accounting, server columnar-frame
    count)."""
    from relayrl_tpu import telemetry
    from relayrl_tpu.runtime.agent import VectorAgent
    from relayrl_tpu.runtime.server import TrainingServer

    server_addrs, agent_addrs = _transport_addrs(transport)
    server = TrainingServer(
        "REINFORCE", obs_dim=4, act_dim=2, env_dir=str(tmp_cwd),
        server_type=transport,
        hyperparams={"traj_per_epoch": 100, "hidden_sizes": [8],
                     "with_vf_baseline": False},
        **server_addrs)
    try:
        agent = VectorAgent(
            num_envs=2, server_type=transport, handshake_timeout_s=60,
            seed=4, probe=False, host_mode="anakin",
            jax_env="CartPole-v1", unroll_length=32,
            columnar_wire=columnar, identity=f"parity-{transport}",
            **agent_addrs)
        try:
            for _ in range(windows):
                agent.rollout()
            sent = dict(agent.spool.sent_counts())
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                acct = server.ingest_accounting()["agents"]
                if all(acct.get(aid, {}).get("accepted") == n
                       for aid, n in sent.items()):
                    break
                time.sleep(0.1)
            server.drain(timeout=30)
            acct = server.ingest_accounting()["agents"]
            lanes = {aid: (row["accepted"], row["max_seq"],
                           row["contiguous"])
                     for aid, row in acct.items()
                     if aid.startswith(f"parity-{transport}.lane")}
            assert lanes, "no lane attribution"
            for aid, n in sent.items():
                assert lanes[aid] == (n, n, True), (aid, lanes[aid], n)
            frames = sum(
                m["value"]
                for m in telemetry.get_registry().snapshot()["metrics"]
                if m["name"] == "relayrl_server_columnar_frames_total")
            return lanes, int(frames)
        finally:
            agent.disable_agent()
    finally:
        server.disable_server()


# ISSUE 17 wall re-fit: zmq fast, grpc/native twins slow (the accounting
# path above the transport is shared; per-transport wire bytes are still
# covered fast by the codec/fuzz suites).
@pytest.mark.parametrize(
    "transport",
    ["zmq",
     pytest.param("grpc", marks=pytest.mark.slow),
     pytest.param("native", marks=pytest.mark.slow)])
def test_live_accounting_parity_all_transports(transport, tmp_cwd):
    """Same seed, same windows, both wire forms over a LIVE transport:
    per-lane accepted-step accounting is identical, zero loss on both,
    and the columnar run actually took the frame fast path (server-side
    decoded-frame counter advanced)."""
    from relayrl_tpu import telemetry

    _require_transport(transport)
    telemetry.reset_for_tests()
    telemetry.set_registry(telemetry.Registry(run_id="columnar-live"))
    try:
        lanes_c, frames_before = _live_accounting(transport, True, tmp_cwd)
        assert frames_before > 0, \
            "columnar run never exercised the fast path"
        lanes_r, frames_after = _live_accounting(transport, False, tmp_cwd)
        assert frames_after == frames_before, \
            "per-record run unexpectedly produced columnar frames"
        assert lanes_c == lanes_r
    finally:
        telemetry.reset_for_tests()


# ---------------------------------------------------------------------------
# the crash drill with frames (satellite: PR 6 chaos drill × columnar)
# ---------------------------------------------------------------------------
def _read_status(scratch: str) -> dict | None:
    try:
        with open(os.path.join(scratch, "status.json")) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None


def _wait_status(scratch, proc, pred, timeout_s, what) -> dict:
    deadline = time.monotonic() + timeout_s
    status = None
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            out, _ = proc.communicate()
            raise AssertionError(
                f"chaos server died waiting for {what} "
                f"(rc={proc.returncode}):\n{out[-3000:]}")
        status = _read_status(scratch)
        if status is not None and pred(status):
            return status
        time.sleep(0.1)
    raise AssertionError(f"timed out waiting for {what}; last={status}")


def _spawn_chaos_server(scratch, transport, addrs, resume):
    cfg = {
        "algorithm": "REINFORCE", "obs_dim": 4, "act_dim": 2,
        "hyperparams": {"traj_per_epoch": 4, "hidden_sizes": [16, 16],
                        "with_vf_baseline": False},
        "server_type": transport, "scratch": scratch,
        "checkpoint_every": 1, "resume": resume,
        "status_path": os.path.join(scratch, "status.json"),
        **addrs,
    }
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    return subprocess.Popen(
        [sys.executable, os.path.join(DRILLS, "_chaos_server.py"),
         json.dumps(cfg)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)


# Tier-1 wall budget (ISSUE 15): slow-marked — the fast set keeps one
# SIGKILL drill per transport (tests/test_recovery.py); this variant
# re-runs the same contract with frames on the wire (~38 s for the
# trio). Run via `pytest -m columnar`.
@pytest.mark.slow
@pytest.mark.parametrize("transport", ["zmq", "grpc", "native"])
def test_learner_sigkill_columnar_replay_zero_loss(transport, tmp_path,
                                                   tmp_cwd):
    """The PR 6 chaos drill on the columnar wire, all three transports:
    SIGKILL the learner while anakin actors ship frames, windows keep
    landing in the spool through the outage, restart with resume, spool
    replays the retained frames, and per-lane accounting closes at
    accepted == max_seq == sent — zero loss, zero double-train, with
    frames (not per-record payloads) on the wire throughout."""
    _require_transport(transport)
    scratch = str(tmp_path)
    server_addrs, agent_addrs = _transport_addrs(transport)
    proc = _spawn_chaos_server(scratch, transport, server_addrs,
                               resume=False)
    agent = None
    try:
        _wait_status(scratch, proc, lambda s: True, 120, "server up")
        from relayrl_tpu.runtime.agent import VectorAgent

        extra = {"heartbeat_s": 1.0} if transport == "native" else {}
        agent = VectorAgent(
            num_envs=2, server_type=transport, handshake_timeout_s=60,
            seed=0, probe=False, host_mode="anakin",
            jax_env="CartPole-v1", unroll_length=16,
            identity=f"colchaos-{transport}", **agent_addrs, **extra)
        assert agent.columnar_wire, "anakin default must be columnar"
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            agent.rollout()
            status = _read_status(scratch)
            if (status and status["version"] >= 2
                    and status["accounting"]["agents"]):
                break
            time.sleep(0.05)
        status = _read_status(scratch)
        assert status and status["version"] >= 2, "no training before kill"
        v_before = status["version"]

        proc.kill()
        proc.wait(timeout=30)
        for _ in range(6):  # frames land in the spool through the outage
            agent.rollout()
        assert agent.spool.depth > 0

        proc = _spawn_chaos_server(scratch, transport, server_addrs,
                                   resume=True)
        _wait_status(scratch, proc, lambda s: True, 120, "server restart")
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            agent.rollout()
            status = _read_status(scratch)
            if status and status["version"] > v_before:
                break
            time.sleep(0.05)
        assert status["version"] > v_before, "no training past the crash"

        agent.spool.replay()
        sent_counts = agent.spool.sent_counts()
        lane_ids = [aid for aid in sent_counts
                    if aid.startswith(f"colchaos-{transport}.lane")]
        assert len(lane_ids) == 2

        def recovered(s):
            rows = s["accounting"]["agents"]
            return all(
                rows.get(aid, {}).get("max_seq") == sent_counts[aid]
                and rows[aid]["contiguous"] for aid in lane_ids)

        status = _wait_status(scratch, proc, recovered, 120,
                              "zero-loss accounting for every lane")
        for aid in lane_ids:
            row = status["accounting"]["agents"][aid]
            assert row["accepted"] == sent_counts[aid], (aid, row)
        assert status["accounting"]["duplicates"] >= 1
        # the wire really carried frames: the server-side decoded-frame
        # counter is in the status telemetry and advanced
        frames = sum(m["value"] for m in status["telemetry"]["metrics"]
                     if m["name"] == "relayrl_server_columnar_frames_total")
        assert frames > 0, "drill ran but no columnar frames were decoded"
    finally:
        if agent is not None:
            agent.disable_agent()
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.communicate(timeout=60)
            except subprocess.TimeoutExpired:
                proc.kill()
