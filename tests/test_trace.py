"""Distributed tracing (ISSUE 14, relayrl_tpu/telemetry/trace.py):
context codec + wire tags, sampling, flight recorder, journal rotation,
analyzer, exporter /traces + remote top, the native C++ id-passthrough
lock, the histogram bucket audit, and a live-zmq end-to-end drill.
"""

from __future__ import annotations

import json
import os
import threading
import time

import pytest

from relayrl_tpu import telemetry
from relayrl_tpu.telemetry import trace
from relayrl_tpu.telemetry.core import (
    AGE_BUCKETS,
    LATENCY_BUCKETS_WIDE,
    Registry,
    log_buckets,
)
from relayrl_tpu.telemetry.events import EventJournal, read_events
from relayrl_tpu.telemetry.trace import split_agent_trace, tag_agent_trace
from relayrl_tpu.transport.base import split_agent_seq, tag_agent_seq

pytestmark = pytest.mark.tracing


@pytest.fixture(autouse=True)
def _reset_telemetry():
    telemetry.reset_for_tests()
    yield
    telemetry.reset_for_tests()


def _live_tracer(rate=1.0, ring=4096, journal=False):
    telemetry.set_registry(Registry(run_id="trace-test"))
    return trace.configure(rate, ring=ring, journal=journal)


# -- context codec + wire tags ---------------------------------------------

def test_ctx_codec_round_trip():
    ctx = trace.TrajCtx("ab12-3", 123456789, 42)
    out = trace.TrajCtx.decode(ctx.encode())
    assert (out.trace_id, out.born_ns, out.born_version) == (
        "ab12-3", 123456789, 42)


def test_ctx_decode_rejects_malformed():
    for bad in ("", "a.b", "a.b.c.d", "xyz!.12.3", "a..3"):
        assert trace.TrajCtx.decode(bad) is None, bad


def test_trace_tag_rides_beside_seq_tag():
    ctx = trace.TrajCtx("dead-1", 0x7b, 5)
    wire = tag_agent_seq(tag_agent_trace("agent.lane3", ctx.encode()), 42)
    assert wire == "agent.lane3#tdead-1.7b.5#s42"
    base, seq = split_agent_seq(wire)
    assert seq == 42
    clean, text = split_agent_trace(base)
    assert clean == "agent.lane3"
    out = trace.TrajCtx.decode(text)
    assert out.born_ns == 0x7b and out.born_version == 5


def test_split_trace_strict_validation():
    # An id that happens to contain "#t" must never be misparsed.
    for ident in ("agent#tail", "a#t1.2", "a#tx.y.z!", "a#tA.B.C"):
        base, text = split_agent_trace(ident)
        assert (base, text) == (ident, None)
    # split_ctx additionally survives undecodable-but-valid-charset tags.
    clean, ctx = trace.split_ctx("plain-agent")
    assert clean == "plain-agent" and ctx is None


# -- sampling + recorder ---------------------------------------------------

def test_stride_sampling_rate_exact():
    tracer = _live_tracer(rate=0.25)
    drawn = sum(tracer.sample_traj(1, 0) is not None for _ in range(100))
    assert drawn == 25


def test_sample_version_deterministic_and_rate_bounded():
    tracer = _live_tracer(rate=1.0)
    assert all(tracer.sample_version(v) for v in range(1, 50))
    assert not tracer.sample_version(0)  # handshake model never sampled
    half = trace.Tracer(0.5, journal=False)
    picks = [half.sample_version(v) for v in range(1, 2001)]
    assert picks == [half.sample_version(v) for v in range(1, 2001)]
    assert 800 < sum(picks) < 1200


def test_ring_bounded_and_snapshot():
    tracer = _live_tracer(ring=32)
    for i in range(100):
        tracer.span("traj", f"t{i}", "env", i, i + 1)
    spans = trace.snapshot_spans()
    assert len(spans) == 32
    assert spans[-1]["trace"] == "t99"  # newest retained, oldest evicted


def test_trace_ids_unique_across_threads():
    """The id seq is minted UNDER the sampling lock — concurrent
    emitters must never share a trace id (the analyzer would join their
    traces into one)."""
    tracer = _live_tracer(rate=1.0)
    ids: list[str] = []
    lock = threading.Lock()

    def mint(n):
        got = [tracer.sample_traj(1, 0).trace_id for _ in range(n)]
        with lock:
            ids.extend(got)

    threads = [threading.Thread(target=mint, args=(200,))
               for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(ids) == 800 and len(set(ids)) == 800


def test_journal_survives_failed_rotation(tmp_path):
    """A failed rotation (rename target unwritable) counts one error and
    keeps appending to the ORIGINAL file — the bounding mechanism must
    never mute the journal it bounds."""
    path = str(tmp_path / "events.ndjson")
    journal = EventJournal(path, run_id="r", max_bytes=512)
    os.mkdir(path + ".1")  # os.replace onto a directory fails
    for i in range(40):
        journal.emit("checkpoint", version=i)
    assert journal.errors >= 1 and journal.written >= 39
    versions = [e["version"] for e in read_events(path, include_rotated=False)
                if e.get("event") == "checkpoint"]
    assert versions[-1] == 39  # later events still landed
    journal.close()
    journal.emit("checkpoint", version=99)  # closed: silent no-op
    assert versions[-1] == 39


def test_null_tracer_and_disabled_configure():
    assert trace.get_tracer() is trace.NULL_TRACER
    assert trace.configure(0.0) is trace.NULL_TRACER
    t = trace.get_tracer()
    assert t.sample_traj(1, 0) is None
    assert not t.sample_version(7)
    t.span("traj", "x", "env", 0, 1)  # no-op, no error
    assert trace.snapshot_spans() == []
    live = _live_tracer()
    assert trace.get_tracer() is live
    # a later rate-0 configure must NOT disable an explicit tracer
    assert trace.configure(0.0) is live


# -- events journal rotation (satellite) -----------------------------------

def test_journal_rotation_and_read_across_boundary(tmp_path):
    path = str(tmp_path / "events.ndjson")
    journal = EventJournal(path, run_id="r", max_bytes=2048)
    for i in range(200):
        journal.emit("trace_span", kind="traj", trace=f"t{i}", hop="env",
                     proc="p", t0_ns=i, t1_ns=i + 1)
    journal.close()
    assert journal.rotations >= 1
    assert os.path.exists(path + ".1")
    assert os.path.getsize(path) <= 2048
    events = read_events(path)
    # the retained window (rotated generation + live file) is
    # chronological, CONTIGUOUS, and ends with the last emit — the
    # rotation boundary loses nothing inside the two-generation window
    ids = [int(e["trace"][1:]) for e in events
           if e.get("event") == "trace_span"]
    assert ids and ids[-1] == 199
    assert ids == list(range(ids[0], 200))


def test_journal_rotation_torn_tail_tolerant(tmp_path):
    path = str(tmp_path / "events.ndjson")
    journal = EventJournal(path, run_id="r", max_bytes=1024)
    for i in range(60):
        journal.emit("checkpoint", version=i)
    journal.close()
    assert os.path.exists(path + ".1")
    # tear the LIVE file mid-line and the ROTATED file mid-line
    for p in (path, path + ".1"):
        with open(p, "ab") as f:
            f.write(b'{"event":"torn')
    events = read_events(path)
    versions = [e["version"] for e in events if e.get("event") == "checkpoint"]
    assert versions == sorted(versions)
    assert versions[-1] == 59


def test_journal_unbounded_without_max_bytes(tmp_path):
    path = str(tmp_path / "events.ndjson")
    journal = EventJournal(path, run_id="r")
    for i in range(100):
        journal.emit("checkpoint", version=i)
    journal.close()
    assert journal.rotations == 0 and not os.path.exists(path + ".1")
    assert len(read_events(path)) == 100


# -- analyzer + exports ----------------------------------------------------

def _synthetic_trace(tid="t1", base=1000, version=3, born_version=1,
                     proc_a="actor", proc_b="server"):
    us = 1000
    return [
        {"kind": "traj", "trace": tid, "hop": "env", "proc": proc_a,
         "t0_ns": base, "t1_ns": base + 50 * us, "version": born_version},
        {"kind": "traj", "trace": tid, "hop": "encode", "proc": proc_a,
         "t0_ns": base + 50 * us, "t1_ns": base + 60 * us},
        {"kind": "traj", "trace": tid, "hop": "send", "proc": proc_a,
         "t0_ns": base + 60 * us, "t1_ns": base + 65 * us},
        {"kind": "traj", "trace": tid, "hop": "ingest", "proc": proc_b,
         "t0_ns": base + 64 * us, "t1_ns": base + 64 * us},
        {"kind": "traj", "trace": tid, "hop": "dedup", "proc": proc_b,
         "t0_ns": base + 64 * us, "t1_ns": base + 66 * us},
        {"kind": "traj", "trace": tid, "hop": "staging", "proc": proc_b,
         "t0_ns": base + 66 * us, "t1_ns": base + 70 * us},
        {"kind": "traj", "trace": tid, "hop": "update", "proc": proc_b,
         "t0_ns": base + 80 * us, "t1_ns": base + 100 * us,
         "version": version},
    ]


def test_analyze_data_age_and_lag():
    spans = _synthetic_trace()
    report = trace.analyze(spans)
    tj = report["trajectories"]
    assert tj["traced"] == 1 and tj["complete"] == 1
    assert abs(tj["data_age_s"]["mean"] - 100e-6) < 1e-9
    assert tj["data_age_versions"]["mean"] == 2.0
    assert report["per_hop"]["traj:env"]["count"] == 1


def test_analyze_skew_guard_drops_cross_host_pairs():
    spans = _synthetic_trace()
    # the "env" stamp came from another HOST: born 400s in the future
    spans[0]["t0_ns"] += int(400e9)
    spans[0]["t1_ns"] += int(400e9)
    report = trace.analyze(spans)
    assert report["trajectories"]["data_age_s"]["count"] == 0
    assert report["skew_dropped"] == 1


def test_analyze_model_trace_ages():
    spans = [
        {"kind": "model", "trace": "v7", "hop": "dispatch", "proc": "s",
         "t0_ns": 0, "t1_ns": 1000, "version": 7},
        {"kind": "model", "trace": "v7", "hop": "publish", "proc": "s",
         "t0_ns": 1000, "t1_ns": 2000, "version": 7},
        {"kind": "model", "trace": "v7", "hop": "relay", "proc": "r",
         "t0_ns": 2500, "t1_ns": 2600, "version": 7},
        {"kind": "model", "trace": "v7", "hop": "swap", "proc": "a1",
         "t0_ns": 3000, "t1_ns": 4000, "version": 7, "actor": "a1"},
        {"kind": "model", "trace": "v7", "hop": "swap", "proc": "a2",
         "t0_ns": 3000, "t1_ns": 5000, "version": 7, "actor": "a2"},
    ]
    report = trace.analyze(spans)
    entry = report["models"]["traces"]["v7"]
    assert entry["actors"] == ["a1", "a2"] and entry["relay_hops"] == 1
    ages = report["models"]["model_age_s"]
    assert ages["count"] == 2 and abs(ages["max"] - 5e-6) < 1e-12
    assert "model age" in trace.render_report(report)


def test_chrome_trace_export():
    doc = trace.to_chrome_trace(_synthetic_trace())
    assert len(doc["traceEvents"]) == 7
    ev = doc["traceEvents"][0]
    assert ev["ph"] == "X" and ev["name"] == "env" and ev["pid"] == "actor"
    assert ev["dur"] == pytest.approx(50.0)  # us
    json.dumps(doc)  # must be valid JSON


def test_spans_round_trip_through_journal(tmp_path):
    path = str(tmp_path / "events.ndjson")
    telemetry.set_registry(Registry(run_id="j"))
    telemetry.set_journal(EventJournal(path, run_id="j"))
    tracer = trace.configure(1.0, journal=True)
    for s in _synthetic_trace():
        tracer.span(s["kind"], s["trace"], s["hop"],
                    s["t0_ns"], s["t1_ns"],
                    **{k: v for k, v in s.items()
                       if k not in ("kind", "trace", "hop", "proc",
                                    "t0_ns", "t1_ns")})
    telemetry.get_journal().close()
    spans = trace.load_spans([path])
    report = trace.analyze(spans)
    assert report["trajectories"]["complete"] == 1
    # the CLI consumes the same file
    import contextlib
    import io

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert trace.main([path]) == 0
    assert "data age" in out.getvalue()


def test_traces_endpoint_and_remote_top():
    """/traces serves the live ring; telemetry.top renders a REMOTE
    /snapshot (the --url fleet-debugging mode) against a live exporter
    (satellite 1)."""
    import urllib.request

    from relayrl_tpu.telemetry import top as top_mod
    from relayrl_tpu.telemetry.export import TelemetryExporter

    reg = Registry(run_id="remote")
    telemetry.set_registry(reg)
    tracer = trace.configure(1.0, journal=False)
    tracer.span("model", "v1", "swap", 0, 1000, version=1)
    tracer.observe_model_age(0.005)
    reg.counter("relayrl_server_trajectories_total").inc(3)
    exporter = TelemetryExporter(reg, port=0)
    try:
        with urllib.request.urlopen(exporter.url + "/traces",
                                    timeout=5) as resp:
            doc = json.loads(resp.read().decode())
        assert doc["schema"] == "relayrl-trace-v1" and doc["enabled"]
        assert doc["spans"][0]["hop"] == "swap"
        import contextlib
        import io

        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = top_mod.main(["--url", exporter.url, "--once"])
        assert rc == 0
        text = out.getvalue()
        assert "-- trace" in text  # the new section renders
        assert "model_age_seconds" in text
        assert "trajectories_total: 3" in text
    finally:
        exporter.close()


# -- spool + wire carriage -------------------------------------------------

def test_spool_trace_tag_keeps_seq_space_clean(tmp_path):
    from relayrl_tpu.runtime.spool import TrajectorySpool

    sent = []
    spool = TrajectorySpool(send_fn=lambda p, i: sent.append((p, i)),
                            max_entries=16)
    ctx_a = trace.TrajCtx("aa-1", 100, 1)
    ctx_b = trace.TrajCtx("bb-2", 200, 2)
    spool.send(b"x", "agent", trace=ctx_a.encode())
    spool.send(b"y", "agent", trace=ctx_b.encode())
    spool.send(b"z", "agent")  # untraced: no tag at all
    ids = [i for _, i in sent]
    assert ids[0] == f"agent#t{ctx_a.encode()}#s1"
    assert ids[1] == f"agent#t{ctx_b.encode()}#s2"
    assert ids[2] == "agent#s3"  # per-trajectory tags never reset seqs
    assert spool.sent_counts() == {"agent": 3}
    # replay re-ships the retained tagged ids verbatim
    sent.clear()
    assert spool.replay() == 3
    assert [i for _, i in sent] == ids


def test_spool_disk_restore_keys_seq_by_clean_id(tmp_path):
    from relayrl_tpu.runtime.spool import TrajectorySpool

    ctx = trace.TrajCtx("cc-3", 1, 1)
    spool = TrajectorySpool(send_fn=None, max_entries=16,
                            directory=str(tmp_path), name="s")
    spool.send(b"x", "agent", trace=ctx.encode())
    spool.send(b"y", "agent")
    spool.close()
    fresh = TrajectorySpool(send_fn=None, max_entries=16,
                            directory=str(tmp_path), name="s")
    # the restored counter is keyed by the CLEAN id — the next send must
    # continue the sequence, not fork a tagged seq space at 1
    assert fresh.next_seq("agent") == 3


def test_server_admit_splits_both_tags():
    """The ingest funnel's tag discipline without a live server: seq
    outermost, then the trace tag, attribution on the clean id."""
    ctx = trace.TrajCtx("dd-4", 123, 7)
    wire = tag_agent_seq(tag_agent_trace("fleet.lane2", ctx.encode()), 9)
    base, seq = split_agent_seq(wire)
    clean, got = trace.split_ctx(base)
    assert (clean, seq) == ("fleet.lane2", 9)
    assert got.born_ns == 123 and got.born_version == 7


@pytest.mark.skipif(
    not __import__("relayrl_tpu.types.columnar",
                   fromlist=["native_codec_available"]
                   ).native_codec_available(),
    reason="native codec not built")
def test_trace_tag_survives_native_columnar_raw_fallback():
    """Satellite 6 (the seq-tag lesson from PR 6, locked explicitly):
    the trace context coalesces with the envelope id, so the native C++
    decode path — including the raw-fallback branch that drops unknown
    envelope KEYS — must carry it verbatim on both the columnar fast
    path and the fallback payload."""
    import numpy as np

    from relayrl_tpu.transport.base import pack_trajectory_envelope
    from relayrl_tpu.types.columnar import (
        DecodedTrajectory,
        NativeDecoder,
        RawTrajectory,
        encode_columnar_frame,
    )

    ctx = trace.TrajCtx("ee-5", 456, 3)
    tagged = tag_agent_seq(tag_agent_trace("lane.7", ctx.encode()), 11)
    decoder = NativeDecoder()

    # columnar frame inside an envelope: the C++ envelope decoder carries
    # the id verbatim even though the RLD1 payload is opaque to it
    dt = DecodedTrajectory(
        agent_id="", n_steps=2, n_records=3, marker_truncated=False,
        columns={"o": np.zeros((2, 4), np.float32),
                 "a": np.zeros(2, np.int64),
                 "r": np.ones(2, np.float32),
                 "t": np.array([0, 1], np.uint8),
                 "u": np.array([1, 0], np.uint8),
                 "x": np.zeros(2, np.uint8)},
        aux={})
    frame = encode_columnar_frame(dt)
    env = pack_trajectory_envelope(tagged, frame)
    out = decoder.decode(env, has_envelope=True)
    assert out.agent_id == tagged, (
        f"native path mangled the tagged id: {out.agent_id!r}")

    # raw fallback: junk the columnar schema cannot represent still rides
    # with the id untouched
    junk_env = pack_trajectory_envelope(tagged, b"\x00not-a-trajectory")
    out = decoder.decode(junk_env, has_envelope=True)
    assert isinstance(out, (RawTrajectory, DecodedTrajectory))
    assert out.agent_id == tagged
    # and the server-side split still recovers the context
    clean, got = trace.split_ctx(split_agent_seq(out.agent_id)[0])
    assert clean == "lane.7" and got.born_ns == 456


# -- the actors' report tag (ISSUE 52) --------------------------------------

REPORT_BORN_NS, REPORT_VERSION = 0x5EED, 9


def _report_text(born_ns=REPORT_BORN_NS):
    """A report whose deltas are told apart by key: 6 steps, 1 install,
    timing ``i`` worth ``i + 1`` milliseconds."""
    from relayrl_tpu.telemetry.actor_ledger import TIMINGS, encode_report

    return encode_report(born_ns, REPORT_VERSION,
                         [6, 1] + [1000 * (i + 1)
                                   for i in range(len(TIMINGS))])


def _tag_orders():
    """Every order the spool and the tracer can apply the three tags in
    (the spool's own is report, trace, seq), with and without each."""
    import itertools

    for n in (1, 2, 3):
        for order in itertools.permutations("rts", n):
            if "r" in order:
                yield "".join(order)


def _tagged(agent, order, seq, ctx):
    from relayrl_tpu.transport.base import tag_agent_report

    wire = agent
    for tag in order:
        if tag == "r":
            # one born stamp on the wire: beside a trace context the
            # report carries none
            wire = tag_agent_report(
                wire, _report_text(0 if "t" in order else REPORT_BORN_NS))
        elif tag == "t":
            wire = tag_agent_trace(wire, ctx.encode())
        else:
            wire = tag_agent_seq(wire, seq)
    return wire


class _NullTransport:
    on_trajectory = on_trajectory_decoded = None
    get_model = on_register = on_unregister = None

    def start(self):
        pass

    def stop(self):
        pass

    def publish_model(self, version, raw):
        pass


@pytest.fixture
def quiet_server(tmp_path, monkeypatch):
    """A TrainingServer that is never started: what its ingest funnel
    admits stays in its queues for the test to read."""
    import relayrl_tpu.runtime.server as srv_mod

    monkeypatch.chdir(tmp_path)
    monkeypatch.setattr(srv_mod, "make_server_transport",
                        lambda *a, **k: _NullTransport())
    server = srv_mod.TrainingServer(
        "REINFORCE", obs_dim=4, act_dim=2, env_dir=str(tmp_path),
        hyperparams={"traj_per_epoch": 2, "hidden_sizes": [8],
                     "seed_salt": 0}, start=False)
    yield server
    server.disable_server()


def _native_or_skip():
    from relayrl_tpu.types.columnar import native_codec_available

    if not native_codec_available():
        pytest.skip("native codec not built")


def _columnar_frame():
    import numpy as np

    from relayrl_tpu.types.columnar import (
        DecodedTrajectory,
        encode_columnar_frame,
    )

    return encode_columnar_frame(DecodedTrajectory(
        agent_id="", n_steps=2, n_records=3, marker_truncated=False,
        columns={"o": np.zeros((2, 4), np.float32),
                 "a": np.zeros(2, np.int64), "r": np.ones(2, np.float32),
                 "t": np.array([0, 1], np.uint8),
                 "u": np.array([1, 0], np.uint8),
                 "x": np.zeros(2, np.uint8)},
        aux={}))


def _deliver(server, carriage, wire_id):
    """One envelope through ``carriage``'s receive path into the server's
    funnel; returns ``(clean id, origin)`` of what was admitted, or None."""
    import numpy as np

    from relayrl_tpu.transport.base import (
        pack_trajectory_envelope,
        unpack_trajectory_envelope,
    )
    from relayrl_tpu.types.action import ActionRecord
    from relayrl_tpu.types.columnar import NativeDecoder, RawTrajectory
    from relayrl_tpu.types.trajectory import serialize_actions

    records = serialize_actions([ActionRecord(
        obs=np.zeros(4, np.float32), act=np.int64(0), rew=1.0, done=True)])
    if carriage in ("zmq", "grpc"):
        # both unpack the shared envelope in Python; grpc asks for an
        # admission verdict first
        got_id, payload = unpack_trajectory_envelope(
            pack_trajectory_envelope(wire_id, records))
        if carriage == "grpc":
            assert server._check_ingest(got_id) is None
        server._on_trajectory(got_id, payload)
    elif carriage == "native":
        # the C++ core decodes the envelope and hands a decoded batch over
        out = NativeDecoder().decode(
            pack_trajectory_envelope(wire_id, _columnar_frame()),
            has_envelope=True)
        assert out.agent_id == wire_id
        if isinstance(out, RawTrajectory):  # as native_bindings routes it
            from relayrl_tpu.types.columnar import parse_frame

            out = parse_frame(out.payload, agent_id=out.agent_id)
        server._on_trajectory_decoded([out])
    else:  # native_raw: what the columnar schema cannot hold, id verbatim
        out = NativeDecoder().decode(
            pack_trajectory_envelope(wire_id, b"\x00not-a-trajectory"),
            has_envelope=True)
        assert isinstance(out, RawTrajectory) and out.agent_id == wire_id
        server._on_trajectory(out.agent_id, records)
    if carriage == "native":
        if server._decoded.empty():
            return None
        item = server._decoded.get_nowait()
        item = item[0] if isinstance(item, list) else item
        return item.agent_id, getattr(item, "trace_ctx", None)
    if server._ingest.empty():
        return None
    agent_id, _seq, ctx, _payload = server._ingest.get_nowait()
    return agent_id, ctx


@pytest.mark.parametrize("carriage", ["zmq", "grpc", "native", "native_raw"])
def test_report_tag_survives_every_carriage_and_counts_once(quiet_server,
                                                            carriage):
    """The ``#r`` tag beside ``#t`` and ``#s`` in every order of
    application, through each transport's receive path: stripped from the
    attribution and dedup keys, its deltas added to the server's ledgers
    for an ADMITTED envelope only, the born stamp kept on what is admitted;
    an untagged id admits as before."""
    from relayrl_tpu.telemetry.actor_ledger import TIMINGS

    if carriage.startswith("native"):
        _native_or_skip()
    server = quiet_server
    ctx = trace.TrajCtx("ab-7", 0x7777, 4)
    admitted = 0
    for seq, order in enumerate(_tag_orders(), start=1):
        wire = _tagged("fleet.lane2", order, seq, ctx)
        clean, origin = _deliver(server, carriage, wire)
        admitted += 1
        assert clean == "fleet.lane2", (order, wire)
        if "t" in order:
            assert (origin.trace_id, origin.born_ns) == ("ab-7", 0x7777)
        else:
            assert origin.trace_id is None
            assert (origin.born_ns, origin.born_version) == (
                REPORT_BORN_NS, REPORT_VERSION)
        assert server.stats["actor_steps"] == 6 * admitted
        assert server.stats["actor_installs"] == admitted
        for i, key in enumerate(TIMINGS):
            assert server.timings[f"actor_{key}"] == pytest.approx(
                admitted * (i + 1) * 1e-3), (order, key)
        if "s" in order:
            # a spool replay re-sends the tagged id: the dedup ledger's
            # verdict keeps it out of the queues AND out of the ledgers —
            # keyed by the clean id, whatever the other tags say
            for again in (wire, _tagged("fleet.lane2", "rs", seq, ctx)):
                assert _deliver(server, carriage, again) is None
            assert server.stats["actor_steps"] == 6 * admitted
            assert server.timings["actor_step_s"] == pytest.approx(
                admitted * 1e-3)
    # a raw transport user, an older actor: no tag, admitted, no origin
    before = dict(server.timings)
    clean, origin = _deliver(server, carriage, "plain-agent")
    assert (clean, origin) == ("plain-agent", None)
    clean, origin = _deliver(server, carriage,
                             tag_agent_seq("plain-agent", 1))
    assert (clean, origin) == ("plain-agent", None)

    def less_admission(timings):
        # what grows with every delivery, tagged or not: the receive
        # thread's own time inside the server's entry (rl:ingest.admit)
        return {k: v for k, v in timings.items() if k != "admit_s"}

    assert less_admission(server.timings) == less_admission(before)
    assert server.timings["admit_s"] > before["admit_s"]
    # a report this build cannot read is stripped all the same
    clean, origin = _deliver(server, carriage, "fleet.lane2#r9.1.2.3")
    assert (clean, origin) == ("fleet.lane2", None)
    assert less_admission(server.timings) == less_admission(before)


def test_report_tag_never_reaches_the_quarantine_key(quiet_server):
    """An ack-capable transport asks ``_check_ingest`` with the tagged id:
    the verdict is the clean agent's."""
    from relayrl_tpu.transport.base import NACK_QUARANTINED

    server = quiet_server
    book = server.guardrails.quarantine
    while not book.is_quarantined("fleet.lane2"):
        book.strike("fleet.lane2", "test")
    ctx = trace.TrajCtx("ab-8", 1, 1)
    for order in _tag_orders():
        verdict = server._check_ingest(_tagged("fleet.lane2", order, 5, ctx))
        assert verdict is not None and verdict[0] == NACK_QUARANTINED, order
        assert server._check_ingest(
            _tagged("fleet.lane3", order, 5, ctx)) is None


def test_spool_and_relay_key_by_the_clean_id(tmp_path):
    from relayrl_tpu.runtime.spool import TrajectorySpool
    from relayrl_tpu.transport.base import split_agent_tags

    sent = []
    spool = TrajectorySpool(send_fn=lambda p, i: sent.append(i),
                            max_entries=16, directory=str(tmp_path),
                            name="s")
    ctx = trace.TrajCtx("cc-9", 5, 1)
    spool.send(b"x", "agent", trace=ctx.encode(), report=_report_text(0))
    spool.send(b"y", "agent", report=_report_text())
    assert sent[0] == f"agent#r{_report_text(0)}#t{ctx.encode()}#s1"
    assert sent[1] == f"agent#r{_report_text()}#s2"
    assert [split_agent_tags(i)[:2] for i in sent] == [("agent", 1),
                                                       ("agent", 2)]
    spool.close()
    fresh = TrajectorySpool(send_fn=None, max_entries=16,
                            directory=str(tmp_path), name="s")
    assert fresh.next_seq("agent") == 3   # not a tagged id's seq space


# -- histogram bucket audit (satellite) ------------------------------------

def test_log_bucket_presets():
    grid = log_buckets(1e-4, 60.0, per_decade=3)
    assert grid[0] == 1e-4 and grid[-1] >= 60.0
    assert list(grid) == sorted(set(grid))
    assert LATENCY_BUCKETS_WIDE[-1] >= 60.0
    assert AGE_BUCKETS[-1] >= 600.0  # past the 300 s skew guard
    with pytest.raises(ValueError):
        log_buckets(0, 1)


def test_audited_sites_use_wide_grids():
    from relayrl_tpu.transport.base import agent_wire_metrics

    telemetry.set_registry(Registry(run_id="audit"))
    m = agent_wire_metrics("zmq")
    assert m["send_seconds"].buckets == LATENCY_BUCKETS_WIDE
    assert m["model_deliver_seconds"].buckets == LATENCY_BUCKETS_WIDE


# -- live end-to-end drill (fast: one direct actor over live zmq) ----------

def _free_port():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_live_zmq_end_to_end_trace(tmp_path, capsys):
    """The tracing acceptance drill on one actor and no relay: one
    trajectory traced env→encode→send→ingest→dedup→staging→update
    over LIVE zmq with monotonic hop starts and per-plane non-overlap,
    dispatch→publish→swap model traces, data-age/model-age observed,
    and the trace-side version lag matching the train_version_lag
    histogram."""
    from relayrl_tpu.envs import make
    from relayrl_tpu.runtime.agent import Agent, run_gym_loop
    from relayrl_tpu.runtime.server import TrainingServer

    telemetry.set_registry(Registry(run_id="drill"))
    trace.configure(1.0, ring=8192, journal=False)
    addrs = {
        "agent_listener_addr": f"tcp://127.0.0.1:{_free_port()}",
        "trajectory_addr": f"tcp://127.0.0.1:{_free_port()}",
        "model_pub_addr": f"tcp://127.0.0.1:{_free_port()}",
    }
    server = TrainingServer(
        "REINFORCE", obs_dim=4, act_dim=2,
        hyperparams={"traj_per_epoch": 2, "seed_salt": 0},
        config_path=str(tmp_path / "relayrl_config.json"),
        env_dir=str(tmp_path), server_type="zmq", **addrs)
    server.wait_warmup(60)
    agent = Agent(server_type="zmq", seed=3,
                  model_path=str(tmp_path / "client.rlx"),
                  config_path=str(tmp_path / "relayrl_config.json"),
                  agent_listener_addr=addrs["agent_listener_addr"],
                  trajectory_addr=addrs["trajectory_addr"],
                  model_sub_addr=addrs["model_pub_addr"])
    env = make("CartPole-v1")
    deadline = time.time() + 60
    while time.time() < deadline and (server.stats["updates"] < 2
                                      or agent.model_version < 1):
        run_gym_loop(agent, env, episodes=2, max_steps=40)
        time.sleep(0.05)
    server.drain(30)
    time.sleep(0.5)
    spans = trace.snapshot_spans()
    agent.disable_agent()
    server.disable_server()

    order = ("env", "encode", "send", "ingest", "dedup", "staging",
             "update")
    traj: dict[str, dict] = {}
    for s in spans:
        if s["kind"] == "traj":
            traj.setdefault(s["trace"], {})[s["hop"]] = s
    complete = {t: h for t, h in traj.items() if set(order) <= set(h)}
    assert complete, f"no complete trace in {len(traj)} traced"
    for hops in complete.values():
        assert all(hops[a]["t0_ns"] <= hops[b]["t0_ns"]
                   for a, b in zip(order, order[1:]))
        for chain in (("env", "encode", "send"),
                      ("ingest", "dedup", "staging", "update")):
            assert all(hops[a]["t1_ns"] <= hops[b]["t0_ns"]
                       for a, b in zip(chain, chain[1:]))
    model = {}
    for s in spans:
        if s["kind"] == "model":
            model.setdefault(s["trace"], set()).add(s["hop"])
    assert any({"dispatch", "publish", "receipt", "swap"} <= hops
               for hops in model.values()), model
    report = trace.analyze(spans)
    assert report["trajectories"]["data_age_s"]["count"] > 0
    assert report["models"]["model_age_s"]["count"] > 0
    snap = telemetry.get_registry().snapshot()
    lag_hist = next(m for m in snap["metrics"]
                    if m["name"] == "relayrl_rlhf_train_lag_versions")
    assert lag_hist["count"] >= len(complete)
    hist_mean = lag_hist["sum"] / lag_hist["count"]
    trace_mean = report["trajectories"]["data_age_versions"]["mean"]
    assert abs(trace_mean - hist_mean) <= 0.5


def test_live_zmq_actor_report_and_data_age(tmp_path):
    """No tracer, one actor over LIVE zmq: the steps the actor took arrive
    in ``server.stats["actor_steps"]`` on the reports its trajectories
    carry, its ledger's time in ``server.timings``, and every update's
    ``host:dispatch`` span says how old the batch it consumed was."""
    import jax
    from _util import xplane_events

    from relayrl_tpu.envs import make
    from relayrl_tpu.runtime.agent import Agent, run_gym_loop
    from relayrl_tpu.runtime.server import TrainingServer

    telemetry.set_registry(Registry(run_id="report"))
    addrs = {
        "agent_listener_addr": f"tcp://127.0.0.1:{_free_port()}",
        "trajectory_addr": f"tcp://127.0.0.1:{_free_port()}",
        "model_pub_addr": f"tcp://127.0.0.1:{_free_port()}",
    }
    server = TrainingServer(
        "REINFORCE", obs_dim=4, act_dim=2,
        hyperparams={"traj_per_epoch": 2, "seed_salt": 0},
        config_path=str(tmp_path / "relayrl_config.json"),
        env_dir=str(tmp_path), server_type="zmq", **addrs)
    server.wait_warmup(60)
    agent = Agent(server_type="zmq", seed=3,
                  model_path=str(tmp_path / "client.rlx"),
                  config_path=str(tmp_path / "relayrl_config.json"),
                  agent_listener_addr=addrs["agent_listener_addr"],
                  trajectory_addr=addrs["trajectory_addr"],
                  model_sub_addr=addrs["model_pub_addr"])
    env = make("CartPole-v1")
    episodes = 0
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        deadline = time.time() + 60
        while time.time() < deadline and (server.stats["updates"] < 2
                                          or agent.model_version < 1):
            run_gym_loop(agent, env, episodes=2, max_steps=40)
            episodes += 2
            time.sleep(0.05)
        while (time.time() < deadline
               and server.stats["trajectories"] < episodes):
            time.sleep(0.05)
        server.drain(30)
    finally:
        jax.profiler.stop_trace()
    ledger = agent.actor.ledger
    stats, timings = dict(server.stats), dict(server.timings)
    installs = ledger.counts["installs"]
    agent.disable_agent()
    server.disable_server()

    assert stats["trajectories"] == episodes and stats["updates"] >= 2
    # every episode ends on a terminal marker, shipped between two steps:
    # each report holds every step taken before it
    assert stats["actor_steps"] == ledger.counts["steps"] > 0
    assert 0 < timings["actor_step_s"] <= ledger.timings["step_s"]
    assert 0 < timings["actor_infer_s"] <= timings["actor_step_s"]
    assert timings["actor_wall_s"] == pytest.approx(
        timings["actor_step_s"] + timings["actor_env_s"], abs=1e-5)
    assert installs >= 1 and stats["actor_installs"] <= installs
    snap = telemetry.get_registry().snapshot()
    age = next(m for m in snap["metrics"]
               if m["name"] == "relayrl_trace_data_age_seconds")
    assert age["count"] == episodes
    events = xplane_events(tmp_path / "trace")
    dispatches = [st for *_x, st in events["host:dispatch"]]
    assert len(dispatches) >= 2
    for st in dispatches:
        assert 0 < st["data_age_us"] <= st["data_age_max_us"] < 60e6

