"""The span primitive (telemetry/spans.py) and what it is wired to: always-on
totals, the profiler's time line, the sampled causal trace, and the stable
device names (the two flash kernels, the jitted update)."""

import gc
import re
import subprocess
import sys
import threading
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _util import burn_cpu, xplane_events

from relayrl_tpu.telemetry import spans as spans_mod
from relayrl_tpu.telemetry import trace as trace_mod
from relayrl_tpu.telemetry.spans import span
from relayrl_tpu.types.action import ActionRecord
from relayrl_tpu.types.trajectory import serialize_actions

OBS_DIM, ACT_DIM = 4, 2

LEARNER_NAMES = (
    "host:wait_data", "host:accumulate", "host:stage_batch", "host:dispatch",
    "host:publish_submit", "host:epoch_log",
    "rl:learner.item", "rl:learner.dispatch", "rl:batch.pad",
    "rl:batch.stack", "rl:dispatch.enqueue", "rl:dispatch.fence",
    "rl:ingest.decode", "rl:ingest.admit", "rl:publish",
    "rl:publish.gather", "rl:publish.encode", "rl:publish.send")
# a span's CPU time against its duration: two clocks, read one after the other
CLOCK_SLACK_NS = 200_000
THREAD_ROLES = ("learner", "staging", "ingest", "publish")
ACTOR_TIMINGS = ("step_s", "infer_s", "record_s", "encode_s", "send_s",
                 "env_s", "cpu_s", "wall_s", "model_decode_s", "swap_s",
                 "model_install_s", "gc_s")
ACTOR_COUNTS = ("steps", "installs")
TIMINGS = ("decode_s", "dispatch_s", "device_wait_s", "publish_s",
           "learner_idle_s", "warmup_s", "gc_s", "admit_s",
           *(f"cpu_{role}_s" for role in (*THREAD_ROLES, "process")),
           *(f"actor_{k}" for k in ACTOR_TIMINGS))
STATS = ("trajectories", "updates", "dropped", "dropped_nonfinite",
         "learner_errors", "publish_errors", "warmup_failed",
         *(f"actor_{k}" for k in ACTOR_COUNTS))


def _episode(n, seed=0):
    rng = np.random.default_rng(seed)
    return [ActionRecord(
        obs=rng.standard_normal(OBS_DIM).astype(np.float32),
        act=np.int64(rng.integers(ACT_DIM)), rew=float(rng.random()),
        data={"logp_a": np.float32(-0.69),
              "v": np.float32(rng.standard_normal())},
        done=(i == n - 1)) for i in range(n)]


class Observed:
    def __init__(self):
        self.values = []

    def observe(self, v):
        self.values.append(v)


class StubTransport:
    def __init__(self):
        self.published = []
        self.on_trajectory = self.on_trajectory_decoded = None
        self.get_model = self.on_register = self.on_unregister = None

    def start(self):
        pass

    def stop(self):
        pass

    def publish_model(self, version, raw):
        self.published.append((version, len(raw)))


@pytest.fixture
def impala_server(tmp_cwd, monkeypatch):
    """A TrainingServer on an in-memory transport, IMPALA, 3 episodes an
    update; fed by ``feed(server, n_updates)``."""
    import relayrl_tpu.runtime.server as srv_mod

    stub = StubTransport()
    monkeypatch.setattr(srv_mod, "make_server_transport",
                        lambda *a, **k: stub)
    server = srv_mod.TrainingServer(
        "IMPALA", obs_dim=OBS_DIM, act_dim=ACT_DIM, env_dir=str(tmp_cwd),
        hyperparams={"traj_per_epoch": 3, "hidden_sizes": [16],
                     "seed_salt": 0}, start=False)
    yield server, stub
    server.disable_server()


def _run_updates(server, n_updates):
    """Half the episodes through the staging threads (raw payloads), half
    straight into the learner's queue."""
    server.enable_server()
    assert server.wait_warmup(120)
    for i in range(3 * n_updates):
        ep = _episode(5 + i, seed=i)
        if i % 2:
            server._on_trajectory(f"agent-{i}", serialize_actions(ep))
        else:
            server._decoded.put(ep)
    assert server.drain(timeout=120)


class TestPrimitive:
    def test_totals_metric_and_stamps(self):
        ledger = {"a_s": 1.0}
        metric = Observed()
        with span("rl:test.a", ledger, "a_s", metric=metric) as sp:
            time.sleep(0.01)
        assert sp.t1_ns > sp.t0_ns
        assert sp.seconds == pytest.approx((sp.t1_ns - sp.t0_ns) * 1e-9)
        assert sp.seconds >= 0.01
        assert ledger["a_s"] == pytest.approx(1.0 + sp.seconds)
        assert metric.values == [pytest.approx(sp.seconds)]

    def test_total_is_kept_when_the_block_raises(self):
        ledger = {"a_s": 0.0}
        with pytest.raises(ValueError):
            with span("rl:test.a", ledger, "a_s") as sp:
                raise ValueError("boom")
        assert ledger["a_s"] == pytest.approx(sp.seconds) and sp.t1_ns

    def test_nesting(self):
        ledger = {"outer_s": 0.0, "inner_s": 0.0}
        with span("rl:test.outer", ledger, "outer_s") as outer:
            for _ in range(3):
                with span("rl:test.inner", ledger, "inner_s") as inner:
                    time.sleep(0.002)
        assert outer.t0_ns <= inner.t0_ns and inner.t1_ns <= outer.t1_ns
        assert 0.006 <= ledger["inner_s"] <= ledger["outer_s"]

    def test_threads_do_not_share_a_span(self):
        ledgers = [{"s": 0.0}, {"s": 0.0}]
        seen = [None, None]
        gate = threading.Barrier(2)

        def work(i):
            gate.wait(timeout=10)
            for _ in range(200):
                with span("rl:test.thread", ledgers[i], "s") as sp:
                    pass
            seen[i] = sp

        threads = [threading.Thread(target=work, args=(i,)) for i in (0, 1)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads)
        assert seen[0] is not seen[1]
        assert all(0 < led["s"] < 1.0 for led in ledgers)

    def test_profiler_off_builds_no_annotation(self, monkeypatch):
        def never(*a, **k):
            raise AssertionError("annotation built with profiler off")

        assert not jax.profiler.TraceAnnotation.is_enabled()
        with span("host:test"):     # resolves the class on first use
            pass
        assert spans_mod._annotation is jax.profiler.TraceAnnotation
        monkeypatch.setattr(spans_mod, "_annotation", never)
        with span("host:test", version=3) as sp:
            sp.note(bytes=1)
        assert sp._ann is None and sp.seconds >= 0

    @pytest.mark.parametrize("name,args", [
        ("host:test_scope", {}),
        ("rl:test.args", {"version": 7, "bytes": 4096}),
    ])
    def test_profiler_on_records_name_and_arguments(self, tmp_path, name,
                                                    args):
        jax.profiler.start_trace(str(tmp_path))
        try:
            with span(name, **args) as sp:
                sp.note(mono_ns=sp.t0_ns)
                jax.block_until_ready(jnp.ones(8) * 2)
        finally:
            jax.profiler.stop_trace()
        (_line, _start, dur, stats), = xplane_events(tmp_path)[name]
        assert dur > 0
        assert 0 <= stats.pop("cpu_ns") <= dur + CLOCK_SLACK_NS
        assert 0 < stats.pop("cpu_wall_ns") <= dur
        assert stats == {**args, "mono_ns": sp.t0_ns}


class TestCpuTime:
    """A traced span carries its thread's CPU time, ``cpu_ns`` — every span
    of a name but no two within ``CPU_STAMP_EVERY_NS`` of each other; with no
    profiler no CPU clock is read."""

    @pytest.fixture(autouse=True)
    def _no_stamp_yet(self, monkeypatch):
        monkeypatch.setattr(spans_mod, "_cpu_stamped", {})

    @pytest.mark.parametrize("body,least,most", [
        ("busy", 0.5, 1.0), ("sleeping", 0.0, 0.1)])
    def test_traced_span_carries_its_threads_cpu_time(self, tmp_path, body,
                                                      least, most):
        work = {"busy": lambda: burn_cpu(0.05),
                "sleeping": lambda: time.sleep(0.1)}[body]
        jax.profiler.start_trace(str(tmp_path))
        try:
            with span("rl:test.cpu"):
                work()
        finally:
            jax.profiler.stop_trace()
        (_line, _start, dur, stats), = xplane_events(tmp_path)["rl:test.cpu"]
        assert 0 <= stats["cpu_ns"] <= dur + CLOCK_SLACK_NS
        assert least * dur <= stats["cpu_ns"] <= most * dur + CLOCK_SLACK_NS
        # the wall time between the same two reads: inside the annotation
        assert 0.9 * dur <= stats["cpu_wall_ns"] <= dur

    def test_a_child_spans_cpu_time_is_inside_its_parents(self, tmp_path):
        jax.profiler.start_trace(str(tmp_path))
        try:
            with span("rl:test.outer"):
                burn_cpu(0.01)
                with span("rl:test.inner"):
                    burn_cpu(0.02)
        finally:
            jax.profiler.stop_trace()
        events = xplane_events(tmp_path)
        (*_o, outer), = events["rl:test.outer"]
        (*_i, inner), = events["rl:test.inner"]
        assert 0.02e9 <= inner["cpu_ns"] <= outer["cpu_ns"] - 0.01e9

    def test_a_names_stamps_are_spaced_and_names_do_not_share(self,
                                                              tmp_path):
        """A burst of one name inside the spacing: its first span alone is
        stamped; another name beside it has a clock of its own; after the
        spacing the name is stamped again."""
        jax.profiler.start_trace(str(tmp_path))
        try:
            t0 = time.monotonic_ns()
            for _ in range(20):
                with span("rl:test.burst"):
                    pass
            with span("rl:test.other"):
                pass
            burst_ns = time.monotonic_ns() - t0
            time.sleep(spans_mod.CPU_STAMP_EVERY_NS * 1.2e-9)
            with span("rl:test.burst"):
                pass
        finally:
            jax.profiler.stop_trace()
        assert burst_ns < spans_mod.CPU_STAMP_EVERY_NS
        events = xplane_events(tmp_path)
        burst = sorted(events["rl:test.burst"], key=lambda e: e[1])
        assert ["cpu_ns" in st for *_x, st in burst] == (
            [True] + 19 * [False] + [True])
        (*_o, other), = events["rl:test.other"]
        assert "cpu_ns" in other

    def test_profiler_off_reads_no_cpu_clock(self, monkeypatch):
        def never():
            raise AssertionError("CPU clock read with the profiler off")

        with span("host:test"):     # resolves the profiler's check
            pass
        assert not jax.profiler.TraceAnnotation.is_enabled()
        monkeypatch.setattr(time, "thread_time_ns", never)
        ledger = {"a_s": 0.0}
        with span("rl:test.a", ledger, "a_s") as sp:
            pass
        assert sp._ann is None and ledger["a_s"] == sp.seconds
        assert not hasattr(sp, "_cpu0_ns")


class TestBatchSpans:
    @pytest.mark.parametrize("lens,moved", [((5, 9, 30), 0),
                                            ((5, 9, 100), 2)])
    def test_one_stack_span_a_drained_batch_with_its_counts(
            self, tmp_path, lens, moved):
        """``rl:batch.pad`` once an episode, ``rl:batch.stack`` once a
        drained batch: ``moved`` counts the rows that an episode of a
        larger bucket made the buffer copy a second time."""
        from relayrl_tpu.data import EpochBuffer

        buf = EpochBuffer(obs_dim=OBS_DIM, act_dim=ACT_DIM,
                          traj_per_epoch=3, buckets=(64, 256))
        jax.profiler.start_trace(str(tmp_path))
        try:
            for i, n in enumerate(lens):
                buf.add_episode(_episode(n, seed=i))
            batch = buf.drain()
        finally:
            jax.profiler.stop_trace()
        events = xplane_events(tmp_path)
        assert len(events["rl:batch.pad"]) == 3
        (_line, _start, dur, stats), = events["rl:batch.stack"]
        assert 0 <= stats.pop("cpu_ns") <= dur + CLOCK_SLACK_NS
        assert 0 < stats.pop("cpu_wall_ns") <= dur
        assert stats == {
            "valid": sum(lens), "padded": 3 * batch.horizon,
            "bytes": sum(v.nbytes for v in batch.as_dict().values()),
            "moved": moved}

    @pytest.mark.parametrize("threshold,flat", [(None, 0), (480, 1), (1, 3)],
                             ids=["as-it-is", "the-frames", "every-array"])
    def test_stage_batch_span_counts_its_flat_puts(
            self, tmp_path, monkeypatch, threshold, flat):
        """``host:stage_batch`` carries ``flat`` — the arrays it put as
        flat bytes and shaped on the device, 0 when the dict went as it
        is — and the batch's ``bytes``; ``relayrl_learner_h2d_flat_total``
        advances by the same."""
        import types

        from relayrl_tpu import telemetry
        from relayrl_tpu.algorithms import base

        rng = np.random.default_rng(0)
        batch = {"obs": rng.integers(0, 256, (6, 5, 16), dtype=np.uint8),
                 "act": np.zeros((6, 5), np.int32),
                 "rew": np.zeros((6, 5), np.float32),
                 "last_val": np.zeros((6,), np.float32)}
        if threshold:
            monkeypatch.setattr(base, "_H2D_FLAT_BYTES", threshold)
        telemetry.set_registry(telemetry.Registry(run_id="h2d"))
        jax.profiler.start_trace(str(tmp_path))
        try:
            for _ in range(2):
                staged = base.AlgorithmBase.stage_batch(
                    types.SimpleNamespace(), batch)
            total = telemetry.get_registry().counter(
                "relayrl_learner_h2d_flat_total").total()
        finally:
            jax.profiler.stop_trace()
            telemetry.reset_for_tests()
        assert all(np.array_equal(staged[k], batch[k]) for k in batch)
        spans = xplane_events(tmp_path)["host:stage_batch"]
        assert [{k: v for k, v in stats.items()
                 if k not in ("cpu_ns", "cpu_wall_ns")}
                for *_x, stats in spans] == 2 * [{
            "flat": flat, "bytes": sum(v.nbytes for v in batch.values())}]
        assert total == 2 * flat


class TestTracerSink:
    @pytest.fixture(autouse=True)
    def _tracer(self):
        trace_mod.configure(1.0, journal=False)
        yield
        trace_mod.reset_for_tests()

    def test_hop_shares_the_spans_stamps(self):
        ledger = {"x_s": 0.0}
        with span("rl:test.hop", ledger, "x_s") as sp:
            sp.hop("model", "v9", "encode", version=9, bytes=10)
        rec, = trace_mod.snapshot_spans()
        assert (rec["t0_ns"], rec["t1_ns"]) == (sp.t0_ns, sp.t1_ns)
        assert rec["hop"] == "encode" and rec["bytes"] == 10
        assert ledger["x_s"] == (rec["t1_ns"] - rec["t0_ns"]) * 1e-9

    def test_hop_after_exit_records_at_once_with_the_spans_stamps(self):
        """An actor draws a trajectory's trace context after the unroll is
        encoded: the hop still carries the encode span's own stamps."""
        with span("rl:test.done") as sp:
            pass
        assert trace_mod.snapshot_spans() == []
        sp.hop("traj", "t-1", "encode", agent="a")
        rec, = trace_mod.snapshot_spans()
        assert (rec["t0_ns"], rec["t1_ns"]) == (sp.t0_ns, sp.t1_ns)
        assert rec["hop"] == "encode" and rec["agent"] == "a"

    def test_fence_total_and_ring_span_are_one_interval(self):
        from relayrl_tpu.algorithms.dispatch import InflightWindow

        win = InflightWindow(max_in_flight=0)
        win.push(jnp.float32(1.0), version=4)
        rec, = [s for s in trace_mod.snapshot_spans() if s["hop"] == "fence"]
        assert rec["version"] == 4 and win.fenced_count == 1
        assert win.device_wait_s == (rec["t1_ns"] - rec["t0_ns"]) * 1e-9


ACTOR_NAMES = ("rl:actor.step", "rl:actor.infer", "rl:actor.record",
               "rl:actor.encode", "rl:actor.send", "rl:actor.model_install",
               "rl:actor.model_decode", "rl:actor.swap")


class FakeClock:
    """Stands in for the span primitive's clock: a microsecond a stamp, and
    ``jump`` for what a patched function is to have cost."""

    def __init__(self):
        self.ns = 1_000_000

    def __call__(self):
        self.ns += 1_000
        return self.ns

    def jump(self, seconds):
        self.ns += int(seconds * 1e9)


class ActorRig:
    """An actor host of either kind over a stub transport: the host's send
    hook ships through ``agent.ship_unroll`` as ``Agent`` / ``VectorAgent``
    do, with no spool, so every span of the actor tier runs."""

    LANES = 3

    def __init__(self, tmp_cwd, kind, max_traj_length=4):
        from relayrl_tpu.algorithms import build_algorithm
        from relayrl_tpu.runtime.agent import ship_unroll
        from relayrl_tpu.runtime.policy_actor import PolicyActor
        from relayrl_tpu.runtime.vector_actor import VectorActorHost

        self.bundle = build_algorithm(
            "REINFORCE", obs_dim=OBS_DIM, act_dim=ACT_DIM, hidden_sizes=[8],
            seed_salt=0,
            logger_kwargs={"output_dir": str(tmp_cwd / "logs")}).bundle()
        self.sent = []
        self.transport = types.SimpleNamespace(
            identity="rig", request_resync=lambda held: None,
            send_trajectory=lambda payload, agent_id=None:
                self.sent.append((agent_id, payload)))
        self.spool = None
        self.model_path = str(tmp_cwd / "client.rlx")
        if kind == "vector":
            self.lanes = self.LANES
            self.host = host = VectorActorHost(
                self.bundle, num_envs=self.lanes,
                max_traj_length=max_traj_length,
                on_send=lambda lane, payload: ship_unroll(
                    self, f"rig.lane{lane}", payload, host.shipping(lane),
                    host.version, host.ledger))
            self.step = lambda: host.request_for_actions(
                np.zeros((self.lanes, OBS_DIM), np.float32))
            self.finish = lambda: host.flag_last_action(0, 1.0)
        else:
            self.lanes = 1
            self.host = host = PolicyActor(
                self.bundle, max_traj_length=max_traj_length,
                on_send=lambda payload: ship_unroll(
                    self, "rig", payload,
                    (host.trajectory.born_ns, host.trajectory.encode_span),
                    host.version, host.ledger))
            self.step = lambda: host.request_for_action(
                np.zeros(OBS_DIM, np.float32))
            self.finish = lambda: host.flag_last_action(1.0)

    def deliver_next_model(self):
        from relayrl_tpu.runtime.agent import _deliver_model
        from relayrl_tpu.types.model_bundle import ModelBundle

        newer = ModelBundle(version=self.host.version + 1,
                            arch=self.bundle.arch, params=self.bundle.params)
        _deliver_model(self.host, self.transport, self.model_path, "rig",
                       newer.version, newer.to_bytes())


@pytest.mark.parametrize("kind", ["vector", "policy"])
class TestActorSpans:
    def test_a_stepped_host_fills_every_key_of_its_ledger(self, tmp_cwd,
                                                          kind):
        from relayrl_tpu.telemetry.actor_ledger import COUNTS, TIMINGS

        rig = ActorRig(tmp_cwd, kind)
        t, counts = rig.host.timings, rig.host.counts
        assert tuple(t) == TIMINGS == ACTOR_TIMINGS
        assert tuple(counts) == COUNTS == ACTOR_COUNTS
        assert not any(t.values()) and not any(counts.values())
        for _ in range(9):     # unrolls of 4: two capacity flushes a lane
            rig.step()
        rig.finish()           # a terminal marker ships outside a request
        rig.deliver_next_model()
        gc.collect()
        for key in TIMINGS:
            assert t[key] > 0, key
        assert counts == {"steps": 9 * rig.lanes, "installs": 1}
        assert len(rig.sent) == 2 * rig.lanes + 1
        # the ledger's own span of time, and what a step nests
        assert t["step_s"] + t["env_s"] == pytest.approx(t["wall_s"],
                                                         rel=1e-9)
        assert (t["infer_s"] + t["record_s"] + t["encode_s"] + t["send_s"]
                <= t["step_s"])
        assert t["model_decode_s"] + t["swap_s"] <= t["model_install_s"]

    def test_wall_is_step_plus_env_to_the_stamp_and_record_is_self_time(
            self, tmp_cwd, kind, monkeypatch):
        """On a clock the test owns: an encode and a send made to cost a
        thousand seconds each land in ``encode_s`` / ``send_s`` and in the
        step that nests them, never in ``record_s``; a pause between two
        steps is the environment's."""
        from relayrl_tpu.types.trajectory import Trajectory

        clock = FakeClock()
        monkeypatch.setattr(spans_mod, "_monotonic_ns", clock)
        rig = ActorRig(tmp_cwd, kind)
        real_to_bytes = Trajectory.to_bytes

        def slow_to_bytes(traj):
            clock.jump(1000.0)
            return real_to_bytes(traj)

        def slow_send(payload, agent_id=None):
            clock.jump(1000.0)
            rig.sent.append((agent_id, payload))

        monkeypatch.setattr(Trajectory, "to_bytes", slow_to_bytes)
        rig.transport.send_trajectory = slow_send
        for i in range(9):
            rig.step()
            clock.jump(10.0)   # the caller's environment
        t = rig.host.timings
        flushes = 2 * rig.lanes
        assert len(rig.sent) == flushes
        assert 1000.0 * flushes <= t["encode_s"] < 1000.0 * flushes + 1
        assert 1000.0 * flushes <= t["send_s"] < 1000.0 * flushes + 1
        assert 0 < t["record_s"] < 1          # stamps only: microseconds
        assert 0 < t["infer_s"] < 1
        assert 80.0 <= t["env_s"] < 81        # 8 pauses between 9 steps
        assert t["step_s"] >= t["encode_s"] + t["send_s"]
        assert t["step_s"] + t["env_s"] == pytest.approx(t["wall_s"],
                                                         rel=1e-12)
        assert (t["infer_s"] + t["record_s"] + t["encode_s"] + t["send_s"]
                <= t["step_s"])

    def test_report_carries_deltas_once(self, tmp_cwd, kind):
        """Every shipment's ``#r`` tag holds the ledger's growth since the
        previous one: the reports of a run sum to the ledger, to the
        microsecond a report rounds to."""
        from relayrl_tpu.telemetry.actor_ledger import decode_report
        from relayrl_tpu.transport.base import split_agent_tags

        rig = ActorRig(tmp_cwd, kind)
        for _ in range(9):
            rig.step()
        rig.finish()
        reports = []
        for wire_id, _payload in rig.sent:
            clean, seq, trace, text = split_agent_tags(wire_id)
            assert clean.startswith("rig") and "#" not in clean
            assert seq is None and trace is None
            reports.append(decode_report(text))
        assert all(r.born_ns > 0 and r.born_version == rig.host.version
                   for r in reports)
        assert sum(r.counts["steps"] for r in reports) == 9 * rig.lanes
        final = decode_report(rig.host.ledger.report(0, 0))
        for key in ("infer_s", "encode_s", "env_s"):
            total = sum(r.timings[key] for r in reports) + final.timings[key]
            assert total == pytest.approx(rig.host.timings[key], abs=2e-6)

    def test_profiler_on_names_and_arguments(self, tmp_cwd, tmp_path, kind):
        rig = ActorRig(tmp_cwd, kind)
        rig.step()             # compile outside the trace
        jax.profiler.start_trace(str(tmp_path / "trace"))
        try:
            for _ in range(8):
                rig.step()
            rig.deliver_next_model()
            gc.collect()
        finally:
            jax.profiler.stop_trace()
        events = xplane_events(tmp_path / "trace")
        for name in ACTOR_NAMES:
            assert name in events, (name, sorted(events))
        assert len(events["rl:actor.step"]) == 8
        assert len(events["rl:actor.infer"]) == 8
        assert len(events["rl:actor.encode"]) == 2 * rig.lanes
        assert len(events["rl:actor.send"]) == 2 * rig.lanes
        (_l, _s, _d, swap), = events["rl:actor.swap"]
        assert {k: v for k, v in swap.items() if k not in (
            "cpu_ns", "cpu_wall_ns")} == {"version": rig.host.version}
        (_l, _s, _d, decode), = events["rl:actor.model_decode"]
        assert decode["bytes"] > 0
        collections = [st for *_x, st in events["rl:gc"]]
        assert collections and all(
            st["generation"] == 2 and st["collected"] >= 0
            for st in collections)
        # nesting: a step holds its infer, record, and the flush's two
        step_line = {e[0] for e in events["rl:actor.step"]}
        for name in ("rl:actor.infer", "rl:actor.record", "rl:actor.encode",
                     "rl:actor.send"):
            assert {e[0] for e in events[name]} == step_line, name


class TestGcAndThinClients:
    def test_gc_hook_names_full_collections_only(self):
        class Owner:
            def __init__(self, timings):
                self.timings = timings

        timings = {"gc_s": 0.0}
        owner = Owner(timings)
        spans_mod.watch_gc(owner)
        spans_mod.watch_gc()   # idempotent: one hook a process
        assert gc.callbacks.count(spans_mod._on_gc) == 1
        gc.collect(0)
        gc.collect(1)
        assert timings["gc_s"] == 0.0
        gc.collect()
        first = timings["gc_s"]
        assert first > 0
        del owner              # a ledger is fed for as long as its owner lives
        gc.collect()
        assert timings["gc_s"] == first

    def test_a_process_without_jax_builds_no_annotation_and_imports_none(
            self):
        code = (
            "import sys\n"
            "from relayrl_tpu.telemetry import spans\n"
            "from relayrl_tpu.telemetry.actor_ledger import ActorLedger\n"
            "ledger = ActorLedger()\n"
            "with ledger.step(2):\n"
            "    with spans.span('rl:actor.infer', ledger.timings,\n"
            "                    'infer_s', version=3) as sp:\n"
            "        sp.note(bytes=1)\n"
            "    with ledger.record():\n"
            "        pass\n"
            "assert sp._ann is None and spans._annotation is None\n"
            "assert ledger.counts['steps'] == 2\n"
            "assert ledger.timings['infer_s'] > 0\n"
            "assert 'jax' not in sys.modules, 'jax was imported'\n"
            "print('JAXLESS_OK')\n")
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "JAXLESS_OK" in out.stdout


class TestLearnerSpans:
    def test_profiler_off_timings_read_as_before(self, impala_server):
        server, stub = impala_server
        _run_updates(server, 2)
        # (the run-queue halves are there only where the kernel keeps them)
        t = {k: v for k, v in server.timings.items()
             if not k.startswith("runq_")}
        st = server.stats
        assert st["updates"] == 2 and st["trajectories"] == 6
        # one sink for the finer spans, the profiler's: the always-on
        # ledgers hold the keys they held and no other
        assert sorted(t) == sorted(TIMINGS) and sorted(st) == sorted(STATS)
        assert not hasattr(server.algorithm, "timings")
        for key in ("dispatch_s", "learner_idle_s", "warmup_s", "decode_s",
                    "publish_s", "admit_s"):
            assert t[key] > 0, key
        # no actor reported: raw payloads and queue items carry no tag
        assert not any(t[k] for k in t if k.startswith("actor_"))
        assert not any(st[k] for k in st if k.startswith("actor_"))
        assert t["device_wait_s"] == server.algorithm.inflight.device_wait_s
        assert stub.published

    @pytest.mark.parametrize("schedstat", [False, True])
    def test_thread_ledger_is_refreshed_once_a_dispatch(
            self, impala_server, tmp_path, monkeypatch, schedstat):
        """After one dispatch every ``cpu_<role>_s`` key holds a thread's
        clock; the totals only grow; the four named threads sum to no more
        than the process; the run-queue keys exist where ``schedstat`` does
        (here a planted one that echoes field 1 as a tenth in field 2)."""
        from relayrl_tpu.telemetry import thread_clock

        server, _stub = impala_server
        monkeypatch.setattr(thread_clock, "TASK_DIR", str(tmp_path))
        if schedstat:
            clock = thread_clock.read_ns

            def read_ns(thread, schedstat):
                got = clock(thread, schedstat)
                return got and (got[0], got[0] // 10)

            monkeypatch.setattr(thread_clock, "read_ns", read_ns)
        server._thread_ledger = thread_clock.ThreadLedger(
            THREAD_ROLES, runq_roles=("learner", "staging"))
        keys = [f"cpu_{role}_s" for role in (*THREAD_ROLES, "process")]
        runq = ["runq_learner_s", "runq_staging_s"] if schedstat else []
        assert all(server.timings[k] == 0.0 for k in keys)
        _run_updates(server, 1)
        first = dict(server.timings)
        assert all(first[k] > 0 for k in keys + runq), first
        assert [k for k in first if k.startswith("runq_")] == runq
        _run_updates(server, 1)
        second = dict(server.timings)
        assert all(second[k] >= first[k] for k in keys + runq)
        assert second["cpu_learner_s"] > first["cpu_learner_s"]
        assert second["cpu_process_s"] > first["cpu_process_s"]
        for t in (first, second):
            assert sum(t[f"cpu_{role}_s"] for role in THREAD_ROLES) <= (
                t["cpu_process_s"] + 0.05)
            if schedstat:
                assert t["runq_learner_s"] == pytest.approx(
                    t["cpu_learner_s"] / 10, rel=1e-6)
        # the threads end with the server; the next refresh raises nothing
        # and no total falls
        server.disable_server()
        server._thread_ledger.refresh(server.timings)
        assert all(server.timings[k] >= second[k] for k in keys)

    def test_profiler_on_names_and_arguments(self, impala_server, tmp_path):
        server, _stub = impala_server
        jax.profiler.start_trace(str(tmp_path / "trace"))
        try:
            _run_updates(server, 2)
        finally:
            jax.profiler.stop_trace()
        events = xplane_events(tmp_path / "trace")
        for name in LEARNER_NAMES:
            assert name in events, (name, sorted(events))
        dispatches = events["host:dispatch"]
        assert len(dispatches) == 2
        assert sorted(d[3]["version"] for d in dispatches) == [1, 2]
        for _line, _start, _dur, stats in dispatches:
            assert stats["mono_ns"] > 0 and stats["cycle_cpu_ns"] >= 0
        assert dispatches[1][3]["cycle_cpu_ns"] > 0
        stacks = events["rl:batch.stack"]
        assert len(stacks) == 2
        for _line, _start, _dur, stats in stacks:
            assert stats["padded"] == 3 * 64 and 0 < stats["valid"] < 192
            assert stats["bytes"] > 3 * 64 * OBS_DIM * 4
            # one bucket, one obs dtype: no row was copied a second time
            assert stats["moved"] == 0
            assert sorted(stats) == ["bytes", "cpu_ns", "cpu_wall_ns",
                                     "moved", "padded", "valid"]
        assert len(events["rl:batch.pad"]) == 6
        items = events["rl:learner.item"]
        assert all(s["n"] == 1 and s["queued_us"] >= 0
                   for *_x, s in items)
        encodes = events["rl:publish.encode"]
        assert all(s["bytes"] > 0 and s["kind"] for *_x, s in encodes)
        # learner, staging and publisher threads are lines of their own
        line_of = {name: {e[0] for e in events[name]} for name in events}
        assert len(line_of["host:dispatch"]) == 1
        assert line_of["host:dispatch"] == line_of["rl:learner.item"]
        assert line_of["rl:publish"].isdisjoint(line_of["host:dispatch"])
        assert line_of["rl:ingest.decode"].isdisjoint(
            line_of["host:dispatch"] | line_of["rl:publish"])
        # one profiler clock: mono_ns shifts CLOCK_MONOTONIC onto it
        shifts = [start - s["mono_ns"] for _l, start, _d, s in dispatches]
        assert abs(shifts[0] - shifts[1]) < 5e6


class TestReceiveThreadSpans:
    def test_live_zmq_names_the_receive_thread(self, tmp_path):
        """Three trajectories pushed at a live zmq server: the PULL thread
        shows ``rl:ingest.recv`` (with the frame's size) and
        ``rl:ingest.admit`` on one line of its own, the staging thread's
        ``rl:ingest.decode`` holds the native call as a child, and
        ``timings["admit_s"]`` grew."""
        import zmq
        from _util import zmq_addr_pair

        from relayrl_tpu.runtime.server import TrainingServer
        from relayrl_tpu.transport.base import pack_trajectory_envelope

        addrs, _agent = zmq_addr_pair()
        server = TrainingServer(
            "REINFORCE", obs_dim=OBS_DIM, act_dim=ACT_DIM,
            hyperparams={"traj_per_epoch": 3, "seed_salt": 0},
            config_path=str(tmp_path / "relayrl_config.json"),
            env_dir=str(tmp_path), server_type="zmq", **addrs)
        push = zmq.Context.instance().socket(zmq.PUSH)
        frames = [pack_trajectory_envelope(
            f"agent-{i}", serialize_actions(_episode(5 + i, seed=i)))
            for i in range(3)]
        try:
            assert server.wait_warmup(120)
            assert server.timings["admit_s"] == 0.0
            push.connect(addrs["trajectory_addr"])
            jax.profiler.start_trace(str(tmp_path / "trace"))
            try:
                for frame in frames:
                    push.send(frame)
                deadline = time.time() + 60
                while (time.time() < deadline
                       and server.stats["trajectories"] < 3):
                    time.sleep(0.02)
                assert server.drain(timeout=60)
            finally:
                jax.profiler.stop_trace()
            timings, decoder = dict(server.timings), server.ingest_decoder
        finally:
            push.close(linger=0)
            server.disable_server()
        assert server.stats["updates"] == 1
        assert 0 < timings["admit_s"] < 60
        events = xplane_events(tmp_path / "trace")
        recvs, admits = events["rl:ingest.recv"], events["rl:ingest.admit"]
        assert sorted(st["bytes"] for *_x, st in recvs) == sorted(
            len(f) for f in frames)
        assert len(admits) == 3
        line_of = {name: {e[0] for e in events[name]} for name in events}
        assert len(line_of["rl:ingest.recv"]) == 1
        assert line_of["rl:ingest.recv"] == line_of["rl:ingest.admit"]
        assert line_of["rl:ingest.recv"].isdisjoint(
            line_of["rl:ingest.decode"] | line_of["host:dispatch"])
        # a frame's admission follows its receive on that thread
        for (_l, r0, rd, _s), (_l2, a0, _ad, _s2) in zip(sorted(recvs),
                                                        sorted(admits)):
            assert r0 + rd <= a0
        decodes = sorted(events["rl:ingest.decode"])
        if decoder != "native":
            assert "rl:ingest.decode_native" not in events
            return
        natives = sorted(events["rl:ingest.decode_native"])
        assert line_of["rl:ingest.decode_native"] == line_of[
            "rl:ingest.decode"]
        assert len(natives) == len(decodes) == 3
        for (_l, d0, dd, dst), (_l2, n0, nd, nst) in zip(decodes, natives):
            assert d0 <= n0 and n0 + nd <= d0 + dd
        # the first of each name is stamped, whatever came 5 ms after it too
        assert 0 <= natives[0][3]["cpu_ns"] <= decodes[0][3]["cpu_ns"] + (
            CLOCK_SLACK_NS)
        assert "cpu_ns" in sorted(recvs)[0][3]


class TestDeviceNames:
    def test_flash_forward_and_backward_are_two_named_calls(self):
        from relayrl_tpu.ops import flash

        q = jnp.ones((1, 128, 2, 64), jnp.float32)

        def loss(q, k, v):
            return flash.flash_attention(q, k, v, interpret=True).sum()

        text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q))
        # the forward, and one backward kernel for dq, dk and dv
        assert text.count("pallas_call") == 2
        assert sorted(set(re.findall(r"relayrl_flash_\w+", text))) == sorted(
            [flash.FWD_NAME, flash.BWD_NAME])

    @pytest.mark.parametrize("algo", ["IMPALA", "PPO", "REINFORCE", "DQN"])
    def test_jitted_update_is_named_after_its_algorithm(self, tmp_cwd, algo):
        from relayrl_tpu.algorithms import build_algorithm

        built = build_algorithm(
            algo, obs_dim=OBS_DIM, act_dim=ACT_DIM, hidden_sizes=[16],
            seed_salt=0, logger_kwargs={"output_dir": str(tmp_cwd / "logs")})
        assert built._update.__name__ == f"{algo.lower()}_update"

    def test_update_module_is_named_in_the_lowering(self, tmp_cwd):
        from relayrl_tpu.algorithms import build_algorithm

        algo = build_algorithm(
            "IMPALA", obs_dim=OBS_DIM, act_dim=ACT_DIM, hidden_sizes=[16],
            traj_per_epoch=2, seed_salt=0,
            logger_kwargs={"output_dir": str(tmp_cwd / "logs")})
        batch = algo.mh_zero_batch(2, 64)
        text = algo._update.lower(algo.state, batch).as_text()
        assert "jit_impala_update" in text
