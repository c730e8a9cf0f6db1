"""The span primitive (telemetry/spans.py) and what it is wired to: always-on
totals, the profiler's time line, the sampled causal trace, and the stable
device names (the three flash kernels, the jitted update)."""

import glob
import os
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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
    "rl:ingest.decode", "rl:publish", "rl:publish.gather",
    "rl:publish.encode", "rl:publish.send")
TIMINGS = ("decode_s", "learn_s", "dispatch_s", "device_wait_s",
           "publish_s", "learner_idle_s", "warmup_s")
STATS = ("trajectories", "updates", "dropped", "dropped_nonfinite",
         "learner_errors", "publish_errors", "warmup_failed")


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


def _xplane_events(trace_dir):
    """{name: [(line index, start_ns, duration_ns, stats)]} of the host
    planes of the newest xplane under ``trace_dir``."""
    path = max(glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                      "*", "*.xplane.pb")),
               key=os.path.getmtime)
    events = {}
    n = 0
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            n += 1
            for ev in line.events:
                if ev.name.startswith(("host:", "rl:")):
                    events.setdefault(ev.name, []).append(
                        (n, ev.start_ns, ev.duration_ns, dict(ev.stats)))
    return events


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
        (_line, _start, dur, stats), = _xplane_events(tmp_path)[name]
        assert dur > 0
        assert stats == {**args, "mono_ns": sp.t0_ns}


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
        events = _xplane_events(tmp_path)
        assert len(events["rl:batch.pad"]) == 3
        (_line, _start, _dur, stats), = events["rl:batch.stack"]
        assert stats == {
            "valid": sum(lens), "padded": 3 * batch.horizon,
            "bytes": sum(v.nbytes for v in batch.as_dict().values()),
            "moved": moved}


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

    def test_fence_total_and_ring_span_are_one_interval(self):
        from relayrl_tpu.runtime.pipeline import InflightWindow

        win = InflightWindow(max_in_flight=0)
        win.push(jnp.float32(1.0), version=4)
        rec, = [s for s in trace_mod.snapshot_spans() if s["hop"] == "fence"]
        assert rec["version"] == 4 and win.fenced_count == 1
        assert win.device_wait_s == (rec["t1_ns"] - rec["t0_ns"]) * 1e-9


class TestLearnerSpans:
    def test_profiler_off_timings_read_as_before(self, impala_server):
        server, stub = impala_server
        _run_updates(server, 2)
        t, st = server.timings, server.stats
        assert st["updates"] == 2 and st["trajectories"] == 6
        # one sink for the finer spans, the profiler's: the always-on
        # ledgers hold the keys they held and no other
        assert sorted(t) == sorted(TIMINGS) and sorted(st) == sorted(STATS)
        assert not hasattr(server.algorithm, "timings")
        for key in ("learn_s", "dispatch_s", "learner_idle_s", "warmup_s",
                    "decode_s", "publish_s"):
            assert t[key] > 0, key
        # the nesting the totals stand for
        assert t["dispatch_s"] <= t["learn_s"]
        assert t["device_wait_s"] == server.algorithm.inflight.device_wait_s
        assert stub.published

    def test_profiler_on_names_and_arguments(self, impala_server, tmp_path):
        server, _stub = impala_server
        jax.profiler.start_trace(str(tmp_path / "trace"))
        try:
            _run_updates(server, 2)
        finally:
            jax.profiler.stop_trace()
        events = _xplane_events(tmp_path / "trace")
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
            assert sorted(stats) == ["bytes", "moved", "padded", "valid"]
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


class TestDeviceNames:
    def test_flash_forward_and_backward_are_three_named_calls(self):
        from relayrl_tpu.ops import flash

        q = jnp.ones((1, 128, 2, 64), jnp.float32)

        def loss(q, k, v):
            return flash.flash_attention(q, k, v, interpret=True).sum()

        text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q))
        assert text.count("pallas_call") == 3
        assert sorted(set(re.findall(r"relayrl_flash_\w+", text))) == sorted(
            [flash.FWD_NAME, flash.DQ_NAME, flash.DKV_NAME])

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
