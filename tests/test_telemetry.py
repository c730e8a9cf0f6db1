"""Telemetry subsystem (relayrl_tpu/telemetry/): metrics core semantics,
Prometheus text-format conformance, snapshot consistency under concurrent
increment, the null-registry no-op path, the HTTP exporter, the NDJSON
event journal, the epoch-logger mirror, and the acceptance guard that
enabling telemetry leaves learner numerics bit-identical."""

import json
import re
import threading
import urllib.request

import numpy as np
import pytest

from relayrl_tpu import telemetry
from relayrl_tpu.telemetry import (
    EventJournal,
    NullRegistry,
    Registry,
    TelemetryExporter,
    read_events,
    render_prometheus,
)

pytestmark = pytest.mark.telemetry


@pytest.fixture(autouse=True)
def _isolated_telemetry():
    """Each test starts from pristine disabled state and restores it —
    the module-global registry must not leak between tests (or into the
    rest of the suite)."""
    telemetry.reset_for_tests()
    yield
    telemetry.reset_for_tests()


class TestCore:
    def test_counter_aggregates_across_threads(self):
        reg = Registry(run_id="t")
        c = reg.counter("relayrl_t_total", "help")

        def work():
            for _ in range(10_000):
                c.inc()

        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.total() == 80_000

    def test_counter_get_or_create_is_idempotent_per_label_set(self):
        reg = Registry()
        a = reg.counter("relayrl_t_total", labels={"backend": "zmq"})
        b = reg.counter("relayrl_t_total", labels={"backend": "zmq"})
        other = reg.counter("relayrl_t_total", labels={"backend": "grpc"})
        assert a is b and a is not other

    def test_kind_collision_raises(self):
        reg = Registry()
        reg.counter("relayrl_t_thing")
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("relayrl_t_thing")

    def test_histogram_buckets_sum_count(self):
        reg = Registry()
        h = reg.histogram("relayrl_t_seconds", buckets=(0.01, 0.1, 1.0))
        for v in (0.005, 0.05, 0.5, 5.0):
            h.observe(v)
        counts, total, n = h.totals()
        assert counts == [1, 1, 1, 1]  # one per bucket incl. +Inf
        assert n == 4 and abs(total - 5.555) < 1e-9

    def test_histogram_timer_context(self):
        reg = Registry()
        h = reg.histogram("relayrl_t_seconds", buckets=(10.0,))
        with h.time():
            pass
        _, _, n = h.totals()
        assert n == 1

    def test_gauge_fn_pulls_at_snapshot_and_survives_errors(self):
        reg = Registry()
        reg.gauge_fn("relayrl_t_depth", lambda: 7)
        reg.gauge_fn("relayrl_t_broken", lambda: 1 / 0)
        entries = {m["name"]: m for m in reg.snapshot()["metrics"]}
        assert entries["relayrl_t_depth"]["value"] == 7
        assert "relayrl_t_broken" not in entries  # omitted, not fatal

    def test_non_finite_values_null_in_snapshot_nan_in_prometheus(self):
        """A diverged stat (NaN loss) must not poison the JSON document:
        the snapshot carries null (strict JSON), the Prometheus text
        renders the legal NaN literal."""
        reg = Registry()
        reg.gauge("relayrl_t_nan").set(float("nan"))
        reg.gauge("relayrl_t_inf").set(float("inf"))
        h = reg.histogram("relayrl_t_seconds", buckets=(1.0,))
        h.observe(float("inf"))
        snap = reg.snapshot()
        text = json.dumps(snap, allow_nan=False)  # raises on bare NaN/Inf
        parsed = {m["name"]: m for m in json.loads(text)["metrics"]}
        assert parsed["relayrl_t_nan"]["value"] is None
        assert parsed["relayrl_t_inf"]["value"] is None
        assert parsed["relayrl_t_seconds"]["sum"] is None
        assert parsed["relayrl_t_seconds"]["count"] == 1
        prom = render_prometheus(snap)
        assert "relayrl_t_nan NaN" in prom
        assert "relayrl_t_seconds_sum NaN" in prom

    def test_gauge_fn_kind_collision_raises_gauge_rebind_allowed(self):
        reg = Registry()
        reg.counter("relayrl_t_total")
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge_fn("relayrl_t_total", lambda: 1)
        reg.gauge_fn("relayrl_t_depth", lambda: 1)
        reg.gauge_fn("relayrl_t_depth", lambda: 2)  # rebind: fine
        entry = [m for m in reg.snapshot()["metrics"]
                 if m["name"] == "relayrl_t_depth"][0]
        assert entry["value"] == 2

    def test_gauge_stores_device_handle_resolves_at_snapshot(self):
        import jax.numpy as jnp

        reg = Registry()
        g = reg.gauge("relayrl_t_lazy")
        g.set(jnp.float32(2.5))  # stored as the handle, no float() here
        entry = [m for m in reg.snapshot()["metrics"]
                 if m["name"] == "relayrl_t_lazy"][0]
        assert entry["value"] == 2.5

    def test_snapshot_under_concurrent_increment_is_consistent(self):
        """Snapshots taken while 4 threads hammer a counter must be
        monotonic non-decreasing and the final total exact — per-thread
        shards may lag each other but may never lose or double-count."""
        reg = Registry()
        c = reg.counter("relayrl_t_total")
        per_thread, n_threads = 50_000, 4
        stop = threading.Event()
        seen: list[float] = []

        def snapshotter():
            while not stop.is_set():
                entry = [m for m in reg.snapshot()["metrics"]
                         if m["name"] == "relayrl_t_total"][0]
                seen.append(entry["value"])

        def work():
            for _ in range(per_thread):
                c.inc()

        snap_t = threading.Thread(target=snapshotter)
        workers = [threading.Thread(target=work) for _ in range(n_threads)]
        snap_t.start()
        for w in workers:
            w.start()
        for w in workers:
            w.join()
        stop.set()
        snap_t.join()
        assert seen, "snapshotter never ran"
        assert all(b >= a for a, b in zip(seen, seen[1:]))
        assert c.total() == per_thread * n_threads

    def test_null_registry_is_total_noop(self):
        reg = NullRegistry()
        c = reg.counter("x")
        h = reg.histogram("y")
        g = reg.gauge("z")
        assert c is h is g  # one shared null object
        c.inc()
        h.observe(1.0)
        g.set(3)
        with h.time():
            pass
        assert c.total() == 0.0
        snap = reg.snapshot()
        assert snap["enabled"] is False and snap["metrics"] == []

    def test_global_default_is_null_and_set_registry_sticks(self):
        assert telemetry.get_registry().enabled is False
        reg = Registry(run_id="explicit")
        telemetry.set_registry(reg)
        assert telemetry.get_registry() is reg


class TestPrometheusConformance:
    """Text exposition format 0.0.4 against a snapshot with all three
    metric kinds and labeled children."""

    def _text(self):
        reg = Registry(run_id="conf")
        c = reg.counter("relayrl_c_total", "a counter",
                        labels={"backend": "zmq"})
        c.inc(3)
        reg.counter("relayrl_c_total", "a counter",
                    labels={"backend": "grpc"}).inc(1)
        reg.gauge("relayrl_g", "a gauge").set(2.5)
        h = reg.histogram("relayrl_h_seconds", "a histogram",
                          buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 2.0):
            h.observe(v)
        return render_prometheus(reg.snapshot())

    def test_help_and_type_once_per_family(self):
        text = self._text()
        assert text.count("# HELP relayrl_c_total a counter") == 1
        assert text.count("# TYPE relayrl_c_total counter") == 1
        assert "# TYPE relayrl_g gauge" in text
        assert "# TYPE relayrl_h_seconds histogram" in text

    def test_histogram_children_cumulative_with_inf_sum_count(self):
        text = self._text()
        assert 'relayrl_h_seconds_bucket{le="0.1"} 1' in text
        assert 'relayrl_h_seconds_bucket{le="1"} 2' in text
        assert 'relayrl_h_seconds_bucket{le="+Inf"} 3' in text
        assert "relayrl_h_seconds_count 3" in text
        assert re.search(r"relayrl_h_seconds_sum 2\.55", text)

    def test_labeled_children_and_escaping(self):
        text = self._text()
        assert 'relayrl_c_total{backend="zmq"} 3' in text
        assert 'relayrl_c_total{backend="grpc"} 1' in text
        reg = Registry()
        reg.counter("relayrl_esc_total",
                    labels={"k": 'a"b\\c\nd'}).inc()
        esc = render_prometheus(reg.snapshot())
        assert '{k="a\\"b\\\\c\\nd"}' in esc

    def test_every_sample_line_parses(self):
        """Each non-comment line is `<name>[{labels}] <value>`."""
        sample = re.compile(
            r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [0-9eE+.\-]+$")
        for line in self._text().strip().splitlines():
            if line.startswith("#"):
                continue
            assert sample.match(line), line

    def test_trailing_newline(self):
        assert self._text().endswith("\n")


class TestExporter:
    def test_endpoints(self):
        reg = Registry(run_id="http")
        reg.counter("relayrl_t_total").inc(5)
        exporter = TelemetryExporter(reg, port=0)
        try:
            with urllib.request.urlopen(exporter.url + "/metrics") as resp:
                assert resp.status == 200
                assert "version=0.0.4" in resp.headers["Content-Type"]
                assert b"relayrl_t_total 5" in resp.read()
            with urllib.request.urlopen(exporter.url + "/snapshot") as resp:
                snap = json.loads(resp.read())
            assert snap["run_id"] == "http"
            assert snap["schema"] == "relayrl-telemetry-v1"
            assert snap["metrics"][0]["value"] == 5
            with urllib.request.urlopen(exporter.url + "/healthz") as resp:
                assert resp.read() == b"ok\n"
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(exporter.url + "/nope")
        finally:
            exporter.close()


class TestEvents:
    def test_journal_ndjson_schema_and_torn_tail(self, tmp_path):
        path = tmp_path / "events.ndjson"
        journal = EventJournal(str(path), run_id="r1")
        journal.emit("model_publish", version=3, bytes=100)
        journal.emit("drop", n=np.int64(2), total=np.float32(2.0))
        journal.close()
        with open(path, "a") as f:
            f.write('{"torn": ')  # crash mid-write
        events = read_events(str(path))
        assert len(events) == 2
        first = events[0]
        assert first["event"] == "model_publish" and first["run_id"] == "r1"
        assert first["version"] == 3
        assert {"t_unix", "mono_ns"} <= set(first)
        # numpy scalars landed as plain JSON numbers
        assert events[1]["n"] == 2 and events[1]["total"] == 2.0

    def test_module_emit_routes_to_configured_journal(self, tmp_path):
        path = tmp_path / "ev.ndjson"
        telemetry.set_journal(EventJournal(str(path), run_id="m"))
        telemetry.emit("checkpoint", version=1)
        telemetry.get_journal().close()
        assert read_events(str(path))[0]["event"] == "checkpoint"

    def test_emit_without_journal_is_noop(self):
        telemetry.emit("drain")  # must not raise


class TestConfigWiring:
    def _loader(self, tmp_path, telem: dict):
        from relayrl_tpu.config import ConfigLoader

        cfg = tmp_path / "relayrl_config.json"
        cfg.write_text(json.dumps({"telemetry": telem}))
        return ConfigLoader(None, str(cfg))

    def test_disabled_config_keeps_null_registry(self, tmp_path):
        reg = telemetry.configure_from_config(
            self._loader(tmp_path, {"enabled": False}))
        assert reg.enabled is False

    def test_enabled_config_installs_registry_and_journal(self, tmp_path):
        loader = self._loader(tmp_path, {
            "enabled": True, "port": 0, "run_id": "cfg",
            "events_path": str(tmp_path / "ev.ndjson")})
        reg = telemetry.configure_from_config(loader)
        assert reg.enabled and reg.run_id == "cfg"
        telemetry.emit("drain")
        assert telemetry.maybe_serve() is not None
        telemetry.shutdown()
        assert read_events(str(tmp_path / "ev.ndjson"))[0]["event"] == "drain"

    def test_maybe_serve_bind_failure_degrades_not_crashes(self, tmp_path):
        """A held telemetry.port must not take down the process being
        observed: maybe_serve returns None, metrics stay in-process."""
        import socket

        blocker = socket.socket()
        blocker.bind(("127.0.0.1", 0))
        blocker.listen(1)
        held_port = blocker.getsockname()[1]
        try:
            loader = self._loader(tmp_path, {"enabled": True,
                                             "port": held_port})
            reg = telemetry.configure_from_config(loader)
            assert reg.enabled
            assert telemetry.maybe_serve() is None
            reg.counter("relayrl_t_total").inc()  # registry still live
        finally:
            blocker.close()

    def test_first_configure_wins(self, tmp_path):
        first = telemetry.configure_from_config(
            self._loader(tmp_path, {"enabled": True, "run_id": "one"}))
        second = telemetry.configure_from_config(
            self._loader(tmp_path, {"enabled": True, "run_id": "two"}))
        assert second is first and first.run_id == "one"

    def test_malformed_section_degrades(self, tmp_path):
        loader = self._loader(tmp_path, {"enabled": "yes", "port": "junk"})
        params = loader.get_telemetry_params()
        assert params["enabled"] is True and params["port"] == 9100

    def test_transport_heartbeat_knob(self, tmp_path):
        from relayrl_tpu.config import ConfigLoader

        cfg = tmp_path / "relayrl_config.json"
        cfg.write_text(json.dumps({"transport": {"heartbeat_s": 1.5}}))
        assert ConfigLoader(
            None, str(cfg)).get_transport_params()["heartbeat_s"] == 1.5
        cfg.write_text(json.dumps({"transport": {"heartbeat_s": "x"}}))
        assert ConfigLoader(
            None, str(cfg)).get_transport_params()["heartbeat_s"] == 5.0

    def test_native_agent_heartbeat_wired_from_config(self, tmp_path):
        """transport.heartbeat_s reaches the native agent transport (the
        old hard-coded 5.0 in start_model_listener), and its liveness
        gauge is registered. Construction is connection-lazy, so no
        server is needed."""
        from relayrl_tpu.config import ConfigLoader
        from relayrl_tpu.transport import make_agent_transport
        from relayrl_tpu.transport.native_backend import native_available

        if not native_available():
            pytest.skip("native library not built")
        telemetry.set_registry(Registry())
        cfg = tmp_path / "relayrl_config.json"
        cfg.write_text(json.dumps({"transport": {"heartbeat_s": 1.25}}))
        transport = make_agent_transport(
            "native", ConfigLoader(None, str(cfg)), probe=False,
            server_addr="127.0.0.1:1")
        try:
            assert transport._heartbeat_default == 1.25
            names = {m["name"] for m in
                     telemetry.get_registry().snapshot()["metrics"]}
            assert "relayrl_transport_heartbeat_state" in names
        finally:
            transport.close()


class TestEpochLoggerMirror:
    def test_dump_tabular_mirrors_row_into_registry(self, tmp_path):
        from relayrl_tpu.utils.logger import EpochLogger

        reg = Registry()
        telemetry.set_registry(reg)
        logger = EpochLogger(output_dir=str(tmp_path))
        logger.store(EpRet=[1.0, 3.0])
        logger.log_tabular("Epoch", 1)
        logger.log_tabular("EpRet", average_only=True)
        logger.dump_tabular()
        by_stat = {m["labels"]["stat"]: m["value"]
                   for m in reg.snapshot()["metrics"]
                   if m["name"] == "relayrl_epoch_stat"}
        assert by_stat["Epoch"] == 1 and by_stat["EpRet"] == 2.0

    def test_dump_tabular_with_null_registry_unchanged(self, tmp_path):
        from relayrl_tpu.utils.logger import EpochLogger

        logger = EpochLogger(output_dir=str(tmp_path))
        logger.log_tabular("Epoch", 1)
        logger.dump_tabular()  # must not raise, must still write the TSV
        with open(tmp_path / "progress.txt") as f:
            assert f.read().splitlines() == ["Epoch", "1"]


class TestTopCli:
    def _snap(self, reg):
        return reg.snapshot()

    def test_render_sections_and_rates(self):
        from relayrl_tpu.telemetry import top

        reg = Registry(run_id="top")
        c = reg.counter("relayrl_server_trajectories_total")
        h = reg.histogram("relayrl_learner_publish_seconds",
                          buckets=(0.1, 1.0))
        c.inc(10)
        h.observe(0.05)
        first = self._snap(reg)
        c.inc(10)
        second = self._snap(reg)
        second["mono_ns"] = first["mono_ns"] + int(2e9)  # 2s apart
        frame = top.render(second, first)
        assert "run top" in frame
        assert "-- server" in frame and "-- learner" in frame
        assert "trajectories_total: 20 (5/s)" in frame
        assert "p50=" in frame

    def test_render_disabled(self):
        from relayrl_tpu.telemetry import top

        assert "disabled" in top.render(NullRegistry().snapshot())

    def test_histogram_quantile_estimate(self):
        from relayrl_tpu.telemetry.top import histogram_quantile

        entry = {"buckets": [1.0, 2.0, 4.0], "counts": [0, 10, 0, 0],
                 "count": 10}
        # all mass in (1, 2]: p50 interpolates to 1.5
        assert histogram_quantile(entry, 0.5) == pytest.approx(1.5)
        assert histogram_quantile({"buckets": [1.0], "counts": [0, 0],
                                   "count": 0}, 0.5) is None

    def test_main_once_against_live_exporter(self, capsys):
        from relayrl_tpu.telemetry import top

        reg = Registry(run_id="cli")
        reg.counter("relayrl_server_updates_total").inc(2)
        exporter = TelemetryExporter(reg, port=0)
        try:
            assert top.main(["--url", exporter.url, "--once"]) == 0
        finally:
            exporter.close()
        out = capsys.readouterr().out
        assert "updates_total: 2" in out

    def test_main_unreachable_errors(self):
        from relayrl_tpu.telemetry import top

        assert top.main(["--url", "http://127.0.0.1:9", "--once"]) == 1


class TestLearnerParity:
    def test_enabled_telemetry_is_bit_identical_to_disabled(self, tmp_path,
                                                            monkeypatch):
        """The acceptance bar: telemetry must be observation only — the
        learner's final params with a live registry + journal are
        BIT-identical to the disabled run on the same stream."""
        import jax

        from relayrl_tpu.algorithms import build_algorithm

        def episode(n, seed):
            rng = np.random.default_rng(seed)
            from relayrl_tpu.types.action import ActionRecord

            return [ActionRecord(
                obs=rng.standard_normal(4).astype(np.float32),
                act=np.int64(rng.integers(2)),
                rew=float(rng.random()),
                data={"logp_a": np.float32(-0.69),
                      "v": np.float32(rng.standard_normal())},
                done=(i == n - 1)) for i in range(n)]

        def run(enabled: bool):
            telemetry.reset_for_tests()
            if enabled:
                telemetry.set_registry(Registry(run_id="parity"))
                telemetry.set_journal(EventJournal(
                    str(tmp_path / "parity.ndjson"), run_id="parity"))
            algo = build_algorithm(
                "REINFORCE", obs_dim=4, act_dim=2, traj_per_epoch=2,
                hidden_sizes=[16], with_vf_baseline=True, train_vf_iters=2,
                seed_salt=0,
                logger_kwargs={"output_dir":
                               str(tmp_path / f"logs_{enabled}")})
            for i in range(6):
                algo.receive_trajectory(episode(8, seed=i))
            params = jax.device_get(algo.state.params)
            version = algo.version
            telemetry.reset_for_tests()
            return params, version

        params_off, v_off = run(enabled=False)
        params_on, v_on = run(enabled=True)
        assert v_on == v_off > 0
        for off, on in zip(jax.tree_util.tree_leaves(params_off),
                           jax.tree_util.tree_leaves(params_on)):
            np.testing.assert_array_equal(np.asarray(off), np.asarray(on))

    def test_instrumented_hot_paths_accept_null_registry(self):
        """Every instrumented primitive constructed under the default
        (disabled) registry runs its hot path with null metrics."""
        import jax.numpy as jnp

        from relayrl_tpu.algorithms.dispatch import InflightWindow

        win = InflightWindow(max_in_flight=1)
        win.push(jnp.float32(1.0))
        win.drain()
        assert win.fenced_count == 1
        assert telemetry.get_registry().enabled is False
