"""Fleet telemetry aggregation (ISSUE 15): snapshot-frame codec, merge
semantics (commutative/associative, epoch-aware counters, bucket-wise
histogram sums), fleet-table staleness, relay fan-in, the SLO alert
engine, the /fleet endpoints, the --fleet pane, and the live-zmq drill
asserting root totals == sum of per-process registries bit-exactly.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import pytest

from tests._util import free_port

pytestmark = pytest.mark.fleet

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def fresh_registry():
    from relayrl_tpu import telemetry

    registry = telemetry.Registry(run_id="test-fleet")
    telemetry.set_registry(registry)
    yield registry
    telemetry.reset_for_tests()


def _registry_with(counters=None, gauges=None, hists=None, run_id="p"):
    from relayrl_tpu.telemetry import Registry

    reg = Registry(run_id=run_id)
    for name, v in (counters or {}).items():
        reg.counter(name).inc(v)
    for name, v in (gauges or {}).items():
        reg.gauge(name).set(v)
    for name, samples in (hists or {}).items():
        h = reg.histogram(name, buckets=(0.01, 0.1, 1.0))
        for s in samples:
            h.observe(s)
    return reg


def _value(doc, name, labels=None):
    from relayrl_tpu.telemetry.aggregate import snapshot_metric

    return snapshot_metric(doc, name, labels)


def _entry(doc, name):
    return next(m for m in doc["metrics"] if m["name"] == name)


# ---------------------------------------------------------------------------
# snapshot frames
# ---------------------------------------------------------------------------

class TestSnapshotFrames:
    def test_round_trip(self):
        from relayrl_tpu.telemetry import aggregate as ag

        reg = _registry_with(counters={"relayrl_x_total": 7})
        sec = ag.snapshot_section(reg.snapshot(), "proc-a", "actor",
                                  123.5, 4)
        frame = ag.encode_snapshot_frame([sec])
        assert ag.is_snapshot_frame(frame)
        back = ag.parse_snapshot_frame(frame)
        assert len(back) == 1
        assert back[0]["proc"] == "proc-a"
        assert back[0]["tier"] == "actor"
        assert back[0]["epoch"] == 123.5 and back[0]["seq"] == 4
        assert _value(back[0]["snapshot"], "relayrl_x_total") == 7

    def test_multi_proc_frame(self):
        from relayrl_tpu.telemetry import aggregate as ag

        secs = [ag.snapshot_section({"metrics": []}, f"p{i}", "actor",
                                    1.0, i) for i in range(3)]
        back = ag.parse_snapshot_frame(ag.encode_snapshot_frame(secs))
        assert [s["proc"] for s in back] == ["p0", "p1", "p2"]

    @pytest.mark.parametrize("bad", [
        b"",
        b"RLS",
        b"NOPE" + b"x" * 10,
        b"RLS1" + b"\xff\xff\xff",                       # undecodable
        b"RLS1" + b"\x81\xa1v\x02",                       # wrong version
    ])
    def test_malformed_frames_raise_value_error(self, bad):
        from relayrl_tpu.telemetry import aggregate as ag

        with pytest.raises(ValueError):
            ag.parse_snapshot_frame(bad)

    def test_section_missing_proc_rejected(self):
        import msgpack

        from relayrl_tpu.telemetry import aggregate as ag

        frame = ag.SNAP_MAGIC + msgpack.packb(
            {"v": 1, "procs": [{"snapshot": {}}]}, use_bin_type=True)
        with pytest.raises(ValueError):
            ag.parse_snapshot_frame(frame)

    def test_unknown_tier_normalizes(self):
        from relayrl_tpu.telemetry import aggregate as ag

        sec = ag.snapshot_section({}, "p", "mystery-tier", 1.0, 1)
        assert sec["tier"] == "other"


# ---------------------------------------------------------------------------
# merge semantics
# ---------------------------------------------------------------------------

class TestMergeSemantics:
    def _three(self):
        a = _registry_with(counters={"relayrl_c_total": 10},
                           gauges={"relayrl_g": 5},
                           hists={"relayrl_h_seconds": [0.005, 0.5]},
                           run_id="a").snapshot()
        b = _registry_with(counters={"relayrl_c_total": 32},
                           gauges={"relayrl_g": 9},
                           hists={"relayrl_h_seconds": [0.05]},
                           run_id="b").snapshot()
        c = _registry_with(counters={"relayrl_c_total": 100},
                           gauges={"relayrl_g": 1},
                           hists={"relayrl_h_seconds": [2.0, 2.0]},
                           run_id="c").snapshot()
        return a, b, c

    def test_counters_sum_gauges_spread_hists_bucketwise(self):
        from relayrl_tpu.telemetry.aggregate import merge_snapshots

        a, b, c = self._three()
        m = merge_snapshots([a, b, c])
        assert _value(m, "relayrl_c_total") == 142
        g = _entry(m, "relayrl_g")
        assert (g["value"], g["min"], g["max"], g["count"]) == (15, 1, 9, 3)
        h = _entry(m, "relayrl_h_seconds")
        assert h["count"] == 5
        assert h["sum"] == pytest.approx(0.005 + 0.5 + 0.05 + 2.0 + 2.0)
        ha, hb, hc = (_entry(s, "relayrl_h_seconds") for s in (a, b, c))
        assert h["counts"] == [x + y + z for x, y, z in
                               zip(ha["counts"], hb["counts"],
                                   hc["counts"])]

    def test_commutative(self):
        from relayrl_tpu.telemetry.aggregate import merge_snapshots

        a, b, c = self._three()
        m1 = merge_snapshots([a, b, c])["metrics"]
        m2 = merge_snapshots([c, a, b])["metrics"]
        # Integer-valued inputs: float addition order cannot matter.
        assert m1 == m2

    def test_associative(self):
        from relayrl_tpu.telemetry.aggregate import merge_snapshots

        a, b, c = self._three()
        flat = merge_snapshots([a, b, c])["metrics"]
        nested = merge_snapshots(
            [merge_snapshots([a, b]), c])["metrics"]
        assert flat == nested

    def test_histogram_grid_mismatch_counted_not_mixed(self):
        from relayrl_tpu.telemetry import Registry
        from relayrl_tpu.telemetry.aggregate import merge_snapshots

        r1, r2 = Registry(run_id="1"), Registry(run_id="2")
        r1.histogram("relayrl_h", buckets=(0.1, 1.0)).observe(0.05)
        r2.histogram("relayrl_h", buckets=(0.2, 2.0)).observe(0.05)
        m = merge_snapshots([r1.snapshot(), r2.snapshot()])
        assert m["grid_mismatches"] == 1
        assert _entry(m, "relayrl_h")["count"] == 1  # first grid kept

    def test_none_values_skipped(self):
        from relayrl_tpu.telemetry.aggregate import merge_snapshots

        snaps = [{"metrics": [
            {"name": "relayrl_c_total", "kind": "counter", "labels": {},
             "value": None},
            {"name": "relayrl_g", "kind": "gauge", "labels": {},
             "value": None}]},
            {"metrics": [
                {"name": "relayrl_c_total", "kind": "counter",
                 "labels": {}, "value": 3.0},
                {"name": "relayrl_g", "kind": "gauge", "labels": {},
                 "value": 2.0}]}]
        m = merge_snapshots(snaps)
        assert _value(m, "relayrl_c_total") == 3.0
        g = _entry(m, "relayrl_g")
        assert g["count"] == 1 and g["value"] == 2.0

    def test_labels_distinguish_children(self):
        from relayrl_tpu.telemetry import Registry
        from relayrl_tpu.telemetry.aggregate import merge_snapshots

        r1, r2 = Registry(run_id="1"), Registry(run_id="2")
        r1.counter("relayrl_c_total", labels={"backend": "zmq"}).inc(1)
        r2.counter("relayrl_c_total", labels={"backend": "grpc"}).inc(2)
        m = merge_snapshots([r1.snapshot(), r2.snapshot()])
        assert _value(m, "relayrl_c_total", {"backend": "zmq"}) == 1
        assert _value(m, "relayrl_c_total", {"backend": "grpc"}) == 2


# ---------------------------------------------------------------------------
# fleet table: epoch-aware counters, staleness, ordering
# ---------------------------------------------------------------------------

class TestFleetTable:
    def _table(self, stale_s=15.0):
        from relayrl_tpu.telemetry import Registry
        from relayrl_tpu.telemetry.aggregate import FleetTable

        return FleetTable(stale_s=stale_s, registry=Registry(run_id="root"))

    def _section(self, proc, epoch, seq, counters, hists=None, tier="actor"):
        from relayrl_tpu.telemetry.aggregate import snapshot_section

        reg = _registry_with(counters=counters, hists=hists, run_id=proc)
        return snapshot_section(reg.snapshot(), proc, tier, epoch, seq)

    def test_counter_monotonic_across_restart(self):
        t = self._table()
        t.ingest_sections([self._section("p", 1.0, 1,
                                         {"relayrl_c_total": 100})])
        assert _value(t.merged(), "relayrl_c_total") == 100
        # Restart: fresh epoch, counter reset to 7 — the fleet total
        # must never go backwards.
        t.ingest_sections([self._section("p", 2.0, 1,
                                         {"relayrl_c_total": 7})])
        assert _value(t.merged(), "relayrl_c_total") == 107
        # Second restart stacks the baseline.
        t.ingest_sections([self._section("p", 3.0, 1,
                                         {"relayrl_c_total": 1})])
        assert _value(t.merged(), "relayrl_c_total") == 108
        assert t.procs()[0]["restarts"] == 2

    def test_histogram_folds_across_restart(self):
        t = self._table()
        t.ingest_sections([self._section(
            "p", 1.0, 1, {}, hists={"relayrl_h_seconds": [0.005, 0.5]})])
        t.ingest_sections([self._section(
            "p", 2.0, 1, {}, hists={"relayrl_h_seconds": [2.0]})])
        h = _entry(t.merged(), "relayrl_h_seconds")
        assert h["count"] == 3
        assert h["sum"] == pytest.approx(2.505)

    def test_out_of_order_sections_dropped(self):
        t = self._table()
        t.ingest_sections([self._section("p", 2.0, 5,
                                         {"relayrl_c_total": 50})])
        # older seq, same epoch
        t.ingest_sections([self._section("p", 2.0, 3,
                                         {"relayrl_c_total": 10})])
        # older epoch entirely
        t.ingest_sections([self._section("p", 1.0, 99,
                                         {"relayrl_c_total": 999})])
        assert _value(t.merged(), "relayrl_c_total") == 50
        assert t._m_stale_sections.total() == 2

    def test_stale_proc_evicted(self):
        t = self._table(stale_s=5.0)
        now = time.monotonic()
        t.ingest_sections([self._section("old", 1.0, 1,
                                         {"relayrl_c_total": 5})], now=now)
        t.ingest_sections([self._section("fresh", 1.0, 1,
                                         {"relayrl_c_total": 3})],
                          now=now + 4)
        evicted = t.sweep(now=now + 6)
        assert evicted == ["old"]
        assert [p["proc"] for p in t.procs()] == ["fresh"]
        assert _value(t.merged(), "relayrl_c_total") == 3
        assert t._m_evicted.total() == 1

    def test_merged_exactly_sums_per_proc(self):
        t = self._table()
        values = [3.0, 11.0, 29.0, 1.5]
        for i, v in enumerate(values):
            t.ingest_sections([self._section(f"p{i}", 1.0, 1,
                                             {"relayrl_c_total": v})])
        expect = 0.0
        for v in values:  # p0..p3 — already the sorted-proc order
            expect += v
        assert _value(t.merged(), "relayrl_c_total") == expect

    def test_frame_ingest_counts_frames_and_sections(self):
        from relayrl_tpu.telemetry.aggregate import encode_snapshot_frame

        t = self._table()
        frame = encode_snapshot_frame([
            self._section("a", 1.0, 1, {"relayrl_c_total": 1}),
            self._section("b", 1.0, 1, {"relayrl_c_total": 2})])
        t.ingest_frame(frame)
        assert t._m_frames.total() == 1
        assert t._m_sections.total() == 2
        assert t.proc_count() == 2

    def test_document_and_prometheus_labels(self):
        t = self._table()
        t.ingest_sections([
            self._section("actor-1", 1.0, 1, {"relayrl_c_total": 4}),
            self._section("relay-1", 1.0, 1, {"relayrl_c_total": 6},
                          tier="relay")])
        doc = t.document()
        assert doc["schema"] == "relayrl-fleet-v1"
        tiers = {p["proc"]: p["tier"] for p in doc["procs"]}
        assert tiers == {"actor-1": "actor", "relay-1": "relay"}
        assert _value(doc["merged"], "relayrl_c_total") == 10
        text = t.prometheus_text()
        assert 'proc="actor-1"' in text and 'tier="relay"' in text
        assert "# TYPE relayrl_c_total counter" in text


# ---------------------------------------------------------------------------
# relay fan-in buffer
# ---------------------------------------------------------------------------

class TestFleetRelayBuffer:
    def test_latest_per_proc_and_dirty_drain(self):
        from relayrl_tpu.telemetry.aggregate import (
            FleetRelayBuffer,
            snapshot_section,
        )

        buf = FleetRelayBuffer()
        buf.ingest_sections([snapshot_section({}, "a", "actor", 1.0, 1)])
        buf.ingest_sections([snapshot_section({}, "a", "actor", 1.0, 2),
                             snapshot_section({}, "b", "actor", 1.0, 1)])
        drained = buf.drain()
        assert [s["proc"] for s in drained] == ["a", "b"]
        assert drained[0]["seq"] == 2  # latest won
        assert buf.drain() == []  # nothing dirty until a new section
        # Stale (older epoch/seq) never replaces the held section.
        buf.ingest_sections([snapshot_section({}, "a", "actor", 1.0, 1)])
        assert buf.drain() == []

    def test_restarted_leaf_replaces_old_epoch(self):
        from relayrl_tpu.telemetry.aggregate import (
            FleetRelayBuffer,
            snapshot_section,
        )

        buf = FleetRelayBuffer()
        buf.ingest_sections([snapshot_section({}, "a", "actor", 1.0, 99)])
        buf.drain()
        buf.ingest_sections([snapshot_section({}, "a", "actor", 2.0, 1)])
        drained = buf.drain()
        assert drained[0]["epoch"] == 2.0 and drained[0]["seq"] == 1


class TestRelayNodeFanIn:
    def _node(self, tmp_path, interval=5.0):
        from tests.test_relay import _make_fakes

        cfg_path = tmp_path / "cfg.json"
        cfg_path.write_text(json.dumps(
            {"telemetry": {"fleet_interval_s": interval}}))
        FakeUp, FakeDown = _make_fakes()
        up, down = FakeUp(), FakeDown()
        from relayrl_tpu.relay import RelayNode

        node = RelayNode(config_path=str(cfg_path), name="relay-t",
                         batch_max=1, spool_entries=0,
                         upstream_transport=up, downstream_transport=down)
        return node, up, down

    def test_subtree_frames_merge_into_one_upstream_frame(
            self, tmp_path, fresh_registry):
        from relayrl_tpu.telemetry import aggregate as ag

        node, up, down = self._node(tmp_path)
        try:
            for i, proc in enumerate(("w1", "w2")):
                reg = _registry_with(
                    counters={"relayrl_actor_env_steps_total": 10 * (i + 1)},
                    run_id=proc)
                frame = ag.encode_snapshot_frame([ag.snapshot_section(
                    reg.snapshot(), proc, "actor", 1.0, 1)])
                node._on_subtree_trajectory(ag.fleet_wire_id(proc), frame)
            assert up.sent == []  # buffered, NOT forwarded per-frame
            node._fleet_flush()
            fleet_sends = [(wid, p) for wid, p in up.sent
                           if ag.is_snapshot_frame(p)]
            assert len(fleet_sends) == 1  # ONE frame for the subtree
            wid, payload = fleet_sends[0]
            assert wid == ag.fleet_wire_id("relay-t")
            sections = ag.parse_snapshot_frame(payload)
            procs = [s["proc"] for s in sections]
            # both leaves verbatim + the relay's own section
            assert procs[:2] == ["w1", "w2"] and "relay-t" in procs
            w1 = next(s for s in sections if s["proc"] == "w1")
            assert w1["epoch"] == 1.0 and w1["seq"] == 1
            assert _value(w1["snapshot"],
                          "relayrl_actor_env_steps_total") == 10
            relay_sec = next(s for s in sections
                             if s["proc"] == "relay-t")
            assert relay_sec["tier"] == "relay"
            # second flush with nothing new: only the relay's own section
            node._fleet_flush()
            _, payload2 = [(w, p) for w, p in up.sent
                           if ag.is_snapshot_frame(p)][-1]
            assert [s["proc"] for s in
                    ag.parse_snapshot_frame(payload2)] == ["relay-t"]
        finally:
            node.close()

    def test_snapshot_frames_never_enter_forward_path(
            self, tmp_path, fresh_registry):
        from relayrl_tpu.telemetry import aggregate as ag

        node, up, down = self._node(tmp_path)
        try:
            frame = ag.encode_snapshot_frame([ag.snapshot_section(
                {}, "w1", "actor", 1.0, 1)])
            node._on_subtree_trajectory("w1", frame)
            node._on_subtree_trajectory("w1#s1", b"real-payload")
            assert [(wid, p) for wid, p in up.sent] == [
                ("w1#s1", b"real-payload")]
        finally:
            node.close()

    def test_fleet_plane_off_forwards_frames_verbatim(
            self, tmp_path, fresh_registry):
        from relayrl_tpu.telemetry import aggregate as ag

        node, up, down = self._node(tmp_path, interval=0.0)
        try:
            assert node._fleet_buf is None
            frame = ag.encode_snapshot_frame([ag.snapshot_section(
                {}, "w1", "actor", 1.0, 1)])
            node._on_subtree_trajectory("@fleet/w1", frame)
            assert up.sent == [("@fleet/w1", frame)]
        finally:
            node.close()


# ---------------------------------------------------------------------------
# SLO alert engine
# ---------------------------------------------------------------------------

class TestAlertEngine:
    def _engine(self, rules, registry=None):
        from relayrl_tpu.telemetry import Registry
        from relayrl_tpu.telemetry.aggregate import AlertEngine, AlertRule

        self.events = []
        reg = registry or Registry(run_id="alerts")
        return AlertEngine(
            [AlertRule.from_dict(r) for r in rules], registry=reg,
            emit=lambda ev, **f: self.events.append({"event": ev, **f})), reg

    @staticmethod
    def _snap(value, name="relayrl_m", kind="gauge"):
        return {"metrics": [{"name": name, "kind": kind, "labels": {},
                             "value": value}]}

    def test_threshold_fire_and_resolve_with_gauge(self):
        eng, reg = self._engine([{"name": "depth", "metric": "relayrl_m",
                                  "agg": "max", "op": ">",
                                  "threshold": 10}])
        eng.evaluate(self._snap(5), now=0)
        assert self.events == [] and eng.active() == []
        eng.evaluate(self._snap(50), now=1)
        assert [e["event"] for e in self.events] == ["alert_fired"]
        assert eng.active() == ["depth"]
        snap = reg.snapshot()
        assert _value(snap, "relayrl_alert_active", {"rule": "depth"}) == 1
        eng.evaluate(self._snap(5), now=2)
        assert [e["event"] for e in self.events] == ["alert_fired",
                                                    "alert_resolved"]
        assert _value(reg.snapshot(), "relayrl_alert_active",
                      {"rule": "depth"}) == 0

    def test_for_s_hold_down(self):
        eng, _ = self._engine([{"name": "d", "metric": "relayrl_m",
                                "agg": "max", "op": ">", "threshold": 1,
                                "for_s": 5.0}])
        eng.evaluate(self._snap(9), now=0)
        assert eng.active() == []  # pending, not fired
        eng.evaluate(self._snap(9), now=3)
        assert eng.active() == []
        # condition cleared mid-hold-down: pending resets
        eng.evaluate(self._snap(0), now=4)
        eng.evaluate(self._snap(9), now=6)
        assert eng.active() == []
        eng.evaluate(self._snap(9), now=11.5)
        assert eng.active() == ["d"]

    def test_increase_agg_needs_two_observations(self):
        eng, _ = self._engine([{"name": "drops",
                                "metric": "relayrl_d_total",
                                "agg": "increase", "op": ">",
                                "threshold": 0}])
        base = self._snap(100, name="relayrl_d_total", kind="counter")
        eng.evaluate(base, now=0)
        assert eng.active() == []  # first sight: no delta yet
        eng.evaluate(self._snap(103, name="relayrl_d_total",
                                kind="counter"), now=1)
        assert eng.active() == ["drops"]
        eng.evaluate(self._snap(103, name="relayrl_d_total",
                                kind="counter"), now=2)
        assert eng.active() == []  # no further increase -> resolved

    def test_histogram_quantile_rule(self):
        from relayrl_tpu.telemetry import Registry

        reg = Registry(run_id="h")
        h = reg.histogram("relayrl_age_seconds", buckets=(0.1, 1.0, 10.0))
        for _ in range(100):
            h.observe(5.0)
        eng, _ = self._engine([{"name": "age", "metric":
                                "relayrl_age_seconds", "agg": "p95",
                                "op": ">", "threshold": 1.0}])
        eng.evaluate(reg.snapshot(), now=0)
        assert eng.active() == ["age"]

    def test_gauge_max_rule_reads_per_proc_spread_not_fleet_sum(self):
        from relayrl_tpu.telemetry.aggregate import merge_snapshots

        # 100 healthy procs each holding depth 5: the fleet SUM is 500
        # but the worst PROCESS is 5 — a max rule must read the spread
        # the merged gauge entry carries, not the collapsed sum.
        snaps = [_registry_with(gauges={"relayrl_depth": 5}).snapshot()
                 for _ in range(100)]
        merged = merge_snapshots(snaps)
        eng, _ = self._engine([{"name": "depth", "metric": "relayrl_depth",
                                "agg": "max", "op": ">", "threshold": 400}])
        eng.evaluate(merged, now=0)
        assert eng.active() == []
        # one genuinely bad proc trips it
        snaps.append(_registry_with(
            gauges={"relayrl_depth": 500}).snapshot())
        eng.evaluate(merge_snapshots(snaps), now=1)
        assert eng.active() == ["depth"]
        # min and avg read the spread too
        eng2, _ = self._engine([
            {"name": "mn", "metric": "relayrl_depth", "agg": "min",
             "op": "<", "threshold": 6},
            {"name": "av", "metric": "relayrl_depth", "agg": "avg",
             "op": ">", "threshold": 6}])
        eng2.evaluate(merged, now=0)  # all procs at 5: min 5, avg 5
        assert eng2.active() == ["mn"]

    def test_increase_rebaselines_on_membership_change(self):
        eng, _ = self._engine([{"name": "steps",
                                "metric": "relayrl_s_total",
                                "agg": "increase", "op": ">",
                                "threshold": 1000}])

        def snap(v):
            return self._snap(v, name="relayrl_s_total", kind="counter")

        eng.evaluate(snap(10_000), now=0, membership={"a", "b"})
        eng.evaluate(snap(10_100), now=1, membership={"a", "b"})
        assert eng.active() == []  # genuine delta 100 < threshold
        # proc b evicted: sum collapses — clamped, no fire
        eng.evaluate(snap(100), now=2, membership={"a"})
        assert eng.active() == []
        # proc b rejoins with its lifetime total: the +10k step must
        # REBASELINE (membership changed), not fire
        eng.evaluate(snap(10_200), now=3, membership={"a", "b"})
        assert eng.active() == []
        # steady membership again: genuine deltas resume
        eng.evaluate(snap(12_000), now=4, membership={"a", "b"})
        assert eng.active() == ["steps"]

    def test_missing_metric_never_fires_and_resolves(self):
        eng, _ = self._engine([{"name": "d", "metric": "relayrl_m",
                                "agg": "max", "op": ">", "threshold": 1}])
        eng.evaluate(self._snap(9), now=0)
        assert eng.active() == ["d"]
        eng.evaluate({"metrics": []}, now=1)
        assert eng.active() == []

    def test_default_pack_and_config_rules(self):
        from relayrl_tpu.telemetry.aggregate import (
            default_alert_rules,
            rules_from_config,
        )

        names = {r.name for r in default_alert_rules()}
        assert names == {"ingest_drops", "breaker_open", "guardrail_halt",
                         "nonfinite_publish_blocked", "ingest_queue_depth",
                         "trace_data_age_p95"}
        rules = rules_from_config({
            "alerts_default_pack": True,
            "alerts": [
                {"name": "ingest_drops", "metric": "relayrl_x_total",
                 "agg": "sum", "op": ">", "threshold": 9},  # override
                {"name": "custom", "metric": "relayrl_y", "agg": "max",
                 "op": ">=", "threshold": 2, "for_s": 3},
                {"name": "broken", "metric": "relayrl_z",
                 "agg": "nonsense", "op": ">", "threshold": 0},
            ]})
        by_name = {r.name: r for r in rules}
        assert by_name["ingest_drops"].metric == "relayrl_x_total"
        assert by_name["custom"].for_s == 3.0
        assert "broken" not in by_name  # warned + skipped
        only_user = rules_from_config({
            "alerts_default_pack": False,
            "alerts": [{"name": "only", "metric": "relayrl_y"}]})
        assert [r.name for r in only_user] == ["only"]

    def test_invalid_rule_shapes_raise(self):
        from relayrl_tpu.telemetry.aggregate import AlertRule

        with pytest.raises(ValueError):
            AlertRule.from_dict({"metric": "m"})  # no name
        with pytest.raises(ValueError):
            AlertRule.from_dict({"name": "r", "metric": "m", "op": "!="})
        with pytest.raises(ValueError):
            AlertRule.from_dict({"name": "r", "metric": "m",
                                 "bogus_key": 1})


# ---------------------------------------------------------------------------
# endpoints + pane + config
# ---------------------------------------------------------------------------

class TestEndpointsAndPane:
    def test_fleet_endpoints(self, fresh_registry):
        import urllib.error
        import urllib.request

        from relayrl_tpu.telemetry.aggregate import (
            AlertEngine,
            FleetTable,
            default_alert_rules,
            snapshot_section,
        )
        from relayrl_tpu.telemetry.export import TelemetryExporter

        exporter = TelemetryExporter(fresh_registry, port=0)
        try:
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(exporter.url + "/fleet", timeout=5)
            assert err.value.code == 404
            table = FleetTable(registry=fresh_registry)
            reg = _registry_with(counters={"relayrl_c_total": 3},
                                 run_id="w")
            table.ingest_sections([snapshot_section(
                reg.snapshot(), "w-1", "actor", 1.0, 1)])
            engine = AlertEngine(default_alert_rules(),
                                 registry=fresh_registry)
            exporter.set_fleet(table, engine)
            with urllib.request.urlopen(exporter.url + "/fleet",
                                        timeout=5) as resp:
                doc = json.loads(resp.read().decode())
            assert doc["schema"] == "relayrl-fleet-v1"
            assert doc["procs"][0]["proc"] == "w-1"
            assert {a["name"] for a in doc["alerts"]} >= {"ingest_drops"}
            with urllib.request.urlopen(exporter.url + "/fleet/metrics",
                                        timeout=5) as resp:
                text = resp.read().decode()
            assert 'relayrl_c_total{proc="w-1",tier="actor"} 3' in text
        finally:
            exporter.close()

    def test_render_fleet_pane(self):
        from relayrl_tpu.telemetry.top import render_fleet

        doc = {
            "schema": "relayrl-fleet-v1",
            "stale_s": 15.0,
            "procs": [
                {"proc": "server-1", "tier": "server", "age_s": 0.2,
                 "uptime_s": 100.0},
                {"proc": "relay-a", "tier": "relay", "age_s": 0.4,
                 "uptime_s": 90.0},
                {"proc": "w-0", "tier": "actor", "age_s": 0.5,
                 "uptime_s": 80.0, "restarts": 1},
            ],
            "merged": {"metrics": [
                {"name": "relayrl_actor_env_steps_total",
                 "kind": "counter", "labels": {}, "value": 12345}]},
            "alerts": [
                {"name": "ingest_drops", "op": ">", "threshold": 0,
                 "active": True, "value": 3.0},
                {"name": "breaker_open", "op": ">=", "threshold": 2,
                 "active": False, "value": 0.0}],
        }
        pane = render_fleet(doc)
        assert "3 proc(s)" in pane
        assert "server=1 relay=1 actor=1" in pane
        assert "ALERTS: 1 active" in pane and "ingest_drops" in pane
        assert "-- server " in pane and "-- relay " in pane \
            and "-- actor " in pane
        assert "restarts 1" in pane
        assert "env_steps_total" in pane
        # no active alerts renders the armed count instead
        doc["alerts"][0]["active"] = False
        assert "alerts: none active (2 rule(s) armed)" \
            in render_fleet(doc)

    def test_config_knobs_clamped(self, tmp_path):
        from relayrl_tpu.config import ConfigLoader

        cfg = tmp_path / "c.json"
        cfg.write_text(json.dumps({"telemetry": {
            "fleet_interval_s": -3, "fleet_stale_s": 0.25,
            "alerts": [{"name": "x", "metric": "m"}],
            "alerts_default_pack": 0}}))
        params = ConfigLoader(None, str(cfg)).get_telemetry_params()
        assert params["fleet_interval_s"] == 0.0
        assert params["fleet_stale_s"] == 1.0  # floor clamp
        assert params["alerts"] == [{"name": "x", "metric": "m"}]
        assert params["alerts_default_pack"] is False
        defaults = ConfigLoader(
            None, str(tmp_path / "missing.json"),
            create_if_missing=False).get_telemetry_params()
        assert defaults["fleet_interval_s"] == 0.0
        assert defaults["fleet_stale_s"] == 15.0
        assert defaults["alerts"] is None
        assert defaults["alerts_default_pack"] is True

    def test_config_stale_floor_and_alert_shapes(self, tmp_path):
        import warnings as _w

        from relayrl_tpu.config import ConfigLoader

        # stale_s must cover >= 2 emission intervals or the table flaps
        cfg = tmp_path / "flap.json"
        cfg.write_text(json.dumps({"telemetry": {
            "fleet_interval_s": 30.0, "fleet_stale_s": 15.0}}))
        with _w.catch_warnings(record=True) as caught:
            _w.simplefilter("always")
            params = ConfigLoader(None, str(cfg)).get_telemetry_params()
        assert params["fleet_stale_s"] == 60.0
        assert any("fleet_stale_s" in str(w.message) for w in caught)
        # a single rule object is accepted as a one-element list
        cfg2 = tmp_path / "one.json"
        cfg2.write_text(json.dumps({"telemetry": {
            "alerts": {"name": "x", "metric": "m"}}}))
        params = ConfigLoader(None, str(cfg2)).get_telemetry_params()
        assert params["alerts"] == [{"name": "x", "metric": "m"}]
        # any other non-list shape warns and drops (never silently)
        cfg3 = tmp_path / "bad.json"
        cfg3.write_text(json.dumps({"telemetry": {"alerts": "nope"}}))
        with _w.catch_warnings(record=True) as caught:
            _w.simplefilter("always")
            params = ConfigLoader(None, str(cfg3)).get_telemetry_params()
        assert params["alerts"] is None
        assert any("telemetry.alerts" in str(w.message) for w in caught)


# ---------------------------------------------------------------------------
# live-zmq drill: root totals == sum of per-process registries, bit-exact
# ---------------------------------------------------------------------------

class TestLiveFleetDrill:
    # ISSUE 17 wall re-fit: live-zmq e2e rides the slow tier;
    # merge/relay semantics stay covered fast by the unit suite above.
    @pytest.mark.slow
    def test_live_zmq_root_totals_bit_exact(self, tmp_path, tmp_cwd):
        from relayrl_tpu import telemetry
        from relayrl_tpu.runtime.server import TrainingServer

        scratch = str(tmp_path)
        interval = 0.25
        cfg = {
            "learner": {"checkpoint_dir": "",
                        "checkpoint_every_epochs": 1_000_000},
            "telemetry": {"enabled": True, "port": 0,
                          "fleet_interval_s": interval,
                          "fleet_stale_s": 60.0},
        }
        cfg_path = os.path.join(scratch, "cfg.json")
        with open(cfg_path, "w") as f:
            json.dump(cfg, f)
        addrs = {
            "agent_listener_addr": f"tcp://127.0.0.1:{free_port()}",
            "trajectory_addr": f"tcp://127.0.0.1:{free_port()}",
            "model_pub_addr": f"tcp://127.0.0.1:{free_port()}",
        }
        server = TrainingServer("REINFORCE", obs_dim=4, act_dim=2,
                                server_type="zmq", env_dir=scratch,
                                config_path=cfg_path, **addrs)
        try:
            assert server._fleet is not None
            stop_file = os.path.join(scratch, "stop")
            workers = []
            results = []
            for w in range(2):
                ident = f"drill-w{w}"
                result_path = os.path.join(scratch, f"{ident}.json")
                results.append(result_path)
                wcfg = {
                    "identity": ident, "agents_per_proc": 2,
                    "scratch": scratch, "config_path": cfg_path,
                    "seed": w, "obs_dim": 4, "episode_len": 3,
                    "duration_s": 120, "stop_file": stop_file,
                    "result_path": result_path,
                    "agent_listener_addr": addrs["agent_listener_addr"],
                    "trajectory_addr": addrs["trajectory_addr"],
                    "model_sub_addr": addrs["model_pub_addr"],
                }
                env = dict(os.environ)
                env["JAX_PLATFORMS"] = "cpu"
                env["PYTHONPATH"] = REPO_ROOT
                workers.append(subprocess.Popen(
                    [sys.executable,
                     os.path.join(REPO_ROOT, "tests", "drills",
                                  "_fleet_worker.py"),
                     json.dumps(wcfg)],
                    env=env, stdout=subprocess.PIPE,
                    stderr=subprocess.STDOUT, text=True))
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                if all(os.path.exists(os.path.join(
                        scratch, f"ready_drill-w{w}")) for w in range(2)):
                    break
                for p in workers:
                    assert p.poll() is None, p.communicate()[0][-3000:]
                time.sleep(0.1)
            time.sleep(8 * interval)  # a few live frames
            with open(stop_file, "w") as f:
                f.write("stop")
            worker_rows = []
            for p, path in zip(workers, results):
                out, _ = p.communicate(timeout=120)
                assert p.returncode == 0 and os.path.exists(path), \
                    out[-3000:]
                with open(path) as f:
                    worker_rows.append(json.load(f))
            time.sleep(2 * interval)
            server._fleet_tick()  # deterministic final tick
            doc = server._fleet.document(alerts=server._alerts)
            tiers = {p["proc"]: p["tier"] for p in doc["procs"]}
            assert tiers.get("drill-w0") == "actor"
            assert tiers.get("drill-w1") == "actor"
            assert "server" in set(tiers.values())
            assert server._fleet._m_frames.total() > 0  # live wire frames
            # THE exactness bar: every relayrl_actor_* counter family in
            # the merged doc equals the float sum of the two workers'
            # committed registries, bit for bit.
            merged = doc["merged"]
            families = {}
            for row in sorted(worker_rows, key=lambda r: r["identity"]):
                for m in row["snapshot"]["metrics"]:
                    if m["kind"] != "counter" or \
                            not m["name"].startswith("relayrl_actor_"):
                        continue
                    key = (m["name"], tuple(sorted(
                        (m.get("labels") or {}).items())))
                    families[key] = families.get(key, 0.0) + m["value"]
            assert families, "workers recorded no actor counters"
            checked = 0
            for (name, labels), expect in sorted(families.items()):
                got = next(
                    (m["value"] for m in merged["metrics"]
                     if m["name"] == name and m["kind"] == "counter"
                     and tuple(sorted(m["labels"].items())) == labels),
                    None)
                assert got == expect, (name, labels, got, expect)
                checked += 1
            # the vector tier's counter families: env_steps + dispatches
            assert checked >= 2
            # steps actually happened and landed in the merged totals
            steps = next(m["value"] for m in merged["metrics"]
                         if m["name"] == "relayrl_actor_env_steps_total")
            assert steps > 0
        finally:
            server.disable_server()
            telemetry.reset_for_tests()
