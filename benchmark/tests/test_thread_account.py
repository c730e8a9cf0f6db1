"""``thread_account`` and the eleven readers that go through it against the
hand-made window of ``data/thread_events.json`` (its ``_about`` works every
number). The twin of tier-1's ``tests/test_thread_account_arithmetic.py``,
which holds the rule's cases one by one. Run by hand: ``python -m pytest
benchmark/tests -q -p no:cacheprovider``."""

import json
import os
import types

import pytest

from benchmark import harness, program_trace, thread_account

HERE = os.path.dirname(os.path.abspath(__file__))


def _recorded():
    with open(os.path.join(HERE, "data", "thread_events.json")) as f:
        return json.load(f)


def _run(events, timings=None, window_s=20.0):
    reduced = program_trace.reduce_events(events) if events else None
    return types.SimpleNamespace(trace=True, timings=timings or {},
                                 window_s=window_s, notes={},
                                 _program_trace=reduced)


@pytest.mark.parametrize("name", sorted(_recorded()["expected"]))
def test_reader(name):
    rec = _recorded()
    reader = harness.load_layer_metric(name)
    assert reader.read(_run(rec["events"], rec["timings"],
                            rec["window_s"])) == pytest.approx(
        rec["expected"][name])
    # a program without the spans, the argument and the ledger (this PR's
    # parent), or an untraced run
    assert reader.read(_run({"threads": [], "modules": {}, "ops": {}})) \
        is None
    assert reader.read(_run(None)) is None


def test_the_parents_own_spans_read_none():
    """The parent has ``rl:batch.pad`` and ``rl:ingest.decode`` — without
    ``cpu_ns`` and without the native call's child span."""
    events = {"threads": [
        [["host:window", 0, 10_000, {}],
         ["host:dispatch", 1000, 100, {"cycle_cpu_ns": 0}],
         ["rl:batch.stack", 2000, 100, {}],
         ["rl:batch.pad", 3000, 1000, {}]],
        [["rl:ingest.decode", 3200, 500, {}]]]}
    run = _run(events, {"decode_s": 1.0})
    for name in _recorded()["expected"]:
        assert harness.load_layer_metric(name).read(run) is None, name
    assert run.notes == {}


def test_the_account_closes():
    rec = _recorded()
    run = _run(rec["events"], rec["timings"], rec["window_s"])
    wait = thread_account.of(run)["pad_wait"]
    assert wait["decode"] + wait["ingest"] + wait["publish"] + wait[
        "unattributed"] == pytest.approx(wait["off"])
    assert wait["off"] == pytest.approx(2.1e6)
    thread_account.note(run)
    table = run.notes["thread_account"]
    assert sum(table["pad_wait_pct"].values()) == pytest.approx(100, abs=0.01)
    for row in table["spans_ms_per_update"].values():
        if "cpu" in row:
            assert row["cpu"] <= row["wall"] and row["off"] >= 0
