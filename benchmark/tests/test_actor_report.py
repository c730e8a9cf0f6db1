"""The readers of the actors' report (``benchmark/actor_report.py``), the data
age and the collections, each against a run made by hand: the arithmetic, and
None for a program that reports nothing. Run by hand: ``python -m pytest
benchmark/tests -q -p no:cacheprovider``."""

import types

import pytest

from benchmark import harness, program_trace

ACTOR = ("actor_frames_per_s", "actor_infer_pct", "actor_record_pct",
         "actor_emit_pct", "actor_env_pct", "actor_swap_pct",
         "actor_offcpu_pct", "model_install_ms")
NEW = ACTOR + ("data_age_ms", "host_gc_ms")


def _read(name, run):
    return harness.load_layer_metric(name).read(run)


def _run(timings=None, stats=None, events=None):
    reduced = program_trace.reduce_events(events) if events else None
    return types.SimpleNamespace(
        trace=True, timings=timings or {}, stats=stats or {}, notes={},
        window_s=20.0, _program_trace=reduced)


def _reporting_run():
    """Seven processes' ledgers over a 20 s window, summed: 140 s of wall."""
    return _run(
        timings={"actor_wall_s": 140.0, "actor_step_s": 133.0,
                 "actor_env_s": 7.0, "actor_infer_s": 105.0,
                 "actor_record_s": 2.8, "actor_encode_s": 9.8,
                 "actor_send_s": 4.2, "actor_cpu_s": 126.0,
                 "actor_model_decode_s": 0.7, "actor_swap_s": 0.7,
                 "actor_model_install_s": 4.2, "actor_gc_s": 0.5,
                 "learner_idle_s": 10.0},
        stats={"actor_steps": 238000, "actor_installs": 168,
               "trajectories": 11900})


def test_shares_rate_and_install_by_hand():
    run = _reporting_run()
    assert _read("actor_frames_per_s", run) == pytest.approx(1700.0)
    assert _read("actor_infer_pct", run) == pytest.approx(75.0)
    assert _read("actor_record_pct", run) == pytest.approx(2.0)
    assert _read("actor_emit_pct", run) == pytest.approx(10.0)
    assert _read("actor_env_pct", run) == pytest.approx(5.0)
    assert _read("actor_swap_pct", run) == pytest.approx(1.0)
    assert _read("actor_offcpu_pct", run) == pytest.approx(10.0)
    assert _read("model_install_ms", run) == pytest.approx(25.0)
    # the decomposition: what is named of a step, and the environment
    named = sum(_read(n, run) for n in ("actor_infer_pct", "actor_record_pct",
                                        "actor_emit_pct", "actor_env_pct"))
    assert named == pytest.approx(92.0)
    # the emit reader leaves the whole ledger in the notes, the learner's
    # own totals out of it
    ledger = run.notes["actor_ledger"]
    assert ledger["actor_encode_s"] == 9.8 and ledger["actor_send_s"] == 4.2
    assert ledger["actor_steps"] == 238000 and ledger["actor_gc_s"] == 0.5
    assert "learner_idle_s" not in ledger and "trajectories" not in ledger


@pytest.mark.parametrize("run", [
    _run(),                                             # the parent: no keys
    _run(timings={"learner_idle_s": 3.0}, stats={"trajectories": 5}),
    _run(timings={"actor_wall_s": 0.0, "actor_infer_s": 0.0,     # no actor
                  "actor_cpu_s": 0.0, "actor_model_install_s": 0.0},
         stats={"actor_steps": 0, "actor_installs": 0}),         # reported
], ids=["no-keys", "learner-only", "zeros"])
def test_every_actor_reader_is_none_without_a_report(run):
    for name in ACTOR:
        assert _read(name, run) is None, name
    assert "actor_ledger" not in run.notes


def _events(dispatch_args, gc=()):
    """One learner thread: a window of 10 ms, two dispatches in it and one
    before it, the collections given."""
    spans = [["host:window", 1000000, 10000000, {}],
             ["host:dispatch", 500000, 100000, {"data_age_us": 9e9}]]
    for i, args in enumerate(dispatch_args):
        spans.append(["host:dispatch", 2000000 + 4000000 * i, 1000000, args])
    spans += [["rl:gc", start, dur, {"generation": 2, "collected": 7}]
              for start, dur in gc]
    return {"threads": [spans]}


def test_data_age_is_the_mean_over_the_windows_dispatches():
    run = _run(events=_events([{"data_age_us": 700000,
                                "data_age_max_us": 1200000},
                               {"data_age_us": 900000,
                                "data_age_max_us": 1300000}]))
    assert _read("data_age_ms", run) == pytest.approx(800.0)
    # a program whose dispatch says nothing of its batch's age
    assert _read("data_age_ms", _run(events=_events([{}, {}]))) is None
    assert _read("data_age_ms", _run()) is None


def test_host_gc_is_collections_over_updates_and_zero_when_none_ran(
        monkeypatch):
    from relayrl_tpu.telemetry import spans

    quiet = _run(events=_events([{}, {}]))
    assert _read("host_gc_ms", quiet) == 0.0
    # two collections of 3 ms and 1 ms inside a window of two updates; a
    # third before the window does not count
    run = _run(events=_events([{}, {}], gc=[(3000000, 3000000),
                                            (7000000, 1000000),
                                            (100000, 200000)]))
    assert _read("host_gc_ms", run) == pytest.approx(2.0)
    assert _read("host_gc_ms", _run()) is None          # not traced
    # a program that does not name its collections: null, not zero
    monkeypatch.delattr(spans, "watch_gc")
    assert _read("host_gc_ms", quiet) is None


def test_the_new_entries_and_their_cells():
    import json
    import os

    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    cells = [w["name"] for w in bench["workloads"]]
    # in PR 52's order, whatever later PRs listed after them
    names = [m["name"] for m in bench["per_layer"]]
    assert [n for n in names if n in NEW] == list(NEW)
    # an actor tier runs in nature-cnn.loop alone; the replay processes of
    # nature-cnn.loop-saturated install models and stamp births too
    loops = [c for c in cells if c.startswith("nature-cnn.loop")]
    for name in ACTOR[:-1]:
        assert entries[name]["workloads"] == ["nature-cnn.loop"], name
    for name in ("model_install_ms", "data_age_ms"):
        assert entries[name]["workloads"] == loops, name
    assert entries["host_gc_ms"]["workloads"] == cells
    assert {entries[n]["layer"] for n in ACTOR[:-1]} == {"actor tiers"}
    assert entries["model_install_ms"]["layer"] == "publish"
    assert entries["data_age_ms"]["layer"] == "transport + ingest"
    assert entries["host_gc_ms"]["layer"] == "update dispatch"
