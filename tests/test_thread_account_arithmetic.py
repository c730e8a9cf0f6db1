"""``benchmark/thread_account.py`` as pure arithmetic, against the hand-made
window of ``benchmark/tests/data/thread_events.json`` (its ``_about`` works
every number): self CPU time, the overlap rule, the cap at ``off``, a clipped
span's scaling, a clock that steps by 10 ms, None for a program that reports
none of it — and the eleven
readers and ``BENCHMARK.json`` entries that go through it. No jax, no run;
``benchmark/tests/test_thread_account.py`` is its twin, run by hand."""

import copy
import json
import os
import types

import pytest

from benchmark import harness, program_trace, thread_account

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOOPS = ["nature-cnn.loop", "nature-cnn.loop-saturated"]
# name -> (unit, source, layer, cells), in BENCHMARK.json's order
NEW = {
    "ingest_recv_ms": ("ms", "program_span", "transport + ingest", LOOPS),
    "ingest_admit_ms": ("ms", "program_span", "transport + ingest", LOOPS),
    "decode_gil_ms": ("ms", "program_span", "transport + ingest", LOOPS),
    "pad_offcpu_ms": ("ms", "program_span", "staging + H2D",
                      ["nature-cnn.update"] + LOOPS),
    "pad_wait_on_decode_pct": ("%", "program_span", "transport + ingest",
                               LOOPS),
    "pad_wait_on_ingest_pct": ("%", "program_span", "transport + ingest",
                               LOOPS),
    "cpu_learner_pct": ("%", "program_counter", "update dispatch", LOOPS),
    "cpu_staging_pct": ("%", "program_counter", "transport + ingest", LOOPS),
    "cpu_ingest_pct": ("%", "program_counter", "transport + ingest", LOOPS),
    "cpu_publish_pct": ("%", "program_counter", "publish", LOOPS),
    "cpu_process_pct": ("%", "program_counter", "transport + ingest", LOOPS),
}


def _recorded():
    with open(os.path.join(REPO, "benchmark", "tests", "data",
                           "thread_events.json")) as f:
        return json.load(f)


def _run(events, timings=None, window_s=20.0):
    reduced = program_trace.reduce_events(events) if events else None
    return types.SimpleNamespace(trace=True, timings=timings or {},
                                 window_s=window_s, notes={},
                                 _program_trace=reduced)


def _account(events=None):
    events = events or _recorded()["events"]
    return thread_account.account(
        program_trace.reduce_events(events)["spans"])


def _without(events, *names, arg=None):
    """The events less the spans ``names`` — or less their argument ``arg``."""
    events = copy.deepcopy(events)
    for line in events["threads"]:
        if arg is None:
            line[:] = [e for e in line if e[0] not in names]
        else:
            for e in line:
                if not names or e[0] in names:
                    e[3].pop(arg, None)
    return events


def test_totals_by_name_and_the_window():
    acct = _account()
    assert acct["updates"] == 2
    pad = acct["names"]["rl:batch.pad"]
    # five inside (the one before the window is dropped), the last clipped
    # to half: its CPU time is scaled with it
    assert pad["n"] == 5
    assert pad["wall"] == 1e6 + 1e6 + 2e6 + 1e6 + 0.5e6
    assert pad["cpu"] == pytest.approx(0.4e6 + 1e6 + 1.5e6 + 0.2e6 + 0.3e6)
    assert pad["off"] == pytest.approx(0.6e6 + 0 + 0.5e6 + 0.8e6 + 0.2e6)
    recv = acct["names"]["rl:ingest.recv"]
    assert (recv["n"], recv["wall"], recv["cpu"]) == (2, 0.6e6, 0.5e6)


def test_self_cpu_is_a_spans_own_less_its_direct_childrens():
    names = _account()["names"]
    decode = names["rl:ingest.decode"]
    assert decode["self"] == 0.6e6 + 0.8e6
    assert decode["self_cpu"] == pytest.approx(0.5e6 + 0.8e6)
    assert names["rl:ingest.decode_native"]["self_cpu"] == 0.6e6
    # on the learner thread: host:accumulate [2.9, 4.1) holds P1 — all of
    # it, priced at the NAME's share (pad: 3.4 of 5.5), not at P1's own 0.4:
    # one span's answer can come out negative, a window's sum does not
    acc = names["host:accumulate"]
    assert acc["self"] == 0.2e6
    assert acc["self_cpu"] == pytest.approx(0.5e6 - 1e6 * 3.4 / 5.5)
    # the publisher's own Python ran on no CPU at all
    publish = names["rl:publish"]
    assert publish["self"] == 1e6 and publish["self_cpu"] == pytest.approx(0)


# a NAME's on-CPU share in the hand-made window: its spans' self CPU time over
# their self time (the file's ``_about`` has each)
R_DECODE, R_RECV, R_ADMIT, R_GATHER, R_ENCODE = 1.3 / 1.4, 0.5 / 0.6, 1, 0.2, 1
OFF_SHARE = 2.1 / 5.5           # pad: wall 5.5, cpu 3.4


def test_overlap_rule_and_the_cap_at_off():
    wait = _account()["pad_wait"]
    assert wait["off"] == pytest.approx(2.1e6)
    off1, off3 = 1e6 * OFF_SHARE, 2e6 * OFF_SHARE
    # P1: 0.4 of D1's self pieces, recv 0.2 and admit 0.1 beside it
    busy1 = {"decode": 0.4e6 * R_DECODE,
             "ingest": 0.2e6 * R_RECV + 0.1e6 * R_ADMIT}
    # P3: D2's self pieces 0.8, recv 0.4, the publisher's gather and encode
    busy3 = {"decode": 0.8e6 * R_DECODE, "ingest": 0.4e6 * R_RECV,
             "publish": 1e6 * R_GATHER + 1e6 * R_ENCODE}
    # both over their cap: scaled down to off
    assert sum(busy1.values()) > off1 and sum(busy3.values()) > off3
    scale1 = off1 / sum(busy1.values())
    scale3 = off3 / sum(busy3.values())
    assert wait["decode"] == pytest.approx(
        scale1 * busy1["decode"] + scale3 * busy3["decode"])
    assert wait["ingest"] == pytest.approx(
        scale1 * busy1["ingest"] + scale3 * busy3["ingest"])
    assert wait["publish"] == pytest.approx(scale3 * busy3["publish"])
    # P2, P4 and P5 with nobody beside them
    assert wait["unattributed"] == pytest.approx(2.5e6 * OFF_SHARE)
    assert sum(wait[k] for k in ("decode", "ingest", "publish",
                                 "unattributed")) == pytest.approx(wait["off"])


def test_under_the_cap_the_rest_stays_unattributed():
    """Without the receive thread P1's busy (0.371) is under its off (0.382):
    decode takes what it was busy for and the rest is nobody's."""
    events = _without(_recorded()["events"], "rl:ingest.recv",
                      "rl:ingest.admit")
    wait = _account(events)["pad_wait"]
    busy1 = 0.4e6 * R_DECODE
    assert busy1 < 1e6 * OFF_SHARE
    busy3 = {"decode": 0.8e6 * R_DECODE, "publish": 1.2e6}
    scale3 = 2e6 * OFF_SHARE / sum(busy3.values())
    assert wait["ingest"] == 0.0
    assert wait["decode"] == pytest.approx(busy1 + scale3 * busy3["decode"])
    assert wait["unattributed"] == pytest.approx(
        1e6 * OFF_SHARE - busy1 + 2.5e6 * OFF_SHARE)


def test_a_coarse_clock_changes_nothing_while_the_sums_hold():
    """The chip machine's thread clock steps by 10 ms: a span reads 0 or a
    whole step. Give all of each name's CPU time to its first span: the
    sums, and so every share, stay what they were."""
    events = copy.deepcopy(_recorded()["events"])
    # the clipped pad keeps its own (a clipped span's CPU time is scaled)
    keep = [e for line in events["threads"] for e in line
            if e[0] == "rl:batch.pad" and e[1] == 20_500_000]
    by_name = {}
    for line in events["threads"]:
        for e in line:
            if "cpu_ns" in e[3] and e not in keep and e[1] >= 1_000_000:
                by_name.setdefault(e[0], []).append(e)
    for found in by_name.values():
        total = sum(e[3]["cpu_ns"] for e in found)
        for e in found:
            e[3]["cpu_ns"] = 0
        found[0][3]["cpu_ns"] = total
    fine, coarse = _account()["pad_wait"], _account(events)["pad_wait"]
    # (decode's two spans hold different shares of their native calls, so
    # its self CPU moves a little with where the step lands: not here)
    for key in ("off", "ingest", "publish", "unattributed"):
        assert coarse[key] == pytest.approx(fine[key], rel=0.05), key
    assert _account(events)["names"]["rl:batch.pad"]["off"] == pytest.approx(
        2.1e6)


def test_a_sample_of_stamped_spans_prices_the_whole_name():
    """The program stamps at most one span of a name in 5 ms. With P1 and P3
    alone stamped, pad's share is theirs (1.9 of 3.0) over all 5.5 of wall;
    a parent whose child went unstamped still gets the child's name's price."""
    events = copy.deepcopy(_recorded()["events"])
    for e in events["threads"][0]:
        if e[0] == "rl:batch.pad" and e[1] not in (3_000_000, 7_000_000):
            del e[3]["cpu_ns"]
    for e in events["threads"][1]:
        if e[0] == "rl:ingest.decode_native" and e[1] == 7_600_000:
            del e[3]["cpu_ns"]
    acct = _account(events)
    pad = acct["names"]["rl:batch.pad"]
    assert (pad["n"], pad["stamped"]) == (5, 2)
    assert pad["cpu"] == pytest.approx(5.5e6 * 1.9 / 3.0)
    assert pad["off"] == pytest.approx(5.5e6 * 1.1 / 3.0)
    assert acct["pad_wait"]["off"] == pytest.approx(pad["off"])
    # decode: 1.9 of cpu in 2.0 of wall; its native calls 0.6 of wall, the
    # one stamped 0.4 of cpu in 0.4: self cpu 1.9 - 0.6 x 1.0
    decode = acct["names"]["rl:ingest.decode"]
    assert decode["self_cpu"] == pytest.approx(1.9e6 - 0.6e6)
    assert acct["names"]["rl:ingest.decode_native"]["stamped"] == 1


def test_a_names_share_is_over_the_clock_reads_own_wall_time():
    """A stamped span's duration also holds the two clock reads and the
    annotation's making; ``cpu_wall_ns`` is the wall time between the reads
    themselves. Pads whose reads were 0.8 of their duration apart: the same
    CPU time is a larger share, of the same wall time."""
    events = copy.deepcopy(_recorded()["events"])
    for e in events["threads"][0]:
        if e[0] == "rl:batch.pad":
            e[3]["cpu_wall_ns"] = int(0.8 * e[2])
    pad = _account(events)["names"]["rl:batch.pad"]
    assert pad["wall"] == 5.5e6
    assert pad["cpu"] == pytest.approx(5.5e6 * 3.4 / (0.8 * 5.5))
    assert pad["off"] == pytest.approx(5.5e6 * (1 - 3.4 / 4.4))


def test_a_name_no_span_of_which_is_stamped_is_in_no_class():
    events = _without(_recorded()["events"], "rl:ingest.recv",
                      "rl:ingest.admit", arg="cpu_ns")
    acct = _account(events)
    assert "cpu" not in acct["names"]["rl:ingest.recv"]
    assert "stamped" not in acct["names"]["rl:ingest.recv"]
    assert acct["pad_wait"]["ingest"] == 0.0


def test_the_native_call_is_no_part_of_what_holds_pad_up():
    """With the native call not named, all of a decode inside a pad counts
    (D1: 0.8 of P1, D2: 1.0 of P3, at R = 1.9/2.0): the child span is what
    takes the lock-free part out."""
    events = _without(_recorded()["events"], "rl:ingest.decode_native")
    wait = _account(events)["pad_wait"]
    r = 1.9 / 2.0
    busy1 = {"decode": 0.8e6 * r, "ingest": 0.2e6 * R_RECV + 0.1e6}
    busy3 = {"decode": 1.0e6 * r, "ingest": 0.4e6 * R_RECV, "publish": 1.2e6}
    assert wait["decode"] == pytest.approx(
        busy1["decode"] * 1e6 * OFF_SHARE / sum(busy1.values())
        + busy3["decode"] * 2e6 * OFF_SHARE / sum(busy3.values()))


def test_a_thread_never_waits_on_itself():
    """Spans of a class on the pad's own thread are left out: move the
    receive thread's spans onto the learner's line."""
    events = _recorded()["events"]
    learner, staging, receive, publisher = events["threads"]
    events = {**events,
              "threads": [learner + receive, staging, publisher]}
    assert _account(events)["pad_wait"]["ingest"] == 0.0


@pytest.mark.parametrize("events", [
    None, {"threads": [], "modules": {}, "ops": {}},
    _without(_without(_recorded()["events"], arg="cpu_ns"),
             "rl:ingest.recv", "rl:ingest.admit", "rl:ingest.decode_native")],
    ids=["untraced", "empty", "parent: no cpu_ns, none of the new spans"])
def test_nothing_to_read_is_none(events):
    run = _run(events)
    assert thread_account.of(run) is None
    for name in NEW:
        assert harness.load_layer_metric(name).read(run) is None, name
    assert run.notes == {}


def test_only_pad_untimed_leaves_the_rest_of_the_account():
    events = _without(_recorded()["events"], "rl:batch.pad", arg="cpu_ns")
    acct = _account(events)
    assert acct["pad_wait"] is None
    assert "off" not in acct["names"]["rl:batch.pad"]
    run = _run(events)
    assert thread_account.pad_wait_pct(run, "decode") is None
    assert thread_account.per_update_ms(
        run, "rl:batch.pad", "off", per="rl:batch.stack") is None
    assert thread_account.decode_gil_ms(run) == pytest.approx(0.7)


def test_decode_gil_ms_wants_the_native_call_named():
    """The parent's ``rl:ingest.decode`` has no child: its whole span would
    read as the Python half."""
    events = _without(_recorded()["events"], "rl:ingest.decode_native")
    assert thread_account.decode_gil_ms(_run(events)) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader(name):
    rec = _recorded()
    run = _run(rec["events"], rec["timings"], rec["window_s"])
    assert harness.load_layer_metric(name).read(run) == pytest.approx(
        rec["expected"][name])


def test_ledger_share_without_the_key_or_the_window_is_none():
    assert thread_account.ledger_pct(
        _run(None, {"cpu_learner_s": 1.0}), "runq_learner_s") is None
    assert thread_account.ledger_pct(
        _run(None, {"cpu_learner_s": 1.0}, window_s=0.0),
        "cpu_learner_s") is None


def test_note_holds_the_whole_table_and_its_shares_close():
    rec = _recorded()
    run = _run(rec["events"], rec["timings"], rec["window_s"])
    thread_account.note(run)
    table = run.notes["thread_account"]
    assert table["updates"] == 2
    assert table["threads_pct_of_window"] == {
        "learner": {"cpu": 45.0, "runq": 1.5},
        "staging": {"cpu": 60.0, "runq": 0.5},
        "ingest": {"cpu": 20.0}, "publish": {"cpu": 12.5},
        "process": {"cpu": 210.0}}
    named = sum(table["threads_pct_of_window"][role]["cpu"]
                for role in ("learner", "staging", "ingest", "publish"))
    assert named <= table["threads_pct_of_window"]["process"]["cpu"]
    assert sum(table["pad_wait_pct"].values()) == pytest.approx(100, abs=0.01)
    pad = table["spans_ms_per_update"]["rl:batch.pad"]
    assert pad == {"n": 5, "stamped": 5, "wall": 2.75, "self": 2.75,
                   "cpu": 1.7, "off": 1.05, "self_cpu": 1.7}
    json.dumps(table)
    # the ledger alone (an untraced window) still gives the threads' rows
    bare = _run(None, rec["timings"], rec["window_s"])
    thread_account.note(bare)
    assert list(bare.notes["thread_account"]) == ["threads_pct_of_window"]


def test_the_run_queue_share_is_the_notes_and_no_metric():
    """The chip machine's kernel keeps no ``schedstat``, so a metric of the
    learner thread's run-queue time could never be reported in its cells:
    the share is read where the ledger has it, into the note's table, and
    ``BENCHMARK.json`` lists nothing that reads it."""
    rec = _recorded()
    run = _run(None, rec["timings"], rec["window_s"])
    assert thread_account.ledger_pct(run, "runq_learner_s") == \
        pytest.approx(1.5)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert not [m for m in bench["per_layer"] if "runq" in m["name"]]
    assert not [f for f in os.listdir(os.path.join(
        REPO, "benchmark", "layer_metrics")) if "runq" in f]


def test_the_eleven_entries_are_appended_with_their_cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert len(bench["per_layer"]) == 99
    tail = bench["per_layer"][-len(NEW):]
    assert [m["name"] for m in tail] == list(NEW)
    cells = {w["name"] for w in bench["workloads"]}
    for m in tail:
        unit, source, layer, listed = NEW[m["name"]]
        assert m == {"name": m["name"], "unit": unit, "better": "lower",
                     "source": source, "layer": layer,
                     "moves": "train_samples_per_s", "workloads": listed}
        assert set(listed) <= cells
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "layer_metrics", m["name"] + ".py"))
