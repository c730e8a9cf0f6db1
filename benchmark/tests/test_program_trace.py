"""``program_trace`` against a small recorded event list with answers worked
by hand, and every new reader through it. Run by hand: ``python -m pytest
benchmark/tests -q -p no:cacheprovider``."""

import json
import os
import types

import pytest

from benchmark import harness, program_trace

HERE = os.path.dirname(os.path.abspath(__file__))


def _recorded():
    with open(os.path.join(HERE, "data", "program_events.json")) as f:
        return json.load(f)


def _run(events):
    """A stand-in for ``harness.Run`` that already holds a reduced trace."""
    reduced = program_trace.reduce_events(events) if events else None
    return types.SimpleNamespace(trace=True, _program_trace=reduced)


def _span(reduced, name, i=0):
    return sorted(reduced["spans"][name], key=lambda s: s["start"])[i]


def test_window_clips_and_drops():
    t = program_trace.reduce_events(_recorded()["events"])
    assert t["window_ns"] == (1000000, 11000000)
    assert "host:window" not in t["spans"]
    pads = t["spans"]["rl:batch.pad"]
    assert [p["start"] for p in pads] == [1160000, 4000000]  # 500000 is out
    tail = _span(t, "host:wait_data", 1)
    assert tail["dur"] == 200000 and tail["inside"]


def test_nested_same_name_pair_counts_once_and_keeps_the_arguments():
    t = program_trace.reduce_events(_recorded()["events"])
    assert len(t["spans"]["host:accumulate"]) == 1
    assert _span(t, "host:accumulate")["dur"] == 400000
    first, second = (_span(t, "host:dispatch", i) for i in (0, 1))
    assert (first["start"], first["dur"]) == (2000000, 1000000)
    assert first["args"]["version"] == 5 and second["args"]["version"] == 6
    assert first["args"]["mono_ns"] == 77000010000


def test_self_time_is_per_thread():
    t = program_trace.reduce_events(_recorded()["events"])
    item = _span(t, "rl:learner.item")
    assert item["dur"] == 2000000
    assert item["self"] == 2000000 - 400000 - 1000000
    assert _span(t, "host:dispatch")["self"] == 1000000 - 200000 - 600000
    publish = _span(t, "rl:publish")        # thread 1, while thread 0 works
    assert publish["thread"] != item["thread"]
    assert publish["self"] == 3000000 - 500000 - 1500000 - 200000
    assert _span(t, "rl:publish.encode")["args"] == {"kind": "delta",
                                                      "bytes": 100}


def test_updates_and_kernels_of_whole_modules_only():
    t = program_trace.reduce_events(_recorded()["events"])
    assert t["updates"] == [[2100000, 800000], [6100000, 1000000]]
    assert len(t["kernels"]["relayrl_flash_fwd"]) == 3  # one is left out:
    run = _run(_recorded()["events"])
    assert program_trace.kernel_ms_per_update(
        run, "relayrl_flash_fwd") == pytest.approx(0.11)


@pytest.mark.parametrize("name", sorted(_recorded()["expected"]))
def test_reader(name):
    rec = _recorded()
    reader = harness.load_layer_metric(name)
    assert reader.read(_run(rec["events"])) == pytest.approx(
        rec["expected"][name])
    # a program without the spans (this PR's parent), or an untraced run
    assert reader.read(_run({"threads": [], "modules": {}, "ops": {}})) \
        is None
    assert reader.read(_run(None)) is None


def test_offcpu_share_is_not_clamped():
    """CPU time above the cycle's wall time is a wrong stamp or a wrong
    attribution: it has to show as a negative share, not as 0."""
    events = {"threads": [[
        ["host:window", 0, 10000, {}],
        ["host:dispatch", 1000, 100, {"cycle_cpu_ns": 0}],
        ["host:dispatch", 3000, 100, {"cycle_cpu_ns": 2500}]]]}
    assert program_trace.learner_offcpu_pct(_run(events)) == \
        pytest.approx(-25.0)


class _Event:
    def __init__(self, name, **stats):
        self.name, self.stats = name, list(stats.items())


def test_kernel_is_found_whatever_scope_called_it():
    of = program_trace._kernel_of
    call = ('= (bf16[128,1024,64]{2,1,0}) custom-call(bf16[128,1024,64] '
            '%bitcast.2), custom_call_target="tpu_custom_call"')
    assert of(_Event("%relayrl_flash_bwd.7 " + call)) == "relayrl_flash_bwd"
    assert of(_Event("%block_18.5 " + call, tf_op=(
        "jit(impala_update)/loss/block_18/attn/relayrl_flash_bwd/"
        "pallas_call"))) == "relayrl_flash_bwd"
    assert of(_Event("%encoder.2 " + call, long_name=(
        "jit(ppo_update)/layer_3/relayrl_flash_fwd/pallas_call"),
        flops=12)) == "relayrl_flash_fwd"
    assert of(_Event("%block_18.5 " + call, tf_op="block_18/attn")) is None
    assert of(_Event("%fusion.3 = bf16[8] fusion(...)",
                     tf_op="relayrl_flash_fwd/mul")) is None
