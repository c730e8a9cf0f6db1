"""The trace reduction against a small recorded set of events with answers
worked by hand. Run by hand: ``python -m pytest benchmark/tests -q -p
no:cacheprovider`` (outside tier-1, which collects ``tests/`` only)."""

import json
import os

import pytest

from benchmark import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


def _load():
    with open(os.path.join(HERE, "data", "small_trace.json")) as f:
        return json.load(f)


def test_reduction_matches_hand_worked_answers():
    rec = _load()
    events = rec["events"]
    window = trace_reduce.host_window_ns(events, "host:window")
    assert window == (1000000, 11000000)
    events["host"] = [e for e in events["host"] if e[0] != "host:window"]
    got = trace_reduce.reduce_events(events, window)
    want = rec["expected"]
    assert got["busy_s"] == pytest.approx(want["busy_s"])
    assert got["window_s"] == pytest.approx(want["window_s"])
    for key in ("device_ops", "idle_gaps"):
        assert [n for n, _ in got[key]] == [n for n, _ in want[key]]
        assert [s for _, s in got[key]] == pytest.approx(
            [s for _, s in want[key]])
    # busy + every gap = the window: nothing is counted twice or lost
    assert got["busy_s"] + sum(s for _, s in got["idle_gaps"]) == \
        pytest.approx(got["window_s"])


def test_no_device_operation_gives_nothing():
    assert trace_reduce.reduce_events({"device": {}, "host": []}) is None


def test_op_key_shortens_what_the_trace_prints():
    key = trace_reduce.op_key
    assert key('%fusion.77 = (f32[]{:T(128)}, bf16[8,8,4,32]{3,2,1,0:T(4,128)'
               '(2,1)S(1)}) fusion(bf16[10240,84,84,4]{0,3,2,1} %reshape.60), '
               'kind=kOutput, calls=%fused_computation.117') == \
        "fusion/fusion/kOutput"
    assert key('%reshape.60 = bf16[10240,84,84,4]{0,3,2,1:T(4,128)(2,1)} '
               'reshape(bf16[512,20,28224]{1,0,2} %copy.142)') == \
        "reshape/reshape"
    assert key('%convolution_add_fusion = bf16[10240,20,20,32]{0,3,2,1} '
               'fusion(bf16[1]{0} %reshape.60), kind=kOutput, calls=%f.12') \
        == "convolution_add_fusion/fusion/kOutput"
    assert key('%block_18.5 = (bf16[128,1024,64]{2,1,0}, bf16[128,1024,64]'
               '{2,1,0}) custom-call(bf16[128,1024,64]{2,1,0} %bitcast.2834),'
               ' custom_call_target="tpu_custom_call", operand_layout_'
               'constraints={}') == "block/custom-call/tpu_custom_call"
    assert key("plain-name") == "plain-name"
