"""``benchmark/scope_table.py`` against an event list and an ``HloProto``
built by hand (run by hand: ``python -m pytest benchmark/tests -q``; not
tier-1), and the six readers against the program's one list of names."""

import json
import os
import types

import numpy as np
import pytest

from benchmark import harness, scope_table
# the protobuf wire format, written by hand as that file's own cases do
from test_scope_trace import _field, _instruction

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "scope_events.json")
READERS = {"optimizer_ms": "relayrl_optimizer", "vtrace_ms": "relayrl_vtrace",
           "op_proj_ms": "relayrl_op_proj", "ffn_ms": "relayrl_ffn",
           "moe_elementwise_ms": "relayrl_moe_elementwise"}


def _computation(name, comp_id, root_id, *instructions):
    return _field(3, _field(1, name) + b"".join(instructions)
                  + _field(5, comp_id) + _field(6, root_id))


def _hlo_proto() -> bytes:
    """The update the events of ``data/scope_events.json`` ran: a scan, a
    matmul fusion, a fusion whose root lost its path (a gather), one with a
    tuple for a root, one that fuses two parts, an unnamed copy."""
    ffn = "jit(u)/transpose(jvp(Core))/block_0/relayrl_ffn/mlp_up/dot"
    opt = "jit(u)/relayrl_optimizer/mul"
    rows = "jit(u)/jvp(Core)/block_0/moe/relayrl_moe_rows/jit(_take)/gather"
    glue = "jit(u)/jvp(Core)/block_0/relayrl_op_proj/mul"
    module = _field(1, "jit_u") + b"".join((
        _computation("fused_ffn", 1, 11,
                     _instruction("dot.1", "dot", 11, ffn)),
        _computation("fused_gather", 2, 22,
                     _instruction("slice.1", "slice", 21, "gather"),
                     _instruction("gather.1", "gather", 22, "gather")),
        _computation("fused_tuple", 3, 33,
                     _instruction("mul.1", "multiply", 31, glue),
                     _instruction("mul.2", "multiply", 32, glue),
                     _instruction("tuple.1", "tuple", 33)),
        _computation("fused_mixed", 4, 42,
                     _instruction("convert.1", "convert", 41, ffn),
                     _instruction("mul.3", "multiply", 42, opt)),
        _computation("body", 5, 51, _instruction(
            "add.7", "add", 51, "jit(u)/jvp(relayrl_vtrace)/while/body/add")),
        _computation("main", 6, 69,
                     _instruction("fusion.1", "fusion", 61, "", calls=1),
                     _instruction("fusion.2", "fusion", 62, rows, calls=2),
                     _instruction("fusion.3", "fusion", 63, "", calls=3),
                     _instruction("fusion.4", "fusion", 64, "stale", calls=4),
                     _instruction("while.1", "while", 65,
                                  "jit(u)/jvp(relayrl_vtrace)/while"),
                     _instruction("relayrl_flash_fwd.5", "custom-call", 66,
                                  "jit(u)/jvp(Core)/block_0/"
                                  "relayrl_flash_fwd/pallas_call"),
                     _instruction("copy.9", "copy", 67),
                     _instruction("copy.10", "copy", 68),
                     _instruction("convert.20", "convert", 70,
                                  "jit(u)/jvp(Core)/block_0/moe/"
                                  "relayrl_moe_elementwise/convert"),
                     _instruction("tuple.9", "tuple", 69))))
    return _field(1, module)


@pytest.fixture(scope="module")
def events():
    with open(DATA) as f:
        return json.load(f)


def test_instruction_names_root_then_own_then_last_fused():
    names = scope_table.instruction_names(_hlo_proto())
    assert names["fusion.1"][0] == "relayrl_ffn"          # its root's
    assert names["fusion.2"][0] == "relayrl_moe_rows"     # root lost its path
    assert names["fusion.3"][0] == "relayrl_op_proj"      # root is a tuple
    assert names["fusion.4"][0] == "relayrl_optimizer"    # not its stale own
    assert names["fusion.4"][2] == {"relayrl_ffn", "relayrl_optimizer"}
    assert names["fusion.1"][2] == {"relayrl_ffn"}
    assert names["add.7"][:2] == ("relayrl_vtrace", "add")
    assert names["relayrl_flash_fwd.5"][0] == "relayrl_flash_fwd"
    assert names["copy.9"] == (None, "copy", set())


def test_innermost_name_of_a_path():
    assert scope_table.scope_name(
        "jit(u)/transpose(jvp(Core))/moe/relayrl_moe_elementwise/jit(f)/"
        "transpose(jvp(held_experts))/relayrl_moe_gmm_drhs/x"
    ) == "relayrl_moe_gmm_drhs"
    assert scope_table.scope_name("jit(u)/jvp(relayrl_loss)/mul") == (
        "relayrl_loss")
    assert scope_table.scope_name("jit(u)/block_0/mul") is None
    assert scope_table.scope_name("") is None


def test_a_container_counts_for_what_is_left_beside_what_it_contains():
    start = np.array([0.0, 0.0, 10.0, 10.0, 50.0, 100.0, 120.0])
    dur = np.array([100.0, 40.0, 10.0, 5.0, 10.0, 20.0, 0.0])
    # 0: outer while (100 - inner's 40 - the leaf at 50); 1: inner while (40
    # - the 10 of 2); 2: holds 3; 3, 4: leaves; 5: a leaf after; 6: an empty
    # event where that one ends is held by nothing
    want = [50.0, 30.0, 5.0, 5.0, 10.0, 20.0, 0.0]
    assert list(scope_table.self_times(start, dur)) == want
    assert sum(want) == 120.0                # the busy time, nothing twice
    shuffled = np.array([4, 0, 6, 2, 5, 1, 3])
    assert list(scope_table.self_times(start[shuffled], dur[shuffled])) == [
        want[i] for i in shuffled]


def test_only_what_can_contain_is_a_container():
    """A kernel that an asynchronous copy's event falls inside stays
    whole; a loop does not."""
    start = np.array([0.0, 10.0, 100.0, 110.0])
    dur = np.array([50.0, 1.0, 50.0, 1.0])
    may = np.array([False, False, True, False])
    assert list(scope_table.self_times(start, dur, may)) == [
        50.0, 1.0, 49.0, 1.0]


def test_the_scan_is_counted_once_and_only_inside_whole_updates(events):
    per = scope_table.reduce_ops(events["ops"], events["updates"])
    # two whole updates; the third update's operations are not counted
    assert per["add.7"] == pytest.approx(4 * 2.5e-3)    # 4 steps an update
    assert per["while.1"] == pytest.approx(0.012 - 0.010)   # the loop's own
    assert per["fusion.1"] == pytest.approx(0.030)
    assert per["copy.9"] == pytest.approx(0.002)
    assert "copy.10" not in per              # ran outside every update
    assert sum(per.values()) == pytest.approx(events["self_ms"])
    # told that nothing contains anything, every event counts whole
    flat = scope_table.reduce_ops(events["ops"], events["updates"],
                                  whole={op[0] for op in events["ops"]})
    assert flat["while.1"] == pytest.approx(0.012)


def test_the_table(events):
    table = scope_table.table_of(
        scope_table.reduce_ops(events["ops"], events["updates"]),
        scope_table.instruction_names(_hlo_proto()))
    assert table["scopes"] == pytest.approx(events["scopes"])
    assert list(table["scopes"]) == sorted(
        events["scopes"], key=lambda k: -events["scopes"][k])
    assert table["unscoped"] == pytest.approx({"copy/copy": 0.002})
    assert table["mixed"] == pytest.approx({"relayrl_optimizer": 0.008})
    assert table["self_ms"] == pytest.approx(events["self_ms"])
    assert table["scoped_ms"] == pytest.approx(events["self_ms"] - 0.002)
    assert scope_table.table_of({}, {}) is None


def _run(table):
    return types.SimpleNamespace(trace=True, run_dir="/nonexistent", notes={},
                                 _scope_table=table)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_reads_its_scope_from_the_program_s_list(events, metric):
    from relayrl_tpu.ops.scopes import DEVICE_SCOPES

    reader = harness.load_layer_metric(metric)
    assert reader.SCOPE == READERS[metric] and reader.SCOPE in DEVICE_SCOPES
    table = {"scopes": dict(events["scopes"]), "self_ms": events["self_ms"],
             "scoped_ms": sum(events["scopes"].values())}
    assert reader.read(_run(table)) == events["scopes"].get(reader.SCOPE)
    # the parent of the PR that added the scope, a trace without metadata,
    # a run without a trace: nothing, and no error
    del table["scopes"][reader.SCOPE]
    assert reader.read(_run(table)) is None
    assert reader.read(_run(None)) is None
    assert reader.read(types.SimpleNamespace(
        trace=False, run_dir="/nonexistent", notes={})) is None


def test_coverage_reader(events):
    reader = harness.load_layer_metric("update_scoped_pct")
    scoped = sum(events["scopes"].values())
    table = {"scopes": dict(events["scopes"]), "self_ms": events["self_ms"],
             "scoped_ms": scoped}
    assert reader.read(_run(table)) == pytest.approx(
        100.0 * scoped / events["self_ms"])
    assert reader.read(_run(None)) is None


def test_every_new_metric_is_listed_where_it_reads():
    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    cells = [name for name in per_layer["update_device_ms"]["workloads"]]
    transformer = [c for c in cells if not c.startswith("nature-cnn")]
    assert per_layer["optimizer_ms"]["workloads"] == cells
    assert per_layer["vtrace_ms"]["workloads"] == cells
    assert per_layer["update_scoped_pct"]["workloads"] == cells
    assert per_layer["op_proj_ms"]["workloads"] == transformer
    # the cells whose trunk holds a dense FFN: the two PR 37 listed, then
    # what later configurations added (never a nature-cnn cell)
    assert per_layer["ffn_ms"]["workloads"][:2] == [
        "gpt2m-policy.update", "lfm2-policy.update"]
    assert set(per_layer["ffn_ms"]["workloads"]) <= set(transformer)
    assert per_layer["moe_elementwise_ms"]["workloads"] == (
        per_layer["moe_ffn_ms"]["workloads"])
    for name in list(READERS) + ["update_scoped_pct"]:
        assert per_layer[name]["source"] == "device_trace"
        assert per_layer[name]["moves"] == "train_samples_per_s"
