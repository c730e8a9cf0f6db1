"""``BENCHMARK.json`` against the limits of the benchmark's contract that a
file can be checked for, and every file it names (run by hand)."""

import json
import os
import re

from benchmark import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _bench():
    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_names_units_and_lengths():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(harness.REPO,
                                        "BENCHMARK.json")) <= 64 * 1024
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith(tuple(p + "/" for p in b["paths"]))
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["moves"] in e2e and m["source"] in SOURCES
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    for text in ([c["why"] for c in b["configs"]]
                 + [c["source"] for c in b["configs"]]
                 + [w["why"] for w in b["workloads"]]
                 + [m["layer"] for m in b["per_layer"]] + b["command"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_every_cell_has_its_files_and_its_metrics():
    b = _bench()
    cells = [w["name"] for w in b["workloads"]]
    used = {w["config"] for w in b["workloads"]}
    assert used == {c["name"] for c in b["configs"]}
    for cell in cells:
        spec = harness.load_cell(cell)          # config + traffic files
        harness.load_driver(spec["traffic"]["driver"])
        harness.load_reference(spec["config_name"])
        mine_e2e = {m["name"] for m in spec["end_to_end"]}
        assert "setup_s" in mine_e2e and len(mine_e2e) >= 2
        assert spec["per_layer"]
        for m in spec["per_layer"]:
            harness.load_layer_metric(m["name"])
            assert m["moves"] in mine_e2e, (cell, m["name"])
        assert set(spec["config"].get("reduced", [])) == set(
            {c["name"]: c for c in b["configs"]}[spec["config_name"]]
            ["reduced"])


def test_layers_are_named_as_perf_md_names_them():
    with open(os.path.join(harness.REPO, "PERF.md")) as f:
        perf = f.read()
    for m in _bench()["per_layer"]:
        assert f"| {m['layer']} |" in perf, m["layer"]
