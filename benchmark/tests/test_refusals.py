"""What the harness must refuse before it prints a result (run by hand,
outside tier-1): an unknown ``device_kind``, a missing TPU, an unknown cell
and a cell whose files are missing."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import harness

REPO = harness.REPO
RUN = [sys.executable, os.path.join(REPO, "benchmark", "run.py")]


def test_unknown_device_kind_is_an_error_not_a_default():
    with pytest.raises(SystemExit) as e:
        harness.load_peaks("TPU v9 imaginary")
    assert e.value.code == 2
    assert harness.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def _run(*args, env=None):
    return subprocess.run(RUN + list(args), cwd=REPO, capture_output=True,
                          text=True, timeout=300,
                          env={**os.environ, "JAX_PLATFORMS": "cpu",
                               **(env or {})})


def test_missing_tpu_exits_nonzero_and_prints_no_result():
    out = _run("--workload", "nature-cnn.update", "--seed", "1",
               "--seconds", "1", "--trace", "0")
    assert out.returncode != 0
    assert "no accelerator" in out.stderr
    assert not any(line.startswith("{") for line in out.stdout.splitlines())


def test_unknown_cell_is_refused():
    out = _run("--workload", "no-such.cell", "--seed", "1")
    assert out.returncode == 2 and "unknown workload" in out.stderr


@pytest.mark.parametrize("kind", ["traffic", "config", "driver", "reader",
                                  "reference"])
def test_cell_with_a_missing_file_is_refused(tmp_path, kind):
    """A copy of the benchmark whose BENCHMARK.json names a file that is
    not there: refused with exit code 2 before any set-up."""
    import shutil

    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(REPO, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = bench["workloads"][0]
    if kind == "traffic":
        cell["traffic"] = "no-such-mix"
    elif kind == "config":
        bench["configs"][0]["file"] = "benchmark/configs/no-such.json"
    elif kind == "driver":
        path = root / "benchmark" / "traffic" / (cell["traffic"] + ".json")
        mix = json.loads(path.read_text())
        mix["driver"] = "no_such_driver"
        path.write_text(json.dumps(mix))
    elif kind == "reader":
        bench["per_layer"].append({
            "name": "no_such_metric", "unit": "ms", "better": "lower",
            "source": "program_span", "layer": "entry points",
            "moves": "setup_s"})
    else:
        (root / "benchmark" / "reference"
         / (cell["config"] + ".py")).unlink()
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    out = subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--workload",
         cell["name"], "--seed", "1", "--seconds", "1", "--trace", "1",
         "--rehearsal", os.path.join(REPO, "benchmark", "tests",
                                     "rehearsal.json")],
        cwd=root, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": REPO})
    assert out.returncode == 2, out.stderr[-800:]
    assert "missing" in out.stderr
    assert not any(line.startswith("{") for line in out.stdout.splitlines())
