"""The entry points that own a chip, checked from a host that has none.

``chip_smoke.py`` must refuse to run without a TPU (no fallback that lets
a CPU run look like a chip run; ``benchmark/tests/test_refusals.py`` holds
the benchmark to the same), and the compile cache
must be placeable from outside and otherwise sit at one fixed path. All in
subprocesses: the resolver mutates ``jax.config``, and the tests themselves
keep the persistent cache off (conftest.py).
"""

import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def _run(argv, cwd, **env_overrides):
    env = {"PYTHONPATH": str(REPO), "PATH": "/usr/bin:/bin", "HOME": "/tmp",
           "JAX_PLATFORMS": "cpu", **env_overrides}
    return subprocess.run([sys.executable, *argv], capture_output=True,
                          text=True, timeout=120, cwd=cwd, env=env)


def test_chip_smoke_refuses_to_run_without_a_tpu(tmp_path):
    out = _run([str(REPO / "chip_smoke.py")], tmp_path)
    assert out.returncode != 0
    lines = out.stdout.splitlines()
    # it names what it found, and says why it stopped ...
    assert "platform=cpu" in lines[0] and "jax" in lines[0]
    assert any("FAIL no accelerator" in l and "'cpu'" in l for l in lines)
    # ... ran no phase, built nothing, and printed no result
    assert not any(l.startswith("chip_smoke: A:") for l in lines)
    assert "native library" not in out.stdout
    assert not any(l.startswith("{") for l in lines)


_RESOLVE = (
    "import os, sys, jax\n"
    "from relayrl_tpu.utils.compile_cache import resolve_compile_cache\n"
    "for d in sys.argv[1:]:\n"
    "    os.chdir(d)\n"
    "    print(resolve_compile_cache(), "
    "jax.config.jax_compilation_cache_dir)\n")


def test_compile_cache_is_one_fixed_path_inside_the_checkout(tmp_path):
    a, b = tmp_path / "a", tmp_path / "b"
    a.mkdir(), b.mkdir()
    out = _run(["-c", _RESOLVE, str(a), str(b)], tmp_path)
    assert out.returncode == 0, out.stderr[-2000:]
    rows = [l.split() for l in out.stdout.splitlines()]
    # same answer from two cwds, set on jax, under the repo, git-ignored
    assert rows[0] == rows[1] and rows[0][0] == rows[0][1]
    cache = Path(rows[0][0])
    assert cache.parent == REPO and cache.is_absolute()
    assert f"{cache.name}/" in (REPO / ".gitignore").read_text().split()


def test_compile_cache_placed_from_outside_is_left_alone(tmp_path):
    placed = str(tmp_path / "placed")
    out = _run(["-c", _RESOLVE, str(tmp_path)], tmp_path,
               JAX_COMPILATION_CACHE_DIR=placed)
    assert out.returncode == 0, out.stderr[-2000:]
    # the resolver reports it, and jax holds the value it read itself
    assert out.stdout.split() == [placed, placed]


def test_cpu_learner_process_stays_off_the_persistent_cache(capsys):
    # In-process is safe here: on a CPU backend the announcement must NOT
    # resolve the cache (tests, and any JAX_PLATFORMS=cpu run, keep it off).
    import jax

    from relayrl_tpu.utils.compile_cache import announce_learner_device

    before = jax.config.jax_compilation_cache_dir
    announce_learner_device("unit")
    assert jax.config.jax_compilation_cache_dir == before
    line = capsys.readouterr().out.strip()
    assert line.startswith("[unit] learner on cpu") and "cache" not in line
