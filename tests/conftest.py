"""Test harness: run everything on a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; sharding/collective paths are
validated on XLA's host platform with 8 virtual devices (the standard JAX
technique for testing pjit/shard_map topologies without a pod).
"""

import os

# Must be set before jax (or anything importing jax) is imported. Force —
# the ambient environment may point JAX_PLATFORMS at a real accelerator,
# and the chip belongs to one process at a time: the tests never take it
# (what runs on it is `python chip_smoke.py`, see the verify skill).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# What tier-1 pays for on XLA:CPU is compiling thousands of toy programs, not
# running them: LLVM's optimisation passes were a quarter of the suite's
# CPU-seconds (PR 71: six kernel and trunk files, 693 -> 510 s of user CPU,
# every case passing) and speed up nothing a test waits for. The HLO passes
# (fusion, layout) run as ever, and a test compares two programs built alike.
# Subprocesses (drill servers, spawned actors) inherit the variable.
if "xla_backend_optimization_level" not in flags:
    os.environ["XLA_FLAGS"] += " --xla_backend_optimization_level=0"
os.environ.setdefault("JAX_ENABLE_X64", "0")

# NOTE: do NOT point JAX_COMPILATION_CACHE_DIR at a persistent cache
# here. It looks like a free wall-clock win for the subprocess drills,
# but on an earlier jaxlib the cache intermittently SIGABRTed/segfaulted
# the orbax async checkpoint saves (tests/test_checkpoint.py, reproduced
# twice under ISSUE 17), and XLA:CPU warns on every cached load that the
# AOT result may not match the host. Entry points that own a chip turn
# the cache on themselves (relayrl_tpu/utils/compile_cache.py); on the
# v5e with jaxlib 0.9.0, chip_smoke.py runs orbax saves with it on.

# Plugins (jaxtyping) import jax before this conftest runs, and jax.config
# snapshots JAX_PLATFORMS at import — update the live config too, which works
# as long as no backend has been initialized yet.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

_NATIVE_SO = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native", "librelayrl_native.so")


def _native_library_line() -> str:
    if os.path.exists(_NATIVE_SO):
        return "native/librelayrl_native.so: present"
    return ("native/librelayrl_native.so: ABSENT, the 41 tests of the native "
            "decoder and transport skip (`make -C native` builds it)")


def pytest_report_header(config):
    """`.gitignore` hides the library, and a checkout without it counts 41
    passes fewer with no failure to show for it: say so before the dots."""
    return _native_library_line()


def pytest_terminal_summary(terminalreporter):
    # -q prints no header: an absent library is said once more at the end
    if terminalreporter.verbosity < 0 and not os.path.exists(_NATIVE_SO):
        terminalreporter.write_line(_native_library_line())


@pytest.fixture
def tmp_cwd(tmp_path, monkeypatch):
    """Run a test inside a throwaway cwd (config auto-create writes there)."""
    monkeypatch.chdir(tmp_path)
    return tmp_path
