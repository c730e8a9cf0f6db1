"""Multi-host init wrapper + profiling hooks."""

import os

import jax
import jax.numpy as jnp
import pytest

from relayrl_tpu.parallel import initialize_distributed, is_coordinator
from relayrl_tpu.telemetry.spans import span
from relayrl_tpu.utils import timed, trace


@pytest.fixture(autouse=True)
def _reset_topology_cache():
    """initialize_distributed caches its first resolution per process;
    tests need a fresh slate."""
    import relayrl_tpu.parallel.distributed as dist

    dist._info = None
    yield
    dist._info = None


class TestInitializeDistributed:
    def test_single_process_noop(self):
        info = initialize_distributed()
        assert info == {"multi_host": False, "process_id": 0,
                        "num_processes": 1}

    def test_config_without_coordinator_noop(self):
        info = initialize_distributed(
            config={"distributed": {"num_processes": 4}})
        assert info["multi_host"] is False

    def test_env_resolution_requires_both(self, monkeypatch):
        monkeypatch.setenv("RELAYRL_NUM_PROCESSES", "4")
        # no coordinator anywhere -> still a no-op (never calls
        # jax.distributed.initialize, which would hang)
        info = initialize_distributed()
        assert info["multi_host"] is False

    def test_repeat_call_returns_cached_topology(self):
        first = initialize_distributed()
        # Later bare query must agree with the first resolution, not
        # re-resolve from (possibly absent) args/env.
        assert initialize_distributed() == first

    def test_multi_host_without_process_id_raises(self):
        with pytest.raises(ValueError, match="per-host process id"):
            initialize_distributed(
                coordinator_address="127.0.0.1:1", num_processes=2)

    def test_config_process_id_rejected(self):
        with pytest.raises(ValueError, match="same rank"):
            initialize_distributed(
                coordinator_address="127.0.0.1:1",
                config={"distributed": {"num_processes": 2,
                                        "process_id": 0}})

    def test_is_coordinator_single_process(self):
        assert is_coordinator() is True


class TestProfiling:
    def test_trace_writes_artifacts(self, tmp_path):
        log_dir = tmp_path / "prof"
        with trace(str(log_dir)):
            jax.block_until_ready(jnp.ones((64, 64)) @ jnp.ones((64, 64)))
        produced = list(log_dir.rglob("*"))
        assert any(p.is_file() for p in produced), produced

    def test_span_scope_lands_in_the_trace(self, tmp_path):
        ledger = {"scope_s": 0.0}
        with trace(str(tmp_path)):
            with span("rl:test.scope", ledger, "scope_s", n=8):
                jax.block_until_ready(jnp.ones(8) * 2)
        path, = tmp_path.rglob("*.xplane.pb")
        found = [dict(ev.stats)
                 for plane in jax.profiler.ProfileData.from_file(
                     str(path)).planes
                 for line in plane.lines for ev in line.events
                 if ev.name == "rl:test.scope"]
        (stats,) = found
        # the span's thread CPU time, and the wall time it was read over
        assert 0 <= stats.pop("cpu_ns") and stats.pop("cpu_wall_ns") > 0
        assert stats == {"n": 8} and ledger["scope_s"] > 0

    def test_timed(self):
        out, secs = timed(lambda: jnp.sum(jnp.ones((128, 128))))
        assert float(out) == 128 * 128
        assert secs >= 0
