"""Scaled-down multi-actor soak and ingest blast (tests/drills/soak.py):
nothing dropped, nothing left in the ingest queue."""

import pytest

pytestmark = pytest.mark.slow


@pytest.fixture
def soak(monkeypatch, tmp_path):
    monkeypatch.chdir(tmp_path)
    from drills import soak

    return soak


def test_multi_actor_soak_no_drops(soak):
    result = soak.run_soak(n_actors=8, agents_per_proc=4, duration_s=5.0,
                           traj_per_epoch=8)
    assert result["agents_completed"] == 8
    assert result["server_stats"]["dropped"] == 0
    assert result["ingest_backlog_after_drain"] == 0
    assert result["env_steps_total"] > 0


def test_ingest_blast_no_drops(soak):
    result = soak.run_ingest_blast(n_traj=300)
    assert result["drained"] is True
    assert result["server_stats"]["dropped"] == 0
    assert result["server_stats"]["trajectories"] == 300
