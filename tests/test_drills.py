"""The multi-process fleet drills of ``tests/drills/soak.py``, tiny.

All ``slow``: each spawns worker processes over live zmq sockets (tier-1
wall budget). Run them with ``pytest -m slow tests/test_drills.py
tests/test_soak.py`` or by their plane markers. They assert counts and
accounting — zero drops, per-agent attribution, seq/dedup reconciliation,
telemetry totals equal to server stats, quarantine and rollback fired —
and nothing timed.
"""

import json
import subprocess
import sys
from pathlib import Path

import pytest

DRILLS = Path(__file__).resolve().parent / "drills"


@pytest.fixture
def soak(monkeypatch, tmp_path):
    """The drill module, with cwd a scratch dir (config auto-create and the
    server's model files land in cwd)."""
    monkeypatch.chdir(tmp_path)
    from drills import soak

    return soak


def _assert_snapshot(snap, *names):
    assert snap["schema"] == "relayrl-telemetry-v1" and snap["enabled"]
    have = {m["name"] for m in snap["metrics"]}
    assert set(names) <= have, set(names) - have


@pytest.mark.slow
def test_soak_and_blast_embed_production_telemetry(soak):
    """The process-per-agent fleet loop and the ingest blast: besides the
    soak's own checks, every result embeds the server-plane telemetry
    snapshot in the production /snapshot schema, its trajectory counter
    equals the server's own stat, and the data-age attribution carries
    real samples (the soak traces at rate 1.0)."""
    result = soak.run_soak(n_actors=16, duration_s=8.0)
    soak.check_soak(result)
    blast = soak.run_ingest_blast(n_traj=500)
    assert blast["drained"] and blast["server_stats"]["dropped"] == 0
    for row in (result, blast):
        _assert_snapshot(row["telemetry"],
                         "relayrl_server_trajectories_total")
    traj = next(m for m in result["telemetry"]["metrics"]
                if m["name"] == "relayrl_server_trajectories_total")
    assert traj["value"] == result["server_stats"]["trajectories"]
    ages = result["age_attribution"]
    for key in ("data_age_s", "model_age_s", "data_age_versions"):
        assert "count" in ages[key], ages
    assert ages["trace_sampled"] > 0
    assert ages["data_age_s"]["count"] > 0


@pytest.mark.slow
def test_chaos_learner_sigkill_zero_loss(soak):
    """The learner SIGKILL/resume drill under the standard fault plan:
    zero-loss accounting, full spool flush, the restarted learner ingests
    again, faults actually injected (``check_chaos``), and every agent's
    ledger line reconciles against its sent count."""
    row = soak.run_chaos(n_actors=4, agents_per_proc=4, duration_s=20.0)
    soak.check_chaos(row)
    assert row["accounting"]["zero_double_train"] is True
    assert row["config"]["fault_plan"]["rules"], "no fault plan in force"
    for ident, n in row["accounting"]["sent_totals"].items():
        ledger = row["accounting"]["agents"][ident]
        assert ledger["max_seq"] == n and ledger["contiguous"], ledger


@pytest.mark.guardrails
@pytest.mark.slow
def test_guardrail_drill_quarantine_and_rollback(soak):
    """A NaN-poison stream against a live fleet must quarantine the
    offending agent, trip the watchdog, auto-roll the learner back to a
    healthy checkpoint (never halt), and end with finite params
    (``check_guardrail_drill``)."""
    row = soak.run_guardrail_drill(duration_s=25.0)
    soak.check_guardrail_drill(row)
    assert row["strikes"] >= row["config"]["guardrails"]["strike_threshold"]
    assert row["poison_episodes_sent"] >= 1
    injected = sum(v for k, v in row["poison_worker_counters"].items()
                   if k.startswith("relayrl_faults_injected_total"))
    assert injected >= 1, "the poison plan never fired"
    _assert_snapshot(row["telemetry"])


@pytest.mark.anakin
@pytest.mark.slow
def test_soak_anakin(soak):
    """A tiny fused-rollout fleet (one process, on-device CartPole lanes)
    must land >= 1 REAL trajectory per logical agent with per-lane
    attribution and zero drops."""
    result = soak.run_soak(
        n_actors=4, agents_per_proc=4, duration_s=3.0,
        traj_per_epoch=8, anakin=True, unroll_length=16)
    soak.check_soak(result)
    assert result["config"]["mode"] == "anakin"
    assert result["config"]["obs_dim"] == 4  # sized to the REAL env
    assert result["min_episodes_per_agent"] >= 1
    assert result["distinct_traj_agents"] == 4  # per-lane attribution
    assert result["anakin_engine"]["windows"] >= 1
    _assert_snapshot(result["telemetry"],
                     "relayrl_server_trajectories_total")


@pytest.mark.serving
@pytest.mark.slow
def test_soak_serving(soak):
    """A tiny thin-client fleet against the server-colocated
    InferenceService must complete >= 1 action round-trip per client,
    land >= 1 trajectory per client through the UNCHANGED ingest plane,
    show batching actually engaged (occupancy > 1) and reject nothing."""
    result = soak.run_soak(
        n_actors=4, agents_per_proc=4, duration_s=4.0,
        traj_per_epoch=8, serving=True, max_batch=4,
        batch_timeout_ms=5.0)
    soak.check_soak(result)
    assert result["config"]["mode"] == "serving"
    assert result["env_steps_total"] >= 4      # >= 1 round-trip each...
    assert result["min_episodes_per_agent"] >= 1  # ...in fact episodes
    assert result["distinct_traj_agents"] == 4  # ingest plane unchanged
    serving = result["serving"]
    assert serving["requests_total"] >= result["env_steps_total"]
    assert serving["rejected_total"] == 0
    assert serving["batch_occupancy_mean"] > 1, \
        "dynamic batching never engaged"
    assert (serving["close_reasons"]["size"]
            + serving["close_reasons"]["deadline"]) > 0
    _assert_snapshot(result["telemetry"], "relayrl_serving_requests_total")


@pytest.mark.serving
@pytest.mark.slow
def test_soak_serving_mux(soak):
    """Two MultiplexedRemoteClient processes x 4 lanes against the
    colocated InferenceService. Each streaming client must demonstrably
    PIPELINE — >= 2 requests in flight on its one DEALER socket at some
    point (lock-step can never exceed 1) — with zero rejects, zero LRU
    evictions and per-lane trajectory attribution intact."""
    result = soak.run_soak(
        n_actors=8, agents_per_proc=4, duration_s=4.0,
        traj_per_epoch=8, serving=True, serving_mux=True,
        max_batch=4, batch_timeout_ms=5.0)
    soak.check_soak(result)
    assert result["config"]["streamed_mux"] is True
    assert result["distinct_traj_agents"] == 8  # per-lane sids intact
    sv = result["serving"]
    assert sv["rejected_total"] == 0
    assert sv["batch_occupancy_mean"] > 1, \
        "dynamic batching never engaged"
    mux = sv["mux"]
    assert mux["clients"] == 2  # one streaming client per worker proc
    assert len(mux["inflight_high_water_per_client"]) == 2
    assert all(hw >= 2 for hw in mux["inflight_high_water_per_client"]), \
        f"a streaming client never pipelined: {mux}"
    split = sv["session_nack_split"]
    assert split["evicted_lru"] == 0  # sized table: no working-set churn
    assert {"evicted_ttl", "session_resyncs",
            "session_nacked"} <= set(split)


@pytest.mark.serving
@pytest.mark.slow
def test_serving_replica_sigkill_drill(tmp_path):
    """Multi-replica SIGKILL drill (ISSUE 18): two StandaloneInferenceHost
    replica PROCESSES serve a windowed transformer policy behind the
    session-affine router; SIGKILL the replica that owns lane 0
    mid-episode. The streamed client must re-route the orphaned lanes to
    the survivor and resync their session windows — every post-kill
    round still answers all lanes, with >= 1 recorded resync."""
    import os
    import time

    from _util import free_port, zmq_addr_pair
    from relayrl_tpu import telemetry
    from relayrl_tpu.runtime.inference import MultiplexedRemoteClient
    from relayrl_tpu.runtime.server import TrainingServer

    telemetry.set_registry(telemetry.Registry(run_id="sigkill-drill"))
    scratch = str(tmp_path)
    cfg_path = os.path.join(scratch, "drill_cfg.json")
    with open(cfg_path, "w") as f:
        json.dump({"serving": {"enabled": True, "max_batch": 4,
                               "batch_timeout_ms": 2.0,
                               "request_timeout_s": 1.0}}, f)
    addrs, agent_addrs = zmq_addr_pair()
    # Root trains + publishes only; serving lives in the replicas.
    server = TrainingServer(
        "REINFORCE", obs_dim=6, act_dim=3, env_dir=scratch,
        server_type="zmq",
        hyperparams={"traj_per_epoch": 10_000,
                     "model_kind": "transformer_discrete", "d_model": 16,
                     "n_layers": 1, "n_heads": 2, "max_seq_len": 16,
                     "bucket_lengths": (16,)},
        **addrs)
    procs, serving_addrs, client = [], [], None
    stop_file = os.path.join(scratch, "replica_stop")
    try:
        for r in range(2):
            saddr = f"tcp://127.0.0.1:{free_port()}"
            serving_addrs.append(saddr)
            rcfg = {
                "name": f"drill-replica-{r}", "config_path": cfg_path,
                "server_type": "zmq", "serving_addr": saddr,
                "ready_file": os.path.join(scratch, f"r{r}_ready"),
                "stop_file": stop_file,
                "result_path": os.path.join(scratch, f"r{r}_result.json"),
                "handshake_timeout_s": 180.0,
                **agent_addrs,
            }
            procs.append(subprocess.Popen(
                [sys.executable, str(DRILLS / "_serving_replica.py"),
                 json.dumps(rcfg)],
                env={**os.environ, "JAX_PLATFORMS": "cpu",
                     "PYTHONPATH": str(DRILLS.parent.parent)},
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, cwd=scratch))
        deadline = time.time() + 180
        for r, proc in enumerate(procs):
            ready = os.path.join(scratch, f"r{r}_ready")
            while not os.path.exists(ready):
                if proc.poll() is not None:
                    raise AssertionError(
                        f"replica {r} died during startup:\n"
                        f"{proc.stdout.read()[-2000:]}")
                assert time.time() < deadline, f"replica {r} never ready"
                time.sleep(0.1)
        import numpy as np

        client = MultiplexedRemoteClient(
            config_path=cfg_path, server_type="zmq", lanes=4, seed=17,
            identity="drill-mux", serving_addrs=serving_addrs,
            **agent_addrs)
        assert len(client._clients) == 2  # one stream per replica
        rng = np.random.default_rng(5)

        def run_rounds(n):
            for _ in range(n):
                obs = [o.astype(np.float32)
                       for o in rng.standard_normal((4, 6))]
                recs = client.request_for_actions(
                    obs, rewards=[0.1] * 4)
                assert len(recs) == 4
                assert all(r is not None for r in recs)

        run_rounds(3)
        victim = client._lane_client[0]  # lane 0's home replica
        procs[victim].kill()             # SIGKILL, no goodbye
        procs[victim].wait(timeout=30)
        run_rounds(3)                    # must still answer every lane
        assert client._lane_client[0] == 1 - victim, \
            "lane 0 never re-routed off the dead replica"
        assert client._m_resyncs.total() >= 1, \
            "re-route happened without a session window resync"
    finally:
        with open(stop_file, "w") as f:
            f.write("stop")
        if client is not None:
            client.disable_agent()
        for proc in procs:
            try:
                proc.communicate(timeout=30)
            except Exception:
                proc.kill()
        server.disable_server()


@pytest.mark.relay
@pytest.mark.slow
def test_soak_relay_tree(soak):
    """2 relays fronting 2 anakin hosts x 4 lanes. The root's broadcast
    plane must serve RELAYS streams (subscriber gauge == 2, not 8), every
    logical agent must land >= 1 trajectory through its relay with zero
    drops, and each relay's telemetry snapshot must carry nonzero relay
    counters on both planes."""
    result = soak.run_soak(
        n_actors=8, agents_per_proc=4, duration_s=4.0,
        traj_per_epoch=8, anakin=True, unroll_length=16, relays=2)
    soak.check_soak(result)
    assert result["min_episodes_per_agent"] >= 1
    assert result["distinct_traj_agents"] == 8  # attribution through hops
    topo = result["relay_topology"]
    assert topo["relays"] == 2
    # THE O(relays) proof: the root publisher sees 2 streams for an
    # 8-actor fleet.
    assert topo["root_subscribers"] == 2
    assert len(topo["relays_detail"]) == 2
    for detail in topo["relays_detail"]:
        stats = detail["stats"]
        assert stats["model_frames_forwarded"] > 0
        assert stats["trajectory_frames_forwarded"] > 0
        snap = detail["telemetry"]
        assert snap["schema"] == "relayrl-telemetry-v1"
        fwd = {tuple(sorted((m.get("labels") or {}).items())): m["value"]
               for m in snap["metrics"]
               if m["name"] == "relayrl_relay_frames_forwarded_total"}
        assert fwd[(("plane", "model"),)] > 0
        assert fwd[(("plane", "trajectory"),)] > 0
