"""Smoke tests: bench scripts emit well-formed JSON lines in --quick mode.

The multi-process / socket bench smokes are ``slow``-marked (tier-1
wall budget, ISSUE 15: clean HEAD overran the 870 s budget and these
ten smokes alone cost ~290 s on the 2-core bench host) — run them via
``pytest -m slow tests/test_benches.py`` or the per-plane markers. The
fast set keeps the cheap harness-contract smokes plus every
committed-artifact invariant test (those only parse files). The
full-scale socket benches and the chip benches stay manual/driver-run.
"""

import json
import subprocess
import sys
from pathlib import Path

import pytest

BENCH_DIR = Path(__file__).resolve().parent.parent / "benches"


def _run_bench(script: str, cwd, *args, timeout: int = 420,
               script_path=None, env_overrides=None, want_stderr=False):
    """Run a bench script in an isolated cwd (config auto-create writes
    there) and return its parsed JSON lines. ``script_path`` overrides
    the default BENCH_DIR/<script> --quick invocation (used for the
    repo-root bench.py, which takes no flags)."""
    argv = ([sys.executable, str(script_path), *args] if script_path
            else [sys.executable, str(BENCH_DIR / script), "--quick", *args])
    env = {"PYTHONPATH": f"{BENCH_DIR.parent}:{BENCH_DIR}",
           "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
           "HOME": "/tmp", **(env_overrides or {})}
    out = subprocess.run(argv, capture_output=True, text=True,
                         timeout=timeout, cwd=cwd, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(l) for l in out.stdout.splitlines()
             if l.startswith("{")]
    assert lines, out.stdout[-500:]
    return (lines, out.stderr) if want_stderr else lines


def test_bench_codec_quick_emits_json(tmp_path):
    lines = _run_bench("bench_codec.py", tmp_path, timeout=240)
    assert len(lines) >= 7 * 3 + 2  # dtypes x sizes + trajectory rows
    for rec in lines:
        assert set(rec) == {"bench", "config", "value", "unit"}
        assert rec["value"] > 0


@pytest.mark.slow
def test_bench_learner_quick_emits_json(tmp_path):
    lines = _run_bench("bench_learner.py", tmp_path)
    algos = {r["config"]["algorithm"] for r in lines}
    assert {"REINFORCE", "IMPALA", "DQN", "SAC"} <= algos
    assert all(r["value"] > 0 for r in lines)


@pytest.mark.slow
def test_bench_inference_quick_emits_json(tmp_path):
    lines = _run_bench("bench_inference.py", tmp_path)
    assert any(r["bench"] == "agent_inference" for r in lines)
    assert any(r["bench"] == "seq_serving_per_step" for r in lines)


def test_headline_bench_needs_a_tpu(tmp_path):
    """bench.py measures the chip or nothing: with no TPU it exits
    non-zero before timing anything and prints no JSON metric line — a
    CPU number must never appear under a device metric's name."""
    out = subprocess.run(
        [sys.executable, str(BENCH_DIR.parent / "bench.py")],
        capture_output=True, text=True, timeout=120, cwd=tmp_path,
        env={"PYTHONPATH": str(BENCH_DIR.parent), "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu", "HOME": "/tmp"})
    assert out.returncode != 0
    assert not [l for l in out.stdout.splitlines() if l.startswith("{")]
    assert "no TPU" in out.stderr and "cpu" in out.stderr


def test_headline_bench_peak_table_is_exact():
    """MFU divides by a peak keyed by the device_kind string the chip
    reports; a device that is not in the table is an error, never a
    substring guess or a silently dropped ``mfu``."""
    sys.path.insert(0, str(BENCH_DIR.parent))
    try:
        import bench
    finally:
        sys.path.remove(str(BENCH_DIR.parent))
    assert bench._chip_peak_flops("TPU v5 lite") == 197e12
    for unknown in ("TPU v5", "tpu v5 lite", "TPU v6 lite", "cpu"):
        with pytest.raises(SystemExit, match="no peak FLOP/s on record"):
            bench._chip_peak_flops(unknown)


@pytest.mark.slow
def test_bench_soak_quick_slos(tmp_path):
    # The full fleet loop in --quick shape: SLOs (0 dropped, all agents
    # complete, drained blast) are asserted inside the script itself.
    lines = _run_bench("bench_soak.py", tmp_path, timeout=600)
    soak = next(r for r in lines if r["bench"].startswith("soak_multi"))
    assert soak["server_stats"]["dropped"] == 0
    blast = next(r for r in lines if r["bench"] == "ingest_blast_zmq")
    assert blast["drained"]
    # Every soak row embeds the server-plane telemetry snapshot in the
    # production /snapshot schema (ISSUE 4): bench artifacts and live
    # scrapes are read by the same tooling.
    for row in (soak, blast):
        snap = row["telemetry"]
        assert snap["schema"] == "relayrl-telemetry-v1" and snap["enabled"]
        names = {m["name"] for m in snap["metrics"]}
        assert "relayrl_server_trajectories_total" in names
    traj = next(m for m in soak["telemetry"]["metrics"]
                if m["name"] == "relayrl_server_trajectories_total")
    assert traj["value"] == soak["server_stats"]["trajectories"]
    # Distributed-tracing block (ISSUE 14): every soak row embeds the
    # pooled data-age / model-age attribution; the soak runs at sample
    # rate 1.0, so data age must carry real samples, and the schema is
    # stable even for empty distributions.
    ages = soak["age_attribution"]
    for key in ("data_age_s", "model_age_s", "data_age_versions"):
        assert "count" in ages[key], ages
    assert ages["trace_sampled"] > 0
    assert ages["data_age_s"]["count"] > 0
    assert {"mean", "p50", "p95"} <= set(ages["data_age_s"])


@pytest.mark.slow
def test_bench_soak_chaos_quick_smoke(tmp_path):
    """Fast --chaos soak smoke (ISSUE 6): the learner SIGKILL/resume
    drill under the standard fault plan must hold its SLOs (asserted
    in-script: zero-loss accounting, full spool flush, MTTR measured,
    faults actually injected) and emit a well-formed chaos row carrying
    the injection ledger + recovery counters."""
    lines = _run_bench("bench_soak.py", tmp_path, "--chaos", timeout=600)
    row = next(r for r in lines if r["bench"].startswith("chaos_soak"))
    assert row["accounting"]["zero_loss"] is True
    assert row["accounting"]["zero_double_train"] is True
    assert row["agents_crashed"] == 0
    assert row["mttr_s"] is not None and row["mttr_s"] >= 0
    assert row["config"]["fault_plan"]["rules"], "no fault plan committed"
    injected = sum(v for k, v in row["worker_fault_counters"].items()
                   if k.startswith("relayrl_faults_injected_total"))
    assert injected > 0, "chaos row ran fault-free"
    # every agent's ledger line must reconcile against its sent count
    for ident, n in row["accounting"]["sent_totals"].items():
        ledger = row["accounting"]["agents"][ident]
        assert ledger["max_seq"] == n and ledger["contiguous"], ledger


@pytest.mark.guardrails
@pytest.mark.slow
def test_bench_soak_guardrail_drill_quick_smoke(tmp_path):
    """Fast --poison guardrail drill smoke (ISSUE 8): a NaN-poison
    stream against a live fleet must quarantine the offending agent,
    trip the watchdog, auto-roll the learner back to a healthy
    checkpoint (never halt), and end with finite params — with the full
    guardrail evidence block in the emitted row. The committed full-
    length row additionally proves reward-target convergence; the smoke
    runs target-free to stay fast."""
    lines = _run_bench("bench_soak.py", tmp_path, "--poison", timeout=600)
    row = next(r for r in lines if r["bench"].startswith("guardrail_drill"))
    # asserted in-script too (_finish_guardrail_drill); re-asserted here
    # so a schema drift can't silently weaken the smoke
    assert row["quarantine"]["quarantines_total"] >= 1
    assert row["rollbacks_total"] >= 1
    assert row["halted"] is False
    assert row["final_params_finite"] is True
    assert row["strikes"] >= row["config"]["guardrails"]["strike_threshold"]
    assert row["poison_episodes_sent"] >= 1
    injected = sum(v for k, v in row["poison_worker_counters"].items()
                   if k.startswith("relayrl_faults_injected_total"))
    assert injected >= 1, "the poison plan never fired"
    # the restored line kept publishing (forced-keyframe resync path;
    # per-actor resync version is asserted in-script when the rollback
    # lands inside the clean window)
    assert row["final_version"] > (
        row["timeline_s"]["version_at_recovery"] or 0)
    snap = row["telemetry"]
    assert snap["schema"] == "relayrl-telemetry-v1" and snap["enabled"]


@pytest.mark.anakin
@pytest.mark.slow
def test_bench_soak_anakin_quick_smoke(tmp_path):
    """Fast bench_soak --anakin smoke (ISSUE 7): a tiny fused-rollout
    fleet (one process, on-device CartPole lanes) must land >= 1 REAL
    trajectory per logical agent with per-lane attribution, zero drops,
    and a row carrying the engine-plane timing block + the server
    /snapshot schema."""
    import os

    sys.path.insert(0, str(BENCH_DIR))
    monkey_cwd = os.getcwd()
    try:
        import bench_soak

        os.chdir(tmp_path)
        result = bench_soak.run_soak(
            n_actors=4, agents_per_proc=4, duration_s=3.0,
            traj_per_epoch=8, anakin=True, unroll_length=16)
    finally:
        os.chdir(monkey_cwd)
        sys.path.pop(0)
    assert result["config"]["mode"] == "anakin"
    assert result["config"]["obs_dim"] == 4  # sized to the REAL env
    assert result["agents_completed"] == 4
    assert result["agents_crashed"] == 0
    assert result["server_stats"]["dropped"] == 0
    assert result["min_episodes_per_agent"] >= 1
    assert result["distinct_traj_agents"] == 4  # per-lane attribution
    engine = result["anakin_engine"]
    assert engine["windows"] >= 1
    assert engine["dispatch_s_total"] > 0
    snap = result["telemetry"]
    assert snap["schema"] == "relayrl-telemetry-v1"
    names = {m["name"] for m in snap["metrics"]}
    assert "relayrl_server_trajectories_total" in names


@pytest.mark.serving
@pytest.mark.slow
def test_bench_soak_serving_quick_smoke(tmp_path):
    """Fast --serving soak smoke (ISSUE 10): a tiny thin-client fleet
    against the server-colocated InferenceService must complete >= 1
    action round-trip per client (steps > 0 per row), land >= 1
    trajectory per client through the UNCHANGED ingest plane, show
    batching actually engaged (measured occupancy > 1), zero drops, and
    carry the serving SLO block (latency percentiles + close-reason
    split) in the row."""
    import os

    sys.path.insert(0, str(BENCH_DIR))
    monkey_cwd = os.getcwd()
    try:
        import bench_soak

        os.chdir(tmp_path)
        result = bench_soak.run_soak(
            n_actors=4, agents_per_proc=4, duration_s=4.0,
            traj_per_epoch=8, serving=True, max_batch=4,
            batch_timeout_ms=5.0)
    finally:
        os.chdir(monkey_cwd)
        sys.path.pop(0)
    assert result["config"]["mode"] == "serving"
    assert result["agents_completed"] == 4
    assert result["agents_crashed"] == 0
    assert result["server_stats"]["dropped"] == 0
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
    assert serving["action_latency_ms"]["p50"] > 0
    assert serving["action_latency_ms"]["p99"] >= \
        serving["action_latency_ms"]["p50"]
    snap = result["telemetry"]
    assert snap["schema"] == "relayrl-telemetry-v1"
    names = {m["name"] for m in snap["metrics"]}
    assert "relayrl_serving_requests_total" in names


@pytest.mark.serving
@pytest.mark.slow
def test_bench_soak_serving_mux_quick_smoke(tmp_path):
    """Streamed-mux --serving smoke (ISSUE 18): two MultiplexedRemoteClient
    processes x 4 lanes against the colocated InferenceService. Each
    streaming client must demonstrably PIPELINE — >= 2 requests in
    flight on its one DEALER socket at some point (the lock-step
    baseline can never exceed 1) — with zero rejects, zero LRU
    evictions, per-lane trajectory attribution intact, and the
    session/nack split present in the SLO block."""
    import os

    sys.path.insert(0, str(BENCH_DIR))
    monkey_cwd = os.getcwd()
    try:
        import bench_soak

        os.chdir(tmp_path)
        result = bench_soak.run_soak(
            n_actors=8, agents_per_proc=4, duration_s=4.0,
            traj_per_epoch=8, serving=True, serving_mux=True,
            max_batch=4, batch_timeout_ms=5.0)
    finally:
        os.chdir(monkey_cwd)
        sys.path.pop(0)
    assert result["config"]["mode"] == "serving"
    assert result["config"]["streamed_mux"] is True
    assert result["agents_completed"] == 8
    assert result["agents_crashed"] == 0
    assert result["server_stats"]["dropped"] == 0
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

    from _util import free_port
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
    addrs = {
        "agent_listener_addr": f"tcp://127.0.0.1:{free_port()}",
        "trajectory_addr": f"tcp://127.0.0.1:{free_port()}",
        "model_pub_addr": f"tcp://127.0.0.1:{free_port()}",
    }
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
                "agent_listener_addr": addrs["agent_listener_addr"],
                "trajectory_addr": addrs["trajectory_addr"],
                "model_sub_addr": addrs["model_pub_addr"],
            }
            procs.append(subprocess.Popen(
                [sys.executable, str(BENCH_DIR / "_serving_replica.py"),
                 json.dumps(rcfg)],
                env={**os.environ, "JAX_PLATFORMS": "cpu"},
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
            agent_listener_addr=addrs["agent_listener_addr"],
            trajectory_addr=addrs["trajectory_addr"],
            model_sub_addr=addrs["model_pub_addr"])
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
def test_bench_soak_relay_quick_smoke(tmp_path):
    """Fast relay-tree soak smoke (ISSUE 11): 2 relays fronting 2 anakin
    hosts x 4 lanes. The root's broadcast plane must serve RELAYS
    streams (subscriber gauge == 2, not 8), every logical agent must
    land >= 1 trajectory through its relay with zero drops, and each
    relay's embedded telemetry snapshot must carry nonzero relay
    counters on both planes."""
    import os

    sys.path.insert(0, str(BENCH_DIR))
    monkey_cwd = os.getcwd()
    try:
        import bench_soak

        os.chdir(tmp_path)
        result = bench_soak.run_soak(
            n_actors=8, agents_per_proc=4, duration_s=4.0,
            traj_per_epoch=8, anakin=True, unroll_length=16, relays=2)
    finally:
        os.chdir(monkey_cwd)
        sys.path.pop(0)
    assert result["bench"].endswith("_relay")
    assert result["agents_completed"] == 8
    assert result["agents_crashed"] == 0
    assert result["server_stats"]["dropped"] == 0
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


@pytest.mark.relay
def test_committed_relay_scaling_curve_invariants():
    """The committed relay curve (ISSUE 11 acceptance artifact): every
    scaling row's root stream count equals its relay count while actors
    grow to 1k+, bytes-per-publish at the root stays flat at fixed
    relay count, zero drops/crashes everywhere, and the relay-SIGKILL
    chaos row reports zero loss, zero double-train, and an MTTR."""
    path = BENCH_DIR / "results" / "soak_scaling_zmq_relay.json"
    rows = [json.loads(line) for line in path.read_text().splitlines()
            if line.strip()]
    scaling = [r for r in rows if r["bench"].startswith("soak_multi")]
    chaos = [r for r in rows if r["bench"] == "relay_chaos_zmq"]
    assert scaling and chaos
    assert max(r["config"]["actors"] for r in scaling) >= 1024
    by_relays: dict[int, list] = {}
    for r in scaling:
        assert r["server_stats"]["dropped"] == 0, r["bench"]
        assert r["agents_crashed"] == 0
        assert r["agents_completed"] == r["config"]["actors"]
        assert r["distinct_traj_agents"] == r["config"]["actors"]
        topo = r["relay_topology"]
        assert topo["root_subscribers"] == topo["relays"]
        assert topo["root_bytes_per_publish"] and topo["root_publishes"]
        by_relays.setdefault(topo["relays"], []).append(r)
    # flatness: at a FIXED relay count, root bytes/publish must not grow
    # with the actor count (allow measurement noise).
    for rows_at in by_relays.values():
        if len(rows_at) < 2:
            continue
        rows_at.sort(key=lambda r: r["config"]["actors"])
        lo = rows_at[0]["relay_topology"]["root_bytes_per_publish"]
        hi = rows_at[-1]["relay_topology"]["root_bytes_per_publish"]
        assert hi <= 1.25 * lo, (lo, hi)
    drill = chaos[0]
    assert drill["accounting"]["zero_loss"] is True
    assert drill["accounting"]["zero_double_train"] is True
    assert drill["agents_crashed"] == 0
    assert drill["mttr_s"] is not None and drill["mttr_s"] >= 0


@pytest.mark.anakin
@pytest.mark.slow
def test_bench_anakin_quick_emits_json(tmp_path):
    """bench_anakin --quick: baseline + fused rate lines for every grid
    point, and a headline carrying the equal-lane-count speedup map plus
    the best fused row's dispatch/unstack split (the full per-row detail
    goes to the results file under --write)."""
    lines = _run_bench("bench_anakin.py", tmp_path, timeout=420)
    base = [r for r in lines if r.get("bench") == "anakin_vector_baseline"]
    fused = [r for r in lines if r.get("bench") == "anakin_fused_rollout"]
    assert base and fused
    # both wire forms measured per grid cell (ISSUE 9)
    assert {r["config"]["wire"] for r in fused} == {"columnar", "records"}
    headline = next(r for r in lines if r.get("bench") == "anakin_headline")
    for lanes, speedup in headline["speedup_rollout_at_equal_lanes"].items():
        assert speedup > 1.0, (lanes, speedup)
    assert headline["best_rollout"]["rollout_steps_per_sec"] > 0
    assert headline["best_e2e_columnar"] > 0
    assert headline["speedup_columnar_e2e_vs_records"], \
        "columnar-vs-records e2e map missing"


@pytest.mark.telemetry
def test_bench_telemetry_quick_asserts_hotpath_cost(tmp_path):
    # The microbench carries its own ceiling asserts (disabled-path inc
    # must stay an attribute call, enabled inc lock-free); this smoke
    # keeps it runnable and its JSON well-formed.
    lines = _run_bench("bench_telemetry.py", tmp_path, timeout=240)
    ops = {r["config"]["op"]: r for r in lines
           if r["bench"] == "telemetry_hotpath"}
    assert {"counter_inc_disabled", "counter_inc_enabled",
            "histogram_observe_enabled"} <= set(ops)
    assert all(r["ns_per_op"] > 0 for r in ops.values())
    assert any(r["bench"] == "telemetry_snapshot" for r in lines)


@pytest.mark.slow
def test_bench_model_wire_quick_smoke(tmp_path):
    """Model-wire v2 bench (--quick): bytes rows with sane ratios, the
    RLHF-style fine-tune scenario beating full-train, and latency rows
    for both wire versions on the live zmq pair."""
    lines = _run_bench("bench_model_wire.py", tmp_path, timeout=420)
    bytes_rows = [r for r in lines if r["bench"] == "model_wire_bytes"]
    assert bytes_rows, "no bytes rows emitted"
    for r in bytes_rows:
        assert r["delta_reduction_x"] >= 1.0
        assert r["keyframe_bytes"] > 0
        assert r["v1_bytes_per_publish"] > r["delta_bytes_mean"] or \
            r["delta_reduction_x"] >= 0.99
        assert r["encode_ms_mean"] > 0 and r["decode_apply_ms_mean"] > 0
    finetune = [r for r in bytes_rows
                if r["config"]["scenario"].startswith("rlhf_finetune")]
    full = [r for r in bytes_rows
            if "train" in r["config"]["scenario"]
            and not r["config"]["scenario"].startswith("rlhf")]
    assert finetune and full
    # The per-leaf skip must show up: frozen-trunk deltas beat the best
    # full-train row.
    assert (max(r["delta_reduction_x"] for r in finetune)
            > min(r["delta_reduction_x"] for r in full))
    lat = {r["config"]["wire_version"]: r for r in lines
           if r["bench"] == "model_wire_latency"
           and r["config"].get("wire_policy") == "auto"}
    assert {1, 2} <= set(lat)
    assert lat[2]["publish_to_swap_ms_p50"] > 0
    # v2 rows carry the wire counters in the /snapshot schema (the
    # soak-row convention).
    snap = lat[2]["telemetry"]
    assert snap["schema"] == "relayrl-telemetry-v1"
    names = {m["name"] for m in snap["metrics"]}
    assert "relayrl_wire_publish_bytes_total" in names


@pytest.mark.rlhf
@pytest.mark.slow
def test_bench_rlhf_quick_smoke(tmp_path):
    """RLHF e2e scenario bench (--quick): schema + the reward-improved
    assert (the satellite contract), the per-stage split, the train-lag
    distribution, zero-loss accounting, and in-scenario frozen-leaf
    wire savings."""
    lines = _run_bench("bench_rlhf.py", tmp_path, timeout=560)
    rows = [r for r in lines if r["bench"] == "rlhf_e2e"]
    assert rows, "no rlhf_e2e row emitted"
    row = rows[0]
    assert row["config"]["scorer"] == "reward_model"
    # reward improved: the run ends above where it started, against the
    # stated threshold's baseline anchors.
    assert row["reward_final_mean"] > row["reward_baseline_mean"]
    assert row["threshold_met"] is True
    # the four-way stage split is present and non-trivial
    stages = row["stage_seconds"]
    for key in ("generate", "score", "emit", "update_dispatch", "publish"):
        assert key in stages and stages[key]["count"] > 0, key
    # behavior-vs-learner lag distribution observed at train time
    lag = row["version_lag"]["train"]
    assert lag["observations"] > 0 and lag["mean"] >= 0
    # dataflow correctness + the frozen-leaf wire claim
    assert row["zero_loss_accounting"] is True
    assert row["wire"]["frozen_leaves"] > 0
    assert row["wire"]["publish_bytes_saved_total"] > 0
    assert row["updates"] > 0 and row["tokens_generated"] > 0


@pytest.mark.rlhf
def test_committed_rlhf_e2e_invariants():
    """The committed benches/results/rlhf_e2e.json artifact keeps the
    acceptance claims: threshold met on the reward-model row, per-stage
    split + lag distribution present, frozen-leaf savings per row."""
    sys.path.insert(0, str(BENCH_DIR))
    try:
        from common import load_results
    finally:
        sys.path.pop(0)
    rows = [r for r in load_results(BENCH_DIR / "results" / "rlhf_e2e.json")
            if r.get("bench") == "rlhf_e2e"]
    assert rows, "committed artifact has no rlhf_e2e rows"
    rm_rows = [r for r in rows if r["config"]["scorer"] == "reward_model"]
    assert rm_rows
    assert any(r["threshold_met"] for r in rm_rows)
    for r in rows:
        assert r["reward_final_mean"] > r["reward_baseline_mean"]
        assert {"generate", "score", "update_dispatch",
                "publish"} <= set(r["stage_seconds"])
        assert r["version_lag"]["train"]["observations"] > 0
        assert r["zero_loss_accounting"] is True
        if r["config"]["freeze"]:
            assert r["wire"]["publish_bytes_saved_total"] > 0
        assert r["telemetry"]["schema"] == "relayrl-telemetry-v1"


def test_committed_results_all_parse_with_shared_loader():
    """Satellite (ISSUE 5): every committed benches/results/*.json file
    parses through common.load_results — the one reader for both the
    NDJSON and single-document shapes (a plain json.load fails on the
    NDJSON ones; see benches/README.md "results format")."""
    sys.path.insert(0, str(BENCH_DIR))
    try:
        from common import load_results
    finally:
        sys.path.pop(0)
    results = sorted((BENCH_DIR / "results").glob("*.json"))
    assert results, "no committed results found"
    for path in results:
        rows = load_results(path)
        assert isinstance(rows, list) and rows, path.name
        assert all(isinstance(r, (dict, list)) for r in rows), path.name


@pytest.mark.slow
@pytest.mark.fleet
def test_bench_fleet_quick_smoke(tmp_path):
    """Fleet aggregation drill in --quick shape (ISSUE 15): 2 relays x
    1 vector worker x 4 lanes over live zmq — /fleet lists every proc
    with its tier, merged actor counters match the per-process
    registries bit-exactly, and the induced-drop alert fires + resolves
    (all asserted inside the script)."""
    lines = _run_bench("bench_fleet.py", tmp_path, timeout=600)
    assert any(r.get("ok") for r in lines if "ok" in r)
    row = next(r for r in lines if r.get("bench") == "fleet_zmq")
    assert row["value"] > 0  # fleet frames arrived at the root


def test_committed_fleet_drill_invariants():
    """The committed fleet drill (ISSUE 15 acceptance artifact): 64+
    logical actors behind >= 2 relays, every proc tabled with its tier,
    bit-exact merged counter check green, the induced alert fired AND
    resolved with journal events, and the root's fleet-frame rate flat
    as actors doubled at fixed relay count (O(relays) ingest)."""
    path = BENCH_DIR / "results" / "fleet_zmq.json"
    doc = json.loads(path.read_text())
    rows = [r for r in doc["rows"] if r.get("bench") == "fleet_zmq"]
    assert rows
    big = max(rows, key=lambda r: r["config"]["logical_actors"])
    assert big["config"]["logical_actors"] >= 64
    assert big["config"]["relays"] >= 2
    tiers = {p["tier"] for p in big["procs"]}
    assert {"server", "relay", "actor"} <= tiers
    n_actor_procs = sum(1 for p in big["procs"] if p["tier"] == "actor")
    assert n_actor_procs == (big["config"]["relays"]
                             * big["config"]["workers_per_relay"])
    for r in rows:
        check = r["counter_check"]
        assert check["exact"] and not check["mismatches"]
        assert check["families_checked"] >= 2
        assert r["env_steps_merged"] and r["env_steps_merged"] > 0
        assert "ingest_drops" in r["alerts_armed"]
    drill = next(r["alert_drill"] for r in rows if r.get("alert_drill"))
    assert drill["fired"]["event"] == "alert_fired"
    assert drill["fired"]["rule"] == "ingest_drops"
    assert drill["resolved"]["event"] == "alert_resolved"
    assert drill["active_gauge_seen"] is True
    o_relays = next(r for r in doc["rows"]
                    if r.get("bench") == "fleet_zmq_o_relays")
    assert 0.5 <= o_relays["ratio"] <= 1.5
