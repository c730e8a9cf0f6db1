"""Multi-process fleet drills: one TrainingServer, N real agents spread
over worker processes, and the coordinators that break things on purpose.

Test support, not measurement: every function here returns the evidence a
test in ``tests/test_drills.py`` / ``tests/test_soak.py`` /
``tests/test_vector_actor.py`` asserts on — counts and accounting (zero
drops, every logical agent attributed, seq/dedup reconciliation, telemetry
totals equal to server stats, an alert or rollback fired) — and no rate,
latency or curve. Servers and workers run on the CPU; speed is measured by
``benchmark/`` on the chip and nowhere else.

* :func:`run_soak` — the fleet loop in process / vector / anakin / serving
  (lock-step, streamed-mux, replicas) / relay-tree topologies.
* :func:`run_ingest_blast` — pre-serialized trajectories pushed at the zmq
  ingest socket with the learner off: everything sent is stored, none dropped.
* :func:`run_chaos` — fault plan on both agent planes plus a learner
  SIGKILL/resume: zero loss, zero double-train.
* :func:`run_guardrail_drill` — a NaN-poison stream: quarantine, rollback,
  finite parameters at the end.

``check_soak`` / ``check_chaos`` / ``check_guardrail_drill`` hold the
assertions every caller of the matching drill wants.
"""

from __future__ import annotations

import functools
import json
import os
import subprocess
import sys
import tempfile
import time

from _util import free_port, zmq_addr_pair

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(os.path.dirname(_HERE))


def _child_env() -> dict:
    """Environment of every spawned worker/server/relay: CPU JAX, the repo
    (and nothing else) importable."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _REPO
    return env


def _spawn(script: str, cfg: dict) -> subprocess.Popen:
    """One drill child process (``tests/drills/<script>`` with its JSON
    config as the only argument), output captured."""
    return subprocess.Popen(
        [sys.executable, os.path.join(_HERE, script), json.dumps(cfg)],
        env=_child_env(), stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)


def _fresh_registry(run_id: str, trace_rate: float = 0.0):
    """One fresh telemetry registry per drill, installed in THIS
    (server-hosting) process: the result then embeds a snapshot in the
    production ``/snapshot`` schema, and drills do not accumulate each
    other's counters. ``trace_rate`` > 0 also installs a fresh tracer
    (journal off) so the result can carry the data-age/model-age block."""
    from relayrl_tpu import telemetry

    registry = telemetry.Registry(run_id=run_id)
    telemetry.set_registry(registry)
    if trace_rate > 0:
        from relayrl_tpu.telemetry import trace

        trace.configure(trace_rate, journal=False)
    return registry


def _age_attribution(snapshots: list[dict]) -> dict:
    """Data-age / model-age block: the ``relayrl_trace_*`` histograms
    pooled across process snapshots (data age is observed server-side,
    model age actor-side) through ``telemetry.aggregate.merge_snapshots``,
    the fleet plane's one merge. A histogram with no samples reports
    ``{"count": 0}``, so the schema is the same either way."""
    from relayrl_tpu.telemetry.aggregate import (
        merge_snapshots,
        snapshot_metric,
    )

    merged = merge_snapshots(snap or {} for snap in snapshots)
    out = {"trace_sampled": int(snapshot_metric(
        merged, "relayrl_trace_sampled_total") or 0)}
    by_name = {m["name"]: m for m in merged["metrics"]
               if m.get("kind") == "histogram"}
    for name, key in (("relayrl_trace_data_age_seconds", "data_age_s"),
                      ("relayrl_trace_model_age_seconds", "model_age_s"),
                      ("relayrl_trace_data_age_versions",
                       "data_age_versions")):
        agg = by_name.get(name)
        out[key] = {"count": int(agg["count"]) if agg else 0}
    return out


def _counter_sum(snap: dict, name: str, labels: dict | None = None) -> float:
    """A metric family summed over the children whose labels contain
    ``labels`` (all of them when None) in one /snapshot document."""
    return sum(
        m.get("value") or 0 for m in snap["metrics"]
        if m["name"] == name and all(
            (m.get("labels") or {}).get(k) == v
            for k, v in (labels or {}).items()))


def _leaf_arrival_ids(agent_id: str, payload: bytes) -> list[str]:
    """Clean LEAF agent ids for one ingest arrival — unwrapping relay
    batch containers exactly the way the server's ingest funnel does."""
    from relayrl_tpu.transport.base import (
        BATCH_KIND_ENVELOPES,
        batch_kind,
        split_agent_tags,
        split_batch,
        unpack_trajectory_envelope,
    )

    def clean(tagged: str) -> str:
        # Wire ids carry the seq tag, the actor's report tag and (tracing
        # on) the trace-context tag; attribution strips all three, like
        # the server's ingest funnel.
        return split_agent_tags(tagged)[0]

    if batch_kind(payload) != BATCH_KIND_ENVELOPES:
        return [clean(agent_id)]
    out = []
    for part in split_batch(payload):
        try:
            inner_id, _ = unpack_trajectory_envelope(part)
        except Exception:
            continue
        out.append(clean(inner_id))
    return out


def _read_json(path: str):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None


def _spawn_relay_tree(scratch: str, upstream_worker_addrs: dict,
                      n_relays: int) -> tuple[list, list, str]:
    """Spawn ``n_relays`` relay-node processes (``python -m
    relayrl_tpu.relay``) subscribed to the root at
    ``upstream_worker_addrs`` (zmq agent-side keys), each binding a
    fresh downstream triple. Returns ``(procs, infos, stop_file)`` —
    ``infos[r]["worker_addrs"]`` is what the subtree's workers use, and
    each relay writes stats + telemetry snapshot to
    ``infos[r]["result_path"]`` once the stop file appears."""
    stop_file = os.path.join(scratch, "relay_stop")
    procs, infos = [], []
    for r in range(n_relays):
        name = f"relay{r}"
        down, down_worker = zmq_addr_pair()
        info = {
            "name": name,
            "worker_addrs": down_worker,
            "ready_file": os.path.join(scratch, f"{name}_ready"),
            "result_path": os.path.join(scratch, f"{name}_result.json"),
        }
        cfg = {
            "name": name,
            "upstream_type": "zmq",
            "upstream": {**upstream_worker_addrs, "probe": False},
            "downstream_type": "zmq",
            "downstream": down,
            "spool_dir": os.path.join(scratch, f"{name}_spool"),
            "batch_max": 8,
        }
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "relayrl_tpu.relay",
             "--json", json.dumps(cfg),
             "--ready-file", info["ready_file"],
             "--stop-file", stop_file,
             "--result-path", info["result_path"]],
            env=_child_env(), stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True))
        infos.append(info)
    deadline = time.time() + 90
    while time.time() < deadline:
        if all(os.path.exists(i["ready_file"]) for i in infos):
            break
        for p, i in zip(procs, infos):
            if p.poll() is not None:
                out, _ = p.communicate()
                raise RuntimeError(
                    f"relay {i['name']} died during bring-up "
                    f"(rc={p.returncode}):\n{out[-3000:]}")
        time.sleep(0.1)
    else:
        raise RuntimeError("relay tree never became ready")
    return procs, infos, stop_file


def _stop_relay_tree(procs: list, infos: list, stop_file: str) -> list[dict]:
    """Signal the tree down and collect per-relay result rows."""
    with open(stop_file, "w") as f:
        f.write("stop")
    rows = []
    for p, info in zip(procs, infos):
        try:
            out, _ = p.communicate(timeout=60)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
        row = _read_json(info["result_path"])
        if row is None:
            raise RuntimeError(
                f"relay {info['name']} left no result "
                f"(rc={p.returncode}):\n{(out or '')[-3000:]}")
        rows.append(row)
    return rows


def _worker_cfg(scratch: str, worker_id: int, n_agents: int,
                duration_s: float, **extra) -> dict:
    """The config every ``_soak_worker.py`` process takes; ``extra``
    carries the mode flags and the addresses."""
    return {
        "worker_id": worker_id, "agents_per_proc": n_agents,
        "duration_s": duration_s, "episode_len": 25, "obs_dim": 8,
        "scratch": scratch, "handshake_timeout_s": 180.0,
        # Cross-process start barrier (see _soak_worker): the go wait
        # outlasts the coordinator's 300 s ready-wait in _release.
        "start_barrier": True, "go_timeout_s": 360.0,
        "result_path": os.path.join(scratch, f"worker_{worker_id}.json"),
        **extra,
    }


def _release(scratch: str, n_procs: int) -> None:
    """Write the go file once EVERY worker has its full complement of
    agents constructed and handshaken (or after 300 s), so the workers'
    windows overlap instead of staggering behind each process's jax
    import."""
    deadline = time.time() + 300
    while time.time() < deadline:
        if all(os.path.exists(os.path.join(scratch, f"ready_{w}"))
               for w in range(n_procs)):
            break
        time.sleep(0.1)
    with open(os.path.join(scratch, "go"), "w") as f:
        f.write(str(time.time()))


def _collect(procs: list, cfgs: list[dict], timeout_s: float,
             also_kill: tuple = ()) -> tuple[list[dict], list[dict]]:
    """Wait for the workers; ``(agent rows, worker telemetry snapshots)``.
    The timeout must outlast the worker's own thread-join bound (duration
    + handshake 180 + go wait 360 + 120 slack) or one hung agent thread
    discards every collected row."""
    outs = [p.communicate(timeout=timeout_s)[0] for p in procs]
    agents, snaps = [], []
    for p, cfg, out in zip(procs, cfgs, outs):
        data = _read_json(cfg["result_path"])
        if p.returncode != 0 or data is None:
            for other in also_kill:  # don't leak relays/replicas
                other.kill()
            raise RuntimeError(
                f"drill worker {cfg['worker_id']} failed "
                f"(rc={p.returncode}):\n{out[-3000:]}")
        agents.extend(data["agents"])
        if data.get("telemetry"):
            snaps.append(data["telemetry"])
    return agents, snaps


def run_soak(n_actors: int = 64, agents_per_proc: int = 8,
             duration_s: float = 30.0, traj_per_epoch: int = 64,
             vector: bool = False, anakin: bool = False,
             unroll_length: int = 32, serving: bool = False,
             max_batch: int | None = None, batch_timeout_ms: float = 5.0,
             serving_mux: bool = False, relays: int = 0) -> dict:
    """One REINFORCE TrainingServer over zmq and ``n_actors`` LOGICAL
    agents in ``ceil(n_actors / agents_per_proc)`` worker processes, for
    ``duration_s``; then drain and report what arrived.

    Default: every agent is a real ``Agent`` (own DEALER/PUSH/SUB) in a
    thread. ``vector=True``: each worker is ONE ``VectorAgent`` stepping
    its lanes through one batched policy dispatch. ``anakin=True``: the
    env itself (on-device CartPole) steps inside the fused rollout
    (``actor.host_mode="anakin"``), so the server model is sized to it and
    episode counts are real episode boundaries. ``serving=True``: the
    server hosts the InferenceService and every agent is a thin client —
    lock-step ``RemoteActorClient`` threads, or with ``serving_mux`` one
    streamed ``MultiplexedRemoteClient`` per worker. ``relays`` > 0 stands
    that many relay processes between the root and the workers (worker w
    parks on relay ``w % relays``)."""
    from relayrl_tpu import telemetry
    from relayrl_tpu.runtime.server import TrainingServer
    from relayrl_tpu.telemetry.aggregate import snapshot_metric
    from relayrl_tpu.transport.base import split_agent_tags

    if relays and serving:
        raise ValueError("relay-tree soaks run the actor tiers, not serving")
    obs_dim, act_dim = (4, 2) if anakin else (8, 4)  # on-device CartPole
    _fresh_registry(f"soak-{n_actors}", trace_rate=1.0)
    scratch = tempfile.mkdtemp(prefix="relayrl_soak_")
    addrs, worker_addrs = zmq_addr_pair()
    mode_flags: dict = {"vector": vector, "anakin": anakin,
                        "unroll_length": unroll_length, "trace_rate": 1.0}
    if serving:
        # Thin-client topology: one shared config file carries the
        # serving knobs to both ends; the session table comfortably
        # covers the whole logical fleet (no eviction/resync cycling).
        if max_batch is None:
            max_batch = max(2, min(32, n_actors))
        config_path = os.path.join(scratch, "serving_config.json")
        with open(config_path, "w") as f:
            json.dump({"serving": {
                "enabled": True, "max_batch": int(max_batch),
                "batch_timeout_ms": float(batch_timeout_ms),
                "max_sessions": int(max(4096, 2 * n_actors))}}, f)
        addrs["serving_addr"] = f"tcp://127.0.0.1:{free_port()}"
        addrs["config_path"] = config_path
        mode_flags.update(serving=True, serving_mux=serving_mux,
                          config_path=config_path,
                          serving_addr=addrs["serving_addr"])
    server = TrainingServer(
        "REINFORCE", obs_dim=obs_dim, act_dim=act_dim, env_dir=scratch,
        hyperparams={"traj_per_epoch": traj_per_epoch,
                     "hidden_sizes": [32, 32], "with_vf_baseline": True,
                     "train_vf_iters": 5},
        **addrs)
    warmed = server.wait_warmup(timeout=120)

    # Per-agent trajectory attribution: distinct agent ids the ingest
    # plane actually saw — the proof that N logical agents multiplexed
    # over one socket (or batched behind a relay) still arrive as N
    # attributed streams.
    seen_traj_agents: set[str] = set()
    orig_on_traj = server.transport.on_trajectory

    def counting_on_traj(agent_id, payload):
        seen_traj_agents.update(_leaf_arrival_ids(agent_id, payload))
        orig_on_traj(agent_id, payload)

    server.transport.on_trajectory = counting_on_traj
    if server.transport.on_trajectory_decoded is not None:
        orig_decoded = server.transport.on_trajectory_decoded

        def counting_decoded(batch):
            seen_traj_agents.update(
                split_agent_tags(t.agent_id)[0] for t in batch)
            orig_decoded(batch)

        server.transport.on_trajectory_decoded = counting_decoded

    relay_procs, relay_infos, relay_stop = [], [], None
    if relays:
        relay_procs, relay_infos, relay_stop = _spawn_relay_tree(
            scratch, worker_addrs, relays)

    n_procs = (n_actors + agents_per_proc - 1) // agents_per_proc
    cfgs = [_worker_cfg(
        scratch, w, min(agents_per_proc, n_actors - w * agents_per_proc),
        duration_s, **mode_flags,
        **(relay_infos[w % relays]["worker_addrs"] if relays
           else worker_addrs)) for w in range(n_procs)]
    procs = [_spawn("_soak_worker.py", cfg) for cfg in cfgs]
    _release(scratch, n_procs)
    agents, worker_snaps = _collect(procs, cfgs, duration_s + 720,
                                    also_kill=tuple(relay_procs))
    server.drain(timeout=120)

    mode = ("serving" if serving else "anakin" if anakin
            else "vector" if vector else "process")
    anakin_rows = [a["anakin"] for a in agents if a.get("anakin")]
    snap = telemetry.get_registry().snapshot()
    result = {
        "config": {"actors": n_actors, "mode": mode,
                   "duration_s": duration_s,
                   "traj_per_epoch": traj_per_epoch,
                   "processes": n_procs, "agents_per_proc": agents_per_proc,
                   "obs_dim": obs_dim, "act_dim": act_dim,
                   **({"relays": relays} if relays else {}),
                   **({"max_batch": max_batch, "streamed_mux": serving_mux}
                      if serving else {})},
        "warmed": warmed,
        "agents_completed": len(agents),
        "agents_crashed": sum(1 for a in agents if a.get("crashed")),
        "distinct_traj_agents": len(seen_traj_agents),
        "min_episodes_per_agent": min((a["episodes"] for a in agents),
                                      default=0),
        "env_steps_total": sum(a["steps"] for a in agents),
        "episodes_total": sum(a["episodes"] for a in agents),
        **({"anakin_engine": {
            "windows": sum(r["windows"] for r in anakin_rows),
            "wire": anakin_rows[0]["wire"]}} if anakin_rows else {}),
        "server_stats": dict(server.stats),
        "ingest_backlog_after_drain": server._ingest.qsize(),
        # Server-plane snapshot (this process), in the live /snapshot
        # endpoint's schema; the workers' actor metrics stay with them.
        "telemetry": snap,
        "age_attribution": _age_attribution([snap] + worker_snaps),
    }
    if serving:
        result["serving"] = _serving_block(server, agents, snap)
    if relays:
        # The ROOT's live stream count (read while the tree is still up)
        # must equal the RELAY count: the whole actor fleet rides the
        # relays' fan-out planes.
        relay_rows = _stop_relay_tree(relay_procs, relay_infos, relay_stop)
        result["relay_topology"] = {
            "relays": relays,
            "root_subscribers": snapshot_metric(
                snap, "relayrl_transport_subscribers", {"backend": "zmq"}),
            "relays_detail": [
                {"name": row["relay"], "stats": row["stats"],
                 "telemetry": row["telemetry"]} for row in relay_rows],
        }
    server.disable_server()
    return result


def _serving_block(server, agents: list[dict], snap: dict) -> dict:
    """The serving plane's accounting for a ``serving=True`` soak: the
    service's own table, request / reject / batch-close counters, mean
    batch occupancy, the session nack split (steady state is "every
    eviction nack answered by a successful client resync") and the
    streamed clients' pipeline depth."""
    counter = functools.partial(_counter_sum, snap)

    occs = [m for m in snap["metrics"]
            if m["name"] == "relayrl_serving_batch_occupancy"]
    occ_sum = sum(m.get("sum") or 0 for m in occs)
    occ_n = sum(m.get("count") or 0 for m in occs)
    mux_rows = [a["mux"] for a in agents if a.get("mux")]
    return {
        **server.inference.accounting(),
        "requests_total": counter("relayrl_serving_requests_total"),
        "rejected_total": counter("relayrl_serving_rejected_total"),
        "close_reasons": {
            reason: counter("relayrl_serving_batches_total",
                            {"reason": reason})
            for reason in ("size", "deadline")},
        "batch_occupancy_mean": (round(occ_sum / occ_n, 2)
                                 if occ_n else None),
        "session_nack_split": {
            "evicted_lru": counter(
                "relayrl_serving_session_evictions_total",
                {"reason": "lru"}),
            "evicted_ttl": counter(
                "relayrl_serving_session_evictions_total",
                {"reason": "ttl"}),
            "session_resyncs": counter(
                "relayrl_serving_session_resyncs_total"),
            "session_nacked": counter(
                "relayrl_serving_session_nacked_total"),
        },
        **({"mux": {
            "clients": len(mux_rows),
            "inflight_high_water_per_client": [
                r["inflight_high_water"] for r in mux_rows],
        }} if mux_rows else {}),
    }


def check_soak(result: dict) -> None:
    """What every soak must hold: nothing dropped, the whole fleet came
    back, no agent thread crashed."""
    assert result["server_stats"]["dropped"] == 0, "ingest dropped trajectories"
    assert result["agents_completed"] == result["config"]["actors"], \
        "fleet silently shrank"
    assert result["agents_crashed"] == 0, "agent thread(s) crashed mid-run"


def run_ingest_blast(n_traj: int = 2000, n_pushers: int = 4) -> dict:
    """Pre-serialized 25-step trajectories pushed at the zmq trajectory
    socket as fast as ``n_pushers`` PUSH sockets go (no actor loop, no
    policy apply), learner OFF (``traj_per_epoch`` > ``n_traj``): the
    socket + native decode + store path must take every one of them."""
    import numpy as np
    import zmq

    from relayrl_tpu import telemetry
    from relayrl_tpu.runtime.server import TrainingServer
    from relayrl_tpu.transport.base import pack_trajectory_envelope
    from relayrl_tpu.types.action import ActionRecord
    from relayrl_tpu.types.trajectory import serialize_actions

    episode_len, obs_dim, act_dim = 25, 8, 4
    _fresh_registry(f"blast-{n_traj}")
    scratch = tempfile.mkdtemp(prefix="relayrl_blast_")
    addrs, _ = zmq_addr_pair()
    server = TrainingServer(
        "REINFORCE", obs_dim=obs_dim, act_dim=act_dim, env_dir=scratch,
        hyperparams={"traj_per_epoch": n_traj + 1,
                     "hidden_sizes": [32, 32], "with_vf_baseline": True},
        **addrs)
    server.wait_warmup(timeout=120)
    rng = np.random.default_rng(0)
    payload = serialize_actions([
        ActionRecord(obs=rng.standard_normal(obs_dim).astype(np.float32),
                     act=np.int64(rng.integers(act_dim)), rew=1.0,
                     data={"logp_a": np.float32(-1.0), "v": np.float32(0.5)},
                     done=(i == episode_len - 1))
        for i in range(episode_len)])
    ctx = zmq.Context.instance()
    pushers = []
    for _ in range(n_pushers):
        s = ctx.socket(zmq.PUSH)
        s.connect(addrs["trajectory_addr"])
        pushers.append(s)
    envs = [pack_trajectory_envelope(f"blast-{i}", payload)
            for i in range(n_pushers)]
    time.sleep(0.5)  # let connects settle
    for i in range(n_traj):
        pushers[i % n_pushers].send(envs[i % n_pushers])
    # drain() only covers trajectories already received; wait for arrival
    # first (sends return before bytes clear the io threads).
    deadline = time.time() + 300
    while (server.stats["trajectories"] + server.stats["dropped"] < n_traj
           and time.time() < deadline):
        time.sleep(0.02)
    drained = server.drain(timeout=60)
    stats = dict(server.stats)
    for s in pushers:
        s.close(0)
    server.disable_server()
    return {
        "config": {"n_traj": n_traj, "episode_len": episode_len,
                   "payload_bytes": len(payload), "pushers": n_pushers},
        "drained": drained,
        "server_stats": stats,
        "telemetry": telemetry.get_registry().snapshot(),
    }


def _chaos_fault_plan(seed: int = 7) -> dict:
    """The standard chaos plan: steady packet-level abuse on both
    agent-side planes. The learner SIGKILL is driven by the coordinator
    (run_chaos), not the plan — a plan rule can only kill the process
    hosting the hook site."""
    return {
        "seed": seed,
        "rules": [
            {"site": "agent.send", "op": "drop", "prob": 0.02},
            {"site": "agent.send", "op": "duplicate", "prob": 0.02},
            {"site": "agent.send", "op": "delay", "prob": 0.02,
             "delay_s": 0.02},
            {"site": "agent.model", "op": "drop", "prob": 0.05},
            {"site": "agent.model", "op": "corrupt", "prob": 0.02},
        ],
    }


def _sum_counters(snapshots: list[dict], prefixes: tuple[str, ...]) -> dict:
    """Matching counter (and gauge) rows summed across process snapshots:
    ``name{labels} -> value`` — injected faults and retries live in the
    workers. Pooling is ``telemetry.aggregate.merge_snapshots``."""
    from relayrl_tpu.telemetry.aggregate import merge_snapshots

    agg: dict[str, float] = {}
    for m in merge_snapshots(snapshots)["metrics"]:
        name = m.get("name", "")
        if m.get("kind") not in ("counter", "gauge") \
                or not name.startswith(prefixes):
            continue
        labels = ",".join(f"{k}={v}" for k, v in
                          sorted((m.get("labels") or {}).items()))
        agg[f"{name}{{{labels}}}" if labels else name] = m.get("value") or 0
    return agg


def run_chaos(n_actors: int = 8, agents_per_proc: int = 4,
              duration_s: float = 45.0) -> dict:
    """The fleet trains over zmq under a deterministic fault plan
    (drops/dups/delays/corruption on both agent planes) while the
    coordinator SIGKILLs the learner (``_chaos_server.py``) a third of
    the way in and restarts it with resume three seconds later. After the
    workers' final spool flush, every sequence each actor assigned must
    have been accepted exactly once by the surviving server line of
    history, replay surplus landing in the duplicate counter."""
    scratch = tempfile.mkdtemp(prefix="relayrl_chaos_")
    server_addrs, worker_addrs = zmq_addr_pair()
    plan = _chaos_fault_plan()
    plan_path = os.path.join(scratch, "fault_plan.json")
    with open(plan_path, "w") as f:
        json.dump(plan, f)
    status_path = os.path.join(scratch, "status.json")
    # Zero-loss needs the spool window to cover every trajectory sent
    # since the last COMMITTED checkpoint: orbax saves are async, so at
    # kill time the committed line can lag several versions — size the
    # window to hold the whole run (docs/operations.md: peak traj rate x
    # (checkpoint interval + commit lag + time to recover)).
    worker_config = os.path.join(scratch, "worker_config.json")
    with open(worker_config, "w") as f:
        json.dump({"actor": {"spool_entries": 16384,
                             "spool_bytes": 512 << 20}}, f)

    def spawn_server(resume: bool) -> subprocess.Popen:
        return _spawn("_chaos_server.py", {
            "algorithm": "REINFORCE", "obs_dim": 8, "act_dim": 4,
            "hyperparams": {"traj_per_epoch": 8, "hidden_sizes": [32, 32]},
            "server_type": "zmq", "scratch": scratch,
            "checkpoint_every": 2, "resume": resume, "dedup_window": 4096,
            "status_path": status_path, **server_addrs})

    server = spawn_server(resume=False)
    t_wait = time.time() + 180
    while (status := _read_json(status_path)) is None \
            and time.time() < t_wait:
        if server.poll() is not None:
            out, _ = server.communicate()
            raise RuntimeError(f"chaos server died at start:\n{out[-3000:]}")
        time.sleep(0.2)
    assert status is not None, "chaos server never ready"
    first_pid = status["pid"]

    n_procs = (n_actors + agents_per_proc - 1) // agents_per_proc
    cfgs = [_worker_cfg(
        scratch, w, min(agents_per_proc, n_actors - w * agents_per_proc),
        duration_s, episode_len=10, fault_plan=plan_path,
        chaos_telemetry=True, final_replay=True, config_path=worker_config,
        **worker_addrs) for w in range(n_procs)]
    procs = [_spawn("_soak_worker.py", cfg) for cfg in cfgs]
    _release(scratch, n_procs)

    # The drill: SIGKILL a third of the way into the window, restart
    # with resume after a short outage.
    time.sleep(duration_s / 3.0)
    server.kill()
    server.wait(timeout=30)
    time.sleep(3.0)
    server = spawn_server(resume=True)
    agents, worker_snaps = _collect(procs, cfgs, duration_s + 720,
                                    also_kill=(server,))

    # Expected per-agent sent counts (spool seq spaces) for the
    # accounting reconciliation below.
    sent_counts: dict[str, int] = {}
    for a in agents:
        for ident, n in (a.get("sent_counts") or {}).items():
            sent_counts[ident] = max(sent_counts.get(ident, 0), int(n))

    def accounted(status: dict | None) -> bool:
        if not status or status["pid"] == first_pid:
            return False
        rows = status["accounting"]["agents"]
        return all(
            ident in rows and rows[ident]["max_seq"] == n
            and rows[ident]["contiguous"]
            for ident, n in sent_counts.items())

    acct_deadline = time.time() + 120
    status = _read_json(status_path)
    while time.time() < acct_deadline and not accounted(status):
        if server.poll() is not None:
            out, _ = server.communicate()
            raise RuntimeError(
                f"restarted chaos server died:\n{out[-3000:]}")
        time.sleep(0.5)
        status = _read_json(status_path)
    import signal

    server.send_signal(signal.SIGTERM)
    try:
        server.communicate(timeout=90)
    except subprocess.TimeoutExpired:
        server.kill()

    status = status or {}
    zero_loss = accounted(status)
    return {
        "config": {"actors": n_actors, "agents_per_proc": agents_per_proc,
                   "duration_s": duration_s,
                   "outage_s": 3.0, "fault_plan": plan},
        "agents_completed": len(agents),
        "agents_crashed": sum(1 for a in agents if a.get("crashed")),
        "spool_flushed_all": all(a.get("spool_flushed", True)
                                 for a in agents),
        "env_steps_total": sum(a["steps"] for a in agents),
        # The restarted learner (another pid than the killed one) took
        # trajectories again: the ingest plane recovered.
        "learner_recovered": (status.get("pid") not in (None, first_pid)
                              and status["stats"]["trajectories"] > 0),
        "accounting": {
            "agents": status.get("accounting", {}).get("agents", {}),
            "duplicates_deduped": status.get(
                "accounting", {}).get("duplicates"),
            "sent_totals": sent_counts,
            "zero_loss": zero_loss,
            # zero double-training is BY CONSTRUCTION of the ledger
            # (accepted == max_seq == sent, each seq at most once);
            # surplus deliveries are visible above as duplicates.
            "zero_double_train": zero_loss,
        },
        "server_stats": status.get("stats"),
        "server_version_final": status.get("version"),
        # Validation/quarantine/watchdog accounting from the surviving
        # server line — under the standard plan nothing should trip
        # (corrupt frames die at the CRC, not the validator).
        "guardrails": status.get("guardrails"),
        "telemetry": status.get("telemetry"),
        "worker_fault_counters": _sum_counters(
            worker_snaps,
            ("relayrl_faults_", "relayrl_retry_", "relayrl_spool_",
             "relayrl_breaker_", "relayrl_transport_swallowed",
             "relayrl_transport_reconnects")),
    }


def check_chaos(result: dict) -> None:
    assert result["agents_crashed"] == 0, "agent thread(s) crashed"
    assert result["accounting"]["zero_loss"], (
        "sequence accounting shows loss or double-training")
    assert result["spool_flushed_all"], "a worker's final flush timed out"
    assert result["learner_recovered"], \
        "the restarted learner never ingested again"
    faults_fired = sum(
        v for k, v in result["worker_fault_counters"].items()
        if k.startswith("relayrl_faults_injected_total"))
    assert faults_fired > 0, "the chaos drill injected no faults"
    assert not (result.get("guardrails") or {}).get("halted"), \
        "guardrails halted under the standard (packet-level) plan"


def run_guardrail_drill(n_lanes: int = 4, duration_s: float = 60.0,
                        unroll_length: int = 32) -> dict:
    """A live zmq fleet trains REINFORCE on on-device CartPole while a
    fault-injected actor streams NaN-poisoned trajectories at it. The
    server runs the deliberately torn defense-in-depth posture
    (``ingest_validation: "warn"`` — the validator counts + strikes but
    ADMITS, and the per-algorithm finite belt stands down), so the drill
    exercises the whole chain:

      poison admitted → params go non-finite → device probes trip at the
      fence → auto-rollback to the newest healthy checkpoint (+ ledger
      sidecar, + forced keyframe so actors resync off the poisoned delta
      chain) → meanwhile 3 strikes quarantined the poison agent → the
      restored line trains clean.

    The publish gate holds the other end: any non-finite snapshot racing
    the rollback is BLOCKED, so zero non-finite params ever reach the
    wire."""
    from relayrl_tpu import telemetry
    from relayrl_tpu.runtime.server import TrainingServer

    _fresh_registry("guard-drill")
    scratch = tempfile.mkdtemp(prefix="relayrl_guard_")
    addrs, worker_addrs = zmq_addr_pair()
    guard_cfg = {
        "ingest_validation": "warn",   # the torn first layer (see above)
        "strike_threshold": 3,
        "strike_window_s": 120.0,
        "quarantine_cooldown_s": 600.0,  # no parole inside the window
        "watchdog": True, "probes": True, "update_norm_probe": True,
        "rollback": True, "checkpoint_ring": 5,
        # a poison burst admitted before the 3rd strike can straddle
        # several epochs — each one trips and rolls back; the budget
        # must cover the burst (bounded-retries is still the contract)
        "max_rollbacks": 5, "rollback_window_s": 600.0,
    }
    config_path = os.path.join(scratch, "server_config.json")
    with open(config_path, "w") as f:
        json.dump({
            "learner": {
                "checkpoint_dir": os.path.join(scratch, "checkpoints"),
                "checkpoint_every_epochs": 2,
            },
            "guardrails": guard_cfg,
            "telemetry": {"enabled": True, "port": 0},
        }, f)
    # CartPole-v1 dims (the on-device env the clean lanes run).
    server = TrainingServer(
        "REINFORCE", obs_dim=4, act_dim=2, env_dir=scratch,
        config_path=config_path,
        hyperparams={"traj_per_epoch": 64, "hidden_sizes": [32, 32],
                     "with_vf_baseline": True, "train_vf_iters": 5},
        **addrs)
    server.wait_warmup(timeout=120)

    # Clean fleet: one anakin host, n_lanes logical agents.
    clean_cfg = _worker_cfg(scratch, 0, n_lanes, duration_s, obs_dim=4,
                            anakin=True, unroll_length=unroll_length,
                            **worker_addrs)
    clean_proc = _spawn("_soak_worker.py", clean_cfg)
    _release(scratch, 1)
    t_go = time.time()

    # Hold the poison until the ring holds a rollback target: the first
    # periodic save must exist, or the trip would degrade to halt (the
    # drill would still be "safe", but the bar is RECOVERY).
    ckpt_deadline = time.time() + duration_s * 0.6
    while server._ckpt_saves < 1 and time.time() < ckpt_deadline:
        time.sleep(0.25)
    assert server._ckpt_saves >= 1, "no checkpoint before poison window"

    poison_plan = {"seed": 11, "rules": [
        {"site": "agent.send", "op": "nan_poison", "prob": 1.0}]}
    plan_path = os.path.join(scratch, "poison_plan.json")
    with open(plan_path, "w") as f:
        json.dump(poison_plan, f)
    # the poison stream outlives its quarantine: rejected sends keep
    # hammering the shed path for the rest of the window
    poison_cfg = _worker_cfg(
        scratch, 1, 1, max(10.0, duration_s - (time.time() - t_go)),
        episode_len=16, obs_dim=4, start_barrier=False,
        fault_plan=plan_path, chaos_telemetry=True, **worker_addrs)
    poison_proc = _spawn("_soak_worker.py", poison_cfg)

    # Observe the drill fire: quarantine + rollback, version at recovery.
    trip_info = {"rollback_seen_s": None, "quarantine_seen_s": None,
                 "version_at_recovery": None}
    watch_deadline = t_go + duration_s + 60
    while time.time() < watch_deadline:
        acct = server.guardrails_accounting()
        q = (acct.get("quarantine") or {})
        if (trip_info["quarantine_seen_s"] is None
                and q.get("quarantines_total", 0) >= 1):
            trip_info["quarantine_seen_s"] = round(time.time() - t_go, 1)
        if (trip_info["rollback_seen_s"] is None
                and acct.get("rollbacks_total", 0) >= 1):
            trip_info["rollback_seen_s"] = round(time.time() - t_go, 1)
            trip_info["version_at_recovery"] = int(
                server.latest_model_version)
        if (trip_info["rollback_seen_s"] is not None
                and trip_info["quarantine_seen_s"] is not None):
            break
        if acct.get("halted"):
            break
        time.sleep(0.25)

    clean_agents, _ = _collect([clean_proc], [clean_cfg], duration_s + 720)
    poison_agents, poison_snaps = _collect([poison_proc], [poison_cfg],
                                           duration_s + 720)
    server.drain(timeout=120)
    final_acct = server.guardrails_accounting()
    stats = dict(server.stats)
    snapshot = telemetry.get_registry().snapshot()

    import jax
    import numpy as np

    params_finite = all(
        np.isfinite(np.asarray(leaf)).all()
        for leaf in jax.tree_util.tree_leaves(
            jax.device_get(server.algorithm.state.params))
        if np.asarray(leaf).dtype.kind == "f")
    final_version = int(server.latest_model_version)
    server.disable_server()

    _counter = functools.partial(_counter_sum, snapshot)
    return {
        "config": {"clean_lanes": n_lanes, "poison_agents": 1,
                   "duration_s": duration_s,
                   "unroll_length": unroll_length,
                   "fault_plan": poison_plan, "guardrails": guard_cfg},
        "timeline_s": trip_info,
        "quarantine": final_acct.get("quarantine"),
        "watchdog": final_acct.get("watchdog"),
        "admission": final_acct.get("admission"),
        "rollbacks_total": final_acct.get("rollbacks_total"),
        "halted": final_acct.get("halted"),
        "validation_rejections": _counter("relayrl_guard_rejected_total"),
        "strikes": _counter("relayrl_guard_strikes_total"),
        "quarantine_rejected_sends": _counter(
            "relayrl_guard_quarantine_rejects_total"),
        "publishes_blocked_nonfinite": _counter(
            "relayrl_guard_publish_blocked_total"),
        "wire_keyframes": _counter("relayrl_wire_keyframes_total"),
        "final_params_finite": params_finite,
        "final_version": final_version,
        "clean_agents_final_version": max(
            (a.get("final_version") or 0) for a in clean_agents),
        "poison_episodes_sent": sum(a["episodes"] for a in poison_agents),
        "server_stats": stats,
        "telemetry": snapshot,
        "poison_worker_counters": _sum_counters(
            poison_snaps, ("relayrl_faults_", "relayrl_spool_")),
    }


def check_guardrail_drill(result: dict) -> None:
    q = result["quarantine"] or {}
    assert q.get("quarantines_total", 0) >= 1, \
        "the poison agent was never quarantined"
    assert (result["rollbacks_total"] or 0) >= 1, \
        "the watchdog never rolled the learner back"
    assert not result["halted"], "guardrails degraded to halt"
    assert result["final_params_finite"], "non-finite params survived"
    assert result["strikes"] >= 3, "strike accounting missed the stream"
    # zero non-finite params ever published: every blocked snapshot was
    # stopped AT the gate, and the restored line kept publishing past
    # the recovery version.
    recovery_v = result["timeline_s"]["version_at_recovery"] or 0
    assert result["final_version"] > recovery_v, \
        "the learner never resumed publishing after the rollback"
    # Actor resync evidence needs the clean window to still be OPEN when
    # the rollback lands (a short run's window can close first).
    rb_s = result["timeline_s"]["rollback_seen_s"]
    if rb_s is not None and rb_s < result["config"]["duration_s"] * 0.8:
        assert result["clean_agents_final_version"] >= recovery_v, \
            "actors never resynced onto the restored line"
