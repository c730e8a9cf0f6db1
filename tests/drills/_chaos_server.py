"""Killable/restartable TrainingServer worker for crash drills.

Shared by ``tests/drills/soak.py::run_chaos`` and the SIGKILL drills of
tests/test_recovery.py, test_columnar_wire.py, test_anakin.py and
test_rlhf.py: the coordinator spawns this process, SIGKILLs it mid-run (the learner crash
drill), then respawns it with ``"resume": true`` — orbax restores the
full train state and the ingest-ledger sidecar restores dedup state
consistent with the restored params.

Usage: ``_chaos_server.py '<json-config>'`` with keys::

    algorithm, obs_dim, act_dim, hyperparams   — TrainingServer ctor
    server_type + addr overrides               — transport plane
    scratch          — working dir (config/checkpoints/status live here)
    checkpoint_every — learner.checkpoint_every_epochs
    resume           — restore from scratch/checkpoints before serving
    status_path      — JSON status file, atomically rewritten ~3x/s:
                       {pid, t, version, stats, accounting, registered,
                        telemetry} — the coordinator's only window into
                       this process (it is expected to die without
                       warning)
    run_s            — optional auto-exit (belt-and-braces for tests)

SIGTERM triggers the server's own signal path (final checkpoint +
ledger sidecar + clean shutdown); SIGKILL is the drill.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

# tests/drills/ -> repo root, for relayrl_tpu when PYTHONPATH is unset
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def _write_status(path: str, payload: dict) -> None:
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, path)


def main() -> None:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    cfg = json.loads(sys.argv[1])
    scratch = cfg["scratch"]
    os.makedirs(scratch, exist_ok=True)
    # A scratch-local config pins the checkpoint plane + telemetry so the
    # restarted process resumes from exactly what the dead one wrote.
    config_path = os.path.join(scratch, "chaos_server_config.json")
    if not os.path.exists(config_path):
        with open(config_path, "w") as f:
            json.dump({
                "learner": {
                    "checkpoint_dir": os.path.join(scratch, "checkpoints"),
                    "checkpoint_every_epochs": int(
                        cfg.get("checkpoint_every", 2)),
                    # Dedup-window sizing rides the drill config: anakin
                    # columnar fleets deliver one SEQ PER EPISODE SEGMENT
                    # (thousands per lane per drill), so a retracted/
                    # corrupted seq must stay re-acceptable for the whole
                    # run or late replays read as duplicates (the window
                    # analog of the PR 6 spool sizing rule).
                    "ingest_dedup_window": int(
                        cfg.get("dedup_window", 4096)),
                },
                "telemetry": {"enabled": True, "port": 0},
            }, f)

    from relayrl_tpu.runtime.server import TrainingServer

    addr_keys = ("bind_addr", "agent_listener_addr", "trajectory_addr",
                 "model_pub_addr")
    addrs = {k: cfg[k] for k in addr_keys if k in cfg}
    server = TrainingServer(
        cfg.get("algorithm", "REINFORCE"),
        obs_dim=int(cfg.get("obs_dim", 8)),
        act_dim=int(cfg.get("act_dim", 4)),
        env_dir=scratch,
        config_path=config_path,
        hyperparams=cfg.get("hyperparams") or {},
        server_type=cfg.get("server_type", "zmq"),
        resume=bool(cfg.get("resume", False)),
        handle_signals=True,
        **addrs,
    )
    server.wait_warmup(timeout=180)

    status_path = cfg["status_path"]
    stop = threading.Event()

    def status_loop() -> None:
        from relayrl_tpu import telemetry

        while not stop.is_set():
            try:
                _write_status(status_path, {
                    "pid": os.getpid(),
                    "t": time.time(),
                    "version": int(server.latest_model_version),
                    "stats": dict(server.stats),
                    "accounting": server.ingest_accounting(),
                    "guardrails": server.guardrails_accounting(),
                    "registered": len(server.agent_ids),
                    "telemetry": telemetry.get_registry().snapshot(),
                })
            except Exception as e:  # a status hiccup must not kill serving
                print(f"[chaos-server] status write failed: {e!r}",
                      flush=True)
            stop.wait(0.3)

    t = threading.Thread(target=status_loop, daemon=True)
    t.start()
    print(f"[chaos-server] serving (pid={os.getpid()}, "
          f"resume={cfg.get('resume', False)})", flush=True)
    deadline = (time.time() + float(cfg["run_s"])
                if cfg.get("run_s") else None)
    try:
        while deadline is None or time.time() < deadline:
            time.sleep(0.2)
    finally:
        stop.set()
        server.disable_server()


if __name__ == "__main__":
    main()
