"""Serving-replica process for the replica SIGKILL drill
(``tests/test_drills.py::test_serving_replica_sigkill_drill``).

Hosts ONE :class:`relayrl_tpu.runtime.inference.StandaloneInferenceHost`:
handshakes the model off the root TrainingServer's agent plane exactly
like an actor, binds its own zmq ROUTER serving endpoint, and follows
model publishes live. Runs until the coordinator writes the stop file,
then writes its accounting + telemetry snapshot to the result path
(session table occupancy, eviction/resync counters and batch occupancy
live HERE, not in the root server's snapshot).

Usage: _serving_replica.py <json-config>
"""

from __future__ import annotations

import json
import os
import sys
import time


def main():
    cfg = json.loads(sys.argv[1])
    os.environ["JAX_PLATFORMS"] = "cpu"  # before anything imports jax
    from relayrl_tpu import telemetry

    telemetry.set_registry(telemetry.Registry(run_id=cfg["name"]))
    from relayrl_tpu.runtime.inference import StandaloneInferenceHost

    addr_overrides = {
        k: cfg[k] for k in ("agent_listener_addr", "trajectory_addr",
                            "model_sub_addr", "server_addr")
        if k in cfg}
    host = StandaloneInferenceHost(
        config_path=cfg.get("config_path"),
        server_type=cfg.get("server_type", "zmq"),
        serving_addr=cfg["serving_addr"],
        handshake_timeout_s=cfg.get("handshake_timeout_s", 180.0),
        identity=cfg["name"],
        **addr_overrides,
    )
    with open(cfg["ready_file"], "w") as f:
        f.write(cfg["name"])
    while not os.path.exists(cfg["stop_file"]):
        time.sleep(0.1)
    result = {
        "replica": cfg["name"],
        "serving_addr": cfg["serving_addr"],
        "model_version": host.service.version,
        "accounting": host.service.accounting(),
        "telemetry": telemetry.get_registry().snapshot(),
    }
    host.stop()
    with open(cfg["result_path"], "w") as f:
        json.dump(result, f)


if __name__ == "__main__":
    main()
