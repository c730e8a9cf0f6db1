"""The training server process: trajectory ingest → jitted learner → model
publish.

Capability parity with the reference's server stack
(reference: relayrl_framework/src/network/server/training_server_wrapper.rs:
199-443 facade + lifecycle; training_zmq.rs / training_grpc.rs loops), with
the central re-design from SURVEY.md §7.4 item 1: the reference funnels every
trajectory through a lock-step JSON-over-stdin subprocess
(python_algorithm_request.rs:199-267); here the learner is **in-process** —
ingest happens on transport threads into a queue, a staging thread decodes
(natively, off-GIL, via native/codec.cc when the library is built — the
reference keeps its decode native too, training_zmq.rs:994-1011), and a
single learner thread drains ready batches into the jitted XLA update while
the next trajectories decode in parallel. The native transport goes one
step further and delivers pre-decoded columnar batches straight to the
decoded queue (rl_server_poll_batch). No subprocess, no stdio bottleneck,
no 50 ms polls, no per-step Python on the ingest path.

Ctor parity with the PyO3 surface (src/bindings/python/network/server/
o3_training_server.rs:78-151): ``TrainingServer(algorithm_name, obs_dim,
act_dim, buf_size, tensorboard=False, multiactor=False, env_dir,
algorithm_dir, config_path, hyperparams, server_type, ...)`` plus
``restart_server/enable_server/disable_server``.
"""

from __future__ import annotations

import os
import queue
import threading
import time
from typing import Any, Mapping

from relayrl_tpu.algorithms import build_algorithm, registered_algorithms
from relayrl_tpu.config import ConfigLoader
from relayrl_tpu.telemetry import actor_ledger
from relayrl_tpu.telemetry.aggregate import is_snapshot_frame
from relayrl_tpu.telemetry.core import LAG_BUCKETS
from relayrl_tpu.telemetry.spans import span, watch_gc
from relayrl_tpu.telemetry.thread_clock import ThreadLedger
from relayrl_tpu.telemetry.trace import SKEW_GUARD_NS, TrajCtx
from relayrl_tpu.transport import make_server_transport
from relayrl_tpu.transport.base import (
    BATCH_KIND_ENVELOPES,
    batch_kind,
    split_agent_tags,
    split_batch,
    swallow_decode_error,
    unpack_trajectory_envelope,
)
from relayrl_tpu.types.columnar import DecodedTrajectory
from relayrl_tpu.types.trajectory import deserialize_actions

# the learner process's threads whose CPU time the per-thread ledger keeps
# apart (telemetry/thread_clock.py): ``timings["cpu_<role>_s"]``
_CLOCKED_ROLES = ("learner", "staging", "ingest", "publish")


class _EventCoalescer:
    """≤1 journal event per ``min_interval_s`` for burst-prone counters
    (ingest drops, duplicate replays): the metric counter is the ledger,
    the journal event is the greppable breadcrumb — one instance per
    event type, mutated under the owner's lock, with ``flush`` covering
    the tail of a burst on quiesce paths."""

    def __init__(self, min_interval_s: float = 1.0):
        self.pending = 0
        self._last = 0.0
        self._min = min_interval_s

    def add(self, n: int) -> int | None:
        """Accumulate; returns the count to emit now, or None while
        still coalescing. Caller holds the owning lock."""
        self.pending += n
        if time.monotonic() - self._last >= self._min:
            due, self.pending = self.pending, 0
            self._last = time.monotonic()
            return due
        return None

    def flush(self) -> int:
        """Drain whatever is still coalescing (caller holds the lock)."""
        due, self.pending = self.pending, 0
        if due:
            self._last = time.monotonic()
        return due


class _StampedQueue(queue.Queue):
    """A ``Queue`` that stamps every item as it is put (CLOCK_MONOTONIC
    ns). After a ``get`` its one consuming thread reads the stamp of the
    item it got from ``got_put_ns``; against the instant the ``get``
    returned it is how long the item waited in the queue."""

    got_put_ns = 0

    def _put(self, item):
        self.queue.append((time.monotonic_ns(), item))

    def _get(self):
        self.got_put_ns, item = self.queue.popleft()
        return item


class _TracedRecords(list):
    """A ``list[ActionRecord]`` that can carry a trace context attribute
    (plain lists can't) — behaves identically through accumulate."""

    trace_ctx = None


def _attach_trace_ctx(item, ctx):
    """Hang a trajectory's origin (born stamp and version; a sampled one's
    trace id too) on the decoded item so the learner thread can attribute
    the consuming update dispatch."""
    if isinstance(item, DecodedTrajectory):
        item.trace_ctx = ctx
        return item
    if isinstance(item, list):
        if item and isinstance(item[0], DecodedTrajectory):
            item[0].trace_ctx = ctx  # coalesced frames: one ctx, one seq
            return item
        wrapped = _TracedRecords(item)
        wrapped.trace_ctx = ctx
        return wrapped
    return item


class TrainingServer:
    def __init__(
        self,
        algorithm_name: str = "REINFORCE",
        obs_dim: int = 4,
        act_dim: int = 2,
        buf_size: int | None = None,
        tensorboard: bool = False,
        multiactor: bool = True,
        env_dir: str | None = None,
        algorithm_dir: str | None = None,
        config_path: str | None = None,
        hyperparams: Mapping[str, Any] | None = None,
        server_type: str = "zmq",
        start: bool = True,
        resume: bool = False,
        handle_signals: bool = False,
        serving: bool | None = None,
        **addr_overrides,
    ):
        self.config = ConfigLoader(algorithm_name, config_path)
        self.server_type = server_type
        self._addr_overrides = addr_overrides

        # Observability first: the registry must be live before any
        # component (algorithm logger, transports, pipeline) grabs its
        # metric handles; disabled mode installs null metrics everywhere
        # (telemetry.* knobs, docs/observability.md).
        from relayrl_tpu import telemetry

        self._telemetry = telemetry.configure_from_config(self.config)
        self._exporter = telemetry.maybe_serve()
        reg = self._telemetry
        self._m_trajectories = reg.counter(
            "relayrl_server_trajectories_total",
            "trajectories handed to the learner plane")
        self._m_updates = reg.counter(
            "relayrl_server_updates_total", "learner updates dispatched")
        self._m_dropped = reg.counter(
            "relayrl_server_dropped_total",
            "payloads lost at ingest (full queue / decode failure)")
        self._m_nonfinite = reg.gauge(
            "relayrl_server_dropped_nonfinite",
            "trajectories rejected by the finite-value guard")
        self._m_decode = reg.histogram(
            "relayrl_server_decode_seconds",
            "one payload decode on a staging worker")
        self._m_columnar_frames = reg.counter(
            "relayrl_server_columnar_frames_total",
            "columnar trajectory frames decoded straight into "
            "DecodedTrajectory (the wire fast path)")
        self._m_columnar_bytes = reg.counter(
            "relayrl_server_columnar_bytes_total",
            "columnar trajectory frame bytes decoded")
        self._m_columnar_rejects = reg.counter(
            "relayrl_server_columnar_rejects_total",
            "columnar frames refused at decode (CRC mismatch / "
            "malformed layout) — also counted in dropped_total")
        self._m_dispatch = reg.histogram(
            "relayrl_server_dispatch_seconds",
            "learner-thread host work per trajectory: accumulate + "
            "assemble + async update dispatch")
        self._m_duplicates = reg.counter(
            "relayrl_server_duplicate_trajectories_total",
            "sequence-tagged trajectories dropped by idempotent ingest "
            "(replays, retry storms, duplicate-injection faults)")
        self._m_rlhf_train_lag = reg.histogram(
            "relayrl_rlhf_train_lag_versions",
            "behavior version (data['bver'], stamped at generation) vs "
            "the learner's dispatched version when the trajectory "
            "trains — the off-policy distance V-trace corrects; "
            "observed for trajectories that carry bver, or a sampled "
            "trace context's born_version (same evidence)",
            buckets=LAG_BUCKETS)
        from relayrl_tpu.telemetry.core import AGE_BUCKETS

        self._m_data_age = reg.histogram(
            "relayrl_trace_data_age_seconds",
            "end-to-end data age of every trajectory that says when it "
            "was born (the actors' report tag, a sampled trace context): "
            "env-step/window production to the start of the update "
            "dispatch that consumed it (same-host monotonic pairs; "
            "skew-guarded)",
            buckets=AGE_BUCKETS)
        self._m_data_lag = reg.histogram(
            "relayrl_trace_data_age_versions",
            "data age in model versions: the version the consuming "
            "update trains from minus the version the trajectory was "
            "generated under (the born-stamp twin of "
            "relayrl_rlhf_train_lag_versions)",
            buckets=(0.0, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0))
        self._m_ckpt_failures = reg.counter(
            "relayrl_server_checkpoint_failures_total",
            "periodic/final checkpoint saves that raised")
        self._m_ckpt_consecutive = reg.gauge(
            "relayrl_server_checkpoint_consecutive_failures",
            "checkpoint failures since the last successful save "
            "(alarm when this grows — resume would lose that window)")
        self._ckpt_consecutive_failures = 0
        self._m_learner_errors = reg.counter(
            "relayrl_server_learner_errors_total",
            "accumulate/stage/update dispatches and window fences that "
            "raised on the learner thread (the batch is lost, the loop "
            "carries on)")
        # Same family the publisher thread counts into
        # (runtime/pipeline.ModelPublisher): the synchronous publish
        # paths land here.
        self._m_publish_errors = reg.counter(
            "relayrl_learner_publish_errors_total",
            "publish attempts that raised (transient socket/fs)")
        self._drop_events = _EventCoalescer()
        self._dup_events = _EventCoalescer()

        # Fleet telemetry aggregation (ISSUE 15, telemetry/aggregate.py):
        # the root holds the fleet table — every process's snapshot
        # frames land here through the ordinary ingest funnel (sniffed by
        # RLS1 magic in _ingest_one, O(relays) frames under a relay
        # tree), the fleet tick folds this server's own registry in,
        # evicts stale procs, and runs the SLO alert rules over the
        # merged snapshot. Gated like tracing: registry live AND
        # telemetry.fleet_interval_s > 0.
        tel_params = self.config.get_telemetry_params()
        self._fleet = None
        self._alerts = None
        self._fleet_interval_s = float(tel_params.get("fleet_interval_s")
                                       or 0.0)
        self._fleet_stop = threading.Event()
        self._fleet_thread: threading.Thread | None = None
        self._fleet_proc = f"server-{os.getpid()}"
        if reg.enabled and self._fleet_interval_s > 0:
            from relayrl_tpu.telemetry.aggregate import (
                AlertEngine,
                FleetTable,
                rules_from_config,
            )

            self._fleet = FleetTable(
                stale_s=tel_params.get("fleet_stale_s", 15.0), registry=reg)
            self._alerts = AlertEngine(rules_from_config(tel_params),
                                       registry=reg)
            if self._exporter is not None:
                self._exporter.set_fleet(self._fleet, self._alerts)

        # Fault-injection plane: the env-driven plan (RELAYRL_FAULT_PLAN)
        # installs before any hook site resolves; production processes
        # without the env var get None sites and pay one identity check.
        from relayrl_tpu import faults

        faults.maybe_install_from_env()
        self._fault_ingest = faults.site("server.ingest")
        self._fault_publish = faults.site("server.publish")

        # Training-health guardrails (relayrl_tpu/guardrails/): ingest
        # validation + quarantine, divergence watchdog, last-known-good
        # rollback, and ingest backpressure. None when guardrails.enabled
        # is false — every hook site below then costs one identity check.
        from relayrl_tpu.guardrails import build_guardrails

        self.guardrails = build_guardrails(self.config)
        # Rollback bookkeeping (learner thread only): timestamps of
        # executed rollbacks inside the budget window, and the degraded
        # halt-and-alarm latch (halted = ingest sheds, training stops,
        # the process survives for operator forensics).
        self._rollback_times: list[float] = []
        self._rollbacks_total = 0
        self._halted = False

        # Multi-host bring-up must precede any other JAX use (no-op for the
        # default single-host config; RELAYRL_COORDINATOR etc. override).
        from relayrl_tpu.parallel.distributed import initialize_distributed

        self.distributed_info = initialize_distributed(
            config=self.config.get_learner_params())
        if self.distributed_info["multi_host"]:
            print(f"[TrainingServer] multi-host learner: process "
                  f"{self.distributed_info['process_id']}/"
                  f"{self.distributed_info['num_processes']}", flush=True)

        if algorithm_dir:
            _load_plugin_algorithms(algorithm_dir)
        # Reference parity: hyperparams may arrive as a dict or as
        # ["k=v", ...] (training_server_wrapper.rs:118-154).
        if isinstance(hyperparams, (list, tuple)):
            hp = {k: _coerce(v) for k, v in
                  (kv.split("=", 1) for kv in hyperparams)}
        else:
            hp = dict(hyperparams or {})
        if self.distributed_info["multi_host"]:
            # SPMD demands bit-identical initial state on every process;
            # the default seed_salt (the pid) would fork the inits.
            hp.setdefault("seed_salt", 0)

        self.algorithm = build_algorithm(
            algorithm_name,
            env_dir=env_dir,
            config_path=str(self.config.config_path) if self.config.config_path else None,
            obs_dim=obs_dim,
            act_dim=act_dim,
            buf_size=buf_size,
            **hp,
        )
        # host:dispatch asks the server what it alone knows of the batch
        # being dispatched: when its trajectories were born.
        self.algorithm._dispatch_note = self._on_dispatch
        if self.guardrails is not None:
            # Installs the device-side health probes (observers — params
            # stay bit-identical to guardrails-off) and aligns the
            # per-algorithm finite guard with the validation mode.
            self.guardrails.attach_algorithm(self.algorithm)

        learner_cfg = self.config.get_learner_params()
        # One resolution for save AND resume — a falsy configured value
        # disables checkpointing entirely, anything else is used by both
        # paths (a split default here would resume from a dir never written).
        # Relative dirs anchor under env_dir (see anchor_path) so example
        # runs don't leave `checkpoints/` in the caller's cwd.
        from relayrl_tpu.algorithms.base import anchor_path

        self._checkpoint_dir = learner_cfg.get("checkpoint_dir", "checkpoints")
        if self._checkpoint_dir:
            self._checkpoint_dir = anchor_path(self._checkpoint_dir, env_dir)
        self._checkpoint_every = max(
            1, int(learner_cfg.get("checkpoint_every_epochs", 10)))
        # Replay-buffer (aux) cadence: snapshotting the ring is a
        # synchronous host copy on the learner thread, so large buffers
        # can throttle it to every Nth periodic save. Final/signal saves
        # always include aux regardless. Retention grows with the
        # cadence (max_to_keep >= cadence) so a crash-resume always finds
        # at least one retained aux-carrying step — the aux-less step
        # dirs are cheap (params + opt state) next to the ring itself.
        from relayrl_tpu.checkpoint import CheckpointManager

        self._aux_every = max(
            1, int(learner_cfg.get("checkpoint_aux_every", 1)))
        self._ckpt_keep = max(CheckpointManager.DEFAULT_MAX_TO_KEEP,
                              self._aux_every)
        if self.guardrails is not None and self.guardrails.params["rollback"]:
            # The last-known-good ring: retain at least checkpoint_ring
            # steps so the rollback search has healthy-tagged candidates
            # even when the newest saves straddled the divergence.
            self._ckpt_keep = max(self._ckpt_keep,
                                  self.guardrails.params["checkpoint_ring"])
        self._ckpt_saves = 0

        # Idempotent ingest (runtime/spool.SequenceLedger): sequence-
        # tagged trajectories are accepted at most once per agent, so
        # actor replay-on-reconnect can never double-train. The ledger
        # snapshots to a per-version JSON sidecar next to each
        # checkpoint and is restored WITH the matching resume, keeping
        # dedup state consistent with the params line of history.
        from relayrl_tpu.runtime.spool import SequenceLedger

        try:
            dedup_window = int(learner_cfg.get("ingest_dedup_window", 4096))
        except (TypeError, ValueError):
            dedup_window = 4096
        self._ingest_ledger = (SequenceLedger(dedup_window)
                               if dedup_window > 0 else None)

        if resume and self._checkpoint_dir:
            # Multi-host: EVERY rank restores the same full state from the
            # shared checkpoint dir BEFORE enable_multihost places it on
            # the global mesh — identical state everywhere, exactly like a
            # fresh seed_salt=0 init. (Saves are already collective; see
            # the broadcast loop.)
            from relayrl_tpu.checkpoint import restore_algorithm

            try:
                restore_algorithm(self.algorithm, self._checkpoint_dir)
                print(f"[TrainingServer] resumed at version "
                      f"{self.algorithm.version}", flush=True)
                self._load_ledger_sidecar(self.algorithm.version)
            except FileNotFoundError:
                print("[TrainingServer] no checkpoint to resume; fresh start",
                      flush=True)

        # The learner step is SPMD over learner.mesh whenever that mesh
        # has more than one device. Multi-host: the global (all-host)
        # mesh — coordinator-side socket ingest assembles batches, the
        # broadcast loop ships them, every process steps in lockstep
        # (SURVEY.md §7.4 item 5's asymmetric-ingest design). One process
        # that sees several chips: the same placement under the ordinary
        # learner loop (a mesh of one process needs no broadcast).
        import jax

        from relayrl_tpu.parallel import accelerator_devices, make_mesh

        mesh_devices = (jax.devices() if self.distributed_info["multi_host"]
                        else accelerator_devices())
        self.mesh = None
        if len(mesh_devices) > 1:
            if not hasattr(self.algorithm, "enable_multihost"):
                raise NotImplementedError(
                    f"{algorithm_name} cannot train over a device mesh "
                    "(no enable_multihost)")
            self.mesh = make_mesh(learner_cfg.get("mesh") or {"dp": -1},
                                  mesh_devices)
            self.algorithm.enable_multihost(self.mesh)
            print(f"[TrainingServer] learner mesh "
                  f"{dict(self.mesh.shape)} over "
                  f"{len(mesh_devices)} {mesh_devices[0].platform} devices",
                  flush=True)

        # Multi-actor registry (ref: MultiactorParams,
        # training_server_wrapper.rs:159-163). Always multi-capable; the
        # flag only gates the registered-agents log.
        self.multiactor = bool(multiactor)
        self.agent_ids: list[str] = []
        self._registry_lock = threading.Lock()

        # Raw payloads from transport threads; a staging thread decodes
        # them (native codec when built) into _decoded, which the learner
        # thread drains — decode overlaps the device step.
        self._ingest: queue.Queue[tuple[str, bytes]] = queue.Queue(maxsize=100_000)
        self._decoded: queue.Queue = _StampedQueue(maxsize=100_000)
        # Pull-gauges: depth is read from the live queues only when an
        # export actually renders — zero hot-path cost. Sources hold a
        # WEAK reference to this server: the registry is process-global,
        # and a strong closure would pin a shut-down server's whole
        # object graph (100k-slot queues, algorithm state) for the
        # process lifetime. A dead source reads None → omitted from
        # snapshots.
        import weakref

        wref = weakref.ref(self)

        def _queue_depth(attr):
            def read():
                server = wref()
                return (None if server is None
                        else getattr(server, attr).qsize())
            return read

        def _registered():
            server = wref()
            return None if server is None else len(server.agent_ids)

        reg.gauge_fn("relayrl_server_ingest_queue_depth",
                     _queue_depth("_ingest"),
                     "raw payloads awaiting a decode worker")
        reg.gauge_fn("relayrl_server_decoded_queue_depth",
                     _queue_depth("_decoded"),
                     "decoded trajectories awaiting the learner thread")
        reg.gauge_fn("relayrl_server_registered_agents", _registered,
                     "logical agents currently in the registry")
        self._bundle_lock = threading.Lock()
        self._bundle_bytes: bytes = self.algorithm.bundle().to_bytes()
        self._bundle_version: int = self.algorithm.version
        # Latest published model as a HOST tree (version, arch, params):
        # the v1 bundle bytes for handshakes/artifacts serialize lazily
        # from it in _get_model, so the wire-v2 publish path never pays a
        # full flax serialize per publish (only per handshake-or-artifact
        # that actually needs one).
        self._bundle_host: tuple[int, dict, object] | None = None
        # Model-wire v2 (transport/modelwire.py): per-leaf delta frames
        # with periodic keyframes replace the full-bundle blob on the
        # broadcast plane. transport.wire_version=1 is the rolling-compat
        # escape hatch (v1 fleets; v2 actors decode either).
        transport_cfg = self.config.get_transport_params()
        self._wire_encoder = None
        if int(transport_cfg.get("wire_version", 2)) >= 2:
            from relayrl_tpu.transport.modelwire import ModelWireEncoder

            self._wire_encoder = ModelWireEncoder(
                keyframe_interval=transport_cfg["keyframe_interval"],
                compress=transport_cfg["compress"],
                small_model_bytes=transport_cfg.get("small_model_bytes"))
        # Broadcast-plane resync requests (CMD_RESYNC — ISSUE 11): a
        # diverged subscriber asks for a keyframe instead of waiting out
        # the interval. Coalesced by nature (force_keyframe is a flag
        # the next publish consumes) and rate-limited so a subtree-wide
        # divergence storm grants ONE forced keyframe per window.
        self._resync_lock = threading.Lock()
        self._last_resync_grant = -1e9
        self._resync_min_interval_s = float(
            transport_cfg.get("resync_min_interval_s", 0.25))
        self._m_resync_requests = reg.counter(
            "relayrl_server_resync_requests_total",
            "CMD_RESYNC keyframe requests received from the broadcast "
            "plane (actors or relays with a diverged delta base)")
        self._m_resync_granted = reg.counter(
            "relayrl_server_resync_keyframes_total",
            "resync requests that forced the next publish to keyframe "
            "(the rest coalesced into an already-granted window)")

        # Non-coordinator processes run learner steps only — the actor
        # plane (sockets) binds on the coordinator host alone.
        from relayrl_tpu.parallel.distributed import is_coordinator

        self.transport = None
        if is_coordinator():
            self.transport = make_server_transport(server_type, self.config,
                                                   **addr_overrides)
            self.transport.on_trajectory = self._on_trajectory
            self.transport.on_trajectory_decoded = self._on_trajectory_decoded
            self.transport.get_model = self._get_model
            self.transport.on_register = self._on_register
            self.transport.on_unregister = self._on_unregister
            self.transport.on_resync = self._on_resync_request
            if self.guardrails is not None:
                # Ack-capable transports (gRPC) answer a refused send
                # with a typed nack (quarantine / overload) instead of a
                # silent server-side shed — see _check_ingest.
                self.transport.check_ingest = self._check_ingest
            if getattr(self.transport, "serves_full_bundles_only", False):
                # This plane (native C++ gRPC long-polls) ships the
                # stored full bundle to every subscriber regardless —
                # encoding delta frames would burn publisher CPU and
                # record wire counters for bytes that never leave.
                self._wire_encoder = None
            if self._wire_encoder is not None:
                # Pull transports (gRPC long-polls) choose delta-vs-full
                # per subscriber through this surface; the version probe
                # keeps their wakeup checks from forcing lazy serializes.
                self.transport.get_model_update = self._get_model_update
                self.transport.get_model_version = (
                    lambda: self.latest_model_version)

        # Disaggregated batched-inference serving plane (ROADMAP item 2,
        # runtime/inference.py): colocated with this learner, fed
        # in-process from the publish path — thin clients
        # (actor.host_mode: "remote") get batched actions with zero
        # model-distribution wire hops. grpc fleets ride the in-band
        # GetActions RPC; zmq/native fleets the dedicated ROUTER plane.
        self.inference = None
        serving_cfg = self.config.get_serving_params()
        if serving is not None:
            # Ctor override for drivers/drills that decide the topology
            # programmatically (examples/train_distributed.py
            # --host-mode remote); config holds every other knob.
            serving_cfg["enabled"] = bool(serving)
        if serving_cfg["enabled"] and self.transport is not None:
            from relayrl_tpu.runtime.inference import InferenceService

            try:
                self.inference = InferenceService.from_config(
                    self.algorithm.bundle(), self.config, validate=False)
            except ValueError as e:
                # Sequence policies are not servable yet — the server
                # must still come up for the local actor tiers.
                print(f"[TrainingServer] serving disabled: {e}",
                      flush=True)
            if self.inference is not None:
                self._wire_serving_plane(addr_overrides)

        self._stop = threading.Event()
        self._learner_thread: threading.Thread | None = None
        self._staging_threads: list[threading.Thread] = []
        self._mh_ready: list = []   # assembled-but-untrained epoch batches
        self._mh_busy = False       # a broadcast step is in flight
        self.active = False
        # Pipelined learner hot path (single-host): the learner thread is
        # dispatch-only — updates enter the algorithm's bounded in-flight
        # window unfenced, the publish runs on a dedicated latest-wins
        # thread, assembled batches prefetch to the device, and epoch
        # logs defer until their update's fence. Knobs (docs/operations):
        #   learner.max_inflight_updates  (algorithm-side; 0 = sync)
        #   learner.async_publish         false = publish on learner thread
        #   learner.ingest_staging_threads  decode workers (default 1)
        self._async_publish = bool(learner_cfg.get("async_publish", True))
        self._staging_count = max(
            1, int(learner_cfg.get("ingest_staging_threads", 1)))
        self._publisher = None
        # Distance-gate anchors for the model artifact and the periodic
        # checkpoint — seeded from the (possibly resumed) version so a
        # resume doesn't immediately re-save what it just restored.
        self._artifact_version = int(self.algorithm.version)
        self._ckpt_version = int(self.algorithm.version)
        from collections import deque

        self._pending_logs: deque = deque()
        # Origins (born stamp, version; a sampled trajectory's trace id)
        # staged-but-not-yet-consumed: the next update dispatch reads
        # their data age into its ``host:dispatch`` span and closes the
        # sampled ones out with an "update" hop (learner thread only).
        # Bounded as a belt: a plugin algorithm that never updates must
        # not hoard them.
        self._trace_pending: deque = deque(maxlen=8192)
        self._dispatched_origins: list = []
        self._timings_lock = threading.Lock()
        # "dropped" counts transport/queue-level losses; the ingest
        # finite-value guard's count is mirrored from the algorithm after
        # each trajectory so operators see poisoning without reaching
        # into algorithm internals.
        # learner_errors / publish_errors / warmup_failed count what the
        # loops below survive: a learner that ingests forever and never
        # updates (a kernel the compiler refuses, a device OOM) shows here
        # instead of only in scrolled-away log lines.
        # actor_<count>: the actor tier's own counts, summed from the
        # reports its trajectories carry (telemetry/actor_ledger.py).
        self.stats = {"trajectories": 0, "updates": 0, "dropped": 0,
                      "dropped_nonfinite": 0, "learner_errors": 0,
                      "publish_errors": 0, "warmup_failed": 0,
                      **{f"actor_{k}": 0 for k in actor_ledger.COUNTS}}
        # Which trajectory decoder the staging threads resolved:
        # "native" (the C++ codec in native/) or "python".
        self.ingest_decoder: str | None = None
        self._warmup_error: Exception | None = None
        # Per-thread time ledger (seconds): where the ingest pipeline
        # actually spends its time — the profile evidence that the learner
        # thread waits on the device, not on msgpack (SURVEY §7.4-1).
        #   decode_s      staging thread(s) inside decode
        #   dispatch_s    learner thread enqueueing host work (assemble +
        #                 async update dispatch + publish handoff)
        #   device_wait_s learner thread fenced on the device (in-flight
        #                 window + idle drains) — split from dispatch_s
        #                 because async dispatch makes a single "learn"
        #                 bucket meaningless (jaxlint JAX06)
        #   publish_s     publisher thread inside gather/serialize/send
        #   learner_idle_s learner thread blocked on an empty queue
        #   warmup_s      learner thread pre-compiling update shapes
        #   gc_s          any thread inside a full collection (rl:gc)
        #   admit_s       the transport's receive thread inside
        #                 on_trajectory (rl:ingest.admit: tag split, dedup,
        #                 guardrails, the report's merge, the queue put).
        #                 Written unlocked by its one writer; a transport
        #                 that calls from a pool of threads (grpc) may lose
        #                 an increment to a race, never corrupt the total
        #   cpu_<role>_s  on-CPU time of the learner | staging | ingest |
        #                 publish thread(s), runq_<role>_s the learner's
        #                 and the staging threads' time runnable and not
        #                 running (absent where the kernel keeps no
        #                 schedstat), cpu_process_s every thread's:
        #                 absolute, rewritten by the learner thread once an
        #                 update dispatch (telemetry/thread_clock.py)
        #   actor_<key>   the actor PROCESSES' own ledgers, summed over
        #                 them: each admitted trajectory's report carries
        #                 its host's deltas (telemetry/actor_ledger.py has
        #                 the keys; shares are of actor_wall_s)
        # Every total is fed by the span round its site (telemetry/spans.py;
        # docs/observability.md has the table).
        self.timings = {"decode_s": 0.0, "dispatch_s": 0.0,
                        "device_wait_s": 0.0, "publish_s": 0.0,
                        "learner_idle_s": 0.0, "warmup_s": 0.0,
                        "gc_s": 0.0, "admit_s": 0.0,
                        **{f"cpu_{role}_s": 0.0 for role in
                           (*_CLOCKED_ROLES, "process")},
                        **{f"actor_{k}": 0.0 for k in actor_ledger.TIMINGS}}
        self._thread_ledger = ThreadLedger(
            _CLOCKED_ROLES, runq_roles=("learner", "staging"))
        watch_gc(self)
        self._warmup_done = threading.Event()

        self._tb = None
        if tensorboard:
            from relayrl_tpu.utils.tb_writer import TensorboardWriter

            self._tb = TensorboardWriter.from_logger(
                self.algorithm.logger, self.config.get_tb_params())

        if handle_signals:
            self._install_signal_handlers()
        if start:
            self.enable_server()

    def _install_signal_handlers(self) -> None:
        """Opt-in SIGTERM/SIGINT handling for long-lived deployments
        (systemd stop, k8s pod eviction, ^C): write a final full-state
        checkpoint, shut the planes down cleanly, then die by the SAME
        signal so supervisors see an honest exit status. The reference
        has no shutdown path at all beyond process death (SURVEY §5.3);
        pairing this with ``resume=True`` on the next start makes a
        restart lose nothing. Only possible on the main thread
        (CPython restriction) — elsewhere this is a no-op with a note."""
        import signal

        def _handler(signum, frame):
            # First thing: restore default disposition on BOTH signals, so
            # a second ^C / a supervisor's follow-up SIGTERM kills
            # immediately instead of re-entering a save in flight.
            for sig in (signal.SIGTERM, signal.SIGINT):
                signal.signal(sig, signal.SIG_DFL)
            name = signal.Signals(signum).name
            print(f"[TrainingServer] {name}: final checkpoint + clean "
                  f"shutdown", flush=True)
            try:
                # Quiesce BEFORE snapshotting: joins the learner/staging
                # threads so state/version/replay ring aren't mid-mutation
                # under the save. Undelivered queue items are dropped —
                # nothing the learner had trained on is lost.
                # Multi-host: peers may be mid-collective and only THIS
                # rank got the signal — an unbounded join can outlive the
                # supervisor's grace period so the re-raise below never
                # runs and the pod is SIGKILLed with sockets still open.
                # Bound the quiesce; a timed-out thread dies with the
                # process (the final save is skipped on multi-host anyway).
                grace = (10.0 if self.distributed_info["multi_host"]
                         else None)
                self.disable_server(join_timeout=grace)
                if (self._checkpoint_dir and self.algorithm.version > 0
                        and not self.distributed_info["multi_host"]):
                    # Multi-host saves are collective and version-gated
                    # (every rank must enter together); an eviction-time
                    # solo save would deadlock the mesh — rely on the
                    # periodic collective checkpoints there.
                    from relayrl_tpu.checkpoint import checkpoint_algorithm

                    try:
                        # overwrite: a periodic save may already sit at
                        # this version WITHOUT the replay snapshot (aux
                        # cadence) — the final save must land with it, so
                        # a same-step collision bumps to a fresh step
                        # instead of being skipped (never deletes).
                        checkpoint_algorithm(self.algorithm,
                                             self._checkpoint_dir, wait=True,
                                             overwrite=True,
                                             extra_meta=self._health_tag())
                        self._save_ledger_sidecar(self.algorithm.version)
                    except Exception as e:
                        self._m_ckpt_failures.inc()
                        from relayrl_tpu import telemetry

                        telemetry.emit("checkpoint_failed",
                                       version=self.algorithm.version,
                                       error=repr(e), consecutive=1,
                                       dir=str(self._checkpoint_dir))
                        print(f"[TrainingServer] final checkpoint skipped: "
                              f"{e!r}", flush=True)
            finally:
                signal.raise_signal(signum)

        try:
            for sig in (signal.SIGTERM, signal.SIGINT):
                signal.signal(sig, _handler)
        except ValueError:  # not the main thread
            print("[TrainingServer] handle_signals requested off the main "
                  "thread — skipped (install handlers in your main thread "
                  "and call disable_server there instead)", flush=True)

    def _wire_serving_plane(self, addr_overrides: dict) -> None:
        """Attach the InferenceService's action channel to the fleet's
        transport kind: in-band ``GetActions`` where the backend carries
        request/response RPCs (pure-grpcio), else the dedicated zmq
        ROUTER plane at ``server.inference_server`` (zmq fleets natively;
        native framed-TCP fleets as the documented passthrough — the C++
        core has no action RPC)."""
        if getattr(self.transport, "supports_inband_infer", False):
            self.transport.on_infer = self.inference.handle_request_blocking
            # Bidi StreamActions (serving v2): one parked RPC thread per
            # stream regardless of in-flight depth — frames go through
            # the non-blocking enqueue, replies ride the batch worker's
            # callbacks.
            self.transport.on_infer_submit = self.inference.handle_request
        else:
            self.inference.bind_zmq(addr_overrides.get(
                "serving_addr",
                self.config.get_inference_server().address))

    @staticmethod
    def _get_tracer():
        from relayrl_tpu.telemetry import trace as trace_mod

        return trace_mod.get_tracer()

    # -- transport callbacks (transport threads!) --
    def _count_dropped(self, n: int = 1) -> None:
        """stats['dropped'] is written from transport threads AND the N
        decode workers — an unlocked += loses increments exactly when
        the operator most needs the counter (docs/operations.md says to
        watch it to size ingest_staging_threads)."""
        with self._timings_lock:
            self.stats["dropped"] += n
            total = self.stats["dropped"]
            due = self._drop_events.add(n)
        self._m_dropped.inc(n)
        if due:
            from relayrl_tpu import telemetry

            telemetry.emit("drop", n=due, total=total)

    def _flush_drop_event(self) -> None:
        """Emit any drop/duplicate count still coalescing (quiesce paths:
        drain success, disable_server) — without this, counts accumulated
        in the 1-s window after the last emitted event would never reach
        the journal."""
        with self._timings_lock:
            pending = self._drop_events.flush()
            total = self.stats["dropped"]
            dup_pending = self._dup_events.flush()
        if pending or dup_pending:
            from relayrl_tpu import telemetry

            if pending:
                telemetry.emit("drop", n=pending, total=total)
            if dup_pending:
                telemetry.emit("duplicate_drop", n=dup_pending)

    def _count_duplicate(self, n: int = 1) -> None:
        """Duplicate-drop accounting, coalesced to <=1 journal event/s
        (a replay burst after a reconnect is hundreds of lines
        otherwise)."""
        self._m_duplicates.inc(n)
        with self._timings_lock:
            due = self._dup_events.add(n)
        if due:
            from relayrl_tpu import telemetry

            telemetry.emit("duplicate_drop", n=due)

    def _admit_seq(self, agent_id: str):
        """Split the sequence, trace AND report tags off an envelope id
        and consult the dedup ledger: ``(clean_agent_id, seq, ctx,
        admit)``. Every tag strips unconditionally — like the seq tag, a
        trace context or an actor's report must never leak into
        attribution/quarantine keys even when this process records no
        spans. ``ctx`` is the trajectory's origin: the sampled trace
        context where one rides, else the report's born stamp and version
        under no trace id, else None. Untagged ids (raw transport users,
        older actors, pre-spool fleets) admit with seq/ctx None.

        The one place an actor's report lands: an ADMITTED envelope adds
        its deltas to ``timings["actor_<key>"]`` / ``stats["actor_<count>"]``;
        a spool replay re-sends the tagged id and the dedup verdict keeps
        it from counting twice. (An envelope shed after admission is
        retracted and counts again when its replay lands.)"""
        clean_id, seq, trace_text, report_text = split_agent_tags(agent_id)
        ctx = None if trace_text is None else TrajCtx.decode(trace_text)
        if (seq is not None and self._ingest_ledger is not None
                and not self._ingest_ledger.accept(clean_id, seq)):
            self._count_duplicate()
            return clean_id, seq, ctx, False
        report = (None if report_text is None
                  else actor_ledger.decode_report(report_text))
        if report is not None:
            if ctx is None and report.born_ns:
                ctx = TrajCtx(None, report.born_ns, report.born_version)
            if report.timings:
                with self._timings_lock:
                    for key, seconds in report.timings.items():
                        self.timings[f"actor_{key}"] += seconds
                    for key, n in report.counts.items():
                        self.stats[f"actor_{key}"] += n
        return clean_id, seq, ctx, True

    def _watch_ingest_thread(self) -> None:
        """The calling (transport) thread joins the per-thread ledger's
        ``ingest`` role, whichever transport it is (one thread for zmq and
        the native poll loop, a pool's for grpc; watching twice is free)."""
        self._thread_ledger.watch("ingest", threading.current_thread())

    def _on_trajectory(self, agent_id: str, payload: bytes) -> None:
        self._watch_ingest_thread()
        with span("rl:ingest.admit", self.timings, "admit_s"):
            if self._fault_ingest is not None:
                # chaos plane: drop/delay/duplicate/corrupt AFTER the wire
                # — the frame arrived but the server mishandles it (actor
                # replay + dedup must make the loop whole again).
                for delay_s, part in self._fault_ingest.inject(payload):
                    if delay_s > 0:
                        time.sleep(delay_s)
                    self._ingest_one(agent_id, part)
                return
            self._ingest_one(agent_id, payload)

    def _check_ingest(self, tagged_id: str):
        """Guardrail admission verdict for ack-capable transports (the
        pure-grpcio servicer calls this BEFORE on_trajectory): ``None``
        admits; ``(nack_code, reason, retry_after_s)`` is returned to
        the sender as a typed nack the actor's spool understands
        (quarantine → discard the entry; overload → keep it, replay
        later). Broadcast planes — and the native C++ gRPC server,
        which acks in C++ before Python sees the send — never call
        this; the same verdicts are enforced server-side in _ingest_one.
        Runs on transport threads."""
        g = self.guardrails
        if g is None:
            return None
        from relayrl_tpu.transport.base import (
            NACK_OVERLOADED,
            NACK_QUARANTINED,
        )

        agent_id = split_agent_tags(tagged_id)[0]
        if self._halted:
            # NOT counted as a halted drop: an overload nack is retained
            # by the sender's spool and replayed — counting each replay
            # would read as unbounded data loss that never happened (the
            # genuine-shed sites in _ingest_one/_on_trajectory_decoded
            # own that counter).
            return (NACK_OVERLOADED, "guardrails halted", 30.0)
        if g.quarantine.is_quarantined(agent_id):
            g.quarantine.count_rejected_send()
            return (NACK_QUARANTINED, "agent quarantined",
                    g.quarantine.retry_after(agent_id))
        adm = g.admission
        if adm is not None and adm.policy == "nack":
            # Under the nack shed policy the back-channel IS the shed:
            # decide here so the sender's spool keeps the entry and
            # retries after the hint. (admit() only mutates shed
            # counters, so an "admit" verdict here followed by the
            # _ingest_one re-check is harmless.)
            verdict = adm.admit(agent_id)
            if verdict in ("nack", "shed_agent"):
                reason = ("agent over fair share"
                          if verdict == "shed_agent" else "ingest overloaded")
                return (NACK_OVERLOADED, reason, adm.retry_after_s)
        return None

    def _ingest_one(self, agent_id: str, payload: bytes,
                    depth: int = 0) -> None:
        if is_snapshot_frame(payload):
            # Fleet telemetry frame (ISSUE 15): route to the fleet table
            # BEFORE dedup/guardrails — telemetry carries no seqs, must
            # never strike a quarantine book, and a fleet-less server
            # treats it as inert noise rather than a decode failure
            # (which would count drops and could fire the drops alert
            # the frames exist to deliver).
            fleet = self._fleet
            if fleet is not None:
                try:
                    fleet.ingest_frame(payload)
                except ValueError as e:
                    swallow_decode_error(self.server_type, "fleet_frame", e)
            return
        if batch_kind(payload) == BATCH_KIND_ENVELOPES and depth < 8:
            # Relay upstream forward (ISSUE 11): one wire send carrying N
            # whole subtree envelopes, each with its leaf agent's id +
            # seq tag verbatim — split and run every inner envelope
            # through the normal per-agent funnel, so dedup/guardrails
            # see exactly what a flat fleet would have sent. Recursion
            # covers relay-behind-relay nesting; the depth cap is the
            # hostile-frame guard.
            try:
                parts = split_batch(payload)
            except ValueError as e:
                swallow_decode_error(self.server_type, "envelope_batch", e)
                self._count_dropped()
                return
            for part in parts:
                try:
                    inner_id, inner_payload = unpack_trajectory_envelope(part)
                except Exception as e:
                    swallow_decode_error(self.server_type,
                                         "envelope_batch", e)
                    self._count_dropped()
                    continue
                self._ingest_one(inner_id, inner_payload, depth=depth + 1)
            return
        # Trace hops (telemetry/trace.py): clock reads gate on a live
        # tracer, span recording on the envelope actually carrying a
        # sampled context — the untraced hot path pays one attribute
        # check plus (tracer live) one monotonic_ns. The origin itself
        # flows always: every update dispatch drains what it consumed.
        tracer = self._get_tracer()
        t_arr = time.monotonic_ns() if tracer.enabled else 0
        agent_id, seq, ctx, admit = self._admit_seq(agent_id)
        if tracer.enabled and ctx is not None and ctx.trace_id:
            t_ded = time.monotonic_ns()
            tracer.span("traj", ctx.trace_id, "ingest", t_arr, t_arr,
                        agent=agent_id, seq=seq)
            tracer.span("traj", ctx.trace_id, "dedup", t_arr, t_ded,
                        admitted=bool(admit))
        if not admit:
            return

        def retract():
            # un-see the seq: the actor's replay must be able to land
            # this trajectory later — a shed is backpressure, not dedup.
            if seq is not None and self._ingest_ledger is not None:
                self._ingest_ledger.retract(agent_id, seq)

        g = self.guardrails
        if g is not None:
            if self._halted:
                g._m_halted_drops.inc()
                retract()
                return
            if g.quarantine.is_quarantined(agent_id):
                # Broadcast planes (zmq PUSH, native) have no per-send
                # back-channel: the quarantine sheds here, silently to
                # the sender, loudly to telemetry.
                g.quarantine.count_rejected_send()
                retract()
                return
            if g.admission is not None:
                verdict = g.admission.admit(agent_id)
                if verdict in ("shed_agent", "nack"):
                    retract()
                    return
                if verdict == "evict":
                    self._evict_oldest_raw()
        try:
            self._ingest.put_nowait((agent_id, seq, ctx, payload))
            if g is not None and g.admission is not None:
                g.admission.note_enqueued(agent_id)
        except queue.Full:
            retract()
            self._count_dropped()

    def _evict_oldest_raw(self) -> None:
        """drop_oldest shed: evict the globally oldest queued raw payload
        to admit a fresh one (freshest-data-wins). The victim's seq is
        retracted from the dedup ledger so the owning actor's spool can
        redeliver it when pressure clears."""
        try:
            victim_id, victim_seq, _ctx, _ = self._ingest.get_nowait()
        except queue.Empty:
            return
        self._ingest.task_done()
        if victim_seq is not None and self._ingest_ledger is not None:
            self._ingest_ledger.retract(victim_id, victim_seq)
        adm = self.guardrails.admission if self.guardrails else None
        if adm is not None:
            adm.note_dequeued(victim_id)

    def _on_trajectory_decoded(self, batch) -> None:
        self._watch_ingest_thread()
        with span("rl:ingest.admit", self.timings, "admit_s"):
            self._admit_decoded(batch)

    def _admit_decoded(self, batch) -> None:
        """Pre-decoded columnar trajectory batch from the native drain —
        skips the staging thread entirely (one queue entry per drain).
        Sequence tags ride the decoded items' agent ids through the C++
        core; they are split + deduped here, and the clean id is written
        back so per-agent attribution stays tag-free downstream."""
        g = self.guardrails
        tracer = self._get_tracer()
        t_arr = time.monotonic_ns() if tracer.enabled else 0
        admitted = []
        for item in batch:
            clean_id, seq, ctx, admit = self._admit_seq(item.agent_id)
            if tracer.enabled and ctx is not None and ctx.trace_id:
                # The native C++ core already decoded this payload; the
                # ingest/dedup hops collapse to the drain's arrival.
                tracer.span("traj", ctx.trace_id, "ingest", t_arr, t_arr,
                            agent=clean_id, seq=seq)
                tracer.span("traj", ctx.trace_id, "dedup", t_arr,
                            time.monotonic_ns(), admitted=bool(admit))
            if ctx is not None and admit:
                item.trace_ctx = ctx
            if not admit:
                continue
            if clean_id != item.agent_id:
                item.agent_id = clean_id
            if g is not None:
                # Same guardrail funnel as the staged path: halted shed,
                # quarantine shed, then validation + strike accounting.
                # (Admission backpressure governs the raw ingest queue;
                # this plane delivers pre-decoded batches whose depth the
                # native core already bounds.)
                if self._halted:
                    g._m_halted_drops.inc()
                    continue
                if g.quarantine.is_quarantined(clean_id):
                    g.quarantine.count_rejected_send()
                    if seq is not None and self._ingest_ledger is not None:
                        self._ingest_ledger.retract(clean_id, seq)
                    continue
                if g.validate(clean_id, item) is None:
                    continue
            admitted.append((item, seq))
        if not admitted:
            return
        try:
            self._decoded.put_nowait([item for item, _ in admitted])
        except queue.Full:
            if self._ingest_ledger is not None:
                for item, seq in admitted:
                    if seq is not None:
                        self._ingest_ledger.retract(item.agent_id, seq)
            self._count_dropped(len(admitted))

    def _get_model(self) -> tuple[int, bytes]:
        """Current full model as v1 bundle bytes (handshakes, artifact
        writes, gRPC resyncs). Serialized lazily from the latest
        published host tree — at most once per version (barring a benign
        handshake race), and not at all for versions nobody handshakes
        during (the wire-v2 serialize saving; v1 publishes still store
        their bytes eagerly). The serialize itself runs OUTSIDE
        ``_bundle_lock``: a multi-second flax serialize of a large model
        under the lock would stall every version probe and the
        publisher's host-snapshot store."""
        with self._bundle_lock:
            host = self._bundle_host
            if host is None or host[0] == self._bundle_version:
                return self._bundle_version, self._bundle_bytes
        ver, arch, params = host
        from relayrl_tpu.types.model_bundle import ModelBundle

        raw = ModelBundle(version=ver, arch=dict(arch),
                          params=params).to_bytes()
        with self._bundle_lock:
            if ver > self._bundle_version:
                self._bundle_bytes = raw
                self._bundle_version = ver
            # A racing caller may have installed a newer version; the
            # cached pair is always internally consistent either way.
            return self._bundle_version, self._bundle_bytes

    def _get_model_update(self, known_version: int) -> tuple[int, bytes]:
        """Freshest blob a subscriber at ``known_version`` can decode:
        the latest wire frame when its base matches (or it is a
        keyframe), else the full v1 bundle (the server-side resync —
        cheaper than bouncing the subscriber through an extra RTT)."""
        enc = self._wire_encoder
        if enc is not None:
            got = enc.frame_for(known_version)
            if got is not None:
                return got
        return self._get_model()

    def _on_resync_request(self, held_version: int = -1) -> None:
        """CMD_RESYNC from the broadcast plane (zmq ROUTER thread): a
        subscriber's delta base diverged mid-stream — force the next
        publish to keyframe so it heals in <= 1 publish instead of <=
        keyframe_interval. ``held_version`` (the requester's, -1 when
        unknown) is only consulted by RELAYS; the root's forced keyframe
        heals any held version. Coalesced (force_keyframe is one flag
        per publish) and rate-limited
        (``transport.resync_min_interval_s``) so a storm of diverged
        subscribers grants one keyframe per window. A v1 server ignores
        it: every publish is already a full model."""
        self._m_resync_requests.inc()
        enc = self._wire_encoder
        if enc is None:
            return
        now = time.monotonic()
        with self._resync_lock:
            if now - self._last_resync_grant < self._resync_min_interval_s:
                return
            self._last_resync_grant = now
        enc.force_keyframe()
        self._m_resync_granted.inc()
        from relayrl_tpu import telemetry

        telemetry.emit("resync_keyframe_forced",
                       version=self.latest_model_version)

    @property
    def latest_model_version(self) -> int:
        """Version of the most recently published model — what an
        agent's hot-swap should converge to (embedder/eval surface).
        Reads the published host snapshot, not the lazily-serialized v1
        byte cache, which may trail it under wire v2."""
        with self._bundle_lock:
            if self._bundle_host is not None:
                return max(self._bundle_version, self._bundle_host[0])
            return self._bundle_version

    def _on_register(self, agent_id: str) -> None:
        with self._registry_lock:
            if agent_id not in self.agent_ids:
                self.agent_ids.append(agent_id)
                fresh = True
            else:
                fresh = False
        if fresh:
            from relayrl_tpu import telemetry

            telemetry.emit("agent_register", agent_id=agent_id,
                           registered=len(self.agent_ids))

    def _on_unregister(self, agent_id: str) -> None:
        """Elastic-fleet reaping (the reference's registry is append-only,
        training_server_wrapper.rs:159-163): a dead agent's id leaves the
        registry so long-lived fleets under churn don't accumulate
        ghosts."""
        with self._registry_lock:
            try:
                self.agent_ids.remove(agent_id)
            except ValueError:
                return
        from relayrl_tpu import telemetry

        telemetry.emit("agent_unregister", agent_id=agent_id,
                       registered=len(self.agent_ids))

    # -- staging: raw payload -> decoded trajectory (overlaps learner) --
    def _staging_loop(self) -> None:
        from relayrl_tpu.transport.base import BATCH_KIND_FRAMES
        from relayrl_tpu.types.columnar import (
            RawTrajectory,
            is_columnar_frame,
            parse_frame,
        )

        decoder = None
        try:
            from relayrl_tpu.types.columnar import NativeDecoder

            decoder = NativeDecoder()
        except Exception:
            pass  # native codec unavailable: pure-Python decode
        self.ingest_decoder = "python" if decoder is None else "native"
        guard = self.guardrails
        while not self._stop.is_set():
            try:
                agent_id, seq, ctx, payload = self._ingest.get(timeout=0.1)
            except queue.Empty:
                continue
            if guard is not None and guard.admission is not None:
                guard.admission.note_dequeued(agent_id)
            item = None
            columnar = False
            with span("rl:ingest.decode", metric=self._m_decode) as sp:
                try:
                    if is_columnar_frame(payload):
                        # Columnar wire fast path (anakin actors): the
                        # frame IS the folded column layout — a CRC check
                        # plus a handful of np.frombuffer views, no
                        # msgpack, no per-step objects, on every transport.
                        columnar = True
                        item = parse_frame(payload, agent_id=agent_id)
                        self._m_columnar_frames.inc()
                        self._m_columnar_bytes.inc(len(payload))
                    elif batch_kind(payload) == BATCH_KIND_FRAMES:
                        # Coalesced columnar segments (actor.emit_
                        # coalesce_frames / relay batch-forward): one
                        # spooled send — one seq, one envelope — carrying N
                        # frames of ONE logical lane; decode each and hand
                        # the learner the list (the native drain's batch
                        # shape).
                        columnar = True
                        parts = split_batch(payload)
                        item = [parse_frame(p, agent_id=agent_id)
                                for p in parts]
                        self._m_columnar_frames.inc(len(parts))
                        self._m_columnar_bytes.inc(len(payload))
                    elif decoder is not None:
                        # off-GIL msgpack -> columns; falls back to the
                        # Python decoder only for payloads the columnar
                        # schema can't represent
                        with span("rl:ingest.decode_native"):
                            item = decoder.decode(payload,
                                                  agent_id=agent_id)
                        if isinstance(item, RawTrajectory):
                            raw = item.payload
                            if item.is_envelope:
                                from relayrl_tpu.transport.base import (
                                    unpack_trajectory_envelope,
                                )

                                _, raw = unpack_trajectory_envelope(raw)
                            item = deserialize_actions(raw)
                    else:
                        item = deserialize_actions(payload)
                except Exception:
                    if columnar:
                        self._m_columnar_rejects.inc()
                    # Un-see the seq: the payload never reached the
                    # learner (CRC/parse failure), so the actor's spool
                    # replay must be able to land its retained clean copy
                    # later.
                    if seq is not None and self._ingest_ledger is not None:
                        self._ingest_ledger.retract(agent_id, seq)
                    self._count_dropped()
                if item is not None and guard is not None:
                    # Ingest validation + per-agent strike accounting:
                    # the semantic trust boundary, BEFORE the decoded item
                    # can reach the staging slabs. None = rejected (counted,
                    # struck; the poison never reaches the learner plane).
                    # Coalesced batches validate per contained trajectory —
                    # one poisoned segment must not veto its clean siblings.
                    if (isinstance(item, list) and item
                            and isinstance(item[0], DecodedTrajectory)):
                        item = [one for one in item if
                                guard.validate(agent_id, one) is not None]
                        if not item:
                            item = None
                    else:
                        item = guard.validate(agent_id, item)
                if ctx is not None and item is not None:
                    # staging hop (decode + validate) + origin handoff:
                    # the learner attributes the consuming update at
                    # dispatch.
                    if ctx.trace_id:
                        sp.hop("traj", ctx.trace_id, "staging",
                               agent=agent_id)
                    item = _attach_trace_ctx(item, ctx)
            with self._timings_lock:  # N decode workers share the ledger
                self.timings["decode_s"] += sp.seconds
            if item is not None:
                try:
                    self._decoded.put_nowait(item)
                except queue.Full:
                    # Same contract as every other shed path: un-see the
                    # seq so the sender's spool replay can land this
                    # trajectory once pressure clears (a shed is
                    # backpressure, not loss).
                    if seq is not None and self._ingest_ledger is not None:
                        self._ingest_ledger.retract(agent_id, seq)
                    self._count_dropped()
            # task_done only after the decoded item is enqueued, so
            # drain()'s two-queue emptiness check never races the handoff
            self._ingest.task_done()

    # -- multi-host learner loop (SPMD broadcast protocol) --
    # Every process loops in lockstep on a fixed-shape control broadcast:
    # IDLE ticks keep non-coordinators synchronized while the coordinator
    # accumulates trajectories; STEP carries the batch shape, then the
    # batch itself, then all processes run the sharded update + the
    # collective bundle all-gather; STOP tears everyone down together.
    _MH_IDLE, _MH_STEP, _MH_STOP = 0, 1, 2

    def _mh_accumulate(self, item) -> dict | None:
        """Coordinator: feed one decoded queue entry into the algorithm
        buffer; returns a ready training batch dict (at most one per call
        — extras queue in _mh_ready). On-policy accumulate yields one
        epoch batch; off-policy yields a LIST of sampled transition
        batches (the update-to-data ratio's worth)."""
        items = (item if (isinstance(item, list) and item
                          and isinstance(item[0], DecodedTrajectory))
                 else [item])
        for one in items:
            self.stats["trajectories"] += 1
            self._m_trajectories.inc()
            try:
                got = self.algorithm.accumulate(one)
            except Exception as e:
                self._learner_error("accumulate error", e)
                continue
            finally:
                self._sync_drop_stats()
            if isinstance(got, list):
                self._mh_ready.extend(got)
            elif got is not None:
                self._mh_ready.append(got)
        return self._mh_ready.pop(0) if self._mh_ready else None

    def _learner_loop_multihost(self) -> None:
        import numpy as np

        from relayrl_tpu.parallel.distributed import (
            broadcast_from_coordinator,
            is_coordinator,
        )

        coord = is_coordinator()
        while True:
            batch = None
            if coord:
                # STOP preempts any ingest backlog: disable_server must
                # terminate the fleet within one in-flight step, not
                # after draining hundreds of queued trajectories.
                if not self._stop.is_set():
                    if self._mh_ready:
                        # _mh_busy flips BEFORE the batch leaves the
                        # queues (here and below, ahead of task_done):
                        # drain() checks queues-empty AND ready-empty AND
                        # not-busy, so a gap between "popped" and "busy"
                        # would let it report drained with a step pending.
                        self._mh_busy = True
                        batch = self._mh_ready.pop(0)
                    tick_deadline = time.monotonic() + 0.2
                    while batch is None and time.monotonic() < tick_deadline:
                        try:
                            item = self._decoded.get(timeout=0.05)
                        except queue.Empty:
                            continue
                        try:
                            batch = self._mh_accumulate(item)
                            if batch is not None:
                                self._mh_busy = True
                        finally:
                            self._decoded.task_done()
                code = (self._MH_STOP if self._stop.is_set()
                        else self._MH_STEP if batch is not None
                        else self._MH_IDLE)
                desc = np.array(
                    [code,
                     batch["obs"].shape[0] if batch is not None else 0,
                     batch["obs"].shape[1] if batch is not None else 0],
                    np.int64)
            else:
                desc = np.zeros(3, np.int64)
            desc = broadcast_from_coordinator(desc)
            code = int(desc[0])
            if code == self._MH_STOP:
                self._mh_busy = False  # a preempted batch is dropped
                # Fence what was dispatched and flush its deferred logs
                # (every rank drains its own window — the programs were
                # dispatched symmetrically, so they all complete), then
                # resolve the fenced probes before shutdown.
                self._pipeline_quiesce()
                if coord:
                    self._guard_poll()
                break
            if code == self._MH_IDLE:
                # Idle is fence-for-free, as in the single-host loop: the
                # device has nothing queued behind the in-flight sharded
                # updates, so resolving them costs no overlap — and it is
                # what lets drain() observe pending -> 0 on every rank.
                self._pipeline_quiesce()
                if coord:
                    self._guard_poll()
                continue
            if not coord:
                batch = self.algorithm.mh_zero_batch(int(desc[1]),
                                                     int(desc[2]))
            self._mh_busy = True
            batch = broadcast_from_coordinator(batch)
            algo = self.algorithm
            with span("rl:learner.dispatch", self.timings, "dispatch_s",
                      metric=self._m_dispatch):
                try:
                    # Eager sharded H2D (device_put with NamedSharding
                    # via the mesh-aware _place): the transfer enqueues
                    # now and overlaps the in-flight updates instead of
                    # running inside the dispatch below.
                    batch = algo.stage_batch(batch)
                    # Dispatch-only: the sharded update enters the in-flight
                    # window unfenced (its collectives live inside the XLA
                    # program, so nothing here blocks the host).
                    algo.train_on_batch(batch)
                except Exception as e:
                    self._learner_error("multi-host update error", e)
                    self._mh_busy = False
                    # symmetric on all ranks: same data, same failure
                    continue
                if (coord and self.guardrails is not None
                        and self.guardrails.watchdog is not None
                        and self.distributed_info["num_processes"] == 1):
                    # Health probes ride LazyMetrics through the window on
                    # every rank (they are jitted over the same sharded
                    # state). The watchdog DETECTOR stays single-process:
                    # its rollback path restores a checkpoint, which is a
                    # collective a coordinator-solo trip would hang on.
                    self.guardrails.watchdog.observe_dispatch(
                        algo.inflight.dispatch_count, algo._last_metrics)
                if coord:
                    self.stats["updates"] += 1
                    self._m_updates.inc()
                    # Epoch log: captured now (on-policy: one per update;
                    # off-policy: the trajectory cadence), dumped once the
                    # update it describes is fenced.
                    payload = algo.capture_epoch_stats(True)
                    if payload is not None:
                        self._pending_logs.append(
                            (algo.inflight.dispatch_count, payload,
                             algo._last_metrics))
            try:
                if self._async_publish:
                    # The publish gather (jitted re-shard to replicated)
                    # is a collective DISPATCH on every rank — symmetric
                    # by construction since async_publish comes from the
                    # shared config; only the coordinator owns a
                    # transport, so only it hands the snapshot to the
                    # publisher thread (D2H + encode off this thread).
                    snapshot = algo.snapshot_for_publish()
                    if coord and self._publisher is not None:
                        self._publisher.submit(snapshot)
                    ckpt_version = algo.dispatched_version
                else:
                    bundle = algo.bundle()  # collective + fences (escape
                    if coord:               # hatch: async_publish false)
                        import jax

                        self._publish_params(bundle.version, bundle.arch,
                                             jax.device_get(bundle.params))
                    ckpt_version = bundle.version
            except Exception as e:
                self._publish_error("publish error", e)
                ckpt_version = algo.dispatched_version
            # Full-state checkpoint is COLLECTIVE on a multi-host mesh
            # (orbax needs every process to contribute its shards to the
            # shared checkpoint_dir); the due-check derives from the
            # host-side version mirror, which advances identically on
            # every rank, so all agree without extra coordination — and
            # the checkpoint path quiesces the window first, extending
            # the quiesce contract to in-flight sharded updates.
            self._maybe_periodic_checkpoint(ckpt_version)
            if coord:
                self._flush_ready_logs()
                self._guard_poll()
            self._mh_busy = False

    # -- learner loop --
    def _learner_loop(self) -> None:
        if not self._warmup_done.is_set():
            # Pre-compile the update for every shape the first epochs can
            # hit, while the fleet is still handshaking/playing its first
            # episodes. Without this, the first compile lands under ingest
            # load — and in a one-process deployment (notebook kernel
            # hosting server + busy actor loop on a small host) a ~2 s
            # compile competing with the actor loop for CPU can stretch
            # past the whole example run, so no update ever happens live.
            n = 0
            try:
                with span("host:warmup", self.timings, "warmup_s") as sp:
                    n = self.algorithm.warmup(
                        should_continue=lambda: (
                            self._decoded.empty() and self._ingest.empty()
                            and not self._stop.is_set()))
            except Exception as e:
                # The update did not compile or did not run (a kernel the
                # compiler refuses, device OOM): every real batch would
                # fail the same way, so this learner is dead — say so in
                # stats, hand the error to wait_warmup(), and let it end
                # the thread instead of ingesting forever.
                self.stats["warmup_failed"] += 1
                self._warmup_error = e
                raise
            finally:
                self._warmup_done.set()
            if n:
                print(f"[TrainingServer] warmup: {n} update shape(s) "
                      f"compiled in {sp.seconds:.1f}s", flush=True)
        while not self._stop.is_set():
            try:
                with span("host:wait_data", self.timings,
                          "learner_idle_s") as wait:
                    item = self._decoded.get(timeout=0.1)
            except queue.Empty:
                # Idle is fence-for-free: the device has nothing queued
                # behind the in-flight updates, so resolving them (and
                # flushing their deferred epoch logs) costs no overlap —
                # and it is what lets drain() observe pending -> 0.
                try:
                    with span("host:quiesce"):
                        self._pipeline_quiesce()
                except Exception as e:
                    # An update that failed ON the device surfaces at its
                    # fence, which under async dispatch is usually here.
                    self._learner_error("update failed at its fence", e)
                    continue
                # Everything dispatched is now fenced: resolve every
                # pending health probe (free post-fence) and act on trips.
                self._guard_poll()
                continue
            if self._halted:
                # Degraded halt-and-alarm: training is stopped (rollback
                # budget spent / no healthy checkpoint); drain and drop
                # so the queues don't balloon while the operator digs.
                if self.guardrails is not None:
                    self.guardrails._m_halted_drops.inc(
                        len(item) if isinstance(item, list) else 1)
                self._decoded.task_done()
                continue
            # A native drain batch is a list of DecodedTrajectory; a
            # Python-decoded single trajectory is a list of ActionRecord
            # (and a staged columnar one is a bare DecodedTrajectory) —
            # disambiguate on the element type.
            batch = (item if isinstance(item, list) and item
                     and isinstance(item[0], DecodedTrajectory) else [item])
            queued_ns = wait.t1_ns - self._decoded.got_put_ns
            try:
                with span("rl:learner.item", queued_us=queued_ns // 1000,
                          n=len(batch)):
                    for one in batch:
                        self._process_one(one)
            finally:
                self._decoded.task_done()
        # Shutdown: fence what was dispatched and flush its logs so
        # disable_server leaves state/progress.txt consistent — then
        # resolve the fenced probes, so the signal-path final save's
        # healthy-at-save tag covers every update baked into it (a
        # poisoned last update must trip here, not get tagged healthy).
        self._pipeline_quiesce()
        self._guard_poll()

    def _observe_behavior_lag(self, item, algo, ctx=None) -> None:
        """RLHF-plane off-policy evidence: trajectories whose records
        carry ``bver`` (the params version the generation sampled
        under — rlhf/scheduler.py stamps it per token) observe
        ``dispatched_version - bver`` into the train-lag histogram, one
        sample per trajectory. A sampled trace context's born_version
        (stamped at emission, telemetry/trace.py) is the same kind of
        behavior-version evidence, so bver-less traced trajectories
        feed the histogram too — the analyzer's version-lag
        distribution and this histogram then describe the same data.
        Non-RLHF untraced traffic pays one dict lookup."""
        try:
            if isinstance(item, DecodedTrajectory):
                arr = (item.aux or {}).get("bver")
                if arr is None or len(arr) == 0:
                    if ctx is not None and ctx.born_version >= 0:
                        self._m_rlhf_train_lag.observe(
                            max(0, algo.dispatched_version
                                - ctx.born_version))
                    return
                bver = int(arr.reshape(-1)[0])
            else:
                data = item[0].data if item else None
                if not data or "bver" not in data:
                    if ctx is not None and ctx.born_version >= 0:
                        self._m_rlhf_train_lag.observe(
                            max(0, algo.dispatched_version
                                - ctx.born_version))
                    return
                bver = int(data["bver"])
            self._m_rlhf_train_lag.observe(
                max(0, algo.dispatched_version - bver))
        except Exception:
            # Lag evidence is diagnostics; malformed aux must never
            # touch the ingest path's health.
            pass

    def _on_dispatch(self, t0_ns: int) -> dict:
        """What the server does once an update dispatch, on the learner
        thread, when the algorithm opens its ``host:dispatch`` span:
        refresh the per-thread CPU ledger and answer the span's data-age
        arguments."""
        self._thread_ledger.refresh(self.timings)
        return self._note_data_age(t0_ns)

    def _note_data_age(self, t0_ns: int) -> dict:
        """The data age of the batch an update dispatch consumes, computed
        once for every consumer (learner thread, called by the algorithm
        at the start of its ``host:dispatch`` span with that span's start
        stamp): the born stamps of the trajectories accumulated since the
        previous dispatch give the span's ``data_age_us`` (mean) and
        ``data_age_max_us`` arguments — what the benchmark's
        ``data_age_ms`` reads — and feed the two data-age histograms.
        Same-host skew-guarded: a cross-host born stamp is dropped, not
        observed."""
        fresh = list(self._trace_pending)
        self._trace_pending.clear()
        self._dispatched_origins += fresh
        consume_ver = self.algorithm.dispatched_version
        ages = []
        for ctx in fresh:
            age_ns = t0_ns - ctx.born_ns
            if 0 <= age_ns < SKEW_GUARD_NS:
                ages.append(age_ns)
                self._m_data_age.observe(age_ns * 1e-9)
                if ctx.born_version >= 0:
                    self._m_data_lag.observe(
                        float(max(0, consume_ver - ctx.born_version)))
        if not ages:
            return {}
        return {"data_age_us": sum(ages) // len(ages) // 1000,
                "data_age_max_us": max(ages) // 1000}

    def _trace_dispatch(self, tracer, algo, t0_ns: int, t1_ns: int,
                        consume_ver: int) -> None:
        """Close out the bookkeeping of one update dispatch (learner
        thread): forget the origins it consumed and, where the tracer is
        live, record the downstream ``dispatch`` hop for a sampled
        version and the upstream ``update`` hop for every sampled
        trajectory among them. The stamps are the ``rl:learner.dispatch``
        span's."""
        from relayrl_tpu.telemetry.trace import model_trace_id

        origins = self._dispatched_origins
        origins += self._trace_pending  # an algorithm that notes no data age
        self._trace_pending.clear()
        if tracer.enabled:
            ver = algo.dispatched_version
            if tracer.sample_version(ver):
                tracer.span("model", model_trace_id(ver), "dispatch",
                            t0_ns, t1_ns, version=int(ver))
            for ctx in origins:
                if ctx.trace_id:
                    # version = the version the batch trained FROM
                    # (matching the train_version_lag convention), not
                    # the freshly-minted one.
                    tracer.span("traj", ctx.trace_id, "update", t0_ns,
                                t1_ns, version=int(consume_ver))
        origins.clear()

    def _learner_error(self, what: str, e: Exception) -> None:
        """One bad batch must not kill the learner loop — but it is
        counted where an operator (and ``chip_smoke.py``) looks, not only
        printed (learner thread)."""
        self.stats["learner_errors"] += 1
        self._m_learner_errors.inc()
        print(f"[TrainingServer] {what}: {e!r}", flush=True)

    def _publish_error(self, what: str, e: Exception) -> None:
        """Same contract for a publish that raised on the learner thread
        (the publisher thread counts its own in ``_publish_snapshot``)."""
        self.stats["publish_errors"] += 1
        self._m_publish_errors.inc()
        print(f"[TrainingServer] {what}: {e!r}", flush=True)

    def _sync_drop_stats(self) -> None:
        """Mirror the algorithm's finite-guard counter into stats — the
        single owner, so every ingest path (single-host, multi-host, any
        future drain) keeps the operator-visible counter fresh."""
        self.stats["dropped_nonfinite"] = getattr(
            self.algorithm, "dropped_nonfinite", 0)
        self._m_nonfinite.set(self.stats["dropped_nonfinite"])

    def _process_one(self, item) -> None:
        """``item``: DecodedTrajectory (columnar fast path) or
        list[ActionRecord] (Python decode). Dispatch-only: the update
        enters the algorithm's in-flight window unfenced, the publish is
        handed to the latest-wins publisher thread, and the epoch log
        defers until the update's fence."""
        algo = self.algorithm
        if not hasattr(algo, "accumulate"):
            # Plugin algorithms implementing only the reference contract
            # (receive_trajectory/train_model/save/log_epoch) keep the
            # original synchronous path — pipelining needs the family
            # accumulate/capture split.
            self._process_one_legacy(item)
            return
        self.stats["trajectories"] += 1
        self._m_trajectories.inc()
        ctx = getattr(item, "trace_ctx", None)
        if ctx is not None:
            self._trace_pending.append(ctx)
        self._observe_behavior_lag(
            item, algo, ctx if ctx is not None and ctx.trace_id else None)
        tracer = self._get_tracer()
        # The version this batch trains FROM (pre-dispatch) — the
        # convention _observe_behavior_lag's histogram uses, so the
        # trace-side version-lag distribution matches it exactly.
        consume_ver = algo.dispatched_version if tracer.enabled else 0
        # dispatch_s: accumulate + stage + enqueue + window fence + epoch
        # capture. It ends before the publish handoff: that is a lock'd
        # slot swap, but a due checkpoint quiesces + saves — seconds of
        # fence/IO that must not masquerade as host-side enqueue (the
        # window fence is also accounted in device_wait_s).
        with span("rl:learner.dispatch", self.timings, "dispatch_s",
                  metric=self._m_dispatch) as sp:
            try:
                got = algo.accumulate(item)
                updated = got is not None
                if updated:
                    batches = got if isinstance(got, list) else [got]
                    # Eager H2D: enqueued now, the transfer overlaps the
                    # in-flight updates instead of running after the window
                    # fence below.
                    batches = [algo.stage_batch(b) for b in batches]
                    if isinstance(got, list):
                        algo.train_on_batches(batches)
                    else:
                        algo.train_on_batch(batches[0])
            except Exception as e:  # never kill the loop on one bad batch
                self._learner_error("learner error", e)
                return
            finally:
                self._sync_drop_stats()
            if (updated and self.guardrails is not None
                    and self.guardrails.watchdog is not None):
                # Queue the dispatched update's (lazy) metrics — probe
                # scalars included — for the watchdog; they resolve at the
                # in-flight fence, never here (the LazyMetrics deferral).
                self.guardrails.watchdog.observe_dispatch(
                    algo.inflight.dispatch_count, algo._last_metrics)
            # Epoch log: captured now (episode counters must not leak
            # across epochs), dumped once the update it describes is fenced.
            payload = algo.capture_epoch_stats(updated)
            if payload is not None:
                self._pending_logs.append(
                    (algo.inflight.dispatch_count, payload,
                     algo._last_metrics))
        if updated:
            self._trace_dispatch(tracer, algo, sp.t0_ns, sp.t1_ns,
                                 consume_ver)
        if updated:
            self.stats["updates"] += 1
            self._m_updates.inc()
            try:
                if self._publisher is not None:
                    with span("host:publish_submit"):
                        self._publisher.submit(algo.snapshot_for_publish())
                    # Full-state checkpointing stays on the learner
                    # thread (orbax save is not publisher-safe); gate on
                    # the host version mirror — int(state.step) would
                    # fence the window.
                    self._maybe_periodic_checkpoint(algo.dispatched_version)
                else:
                    self._publish()  # sync escape hatch (async_publish off)
            except Exception as e:  # transient socket/fs errors must not
                self._publish_error("publish error", e)
        self._flush_ready_logs()
        self._guard_poll()

    def _process_one_legacy(self, item) -> None:
        """Pre-pipeline path for plugin algorithms: train + log inside
        receive_trajectory, synchronous publish."""
        self.stats["trajectories"] += 1
        self._m_trajectories.inc()
        try:
            updated = self.algorithm.receive_trajectory(item)
        except Exception as e:  # never kill the loop on one bad batch
            self._learner_error("learner error", e)
            return
        finally:
            self._sync_drop_stats()
        if updated:
            self.stats["updates"] += 1
            self._m_updates.inc()
            try:
                self._publish()
            except Exception as e:  # transient socket/fs errors must not
                self._publish_error("publish error", e)
            if self._tb is not None:
                try:
                    self._tb.poll()
                except Exception as e:
                    print(f"[TrainingServer] tensorboard error: {e!r}",
                          flush=True)

    def _flush_ready_logs(self, force: bool = False) -> None:
        """Dump deferred epoch logs whose update has been fenced by the
        in-flight window (FIFO — rows land in dispatch order). Runs on
        the learner thread only."""
        win = self.algorithm.inflight
        dumped = False
        while self._pending_logs:
            after_dispatch, payload, metrics = self._pending_logs[0]
            if not force and after_dispatch > win.fenced_count:
                break
            self._pending_logs.popleft()
            try:
                with span("host:epoch_log"):
                    self.algorithm.log_epoch(stats=payload, metrics=metrics)
                dumped = True
            except Exception as e:
                print(f"[TrainingServer] log error: {e!r}", flush=True)
        if dumped and self._tb is not None:
            try:
                self._tb.poll()
            except Exception as e:
                print(f"[TrainingServer] tensorboard error: {e!r}",
                      flush=True)
        self.timings["device_wait_s"] = win.device_wait_s
        if self._publisher is not None:
            self.timings["publish_s"] = self._publisher.publish_s

    def _pipeline_quiesce(self) -> None:
        """Fence every in-flight update and flush the deferred logs —
        called when the learner is idle or exiting (learner thread only)."""
        win = getattr(self.algorithm, "_inflight", None)
        if win is not None and win.pending:
            win.drain()
        if self._pending_logs:
            self._flush_ready_logs(force=True)

    # -- divergence watchdog + last-known-good rollback (learner thread) --
    def _guard_poll(self) -> bool:
        """Resolve fenced health probes and evaluate the watchdog's
        detectors; a Trip executes the rollback path (or the degraded
        halt). True when a trip fired — callers gating a checkpoint on
        health skip the save then. Learner thread only."""
        g = self.guardrails
        if g is None or g.watchdog is None or self._halted:
            return False
        win = getattr(self.algorithm, "_inflight", None)
        fenced = win.fenced_count if win is not None else 0
        with span("host:guard_poll"):
            trip = g.watchdog.poll(fenced)
            if trip is None:
                return False
            self._execute_rollback(trip)
            return True

    def _execute_rollback(self, trip) -> None:
        """The watchdog tripped: halt dispatch, restore the newest
        healthy-tagged checkpoint AND its dedup-ledger sidecar, fast-
        forward the version past the poisoned line, force a model-wire
        keyframe so actors resync off the poisoned delta chain, publish
        the restored params, and resume. Bounded: more than
        ``max_rollbacks`` inside ``rollback_window_s`` (or no healthy
        checkpoint to restore) degrades to halt-and-alarm. Learner
        thread only — nothing else dispatches while this runs."""
        from relayrl_tpu import telemetry

        g = self.guardrails
        # 1. Halt dispatch: fence everything in flight, drop the deferred
        # logs (they describe the rolled-back line of history), and let
        # the publisher finish so no poisoned-line publish races the
        # restored one.
        win = getattr(self.algorithm, "_inflight", None)
        if win is not None and win.pending:
            win.drain()
        self._pending_logs.clear()
        if self._publisher is not None:
            self._publisher.drain(timeout=30.0)
        if not g.params["rollback"] or not self._checkpoint_dir:
            self._enter_halt(trip, "rollback disabled")
            return
        now = time.monotonic()
        window = g.params["rollback_window_s"]
        self._rollback_times = [t for t in self._rollback_times
                                if now - t < window]
        if len(self._rollback_times) >= g.params["max_rollbacks"]:
            self._enter_halt(trip, "rollback budget spent")
            return
        self._rollback_times.append(now)
        # 2. Restore the newest healthy step (settle any in-flight async
        # save first so the step listing is complete).
        mgr = getattr(self.algorithm, "_ckpt_mgr", None)
        if mgr is not None:
            try:
                mgr.wait()
            except Exception:
                pass
        try:
            from relayrl_tpu.checkpoint import restore_latest_healthy

            step = restore_latest_healthy(self.algorithm,
                                          self._checkpoint_dir)
        except FileNotFoundError:
            self._enter_halt(trip, "no healthy checkpoint retained")
            return
        except Exception as e:
            self._enter_halt(trip, f"restore failed: {e!r}")
            return
        # 3. The dedup ledger must match the restored params' line of
        # history (PR 6's consistency contract): a newer ledger would
        # dedup (lose) trajectories whose updates just rolled back.
        self._load_ledger_sidecar(step)
        # 4. Fast-forward the version PAST anything the poisoned line
        # published, so actor swap gates and checkpoint step numbering
        # stay monotonic (step numbers are labels; the state is the
        # restored tree).
        new_version = max(self.latest_model_version,
                          int(self.algorithm.version)) + 1
        self.algorithm.force_version(new_version)
        # 5. Host-side ingest state part-filled by the poisoned stream
        # belongs to the rolled-back line.
        self.algorithm.reset_ingest_buffers()
        # 6. Re-arm BEFORE the publish below: its checkpoint due-check
        # re-enters _guard_poll, and a watchdog still holding poisoned-
        # line probes would recurse straight back into rollback. The
        # detector windows describe the dead line anyway, and the
        # re-anchored distance gates put the restored line on its own
        # checkpoint cadence.
        g.watchdog.reset_after_rollback()
        self._ckpt_version = new_version
        self._artifact_version = new_version
        # 7. Forced keyframe + immediate publish: every actor resyncs to
        # the restored params regardless of what deltas it held.
        if self._wire_encoder is not None:
            self._wire_encoder.force_keyframe()
        try:
            self._publish()
        except Exception as e:
            print(f"[TrainingServer] rollback publish error: {e!r}",
                  flush=True)
        self._rollbacks_total += 1
        g._m_rollbacks.inc()
        telemetry.emit("rollback", signal=trip.signal, value=trip.value,
                       threshold=trip.threshold, restored_step=int(step),
                       new_version=int(new_version),
                       attempt=len(self._rollback_times))
        print(f"[TrainingServer] ROLLBACK #{self._rollbacks_total}: "
              f"{trip.signal} tripped → restored healthy step {step}, "
              f"resuming as version {new_version}", flush=True)

    def _enter_halt(self, trip, reason: str) -> None:
        """Degrade to halt-and-alarm: training stops, ingest sheds, the
        process survives for operator forensics (docs/operations.md
        runbook). One-way until an operator restarts the server."""
        from relayrl_tpu import telemetry

        self._halted = True
        g = self.guardrails
        g._m_halted.set(1)
        telemetry.emit("guardrails_halt", signal=trip.signal,
                       value=trip.value, reason=reason,
                       rollbacks=self._rollbacks_total)
        print(f"[TrainingServer] GUARDRAILS HALT ({reason}): "
              f"{trip.signal} tripped and recovery is exhausted — "
              f"training stopped, ingest shedding, process alive for "
              f"inspection", flush=True)

    @property
    def guardrails_halted(self) -> bool:
        return self._halted

    def guardrails_accounting(self) -> dict:
        """Guardrail evidence block for drills and status loops:
        validation + quarantine + watchdog + admission accounting plus
        the server-side rollback/halt ledger. Empty when disabled."""
        g = self.guardrails
        if g is None:
            return {}
        out = g.accounting()
        out["rollbacks_total"] = self._rollbacks_total
        out["halted"] = self._halted
        return out

    def _learner_pending(self) -> int:
        """Dispatched-but-unfenced updates + deferred logs + queued or
        in-progress publishes — the single-host half of the drain()
        contract (the multi-host half is _mh_ready/_mh_busy)."""
        win = getattr(self.algorithm, "_inflight", None)
        n = (win.pending if win is not None else 0) + len(self._pending_logs)
        if self._publisher is not None:
            n += self._publisher.pending
        return n

    def drain(self, timeout: float = 60.0) -> bool:
        """Block until every trajectory already in the ingest pipeline
        (raw + decoded queues) has been processed (trained + published):
        dispatched updates fenced, deferred epoch logs dumped, and the
        final (latest-wins) model publish landed. True if drained within
        timeout.

        Note this covers trajectories the server has *received*; bytes still
        in transit in socket buffers are invisible here, so to observe an
        exact update count poll ``stats['updates']`` first, then drain."""
        from relayrl_tpu import telemetry

        t0 = time.monotonic()
        deadline = t0 + timeout
        while time.monotonic() < deadline:
            if (self._ingest.unfinished_tasks == 0
                    and self._decoded.unfinished_tasks == 0
                    # single-host pipeline: dispatched-but-unfenced
                    # updates, deferred logs, pending publishes (the
                    # learner thread fences + flushes on its idle tick)
                    and self._learner_pending() == 0
                    # multi-host: assembled-but-untrained epoch batches and
                    # the broadcast step in flight also count as pending
                    and not self._mh_ready
                    and not self._mh_busy):
                self._flush_drop_event()
                telemetry.emit("drain",
                               wait_s=round(time.monotonic() - t0, 3),
                               updates=self.stats["updates"])
                return True
            time.sleep(0.05)
        return False

    # -- idempotent-ingest ledger persistence (crash-recovery plane) --
    def _ledger_sidecar_path(self, version: int) -> str:
        return os.path.join(self._checkpoint_dir,
                            f"ingest_ledger_{int(version)}.json")

    def _save_ledger_sidecar(self, version: int) -> None:
        """Snapshot the dedup ledger next to the checkpoint at
        ``version`` (atomic write; older sidecars pruned to the
        checkpoint retention depth). Keyed BY VERSION so a resume
        restores exactly the dedup state consistent with the restored
        params — a newer ledger would dedup (lose) trajectories whose
        updates rolled back; an older one would double-train."""
        if self._ingest_ledger is None or not self._checkpoint_dir:
            return
        try:
            self._ingest_ledger.save(self._ledger_sidecar_path(version))
            import glob

            sidecars = sorted(
                glob.glob(os.path.join(self._checkpoint_dir,
                                       "ingest_ledger_*.json")),
                key=lambda p: int(p.rsplit("_", 1)[1].split(".")[0]))
            for stale in sidecars[:-max(2, self._ckpt_keep)]:
                os.remove(stale)
        except (OSError, ValueError) as e:
            print(f"[TrainingServer] ingest-ledger sidecar write failed: "
                  f"{e!r}", flush=True)

    def _load_ledger_sidecar(self, version: int) -> None:
        """Restore the ledger matching the resumed version; a missing
        sidecar (pre-recovery checkpoints) starts empty — replays of
        already-trained trajectories then train again, which the runbook
        documents as the bounded cost of a ledgerless resume."""
        if self._ingest_ledger is None or not self._checkpoint_dir:
            return
        path = self._ledger_sidecar_path(version)
        try:
            from relayrl_tpu.runtime.spool import SequenceLedger

            self._ingest_ledger = SequenceLedger.load(path)
            print(f"[TrainingServer] ingest ledger restored "
                  f"({len(self._ingest_ledger.counts())} agent(s), "
                  f"version {version})", flush=True)
        except FileNotFoundError:
            print(f"[TrainingServer] no ingest-ledger sidecar at version "
                  f"{version}; dedup starts empty (replays of "
                  f"already-trained trajectories will re-train)",
                  flush=True)
        except (OSError, ValueError, KeyError) as e:
            print(f"[TrainingServer] ingest-ledger sidecar unreadable: "
                  f"{e!r}; dedup starts empty", flush=True)

    def ingest_accounting(self) -> dict:
        """Sequence accounting for drills: per-agent
        ``{max_seq, accepted, contiguous}`` + duplicate count. Empty when
        dedup is disabled."""
        if self._ingest_ledger is None:
            return {"agents": {}, "duplicates": 0}
        return {"agents": self._ingest_ledger.counts(),
                "duplicates": self._ingest_ledger.total_duplicates()}

    def _write_model_artifact(self, raw: bytes, version: int) -> None:
        """Periodic on-disk model bytes (ref: server reads the .pt file to
        serve agents, training_zmq.rs:905-919; for us handshakes are
        served from memory and the file is a resume/debug aid). Reuses the
        (lazily) serialized v1 bytes, throttled by
        learner.checkpoint_every_epochs. Distance-gated, not
        modulo-gated: latest-wins publish coalescing makes published
        versions an arbitrary subsequence, so waiting for a version
        divisible by the cadence could starve the file forever (with
        every version published the two rules write identically)."""
        if version - self._artifact_version < self._checkpoint_every:
            return
        if raw is None:
            raw = self._get_model()[1]
        try:
            path = self.algorithm.server_model_path
            tmp = f"{path}.tmp"
            with open(tmp, "wb") as f:
                f.write(raw)
            os.replace(tmp, path)
            self._artifact_version = version
        except OSError:
            pass

    def _publish_params(self, version: int, arch: dict, host_params) -> None:
        """The ONE broadcast path (pipelined, synchronous, and multi-host
        publishes all land here with a host params tree). Wire v2: the
        encoder turns the publish into a keyframe or per-leaf delta frame
        off the learner thread; the full v1 bundle serializes lazily only
        when a handshake, artifact write, or native set_model needs it.
        Wire v1: the legacy full-bundle bytes ship on every publish."""
        from relayrl_tpu import telemetry

        from relayrl_tpu.guardrails.validate import params_tree_finite

        g = self.guardrails
        if g is not None and not params_tree_finite(host_params):
            # The publish gate: non-finite params NEVER reach the wire,
            # the handshake cache, or the artifact file — the fleet keeps
            # serving the last good model while the watchdog's rollback
            # replaces the poisoned line (trip_external surfaces on the
            # learner thread's next poll).
            g._m_publish_blocked.inc()
            if g.watchdog is not None:
                g.watchdog.trip_external("publish_nonfinite",
                                         float("nan"), 0.0)
            telemetry.emit("publish_blocked", version=int(version))
            print(f"[TrainingServer] publish BLOCKED: version {version} "
                  f"params are non-finite", flush=True)
            return
        enc = self._wire_encoder
        with self._bundle_lock:
            self._bundle_host = (int(version), dict(arch), host_params)
        tracer = self._get_tracer()
        traced = tracer.enabled and tracer.sample_version(version)
        trace_id = None
        if traced:
            from relayrl_tpu.telemetry.trace import model_trace_id

            trace_id = model_trace_id(version)
        try:
            if enc is not None:
                with span("rl:publish.encode") as sp:
                    frame, info = enc.encode(version, arch, host_params)
                    sp.note(kind=info["kind"], bytes=info["frame_bytes"])
                    if traced:
                        sp.hop("model", trace_id, "encode",
                               version=int(version), frame_kind=info["kind"],
                               bytes=info["frame_bytes"])
                if getattr(self.transport, "needs_handshake_bytes", False):
                    # The native core answers handshakes from pushed
                    # bytes; a v2 publish rides with the v1 bundle for
                    # set_model.
                    self._wire_publish(trace_id, version, frame,
                                       handshake_bytes=self._get_model()[1])
                else:
                    self._wire_publish(trace_id, version, frame)
                telemetry.emit("model_publish", version=version,
                               bytes=info["frame_bytes"], kind=info["kind"],
                               raw_bytes=info["raw_bytes"])
            else:
                from relayrl_tpu.types.model_bundle import ModelBundle

                with span("rl:publish.encode", kind="bundle") as sp:
                    raw = ModelBundle(version=int(version), arch=dict(arch),
                                      params=host_params).to_bytes()
                    sp.note(bytes=len(raw))
                with self._bundle_lock:
                    self._bundle_bytes = raw
                    self._bundle_version = int(version)
                self._wire_publish(trace_id, version, raw)
                telemetry.emit("model_publish", version=version,
                               bytes=len(raw))
        finally:
            # Distance-gated; a transient publish error must not starve
            # the on-disk artifact (the multi-host path always wrote it).
            self._write_model_artifact(None, version)
            # Colocated serving feed: the inference plane sees every
            # published version straight from the host tree — no wire
            # hop, no subscription, same finite-publish gate as the
            # fleet (the non-finite early-return above never reaches
            # here with poisoned params).
            if self.inference is not None:
                try:
                    self.inference.install_params(version, arch,
                                                  host_params)
                except Exception as e:
                    self._publish_error("serving install error", e)

    def _wire_publish(self, trace_id: str | None, version: int,
                      frame: bytes, **kwargs) -> None:
        """The fault-site-wrapped broadcast in its ``rl:publish.send`` span
        (socket wall time on the publisher thread); ``trace_id`` makes it
        the sampled version's ``publish`` hop as well."""
        with span("rl:publish.send") as sp:
            if trace_id is not None:
                sp.hop("model", trace_id, "publish", version=int(version),
                       backend=self.server_type)
            self._faulted_publish(version, frame, **kwargs)

    def _faulted_publish(self, version: int, frame: bytes,
                         **kwargs) -> None:
        """Model broadcast through the ``server.publish`` fault site:
        drop loses the frame for the whole fleet (keyframe cadence or
        resync recovers), corrupt lands in every actor's CRC check,
        delay stalls the publisher thread. No plan → straight through."""
        if self._fault_publish is None:
            self.transport.publish_model(version, frame, **kwargs)
            return
        for delay_s, part in self._fault_publish.inject(frame):
            if delay_s > 0:
                time.sleep(delay_s)
            self.transport.publish_model(version, part, **kwargs)

    def _publish(self) -> None:
        """Synchronous publish on the learner thread — the multi-host
        loop's path and the ``async_publish: false`` escape hatch (the
        pipelined path hands :meth:`_publish_snapshot` to the publisher
        thread instead)."""
        import jax

        with span("rl:publish.gather"):
            bundle = self.algorithm.bundle()
            host_params = jax.device_get(bundle.params)
        self._publish_params(bundle.version, bundle.arch, host_params)
        self._maybe_periodic_checkpoint(bundle.version)

    def _maybe_periodic_checkpoint(self, version: int) -> None:
        """Distance-gated full-state checkpoint (params + optimizer +
        RNG + epoch; async orbax save). Distance, not modulo: off-policy
        versions advance by the whole update-debt between checks, so a
        ``% N == 0`` gate can skip cadences indefinitely (the same
        starvation `_write_model_artifact` guards against). Quiesces the
        pipeline first — the save fences the params anyway, and flushing
        the deferred logs keeps the checkpointed epoch counter in step
        with the checkpointed params (a resume must not repeat Epoch
        rows already logged before the save); a no-op when nothing is
        pending (the synchronous and multi-host paths)."""
        if (not self._checkpoint_dir
                or version - self._ckpt_version < self._checkpoint_every):
            return
        with span("host:checkpoint", version=int(version)):
            self._pipeline_quiesce()
            # Post-quiesce the in-flight window is empty, so every pending
            # health probe resolves for free here — a trip rolls back (the
            # save is skipped: the state it would capture is the poisoned
            # line) and a clean poll makes the healthy-at-save tag honest.
            if self._guard_poll():
                return
            self._periodic_checkpoint()
        # Advance even on a (caught) failed save — retrying every epoch
        # would hammer a broken checkpoint dir, and multi-host ranks must
        # stay in lockstep on the due-check regardless of local errors.
        self._ckpt_version = version

    def _publish_snapshot(self, snapshot) -> None:
        """Publisher-thread body: the blocking D2H gather, wire encode
        (delta/keyframe under v2, full serialize under v1), socket
        publish, and artifact write all happen here — a slow subscriber
        or disk never stalls the learner thread, and back-to-back epochs
        coalesce latest-wins upstream (runtime/pipeline.ModelPublisher).
        Exceptions are logged and counted into the shared
        ``publish_errors_total`` by the publisher loop; ``stats`` gets
        its copy here."""
        try:
            with span("rl:publish.gather"):
                host_params = snapshot.host_params()
            self._publish_params(snapshot.version, snapshot.arch,
                                 host_params)
        except Exception:
            self.stats["publish_errors"] += 1
            raise

    def _health_tag(self) -> dict:
        """The healthy-at-save tag every checkpoint carries (JSON
        extras): True iff the watchdog's most recently resolved probes
        were clean and guardrails are not halted. The periodic path
        quiesces + polls BEFORE saving, so a True tag means every update
        baked into the step had its probes resolved clean — the
        last-known-good ring's membership test (restore_latest_healthy).
        Guardrails/watchdog off ⇒ True: the ring stays usable as a
        plain resume source."""
        g = self.guardrails
        healthy = not self._halted and (
            g is None or g.watchdog is None or g.watchdog.healthy())
        return {"healthy": healthy}

    def _periodic_checkpoint(self) -> None:
        """One periodic save, with the replay-buffer (aux) snapshot
        throttled to every ``checkpoint_aux_every``-th save — the ring
        copy is synchronous on this (learner) thread, so large buffers
        pay it on a cadence instead of every save."""
        try:
            from relayrl_tpu.checkpoint import checkpoint_algorithm

            include_aux = self._ckpt_saves % self._aux_every == 0
            checkpoint_algorithm(self.algorithm, self._checkpoint_dir,
                                 include_aux=include_aux,
                                 max_to_keep=self._ckpt_keep,
                                 extra_meta=self._health_tag())
            from relayrl_tpu import telemetry

            telemetry.emit("checkpoint", version=self.algorithm.version,
                           include_aux=include_aux,
                           dir=str(self._checkpoint_dir))
            # The dedup ledger rides every checkpoint as a per-version
            # sidecar, so a crash-resume restores dedup state consistent
            # with the restored params (see _save_ledger_sidecar).
            self._save_ledger_sidecar(self.algorithm.version)
            # Count after submit so a SYNCHRONOUS failure (same-step
            # collision, bad tree) doesn't consume the aux slot. Saves
            # are async, so a deferred write failure surfaces at the
            # NEXT call and that slot is still lost — best effort only.
            self._ckpt_saves += 1
            if self._ckpt_consecutive_failures:
                self._ckpt_consecutive_failures = 0
                self._m_ckpt_consecutive.set(0)
        except Exception as e:
            # A step collision happens after a signal-path final save
            # bumped past this version (see manager.save overwrite) —
            # benign, the state is already on disk at the bumped step.
            if type(e).__name__ == "StepAlreadyExistsError":
                print(f"[TrainingServer] checkpoint step exists, skipped "
                      f"(post-resume overlap with a bumped final save)",
                      flush=True)
            else:
                # Satellite (ISSUE 6): a failed save used to leave NO
                # trace beyond this line while _ckpt_version advanced
                # past it — operators could lose a whole resume window
                # silently. Counter + consecutive-failure gauge + journal
                # event make it alarmable.
                self._ckpt_consecutive_failures += 1
                self._m_ckpt_failures.inc()
                self._m_ckpt_consecutive.set(
                    self._ckpt_consecutive_failures)
                from relayrl_tpu import telemetry

                telemetry.emit(
                    "checkpoint_failed", version=self.algorithm.version,
                    error=repr(e),
                    consecutive=self._ckpt_consecutive_failures,
                    dir=str(self._checkpoint_dir))
                print(f"[TrainingServer] checkpoint failed "
                      f"(#{self._ckpt_consecutive_failures} consecutive): "
                      f"{e!r}", flush=True)

    # -- fleet telemetry tick (ISSUE 15) --
    def _fleet_loop(self) -> None:
        while not self._fleet_stop.wait(self._fleet_interval_s):
            self._fleet_tick()

    def _fleet_tick(self) -> None:
        """One aggregation interval at the root: fold this server's own
        registry into the table, evict stale procs, evaluate the SLO
        rules over the merged snapshot. Public-ish so drills/tests can
        tick deterministically; isolated — the pane must never take
        down the plane it watches."""
        from relayrl_tpu import telemetry

        try:
            self._fleet.ingest_registry(self._telemetry, self._fleet_proc,
                                        "server")
            for proc in self._fleet.sweep():
                telemetry.emit("fleet_evict", proc=proc)
            if self._alerts is not None:
                # Membership rides along so increase rules rebaseline
                # across evict/rejoin churn instead of firing on it.
                self._alerts.evaluate(
                    self._fleet.merged(),
                    membership=[p["proc"] for p in self._fleet.procs()])
        except Exception as e:
            print(f"[TrainingServer] fleet tick failed: {e!r}", flush=True)

    # -- lifecycle (ref: training_zmq.rs:322-465 / o3_training_server.rs:153-272) --
    def enable_server(self) -> None:
        if self.active:
            return
        self._stop.clear()
        multi_host = self.distributed_info["multi_host"]
        if self.transport is not None:
            self.transport.start()
            # N decode workers (learner.ingest_staging_threads): once the
            # learner thread is dispatch-only, a single decode thread is
            # the next ingest bottleneck; the native decoder drops the
            # GIL, so extra workers scale on real cores.
            self._staging_threads = [
                threading.Thread(target=self._staging_loop,
                                 name=f"ingest-staging-{i}", daemon=True)
                for i in range(self._staging_count)]
            for t in self._staging_threads:
                t.start()
                self._thread_ledger.watch("staging", t)
        if self.inference is not None:
            self.inference.start()
        # The publisher thread exists wherever there is a transport to
        # feed — including the multi-host coordinator (non-coordinators
        # own no actor plane, so they dispatch the publish gather and
        # drop the snapshot). async_publish=false is the sync escape
        # hatch on both loops.
        if (self.transport is not None
                and self._async_publish and self._publisher is None):
            from relayrl_tpu.runtime.pipeline import ModelPublisher

            self._publisher = ModelPublisher(self._publish_snapshot)
            self._thread_ledger.watch("publish", self._publisher._thread)
        self._mh_ready = []
        self._mh_busy = False
        if multi_host:
            # The multi-host update is collective — a solo pre-compile
            # would hang the other ranks; wait_warmup() must not block.
            self._warmup_done.set()
        self._learner_thread = threading.Thread(
            target=(self._learner_loop_multihost if multi_host
                    else self._learner_loop),
            name="learner", daemon=True)
        self._learner_thread.start()
        self._thread_ledger.watch("learner", self._learner_thread)
        if self._fleet is not None:
            self._fleet_stop.clear()
            self._fleet_thread = threading.Thread(
                target=self._fleet_loop, name="fleet-tick", daemon=True)
            self._fleet_thread.start()
        self.active = True

    def wait_warmup(self, timeout: float | None = None) -> bool:
        """Block until the learner thread has pre-compiled its update
        shapes (no-op/immediate on multi-host and after the first enable).
        One-process deployments that run the actor loop on the main thread
        (notebooks) call this right after construction: the main thread
        sleeps on the event, so the compile gets the core to itself.
        Returns False immediately when the server isn't running
        (``start=False`` and no enable yet): no learner thread exists to
        ever set the event, so blocking would hang forever. Raises
        ``RuntimeError`` (from the original error) when the warmup failed:
        that learner thread has ended."""
        if not self.active and not self._warmup_done.is_set():
            return False
        done = self._warmup_done.wait(timeout)
        if self._warmup_error is not None:
            raise RuntimeError(
                "learner warmup failed; the update does not compile or run "
                "on this backend") from self._warmup_error
        return done

    def disable_server(self, join_timeout: float | None = None) -> None:
        """``join_timeout`` overrides the per-thread join bounds — the
        signal path passes a short grace on multi-host so a peer stuck
        mid-collective can't hold this rank past its supervisor's
        termination window."""
        if not self.active:
            return
        self._stop.set()
        if self._fleet_thread is not None:
            self._fleet_stop.set()
            self._fleet_thread.join(timeout=5)
            self._fleet_thread = None
            # One closing tick so the table holds this life's final
            # registry state (and alerts get a last look) before the
            # ingest plane stops feeding it.
            self._fleet_tick()
        # Serving plane first: parked thin-client requests answer with a
        # retryable nack instead of hanging out their timeouts against a
        # closing socket (clients ride their breaker until a restart).
        if self.inference is not None:
            self.inference.stop()
        # Join the learner BEFORE stopping the transport: a trajectory being
        # processed right now may still publish, which needs a live socket.
        # (Multi-host: the coordinator's learner thread broadcasts STOP on
        # its way out, releasing every non-coordinator's loop — shut the
        # fleet down together or coordinator-last.)
        # join_timeout is ONE deadline across both joins (the signal path
        # sizes it to the supervisor grace window — two full grants would
        # double it), not a per-thread grant.
        deadline = (None if join_timeout is None
                    else time.monotonic() + join_timeout)
        for t in self._staging_threads:
            t.join(timeout=30 if deadline is None
                   else max(0.0, deadline - time.monotonic()))
        self._staging_threads = []
        if self._learner_thread is not None:
            # Multi-host: the thread may be mid-collective (a step can
            # include a fresh XLA compile) — give it long enough to reach
            # the STOP broadcast; killing the transport under a live
            # publish would be worse than waiting.
            default = 600 if self.distributed_info["multi_host"] else 30
            self._learner_thread.join(
                timeout=default if deadline is None
                else max(0.0, deadline - time.monotonic()))
            self._learner_thread = None
        if self._publisher is not None:
            # After the learner join (no more submits), before the
            # transport stops (the final publish needs a live socket).
            self._publisher.stop(
                timeout=30 if deadline is None
                else max(0.0, deadline - time.monotonic()))
            self._publisher = None
        if self.transport is not None:
            self.transport.stop()
        self._flush_drop_event()
        # Drain any in-flight async orbax save — the most recent checkpoint
        # is exactly the one a subsequent resume needs.
        mgr = getattr(self.algorithm, "_ckpt_mgr", None)
        if mgr is not None and join_timeout is None:
            # Drain in-flight async saves — but NOT on the bounded
            # (signal/emergency) path: a multi-host collective save waits
            # on a cross-process commit barrier un-signaled peers never
            # complete, and an unbounded wait here would defeat the
            # bounded joins above (the process is about to die by signal;
            # single-host final saves use wait=True themselves).
            try:
                mgr.wait()
            except Exception as e:
                print(f"[TrainingServer] checkpoint drain failed: {e!r}",
                      flush=True)
        self.active = False

    def restart_server(self, **addr_overrides) -> None:
        from relayrl_tpu.parallel.distributed import is_coordinator

        self.disable_server()
        if addr_overrides and is_coordinator():
            # Non-coordinators never own a transport (the actor plane
            # binds on the coordinator only) — a symmetric restart call
            # across the fleet must not create one.
            self._addr_overrides.update(addr_overrides)
            self.transport = make_server_transport(
                self.server_type, self.config, **self._addr_overrides)
            self.transport.on_trajectory = self._on_trajectory
            self.transport.on_trajectory_decoded = self._on_trajectory_decoded
            self.transport.get_model = self._get_model
            self.transport.on_register = self._on_register
            self.transport.on_unregister = self._on_unregister
            self.transport.on_resync = self._on_resync_request
            if self.guardrails is not None:
                self.transport.check_ingest = self._check_ingest
            if self.inference is not None:
                self._wire_serving_plane(self._addr_overrides)
        self.enable_server()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.disable_server()


def _coerce(v: str):
    for cast in (int, float):
        try:
            return cast(v)
        except ValueError:
            pass
    if v.lower() in ("true", "false"):
        return v.lower() == "true"
    return v


def _load_plugin_algorithms(algorithm_dir: str) -> None:
    """Import ``<dir>/<ALGO>/<ALGO>.py`` modules so they can
    ``register_algorithm`` themselves (the reference's dynamic
    sys.path+importlib scheme, python_algorithm_reply.py:23-52)."""
    import importlib.util
    import os
    import sys

    if algorithm_dir not in sys.path:
        sys.path.insert(0, algorithm_dir)
    for entry in sorted(os.listdir(algorithm_dir)):
        mod_file = os.path.join(algorithm_dir, entry, f"{entry}.py")
        if os.path.isfile(mod_file):
            name = f"relayrl_plugin_{entry}"
            if name in sys.modules:
                continue
            spec = importlib.util.spec_from_file_location(name, mod_file)
            module = importlib.util.module_from_spec(spec)
            sys.modules[name] = module
            spec.loader.exec_module(module)


__all__ = ["TrainingServer", "registered_algorithms"]
