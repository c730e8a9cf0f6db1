"""The agent (actor) process: local policy inference + trajectory streaming
+ model hot-swap.

Capability parity with the reference's agent stack
(reference: relayrl_framework/src/network/client/agent_wrapper.rs:213-270
facade; agent_zmq.rs / agent_grpc.rs; PyO3 surface
src/bindings/python/network/client/o3_agent.rs:49-330 —
``RelayRLAgent(model_path, config_path, server_type, ...)``,
``request_for_action(obs, mask, reward)``, ``flag_last_action(reward)``,
``record_action``, restart/enable/disable).

Bring-up mirrors the reference handshake (agent_zmq.rs:316-442): fetch model
→ validate with a dummy forward → persist to ``client_model`` path →
register → start the model listener. Hot-swaps are version-gated and
arch-checked (the reference's version field is unimplemented server-side —
training_grpc.rs:722-725; here it's real).
"""

from __future__ import annotations

import os

import numpy as np

from relayrl_tpu.config import ConfigLoader
from relayrl_tpu.runtime.policy_actor import PolicyActor
from relayrl_tpu.telemetry.spans import span
from relayrl_tpu.transport import make_agent_transport
from relayrl_tpu.types.action import ActionRecord
from relayrl_tpu.types.model_bundle import ModelBundle


def _deliver_model(actor_host, transport, client_model_path: str, tag: str,
                   version: int, blob: bytes) -> None:
    """Shared model-delivery handler for Agent and VectorAgent (both own
    one subscription feeding one wire-aware swap): sniffing decode via
    ``swap_from_wire``, resync on a base mismatch (raised once per
    divergence — pull transports re-poll with ``ver=-1``, broadcast
    transports wait out the keyframe interval), isolation of any other
    decode/validation failure, and the client-model persist on install.
    One body, so resync semantics can never drift between the two
    actor-host kinds."""
    from relayrl_tpu.transport.modelwire import WireBaseMismatch

    # The actor's half of model lag — the subscriber thread's hand-over of
    # the frame to installed — is the span round the call (the host's own
    # spans sit inside it: rl:actor.model_decode, rl:actor.swap).
    try:
        with span("rl:actor.model_install", version=int(version)) as sp:
            installed = actor_host.swap_from_wire(version, blob)
    except WireBaseMismatch as e:
        from relayrl_tpu import telemetry

        telemetry.emit("model_resync", agent_id=transport.identity,
                       base=e.base, held=e.held, side="agent")
        # The held version rides the request: a relay serves a late
        # joiner from cache but must ESCALATE a subscriber newer than
        # its cached keyframe (stale keyframes are dropped by decoders).
        transport.request_resync(e.held)
        return
    except Exception as e:
        print(f"[{tag}] rejected model update: {e!r}", flush=True)
        return
    if installed is not None:
        ledger = getattr(actor_host, "ledger", None)
        if ledger is not None:
            ledger.timings["model_install_s"] += sp.seconds
            ledger.counts["installs"] += 1
        try:
            installed.save(client_model_path)
        except OSError:
            pass


def ship_unroll(owner, agent_id: str, payload: bytes, origin, version: int,
                ledger=None) -> None:
    """The one send of a serialized unroll (Agent, VectorAgent, the thin
    client): through the owner's spool, or straight to its transport where
    ``actor.spool_entries`` is 0.

    ``origin`` is ``(born_ns, rl:actor.encode span)`` of the unroll, or
    None for a payload a dataflow stage re-injects long after its
    production. The envelope id always carries the shipper's report
    (``#r``: the born stamp and version, and ``ledger``'s deltas where it
    keeps one — telemetry/actor_ledger.py); a sampled trajectory also draws
    a trace context (``#t``) and records its actor-side hops from the
    stamps the spans already took: ``env`` (born → encode start),
    ``encode`` and ``send`` — one tracer read per *trajectory*, never per
    step."""
    from relayrl_tpu.telemetry.actor_ledger import encode_report
    from relayrl_tpu.telemetry import trace as trace_mod

    born_ns, encode = origin if origin is not None else (0, None)
    tracer = trace_mod.get_tracer()
    ctx = tracer.sample_traj(born_ns, version) if born_ns else None
    if ctx is not None:
        tracer.span("traj", ctx.trace_id, "env", born_ns, encode.t0_ns,
                    agent=agent_id, version=int(version))
        encode.hop("traj", ctx.trace_id, "encode", agent=agent_id)
    # One born stamp on the wire: a trace context carries it already.
    report = (encode_report if ledger is None else ledger.report)(
        0 if ctx is not None else born_ns, version)
    trace = None if ctx is None else ctx.encode()
    with span("rl:actor.send", None if ledger is None else ledger.timings,
              "send_s") as sp:
        if ctx is not None:
            sp.hop("traj", ctx.trace_id, "send", agent=agent_id)
        if owner.spool is not None:
            owner.spool.send(payload, agent_id, trace=trace, report=report)
            return
        # actor.spool_entries == 0: the pre-recovery direct path
        from relayrl_tpu.telemetry.trace import tag_agent_trace
        from relayrl_tpu.transport.base import IngestNack, tag_agent_report

        wire_id = tag_agent_report(agent_id, report)
        if trace is not None:
            wire_id = tag_agent_trace(wire_id, trace)
        try:
            owner.transport.send_trajectory(payload, agent_id=wire_id)
        except IngestNack:
            # The server answered with a guardrail verdict
            # (quarantine/overload). Spool-less there is nothing to
            # retain or replay — drop, never crash the env loop (the
            # spooled path routes this through spool._attempt).
            pass


def _bind_spool_impl(owner, name: str) -> None:
    """Create (first enable) or re-bind (restart) the owner's trajectory
    spool (runtime/spool.py). Shared by Agent and VectorAgent so the
    spool lifecycle — survives restart_agent with its seq counters and
    retained window intact, send_fn re-bound to the fresh transport —
    exists exactly once. ``actor.spool_entries: 0`` disables the spool
    (sends go straight to the transport, untagged)."""
    params = owner.config.get_actor_params()
    if params["spool_entries"] <= 0:
        owner.spool = None
        return

    def send_fn(payload: bytes, tagged_id: str) -> None:
        owner.transport.send_trajectory(payload, agent_id=tagged_id)

    if owner.spool is None:
        from relayrl_tpu.runtime.spool import TrajectorySpool
        from relayrl_tpu.transport.retry import breaker_from_config

        retry_cfg = owner.config.get_transport_params()["retry"]
        owner.spool = TrajectorySpool(
            send_fn=send_fn,
            max_entries=params["spool_entries"],
            max_bytes=params["spool_bytes"],
            directory=params["spool_dir"],
            name=name,
            breaker=breaker_from_config(f"agent:{name}", retry_cfg),
        )
        if params["spool_dir"] and owner.spool.depth:
            # A prior process life left trajectories in flight (actor
            # crash drill): replay them now that a transport is live.
            owner.spool.replay()
    else:
        owner.spool.send_fn = send_fn


def _start_fleet_emitter(owner, tier: str):
    """Start the per-process fleet snapshot emitter (ISSUE 15,
    telemetry/aggregate.py) when the plane is on: registry live AND
    ``telemetry.fleet_interval_s`` > 0. The frame rides the owner's
    agent transport beside trajectories (no new socket); shared by
    Agent / VectorAgent / RemoteActorClient so the gating and the wire
    id convention exist exactly once. Returns the emitter or None."""
    from relayrl_tpu import telemetry

    reg = telemetry.get_registry()
    try:
        interval = float(owner.config.get_telemetry_params()
                         .get("fleet_interval_s") or 0.0)
    except Exception:
        interval = 0.0
    if not reg.enabled or interval <= 0:
        return None
    from relayrl_tpu.telemetry.aggregate import FleetEmitter

    transport = owner.transport

    def send(frame: bytes, wire_id: str) -> None:
        transport.send_trajectory(frame, agent_id=wire_id)

    return FleetEmitter(send, proc=transport.identity, tier=tier,
                        interval_s=interval, registry=reg)


def _close_fleet_emitter(owner) -> None:
    """Final-frame flush + thread stop BEFORE the transport closes (the
    last frame carries this life's closing totals to the root)."""
    emitter = getattr(owner, "_fleet_emitter", None)
    if emitter is not None:
        emitter.close(final=True)
        owner._fleet_emitter = None


def _handle_reconnect_impl(owner, agent_ids: list[str]) -> None:
    """Shared transport-heal handler: re-register every logical agent
    (the server may have reaped them on kernel close — _on_register
    dedups, so this is idempotent on servers that kept them) and replay
    the spool window (the server's sequence dedup makes the replay
    exactly-once). Runs on a transport thread; failures degrade to the
    next heal rather than killing the listener."""
    from relayrl_tpu import telemetry

    for agent_id in agent_ids:
        try:
            owner.transport.register(agent_id, timeout_s=5.0)
        except Exception as e:
            print(f"[Agent] re-register {agent_id!r} after reconnect "
                  f"failed: {e!r}", flush=True)
    replayed = owner.spool.replay() if owner.spool is not None else 0
    telemetry.emit("agent_reconnect",
                   agent_id=agent_ids[0] if agent_ids else "?",
                   lanes=len(agent_ids), replayed=replayed)


class Agent:
    def __init__(
        self,
        model_path: str | None = None,
        config_path: str | None = None,
        server_type: str = "zmq",
        handshake_timeout_s: float = 60.0,
        seed: int | None = None,
        start: bool = True,
        **addr_overrides,
    ):
        self.config = ConfigLoader(None, config_path)
        # Actor-process observability: idempotent, so an agent living in
        # the server's process joins the registry the server installed.
        from relayrl_tpu import faults, telemetry

        telemetry.configure_from_config(self.config)
        # Fault plan (chaos drills): env-driven install must precede
        # transport construction so its hook sites resolve.
        faults.maybe_install_from_env()
        self.server_type = server_type
        self._addr_overrides = addr_overrides
        self.client_model_path = model_path or self.config.get_client_model_path()
        self._handshake_timeout_s = handshake_timeout_s
        self._seed = os.getpid() if seed is None else seed
        self.actor: PolicyActor | None = None
        self.transport = None
        self.spool = None  # TrajectorySpool, built on first enable
        self._fleet_emitter = None
        self.active = False
        if start:
            self.enable_agent()

    # -- bring-up / lifecycle (ref: agent_zmq.rs:163-300) --
    def enable_agent(self) -> None:
        if self.active:
            return
        # Auto-negotiation may retry-probe until the server binds; give it
        # the agent's own handshake budget rather than a fixed 3s window.
        overrides = dict(self._addr_overrides)
        overrides.setdefault("negotiate_window_s",
                             min(self._handshake_timeout_s * 0.5, 30.0))
        self.transport = make_agent_transport(
            self.server_type, self.config, **overrides)
        version, bundle_bytes = self.transport.fetch_model(self._handshake_timeout_s)
        bundle = ModelBundle.from_bytes(bundle_bytes,
                                        params_template=ModelBundle.RAW_TREE)
        bundle.version = version
        # Persist before loading, like the reference writes client_model.pt
        # (agent_zmq.rs:388-396) — survives restarts / aids debugging.
        try:
            bundle.save(self.client_model_path)
        except OSError:
            pass
        self._bind_spool()
        if self.actor is None:
            self.actor = PolicyActor(
                bundle,
                max_traj_length=self.config.get_max_traj_length(),
                on_send=self._send_traj,
                seed=self._seed,
            )
        else:
            self.actor.maybe_swap(bundle)
            self.actor.trajectory._on_send = self._send_traj
        if not self.transport.register(self.transport.identity):
            raise RuntimeError("agent registration (MODEL_SET/ID_LOGGED) failed")
        self.transport.on_model = self._on_model
        self.transport.on_reconnect = self._handle_reconnect
        self.transport.start_model_listener()
        self._fleet_emitter = _start_fleet_emitter(self, "actor")
        self.active = True
        from relayrl_tpu import telemetry

        telemetry.emit("agent_register", agent_id=self.transport.identity,
                       version=version, side="agent")

    def _send_traj(self, payload: bytes) -> None:
        # Runs inside Trajectory.flush, so the trajectory's born stamp
        # and encode span describe exactly the chunk in `payload`.
        traj = self.actor.trajectory
        ship_unroll(self, self.transport.identity, payload,
                    (traj.born_ns, traj.encode_span), self.actor.version,
                    self.actor.ledger)

    def _bind_spool(self) -> None:
        name = self._addr_overrides.get("identity") or "agent"
        _bind_spool_impl(self, name)

    def _handle_reconnect(self) -> None:
        _handle_reconnect_impl(self, [self.transport.identity])

    def disable_agent(self) -> None:
        if not self.active:
            return
        _close_fleet_emitter(self)
        if self.spool is not None:
            # The spool outlives the transport (its retained window and
            # seq counters survive restart_agent); detach the send hook
            # so a send while disabled buffers instead of touching a
            # closed socket.
            self.spool.send_fn = None
        self.transport.close()
        self.transport = None
        self.active = False

    def restart_agent(self, **addr_overrides) -> None:
        from relayrl_tpu import telemetry

        self.disable_agent()
        self._addr_overrides.update(addr_overrides)
        self.enable_agent()
        if self.spool is not None:
            # An explicit restart exists because something broke: replay
            # the retained window (dedup makes it exactly-once).
            self.spool.replay()
        telemetry.emit("agent_reconnect", agent_id=self.transport.identity)

    def _on_model(self, version: int, bundle_bytes: bytes) -> None:
        _deliver_model(self.actor, self.transport, self.client_model_path,
                       "Agent", version, bundle_bytes)

    # -- action API (ref: o3_agent.rs:117-217) --
    def request_for_action(self, obs, mask=None, reward: float = 0.0) -> ActionRecord:
        self._require_active()
        return self.actor.request_for_action(obs, mask, reward)

    def flag_last_action(self, reward: float = 0.0, truncated: bool = False,
                         final_obs=None, terminated: bool | None = None,
                         final_mask=None) -> None:
        self._require_active()
        self.actor.flag_last_action(reward, truncated=truncated,
                                    final_obs=final_obs, terminated=terminated,
                                    final_mask=final_mask)

    def record_action(self, action: ActionRecord) -> None:
        self._require_active()
        self.actor.record_action(action)

    @property
    def model_version(self) -> int:
        return -1 if self.actor is None else self.actor.version

    def _require_active(self) -> None:
        if not self.active or self.actor is None:
            raise RuntimeError("agent is not active (call enable_agent())")

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.disable_agent()


class VectorAgent:
    """Networked vector actor host: N logical agents over ONE connection.

    The process-topology answer to the north-star "64 actors" row: where
    64 :class:`Agent` processes oversubscribe a host, one VectorAgent
    steps ``num_envs`` environment lanes through a single batched jitted
    policy dispatch (:class:`~relayrl_tpu.runtime.vector_actor.
    VectorActorHost`) and presents each lane to the training server as
    its own logical agent — N registry entries, N attributed trajectory
    streams, one socket, one model subscription, one atomic hot-swap.

    Agent-compatible lifecycle (``enable_agent``/``disable_agent``/
    context manager/``model_version``); the action surface is batched
    (``request_for_actions`` / per-lane ``flag_last_action``) because
    that is the point.

    ``host_mode="anakin"`` (or config ``actor.host_mode: "anakin"``)
    swaps the per-step batched host for the fused on-device rollout
    engine (:class:`~relayrl_tpu.runtime.anakin.AnakinActorHost`): the
    env itself runs as pure JAX (``actor.jax_env``) and the action
    surface becomes :meth:`rollout` — one dispatch per
    ``num_envs × actor.unroll_length`` window. Everything network-side
    is IDENTICAL: N logical lane registrations, N attributed trajectory
    streams through the same spool, one model subscription, one atomic
    swap gate — the server cannot tell the tiers apart.
    """

    def __init__(
        self,
        num_envs: int | None = None,
        model_path: str | None = None,
        config_path: str | None = None,
        server_type: str = "zmq",
        handshake_timeout_s: float = 60.0,
        seed: int | None = None,
        start: bool = True,
        identity: str | None = None,
        host_mode: str | None = None,
        jax_env: str | None = None,
        jax_env_kwargs: dict | None = None,
        unroll_length: int | None = None,
        columnar_wire: bool | None = None,
        async_emit: bool | None = None,
        emit_coalesce_frames: int | None = None,
        window_size: int | None = None,
        record_bver: bool = False,
        send_interceptor=None,
        rng_keys=None,
        **addr_overrides,
    ):
        # Dataflow-stage hook (the RLHF scheduler's seam,
        # rlhf/scheduler.py): when set, every completed lane episode is
        # offered to ``send_interceptor(lane, payload)`` BEFORE the
        # spool/transport path. A non-None return ships immediately
        # (possibly rewritten); None means the stage took ownership and
        # will re-inject via :meth:`emit_lane` once its own work (reward
        # scoring) is done — generate and downstream stages decouple
        # without forking the send path.
        self._send_interceptor = send_interceptor
        # Per-lane PRNG override (vector tier only): the bit-identity
        # locks hand lane 0 the exact key a single PolicyActor carries.
        self._rng_keys = rng_keys
        self.config = ConfigLoader(None, config_path)
        from relayrl_tpu import faults, telemetry

        telemetry.configure_from_config(self.config)
        faults.maybe_install_from_env()
        actor_params = self.config.get_actor_params()
        self.num_envs = int(num_envs if num_envs is not None
                            else actor_params.get("num_envs", 1))
        if self.num_envs < 1:
            raise ValueError(f"num_envs must be >= 1, got {self.num_envs}")
        self.host_mode = str(host_mode if host_mode is not None
                             else actor_params["host_mode"])
        if self.host_mode not in ("vector", "anakin"):
            # A VectorAgent *is* the vector topology; "process" configs
            # constructing one explicitly just mean the batched default.
            self.host_mode = "vector"
        self.jax_env = str(jax_env if jax_env is not None
                           else actor_params["jax_env"])
        # Env-construction kwargs for the anakin tier (e.g. TokenGen's
        # vocab_size/prompt_len/max_new_tokens), forwarded to the JAX
        # env registry; inert on the vector tier (host-bound envs are
        # built by the driver, not the agent).
        self.jax_env_kwargs = dict(jax_env_kwargs or {})
        self.unroll_length = int(unroll_length if unroll_length is not None
                                 else actor_params["unroll_length"])
        # actor.window_size: narrows the sequence-policy rolling window
        # below the model context (anakin scan carry; the vector host
        # sizes its windows from the model arch directly).
        self.window_size = (actor_params.get("window_size")
                            if window_size is None else window_size)
        # Per-token behavior-version evidence (RLHF): stamp ``bver``
        # into each record's aux on the anakin tier.
        self.record_bver = bool(record_bver)
        # actor.columnar_wire: "auto" -> columnar frames on the anakin
        # tier (whole-segment frames decoded server-side straight into
        # the staging slabs), per-record wire on the host-bound tiers.
        if columnar_wire is None:
            columnar_wire = actor_params.get("columnar_wire", "auto")
        self.columnar_wire = (self.host_mode == "anakin"
                              if not isinstance(columnar_wire, bool)
                              else bool(columnar_wire))
        # actor.async_emit: off-thread frame emitter on the anakin tier
        # (the ROADMAP item 1 host shave); inert on the vector tier.
        self.async_emit = bool(actor_params.get("async_emit", False)
                               if async_emit is None else async_emit)
        # actor.emit_coalesce_frames: pack several completed columnar
        # segments per lane into one send (inert on the vector tier).
        self.emit_coalesce_frames = max(1, int(
            actor_params.get("emit_coalesce_frames", 1)
            if emit_coalesce_frames is None else emit_coalesce_frames))
        self.server_type = server_type
        self._addr_overrides = addr_overrides
        self._identity = identity
        self.client_model_path = (model_path
                                  or self.config.get_client_model_path())
        self._handshake_timeout_s = handshake_timeout_s
        self._seed = os.getpid() if seed is None else seed
        self.host = None
        self.transport = None
        self.spool = None
        self._fleet_emitter = None
        self.agent_ids: list[str] = []
        self.active = False
        if start:
            self.enable_agent()

    def enable_agent(self) -> None:
        if self.active:
            return
        from relayrl_tpu.runtime.vector_actor import VectorActorHost

        overrides = dict(self._addr_overrides)
        overrides.setdefault("negotiate_window_s",
                             min(self._handshake_timeout_s * 0.5, 30.0))
        if self._identity is not None:
            overrides.setdefault("identity", self._identity)
        self.transport = make_agent_transport(
            self.server_type, self.config, **overrides)
        version, bundle_bytes = self.transport.fetch_model(
            self._handshake_timeout_s)
        bundle = ModelBundle.from_bytes(bundle_bytes,
                                        params_template=ModelBundle.RAW_TREE)
        bundle.version = version
        try:
            bundle.save(self.client_model_path)
        except OSError:
            pass
        # Lane ids derive from the connection identity so a fleet of
        # vector hosts never collides; the server sees N distinct agents.
        self.agent_ids = [f"{self.transport.identity}.lane{k}"
                          for k in range(self.num_envs)]
        _bind_spool_impl(self, self._identity or "vector")
        if self.host is not None and hasattr(self.host, "start_emitter"):
            # Re-enable after a disable: the emitter thread was closed
            # with the transport; a reused host needs it back.
            self.host.start_emitter()
        if self.host is None:
            if self.host_mode == "anakin":
                from relayrl_tpu.runtime.anakin import AnakinActorHost

                self.host = AnakinActorHost(
                    bundle,
                    env=self.jax_env,
                    num_envs=self.num_envs,
                    unroll_length=self.unroll_length,
                    max_traj_length=self.config.get_max_traj_length(),
                    on_send=self._send_lane,
                    seed=self._seed,
                    rng_keys=self._rng_keys,
                    columnar_wire=self.columnar_wire,
                    async_emit=self.async_emit,
                    emit_coalesce_frames=self.emit_coalesce_frames,
                    window_size=self.window_size,
                    record_bver=self.record_bver,
                    **self.jax_env_kwargs,
                )
            else:
                self.host = VectorActorHost(
                    bundle,
                    num_envs=self.num_envs,
                    max_traj_length=self.config.get_max_traj_length(),
                    on_send=self._send_lane,
                    seed=self._seed,
                    rng_keys=self._rng_keys,
                )
        else:
            self.host.maybe_swap(bundle)
        # One registration round-trip per logical lane, all over the one
        # connection (the transports' multi-id contract, base.py).
        for agent_id in self.agent_ids:
            if not self.transport.register(agent_id):
                raise RuntimeError(
                    f"logical-agent registration failed for {agent_id!r}")
        self.transport.on_model = self._on_model
        self.transport.on_reconnect = (
            lambda: _handle_reconnect_impl(self, self.agent_ids))
        self.transport.start_model_listener()
        self._fleet_emitter = _start_fleet_emitter(self, "actor")
        self.active = True
        from relayrl_tpu import telemetry

        telemetry.emit("agent_register", agent_id=self.transport.identity,
                       lanes=self.num_envs, version=version, side="agent")

    def disable_agent(self) -> None:
        if not self.active:
            return
        if hasattr(self.host, "close"):
            # Async-emit anakin hosts: drain queued windows onto the
            # wire, then stop the emitter thread — a disable/enable
            # cycle must not leak one thread (and one pinned host) per
            # cycle; enable_agent restarts it via start_emitter.
            self.host.close()
        _close_fleet_emitter(self)
        if self.spool is not None:
            self.spool.send_fn = None  # see Agent.disable_agent
        self.transport.close()
        self.transport = None
        self.active = False

    def _send_lane(self, lane: int, payload: bytes) -> None:
        # The unroll's origin is read BEFORE the interceptor (it may
        # withhold and re-inject much later, when the host's stamps
        # describe a different episode — re-injected payloads trace
        # through the RLHF plane's own stage spans instead).
        origin = self.host.shipping(lane)
        if self._send_interceptor is not None:
            payload = self._send_interceptor(lane, payload)
            if payload is None:
                return  # the stage owns it now; emit_lane re-injects
        self.emit_lane(lane, payload, _stamps=origin)

    def emit_lane(self, lane: int, payload: bytes, _stamps=None) -> None:
        """Ship one lane's serialized episode through the normal
        spool/seq/transport path — the re-injection surface for a
        ``send_interceptor`` stage (the RLHF score stage emits here
        after assigning the terminal reward). Spool sequence numbers are
        assigned HERE, so withheld episodes only enter the at-least-once
        window once they are final — a replay after a crash redelivers
        the scored bytes, never the unscored ones. ``_stamps`` is
        ``_send_lane``'s: the host's ``shipping(lane)``."""
        ship_unroll(self, self.agent_ids[lane], payload, _stamps,
                    self.host.version, getattr(self.host, "ledger", None))

    def _on_model(self, version: int, bundle_bytes: bytes) -> None:
        # ONE receipt serves all lanes: a single wire-aware swap
        # atomically installs the new params for the whole batch.
        _deliver_model(self.host, self.transport, self.client_model_path,
                       "VectorAgent", version, bundle_bytes)

    # -- batched action API --
    def request_for_actions(self, obs, masks=None, rewards=None):
        self._require_active()
        if self.host_mode == "anakin":
            raise RuntimeError(
                "anakin host: the env steps on-device inside rollout() — "
                "there is no per-step action request surface")
        return self.host.request_for_actions(obs, masks=masks,
                                             rewards=rewards)

    # -- fused rollout API (host_mode="anakin") --
    def rollout(self) -> dict:
        """One fused ``[num_envs, unroll_length]`` on-device window:
        dispatch + unstack into the N logical-agent trajectory streams
        (see :meth:`AnakinActorHost.rollout`)."""
        self._require_active()
        if self.host_mode != "anakin":
            raise RuntimeError(
                "rollout() is the anakin-host surface; this agent runs "
                f"host_mode={self.host_mode!r} (per-step "
                "request_for_actions)")
        return self.host.rollout()

    def flag_last_action(self, lane: int, reward: float = 0.0,
                         truncated: bool = False, final_obs=None,
                         terminated: bool | None = None,
                         final_mask=None) -> None:
        self._require_active()
        if self.host_mode == "anakin":
            raise RuntimeError(
                "anakin host: episode boundaries happen in-scan "
                "(autoreset) — terminal markers are emitted by the "
                "window unstacker, not by the driver")
        self.host.flag_last_action(lane, reward, truncated=truncated,
                                   final_obs=final_obs,
                                   terminated=terminated,
                                   final_mask=final_mask)

    @property
    def model_version(self) -> int:
        return -1 if self.host is None else self.host.version

    def _require_active(self) -> None:
        if not self.active or self.host is None:
            raise RuntimeError(
                "vector agent is not active (call enable_agent())")

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.disable_agent()


def run_gym_loop(agent: Agent, env, episodes: int, max_steps: int = 1000,
                 seed: int | None = None) -> list[float]:
    """The reference's canonical notebook loop (examples/README.md:125-152):
    request_for_action → env.step → flag_last_action."""
    returns = []
    for ep in range(episodes):
        obs, _ = env.reset(seed=None if seed is None else seed + ep)
        ep_ret, reward = 0.0, 0.0
        terminated = truncated = False
        for _ in range(max_steps):
            record = agent.request_for_action(obs, reward=reward)
            obs, reward, terminated, truncated, _ = env.step(
                coerce_env_action(record.act))
            ep_ret += float(reward)
            if terminated or truncated:
                break
        # A time-limit ending (env truncation or this loop's max_steps cap)
        # ships the post-step obs so value targets bootstrap through it; a
        # genuine terminal takes precedence even when both flags are set.
        time_limited = not terminated
        agent.flag_last_action(reward, truncated=time_limited,
                               final_obs=obs if time_limited else None)
        returns.append(ep_ret)
    return returns


def coerce_env_action(act) -> object:
    """Wire action → what ``env.step`` expects: python scalar for 0-d
    (int for integer dtypes, float otherwise), ndarray for vectors."""
    arr = np.asarray(act)
    if arr.ndim == 0:
        return int(arr) if np.issubdtype(arr.dtype, np.integer) else float(arr)
    return arr


def greedy_episodes(actor, env, episodes: int, max_steps: int = 1000,
                    seed: int | None = None) -> list[float]:
    """The shared deterministic-eval loop: greedy actions, nothing recorded
    or shipped to the learner. Refuses to run mid-episode — a sampling
    episode in flight would be silently corrupted by the window/cache
    resets (finish it with ``flag_last_action`` first); any stale eval
    serving state is cleared up front."""
    if actor.trajectory.get_actions():
        raise RuntimeError(
            "greedy eval requested mid-episode: the current sampling "
            "episode has unsent steps — call flag_last_action first")
    actor.reset_episode()
    returns = []
    for ep in range(episodes):
        obs, _ = env.reset(seed=None if seed is None else seed + ep)
        ep_ret = 0.0
        for _ in range(max_steps):
            act = actor.deterministic_action(obs)
            obs, reward, terminated, truncated, _ = env.step(
                coerce_env_action(act))
            ep_ret += float(reward)
            if terminated or truncated:
                break
        actor.reset_episode()
        returns.append(ep_ret)
    return returns


def run_eval_loop(agent: Agent, env, episodes: int,
                  max_steps: int = 1000,
                  seed: int | None = None) -> list[float]:
    """Deterministic (greedy) evaluation episodes through a networked
    Agent — the policy is probed, not trained (the reference has no eval
    path at all; its only loop is the training notebook loop)."""
    agent._require_active()
    return greedy_episodes(agent.actor, env, episodes, max_steps, seed)
