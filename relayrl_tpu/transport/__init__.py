"""Transport plane (ref layer L4, SURVEY.md §1): ZMQ, gRPC, native C++.

``make_server_transport`` / ``make_agent_transport`` resolve a backend by
name the way the reference's wrappers pick ZMQ (default) vs gRPC
(training_server_wrapper.rs:329-379, agent_wrapper.rs:231-270).
"""

from __future__ import annotations

from relayrl_tpu.config import ConfigLoader
from relayrl_tpu.transport.base import (
    AgentTransport,
    ServerTransport,
    pack_model_frame,
    pack_trajectory_envelope,
    unpack_model_frame,
    unpack_trajectory_envelope,
)
from relayrl_tpu.transport.probe import (
    ProtocolMismatchError,
    parse_host_port,
    probe_endpoint,
)


def _resolve_auto() -> str:
    """``auto`` -> native framed-TCP when the C++ core loads, else zmq.

    Native was preferred on a CPU host's fan-out comparison; no benchmark
    cell measures the transports against each other (ROADMAP 3.9, 3.11).
    ``zmq`` stays the DEFAULT for reference parity. On the *server*
    (bind) side this local resolution defines the fleet's protocol; on
    the agent side ``auto`` additionally *negotiates* against the live
    server via :func:`probe_endpoint`, so a mixed fleet converges on
    whatever the server actually speaks instead of splitting protocols.
    """
    from relayrl_tpu.transport.native_backend import native_available

    return "native" if native_available() else "zmq"


# Conclusive probe verdicts, cached per endpoint with a short TTL: a
# process that builds many agents against one server (soaks, drills,
# vector envs) pays the probe round-trip once, while a server swapped to
# a different backend on the same port ages out quickly. Inconclusive
# verdicts are never cached — the server may simply not be up yet — and
# a mismatch is never raised off a cached verdict (see
# _verify_agent_protocol), only off a fresh probe.
_PROBE_TTL_S = 10.0
_probe_cache: dict[tuple[str, int], tuple[str, float]] = {}


def _probe_cached(host: str, port: int, timeout_s: float = 0.75,
                  refresh: bool = False) -> tuple[str, bool]:
    """Returns ``(verdict, from_cache)`` so callers can tell a fresh probe
    from a cache hit (mismatch errors must never rest on a stale entry)."""
    import time

    hit = _probe_cache.get((host, port))
    if hit is not None and not refresh and time.monotonic() - hit[1] < _PROBE_TTL_S:
        return hit[0], True
    verdict = probe_endpoint(host, port, timeout_s=timeout_s)
    if verdict in ("zmq", "native", "grpc"):
        _probe_cache[(host, port)] = (verdict, time.monotonic())
    else:
        _probe_cache.pop((host, port), None)
    return verdict, False


_KNOWN_TYPES = ("zmq", "grpc", "native")


def _agent_handshake_addr(server_type: str, config: ConfigLoader,
                          overrides: dict) -> str:
    """The single source of each backend's agent-side handshake address —
    used both by the pre-flight probe and by the constructor branches in
    :func:`make_agent_transport`, so the probe can never verify an address
    the transport doesn't actually connect to."""
    if server_type == "zmq":
        return overrides.get("agent_listener_addr",
                             config.get_agent_listener().address)
    if server_type == "grpc":
        return overrides.get("server_addr", config.get_train_server().host_port)
    return overrides.get("server_addr", config.get_traj_server().host_port)


def _negotiate_agent_auto(config: ConfigLoader, overrides: dict,
                          retry_window_s: float = 3.0) -> str:
    """Agent-side ``auto``: probe each candidate backend's handshake
    endpoint and pick the one whose server is actually answering.

    Retries the probe sweep for ``retry_window_s`` (fleets commonly start
    agents before the server finishes binding). If every probe stays
    inconclusive, falls back to local .so resolution — which, in a mixed
    fleet whose server comes up later on a different protocol, can still
    split; the fallback is printed loudly so that case leaves a breadcrumb,
    and pinning ``server_type`` explicitly avoids it entirely."""
    import time

    from relayrl_tpu.transport.native_backend import native_available

    candidates = ["native", "zmq", "grpc"] if native_available() else \
                 ["zmq", "native", "grpc"]
    deadline = time.monotonic() + retry_window_s
    while True:
        verdicts: dict[tuple[str, int], str] = {}
        for cand in candidates:
            host, port = parse_host_port(
                _agent_handshake_addr(cand, config, overrides))
            verdict = verdicts.get((host, port))
            if verdict is None:
                verdict, _ = _probe_cached(host, port)
                verdicts[(host, port)] = verdict
            if verdict == cand:
                print(f"[Transport] auto -> {cand} (negotiated: server at "
                      f"{host}:{port} speaks {verdict})", flush=True)
                return cand
        if time.monotonic() >= deadline:
            break
        time.sleep(0.25)
    fallback = _resolve_auto()
    print(f"[Transport] auto -> {fallback} (LOCAL FALLBACK — no server "
          f"answered the protocol probes ({verdicts}); if the server comes "
          f"up on a different backend this agent will time out. Pin "
          f"server_type explicitly to avoid auto in mixed fleets.)",
          flush=True)
    return fallback


def _verify_agent_protocol(server_type: str, config: ConfigLoader,
                           overrides: dict) -> None:
    """Fail fast when the server at the configured endpoint demonstrably
    speaks a different protocol (instead of a silent handshake timeout)."""
    host, port = parse_host_port(
        _agent_handshake_addr(server_type, config, overrides))
    verdict, from_cache = _probe_cached(host, port)
    if (from_cache and verdict in ("zmq", "native", "grpc")
            and verdict != server_type):
        # Never error off a (possibly stale) cache entry.
        verdict, _ = _probe_cached(host, port, refresh=True)
    if verdict in ("zmq", "native", "grpc") and verdict != server_type:
        raise ProtocolMismatchError(
            f"server at {host}:{port} speaks {verdict!r} but this agent is "
            f"configured with server_type={server_type!r} — fix server_type "
            f"on one end (or use server_type='auto' on agents to negotiate)")


def make_server_transport(server_type: str, config: ConfigLoader,
                          **overrides) -> ServerTransport:
    server_type = (server_type or "zmq").lower()
    if server_type == "auto":
        server_type = _resolve_auto()
        print(f"[Transport] auto -> {server_type} (server bind side)",
              flush=True)
    transport_params = config.get_transport_params()
    chunk_bytes = overrides.get("chunk_bytes",
                                transport_params["chunk_bytes"])
    if int(transport_params.get("wire_version", 2)) < 2:
        # wire_version=1 is the rolling-compat escape hatch for PRE-v2
        # actors — which have no chunk reassembler, so chunk frames
        # would break exactly the fleet that knob serves.
        chunk_bytes = 0
    if server_type == "zmq":
        from relayrl_tpu.transport.zmq_backend import ZmqServerTransport

        return ZmqServerTransport(
            agent_listener_addr=overrides.get(
                "agent_listener_addr", config.get_agent_listener().address),
            trajectory_addr=overrides.get(
                "trajectory_addr", config.get_traj_server().address),
            model_pub_addr=overrides.get(
                "model_pub_addr", config.get_train_server().address),
            chunk_bytes=chunk_bytes,
        )
    if server_type == "grpc":
        bind_addr = overrides.get("bind_addr",
                                  config.get_train_server().host_port)
        idle_s = config.get_grpc_idle_timeout_s()
        # The native C++ gRPC server (grpc_server.cc: HTTP/2 + the two
        # RPCs, EventHub batch decode) is the default when the library is
        # built — same wire protocol, so grpcio agents are unaffected.
        # native_grpc=False pins the pure-grpcio fallback.
        from relayrl_tpu.transport.native_backend import native_available

        if overrides.get("native_grpc", True) and native_available():
            from relayrl_tpu.transport.native_backend import (
                NativeGrpcServerTransport,
            )

            return NativeGrpcServerTransport(bind_addr=bind_addr,
                                             idle_timeout_s=idle_s)
        from relayrl_tpu.transport.grpc_backend import GrpcServerTransport

        return GrpcServerTransport(bind_addr=bind_addr, idle_timeout_s=idle_s)
    if server_type == "native":
        from relayrl_tpu.transport.native_backend import NativeServerTransport

        return NativeServerTransport(
            bind_addr=overrides.get("bind_addr", config.get_traj_server().host_port),
            chunk_bytes=chunk_bytes,
        )
    raise ValueError(f"unknown server_type {server_type!r} (zmq|grpc|native|auto)")


def make_agent_transport(server_type: str, config: ConfigLoader,
                         **overrides) -> AgentTransport:
    """Build an agent transport. ``server_type="auto"`` negotiates the
    protocol against the live server; an explicit type is verified with a
    quick probe so a mismatched fleet errors at construction
    (:class:`ProtocolMismatchError`) rather than timing out on
    ``fetch_model``. Pass ``probe=False`` to skip the pre-flight check.
    """
    server_type = (server_type or "zmq").lower()
    if server_type != "auto" and server_type not in _KNOWN_TYPES:
        raise ValueError(
            f"unknown server_type {server_type!r} (zmq|grpc|native|auto)")
    should_probe = overrides.pop("probe", True)
    # Agents that start long before the server binds can spend more of
    # their handshake budget negotiating instead of hitting the 3s default
    # and splitting a mixed fleet on the local fallback (advisor r3).
    negotiate_window_s = float(overrides.pop("negotiate_window_s", 3.0))
    if server_type == "auto":
        server_type = (_negotiate_agent_auto(
                           config, overrides,
                           retry_window_s=negotiate_window_s)
                       if should_probe else _resolve_auto())
    elif should_probe:
        _verify_agent_protocol(server_type, config, overrides)
    # transport.retry: the unified handshake/connect backoff policy all
    # three backends share (transport/retry.py); an explicit override
    # dict wins over the config section.
    retry_cfg = overrides.get("retry", config.get_transport_params()["retry"])
    if server_type == "zmq":
        from relayrl_tpu.transport.zmq_backend import ZmqAgentTransport

        return ZmqAgentTransport(
            agent_listener_addr=_agent_handshake_addr("zmq", config, overrides),
            trajectory_addr=overrides.get(
                "trajectory_addr", config.get_traj_server().address),
            model_sub_addr=overrides.get(
                "model_sub_addr", config.get_train_server().address),
            identity=overrides.get("identity"),
            retry=retry_cfg,
        )
    if server_type == "grpc":
        from relayrl_tpu.transport.grpc_backend import GrpcAgentTransport

        return GrpcAgentTransport(
            server_addr=_agent_handshake_addr("grpc", config, overrides),
            identity=overrides.get("identity"),
            poll_timeout_s=config.get_grpc_idle_timeout_s() + 5.0,
            retry=retry_cfg,
        )
    from relayrl_tpu.transport.native_backend import NativeAgentTransport

    return NativeAgentTransport(
        server_addr=_agent_handshake_addr("native", config, overrides),
        identity=overrides.get("identity"),
        # transport.heartbeat_s config knob (was hard-coded 5.0 in
        # start_model_listener); an explicit override wins.
        heartbeat_s=overrides.get(
            "heartbeat_s", config.get_transport_params()["heartbeat_s"]),
        retry=retry_cfg,
    )


__all__ = [
    "ServerTransport",
    "AgentTransport",
    "ProtocolMismatchError",
    "probe_endpoint",
    "make_server_transport",
    "make_agent_transport",
    "pack_model_frame",
    "unpack_model_frame",
    "pack_trajectory_envelope",
    "unpack_trajectory_envelope",
]
