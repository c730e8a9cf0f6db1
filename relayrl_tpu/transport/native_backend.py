"""Native C++ transport backend (ctypes bindings over native/librelayrl_native.so).

The reference's transport core is native Rust (tokio + zmq + tonic); the
TPU-native equivalent is the C++ core under ``native/`` — a framed-TCP
epoll event loop speaking the same envelopes as the Python backends.
This module is the thin ctypes binding; build the library with
``make -C native`` first.
"""

from __future__ import annotations

import os

from relayrl_tpu._native import find_library


def _try_build() -> None:
    """Best-effort `make -C native` when the toolchain is present."""
    import shutil
    import subprocess

    here = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    native_dir = os.path.join(here, "native")
    if not os.path.isfile(os.path.join(native_dir, "Makefile")):
        return
    if shutil.which("make") is None:
        return
    try:
        subprocess.run(["make", "-C", native_dir], check=True,
                       capture_output=True, timeout=120)
    except (subprocess.SubprocessError, OSError):
        pass


def native_available(build: bool = True) -> bool:
    if find_library() is not None:
        return True
    if build:
        _try_build()
    return find_library() is not None


def _require_lib() -> str:
    path = find_library()
    if path is None:
        _try_build()
        path = find_library()
    if path is None:
        raise RuntimeError(
            "native transport library not built and auto-build failed; run "
            "`make -C native` (falls back: use server_type='zmq' or 'grpc')")
    return path


# Real implementations are bound in native_bindings once the .so exists;
# import them lazily so zmq/grpc users never touch ctypes.
def NativeServerTransport(*args, **kwargs):
    from relayrl_tpu.transport.native_bindings import NativeServerTransportImpl

    return NativeServerTransportImpl(_require_lib(), *args, **kwargs)


def NativeGrpcServerTransport(*args, **kwargs):
    from relayrl_tpu.transport.native_bindings import (
        NativeGrpcServerTransportImpl,
    )

    return NativeGrpcServerTransportImpl(_require_lib(), *args, **kwargs)


def NativeAgentTransport(*args, **kwargs):
    from relayrl_tpu.transport.native_bindings import NativeAgentTransportImpl

    return NativeAgentTransportImpl(_require_lib(), *args, **kwargs)
