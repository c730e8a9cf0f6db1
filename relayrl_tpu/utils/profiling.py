"""Profiling hooks: scoped ``jax.profiler`` trace capture and a timing helper.

TPU-native equivalent of the reference's tracing stack (SURVEY.md §5.1 —
tokio-console behind a feature flag plus an optional flamegraph dep):
scoped trace capture to disk and a block-until-ready timing helper for
quick latency checks without the full profiler. Named spans on the
profiler's time line are ``relayrl_tpu.telemetry.spans.span``.
"""

from __future__ import annotations

import contextlib
import time


@contextlib.contextmanager
def trace(log_dir: str, create_perfetto_link: bool = False):
    """Capture a trace of the enclosed block to ``log_dir`` (viewable in
    TensorBoard -> Profile, or Perfetto)."""
    import jax

    jax.profiler.start_trace(log_dir,
                             create_perfetto_link=create_perfetto_link)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def timed(fn, *args, **kwargs):
    """(result, seconds) with device work flushed — the
    ``block_until_ready`` timing harness of SURVEY.md §5.1."""
    import jax

    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0
