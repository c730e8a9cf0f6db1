"""The one span primitive of the learner process.

``with span(name, ...) as sp:`` reads ``time.monotonic_ns()`` once on entry
and once on exit and feeds every consumer of that interval from the one
pair of stamps:

1. **always-on totals**, where the site has one — ``into[key] += seconds``
   (``server.timings``) and ``metric.observe(seconds)`` (a registry
   histogram); callers that keep their total elsewhere read ``sp.seconds``;
2. **the profiler's time line** — while a ``jax.profiler`` session runs, a
   ``TraceAnnotation(name, **args)``, so the span sits in the xplane on the
   device trace's clock with its arguments as event stats. With the profiler
   off no annotation object is built;
3. **the sampled causal trace** — ``sp.hop(kind, trace_id, hop, **fields)``
   records a :mod:`relayrl_tpu.telemetry.trace` hop span with the same two
   stamps when the block exits (callers gate on the tracer's sampling).

Nothing switches it: sink 2 follows the profiler, sink 3
``telemetry.trace_sample_rate``.

Names. ``host:<phase>`` are the learner thread's sequential phases (at most
one open at a time on that thread; the benchmark's reducer attributes device
idle gaps to them). ``rl:<layer>.<what>`` is everything nested or on
another thread. docs/observability.md has the table.

Clocks. The stamps are CLOCK_MONOTONIC; the profiler has its own. The
once-per-update ``host:dispatch`` span carries its own start stamp as the
argument ``mono_ns``: ``event.start_ns - mono_ns`` of any one of them is the
shift that places ring spans and journal events on the profiler's clock.
"""

from __future__ import annotations

import time

_monotonic_ns = time.monotonic_ns
_annotation = None  # jax.profiler.TraceAnnotation, resolved on first use


def _profiling() -> bool:
    """Whether a profiler session is recording. Resolves the annotation
    class on the first call (not at import: telemetry is imported by
    processes that never load jax), then rebinds itself to the class's own
    check."""
    global _annotation, _profiling
    from jax.profiler import TraceAnnotation

    _annotation = TraceAnnotation
    _profiling = TraceAnnotation.is_enabled
    return _profiling()


class span:
    """One timed interval; see the module docstring. ``t0_ns``/``t1_ns``
    are the stamps, ``seconds`` their distance (after exit)."""

    __slots__ = ("name", "into", "key", "metric", "args", "t0_ns", "t1_ns",
                 "_ann", "_hops")

    def __init__(self, name: str, into=None, key: str | None = None,
                 metric=None, **args):
        self.name = name
        self.into = into
        self.key = key
        self.metric = metric
        self.args = args
        self._ann = None
        self._hops = None

    def __enter__(self) -> "span":
        if _profiling():
            self._ann = _annotation(self.name, **self.args)
            self._ann.__enter__()
        self.t0_ns = _monotonic_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = self.t1_ns = _monotonic_ns()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        if self.into is not None:
            self.into[self.key] += (t1 - self.t0_ns) * 1e-9
        if self.metric is not None:
            self.metric.observe((t1 - self.t0_ns) * 1e-9)
        if self._hops is not None:
            from relayrl_tpu.telemetry.trace import get_tracer

            tracer = get_tracer()
            for kind, trace_id, hop, fields in self._hops:
                tracer.span(kind, trace_id, hop, self.t0_ns, t1, **fields)
        return False

    @property
    def seconds(self) -> float:
        return (self.t1_ns - self.t0_ns) * 1e-9

    @property
    def traced(self) -> bool:
        """Whether a profiler session is recording this span: arguments
        that cost something to produce are worth producing only then."""
        return self._ann is not None

    def note(self, **args) -> None:
        """Arguments known only inside the block (a frame's size, the
        span's own start stamp): added to the annotation, if there is one."""
        if self._ann is not None:
            self._ann.set_metadata(**args)

    def hop(self, kind: str, trace_id: str, hop: str, **fields) -> None:
        """Also record this interval as a sampled causal hop span."""
        if self._hops is None:
            self._hops = []
        self._hops.append((kind, trace_id, hop, fields))


__all__ = ["span"]
