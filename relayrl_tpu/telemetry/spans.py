"""The one span primitive of every process of the program: the learner, its
staging and publisher threads, and the actor processes.

``with span(name, ...) as sp:`` reads ``time.monotonic_ns()`` once on entry
and once on exit and feeds every consumer of that interval from the one
pair of stamps:

1. **always-on totals**, where the site has one — ``into[key] += seconds``
   (``server.timings``) and ``metric.observe(seconds)`` (a registry
   histogram); callers that keep their total elsewhere read ``sp.seconds``;
2. **the profiler's time line** — while a ``jax.profiler`` session runs, a
   ``TraceAnnotation(name, **args)``, so the span sits in the xplane on the
   device trace's clock with its arguments as event stats, and with one
   argument of its own: ``cpu_ns``, the thread's CPU time inside the span
   (``time.thread_time_ns`` on entry and on exit; ``cpu_wall_ns`` beside it
   is the wall time between the same two reads — the annotation's own
   duration also holds both reads and the annotation's making, 11 us or more
   of a span that may last 80), so that ``cpu_wall_ns - cpu_ns`` of a span
   whose body does no I/O is the time its thread stood runnable or blocked.
   A NAME is stamped at most once in ``CPU_STAMP_EVERY_NS``: on the
   v5e machine's host one read of that clock is a 6 us system call under
   the interpreter's lock and the clock steps by 10 ms, so a pair on each of
   the 5,300 spans of a loop update cost a fifth of the traced window's rate
   and said no more than a sample of them does (PERF.md section 6, PR 70);
   a reader takes a name's ``cpu_ns`` over ``cpu_wall_ns`` from the spans
   that carry them (``benchmark/thread_account.py``). With the profiler off no
   annotation object is built and no CPU clock is read;
3. **the sampled causal trace** — ``sp.hop(kind, trace_id, hop, **fields)``
   records a :mod:`relayrl_tpu.telemetry.trace` hop span with the same two
   stamps when the block exits, or at once if it already has (an actor draws
   a trajectory's trace context after the unroll is encoded); callers gate
   on the tracer's sampling.

Nothing switches it: sink 2 follows the profiler, sink 3
``telemetry.trace_sample_rate``. In a process that never loaded jax (a thin
client) no profiler session can run: sink 2 is off and jax stays unloaded.

:func:`watch_gc` puts the interpreter's full collections on the same
primitive (``rl:gc``): the one stall of the host no other span can name.

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

import gc
import sys
import time
import weakref

_monotonic_ns = time.monotonic_ns
_annotation = None  # jax.profiler.TraceAnnotation, resolved on first use
# a span name's CPU stamps are at least this far apart (traced spans only):
# at most 0.2% of a thread's time a name at 6 us a clock read
CPU_STAMP_EVERY_NS = 5_000_000
_cpu_stamped: dict[str, int] = {}  # name -> start of its last stamped span


def _profiling() -> bool:
    """Whether a profiler session is recording. Resolves the annotation
    class on the first call in a process that has loaded jax (not at
    import, and never by importing it: telemetry is imported by processes
    that never load jax), then rebinds itself to the class's own check."""
    global _annotation, _profiling
    if "jax" not in sys.modules:
        return False
    from jax.profiler import TraceAnnotation

    _annotation = TraceAnnotation
    _profiling = TraceAnnotation.is_enabled
    return _profiling()


class span:
    """One timed interval; see the module docstring. ``t0_ns``/``t1_ns``
    are the stamps, ``seconds`` their distance (after exit)."""

    __slots__ = ("name", "into", "key", "metric", "args", "t0_ns", "t1_ns",
                 "_ann", "_cpu0_ns", "_hops")

    def __init__(self, name: str, into=None, key: str | None = None,
                 metric=None, **args):
        self.name = name
        self.into = into
        self.key = key
        self.metric = metric
        self.args = args
        self.t1_ns = 0
        self._ann = None
        self._hops = None

    def __enter__(self) -> "span":
        if _profiling():
            self._ann = _annotation(self.name, **self.args)
            self._ann.__enter__()
            now = _monotonic_ns()
            if now - _cpu_stamped.get(self.name, 0) >= CPU_STAMP_EVERY_NS:
                _cpu_stamped[self.name] = now
                self._cpu0_ns = (now, time.thread_time_ns())
            else:
                self._cpu0_ns = None
        self.t0_ns = _monotonic_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = self.t1_ns = _monotonic_ns()
        if self._ann is not None:
            if self._cpu0_ns is not None:
                # the wall time of the same bracket: from before the first
                # clock read to before the second, so each number holds
                # one read's worth of the two
                wall0, cpu0 = self._cpu0_ns
                self._ann.set_metadata(cpu_ns=time.thread_time_ns() - cpu0,
                                       cpu_wall_ns=t1 - wall0)
            self._ann.__exit__(exc_type, exc, tb)
        if self.into is not None:
            self.into[self.key] += (t1 - self.t0_ns) * 1e-9
        if self.metric is not None:
            self.metric.observe((t1 - self.t0_ns) * 1e-9)
        if self._hops is not None:
            for hop in self._hops:
                self._record_hop(*hop)
        return False

    def _record_hop(self, kind, trace_id, hop, fields) -> None:
        from relayrl_tpu.telemetry.trace import get_tracer

        get_tracer().span(kind, trace_id, hop, self.t0_ns, self.t1_ns,
                          **fields)

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
        """Also record this interval as a sampled causal hop span: when the
        block exits, or now if it has."""
        if self.t1_ns:
            self._record_hop(kind, trace_id, hop, fields)
            return
        if self._hops is None:
            self._hops = []
        self._hops.append((kind, trace_id, hop, fields))


# -- full collections --------------------------------------------------------

_gc_watchers: "weakref.WeakSet" = weakref.WeakSet()
_gc_span: span | None = None


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook: one ``rl:gc`` span a generation-2 collection
    (start and stop run on the collecting thread, and collections do not
    nest). Takes no lock — a collection can start under any — and is the
    only writer of ``gc_s``."""
    global _gc_span
    if info["generation"] != 2:
        return
    if phase == "start":
        _gc_span = span("rl:gc", generation=2)
        _gc_span.__enter__()
    elif _gc_span is not None:
        sp, _gc_span = _gc_span, None
        sp.note(collected=info["collected"])
        sp.__exit__(None, None, None)
        for owner in list(_gc_watchers):
            owner.timings["gc_s"] += sp.seconds


def watch_gc(owner=None) -> None:
    """Install the process's one collection hook (idempotent) and, for an
    ``owner`` that keeps a ledger, feed its ``owner.timings["gc_s"]`` for as
    long as it lives. Whoever builds a ledger calls this: the training
    server, the actor hosts, ``build_algorithm``."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    if owner is not None:
        _gc_watchers.add(owner)


__all__ = ["span", "watch_gc"]
