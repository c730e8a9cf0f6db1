"""The actor tier's time ledger, and its report to the learner.

An actor host (:class:`~relayrl_tpu.runtime.policy_actor.PolicyActor`,
:class:`~relayrl_tpu.runtime.vector_actor.VectorActorHost`) times its own
phases with the program's one span primitive (``telemetry/spans.py``) into
always-on totals, ``ledger.timings`` (seconds, as ``server.timings``) and
``ledger.counts`` beside it:

==================  ========================================================
``step_s``          inside ``rl:actor.step``: everything the program does on
                    the stepping thread (a request for actions, a terminal
                    marker, a recorded action); ``counts["steps"]`` += lanes
``infer_s``         ``rl:actor.infer``: the jitted policy call through the
                    last ``np.asarray`` of its results (the fence)
``record_s``        ``rl:actor.record`` SELF time: building the records and
                    ``add_action``, less the encode and send a flush nests
``encode_s``        ``rl:actor.encode``: one unroll serialised
``send_s``          ``rl:actor.send``: spool + transport send of one unroll
``env_s``           end of one ``rl:actor.step`` to the start of the next on
                    the same thread: the caller's environment and glue
``cpu_s``           the stepping thread's CPU time over the same cycles
                    (``cycle_cpu_ns`` of ``host:dispatch``, same meaning)
``wall_s``          ``step_s + env_s``: the ledger's own span of time
``model_decode_s``  ``rl:actor.model_decode`` (subscriber thread)
``swap_s``          ``rl:actor.swap``: lock wait + install (subscriber)
``model_install_s`` the subscriber's hand-over of a frame to installed;
                    ``counts["installs"]``
``gc_s``            ``rl:gc``: full collections (``spans.watch_gc``)
==================  ========================================================

``infer_s + record_s + encode_s + send_s <= step_s``; what is left of a step
(``normalize_obs``, the reward hand-back, the lock) has no name of its own.

**The report.** Every trajectory a host ships carries the ledger's deltas
since the host's previous shipment and the unroll's born stamp and version,
as the ``#r`` tag of the envelope id (``transport/base.py``): integers in a
fixed order — format version, ``born_ns`` (CLOCK_MONOTONIC; 0 where a ``#t``
trace context rides beside it and carries the stamp), the version the unroll
was born under, the counts, then the timings in whole microseconds.
Durations mean the same on another host; the born stamp is used only under
the skew guard (``telemetry.trace.SKEW_GUARD_NS``). The server's admission
funnel adds an ADMITTED envelope's deltas into ``server.timings["actor_<key>"]``
and ``server.stats["actor_<count>"]``; a replayed duplicate adds nothing.
"""

from __future__ import annotations

import contextlib
import threading
import time

from relayrl_tpu.telemetry.spans import span, watch_gc

REPORT_VERSION = 1
COUNTS = ("steps", "installs")
TIMINGS = ("step_s", "infer_s", "record_s", "encode_s", "send_s", "env_s",
           "cpu_s", "wall_s", "model_decode_s", "swap_s", "model_install_s",
           "gc_s")
_HEAD = 3  # format version, born_ns, born version


class ActorLedger:
    """One actor host's totals; see the module docstring. The stepping
    thread owns the cycle (``step``, ``record``, ``report``); the subscriber
    thread writes only its own keys."""

    def __init__(self):
        self.timings = dict.fromkeys(TIMINGS, 0.0)
        self.counts = dict.fromkeys(COUNTS, 0)
        # what the reports so far have carried, in the wire's units
        self._reported = [0] * (len(COUNTS) + len(TIMINGS))
        # the stepping thread, and the end of its previous step on both
        # clocks (wall, the thread's CPU)
        self._cycle: tuple[int, int, int] | None = None
        watch_gc(self)

    @contextlib.contextmanager
    def step(self, lanes: int):
        """``rl:actor.step`` round one call of the stepping thread, and the
        cycle's bookkeeping from the span's own stamps: ``env_s`` is the
        time since the previous step ended on this thread. The gap is
        booked when the step opens and the step when it closes, so that
        ``wall_s == step_s + env_s`` at every instant a report can be
        made (a full trajectory ships from inside a step)."""
        t = self.timings
        ident = threading.get_ident()
        cpu_ns = time.thread_time_ns()
        try:
            with span("rl:actor.step", t, "step_s") as sp:
                prev = self._cycle
                if prev is None or prev[0] != ident:
                    # a first step, or another thread took over: no
                    # environment time is known for this cycle
                    prev = (ident, sp.t0_ns, cpu_ns)
                gap = (sp.t0_ns - prev[1]) * 1e-9
                t["env_s"] += gap
                t["wall_s"] += gap
                t["cpu_s"] += (cpu_ns - prev[2]) * 1e-9
                yield sp
        finally:
            t["wall_s"] += sp.seconds
            end_cpu_ns = time.thread_time_ns()
            t["cpu_s"] += (end_cpu_ns - cpu_ns) * 1e-9
            self._cycle = (ident, sp.t1_ns, end_cpu_ns)
            self.counts["steps"] += lanes

    @contextlib.contextmanager
    def record(self):
        """``rl:actor.record``; ``record_s`` takes its SELF time — a full
        trajectory's flush nests its encode and send in the block."""
        t = self.timings
        nested = t["encode_s"] + t["send_s"]
        try:
            with span("rl:actor.record") as sp:
                yield sp
        finally:
            t["record_s"] += sp.seconds - (t["encode_s"] + t["send_s"]
                                           - nested)

    def report(self, born_ns: int, version: int) -> str:
        """The ``#r`` tag's text for the shipment being made now: born
        stamp, version, and every total's growth since the last call (whole
        counts and microseconds of the running totals, so nothing is lost
        to rounding between reports)."""
        counts, timings = self.counts, self.timings
        now = ([counts[k] for k in COUNTS]
               + [int(timings[k] * 1e6) for k in TIMINGS])
        before, self._reported = self._reported, now
        return encode_report(born_ns, version,
                             [a - b for a, b in zip(now, before)])


def encode_report(born_ns: int, version: int, deltas=()) -> str:
    """``deltas`` empty: a shipper that keeps no ledger (a thin client, the
    fused rollout host) still says when its unroll was born."""
    return ".".join(map("%x".__mod__, (
        REPORT_VERSION, born_ns, version & 0xFFFFFFFFFFFF, *deltas)))


class ActorReport:
    """A decoded ``#r`` tag: ``born_ns`` / ``born_version`` of the unroll
    and the shipper's ledger deltas (``counts``, ``timings`` in seconds;
    empty from a shipper without a ledger)."""

    __slots__ = ("born_ns", "born_version", "counts", "timings")

    def __init__(self, born_ns, born_version, counts, timings):
        self.born_ns = born_ns
        self.born_version = born_version
        self.counts = counts
        self.timings = timings


def decode_report(text: str) -> ActorReport | None:
    """None for a format version this build does not know or a field count
    that is not the version's: the tag is still stripped, nothing is
    added."""
    try:
        fields = [int(p, 16) for p in text.split(".")]
    except ValueError:
        return None
    if fields[0] != REPORT_VERSION or len(fields) not in (
            _HEAD, _HEAD + len(COUNTS) + len(TIMINGS)):
        return None
    body = fields[_HEAD:]
    counts = dict(zip(COUNTS, body))
    timings = {k: us * 1e-6 for k, us in zip(TIMINGS, body[len(COUNTS):])}
    return ActorReport(fields[1], fields[2], counts, timings)


__all__ = ["ActorLedger", "ActorReport", "COUNTS", "TIMINGS",
           "decode_report", "encode_report"]
