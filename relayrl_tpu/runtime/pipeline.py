"""Off-thread model publish: the learner hands over a snapshot and goes on.

:class:`ModelPublisher` is a dedicated thread fed latest-wins: a slow
socket or artifact write never stalls training, and back-to-back epochs
coalesce into one publish of the newest params. What it is fed is an
``algorithms/dispatch.PublishSnapshot`` — a device-to-device params copy
the learner thread took at dispatch; the blocking gather and the
serialization run here, on the publisher's thread. The server's
``drain()`` counts ``pending`` (the queued slot plus a publish in
progress) on top of the dispatch window, in the multi-host broadcast loop
beside the ``_mh_ready``/``_mh_busy`` step flags.
"""

from __future__ import annotations

import threading
import time
from typing import TYPE_CHECKING, Callable

from relayrl_tpu.telemetry.spans import span

if TYPE_CHECKING:
    from relayrl_tpu.algorithms.dispatch import PublishSnapshot


class ModelPublisher:
    """Dedicated publish thread fed latest-wins.

    ``submit`` replaces any not-yet-started snapshot (the dropped one
    counts as ``coalesced`` — back-to-back epochs fold into one publish
    of the newest params); the publish callable runs outside the lock so
    a slow socket/disk never blocks the submitting learner thread.
    ``pending`` counts the queued slot plus an in-progress publish, which
    is what extends the server ``drain()`` contract to "the final publish
    landed"."""

    def __init__(self, publish_fn: Callable[[PublishSnapshot], None],
                 name: str = "model-publisher"):
        from relayrl_tpu import telemetry

        self._publish_fn = publish_fn
        self._cond = threading.Condition()
        self._slot: PublishSnapshot | None = None
        self._busy = False
        self._stop = False
        self.published = 0
        self.coalesced = 0
        self.errors = 0
        self.publish_s = 0.0
        reg = telemetry.get_registry()
        self._m_published = reg.counter(
            "relayrl_learner_publishes_total",
            "model publishes that landed (gather+serialize+send)")
        self._m_coalesced = reg.counter(
            "relayrl_learner_publish_coalesced_total",
            "queued publishes replaced latest-wins before starting")
        self._m_errors = reg.counter(
            "relayrl_learner_publish_errors_total",
            "publish attempts that raised (transient socket/fs)")
        self._m_publish = reg.histogram(
            "relayrl_learner_publish_seconds",
            "one publish on the publisher thread: D2H gather + serialize "
            "+ socket + artifact write")
        self._thread = threading.Thread(target=self._loop, name=name,
                                        daemon=True)
        self._thread.start()

    @property
    def pending(self) -> int:
        with self._cond:
            return int(self._slot is not None) + int(self._busy)

    def submit(self, snapshot: PublishSnapshot) -> None:
        with self._cond:
            if self._stop:
                return
            if self._slot is not None:
                self.coalesced += 1
                self._m_coalesced.inc()
            self._slot = snapshot
            self._cond.notify()

    def drain(self, timeout: float | None = None) -> bool:
        """Block until the queued + in-progress publishes have landed."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cond:
            while self._slot is not None or self._busy:
                remaining = (None if deadline is None
                             else deadline - time.monotonic())
                if remaining is not None and remaining <= 0:
                    return False
                self._cond.wait(remaining)
        return True

    def stop(self, timeout: float | None = 30.0) -> None:
        """Finish the pending publish (if any), then join the thread."""
        with self._cond:
            self._stop = True
            self._cond.notify()
        self._thread.join(timeout)

    def _loop(self) -> None:
        while True:
            with self._cond:
                while self._slot is None and not self._stop:
                    self._cond.wait()
                if self._slot is None and self._stop:
                    return
                snapshot, self._slot = self._slot, None
                self._busy = True
            sp = span("rl:publish", metric=self._m_publish,
                      version=int(getattr(snapshot, "version", -1)))
            try:
                with sp:
                    self._publish_fn(snapshot)
                self.published += 1
                self._m_published.inc()
            except Exception as e:  # a transient socket/fs error must not
                self.errors += 1    # kill the publish plane
                self._m_errors.inc()
                print(f"[ModelPublisher] publish error: {e!r}", flush=True)
            finally:
                self.publish_s += sp.seconds
                with self._cond:
                    self._busy = False
                    self._cond.notify_all()


__all__ = ["ModelPublisher"]
