"""The learner's dispatch primitives: what an algorithm family holds
between handing an update to the device and somebody reading its result.

Podracer's Sebulba split (arxiv 2104.06272) gets TPU throughput from
overlapping host data work and model publishing with device compute; an
update is therefore a non-blocking dispatch, and these three pieces are
what every family's ``train_on_batch`` and ``snapshot_for_publish`` are
written against:

* :class:`LazyMetrics` — update metrics stay device arrays until
  ``log_epoch``/``stats`` actually read them, so ``train_on_batch``
  returns at dispatch instead of fencing every epoch.
* :class:`InflightWindow` — bounds how many dispatched-but-unfenced
  updates may be outstanding (donation-safe: the train state threads
  through dispatches in program order, so XLA sequences them; the bound
  only stops the host from running unboundedly ahead and anchors the
  staging-buffer reuse proof in ``data/batching.py``).
* :class:`PublishSnapshot` — the cheap handoff to whoever publishes
  (``runtime/pipeline.ModelPublisher``): a device-to-device params copy
  taken on the learner thread (dispatched async, never a host sync)
  that the publisher gathers and serializes off-thread. The copy is
  what makes the handoff donation-safe: the live state buffers may be
  consumed by the very next update while the publisher is still reading
  the snapshot.

The multi-host broadcast loop rides the same pieces: the sharded update
is just as much a non-blocking dispatch as the single-host one (its
collectives live inside the XLA program), so it enters the same
:class:`InflightWindow`; the publish handoff swaps the ``jnp.copy`` for
the algorithm's jitted re-shard-to-replicated gather (a collective every
rank dispatches at the same point — coordinator-side, the publisher
thread then reads one addressable shard of the replicated result).
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Any, Iterator, Mapping

from relayrl_tpu.telemetry.spans import span


class LazyMetrics(Mapping):
    """Mapping view over a dict of device scalars that resolves to host
    floats only when read. ``train_on_batch`` returns one of these at
    dispatch time; the fence happens where the value is consumed
    (``log_epoch``'s ``dump_tabular``, a test's ``_last_metrics[k]``),
    not on the learner hot path. Resolution is cached: the first read
    fences, later reads are free."""

    def __init__(self, device_metrics: Mapping[str, Any]):
        self._device = dict(device_metrics)
        self._host: dict[str, float] | None = None

    @property
    def device(self) -> dict[str, Any]:
        """The raw device arrays — what :class:`InflightWindow` fences."""
        return self._device

    def resolve(self) -> dict[str, float]:
        if self._host is None:
            self._host = {k: float(v) for k, v in self._device.items()}
        return self._host

    def __getitem__(self, key: str) -> float:
        return self.resolve()[key]

    def __iter__(self) -> Iterator[str]:
        return iter(self._device)

    def __len__(self) -> int:
        return len(self._device)

    def __repr__(self) -> str:
        state = "resolved" if self._host is not None else "in-flight"
        return f"LazyMetrics({sorted(self._device)}, {state})"


class InflightWindow:
    """Bounded window of dispatched-but-unfenced updates.

    Every dispatch pushes the update's output leaves (its metrics — made
    by the same XLA program as the new state, so "metrics ready" ⟺
    "update done"); pushing past ``max_in_flight`` fences the oldest
    first. ``max_in_flight=0`` degenerates to the old synchronous
    behavior (every dispatch fenced immediately) — the equivalence-test
    escape hatch and the operator's kill switch.

    Owned by the learner thread alone: no locks (deliberate — a fence
    under a lock is exactly the CONC01 stall jaxlint exists to catch).
    ``device_wait_s`` accumulates the real blocked time so the server's
    ``timings`` can report the fence separately from dispatch work.
    """

    def __init__(self, max_in_flight: int = 2):
        from relayrl_tpu import telemetry

        self.max_in_flight = max(0, int(max_in_flight))
        self._entries: deque[Any] = deque()
        self.dispatch_count = 0   # total updates ever pushed
        self.fenced_count = 0     # total updates known complete
        self.device_wait_s = 0.0
        reg = telemetry.get_registry()
        self._m_device_wait = reg.histogram(
            "relayrl_learner_device_wait_seconds",
            "learner thread blocked fencing an in-flight update")
        self._m_pending = reg.gauge(
            "relayrl_learner_inflight_pending",
            "dispatched-but-unfenced updates in the async window")

    @property
    def pending(self) -> int:
        """Dispatched-but-unfenced updates (the drain() contract)."""
        return len(self._entries)

    def push(self, fences: Any, version: int | None = None,
             note: tuple[str, ...] = ()) -> None:
        """Record one dispatched update; blocks only when the window is
        already full (fencing the oldest). ``version`` (the dispatching
        algorithm's host version mirror) labels the eventual fence span
        on the distributed-tracing plane — optional, never read
        otherwise. ``note`` names metrics of ``fences`` to write on that
        span as arguments: read back after the fence, and only while a
        profiler records."""
        self._entries.append((fences, version, note))
        self.dispatch_count += 1
        while len(self._entries) > self.max_in_flight:
            self._fence_oldest()
        self._m_pending.set(len(self._entries))

    def drain(self) -> None:
        """Fence every outstanding update (learner idle / shutdown /
        pre-checkpoint)."""
        while self._entries:
            self._fence_oldest()

    def _fence_oldest(self) -> None:
        import jax

        fences, version, note = self._entries.popleft()
        with span("rl:dispatch.fence", metric=self._m_device_wait,
                  version=-1 if version is None else int(version)) as sp:
            if version is not None:
                from relayrl_tpu.telemetry import trace as trace_mod

                tracer = trace_mod.get_tracer()
                if tracer.enabled and tracer.sample_version(version):
                    sp.hop("model", trace_mod.model_trace_id(version),
                           "fence", version=int(version))
            jax.block_until_ready(fences)
            if note and sp.traced:
                sp.note(**{k: float(fences[k]) for k in note})
        self.device_wait_s += sp.seconds
        self.fenced_count += 1
        self._m_pending.set(len(self._entries))


@dataclasses.dataclass
class PublishSnapshot:
    """Learner-thread handoff to the publisher: ``params`` are
    device-to-device copies (async dispatch, no host sync) so the next
    update's donation cannot invalidate them; ``version`` is the
    host-side dispatch mirror (reading ``state.step`` would fence)."""

    version: int
    arch: dict
    params: Any

    def host_params(self):
        """The blocking D2H gather — runs on the publisher thread, never
        the learner thread. The wire-v2 publish path consumes the host
        tree directly (the encoder keeps it as the next delta's base);
        :meth:`to_bundle` wraps it for the v1 full-bundle path.

        Multi-host snapshots carry the replicated output of the publish
        gather, which is not fully addressable — ``device_get`` refuses
        those, but every process holds a complete local copy, so one
        addressable shard IS the global value."""
        import jax
        import numpy as np

        def read(x):
            if isinstance(x, jax.Array) and not x.is_fully_addressable:
                return np.asarray(x.addressable_data(0))
            return jax.device_get(x)

        return jax.tree_util.tree_map(read, self.params)

    def to_bundle(self):
        from relayrl_tpu.types.model_bundle import ModelBundle

        return ModelBundle(version=self.version, arch=self.arch,
                           params=self.host_params())


__all__ = ["InflightWindow", "LazyMetrics", "PublishSnapshot"]
