"""The benchmark's spans and counts round the learner's three calls.

``server._process_one`` (and the ``update`` driver, which makes the same
calls) looks ``accumulate``, ``stage_batch`` and ``train_on_batch`` up on
the algorithm INSTANCE, so shadowing them there puts a span round each
without editing the program. What is recorded:

* ``host:accumulate`` / ``host:stage_batch`` / ``host:dispatch`` spans;
* per assembled batch: its valid timesteps (padding does not count) and
  its bytes, from the host batch ``accumulate`` returns;
* per dispatched update: the version it produces and the instant
  (``time.monotonic``) the learner thread entered ``train_on_batch`` —
  the start of that version's model lag;
* the part of each dispatch spent blocked on the in-flight fence
  (``InflightWindow.device_wait_s`` before and after), so that
  ``dispatch_ms`` is host work and not device time.
"""

from __future__ import annotations

import time


class LearnerProbe:
    def __init__(self, run, algo):
        self.algo = algo
        self.valid_per_update: list[float] = []
        self.bytes_per_update: list[int] = []
        self.dispatched: list[tuple[int, float]] = []  # (version, t_call)
        self.wait_in_dispatch_s = 0.0
        self.last_metrics = None  # LazyMetrics of the newest dispatch
        spans = run.spans
        inner_acc, inner_stage, inner_train = (
            algo.accumulate, algo.stage_batch, algo.train_on_batch)

        def accumulate(item):
            with spans.span("accumulate"):
                got = inner_acc(item)
            if got is not None:
                for batch in (got if isinstance(got, list) else [got]):
                    self.valid_per_update.append(float(batch["valid"].sum()))
                    self.bytes_per_update.append(
                        sum(int(v.nbytes) for v in batch.values()))
            return got

        def stage_batch(batch):
            with spans.span("stage_batch"):
                return inner_stage(batch)

        def train_on_batch(batch):
            t_call = time.monotonic()
            wait0 = algo.inflight.device_wait_s
            with spans.span("dispatch"):
                out = inner_train(batch)
            self.wait_in_dispatch_s += algo.inflight.device_wait_s - wait0
            self.dispatched.append((int(algo.dispatched_version), t_call))
            self.last_metrics = out
            return out

        algo.accumulate = accumulate
        algo.stage_batch = stage_batch
        algo.train_on_batch = train_on_batch

    def mark(self) -> dict:
        """State at a window edge; two marks give the window's deltas."""
        win = self.algo.inflight
        return {"fenced": win.fenced_count, "dispatched": win.dispatch_count,
                "device_wait_s": win.device_wait_s,
                "wait_in_dispatch_s": self.wait_in_dispatch_s,
                "t": time.monotonic()}

    def fill(self, run, m0: dict, m1: dict) -> None:
        """Samples are the valid timesteps of the updates FENCED between
        the marks (update k of this process is the k-th push into the
        in-flight window; warm-up compiles do not push)."""
        fenced = slice(m0["fenced"], m1["fenced"])
        run.updates = m1["fenced"] - m0["fenced"]
        run.samples = sum(self.valid_per_update[fenced])
        sizes = self.bytes_per_update[fenced]
        run.counters.update(
            device_wait_s=m1["device_wait_s"] - m0["device_wait_s"],
            wait_in_dispatch_s=(m1["wait_in_dispatch_s"]
                                - m0["wait_in_dispatch_s"]),
            batch_bytes_mean=(sum(sizes) / len(sizes)) if sizes else 0.0,
            updates_dispatched=m1["dispatched"] - m0["dispatched"])
