"""Epoch buffer: accumulate episodes host-side, emit device-ready batches.

Capability parity with the reference's REINFORCE buffer
(reference: relayrl_framework/src/native/python/algorithms/REINFORCE/
replay_buffer.py — per-step store, GAE on finish_path at :48-79, normalized
get() at :81-111), restructured for TPU: the host buffer only pads and
stacks; **all math (GAE, normalization) happens inside the jitted learner
step on device** so ingest overlaps compute and nothing round-trips
(SURVEY.md §7.4 item 1).
"""

from __future__ import annotations

from collections import deque
from typing import Sequence

import numpy as np

from relayrl_tpu.data.batching import (
    BatchStaging,
    TrajectoryBatch,
    copy_episode,
    copy_rows,
    decoded_obs_dtype,
    pad_decoded,
    pad_trajectory,
    padded_obs_dtype,
    pick_bucket,
    slab_row,
)
from relayrl_tpu.telemetry.spans import span
from relayrl_tpu.types.action import ActionRecord
from relayrl_tpu.types.columnar import DecodedTrajectory

DEFAULT_BUCKETS = (64, 256, 1000)


class _OpenBatch:
    """A batch being filled: its slab, the rows written so far, their
    valid steps, and the rows copied a second time (see
    :meth:`EpochBuffer.add_episode`)."""

    __slots__ = ("slab", "rows", "valid", "moved")

    def __init__(self, slab: dict[str, np.ndarray]):
        self.slab = slab
        self.rows = self.valid = self.moved = 0


class EpochBuffer:
    """Collects ``traj_per_epoch`` episodes, then drains one batch.

    Bucketing: each episode pads to the smallest configured bucket that fits;
    the drained batch uses the largest bucket present, so the learner step
    compiles once per (batch_size, bucket) pair.

    One copy: a batch's ``[traj_per_epoch, T, ...]`` slab is opened by its
    first episode, every episode is padded straight into its row, and
    :meth:`drain` hands the slab out. With staging on (the default) the
    slab comes from a ring of ``staging_slots`` persistent ones, and the
    caller owes the ring an order, stated at :meth:`add_episode`.
    """

    def __init__(
        self,
        obs_dim: int,
        act_dim: int,
        traj_per_epoch: int,
        discrete: bool = True,
        buckets: Sequence[int] = DEFAULT_BUCKETS,
        max_traj_length: int | None = None,
        staging_slots: int = 3,
    ):
        self.obs_dim = int(obs_dim)
        self.act_dim = int(act_dim)
        self.traj_per_epoch = int(traj_per_epoch)
        self.discrete = bool(discrete)
        # Sorted (and deduped) ONCE here; pick_bucket and warmup's
        # smallest-first early stop rely on ascending order instead of
        # re-sorting per trajectory on the ingest path.
        self.buckets = tuple(sorted({int(b) for b in buckets}))
        if max_traj_length is not None:
            self.buckets = tuple(b for b in self.buckets if b <= max_traj_length) or (
                int(max_traj_length),
            )
        # Construction-time invariant for every later ascending-order
        # consumer (guards future edits to the two rebuilds above).
        assert all(a < b for a, b in zip(self.buckets, self.buckets[1:])), \
            f"bucket lengths must be strictly ascending: {self.buckets}"
        # Zero-alloc assembly: batches are built in a ring of persistent
        # staging slabs instead of eight fresh arrays per epoch.
        # staging_slots=0 disables (every batch opens a fresh slab —
        # required when drained batches outlive `slots` further batches,
        # e.g. the multi-host broadcast queue).
        self._staging = (BatchStaging(staging_slots, self.obs_dim,
                                      self.act_dim, self.discrete)
                         if staging_slots else None)
        # Batches not yet drained, oldest first; only the last has rows
        # to fill. In the learner's order there is at most one.
        self._open: deque[_OpenBatch] = deque()
        # Obs dtype of the last batch drained (None before the first): a
        # batch keeps the dtype its episodes were decoded in, so this is
        # what the stream's next batch will most likely be — the warm-up
        # placeholder reads it (OnPolicyAlgorithm.mh_zero_batch).
        self.obs_dtype: np.dtype | None = None
        self._wire_obs = True
        self.episode_returns: list[float] = []
        self.episode_lengths: list[int] = []

    def disable_staging(self) -> None:
        """Every batch from now on opens a fresh slab (consumers that hold
        drained batches across later ones — the multi-host ready queue)."""
        self._staging = None

    def pin_float32_obs(self) -> None:
        """Every batch's obs is float32 whatever the episodes' (bytes widen
        exactly on their way into the row) — for a consumer whose peers
        must know the batch's dtypes without seeing the data: the
        multi-host broadcast describes a batch by (B, T) alone."""
        self._wire_obs = False
        self.obs_dtype = np.dtype(np.float32)

    def __len__(self) -> int:
        return sum(b.rows for b in self._open)

    @property
    def ready(self) -> bool:
        return bool(self._open) and self._open[0].rows >= self.traj_per_epoch

    def _slab(self, horizon: int, obs_dtype, first: bool
              ) -> dict[str, np.ndarray]:
        """A slab for the oldest open batch comes from the ring; one opened
        while an older batch waits to be drained is fresh, because the
        ring's order (see :meth:`add_episode`) says nothing about it."""
        if first and self._staging is not None:
            return self._staging.acquire(self.traj_per_epoch, horizon,
                                         obs_dtype)
        return TrajectoryBatch.zeros(self.traj_per_epoch, horizon,
                                     self.obs_dim, self.act_dim,
                                     self.discrete, obs_dtype=obs_dtype)

    def _batch_for(self, bucket: int, obs_dtype) -> _OpenBatch:
        """The open batch with a free row, in a slab no shorter than
        ``bucket`` and of an obs dtype no narrower than ``obs_dtype``."""
        if not self._wire_obs:
            obs_dtype = np.dtype(np.float32)
        batch = self._open[-1] if self._open else None
        if batch is None or batch.rows == self.traj_per_epoch:
            batch = _OpenBatch(self._slab(bucket, obs_dtype,
                                          first=not self._open))
            self._open.append(batch)
            return batch
        horizon, dtype = batch.slab["obs"].shape[1], batch.slab["obs"].dtype
        # The batch's key is its largest bucket and widest obs dtype (its
        # own episodes': a slab kept over reset() holds none yet).
        key = ((max(bucket, horizon), padded_obs_dtype((dtype, obs_dtype)))
               if batch.rows else (bucket, obs_dtype))
        if key != (horizon, dtype):
            # This episode raises the key: the rows filled so far move to
            # a slab of the new one — the copy every row once took.
            slab = self._slab(*key, first=batch is self._open[0])
            if batch.rows:
                copy_rows(slab, batch.slab, batch.rows)
            batch.slab = slab
            batch.moved += batch.rows
        return batch

    def add_episode(
        self, actions: Sequence[ActionRecord] | DecodedTrajectory
    ) -> bool:
        """Pad one episode into its row of the open batch; True when a
        batch is ready to drain.

        Accepts either the ActionRecord list (Python decode path) or a
        :class:`DecodedTrajectory` from the native columnar decoder —
        ``len()`` of both is the raw record count, so bucketing is
        identical across paths.

        The slab is keyed by (rows, horizon, obs dtype) from the batch's
        first episode. A later episode of a smaller bucket zero-fills its
        row's tail; one of a larger bucket or a wider obs dtype moves the
        rows filled so far into a slab of the new key (counted as
        ``moved`` on the batch's ``rl:batch.stack`` span).

        **Order the staging ring relies on.** The first episode after a
        drain takes the next slab of the ring and writes into it at once,
        so by then the update that last read that slab must be fenced.
        With ``staging_slots = window + 1`` it is, provided the caller
        hands each drained batch to ``train_on_batch`` (whose window push
        fences update ``k - window``) *before* it adds the next episode:
        ``drain k → stage_batch → train_on_batch k → add_episode``. Every
        caller in this repo does (``accumulate`` drains the moment a batch
        is full). ``stage_batch`` puts a large array as a flat view of
        the slab and shapes it on the device: the update reads the shaped
        array, the shaping runs once the bytes have landed, so the
        update's fence still says the slab was read. Episodes added
        while an older batch still waits to be drained go to a fresh slab,
        not to the ring; a caller that keeps drained batches longer calls
        :meth:`disable_staging`."""
        bucket = pick_bucket(len(actions), self.buckets)
        with span("rl:batch.pad"):
            if isinstance(actions, DecodedTrajectory):
                batch = self._batch_for(bucket, decoded_obs_dtype(actions))
                # shorter than the slab's horizon means shorter than its
                # own bucket: padding to either keeps the same steps
                padded = pad_decoded(
                    actions, batch.slab["obs"].shape[1], self.obs_dim,
                    self.act_dim, self.discrete,
                    out=slab_row(batch.slab, batch.rows))
            else:
                # not the hot path: padded apart, then copied into the row
                padded = pad_trajectory(actions, bucket, self.obs_dim,
                                        self.act_dim, self.discrete)
                batch = self._batch_for(bucket, padded.obs.dtype)
                copy_episode(slab_row(batch.slab, batch.rows), padded)
            batch.slab["last_val"][batch.rows] = padded.last_val
            batch.rows += 1
            batch.valid += padded.length
        # over the episode's own bucket, as when it was padded apart: a
        # float32 sum depends on the length summed over
        self.episode_returns.append(float(padded.rew[:bucket].sum()))
        self.episode_lengths.append(padded.length)
        return self.ready

    def drain(self) -> TrajectoryBatch:
        """Hand out the oldest open batch: the slab its episodes were
        padded into (its leading rows, a contiguous view, when the batch
        is part-filled). Its horizon is the largest bucket among them.

        With staging enabled (the default), the batch IS a persistent
        slab that the ring hands out again ``staging_slots`` batches
        later — valid under the algorithm in-flight window
        (``slots = window + 1``) in the order :meth:`add_episode` states:
        dispatch this batch before adding the next episode. Callers that
        hold batches longer (multi-host ready queues) must
        :meth:`disable_staging` first."""
        if not self._open or not self._open[0].rows:
            raise ValueError("drain() on empty buffer")
        done = self._open.popleft()
        slab, rows = done.slab, done.rows
        # Host numbers only, counted as the rows were written: a counter
        # never reads a device array.
        with span("rl:batch.stack", valid=done.valid,
                  padded=rows * slab["obs"].shape[1],
                  moved=done.moved) as sp:
            if rows < self.traj_per_epoch:
                slab = {name: arr[:rows] for name, arr in slab.items()}
            batch = TrajectoryBatch(**slab)
            sp.note(bytes=sum(arr.nbytes for arr in slab.values()))
        self.obs_dtype = batch.obs.dtype
        return batch

    def pop_episode_stats(self) -> tuple[list[float], list[int]]:
        rets, lens = self.episode_returns, self.episode_lengths
        self.episode_returns, self.episode_lengths = [], []
        return rets, lens

    def reset(self) -> None:
        """Drop the part-filled epoch (and its stats) — the guardrail
        rollback path: episodes buffered on a rolled-back line of
        history must not leak into the restored line's first epoch.
        The oldest open batch keeps its slab and starts again at row 0:
        a ring slab taken and never dispatched would put the ring one
        update ahead of the window."""
        while len(self._open) > 1:
            self._open.pop()
        for batch in self._open:
            batch.rows = batch.valid = batch.moved = 0
        self.episode_returns.clear()
        self.episode_lengths.clear()
