"""Shared host-side orchestration for the on-policy algorithm family.

REINFORCE, PPO, and IMPALA share one loop (the reference runs it inside its
learner subprocess — relayrl_framework/src/native/python/algorithms/
REINFORCE/REINFORCE.py:70-95: buffer episodes, train every
``traj_per_epoch``, log, save): episodes stream into an
:class:`~relayrl_tpu.data.EpochBuffer`, full epochs drain into one jitted
update, and ``receive_trajectory -> True`` drives the server's model
publish. Subclasses implement ``_setup`` (arch/policy/state + the pure
jitted ``(state, batch) -> (state, metrics)`` update) and ``_log_keys``.
"""

from __future__ import annotations

import os
from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from relayrl_tpu.algorithms.base import AlgorithmBase, anchor_path
from relayrl_tpu.algorithms.dispatch import LazyMetrics
from relayrl_tpu.config import ConfigLoader
from relayrl_tpu.config.loader import normalize_freeze_spec
from relayrl_tpu.data import EpochBuffer
from relayrl_tpu.telemetry.spans import span
from relayrl_tpu.types.action import ActionRecord
from relayrl_tpu.types.model_bundle import ModelBundle
from relayrl_tpu.utils import EpochLogger, setup_logger_kwargs


class OnPolicyAlgorithm(AlgorithmBase):
    """Epoch-buffer learner loop shared by REINFORCE/PPO/IMPALA."""

    ALGO_NAME = "ONPOLICY"  # subclasses override

    def __init__(
        self,
        env_dir: str | None = None,
        config_path: str | None = None,
        obs_dim: int = 4,
        act_dim: int = 2,
        buf_size: int | None = None,
        logger_kwargs: Mapping[str, Any] | None = None,
        **overrides,
    ):
        loader = ConfigLoader(self.ALGO_NAME, config_path,
                              create_if_missing=False)
        params = loader.get_algorithm_params()
        params.update(overrides)
        learner = loader.get_learner_params()

        self.obs_dim, self.act_dim = int(obs_dim), int(act_dim)
        self.discrete = bool(params.get("discrete", True))
        self.traj_per_epoch = int(params.get("traj_per_epoch", 8))
        self.gamma = float(params.get("gamma", 0.99))
        seed = int(params.get("seed", 1))
        # Ref seeds `seed + 10000 * proc_id` (REINFORCE.py:40-42); fold_in is
        # the JAX-native equivalent with better key hygiene.
        # seed_salt overrides the pid fold-in for deterministic runs
        # (learning tests, reproducibility studies) without patching os.
        salt = int(params.get("seed_salt", os.getpid()))
        rng = jax.random.fold_in(jax.random.PRNGKey(seed), salt)

        # Update metrics mirrored into registry gauges at dispatch, as
        # in-flight device scalars (``Gauge.set`` never fences), and those
        # of them written on the update's fence span while a profiler
        # records (each has a reader: docs/observability.md): filled by a
        # subclass whose update reports more than losses.
        self._metric_gauges: dict[str, Any] = {}
        self._fence_notes: tuple[str, ...] = ()
        # Subclass: sets self.arch, self.policy, self.state, self._update.
        self._setup(params, learner, rng)

        # Async-dispatch window (runtime/pipeline): how many updates may
        # be dispatched-but-unfenced. 0 = fence every dispatch (the old
        # synchronous behavior).
        self.max_inflight_updates = int(params.get(
            "max_inflight_updates",
            learner.get("max_inflight_updates", 2)))

        self.buffer = EpochBuffer(
            obs_dim=self.obs_dim,
            act_dim=self.act_dim,
            traj_per_epoch=self.traj_per_epoch,
            discrete=self.discrete,
            # Hyperparam override first: short fixed-horizon tasks (memory
            # envs) want tight buckets so sequence models size max_seq_len
            # to the real episode length, not the default padding.
            buckets=params.get(
                "bucket_lengths",
                learner.get("bucket_lengths", (64, 256, 1000))),
            max_traj_length=loader.get_max_traj_length(),
            # Staging slabs are reused after (window + 1) drains — by
            # then the window has fenced the update that consumed the
            # slab (see EpochBuffer.drain's reuse contract).
            staging_slots=self.max_inflight_updates + 1,
        )

        lk = dict(logger_kwargs) if logger_kwargs else setup_logger_kwargs(
            f"relayrl-{self.ALGO_NAME.lower()}", seed,
            data_dir=os.path.join(env_dir or ".", "logs"))
        self.logger = EpochLogger(**lk)
        self.logger.save_config({"algorithm": self.ALGO_NAME, **params,
                                 "obs_dim": obs_dim, "act_dim": act_dim})
        self.epoch = 0
        self._last_metrics: dict[str, float] = {}
        # A relative model path (the default "server_model.rlx") anchors
        # under env_dir so example runs don't litter the caller's cwd; an
        # absolute configured path is honoured verbatim.
        self.server_model_path = anchor_path(
            loader.get_server_model_path(), env_dir)
        self._mesh = None    # set by enable_multihost
        self._place = None   # mesh-aware batch placement

    # -- subclass contract --
    def _setup(self, params: dict, learner: dict, rng: jax.Array) -> None:
        raise NotImplementedError

    def _resolve_freeze(self, params: dict, learner: dict,
                        net_params) -> tuple[str, ...]:
        """The ``learner.freeze`` knob (per-algorithm ``freeze`` override
        wins): validated regex patterns over param leaf paths →
        optax.multi_transform masks (algorithms/freeze.py). Records
        ``self.freeze_info`` — which rides every checkpoint's JSON
        extras and is what the wire-v2 frozen-leaf savings claim is
        audited against. Shared by the whole family so the mask
        semantics cannot drift between REINFORCE/PPO/IMPALA."""
        from relayrl_tpu.algorithms.freeze import freeze_info

        patterns = normalize_freeze_spec(
            params.get("freeze", learner.get("freeze")))
        if not patterns:
            return ()
        self.freeze_info = freeze_info(net_params, patterns)
        if self.freeze_info["frozen_leaves"] == 0:
            import warnings

            warnings.warn(
                f"learner.freeze patterns {list(patterns)} matched no "
                f"param leaves — check them against e.g. "
                f"'params/block_0/qkv/kernel' style paths")
        print(f"[{self.ALGO_NAME}] learner.freeze: "
              f"{self.freeze_info['frozen_leaves']}/"
              f"{self.freeze_info['total_leaves']} leaves frozen "
              f"({self.freeze_info['frozen_bytes']} bytes) by "
              f"{list(patterns)}", flush=True)
        return patterns

    def _log_keys(self) -> Sequence[str]:
        return ("LossPi",)

    # -- reference contract --
    def receive_trajectory(self, actions) -> bool:
        """Accepts ``Sequence[ActionRecord]`` (Python decode) or a
        :class:`~relayrl_tpu.types.columnar.DecodedTrajectory` (native
        columnar decode — markers pre-folded)."""
        batch = self.accumulate(actions)
        if batch is None:
            return False
        self.train_on_batch(batch)
        self.log_epoch()
        return True

    def accumulate(self, item):
        """Buffer one trajectory WITHOUT training; returns the drained
        epoch batch dict when the buffer fills, else None. This is the
        single owner of the empty/marker-only validation;
        :meth:`receive_trajectory` is accumulate + train + log, and the
        multi-host server calls accumulate alone on the coordinator (the
        training step is collective — :meth:`train_on_batch` runs on
        every process with the broadcast batch)."""
        from relayrl_tpu.types.columnar import (
            DecodedTrajectory,
            trajectory_is_finite,
        )

        with span("host:accumulate"):
            if isinstance(item, DecodedTrajectory):
                if item.n_steps == 0:
                    return None
            elif not item or all(a.act is None for a in item):
                # Marker-only trajectories (stranded by a capacity flush)
                # carry no steps; padding would raise on the empty fold.
                return None
            if self.ingest_finite_guard and not trajectory_is_finite(item):
                self._drop_nonfinite()
                return None
            if self.buffer.add_episode(item):
                return self.buffer.drain().as_dict()
            return None

    def train_on_batch(self, host_batch: Mapping[str, Any]) -> Mapping[str, float]:
        """One jitted update on an assembled batch dict (host or device
        arrays), dispatched asynchronously: metrics come back as a
        :class:`~relayrl_tpu.algorithms.dispatch.LazyMetrics` that fences
        only when read (``log_epoch``/``stats``), and the in-flight
        window bounds how far dispatch runs ahead of the device.
        Multi-host: every process must call this with the same batch
        (see the server's broadcast loop)."""
        self._sync_version_mirror()
        with self._dispatch_span():
            # Health-probe base copy BEFORE the donating update (guardrails
            # plane; None without probes) — see base._guard_pre_update.
            probe_base = self._guard_pre_update()
            with span("rl:dispatch.enqueue"):
                self.state, metrics = self._update(
                    self.state, self._to_device(host_batch))
            self._dispatched_updates += 1
            for key, gauge in self._metric_gauges.items():
                gauge.set(metrics[key])
            metrics = self._guard_merge_probes(metrics, probe_base)
            self._last_metrics = LazyMetrics(metrics)
            self.inflight.push(metrics, version=self.dispatched_version,
                               note=self._fence_notes)
        return self._last_metrics

    def train_model(self) -> Mapping[str, float]:
        return self.train_on_batch(self.buffer.drain().as_dict())

    def mh_zero_batch(self, b: int, t: int) -> dict:
        """Placeholder epoch batch (shape/dtype only): what warm-up
        compiles the update for, and what non-coordinators feed the batch
        broadcast — the descriptor carries (B, T).

        A batch's obs keeps the dtype its episodes were decoded in
        (``data.batching.batch_obs_dtype``), so the placeholder takes the
        last drained batch's; multi-host pins that to float32 on every
        rank (:meth:`enable_multihost`). Before any data it goes by what
        the learner can see: a policy that scales its observations by
        1/255 (``scale_obs``, the pixel trunk) is fed byte frames, so
        uint8; everything else float32. A ``scale_obs`` learner fed
        float32 frames compiles once more, at its first batch."""
        from relayrl_tpu.data.batching import TrajectoryBatch

        obs_dtype = self.buffer.obs_dtype
        if obs_dtype is None:
            obs_dtype = (np.uint8 if self.policy.arch.get("scale_obs")
                         else np.float32)
        return TrajectoryBatch.zeros(b, t, self.obs_dim, self.act_dim,
                                     self.discrete, obs_dtype=obs_dtype)

    def warmup(self, should_continue=None) -> int:
        """Epoch batches are always ``[traj_per_epoch, bucket]`` — one
        compile per configured bucket length covers every batch this
        family can ever assemble. Buckets go smallest-first (they arrive
        sorted): short-episode tasks hit the small buckets, so an
        early-stopped warmup has most likely already compiled the shape
        that is about to be needed."""
        if self._warmup_is_collective():
            return 0
        compiled = 0
        for t in self.buffer.buckets:
            if self.traj_per_epoch * int(t) > self.warmup_max_elements:
                break  # buckets ascend: everything further is bigger
            if should_continue is not None and not should_continue():
                break
            self._warmup_update(
                self.mh_zero_batch(self.traj_per_epoch, int(t)))
            compiled += 1
        return compiled

    def maybe_log_epoch(self) -> None:
        # One collective update == one epoch for the on-policy family.
        self.log_epoch()

    def enable_multihost(self, mesh) -> None:
        """Re-compile the update over a (possibly multi-process) mesh and
        place the state on it. Call once, on every process, right after
        construction (identical seeds give identical initial state; see
        TrainingServer's seed_salt handling)."""
        from relayrl_tpu.parallel import (
            make_sharded_update,
            place_batch,
            place_state,
        )

        self._mesh = mesh
        self._update = make_sharded_update(self._update, mesh, self.state)
        self.state = place_state(self.state, mesh)
        self._place = lambda b: place_batch(b, mesh)
        # The broadcast loop queues assembled batches (_mh_ready) for an
        # unbounded time before training them — staging-slab reuse would
        # corrupt them — so host assembly keeps copying (staging off).
        # The in-flight window itself survives: the sharded update is a
        # non-blocking dispatch exactly like the single-host one (the
        # collective lives inside the XLA program, not on the host), so
        # the broadcast loop overlaps ingest/broadcast/prefetch with the
        # in-flight updates under the same max_inflight_updates bound.
        self.buffer.disable_staging()
        # ...and every rank must build the same placeholder from (B, T)
        # alone, so the coordinator's batches keep one obs dtype whatever
        # its actors send.
        self.buffer.pin_float32_obs()
        self._inflight = None  # rebuilt over the (unchanged) window bound
        # One jitted params gather, reused by every bundle() call (a fresh
        # lambda per call would retrace + recompile the all-gather each
        # publish).
        from relayrl_tpu.parallel.sharding import replicated

        self._gather_params = jax.jit(lambda p: p,
                                      out_shardings=replicated(mesh))

    def reset_ingest_buffers(self) -> None:
        """Guardrail rollback: a poisoned stream may have part-filled the
        epoch buffer; those episodes belong to the rolled-back line."""
        self.buffer.reset()

    def capture_epoch_stats(self, updated: bool):
        """One update == one epoch for this family: a log is due exactly
        when an update dispatched. Pops the episode stats NOW so
        episodes arriving while the update is still in flight land in
        the next epoch's row, not this one's."""
        if not updated:
            return None
        return self.buffer.pop_episode_stats()

    def log_epoch(self, stats=None, metrics=None) -> None:
        """``stats``/``metrics`` are deferred :meth:`capture_epoch_stats`
        payloads (the pipelined server logs an epoch only after its
        update's fence, by which time ``_last_metrics`` may already
        belong to a newer update); without them the episode stats pop
        here and the latest metrics apply (the direct/synchronous
        path). Reading the metrics is what fences the update."""
        rets, lens = (self.buffer.pop_episode_stats() if stats is None
                      else stats)
        if metrics is None:
            metrics = self._last_metrics
        self.epoch += 1
        self.logger.store(EpRet=rets or [0.0], EpLen=lens or [0])
        self.logger.log_tabular("Epoch", self.epoch)
        self.logger.log_tabular("EpRet", with_min_and_max=True)
        self.logger.log_tabular("EpLen", average_only=True)
        for key in self._log_keys():
            self.logger.log_tabular(key, metrics.get(key, 0.0))
        self.logger.dump_tabular()

    def save(self, path=None) -> None:
        self.bundle().save(path or self.server_model_path)

    def _publish_params(self):
        return self.state.params

    def bundle(self) -> ModelBundle:
        """Serialize the current policy for actors.

        Multi-host: params may be sharded across processes; an all-gather
        (re-shard to replicated) assembles the full copy — which makes
        this a COLLECTIVE when ``jax.process_count() > 1``: every process
        must call it at the same point (the server's broadcast loop does).
        """
        params = self.state.params
        if self._mesh is not None and jax.process_count() > 1:
            params = self._gather_params(params)
            host_params = jax.tree_util.tree_map(
                lambda x: np.asarray(x.addressable_data(0)), params)
        else:
            host_params = jax.device_get(params)
        return ModelBundle(version=self.version, arch=self.arch,
                           params=host_params)

    @property
    def version(self) -> int:
        step = self.state.step
        try:
            return int(step)
        except Exception:  # multi-host replicated array: read a local shard
            return int(np.asarray(step.addressable_data(0)))

    # convenience for in-process actors/tests
    def act(self, obs, mask=None):
        rng, sub = jax.random.split(self.state.rng)
        self.state = self.state.replace(rng=rng)
        act, aux = self._jitted_policy_step()(self.state.params, sub,
                                              jnp.asarray(obs), mask)
        return np.asarray(act), {k: np.asarray(v) for k, v in aux.items()}
