"""Shared host-side orchestration for the off-policy algorithm family.

The reference registry whitelists C51/DDPG/DQN/SAC/TD3 without implementing
them (reference: relayrl_framework/src/sys_utils/config_loader.rs:148-159);
each of those here is a thin subclass of this base: transitions stream into
a :class:`~relayrl_tpu.data.StepReplayBuffer`, and after a warmup the
learner runs jitted gradient steps per received trajectory (the
"update-to-data ratio"), publishing a fresh actor policy each time
(``receive_trajectory -> True`` drives the server's model push exactly as
for the on-policy family — training_zmq.rs:1016-1029 behavior).

Subclasses implement ``_setup`` (build policy/arch/state + the pure jitted
``(state, batch) -> (state, metrics)`` update) and ``_actor_params``
(the slice of learner state that ships to actors in the ModelBundle).
"""

from __future__ import annotations

import os
from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax

from relayrl_tpu.algorithms.base import AlgorithmBase, anchor_path
from relayrl_tpu.algorithms.dispatch import LazyMetrics
from relayrl_tpu.config import ConfigLoader
from relayrl_tpu.data.step_buffer import StepReplayBuffer
from relayrl_tpu.telemetry.spans import span
from relayrl_tpu.types.action import ActionRecord
from relayrl_tpu.types.model_bundle import ModelBundle
from relayrl_tpu.utils import EpochLogger, setup_logger_kwargs


def polyak_update(online_params, target_params, polyak: float):
    """target <- polyak * target + (1 - polyak) * online (SpinningUp
    convention: polyak close to 1 means slow targets)."""
    return optax.incremental_update(online_params, target_params,
                                    step_size=1.0 - polyak)


class OffPolicyAlgorithm(AlgorithmBase):
    """Transition-replay learner loop shared by DQN/C51/DDPG/TD3/SAC."""

    ALGO_NAME = "OFFPOLICY"  # subclasses override
    DEFAULT_DISCRETE = True

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
        self.gamma = float(params.get("gamma", 0.99))
        self.polyak = float(params.get("polyak", 0.995))
        self.batch_size = int(params.get("batch_size", 256))
        self.update_after = int(params.get("update_after", 1000))
        self.updates_per_step = float(params.get("updates_per_step", 1.0))
        # Bound on jitted updates per receive_trajectory call: a long
        # episode past warmup owes stored*updates_per_step updates, but
        # running them all inside one ingest call starves the ingest queue
        # and delays the model publish for the whole burst. The backlog is
        # carried in ``_update_debt`` and amortized across future calls.
        self.max_updates_per_ingest = int(
            params.get("max_updates_per_ingest", 64))
        if self.max_updates_per_ingest < 1:
            raise ValueError(
                "max_updates_per_ingest must be >= 1 (it bounds the jitted "
                "updates run per ingest call; use updates_per_step=0 to "
                "disable training on ingest)")
        self._update_debt = 0.0
        # Dispatch fusion: run K sampled-batch updates inside ONE jitted
        # call (lax.scan over a [K, B, ...] stack). Small per-update
        # batches on a fast accelerator are dominated by per-dispatch
        # host->device latency (ROADMAP 1.6; no benchmark cell is small
        # enough to show it yet: a 2x128 MLP at B=256 would be); fusing K
        # of them amortizes that fixed cost without changing the math —
        # the scan threads state through the same K sequential updates
        # the unfused loop would run. Single-host only (the multi-host
        # broadcast loop ships one batch per collective step).
        self.updates_per_dispatch = max(
            1, int(params.get("updates_per_dispatch", 1)))
        self._update_k = None  # compiled lazily on first fused dispatch
        # Async-dispatch window (runtime/pipeline): how many updates may
        # be dispatched-but-unfenced. 0 = fence every dispatch.
        self.max_inflight_updates = int(params.get(
            "max_inflight_updates",
            learner.get("max_inflight_updates", 2)))
        # Persistent sample staging (zero-alloc steady state): sampled
        # batches write into a ring of reusable host buffers instead of
        # eight fresh fancy-index allocations per draw. Ring slots are
        # reused only after (round + window + 1) further draws — by then
        # the update that consumed the slot has been fenced by the
        # in-flight window (same proof as EpochBuffer's staging slabs).
        self._sample_ring: list[dict] = []
        self._sample_slot = 0
        self.traj_per_epoch = int(params.get("traj_per_epoch", 8))
        seed = int(params.get("seed", 1))
        # Param init is deterministic given the seed (reproducible learners);
        # only the action-sampling stream folds in the pid so concurrent
        # actor processes explore differently.
        self._rng_init = jax.random.PRNGKey(seed)
        self._rng_state = jax.random.fold_in(
            jax.random.PRNGKey(seed ^ 0x5EED),
            int(params.get("seed_salt", os.getpid())))

        self.buffer = StepReplayBuffer(
            obs_dim=self.obs_dim,
            act_dim=self.act_dim,
            capacity=int(buf_size or params.get("buffer_size", 100_000)),
            discrete=bool(params.get("discrete", self.DEFAULT_DISCRETE)),
            seed=seed,
            # "uint8" for pixel replay (pair with envs obs_dtype="uint8"):
            # 4x smaller ring/aux-checkpoint/device-transfer; the CNN
            # q-trunk casts + scales /255 on-device.
            obs_dtype=str(params.get("obs_dtype", "float32")),
        )

        # Subclass: sets self.policy, self.arch, self.state, self._update.
        self._setup(params, learner)

        lk = dict(logger_kwargs) if logger_kwargs else setup_logger_kwargs(
            f"relayrl-{self.ALGO_NAME.lower()}", seed,
            data_dir=os.path.join(env_dir or ".", "logs"))
        self.logger = EpochLogger(**lk)
        self.logger.save_config({"algorithm": self.ALGO_NAME, **params,
                                 "obs_dim": obs_dim, "act_dim": act_dim})
        self.epoch = 0
        self._traj_since_log = 0
        self._ep_returns: list[float] = []
        self._ep_lengths: list[int] = []
        self._last_metrics: dict[str, float] = {}
        self._mesh = None    # set by enable_multihost
        self._place = None   # mesh-aware batch placement
        # Relative default ("server_model.rlx") anchors under env_dir so
        # example runs don't litter the caller's cwd (see anchor_path).
        self.server_model_path = anchor_path(
            loader.get_server_model_path(), env_dir)

    # -- subclass contract --
    def _setup(self, params: dict, learner: dict) -> None:
        raise NotImplementedError

    def _actor_params(self):
        """Slice of self.state that the registered policy kind applies."""
        raise NotImplementedError

    def _publish_arch(self) -> dict:
        """Arch shipped with the bundle (hook for annealing exploration)."""
        return self.arch

    def _metric_keys(self) -> Sequence[str]:
        return ("LossQ",)

    # -- reference contract --
    def receive_trajectory(self, actions) -> bool:
        """Accepts ``Sequence[ActionRecord]`` (Python decode) or a
        :class:`~relayrl_tpu.types.columnar.DecodedTrajectory` (native
        columnar decode — marker rewards already folded, so the reward
        totals agree across paths)."""
        # accumulate() owns the empty/marker-only validation and the
        # update-debt ledger; here (single-host) the sampled batches train
        # immediately. Empty/marker-only trajectories (a capacity flush
        # can strand the terminal marker in its own send) store nothing
        # and log no phantom zero-length episode.
        batches = self.accumulate(actions)
        trained = False
        if batches:
            self.train_on_batches(batches)
            trained = True
        if self._traj_since_log >= self.traj_per_epoch:
            self.log_epoch()
        return trained

    def train_model(self) -> Mapping[str, float]:
        self._train_batches(1)
        return self._last_metrics

    def _train_batches(self, n: int) -> None:
        self.train_on_batches(
            [self.buffer.sample(self.batch_size) for _ in range(int(n))])

    def _fused_update(self):
        """jit(scan(update)) over a stacked [K, B, ...] batch — one
        dispatch for K sequential updates (same math as the loop; the
        inner already-jitted update inlines into the scan trace)."""
        if self._update_k is None:
            def run(state, stacked):
                return jax.lax.scan(
                    lambda s, b: self._update(s, b), state, stacked)

            self._update_k = jax.jit(run, donate_argnums=0)
        return self._update_k

    def train_on_batches(self, host_batches: Sequence[Mapping[str, Any]]
                         ) -> Mapping[str, float]:
        """Run the due updates, fusing groups of ``updates_per_dispatch``
        into single jitted dispatches; the remainder (and the K=1 or
        multi-host cases) go through the per-batch path."""
        k = self.updates_per_dispatch
        i, n = 0, len(host_batches)
        # _place is the mesh-aware [B, ...] placement — fused stacks are
        # [K, B, ...] and multi-host updates are one-batch collectives,
        # so fusion is single-host only.
        while k > 1 and self._place is None and n - i >= k:
            chunk = host_batches[i:i + k]
            # Device-prefetched batches stack ON DEVICE (async dispatch):
            # np.stack on a just-uploaded jax.Array would block on the
            # H2D, read it back, and re-upload the stack — a fence on the
            # dispatch-only thread.
            stacked = {
                key: (jnp.stack([b[key] for b in chunk])
                      if isinstance(chunk[0][key], jax.Array)
                      else np.stack([np.asarray(b[key]) for b in chunk]))
                for key in chunk[0]}
            self._sync_version_mirror()
            with self._dispatch_span(k):
                probe_base = self._guard_pre_update()
                with span("rl:dispatch.enqueue"):
                    self.state, ms = self._fused_update()(
                        self.state, self._to_device(stacked))
                self._dispatched_updates += k
                # Per-row device slices dispatch lazily — no host readback
                # on the dispatch path; resolution happens where the values
                # are read (log_epoch / a test's _last_metrics access).
                # Probes cover the whole fused dispatch (the k-th update's
                # params).
                self._last_metrics = LazyMetrics(self._guard_merge_probes(
                    {key: v[-1] for key, v in ms.items()}, probe_base))
                self.inflight.push((ms, self._last_metrics.device),
                                   version=self.dispatched_version)
            i += k
        for b in host_batches[i:]:
            self.train_on_batch(b)
        return self._last_metrics

    def train_on_batch(self, host_batch: Mapping[str, Any]
                       ) -> Mapping[str, float]:
        """One jitted update on a sampled transition batch, dispatched
        asynchronously (metrics resolve lazily; the in-flight window
        bounds outstanding updates). Multi-host: every process calls
        this with the same (broadcast) batch — the replay buffer itself
        stays coordinator-side."""
        self._sync_version_mirror()
        with self._dispatch_span():
            probe_base = self._guard_pre_update()
            with span("rl:dispatch.enqueue"):
                self.state, metrics = self._update(
                    self.state, self._to_device(host_batch))
            self._dispatched_updates += 1
            metrics = self._guard_merge_probes(metrics, probe_base)
            self._last_metrics = LazyMetrics(metrics)
            self.inflight.push(metrics, version=self.dispatched_version)
        # No logger.store here (the old per-update rows were never
        # consumed: log_epoch passes explicit values to log_tabular, so
        # the stored lists only grew for the life of the process — and as
        # device scalars they would also pin XLA buffers).
        return self._last_metrics

    def reset_ingest_buffers(self) -> None:
        """Guardrail rollback: stale-but-finite replay experience is
        valid off-policy data, so the ring is normally kept (or replaced
        wholesale by the restored checkpoint's aux snapshot). But when
        the ingest finite belt is standing down (guardrails' "warn"
        posture sets ``ingest_finite_guard = False``), admitted poison
        may sit in the ring — including inside a restored aux snapshot,
        whose healthy-at-save tag covers the params, not unsampled
        experience — and every post-restore update would re-diverge
        until the rollback budget burns down to halt. Scrub it."""
        if not self.ingest_finite_guard:
            dropped = self.buffer.scrub_nonfinite()
            if dropped:
                print(f"[guardrails] replay ring scrubbed after rollback: "
                      f"{dropped} non-finite transition(s) dropped",
                      flush=True)

    # -- multi-host contract (server broadcast loop; SURVEY §7.4 item 5) --
    def accumulate(self, item):
        """Coordinator-side ingest WITHOUT training: store the episode,
        keep the update-debt ledger, and return the list of sampled
        training batches now due (None when no update is due — warmup, or
        updates_per_step=0). The training step itself is collective:
        :meth:`train_on_batch` runs on every process with each batch."""
        from relayrl_tpu.types.columnar import (
            DecodedTrajectory,
            trajectory_is_finite,
        )

        with span("host:accumulate"):
            if isinstance(item, DecodedTrajectory):
                if item.n_steps == 0:
                    return None
                rew_total = item.total_reward
            elif not item or all(a.act is None for a in item):
                return None
            else:
                rew_total = float(sum(a.rew for a in item))
            if self.ingest_finite_guard and not trajectory_is_finite(item):
                # Replay poisoning is worse than the on-policy case — a
                # non-finite transition keeps resampling forever.
                self._drop_nonfinite()
                return None
            stored = self.buffer.add_episode(item)
            self._ep_returns.append(rew_total)
            self._ep_lengths.append(stored)
            self._traj_since_log += 1
            if (self.updates_per_step <= 0
                    or self.buffer.total_steps < self.update_after
                    or stored == 0):
                return None
            self._update_debt += stored * self.updates_per_step
            n = min(self.max_updates_per_ingest,
                    max(1, int(self._update_debt)))
            self._update_debt = max(0.0, self._update_debt - n)
            return [self._sample_staged(n) for _ in range(n)]

    def _sample_staged(self, round_size: int) -> dict:
        """One sampled batch written into a reusable staging slot (no
        per-draw allocation). Falls back to fresh allocations on a
        multi-process mesh, where the broadcast loop may queue batches
        (``_mh_ready``) long enough for the ring to lap them."""
        if self._place is not None or self._sample_ring is None:
            return self.buffer.sample(self.batch_size)
        # One in-flight WINDOW ENTRY covers up to updates_per_dispatch
        # batches (a fused dispatch pushes once for k consumed batches),
        # so the reuse distance must count batches, not dispatches:
        # while W entries are unfenced, W*k slots may still be feeding
        # async H2D transfers.
        need = (round_size
                + self.max_inflight_updates * self.updates_per_dispatch + 1)
        while len(self._sample_ring) < need:
            self._sample_ring.append(
                self.buffer.make_sample_out(self.batch_size))
        self._sample_slot = (self._sample_slot + 1) % len(self._sample_ring)
        return self.buffer.sample(self.batch_size,
                                  out=self._sample_ring[self._sample_slot])

    def mh_zero_batch(self, b: int, t: int) -> dict:
        """Placeholder transition batch matching :meth:`StepReplayBuffer.
        sample`'s schema — what non-coordinators feed the broadcast
        (values are overwritten; only shape/dtype matter). ``t`` is unused
        (transition batches have no time axis); the descriptor's second
        slot carries obs_dim instead."""
        act = (np.zeros((b,), np.int32) if self.buffer.discrete
               else np.zeros((b, self.act_dim), np.float32))
        obs_dt = self.buffer.obs_dtype  # warmup must match the ring dtype
        return {
            "obs": np.zeros((b, self.obs_dim), obs_dt),
            "act": act,
            "rew": np.zeros((b,), np.float32),
            "obs2": np.zeros((b, self.obs_dim), obs_dt),
            "mask2": np.ones((b, self.act_dim), np.float32),
            "done": np.zeros((b,), np.float32),
        }

    def checkpoint_aux(self):
        """Replay buffer contents (chronological) + counters: a resumed
        off-policy learner keeps its experience instead of re-warming from
        an empty ring (the reference loses everything but policy weights
        on restart — SURVEY §5.4)."""
        if len(self.buffer) == 0:
            return None
        return {"replay": self.buffer.state_arrays()}

    def restore_aux(self, aux) -> None:
        if aux and "replay" in aux:
            self.buffer.load_state_arrays(aux["replay"])

    def warmup(self, should_continue=None) -> int:
        """Replay samples are always ``[batch_size]`` transitions — one
        compile covers every training batch this family draws (two when
        dispatch fusion is on: the [K, B, ...] scan shape as well)."""
        if self._warmup_is_collective():
            return 0
        if self.batch_size > self.warmup_max_elements:
            return 0
        if should_continue is not None and not should_continue():
            return 0
        self._warmup_update(self.mh_zero_batch(self.batch_size, 0))
        done = 1
        k = self.updates_per_dispatch
        if (k > 1 and k * self.batch_size <= self.warmup_max_elements
                and (should_continue is None or should_continue())):
            single = self.mh_zero_batch(self.batch_size, 0)
            stacked = {key: np.stack([v] * k) for key, v in single.items()}
            # same copy/donation discipline as the single-shape warmup
            self._warmup_update(stacked, update_fn=self._fused_update())
            done += 1
        return done

    def maybe_log_epoch(self) -> None:
        """Epoch logging is per ``traj_per_epoch`` trajectories, not per
        update (the broadcast loop calls this after every collective
        step)."""
        if self._traj_since_log >= self.traj_per_epoch:
            self.log_epoch()

    def capture_epoch_stats(self, updated: bool):
        """A log is due on trajectory cadence — even without an update
        (pre-``update_after`` warmup still logs). Pops the episode
        counters NOW so the deferred log row matches what the old
        synchronous path would have printed."""
        if self._traj_since_log < self.traj_per_epoch:
            return None
        stats = (self._ep_returns or [0.0], self._ep_lengths or [0],
                 self.buffer.total_steps)
        self._ep_returns, self._ep_lengths = [], []
        self._traj_since_log = 0
        return stats

    def enable_multihost(self, mesh) -> None:
        """Re-compile the update over a (possibly multi-process) mesh and
        place the state on it; see OnPolicyAlgorithm.enable_multihost."""
        from relayrl_tpu.parallel import (
            make_sharded_update,
            place_batch,
            place_state,
        )
        from relayrl_tpu.parallel.sharding import replicated

        self._mesh = mesh
        self._update = make_sharded_update(self._update, mesh, self.state)
        self.state = place_state(self.state, mesh)
        self._place = lambda b: place_batch(b, mesh)
        self._gather_params = jax.jit(lambda p: p,
                                      out_shardings=replicated(mesh))
        # _mh_ready may hold sampled batches unboundedly before the
        # broadcast ships them, so sample-ring slot reuse is unsafe —
        # fall back to fresh per-sample allocations. The in-flight
        # window survives: the sharded update dispatches async (the
        # collective lives inside the XLA program, not on the host),
        # bounded by the same max_inflight_updates.
        self._inflight = None  # rebuilt over the (unchanged) window bound
        self._sample_ring = None

    def log_epoch(self, stats=None, metrics=None) -> None:
        """``stats``/``metrics`` are deferred :meth:`capture_epoch_stats`
        payloads (the pipelined server logs an epoch only after its
        update's fence, by which time ``_last_metrics`` may already
        belong to a newer update); without them the counters pop here
        and the latest metrics apply (the direct/synchronous path)."""
        if stats is None:
            stats = (self._ep_returns or [0.0], self._ep_lengths or [0],
                     self.buffer.total_steps)
            self._ep_returns, self._ep_lengths = [], []
            self._traj_since_log = 0
        if metrics is None:
            metrics = self._last_metrics
        rets, lens, total_steps = stats
        self.epoch += 1
        self.logger.store(EpRet=rets, EpLen=lens)
        self.logger.log_tabular("Epoch", self.epoch)
        self.logger.log_tabular("EpRet", with_min_and_max=True)
        self.logger.log_tabular("EpLen", average_only=True)
        self.logger.log_tabular("TotalEnvInteracts", total_steps)
        for key in self._metric_keys():
            self.logger.log_tabular(key, metrics.get(key, 0.0))
        self.logger.dump_tabular()

    def save(self, path=None) -> None:
        self.bundle().save(path or self.server_model_path)

    def _publish_params(self):
        return self._actor_params()

    def bundle(self) -> ModelBundle:
        """Multi-host: params may be sharded across processes; the jitted
        re-shard to replicated assembles the full copy, making this a
        COLLECTIVE when ``jax.process_count() > 1`` (the server's
        broadcast loop calls it at the same point on every process)."""
        params = self._actor_params()
        if self._mesh is not None and jax.process_count() > 1:
            params = self._gather_params(params)
            host_params = jax.tree_util.tree_map(
                lambda x: np.asarray(x.addressable_data(0)), params)
        else:
            host_params = jax.device_get(params)
        return ModelBundle(version=self.version, arch=self._publish_arch(),
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
        from relayrl_tpu.types.model_bundle import exploration_kwargs

        self._rng_state, sub = jax.random.split(self._rng_state)
        # Current (possibly annealed) exploration knobs ride as traced args.
        explore = exploration_kwargs(self._publish_arch())
        act, aux = self._jitted_policy_step()(
            self._actor_params(), sub, jnp.asarray(obs), mask, **explore)
        return np.asarray(act), {k: np.asarray(v) for k, v in aux.items()}


class EpsilonGreedyMixin:
    """Linear epsilon annealing shared by the epsilon-greedy family
    (DQN/C51): parse the schedule in ``_setup`` via ``_setup_epsilon``,
    publish the current value in the bundle arch."""

    def _setup_epsilon(self, params: dict) -> float:
        self.eps_start = float(params.get("epsilon_start", 1.0))
        self.eps_end = float(params.get("epsilon_end", 0.05))
        self.eps_decay_steps = int(params.get("epsilon_decay_steps", 10_000))
        return self.eps_start

    def current_epsilon(self) -> float:
        frac = min(1.0, self.buffer.total_steps / max(1, self.eps_decay_steps))
        return self.eps_start + frac * (self.eps_end - self.eps_start)

    def _publish_arch(self) -> dict:
        return {**self.arch, "epsilon": self.current_epsilon()}

    def _metric_keys(self):
        return ("LossQ", "QVals")
