"""Algorithm plugin contract.

Capability parity with the reference's learner plugin interface
(reference: relayrl_framework/src/native/python/_common/_algorithms/
BaseAlgorithm.py:4-39 — ``save``, ``receive_trajectory -> bool``,
``train_model``, ``log_epoch``), extended with the TPU-native pieces the
reference lacks: a pure jitted ``learner_step``, a versioned
:class:`~relayrl_tpu.types.ModelBundle` surface for transport, and full
checkpoint/resume (params + optimizer state + RNG + counters; the
reference checkpoints only the TorchScript policy file — SURVEY.md §5.4).

Algorithms register by name; the training server resolves
``algorithm_name`` through :func:`build_algorithm` the way the reference's
learner subprocess dynamically imports ``{ALGO}.{ALGO}``
(python_algorithm_reply.py:41-46).
"""

from __future__ import annotations

import abc
import contextlib
import functools
import threading
import time
from typing import Any, Callable, Mapping, Sequence

from relayrl_tpu import telemetry
from relayrl_tpu.algorithms.dispatch import InflightWindow, PublishSnapshot
from relayrl_tpu.telemetry.spans import span, watch_gc
from relayrl_tpu.types.action import ActionRecord
from relayrl_tpu.types.model_bundle import ModelBundle

_ALGO_REGISTRY: dict[str, Callable[..., "AlgorithmBase"]] = {}


def register_algorithm(name: str):
    def deco(cls):
        _ALGO_REGISTRY[name.upper()] = cls
        return cls
    return deco


def build_algorithm(name: str, **kwargs) -> "AlgorithmBase":
    try:
        cls = _ALGO_REGISTRY[name.upper()]
    except KeyError:
        raise ValueError(
            f"unknown algorithm {name!r}; registered: {sorted(_ALGO_REGISTRY)}"
        ) from None
    algo = cls(**kwargs)
    own_loss = getattr(getattr(algo, "policy", None), "own_loss", None)
    if own_loss and not cls.ADDS_OWN_LOSS:
        raise ValueError(
            f"{name}: the policy {algo.arch.get('kind')!r} brings a loss of "
            f"its own ({own_loss}: models/base.Policy.own_loss) that this "
            f"algorithm's update would drop — its indexers would never "
            f"train; IMPALA's update adds it")
    # A process that updates names its full collections (rl:gc): a stall
    # of the dispatching thread no other span can account for.
    watch_gc()
    return algo


def registered_algorithms() -> list[str]:
    return sorted(_ALGO_REGISTRY)


def anchor_path(path: str, env_dir: str | None) -> str:
    """Anchor a relative artifact path (model file, checkpoint dir) under
    ``env_dir`` so default-named run artifacts land in the run's directory
    instead of the caller's cwd. Absolute paths pass through untouched."""
    import os

    if env_dir and not os.path.isabs(path):
        return os.path.join(env_dir, path)
    return path


# ``stage_batch``'s rule for a large array: one of at least this many bytes
# crosses as its flat bytes and is given its shape on the device. A constant,
# not configuration (the code reads the array's size), from a sweep on the
# v5e (PERF.md section 6, PR 64): at 36 MB the two ways land together, at 72 MB
# and above the flat one lands in half the time or less, and at
# nature-cnn.update's 289 MB of [512, 20, 28224] uint8 frames it takes the
# host an eighth of the CPU time.
_H2D_FLAT_BYTES = 64 << 20


@functools.cache
def _shape_on_device():
    """The device's half of a flat put: one small program that gives the
    flat bytes the host array's shape, built on first use (this module
    loads without jax)."""
    import jax

    @functools.partial(jax.jit, static_argnums=1)
    def relayrl_staged_shape(flat, shape):
        return flat.reshape(shape)

    return relayrl_staged_shape


class AlgorithmBase(abc.ABC):
    """Host-side orchestration wrapper around a pure jitted learner step."""

    # Warmup executes one real (discarded) update per shape, so its cost
    # scales with B*T (times vf iters for the actor-critic families) — a
    # [2001, 1000] placeholder epoch measured 4+ minutes on a 1-core host.
    # Shapes above this B*T bound are skipped and compile on first use
    # instead (the bound covers every default config: traj_per_epoch=8 x
    # the largest default bucket 1000 = 8000; override per-instance when a
    # deployment with bigger epochs wants full pre-compilation anyway).
    warmup_max_elements = 32768

    # Whether the update adds the loss a model brings itself
    # (``models/base.Policy.own_loss``); ``build_algorithm`` refuses such a
    # policy for an algorithm that would drop it.
    ADDS_OWN_LOSS = False

    # Trajectories rejected by the ingest finite-value guard
    # (types/columnar.py trajectory_is_finite); class default so the
    # first increment materializes the instance counter.
    dropped_nonfinite = 0

    # The per-algorithm finite guard's enable flag. The guardrail plane
    # (relayrl_tpu/guardrails) sets it False ONLY in the observe-only
    # "warn" validation mode — the plane then owns the boundary and this
    # belt must stand down or warn-mode silently re-enforces. Everywhere
    # else it stays True (belt-and-suspenders under "enforce").
    ingest_finite_guard = True

    # Divergence-watchdog probe source (guardrails/watchdog.GuardProbes),
    # installed by Guardrails.attach_algorithm; None = no probes, the
    # dispatch paths pay one identity check.
    _guard_probes = None

    # Bounded async-dispatch window (algorithms/dispatch.InflightWindow);
    # class defaults so pre-existing subclasses/tests that never touch
    # the pipeline keep working. max_inflight_updates=0 restores the
    # fully synchronous fence-every-dispatch behavior.
    max_inflight_updates = 2
    _inflight = None
    # Host-side mirror of state.step: once updates dispatch async,
    # reading int(state.step) fences the whole in-flight window, so the
    # publish path needs a version that never touches the device. None
    # until the first dispatch (or after a checkpoint restore) — it
    # re-syncs from the (then resolved) device step before dispatching.
    _dispatched_updates = None
    # The dispatching thread and its CPU clock at its previous dispatch
    # (``_dispatch_span``).
    _cpu_mark = None
    # ``start_ns -> {argument: value}``: what the algorithm's owner adds to
    # a ``host:dispatch`` span (the training server: the batch's data age).
    _dispatch_note = None

    @contextlib.contextmanager
    def _dispatch_span(self, updates: int = 1):
        """The once-per-dispatch ``host:dispatch`` span. Its arguments are
        what only the program knows at this instant: the ``version`` the
        dispatch produces, ``mono_ns`` (its own start stamp: the shift
        from CLOCK_MONOTONIC to the profiler's clock), ``cycle_cpu_ns``,
        this thread's CPU time since its previous dispatch — against the
        wall time between the two it says how long the thread was off the
        CPU (blocked, or runnable and not running) — and, under a training
        server fed by actors that say when an unroll was born,
        ``data_age_us`` / ``data_age_max_us`` of the batch
        (``_dispatch_note``)."""
        cpu_ns = time.thread_time_ns()
        ident = threading.get_ident()
        mark = self._cpu_mark
        cycle = cpu_ns - mark[1] if mark and mark[0] == ident else 0
        self._cpu_mark = (ident, cpu_ns)
        with span("host:dispatch",
                  version=self._dispatched_updates + updates,
                  cycle_cpu_ns=cycle) as sp:
            note = self._dispatch_note
            sp.note(mono_ns=sp.t0_ns, **(note(sp.t0_ns) if note else {}))
            yield

    def _drop_nonfinite(self) -> None:
        """Count + log one trajectory rejected by the finite-value guard —
        the single owner of the drop policy for both algorithm families
        (a NaN/inf would not crash; it would silently poison the learner
        state and, through the next publish, the fleet)."""
        self.dropped_nonfinite += 1
        print(f"[{self.ALGO_NAME}] dropped non-finite trajectory "
              f"(#{self.dropped_nonfinite})", flush=True)

    # -- reference contract (BaseAlgorithm.py:4-39) --
    @abc.abstractmethod
    def receive_trajectory(self, actions: Sequence[ActionRecord]) -> bool:
        """Ingest one episode; returns True when a train step ran (the
        training server publishes a new model on True, mirroring
        training_zmq.rs:1016-1029)."""

    @abc.abstractmethod
    def train_model(self) -> Mapping[str, Any]:
        """Run one epoch update; returns metrics."""

    @abc.abstractmethod
    def save(self, path) -> None:
        """Write the distributable model artifact (ref: torch.jit.save)."""

    @abc.abstractmethod
    def log_epoch(self) -> None:
        """Dump the epoch's tabular diagnostics."""

    # -- multi-host contract (optional; the TrainingServer broadcast loop
    # uses it when jax.process_count() > 1 — SURVEY §7.4 item 5). A family
    # supports multi-host by providing:
    #   accumulate(item)       coordinator-side ingest, returns ready host
    #                          batch(es) (dict, list of dicts, or None)
    #   train_on_batch(batch)  the collective update, called on every rank
    #   mh_zero_batch(d1, d2)  shape/dtype placeholder for non-coordinators
    #   maybe_log_epoch()      epoch logging policy after a collective step
    #   enable_multihost(mesh) re-compile the update over the global mesh

    # -- TPU-native surface --
    def warmup(self, should_continue=None) -> int:
        """Pre-compile the jitted update for every batch shape the first
        real epochs can hit, so the first update under load is a cache
        hit instead of a compile. XLA compiles on a learner thread that —
        in a one-process, few-core deployment (a notebook kernel hosting
        both the server and a busy actor loop) — otherwise competes with
        the actor for CPU and can stretch a ~2 s compile past the whole
        example run. Returns the number of shapes compiled; families
        without a known shape set return 0. Best-effort: callers treat
        failures as non-fatal.

        ``should_continue`` (nullary → bool) is consulted before each
        shape: once real work is already queued, compiling on demand is
        just as fast as warming up, so implementations stop early instead
        of pre-paying shapes the caller may never hit.
        """
        return 0

    def checkpoint_aux(self):
        """Host-side arrays to persist alongside the train state (a pytree
        of numpy arrays, or None). The off-policy family returns its
        replay buffer here; on-policy has no host state worth carrying
        (an epoch buffer refills within one epoch)."""
        return None

    def restore_aux(self, aux) -> None:
        """Apply a previously saved :meth:`checkpoint_aux` payload."""

    def _warmup_is_collective(self) -> bool:
        """True when this algorithm's update is a multi-process collective
        (``enable_multihost`` over >1 jax processes) — warming up solo
        would hang every other rank in the collective, so family
        ``warmup()`` implementations refuse and return 0. This guard lives
        at the algorithm altitude on purpose: the server's broadcast loop
        is not the only possible caller."""
        if getattr(self, "_mesh", None) is None:
            return False
        import jax

        return jax.process_count() > 1

    @property
    def inflight(self) -> InflightWindow:
        """The dispatched-but-unfenced update window, created lazily so
        algorithms built before any training pay nothing. One per
        instance: every family's ``train_on_batch`` pushes its update's
        metric leaves here, which (a) bounds how far the host runs ahead
        of the device and (b) is the fence ledger the server's
        ``drain()`` and the staging-buffer reuse proof rely on."""
        if self._inflight is None:
            self._inflight = InflightWindow(self.max_inflight_updates)
        return self._inflight

    # -- divergence-watchdog probes (guardrails plane) --
    def _guard_probe_tree(self):
        """The param tree the health probes observe. The on-policy and
        value families keep trainable params at ``state.params``; the
        actor-critic families (SAC/DDPG/TD3) split them across
        ``*_params`` fields — collect those, excluding ``target_*``
        (polyak copies of what is already probed). Anything else falls
        back to the whole state tree: the finiteness probe stays
        meaningful on any pytree of arrays."""
        state = self.state
        params = getattr(state, "params", None)
        if params is not None:
            return params
        fields = getattr(type(state), "__dataclass_fields__", None)
        if fields:
            tree = {name: getattr(state, name) for name in fields
                    if name.endswith("_params")
                    and not name.startswith("target_")}
            if tree:
                return tree
        return state

    def _guard_pre_update(self):
        """Async D2D copy of the probe target, taken BEFORE the donating
        update so the old buffers are still live (the update-norm
        probe's base). None when probes are off — one identity check.
        A probe failure DISABLES probes (logged once) instead of
        propagating: the guardrail plane must never break the learner
        it protects."""
        probes = self._guard_probes
        if probes is None:
            return None
        try:
            return probes.pre_update(self._guard_probe_tree())
        except Exception as e:
            self._guard_probes = None
            print(f"[guardrails] health probes DISABLED "
                  f"(pre-update probe failed: {e!r})", flush=True)
            return None

    def _guard_merge_probes(self, metrics, old_copy) -> Mapping[str, Any]:
        """Merge the post-update probe scalars (unresolved device
        arrays) into ``metrics``; pass-through when probes are off. The
        merged dict rides the in-flight window and LazyMetrics exactly
        like the update's own metrics — resolved at the fence, never on
        the dispatch path."""
        probes = self._guard_probes
        if probes is None:
            return metrics
        merged = dict(metrics)
        try:
            merged.update(probes.post_update(old_copy,
                                             self._guard_probe_tree()))
        except Exception as e:
            self._guard_probes = None
            print(f"[guardrails] health probes DISABLED "
                  f"(post-update probe failed: {e!r})", flush=True)
            return metrics
        return merged

    def force_version(self, version: int) -> None:
        """Fast-forward the model version PAST a rolled-back line of
        history (guardrail rollback): the restored params keep training
        under a version higher than anything the poisoned line
        published, so actor swap gates, artifact gates, and checkpoint
        step numbering all stay monotonic. Step numbers are labels — the
        true state is the restored tree (checkpoint/manager.py)."""
        import jax.numpy as jnp

        step = self.state.step
        self.state = self.state.replace(
            step=jnp.asarray(int(version), dtype=step.dtype))
        self._dispatched_updates = None

    def reset_ingest_buffers(self) -> None:
        """Drop partially-accumulated host-side ingest state after a
        rollback (a poisoned stream may have part-filled it). Base:
        nothing to drop; on-policy clears its epoch buffer. The
        off-policy replay ring is restored by the checkpoint's aux
        snapshot instead (or deliberately kept when the step carried
        none — stale-but-finite experience is valid off-policy data)."""

    def _sync_version_mirror(self) -> None:
        """Initialize the host-side step mirror BEFORE the first async
        dispatch — at that point ``state.step`` is resolved (construction
        or checkpoint restore both finish synchronously), so the one
        ``int()`` here is free; after dispatching it would fence."""
        if self._dispatched_updates is None:
            self._dispatched_updates = int(self.version)

    @property
    def dispatched_version(self) -> int:
        """Model version including dispatched-but-unfenced updates —
        what an async publish stamps on its snapshot (``version`` reads
        the device and would fence the in-flight window)."""
        if self._dispatched_updates is not None:
            return self._dispatched_updates
        return int(self.version)

    def snapshot_for_publish(self):
        """Cheap, non-blocking publish handoff: a device-to-device copy
        of the publishable params (dispatched async — the copy runs
        after the last queued update, so it observes it) stamped with
        the host-side version mirror. The publisher thread turns it into
        a :class:`~relayrl_tpu.types.ModelBundle` with the blocking
        ``device_get`` off the learner thread.

        On a mesh (``enable_multihost``) the copy is the jitted
        re-shard-to-replicated ``_gather_params`` — still a non-blocking
        dispatch, but on a multi-process mesh it is a COLLECTIVE: every
        rank must call this at the same point (the server's broadcast
        loop does); the coordinator's publisher thread then reads one
        local shard of the replicated result (``host_params`` handles
        the non-fully-addressable read).
        """
        import jax
        import jax.numpy as jnp

        gather = getattr(self, "_gather_params", None)
        if gather is not None:
            # A fresh replicated buffer (jit never aliases output to a
            # non-donated input), so the next update's donation cannot
            # invalidate it — the same safety jnp.copy provides below.
            params = gather(self._publish_params())
        else:
            params = jax.tree_util.tree_map(
                lambda x: jnp.copy(x) if isinstance(x, jax.Array) else x,
                self._publish_params())
        return PublishSnapshot(version=self.dispatched_version,
                               arch=self._publish_arch(), params=params)

    def _publish_params(self):
        """The param slice a published bundle carries (on-policy: full
        policy params; off-policy: the actor slice)."""
        raise NotImplementedError

    def _publish_arch(self) -> dict:
        """Arch shipped with the bundle (hook for annealing knobs)."""
        return self.arch

    def capture_epoch_stats(self, updated: bool):
        """Snapshot-and-reset the host counters an epoch log needs, at
        DISPATCH time — when the server defers ``log_epoch`` behind the
        in-flight window, episodes arriving for the *next* epoch must
        not leak into this epoch's row. Returns an opaque payload for
        ``log_epoch(stats=...)``, or None when no log is due."""
        return None

    def stage_batch(self, host_batch) -> dict:
        """Prefetch an assembled host batch to the device ahead of
        dispatch. ``jax.device_put`` enqueues the H2D copy without
        waiting, so a batch staged while the previous update still runs
        overlaps its transfer with device compute instead of paying it
        inside the (window-fenced) dispatch path. ``_to_device`` passes
        already-placed arrays through untouched, so a staged batch and a
        host batch are interchangeable downstream. Single-host only —
        mesh placement (``_place``) already owns multihost batches.

        An array of at least ``_H2D_FLAT_BYTES`` crosses as a flat view of
        its bytes and is given its shape by one small program dispatched
        here, outside the update. ``device_put`` of an array in its own
        shape has the HOST turn it into the device's tiled layout first
        (for ``[512, 20, 28224]`` bytes a transposition: 190 ms of CPU on
        the transfer's threads, landing 100 ms later); a one-dimensional
        array crosses as it lies and the device does the turning. What
        comes back is the same either way — every value one ``jax.Array``
        of the host array's shape and dtype — and a batch with no such
        array takes one ``device_put`` of the whole dict.

        **The staging ring's order still frees the slab** (``EpochBuffer.
        add_episode``): the flat array is a view of the slab, the update's
        operand is the shaping program's result and that program runs only
        when the bytes have landed, so "update fenced" still implies "slab
        read".

        The span carries ``flat`` (arrays put that way; 0 when the dict
        went as it is) and ``bytes``;
        ``relayrl_learner_h2d_flat_total`` counts the same arrays."""
        import jax
        import numpy as np

        with span("host:stage_batch") as sp:
            place = getattr(self, "_place", None)
            if place is not None:
                return place(dict(host_batch))
            flat = {k: v.reshape(-1) for k, v in host_batch.items()
                    if isinstance(v, np.ndarray) and v.ndim > 1
                    and v.nbytes >= _H2D_FLAT_BYTES and v.flags.c_contiguous}
            staged = jax.device_put({**host_batch, **flat})
            for k in flat:
                staged[k] = _shape_on_device()(staged[k],
                                               host_batch[k].shape)
            if flat:
                telemetry.get_registry().counter(
                    "relayrl_learner_h2d_flat_total",
                    "arrays stage_batch put as flat bytes and shaped on "
                    "the device").inc(len(flat))
            if sp.traced:
                sp.note(flat=len(flat), bytes=sum(
                    getattr(v, "nbytes", 0) for v in host_batch.values()))
            return staged

    def _to_device(self, host_batch) -> dict:
        """The single owner of host-batch → device-batch placement
        (mesh-aware ``_place`` when multihost, plain ``asarray``
        otherwise). Both families' ``train_on_batch`` and the warmup path
        share it so a placement change cannot leave warmup compiling cache
        entries the real update never hits."""
        import jax.numpy as jnp

        place = getattr(self, "_place", None)
        if place is not None:
            return place(dict(host_batch))
        return {k: jnp.asarray(v) for k, v in host_batch.items()}

    def _warmup_update(self, host_batch, update_fn=None) -> None:
        """Run ``update_fn`` (default ``self._update``) once on a
        shape/dtype placeholder batch and
        discard every output. The state argument is donated
        (``donate_argnums=0``), so the update consumes a copy — the live
        ``self.state`` buffers, version, metrics, and logger are untouched.
        Non-array state leaves pass through un-copied to keep the call
        signature identical to the real update's (a dtype-changed leaf
        would compile a cache entry the real call never hits).

        Ordering: warmup must finish before any OTHER thread drives
        ``train_on_batch`` — the real update donates its state argument
        (``donate_argnums=0``), so a concurrent update can delete the
        live buffers mid-copy here and this raises (the server's own
        learner thread is already ordered warmup-then-train; out-of-band
        callers should ``server.wait_warmup()`` first — a raise here is
        caught as non-fatal and warmup is merely skipped)."""
        import jax
        import jax.numpy as jnp

        live = self.state  # one read: a swap mid-warmup can't mix trees
        state_copy = jax.tree_util.tree_map(
            lambda x: jnp.copy(x) if isinstance(x, jax.Array) else x,
            live)
        fn = update_fn if update_fn is not None else self._update
        _, metrics = fn(state_copy, self._to_device(host_batch))
        jax.block_until_ready(metrics)

    def _jitted_policy_step(self):
        """``self.policy.step`` jitted once per instance — rebuilding the
        wrapper per call would bypass the compile cache and retrace every
        action."""
        if getattr(self, "_jit_step_fn", None) is None:
            import jax

            self._jit_step_fn = jax.jit(self.policy.step)
        return self._jit_step_fn

    @abc.abstractmethod
    def bundle(self) -> ModelBundle:
        """Current policy as a versioned transportable bundle."""

    @property
    @abc.abstractmethod
    def version(self) -> int:
        """Monotonic model version (bumped once per train step)."""
