"""Fused on-device rollout engine — the Anakin tier of the actor plane.

The vector actor host (``runtime/vector_actor.py``) batched the POLICY:
one ``jit(vmap(step))`` dispatch serves N env lanes. But each lane's env
still steps on the host, one Python call per step, so the system pays one
device dispatch + one numpy env loop + one ActionRecord build *per env
step* — ~30k env-steps/s end to end. The Podracer Anakin architecture
(arXiv:2104.06272) fuses the other half: with env dynamics as pure JAX
(``envs/jax/``), an entire ``[lanes, unroll]`` trajectory window becomes
ONE dispatch of

    jit(vmap_over_lanes(lax.scan(env.step ∘ policy.step)))

with per-lane PRNG keys split from one seed, in-scan autoreset
(``envs.jax.base.step_autoreset`` — lanes never leave the device between
episodes), and the whole carry (keys + env states + observations; for a
sequence policy its observation ring and, where no episode can outgrow the
ring, its decode cache) donated back to the next window. Amortized per env
step, the dispatch cost tends to zero as ``unroll_length`` grows. Its rate
on the chip is the benchmark cell ``gpt2m-policy.rollout``'s (PERF.md).

The host side of the engine is an **unstacker**: one ``device_get`` of
the stacked window, then a replay of the window into the existing
per-lane :class:`~relayrl_tpu.types.trajectory.Trajectory` streams —
byte-compatible with what a live ``VectorActorHost`` loop would have put
on the wire (reward-credit placement, terminal markers,
terminated-beats-truncated precedence, time-limit bootstrap
observations), so the spool/sequence/transport plane and the server's
ingest funnel work unchanged. This is a new fastest tier, not a
replacement: the gym/vector paths remain for host-bound envs
(Gymnasium, Atari) and external simulators.

Model hot-swap shares the exact gates of the other two actor hosts
(``apply_bundle_swap`` / ``apply_wire_swap`` — same attribute contract),
and the fused step reads ``params`` once per window under the lock: every
step of a window is computed by ONE model version by construction.

The host keeps ONE WINDOW IN FLIGHT ahead of itself: the constructor
launches the first, and :meth:`AnakinActorHost.rollout` launches window
k+1 before it fetches and emits window k, so the device computes the next
window while the host reads the last. A window carries the version and the
production stamp its LAUNCH read, so a swap takes effect one window later
than its call: lag the records state (``bver``, ``born_ns``), never a wrong
stamp. No window launched is ever dropped: it stays in flight, through
``close()`` and through a wait that raised, until a ``rollout()`` returns
it.
"""

from __future__ import annotations

import math
import threading
import time
from collections import deque
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from relayrl_tpu.envs.jax.base import JaxEnv, step_autoreset
from relayrl_tpu.models import build_policy, validate_policy
from relayrl_tpu.models.base import held_dtypes, hold_params
from relayrl_tpu.runtime.policy_actor import (
    apply_bundle_swap,
    apply_wire_swap,
    resolve_actor_context,
    window_advance,
)
from relayrl_tpu.telemetry.spans import span
from relayrl_tpu.types.action import ActionRecord
from relayrl_tpu.types.columnar import (
    DecodedTrajectory,
    encode_columnar_frame,
)
from relayrl_tpu.types.model_bundle import ModelBundle, exploration_kwargs
from relayrl_tpu.types.trajectory import Trajectory


class _Launched(NamedTuple):
    """A window the device holds or is computing, not yet fetched, with
    what its launch read under the lock (``born_ns`` None: the
    constructor's, stamped by the ``rollout()`` that takes it)."""

    window: dict
    version: int
    born_ns: int | None


def resolve_jax_env(env, **env_kwargs) -> JaxEnv:
    """Env argument → :class:`JaxEnv` instance: ids go through the
    on-device registry (``envs.jax.JAX_ENVS`` — the same table
    ``envs.list_envs()`` reports), instances pass through."""
    if isinstance(env, JaxEnv):
        return env
    from relayrl_tpu.envs.jax import make_jax

    return make_jax(str(env), **env_kwargs)


def carry_holds_cache(policy, env: JaxEnv, window_size: int | None) -> bool:
    """Whether the fused sequence scan steps from a per-lane decode cache
    in its carry (``policy.step_cached``) or recomputes each step from the
    observation ring (``policy.step_window``). Decided from what the host
    can observe, once, when the program is built:

    * the policy brings ``step_cached``, ``init_cache`` and
      ``prefill_cache``, and a new sequence may start over a used cache
      (``Policy.cache_restarts``): under ``restart=True``, which the scan
      passes, every layer's cached step at position 0 reads nothing the
      last episode left — rows at their positions are
      masked after ``t`` (attention's ``(k, v)``), a state without positions
      is read as zeros there (a convolution's last rows, a Mamba-2 / delta
      rule / lane-decay layer's rows and float32 state) — so an in-scan
      reset clears nothing;
    * the environment states a limit on an episode's steps
      (``JaxEnv.max_episode_steps``) and it is no longer than the window:
      the ring never rolls, so no cached row's position ever shifts.

    Where an episode can outgrow the window the ring program is the right
    one and stays (the process tier lives by the same rule one step at a
    time: cached while not rolled, else the window). So does a trunk with a
    layer whose operator does not declare the restart (an indexer's key
    rows, a latent layer's compressed rows: by position, and not yet held
    to it under ``vmap(scan)``)."""
    limit = env.max_episode_steps
    return (window_size is not None and limit is not None
            and limit <= window_size and policy.cache_restarts
            and None not in (policy.step_cached, policy.init_cache,
                             policy.prefill_cache))


def init_lane_caches(policy, lanes: int, window_size: int):
    """``policy.init_cache(window_size)`` a lane, stacked: the zeroed
    decode states the cached scan's carry starts from."""
    one = jax.eval_shape(lambda: policy.init_cache(window_size))
    return jax.tree.map(
        lambda x: jnp.zeros((lanes, *x.shape), x.dtype), one)


# Lanes a pass of the swap's rebuild: ``prefill_cache`` runs dense
# attention over the whole window (float32 scores ``[heads, W, W]`` a lane
# and layer), so the lanes go through in groups and the rebuild needs
# little beside the cache itself: no more than the step program.
REBUILD_GROUP = 2


def make_cache_rebuild(policy, lanes: int):
    """``fn(params, carry) -> carry``: every lane's cache made again from
    its observation ring with ``policy.prefill_cache(params, cache, win,
    n_valid=wlen)`` — what the first dispatch after a model swap runs, so
    that a step attends keys and values the NEW parameters computed, as
    ``step_window`` over the ring would. A program of its own, outside the
    rollout: :data:`REBUILD_GROUP` lanes a pass of a loop whose carry is
    the (donated) cache, written back in place."""
    group = math.gcd(lanes, REBUILD_GROUP)

    def lane_prefill(params, cache, win, wlen):
        return policy.prefill_cache(params, cache, win, n_valid=wlen,
                                    restart=True)

    def rebuild(params, carry):
        *head, win, wlen, cache = carry

        def one_group(i, cache):
            def rows(x):
                return jax.lax.dynamic_slice_in_dim(x, i * group, group)

            made = jax.vmap(lane_prefill, in_axes=(None, 0, 0, 0))(
                params, jax.tree.map(rows, cache), rows(win), rows(wlen))
            return jax.tree.map(
                lambda x, new: jax.lax.dynamic_update_slice_in_dim(
                    x, new, i * group, 0), cache, made)

        cache = jax.lax.fori_loop(0, lanes // group, one_group, cache)
        return (*head, win, wlen, cache)

    donate = (1,) if jax.default_backend() != "cpu" else ()
    return jax.jit(rebuild, donate_argnums=donate)


def make_fused_rollout(policy, env: JaxEnv, unroll_length: int,
                       sequence: bool = False, cached: bool = False):
    """Build the one-dispatch window producer:

    ``fn(params, explore, carry) -> (carry, window)`` where ``carry`` is
    the stacked per-lane ``(policy_key, env_key, env_state, obs)`` and
    ``window`` is a dict of ``[lanes, unroll, ...]`` arrays (obs, act,
    rew, term, trunc, final_obs, aux). The policy composition per step is
    exactly the vector host's (``split`` inside the trace, params
    broadcast, exploration knobs as traced scalars so annealing never
    retraces); the env composition is :func:`step_autoreset`, so episode
    boundaries stay on-device. The carry is donated on accelerator
    backends — the window producer is a ring, not an allocator.

    ``sequence=True`` runs sequence policies: the carry grows a per-lane
    rolling observation window (``[W, obs_dim]`` ring + valid-length
    counter, advanced by :func:`window_advance` — the same rule every
    host tier pushes with) and each step dispatches through
    ``policy.step_window`` with the post-push count of real rows, so the
    action stream is bit-identical to a vector-tier ``step_window`` lane
    at the same key. The window resets to empty at in-scan autoreset
    boundaries via the same ``jnp.where`` masking ``step_autoreset``
    uses for the env state — a new episode never attends the previous
    one's tail. Shipped obs follow ``normalize_obs``'s wire-dtype rule
    (uint8 stays uint8, everything else float32) because the vector
    tier normalizes BEFORE windowing, and byte parity rides on it.

    ``cached=True`` (with ``sequence``; the host sets it by
    :func:`carry_holds_cache`, no option of a user's) makes the one
    difference in that body: the carry holds, after the ring, each lane's
    decode cache (``policy.init_cache(W)``), and the policy step is
    ``policy.step_cached`` for the ONE new row at its position — the
    lane's count of real rows before the push — in place of
    ``step_window`` over all ``W``. It is the same action at the same
    key, with ``logp_a`` and ``v`` equal to ``step_window``'s to float
    rounding (1e-4 at float32; ``tests/test_kv_cache.py`` holds the pair
    to it), not to the byte. The cache is NOT zeroed at an in-scan
    autoreset, and no ``jnp.where`` walks it (one over the whole cache
    every step would cost more than the step): the new episode's first
    step is at ``t`` = 0, where a cached step attends rows <= ``t`` alone
    (the episode overwrites the rest in order) and reads a state without
    positions — a recurrence's, a convolution's last rows — as zeros, a
    select fused into the read that step makes anyway
    (``step_cached(..., restart=True)``, ``Policy.cache_restarts``). The ring stays in the carry: it is what a
    model swap rebuilds the cache from (:func:`make_cache_rebuild`).
    """
    def lane_rollout(params, explore, carry):
        def seq_body(c, _):
            pkey, ekey, state, obs, win, wlen, *cache = c
            pkey, sub = jax.random.split(pkey)
            wire_obs = (obs if obs.dtype == jnp.uint8
                        else jnp.asarray(obs, jnp.float32))
            at = wlen   # the new row's position, while the ring never rolls
            win, wlen = window_advance(win, wlen, wire_obs)
            if cached:
                act, aux, cache[0] = policy.step_cached(
                    params, sub, cache[0], jnp.asarray(wire_obs, win.dtype),
                    at, None, restart=True)
            else:
                # step_window takes the post-push count of REAL rows (it
                # reads out at t-1 itself) — same convention as the hosts.
                act, aux = policy.step_window(params, sub, win, wlen, None)
            (ekey, state, next_obs, rew, term, trunc,
             final_obs) = step_autoreset(env, ekey, state, act)
            done = jnp.logical_or(term, trunc)
            win = jnp.where(done, jnp.zeros_like(win), win)
            wlen = jnp.where(done, jnp.int32(0), wlen)
            out = {"obs": wire_obs, "act": act, "rew": rew, "term": term,
                   "trunc": trunc, "final_obs": final_obs, "aux": aux}
            return (pkey, ekey, state, next_obs, win, wlen, *cache), out

        def body(c, _):
            pkey, ekey, state, obs = c
            pkey, sub = jax.random.split(pkey)
            act, aux = policy.step(params, sub, obs, None, **explore)
            (ekey, state, next_obs, rew, term, trunc,
             final_obs) = step_autoreset(env, ekey, state, act)
            out = {"obs": obs, "act": act, "rew": rew, "term": term,
                   "trunc": trunc, "final_obs": final_obs, "aux": aux}
            return (pkey, ekey, state, next_obs), out

        return jax.lax.scan(seq_body if sequence else body, carry, None,
                            length=unroll_length)

    vect = jax.vmap(lane_rollout, in_axes=(None, None, 0))
    # Donation is honored on TPU/GPU; CPU hosts would warn per dispatch.
    donate = (2,) if jax.default_backend() != "cpu" else ()
    return jax.jit(vect, donate_argnums=donate)


class AnakinActorHost:
    """N on-device env lanes × ``unroll_length`` steps per fused dispatch.

    Same logical-agent surface as :class:`VectorActorHost` — N per-lane
    trajectory streams through ``on_send(lane, payload)``, one atomic
    model gate for all lanes — but the action API is :meth:`rollout`:
    there is no per-step request because the env lives inside the
    dispatch. ``rng_keys`` (stacked ``[N, 2]``) overrides the default
    per-lane policy-key derivation, mirroring VectorActorHost's parity
    hook.

    Sequence policies (windowed transformers) run fused too: the scan
    carry holds each lane's rolling observation window, ``window_size``
    optionally narrows it below the model context (clamped exactly like
    ``actor.window_size`` on the other tiers), and ``record_bver=True``
    stamps each record's producing model version into the aux plane —
    the per-token behavior evidence the RLHF score stage reads. Where no
    episode can outgrow the window (:func:`carry_holds_cache`) the carry
    also holds each lane's decode cache and a step computes one new row;
    the first dispatch after a model swap then rebuilds the caches from
    the rings under the new parameters.

    ``params`` are held as the program uses them: where the scan decodes
    from a cache (``carry_holds_cache``), ``models.base.held_dtypes`` reads
    off the cached step which leaves every use casts to a narrower float
    type (a matmul weight under a bfloat16 compute type) and every install —
    the constructor's, a swap's — casts exactly those, leaf by leaf
    (:meth:`hold`), so a bfloat16 policy costs 2 B a parameter on the device
    and no cast a dispatch, and a step's operands are the same numbers. A
    host whose scan steps from the window holds the tree as published. A
    swap needs the old and the new tree at once: at a size where two held
    trees and the caches do not fit, the policy is a frozen one.

    One window is always in flight ahead of the host (the constructor
    launches the first): :meth:`rollout` queues window k+1 behind window k
    on the device and only then fetches and emits window k. A stated
    property follows: a model swap takes effect at the next LAUNCH, which is
    one window later than the swap's call — the window already in flight was
    computed by the parameters it was launched with, and is stamped with
    their version (``bver``) and its launch's ``born_ns`` (the constructor's
    window with the first ``rollout()``'s: a host may be built long before
    it rolls). The window in flight outlives :meth:`close`: the first
    ``rollout()`` of a host enabled again returns it, so every lane's
    stream runs on without a gap. :meth:`rollout` is one thread's to call.
    """

    def __init__(
        self,
        bundle: ModelBundle,
        env,
        num_envs: int,
        unroll_length: int = 32,
        max_traj_length: int = 1000,
        on_send=None,
        seed: int = 0,
        validate: bool = True,
        rng_keys=None,
        columnar_wire: bool = True,
        async_emit: bool = False,
        emit_coalesce_frames: int = 1,
        window_size: int | None = None,
        record_bver: bool = False,
        **env_kwargs,
    ):
        if num_envs < 1:
            raise ValueError(f"num_envs must be >= 1, got {num_envs}")
        if unroll_length < 1:
            raise ValueError(
                f"unroll_length must be >= 1, got {unroll_length}")
        self._lock = threading.Lock()
        self.num_envs = int(num_envs)
        self.unroll_length = int(unroll_length)
        self.env = resolve_jax_env(env, **env_kwargs)
        self.arch = dict(bundle.arch)
        obs_dim = int(self.arch["obs_dim"])
        if obs_dim != self.env.obs_dim:
            raise ValueError(
                f"model obs_dim {obs_dim} != env obs_dim "
                f"{self.env.obs_dim} — the fused rollout feeds the env's "
                f"observation straight into the policy")
        self.policy = build_policy(self.arch)
        if validate:
            validate_policy(self.policy, bundle.params)
        # Sequence policies run fused: the scan carry grows a per-lane
        # rolling window sized to the model's serving context (narrowed
        # by actor.window_size when set — never widened past it, the
        # same clamp resolve_actor_context applies on the other tiers).
        self._window_size: int | None = None
        if self.policy.step_window is not None:
            ctx = resolve_actor_context(self.arch)
            self._window_size = (ctx if window_size is None
                                 else max(1, min(int(window_size), ctx)))
        elif getattr(self.policy, "step_cached", None) is not None:
            raise ValueError(
                "KV-cache-only policies (step_cached without step_window) "
                "cannot run in the fused scan — its carry holds a cache "
                "only beside the rolling window: where an episode can "
                "outgrow the window the scan steps from the window "
                "(step_window), and a model swap rebuilds the cache from "
                "it; use actor.host_mode=\"process\" for the cached "
                "single-lane path or the serving plane (InferenceService) "
                "for stateless clients")
        from relayrl_tpu import telemetry

        reg = telemetry.get_registry()
        self._m_param_bytes = reg.gauge(
            "relayrl_actor_param_bytes",
            "fused rollout: bytes of parameters the host holds on the "
            "device (a matmul weight at the compute type)")
        # The cached scan, where no episode can outgrow the window; its
        # caches hold what ``_cache_version``'s parameters computed.
        cached = carry_holds_cache(self.policy, self.env, self._window_size)
        # What every install casts: read once, off the cached step — the
        # program this host then compiles. A host whose scan steps from the
        # window (or a feed-forward policy's) holds the tree as published.
        self._held_dtypes = (held_dtypes(self.policy, bundle.params)
                             if cached else None)
        self._wire_decoder = None  # one decoder, all lanes (see VectorActorHost)
        self.params = bundle.params
        self.version = bundle.version
        self._explore_kwargs = exploration_kwargs(self.arch)
        # Per-token behavior evidence for the RLHF plane: stamp each
        # record's producing model version (``bver``) into the window's
        # aux at unstack. Opt-in — it widens the wire by one int32
        # column, so plain RL rollouts keep their bytes.
        self.record_bver = bool(record_bver)
        self._rollout_fn = make_fused_rollout(
            self.policy, self.env, self.unroll_length,
            sequence=self._window_size is not None, cached=cached)
        self._rebuild_fn = (make_cache_rebuild(self.policy, self.num_envs)
                            if cached else None)
        self._cache_version = self.version
        self._cache_bytes = self._cache_rows_bytes = 0

        # Per-lane key derivation matches VectorActorHost (policy keys
        # split from PRNGKey(seed)); env reset/autoreset keys come from an
        # independent fold so policy and env streams never alias.
        if rng_keys is not None:
            keys = jnp.asarray(np.asarray(rng_keys))
            if keys.shape[0] != self.num_envs:
                raise ValueError(
                    f"rng_keys has {keys.shape[0]} rows for "
                    f"{self.num_envs} lanes")
            pol_keys = keys
        else:
            pol_keys = jax.random.split(
                jax.random.PRNGKey(seed), self.num_envs)
        env_root = jax.random.fold_in(jax.random.PRNGKey(seed), 0x0E74)
        reset_keys = jax.random.split(env_root, 2 * self.num_envs)
        init_keys, carry_keys = (reset_keys[: self.num_envs],
                                 reset_keys[self.num_envs:])
        states, obs = jax.jit(jax.vmap(self.env.reset))(init_keys)
        if self._window_size is not None:
            # Windows are ALWAYS float32, matching both host tiers —
            # the push casts, the wire obs keeps normalize_obs's dtype.
            win = jnp.zeros(
                (self.num_envs, self._window_size, int(self.env.obs_dim)),
                jnp.float32)
            wlen = jnp.zeros(self.num_envs, jnp.int32)
            self._carry = (pol_keys, carry_keys, states, obs, win, wlen)
            if cached:
                caches = init_lane_caches(self.policy, self.num_envs,
                                          self._window_size)
                self._cache_bytes = sum(
                    int(x.nbytes) for x in jax.tree.leaves(caches))
                # rows: the leaves that grow with the window (a (k, v)
                # pair); state: those that do not (a recurrence's, a
                # convolution's last rows, a ring shorter than the window)
                twice = jax.eval_shape(
                    lambda: self.policy.init_cache(2 * self._window_size))
                self._cache_rows_bytes = sum(
                    int(x.nbytes) for x, wide in zip(
                        jax.tree.leaves(caches), jax.tree.leaves(twice))
                    if wide.shape != x.shape[1:])
                self._carry += (caches,)
        else:
            self._carry = (pol_keys, carry_keys, states, obs)

        # Wire form: ``columnar_wire=True`` (the anakin-tier default,
        # config ``actor.columnar_wire``) ships each completed per-lane
        # segment as ONE contiguous columnar frame (types/columnar.py)
        # sliced straight out of the host-resident window — zero per-step
        # Python objects, zero per-record msgpack. False keeps the
        # per-record ActionRecord streams (rolling compat / pre-columnar
        # servers), now unstacked with O(episodes) boundary slicing.
        self.columnar_wire = bool(columnar_wire)
        self.max_traj_length = int(max_traj_length)
        self._on_send = on_send
        # actor.emit_coalesce_frames (ROADMAP item 5 host shave): pack
        # up to N completed columnar segments of one lane into a single
        # batch-container send (transport/base.pack_batch) — short
        # episodes complete several segments per window, and each send
        # pays the envelope + spool + socket path. Flushed at window
        # end, so a frame never waits past its own rollout dispatch.
        # Only meaningful on the columnar wire (per-record payloads are
        # already per-episode msgpack).
        self.emit_coalesce = max(1, int(emit_coalesce_frames))
        self._coalesce_buf: list[list[bytes]] = [
            [] for _ in range(self.num_envs)]
        # The window production stamp (rollout dispatch start) and, with
        # it, the last columnar frame's origin — what ``shipping`` hands
        # the agent's send hook for an emitted segment.
        self._window_born_ns = 0
        self._frame_origin = None
        self.trajectories = [
            Trajectory(
                max_length=max_traj_length,
                on_send=(None if on_send is None
                         else (lambda payload, _lane=lane:
                               on_send(_lane, payload))))
            for lane in range(self.num_envs)
        ]
        # Per-lane columnar accumulators: column chunks (window slices)
        # pending until an episode boundary / max_traj_length flush.
        self._pending = [
            {"len": 0, "cols": {"o": [], "a": [], "r": []}, "aux": {}}
            for _ in range(self.num_envs)]
        # Per-lane episode accounting (drivers read these like
        # run_vector_gym_loop's return value).
        self._ep_ret = np.zeros(self.num_envs, np.float64)
        self.episode_returns: list[list[float]] = [
            [] for _ in range(self.num_envs)]

        # Off-thread emitter (ROADMAP item 1's host shave): with
        # host_share_of_wall at 0.43-0.55, frame encode is ~coequal with
        # device dispatch — ``async_emit=True`` (config
        # ``actor.async_emit``) moves the encode/unstack + send off the
        # rollout thread onto a dedicated emitter, overlapping it with
        # the NEXT window's device compute. The hand-off queue is
        # bounded (depth 2): a slow wire backpressures the rollout loop
        # instead of ballooning host memory, and one emitter thread
        # keeps per-lane trajectory order intact. ``flush_emits`` drains
        # it (drivers call it before reading episode_returns or tearing
        # down).
        self.async_emit = bool(async_emit)
        self._emit_cond = threading.Condition()
        self._emit_queue: list[dict] = []
        self._emit_pending = 0
        self._emit_error: Exception | None = None
        self._emit_stop = False
        self._emit_thread: threading.Thread | None = None
        self.start_emitter()

        self._m_steps = reg.counter(
            "relayrl_actor_env_steps_total",
            "policy steps served (one per env step per lane)")
        self._m_cached_steps = reg.counter(
            "relayrl_actor_cached_steps_total",
            "fused rollout: env steps served from the scan carry's decode "
            "cache (one new row computed, not the whole window)")
        self._m_in_flight = reg.gauge(
            "relayrl_actor_windows_in_flight",
            "fused rollout: windows launched and not yet fetched (2 while "
            "rollout() waits for the older, 1 when it returns and through "
            "close())")
        self._m_launch_gap_s = reg.histogram(
            "relayrl_actor_rollout_launch_gap_seconds",
            "fused rollout: a rollout() that found the window in flight "
            "already finished (the device had nothing queued), its host "
            "time until the next launch returned — a lower bound of the "
            "device's idle time; 0 when the window was still running")
        self._m_rebuilds = reg.counter(
            "relayrl_actor_cache_rebuilds_total",
            "fused rollout: model swaps after which every lane's decode "
            "cache was rebuilt from its observation window")
        for kind, held in (
                ("rows", self._cache_rows_bytes),
                ("state", self._cache_bytes - self._cache_rows_bytes)):
            reg.gauge(
                "relayrl_actor_cache_bytes",
                "fused rollout: bytes of decode cache in the scan carry, "
                "all lanes, by kind — rows at their positions (a (k, v) "
                "pair) | state without positions (a recurrence's, a "
                "convolution's last rows); 0 and 0: the scan steps from "
                "the observation window", labels={"kind": kind}).set(held)
        self._m_resets = reg.counter(
            "relayrl_actor_state_resets_total",
            "fused rollout: lane episode ends inside dispatched windows "
            "(term | trunc): each one a sequence the cached scan starts "
            "over a used cache")
        self._m_dispatches = reg.counter(
            "relayrl_actor_rollout_dispatches_total",
            "fused rollout dispatches (each serves lanes x unroll steps)")
        self._m_dispatch_s = reg.histogram(
            "relayrl_actor_rollout_dispatch_seconds",
            "fused rollout: a rollout() from its launch of the next window "
            "until the window it returns is ready (what is left of a "
            "window's device time once the host's work ran beside it)")
        self._m_unstack_s = reg.histogram(
            "relayrl_actor_rollout_unstack_seconds",
            "fused rollout: host unstack of one window into trajectories")
        self._m_encode_s = reg.histogram(
            "relayrl_actor_rollout_encode_seconds",
            "fused rollout: columnar frame encode of one window")
        self._m_frames = reg.counter(
            "relayrl_actor_columnar_frames_total",
            "columnar trajectory frames encoded and handed to the wire")
        self._m_frame_bytes = reg.counter(
            "relayrl_actor_columnar_bytes_total",
            "columnar trajectory frame bytes encoded")
        self._m_sends = reg.counter(
            "relayrl_actor_emit_sends_total",
            "transport sends of encoded segments (emit_coalesce_frames "
            "folds several frames into one send)")
        reg.gauge("relayrl_actor_lanes",
                  "env lanes per batched dispatch on this host").set(
                      self.num_envs)
        reg.gauge("relayrl_actor_unroll_length",
                  "env steps per lane per fused rollout dispatch").set(
                      self.unroll_length)
        if self._window_size is not None:
            reg.gauge(
                "relayrl_actor_window_size",
                "rolling observation-window rows per lane in the fused "
                "sequence scan carry (0 rows = feed-forward policy)"
            ).set(self._window_size)
        # The pipe: windows launched and not yet fetched, oldest first;
        # the rolling thread's alone after the first, launched here.
        self._in_flight: deque[_Launched] = deque()
        self._launch(stamp=False)

    @property
    def params(self):
        return self._params

    @params.setter
    def params(self, tree) -> None:
        """Every install, the constructor's and a swap's (the shared gates
        assign under the lock): held as the step uses it. A wire-v2 delta's
        base is the PUBLISHED tree, which the host no longer has where it
        cast a leaf: a delivery that came as host arrays (the handshake
        bundle, a v1 bundle) is kept by reference for the decoder's first
        seed (``policy_actor._decode_wire_frame``), and dropped there."""
        self._params = self.hold(tree)
        self._published = tree if (
            self._held_dtypes is not None and self._wire_decoder is None
            and all(isinstance(x, np.ndarray)
                    for x in jax.tree.leaves(tree))) else None
        self._m_param_bytes.set(sum(
            int(x.nbytes) for x in jax.tree.leaves(self._params)))

    def hold(self, tree):
        """``tree`` as this host holds it, LEAF BY LEAF (``rl:actor.
        param_cast``): where the scan decodes from a cache, a leaf the
        cached step casts is put on the device and cast on its own, so the
        published float32 tree is never whole there beside the held one; a
        leaf already held passes through. The wire gate calls it BEFORE the
        lock (``policy_actor._decode_wire_frame``), the setter under it."""
        with span("rl:actor.param_cast"):
            if self._held_dtypes is not None:
                tree = hold_params(self.policy, tree, self._held_dtypes)
            if jax.default_backend() != "cpu":
                tree = jax.device_put(tree)     # what is left: small leaves
            return tree

    # -- fused action API --
    def _launch(self, stamp: bool = True) -> None:
        """Queue the next window on the device, behind whatever it is
        running: the dispatch returns futures at once, and the window's
        input is the carry the last launch returned. Where a swap landed
        since the last launch, the caches are rebuilt first, in the same
        order on the device."""
        born_ns = time.monotonic_ns() if stamp else None
        with self._lock:
            # ONE params/explore read under the lock for the whole
            # window: every step of this window is computed by a single
            # model version (maybe_swap's atomicity across lanes AND
            # unroll steps), and that version rides with the window.
            version = self.version
            if self._rebuild_fn is not None and self._cache_version != version:
                # the caches hold what the old parameters computed
                self._carry = self._rebuild_fn(self.params, self._carry)
                self._cache_version = version
                self._m_rebuilds.inc()
            self._carry, window = self._rollout_fn(
                self.params, self._explore_kwargs, self._carry)
            self._in_flight.append(_Launched(window, version, born_ns))
        self._m_in_flight.set(len(self._in_flight))

    def rollout(self) -> dict:
        """ONE finished window of ``lanes × unroll`` env steps a call,
        unstacked into the per-lane trajectory streams — after the NEXT
        window's launch: the device runs window k+1 while the host waits
        for, fetches and emits window k.

        The window returned was launched by the call before (the first by
        the constructor) and carries what that launch read: its model
        version (the ``bver`` fill) and its ``born_ns``. A swap between two
        calls therefore first shows in the window the second call LAUNCHES,
        which the call after returns. A wait that raises leaves both windows
        in flight: the next call launches none and returns the same one.

        Returns ``{"steps", "episodes", "dispatch_s", "unstack_s"}`` for
        the calling driver's accounting (``dispatch_s``: the host's clock
        from this call's launch until the window it returns is ready — the
        same span of this function as ever, now what is LEFT of a window
        once the host's own work ran beside it); completed episode returns
        accumulate on :attr:`episode_returns` per lane.
        """
        t0 = time.monotonic()
        launched = self._in_flight[0]
        born_ns = (time.monotonic_ns() if launched.born_ns is None
                   else launched.born_ns)
        if len(self._in_flight) < 2:    # two where the last wait raised
            # one computation's outputs are ready together: ask one
            ran_dry = jax.tree.leaves(launched.window)[0].is_ready()
            self._launch()
            self._m_launch_gap_s.observe(
                time.monotonic() - t0 if ran_dry else 0.0)
        window = jax.block_until_ready(launched.window)
        t1 = time.monotonic()
        host_window = jax.device_get(window)
        self._in_flight.popleft()
        self._m_in_flight.set(len(self._in_flight))
        if self.record_bver:
            # The whole window is one model version by construction
            # (params read once under the lock), so the stamp is a fill.
            host_window = dict(host_window)
            host_window["aux"] = dict(host_window["aux"])
            host_window["aux"]["bver"] = np.full(
                (self.num_envs, self.unroll_length), launched.version,
                np.int32)
        if self.async_emit:
            if self._emit_error is not None:
                err, self._emit_error = self._emit_error, None
                raise RuntimeError(
                    f"anakin emitter thread failed: {err!r}") from err
            with self._emit_cond:
                # Bounded hand-off: past depth 2 the rollout thread
                # waits — backpressure, not unbounded window buffering.
                while self._emit_pending >= 2 and not self._emit_stop:
                    self._emit_cond.wait(0.5)
                self._emit_queue.append((born_ns, host_window))
                self._emit_pending += 1
                self._emit_cond.notify_all()
            episodes = 0  # completed counts surface via episode_returns
        elif self.columnar_wire:
            self._window_born_ns = born_ns
            episodes = self._emit_columnar(host_window)
        else:
            self._window_born_ns = born_ns
            episodes = self._unstack(host_window)
        t2 = time.monotonic()
        steps = self.num_envs * self.unroll_length
        self._m_steps.inc(steps)
        self._m_resets.inc(int(np.count_nonzero(
            np.logical_or(host_window["term"], host_window["trunc"]))))
        if self._rebuild_fn is not None:
            self._m_cached_steps.inc(steps)
        self._m_dispatches.inc()
        self._m_dispatch_s.observe(t1 - t0)
        if not self.async_emit:
            if self.columnar_wire:
                self._m_encode_s.observe(t2 - t1)
            else:
                self._m_unstack_s.observe(t2 - t1)
        return {"steps": steps, "episodes": episodes,
                "dispatch_s": t1 - t0, "unstack_s": t2 - t1,
                "encode_s": t2 - t1 if self.columnar_wire else 0.0,
                "wire": "columnar" if self.columnar_wire else "records"}

    # -- off-thread emitter (async_emit=True) --
    def start_emitter(self) -> None:
        """(Re)start the async emitter thread — a no-op when
        ``async_emit`` is off or it is already running. The re-enable
        half of :meth:`close`: an agent cycling disable/enable must get
        a live emitter back, or the depth-2 hand-off would deadlock on
        the third window."""
        if not self.async_emit or self._emit_thread is not None:
            return
        self._emit_stop = False
        self._emit_thread = threading.Thread(
            target=self._emit_loop, name="anakin-emitter", daemon=True)
        self._emit_thread.start()

    def _emit_loop(self) -> None:
        while True:
            with self._emit_cond:
                while not self._emit_queue and not self._emit_stop:
                    self._emit_cond.wait(0.5)
                if self._emit_stop and not self._emit_queue:
                    return
                born_ns, w = self._emit_queue.pop(0)
            self._window_born_ns = born_ns  # single emitter thread
            t0 = time.monotonic()
            try:
                if self.columnar_wire:
                    self._emit_columnar(w)
                    self._m_encode_s.observe(time.monotonic() - t0)
                else:
                    self._unstack(w)
                    self._m_unstack_s.observe(time.monotonic() - t0)
            except Exception as e:
                # Surfaced on the NEXT rollout() — the emitter must not
                # die silently with windows still queuing behind it.
                self._emit_error = e
            finally:
                with self._emit_cond:
                    self._emit_pending -= 1
                    self._emit_cond.notify_all()

    def flush_emits(self, timeout_s: float = 30.0) -> bool:
        """Drain the async emitter's hand-off queue (no-op when
        ``async_emit`` is off): drivers call this before reading
        ``episode_returns`` or tearing down, so every window ``rollout``
        returned has reached the wire (the one in flight is no caller's
        yet). True when fully drained in time. A
        pending emit failure re-raises HERE too, not only on the next
        rollout — otherwise an error on the FINAL window (no next
        rollout coming) would silently lose it at teardown, where the
        sync path would have raised."""
        if not self.async_emit:
            return True
        deadline = time.monotonic() + timeout_s
        drained = True
        with self._emit_cond:
            while self._emit_pending > 0:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    drained = False
                    break
                self._emit_cond.wait(min(0.5, remaining))
        if self._emit_error is not None:
            err, self._emit_error = self._emit_error, None
            raise RuntimeError(
                f"anakin emitter thread failed: {err!r}") from err
        return drained

    def close(self, timeout_s: float = 30.0) -> None:
        """Stop the emitter thread after draining its queue (hosts
        without ``async_emit`` have nothing to do). The window in flight
        stays where it is: it is the next ``rollout()``'s, should the host
        be rolled again (the agent's disable/enable cycle)."""
        if self._emit_thread is None:
            return
        self.flush_emits(timeout_s)
        with self._emit_cond:
            self._emit_stop = True
            self._emit_cond.notify_all()
        self._emit_thread.join(timeout=5)
        self._emit_thread = None

    @staticmethod
    def _cat(chunks: list) -> np.ndarray:
        return chunks[0] if len(chunks) == 1 else np.concatenate(chunks)

    def _emit_columnar(self, w: dict) -> int:
        """Columnar wire: slice each completed per-lane segment out of
        the host-resident ``[lanes, unroll]`` window and ship it as one
        contiguous frame (types/columnar.py), already in the FOLDED form
        the server's native decoder produces from the per-record wire:
        the final step carries its full reward (``r``), ``t``/``x`` mark
        the terminal (terminated beats truncated), ``u`` mirrors
        ``reward_updated`` (zero on the terminal step, whose reward
        "rides the marker" — ``n_records`` counts it), and a pure
        time-limit ending ships the pre-reset observation as
        ``final_obs``. Episode-boundary detection is one vectorized
        pass; the only per-episode Python is the frame flush."""
        term, trunc = w["term"], w["trunc"]
        done = np.logical_or(term, trunc)
        episodes = 0
        for lane in range(self.num_envs):
            start = 0
            for b in np.flatnonzero(done[lane]).tolist():
                self._append_segment(lane, w, start, b + 1)
                terminated = bool(term[lane, b])
                self._flush_frame(
                    lane, ended=True, truncated=not terminated,
                    final=(None if terminated else
                           np.asarray(w["final_obs"][lane, b], np.float32)))
                episodes += 1
                start = b + 1
            if start < self.unroll_length:
                self._append_segment(lane, w, start, self.unroll_length)
        if self.emit_coalesce > 1:
            # Window-end flush: coalescing trades sends for latency
            # bounded by ONE window, never more.
            for lane in range(self.num_envs):
                self._flush_coalesced(lane)
        return episodes

    def _flush_coalesced(self, lane: int) -> None:
        """Ship the lane's pending frames as one send: a single frame
        goes verbatim (the server's columnar sniff path), several pack
        into a BATCH_KIND_FRAMES container (split + decoded per frame
        by a staging worker). Either way it is ONE spool entry — one
        seq, one envelope — so replay/dedup act on the whole group."""
        buf = self._coalesce_buf[lane]
        if not buf:
            return
        if len(buf) == 1:
            payload = buf[0]
        else:
            from relayrl_tpu.transport.base import (
                BATCH_KIND_FRAMES,
                pack_batch,
            )

            payload = pack_batch(BATCH_KIND_FRAMES, buf)
        buf.clear()
        if self._on_send is not None:
            self._m_sends.inc()
            self._on_send(lane, payload)

    def _append_segment(self, lane: int, w: dict, a: int, b: int) -> None:
        """Stash window slice ``[a, b)`` on the lane's pending columns,
        flushing max_traj_length-sized chunks exactly where the
        per-record path would (Trajectory.add_action flushes when a real
        step arrives at capacity, so chunks are exactly max_traj_length
        steps and the terminal marker always joins its chunk)."""
        p = self._pending[lane]
        cols, aux_p = p["cols"], p["aux"]
        while a < b:
            if p["len"] >= self.max_traj_length:
                self._flush_frame(lane, ended=False)
            stop = min(b, a + self.max_traj_length - p["len"])
            cols["o"].append(w["obs"][lane, a:stop])
            cols["a"].append(w["act"][lane, a:stop])
            cols["r"].append(w["rew"][lane, a:stop])
            for k, v in w["aux"].items():
                aux_p.setdefault(k, []).append(v[lane, a:stop])
            p["len"] += stop - a
            self._ep_ret[lane] += float(
                np.sum(w["rew"][lane, a:stop], dtype=np.float64))
            a = stop

    def _flush_frame(self, lane: int, ended: bool, truncated: bool = False,
                     final=None) -> None:
        p = self._pending[lane]
        n = p["len"]
        if n == 0:
            return
        r = self._cat(p["cols"]["r"])
        t_col = np.zeros(n, np.uint8)
        x_col = np.zeros(n, np.uint8)
        u_col = (r != 0.0).astype(np.uint8)
        if ended:
            t_col[-1] = 1
            u_col[-1] = 0
            if truncated:
                x_col[-1] = 1
        time_limited = bool(ended and truncated)
        dt = DecodedTrajectory(
            agent_id="",  # attribution rides the transport envelope
            n_steps=n, n_records=n + (1 if ended else 0),
            marker_truncated=time_limited,
            columns={"o": self._cat(p["cols"]["o"]),
                     "a": self._cat(p["cols"]["a"]),
                     "r": r, "t": t_col, "u": u_col, "x": x_col},
            aux={k: self._cat(chunks) for k, chunks in p["aux"].items()},
            final_obs=final if time_limited else None)
        with span("rl:actor.encode") as enc:
            frame = encode_columnar_frame(dt)
        self._frame_origin = (self._window_born_ns or enc.t0_ns, enc)
        self._m_frames.inc()
        self._m_frame_bytes.inc(len(frame))
        if self.emit_coalesce > 1:
            buf = self._coalesce_buf[lane]
            buf.append(frame)
            if len(buf) >= self.emit_coalesce:
                self._flush_coalesced(lane)
        elif self._on_send is not None:
            self._m_sends.inc()
            self._on_send(lane, frame)
        if ended:
            self.episode_returns[lane].append(float(self._ep_ret[lane]))
            self._ep_ret[lane] = 0.0
        p["len"] = 0
        for chunks in p["cols"].values():
            chunks.clear()
        for chunks in p["aux"].values():
            chunks.clear()

    def _unstack(self, w: dict) -> int:
        """Per-record fallback (``columnar_wire=False``): replay one
        host-side window into the per-lane trajectories, reproducing the
        live loop's wire shape exactly: reward r_t lands on the record of
        the action that EARNED it (``reward_updated`` set only for
        nonzero rewards, as ``update_reward`` would have), the final
        action of an episode keeps rew=0 with its reward riding the
        terminal marker (``flag_last_action`` semantics), terminated
        beats truncated, and a pure time-limit ending ships the
        pre-reset observation for the value bootstrap.

        Episode boundaries come from one vectorized pass
        (``np.flatnonzero(term | trunc)``), scalars bulk-convert via
        ``tolist``, and records land through the bulk
        ``Trajectory.add_actions`` — O(episodes) loop control instead of
        the old per-step ``add_action`` calls."""
        obs, act, rew = w["obs"], w["act"], w["rew"]
        term, trunc, final_obs = w["term"], w["trunc"], w["final_obs"]
        aux_items = list(w["aux"].items())
        done = np.logical_or(term, trunc)
        episodes = 0
        for lane in range(self.num_envs):
            traj = self.trajectories[lane]
            obs_l, act_l = obs[lane], act[lane]
            rew_l = rew[lane].tolist()
            aux_l = [(k, v[lane]) for k, v in aux_items]

            def seg_records(a, b, last_masked, _obs_l=obs_l, _act_l=act_l,
                            _rew_l=rew_l, _aux_l=aux_l):
                # last_masked: index whose record keeps rew=0 (the
                # terminal step — its reward rides the marker), -1 for
                # an unterminated trailing segment.
                return [ActionRecord(
                    obs=_obs_l[t],
                    act=np.asarray(_act_l[t]),
                    mask=None,
                    rew=0.0 if t == last_masked else _rew_l[t],
                    reward_updated=bool(t != last_masked
                                        and _rew_l[t] != 0.0),
                    data={k: np.asarray(v[t]) for k, v in _aux_l},
                    done=False,
                ) for t in range(a, b)]

            start = 0
            for b in np.flatnonzero(done[lane]).tolist():
                records = seg_records(start, b + 1, last_masked=b)
                terminated = bool(term[lane, b])
                time_limited = not terminated
                records.append(ActionRecord(
                    obs=(np.asarray(final_obs[lane, b], np.float32)
                         if time_limited else None),
                    rew=rew_l[b], done=True, truncated=time_limited))
                traj.add_actions(records)
                self._ep_ret[lane] += float(
                    np.sum(rew[lane, start:b + 1], dtype=np.float64))
                self.episode_returns[lane].append(float(self._ep_ret[lane]))
                self._ep_ret[lane] = 0.0
                episodes += 1
                start = b + 1
            if start < self.unroll_length:
                traj.add_actions(seg_records(start, self.unroll_length,
                                             last_masked=-1))
                self._ep_ret[lane] += float(
                    np.sum(rew[lane, start:], dtype=np.float64))
        return episodes

    def shipping(self, lane: int):
        """``(born_ns, rl:actor.encode span)`` of what lane ``lane`` is
        handing to ``on_send`` right now: the columnar frame just encoded
        (born with its window), or the per-record fallback's trajectory
        chunk."""
        if self.columnar_wire:
            return self._frame_origin
        traj = self.trajectories[lane]
        return traj.born_ns, traj.encode_span

    # -- model hot-swap (one gate, all lanes, whole windows) --
    def maybe_swap(self, bundle: ModelBundle) -> bool:
        """Install a newer model for every lane atomically; the window in
        flight finishes on the version it was launched with, the next
        LAUNCH reads the new one — so the next ``rollout()`` still returns
        a window of the old version, stamped as such (shared gate with
        PolicyActor/VectorActorHost)."""
        return apply_bundle_swap(self, bundle)

    def swap_from_bytes(self, buf: bytes) -> bool:
        return self.maybe_swap(
            ModelBundle.from_bytes(buf, params_template=ModelBundle.RAW_TREE))

    def swap_from_wire(self, version: int, blob: bytes):
        """Wire-v2-aware swap shared with the other actor hosts."""
        return apply_wire_swap(self, version, blob)


def run_anakin_loop(host, windows: int) -> list[list[float]]:
    """Drive ``windows`` fused dispatches through an
    :class:`AnakinActorHost` (or the networked anakin-mode
    ``VectorAgent`` — same ``rollout()`` surface). Returns per-lane
    completed episode returns, mirroring ``run_vector_gym_loop``."""
    for _ in range(windows):
        host.rollout()
    returns = getattr(host, "episode_returns", None)
    if returns is None:  # networked facade: reach through to the host
        returns = host.host.episode_returns
    return [list(r) for r in returns]
