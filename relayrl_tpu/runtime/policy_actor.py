"""Actor-side policy holder: inference + ActionRecord assembly + hot-swap.

This is the compute core of the reference's agent
(reference: relayrl_framework/src/network/client/agent_zmq.rs:458-571 —
``request_for_action`` runs TorchScript ``step(obs, mask)`` under no_grad,
wraps the result + ``{logp_a, v}`` into a RelayRLAction and appends it to the
trajectory; model hot-swap under a mutex at :645-679), shared by the
in-process LocalRunner and the networked Agent so both paths run identical
inference code.

The policy apply is jitted once per architecture; on actor hosts without a
TPU this compiles for CPU — the same ModelBundle serves both placements
(SURVEY.md §7.4 item 2).
"""

from __future__ import annotations

import threading
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from relayrl_tpu.models import build_policy, validate_policy
from relayrl_tpu.telemetry.actor_ledger import ActorLedger
from relayrl_tpu.telemetry.spans import span
from relayrl_tpu.types.action import ActionRecord
from relayrl_tpu.types.model_bundle import (
    ModelBundle,
    arch_equal,
    exploration_kwargs,
)
from relayrl_tpu.types.trajectory import Trajectory


def resolve_actor_context(arch) -> int:
    """Serving-window length for sequence policies: the model's full
    context unless ``actor_context`` narrows it. Shared by PolicyActor
    and VectorActorHost so the positional-table guard can never drift
    between the single and batched serving paths."""
    # Same default as build_transformer_discrete (transformer.py): the
    # model's positional table is 1024 rows when the arch omits the key,
    # so the serving window must agree or context silently truncates.
    max_seq = int(arch.get("max_seq_len", 1024))
    ctx = int(arch.get("actor_context", max_seq))
    if ctx > max_seq:
        raise ValueError(
            f"actor_context {ctx} exceeds the model's max_seq_len "
            f"{max_seq} (positional table size)")
    return ctx


def push_window(window: np.ndarray, length: int, obs) -> tuple[int, bool]:
    """Advance one rolling observation-history window in place: write
    ``obs`` at ``length`` while the window is filling, else shift left by
    one and write at the end. Returns ``(new_length, rolled)``.

    This is THE window-advance rule — the single copy every tier that
    serves sequence policies goes through (PolicyActor's per-episode
    window, VectorActorHost's stacked per-lane windows, the serving
    plane's session table), and the numpy half of the parity pair with
    :func:`window_advance`, the functional JAX twin the anakin scan
    carry uses. The byte-parity contract across tiers rides on all four
    call sites advancing identically (the PR 3 window off-by-one lived
    in exactly this duplication)."""
    cap = window.shape[0]
    if length < cap:
        window[length] = obs
        return length + 1, False
    window[:-1] = window[1:]  # rolling: drop the oldest
    window[-1] = obs
    return cap, True


def window_advance(window, length, obs):
    """Functional JAX twin of :func:`push_window` for scan carries (the
    anakin tier's per-lane rolling window): fixed shapes, traced length,
    no in-place mutation. Returns ``(new_window, new_length)`` with
    exactly :func:`push_window`'s semantics — filling writes at
    ``length``, a full window shifts left and writes at ``cap - 1``,
    ``new_length`` saturates at ``cap`` (the count of real rows
    ``step_window`` expects). The numpy/JAX pair is locked row-for-row
    by tests/test_anakin.py's helper-parity golden."""
    cap = window.shape[0]
    length = jnp.asarray(length, jnp.int32)
    rolled = length >= cap
    shifted = jnp.where(rolled, jnp.roll(window, -1, axis=0), window)
    new_window = shifted.at[jnp.minimum(length, cap - 1)].set(
        jnp.asarray(obs, window.dtype))
    return new_window, jnp.minimum(length + 1, cap)


def apply_bundle_swap(actor, bundle: "ModelBundle") -> bool:
    """Shared hot-swap gate: version check, arch-ABI guard, params
    install under the actor's lock. PolicyActor and VectorActorHost
    delegate here (same attribute contract: ``version``, ``arch``,
    ``params``, ``_explore_kwargs``, ``_lock``, and ``timings`` where
    the host keeps a ledger) so the swap semantics — including the
    exploration-knob refresh that must NOT rebuild the policy — exist
    exactly once. Being the one gate also makes it the one swap-latency
    instrumentation point: ``rl:actor.swap`` measures the lock wait +
    install (what a slow batched step in flight costs every model
    delivery) once, for the host's ``swap_s``, the histogram and the
    sampled ``model`` / ``swap`` hop, and each installed version lands
    in the event journal."""
    from relayrl_tpu import telemetry
    from relayrl_tpu.telemetry import trace as trace_mod

    if bundle.version <= actor.version:
        return False
    if not arch_equal(bundle.arch, actor.arch):
        raise ValueError(
            f"model arch changed {actor.arch} -> {bundle.arch}; "
            "actor refuses hot-swap (param-ABI guard)")
    with span("rl:actor.swap", getattr(actor, "timings", None), "swap_s",
              metric=telemetry.get_registry().histogram(
                  "relayrl_actor_swap_seconds",
                  "model hot-swap: lock wait + params install"),
              version=int(bundle.version)) as sp:
        if trace_mod.get_tracer().sample_version(bundle.version):
            # The downstream trace's terminal hop: this actor host
            # applied the sampled version (actor field distinguishes
            # hosts sharing one process — the in-process drill's
            # topology).
            sp.hop("model", trace_mod.model_trace_id(bundle.version),
                   "swap", version=int(bundle.version),
                   actor=f"{id(actor):x}")
        with actor._lock:
            if dict(bundle.arch) != actor.arch:
                # Exploration knobs (epsilon/act_noise) changed: they are
                # traced step arguments, so only the scalar values
                # refresh — no policy rebuild, no retrace.
                actor.arch = dict(bundle.arch)
                actor._explore_kwargs = exploration_kwargs(actor.arch)
            actor.params = bundle.params
            actor.version = bundle.version
    telemetry.emit("model_swap", version=bundle.version)
    return True


def apply_wire_swap(actor, version: int, blob: bytes):
    """Shared model-delivery decode + swap for both actor hosts: sniffs
    wire-v2 frames vs legacy v1 bundles and returns the installed
    :class:`ModelBundle` (or None when nothing was installed).

    v2 path (the hot path): the frame applies into the actor's
    :class:`~relayrl_tpu.transport.modelwire.ModelWireDecoder`
    preallocated host buffers via ``np.frombuffer`` views — no flax
    ``from_bytes`` deep restore — then ONE ``jax.device_put`` of the
    assembled pytree feeds the existing :func:`apply_bundle_swap` gate.
    The device_put copies out of the buffers, so the next frame's
    in-place delta apply can never corrupt installed params (asserted by
    tests/test_model_wire.py). Installing *device* arrays also spares
    every subsequent policy dispatch the per-call host transfer.

    v1 path: legacy decode, plus a decoder reseed so a mixed-version
    fleet (v1 server, v2-capable actor) keeps the wire state coherent.

    Everything before the gate — sniff, decode, copy, ``device_put`` — is
    ``rl:actor.model_decode`` (the host's ``model_decode_s``): work of the
    subscriber thread on the core that steps the lanes.

    Raises :class:`~relayrl_tpu.transport.modelwire.WireBaseMismatch`
    (once per divergence) so the transport owner can trigger a resync —
    gRPC re-polls with ``ver=-1``; broadcast planes wait out the
    keyframe interval.
    """
    from relayrl_tpu.transport import modelwire

    with span("rl:actor.model_decode", getattr(actor, "timings", None),
              "model_decode_s", bytes=len(blob)):
        v1 = not modelwire.is_wire_frame(blob)
        if v1:
            bundle = ModelBundle.from_bytes(
                blob, params_template=ModelBundle.RAW_TREE)
            bundle.version = version
        else:
            bundle = _decode_wire_frame(actor, blob)
    if bundle is None or not apply_bundle_swap(actor, bundle):
        return None
    if v1 and actor._wire_decoder is not None:
        actor._wire_decoder.seed(bundle.version, bundle.arch, bundle.params)
    return bundle


def _decode_wire_frame(actor, blob: bytes) -> "ModelBundle | None":
    """A wire-v2 frame through the actor's decoder into a bundle that owns
    its memory; None for a stale duplicate, or while awaiting a keyframe
    after a resync."""
    from relayrl_tpu.transport import modelwire

    dec = actor._wire_decoder
    if dec is None:
        dec = actor._wire_decoder = modelwire.ModelWireDecoder()
        # the delta base is the tree as PUBLISHED: a host that holds its
        # parameters in another form (the fused tier: matmul weights at the
        # compute type) kept the delivery it installed from, where that came
        # as host arrays; where it did not, the held tree's manifest is not
        # the publisher's and the first delta asks for a keyframe, once
        published = getattr(actor, "_published", None)
        dec.seed(actor.version, actor.arch,
                 jax.device_get(actor.params) if published is None
                 else published)
        if published is not None:
            actor._published = None
    out = dec.decode(blob)
    if out is None:
        return None
    ver, arch, host_tree = out
    # The decoder's buffers are its LIVE delta targets — the next frame
    # mutates them in place — so the install must own its memory:
    # np.array copies first (device_put alone zero-copy aliases host
    # numpy on CPU backends; the isolation test in test_model_wire.py
    # catches exactly that), then ONE device_put of the assembled pytree
    # where a real transfer exists. On CPU actor hosts the host copies
    # install directly — same placement semantics as the v1 path, and a
    # device_put dispatch per leaf would cost more than the memcpy.
    params = jax.tree.map(np.array, host_tree)
    hold = getattr(actor, "hold", None)
    if hold is not None:
        # the fused tier: leaf by leaf — put, cast — here, before the gate
        params = hold(params)
    elif jax.default_backend() != "cpu":
        params = jax.device_put(params)
    return ModelBundle(version=ver, arch=arch, params=params)


def normalize_obs(obs) -> np.ndarray:
    """The ONE wire-dtype rule for observations entering any actor tier
    (PolicyActor, VectorActorHost, RemoteActorClient): byte frames stay
    bytes (uint8 pixel payloads are 4x smaller on the wire; the CNN
    trunk casts + scales on-device) with a defensive copy — envs
    commonly hand out views of a reused frame buffer, and a stored view
    would turn every recorded step into the episode's final frame —
    while everything else normalizes to float32. Shared so the tiers'
    byte-identical-trajectory parity can never drift on this rule."""
    obs = np.asarray(obs)
    return (obs.copy() if obs.dtype == np.uint8
            else obs.astype(np.float32, copy=False))


def make_batched_step(policy):
    """One jitted, vmapped sampling step over stacked per-lane inputs:
    ``fn(params, keys[N,2], obs[N,...], masks, explore) -> (acts, aux,
    next_keys)`` — the VectorActorHost hot path (N logical agents, one
    dispatch). Composition is exactly ``_fuse_rng(policy.step)`` per lane
    (split inside the trace, params broadcast), so a batch-of-1 call is
    bit-identical to PolicyActor's single step for the same key — the
    vector host is a batching change, not a numerics change. ``masks`` is
    ``None`` (maskless policies: no leaves, so the in_axes spec is inert)
    or a stacked ``[N, act_dim]`` array; ``explore`` is the
    :func:`exploration_kwargs` dict, broadcast as traced scalars so
    annealing a knob never retraces."""
    def _single(params, rng, obs, mask, explore):
        next_rng, sub = jax.random.split(rng)
        act, aux = policy.step(params, sub, obs, mask, **explore)
        return act, aux, next_rng

    return jax.jit(jax.vmap(_single, in_axes=(None, 0, 0, 0, None)))


def make_batched_window_step(policy):
    """Vmapped :attr:`Policy.step_window` for sequence policies:
    ``fn(params, keys[N,2], windows[N,W,obs], ts[N], masks) -> (acts, aux,
    next_keys)``. Per-lane window lengths ride as a traced vector, so
    lanes at different episode positions share one compiled signature
    (same property the single-actor padded-window path relies on)."""
    def _single(params, rng, window, t, mask):
        next_rng, sub = jax.random.split(rng)
        act, aux = policy.step_window(params, sub, window, t, mask)
        return act, aux, next_rng

    return jax.jit(jax.vmap(_single, in_axes=(None, 0, 0, 0, 0)))


def _fuse_rng(step_fn):
    """Move the per-step ``jax.random.split`` INSIDE the jitted function:
    the wrapped fn takes the carried key and returns ``(*outputs,
    next_key)``. An un-jitted split is its own XLA dispatch producing two
    device arrays — measured 162 µs/step vs 31 µs fused on a CPU actor
    host for the 2x128 MLP (81% of the reference-shaped
    ``request_for_action`` hot path, SURVEY §3.2). One dispatch per
    action, same key stream."""
    def fused(params, rng, *args, **kwargs):
        next_rng, sub = jax.random.split(rng)
        out = step_fn(params, sub, *args, **kwargs)
        return (*out, next_rng)  # every policy step returns a tuple
    return fused


class PolicyActor:
    """Local policy + current trajectory; thread-safe hot-swap."""

    def __init__(
        self,
        bundle: ModelBundle,
        max_traj_length: int = 1000,
        on_send=None,
        seed: int = 0,
        validate: bool = True,
        use_kv_cache: bool = True,
    ):
        self._lock = threading.Lock()
        self.arch = dict(bundle.arch)
        self.policy = build_policy(self.arch)
        if validate:
            validate_policy(self.policy, bundle.params)
        self.params = bundle.params
        self.version = bundle.version
        self._step_fn = jax.jit(_fuse_rng(self.policy.step))
        self._mode_fn = jax.jit(self.policy.mode)
        # Sequence policies act from a rolling obs-history window so
        # serving context matches training (ADVICE r1: context-1 serving).
        # Default window = the model's full context, so serving positions
        # match training exactly up to max_seq_len; past that the window
        # rolls (newest max_seq_len obs at positions 0..W-1), an
        # approximation since training pads/truncates from the episode
        # start — keep episodes within max_seq_len for exact parity.
        self._window_fn = None
        self._mode_window_fn = None
        self._window = None
        self._window_len = 0
        if self.policy.step_window is not None:
            ctx = resolve_actor_context(self.arch)
            self._window = np.zeros((ctx, int(self.arch["obs_dim"])),
                                    np.float32)
            self._window_fn = jax.jit(_fuse_rng(self.policy.step_window))
            if self.policy.mode_window is not None:
                self._mode_window_fn = jax.jit(self.policy.mode_window)
        # KV-cache incremental serving: O(W) per step instead of the
        # window path's O(W^2) full recompute. The window is still
        # maintained alongside — it is the replay source after a model
        # hot-swap (cache holds K/V computed by the OLD params) and the
        # fallback once an episode outgrows the context and the window
        # starts rolling (absolute positions shift, invalidating the
        # cache wholesale).
        self._cached_fn = None
        self._prefill_fn = None
        self._cache = None
        self._cache_version = -1
        if (use_kv_cache and self.policy.step_cached is not None
                and self.policy.prefill_cache is not None
                and self._window is not None):
            # prefill is required, not optional: cache rebuild (hot-swap,
            # greedy-path interleave) calls it with t > 0.
            # Donation is honored on TPU/GPU; CPU actor hosts would emit a
            # "donated buffers were not usable" warning on every step.
            donate = jax.default_backend() != "cpu"
            # _fuse_rng keeps positional order (params, rng, cache, ...),
            # so the donated cache stays argument 2.
            self._cached_fn = jax.jit(
                _fuse_rng(self.policy.step_cached),
                donate_argnums=(2,) if donate else ())
            self._prefill_fn = jax.jit(
                self.policy.prefill_cache,
                donate_argnums=(1,) if donate else ())
        self._explore_kwargs = exploration_kwargs(self.arch)
        self._rng = jax.random.PRNGKey(seed)
        # Wire-v2 decode state (preallocated per-leaf host buffers),
        # created lazily on the first v2 frame (apply_wire_swap) so
        # in-process actors that never touch the network pay nothing.
        self._wire_decoder = None
        # Where this process's time goes, always on, reported to the
        # learner on every trajectory shipped (telemetry/actor_ledger.py).
        self.ledger = ActorLedger()
        self.timings = self.ledger.timings
        self.counts = self.ledger.counts
        self.trajectory = Trajectory(max_length=max_traj_length,
                                     on_send=on_send, timings=self.timings)
        from relayrl_tpu import telemetry

        self._m_steps = telemetry.get_registry().counter(
            "relayrl_actor_env_steps_total",
            "policy steps served (one per env step per lane)")

    # -- reference API (agent_zmq.rs:458-571 / o3_agent.rs:117-182) --
    def request_for_action(
        self,
        obs,
        mask=None,
        reward: float = 0.0,
    ) -> ActionRecord:
        """Run the policy, append the step to the current trajectory.

        ``reward`` is the env reward earned since the previous request —
        it is attached to the PREVIOUS record via ``update_reward`` so
        ``ActionRecord.rew`` always means "reward earned BY this action".
        The reference stores the incoming reward on the NEW record instead
        (agent_grpc.rs:434-441 builds the fresh action with it), a
        one-step credit shift its return-to-go REINFORCE tolerates but
        that inverts 1-step TD targets (DQN credited a_t with r_{t-1});
        deliberate departure, SURVEY.md §7.5 spirit. The only reward that
        can be lost is one spanning a capacity-flush chunk boundary (the
        previous record already left the process)."""
        # Byte frames stay bytes, everything else float32 — the shared
        # rule (see normalize_obs: an unconditional float32 cast here
        # silently made every "byte-sized" pixel payload 112,989 B/step
        # instead of 28,226).
        with self.ledger.step(1):
            obs = normalize_obs(obs)
            mask_arr = (None if mask is None
                        else np.asarray(mask, dtype=np.float32))
            with self._lock:
                if reward and self.trajectory.get_actions():
                    self.trajectory.get_actions()[-1].update_reward(
                        float(reward))
                with span("rl:actor.infer", self.timings, "infer_s"):
                    act, aux = self._infer(obs, mask_arr)
                    act = np.asarray(act)
                    data = {k: np.asarray(v) for k, v in aux.items()}
                with self.ledger.record():
                    record = ActionRecord(
                        obs=obs, act=act, mask=mask_arr,
                        rew=0.0,  # filled by the NEXT request / terminal
                        data=data, done=False)
                    self.trajectory.add_action(record, send_if_done=True)
        self._m_steps.inc()
        return record

    def _infer(self, obs, mask_arr):
        """One jitted sampling step (lock held): ``(act, aux)`` still on
        the device. The RNG split rides inside each jitted step
        (_fuse_rng): every branch returns next_rng as its last output."""
        if self._window_fn is None:
            act, aux, self._rng = self._step_fn(
                self.params, self._rng, obs, mask_arr,
                **self._explore_kwargs)
            return act, aux
        rolled = self._push_window(obs)
        t = self._window_len - 1
        if self._cached_fn is not None and not rolled:
            if self._cache is None or self._cache_version != self.version:
                self._rebuild_cache(t)
            act, aux, self._cache, self._rng = self._cached_fn(
                self.params, self._rng, self._cache, obs, t, mask_arr)
        else:
            self._cache = None  # rolling: positions shifted
            act, aux, self._rng = self._window_fn(
                self.params, self._rng, self._window, self._window_len,
                mask_arr)
        return act, aux

    def flag_last_action(
        self,
        reward: float = 0.0,
        truncated: bool = False,
        final_obs=None,
        terminated: bool | None = None,
        final_mask=None,
    ) -> None:
        """Terminal marker: appends a done action carrying the final reward,
        which triggers the trajectory send (ref: agent_zmq.rs:605-610).

        ``truncated=True`` marks a time-limit ending (Gymnasium semantics):
        the learner then bootstraps the value target through the boundary
        instead of zeroing it. Pass the post-step observation as
        ``final_obs`` so off-policy learners have a successor state to
        bootstrap from (plus ``final_mask`` in action-masked envs, so the
        bootstrap max ranges only over actions legal in that state).
        Gymnasium can report ``terminated`` and ``truncated`` both True; a
        genuine terminal must win (no bootstrapping past a real end
        state), so callers mapping ``env.step`` output directly can pass
        ``terminated`` and let this method resolve the precedence instead
        of pre-computing it.
        """
        if terminated:
            truncated = False
        # A step of the program's like any other (no lane stepped): the
        # marker's flush must not read as the caller's environment.
        with self.ledger.step(0), self._lock:
            if self._window is not None:
                # Episode boundary: the next episode must not attend this
                # one's observations.
                self._window[:] = 0.0
                self._window_len = 0
                self._cache = None
            with self.ledger.record():
                record = ActionRecord(
                    obs=(None if final_obs is None
                         else np.asarray(final_obs, np.float32)),
                    mask=(None if final_mask is None
                          else np.asarray(final_mask, np.float32)),
                    rew=float(reward), done=True, truncated=bool(truncated))
                self.trajectory.add_action(record, send_if_done=True)

    def record_action(self, action: ActionRecord) -> None:
        """Append an externally-chosen action (the reference declares this
        but left it ``todo!()`` — agent_zmq.rs:585-596)."""
        with self.ledger.step(1), self._lock, self.ledger.record():
            self.trajectory.add_action(action, send_if_done=True)

    # -- model hot-swap --
    def maybe_swap(self, bundle: ModelBundle) -> bool:
        """Install a newer model; stale or arch-mismatched bundles are
        rejected (version checking the reference's proto defines but never
        implements — training_grpc.rs:722-725)."""
        return apply_bundle_swap(self, bundle)

    def swap_from_bytes(self, buf: bytes) -> bool:
        return self.maybe_swap(
            ModelBundle.from_bytes(buf, params_template=ModelBundle.RAW_TREE))

    def swap_from_wire(self, version: int, blob: bytes):
        """Wire-v2-aware swap (sniffs v1 bundles too); returns the
        installed ModelBundle or None — see :func:`apply_wire_swap`."""
        return apply_wire_swap(self, version, blob)

    def _push_window(self, obs: np.ndarray) -> bool:
        """Append one observation to the rolling history (lock held).
        Returns True once the window has started rolling."""
        self._window_len, rolled = push_window(
            self._window, self._window_len, obs)
        return rolled

    def _rebuild_cache(self, t: int) -> None:
        """Fresh cache, refilled from the stored window (lock held) —
        called lazily after a model hot-swap (old params' K/V are stale)
        or on the first cached step of an episode. One prefill dispatch
        over the full padded window (fixed shape, so one jit signature;
        padding rows write K/V that later steps overwrite in order and
        never attend before that). Masks are not replayed: they only gate
        the readout logits, never the K/V trunk."""
        self._cache = self.policy.init_cache(self._window.shape[0])
        if t > 0:
            self._cache = self._prefill_fn(self.params, self._cache,
                                           self._window, t)
        self._cache_version = self.version

    def reset_episode(self) -> None:
        """Reset per-episode serving state (history window + KV cache)
        WITHOUT touching the trajectory — the episode boundary for eval
        loops, where nothing must be shipped to the learner
        (flag_last_action both resets and sends)."""
        with self._lock:
            if self._window is not None:
                self._window[:] = 0.0
                self._window_len = 0
            self._cache = None

    def deterministic_action(self, obs, mask=None):
        """Greedy action. For sequence policies this ADVANCES the history
        window (greedy eval episodes need context too); call
        flag_last_action (sampling loops) or reset_episode (eval loops)
        at episode end to reset it."""
        obs_arr = np.asarray(obs, np.float32)
        mask_arr = None if mask is None else np.asarray(mask, np.float32)
        with self._lock:
            if self._mode_window_fn is not None:
                self._push_window(obs_arr)
                # The greedy path bypasses the cache but still advances the
                # window; drop the cache so the sampling path rebuilds with
                # every position present.
                self._cache = None
                act = self._mode_window_fn(self.params, self._window,
                                           self._window_len, mask_arr)
            else:
                act = self._mode_fn(self.params, obs_arr, mask_arr)
        return np.asarray(act)


def actor_aux_to_host(aux: Mapping[str, Any]) -> dict[str, np.ndarray]:
    return {k: np.asarray(v) for k, v in aux.items()}
