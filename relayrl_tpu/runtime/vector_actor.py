"""Vectorized actor host: N logical agents, one batched jitted policy step.

One actor process per agent stops scaling at the host's core count:
past it the processes oversubscribe the cores (scheduler churn, one jit
cache and one set of listener threads per process) — not a transport
cost. The fix that transfers from large-scale RL practice is
actor-side batching: Podracer's Anakin steps many environments against a
single jitted policy call (arxiv 2104.06272), and TorchBeast/IMPALA batch
actor inference so env count decouples from process count (arxiv
1910.03552). :class:`VectorActorHost` is that architecture for this
framework: one process steps ``num_envs`` environment lanes through ONE
vmapped, jitted policy dispatch (per-lane PRNG keys split from one seed
key, params broadcast) and presents each lane to the training server as
its own *logical* agent — N trajectory streams with distinct agent ids
multiplexed over one transport connection (see the transport ``base.py``
contract), one shared model-receipt subscription, and a single
:meth:`maybe_swap` that atomically installs new params for every lane (a
batched step reads one params pytree, so no lane can ever act on a mixed
version).

Numerics: the batched step is ``vmap`` of exactly the composition
PolicyActor jits for one agent (``_fuse_rng(policy.step)``), so a
batch-of-1 host is bit-identical to a plain PolicyActor for the same key
(asserted by tests/test_vector_actor.py). Sequence policies run the
vmapped padded-window path with stacked per-lane windows; the KV-cache
incremental path is single-lane-only and intentionally not used here (a
per-lane cache pytree would be donated/rebuilt per swap per lane — the
window recompute is the simpler batched serving story).
"""

from __future__ import annotations

import threading

import jax
import numpy as np

from relayrl_tpu.models import build_policy, validate_policy
from relayrl_tpu.telemetry.actor_ledger import ActorLedger
from relayrl_tpu.runtime.policy_actor import (
    apply_bundle_swap,
    apply_wire_swap,
    make_batched_step,
    make_batched_window_step,
    normalize_obs,
    push_window,
    resolve_actor_context,
)
from relayrl_tpu.telemetry.spans import span
from relayrl_tpu.types.action import ActionRecord
from relayrl_tpu.types.model_bundle import ModelBundle, exploration_kwargs
from relayrl_tpu.types.trajectory import Trajectory


class VectorActorHost:
    """N env lanes → one batched policy dispatch → N trajectory streams.

    ``on_send(lane, payload)`` receives each lane's serialized episodes;
    the networked facade (:class:`relayrl_tpu.runtime.agent.VectorAgent`)
    stamps lane ``lane``'s payloads with that lane's logical agent id.
    ``rng_keys`` (stacked ``[N, 2]``) overrides the default per-lane key
    derivation (``jax.random.split(PRNGKey(seed), N)``) — parity tests
    hand lane 0 the exact key a single PolicyActor would carry.
    """

    def __init__(
        self,
        bundle: ModelBundle,
        num_envs: int,
        max_traj_length: int = 1000,
        on_send=None,
        seed: int = 0,
        validate: bool = True,
        rng_keys=None,
    ):
        if num_envs < 1:
            raise ValueError(f"num_envs must be >= 1, got {num_envs}")
        self._lock = threading.Lock()
        self.num_envs = int(num_envs)
        self.arch = dict(bundle.arch)
        self.policy = build_policy(self.arch)
        if validate:
            validate_policy(self.policy, bundle.params)
        self.params = bundle.params
        self.version = bundle.version
        self._batched_fn = make_batched_step(self.policy)
        self._windows = None
        self._window_lens = None
        self._batched_window_fn = None
        if self.policy.step_window is not None:
            ctx = resolve_actor_context(self.arch)
            self._windows = np.zeros(
                (self.num_envs, ctx, int(self.arch["obs_dim"])), np.float32)
            self._window_lens = np.zeros(self.num_envs, np.int32)
            self._batched_window_fn = make_batched_window_step(self.policy)
        self._explore_kwargs = exploration_kwargs(self.arch)
        # Wire-v2 decode state, lazily created on the first v2 frame —
        # ONE decoder for all lanes (the whole point: one subscription,
        # one delta apply, one device_put, N lanes served).
        self._wire_decoder = None
        if rng_keys is not None:
            keys = np.asarray(rng_keys)
            if keys.shape[0] != self.num_envs:
                raise ValueError(
                    f"rng_keys has {keys.shape[0]} rows for "
                    f"{self.num_envs} lanes")
            self._keys = jax.numpy.asarray(keys)
        else:
            self._keys = jax.random.split(
                jax.random.PRNGKey(seed), self.num_envs)
        # Where this process's time goes, always on, reported to the
        # learner on every trajectory shipped (telemetry/actor_ledger.py).
        self.ledger = ActorLedger()
        self.timings = self.ledger.timings
        self.counts = self.ledger.counts
        self.trajectories = [
            Trajectory(
                max_length=max_traj_length,
                on_send=(None if on_send is None
                         else (lambda payload, _lane=lane:
                               on_send(_lane, payload))),
                timings=self.timings)
            for lane in range(self.num_envs)
        ]
        from relayrl_tpu import telemetry

        reg = telemetry.get_registry()
        self._m_steps = reg.counter(
            "relayrl_actor_env_steps_total",
            "policy steps served (one per env step per lane)")
        self._m_dispatches = reg.counter(
            "relayrl_actor_batched_dispatches_total",
            "batched policy dispatches (each serves num_envs lanes)")
        reg.gauge("relayrl_actor_lanes",
                  "env lanes per batched dispatch on this host").set(
                      self.num_envs)

    # -- batched action API --
    def request_for_actions(self, obs, masks=None,
                            rewards=None) -> list[ActionRecord]:
        """One batched policy dispatch for all lanes; appends one
        ActionRecord per lane to that lane's trajectory.

        ``obs`` is stacked ``[N, ...]``; ``rewards`` (length N, or None)
        carries each lane's env reward earned since its previous request
        and is attached to that lane's PREVIOUS record (same
        credit-assignment semantics as ``PolicyActor.request_for_action``
        — ``ActionRecord.rew`` always means "reward earned BY this
        action"). ``masks`` is None or stacked ``[N, act_dim]``.
        """
        with self.ledger.step(self.num_envs):
            records = self._step(obs, masks, rewards)
        self._m_steps.inc(self.num_envs)
        self._m_dispatches.inc()
        return records

    def _step(self, obs, masks, rewards) -> list[ActionRecord]:
        obs = np.asarray(obs)
        if obs.shape[0] != self.num_envs:
            raise ValueError(
                f"obs batch {obs.shape[0]} != num_envs {self.num_envs}")
        # Byte frames stay bytes on the wire, everything else float32 —
        # the shared rule (normalize_obs), including the defensive copy
        # of possibly-reused frame buffers.
        obs = normalize_obs(obs)
        masks_arr = (None if masks is None
                     else np.asarray(masks, dtype=np.float32))
        with self._lock:
            if rewards is not None:
                for lane, r in enumerate(rewards):
                    if r and self.trajectories[lane].get_actions():
                        self.trajectories[lane].get_actions()[-1] \
                            .update_reward(float(r))
            # ONE params read under the lock for the whole batch: every
            # lane acts on the same model version by construction
            # (maybe_swap's atomicity across lanes).
            with span("rl:actor.infer", self.timings, "infer_s"):
                if self._batched_window_fn is not None:
                    self._push_windows(obs)
                    # step_window takes the per-lane count of REAL rows
                    # (it reads out at t-1 itself) — same convention as
                    # PolicyActor passing _window_len, asserted
                    # bit-identical by the window parity test.
                    acts, aux, self._keys = self._batched_window_fn(
                        self.params, self._keys, self._windows,
                        self._window_lens, masks_arr)
                else:
                    acts, aux, self._keys = self._batched_fn(
                        self.params, self._keys, obs, masks_arr,
                        self._explore_kwargs)
                acts_np = np.asarray(acts)
                aux_np = {k: np.asarray(v) for k, v in aux.items()}
            records = []
            with self.ledger.record():
                for lane in range(self.num_envs):
                    record = ActionRecord(
                        obs=obs[lane],
                        act=acts_np[lane],
                        mask=None if masks_arr is None else masks_arr[lane],
                        rew=0.0,  # filled by the lane's NEXT request
                        # np.asarray: indexing a stacked [N] aux column
                        # yields a numpy SCALAR, which the wire codec
                        # would encode as a float64 — the 0-d ndarray
                        # keeps dtype (and bytes) identical to the
                        # single-actor path.
                        data={k: np.asarray(v[lane])
                              for k, v in aux_np.items()},
                        done=False,
                    )
                    self.trajectories[lane].add_action(record,
                                                       send_if_done=True)
                    records.append(record)
        return records

    def flag_last_action(self, lane: int, reward: float = 0.0,
                         truncated: bool = False, final_obs=None,
                         terminated: bool | None = None,
                         final_mask=None) -> None:
        """Terminal marker for ONE lane (lanes end episodes independently
        under autoreset): appends a done action carrying the final reward,
        which ships that lane's trajectory. Semantics identical to
        ``PolicyActor.flag_last_action`` including terminated-beats-
        truncated precedence and the bootstrap ``final_obs``."""
        if terminated:
            truncated = False
        # A step of the program's like any other (no lane stepped): the
        # marker's flush must not read as the caller's environment.
        with self.ledger.step(0), self._lock:
            if self._windows is not None:
                # Episode boundary for this lane only: its next episode
                # must not attend this one's observations.
                self._windows[lane, :, :] = 0.0
                self._window_lens[lane] = 0
            with self.ledger.record():
                record = ActionRecord(
                    obs=(None if final_obs is None
                         else np.asarray(final_obs, np.float32)),
                    mask=(None if final_mask is None
                          else np.asarray(final_mask, np.float32)),
                    rew=float(reward), done=True, truncated=bool(truncated))
                self.trajectories[lane].add_action(record,
                                                   send_if_done=True)

    def shipping(self, lane: int):
        """``(born_ns, rl:actor.encode span)`` of the unroll lane ``lane``
        is handing to ``on_send`` right now (the send hook runs inside its
        trajectory's flush)."""
        traj = self.trajectories[lane]
        return traj.born_ns, traj.encode_span

    # -- model hot-swap (one gate, all lanes) --
    def maybe_swap(self, bundle: ModelBundle) -> bool:
        """Install a newer model for EVERY lane atomically: the params
        swap (shared gate with PolicyActor, ``apply_bundle_swap``)
        happens under the same lock the batched step holds, and the step
        reads params exactly once — there is no interleaving in which
        some lanes act on the old version and some on the new within one
        dispatch."""
        return apply_bundle_swap(self, bundle)

    def swap_from_bytes(self, buf: bytes) -> bool:
        return self.maybe_swap(
            ModelBundle.from_bytes(buf, params_template=ModelBundle.RAW_TREE))

    def swap_from_wire(self, version: int, blob: bytes):
        """Wire-v2-aware swap shared with PolicyActor (same attribute
        contract); one frame updates every lane atomically."""
        return apply_wire_swap(self, version, blob)

    def reset_episode(self, lane: int | None = None) -> None:
        """Reset per-episode serving state (history windows) without
        touching trajectories — one lane, or all lanes when ``lane`` is
        None."""
        with self._lock:
            if self._windows is None:
                return
            if lane is None:
                self._windows[:] = 0.0
                self._window_lens[:] = 0
            else:
                self._windows[lane, :, :] = 0.0
                self._window_lens[lane] = 0

    def _push_windows(self, obs: np.ndarray) -> None:
        """Append one observation per lane to the stacked rolling history
        (lock held). Lanes at capacity roll independently — each goes
        through the shared push_window rule so the byte-parity contract
        can't drift across tiers."""
        for lane in range(self.num_envs):
            self._window_lens[lane], _ = push_window(
                self._windows[lane], int(self._window_lens[lane]),
                obs[lane])


def run_vector_gym_loop(host, venv, steps: int,
                        seed: int | None = None) -> list[list[float]]:
    """Drive a :class:`~relayrl_tpu.envs.vector.SyncVectorEnv` (or any
    stacked gym-like with autoreset) through a vector host/agent for
    ``steps`` batched policy dispatches. Returns per-lane completed
    episode returns. Works with both a raw VectorActorHost and the
    networked VectorAgent (same batched action surface)."""
    from relayrl_tpu.runtime.agent import coerce_env_action

    n = venv.num_envs
    obs, _ = venv.reset(seed=seed)
    rewards = np.zeros(n, np.float32)
    ep_ret = np.zeros(n, np.float64)
    returns: list[list[float]] = [[] for _ in range(n)]
    for _ in range(steps):
        records = host.request_for_actions(obs, rewards=rewards)
        actions = [coerce_env_action(r.act) for r in records]
        obs, rews, terms, truncs, infos = venv.step(actions)
        ep_ret += rews
        for lane in range(n):
            if terms[lane] or truncs[lane]:
                # Autoreset already happened inside venv.step; the
                # pre-reset observation rides the info dict for the
                # time-limit bootstrap.
                time_limited = not terms[lane]
                host.flag_last_action(
                    lane, float(rews[lane]),
                    truncated=bool(time_limited),
                    final_obs=(infos[lane].get("final_observation")
                               if time_limited else None),
                    terminated=bool(terms[lane]))
                returns[lane].append(float(ep_ret[lane]))
                ep_ret[lane] = 0.0
                rewards[lane] = 0.0  # new episode: nothing earned yet
            else:
                rewards[lane] = rews[lane]
    return returns
