"""Variable-length trajectories → fixed-shape padded/masked batches.

The reference pickles arbitrary-length ``Vec<RelayRLAction>`` and loops over
actions in Python (reference: relayrl_framework/src/native/python/algorithms/
REINFORCE/REINFORCE.py:70-95 unpacks one action at a time into the buffer).
Under XLA every distinct shape is a recompilation, so here trajectories are
padded to **bucketed** lengths with a validity mask and stacked into
``[B, T, ...]`` batches — the learner compiles once per bucket, not once per
episode length (SURVEY.md §7.4 item 3).
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

from relayrl_tpu.types.action import ActionRecord


@dataclasses.dataclass
class PaddedTrajectory:
    """One episode padded to ``T`` with host (numpy) arrays."""

    obs: np.ndarray        # [T, obs_dim] u8 (byte frames) or f32
    act: np.ndarray        # [T] i32 (discrete) or [T, act_dim] f32
    act_mask: np.ndarray   # [T, act_dim] f32
    rew: np.ndarray        # [T] f32
    val: np.ndarray        # [T] f32 — critic value stored at sample time
    logp: np.ndarray       # [T] f32 — behavior log-prob stored at sample time
    valid: np.ndarray      # [T] f32
    length: int
    terminated: bool       # final action had done=True
    last_val: float        # bootstrap value for truncated episodes


@dataclasses.dataclass
class TrajectoryBatch:
    """Stacked episodes ``[B, T, ...]`` — the learner-step input."""

    obs: np.ndarray        # [B, T, obs_dim] u8 or f32 (batch_obs_dtype)
    act: np.ndarray        # [B, T] or [B, T, act_dim]
    act_mask: np.ndarray   # [B, T, act_dim]
    rew: np.ndarray        # [B, T]
    val: np.ndarray        # [B, T]
    logp: np.ndarray       # [B, T]
    valid: np.ndarray      # [B, T]
    last_val: np.ndarray   # [B]

    @property
    def batch_size(self) -> int:
        return self.obs.shape[0]

    @property
    def horizon(self) -> int:
        return self.obs.shape[1]

    def as_dict(self) -> dict[str, np.ndarray]:
        # Shallow on purpose: dataclasses.asdict would deep-copy every
        # array, silently undoing the staging-slab zero-alloc path (the
        # batch must stay a VIEW of the persistent buffers all the way
        # to device placement). Consumers only read.
        return {f.name: getattr(self, f.name)
                for f in dataclasses.fields(self)}

    @classmethod
    def zeros(cls, batch_size: int, horizon: int, obs_dim: int, act_dim: int,
              discrete: bool = True,
              obs_dtype=np.float32) -> dict[str, np.ndarray]:
        """Zero batch dict with this schema's exact keys/dtypes/shapes —
        the single owner used by the staging slabs, the warm-up
        placeholder and the multi-host broadcast protocol, where
        non-coordinator processes must hold a pytree-identical template
        before ``broadcast_one_to_all`` fills it. ``obs_dtype`` is the one
        dtype that follows the stream (:func:`batch_obs_dtype`)."""
        b, t = int(batch_size), int(horizon)
        act = (np.zeros((b, t), np.int32) if discrete
               else np.zeros((b, t, act_dim), np.float32))
        return {
            "obs": np.zeros((b, t, obs_dim), obs_dtype),
            "act": act,
            "act_mask": np.zeros((b, t, act_dim), np.float32),
            "rew": np.zeros((b, t), np.float32),
            "val": np.zeros((b, t), np.float32),
            "logp": np.zeros((b, t), np.float32),
            "valid": np.zeros((b, t), np.float32),
            "last_val": np.zeros((b,), np.float32),
        }


_U8 = np.dtype(np.uint8)
_F32 = np.dtype(np.float32)


def padded_obs_dtype(source_dtypes) -> np.dtype:
    """The dtype observations keep from the wire to the jitted update:
    byte frames (``uint8``) stay bytes — the model casts to its compute
    dtype on entry, on the device, and 0..255 are exact in uint8, float32
    and bfloat16 alike, so a float32 stop on the host would only make
    every byte four for the pad, the stack and the H2D copy. Everything
    else (float64, ints, mixed, no observations at all) is ``float32``
    as it always was."""
    seen = False
    for dt in source_dtypes:
        if dt != _U8:
            return _F32
        seen = True
    return _U8 if seen else _F32


def batch_obs_dtype(trajs) -> np.dtype:
    """A batch's obs dtype from the padded episodes it takes: ``uint8``
    iff every one of them is, else ``float32`` — the row assignment
    widens a byte exactly, so two fleets with different env wrappers
    never break a batch."""
    return padded_obs_dtype(t.obs.dtype for t in trajs)


def fold_trailing_markers(
    actions: Sequence[ActionRecord],
) -> tuple[list[ActionRecord], np.ndarray | None, bool, np.ndarray | None]:
    """Fold ``flag_last_action`` markers (act-less records) into the last
    real step.

    The marker's reward is added to the preceding step and its done /
    truncated flags OR-merged in. Returns ``(steps, final_obs, truncated,
    final_mask)`` where ``final_obs`` is the post-step observation a
    truncation marker may carry (the off-policy bootstrap successor),
    ``truncated`` is True if any marker flagged a time-limit ending, and
    ``final_mask`` is the marker's action mask for that successor state
    (action-masked envs). Shared by the epoch and step replay buffers so
    marker semantics cannot diverge between them.
    """
    steps = list(actions)
    final_obs: np.ndarray | None = None
    final_mask: np.ndarray | None = None
    truncated = False
    while steps and steps[-1].act is None:
        marker = steps.pop()
        truncated = truncated or marker.truncated
        if marker.obs is not None:
            final_obs = np.asarray(marker.obs, np.float32)
        if marker.mask is not None:
            final_mask = np.asarray(marker.mask, np.float32)
        if steps:
            last = steps[-1]
            steps[-1] = ActionRecord(
                obs=last.obs, act=last.act, mask=last.mask,
                rew=last.rew + marker.rew, data=last.data,
                done=last.done or marker.done,
                truncated=last.truncated or marker.truncated,
            )
    return steps, final_obs, truncated, final_mask


def pick_bucket(length: int, buckets: Sequence[int]) -> int:
    """Smallest bucket ≥ length (lengths above the largest clamp to it).

    One scan, no per-call ``sorted()`` — this runs once per ingested
    trajectory and the old re-sort was pure hot-path overhead
    (:class:`~relayrl_tpu.data.EpochBuffer` sorts its buckets once at
    construction; the scan keeps the public API order-independent for
    any other caller)."""
    best = largest = None
    for b in buckets:
        b = int(b)
        if length <= b and (best is None or b < best):
            best = b
        if largest is None or b > largest:
            largest = b
    return best if best is not None else largest


def pad_trajectory(
    actions: Sequence[ActionRecord],
    horizon: int,
    obs_dim: int,
    act_dim: int,
    discrete: bool = True,
) -> PaddedTrajectory:
    """ActionRecords → fixed-shape padded arrays.

    Aux ``logp_a``/``v`` come from the action's data dict (the reference's
    REINFORCE reads ``data['v']``/``data['logp_a']`` the same way). Episodes
    longer than ``horizon`` are truncated (bootstrapped from the stored value
    of the last kept step). Observations keep ``uint8`` when every step's
    are bytes, else they are ``float32`` (:func:`padded_obs_dtype`).
    """
    if not actions:
        raise ValueError("empty trajectory")
    # ``flag_last_action`` terminates an episode with a marker record that
    # carries only the final reward + done flag (no obs/act — ref:
    # agent_zmq.rs:605-610). Markers are not steps: fold their reward into
    # the preceding real step so the policy-gradient loss never sees a
    # fictitious action at a zero observation.
    actions, _, _, _ = fold_trailing_markers(actions)
    if not actions:
        raise ValueError("trajectory contained only terminal markers")
    n = min(len(actions), horizon)

    obs_dtype = padded_obs_dtype(
        np.asarray(a.obs).dtype for a in actions[:n] if a.obs is not None)
    obs = np.zeros((horizon, obs_dim), dtype=obs_dtype)
    act = np.zeros((horizon,), dtype=np.int32) if discrete else np.zeros(
        (horizon, act_dim), dtype=np.float32)
    act_mask = np.zeros((horizon, act_dim), dtype=np.float32)
    act_mask[:n] = 1.0
    rew = np.zeros((horizon,), dtype=np.float32)
    val = np.zeros((horizon,), dtype=np.float32)
    logp = np.zeros((horizon,), dtype=np.float32)
    valid = np.zeros((horizon,), dtype=np.float32)

    for t in range(n):
        a = actions[t]
        if a.obs is not None:
            obs[t] = np.asarray(a.obs, dtype=obs_dtype).reshape(-1)[:obs_dim]
        if a.act is not None:
            if discrete:
                act[t] = int(np.asarray(a.act).reshape(-1)[0])
            else:
                act[t] = np.asarray(a.act, dtype=np.float32).reshape(-1)[:act_dim]
        if a.mask is not None:
            act_mask[t] = np.asarray(a.mask, dtype=np.float32).reshape(-1)[:act_dim]
        rew[t] = float(a.rew)
        data = a.data or {}
        val[t] = float(np.asarray(data.get("v", 0.0)).reshape(-1)[0]) if "v" in data else 0.0
        logp[t] = (
            float(np.asarray(data.get("logp_a", 0.0)).reshape(-1)[0])
            if "logp_a" in data else 0.0
        )
        valid[t] = 1.0

    # ``terminated`` means a true terminal state: the value target stops
    # there. A time-limit truncation (Gymnasium ``truncated``) must still
    # bootstrap — v(s_{T+1}) is unavailable on the wire, so the stored
    # v(s_T) is the standard stand-in (the reference never bootstraps:
    # finish_path(last_val=0)).
    terminated = (bool(actions[n - 1].done)
                  and not bool(actions[n - 1].truncated)
                  and n == len(actions))
    last_val = 0.0 if terminated else float(val[n - 1])
    return PaddedTrajectory(
        obs=obs, act=act, act_mask=act_mask, rew=rew, val=val, logp=logp,
        valid=valid, length=n, terminated=terminated, last_val=last_val,
    )


def decoded_obs_dtype(dt) -> np.dtype:
    """What :func:`pad_decoded` makes of ``dt``'s observation column."""
    col = dt.columns.get("o")
    return padded_obs_dtype(() if col is None else (col.dtype,))


def pad_decoded(
    dt,
    horizon: int,
    obs_dim: int,
    act_dim: int,
    discrete: bool = True,
    out: PaddedTrajectory | None = None,
) -> PaddedTrajectory:
    """Columnar fast path of :func:`pad_trajectory`.

    ``dt`` is a :class:`relayrl_tpu.types.columnar.DecodedTrajectory` (the
    native decoder already folded terminal markers), so padding is pure
    vectorized slice assignment — no per-step Python loop. Semantics are
    kept identical to the ActionRecord path (tests/test_native_codec.py
    asserts byte equality of the padded outputs across both paths).

    ``out`` is a padded trajectory of the same horizon and widths that
    nothing reads any more — a row of a batch slab (:func:`slab_row`):
    its arrays are written over and returned in a new
    :class:`PaddedTrajectory`, equal to what a fresh call gives. Its obs
    may be float32 where the episode's are bytes (they widen exactly, as
    they did at the stack), never the other way round.
    """
    cols, aux = dt.columns, dt.aux
    total = dt.n_steps
    if total == 0:
        raise ValueError("trajectory contained only terminal markers"
                         if dt.n_records else "empty trajectory")
    n = min(total, horizon)
    obs_dtype = decoded_obs_dtype(dt)
    if out is None:
        obs = np.zeros((horizon, obs_dim), dtype=obs_dtype)
        act = (np.zeros((horizon,), dtype=np.int32) if discrete
               else np.zeros((horizon, act_dim), dtype=np.float32))
        act_mask = np.zeros((horizon, act_dim), dtype=np.float32)
        rew, val, logp, valid = (np.zeros((horizon,), dtype=np.float32)
                                 for _ in range(4))
    else:
        if out.obs.dtype != obs_dtype and out.obs.dtype != _F32:
            raise ValueError(f"cannot pad {obs_dtype} observations over a "
                             f"{out.obs.dtype} episode")
        obs, act, act_mask = out.obs, out.act, out.act_mask
        rew, val, logp, valid = out.rew, out.val, out.logp, out.valid
        if n < horizon:  # rows [:n] are each assigned below
            for arr in (obs, act, act_mask, rew, val, logp, valid):
                arr[n:] = 0
    if "o" in cols:
        flat = cols["o"].reshape(total, -1)
        if flat.shape[1] < obs_dim:
            raise ValueError(
                f"obs has {flat.shape[1]} features, expected >= {obs_dim}")
        obs[:n] = flat[:n, :obs_dim]
    else:
        obs[:n] = 0
    if "a" not in cols:
        act[:n] = 0
    elif discrete:
        act[:n] = cols["a"].reshape(total, -1)[:n, 0]
    else:
        act[:n] = cols["a"].reshape(total, -1)[:n, :act_dim]
    act_mask[:n] = (cols["m"].reshape(total, -1)[:n, :act_dim]
                    if "m" in cols else 1.0)
    rew[:n] = cols["r"][:n]
    val[:n] = aux["v"].reshape(total, -1)[:n, 0] if "v" in aux else 0
    logp[:n] = (aux["logp_a"].reshape(total, -1)[:n, 0]
                if "logp_a" in aux else 0)
    valid[:n] = 1.0

    done = cols["t"]
    trunc = cols["x"]
    terminated = (bool(done[n - 1]) and not bool(trunc[n - 1])
                  and n == total)
    last_val = 0.0 if terminated else float(val[n - 1])
    return PaddedTrajectory(
        obs=obs, act=act, act_mask=act_mask, rew=rew, val=val, logp=logp,
        valid=valid, length=n, terminated=terminated, last_val=last_val,
    )


_BATCH_FIELDS = ("obs", "act", "act_mask", "rew", "val", "logp", "valid")


def stack_trajectories(
    trajs: Sequence[PaddedTrajectory],
    obs_dtype=None,
) -> TrajectoryBatch:
    """Padded episodes of one horizon → one freshly allocated
    ``[B, T, ...]`` batch; obs stack to ``obs_dtype``, by default what
    :func:`batch_obs_dtype` makes of the episodes. The plain form:
    :class:`~relayrl_tpu.data.EpochBuffer` pads each episode straight
    into its row of a slab and never stacks."""
    horizons = {t.obs.shape[0] for t in trajs}
    if len(horizons) != 1:
        raise ValueError(f"mixed horizons in batch: {sorted(horizons)}")
    if obs_dtype is None:
        obs_dtype = batch_obs_dtype(trajs)
    return TrajectoryBatch(
        obs=np.stack([t.obs for t in trajs], dtype=obs_dtype),
        act=np.stack([t.act for t in trajs]),
        act_mask=np.stack([t.act_mask for t in trajs]),
        rew=np.stack([t.rew for t in trajs]),
        val=np.stack([t.val for t in trajs]),
        logp=np.stack([t.logp for t in trajs]),
        valid=np.stack([t.valid for t in trajs]),
        last_val=np.asarray([t.last_val for t in trajs], dtype=np.float32),
    )


def slab_row(slab: dict[str, np.ndarray], i: int) -> PaddedTrajectory:
    """Row ``i`` of a ``[B, T, ...]`` slab as the ``out`` of
    :func:`pad_decoded`: contiguous views, one a field."""
    return PaddedTrajectory(*(slab[name][i] for name in _BATCH_FIELDS),
                            length=0, terminated=False, last_val=0.0)


def copy_episode(row: PaddedTrajectory, traj: PaddedTrajectory) -> None:
    """A padded episode into a slab row of a horizon no shorter (the tail
    zero-filled) and an obs dtype no narrower."""
    n = traj.obs.shape[0]
    for name in _BATCH_FIELDS:
        dst = getattr(row, name)
        dst[:n] = getattr(traj, name)
        dst[n:] = 0


def copy_rows(dst: dict[str, np.ndarray], src: dict[str, np.ndarray],
              rows: int) -> None:
    """The leading ``rows`` rows of slab ``src`` into slab ``dst`` of a
    horizon no shorter (the tail zero-filled) and an obs dtype no
    narrower (bytes widen exactly)."""
    horizon = src["obs"].shape[1]
    for name in _BATCH_FIELDS:
        dst[name][:rows, :horizon] = src[name][:rows]
        dst[name][:rows, horizon:] = 0
    dst["last_val"][:rows] = src["last_val"][:rows]


class BatchStaging:
    """Ring of persistent ``[B, T, ...]`` host staging slabs, one ring
    per distinct (batch, horizon, obs dtype) — the zero-alloc steady state
    for epoch assembly. A slab is handed out round-robin and REUSED
    after ``slots`` further acquires of the same shape; the owner must
    guarantee the slab's previous consumer is done by then (the
    algorithm in-flight window provides exactly that, in the order
    :meth:`EpochBuffer.add_episode` states: with window W and
    ``slots = W + 1``, the update that read the slab of batch k has been
    fenced by ``train_on_batch`` k+W, before the first episode of batch
    k+W+1 writes its row)."""

    def __init__(self, slots: int, obs_dim: int, act_dim: int,
                 discrete: bool = True):
        if slots < 1:
            raise ValueError("BatchStaging needs at least one slot")
        self.slots = int(slots)
        self.obs_dim, self.act_dim = int(obs_dim), int(act_dim)
        self.discrete = bool(discrete)
        self._rings: dict[tuple, list[dict[str, np.ndarray]]] = {}
        self._next: dict[tuple, int] = {}

    def acquire(self, batch_size: int, horizon: int,
                obs_dtype=np.float32) -> dict[str, np.ndarray]:
        key = (int(batch_size), int(horizon), np.dtype(obs_dtype))
        ring = self._rings.setdefault(key, [])
        if len(ring) < self.slots:
            ring.append(TrajectoryBatch.zeros(
                key[0], key[1], self.obs_dim, self.act_dim, self.discrete,
                obs_dtype=key[2]))
            return ring[-1]
        i = self._next.get(key, 0)
        self._next[key] = (i + 1) % self.slots
        return ring[i]
