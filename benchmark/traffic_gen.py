"""The one general traffic generator: a traffic mix is a data file of
parameters (``benchmark/traffic/<mix>.json``) that this module reads.

Everything is drawn from ``--seed`` with numpy's ``default_rng``: the same
seed gives the same inputs. Every seed gives the same SIZES (each mix fixes
its trajectory length and batch), so the seed changes values, never work.
Pools are the smallest the mix needs, made by one vectorised call each: a
few hundred distinct unrolls that the driver cycles through, not gigabytes
of host random numbers per run.
"""

from __future__ import annotations

import numpy as np


def _obs_block(rng, n: int, steps: int, config: dict) -> np.ndarray:
    """``[n, steps, obs_dim]`` observations in the configuration's wire
    dtype: uint8 frames stay bytes (``normalize_obs``'s rule), everything
    else is float32."""
    obs_dim = int(config["obs_dim"])
    if config.get("obs_dtype", "float32") == "uint8":
        raw = rng.bytes(n * steps * obs_dim)
        return np.frombuffer(raw, np.uint8).reshape(n, steps, obs_dim)
    return rng.standard_normal((n, steps, obs_dim), dtype=np.float32)


def decoded_pool(config: dict, traffic: dict, seed: int) -> list:
    """``pool_trajectories`` decoded trajectories of ``traj_len`` valid
    steps each, as the server's staging thread hands them to the learner
    (columnar ``DecodedTrajectory``: what the native decoder emits)."""
    from relayrl_tpu.types.columnar import DecodedTrajectory

    rng = np.random.default_rng(seed)
    n, steps = int(traffic["pool_trajectories"]), int(traffic["traj_len"])
    act_dim = int(config["act_dim"])
    obs = _obs_block(rng, n, steps, config)
    act = rng.integers(0, act_dim, (n, steps), dtype=np.int64)
    # sparse rewards and small stored values, as Atari and Recall give
    rew = (rng.random((n, steps)) < 0.02).astype(np.float32)
    val = 0.1 * rng.standard_normal((n, steps), dtype=np.float32)
    logp = np.full((n, steps), -np.log(act_dim), np.float32)
    flags = np.zeros((steps,), np.bool_)
    return [DecodedTrajectory(
        agent_id=f"pool-{i}", n_steps=steps, n_records=steps,
        marker_truncated=False,
        columns={"o": obs[i], "a": act[i], "r": rew[i], "t": flags,
                 "u": flags, "x": flags},
        aux={"v": val[i], "logp_a": logp[i]}) for i in range(n)]


def obs_sample(config: dict, n_seq: int, steps: int, seed: int) -> np.ndarray:
    """A seeded sample for the comparison with the plain reference."""
    rng = np.random.default_rng(seed ^ 0x5EED)
    return _obs_block(rng, n_seq, steps, config)


class SyntheticEnv:
    """``lanes`` never-ending environments whose observations cycle through
    a seeded pool of ``env_pool_frames`` frames of the configuration's
    shape and dtype. Stepping costs an index, so an actor process is paced
    by its policy forward and the wire, not by an emulator (a real Atari
    emulator adds its own cost per frame: PERF.md says so where the loop
    cell's numbers are read)."""

    def __init__(self, config: dict, traffic: dict, lanes: int, seed: int):
        rng = np.random.default_rng(seed)
        self.lanes = int(lanes)
        self.act_dim = int(config["act_dim"])
        k = int(traffic["env_pool_frames"])
        self.pool = _obs_block(rng, 1, k, config)[0]
        self.offset = (np.arange(self.lanes) * 7) % k
        self.t = 0

    def observe(self) -> np.ndarray:
        return self.pool[(self.offset + self.t) % len(self.pool)]

    def step(self, actions: np.ndarray):
        rewards = (np.asarray(actions) == self.t % self.act_dim).astype(
            np.float32)
        self.t += 1
        return self.observe(), rewards
