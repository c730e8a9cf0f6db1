"""``drivers/loop_replay`` on the CPU, by hand (``python -m pytest
benchmark/tests/test_loop_replay.py -q -p no:cacheprovider``): the bytes a
replay process sends are the bytes a ``VectorAgent``'s host ships for the same
steps and decode to them; the credit rule as a pure function; a whole run at a
toy size against a live zmq ``TrainingServer`` ends with every trajectory
accounted and nothing shed; and the same run with the relay made to lose three
payloads comes out not correct."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import harness
from benchmark.drivers import loop_replay

REPO = harness.REPO
HERE = os.path.dirname(os.path.abspath(__file__))
OBS_SHAPE = [36, 36, 2]
OBS_DIM = 36 * 36 * 2


def test_the_module_level_touches_no_jax():
    code = ("import sys; import benchmark.drivers.loop_replay; "
            "sys.exit(int('jax' in sys.modules))")
    assert subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          timeout=120).returncode == 0


# -- the bytes -------------------------------------------------------------

def _shipped_by_a_vector_host(tmp_path, lanes=2, unroll=4, steps=9):
    """Every unroll a ``VectorActorHost`` (the host of ``VectorAgent``'s
    ``host_mode="vector"``) ships over ``steps`` requests on uint8 frames
    with sparse rewards, as ``[(lane, payload)]``."""
    from relayrl_tpu.algorithms import build_algorithm
    from relayrl_tpu.runtime.vector_actor import VectorActorHost

    algo = build_algorithm(
        "IMPALA", env_dir=str(tmp_path), obs_dim=OBS_DIM, act_dim=5,
        model_kind="cnn_discrete", obs_shape=OBS_SHAPE,
        conv_spec=[[8, 4, 2], [8, 3, 1]], dense=16, scale_obs=True,
        traj_per_epoch=2)
    shipped = []
    host = VectorActorHost(
        algo.bundle(), num_envs=lanes, max_traj_length=unroll,
        on_send=lambda lane, payload: shipped.append((lane, payload)),
        seed=7)
    rng = np.random.default_rng(5)
    rewards = np.zeros(lanes, np.float32)
    for _ in range(steps):
        obs = rng.integers(0, 256, (lanes, OBS_DIM), dtype=np.uint8)
        host.request_for_actions(obs, rewards=rewards)
        rewards = (rng.random(lanes) < 0.5).astype(np.float32) * 2.5
    return shipped


def test_replayed_bytes_are_a_vector_agents_bytes(tmp_path):
    from relayrl_tpu.types.trajectory import (deserialize_actions,
                                              serialize_actions)

    shipped = _shipped_by_a_vector_host(tmp_path)
    assert len(shipped) == 4  # 2 lanes x 2 full unrolls of 4 in 9 requests
    rewarded = 0
    for _lane, payload in shipped:
        steps = deserialize_actions(payload)
        assert len(steps) == 4 and not any(s.done for s in steps)
        rewarded += sum(s.reward_updated for s in steps)
        again = serialize_actions(loop_replay.unroll_records(
            np.stack([s.obs for s in steps]),
            np.stack([s.act for s in steps]),
            np.asarray([s.rew for s in steps], np.float32),
            {k: np.stack([s.data[k] for s in steps])
             for k in steps[0].data}))
        assert again == payload  # byte for byte
    assert rewarded  # the reward hand-back was exercised


def test_a_pooled_unroll_decodes_to_the_steps_it_was_made_from():
    from relayrl_tpu.types.trajectory import deserialize_actions

    from benchmark import traffic_gen

    cfg = {"obs_dim": OBS_DIM, "obs_dtype": "uint8", "act_dim": 5}
    tr = {"pool_unrolls": 3, "traj_len": 4}
    pool = loop_replay.encoded_pool(cfg, tr, 2**31 + 11)
    made = traffic_gen.decoded_pool(
        cfg, {"pool_trajectories": 3, "traj_len": 4}, 2**31 + 11)
    assert len(pool) == 3 and len({len(p) for p in pool}) <= 2
    for payload, d in zip(pool, made):
        steps = deserialize_actions(payload)
        assert np.array_equal(np.stack([s.obs for s in steps]),
                              d.columns["o"])
        assert steps[0].obs.dtype == np.uint8
        assert [int(s.act) for s in steps] == d.columns["a"].tolist()
        assert [s.rew for s in steps] == d.columns["r"].tolist()
        for key in ("logp_a", "v"):
            assert np.array_equal(
                np.stack([s.data[key] for s in steps]), d.aux[key])
    # and through the decoder the server's staging thread uses
    try:
        from relayrl_tpu.types.columnar import NativeDecoder

        decoder = NativeDecoder()
    except Exception:
        pytest.skip("native codec not built: the Python decode is above")
    got = decoder.decode(pool[0], agent_id="a")
    assert got.n_steps == 4
    assert np.array_equal(np.asarray(got.columns["o"]).reshape(4, -1),
                          made[0].columns["o"])


# -- the credit rule ---------------------------------------------------------

@pytest.mark.parametrize("processes", [1, 3, 4, 6])
def test_credit_is_a_share_of_versions_seen_plus_credit(processes):
    allow = loop_replay.credit_allowance
    for version in (0, 1, 2, 7, 40):
        shares = [allow(version, 0, 4, 512, processes, i)
                  for i in range(processes)]
        # never more than the whole, and the shares make the whole exactly
        assert sum(shares) == (version + 4) * 512
        assert max(shares) - min(shares) <= 1


def test_nothing_more_while_the_version_stands_still():
    allow = loop_replay.credit_allowance
    at_credit = allow(3, 3, 4, 512, 4, 0)
    assert at_credit == 4 * 512 // 4
    # a sender that has sent its allowance gets no more until a version
    assert all(allow(3, 3, 4, 512, 4, 0) == at_credit for _ in range(5))
    assert allow(4, 3, 4, 512, 4, 0) == at_credit + 128
    # versions that skip (coalesced publishes) count by their number
    assert allow(9, 3, 4, 512, 4, 0) == at_credit + 6 * 128
    # a version older than the first send (a late keyframe) takes nothing
    assert allow(2, 3, 4, 512, 4, 0) == at_credit


def test_clipped_seconds():
    waits = [(0.0, 1.0), (2.0, 3.5), (9.0, 12.0)]
    assert loop_replay.clipped_s(waits, 0.5, 10.0) == pytest.approx(3.0)
    assert loop_replay.clipped_s(waits, 20.0, 30.0) == 0.0


# -- a whole run at a toy size, against a live zmq TrainingServer -----------

def _rehearse(seed: int, env=None):
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", "nature-cnn.loop-saturated", "--seed", str(seed),
         "--seconds", "2", "--trace", "0", "--rehearsal",
         os.path.join(HERE, "rehearsal-loop-saturated.json")],
        cwd=REPO, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})})
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    assert lines, out.stdout[-2000:] + out.stderr[-2000:]
    return json.loads(lines[-1]), out


def test_a_toy_run_accounts_for_every_trajectory_and_sheds_nothing():
    line, out = _rehearse(2**31 + 5)
    assert line["correct"], line["checks"]
    for check in ("saturated", "nothing_dropped",
                  "every_trajectory_accounted", "lag_complete",
                  "actors_hold_learner_params", "reference"):
        assert line["checks"][check] is True
    compared = [ln for ln in out.stderr.splitlines()
                if ln.startswith("compared ")]
    assert "compared sent_less_counted 0 limit 0" in compared
    assert "compared sheds 0 limit 0" in compared


def test_a_relay_that_loses_payloads_is_not_correct():
    line, out = _rehearse(2**31 + 6, env={
        "BENCH_PLANT": "lose_trajectories",
        "PYTHONPATH": os.pathsep.join(filter(None, [
            os.path.join(HERE, "plant"), os.environ.get("PYTHONPATH")]))})
    assert line["correct"] is False
    assert line["checks"]["every_trajectory_accounted"] is False
    assert "compared sent_less_counted 3 limit 0" in out.stderr
