"""The rollout cells' arithmetic, as pure functions: the pure cases of
``benchmark/tests/test_rollout_driver.py`` (``expected_emitted_steps``,
``account``, ``rollout_flops_per_step``: run by hand there) held in tier-1
too, beside ``granite4h-micro-policy``'s own counts
(``benchmark/flops_granite.py``: ``rollout_flops_per_step``,
``ssm_step_bytes``) against hand arithmetic. No jax, no run.
"""

import json
import os

import numpy as np
import pytest

from benchmark import flops_granite, flops_rollout
from benchmark.drivers import rollout

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(REPO, "benchmark", "configs",
                           name + ".json")) as f:
        return json.load(f)


def test_gpt2m_rollout_flops_per_step_by_hand():
    # one new row, d = 1024, n_inner = 4096, per layer: QKVO 8 d^2 =
    # 8,388,608; MLP 4 d n_inner = 16,777,216; scores and values over t keys
    # 4 d t; embedding 36,864; policy head 32,768; value head 2,099,200
    first = 24 * (8388608 + 16777216 + 4096) + 2168832
    last = 24 * (8388608 + 16777216 + 4194304) + 2168832
    cfg = _config("gpt2m-policy")
    assert flops_rollout.rollout_flops_per_step(cfg, 1) == first == 606246912
    assert flops_rollout.rollout_flops_per_step(cfg, 1024) == last
    assert flops_rollout.rollout_flops_per_step(cfg, 512.5) == \
        (first + last) / 2
    for t in (0, 1025):
        with pytest.raises(ValueError):
            flops_rollout.rollout_flops_per_step(cfg, t)


def test_granite_rollout_flops_per_step_by_hand():
    cfg = _config("granite4h-micro-policy")
    assert flops_granite.layer_counts(cfg) == (36, 4)
    # a mamba layer: in 2 x 2048 x (4096 + 4096 + 256 + 64) = 34,865,152;
    # out 2 x 4096 x 2048 = 16,777,216; x (x) B and C . h over [64, 64, 128]:
    # 4 x 524,288 = 2,097,152
    mamba = 34865152 + 16777216 + 2097152
    # an attention layer: q and o 2 x 2 x 2048^2 = 16,777,216; k and v
    # 2 x 2 x 2048 x 512 = 4,194,304; the row over t keys 4 x 2048 x t
    attention = 16777216 + 4194304
    mlp = 6 * 2048 * 8192                       # 100,663,296 a layer
    heads = 2 * 18 * 2048 + 2 * 2048 * 16 + 2 * 2048 * 2048 + 2 * 2048
    fixed = 36 * mamba + 4 * attention + 40 * mlp + heads
    assert fixed == 6053572608
    assert flops_granite.rollout_flops_per_step(cfg, 1) == fixed + 4 * 8192
    assert flops_granite.rollout_flops_per_step(cfg, 512) == \
        fixed + 4 * 8192 * 512
    # linear in t; 64 lanes of it are the issue's 0.38 TFLOP a scan step
    mid = flops_granite.rollout_flops_per_step(cfg, 256.5)
    assert mid == fixed + 4 * 8192 * 256.5
    assert 0.38e12 < 64 * mid < 0.39e12
    for t in (0, 513):
        with pytest.raises(ValueError):
            flops_granite.rollout_flops_per_step(cfg, t)


def test_granite_ssm_step_bytes_by_hand():
    cfg = _config("granite4h-micro-policy")
    state = 64 * 64 * 128 * 4                   # 2,097,152 B a lane and layer
    assert flops_granite.ssm_step_bytes(cfg, 1) == 2 * 36 * state
    assert flops_granite.ssm_step_bytes(cfg, 64) == 9663676416.0
    # the parameters the cell's sizing stands on, from the same file
    d, ff, inner = 2048, 8192, 4096
    mamba = (d * (2 * inner + 2 * 128 + 64) + 5 * (inner + 256)
             + 3 * 64 + inner + inner * d)
    mlp = 3 * d * ff
    attention = 2 * d * d + 2 * d * 512
    assert (mamba, mlp, attention) == (25847232, 50331648, 10485760)
    assert 36 * (mamba + mlp + 2 * d) + 4 * (attention + mlp + 2 * d) == \
        2985873152


@pytest.mark.parametrize("dispatched,want", [
    (0, 0), (1, 0), (16, 0), (17, 16), (32, 16), (33, 32), (100, 96),
    (1023, 1008), (1024, 1024), (1025, 1024), (1041, 1040), (2048, 2048)])
def test_steps_that_have_left_the_host(dispatched, want):
    assert rollout.expected_emitted_steps(dispatched, 1024, 16) == want
    if 0 < dispatched < 1024:
        assert want == 16 * ((dispatched - 1) // 16)


@pytest.mark.parametrize("dispatched,want", [
    (16, 0), (17, 16), (512, 512), (513, 512), (529, 528), (1024, 1024)])
def test_steps_that_have_left_the_host_at_the_new_cells_horizon(dispatched,
                                                                want):
    """``granite4h-micro-policy.rollout``: episodes of 512, frames of 16 =
    one dispatch, so one cool dispatch pushes the window's last rows out."""
    assert rollout.expected_emitted_steps(dispatched, 512, 16) == want


class _Frame:
    def __init__(self, first, n, horizon, act_dim=16, logp=-1.0):
        t = (first + np.arange(n)) % horizon
        obs = np.zeros((n, 18), np.float32)
        obs[:, -1] = t / horizon
        self.n_steps = n
        self.columns = {"o": obs, "a": (t % act_dim).astype(np.int32)}
        self.aux = {"logp_a": np.full(n, logp, np.float32),
                    "v": np.zeros(n, np.float32)}


def _lanes(firsts, horizon=32, chunk=4):
    return [[_Frame(f, chunk, horizon) for f in lane] for lane in firsts]


def test_account_finds_every_step_across_an_episode_end():
    frames = _lanes([range(0, 40, 4), range(0, 40, 4)])
    seen = rollout.account(frames, 42, (8, 36), 32, 4, 16)
    assert seen["steps_wanted"] == 40 and seen["frames"] == 20
    assert not any(seen[k] for k in (
        "lanes_short", "lanes_out_of_order", "frames_off_size",
        "window_steps_missing", "nonfinite_steps", "actions_out_of_range",
        "logp_positive"))


def test_account_counts_what_a_missing_frame_takes():
    frames = _lanes([range(0, 40, 4), [0, 4, 8, 16, 20, 24, 28, 32, 36]])
    seen = rollout.account(frames, 42, (8, 36), 32, 4, 16)
    assert seen["window_steps_missing"] == 4       # steps 12..15 of lane 1
    assert seen["lanes_short"] == 1 and seen["lanes_out_of_order"] == 1
    # a frame sent twice is out of order and hides nothing
    twice = _lanes([[0, 4, 4, 8]])
    seen = rollout.account(twice, 13, (0, 12), 32, 4, 16)
    assert seen["lanes_out_of_order"] == 1 and seen["lanes_short"] == 1


def test_account_counts_outputs_out_of_range():
    frames = _lanes([range(0, 16, 4)])
    frames[0][1].aux["logp_a"][2] = 0.25
    frames[0][2].aux["v"][0] = np.nan
    frames[0][2].columns["a"][1] = 16
    frames[0][0].aux["v"][0] = np.inf              # before the window
    seen = rollout.account(frames, 17, (4, 16), 32, 4, 16)
    assert (seen["logp_positive"], seen["nonfinite_steps"],
            seen["actions_out_of_range"]) == (1, 1, 1)


def test_the_new_cell_and_its_lists():
    """``BENCHMARK.json``: the cell, its configuration with nothing reduced,
    the traffic ISSUE 68 gives letter for letter, and the metrics that list
    it."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = "granite4h-micro-policy.rollout"
    entry = {w["name"]: w for w in bench["workloads"]}[cell]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "granite4h-micro-policy", "anakin-recall512-lanes64", 1)
    config = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    assert config["reduced"] == [] == _config(entry["config"])["reduced"]
    with open(os.path.join(REPO, "benchmark", "traffic",
                           entry["traffic"] + ".json")) as f:
        tr = json.load(f)
    want = {"driver": "rollout", "env": "Recall-v0",
            "env_kwargs": {"horizon": 512, "n_cues": 16, "noise": 0.0},
            "lanes": 64, "window_size": 512, "unroll_length": 16,
            "max_traj_length": 16, "columnar_wire": True,
            "async_emit": False, "emit_coalesce_frames": 1,
            "warm_dispatches": 2, "cool_dispatches": 1,
            "min_dispatches": 10, "trace_seconds": 4, "reference_lanes": 2}
    assert {k: tr[k] for k in want} == want
    lists = {m["name"]: m.get("workloads", [])
             for m in bench["end_to_end"] + bench["per_layer"]}
    for name in ("rollout_steps_per_s", "rollout_dispatch_ms", "emit_ms",
                 "device_idle_pct.rollout", "mfu_pct.rollout",
                 "peak_hbm_gb.rollout", "d2h_mb_per_dispatch", "ssm_step_ms",
                 "ssm_step_roofline", "param_gb.rollout",
                 "cache_gb.rollout"):
        assert cell in lists[name], name
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "layer_metrics", name + ".py")) or \
            name == "rollout_steps_per_s"
    assert "train_samples_per_s" not in [
        n for n, cells in lists.items() if cell in cells]
    # the two gauge readers install a live registry when they are imported
    # (benchmark/actor_gauges.py), and a run imports its own cell's readers
    # alone: the accepted cell's window stays under the no-op registry
    assert lists["param_gb.rollout"] == lists["cache_gb.rollout"] == [cell]
