"""``drivers/rollout`` on the CPU, by hand (``python -m pytest
benchmark/tests/test_rollout_driver.py -q -p no:cacheprovider``, a minute):
the accounting as pure functions; whole runs of ``gpt2m-policy.rollout`` at
``rehearsal-rollout.json``'s toy size through ``harness.start_run`` ->
``drive`` -> ``finish_run`` with the look for a chip skipped — one sound, one
with a frame withheld from the sink, one with an emitted ``logp_a`` shifted
where it is produced, one with a step's action altered there, one whose fused
window hands its state back unchanged for a dispatch; the rate
against steps over window by hand; the float8 control refused and the
exact reference passed; ``rollout_flops_per_step`` against hand arithmetic.
"""

import json
import os

import numpy as np
import pytest

from benchmark import flops_rollout
from benchmark.drivers import rollout

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "gpt2m-policy.rollout"
REHEARSAL = os.path.join(HERE, "rehearsal-rollout.json")


# -- the arithmetic -----------------------------------------------------------

def _config(name):
    with open(os.path.join(HERE, "..", "configs", name + ".json")) as f:
        return json.load(f)


def test_rollout_flops_per_step_by_hand():
    # one new row, d = 1024, n_inner = 4096, per layer:
    #   QKVO 8 d^2 = 8,388,608; MLP 4 d n_inner = 16,777,216;
    #   scores and values over t keys 4 d t: t = 1 -> 4,096;
    #   t = 1,024 -> 4,194,304
    # embedding 2*18*1024 = 36,864; policy head 2*1024*16 = 32,768;
    # value head 2*1024*1024 + 2*1024 = 2,099,200          => 2,168,832
    first = 24 * (8388608 + 16777216 + 4096) + 2168832
    last = 24 * (8388608 + 16777216 + 4194304) + 2168832
    assert (first, last) == (606246912, 706811904)
    cfg = _config("gpt2m-policy")
    assert flops_rollout.rollout_flops_per_step(cfg, 1) == first
    assert flops_rollout.rollout_flops_per_step(cfg, 1024) == last
    # linear in t: the mean over positions is the count at their mean
    assert flops_rollout.rollout_flops_per_step(cfg, 512.5) == \
        (first + last) / 2
    for t in (0, 1025):
        with pytest.raises(ValueError):
            flops_rollout.rollout_flops_per_step(cfg, t)


@pytest.mark.parametrize("dispatched,want", [
    (0, 0), (1, 0), (16, 0), (17, 16), (32, 16), (33, 32), (100, 96),
    (1023, 1008), (1024, 1024), (1025, 1024), (1041, 1040), (2048, 2048)])
def test_steps_that_have_left_the_host(dispatched, want):
    """ISSUE 66's floor((steps - 1) / 16) frames of 16 while no episode has
    ended; an episode's end flushes its last chunk at once."""
    assert rollout.expected_emitted_steps(dispatched, 1024, 16) == want
    if 0 < dispatched < 1024:
        assert want == 16 * ((dispatched - 1) // 16)


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


# -- whole toy runs -----------------------------------------------------------

def _controls():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "controls_rollout", os.path.join(HERE, "controls_rollout.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _toy_run(seed, seconds=0.6):
    return _controls().run_cell(seed, seconds, REHEARSAL)


def test_a_toy_run_is_correct_and_its_rate_is_steps_over_window(capfd):
    run, rolled, line = _toy_run(2**31 + 66)
    assert line["correct"], line["checks"]
    assert set(line["checks"]) == {
        "enough_dispatches", "no_emit_error", "every_step_accounted",
        "outputs_in_range", "reference", "compiles_in_window"}
    tr = run.traffic
    dispatches = len(run.notes["rollout"]["dispatch_ms"])
    steps = int(tr["lanes"]) * int(tr["unroll_length"]) * dispatches
    assert dispatches == run.updates >= int(tr["min_dispatches"])
    assert line["attempted"] == steps and line["failed"] == 0
    assert run.window_s >= run.seconds
    assert run.e2e["rollout_steps_per_s"] == steps / run.window_s
    assert line["metrics"]["rollout_steps_per_s"]["value"] == \
        steps / run.window_s
    # the window's steps of a lane, and every one of the reference lanes'
    # compared: the toy's window runs over many episode ends
    p0, p1 = rolled["window"]
    assert p1 - p0 == int(tr["unroll_length"]) * dispatches
    assert p1 - p0 > int(tr["env_kwargs"]["horizon"])
    assert run.notes["reference"]["steps_compared"] == \
        int(tr["reference_lanes"]) * (p1 - p0)
    # each number compared beside its limit: last in the line, and on
    # standard error
    assert list(line)[-1] == "compared"
    err = capfd.readouterr().err
    assert "compared window_steps_missing 0 limit 0" in err
    assert "compared rel_dlogp " in err


def test_a_frame_withheld_from_the_sink_is_not_correct(monkeypatch):
    keep = rollout.Sink.__call__
    calls = {"n": 0}

    def withhold(self, lane, payload):
        calls["n"] += 1
        if calls["n"] != 40:
            keep(self, lane, payload)

    monkeypatch.setattr(rollout.Sink, "__call__", withhold)
    run, _rolled, line = _toy_run(2**31 + 67)
    assert line["correct"] is False
    assert line["checks"]["every_step_accounted"] is False
    assert line["compared"]["window_steps_missing"] == [4, 0]
    assert line["failed"] == 4


def _alter_one_step(monkeypatch, seed, alter):
    """``alter(window, lane)`` on the host's window of one dispatch inside
    the measured window, where the host hands it to its emit, for a lane
    the reference reads."""
    from relayrl_tpu.runtime import anakin

    with open(REHEARSAL) as f:
        tr = json.load(f)[CELL]["traffic"]
    lane = rollout.reference_lanes(seed, tr["lanes"],
                                   tr["reference_lanes"])[0]
    emit = anakin.AnakinActorHost._emit_columnar
    calls = {"n": 0}

    def altered(self, w):
        calls["n"] += 1
        if calls["n"] == 12:  # warm 2, then the window: its tenth dispatch
            w = dict(w, aux={k: np.array(v) for k, v in w["aux"].items()},
                     act=np.array(w["act"]))
            alter(w, lane)
        return emit(self, w)

    monkeypatch.setattr(anakin.AnakinActorHost, "_emit_columnar", altered)
    return _toy_run(seed)


def test_a_shifted_log_probability_is_not_correct(monkeypatch):
    def shift(w, lane):
        w["aux"]["logp_a"][lane, 1] -= 0.05

    run, _rolled, line = _alter_one_step(monkeypatch, 2**31 + 68, shift)
    assert line["correct"] is False
    assert line["checks"]["reference"] is False
    assert line["checks"]["every_step_accounted"] is True
    ref = run.notes["reference"]
    assert abs(ref["max_abs_dlogp"] - 0.05) < 1e-4


def test_an_altered_action_is_not_correct(monkeypatch):
    def swap(w, lane):
        w["act"][lane, 1] = (w["act"][lane, 1] + 1) % 16

    _run, _rolled, line = _alter_one_step(monkeypatch, 2**31 + 69, swap)
    assert line["correct"] is False and line["checks"]["reference"] is False


def test_a_dispatch_that_hands_back_its_carry_unchanged_is_not_correct(
        monkeypatch):
    """The fused window's state left as it was for one dispatch: the lanes
    walk the same steps again, and the phase the environment writes into
    every observation says so."""
    from relayrl_tpu.runtime import anakin

    make = anakin.make_fused_rollout

    def stuck(*args, **kwargs):
        produce = make(*args, **kwargs)
        calls = {"n": 0}

        def once_stuck(params, explore, carry):
            calls["n"] += 1
            new, window = produce(params, explore, carry)
            return (carry if calls["n"] == 12 else new), window

        return once_stuck

    monkeypatch.setattr(anakin, "make_fused_rollout", stuck)
    _run, _rolled, line = _toy_run(2**31 + 71)
    assert line["correct"] is False
    assert line["checks"]["every_step_accounted"] is False
    assert line["compared"]["lanes_out_of_order"] == [4, 0]


def test_the_float8_control_is_refused_and_the_exact_reference_passes():
    controls = _controls()
    run, rolled, line = _toy_run(2**31 + 70, seconds=0.3)
    assert line["correct"]
    exact = controls.control_readings(run, rolled, "float32")
    assert not exact["refused"] and exact["rel_dlogp"] < 1e-5
    for operands in controls.HELD:
        got = controls.control_readings(run, rolled, operands)
        assert got["refused"], (operands, got)
        assert got["steps_compared"] == exact["steps_compared"]
