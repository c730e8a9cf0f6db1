"""``benchmark/flops_granite.py`` against counts worked by hand (run by hand:
``python -m pytest benchmark/tests -q``; tier-1 holds the same arithmetic in
``tests/test_rollout_counts.py``) and through the reference file, which is
where the readers find the two functions."""

import importlib.util
import json
import os

from benchmark import flops_granite

HERE = os.path.dirname(os.path.abspath(__file__))


def _cfg():
    with open(os.path.join(HERE, "..", "configs",
                           "granite4h-micro-policy.json")) as f:
        return json.load(f)


def _reference():
    path = os.path.join(HERE, "..", "reference", "granite4h-micro-policy.py")
    spec = importlib.util.spec_from_file_location("granite_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_layers_the_file_names():
    assert flops_granite.layer_counts(_cfg()) == (36, 4)
    assert [i for i, k in enumerate(_cfg()["layer_types"])
            if k == "attention"] == [5, 15, 25, 35]


def test_a_step_by_hand():
    mamba = 2 * 2048 * 8512 + 2 * 4096 * 2048 + 4 * 64 * 64 * 128
    attention = 4 * 2048 * 2048 + 4 * 2048 * 512
    fixed = (36 * mamba + 4 * attention + 40 * 6 * 2048 * 8192
             + 2 * 18 * 2048 + 2 * 2048 * 16 + 2 * 2048 * 2048 + 2 * 2048)
    for t in (1, 256.5, 512):
        assert flops_granite.rollout_flops_per_step(_cfg(), t) == \
            fixed + 4 * 4 * 2048 * t


def test_the_recurrences_bytes_a_scan_step():
    # 64 lanes x 36 layers x [64, 64, 128] float32, read once, written once
    assert flops_granite.ssm_step_bytes(_cfg(), 64) == \
        2 * 64 * 36 * 64 * 64 * 128 * 4 == 9663676416


def test_the_reference_file_hands_both_on():
    ref = _reference()
    assert ref.rollout_flops_per_step is flops_granite.rollout_flops_per_step
    assert ref.ssm_step_bytes is flops_granite.ssm_step_bytes
