"""``benchmark/flops_ouro.py`` against counts worked by hand (run by hand:
``python -m pytest benchmark/tests -q``; not tier-1, where
``tests/test_ouro_reference.py`` holds the same arithmetic)."""

import importlib.util
import json
import os

from benchmark import flops_ouro

HERE = os.path.dirname(os.path.abspath(__file__))
T = 8_192


def _cfg():
    with open(os.path.join(HERE, "..", "configs", "ouro-policy.json")) as f:
        return json.load(f)


def _reference():
    path = os.path.join(HERE, "..", "reference", "ouro-policy.py")
    spec = importlib.util.spec_from_file_location("ouro_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_a_layer_a_token():
    cfg = _cfg()
    # q, k, v and o 2048 x 2048 each, the FFN's three 2048 x 5632
    matmul = 2 * (4 * 2048 * 2048 + 3 * 2048 * 5632)
    assert matmul == 2 * (51_388_416 - 4 * 2048) == 102_760_448
    # QK^T and PV over the (T + 1) / 2 keys a token sees on average
    scores = 4 * 16 * 128 * (T * (T + 1) // 2) / T
    assert flops_ouro.layer_fwd_flops_per_token(cfg, T) == matmul + scores
    # ISSUE 50's forward TFLOP a layer application over 16,384 tokens
    assert round(matmul * 16_384 / 1e12, 2) == 1.68
    assert round(scores * 16_384 / 1e12, 2) == 0.55


def test_four_passes_and_no_recompute():
    cfg = _cfg()
    assert flops_ouro.applications(cfg) == 4 * 8 == 32
    one = flops_ouro.layer_fwd_flops_per_token(cfg, T)
    ends = 2 * 18 * 2048 + 2 * 2048 * 17
    assert flops_ouro.ouro_fwd_flops_per_token(cfg, T) == 32 * one + ends
    once = dict(cfg, total_ut_steps=1)
    assert flops_ouro.ouro_fwd_flops_per_token(cfg, T) - ends == 4 * (
        flops_ouro.ouro_fwd_flops_per_token(once, T) - ends)
    # forward + backward = 3 x forward; the checkpoint's second forward (a
    # fourth) is the program's and counts for nothing
    per_update = 16_384 * _reference().train_flops_per_sample(cfg, T)
    assert per_update == 16_384 * 3 * (32 * one + ends)
    assert round(per_update / 1e12, 1) == 214.4


def test_the_flash_kernels_of_an_update():
    cfg = _cfg()
    ops, nbytes = _reference().flash_gqa_train_ops_bytes(cfg, 2, T)
    # 2 matmuls forward and 4 backward of 2 x 128 a score, 16 heads, 2
    # sequences, 32 applications
    assert ops == 32 * 6 * 2 * 2 * 16 * (T * (T + 1) // 2) * 128
    # q, k, v, o forward; q, k, v, o, do, dq, dk, dv backward: 12 arrays of
    # 16 heads (k/v at the query heads' count) in bfloat16
    assert nbytes == 32 * 12 * 16 * (2 * T * 128 * 2)
    assert ops / 197e12 > nbytes / 819e9        # compute-bound on a v5e
