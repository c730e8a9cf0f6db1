"""``benchmark/flops_keye.py`` against counts worked by hand (run by hand:
``python -m pytest benchmark/tests -q``; not tier-1, where
``tests/test_keye_vl2_reference.py`` holds the same arithmetic)."""

import importlib.util
import json
import os

import pytest

from benchmark import flops_keye

HERE = os.path.dirname(os.path.abspath(__file__))
T = 16_384


def _cfg():
    with open(os.path.join(HERE, "..", "configs",
                           "keye-vl2-policy.json")) as f:
        return json.load(f)


def _reference():
    path = os.path.join(HERE, "..", "reference", "keye-vl2-policy.py")
    spec = importlib.util.spec_from_file_location("keye_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_pairs_of_one_episode():
    assert flops_keye.indexer_widths(_cfg()) == (16, 64, 2048)
    assert flops_keye.causal_pairs(T) == T * (T + 1) // 2 == 134_225_920
    # the first 2,048 queries keep every causal key, the 14,336 after them
    # 2,048 each
    assert flops_keye.kept_pairs(T, 2048) == (
        2048 * 2049 // 2 + 14_336 * 2048) == 31_458_304
    assert flops_keye.kept_pairs(1000, 2048) == flops_keye.causal_pairs(1000)
    # 87.5% of the queries choose among more keys than they keep
    assert 14_336 / T == 0.875


def test_a_layer_a_token():
    cfg = _cfg()
    d = 2048
    # q and o 2048 x 4096, k and v 2048 x 512
    assert flops_keye.attention_proj_fwd_flops(cfg) == 2 * (
        2 * d * 4096 + 2 * d * 512) == 37_748_736
    # 16 heads of 64, one key head of 64, 16 weights
    assert flops_keye.index_proj_fwd_flops(cfg) == 2 * d * 1104 == 4_521_984
    assert flops_keye.index_pair_flops(cfg) == 2_048
    assert flops_keye.attention_pair_flops(cfg) == 16_384
    # router over 128, one held token-slot a token at 16 of 128, top-8
    assert flops_keye.experts_fwd_flops(cfg) == (
        2 * d * 128 + 3 * 2 * d * 768) == 524_288 + 9_437_184
    # ISSUE 47's forward TFLOP a layer and episode
    assert round(2_048 * 134_225_920 / 1e12, 3) == 0.275
    assert round(16_384 * 31_458_304 / 1e12, 3) == 0.515
    assert round(16_384 * 134_225_920 / 1e12, 2) == 2.20    # dense causal
    assert round((37_748_736 + 4_521_984) * T / 1e12, 3) == 0.693


def test_the_whole_forward_and_the_references_count():
    cfg = _cfg()
    layer = (37_748_736 + 4_521_984 + 2_048 * 134_225_920 / T
             + 16_384 * 31_458_304 / T + 9_961_472)
    want = 4 * layer + 2 * 18 * 2048 + 2 * 2048 * 17
    assert flops_keye.keye_fwd_flops_per_token(cfg, T) == want
    assert _reference().train_flops_per_sample(cfg, T) == 3 * want


def test_the_two_rooflines_counts():
    cfg, ref = _cfg(), _reference()
    ops, nbytes = ref.sparse_attn_train_ops_bytes(cfg, 1, T)
    assert ops == 4 * 3 * 16_384 * 31_458_304
    # q, o, do, dq and q again at 4096; k, v twice and dk, dv at 512
    assert nbytes == 4 * (5 * 4096 + 6 * 512) * T * 2
    # from the share the run counted
    ops_run, _ = ref.sparse_attn_train_ops_bytes(cfg, 1, T, 0.25)
    assert ops_run == pytest.approx(4 * 3 * 16_384 * 0.25 * 134_225_920)
    ops, nbytes = ref.index_train_ops_bytes(cfg, 1, T)
    assert ops == 4 * (3 * 4_521_984 * T
                       + 2_048 * (134_225_920 + 2 * 31_458_304))
    assert nbytes == 4 * ((2048 + 5 * 1104) * T * 2 + 4 * 31_458_304)
    # least times on a v5e: compute-bound both
    assert ops / 197e12 > nbytes / 819e9


def test_the_reference_counts_no_dense_flash_work():
    """The cell stays off ``flash_gqa_roofline``'s list: a masked-dense
    kernel held to a dense count would read a share of the wrong work."""
    assert not hasattr(_reference(), "flash_gqa_train_ops_bytes")
    assert hasattr(_reference(), "held_grouped_matmul_train_ops_bytes")
