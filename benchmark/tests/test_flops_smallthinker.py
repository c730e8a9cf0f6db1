"""``benchmark/flops_smallthinker.py`` against counts worked by hand (run by
hand: ``python -m pytest benchmark/tests -q``; not tier-1)."""

import importlib.util
import json
import os

from benchmark import flops_smallthinker

HERE = os.path.dirname(os.path.abspath(__file__))


def _cfg():
    with open(os.path.join(HERE, "..", "configs",
                           "smallthinker-policy.json")) as f:
        return json.load(f)


def _reference():
    path = os.path.join(HERE, "..", "reference", "smallthinker-policy.py")
    spec = importlib.util.spec_from_file_location("smallthinker_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_scores_a_mask_needs():
    t, w = 16384, 4096
    assert flops_smallthinker.band_scores(t, None) == 134_225_920
    # the first 4096 queries see 1 .. 4096 keys, the other 12,288 see 4096
    assert flops_smallthinker.band_scores(t, w) == (
        4096 * 4097 // 2 + 12288 * 4096) == 58_722_304
    assert flops_smallthinker.band_scores(t, t) == 134_225_920
    assert flops_smallthinker.band_scores(8, 1) == 8
    # 21.9% of T x T, what ISSUE 34 says the band needs
    assert round(100 * 58_722_304 / t ** 2, 1) == 21.9
    assert flops_smallthinker.layer_windows(_cfg()) == [None, 4096, 4096,
                                                        4096]


def test_a_token_forward_layer_by_layer():
    d, t = 2560, 16384
    # q and o 2560 x 3584, k and v 2560 x 512: the heads' own width
    proj = 2 * (9_175_040 + 1_310_720) * 2
    assert proj == 41_943_040
    glob = flops_smallthinker.attention_fwd_flops(d, 28, 4, 128, t, None)
    band = flops_smallthinker.attention_fwd_flops(d, 28, 4, 128, t, 4096)
    assert glob == proj + 14_336 * 134_225_920 / t == 41_943_040 + 117_447_680
    assert band == proj + 14_336 * 58_722_304 / t == 41_943_040 + 51_382_016
    assert flops_smallthinker.reglu_fwd_flops(d, 768) == 11_796_480
    total = flops_smallthinker.smallthinker_fwd_flops_per_token(_cfg(), t)
    by_hand = (glob + 3 * band + 4 * (1.5 * 11_796_480 + 2 * 2560 * 64)
               + 2 * 18 * 2560 + 2 * 2560 * 17)
    assert total == by_hand == 511_634_688


def test_flash_counts_each_layer_by_its_own_mask():
    ref = _reference()
    cfg = _cfg()
    ops, nbytes = ref.flash_gqa_train_ops_bytes(cfg, 1, 16384)
    per_score = 6 * 2 * 128
    assert ops == per_score * 28 * (134_225_920 + 3 * 58_722_304)
    one = 16384 * 128 * 2
    assert nbytes == 4 * 3 * (2 * 28 + 2 * 4) * one
    win_ops, win_bytes = ref.flash_window_train_ops_bytes(cfg, 1, 16384)
    assert win_ops == per_score * 28 * 3 * 58_722_304
    assert win_bytes == 3 * 3 * 64 * one
    # compute-bound on a v5e: 38.5 ms least for the three windowed layers
    assert win_ops / 197e12 > win_bytes / 819e9
    assert round(1e3 * win_ops / 197e12, 1) == 38.5
    # a window of the whole sequence is the causal count
    wide = {**cfg, "sliding_window_size": 16384}
    assert ref.flash_gqa_train_ops_bytes(wide, 1, 16384)[0] == (
        per_score * 28 * 4 * 134_225_920)


def test_held_grouped_matmuls_count_the_held_rows_only():
    ref = _reference()
    # 98,304 held slots an update (24,576 a layer at even routing)
    ops, nbytes = ref.held_grouped_matmul_train_ops_bytes(_cfg(), 98304)
    assert ops == 9 * 2 * 98304 * 2560 * 768 == 3_478_923_509_760
    assert nbytes == 9 * 2 * (98304 * (2560 + 768) + 4 * 16 * 2560 * 768)
    assert ops / 197e12 > nbytes / 819e9


def test_train_flops_are_three_forwards():
    ref = _reference()
    assert ref.train_flops_per_sample(_cfg(), 16384) == 3 * 511_634_688
