"""``benchmark/flops_lfm2.py`` against counts worked by hand (run by hand:
``python -m pytest benchmark/tests -q``; not tier-1)."""

import json
import os

from benchmark import flops_lfm2

HERE = os.path.dirname(os.path.abspath(__file__))


def _cfg():
    with open(os.path.join(HERE, "..", "configs", "lfm2-policy.json")) as f:
        return json.load(f)


def test_a_token_forward_layer_by_layer():
    d, t = 2048, 8192
    # attention: q and o 2048 x 2048, k and v 2048 x 512, scores over T/2
    assert flops_lfm2.attention_fwd_flops(d, 32, 8, 64, t) == (
        2 * (4_194_304 + 1_048_576) * 2 + 2 * 2048 * 8192) == 54_525_952
    # short convolution: 2048 -> 6144 and 2048 -> 2048
    assert flops_lfm2.short_conv_fwd_flops(d) == 2 * (
        12_582_912 + 4_194_304) == 33_554_432
    assert flops_lfm2.swiglu_fwd_flops(d, 11776) == 144_703_488
    assert flops_lfm2.swiglu_fwd_flops(d, 1536) == 18_874_368
    assert flops_lfm2.held_slots_per_token(4, 8, 64) == 0.5
    total = flops_lfm2.lfm2_fwd_flops_per_token(_cfg(), t)
    by_hand = (4 * 33_554_432 + 54_525_952 + 144_703_488
               + 4 * (0.5 * 18_874_368 + 2 * 2048 * 64)
               + 2 * 18 * 2048 + 2 * 2048 * 17)
    assert total == by_hand == 372_387_840


def test_held_grouped_matmuls_count_the_held_rows_only():
    # 32,768 held slots an update (8,192 a layer at even routing)
    ops, nbytes = flops_lfm2.held_grouped_matmul_train_ops_bytes(
        32768, 4, 8, 2048, 1536)
    assert ops == 9 * 2 * 32768 * 2048 * 1536 == 1_855_425_871_872
    assert nbytes == 9 * 2 * (32768 * (2048 + 1536) + 4 * 8 * 2048 * 1536)
    # compute-bound on a v5e, and an eighth of what every slot would cost
    assert ops / 197e12 > nbytes / 819e9
    all_slots, _ = flops_lfm2.held_grouped_matmul_train_ops_bytes(
        4 * 65536, 4, 64, 2048, 1536)
    assert all_slots == 8 * ops


def test_flash_gqa_counts_kv_at_their_own_heads():
    ops, nbytes = flops_lfm2.flash_gqa_train_ops_bytes(2, 32, 8, 8192, 64)
    # the causal minimum: 8192 x 8193 / 2 scores a q head, 50.006% of T x T
    scores = 2 * 32 * 33_558_528
    assert ops == 6 * 2 * scores * 64 == 1_649_468_768_256
    one = 2 * 8192 * 64 * 2
    assert nbytes == 3 * (2 * 32 + 2 * 8) * one == 3 * 80 * one
    # plain multi-head attention would move 3 x 128 arrays' worth
    _, mha = flops_lfm2.flash_gqa_train_ops_bytes(2, 32, 32, 8192, 64)
    assert mha == 3 * 128 * one
    assert ops / 197e12 > nbytes / 819e9


def test_short_conv_bytes():
    assert flops_lfm2.short_conv_train_bytes(16384, 2048) == (
        11 * 16384 * 2048 * 2)
