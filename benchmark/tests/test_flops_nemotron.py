"""``benchmark/flops_nemotron.py`` against counts worked by hand (run by
hand: ``python -m pytest benchmark/tests -q``; not tier-1)."""

import importlib.util
import json
import os

from benchmark import flops_nemotron

HERE = os.path.dirname(os.path.abspath(__file__))


def _cfg():
    with open(os.path.join(HERE, "..", "configs",
                           "nemotron-twotower-policy.json")) as f:
        return json.load(f)


def _reference():
    path = os.path.join(HERE, "..", "reference",
                        "nemotron-twotower-policy.py")
    spec = importlib.util.spec_from_file_location("nemotron_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_layers_the_pattern_names():
    kinds = flops_nemotron.layer_kinds(_cfg())
    assert kinds == ["mamba2", "ffn", "mamba2", "ffn", "mamba2", "attention",
                     "ffn", "mamba2", "ffn"]
    # 64 heads of 64: 4096 inside, not expand x hidden = 5376
    assert flops_nemotron.mamba_widths(_cfg()) == (64, 64, 128, 8, 4096)


def test_the_scan_a_token_and_layer():
    # inside a chunk of 128 a token sees 64.5 rows on average (the pairs on
    # and under the diagonal): C B^T over 8 groups of 128, scores x x over 64
    # heads of 64; then x (x) B and S C, 64 x 64 x 128 each
    inside = 2 * 8 * 128 * 64.5 + 2 * 64 * 64 * 64.5
    assert inside == 132_096 + 528_384
    across = 2 * 2 * 64 * 64 * 128
    assert across == 2_097_152
    assert flops_nemotron.ssd_fwd_flops(_cfg()) == inside + across == (
        2_757_632)
    # whole tiles, as ISSUE 39 counted: 3.41 M a layer, 13.6 over four
    assert 2 * 8 * 128 * 128 + 2 * 64 * 64 * 128 + across == 3_407_872


def test_the_mixers_projections():
    # d -> [z 4096 | xBC 4096 + 2 * 8 * 128 | dt 64] = 10,304; 4096 -> d
    assert 2 * 4096 + 2 * 8 * 128 + 64 == 10_304
    assert flops_nemotron.mamba_proj_fwd_flops(_cfg()) == (
        2 * 2688 * 10_304 + 2 * 4096 * 2688) == 77_414_400


def test_a_token_forward_layer_by_layer():
    t = 8192
    mamba = 77_414_400 + 2_757_632
    # q and o 2688 x 4096, k and v 2688 x 256; 4 x 32 x 128 a score pair
    attention = 2 * (2 * 2688 * 4096 + 2 * 2688 * 256) + 16_384 * (t + 1) / 2
    assert attention == 46_792_704 + 67_117_056
    assert flops_nemotron.relu2_fwd_flops(2688, 1856) == 19_955_712
    assert flops_nemotron.relu2_fwd_flops(2688, 3712) == 39_911_424
    # 6 of 128 chosen, 8 held: 0.375 slot a token
    experts = 2 * 2688 * 128 + 0.375 * 19_955_712 + 39_911_424
    assert experts == 688_128 + 7_483_392 + 39_911_424
    by_hand = 4 * mamba + attention + 4 * experts + 2 * 18 * 2688 + (
        2 * 2688 * 17)
    total = flops_nemotron.nemotron_fwd_flops_per_token(_cfg(), t)
    assert total == by_hand == 627_117_824


def test_train_flops_are_three_forwards():
    assert _reference().train_flops_per_sample(_cfg(), 8192) == (
        3 * 627_117_824)


def test_the_scan_is_memory_bound_at_its_least():
    ref = _reference()
    ops, nbytes = ref.ssd_train_ops_bytes(_cfg(), 2, 8192)
    tokens, layers = 16_384, 4
    assert ops == 3 * 2_757_632 * tokens * layers == 542_172_512_256
    # a row: x and y 4096 each, B and C 1024 each in bfloat16, 64 step
    # sizes in float32; forward once, backward twice
    row = (2 * 4096 + 2 * 1024) * 2 + 64 * 4
    assert row == 20_736
    assert nbytes == 3 * row * tokens * layers == 4_076_863_488
    assert nbytes / 819e9 > ops / 197e12
    assert round(1e3 * nbytes / 819e9, 2) == 4.98
    assert round(1e3 * ops / 197e12, 2) == 2.75


def test_held_grouped_matmuls_count_two_stacks_over_the_held_rows():
    ref = _reference()
    # 24,576 held slots an update (6,144 a layer at even routing)
    ops, nbytes = ref.held_grouped_matmul_train_ops_bytes(_cfg(), 24_576)
    assert ops == 2 * 3 * 2 * 24_576 * 2688 * 1856 == 1_471_294_734_336
    assert nbytes == 6 * 2 * (24_576 * (2688 + 1856)
                              + 4 * 8 * 2688 * 1856)
    # compute-bound at its least: 7.47 ms of operations, 3.98 ms of bytes
    assert round(1e3 * ops / 197e12, 2) == 7.47
    assert round(1e3 * nbytes / 819e9, 2) == 3.98


def test_flash_counts_the_one_attention_layer():
    ref = _reference()
    ops, nbytes = ref.flash_gqa_train_ops_bytes(_cfg(), 2, 8192)
    scores = 2 * 32 * 8192 * 8193 // 2
    assert ops == 6 * 2 * 128 * scores == 3_298_937_536_512
    one = 2 * 8192 * 128 * 2
    assert nbytes == 3 * (2 * 32 + 2 * 2) * one
    assert round(1e3 * ops / 197e12, 1) == 16.7
