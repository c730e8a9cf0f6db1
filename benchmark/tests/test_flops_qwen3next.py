"""``benchmark/flops_qwen3next.py`` against counts worked by hand (run by
hand: ``python -m pytest benchmark/tests -q``; not tier-1)."""

import importlib.util
import json
import os

from benchmark import flops_qwen3next

HERE = os.path.dirname(os.path.abspath(__file__))


def _cfg():
    with open(os.path.join(HERE, "..", "configs",
                           "qwen3next-policy.json")) as f:
        return json.load(f)


def _reference():
    path = os.path.join(HERE, "..", "reference", "qwen3next-policy.py")
    spec = importlib.util.spec_from_file_location("qwen3next_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_layers_the_interval_names():
    assert flops_qwen3next.layer_kinds(_cfg()) == [
        "linear_attention"] * 3 + ["full_attention"]
    whole = {**_cfg(), "num_hidden_layers": 48}
    kinds = flops_qwen3next.layer_kinds(whole)
    assert kinds.count("full_attention") == 12
    assert [i for i, k in enumerate(kinds) if k == "full_attention"][:3] == [
        3, 7, 11]
    assert flops_qwen3next.gdn_widths(_cfg()) == (16, 32, 128, 128)


def test_the_delta_rule_a_token_and_layer():
    # inside a chunk of 64 a token sees 32.5 rows on and under the diagonal
    # and 31.5 strictly under it. K K^T (strict) and Q K^T over 16 KEY heads
    # of 128; forward substitution, 63 x 62 / 6 multiply-adds a token, over
    # 32 value heads; W, U and the scores times v' over 32 heads of 128
    products = 2 * 16 * 128 * 31.5 + 2 * 16 * 128 * 32.5
    assert products == 129_024 + 133_120
    solve = 32 * 2 * 63 * 62 / 6
    assert solve == 41_664
    triangular = 3 * 2 * 32 * 128 * 32.5
    assert triangular == 3 * 266_240
    # W S, (Q e^gamma) S and K^T v': 128 x 128 a head each
    state = 3 * 2 * 32 * 128 * 128
    assert state == 3_145_728
    assert flops_qwen3next.gdn_fwd_flops(_cfg()) == (
        products + solve + triangular + state) == 4_248_256
    # whole tiles and the solve as eleven 64^3 products: what an
    # implementation may compute and this count does not
    whole = (2 * 2 * 16 * 128 * 64 + 32 * 11 * 2 * 64 * 64
             + 3 * 2 * 32 * 128 * 64 + state)
    assert whole == 8_126_464


def test_the_mixers_projections():
    # d -> [q 2048 | k 2048 | v 4096 | z 4096] = 12,288 and [b | a] = 64;
    # 4096 -> d
    assert 2 * 16 * 128 + 2 * 32 * 128 == 12_288
    assert flops_qwen3next.gdn_proj_fwd_flops(_cfg()) == (
        2 * 2048 * 12_352 + 2 * 4096 * 2048) == 67_371_008


def test_a_token_forward_layer_by_layer():
    t = 8192
    linear = 67_371_008 + 4_248_256
    # q 2048 x 4096 and its gate the same again, o 4096 x 2048, k and v
    # 2048 x 512; 4 x 16 x 256 a score pair
    attention = (2 * (2 * 2048 * 4096 + 2 * 2048 * 512) + 2 * 2048 * 4096
                 + 16_384 * (t + 1) / 2)
    assert attention == 54_525_952 + 67_117_056
    assert flops_qwen3next.gated_attention_fwd_flops(_cfg(), t) == attention
    # 10 of 512 chosen, 32 held: 0.625 slot a token; three matmuls an
    # expert; the shared expert and its gate; the router over 512
    experts = (2 * 2048 * 512 + 0.625 * 3 * 2 * 2048 * 512
               + 3 * 2 * 2048 * 512 + 2 * 2048)
    assert experts == 2_097_152 + 3_932_160 + 6_291_456 + 4_096
    assert flops_qwen3next.experts_fwd_flops(_cfg()) == experts
    by_hand = 3 * linear + attention + 4 * experts + 2 * 18 * 2048 + (
        2 * 2048 * 17)
    total = flops_qwen3next.qwen3next_fwd_flops_per_token(_cfg(), t)
    assert total == by_hand == 385_943_616


def test_train_flops_are_three_forwards():
    assert _reference().train_flops_per_sample(_cfg(), 8192) == (
        3 * 385_943_616)


def test_the_rule_is_memory_bound_at_its_least():
    ref = _reference()
    ops, nbytes = ref.gdn_train_ops_bytes(_cfg(), 2, 8192)
    tokens, layers = 16_384, 3
    assert ops == 3 * 4_248_256 * tokens * layers == 626_430_836_736
    # a row: q and k 2048 each, v and o 4096 each in bfloat16, g and beta 32
    # each in float32, forward once and backward twice; the chunk-start
    # states, 32 x 128 x 128 float32 a chunk of 64, once each way
    row = (2 * 2048 + 2 * 4096) * 2 + 2 * 32 * 4
    assert row == 24_832
    states = 2 * 32 * 128 * 128 * 4 / 64
    assert states == 65_536
    assert nbytes == (3 * row + states) * tokens * layers == 6_882_852_864
    assert nbytes / 819e9 > ops / 197e12
    assert round(1e3 * nbytes / 819e9, 2) == 8.40
    assert round(1e3 * ops / 197e12, 2) == 3.18


def test_held_grouped_matmuls_count_three_stacks_over_the_held_rows():
    ref = _reference()
    # 40,960 held slots an update (10,240 a layer at even routing)
    ops, nbytes = ref.held_grouped_matmul_train_ops_bytes(_cfg(), 40_960)
    assert ops == 3 * 3 * 2 * 40_960 * 2048 * 512 == 773_094_113_280
    assert nbytes == 9 * 2 * (40_960 * (2048 + 512) + 4 * 32 * 2048 * 512)
    # memory-bound at its least: 3.92 ms of operations, 5.25 ms of bytes
    assert round(1e3 * ops / 197e12, 2) == 3.92
    assert round(1e3 * nbytes / 819e9, 2) == 5.25


def test_flash_counts_the_one_attention_layer_at_256():
    ref = _reference()
    ops, nbytes = ref.flash_gqa_train_ops_bytes(_cfg(), 2, 8192)
    scores = 2 * 16 * 8192 * 8193 // 2
    assert ops == 6 * 2 * 256 * scores == 3_298_937_536_512
    one = 2 * 8192 * 256 * 2
    assert nbytes == 3 * (2 * 16 + 2 * 2) * one
    assert round(1e3 * ops / 197e12, 1) == 16.7
