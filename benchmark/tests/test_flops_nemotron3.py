"""``benchmark/flops_nemotron3.py`` against counts worked by hand, at the
sizes AS RUN — one chip's share of the heads and of the experts — (run by
hand: ``python -m pytest benchmark/tests -q``; not tier-1)."""

import importlib.util
import json
import os

from benchmark import flops_nemotron3

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = "nemotron3-super-policy"


def _cfg():
    with open(os.path.join(HERE, "..", "configs", f"{NAME}.json")) as f:
        return json.load(f)


def _reference():
    path = os.path.join(HERE, "..", "reference", f"{NAME}.py")
    spec = importlib.util.spec_from_file_location("nemotron3_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_layers_the_pattern_names():
    assert flops_nemotron3.layer_kinds(_cfg()) == (
        ["ffn", "mamba2"] * 5 + ["attention"])
    # 32 HELD heads of 64 in 2 held groups: 2048 inside
    assert flops_nemotron3.mamba_widths(_cfg()) == (32, 64, 128, 2, 2048)


def test_the_scan_a_token_and_layer_at_the_held_heads():
    inside = 2 * 2 * 128 * 64.5 + 2 * 32 * 64 * 64.5
    assert inside == 33_024 + 264_192
    across = 2 * 2 * 32 * 64 * 128
    assert across == 1_048_576
    by_kind = flops_nemotron3.fwd_flops_by_kind(_cfg(), 8192)
    # d -> [z 2048 | xBC 2048 + 2 * 2 * 128 | dt 32] = 4640; 2048 -> d
    proj = 2 * 4096 * 4640 + 2 * 2048 * 4096
    assert proj == 38_010_880 + 16_777_216
    assert by_kind["mamba2"] == proj + inside + across == 56_133_888
    # at the PUBLISHED 128 heads in 8 groups it would be four times that
    whole = {**_cfg(), "mamba_num_heads": 128, "n_groups": 8}
    assert flops_nemotron3.fwd_flops_by_kind(whole, 8192)["mamba2"] == (
        4 * (38_010_880 - 2 * 4096 * 32 + inside + across)
        + 2 * 4096 * 128 + 4 * 16_777_216)


def test_an_expert_layer_a_token_by_part():
    parts = flops_nemotron3.expert_layer_fwd_flops(_cfg())
    assert parts == {
        "router": 2 * 4096 * 512,               # 4,194,304
        "latent": 2 * 2 * 4096 * 1024,          # 16,777,216: down and up
        # 22 of 512 chosen, 8 held: 0.34375 slot a token, K 1024 / N 2688
        "held": 0.34375 * 2 * 2 * 1024 * 2688,  # 3,784,704
        "shared": 2 * 2 * 4096 * 5376}          # 88,080,384
    assert sum(parts.values()) == 112_836_608


def test_a_token_forward_layer_by_layer_and_the_shares():
    t = 8192
    # q and o 4096 x 1024, k and v 4096 x 128; 4 x 8 x 128 a score pair
    attention = 2 * (2 * 4096 * 1024 + 2 * 4096 * 128) + 4096 * (t + 1) / 2
    assert attention == 18_874_368 + 16_779_264
    by_hand = (5 * 56_133_888 + 5 * 112_836_608 + attention
               + 2 * 18 * 4096 + 2 * 4096 * 17)
    total = flops_nemotron3.nemotron3_fwd_flops_per_token(_cfg(), t)
    assert total == by_hand == 880_792_832
    assert _reference().train_flops_per_sample(_cfg(), t) == 3 * total
    # the cell's `why`: experts 64% (shared 50, the latent path 14),
    # Mamba-2 32%, attention 4%
    share = lambda x: round(100 * x / total)
    assert share(5 * 112_836_608) == 64 and share(5 * 88_080_384) == 50
    assert share(5 * (4_194_304 + 16_777_216 + 3_784_704)) == 14
    assert share(5 * 56_133_888) == 32 and share(attention) == 4


def test_the_scan_is_memory_bound_at_its_least():
    ops, nbytes = _reference().ssd_train_ops_bytes(_cfg(), 2, 8192)
    tokens, layers = 16_384, 5
    assert ops == 3 * 1_345_792 * tokens * layers == 330_741_841_920
    # a row: x and y 2048 each, B and C 256 each in bfloat16, 32 step
    # sizes in float32; forward once, backward twice
    row = (2 * 2048 + 2 * 256) * 2 + 32 * 4
    assert row == 9_344
    assert nbytes == 3 * row * tokens * layers == 2_296_381_440
    assert nbytes / 819e9 > ops / 197e12
    assert round(1e3 * nbytes / 819e9, 2) == 2.80
    assert round(1e3 * ops / 197e12, 2) == 1.68


def test_held_grouped_matmuls_count_latent_wide_rows():
    # 28,160 held slots an update (5,632 a layer at even routing)
    ops, nbytes = _reference().held_grouped_matmul_train_ops_bytes(
        _cfg(), 28_160)
    assert ops == 2 * 3 * 2 * 28_160 * 1024 * 2688 == 930_128_855_040
    assert nbytes == 6 * 2 * (28_160 * (1024 + 2688)
                              + 5 * 8 * 1024 * 2688)
    # memory-bound at its least: the stacks, read and written three times
    # over, outweigh 704 rows an expert
    assert round(1e3 * ops / 197e12, 2) == 4.72
    assert round(1e3 * nbytes / 819e9, 2) == 3.14
