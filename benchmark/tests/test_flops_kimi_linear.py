"""``benchmark/flops_kimi_linear.py`` against counts worked by hand (run by
hand: ``python -m pytest benchmark/tests -q``; not tier-1). A roofline share
over 100% is a wrong count: each count the readers take is held here to a
figure derived by hand, at the published widths and at a small size."""

import importlib.util
import json
import os

from benchmark import flops_kimi_linear

HERE = os.path.dirname(os.path.abspath(__file__))


def _cfg():
    with open(os.path.join(HERE, "..", "configs",
                           "kimi-linear-policy.json")) as f:
        return json.load(f)


def _small():
    """2 KDA heads of 4, chunks of 4; 2 latent heads, q / k 3 + 1 wide, v
    2, a latent row of 5; hidden 6."""
    cfg = _cfg()
    cfg.update(hidden_size=6, num_attention_heads=2, kv_lora_rank=5,
               qk_nope_head_dim=3, qk_rope_head_dim=1, v_head_dim=2,
               kda_chunk=4)
    cfg["linear_attn_config"] = {**cfg["linear_attn_config"],
                                 "num_heads": 2, "head_dim": 4}
    return cfg


def _reference():
    path = os.path.join(HERE, "..", "reference", "kimi-linear-policy.py")
    spec = importlib.util.spec_from_file_location("kimi_linear_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_layers_the_two_lists_name():
    assert flops_kimi_linear.layer_kinds(_cfg()) == [
        "kda", "kda", "kda", "latent_attention", "kda"]
    whole = {**_cfg(), "num_hidden_layers": 27,
             "linear_attn_config": _cfg()["published"]["linear_attn_config"]}
    kinds = flops_kimi_linear.layer_kinds(whole)
    assert kinds.count("latent_attention") == 7 and kinds.count("kda") == 20
    assert [i + 1 for i, k in enumerate(kinds)
            if k == "latent_attention"] == [4, 8, 12, 16, 20, 24, 27]
    assert flops_kimi_linear.kda_widths(_cfg()) == (32, 128, 128)
    assert flops_kimi_linear.mla_widths(_cfg()) == (32, 512, 192, 128)


def test_the_rule_a_token_and_layer():
    # inside a chunk of 64 a token sees 32.5 rows on and under the diagonal
    # and 31.5 strictly under it; every one of the 32 heads has its own
    # keys, so KK and QK are counted a head: 128 multiply-adds a pair,
    # however the lane-wise weights split the sum
    products = 2 * 32 * 128 * 31.5 + 2 * 32 * 128 * 32.5
    assert products == 258_048 + 266_240
    solve = 32 * 2 * 63 * 62 / 6
    assert solve == 41_664
    triangular = 3 * 2 * 32 * 128 * 32.5      # W, U, scores x v'
    assert triangular == 798_720
    state = 3 * 2 * 32 * 128 * 128
    assert state == 3_145_728
    assert flops_kimi_linear.kda_fwd_flops(_cfg()) == (
        products + solve + triangular + state) == 4_510_400
    # small: 2 heads of 4, chunk 4: 2.5 on, 1.5 under; solve 3 x 2 / 6 = 1
    small = (2 * 2 * 4 * 1.5 + 2 * 2 * 4 * 2.5 + 2 * 2 * 1
             + 3 * 2 * 2 * 4 * 2.5 + 3 * 2 * 2 * 4 * 4)
    assert flops_kimi_linear.kda_fwd_flops(_small()) == small == 380


def test_the_mixers_projections():
    # d -> q | k | v (3 x 4096) and beta (32); two low-rank paths 2304 ->
    # 128 -> 4096; 4096 -> d
    want = (2 * 2304 * (12288 + 32) + 2 * 2 * (2304 * 128 + 128 * 4096)
            + 2 * 4096 * 2304)
    assert want == 56_770_560 + 3_276_800 + 18_874_368 == 78_921_728
    assert flops_kimi_linear.kda_proj_fwd_flops(_cfg()) == want


def test_latent_attention_a_token():
    # projections: q 2304 x 6144, [c | k_pe] 2304 x 576, [k_nope | v] 512 x
    # 8192, out 4096 x 2304; scores at a mean of 8192.5 keys over 192 + 128
    proj = 2 * (2304 * 6144 + 2304 * 576 + 512 * 8192 + 4096 * 2304)
    assert proj == 58_228_736
    scores = 2 * 32 * 320 * 8192.5
    assert flops_kimi_linear.mla_fwd_flops(_cfg(), 16384) == proj + scores
    assert round(scores / 1e6, 1) == 167.8


def test_an_expert_layer_as_held_and_the_whole_forward():
    cfg = _cfg()
    one = 3 * 2 * 2304 * 1024
    # router over 256; 8 x 8 / 256 = 0.25 slots a token; one shared expert
    assert flops_kimi_linear.experts_fwd_flops(cfg) == (
        2 * 2304 * 256 + 1.25 * one) == 18_874_368
    dense = 3 * 2 * 2304 * 9216
    total = (4 * (78_921_728 + 4_510_400)
             + flops_kimi_linear.mla_fwd_flops(cfg, 16384)
             + dense + 4 * 18_874_368 + 2 * 18 * 2304 + 2 * 2304 * 17)
    assert flops_kimi_linear.kimi_linear_fwd_flops_per_token(
        cfg, 16384) == total
    assert round(total / 1e6) == 763
    ref = _reference()
    assert ref.train_flops_per_sample(cfg, 16384) == 3 * total
    # an update of 16,384 tokens: 37.5 TFLOP forward and backward
    assert round(3 * total * 16384 / 1e12, 1) == 37.5


def test_the_rules_operations_and_bytes_an_update():
    cfg = _cfg()
    ops, nbytes = _reference().kda_train_ops_bytes(cfg, 1, 16384)
    assert ops == 3 * 4_510_400 * 16384 * 4
    # a token and layer: q, k, v, o at 4096 bfloat16 lanes, g at 4096
    # float32 lanes, beta 32 float32, three passes; the chunk-start states
    # 32 x 128 x 128 float32 once each way a 64-token chunk
    row = 4 * 4096 * 2 + 4096 * 4 + 32 * 4
    assert row == 49_280
    states = 2 * 32 * 128 * 128 * 4 / 64
    assert states == 65_536
    assert nbytes == (3 * row + states) * 16384 * 4 == 13_983_809_536
    # bound by bytes on a v5e: 17.1 ms against 4.5 by operations
    assert round(1e3 * nbytes / 819e9, 1) == 17.1
    assert round(1e3 * ops / 197e12, 1) == 4.5
    # small, by hand: 2 heads of 4 — row (4 x 8) x 2 + 8 x 4 + 2 x 4 = 104,
    # states 2 x 2 x 16 x 4 / 4 = 64; 3 tokens, 4 KDA layers
    ops, nbytes = flops_kimi_linear.kda_train_ops_bytes(_small(), 1, 3)
    assert ops == 3 * 380 * 3 * 4
    assert nbytes == (3 * 104 + 64) * 3 * 4


def test_the_latent_kernels_operations_and_bytes_an_update():
    cfg = _cfg()
    ops, nbytes = _reference().mla_flash_train_ops_bytes(cfg, 1, 16384)
    scores = 32 * 16384 * 16385 // 2
    # q k^T, dQ, dK at 192 lanes; p v, dV, dP at 128: 2 x 3 x 320 a score
    assert ops == scores * 2 * 3 * (192 + 128)
    # q, k, dq, dk, and q, k again in the backward: 6 arrays of 192 lanes;
    # v, o, do, dv and v, o again: 6 of 128
    assert nbytes == 6 * 32 * 16384 * (192 + 128) * 2 == 2_013_265_920
    assert round(1e3 * ops / 197e12, 1) == 41.9     # bound by operations
    # padded lanes are no work: at 256 / 128 the count would be a fifth more
    assert ops * (256 + 128) / (192 + 128) == scores * 2 * 3 * 384
    # small, by hand: 2 heads, T 3: 6 scores a head, q / k 4 wide, v 2
    ops, nbytes = flops_kimi_linear.mla_flash_train_ops_bytes(_small(), 1, 3)
    assert ops == 2 * 6 * 2 * 3 * (4 + 2) == 432
    assert nbytes == 6 * 2 * 3 * (4 + 2) * 2 == 432


def test_the_held_grouped_matmuls_count_four_expert_layers():
    cfg = _cfg()
    ops, _ = _reference().held_grouped_matmul_train_ops_bytes(cfg, 16384.0)
    # 3 matmuls forward and two gradients each, 2 d ff a row
    assert ops == 9 * 2 * 16384 * 2304 * 1024
