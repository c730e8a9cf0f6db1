"""``benchmark/flops_joyai.py`` against counts worked by hand (run by hand:
``python -m pytest benchmark/tests -q``; not tier-1). A roofline share over
100% is a wrong count: each count the readers take is held here to a figure
derived by hand, at the published widths and at a small size."""

import importlib.util
import json
import os

from benchmark import flops_joyai

HERE = os.path.dirname(os.path.abspath(__file__))


def _cfg():
    with open(os.path.join(HERE, "..", "configs",
                           "joyai-flash-policy.json")) as f:
        return json.load(f)


def _small():
    """2 heads, q / k 3 + 1 wide, v 2, a query rank of 7 and a latent row
    of 5; hidden 6; 3 layers."""
    cfg = _cfg()
    cfg.update(hidden_size=6, num_attention_heads=2, q_lora_rank=7,
               kv_lora_rank=5, qk_nope_head_dim=3, qk_rope_head_dim=1,
               v_head_dim=2, num_hidden_layers=3)
    return cfg


def _reference():
    path = os.path.join(HERE, "..", "reference", "joyai-flash-policy.py")
    spec = importlib.util.spec_from_file_location("joyai_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_widths():
    assert flops_joyai.mla_widths(_cfg()) == (32, 1536, 512, 192, 128)


def test_a_latent_layers_projections_a_token():
    # W_qa 2048 x 1536, W_qb 1536 x 6144, W_kva 2048 x 576, W_kvb 512 x
    # 8192, W_o 4096 x 2048: the layer's 26,347,520 parameters (ISSUE 62)
    # less its two inner norms' 1,536 + 512
    weights = (3_145_728 + 9_437_184 + 1_179_648 + 4_194_304 + 8_388_608)
    assert weights == 26_347_520 - 1_536 - 512 == 26_345_472
    assert flops_joyai.mla_proj_fwd_flops(_cfg()) == 2 * weights
    # small, by hand: 6 x 7 + 7 x 8 + 6 x 6 + 5 x 10 + 4 x 6 = 208
    assert flops_joyai.mla_proj_fwd_flops(_small()) == 2 * 208


def test_the_scores_a_token():
    # 32 heads, q . k at 192 lanes and p v at 128, a mean of 8,192.5 keys
    assert flops_joyai.mla_scores_fwd_flops(_cfg(), 16384) == (
        2 * 32 * 320 * 8192.5) == 167_782_400
    assert flops_joyai.mla_scores_fwd_flops(_small(), 3) == 2 * 2 * 6 * 2


def test_an_expert_layer_a_token():
    # the router over all 256; 8 x 16 / 256 = 0.5 held slot; one shared
    one = 3 * 2 * 2048 * 768
    assert one == 9_437_184
    assert flops_joyai.experts_fwd_flops(_cfg()) == (
        2 * 2048 * 256 + 1.5 * one) == 15_204_352


def test_forward_operations_a_token_and_the_shares_issue_62_gives():
    cfg = _cfg()
    proj, scores = 52_690_944, 167_782_400
    dense = 3 * 2 * 2048 * 7168
    total = (6 * (proj + scores) + dense + 5 * 15_204_352
             + 2 * 18 * 2048 + 2 * 2048 * 17)
    assert flops_joyai.joyai_fwd_flops_per_token(cfg, 16384) == total
    # an update's forward: 24.4 TFLOP, latent attention 89% of it (scores
    # 68, projections 21), the dense FFN 6, the expert layers 5
    assert round(total * 16384 / 1e12, 1) == 24.4
    assert round(6 * scores / total, 2) == 0.68
    assert round(6 * proj / total, 2) == 0.21
    assert round(dense / total, 2) == 0.06
    assert round(5 * 15_204_352 / total, 2) == 0.05
    ref = _reference()
    assert ref.train_flops_per_sample(cfg, 16384) == 3 * total
    # forward and backward: 73.1 TFLOP an update, 0.37 s at the v5e's peak
    assert round(3 * total * 16384 / 1e12, 1) == 73.1


def test_the_latent_kernels_operations_and_bytes_an_update():
    cfg = _cfg()
    ops, nbytes = _reference().mla_flash_train_ops_bytes(cfg, 1, 16384)
    scores = 32 * 16384 * 16385 // 2
    # q k^T, dQ, dK at 192 lanes; p v, dV, dP at 128: 2 x 3 x 320 a score,
    # six layers
    assert ops == 6 * scores * 2 * 3 * (192 + 128)
    # q, k, dq, dk, and q, k again in the backward: 6 arrays of 192 lanes;
    # v, o, do, dv and v, o again: 6 of 128
    assert nbytes == 6 * 6 * 32 * 16384 * (192 + 128) * 2 == 12_079_595_520
    assert round(1e3 * ops / 197e12, 1) == 251.2    # bound by operations
    assert round(1e3 * nbytes / 819e9, 1) == 14.7
    # small, by hand: 2 heads, T 3: 6 scores a head, q / k 4 wide, v 2,
    # three layers
    ops, nbytes = flops_joyai.mla_flash_train_ops_bytes(_small(), 1, 3)
    assert ops == 3 * 2 * 6 * 2 * 3 * (4 + 2) == 1296
    assert nbytes == 3 * 6 * 2 * 3 * (4 + 2) * 2 == 1296


def test_the_held_grouped_matmuls_count_five_expert_layers():
    cfg = _cfg()
    ops, _ = _reference().held_grouped_matmul_train_ops_bytes(cfg, 16384.0)
    # 3 matmuls forward and two gradients each, 2 d ff a row
    assert ops == 9 * 2 * 16384 * 2048 * 768
    assert int(cfg["num_hidden_layers"]) - int(cfg["num_dense_layers"]) == 5
