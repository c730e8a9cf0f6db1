"""``benchmark/flops.py`` against counts worked by hand for both
configurations (run by hand, outside tier-1)."""

import json
import os

import pytest

from benchmark import flops, harness

HERE = os.path.dirname(os.path.abspath(__file__))


def _config(name):
    with open(os.path.join(HERE, "..", "configs", name + ".json")) as f:
        return json.load(f)


def test_nature_cnn_forward_by_hand():
    # 84x84x4 -> conv 32x8x8/4 -> 20x20x32: 2*400*32*(8*8*4)  = 6,553,600
    #         -> conv 64x4x4/2 ->  9x9x64 : 2*81*64*(4*4*32)   = 5,308,416
    #         -> conv 64x3x3/1 ->  7x7x64 : 2*49*64*(3*3*64)   = 3,612,672
    #         -> dense 3136x512           : 2*3136*512         = 3,211,264
    #         -> heads 512x(18+1)         : 2*512*19           =    19,456
    by_hand = 6553600 + 5308416 + 3612672 + 3211264 + 19456
    assert by_hand == 18705408
    cfg = _config("nature-cnn")
    assert flops.cnn_fwd_flops(1, cfg["obs_shape"], cfg["conv_spec"],
                               cfg["dense"], cfg["act_dim"]) == by_hand
    ref = harness.load_reference("nature-cnn")
    assert ref.train_flops_per_sample(cfg, 20) == 3 * by_hand  # 56.1 MFLOP


def test_gpt2m_policy_forward_by_hand():
    # per token per layer, d = 1024, T = 1024:
    #   QKVO 8 d^2 = 8,388,608; MLP 4*4 d^2 = 16,777,216;
    #   causal attention 2 d T = 2,097,152   => 27,262,976
    # 24 layers = 654,311,424; embed 2*18*1024 = 36,864;
    # heads 2*1024*(16+1) = 34,816           => 654,383,104 per token
    by_hand = 24 * (8388608 + 16777216 + 2097152) + 36864 + 34816
    assert by_hand == 654383104
    cfg = _config("gpt2m-policy")
    assert flops.transformer_fwd_flops(
        1, 1024, cfg["obs_dim"], cfg["act_dim"], cfg["n_embd"],
        cfg["n_layer"], cfg["n_inner"] // cfg["n_embd"]) == by_hand
    ref = harness.load_reference("gpt2m-policy")
    assert ref.train_flops_per_sample(cfg, 1024) == 3 * by_hand  # 1.96 GFLOP
    # tokens scale linearly
    assert flops.transformer_fwd_flops(8192, 1024, 18, 16, 1024, 24) == \
        8192 * by_hand


def test_flash_attention_ops_and_bytes():
    ops, nbytes = flops.flash_attention_ops_bytes(8, 16, 1024, 64)
    assert ops == 4 * 8 * 16 * 1024 * 1024 * 64 // 2
    assert nbytes == 4 * 8 * 16 * 1024 * 64 * 2
    assert flops.flash_attention_ops_bytes(1, 1, 8, 8, causal=False)[0] == \
        4 * 8 * 8 * 8


def test_mfu_reader_cannot_pass_its_own_arithmetic():
    mfu = harness.load_layer_metric("mfu_pct")

    class R:
        peaks = {"bf16_flops_per_s": 197e12}
        train_rate = 37600.0
        train_flops_per_sample = 3 * 18705408
        spec = {"cell": {"chips": 1}}

    # 37.6k frames/s x 56.1 MFLOP = 2.11 TFLOP/s = 1.07% of 197 (ISSUE 23)
    assert mfu.read(R) == pytest.approx(1.0711, abs=1e-3)
