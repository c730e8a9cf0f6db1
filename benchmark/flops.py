"""Operations a configuration's forward pass needs, from its shapes alone.

Matmul and convolution terms only (2 x multiply-adds); element-wise work,
softmax and layer norms are not counted. Copied from
``benches/bench_learner.{transformer,cnn}_fwd_flops`` (sound arithmetic;
the originals are listed in PERF.md's Open questions for deletion) so that a
later PR cannot change the yardstick. A training update of the on-policy
family evaluates the policy once inside the fused loss, so forward +
backward is counted as 3 x forward; recomputed operations never count.
"""

from __future__ import annotations

TRAIN_OVER_FWD = 3  # forward + backward (2x forward), no recomputation


def transformer_fwd_flops(n_tokens: int, seq_len: int, obs: int, act: int,
                          d_model: int, n_layers: int,
                          ffn_mult: int = 4) -> int:
    """Decoder-only trunk over ``n_tokens`` tokens in sequences of
    ``seq_len``: per token per layer the QKVO projections (8 d^2), the MLP
    (2 x 2 d x ffn d) and causal attention (QK^T and AV over ~T/2 keys
    each: 2 d T); plus the observation embedding and the policy/value
    heads."""
    per_layer = (8 * d_model * d_model
                 + 4 * ffn_mult * d_model * d_model
                 + 2 * d_model * seq_len)
    embed_heads = 2 * obs * d_model + 2 * d_model * (act + 1)
    return n_tokens * (n_layers * per_layer + embed_heads)


def cnn_fwd_flops(n_frames: int, obs_shape, conv_spec, dense: int,
                  act: int) -> int:
    """VALID-padded conv stack ``[(features, kernel, stride), ...]`` on
    ``(H, W, C)`` frames, one dense layer, policy and value heads."""
    h, w, c = obs_shape
    per_frame = 0
    for feat, kern, stride in conv_spec:
        h = (h - kern) // stride + 1
        w = (w - kern) // stride + 1
        per_frame += 2 * h * w * feat * (kern * kern * c)
        c = feat
    per_frame += 2 * (h * w * c) * dense + 2 * dense * (act + 1)
    return n_frames * per_frame


def flash_attention_ops_bytes(batch: int, heads: int, seq_len: int,
                              head_dim: int, itemsize: int = 2,
                              causal: bool = True) -> tuple[int, int]:
    """(operations, bytes) of one fused attention forward call: QK^T and AV
    (halved when causal), reading q, k, v and writing o once."""
    ops = 4 * batch * heads * seq_len * seq_len * head_dim
    if causal:
        ops //= 2
    nbytes = 4 * batch * heads * seq_len * head_dim * itemsize
    return ops, nbytes


def flash_attention_train_ops_bytes(batch: int, heads: int, seq_len: int,
                                    head_dim: int, itemsize: int = 2
                                    ) -> tuple[int, int]:
    """(operations, bytes) the forward AND backward of one causal attention
    layer need: forward 2 matmuls (QK^T, PV), backward 4 (dV, dP, dQ, dK) —
    the backward's recomputation of the scores does not count; bytes are
    q, k, v, o read or written once forward (4 arrays) and q, k, v, o, do,
    dq, dk, dv once backward (8 arrays)."""
    fwd_ops, fwd_bytes = flash_attention_ops_bytes(
        batch, heads, seq_len, head_dim, itemsize, causal=True)
    return 3 * fwd_ops, 3 * fwd_bytes
