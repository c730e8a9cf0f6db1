"""Operations and bytes of a sparse-MoE decoder trunk, from its shapes alone
(beside ``flops.py``, which later PRs do not edit; the same rules: matmul
terms only, 2 x multiply-adds, forward + backward = 3 x forward, nothing
recomputed counts).

Only the ACTIVE work of the expert layer is counted — each token through
its k chosen experts, not through all E: a dispatch that runs every expert
on every token does E/k times these operations and is credited none of the
surplus.
"""

from __future__ import annotations


def moe_transformer_fwd_flops(n_tokens: int, seq_len: int, obs: int,
                              act: int, d_model: int, n_layers: int,
                              n_experts: int, top_k: int,
                              expert_ff: int) -> int:
    """Decoder-only trunk whose FFN is a top-k MoE of gated (SwiGLU)
    experts, over ``n_tokens`` tokens in sequences of ``seq_len``: per
    token per layer the QKVO projections (8 d^2), causal attention (QK^T
    and AV over ~T/2 keys each: 2 d T), the router (2 d E) and k experts of
    three matmuls each (k x 6 d ff); plus the observation embedding and
    the policy/value heads, as ``flops.transformer_fwd_flops`` counts
    them."""
    per_layer = (8 * d_model * d_model + 2 * d_model * seq_len
                 + 2 * d_model * n_experts + top_k * 6 * d_model * expert_ff)
    embed_heads = 2 * obs * d_model + 2 * d_model * (act + 1)
    return n_tokens * (n_layers * per_layer + embed_heads)


def grouped_matmul_train_ops_bytes(n_tokens: int, top_k: int, d_model: int,
                                   expert_ff: int, n_experts: int,
                                   itemsize: int = 2) -> tuple[int, int]:
    """(operations, bytes) the grouped matmuls of ONE gated expert layer
    need forward AND backward over ``n_tokens`` tokens. Rows M = n_tokens x
    top_k. Forward: gate, up (``[M, d] x [E, d, ff]``) and down (``[M, ff]
    x [E, ff, d]``), 2 M d ff each; backward: the input gradient and the
    weight gradient of each, the same size again twice. Bytes: every
    operand read and every result written once per matmul at ``itemsize``
    (9 matmuls: per projection the row operand, the weight stack and the
    result, forward, d-input and d-weight) — a lower bound, since float32
    results are wider."""
    m = n_tokens * top_k
    ops = 3 * 3 * 2 * m * d_model * expert_ff
    rows_in, rows_out = m * d_model, m * expert_ff
    stack = n_experts * d_model * expert_ff
    per_matmul = rows_in + rows_out + stack       # the same three arrays
    return ops, 9 * per_matmul * itemsize
