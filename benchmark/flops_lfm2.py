"""Operations and bytes of ``lfm2-policy``'s layers, from their shapes alone
(beside ``flops.py`` and ``flops_moe.py``, which later PRs do not edit; the
same rules: matmul terms only, 2 x multiply-adds, forward + backward = 3 x
forward, nothing recomputed counts).

The trunk holds layers of several kinds, so the count goes layer by layer:
an operator (grouped-query attention or the gated short convolution) and an
FFN (dense SwiGLU or the expert layer). Of the expert layer only the work of
the experts THIS CHIP HOLDS is counted: ``held`` of ``n_experts``, each
token's ``top_k`` slots falling on a held expert with probability
``held / n_experts`` at even routing (0.5 slot a token at 8 of 64, top-4).
What the absent chips would compute is nobody's work here.
"""

from __future__ import annotations


def attention_fwd_flops(d_model: int, heads: int, kv_heads: int,
                        head_dim: int, seq_len: int) -> int:
    """One token through a grouped-query attention operator: the q and
    output projections (``d x H hd`` each), the k and v projections
    (``d x Hkv hd`` each), and causal attention over ~T/2 keys (QK^T and
    PV, ``2 x 2 x H hd x T/2``). k/v are shared by a group: their
    projections shrink, the score work does not."""
    proj = 2 * (2 * d_model * heads * head_dim
                + 2 * d_model * kv_heads * head_dim)
    return proj + 2 * heads * head_dim * seq_len


def short_conv_fwd_flops(d_model: int) -> int:
    """One token through the gated short convolution's two projections
    (``d -> 3d`` and ``d -> d``); the taps and the two gate products are
    element-wise and not counted."""
    return 2 * d_model * 3 * d_model + 2 * d_model * d_model


def swiglu_fwd_flops(d_model: int, width: int) -> int:
    return 3 * 2 * d_model * width


def held_slots_per_token(top_k: int, held: int, n_experts: int) -> float:
    """Token-slots a token sends to held experts at even routing."""
    return top_k * held / n_experts


def lfm2_fwd_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward operations a token of ``lfm2-policy`` as configured: each
    layer's operator by ``layer_types``, the dense FFN in the
    ``num_dense_layers`` leading layers and router + held experts (even
    routing) after, the observation embedding and the heads as
    ``flops.transformer_fwd_flops`` counts them."""
    d = int(cfg["hidden_size"])
    heads, kv = int(cfg["num_attention_heads"]), int(
        cfg["num_key_value_heads"])
    n_experts = int(cfg["published"]["num_experts"])
    slots = held_slots_per_token(int(cfg["num_experts_per_tok"]),
                                 int(cfg["num_experts"]), n_experts)
    total = 0.0
    for i, kind in enumerate(cfg["layer_types"]):
        total += (attention_fwd_flops(d, heads, kv, d // heads, seq_len)
                  if kind == "full_attention" else short_conv_fwd_flops(d))
        if i < int(cfg["num_dense_layers"]):
            total += swiglu_fwd_flops(d, int(cfg["intermediate_size"]))
        else:
            total += 2 * d * n_experts + slots * swiglu_fwd_flops(
                d, int(cfg["moe_intermediate_size"]))
    return total + 2 * int(cfg["obs_dim"]) * d + 2 * d * (
        int(cfg["act_dim"]) + 1)


def held_grouped_matmul_train_ops_bytes(held_slots: float, expert_layers: int,
                                        held: int, d_model: int,
                                        expert_ff: int, itemsize: int = 2
                                        ) -> tuple[float, float]:
    """(operations, bytes) of the grouped matmuls of one update, forward
    and backward, over ``held_slots`` rows IN ALL (the token-slots routed to
    held experts, summed over the ``expert_layers`` expert layers: the
    run's own count). Per row three matmuls of ``2 d ff`` forward and the
    input and weight gradient of each, the same size again twice (the
    forward's gate and up products recomputed in the backward are not
    counted). Bytes: per matmul the row operand, the result and the held
    weight stack once, at ``itemsize`` — ``flops_moe``'s rule with the rows
    and the stacks that are here."""
    ops = 3 * 3 * 2 * held_slots * d_model * expert_ff
    rows = held_slots * (d_model + expert_ff)
    stacks = expert_layers * held * d_model * expert_ff
    return ops, 9 * (rows + stacks) * itemsize


def flash_gqa_train_ops_bytes(batch: int, heads: int, kv_heads: int,
                              seq_len: int, head_dim: int,
                              itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) of ONE grouped-query attention layer's flash
    kernels, forward and backward. Operations: the scores a causal call
    NEEDS, ``T (T + 1) / 2`` a q head — whatever the kernels' tiling
    computes above the diagonal besides (``ops/flash.py`` prints its
    executed area; at T 8192 it is 51.6% of ``T x T`` against this 50.006%)
    is no useful work — through 2 matmuls forward (QK^T, PV) and 4 backward
    (dV, dP, dQ, dK) of ``2 hd`` each; the backward's recomputation of the
    scores does not count. Bytes: q, o (and do, dq) at
    ``heads``, k, v (and dk, dv) at ``kv_heads`` — k/v are read as they
    are, not repeated: 4 arrays forward, 8 backward."""
    scores = batch * heads * seq_len * (seq_len + 1) // 2
    ops = 6 * 2 * scores * head_dim
    one = batch * seq_len * head_dim * itemsize
    return ops, 3 * (2 * heads + 2 * kv_heads) * one


def short_conv_train_bytes(n_tokens: int, d_model: int,
                           itemsize: int = 2) -> int:
    """Least bytes the short convolution's element-wise part moves for
    ``n_tokens`` tokens, forward and backward: forward reads B, C, u and
    writes ``C * conv(B * u)`` (4 d-wide rows); backward reads them and the
    output's cotangent and writes the three cotangents (7 more). By hand in
    PERF.md section 5 against ``short_conv_ms``; no roofline metric reads
    it."""
    return 11 * n_tokens * d_model * itemsize
