"""Operations and bytes of ``ouro-policy``'s looped stack, from its shapes
alone (beside ``flops.py`` and the other ``flops_*.py``, which later PRs do
not edit; the same rules: matmul terms only, 2 x multiply-adds, forward +
backward = 3 x forward, nothing recomputed counts).

The stack is ``num_hidden_layers`` identical layers — multi-head attention
(k/v at the query heads' own count) and a dense SwiGLU FFN — applied
``total_ut_steps`` times over the SAME weights: a parameter does that many
times the work of one elsewhere, so a pass is counted once and multiplied.
The program runs each block application's forward a second time in its
backward (``block_checkpoint``); that is the program's choice, not the
model's, and is NOT counted: ``mfu_pct`` and ``flash_gqa_roofline`` read
lower for it, which is what a later change to what the checkpoint keeps
moves.
"""

from __future__ import annotations

from benchmark import flops_smallthinker


def layer_fwd_flops_per_token(cfg: dict, seq_len: int) -> float:
    """One token through one application of one layer: q, k, v and the
    output projection (``d x H hd`` and ``d x Hkv hd``), QK^T and PV over
    the ``(T + 1) / 2`` keys a token sees on average, and the FFN's three
    ``d x intermediate`` matmuls. The four norms are element-wise."""
    attention = flops_smallthinker.attention_fwd_flops(
        int(cfg["hidden_size"]), int(cfg["num_attention_heads"]),
        int(cfg["num_key_value_heads"]), int(cfg["head_dim"]), seq_len,
        None)
    return attention + 3 * 2 * int(cfg["hidden_size"]) * int(
        cfg["intermediate_size"])


def applications(cfg: dict) -> int:
    """Block applications a forward: every layer once a pass."""
    return int(cfg["total_ut_steps"]) * int(cfg["num_hidden_layers"])


def ouro_fwd_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward operations a token of ``ouro-policy`` as configured: a layer
    application times :func:`applications`; the observation embedding and
    the heads once, as ``flops.transformer_fwd_flops`` counts them."""
    d = int(cfg["hidden_size"])
    return (applications(cfg) * layer_fwd_flops_per_token(cfg, seq_len)
            + 2 * int(cfg["obs_dim"]) * d + 2 * d * (int(cfg["act_dim"]) + 1))


def flash_train_ops_bytes(cfg: dict, batch: int, seq_len: int
                          ) -> tuple[float, float]:
    """(operations, bytes) of one update's flash kernels, forward and
    backward, every application: ``flops_smallthinker.flash_train_ops_bytes``
    of one causal layer (2 matmuls forward and 4 backward over the ``T (T +
    1) / 2`` scores a head; q, o, do, dq and k, v, dk, dv each once — 4
    arrays forward, 8 backward) times :func:`applications`. The forward
    kernel's second run under the checkpoint counts for nothing."""
    ops, nbytes = flops_smallthinker.flash_train_ops_bytes(
        batch, int(cfg["num_attention_heads"]),
        int(cfg["num_key_value_heads"]), seq_len, int(cfg["head_dim"]), None)
    return applications(cfg) * ops, applications(cfg) * nbytes
