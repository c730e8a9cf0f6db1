"""Operations and bytes of ``smallthinker-policy``'s layers, from their
shapes alone (beside ``flops.py``, ``flops_moe.py`` and ``flops_lfm2.py``,
which later PRs do not edit; the same rules: matmul terms only, 2 x
multiply-adds, forward + backward = 3 x forward, nothing recomputed
counts).

Every layer is grouped-query attention (heads of a width of their own, so
q and the output projection are ``H hd`` wide, not ``d``) and an expert
layer of which THIS CHIP HOLDS ``held`` of ``n_experts`` experts
(``flops_lfm2.held_slots_per_token``: 1.5 token-slots a token and layer at
16 of 64, top-6, even routing). A layer is global — a query sees every key
up to its own, ``T (T + 1) / 2`` scores a q head — or windowed: the
``window`` keys up to its own, ``W (W + 1) / 2 + (T - W) W`` scores a q
head (:func:`band_scores`). Only the scores a layer's mask NEEDS count,
whatever the kernels' tiling computes beside them.
"""

from __future__ import annotations

from benchmark import flops_lfm2


def band_scores(seq_len: int, window: int | None) -> int:
    """Scores one q head needs over a sequence: every (query, key) pair
    with ``0 <= t - s < window`` (None, or a window of ``seq_len`` or
    more: the causal triangle)."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def layer_windows(cfg: dict) -> list[int | None]:
    """Each layer's window (None: global), from the configuration's
    ``sliding_window_layout`` and ``sliding_window_size``."""
    return [int(cfg["sliding_window_size"]) if flag else None
            for flag in cfg["sliding_window_layout"]]


def attention_fwd_flops(d_model: int, heads: int, kv_heads: int,
                        head_dim: int, seq_len: int,
                        window: int | None) -> float:
    """One token through one attention operator: the q and output
    projections (``d x H hd`` each), k and v (``d x Hkv hd`` each), and
    QK^T and PV over the keys the token sees on average (``2 x 2 x H hd``
    a key)."""
    proj = 2 * (2 * d_model * heads * head_dim
                + 2 * d_model * kv_heads * head_dim)
    return proj + 4 * heads * head_dim * band_scores(seq_len,
                                                     window) / seq_len


def reglu_fwd_flops(d_model: int, width: int) -> int:
    return 3 * 2 * d_model * width


def smallthinker_fwd_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward operations a token of ``smallthinker-policy`` as configured:
    each layer's attention by its window, the router over all experts and
    the held experts at even routing; the observation embedding and the
    heads as ``flops.transformer_fwd_flops`` counts them."""
    d = int(cfg["hidden_size"])
    heads, kv = int(cfg["num_attention_heads"]), int(
        cfg["num_key_value_heads"])
    n_experts = int(cfg["published"]["moe_num_primary_experts"])
    slots = flops_lfm2.held_slots_per_token(
        int(cfg["moe_num_active_primary_experts"]),
        int(cfg["moe_num_primary_experts"]), n_experts)
    total = 0.0
    for window in layer_windows(cfg):
        total += attention_fwd_flops(d, heads, kv, int(cfg["head_dim"]),
                                     seq_len, window)
        total += 2 * d * n_experts + slots * reglu_fwd_flops(
            d, int(cfg["moe_ffn_hidden_size"]))
    return total + 2 * int(cfg["obs_dim"]) * d + 2 * d * (
        int(cfg["act_dim"]) + 1)


def flash_train_ops_bytes(batch: int, heads: int, kv_heads: int,
                          seq_len: int, head_dim: int, window: int | None,
                          itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) of ONE attention layer's flash kernels, forward
    and backward, ``flops_lfm2.flash_gqa_train_ops_bytes``'s rule with the
    scores of the layer's own mask: 2 matmuls forward and 4 backward of
    ``2 hd`` a score; q, o, do, dq at ``heads``, k, v, dk, dv at
    ``kv_heads``, each array once — 4 forward, 8 backward."""
    ops = 6 * 2 * batch * heads * band_scores(seq_len, window) * head_dim
    one = batch * seq_len * head_dim * itemsize
    return ops, 3 * (2 * heads + 2 * kv_heads) * one


def flash_layers_train_ops_bytes(cfg: dict, batch: int, seq_len: int,
                                 windowed_only: bool = False
                                 ) -> tuple[float, float]:
    """The sum over the configuration's attention layers (``windowed_only``:
    over the windowed ones alone)."""
    ops = nbytes = 0.0
    for window in layer_windows(cfg):
        if windowed_only and window is None:
            continue
        o, b = flash_train_ops_bytes(
            batch, int(cfg["num_attention_heads"]),
            int(cfg["num_key_value_heads"]), seq_len, int(cfg["head_dim"]),
            window)
        ops, nbytes = ops + o, nbytes + b
    return ops, nbytes
