"""Operations and bytes of ``joyai-flash-policy``'s layers, from their shapes
alone (beside ``flops.py``, ``flops_moe.py``, ``flops_lfm2.py`` ...
``flops_kimi_linear.py``, which later PRs do not edit; the same rules: matmul
terms only, 2 x multiply-adds, forward + backward = 3 x forward, nothing
recomputed counts).

Every layer is a latent-attention layer with a query path of a low rank of
its own: ``d -> q_lora_rank -> heads x (nope + rope)`` for q, ``d ->
kv_lora_rank + rope`` and ``kv_lora_rank -> heads x (nope + v)`` for k and v,
``heads x v -> d`` out, and the causal scores at the q / k width (192) and
the v width (128). The rotation of the ``rope`` lanes, the three norms and
the de-interleave are element-wise and not counted. The first
``first_k_dense_replace`` layers end in the dense SwiGLU FFN of
``intermediate_size``, every other in an expert layer of which THIS CHIP
HOLDS ``n_routed_experts`` of ``published.n_routed_experts`` experts
(``flops_lfm2.held_slots_per_token``: 0.5 token-slot a token and layer at 16
of 256, top-8, even routing) beside ``n_shared_experts`` shared experts that
every token takes.

At the published widths (d 2048, 32 heads, 1536 / 512 / 128 / 64 / 128), a
token and layer forward: projections 2 x 26,345,472 = 52,690,944 (the
layer's 26,347,520 parameters less its two norms' 2,048), scores at T 16,384
2 x 32 x 320 x 8,192.5 = 167,782,400.
"""

from __future__ import annotations

from benchmark import flops_lfm2


def mla_widths(cfg: dict) -> tuple[int, int, int, int, int]:
    """(heads, query rank, latent rank, q / k width, v width)."""
    return (int(cfg["num_attention_heads"]), int(cfg["q_lora_rank"]),
            int(cfg["kv_lora_rank"]),
            int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"]),
            int(cfg["v_head_dim"]))


def mla_proj_fwd_flops(cfg: dict) -> int:
    """One token through one layer's five projections."""
    heads, q_rank, rank, qk, vd = mla_widths(cfg)
    d = int(cfg["hidden_size"])
    nope, pe = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    return (2 * d * q_rank + 2 * q_rank * heads * qk + 2 * d * (rank + pe)
            + 2 * rank * heads * (nope + vd) + 2 * heads * vd * d)


def mla_scores_fwd_flops(cfg: dict, seq_len: int) -> float:
    """One token's causal scores at a mean of ``(T + 1) / 2`` keys: ``q .
    k`` over the q / k width and ``p v`` over the v width."""
    heads, _q_rank, _rank, qk, vd = mla_widths(cfg)
    return 2 * heads * (qk + vd) * (seq_len + 1) / 2


def experts_fwd_flops(cfg: dict) -> float:
    """One token through one expert layer as this chip runs it: the router
    over all the model's experts, the held experts at even routing, the
    shared experts."""
    d = int(cfg["hidden_size"])
    n_experts = int(cfg["published"]["n_routed_experts"])
    slots = flops_lfm2.held_slots_per_token(
        int(cfg["num_experts_per_tok"]), int(cfg["n_routed_experts"]),
        n_experts)
    one = flops_lfm2.swiglu_fwd_flops(d, int(cfg["moe_intermediate_size"]))
    return 2 * d * n_experts + (slots + int(cfg["n_shared_experts"])) * one


def joyai_fwd_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward operations a token of ``joyai-flash-policy`` as configured:
    every layer's latent attention, the dense FFN in the
    ``first_k_dense_replace`` leading layers and the expert layer after; the
    observation embedding and the heads as ``flops.transformer_fwd_flops``
    counts them."""
    d = int(cfg["hidden_size"])
    layers, dense = int(cfg["num_hidden_layers"]), int(
        cfg["first_k_dense_replace"])
    total = layers * (mla_proj_fwd_flops(cfg)
                      + mla_scores_fwd_flops(cfg, seq_len))
    total += dense * flops_lfm2.swiglu_fwd_flops(
        d, int(cfg["intermediate_size"]))
    total += (layers - dense) * experts_fwd_flops(cfg)
    return total + 2 * int(cfg["obs_dim"]) * d + 2 * d * (
        int(cfg["act_dim"]) + 1)


def mla_flash_train_ops_bytes(cfg: dict, batch: int, seq_len: int,
                              itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) of one update's latent-attention flash kernels,
    every layer, forward and backward, at the REAL lanes: the scores a
    causal call needs, ``T (T + 1) / 2`` a head, through ``q k^T`` forward
    and dQ, dK backward at the q / k width (192) and ``p v`` forward and dV,
    dP backward at the v width (128) — lanes a kernel pads in VMEM are no
    counted work, nor is the backward's recomputation of the scores. Bytes:
    q, k (and dq, dk) at the q / k width, v, o (and do, dv) at the v width,
    once forward and twice backward."""
    heads, _q_rank, _rank, qk, vd = mla_widths(cfg)
    layers = int(cfg["num_hidden_layers"])
    scores = batch * heads * seq_len * (seq_len + 1) // 2
    ops = 2 * scores * 3 * (qk + vd)
    nbytes = 3 * 2 * batch * heads * seq_len * (qk + vd) * itemsize
    return layers * ops, layers * nbytes
