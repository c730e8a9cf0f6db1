"""Operations and bytes of ``kimi-linear-policy``'s layers, from their shapes
alone (beside ``flops.py``, ``flops_moe.py``, ``flops_lfm2.py`` ...
``flops_qwen3next.py``, which later PRs do not edit; the same rules: matmul
terms only, 2 x multiply-adds, forward + backward = 3 x forward, nothing
recomputed counts).

Layer ``i`` (1-based, as ``linear_attn_config`` lists them) is a KDA layer
(``kda_layers``) or a latent-attention layer (``full_attn_layers``); the
first ``first_k_dense_replace`` layers end in the dense SwiGLU FFN of
``intermediate_size``, every other in an expert layer of which THIS CHIP
HOLDS ``num_experts`` of ``published.num_experts`` experts
(``flops_lfm2.held_slots_per_token``: 0.25 token-slots a token and layer at
8 of 256, top-8, even routing) beside ``num_shared_experts`` shared experts
that every token takes.

**The rule** (:func:`kda_fwd_flops`) is counted as the chunked form needs it
at the configuration's ``kda_chunk`` C, ``flops_qwen3next.gdn_fwd_flops``'s
terms with every head a key head (``H`` heads of ``K`` keys and ``V``
values): a pair's weight ``sum_c k_i[c] k_j[c] e^{Gamma_i[c] - Gamma_j[c]}``
is counted as the ``K`` multiply-adds the product needs — re-weighting an
operand lane by lane is element-wise and not counted, however an
implementation splits the sum over sub-chunks. A token and layer:

* ``KK`` strictly under the diagonal: ``H 2 K (C - 1) / 2``; ``QK`` on and
  under it: ``H 2 K (C + 1) / 2``;
* the solve by forward substitution: ``H 2 (C - 1)(C - 2) / 6``;
* ``W = T (K_beta e^Gamma)``, ``U = T V_beta`` and the scores times ``v'``
  (``T`` and the scores lower triangular): ``H 2 K (C + 1) / 2 + 2 H 2 V (C
  + 1) / 2``;
* the three products with the carried state: ``3 H 2 K V``.

At C 64, H 32, K = V 128: 258,048 + 266,240 + 41,664 + 798,720 + 3,145,728
= 4,510,400 (ISSUE 55's "34 MFLOP of the rule" over four layers counts
whole tiles; this count is 18.0).
"""

from __future__ import annotations

from benchmark import flops_lfm2


def layer_kinds(cfg: dict) -> list[str]:
    """Each layer's kind in the program's names, from
    ``linear_attn_config``'s two 1-based lists."""
    lists = cfg["linear_attn_config"]
    kda, full = set(lists["kda_layers"]), set(lists["full_attn_layers"])
    kinds = []
    for i in range(1, int(cfg["num_hidden_layers"]) + 1):
        if (i in kda) == (i in full):
            raise ValueError(f"layer {i} is in {'both' if i in kda else 'neither'}"
                             f" of kda_layers and full_attn_layers")
        kinds.append("kda" if i in kda else "latent_attention")
    return kinds


def kda_widths(cfg: dict) -> tuple[int, int, int]:
    """(H, K, V): keys and values are one width."""
    lin = cfg["linear_attn_config"]
    return int(lin["num_heads"]), int(lin["head_dim"]), int(lin["head_dim"])


def mla_widths(cfg: dict) -> tuple[int, int, int, int]:
    """(heads, latent rank, q / k width, v width)."""
    return (int(cfg["num_attention_heads"]), int(cfg["kv_lora_rank"]),
            int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"]),
            int(cfg["v_head_dim"]))


def kda_fwd_flops(cfg: dict) -> float:
    """One token through one layer's rule, forward (module docstring)."""
    h, k, v = kda_widths(cfg)
    c = int(cfg["kda_chunk"])
    on, under = (c + 1) / 2, (c - 1) / 2
    return (h * 2 * k * under + h * 2 * k * on
            + h * 2 * (c - 1) * (c - 2) / 6
            + h * 2 * k * on + 2 * h * 2 * v * on
            + 3 * h * 2 * k * v)


def kda_proj_fwd_flops(cfg: dict) -> int:
    """The mixer's projections: ``d -> [q | k | v]`` (3 H K), ``d -> beta``
    (H), the two low-rank paths ``d -> K -> H K`` (a head's width inside)
    and ``H V -> d``; convolution, norms and gates are element-wise and not
    counted."""
    h, k, v = kda_widths(cfg)
    d = int(cfg["hidden_size"])
    return (2 * d * (3 * h * k + h) + 2 * 2 * (d * k + k * h * k)
            + 2 * h * v * d)


def mla_fwd_flops(cfg: dict, seq_len: int) -> float:
    """One token through one latent-attention layer: ``d -> q`` (heads x
    192), ``d -> [c | k_pe]``, ``c -> [k_nope | v]``, ``heads x v -> d``,
    and the causal scores at a mean of ``(T + 1) / 2`` keys: ``q . k`` over
    the q / k width and ``p v`` over the v width."""
    heads, rank, qk, vd = mla_widths(cfg)
    d = int(cfg["hidden_size"])
    nope, pe = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    proj = (2 * d * heads * qk + 2 * d * (rank + pe)
            + 2 * rank * heads * (nope + vd) + 2 * heads * vd * d)
    return proj + 2 * heads * (qk + vd) * (seq_len + 1) / 2


def experts_fwd_flops(cfg: dict) -> float:
    """One token through one expert layer as this chip runs it: the router
    over all the model's experts, the held experts at even routing, the
    shared experts."""
    d = int(cfg["hidden_size"])
    n_experts = int(cfg["published"]["num_experts"])
    slots = flops_lfm2.held_slots_per_token(
        int(cfg["num_experts_per_token"]), int(cfg["num_experts"]),
        n_experts)
    one = flops_lfm2.swiglu_fwd_flops(d, int(cfg["moe_intermediate_size"]))
    return 2 * d * n_experts + (slots + int(cfg["num_shared_experts"])) * one


def kimi_linear_fwd_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward operations a token of ``kimi-linear-policy`` as configured:
    each layer by its kind, the dense FFN in the ``first_k_dense_replace``
    leading layers and the expert layer after; the observation embedding and
    the heads as ``flops.transformer_fwd_flops`` counts them."""
    d = int(cfg["hidden_size"])
    per_kind = {"kda": kda_proj_fwd_flops(cfg) + kda_fwd_flops(cfg),
                "latent_attention": mla_fwd_flops(cfg, seq_len)}
    dense = int(cfg["first_k_dense_replace"])
    total = 0.0
    for i, kind in enumerate(layer_kinds(cfg)):
        total += per_kind[kind] + (
            flops_lfm2.swiglu_fwd_flops(d, int(cfg["intermediate_size"]))
            if i < dense else experts_fwd_flops(cfg))
    return total + 2 * int(cfg["obs_dim"]) * d + 2 * d * (
        int(cfg["act_dim"]) + 1)


def kda_train_ops_bytes(cfg: dict, batch: int, seq_len: int,
                        itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) of one update's rules, every KDA layer, forward
    and backward. Bytes: forward reads ``q``, ``k``, ``v`` (H K, H K, H V at
    ``itemsize``), ``g`` (H K, float32: a decay a lane) and ``beta`` (H,
    float32) and writes ``o`` (H V); backward reads them with ``o``'s
    cotangent and writes the five cotangents, ``g``'s as wide as ``g``:
    three passes over ``2 H K + 2 H V`` columns, over the float32 lanes of
    ``g`` and over ``beta``; and the chunk-start states, ``H K V`` float32 a
    chunk, written once by the forward and read once by the backward. What
    an implementation makes again in its backward is time and no counted
    work."""
    h, k, v = kda_widths(cfg)
    layers = layer_kinds(cfg).count("kda")
    tokens = batch * seq_len
    ops = 3 * kda_fwd_flops(cfg) * tokens * layers
    row = (2 * h * k + 2 * h * v) * itemsize + h * k * 4 + h * 4
    states = 2 * h * k * v * 4 / int(cfg["kda_chunk"])
    return ops, (3 * row + states) * tokens * layers


def mla_flash_train_ops_bytes(cfg: dict, batch: int, seq_len: int,
                              itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) of one update's latent-attention flash kernels,
    forward and backward, at the REAL lanes: the scores a causal call needs,
    ``T (T + 1) / 2`` a head, through ``q k^T`` forward and dQ, dK backward
    at the q / k width (192) and ``p v`` forward and dV, dP backward at the v
    width (128) — lanes a kernel pads in VMEM are no counted work, nor is the
    backward's recomputation of the scores. Bytes: q, k (and dq, dk) at the
    q / k width, v, o (and do, dv) at the v width, once forward and twice
    backward."""
    heads, _rank, qk, vd = mla_widths(cfg)
    layers = layer_kinds(cfg).count("latent_attention")
    scores = batch * heads * seq_len * (seq_len + 1) // 2
    ops = 2 * scores * 3 * (qk + vd)
    nbytes = 3 * 2 * batch * heads * seq_len * (qk + vd) * itemsize
    return layers * ops, layers * nbytes
