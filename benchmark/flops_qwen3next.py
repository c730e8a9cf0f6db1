"""Operations and bytes of ``qwen3next-policy``'s layers, from their shapes
alone (beside ``flops.py``, ``flops_moe.py``, ``flops_lfm2.py``,
``flops_smallthinker.py`` and ``flops_nemotron.py``, which later PRs do not
edit; the same rules: matmul terms only, 2 x multiply-adds, forward +
backward = 3 x forward, nothing recomputed counts).

Layer ``i`` is a full-attention layer where ``(i + 1) %
full_attention_interval == 0`` and a linear-attention (Gated DeltaNet) layer
otherwise; every layer then has an expert layer of which THIS CHIP HOLDS
``num_experts`` of ``published.num_experts`` experts
(``flops_lfm2.held_slots_per_token``: 0.625 token-slots a token and layer at
32 of 512, top-10, even routing) beside a gated shared expert that every
token takes. An expert is SwiGLU: three matmuls.

**The delta rule** (:func:`gdn_fwd_flops`) is counted as the chunked form
needs it at the configuration's ``gdn_chunk`` C, a count of the WORK that
does not change with what implements it — inside a chunk only the pairs a
triangular product needs, ``(C + 1) / 2`` a token on and under the diagonal
and ``(C - 1) / 2`` strictly under it, whatever tiles an implementation
computes whole, and the in-chunk inverse by forward substitution, ``(C - 1)
(C - 2) / 6`` multiply-adds a token (an implementation that squares whole
``C x C`` tiles does 60 times that, and it is no work). A token and layer,
``Hk`` key heads of ``K`` under ``H`` value heads of ``V``:

* ``K K^T`` strictly under the diagonal, a KEY head (two value heads read
  it): ``Hk 2 K (C - 1) / 2``; ``Q K^T`` on and under: ``Hk 2 K (C + 1) / 2``;
* the solve ``T = (I - A)^-1``, a value head: ``H 2 (C - 1)(C - 2) / 6``;
* ``W = T (K_beta e^gamma)`` and ``U = T V_beta`` (``T`` is lower
  triangular): ``H 2 K (C + 1) / 2`` and ``H 2 V (C + 1) / 2``;
* the scores times ``v'``: ``H 2 V (C + 1) / 2``;
* the three products with the carried state, ``W S``, ``(Q e^gamma) S`` and
  ``K^T v'``: ``3 H 2 K V``.

At C 64, Hk 16, H 32, K = V 128: 129,024 + 133,120 + 41,664 + 3 x 266,240 +
3,145,728 = 4,248,256 — ISSUE 42's "the delta rule 3 x ~ 6 [MFLOP a token]"
counts whole tiles; this count is 12.7 over the three layers.
"""

from __future__ import annotations

from benchmark import flops_lfm2, flops_smallthinker


def layer_kinds(cfg: dict) -> list[str]:
    """Each layer's kind in the program's names, from
    ``full_attention_interval``."""
    every = int(cfg["full_attention_interval"])
    return ["full_attention" if (i + 1) % every == 0 else "linear_attention"
            for i in range(int(cfg["num_hidden_layers"]))]


def gdn_widths(cfg: dict) -> tuple[int, int, int, int]:
    """(Hk, H, K, V)."""
    return (int(cfg["linear_num_key_heads"]),
            int(cfg["linear_num_value_heads"]),
            int(cfg["linear_key_head_dim"]),
            int(cfg["linear_value_head_dim"]))


def gdn_fwd_flops(cfg: dict) -> float:
    """One token through one layer's delta rule, forward (module
    docstring)."""
    hk, h, k, v = gdn_widths(cfg)
    c = int(cfg["gdn_chunk"])
    on, under = (c + 1) / 2, (c - 1) / 2
    return (hk * 2 * k * under + hk * 2 * k * on
            + h * 2 * (c - 1) * (c - 2) / 6
            + h * 2 * k * on + 2 * h * 2 * v * on
            + 3 * h * 2 * k * v)


def gdn_proj_fwd_flops(cfg: dict) -> int:
    """The mixer's projections: ``d -> [q | k | v | z]`` (2 Hk K + 2 H V
    wide), ``d -> [b | a]`` (2 H) and ``H V -> d``; convolution, norms and
    gate are element-wise and not counted."""
    hk, h, k, v = gdn_widths(cfg)
    d = int(cfg["hidden_size"])
    return 2 * d * (2 * hk * k + 2 * h * v + 2 * h) + 2 * h * v * d


def gated_attention_fwd_flops(cfg: dict, seq_len: int) -> float:
    """``flops_smallthinker.attention_fwd_flops`` and the gate's half of the
    doubled q projection."""
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    hd = int(cfg["head_dim"])
    return flops_smallthinker.attention_fwd_flops(
        d, heads, int(cfg["num_key_value_heads"]), hd, seq_len,
        None) + 2 * d * heads * hd


def experts_fwd_flops(cfg: dict) -> float:
    """One token through one expert layer as this chip runs it: the router
    over all the model's experts, the held experts at even routing, the
    shared expert and its gate."""
    d = int(cfg["hidden_size"])
    n_experts = int(cfg["published"]["num_experts"])
    slots = flops_lfm2.held_slots_per_token(
        int(cfg["num_experts_per_tok"]), int(cfg["num_experts"]), n_experts)
    return (2 * d * n_experts
            + slots * flops_lfm2.swiglu_fwd_flops(
                d, int(cfg["moe_intermediate_size"]))
            + flops_lfm2.swiglu_fwd_flops(
                d, int(cfg["shared_expert_intermediate_size"]))
            + 2 * d)


def qwen3next_fwd_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward operations a token of ``qwen3next-policy`` as configured:
    each layer by its kind; the observation embedding and the heads as
    ``flops.transformer_fwd_flops`` counts them."""
    d = int(cfg["hidden_size"])
    per_kind = {
        "linear_attention": gdn_proj_fwd_flops(cfg) + gdn_fwd_flops(cfg),
        "full_attention": gated_attention_fwd_flops(cfg, seq_len),
    }
    total = sum(per_kind[kind] + experts_fwd_flops(cfg)
                for kind in layer_kinds(cfg))
    return total + 2 * int(cfg["obs_dim"]) * d + 2 * d * (
        int(cfg["act_dim"]) + 1)


def gdn_train_ops_bytes(cfg: dict, batch: int, seq_len: int,
                        itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) of one update's delta rules, every
    linear-attention layer, forward and backward. Bytes: forward reads
    ``q``, ``k`` (Hk K each), ``v`` (H V) at ``itemsize`` and ``g``,
    ``beta`` (H each, float32) and writes ``o`` (H V); backward reads them
    with ``o``'s cotangent and writes the five cotangents: three passes over
    ``2 Hk K + 2 H V`` columns and over the two scalars a head; and the
    chunk-start states, ``H K V`` float32 a chunk, written once by the
    forward and read once by the backward (the state is the one thing the
    backward cannot make again without running the whole rule)."""
    hk, h, k, v = gdn_widths(cfg)
    layers = layer_kinds(cfg).count("linear_attention")
    tokens = batch * seq_len
    ops = 3 * gdn_fwd_flops(cfg) * tokens * layers
    row = (2 * hk * k + 2 * h * v) * itemsize + 2 * h * 4
    states = 2 * h * k * v * 4 / int(cfg["gdn_chunk"])
    return ops, (3 * row + states) * tokens * layers
