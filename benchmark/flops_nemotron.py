"""Operations and bytes of ``nemotron-twotower-policy``'s layers, from their
shapes alone (beside ``flops.py``, ``flops_moe.py``, ``flops_lfm2.py`` and
``flops_smallthinker.py``, which later PRs do not edit; the same rules:
matmul terms only, 2 x multiply-adds, forward + backward = 3 x forward,
nothing recomputed counts).

Every layer is ONE part, by ``hybrid_override_pattern``: ``M`` a Mamba-2
mixer, ``*`` grouped-query attention without positions, ``E`` an expert
layer of which THIS CHIP HOLDS ``n_routed_experts`` of
``published.n_routed_experts`` experts (``flops_lfm2.held_slots_per_token``:
0.375 token-slots a token and layer at 8 of 128, top-6, even routing)
beside a shared expert that every token takes. An expert is two matmuls
(``relu(up)^2``, no gate).

**The scan** (:func:`ssd_fwd_flops`) is counted as the chunked form needs
it at the configuration's ``chunk_size`` L, the way the flash kernels'
scores are: inside a chunk only the pairs on and under the diagonal, ``(L +
1) / 2`` a token on average, whatever tiles an implementation computes
whole. A token and layer: ``C B^T`` a group (``2 G N (L + 1) / 2``), the
scores times ``x`` a head (``2 H P (L + 1) / 2``), the chunk's own state
(``2 H P N``: ``x (x) B`` summed over the chunk) and the carried state's
part of the output (``2 H P N``). At L 128, H 64, P 64, N 128, G 8 that is
132,096 + 528,384 + 1,048,576 + 1,048,576 = 2,757,632 — ISSUE 39's "the
scan 14 [MFLOP a token]" counts the two inside-chunk products over whole
tiles (3.41 M a layer); this count is 11.0 over the four layers.
"""

from __future__ import annotations

from benchmark import flops_lfm2, flops_smallthinker

LAYER_KINDS = {"M": "mamba2", "E": "ffn", "*": "attention"}


def layer_kinds(cfg: dict) -> list[str]:
    """Each layer's kind in the program's names, from the pattern."""
    return [LAYER_KINDS[c] for c in cfg["hybrid_override_pattern"]]


def mamba_widths(cfg: dict) -> tuple[int, int, int, int, int]:
    """(H, P, N, G, inner = H P): the inner width is heads x head_dim, not
    ``expand`` x hidden."""
    heads, width = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    return (heads, width, int(cfg["ssm_state_size"]), int(cfg["n_groups"]),
            heads * width)


def ssd_fwd_flops(cfg: dict) -> float:
    """One token through one layer's scan, forward (module docstring)."""
    heads, width, state, groups, _ = mamba_widths(cfg)
    pairs = (int(cfg["chunk_size"]) + 1) / 2
    return (2 * groups * state * pairs + 2 * heads * width * pairs
            + 2 * 2 * heads * width * state)


def mamba_proj_fwd_flops(cfg: dict) -> int:
    """The mixer's two projections: ``d -> [z | xBC | dt]`` (2 inner + 2 G N
    + H wide) and ``inner -> d``; convolution, gate and norm are
    element-wise and not counted."""
    heads, _, state, groups, inner = mamba_widths(cfg)
    d = int(cfg["hidden_size"])
    return 2 * d * (2 * inner + 2 * groups * state + heads) + 2 * inner * d


def relu2_fwd_flops(d_model: int, width: int) -> int:
    """An FFN without a gate: up and down."""
    return 2 * 2 * d_model * width


def nemotron_fwd_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward operations a token of ``nemotron-twotower-policy`` as
    configured: each layer by its kind; the observation embedding and the
    heads as ``flops.transformer_fwd_flops`` counts them."""
    d = int(cfg["hidden_size"])
    n_experts = int(cfg["published"]["n_routed_experts"])
    slots = flops_lfm2.held_slots_per_token(
        int(cfg["num_experts_per_tok"]), int(cfg["n_routed_experts"]),
        n_experts)
    per_kind = {
        "mamba2": mamba_proj_fwd_flops(cfg) + ssd_fwd_flops(cfg),
        "attention": flops_smallthinker.attention_fwd_flops(
            d, int(cfg["num_attention_heads"]),
            int(cfg["num_key_value_heads"]), int(cfg["head_dim"]), seq_len,
            None),
        "ffn": (2 * d * n_experts
                + slots * relu2_fwd_flops(
                    d, int(cfg["moe_intermediate_size"]))
                + int(cfg["n_shared_experts"]) * relu2_fwd_flops(
                    d, int(cfg["moe_shared_expert_intermediate_size"]))),
    }
    total = sum(per_kind[kind] for kind in layer_kinds(cfg))
    return total + 2 * int(cfg["obs_dim"]) * d + 2 * d * (
        int(cfg["act_dim"]) + 1)


def ssd_train_ops_bytes(cfg: dict, batch: int, seq_len: int,
                        itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) of one update's scans, every Mamba-2 layer,
    forward and backward. Bytes: what the scan has to move whatever its
    form — forward reads ``x`` (inner wide), ``B`` and ``C`` (G N each) at
    ``itemsize`` and the step sizes (H, float32) and writes ``y`` (inner);
    backward reads them with ``y``'s cotangent and writes the four
    cotangents: three passes over ``2 inner + 2 G N`` columns and over the
    step sizes. The state never leaves the chip's fast memory in the least
    form, so no byte of it counts."""
    heads, _, state, groups, inner = mamba_widths(cfg)
    layers = layer_kinds(cfg).count("mamba2")
    tokens = batch * seq_len
    ops = 3 * ssd_fwd_flops(cfg) * tokens * layers
    row = (2 * inner + 2 * groups * state) * itemsize + heads * 4
    return ops, 3 * row * tokens * layers


def held_grouped_matmul_train_ops_bytes(held_slots: float, expert_layers: int,
                                        held: int, d_model: int,
                                        expert_ff: int, itemsize: int = 2
                                        ) -> tuple[float, float]:
    """``flops_lfm2.held_grouped_matmul_train_ops_bytes`` for experts of
    TWO stacks: per row two matmuls of ``2 d ff`` forward and the input and
    weight gradient of each; per matmul the row operand, the result and the
    held stack once."""
    ops = 2 * 3 * 2 * held_slots * d_model * expert_ff
    rows = held_slots * (d_model + expert_ff)
    stacks = expert_layers * held * d_model * expert_ff
    return ops, 6 * (rows + stacks) * itemsize
