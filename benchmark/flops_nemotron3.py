"""Operations and bytes of ``nemotron3-super-policy``'s layers, from their
shapes alone (beside ``flops.py`` and the other ``flops_*.py``, which later
PRs do not edit; the same rules: matmul terms only, 2 x multiply-adds,
forward + backward = 3 x forward, nothing recomputed counts).

Every count is at the sizes AS RUN — one chip's share of a layer — which is
what the configuration file's keys hold: ``mamba_num_heads`` 32 of the
published 128 and ``n_groups`` 2 of 8, ``num_attention_heads`` 8 over
``num_key_value_heads`` 1 of 32 over 2, ``n_routed_experts`` 8 held of
``published.n_routed_experts`` 512. A count at the published head numbers
would read the scan's roofline share four times too high.

``M`` and ``*`` are ``flops_nemotron.py``'s (the same mixers at other
sizes). ``E`` is new: the routed experts work in a latent
``moe_latent_size`` wide — a token and layer: the router over all the
experts (``2 d E``), the two latent projections (``2 d L`` each), this
chip's held experts at EVEN routing (``flops_lfm2.held_slots_per_token``:
22 x 8 / 512 = 0.34375 token-slots, each two matmuls ``L x ff``) and the
shared expert whole at the stream's width (two matmuls ``d x shared``).
"""

from __future__ import annotations

from benchmark import flops_lfm2, flops_nemotron, flops_smallthinker

layer_kinds = flops_nemotron.layer_kinds
mamba_widths = flops_nemotron.mamba_widths
# (operations, bytes) of one update's scans: it reads ``mamba_num_heads`` and
# ``n_groups``, which this configuration's file gives as run — the HELD ones
ssd_train_ops_bytes = flops_nemotron.ssd_train_ops_bytes


def latent_proj_fwd_flops(cfg: dict) -> int:
    """The down- and the up-projection of one expert layer, a token."""
    return 2 * 2 * int(cfg["hidden_size"]) * int(cfg["moe_latent_size"])


def held_slots_per_token(cfg: dict) -> float:
    return flops_lfm2.held_slots_per_token(
        int(cfg["num_experts_per_tok"]), int(cfg["n_routed_experts"]),
        int(cfg["published"]["n_routed_experts"]))


def expert_layer_fwd_flops(cfg: dict) -> dict:
    """One ``E`` layer's forward operations a token, by part."""
    d, latent = int(cfg["hidden_size"]), int(cfg["moe_latent_size"])
    return {
        "router": 2 * d * int(cfg["published"]["n_routed_experts"]),
        "latent": latent_proj_fwd_flops(cfg),
        "held": held_slots_per_token(cfg) * flops_nemotron.relu2_fwd_flops(
            latent, int(cfg["moe_intermediate_size"])),
        "shared": int(cfg["n_shared_experts"])
        * flops_nemotron.relu2_fwd_flops(
            d, int(cfg["moe_shared_expert_intermediate_size"])),
    }


def fwd_flops_by_kind(cfg: dict, seq_len: int) -> dict:
    """Forward operations a token of one layer of each kind."""
    return {
        "mamba2": (flops_nemotron.mamba_proj_fwd_flops(cfg)
                   + flops_nemotron.ssd_fwd_flops(cfg)),
        "attention": flops_smallthinker.attention_fwd_flops(
            int(cfg["hidden_size"]), int(cfg["num_attention_heads"]),
            int(cfg["num_key_value_heads"]), int(cfg["head_dim"]), seq_len,
            None),
        "ffn": sum(expert_layer_fwd_flops(cfg).values()),
    }


def nemotron3_fwd_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward operations a token of ``nemotron3-super-policy`` as
    configured: each layer by its kind; the observation embedding and the
    heads as ``flops.transformer_fwd_flops`` counts them."""
    d = int(cfg["hidden_size"])
    per_kind = fwd_flops_by_kind(cfg, seq_len)
    total = sum(per_kind[kind] for kind in layer_kinds(cfg))
    return total + 2 * int(cfg["obs_dim"]) * d + 2 * d * (
        int(cfg["act_dim"]) + 1)


def held_grouped_matmul_train_ops_bytes(cfg: dict, held_slots: float):
    """(operations, bytes) of one update's grouped matmuls over the
    ``held_slots`` token-slots the run itself counted (all expert layers):
    two stacks an expert, rows ``moe_latent_size`` wide — K 1024, N 2688 —
    not the stream's 4096."""
    return flops_nemotron.held_grouped_matmul_train_ops_bytes(
        held_slots, layer_kinds(cfg).count("ffn"),
        int(cfg["n_routed_experts"]), int(cfg["moe_latent_size"]),
        int(cfg["moe_intermediate_size"]))

