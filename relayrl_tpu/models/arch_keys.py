"""The sequence trunk's arch keys, each declared once with its default.

Plain data and nothing else: :mod:`relayrl_tpu.models.base` computes
``ARCH_PASSTHROUGH_KEYS`` from it, the operators of
:mod:`relayrl_tpu.models.layers` read their settings' defaults from it,
``models/transformer.py`` the block's, and ``transformer_pp_discrete``
refuses what :data:`DECLARED` names. It imports nothing of the package, so
every one of them may import it.
"""

from __future__ import annotations

from typing import Any, Mapping

# What every sequence trunk takes, the pipeline family's GPT-2 shaped one
# included: sizes, the attention backend, the actors' context.
TRUNK_KEYS = ("d_model", "n_layers", "n_heads", "mlp_ratio", "max_seq_len",
              "attention", "attention_block", "actor_context",
              "moe_experts", "moe_top_k", "pp_microbatches")

# The core's own: what kind each layer is (``layer_types``: a name of
# ``layers.LAYER_KINDS`` a layer; ``moe_dense_layers`` leading layers keep
# the dense FFN in a MoE trunk; ``sliding_window`` of the windowed layers)
# and where the positions come from (``positions``: "learned" | "rope" |
# "none"; under "rope", ``rope_layers`` says layer by layer which rotate);
# how often the stack runs (``loop_steps`` passes over ONE parameter tree,
# the final norm's output of a pass the next pass's input; 1: once) and
# whether the learner's forward keeps of a block application its input and
# the flash kernel's output alone and makes the rest again in its backward
# (``block_checkpoint``); what multiplies the embedded observation
# (``embed_multiplier``) and divides the policy logits (``logit_divisor``;
# the value head is no logit and is not divided), 1 where absent: nothing is
# traced; and whether ``init_params`` returns the parameters as a tier that
# only steps the policy holds them (``held_params``: the leaves every use
# casts to the compute type, at that type — ``base.hold_params``).
CORE_KEYS = ("layer_types", "moe_dense_layers", "sliding_window",
             "positions", "rope_theta", "rope_layers",
             "loop_steps", "block_checkpoint",
             "embed_multiplier", "logit_divisor", "held_params")

# What every layer shares (``TransformerBlock``'s fields of the same names).
# With none of them given it is the GPT-2 shaped block: LayerNorm at flax's
# epsilon, biases, a GELU FFN of ``mlp_ratio * d_model``.
BLOCK_KEYS: Mapping[str, Any] = {
    "norm": "layer",                # | "rms"
    "norm_eps": None,               # None: flax's 1e-6
    "norm_zero_centred": False,     # RMSNorm weights as offsets from one
    "norm_sandwich": False,         # a 2nd norm on each half's OUTPUT
    "residual_multiplier": 1.0,     # x + m * half(norm(x)), both halves
    "use_bias": True,
    "ffn": "gelu",                  # | "relu2" | "swiglu" | "reglu"
    "d_ff": None,                   # FFN width; None: mlp_ratio * d_model
    # the expert half (models/moe.py), where a layer's FFN is one
    "moe_d_ff": None,               # one expert's width; None: d_ff
    "moe_norm_topk_prob": True,
    "moe_dispatch": None,           # None: models/moe.py picks
    "moe_router_input": "ffn",      # | "layer": the layer's un-normed input
}

# ``MoEMLP``'s further fields by the arch key that sets each (the block's
# ``moe_kw``); an arch without one leaves the field at ``MoEMLP``'s default.
MOE_KEYS: Mapping[str, str] = {
    "moe_router": "router", "moe_expert_bias": "expert_bias",
    "moe_held": "held", "moe_routed_scaling": "routed_scaling",
    "moe_shared_d_ff": "shared_d_ff",
    "moe_shared_expert_gate": "shared_gate",
    # the routed experts' width where it is narrower than d_model: one
    # down-projection before the sort, one up-projection after the un-sort
    "moe_latent": "latent"}

# An operator's own settings (``layers.OPERATORS``' names; a block's
# ``cfg``). "none", the FFN alone, has none.
OPERATOR_KEYS: Mapping[str, Mapping[str, Any]] = {
    "attention": {
        "n_kv_heads": None,         # grouped-query k/v heads; None: n_heads
        "head_dim": None,           # None: d_model // n_heads
        "qk_norm": False,           # | True (the projection) | "head"
        "rope_share": 1.0,          # the share of a head's lanes RoPE turns
        "attn_gate": False,         # q twice as wide, the 2nd half a gate
        "attn_scale": None,         # of the scores; None: head_dim ** -0.5
    },
    "conv": {"conv_taps": 3},
    "mamba2": {
        "mamba_heads": 8, "mamba_head_dim": 64, "mamba_state": 128,
        "mamba_groups": 1, "mamba_conv_taps": 4, "mamba_chunk": 128,
    },
    "gdn": {
        "gdn_key_heads": 4, "gdn_value_heads": 8, "gdn_key_dim": 64,
        "gdn_value_dim": 64, "gdn_conv_taps": 4, "gdn_chunk": 64,
    },
    # the delta rule under a decay a key lane; keys and values one width
    "kda": {
        "kda_heads": 8, "kda_head_dim": 64, "kda_conv_taps": 4,
        "kda_chunk": 64,
    },
    # keys and values expanded from one compressed row a token
    "latent_attention": {
        "kv_lora_rank": 64, "qk_nope_head_dim": 32, "qk_rope_head_dim": 16,
        "v_head_dim": 32,
        "q_lora_rank": None,        # the query's own low rank; None: q_proj
        "rope_interleave": False,   # RoPE pairs lanes (2i, 2i + 1)
    },
    # "attention" (OPERATOR_BASE) with a learned indexer in front of it
    "sparse_attention": {
        "index_heads": 4, "index_head_dim": 32,  # over ONE key head
        "index_topk": 2048,         # the keys a query attends
        "index_chunk": 512,         # the query tile; no part of the model
    },
    "none": {},
}

# An operator that is another with more: its settings hold the other's too.
OPERATOR_BASE: Mapping[str, str] = {"sparse_attention": "attention"}

# Everything but TRUNK_KEYS: what only ``TransformerCore``'s trunks take.
DECLARED = (CORE_KEYS + tuple(BLOCK_KEYS) + tuple(MOE_KEYS)
            + tuple(k for keys in OPERATOR_KEYS.values() for k in keys))


def settings(keys: Mapping[str, Any], arch: Mapping[str, Any]) -> dict:
    """``keys``' values as ``arch`` gives them, else their defaults."""
    return {k: arch.get(k, default) for k, default in keys.items()}
