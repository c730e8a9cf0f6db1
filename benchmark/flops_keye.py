"""Operations and bytes of ``keye-vl2-policy``'s layers, from their shapes
alone (beside ``flops.py``, ``flops_moe.py``, ``flops_lfm2.py``,
``flops_smallthinker.py``, ``flops_nemotron.py`` and ``flops_qwen3next.py``,
which later PRs do not edit; the same rules: matmul terms only, 2 x
multiply-adds, forward + backward = 3 x forward, nothing recomputed counts).

Every layer is sparse attention then experts. A layer's attention has three
parts, counted as the WORK THE FUNCTION NEEDS, whatever a masked-dense form
executes beside it:

* **the indexer**: ``Hi`` heads of ``Di`` over one key head, a score for
  every causal (query, key) pair — ``2 Hi Di`` operations a pair (2,048 at 16
  x 64) — and its projections (``d -> Hi Di + Di + Hi``). Its backward is the
  KL loss's: the loss reads the scores of the KEPT pairs only, so the two
  products of the backward are counted over those;
* **the selection**: compares, no matmul term: nothing counted;
* **the attention over the kept pairs**: ``QK^T`` and ``PV``, ``2 x 2 x H
  hd`` operations a KEPT pair (16,384 at 32 x 128), ``topk`` keys a query
  past the first ``topk`` rows: 31,458,304 pairs of the 134,225,920 causal
  ones at T 16,384 (23.4%).

The experts: the router over all of ``published.num_experts``, the held
experts at even routing (``flops_lfm2.held_slots_per_token``: one token-slot
a token and layer at 16 of 128, top-8); no shared expert.
"""

from __future__ import annotations

from benchmark import flops_lfm2


def causal_pairs(seq_len: int) -> int:
    return seq_len * (seq_len + 1) // 2


def kept_pairs(seq_len: int, topk: int) -> int:
    """(query, key) pairs of one sequence after the selection: query ``t``
    keeps ``min(t + 1, topk)`` keys."""
    first = min(seq_len, topk)
    return causal_pairs(first) + (seq_len - first) * topk


def indexer_widths(cfg: dict) -> tuple[int, int, int]:
    """(Hi, Di, topk)."""
    sa = cfg["sa_config"]
    if int(sa["indexer_num_kv_heads"]) != 1:
        raise ValueError("the indexer is written for ONE key head")
    return (int(sa["indexer_num_heads"]), int(sa["indexer_head_dim"]),
            int(sa["topk"]))


def index_proj_fwd_flops(cfg: dict) -> int:
    """One token through the indexer's three projections."""
    hi, di, _ = indexer_widths(cfg)
    return 2 * int(cfg["hidden_size"]) * (hi * di + di + hi)


def index_pair_flops(cfg: dict) -> int:
    """One (query, key) pair's index score: a ``Di``-wide product a head."""
    hi, di, _ = indexer_widths(cfg)
    return 2 * hi * di


def attention_proj_fwd_flops(cfg: dict) -> int:
    """q and output projections (``d x H hd`` each), k and v (``d x Hkv
    hd`` each)."""
    d, hd = int(cfg["hidden_size"]), int(cfg["head_dim"])
    return 2 * (2 * d * int(cfg["num_attention_heads"]) * hd
                + 2 * d * int(cfg["num_key_value_heads"]) * hd)


def attention_pair_flops(cfg: dict) -> int:
    """One kept pair through every query head: ``QK^T`` and ``PV``."""
    return 4 * int(cfg["num_attention_heads"]) * int(cfg["head_dim"])


def experts_fwd_flops(cfg: dict) -> float:
    """One token through one expert layer as this chip runs it: the router
    over all the model's experts and the held experts at even routing."""
    d = int(cfg["hidden_size"])
    n_experts = int(cfg["published"]["num_experts"])
    slots = flops_lfm2.held_slots_per_token(
        int(cfg["num_experts_per_tok"]), int(cfg["num_experts"]), n_experts)
    return 2 * d * n_experts + slots * flops_lfm2.swiglu_fwd_flops(
        d, int(cfg["moe_intermediate_size"]))


def keye_fwd_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward operations a token of ``keye-vl2-policy`` as configured: the
    projections, the index scores over the causal pairs, the attention over
    the kept pairs and the experts a layer; the observation embedding and
    the heads as ``flops.transformer_fwd_flops`` counts them."""
    d = int(cfg["hidden_size"])
    topk = indexer_widths(cfg)[2]
    layer = (attention_proj_fwd_flops(cfg) + index_proj_fwd_flops(cfg)
             + index_pair_flops(cfg) * causal_pairs(seq_len) / seq_len
             + attention_pair_flops(cfg) * kept_pairs(seq_len, topk)
             / seq_len + experts_fwd_flops(cfg))
    return (int(cfg["num_hidden_layers"]) * layer
            + 2 * int(cfg["obs_dim"]) * d + 2 * d * (int(cfg["act_dim"]) + 1))


def index_train_ops_bytes(cfg: dict, batch: int, seq_len: int,
                          itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) of one update's indexers, every layer, forward
    and backward: the projections three times over, the scores of every
    causal pair once and the backward's two products over the kept pairs.
    Bytes: the normed rows in, ``qi``, ``ki`` and ``w`` written by the
    projections and read by the scores, forward and backward, their three
    cotangents, and the selection's result at 4 bytes a kept pair."""
    hi, di, topk = indexer_widths(cfg)
    layers, tokens = int(cfg["num_hidden_layers"]), batch * seq_len
    kept = batch * kept_pairs(seq_len, topk)
    ops = (3 * index_proj_fwd_flops(cfg) * tokens
           + index_pair_flops(cfg) * (batch * causal_pairs(seq_len)
                                      + 2 * kept))
    row = int(cfg["hidden_size"]) + 5 * (hi * di + di + hi)
    return layers * ops, layers * (row * tokens * itemsize + 4 * kept)


def sparse_attn_train_ops_bytes(cfg: dict, batch: int, seq_len: int,
                                kept_share: float | None = None,
                                itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) of one update's attention over the KEPT pairs,
    every layer, forward and backward (six products where the forward has
    two). ``kept_share``: the kept pairs over the causal ones as the run
    itself counted them (None: from the shapes). Bytes: q, the output, its
    cotangent and q's at ``H hd`` a token, k, v and their cotangents at
    ``Hkv hd`` (k and v are not repeated for the heads of a group), q, k
    and v read once more by the backward."""
    pairs = batch * (kept_pairs(seq_len, indexer_widths(cfg)[2])
                     if kept_share is None
                     else kept_share * causal_pairs(seq_len))
    hd = int(cfg["head_dim"])
    row = (5 * int(cfg["num_attention_heads"])
           + 6 * int(cfg["num_key_value_heads"])) * hd
    layers = int(cfg["num_hidden_layers"])
    return (layers * 3 * attention_pair_flops(cfg) * pairs,
            layers * row * batch * seq_len * itemsize)
