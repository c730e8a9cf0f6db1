"""Device time per update of the sparse-attention layers' indexers, forward
and backward: the operations under the program's scope ``relayrl_index``
(``relayrl_tpu/models/layers/sparse_attention.py``: the indexer's three
projections, its key's LayerNorm and RoPE; ``relayrl_tpu/ops/sparse_attn.py``:
the index scores of every computed pair, the selection's threshold search,
both made again in the backward, and the backward of the indexer's loss
through the scores) — ``benchmark/scope_table.py``. The KL loss itself is
under ``relayrl_loss``, the attention over the selected keys under
``relayrl_sparse_attn``. None where the trace holds no module metadata or
the program has no such scope."""

from benchmark import scope_table

SCOPE = "relayrl_index"


def read(run):
    return scope_table.ms_per_update(run, SCOPE)
