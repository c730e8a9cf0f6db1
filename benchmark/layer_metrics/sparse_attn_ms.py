"""Device time per update of the attention over the keys the indexers
selected, forward and backward, with the heads' mean probability ``p^`` that
the indexers' loss reads: the operations under the program's scope
``relayrl_sparse_attn`` (``relayrl_tpu/ops/sparse_attn.py``: the scores of
every computed pair, the masked softmax, the product with the values, all
made again in the backward) — ``benchmark/scope_table.py``. The main
projections are under ``relayrl_op_proj``, the indexer under
``relayrl_index``. None where the trace holds no module metadata or the
program has no such scope."""

from benchmark import scope_table

SCOPE = "relayrl_sparse_attn"


def read(run):
    return scope_table.ms_per_update(run, SCOPE)
