"""Device time per update of the trunk's dense FFNs: ``ln_mlp``, up / gate /
activation / down and the residual, forward and backward (``relayrl_ffn``
round the dense branch of ``models/transformer._block_ffn``). A layer whose
FFN is the expert layer has none — ``benchmark/scope_table.py``. None where
the trace holds no module metadata or the program has no such scope."""

from benchmark import scope_table

SCOPE = "relayrl_ffn"


def read(run):
    return scope_table.ms_per_update(run, SCOPE)
