"""Device time per update of the Mamba-2 layers' state-space scan, forward
and backward (the backward makes the forward's score tiles and per-chunk
states again: that time is in it): the operations under the program's scope
``relayrl_ssd`` (``relayrl_tpu/ops/ssd.py`` — the products inside a chunk,
the chunks' own states, the pass across the chunks and the carried state's
part of the output, plain XLA) — ``benchmark/scope_table.py``. The
projections, the convolution, the gate and the norm round it carry other
names and are not in it. None where the trace holds no module metadata or
the program has no such scope."""

from benchmark import scope_table

SCOPE = "relayrl_ssd"


def read(run):
    return scope_table.ms_per_update(run, SCOPE)
