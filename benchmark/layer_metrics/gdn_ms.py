"""Device time per update of the linear-attention layers' gated delta rule,
forward and backward (the backward makes the forward's tiles, the in-chunk
solve and the chunk-start states again: that time is in it): the operations
under the program's scope ``relayrl_gdn`` (``relayrl_tpu/ops/gdn.py`` — the
products inside a chunk, the solve, the pass across the chunks with the
carried state, plain XLA) — ``benchmark/scope_table.py``. The projections,
the convolution, the L2 norms, the gated norm round it carry other names and
are not in it. None where the trace holds no module metadata or the program
has no such scope."""

from benchmark import scope_table

SCOPE = "relayrl_gdn"


def read(run):
    return scope_table.ms_per_update(run, SCOPE)
