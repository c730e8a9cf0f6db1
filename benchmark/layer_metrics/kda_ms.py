"""Device time per update of the KDA layers' rule — the delta rule under a
decay a key lane —, forward and backward (the backward makes the forward's
tiles, the lane-wise pair weights, the in-chunk solve and the chunk-start
states again: that time is in it): the operations under the program's scope
``relayrl_kda`` (``relayrl_tpu/ops/kda.py``) — ``benchmark/scope_table.py``.
The projections, the low-rank paths, the convolution, the L2 norms and the
gated norm round it carry other names and are not in it. None where the trace
holds no module metadata or the program has no such scope."""

from benchmark import scope_table

SCOPE = "relayrl_kda"


def read(run):
    return scope_table.ms_per_update(run, SCOPE)
