"""Device time per update of the linear-attention mixer's convolution: the
taps and the SiLU over ``[q | k | v]``, forward and backward — the
operations under the program's scope ``relayrl_gdn_conv``
(``relayrl_tpu/models/transformer._gdn_conv``, plain XLA) —
``benchmark/scope_table.py``. None where the trace holds no module metadata
or the program has no such scope."""

from benchmark import scope_table

SCOPE = "relayrl_gdn_conv"


def read(run):
    return scope_table.ms_per_update(run, SCOPE)
