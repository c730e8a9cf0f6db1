"""Device time per update of the KDA mixer's convolution: the taps and the
SiLU over ``[q | k | v]``, forward and backward — the operations under the
program's scope ``relayrl_kda_conv`` (``relayrl_tpu/models/layers/kda.py``
through ``ops/conv.py``: the Pallas kernels on a TPU where the shape tiles,
plain XLA elsewhere) — ``benchmark/scope_table.py``. None where the trace
holds no module metadata or the program has no such scope."""

from benchmark import scope_table

SCOPE = "relayrl_kda_conv"


def read(run):
    return scope_table.ms_per_update(run, SCOPE)
