"""Device time per update of the Mamba-2 mixer's convolution: the taps, the
bias and the SiLU over ``xBC``, forward and backward — the operations under
the program's scope ``relayrl_mamba_conv``
(``relayrl_tpu/models/transformer._mamba_conv``, plain XLA) —
``benchmark/scope_table.py``. None where the trace holds no module metadata
or the program has no such scope."""

from benchmark import scope_table

SCOPE = "relayrl_mamba_conv"


def read(run):
    return scope_table.ms_per_update(run, SCOPE)
