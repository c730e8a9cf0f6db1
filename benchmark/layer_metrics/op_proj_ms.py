"""Device time per update of the trunk's operators less their kernels: the
layer norm, the q / k / v (or conv in / out) projections, QK-norm, RoPE, the
head reshapes and transposes round the flash calls, the output projection
and its residual, forward and backward (``relayrl_op_proj`` in
``models/transformer.py`` and round the kernels' glue in ``ops/flash.py``).
The flash kernels and the short convolution keep their own names and are
not in it — ``benchmark/scope_table.py``. None where the trace holds no
module metadata or the program has no such scope."""

from benchmark import scope_table

SCOPE = "relayrl_op_proj"


def read(run):
    return scope_table.ms_per_update(run, SCOPE)
