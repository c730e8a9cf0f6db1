"""Device time per update of the gated short convolution's element-wise
part (the taps and the two gate products, forward and backward): the
operations whose ``op_name`` in the compiled update's metadata carries the
program's scope ``relayrl_short_conv`` (``models/transformer._short_conv``),
a fusion counting for the scope of its root — ``benchmark/scope_trace.py``.
The projections on either side are matmul fusions of their own and are not
in it. None where the trace holds no module metadata or the program has no
such scope."""

from benchmark import scope_trace


def read(run):
    return scope_trace.ms_per_update(run, "relayrl_short_conv")
