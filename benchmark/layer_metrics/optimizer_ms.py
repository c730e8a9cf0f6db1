"""Device time per update of the optimizer: the global-norm clip, Adam and
the apply over every parameter (``relayrl_optimizer`` round ``tx.update`` +
``optax.apply_updates`` in ``algorithms/impala.py``) —
``benchmark/scope_table.py``. At least one pass's bandwidth floor, 28 B a
parameter; the transposed casts of bf16 gradients fuse into it (``mixed``).
None where the trace holds no module metadata or the program has no such
scope."""

from benchmark import scope_table

SCOPE = "relayrl_optimizer"


def read(run):
    return scope_table.ms_per_update(run, SCOPE)
