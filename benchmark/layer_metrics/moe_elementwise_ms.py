"""Device time per update of the expert layer's element-wise passes: what
lies between the grouped matmuls and after them — activation x up, the
weighting and the sum over k, the float32 -> bf16 casts of the expert
stacks and of the rows, the layer's norm and residual, the sums of
cotangents — forward and backward (``relayrl_moe_elementwise`` in
``models/moe.py``; the grouped matmuls inside it keep their own innermost
names and are not in it) — ``benchmark/scope_table.py``. None where the
trace holds no module metadata or the program has no such scope."""

from benchmark import scope_table

SCOPE = "relayrl_moe_elementwise"


def read(run):
    return scope_table.ms_per_update(run, SCOPE)
