"""The attribution's coverage: of the device time inside the window's whole
updates (each operation's self time), the share whose ``op_name`` path holds
any ``relayrl_`` name, the kernels' own among them — ``benchmark/scope_table.py``. A later change
that adds an unnamed part to the update shows here; what is left is listed
by name in the result line's ``notes.scope_table.unscoped``. None where the
trace holds no module metadata."""

from benchmark import scope_table


def read(run):
    return scope_table.scoped_pct(run)
