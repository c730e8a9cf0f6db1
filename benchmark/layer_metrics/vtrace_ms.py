"""Device time per update of IMPALA's V-trace: the ratios, ``delta``, the
reverse ``lax.scan`` over T sequential steps and ``pg_adv``
(``relayrl_vtrace`` round the body of ``ops/vtrace.vtrace``). The scan
counts once: its ``while`` event spans its body's events on the operations
line and counts for what is left of it beside them, the loop's own time
between its steps' operations — ``benchmark/scope_table.py``.
None where the trace holds no module metadata or the program has no such
scope."""

from benchmark import scope_table

SCOPE = "relayrl_vtrace"


def read(run):
    return scope_table.ms_per_update(run, SCOPE)
