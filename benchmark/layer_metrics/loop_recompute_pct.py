"""The share of an update's device time spent in the forward that the
trunk's block checkpoint runs a SECOND time, in the backward: the operations
whose ``op_name`` path holds ``rematted_computation`` (what ``jax.checkpoint``
names the computation it makes again; the first forward's paths hold no
``checkpoint`` at all and the backward proper's hold ``checkpoint/`` without
it) over the self time of all operations inside the window's whole updates
(``benchmark/loop_trace.py``; the denominator is ``update_scoped_pct``'s).
Some 25 with nothing kept beside a block's input (a forward is a third of
forward + backward, and runs twice); what a change to what the checkpoint
keeps moves. The model's operations do not count it (``flops_ouro``), so
``mfu_pct`` reads lower by about this share. None where the trace holds no
module metadata or the program checkpoints nothing by that name."""

from benchmark import loop_trace


def read(run):
    again = loop_trace.ms_where(
        run, lambda path: loop_trace.RECOMPUTED in path)
    table = loop_trace.of(run)
    if again is None or not table["self_ms"]:
        return None
    return 100.0 * again / table["self_ms"]
