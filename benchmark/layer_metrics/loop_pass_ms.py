"""Device time per update of ONE pass of a looped trunk's stack: the
operations whose ``op_name`` path holds the pass scope the program opens
round a pass (``relayrl_loop_pass``, ``relayrl_tpu/models/transformer.py``:
the body of the scan over the passes, forward and transposed) — the passes'
forward, the forward the block checkpoint runs again and the backward, the
final norm between passes with them — over the configuration's
``total_ut_steps``. What one round of this pipeline stage costs: the number a
ring schedule over the model's stages is planned from. Self time inside the
window's whole updates (``benchmark/loop_trace.py``; a scan's ``while`` event
counts for what its body's events leave of it, and carries the name too).
None where the trace holds no module metadata or the program opens no such
scope (an un-looped trunk; the parent of the PR that added the loop)."""

from benchmark import loop_trace


def read(run):
    total = loop_trace.ms_where(run, lambda path: loop_trace.PASS in path)
    passes = int(run.config.get("total_ut_steps", 0))
    return None if total is None or not passes else total / passes
