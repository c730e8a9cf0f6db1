"""Device time per update by what an operation's WHOLE ``op_name`` path holds,
for the readers of a looped, checkpointed trunk (``loop_pass_ms``,
``loop_recompute_pct``).

``scope_table`` gives an operation to the innermost ``relayrl_`` name of its
path, which is the part (``relayrl_ffn``) and never the pass round it or the
transform above it. The two readers here ask the other question — does the
path hold a substring anywhere — of the same reduction: ``scope_trace.of``'s
map from instruction to ``op_name`` (a fusion under its root's), and
``scope_table.reduce_ops``' self time per instruction inside the window's
whole updates (a ``while`` less its body, nothing twice).

A run without a trace, a trace without the module's metadata, or a program
whose paths hold no such substring (the parent of the PR that added the
loop): ``None``.
"""

from __future__ import annotations

import os

from benchmark import program_trace, scope_table, scope_trace, trace_reduce

# the trunk's scope round a pass of its stack (relayrl_tpu/ops/scopes.py): the
# learner's passes are one body of a scan, so the name carries no number
PASS = "relayrl_loop_pass"
# what jax.checkpoint names the forward it runs again in the backward
# (jax/_src/ad_checkpoint.py; tests/test_device_scopes.py pins it)
RECOMPUTED = "rematted_computation"


def of(run) -> dict | None:
    """``{"self_ms": <all operations>, "by_path": [(op_name, ms), ...]}`` per
    whole update of the traced sub-window, once a run."""
    if not hasattr(run, "_loop_trace"):
        run._loop_trace = None
        t, s = program_trace.of(run), scope_trace.of(run)
        if t and t["updates"] and s:
            path = trace_reduce.newest_xplane(
                os.path.join(run.run_dir, "trace"))
            with open(path, "rb") as f:
                protos = scope_trace.module_protos(f.read())
            whole = set()
            for module, proto in protos.items():
                if program_trace.UPDATE_MODULE in module:
                    whole |= {
                        name for name, (_n, opcode, _f)
                        in scope_table.instruction_names(proto).items()
                        if opcode not in scope_table.CONTAINERS}
            per = scope_table.reduce_ops(s["ops"], t["updates"], whole)
            if per:
                run._loop_trace = {
                    "self_ms": sum(per.values()),
                    "by_path": [(s["scopes"].get(name, ""), ms)
                                for name, ms in per.items()]}
    return run._loop_trace


def ms_where(run, holds) -> float | None:
    """Self time per update of the operations whose path ``holds(path)``;
    None where no operation's does."""
    table = of(run)
    if not table:
        return None
    found = [ms for path, ms in table["by_path"] if holds(path)]
    return sum(found) if found else None
