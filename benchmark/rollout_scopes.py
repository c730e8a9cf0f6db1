"""Device time of the fused rollout by the program's scopes: ``scope_table``'s
reduction over the rollout's module instead of the learner's update.

``scope_table.of`` counts the operations of ``jit_<algo>_update``; a rollout
cell runs no update, its one program is the host's jitted window producer
(``runtime/anakin.make_fused_rollout``: ``jit_lane_rollout``, one execution a
dispatch, ``unroll_length`` iterations of its ``while`` each). This file
takes that module's ``HloProto`` from the same metadata plane, the
operations that start inside its executions lying wholly in ``host:window``,
and hands both to ``scope_table``'s own functions (``instruction_names``,
``reduce_ops``, ``table_of``: self time, nothing twice, innermost
``relayrl_`` name, a fusion under its root's). The table is ms a DISPATCH;
:func:`ms_per_scan_step` divides by the traffic's ``unroll_length``.

``run.notes["rollout_scope_table"]`` carries it on the result line. A run
without a trace, a trace without the module's metadata, or a program
without the scope (the parent of the PR that added it): ``None``.
"""

from __future__ import annotations

import os

from benchmark import program_trace, scope_table, scope_trace, trace_reduce

ROLLOUT_MODULE = "_rollout("     # jit_lane_rollout(<fingerprint>)


def of(run) -> dict | None:
    if not hasattr(run, "_rollout_scope_table"):
        run._rollout_scope_table = None
        trace_dir = os.path.join(run.run_dir, "trace")
        path = trace_reduce.newest_xplane(trace_dir)
        reduced = program_trace.of(run)
        if not run.trace or path is None or not reduced:
            return None
        w0, w1 = reduced["window_ns"] or (float("-inf"), float("inf"))
        events = program_trace.load_events(trace_dir)
        dispatches = sorted(
            [s, d] for mods in events["modules"].values()
            for name, s, d in mods
            if ROLLOUT_MODULE in name and s >= w0 and s + d <= w1)
        with open(path, "rb") as f:
            protos = scope_trace.module_protos(f.read())
        names: dict = {}
        for module, proto in protos.items():
            if ROLLOUT_MODULE in module:
                names.update(scope_table.instruction_names(proto))
        if not dispatches or not names:
            return None
        import jax

        ops = []
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if not plane.name.startswith(trace_reduce.DEVICE_PLANE_PREFIX):
                continue
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    ops += [[scope_trace.instruction_name(ev.name),
                             float(ev.start_ns), float(ev.duration_ns)]
                            for ev in line.events]
        whole = {name for name, (_n, opcode, _f) in names.items()
                 if opcode not in scope_table.CONTAINERS}
        table = scope_table.table_of(
            scope_table.reduce_ops(ops, dispatches, whole), names)
        if table is not None:
            table["dispatches"] = len(dispatches)
            run._rollout_scope_table = table
            run.notes["rollout_scope_table"] = {
                **table, "unscoped": dict(list(table["unscoped"].items())[
                    :scope_table.TOP_UNSCOPED])}
    return run._rollout_scope_table


def ms_per_scan_step(run, scope: str) -> float | None:
    """Device time (self time) a scan step of the operations that count for
    ``scope``; None where the program has no such scope."""
    table = of(run)
    ms = table["scopes"].get(scope) if table else None
    return None if ms is None else ms / int(run.traffic["unroll_length"])
