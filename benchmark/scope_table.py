"""Device time per update by the program's scopes, all scopes in one pass:
what the readers of the ``relayrl_*`` parts share, and the whole table when
run by hand.

    python benchmark/scope_table.py <run directory>

``scope_trace.of(run)`` gives the traced window's operations (instruction
name, start, duration); the update's ``HloProto`` in the same xplane gives
every instruction's ``op_name``. :func:`reduce_ops` sums the operations ONCE
for all scopes (a reader a scope would walk a 16,384-step scan's events once
more each):

* **only whole updates count**, as in ``program_trace``: an operation that
  starts inside an update module lying wholly in the window;
* **self time, nothing twice**: the operations line lists a loop's body
  operations beside the loop's own ``while`` event, which spans them
  (``scope_trace.ms_per_update`` sums both and counts a scan twice). An
  event that wholly contains the event that starts next on the line is a
  container, and counts for its duration LESS the operations inside it:
  what a loop spends between its body's operations (most of V-trace's
  scan: PERF.md section 6, PR 37) is the loop's. Only a ``while``, ``call`` or
  ``conditional`` can be one where the module says what an instruction is:
  a kernel that an asynchronous copy's event falls inside stays whole. One
  chip, one line: the cells' planes are not told apart;
* an operation counts for the **innermost ``relayrl_`` name** of its path
  (``.../relayrl_op_proj/...`` or a kernel's own ``relayrl_flash_fwd``); one
  with no such name is ``unscoped`` and listed by ``<hlo name>/<opcode>``;
* **a fusion counts for its root's name** (``scope_trace``'s rule). Where
  the root carries none, for the fusion instruction's own, then for the last
  fused instruction that has one: XLA:TPU rewrites the metadata of a
  gather's expansion to a bare ``gather`` — the expert layer's row gathers —
  and the root of a fusion with several outputs is a tuple.

``mixed`` is the error bar of the root rule: the time of the fusions whose
fused instructions carry more than one such name, listed under the name
that got the time (:func:`instruction_names` walks the fused computations).

:func:`of` does it once a run and leaves the table in
``run.notes["scope_table"]``, which the result line prints: a run removes
its trace, so that is where a chip run's table is read. A trace without the
module's metadata, or a run without a trace: ``None``, and every reader
returns ``None``; so does a reader whose scope the program does not have
(the parent of the PR that added it).
"""

from __future__ import annotations

import os
import re
import sys
import types

import numpy as np

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmark import program_trace, scope_trace, trace_reduce

NAME = re.compile(r"relayrl_\w+")
UNSCOPED = "unscoped"
TOP_UNSCOPED = 12


def scope_name(path: str) -> str | None:
    """The innermost ``relayrl_`` name of an ``op_name`` path."""
    found = NAME.findall(path or "")
    return found[-1] if found else None


CONTAINERS = ("while", "call", "conditional")


def self_times(start, dur, may_contain=None):
    """Each event's duration less the events inside it (numpy arrays, ns).
    Sorted by start, longer first on a tie, a container's first child
    follows it directly, so one comparison an event finds every container,
    nested ones too (``may_contain``: a mask of the events that can be
    one; None: all). What lies inside a container is a run of the sorted
    order: its leaves by a prefix sum, its nested containers' own self
    times innermost first."""
    order = np.lexsort((-dur, start))
    s, d = start[order], dur[order]
    e = s + d
    holds = np.zeros(len(s), bool)
    holds[:-1] = (s[1:] < e[:-1]) & (e[1:] <= e[:-1])
    if may_contain is not None:
        holds &= may_contain[order]
    at = np.flatnonzero(holds)
    own = d.copy()
    if at.size:
        leaf_before = np.concatenate(([0.0], np.cumsum(np.where(holds, 0.0,
                                                                 d))))
        after = np.searchsorted(s, e[at], side="left")   # first event past
        inside = leaf_before[after] - leaf_before[at + 1]
        nested_to = np.searchsorted(at, after, side="left")
        later = np.zeros(at.size + 1)    # containers' self times from m on
        for m in range(at.size - 1, -1, -1):
            own[at[m]] = d[at[m]] - inside[m] - (later[m + 1]
                                                 - later[nested_to[m]])
            later[m] = later[m + 1] + own[at[m]]
    out = np.empty(len(s))
    out[order] = own
    return out


def reduce_ops(ops, updates, whole=()) -> dict:
    """``{instruction: ms per update}``: the self time of the operations of
    ``ops`` (``[[instruction, start_ns, dur_ns], ...]``) that start inside
    ``updates`` (``[[start_ns, dur_ns], ...]``). ``whole``: the
    instructions known to contain no other (every other one can)."""
    if not ops or not updates:
        return {}
    code: dict = {}
    which = np.fromiter((code.setdefault(o[0], len(code)) for o in ops),
                        np.int64, len(ops))
    start = np.fromiter((o[1] for o in ops), np.float64, len(ops))
    dur = np.fromiter((o[2] for o in ops), np.float64, len(ops))
    may = np.fromiter((name not in whole for name in code), bool,
                      len(code))[which]
    spans = sorted((s, s + d) for s, d in updates)
    u0, u1 = (np.array(edge) for edge in zip(*spans))
    at = np.searchsorted(u0, start, side="right") - 1
    keep = (at >= 0) & (start < u1[np.clip(at, 0, None)])
    per = np.bincount(which[keep], weights=self_times(start, dur, may)[keep],
                      minlength=len(code)) / len(updates) / 1e6
    return {name: float(per[i]) for name, i in code.items() if per[i] > 0}


def instruction_names(hlo_proto) -> dict:
    """``{instruction: (name | None, opcode, names fused)}`` over every
    computation of one ``HloProto``: the ``relayrl_`` name the instruction
    counts for (module docstring), its opcode and, of a fusion, the set of
    names its fused instructions carry. Field numbers as in
    ``scope_trace.instruction_scopes``."""
    module = scope_trace._first(hlo_proto, 1)
    computations: dict = {}   # id -> (root's name, last name, all names)
    instructions = []
    for number, comp in scope_trace.fields(module or b""):
        if number != 3:
            continue
        comp_id = root_id = None
        own = []
        for n, value in scope_trace.fields(comp):
            if n == 5:
                comp_id = value
            elif n == 6:
                root_id = value
            elif n == 2:
                name = opcode = ""
                found = ins_id = called = None
                for m, v in scope_trace.fields(value):
                    if m == 1:
                        name = scope_trace._text(v)
                    elif m == 2:
                        opcode = scope_trace._text(v)
                    elif m == 7:
                        found = scope_name(scope_trace._text(
                            scope_trace._first(v, 2)))
                    elif m == 35:
                        ins_id = v
                    elif m == 38 and called is None:
                        called = v if isinstance(v, int) else (
                            scope_trace._packed_varints(v) or [None])[0]
                own.append((name, opcode, found, ins_id, called))
        named = [found for _n, _o, found, _i, _c in own if found]
        computations[comp_id] = (
            next((found for _n, _o, found, ins_id, _c in own
                  if ins_id == root_id), None),
            named[-1] if named else None, set(named))
        instructions += own
    out = {}
    for name, opcode, found, _id, called in instructions:
        fused: set = set()
        if opcode == "fusion" and called in computations:
            root, last, fused = computations[called]
            found = root or found or last
        out[name] = (found, opcode, fused)
    return out


def table_of(per_instruction: dict, names: dict) -> dict | None:
    """``{"self_ms", "scoped_ms", "scopes": {name: ms}, "mixed": {name:
    ms}, "unscoped": {<hlo name>/<opcode>: ms}}`` from the self time by
    instruction and :func:`instruction_names`' map."""
    if not per_instruction:
        return None
    scopes: dict = {}
    mixed: dict = {}
    unscoped: dict = {}
    for instruction, ms in per_instruction.items():
        name, opcode, fused = names.get(instruction, (None, "?", ()))
        if name is None:
            base = re.sub(r"([._]\d+)+$", "", instruction) or instruction
            key = f"{base}/{opcode}"
            unscoped[key] = unscoped.get(key, 0.0) + ms
        else:
            scopes[name] = scopes.get(name, 0.0) + ms
        if len(fused) > 1:
            mixed[name or UNSCOPED] = mixed.get(name or UNSCOPED, 0.0) + ms

    def ranked(d: dict) -> dict:
        return dict(sorted(d.items(), key=lambda kv: -kv[1]))

    return {"self_ms": sum(per_instruction.values()),
            "scoped_ms": sum(scopes.values()), "scopes": ranked(scopes),
            "mixed": ranked(mixed), "unscoped": ranked(unscoped)}


def of(run) -> dict | None:
    """:func:`table_of` this run's traced sub-window, once a run; what the
    result line carries of it goes to ``run.notes["scope_table"]``."""
    if not hasattr(run, "_scope_table"):
        run._scope_table = None
        t, s = program_trace.of(run), scope_trace.of(run)
        if t and t["updates"] and s:
            path = trace_reduce.newest_xplane(
                os.path.join(run.run_dir, "trace"))
            with open(path, "rb") as f:
                protos = scope_trace.module_protos(f.read())
            names: dict = {}
            for module, proto in protos.items():
                if program_trace.UPDATE_MODULE in module:
                    names.update(instruction_names(proto))
            whole = {name for name, (_n, opcode, _f) in names.items()
                     if opcode not in CONTAINERS}
            table = table_of(reduce_ops(s["ops"], t["updates"], whole),
                             names)
            if table is not None:
                table["updates"] = len(t["updates"])
                run._scope_table = table
                run.notes["scope_table"] = {
                    **table, "unscoped": dict(
                        list(table["unscoped"].items())[:TOP_UNSCOPED])}
    return run._scope_table


# -- what the readers share --------------------------------------------------

def ms_per_update(run, scope: str) -> float | None:
    """Device time per update of the operations that count for ``scope``
    (self time); None where the program has no such scope."""
    table = of(run)
    return table["scopes"].get(scope) if table else None


def scoped_pct(run) -> float | None:
    """Share of the updates' device time under any ``relayrl_`` name, the
    kernels' own among them."""
    table = of(run)
    if not table or not table["self_ms"]:
        return None
    return 100.0 * table["scoped_ms"] / table["self_ms"]


def main(argv) -> int:
    table = of(types.SimpleNamespace(trace=True, run_dir=argv[1], notes={}))
    if table is None:
        print("no traced update with module metadata under", argv[1])
        return 1
    total = table["self_ms"]
    print(f"{table['updates']} updates, {total:.3f} ms of device time an "
          f"update, {100 * table['scoped_ms'] / total:.2f}% scoped")
    for name, ms in table["scopes"].items():
        print(f"{name:32s} {ms:10.3f} ms {100 * ms / total:6.2f}%")
    for kind in ("unscoped", "mixed"):
        part = sum(table[kind].values())
        print(f"{kind:32s} {part:10.3f} ms {100 * part / total:6.2f}%")
        for key, ms in list(table[kind].items())[:TOP_UNSCOPED]:
            print(f"    {key:40s} {ms:10.3f} ms")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
