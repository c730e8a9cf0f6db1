"""Device time by ``jax.named_scope``: the map from the instruction names a
TPU trace prints to the scopes of the compiled module's metadata.

A ``named_scope`` round ordinary XLA operations reaches the compiled
module's metadata (every instruction's ``op_name`` is the scope path it was
traced under) and not the xplane's operations line, whose events carry the
instruction's text alone (PERF.md section 3). But the profiler saves the
modules it saw running beside the events: the xplane's ``/host:metadata``
plane holds one serialized ``HloProto`` a module (stat "Hlo Proto" of the
event metadata named like the module's "XLA Modules" events,
``jit_impala_update(<fingerprint>)``). This module reads the update's
``HloProto`` from there and gives every instruction name its scope:

* an instruction's scope is its own ``metadata.op_name``;
* **a fusion counts for the scope of its root**: the ``op_name`` of the
  root instruction of the computation it calls.

``jax.profiler.ProfileData`` does not expose event metadata, and importing
a generated protobuf module would pull TensorFlow into the run, so the few
fields needed are read off the wire format directly (field numbers from
``xplane.proto`` and ``hlo.proto``; ``benchmark/tests/test_scope_trace.py``
checks the decoding on bytes built by hand).

A trace without the plane or the module, a run without a trace, or a
program without the scope (the parent of the PR that added it): ``None``.
"""

from __future__ import annotations

import os
import re

from benchmark import program_trace, trace_reduce

METADATA_PLANE = "/host:metadata"
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")


def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def fields(buf):
    """``(field number, value)`` of one protobuf message: ints for varints,
    ``memoryview`` slices for length-delimited and fixed-width fields."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield number, value


def _first(message, number: int, default=None):
    return next((v for n, v in fields(message) if n == number), default)


def _text(view) -> str:
    return "" if view is None else bytes(view).decode("utf-8", "replace")


def module_protos(xplane_bytes) -> dict[str, bytes]:
    """``{module name: serialized HloProto}`` of the metadata plane.
    XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 (map entry:
    value = 2); XEventMetadata.name = 2, .stats = 5; XStat.bytes_value =
    6."""
    out = {}
    for number, plane in fields(xplane_bytes):
        if number != 1 or _text(_first(plane, 2)) != METADATA_PLANE:
            continue
        for number, entry in fields(plane):
            if number != 4:
                continue
            meta = _first(entry, 2)
            if meta is None:
                continue
            for n, stat in fields(meta):
                proto = _first(stat, 6) if n == 5 else None
                if proto is not None:
                    out[_text(_first(meta, 2))] = bytes(proto)
    return out


def instruction_scopes(hlo_proto) -> dict[str, str]:
    """``{instruction name: op_name}`` over every computation of one
    ``HloProto``, a fusion under its root's. HloProto.hlo_module = 1;
    HloModuleProto.computations = 3; HloComputationProto.instructions = 2,
    .id = 5, .root_id = 6; HloInstructionProto.name = 1, .opcode = 2,
    .metadata = 7 (OpMetadata.op_name = 2), .id = 35,
    .called_computation_ids = 38."""
    module = _first(hlo_proto, 1)
    if module is None:
        return {}
    instructions, root_scope = [], {}
    for number, comp in fields(module):
        if number != 3:
            continue
        comp_id = root_id = None
        own = []
        for n, value in fields(comp):
            if n == 5:
                comp_id = value
            elif n == 6:
                root_id = value
            elif n == 2:
                name = opcode = ""
                scope, ins_id, called = "", None, []
                for m, v in fields(value):
                    if m == 1:
                        name = _text(v)
                    elif m == 2:
                        opcode = _text(v)
                    elif m == 7:
                        scope = _text(_first(v, 2))
                    elif m == 35:
                        ins_id = v
                    elif m == 38:
                        # packed or one at a time
                        called += ([v] if isinstance(v, int) else
                                   _packed_varints(v))
                own.append((name, opcode, scope, ins_id, called))
        for name, opcode, scope, ins_id, called in own:
            if ins_id == root_id:
                root_scope[comp_id] = scope
        instructions += own
    out = {}
    for name, opcode, scope, _id, called in instructions:
        if opcode == "fusion" and called and root_scope.get(called[0]):
            scope = root_scope[called[0]]
        out[name] = scope
    return out


def _packed_varints(view) -> list[int]:
    out, i = [], 0
    while i < len(view):
        value, i = _varint(view, i)
        out.append(value)
    return out


def instruction_name(event_name: str) -> str | None:
    """``%fusion.77 = (...) fusion(...)`` -> ``fusion.77``."""
    m = _INSTRUCTION.match(event_name)
    return m.group(1) if m else None


def of(run) -> dict | None:
    """``{"scopes": {instruction: op_name}, "ops": [[instruction, start_ns,
    dur_ns], ...]}`` of the traced sub-window's update module, read once a
    run; None where the module's text is not at hand."""
    if not hasattr(run, "_scope_trace"):
        run._scope_trace = None
        path = trace_reduce.newest_xplane(os.path.join(run.run_dir, "trace"))
        if run.trace and path is not None:
            with open(path, "rb") as f:
                protos = module_protos(f.read())
            scopes = {}
            for name, proto in protos.items():
                if program_trace.UPDATE_MODULE in name:
                    scopes.update(instruction_scopes(proto))
            if scopes:
                import jax

                ops = []
                for plane in jax.profiler.ProfileData.from_file(path).planes:
                    if not plane.name.startswith(
                            trace_reduce.DEVICE_PLANE_PREFIX):
                        continue
                    for line in plane.lines:
                        if line.name == trace_reduce.OPS_LINE:
                            ops += [[instruction_name(ev.name),
                                     float(ev.start_ns),
                                     float(ev.duration_ns)]
                                    for ev in line.events]
                run._scope_trace = {"scopes": scopes, "ops": ops}
    return run._scope_trace


def ms_per_update(run, scope: str) -> float | None:
    """Summed device time of the operations whose scope path holds
    ``scope``, inside the whole updates of the window, per such update."""
    t, s = program_trace.of(run), of(run)
    if not t or not t["updates"] or not s:
        return None
    names = {n for n, path in s["scopes"].items() if scope in path}
    total, found = 0.0, False
    for name, start, dur in s["ops"]:
        if name in names and any(u0 <= start < u0 + ud
                                 for u0, ud in t["updates"]):
            total += dur
            found = True
    return total / len(t["updates"]) / 1e6 if found else None
