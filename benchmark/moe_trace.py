"""Device time of the expert layer's operations, per update, from the
traced sub-window's xplane: what the four ``moe_*`` readers share.

``program_trace`` keeps only the flash kernels' operations, so this module
reads the operations line once more and keeps every operation under
``trace_reduce.op_key``'s key (``<hlo name>/<opcode>[/<fusion kind or
custom-call target>]``). Only operations that start inside an update module
lying wholly in the window count, as ``program_trace.kernel_ms_per_update``
counts the flash kernels. A program without these operations (or a run
without a trace) gives ``None``.

Names, as one v5e trace of ``olmoe-policy.update`` shows them (PERF.md
section 3, docs/observability.md):

* grouped matmuls — the Mosaic calls the program names
  ``relayrl_moe_gmm_fwd`` / ``_dlhs`` / ``_drhs``
  (``relayrl_tpu/ops/grouped_matmul.py``); XLA's own lowering of
  ``lax.ragged_dot`` (``ragged-dot-none``, where the program falls back to
  it) counts too;
* dispatch — ordinary XLA operations, found by opcode as the observation
  relayout is: ``sort``, ``gather``, ``scatter`` and the ``kCustom``
  fusions XLA:TPU wraps its row gathers and scatters in. That is EVERY such
  operation of the update, not the expert layer's alone: the operations
  line carries no scope, so IMPALA's own gathers (the taken action's
  log-probability) are in the sum, three small ``kCustom`` calls an update
  beside the layer's four ``[N*k, d]`` row gathers, which are 15.9 of the
  16.1 ms. A change to those other gathers moves this metric too. The
  element-wise passes between them (the router's softmax, the weighted sum
  over k) are ``kLoop`` fusions shared with the rest of the update and are
  not counted.
"""

from __future__ import annotations

import os

from benchmark import program_trace, trace_reduce

GMM_NAMES = ("relayrl_moe_gmm", "ragged-dot-none")
DISPATCH_OPCODES = ("sort", "gather", "scatter")


def is_gmm(key: str) -> bool:
    return key.startswith(GMM_NAMES)


def is_dispatch(key: str) -> bool:
    parts = key.split("/")
    return (len(parts) > 1 and parts[1] in DISPATCH_OPCODES) or (
        len(parts) > 2 and parts[1] == "fusion" and parts[2] == "kCustom")


def _device_ops(run) -> list:
    """``[[key, start_ns, dur_ns], ...]`` of every device operation."""
    if not hasattr(run, "_moe_device_ops"):
        ops: list = []
        path = trace_reduce.newest_xplane(os.path.join(run.run_dir, "trace"))
        if run.trace and path is not None:
            import jax

            for plane in jax.profiler.ProfileData.from_file(path).planes:
                if not plane.name.startswith(
                        trace_reduce.DEVICE_PLANE_PREFIX):
                    continue
                for line in plane.lines:
                    if line.name == trace_reduce.OPS_LINE:
                        ops += [[trace_reduce.op_key(ev.name),
                                 float(ev.start_ns), float(ev.duration_ns)]
                                for ev in line.events]
        run._moe_device_ops = ops
    return run._moe_device_ops


def ms_per_update(run, pick) -> float | None:
    """Summed device time of the operations whose key ``pick`` accepts,
    inside the whole updates of the window, per such update."""
    t = program_trace.of(run)
    if not t or not t["updates"]:
        return None
    total, found = 0.0, False
    for key, start, dur in _device_ops(run):
        if pick(key) and any(u0 <= start < u0 + ud
                             for u0, ud in t["updates"]):
            total += dur
            found = True
    return total / len(t["updates"]) / 1e6 if found else None
