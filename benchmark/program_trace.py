"""The program's own spans and named device events, read from the traced
sub-window's xplane: the one source of every per-layer reader that reads a
span, a span's argument or a named kernel.

The program (``relayrl_tpu/telemetry/spans.py``) writes a
``jax.profiler.TraceAnnotation`` for each of its spans while the profiler
runs, so they sit in the xplane on the device trace's clock: ``host:<phase>``
for the learner thread's sequential phases, ``rl:<layer>.<what>`` for what is
nested or on another thread, arguments as event stats. The device side
carries the names the program sets: the jitted update's module
(``jit_<algo>_update``) and ``relayrl_flash_fwd`` / ``relayrl_flash_bwd`` on
the two flash kernels. PERF.md section 3 says where each lands.

Two steps, as in ``trace_reduce``, so that the arithmetic can be checked
without a chip (``benchmark/tests/test_program_trace.py`` against
``benchmark/tests/data/program_events.json``):

* :func:`load_events` reads the newest ``*.xplane.pb`` into plain lists;
* :func:`reduce_events` clips to ``host:window``, counts a span nested in one
  of its own name once, works out self times per thread, and groups device
  events by name.

:func:`of` does both once per run and keeps the result on the run. A program
without these spans (the parent of the PR that added them) gives empty
groups, and every reader returns ``None``.
"""

from __future__ import annotations

import os
import statistics

from benchmark import trace_reduce

SPAN_PREFIXES = ("host:", "rl:")
WINDOW = "host:window"
MODULES_LINE = "XLA Modules"
UPDATE_MODULE = "_update("     # jit_impala_update(<fingerprint>)
KERNELS = ("relayrl_flash_fwd", "relayrl_flash_bwd")
# the learner thread's waits that the program names
BLOCKED = ("host:wait_data", "rl:dispatch.fence")


def load_events(trace_dir: str) -> dict | None:
    """``{"threads": [[[name, start, dur, args], ...], ...], "modules":
    {plane: [[name, start, dur], ...]}, "ops": {plane: [[kernel, start,
    dur], ...]}}`` (ns) or None without a trace. One entry of ``threads``
    per host line that holds a program span; ``ops`` holds only the device
    operations that carry one of the program's kernel names, under that
    name."""
    path = trace_reduce.newest_xplane(trace_dir)
    if path is None:
        return None
    import jax

    threads, modules, ops = [], {}, {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        is_device = plane.name.startswith(trace_reduce.DEVICE_PLANE_PREFIX)
        for line in plane.lines:
            if is_device and line.name == MODULES_LINE:
                modules[plane.name] = [
                    [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                    for ev in line.events]
            elif is_device and line.name == trace_reduce.OPS_LINE:
                found = ops.setdefault(plane.name, [])
                for ev in line.events:
                    kernel = _kernel_of(ev)
                    if kernel is not None:
                        found.append([kernel, float(ev.start_ns),
                                      float(ev.duration_ns)])
            elif not is_device:
                spans = [[ev.name, float(ev.start_ns), float(ev.duration_ns),
                          dict(ev.stats)]
                         for ev in line.events
                         if ev.name.startswith(SPAN_PREFIXES)]
                if spans:
                    threads.append(spans)
    return {"threads": threads, "modules": modules, "ops": ops}


def _kernel_of(ev) -> str | None:
    """The program-set kernel name an operation carries: in the HLO
    instruction's own name (the trace prints ``%relayrl_flash_fwd.5 = ...``)
    or, failing that, in one of its string stats (the op's source scope)."""
    for kernel in KERNELS:
        if kernel in ev.name:
            return kernel
    if "custom-call" not in ev.name:
        return None
    for _key, value in ev.stats:
        if isinstance(value, str):
            for kernel in KERNELS:
                if kernel in value:
                    return kernel
    return None


def _clip(start: float, dur: float, w0: float, w1: float) -> float:
    return max(0.0, min(start + dur, w1) - max(start, w0))


def reduce_events(events: dict) -> dict:
    """``{"window_ns": (w0, w1) | None, "spans": {name: [span, ...]},
    "updates": [[start, dur], ...], "kernels": {name: [[start, dur], ...]}}``.

    A span is ``{"thread": i, "start": ns, "dur": ns clipped to the window,
    "self": dur less the clipped durations of its direct children, "args":
    {...}, "inside": started inside the window}``. A span whose ancestor on
    the same thread has its name (the benchmark's ``host:accumulate`` round
    the program's) is counted once: the ancestor stays and takes over the
    arguments only the inner one carries."""
    window = None
    for spans in events.get("threads", []):
        for name, start, dur, _args in spans:
            if name == WINDOW:
                window = (start, start + dur)
    w0, w1 = window if window else (float("-inf"), float("inf"))
    out: dict[str, list] = {}
    for i, spans in enumerate(events.get("threads", [])):
        stack: list[dict] = []
        # parents before children: by start, longer first on a tie
        for name, start, dur, args in sorted(
                spans, key=lambda s: (s[1], -s[2])):
            if name == WINDOW:
                continue
            while stack and start >= stack[-1]["end"]:
                stack.pop()
            same = next((s for s in stack if s["name"] == name), None)
            if same is not None:
                for k, v in args.items():
                    same["args"].setdefault(k, v)
                continue
            part = _clip(start, dur, w0, w1)
            span = {"name": name, "thread": i, "start": start,
                    "end": start + dur, "dur": part, "self": part,
                    "args": dict(args), "inside": w0 <= start < w1}
            if stack:
                stack[-1]["self"] -= part
            stack.append(span)
            if part > 0:
                out.setdefault(name, []).append(span)
    updates, kernels = [], {}
    for mods in events.get("modules", {}).values():
        updates += [[s, d] for name, s, d in mods
                    if UPDATE_MODULE in name and s >= w0 and s + d <= w1]
    for found in events.get("ops", {}).values():
        for kernel, s, d in found:
            kernels.setdefault(kernel, []).append([s, d])
    return {"window_ns": window, "spans": out, "updates": sorted(updates),
            "kernels": kernels}


def of(run) -> dict | None:
    """The reduced program trace of this run's traced sub-window, parsed
    once; None when the run was not traced or left no xplane."""
    if not hasattr(run, "_program_trace"):
        events = None
        if run.trace:
            events = load_events(os.path.join(run.run_dir, "trace"))
        run._program_trace = reduce_events(events) if events else None
    return run._program_trace


# -- what the readers share --------------------------------------------------

def per_count_ms(run, name: str, per: str) -> float | None:
    """Summed duration of spans ``name`` over the number of spans ``per``
    that started inside the window, in ms (``rl:batch.pad`` per
    ``rl:batch.stack``: padding per update)."""
    t = of(run)
    if not t or name not in t["spans"]:
        return None
    n = sum(1 for s in t["spans"].get(per, []) if s["inside"])
    if not n:
        return None
    return sum(s["dur"] for s in t["spans"][name]) / n / 1e6


def mean_arg(run, name: str, arg: str) -> float | None:
    t = of(run)
    if not t:
        return None
    values = [s["args"][arg] for s in t["spans"].get(name, [])
              if s["inside"] and arg in s["args"]]
    return sum(values) / len(values) if values else None


def update_device_ms(run) -> float | None:
    t = of(run)
    if not t or not t["updates"]:
        return None
    return statistics.median(d for _s, d in t["updates"]) / 1e6


def kernel_ms_per_update(run, kernel: str) -> float | None:
    """Device time of ``kernel`` inside the update modules that lie wholly
    in the window, per such update."""
    t = of(run)
    if not t or not t["updates"] or kernel not in t["kernels"]:
        return None
    total = 0.0
    for s, d in t["kernels"][kernel]:
        if any(u0 <= s < u0 + ud for u0, ud in t["updates"]):
            total += d
    return total / len(t["updates"]) / 1e6


def learner_offcpu_pct(run) -> float | None:
    """Share of the learner thread's update cycles it spent off the CPU
    outside its two known waits. A cycle is the wall time between two
    consecutive ``host:dispatch`` spans of one thread; ``cycle_cpu_ns`` of
    the later one is the thread's CPU time in it; the ``host:wait_data`` and
    ``rl:dispatch.fence`` spans inside it are waits the program names. What
    is left is every other time off the CPU: blocking inside ``device_put``
    or the jitted call, a lock, the GIL, the scheduler. The cell with one
    busy thread is the baseline: only what a cell reads above it can be
    contention. Nothing is clamped: a negative share means a stamp or an
    attribution is wrong."""
    t = of(run)
    if not t:
        return None
    by_thread: dict[int, list] = {}
    for s in t["spans"].get("host:dispatch", []):
        if "cycle_cpu_ns" in s["args"]:
            by_thread.setdefault(s["thread"], []).append(s)
    wall = off = 0.0
    for thread, dispatches in by_thread.items():
        blocked = [s for name in BLOCKED for s in t["spans"].get(name, [])
                   if s["thread"] == thread]
        dispatches.sort(key=lambda s: s["start"])
        for prev, cur in zip(dispatches, dispatches[1:]):
            if not (prev["inside"] and cur["inside"]):
                continue
            c0, c1 = prev["start"], cur["start"]
            waited = sum(_clip(b["start"], b["end"] - b["start"], c0, c1)
                         for b in blocked)
            wall += c1 - c0
            off += c1 - c0 - cur["args"]["cycle_cpu_ns"] - waited
    return 100.0 * off / wall if wall else None
