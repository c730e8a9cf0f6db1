"""From a profiler trace to numbers: device busy time, the operations that
took it, and what the host was doing in the gaps.

Two steps, so that the arithmetic can be checked without a chip:

* :func:`load_events` reads the newest ``*.xplane.pb`` under a trace
  directory with nothing but ``jax.profiler.ProfileData`` into plain lists
  ``[name, start_ns, duration_ns]``: per device plane the events of its
  operations line, and from the host planes the benchmark's own
  ``host:<name>`` annotations (``benchmark/spans.py``).
* :func:`reduce_events` turns such lists into busy seconds (union of the
  operation intervals, averaged over the devices), the top operations by
  summed duration, and the idle gaps attributed to the host span that
  covers them. ``benchmark/tests/test_trace_reduce.py`` checks it against
  ``benchmark/tests/data/small_trace.json``.

No kernel or jitted step of the program carries a stable ``named_scope``
yet, so operations are keyed by the names the trace prints today.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE_PREFIX = "/device:TPU:"
# The per-operation line of a TPU plane. "XLA Modules" and "Steps" cover
# the same time at a coarser grain and would double count.
OPS_LINE = "XLA Ops"
HOST_PREFIX = "host:"
SMALL_GAP_NS = 20_000  # gaps under 20 us are launch latency, not the host
TOP_N = 10


def op_key(text: str) -> str:
    """A short, stable-enough key for a device operation. The trace prints
    the whole HLO instruction (``%fusion.77 = (f32[], bf16[8,8,4,32]{...})
    fusion(...), kind=kOutput, calls=...``); the key is
    ``<name without its number>/<opcode>[/<fusion kind or custom-call
    target>]``, e.g. ``convolution_add_fusion/fusion/kOutput`` or
    ``block/custom-call/tpu_custom_call`` (a Mosaic kernel: the name is the
    flax scope it was called from, not the kernel's own)."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text[:80]
    name = name.strip().lstrip("%")
    base = re.sub(r"([._]\d+)+$", "", name) or name
    rest = rest.strip()
    if rest.startswith("("):  # tuple type: skip to its closing bracket
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:].strip()
                break
    else:
        rest = rest.partition(" ")[2]
    opcode = rest.partition("(")[0].strip() or "?"
    key = f"{base}/{opcode}"
    for marker in ("custom_call_target=\"", "kind="):
        if marker in text:
            tail = text.split(marker, 1)[1]
            extra = tail.split("\"")[0] if marker.endswith("\"") else \
                tail.split(",")[0].split(" ")[0]
            return f"{key}/{extra}"[:80]
    return key[:80]


def newest_xplane(trace_dir: str) -> str | None:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def load_events(trace_dir: str) -> dict | None:
    """``{"device": {plane: [[name, start, dur], ...]}, "host": [...],
    "layout": {plane: {line: n_events}}}`` or None without a trace."""
    path = newest_xplane(trace_dir)
    if path is None:
        return None
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    device: dict[str, list] = {}
    host: list = []
    layout: dict[str, dict] = {}
    for plane in data.planes:
        lines = layout.setdefault(plane.name, {})
        is_device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        for line in plane.lines:
            n = 0
            for ev in line.events:
                n += 1
                if is_device and line.name == OPS_LINE:
                    device.setdefault(plane.name, []).append(
                        [op_key(ev.name), float(ev.start_ns),
                         float(ev.duration_ns)])
                elif not is_device and ev.name.startswith(HOST_PREFIX):
                    host.append([ev.name, float(ev.start_ns),
                                 float(ev.duration_ns)])
            lines[line.name] = lines.get(line.name, 0) + n
    return {"device": device, "host": host, "layout": layout}


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _overlap(a0: float, a1: float, spans: list[tuple[float, float]]) -> float:
    return sum(max(0.0, min(a1, b1) - max(a0, b0)) for b0, b1 in spans)


def reduce_events(events: dict, window_ns: tuple[float, float] | None = None
                  ) -> dict | None:
    """Busy seconds, window seconds, top operations and attributed gaps.

    The window is ``window_ns`` if given, else from the first start to the
    last end of anything recorded (device operations and host spans).
    Returns None when no device operation was recorded."""
    device = {k: v for k, v in events.get("device", {}).items() if v}
    if not device:
        return None
    host = events.get("host", [])
    if window_ns is None:
        starts = [s for evs in device.values() for _, s, _ in evs]
        ends = [s + d for evs in device.values() for _, s, d in evs]
        starts += [s for _, s, _ in host]
        ends += [s + d for _, s, d in host]
        window_ns = (min(starts), max(ends))
    w0, w1 = window_ns
    host_by_name: dict[str, list[tuple[float, float]]] = {}
    for name, s, d in host:
        host_by_name.setdefault(name, []).append((s, s + d))
    host_by_name = {k: _union(v) for k, v in host_by_name.items()}

    busy_ns = 0.0
    op_ns: dict[str, float] = {}
    op_n: dict[str, int] = {}
    gap_ns: dict[str, float] = {}
    for evs in device.values():
        clipped = [(max(s, w0), min(s + d, w1)) for _, s, d in evs
                   if s + d > w0 and s < w1]
        merged = _union(clipped)
        busy_ns += sum(b - a for a, b in merged)
        for name, s, d in evs:
            part = max(0.0, min(s + d, w1) - max(s, w0))
            if part > 0:
                op_ns[name] = op_ns.get(name, 0.0) + part
                op_n[name] = op_n.get(name, 0) + 1
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            gap = g1 - g0
            if gap <= 0:
                continue
            if gap < SMALL_GAP_NS:
                gap_ns["device:gaps_under_20us"] = gap_ns.get(
                    "device:gaps_under_20us", 0.0) + gap
                continue
            left = gap
            for name, spans in host_by_name.items():
                part = min(left, _overlap(g0, g1, spans))
                if part > 0:
                    gap_ns[name] = gap_ns.get(name, 0.0) + part
                    left -= part
            if left > 0:
                gap_ns["host:unattributed"] = gap_ns.get(
                    "host:unattributed", 0.0) + left
    n_dev = len(device)

    def top(d: dict) -> list:
        ranked = sorted(d.items(), key=lambda kv: -kv[1])[:TOP_N]
        return [[k, v / n_dev / 1e9] for k, v in ranked]

    return {
        "busy_s": busy_ns / n_dev / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "n_devices": n_dev,
        "n_ops": sum(len(v) for v in device.values()),
        "op_s": {k: v / n_dev / 1e9 for k, v in op_ns.items()},
        "op_n": {k: v / n_dev for k, v in op_n.items()},
        "device_ops": top(op_ns),
        "idle_gaps": top(gap_ns),
    }


def host_window_ns(events: dict, name: str) -> tuple[float, float] | None:
    """The interval of the (single) host span ``name`` — the benchmark
    wraps the traced window in ``host:window`` so that profiler start-up
    and shut-down are not counted as device idle time."""
    for n, s, d in events.get("host", []):
        if n == name:
            return (s, s + d)
    return None
