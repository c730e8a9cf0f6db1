"""Time inside full (generation 2) collections of the interpreter per update,
in the process that holds the chip: the ``rl:gc`` spans of the traced window
over its ``host:dispatch`` spans. 0.0 where the program names its collections
and none ran; None for a program that does not name them."""

from benchmark import program_trace


def read(run):
    from relayrl_tpu.telemetry import spans

    t = program_trace.of(run)
    if not t or not hasattr(spans, "watch_gc"):
        return None
    n = sum(1 for s in t["spans"].get("host:dispatch", []) if s["inside"])
    if not n:
        return None
    return sum(s["dur"] for s in t["spans"].get("rl:gc", [])) / n / 1e6
