"""Publisher-thread time per publish that landed: D2H gather + wire
encode + socket (``server.timings["publish_s"]`` over publishes, window
deltas)."""


def read(run):
    n = run.counters.get("publishes", 0)
    if not n or "publish_s" not in run.timings:
        return None
    return 1e3 * run.timings["publish_s"] / n
