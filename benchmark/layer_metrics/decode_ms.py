"""Staging-thread decode time per trajectory handed to the learner
(``server.timings["decode_s"]`` over ``stats["trajectories"]``, window deltas)."""


def read(run):
    n = run.stats.get("trajectories", 0)
    if not n or "decode_s" not in run.timings:
        return None
    return 1e3 * run.timings["decode_s"] / n
