"""Host work per ``train_on_batch`` call: the benchmark's ``dispatch`` span
less the part of it spent blocked on the in-flight fence."""


def read(run):
    n = run.spans.count.get("dispatch", 0)
    if not n:
        return None
    work = run.spans.total_s["dispatch"] - run.counters.get(
        "wait_in_dispatch_s", 0.0)
    return 1e3 * max(work, 0.0) / n
