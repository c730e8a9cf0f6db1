"""The model publisher thread's time on a CPU as a share of the measured
window, of one core: ``server.timings["cpu_publish_s"]`` (the program's
per-thread ledger), window delta, over ``window_s``."""

from benchmark import thread_account


def read(run):
    return thread_account.ledger_pct(run, "cpu_publish_s")
