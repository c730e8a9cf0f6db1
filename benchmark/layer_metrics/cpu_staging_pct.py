"""The staging thread(s)' time on a CPU as a share of the measured window, of
one core: ``server.timings["cpu_staging_s"]`` (the program's per-thread
ledger, summed over the decode workers), window delta, over ``window_s``."""

from benchmark import thread_account


def read(run):
    return thread_account.ledger_pct(run, "cpu_staging_s")
