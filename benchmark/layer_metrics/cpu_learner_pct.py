"""The learner thread's time on a CPU as a share of the measured window, of
one core: ``server.timings["cpu_learner_s"]`` (the program's per-thread
ledger, refreshed once a dispatch), window delta, over ``window_s``."""

from benchmark import thread_account


def read(run):
    return thread_account.ledger_pct(run, "cpu_learner_s")
