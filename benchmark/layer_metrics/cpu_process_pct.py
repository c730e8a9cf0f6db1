"""Every thread of the learner process's time on a CPU as a share of the
measured window, of ONE core, so it may pass 100: the four named threads,
XLA's, libzmq's I/O threads and the rest (``server.timings["cpu_process_s"]``,
``time.process_time`` at each dispatch, window delta, over ``window_s``)."""

from benchmark import thread_account


def read(run):
    return thread_account.ledger_pct(run, "cpu_process_s")
