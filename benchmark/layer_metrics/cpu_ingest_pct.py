"""The receive thread's time on a CPU as a share of the measured window, of
one core — whoever calls the server's ``on_trajectory``: the zmq PULL loop,
the native poll loop, grpc's handlers: ``server.timings["cpu_ingest_s"]``
(the program's per-thread ledger), window delta, over ``window_s``."""

from benchmark import thread_account


def read(run):
    return thread_account.ledger_pct(run, "cpu_ingest_s")
