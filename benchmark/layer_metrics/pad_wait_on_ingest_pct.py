"""Of the time ``rl:batch.pad`` stood off the CPU, the share during which the
receive thread was on it (``rl:ingest.recv`` + ``rl:ingest.admit``):
``benchmark/thread_account.py`` has the rule. An estimate: on the CPU is not
the same as holding the lock."""

from benchmark import thread_account


def read(run):
    return thread_account.pad_wait_pct(run, "ingest")
