"""The staging thread's Python half of decoding, per update: the SELF time of
``rl:ingest.decode`` (the span less ``rl:ingest.decode_native``, the one call
that runs without the interpreter's lock), summed over the traced window, per
``host:dispatch`` inside it (``benchmark/thread_account.py``)."""

from benchmark import thread_account


def read(run):
    return thread_account.decode_gil_ms(run)
