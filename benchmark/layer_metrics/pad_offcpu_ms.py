"""Time per update the learner thread stood OFF the CPU inside ``rl:batch.pad``
(``dur - cpu_ns`` of each span: runnable and not running, or blocked; pad does
no I/O), per batch assembled (``rl:batch.stack``) — ``pad_ms``'s part that is
not pad's own work. Also writes the whole account into
``notes.thread_account`` (``benchmark/thread_account.py``)."""

from benchmark import thread_account


def read(run):
    thread_account.note(run)
    return thread_account.per_update_ms(run, "rl:batch.pad", "off",
                                        per="rl:batch.stack")
