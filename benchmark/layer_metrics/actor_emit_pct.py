"""Share of the actor processes' wall time emitting unrolls: ``rl:actor.encode``
(one unroll serialised) + ``rl:actor.send`` (spool + transport send). The two
apart, and every other total of the actors' ledger, go to the result line's
``notes.actor_ledger`` (``benchmark/actor_report.py``)."""

from benchmark import actor_report


def read(run):
    actor_report.note(run)
    return actor_report.share(run, "actor_encode_s", "actor_send_s")
