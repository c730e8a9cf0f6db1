"""Share of the actor processes' wall time in ``rl:actor.record``'s SELF time:
the per-lane ``ActionRecord`` loop with ``add_action``, less the encode and
send a full trajectory's flush nests in it — the program's own Python a step
(``benchmark/actor_report.py``)."""

from benchmark import actor_report


def read(run):
    return actor_report.share(run, "actor_record_s")
