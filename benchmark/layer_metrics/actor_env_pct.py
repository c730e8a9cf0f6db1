"""Share of the actor processes' wall time BETWEEN two ``rl:actor.step`` spans:
what the program does not own — here ``traffic_gen.SyntheticEnv`` and the
driver's own glue round it (``benchmark/actor_report.py``)."""

from benchmark import actor_report


def read(run):
    return actor_report.share(run, "actor_env_s")
