"""The subscriber thread's work on the stepping thread's core, as a share of
the actor processes' wall time: ``rl:actor.model_decode`` (frame sniff, decode,
copy) + ``rl:actor.swap`` (lock wait + install). It overlaps the stepping
thread's spans — another thread's time, not a part of the decomposition
(``benchmark/actor_report.py``)."""

from benchmark import actor_report


def read(run):
    return actor_report.share(run, "actor_model_decode_s", "actor_swap_s")
