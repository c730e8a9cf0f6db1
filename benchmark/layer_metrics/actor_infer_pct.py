"""Share of the actor processes' wall time inside ``rl:actor.infer``: the
batched jitted policy call through the last ``np.asarray`` of its results.
A part of a decomposition (``benchmark/actor_report.py``): "lower" is the
contract's demand of every metric, not a goal."""

from benchmark import actor_report


def read(run):
    return actor_report.share(run, "actor_infer_s")
