"""Environment frames a second ONE actor process steps: the actors' own count
of lane-steps (``server.stats["actor_steps"]``) over their own wall time
(``server.timings["actor_wall_s"]``, which sums over the processes) — both
from the reports their trajectories carry, window deltas
(``benchmark/actor_report.py``)."""


def read(run):
    wall = run.timings.get("actor_wall_s")
    if not wall or "actor_steps" not in run.stats:
        return None
    return run.stats["actor_steps"] / wall
