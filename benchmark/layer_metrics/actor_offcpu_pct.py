"""Share of the actor processes' wall time their stepping threads were OFF the
CPU: ``actor_wall_s`` less ``actor_cpu_s`` (``time.thread_time_ns`` over the
same cycles) — the subscriber thread, XLA's pool, the scheduler.
``learner_offcpu_pct``'s twin (``benchmark/actor_report.py``)."""


def read(run):
    wall = run.timings.get("actor_wall_s")
    if not wall or "actor_cpu_s" not in run.timings:
        return None
    return 100.0 * (wall - run.timings["actor_cpu_s"]) / wall
