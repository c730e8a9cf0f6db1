"""What the actor processes say of their own time, as the learner's server
summed it: the one source of the per-layer readers of the layer "actor tiers".

An actor host of the program times its own phases
(``relayrl_tpu/telemetry/actor_ledger.py``: ``rl:actor.*`` spans with always-on
totals) and every trajectory it ships carries the totals' growth since its
previous shipment; the server's admission funnel adds them into
``server.timings["actor_<key>"]`` and ``server.stats["actor_<count>"]``.
``run.timings`` / ``run.stats`` hold the measured window's deltas of both
(``drivers/loop.py``), summed over the actor processes — so a share is of
``actor_wall_s``, the processes' own wall time (first step to last, each
process's ``step_s + env_s``), and a rate is a PROCESS's. The shares are a
decomposition: infer + record + emit + env is a step's named time and the
environment's; the rest of a step (``normalize_obs``, rewards, the lock) is
what is left of 100.

A program whose actors report nothing (the parent of the PR that added the
report; a cell with no actors) leaves the keys out or at zero, and every
reader returns None.
"""

from __future__ import annotations


def share(run, *keys: str) -> float | None:
    """``100 * sum(timings[key]) / timings["actor_wall_s"]``."""
    wall = run.timings.get("actor_wall_s")
    if not wall or any(k not in run.timings for k in keys):
        return None
    return 100.0 * sum(run.timings[k] for k in keys) / wall


def note(run) -> None:
    """The whole ledger into the result line's notes, so that what no metric
    reads (encode and send apart, decode and swap apart, ``cpu_s``, ``gc_s``)
    can be read by hand."""
    if run.timings.get("actor_wall_s"):
        run.notes["actor_ledger"] = {
            **{k: v for k, v in run.timings.items()
               if k.startswith("actor_")},
            **{k: v for k, v in run.stats.items() if k.startswith("actor_")}}
