"""The fused actor host's own gauges, for the readers that report them.

``AnakinActorHost`` keeps its counts in the process's telemetry registry
(``relayrl_tpu.telemetry``), and a run of ``benchmark/run.py`` has none: the
rollout driver builds the host directly, no component reads the config's
``telemetry.*``, and every metric object the host asks for is the shared
no-op. The driver hands readers neither the host nor a registry, and is not
this PR's to edit. So importing this file installs a registry for the run,
unless the process has one already — and this is what that rests on:

* ``run.py`` imports a cell's readers BEFORE set-up, so the host finds the
  registry when it is built. A harness that imported readers after the run
  would read None here (no gauge), never a wrong number.
* ``run.py`` imports the readers of the run's OWN cell alone
  (``harness.load_cell``), and the two readers that import this file list
  ``granite4h-micro-policy.rollout`` and no other cell
  (``tests/test_rollout_counts.py`` holds that): every accepted cell's
  window runs under the no-op registry as before. Listing them in a second
  cell changes what that cell's timed window runs — a dozen counter
  increments and three spans a dispatch.

The repair is the harness's (PERF.md section 7): the driver hands readers
the host, or installs the registry itself, and this file goes.

:func:`read` sums a gauge over its label sets; a program without the gauge
(the parent of the PR that added it), or one that never built a host: None.
"""

from __future__ import annotations

from relayrl_tpu import telemetry

if not getattr(telemetry.get_registry(), "enabled", False):
    telemetry.set_registry(telemetry.Registry(run_id="benchmark"))


def read(name: str, **labels) -> float | None:
    found = [m["value"]
             for m in telemetry.get_registry().snapshot()["metrics"]
             if m["name"] == name and m.get("value") is not None and all(
                 m["labels"].get(k) == v for k, v in labels.items())]
    return float(sum(found)) if found else None
