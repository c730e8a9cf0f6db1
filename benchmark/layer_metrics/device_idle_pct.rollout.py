"""Share of the traced window in which no operation ran on the device, in a
rollout cell: ``device_idle_pct``'s reader under a name that moves
``rollout_steps_per_s``."""

from benchmark import harness

read = harness.load_layer_metric("device_idle_pct").read
