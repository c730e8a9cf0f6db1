"""Peak device memory after the window, GB, in a rollout cell:
``peak_hbm_gb``'s reader (``device.memory_peak_bytes``: buffers plus the
running program's temporaries, on the fullest chip) under a name that moves
``rollout_steps_per_s``."""

from benchmark import harness

read = harness.load_layer_metric("peak_hbm_gb").read
