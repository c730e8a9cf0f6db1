"""Peak device memory after the window, GB: ``memory_stats()``'s
``peak_bytes_in_use`` (buffers) plus ``peak_bytes_reserved`` (the running
program's temporaries), on the fullest chip — the same number as
``device.memory_peak_bytes``."""


def read(run):
    return run.memory_peak_bytes / 1e9 if run.memory_peak_bytes else None
