"""Bytes of one staged batch (sum over its arrays as ``accumulate`` returns
them), in MB: what ``stage_batch`` copies to the device per update."""


def read(run):
    size = run.counters.get("batch_bytes_mean")
    return None if not size else size / 1e6
