"""Host time per update to assemble a batch and enqueue its H2D copy: the
benchmark's ``accumulate`` spans of the update's trajectories plus its
``stage_batch`` span."""


def read(run):
    n = run.spans.count.get("stage_batch", 0)
    if not n:
        return None
    total = run.spans.total_s["accumulate"] + run.spans.total_s["stage_batch"]
    return 1e3 * total / n
