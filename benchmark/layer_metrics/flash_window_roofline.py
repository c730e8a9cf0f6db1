"""The windowed flash calls' share of their roofline: the least time the
chip could take for one update's windowed attention layers — the larger of
operations / peak FLOP/s and bytes / peak bytes/s, from the reference
file's ``flash_window_train_ops_bytes`` (operations of the scores the band
needs, ``W (W + 1) / 2 + (T - W) W`` a q head, whatever the kernels' tiling
executes beside them; bytes with k/v at their own head count) — over
``flash_window_ms``. A reading over 100% is a wrong count, not a result."""

from benchmark import harness


def read(run):
    ms = harness.load_layer_metric("flash_window_ms").read(run)
    count = getattr(run.reference, "flash_window_train_ops_bytes", None)
    if not ms or count is None:
        return None
    ops, nbytes = count(run.config, int(run.traffic["traj_per_update"]),
                        int(run.traffic["traj_len"]))
    by_ops = ops / run.peaks["bf16_flops_per_s"]
    by_bytes = nbytes / run.peaks["hbm_bytes_per_s"]
    run.notes["flash_window_roofline"] = {
        "bound": "compute" if by_ops >= by_bytes else "memory",
        "least_s_by_operations": by_ops, "least_s_by_bytes": by_bytes,
        "window_s_per_update": ms / 1e3}
    return 100.0 * max(by_ops, by_bytes) / (ms / 1e3)
