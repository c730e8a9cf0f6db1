"""The grouped matmuls' share of their roofline: the least time the chip
could take for one update's expert layers — the larger of operations / peak
FLOP/s and bytes / peak bytes/s, from the reference file's
``grouped_matmul_train_ops_bytes`` (active work only: each token through
its k experts) — over their device time per update (``moe_ffn_ms``). A
reading over 100% is a wrong count, not a result."""

from benchmark import moe_trace


def read(run):
    ms = moe_trace.ms_per_update(run, moe_trace.is_gmm)
    count = getattr(run.reference, "grouped_matmul_train_ops_bytes", None)
    if not ms or count is None:
        return None
    tokens = int(run.traffic["traj_per_update"]) * int(
        run.traffic["traj_len"])
    ops, nbytes = count(run.config, tokens)
    by_ops = ops / run.peaks["bf16_flops_per_s"]
    by_bytes = nbytes / run.peaks["hbm_bytes_per_s"]
    run.notes["moe_ffn_roofline"] = {
        "bound": "compute" if by_ops >= by_bytes else "memory",
        "least_s_by_operations": by_ops, "least_s_by_bytes": by_bytes,
        "gmm_s_per_update": ms / 1e3}
    return 100.0 * max(by_ops, by_bytes) / (ms / 1e3)
