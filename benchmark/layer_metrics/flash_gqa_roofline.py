"""The grouped-query flash kernels' share of their roofline: the least time
the chip could take for one update's attention layers — the larger of
operations / peak FLOP/s and bytes / peak bytes/s, from the reference
file's ``flash_gqa_train_ops_bytes`` (operations of the scores a causal
call needs, T (T + 1) / 2 a q head, whatever the kernels' tiling executes
beside them; bytes with k/v at their own head count) — over the device
time per update of the operations named ``relayrl_flash_fwd`` /
``relayrl_flash_bwd`` ONLY (``program_trace.KERNELS``; ``flash_roofline`` sums
every Mosaic call, which here would take the expert layer's grouped matmuls
for attention). The count is of the work, not of an implementation: it stood
when one backward kernel took the place of dq and dkv (PR 53)."""

from benchmark import program_trace


def read(run):
    count = getattr(run.reference, "flash_gqa_train_ops_bytes", None)
    parts = [program_trace.kernel_ms_per_update(run, k)
             for k in program_trace.KERNELS]
    if count is None or any(p is None for p in parts):
        return None
    ops, nbytes = count(run.config, int(run.traffic["traj_per_update"]),
                        int(run.traffic["traj_len"]))
    by_ops = ops / run.peaks["bf16_flops_per_s"]
    by_bytes = nbytes / run.peaks["hbm_bytes_per_s"]
    run.notes["flash_gqa_roofline"] = {
        "bound": "compute" if by_ops >= by_bytes else "memory",
        "least_s_by_operations": by_ops, "least_s_by_bytes": by_bytes,
        "flash_s_per_update": sum(parts) / 1e3}
    return 100.0 * max(by_ops, by_bytes) / (sum(parts) / 1e3)
