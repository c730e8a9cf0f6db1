"""The latent-attention flash calls' share of their roofline: the least time
the chip could take for one update's latent-attention layers — the larger of
operations / peak FLOP/s and bytes / peak bytes/s, from the reference file's
``mla_flash_train_ops_bytes`` (the scores a causal call needs at 192 REAL
lanes of q / k and 128 of v, whatever a kernel pads in VMEM or computes above
the diagonal beside them) — over ``flash_mla_ms``. A reading over 100% is a
wrong count, not a result."""

from benchmark import harness


def read(run):
    ms = harness.load_layer_metric("flash_mla_ms").read(run)
    count = getattr(run.reference, "mla_flash_train_ops_bytes", None)
    if not ms or count is None:
        return None
    ops, nbytes = count(run.config, int(run.traffic["traj_per_update"]),
                        int(run.traffic["traj_len"]))
    by_ops = ops / run.peaks["bf16_flops_per_s"]
    by_bytes = nbytes / run.peaks["hbm_bytes_per_s"]
    run.notes["flash_mla_roofline"] = {
        "bound": "compute" if by_ops >= by_bytes else "memory",
        "least_s_by_operations": by_ops, "least_s_by_bytes": by_bytes,
        "mla_s_per_update": ms / 1e3}
    return 100.0 * max(by_ops, by_bytes) / (ms / 1e3)
