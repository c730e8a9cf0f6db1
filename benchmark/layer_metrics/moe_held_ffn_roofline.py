"""The held experts' grouped matmuls' share of their roofline: the least
time the chip could take for the token-slots the run ITSELF counted as
routed to held experts (``moe_held_slots`` on ``rl:dispatch.fence``, all
expert layers of an update) — the larger of operations / peak FLOP/s and
bytes / peak bytes/s, from the reference file's
``held_grouped_matmul_train_ops_bytes`` — over the grouped matmuls' device
time per update (``moe_ffn_ms``'s sum). ``moe_ffn_roofline`` counts every
token through its k experts, eight times the work that is here. A reading
over 100% is a wrong count, not a result."""

from benchmark import moe_trace, program_trace


def read(run):
    ms = moe_trace.ms_per_update(run, moe_trace.is_gmm)
    held = program_trace.mean_arg(run, "rl:dispatch.fence", "moe_held_slots")
    count = getattr(run.reference, "held_grouped_matmul_train_ops_bytes",
                    None)
    if not ms or held is None or count is None:
        return None
    ops, nbytes = count(run.config, held)
    by_ops = ops / run.peaks["bf16_flops_per_s"]
    by_bytes = nbytes / run.peaks["hbm_bytes_per_s"]
    run.notes["moe_held_ffn_roofline"] = {
        "bound": "compute" if by_ops >= by_bytes else "memory",
        "held_slots_per_update": held,
        "least_s_by_operations": by_ops, "least_s_by_bytes": by_bytes,
        "gmm_s_per_update": ms / 1e3}
    return 100.0 * max(by_ops, by_bytes) / (ms / 1e3)
