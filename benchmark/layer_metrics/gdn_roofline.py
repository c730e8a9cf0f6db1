"""The gated delta rule's share of its roofline: the least time the chip
could take for one update's rules — the larger of operations / peak FLOP/s
and bytes / peak bytes/s, from the reference file's ``gdn_train_ops_bytes``
(``benchmark/flops_qwen3next.py``: the chunked form's matmul terms at the
configuration's chunk with the pairs inside a chunk counted as a triangular
product needs them and the solve as forward substitution; bytes of the
rule's arguments, result and cotangents and of the chunk-start states once
each way) — over ``gdn_ms``, the device time per update under the scope
``relayrl_gdn``. The rule recomputed in the backward is time and no counted
work. A reading over 100% is a wrong count, not a result."""

from benchmark import scope_table


def read(run):
    count = getattr(run.reference, "gdn_train_ops_bytes", None)
    ms = scope_table.ms_per_update(run, "relayrl_gdn")
    if count is None or not ms:
        return None
    ops, nbytes = count(run.config, int(run.traffic["traj_per_update"]),
                        int(run.traffic["traj_len"]))
    by_ops = ops / run.peaks["bf16_flops_per_s"]
    by_bytes = nbytes / run.peaks["hbm_bytes_per_s"]
    run.notes["gdn_roofline"] = {
        "bound": "compute" if by_ops >= by_bytes else "memory",
        "least_s_by_operations": by_ops, "least_s_by_bytes": by_bytes,
        "gdn_s_per_update": ms / 1e3}
    return 100.0 * max(by_ops, by_bytes) / (ms / 1e3)
