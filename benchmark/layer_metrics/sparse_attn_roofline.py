"""The sparse attention's share of its roofline: the least time the chip
could take for one update's attention over the pairs the run ITSELF counted
as kept (``index_kept_pct`` on ``rl:dispatch.fence``: kept pairs over causal
pairs, all sparse-attention layers of an update; from the shapes where the
fence carries none) — the larger of operations / peak FLOP/s and bytes /
peak bytes/s, from the reference file's ``sparse_attn_train_ops_bytes``
(``benchmark/flops_keye.py``: ``QK^T`` and ``PV`` of the kept pairs,
forward and backward, k and v at their own head count) — over
``sparse_attn_ms``, the device time per update under the scope
``relayrl_sparse_attn``. A masked-dense form computes every causal pair and
more, 4.3 times the kept ones at T 16,384: that is time and no counted work,
as is everything made again in the backward. A reading over 100% is a wrong
count, not a result."""

from benchmark import program_trace, scope_table


def read(run):
    count = getattr(run.reference, "sparse_attn_train_ops_bytes", None)
    ms = scope_table.ms_per_update(run, "relayrl_sparse_attn")
    if count is None or not ms:
        return None
    kept = program_trace.mean_arg(run, "rl:dispatch.fence", "index_kept_pct")
    ops, nbytes = count(run.config, int(run.traffic["traj_per_update"]),
                        int(run.traffic["traj_len"]),
                        None if kept is None else kept / 100.0)
    by_ops = ops / run.peaks["bf16_flops_per_s"]
    by_bytes = nbytes / run.peaks["hbm_bytes_per_s"]
    run.notes["sparse_attn_roofline"] = {
        "bound": "compute" if by_ops >= by_bytes else "memory",
        "kept_pct_of_causal_pairs": kept,
        "least_s_by_operations": by_ops, "least_s_by_bytes": by_bytes,
        "sparse_attn_s_per_update": ms / 1e3}
    return 100.0 * max(by_ops, by_bytes) / (ms / 1e3)
