"""The KDA rule's share of its roofline: the least time the chip could take
for one update's rules — the larger of operations / peak FLOP/s and bytes /
peak bytes/s, from the reference file's ``kda_train_ops_bytes``
(``benchmark/flops_kimi_linear.py``: the chunked form's matmul terms at the
configuration's chunk, the lane-wise pair weights counted as the products
they need, the solve as forward substitution; bytes of q, k, v, the decay a
lane, beta, o, their cotangents and the chunk-start states once each way) —
over ``kda_ms``, the device time per update under the scope ``relayrl_kda``.
What the rule makes again in its backward is time and no counted work. A
reading over 100% is a wrong count, not a result."""

from benchmark import scope_table


def read(run):
    count = getattr(run.reference, "kda_train_ops_bytes", None)
    ms = scope_table.ms_per_update(run, "relayrl_kda")
    if count is None or not ms:
        return None
    ops, nbytes = count(run.config, int(run.traffic["traj_per_update"]),
                        int(run.traffic["traj_len"]))
    by_ops = ops / run.peaks["bf16_flops_per_s"]
    by_bytes = nbytes / run.peaks["hbm_bytes_per_s"]
    run.notes["kda_roofline"] = {
        "bound": "compute" if by_ops >= by_bytes else "memory",
        "least_s_by_operations": by_ops, "least_s_by_bytes": by_bytes,
        "kda_s_per_update": ms / 1e3}
    return 100.0 * max(by_ops, by_bytes) / (ms / 1e3)
