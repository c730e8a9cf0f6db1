"""The indexers' share of their roofline: the least time the chip could take
for one update's indexers — the larger of operations / peak FLOP/s and
bytes / peak bytes/s, from the reference file's ``index_train_ops_bytes``
(``benchmark/flops_keye.py``: the three projections forward and backward, a
score for every CAUSAL pair once, the loss's backward over the KEPT pairs;
bytes of the normed rows, ``qi``, ``ki``, ``w``, their cotangents and the
selection's result) — over ``index_ms``, the device time per update under
the scope ``relayrl_index``. What a masked-dense form computes beside the
causal pairs, the threshold search's passes and everything made again in the
backward are time and no counted work. A reading over 100% is a wrong count,
not a result."""

from benchmark import scope_table


def read(run):
    count = getattr(run.reference, "index_train_ops_bytes", None)
    ms = scope_table.ms_per_update(run, "relayrl_index")
    if count is None or not ms:
        return None
    ops, nbytes = count(run.config, int(run.traffic["traj_per_update"]),
                        int(run.traffic["traj_len"]))
    by_ops = ops / run.peaks["bf16_flops_per_s"]
    by_bytes = nbytes / run.peaks["hbm_bytes_per_s"]
    run.notes["index_roofline"] = {
        "bound": "compute" if by_ops >= by_bytes else "memory",
        "least_s_by_operations": by_ops, "least_s_by_bytes": by_bytes,
        "index_s_per_update": ms / 1e3}
    return 100.0 * max(by_ops, by_bytes) / (ms / 1e3)
