"""The Mosaic (Pallas flash attention) calls' share of their roofline: the
least time the chip could take for the attention layers executed in the
traced window — the larger of operations / peak FLOP/s and bytes / peak
bytes/s, from ``flops.flash_attention_train_ops_bytes`` — over the device
time of every ``custom-call/tpu_custom_call`` operation in the reduced trace.

Every Mosaic call of this program is a flash kernel (forward, backward dq,
backward dk/dv: 3 a layer, counted in the compiled update outside the
window), so the group is unambiguous even though no kernel carries a name
of its own yet; the split between the three waits for the tracing issue.
"""

from benchmark import flops


def read(run):
    t = run.trace_reduced
    per_update = run.notes.get("mosaic_calls_in_update")
    cfg = run.config
    if not t or not per_update or "n_head" not in cfg:
        return None
    keys = [k for k in t["op_s"] if k.endswith("/tpu_custom_call")]
    seconds = sum(t["op_s"][k] for k in keys)
    calls = sum(t["op_n"][k] for k in keys)
    if not seconds or not calls:
        return None
    layers = int(cfg["n_layer"])
    updates = calls / per_update          # updates' worth of kernels traced
    ops, nbytes = flops.flash_attention_train_ops_bytes(
        int(run.traffic["traj_per_update"]), int(cfg["n_head"]),
        int(run.traffic["traj_len"]),
        int(cfg["n_embd"]) // int(cfg["n_head"]))
    least = max(ops / run.peaks["bf16_flops_per_s"],
                nbytes / run.peaks["hbm_bytes_per_s"])
    run.notes["flash_roofline"] = {
        "bound": "compute" if ops / run.peaks["bf16_flops_per_s"]
        >= nbytes / run.peaks["hbm_bytes_per_s"] else "memory",
        "mosaic_seconds": seconds, "mosaic_calls": calls,
        "least_s_per_layer": least}
    return 100.0 * least * layers * updates / seconds
