"""The recurrence's step as a share of its roofline, which is of bytes: the
least time the chip could take to move what a scan step's recurrences must
move — every lane's float32 state read once and written once in each Mamba-2
layer, the reference file's ``ssm_step_bytes(cfg, lanes)``
(``benchmark/flops_granite.py``) over the peak bytes/s — over ``ssm_step_ms``.
The step's ``x``, ``B``, ``C`` and ``dt`` (a few KB a lane) are not counted,
so the share errs low. A reading over 100% is a wrong count, not a
result. None where the configuration counts no such bytes or the trace has no
``relayrl_ssd`` in its rollout."""

from benchmark import rollout_scopes


def read(run):
    count = getattr(run.reference, "ssm_step_bytes", None)
    ms = rollout_scopes.ms_per_scan_step(run, "relayrl_ssd")
    peak = run.peaks.get("hbm_bytes_per_s")
    if count is None or not ms or not peak:
        return None
    nbytes = count(run.config, int(run.traffic["lanes"]))
    run.notes["ssm_step_roofline"] = {
        "bytes_per_scan_step": nbytes, "least_s": nbytes / peak,
        "ssm_s_per_scan_step": ms / 1e3}
    return 100.0 * (nbytes / peak) / (ms / 1e3)
