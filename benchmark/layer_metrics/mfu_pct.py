"""End-to-end model FLOP/s utilisation: the operations forward + backward
need per valid timestep (``benchmark/flops.py``, from shapes; nothing
recomputed counts) x timesteps per second of the measured window, over
chips x the published bf16 peak. Not a roofline share of any kernel."""


def read(run):
    peak = run.peaks.get("bf16_flops_per_s")
    rate = run.train_rate
    if not peak or not rate or not run.train_flops_per_sample:
        return None
    chips = int(run.spec["cell"]["chips"])
    return 100.0 * run.train_flops_per_sample * rate / (chips * peak)
