"""The whole rollout step's share of the chip's peak: the operations ONE
NEW env step of one lane needs (``benchmark/flops_rollout.py``: every
weight once, the scores over the keys its position may see, at the mean
position the window ran) x env steps of all lanes per second of the
measured window, over chips x the published bf16 peak. Counted by what a
step needs, not by what the program executes: a program that runs a
lane's whole window again for every step is credited with one row of it.
Not a roofline share of any kernel."""


def read(run):
    peak = run.peaks.get("bf16_flops_per_s")
    rate = run.e2e.get("rollout_steps_per_s")
    per_step = run.counters.get("rollout_flops_per_step")
    if not peak or not rate or not per_step:
        return None
    chips = int(run.spec["cell"]["chips"])
    return 100.0 * per_step * rate / (chips * peak)
