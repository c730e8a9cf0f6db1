"""Share of the window a replay process stood at its credit (held back by the
driver: ``drivers/loop_replay.credit_allowance``), mean over the processes,
from the processes' own stamps clipped to the window. Near 100 less a
process's send time when the learner sets the rate, near 0 when the senders
do. The driver's own count: none of the program's."""


def read(run):
    held = run.counters.get("credit_wait_s_mean")
    if held is None or not run.window_s:
        return None
    return 100.0 * held / run.window_s
