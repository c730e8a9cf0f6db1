"""Share of the window the learner thread spent blocked on the in-flight
fence (``InflightWindow.device_wait_s``): high means the device paces."""


def read(run):
    if "device_wait_s" not in run.counters or not run.window_s:
        return None
    return 100.0 * run.counters["device_wait_s"] / run.window_s
