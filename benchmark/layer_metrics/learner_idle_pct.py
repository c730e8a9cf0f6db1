"""Share of the window the learner thread waited for a decoded trajectory
(``server.timings["learner_idle_s"]``): high means the actors set the pace."""


def read(run):
    if "learner_idle_s" not in run.timings or not run.window_s:
        return None
    return 100.0 * run.timings["learner_idle_s"] / run.window_s
