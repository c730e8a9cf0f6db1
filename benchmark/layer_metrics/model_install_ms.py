"""The actor's half of model lag: from the subscriber thread handing a model
frame to the agent to the frame installed (decode, copy, the wait for the
step's lock, the install), per install
(``server.timings["actor_model_install_s"]`` over
``server.stats["actor_installs"]``, window deltas, all actors)."""


def read(run):
    n = run.stats.get("actor_installs")
    if not n or "actor_model_install_s" not in run.timings:
        return None
    return 1e3 * run.timings["actor_model_install_s"] / n
