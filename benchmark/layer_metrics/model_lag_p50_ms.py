"""Median model lag: over every (version dispatched in the window, actor)
pair, the time from the learner thread entering ``train_on_batch`` for
version v to that actor's install of a version >= v (one host,
``CLOCK_MONOTONIC``; the sample count is in the run's notes)."""


def read(run):
    return run.e2e.get("model_lag_p50_ms")
