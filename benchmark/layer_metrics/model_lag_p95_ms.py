"""95th percentile of the model lags whose median is ``model_lag_p50_ms``
(same samples). Some 170 samples a run: a few host stalls set it."""


def read(run):
    return run.e2e.get("model_lag_p95_ms")
