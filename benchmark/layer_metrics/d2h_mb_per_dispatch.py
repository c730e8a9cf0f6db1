"""Bytes of the ``[lanes, unroll]`` window one fused dispatch hands the
host, in MB: the sum over the arrays the host's jitted window producer
returns beside the carry (observations, actions, rewards, both end flags,
the pre-reset observations, ``logp_a`` and ``v``), which ``rollout()``
brings back with one ``device_get`` — counted by the driver where the host
looks the producer up, the mean over the window's dispatches."""


def read(run):
    size = run.counters.get("d2h_bytes_per_dispatch")
    return None if not size else size / 1e6
