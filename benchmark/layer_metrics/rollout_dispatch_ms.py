"""``AnakinActorHost.rollout()``'s own ``dispatch_s`` a dispatch, ms: from
the launch of the fused window until it is ready on the device (the host's
clock round ``block_until_ready``) — the mean over the window's dispatches."""


def read(run):
    n = run.counters.get("rollout_dispatches")
    if not n:
        return None
    return 1e3 * run.counters["rollout_dispatch_s"] / n
