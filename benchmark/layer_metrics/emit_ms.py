"""``AnakinActorHost.rollout()``'s ``encode_s`` a dispatch, ms: the
``device_get`` of the window, the rows' append to the lanes' pending columns
and, on the dispatches that fill a chunk, every lane's columnar encode and
``on_send`` — the mean over ALL the window's dispatches, so it is what the
emit costs a dispatch, not what a flushing dispatch costs."""


def read(run):
    n = run.counters.get("rollout_dispatches")
    if not n:
        return None
    return 1e3 * run.counters["rollout_emit_s"] / n
