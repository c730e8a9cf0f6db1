"""Wire bytes of trajectories the server admitted and handed to the learner,
per second of the window: ``server.stats["trajectories"]`` (window delta)
times the bytes of one unroll's frame as the senders shipped it (every frame
of a mix holds the same steps; the driver reports the pool's mean), in MB
(1e6). What the transport thread, the ingest queue and the decode thread carry
when the learner sets the rate."""


def read(run):
    nbytes = run.counters.get("ingest_wire_bytes")
    if not nbytes or not run.window_s:
        return None
    return nbytes / run.window_s / 1e6
