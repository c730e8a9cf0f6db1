"""Median depth of the server's raw ingest queue and decoded queue together
over the window, in updates' worth (payloads over ``traj_per_update``): what
stands between the wire and the learner thread. The driver samples both
queues' ``qsize()`` every ``queue_sample_s``. Under the credit rule it stands
near ``credit_updates`` less what is in the accumulate buffer and in flight;
0 means the senders set the rate."""


def read(run):
    depth = run.counters.get("queue_depth_median")
    if depth is None:
        return None
    return depth / int(run.traffic["traj_per_update"])
