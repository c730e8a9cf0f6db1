"""Passes an expert layer's sparse dispatch took over its row buffers, mean
over the expert layers: the program's own counter (``moe_row_passes`` of the
update's metrics: the trip count of each held-experts layer's pass loop —
``ceil(live rows / buffer rows)`` — summed over the expert layers), as the
argument the learner writes on its ``rl:dispatch.fence`` spans while a
profiler runs, over the number of expert layers; mean over the updates
fenced in the traced window. 1.0: one compact pass a layer did all the
work; above 1: the router sent a layer more rows than its buffer has (the
module's margin is too small for this routing) and the layer walked the
buffer again — nothing is dropped, the passes are the price. None for a
program that writes no such argument."""

from benchmark import program_trace


def read(run):
    passes = program_trace.mean_arg(run, "rl:dispatch.fence",
                                    "moe_row_passes")
    if passes is None:
        return None
    cfg = run.config
    return passes / (int(cfg["num_hidden_layers"])
                     - int(cfg["num_dense_layers"]))
