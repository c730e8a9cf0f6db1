"""Share of an update's token-slots that went to experts this chip holds,
in percent: the program's own counter (``moe_held_slots`` of the update's
metrics: the group sizes the dispatch computes, summed over the expert
layers), as the argument the learner writes on its ``rl:dispatch.fence``
spans while a profiler runs, over all ``N x k`` slots of those layers; mean
over the updates fenced in the traced window. 100 x held / E at even
routing (12.5 for 8 of 64). A routing share has no better direction: it
says how much of the layer's work fell to this chip, and reads beside
``moe_ffn_ms`` and ``moe_held_ffn_roofline``, which move with it
(``BENCHMARK.json`` has to give every metric a direction; "higher" there
means only "more held rows, more grouped-matmul work"). None for a program
that writes no such argument."""

from benchmark import program_trace


def read(run):
    held = program_trace.mean_arg(run, "rl:dispatch.fence", "moe_held_slots")
    if held is None:
        return None
    cfg, tr = run.config, run.traffic
    slots = (int(tr["traj_per_update"]) * int(tr["traj_len"])
             * int(cfg["num_experts_per_tok"])
             * (int(cfg["num_hidden_layers"]) - int(cfg["num_dense_layers"])))
    return 100.0 * held / slots
