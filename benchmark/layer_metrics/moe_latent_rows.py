"""Latent-wide rows an update's expert layers move through the sort and the
un-sort, where the routed experts work in a latent
(``moe_latent_size`` in the configuration): the token-slots the program
itself counted as routed to held experts (``moe_held_slots`` of the update's
metrics, summed over the expert layers), as the argument the learner writes
on its ``rl:dispatch.fence`` spans while a profiler runs; mean over the
updates fenced in the traced window. Each such row is gathered once into a
layer's row buffer, goes through the held experts' grouped matmuls and is
gathered back, ``moe_latent_size`` wide where every other configuration's
rows are as wide as the residual stream. ``N x k x held / E`` a layer at
even routing (5,632 a layer for 16,384 tokens, top-22, 8 of 512 held). None
for a configuration without a latent or a program that writes no such
argument."""

from benchmark import program_trace


def read(run):
    if not run.config.get("moe_latent_size"):
        return None
    return program_trace.mean_arg(run, "rl:dispatch.fence", "moe_held_slots")
