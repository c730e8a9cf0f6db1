"""GB of parameters the fused actor host holds on the device: its gauge
``relayrl_actor_param_bytes`` (``runtime/anakin.py``: the sum over the leaves
it installed, a matmul weight at the compute type). None where the program
has no such gauge."""

from benchmark import actor_gauges


def read(run):
    nbytes = actor_gauges.read("relayrl_actor_param_bytes")
    return None if not nbytes else nbytes / 1e9
