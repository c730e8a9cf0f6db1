"""Parallelism: meshes, sharding rules, sharded learner compilation.

First-class in this framework where the reference has none (SURVEY.md §2.3
"Parallelism strategies: none present"; §7.1 item 12 requires DP, sharded
buffers, TP/FSDP, and sequence-parallel hooks).
"""

from relayrl_tpu.parallel.mesh import (
    AXES,
    accelerator_devices,
    data_axes,
    make_mesh,
    resolve_mesh_shape,
    single_device_mesh,
)
from relayrl_tpu.parallel.sharding import (
    batch_pspec,
    batch_sharding,
    param_pspec,
    params_shardings,
    replicated,
    sequence_batch_pspec,
    state_shardings,
)
from relayrl_tpu.parallel.learner import (
    make_sharded_update,
    place_batch,
    place_state,
)
from relayrl_tpu.parallel.context import current_mesh, use_mesh
from relayrl_tpu.parallel.distributed import (
    broadcast_from_coordinator,
    initialize_distributed,
    is_coordinator,
)
from relayrl_tpu.parallel.ring import (
    make_ring_attention,
    ring_attention_sharded,
)
from relayrl_tpu.parallel.ring_flash import (
    make_ring_flash_attention,
    ring_flash_attention_sharded,
)

__all__ = [
    "AXES",
    "accelerator_devices",
    "data_axes",
    "make_mesh",
    "resolve_mesh_shape",
    "single_device_mesh",
    "batch_pspec",
    "batch_sharding",
    "param_pspec",
    "params_shardings",
    "replicated",
    "sequence_batch_pspec",
    "state_shardings",
    "make_sharded_update",
    "place_batch",
    "place_state",
    "current_mesh",
    "use_mesh",
    "broadcast_from_coordinator",
    "initialize_distributed",
    "is_coordinator",
    "make_ring_attention",
    "ring_attention_sharded",
    "make_ring_flash_attention",
    "ring_flash_attention_sharded",
]
