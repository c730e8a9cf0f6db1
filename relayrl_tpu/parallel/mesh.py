"""Device-mesh construction.

The reference has no device story at all (SURVEY.md §2.3: "Parallelism
strategies: none present") — the TPU-native framework makes the mesh a
first-class config object. Axis convention (order matters for ICI layout):

* ``dp``   — data parallel (batch split, gradient psum)
* ``fsdp`` — fully-sharded data parallel (params sharded, batch also split)
* ``ep``   — expert parallel (MoE expert stacks sharded over experts —
             :mod:`relayrl_tpu.models.moe`; GSPMD inserts the
             dispatch/combine collectives)
* ``tp``   — tensor parallel (weight matrices split within a layer)
* ``sp``   — sequence/context parallel (trajectory time axis, ring
             collectives — long-context path)
* ``pp``   — pipeline parallel (layer stages, ppermute activation
             hand-off — :mod:`relayrl_tpu.parallel.pipeline`); last in the
             axis order so consecutive stages land on adjacent device ids
             (ICI neighbors on a real slice)

Config form (learner.mesh in relayrl_config.json): ``{"dp": -1, "fsdp": 1,
"ep": 1, "tp": 1, "sp": 1, "pp": 1}`` where -1 means "fill with the
remaining devices".
"""

from __future__ import annotations

from typing import Mapping, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

AXES = ("dp", "fsdp", "ep", "tp", "sp", "pp")


def resolve_mesh_shape(spec: Mapping[str, int], n_devices: int) -> dict[str, int]:
    """Resolve a mesh spec against a device count (one -1 axis fills)."""
    shape = {ax: int(spec.get(ax, 1)) for ax in AXES}
    fill_axes = [ax for ax, v in shape.items() if v == -1]
    if len(fill_axes) > 1:
        raise ValueError(f"at most one mesh axis may be -1, got {fill_axes}")
    fixed = 1
    for ax, v in shape.items():
        if v != -1:
            if v <= 0:
                raise ValueError(f"mesh axis {ax} must be positive or -1, got {v}")
            fixed *= v
    if fill_axes:
        if n_devices % fixed != 0:
            raise ValueError(
                f"{n_devices} devices not divisible by fixed axes product {fixed}")
        shape[fill_axes[0]] = n_devices // fixed
    else:
        if fixed != n_devices:
            raise ValueError(
                f"mesh {shape} needs {fixed} devices but {n_devices} available")
    return shape


def make_mesh(spec: Mapping[str, int] | None = None,
              devices: Sequence[jax.Device] | None = None) -> Mesh:
    """Build a Mesh over the given (default: all) devices."""
    devices = list(devices if devices is not None else jax.devices())
    shape = resolve_mesh_shape(spec or {"dp": -1}, len(devices))
    dims = [shape[ax] for ax in AXES]
    arr = np.asarray(devices).reshape(dims)
    return Mesh(arr, AXES)


def accelerator_devices() -> list[jax.Device]:
    """The devices a single-process learner spreads over without being
    told to: every device of an accelerator backend, none of the CPU's.
    ``--xla_force_host_platform_device_count`` devices are a technique for
    testing sharded code, so code that wants them passes them to
    :func:`make_mesh` itself."""
    return [] if jax.default_backend() == "cpu" else list(jax.devices())


def single_device_mesh() -> Mesh:
    return make_mesh({ax: 1 for ax in AXES}, jax.devices()[:1])


def data_axes(mesh: Mesh) -> tuple[str, ...]:
    """Axes the batch dimension shards over (dp and fsdp both consume batch)."""
    return tuple(ax for ax in ("dp", "fsdp") if mesh.shape[ax] > 1)
