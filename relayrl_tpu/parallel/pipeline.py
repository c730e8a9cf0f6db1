"""Pipeline parallelism: GPipe-style microbatch schedule over the ``pp``
mesh axis.

The reference has no model parallelism of any kind (SURVEY.md §2.3
"Parallelism strategies: none present"); this is one of the TPU-first
additions §7.1 item 12 requires. Design follows the standard JAX/SPMD
pipeline recipe: the layer stack is *stacked* on a leading axis sharded
over ``pp`` (each device owns a contiguous stage of layers), activations
hand off stage-to-stage with ``lax.ppermute`` (neighbor ICI hops — the
``pp`` axis is last in the mesh order so stages are adjacent devices),
and a ``lax.scan`` over ``n_micro + n_stages - 1`` ticks drains the
bubble. Everything is differentiable (scan + ppermute + psum transpose
cleanly), so the same function serves forward and backward of the jitted
learner step.

Schedule (stage s processes microbatch ``t - s`` at tick ``t``)::

    tick:     0    1    2    3    4        (M=3 microbatches, S=3 stages)
    stage 0:  m0   m1   m2   -    -
    stage 1:  -    m0   m1   m2   -
    stage 2:  -    -    m0   m1   m2   ->  outputs at ticks S-1 .. S+M-2

The final psum over ``pp`` replicates the last stage's outputs to every
stage (activation-sized, negligible next to the matmuls), which keeps the
output spec pp-free so downstream loss code is unchanged.
"""

from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from relayrl_tpu.parallel.mesh import data_axes


def resolve_microbatches(local_batch: int, n_stages: int,
                         requested: int | None = None) -> int:
    """Pick a microbatch count: the requested value when it divides the
    per-data-shard batch, else the largest divisor of ``local_batch`` not
    exceeding ``max(requested, n_stages)`` (more microbatches shrink the
    pipeline bubble — fraction (S-1)/(M+S-1))."""
    if requested is not None and local_batch % requested == 0:
        return requested
    target = max(requested or 0, n_stages)
    best = 1
    for m in range(1, local_batch + 1):
        if local_batch % m == 0 and m <= target:
            best = m
    return best


def pipeline_apply(stage_fn: Callable, stage_params, x: jax.Array,
                   mesh: Mesh, n_microbatches: int | None = None,
                   axis: str = "pp") -> jax.Array:
    """Apply a pipelined layer stack to activations ``x``.

    ``stage_params``: pytree whose leaves have a leading layer axis
    divisible by the ``pp`` size (placed with ``P("pp", ...)`` by the param
    rules); each device receives its own ``layers_per_stage`` slice.
    ``stage_fn(local_params, h) -> h`` applies one stage's layers (usually
    an inner ``lax.scan`` over the local slice).
    ``x``: global ``[B, ...]`` activations, batch sharded over dp×fsdp.
    """
    n_stages = mesh.shape[axis]
    if n_stages <= 1:
        return stage_fn(stage_params, x)
    leaves = jax.tree.leaves(stage_params)
    bad = [tuple(l.shape) for l in leaves if l.shape[0] % n_stages != 0]
    if bad:
        raise ValueError(
            f"layer stack of {leaves[0].shape[0]} layers is not divisible "
            f"by the pp mesh axis ({n_stages} stages); pick n_layers as a "
            f"multiple of pp (offending leaf shapes: {bad[:3]})")
    daxes = data_axes(mesh)
    bspec = daxes if daxes else None
    data = math.prod(mesh.shape[ax] for ax in daxes) if daxes else 1
    local_b = x.shape[0] // data
    n_micro = resolve_microbatches(local_b, n_stages, n_microbatches)

    x_spec = P(bspec, *([None] * (x.ndim - 1)))
    param_specs = jax.tree.map(
        lambda leaf: P(*((axis,) + (None,) * (leaf.ndim - 1))), stage_params)

    def per_device(params_local, x_local):
        s_idx = jax.lax.axis_index(axis)
        mbs = x_local.reshape(n_micro, local_b // n_micro,
                              *x_local.shape[1:])
        ticks = n_micro + n_stages - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(buf, t):
            feed = jax.lax.dynamic_index_in_dim(
                mbs, jnp.minimum(t, n_micro - 1), axis=0, keepdims=False)
            inp = jnp.where(s_idx == 0, feed, buf)
            out = stage_fn(params_local, inp)
            nxt = jax.lax.ppermute(out, axis, perm)
            return nxt, out

        _, outs = jax.lax.scan(tick, jnp.zeros_like(mbs[0]),
                               jnp.arange(ticks))
        # Valid outputs live on the LAST stage at ticks S-1 .. S+M-2;
        # everything else is bubble garbage — zero it and psum to
        # replicate the result across stages.
        ys = jax.lax.dynamic_slice_in_dim(outs, n_stages - 1, n_micro,
                                          axis=0)
        ys = jnp.where(s_idx == n_stages - 1, ys, jnp.zeros_like(ys))
        ys = jax.lax.psum(ys, axis)
        return ys.reshape(x_local.shape)

    return jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(param_specs, x_spec),
        out_specs=x_spec,
        check_vma=False,
    )(stage_params, x)
