"""What the two recurrent mixers (:mod:`.mamba2`, :mod:`.gdn`) share: the
frame of a layer round its mixer, the depthwise convolution both run and
the record of what their kernels ran as.

A mixer has no positions (its recurrence orders the tokens) and a state
whose size does not grow with the sequence: ``(the convolution's last ``taps
- 1`` input rows, the recurrence's state in float32)``. One cached row
continues from it in one step of the recurrence, O(1) in the position;
several rows (a prefill) run the chunked form from it and leave the state
after the ``n_valid`` real ones. Both are a state without positions
(``layers.CACHE_RESTARTS = "zeroed"``): where the caller says a new sequence
may start over a used cache, the block reads them as zeros at position 0 and
nothing here changes. The readout row of a window needs the whole
recurrence before it: the core runs a final mixer layer in full and slices.

``ops.ssd.ssd``, ``ops.gdn.gdn`` and ``ops.conv.conv`` pick their
implementation at trace time from the platform and the shapes (the Pallas
kernels on a TPU where the shapes tile, plain XLA on CPU actor hosts, in CI
and for a shape that does not), so that the SAME arch config serves both.
That choice is never silent: a policy carries each entry behind
:func:`kernel`'s record.
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax
from jax.ad_checkpoint import checkpoint_name

from relayrl_tpu.models.layers.block import (
    block_ffn,
    block_norm,
    block_residual,
)
from relayrl_tpu.ops import conv as conv_ops
from relayrl_tpu.ops.scopes import OP_PROJ


def kernel(tag: str, fn: Callable, describe: Callable) -> Callable:
    """A ``KERNELS`` entry (``layers``' interface): ``(arch) -> ({tag: fn
    behind its record}, {"<tag>_backends": the record})``. ``describe(*args)
    -> (key, backend, text)`` for ``fn``'s own arguments: the record's key
    from their shapes, the name of what ``fn`` runs them as, and the shapes
    as the line says them. The record (a ``Policy`` field) maps every traced
    shape to what it was compiled as, and each new entry prints one
    ``[tag]`` line naming the platform it was resolved on."""
    def resolve(arch):
        resolved: dict[tuple, str] = {}

        def call(*args):
            key, ran, text = describe(*args)
            if resolved.get(key) != ran:
                resolved[key] = ran
                print(f"[{tag}] {text} -> {ran} "
                      f"(platform {jax.default_backend()})", flush=True)
            return fn(*args)

        return {tag: call}, {f"{tag}_backends": resolved}

    return resolve


# What a checkpoint round the WHOLE layer (the trunk's ``block_checkpoint``)
# keeps of a mixer beside the mixer's own ``kept``, dearest first by device
# time a byte: the mixer's output (named in :func:`mixer_apply`) — with it
# that checkpoint's backward runs no part of the mixer to feed the FFN behind
# it — and the input projection's product before its split (named in each
# mixer's ``mix``), the widest matmul of the layer. A mixer's own checkpoint lists neither: to
# a trunk without ``block_checkpoint`` both are marks that lower to nothing.
MIXER_OUT = "relayrl_mixer_out"
MIXER_IN = "relayrl_mixer_in"
BLOCK_KEPT = (MIXER_OUT, MIXER_IN)


def mixer_apply(build: Callable, kept: Sequence[str]) -> Callable:
    """A mixer's ``apply``: ``x + mix(norm(x))`` behind the layer's one
    norm, then the FFN if the layer has one. ``build(block, x.shape) ->
    (weights, mix, back)`` makes the mixer's parameters in ``block``'s scope
    and returns them with ``mix(h, weights, conv_rows, state, n_valid) ->
    (the mixer's output, its convolution's input with the rows before it,
    the state after the last real row)`` and ``back``, how many rows the
    convolution looks back. (The frame is the ``apply`` itself, not a
    function the mixers call: a frame more above ``mix`` moves the depth the
    kernels are traced at, ROADMAP 1.5.)

    Full mode (``cache=None``), the learner's: the mixer's inner activations
    (its wide projection, the convolution's rows, the gate and the norm in
    float32: 1.7 GB a Mamba-2 layer at 16,384 tokens) are made again in the
    backward from the normed rows; of them only the ``kept`` names are
    saved — the recurrence's output and, where its backward needs more than
    that, what its forward wrote for it —, so that the backward runs the
    recurrence's backward alone and never its forward a second time.

    Under a checkpoint round the whole layer (``block_checkpoint``) with an
    FFN behind the mixer, the outer backward first runs the mixer again for
    the FFN's input — from the kept recurrence's output on: the gate, the
    norm and the output projection, and the input projection too where the
    gate is a slice of it (the gated delta rule's ``z``) —, then the mixer's
    own checkpoint runs its inside again. The outer policy is applied inside
    the nested checkpoint too, and what it saves enters the inner one as an
    input: with :data:`BLOCK_KEPT` in it (and in it alone) the output named
    here spares the outer backward the mixer altogether, and the input
    projection's named product is made once where it was made twice (three
    times for that ``z``)."""

    def apply(block, x, cache, t, readout_idx, n_valid):
        weights, mix, back = build(block, x.shape)
        with jax.named_scope(OP_PROJ):
            h = block_norm(block, "ln_attn")(x).astype(block.compute_dtype)
        if cache is None:
            y, _, _ = jax.checkpoint(
                mix, policy=jax.checkpoint_policies.save_only_these_names(
                    *kept))(h, weights, None, None, None)
            if block.has_ffn or block.norm_sandwich:
                # where something behind the mixer reads it (a residual
                # add alone does not)
                y = checkpoint_name(y, MIXER_OUT)
        else:
            y, padded, state = mix(h, weights, *cache, n_valid)
        with jax.named_scope(OP_PROJ):
            x_out = block_residual(block, x, y, "ln_attn_out")
        out = block_ffn(block, x_out, x)
        if cache is None:
            return out
        # padded row j is the convolution's input row j - back: after n real
        # rows the convolution wants rows n - back .. n - 1
        n = x.shape[1] if n_valid is None else n_valid
        rows = jax.lax.dynamic_slice_in_dim(padded, n, back, axis=1)
        return out, (rows.astype(cache[0].dtype), state)

    return apply


def mixer_conv(x, w, bias, state, scope: str, conv_fn: Callable):
    """A mixer's convolution: ``silu(conv(x) + bias)``, depthwise and
    causal, ``L = w.shape[0]`` taps, returns ``(out, x_padded)``. ``state
    [batch, L-1, c]`` holds the rows of ``x`` before this call's first
    (zeros at a sequence's start, which ``None`` means); ``x_padded =
    concat(state, x)`` is what a cache takes its next rows from (full mode
    drops it unmade). Under the mixer's own named ``scope``, the tap sums in
    float32: :mod:`relayrl_tpu.ops.conv`, as two Pallas kernels on a TPU at
    a sequence's start and as plain XLA everywhere else (``conv_fn``: the
    policy's record of it, :data:`CONV_KERNEL`). ``bias`` None: none is
    added."""
    out = conv_fn(x, w, bias, state, scope)
    with jax.named_scope(scope):
        return out, conv_ops.padded(x, w.shape[0], state)


def _conv_shape(x, w, bias, state, scope):
    key = (int(x.shape[1]), int(x.shape[2]), int(w.shape[0]),
           state is not None, x.dtype.name)
    return key, conv_ops.backend(*key[:4]), (
        f"T={key[0]} columns={key[1]} taps={key[2]} "
        f"bias={'no' if bias is None else 'yes'} "
        f"from={'cache' if key[3] else 'start'} {key[4]}")


# ``Policy.conv_backends``: ``{(T, columns, taps, continues from a cache's
# rows, dtype): "conv_pallas" | "conv_xla"}``; ONE entry, both mixers'
CONV_KERNEL = kernel("conv", conv_ops.conv, _conv_shape)
