"""What every layer shares, in a block's param scope — the arch's norm, a
dense layer of the block's dtype and bias, the FFN half — and the operator
``"none"``: a layer that is its FFN alone (``layer_types`` entry ``"ffn"``:
one norm, one residual; Nemotron-H keeps its experts in layers of their own).

:func:`block_ffn` is what every operator's ``apply`` ends in: the arch's
dense FFN (``ffn``: "gelu" | "relu2" ungated, "swiglu" | "reglu" with
``mlp_gate`` beside ``mlp_up``; ``d_ff`` wide, ``mlp_ratio * d_model`` by
default) or, where the block has experts, the expert layer of
:mod:`relayrl_tpu.models.moe`. It keeps no state. Plain functions
throughout: a module method would be wrapped by flax once per call."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn

from relayrl_tpu.models.mlp import GATED_FFN, UNGATED_FFN
from relayrl_tpu.ops.scopes import FFN, MOE_ELEMENTWISE


class _ZeroCentredRMSNorm(nn.Module):
    """RMSNorm whose learned weight is an offset from one, ``x^ (1 + w)``
    (Qwen3-Next's, Gemma's), float32. ``w`` is seeded at std 0.02 round 0
    (the sources start it at 0) so that ``1 + w`` and ``w`` differ."""

    epsilon: float = 1e-6

    @nn.compact
    def __call__(self, x):
        w = self.param("scale", nn.initializers.normal(0.02),
                       (x.shape[-1],), jnp.float32)
        x = x.astype(jnp.float32)
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), -1, keepdims=True)
            + self.epsilon) * (1.0 + w)


def norm(arch_norm: str, eps, name: str, zero_centred: bool = False):
    """The arch's normalisation layer in float32: ``"layer"`` (LayerNorm,
    scale + bias) or ``"rms"`` (RMSNorm, scale only; ``zero_centred``: the
    weight is ``1 + scale``). ``eps=None`` keeps flax's default, 1e-6."""
    if arch_norm not in ("layer", "rms"):
        raise ValueError(f"unknown norm {arch_norm!r} (layer | rms)")
    kw = {} if eps is None else {"epsilon": float(eps)}
    if zero_centred:
        if arch_norm != "rms":
            raise ValueError("norm_zero_centred needs norm 'rms'")
        return _ZeroCentredRMSNorm(name=name, **kw)
    cls = nn.LayerNorm if arch_norm == "layer" else nn.RMSNorm
    return cls(dtype=jnp.float32, name=name, **kw)


def block_norm(block, name: str, kind: str | None = None):
    """``block``'s norm under ``name`` (``kind``: "rms" for the q/k norms)."""
    return norm(kind or block.norm, block.norm_eps, name,
                block.norm_zero_centred)


def block_dense(block, features: int, name: str):
    return nn.Dense(features, dtype=block.compute_dtype, name=name,
                    use_bias=block.use_bias)


def block_residual(block, x, h, name: str):
    """A half's residual add, ``x + h`` in ``x``'s dtype — under the arch's
    ``norm_sandwich`` ``x + norm(h)``: a second norm, ``name``, on the
    half's OUTPUT (Ouro's ``input_layernorm_2`` /
    ``post_attention_layernorm_2``); under ``residual_multiplier`` ``m``,
    ``x + m * h`` (Granite's 0.22; 1: no product is traced). The caller
    opens the scope."""
    h = h.astype(x.dtype)
    if block.norm_sandwich:
        h = block_norm(block, name)(h)
    if block.residual_multiplier != 1.0:
        h = block.residual_multiplier * h
    return x + h


def block_ffn(block, x, layer_in):
    """``x + FFN(norm(x))`` in ``block``'s param scope: the arch's dense
    FFN or the MoE layer. ``layer_in``: the rows of the layer's own input
    that ``x``'s rows came from, which the MoE layer's router reads under
    ``moe_router_input: "layer"``. The norm and the residual are the FFN's
    element-wise passes, the dense one's or the expert layer's
    (``ops/scopes.py``). A layer that is an operator alone (``has_ffn``
    false) has none: ``x``."""
    if not block.has_ffn:
        return x
    part = MOE_ELEMENTWISE if block.moe_experts > 0 else FFN
    with jax.named_scope(part):
        h = block_norm(block, "ln_mlp")(x)
    width = block.d_ff or block.mlp_ratio * block.d_model
    if block.ffn not in UNGATED_FFN and block.ffn not in GATED_FFN:
        raise ValueError(f"unknown ffn {block.ffn!r} "
                         f"(gelu | relu2 | swiglu | reglu)")
    if block.moe_experts > 0:
        from relayrl_tpu.models.moe import MoEMLP

        if block.moe_router_input not in ("ffn", "layer"):
            raise ValueError(f"unknown moe_router_input "
                             f"{block.moe_router_input!r} (ffn | layer)")
        # the policy's record of what this layer's shapes run as, asked
        # here and not from inside the layer: nothing stands between the
        # layer's call and its kernels that was not there (ROADMAP 1.5)
        if "moe" in block.fns:
            block.fns["moe"](h.shape[0] * h.shape[1], block.moe_top_k,
                             block.moe_experts, block.moe_kw.get("held"),
                             block.moe_dispatch)
        h = MoEMLP(block.d_model, block.moe_d_ff or width,
                   block.moe_experts, block.moe_top_k, block.compute_dtype,
                   norm_topk_prob=block.moe_norm_topk_prob, ffn=block.ffn,
                   dispatch=block.moe_dispatch, use_bias=block.use_bias,
                   **block.moe_kw, name="moe")(
                       h, layer_in if block.moe_router_input == "layer"
                       else None)
        with jax.named_scope(part):
            return block_residual(block, x, h, "ln_mlp_out")
    with jax.named_scope(part):
        h = h.astype(block.compute_dtype)
        up = block_dense(block, width, "mlp_up")(h)
        if block.ffn in GATED_FFN:
            h = GATED_FFN[block.ffn](
                block_dense(block, width, "mlp_gate")(h)) * up
        else:
            h = UNGATED_FFN[block.ffn](up)
        h = block_dense(block, block.d_model, "mlp_down")(h)
        return block_residual(block, x, h, "ln_mlp_out")


# -- the operator "none" (``layers``' interface) ----------------------------

def _moe_record(arch):
    """The expert layer's entry: nothing to pick — the layer takes its form
    by its own rule (``models/moe.held_form``) — but the record of it,
    ``Policy.moe_backends``: what each distinct layer shape's sparse
    dispatch runs as, one ``[moe]`` line a shape."""
    resolved: dict[tuple, str] = {}

    def say(*shape):
        from relayrl_tpu.models.moe import dispatch_form

        said = dispatch_form(*shape)
        if said is None or resolved.get(said[0]) == said[1]:
            return      # the dense dispatch walks no slots; or said before
        key, ran, text = said
        resolved[key] = ran
        print(f"[moe] {text} -> {ran} "
              f"(platform {jax.default_backend()})", flush=True)

    return {"moe": say}, {"moe_backends": resolved}


def _checkpoint_record(arch):
    """The trunk's checkpoint round a whole layer (``block_checkpoint``):
    ``fns["checkpoint"](kind, names) -> (policy, said)`` for a kind of layer,
    ``(operator, "experts" | "dense" | "none")`` — a ``jax.checkpoint``
    policy, ``save_only_these_names(*names)`` that notes each value it keeps
    (the trunk asks for ONE a kind and pass: a policy an application would
    lower each layer's inner functions apart), and ``said()``, which the
    trunk calls once its layers have been traced: ``Policy.checkpoint_kept[kind] = {name: bytes}`` (a
    name marks one value a layer; the bytes of one application) and one
    ``[checkpoint]`` line a distinct kind, the names and the MB they hold at
    the traced shape. The policy is asked when a gradient is taken through
    the layer: a forward alone keeps nothing and says nothing."""
    resolved: dict[tuple, dict] = {}

    def checkpoint(kind, names):
        listed = jax.checkpoint_policies.save_only_these_names(*names)
        kept: dict[str, int] = {}

        def policy(prim, *avals, **params):
            keep = listed(prim, *avals, **params)
            if keep:
                kept[params["name"]] = avals[0].size * avals[0].dtype.itemsize
            return keep

        def said():
            if not kept or resolved.get(kind) == kept:
                return      # no gradient through it; or said before
            resolved[kind] = dict(kept)
            parts = ", ".join(f"{name} {size / 1e6:.1f}"
                              for name, size in kept.items())
            print(f"[checkpoint] {kind[0]}+{kind[1]} keeps its input and "
                  f"{parts} = {sum(kept.values()) / 1e6:.1f} MB by name "
                  f"(platform {jax.default_backend()})", flush=True)

        return policy, said

    return {"checkpoint": checkpoint}, {"checkpoint_kept": resolved}


# every layer kind ends in :func:`block_ffn`, which asks ``fns["moe"]``; the
# trunk asks ``fns["checkpoint"]`` round a layer of any kind
KERNELS = (_moe_record, _checkpoint_record)
# the FFN is per row: a final layer runs for the readout row alone
ROW_READOUT = True
CACHE_RESTARTS = "masked"    # no state at all


def apply(block, x, cache, t, readout_idx, n_valid):
    if readout_idx is not None:
        x = jax.lax.dynamic_slice_in_dim(x, readout_idx, 1, axis=1)
    out = block_ffn(block, x, x)
    return out if cache is None else (out, ())


def init_cache(cfg, d_model, batch, length, dtype, window):
    return ()
