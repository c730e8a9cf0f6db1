"""Decoder-only transformer sequence policy (the long-context model family).

No counterpart exists in the reference — its only models are 2x128 MLPs
(relayrl_framework/src/native/python/algorithms/REINFORCE/kernel.py:14-21)
and SURVEY.md §5.7 records long-context support as absent. This family is
the TPU-first addition: a causal transformer over the trajectory time axis,
so the policy conditions on history instead of a single observation, with
four attention backends selected by arch config:

* ``"dense"``     — plain softmax attention (small T, correctness anchor)
* ``"blockwise"`` — online-softmax scan over KV blocks (long T, one device)
* ``"flash"``     — fused Pallas TPU kernels (ops/flash.py; resolves to
                    blockwise off-TPU)
* ``"ring"``      — ring attention over the mesh ``sp`` axis
                    (:mod:`relayrl_tpu.parallel.ring`); requires an ambient
                    mesh (``parallel.context.use_mesh``) at trace time and
                    falls back to blockwise without one, so the SAME arch
                    config applies on CPU actor hosts and the TPU learner
                    (the heterogeneous-placement requirement of SURVEY.md
                    §7.4 item 2).

The block is described by the arch, not by knobs: ``norm`` (``"layer"`` |
``"rms"``) with ``norm_eps``, ``positions`` (``"learned"`` table |
``"rope"`` with ``rope_theta``, applied to q and k), ``qk_norm``,
``use_bias``, ``ffn`` (``"gelu"`` | ``"swiglu"``) with ``d_ff``. With none
of them given it is the GPT-2 shaped block (LayerNorm, learned positions,
biases, GELU FFN of ``mlp_ratio * d_model``) — the same parameter tree and
operations as before these keys existed. OLMoE-1B-7B's layer is
``transformer_moe_discrete`` with rms / rope / qk_norm / no bias / swiglu
(``benchmark/configs/olmoe-policy.json``).

A trunk may hold layers of several kinds (LFM2-24B-A2B's,
``benchmark/configs/lfm2-policy.json``): ``layer_types`` names each
layer's operator — ``"full_attention"`` or ``"conv"``, the gated short
convolution (:func:`_short_conv`) — and ``moe_dense_layers`` how many
leading layers keep the dense FFN of ``d_ff`` in a MoE trunk, the rest
taking the expert layer. Attention may be grouped-query (``n_kv_heads``
k/v heads under ``n_heads`` query heads, every head ``d_model //
n_heads`` wide: separate ``q_proj`` / ``k_proj`` / ``v_proj`` where the
default block splits one fused ``qkv``), with ``qk_norm: "head"``
normalising each head's width (``True``: the whole projection, OLMoE's).
RoPE turns q and k in attention layers only; a conv layer sees no
positions.

A third kind of layer is windowed attention (SmallThinker-21BA3B's,
``benchmark/configs/smallthinker-policy.json``): ``layer_types`` entry
``"sliding_attention"`` with ``sliding_window`` — query ``t`` sees the
``sliding_window`` keys up to its own — in every backend (the flash
kernels visit the band's blocks only; ``"ring"`` refuses a window) and
every mode: a windowed layer's cache is a RING of ``min(sliding_window,
length)`` rows, position ``p`` in row ``p % rows``, beside the full-length
pairs of the global layers. ``head_dim`` gives the heads a width of their
own (28 heads of 128 under a hidden size of 2560: q and the output
projection are ``n_heads * head_dim`` wide); ``rope_layers`` says, layer
by layer, which attention layers RoPE turns (the others see NO positional
signal: under ``positions: "rope"`` there is no table either);
``moe_router_input: "layer"`` hands the expert layer's router the layer's
un-normed input (models/moe.py); ``ffn: "reglu"`` gates with ReLU.

A layer may also be ONE part behind one norm, ``x + part(norm(x))``
(Nemotron-H's, ``benchmark/configs/nemotron-twotower-policy.json``), by
three more ``layer_types`` entries: ``"mamba2"`` — the Mamba-2 mixer
(:func:`_mamba_layer`: one input projection to ``[z | xBC | dt]``, a
depthwise causal convolution of ``mamba_conv_taps`` taps with bias and SiLU
over ``xBC`` (:func:`_mamba_conv`), the state-space scan of
:mod:`relayrl_tpu.ops.ssd` over ``mamba_heads`` heads of ``mamba_head_dim``
with a state of ``mamba_state`` columns and ``mamba_groups`` groups of B
and C in chunks of ``mamba_chunk``, an RMSNorm by groups of the output
gated by ``silu(z)``, the output projection) —, ``"attention"`` — global
attention and no FFN — and ``"ffn"`` — the FFN (dense, or the expert layer
past ``moe_dense_layers``) and no operator. A Mamba-2 layer's cache is the
FOURTH kind: the convolution's last ``mamba_conv_taps - 1`` rows of ``xBC``
and the ``[H, P, N]`` state in float32 — a decode step is O(1) in the
position; an ``"ffn"`` layer's is empty. ``positions: "none"`` gives a trunk
no positional signal at all (no table, no rotation: the state-space layers
order the tokens).

A fifth kind of layer is linear attention (Qwen3-Next's Gated DeltaNet,
``benchmark/configs/qwen3next-policy.json``): ``layer_types`` entry
``"linear_attention"`` — :func:`_gdn_layer`: fused projections to ``[q | k |
v | z]`` and ``[b | a]``, a ``gdn_conv_taps``-tap convolution without bias
over q, k and v, L2-normed q and k, the gated delta rule of
:mod:`relayrl_tpu.ops.gdn` on a ``[gdn_key_dim, gdn_value_dim]`` matrix state
a value head (``gdn_key_heads`` q/k heads under ``gdn_value_heads``) in
chunks of ``gdn_chunk``, an RMSNorm a head before the ``silu(z)`` gate — and
an FFN; its cache is the FIFTH kind, the convolution's last rows and the
float32 state. The attention block beside it may carry ``attn_gate`` (a q
projection twice as wide whose second half gates the attention's output),
``rope_share`` (RoPE on the first share of a head's lanes) and
``norm_zero_centred`` (RMSNorm weights as offsets from one).

Sequence ABI: ``evaluate(params, obs[B,T,D], act[B,T], mask[B,T,A]) ->
(logp[B,T], ent[B,T], v[B,T])`` — same shapes the per-step MLP family
broadcasts to, so REINFORCE/PPO updates take this policy unchanged.
``step`` treats the second-to-last axis as time (``[T,D]`` or ``[B,T,D]``)
and returns the action at the last position; a bare ``[D]`` obs is a
context of one.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import flax
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from relayrl_tpu.models.base import Policy, register_model
from relayrl_tpu.models.mlp import (
    GATED_FFN,
    UNGATED_FFN,
    _MASK_FILL,
    _categorical_entropy,
    _categorical_logp,
    _compute_dtype,
)
from relayrl_tpu.ops.attention import blockwise_attention, dense_attention
from relayrl_tpu.ops.scopes import (  # noqa: F401  (SHORT_CONV_NAME's home)
    EMBED,
    FFN,
    GDN_CONV_NAME,
    HEADS,
    MAMBA_CONV_NAME,
    MOE_ELEMENTWISE,
    OP_PROJ,
    SHORT_CONV_NAME,
)
from relayrl_tpu.ops.conv import backend as conv_backend
from relayrl_tpu.ops.conv import conv
from relayrl_tpu.ops.conv import padded as conv_padded
from relayrl_tpu.ops.gdn import SOLVE_NAME as _GDN_SOLVE
from relayrl_tpu.ops.gdn import backend as gdn_backend
from relayrl_tpu.ops.gdn import gdn, gdn_step
from relayrl_tpu.ops.ssd import backend as ssd_backend
from relayrl_tpu.ops.ssd import ssd, ssd_step

# the one activation a Mamba-2 layer's checkpoint keeps (_mamba_layer)
_SSD_OUT = "relayrl_ssd_out"
# ... and a linear-attention layer's (_gdn_layer)
_GDN_OUT = "relayrl_gdn_out"


def _resolve_attention(arch: Mapping[str, Any]
                       ) -> tuple[Callable, dict, dict, dict]:
    """Arch config -> ``(attn_fn, resolved, score_area, layout)``: the
    [B,T,H,D]x3 -> [B,T,H,D] attention callable, and the records of what
    it ran as.

    ``"flash"`` and ``"ring"`` pick their implementation at trace time
    from the platform, the sequence length and the ambient mesh, so the
    SAME arch config serves CPU actor hosts and the TPU learner. That
    choice is never silent: ``resolved`` maps every traced
    ``(T, head_dim, dtype)`` to the backend that was compiled for it
    (``dense`` / ``blockwise`` / ``flash_pallas`` / ``ring_flash_pallas``
    / ``ring_scan``), surfaced as ``Policy.attention_backends``, and each
    new entry prints one line naming the platform it was resolved on.
    ``score_area`` (``Policy.attention_score_area_pct``) maps the shapes
    that run the Pallas flash kernels to the share of the T x T score
    matrix those compute (``ops.flash.score_area_pct``: how far the causal
    skip engages at that shape's tiling), and ``layout``
    (``Policy.attention_layout``) to the operand layout they ran in
    (``ops.flash.lane_layout``: ``"2 heads a step"`` of the projections'
    own ``[B, T, H * D]``, or ``"head-major"`` where the head axis is
    transposed out of the lanes); the line says both. ``attn_fn`` takes a
    fourth argument, a layer's ``window``; a windowed layer's score area
    and layout are recorded under ``(T, head_dim, dtype, window)``, beside
    the global layers' of the same shape, and its line says ``window W``
    (``resolved`` keeps the three-part key: a shape runs one backend
    whatever the mask).
    """
    kind = arch.get("attention", "dense")
    block = int(arch.get("attention_block", 128))
    resolved: dict[tuple[int, int, str], str] = {}
    score_area: dict[tuple, float] = {}
    layout: dict[tuple, str] = {}
    said: dict[tuple, str] = {}  # one line a shape and layer kind

    def ran(q, backend: str, area_pct: float | None = None,
            k=None, heads_a_step: int | None = None,
            window: int | None = None) -> None:
        key = (int(q.shape[1]), int(q.shape[3]), q.dtype.name)
        # a windowed layer's records sit beside the global layers' of the
        # same shape, under the shape's key with the window appended
        kind_key = key if window is None else key + (int(window),)
        if said.get(kind_key) != backend:
            said[kind_key] = resolved[key] = backend
            area = ""
            heads = ""
            if k is not None and k.shape[2] != q.shape[2]:  # grouped-query
                heads = f" heads {q.shape[2]}/{k.shape[2]}"
            if window is not None:
                heads += f" window {window}"
            if area_pct is not None:
                score_area[kind_key] = area_pct
                layout[kind_key] = ("head-major" if heads_a_step is None
                                    else f"{heads_a_step} heads a step")
                area = (f", score area {area_pct:g}%, "
                        f"layout {layout[kind_key]}")
            if kind in ("flash", "ring"):
                print(f"[attention] {kind!r} T={key[0]} head_dim={key[1]} "
                      f"{key[2]}{heads} -> {backend}{area} "
                      f"(platform {jax.default_backend()})", flush=True)

    def dense(q, k, v, window=None):
        ran(q, "dense", window=window)
        return dense_attention(q, k, v, causal=True, window=window)

    def blockwise(q, k, v, window=None):
        ran(q, "blockwise", window=window)
        return blockwise_attention(q, k, v, block, causal=True,
                                   window=window)

    def local(q, k, v, window=None):
        """The single-device XLA path "flash" and "ring" fall back to."""
        return (blockwise if q.shape[1] % block == 0 else dense)(
            q, k, v, window)

    if kind == "dense":
        return dense, resolved, score_area, layout
    if kind == "blockwise":
        return blockwise, resolved, score_area, layout
    if kind == "flash":
        def flash_or_local(q, k, v, window=None):
            # Pallas kernel on TPU; off-TPU (CPU actor hosts, CI) the same
            # arch config resolves to the lax.scan blockwise path — the
            # heterogeneous-placement rule ring attention also follows.
            # The kernel has its OWN block knob (arch "flash_block"):
            # it wants few large grid steps, while the lax.scan
            # fallback's "attention_block" is a memory/fusion knob that
            # wants small ones — one shared key would silently deoptimize
            # whichever path tuned second.
            from relayrl_tpu.ops import flash

            T = q.shape[1]
            fblock = int(arch.get("flash_block", 1024))
            if jax.default_backend() == "tpu" and T % min(fblock, T) == 0:
                band = window if window is not None and window < T else None
                ran(q, "flash_pallas", flash.score_area_pct(
                    T, *flash.tiling(T, True, fblock, fblock, band), True,
                    band), k,
                    flash.lane_layout(q.shape[2], k.shape[2], q.shape[3]),
                    window)
                return flash.flash_attention(q, k, v, causal=True,
                                             block_q=fblock, block_kv=fblock,
                                             window=window)
            return local(q, k, v, window)
        return flash_or_local, resolved, score_area, layout
    if kind == "ring":
        def ring_or_local(q, k, v, window=None):
            from relayrl_tpu.parallel.context import current_mesh
            from relayrl_tpu.parallel.ring import make_ring_attention
            from relayrl_tpu.parallel.ring_flash import (
                make_ring_flash_attention,
                pick_chunk_block,
            )

            mesh = current_mesh()
            if mesh is None or mesh.shape.get("sp", 1) <= 1:
                return local(q, k, v, window)
            if window is not None:
                raise ValueError(
                    "ring attention takes no window; sliding_attention "
                    "layers run under attention 'flash', 'blockwise' or "
                    "'dense'")
            if k.shape[2] != q.shape[2]:
                raise ValueError(
                    "ring attention takes one head count for q, k and v; "
                    "grouped-query heads run under attention 'flash', "
                    "'blockwise' or 'dense'")
            # On TPU the per-round combine runs as Pallas flash chunk
            # kernels when the local chunk tiles; the scan ring is the
            # portable fallback (and the off-TPU path).
            chunk = q.shape[1] // mesh.shape["sp"]
            if (jax.default_backend() == "tpu"
                    and pick_chunk_block(chunk) is not None):
                ran(q, "ring_flash_pallas")
                return make_ring_flash_attention(mesh)(q, k, v)
            ran(q, "ring_scan")
            return make_ring_attention(mesh)(q, k, v)
        return ring_or_local, resolved, score_area, layout
    raise ValueError(f"unknown attention kind {kind!r}")


def _resolve_scan() -> tuple[Callable, dict]:
    """``(scan_fn, resolved)``: the Mamba-2 layers' scan, ``ops.ssd.ssd``
    behind a record of what it ran as. Platform and shape pick the
    implementation at trace time (``ops.ssd.backend``: the Pallas kernels
    on a TPU where the shapes tile, plain XLA on CPU actor hosts, in CI and
    for a shape that does not tile), and as with ``attention: "flash"`` the
    choice is never silent: ``resolved`` (``Policy.scan_backends``) maps
    every traced ``(T, heads, head_dim, state, dtype)`` to ``ssd_pallas`` |
    ``ssd_xla``, and each new entry prints one ``[scan]`` line naming the
    platform it was resolved on."""
    resolved: dict[tuple, str] = {}

    def scan_fn(x, dt, a, b, c, skip, chunk, state):
        key = (int(x.shape[1]), int(x.shape[2]), int(x.shape[3]),
               int(b.shape[3]), x.dtype.name)
        ran = ssd_backend(*x.shape[1:], *b.shape[2:], chunk)
        if resolved.get(key) != ran:
            resolved[key] = ran
            print(f"[scan] T={key[0]} heads={key[1]} head_dim={key[2]} "
                  f"state={key[3]} groups={b.shape[2]} chunk={chunk} "
                  f"{key[4]} -> {ran} (platform {jax.default_backend()})",
                  flush=True)
        return ssd(x, dt, a, b, c, skip, chunk, state)

    return scan_fn, resolved


def _resolve_delta_rule() -> tuple[Callable, dict]:
    """``(rule_fn, resolved)``: the linear-attention layers' delta rule,
    ``ops.gdn.gdn`` behind a record of what it ran as, as
    :func:`_resolve_scan`: ``resolved`` (``Policy.gdn_backends``) maps every
    traced ``(T, value heads, key width, value width, dtype)`` to
    ``ops.gdn.backend``'s answer (``gdn_pallas`` | ``gdn_xla``), and each new
    entry prints one ``[gdn]`` line naming the platform."""
    resolved: dict[tuple, str] = {}

    def rule_fn(q, k, v, g, beta, chunk, state):
        key = (int(v.shape[1]), int(v.shape[2]), int(k.shape[3]),
               int(v.shape[3]), v.dtype.name)
        ran = gdn_backend(key[0], key[1], int(k.shape[2]), key[2], key[3],
                          chunk)
        if resolved.get(key) != ran:
            resolved[key] = ran
            print(f"[gdn] T={key[0]} heads={key[1]}/{k.shape[2]} "
                  f"key_dim={key[2]} value_dim={key[3]} chunk={chunk} "
                  f"{key[4]} -> {ran} (platform {jax.default_backend()})",
                  flush=True)
        return gdn(q, k, v, g, beta, chunk, state)

    return rule_fn, resolved


def _resolve_conv() -> tuple[Callable, dict]:
    """``(conv_fn, resolved)``: the mixers' depthwise convolution,
    ``ops.conv.conv`` behind a record of what it ran as, as
    :func:`_resolve_scan`: ``resolved`` (``Policy.conv_backends``) maps every
    traced ``(T, columns, taps, continues from a cache's rows, dtype)`` to
    ``ops.conv.backend``'s answer (``conv_pallas`` | ``conv_xla``), and each
    new entry prints one ``[conv]`` line naming the platform."""
    resolved: dict[tuple, str] = {}

    def conv_fn(x, w, bias, state, scope):
        key = (int(x.shape[1]), int(x.shape[2]), int(w.shape[0]),
               state is not None, x.dtype.name)
        ran = conv_backend(*key[:4])
        if resolved.get(key) != ran:
            resolved[key] = ran
            print(f"[conv] T={key[0]} columns={key[1]} taps={key[2]} "
                  f"bias={'no' if bias is None else 'yes'} "
                  f"from={'cache' if key[3] else 'start'} {key[4]} -> {ran} "
                  f"(platform {jax.default_backend()})", flush=True)
        return conv(x, w, bias, state, scope)

    return conv_fn, resolved


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


def _norm(arch_norm: str, eps, name: str, zero_centred: bool = False):
    """The arch's normalisation layer in float32: ``"layer"`` (LayerNorm,
    scale + bias) or ``"rms"`` (RMSNorm, scale only; ``zero_centred``: the
    weight is ``1 + scale``). ``eps=None`` keeps flax's default, 1e-6 — what
    every arch without ``norm_eps`` has always run."""
    if arch_norm not in ("layer", "rms"):
        raise ValueError(f"unknown norm {arch_norm!r} (layer | rms)")
    kw = {} if eps is None else {"epsilon": float(eps)}
    if zero_centred:
        if arch_norm != "rms":
            raise ValueError("norm_zero_centred needs norm 'rms'")
        return _ZeroCentredRMSNorm(name=name, **kw)
    cls = nn.LayerNorm if arch_norm == "layer" else nn.RMSNorm
    return cls(dtype=jnp.float32, name=name, **kw)


def _block_norm(block: "TransformerBlock", name: str, kind: str | None = None):
    """``block``'s norm under ``name`` (``kind``: "rms" for the q/k norms)."""
    return _norm(kind or block.norm, block.norm_eps, name,
                 block.norm_zero_centred)


def apply_rope(x, start, theta: float, share: float = 1.0):
    """Rotary position embedding on ``x [B, T, H, hd]`` whose row j sits at
    absolute position ``start + j`` (``start`` may be traced): pairs
    (i, i + hd/2) rotate by ``pos * theta^(-2i/hd)`` — the half-split
    convention of the published ``olmoe`` / GPT-NeoX code. Angles in
    float32, result in ``x``'s dtype. ``share`` below 1 (a
    ``partial_rotary_factor``): only the FIRST ``share * hd`` lanes turn, as
    a head of that width would, the rest pass untouched."""
    if share != 1.0:
        turned = int(x.shape[-1] * share)
        if not 0 < turned <= x.shape[-1] or turned % 2:
            raise ValueError(f"rope_share {share} of a head of "
                             f"{x.shape[-1]} turns {turned} lanes")
        return jnp.concatenate(
            [apply_rope(x[..., :turned], start, theta), x[..., turned:]],
            axis=-1)
    hd = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    pos = jnp.asarray(start, jnp.float32) + jnp.arange(
        x.shape[1], dtype=jnp.float32)
    ang = pos[:, None] * inv_freq[None, :]                  # [T, hd/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _short_conv(bcu, w, state=None):
    """The gated short convolution between its two projections:
    ``(B, C, u) = split3(bcu)``, ``z = B * u``, ``c_t = sum_j w[j] *
    z_{t-(L-1)+j}`` (depthwise, causal, ``L = w.shape[0]`` taps, no bias),
    returns ``(C * c, z_padded)``. ``state [batch, L-1, d]`` holds the
    ``z`` rows before this call's first (zeros at a sequence's start, which
    ``None`` means). Plain XLA under one named scope: L shifted
    multiply-adds fused with the two gate products, accumulated in float32.
    ``z_padded = concat(state, z)`` is what a cache takes its next state
    from."""
    with jax.named_scope(SHORT_CONV_NAME):
        taps = w.shape[0]
        T = bcu.shape[1]
        b_gate, c_gate, u = jnp.split(bcu, 3, axis=-1)
        z = b_gate * u
        if state is None:
            zp = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
        else:
            zp = jnp.concatenate([state.astype(z.dtype), z], axis=1)
        c = sum(w[j].astype(jnp.float32) * zp[:, j:j + T].astype(jnp.float32)
                for j in range(taps))
        return c_gate * c.astype(bcu.dtype), zp


def _mamba_conv(xbc, w, bias, state=None, scope: str = MAMBA_CONV_NAME,
                conv_fn: Callable = conv):
    """The Mamba-2 mixer's convolution: ``silu(conv(xbc) + bias)``,
    depthwise and causal, ``L = w.shape[0]`` taps, returns ``(out,
    xbc_padded)``. ``state [batch, L-1, c]`` holds the ``xbc`` rows before
    this call's first (zeros at a sequence's start, which ``None`` means);
    ``xbc_padded = concat(state, xbc)`` is what a cache takes its next rows
    from (full mode drops it unmade). Under one named scope, the tap sums in
    float32, as :func:`_short_conv`: :mod:`relayrl_tpu.ops.conv`, as two
    Pallas kernels on a TPU at a sequence's start and as plain XLA
    everywhere else (``conv_fn``: ``ops.conv.conv``, or the policy's
    recording wrapper of it, :func:`_resolve_conv`). ``bias`` None: none is
    added (a linear-attention mixer's, :func:`_gdn_conv`, under its own
    ``scope``)."""
    out = conv_fn(xbc, w, bias, state, scope)
    with jax.named_scope(scope):
        return out, conv_padded(xbc, w.shape[0], state)


def _block_dense(block: "TransformerBlock", features: int, name: str):
    return nn.Dense(features, dtype=block.compute_dtype, name=name,
                    use_bias=block.use_bias)


def _block_ffn(block: "TransformerBlock", x, layer_in):
    """``x + FFN(norm(x))`` in ``block``'s param scope: the arch's dense
    FFN or the MoE layer. ``layer_in``: the rows of the layer's own input
    that ``x``'s rows came from, which the MoE layer's router reads under
    ``moe_router_input: "layer"``. (A plain function, like
    :func:`_embed_obs`: a module method would be wrapped by flax once per
    call.) The norm and the residual are the FFN's element-wise passes,
    the dense one's or the expert layer's (``ops/scopes.py``). A layer
    that is an operator alone (``has_ffn`` false) has none: ``x``."""
    if not block.has_ffn:
        return x
    part = MOE_ELEMENTWISE if block.moe_experts > 0 else FFN
    with jax.named_scope(part):
        h = _block_norm(block, "ln_mlp")(x)
    width = block.d_ff or block.mlp_ratio * block.d_model
    if block.ffn not in UNGATED_FFN and block.ffn not in GATED_FFN:
        raise ValueError(f"unknown ffn {block.ffn!r} "
                         f"(gelu | relu2 | swiglu | reglu)")
    if block.moe_experts > 0:
        from relayrl_tpu.models.moe import MoEMLP

        if block.moe_router_input not in ("ffn", "layer"):
            raise ValueError(f"unknown moe_router_input "
                             f"{block.moe_router_input!r} (ffn | layer)")
        h = MoEMLP(block.d_model, block.moe_d_ff or width,
                   block.moe_experts, block.moe_top_k, block.compute_dtype,
                   norm_topk_prob=block.moe_norm_topk_prob, ffn=block.ffn,
                   dispatch=block.moe_dispatch, use_bias=block.use_bias,
                   **block.moe_kw, name="moe")(
                       h, layer_in if block.moe_router_input == "layer"
                       else None)
        with jax.named_scope(part):
            return x + h.astype(x.dtype)
    with jax.named_scope(part):
        h = h.astype(block.compute_dtype)
        up = _block_dense(block, width, "mlp_up")(h)
        if block.ffn in GATED_FFN:
            h = GATED_FFN[block.ffn](
                _block_dense(block, width, "mlp_gate")(h)) * up
        else:
            h = UNGATED_FFN[block.ffn](up)
        h = _block_dense(block, block.d_model, "mlp_down")(h)
        return x + h.astype(x.dtype)


class TransformerBlock(nn.Module):
    d_model: int
    n_heads: int
    mlp_ratio: int
    attn_fn: Callable
    compute_dtype: Any
    # MoE variant: >0 replaces the dense FFN with a per-token top-k MoE of
    # this many experts (models/moe.py; weights shard over the mesh ``ep``
    # axis). 0 keeps the dense mlp_up/mlp_down FFN — param names for the
    # dense family are unchanged.
    moe_experts: int = 0
    moe_top_k: int = 2
    # What the arch says of the model's block; every default is the GPT-2
    # shaped block this family has always built (same parameter tree, same
    # operations): LayerNorm at flax's epsilon, no rotary positions (the
    # core adds a learned table), no QK-norm, biases, a GELU FFN of
    # mlp_ratio * d_model.
    norm: str = "layer"
    norm_eps: float | None = None
    rope_theta: float | None = None     # set = RoPE on q and k
    qk_norm: bool = False
    use_bias: bool = True
    ffn: str = "gelu"                   # | "swiglu" (mlp_gate beside mlp_up)
    d_ff: int | None = None             # FFN width; None = mlp_ratio * d
    moe_d_ff: int | None = None         # one expert's width; None = d_ff
    moe_norm_topk_prob: bool = True
    moe_dispatch: str | None = None     # None: models/moe.py picks
    # MoEMLP's further fields (router, expert_bias, held)
    moe_kw: Mapping[str, Any] = flax.core.FrozenDict()
    # The layer's operator: "attention" | "conv" (gated short convolution
    # of conv_taps taps: conv_in d -> 3d, conv_w [taps, d], conv_out) |
    # "mamba2" (the Mamba-2 mixer, _mamba_layer) | "none"; and whether an
    # FFN follows it. A layer of one part is an operator with has_ffn false,
    # or "none" with its FFN: one norm, one residual.
    op: str = "attention"
    has_ffn: bool = True
    conv_taps: int = 3
    # The Mamba-2 mixer: heads x head_dim wide inside (not a multiple of
    # d_model), a state of mamba_state columns a head, B and C in
    # mamba_groups groups, the scan in chunks of mamba_chunk.
    mamba_heads: int = 8
    mamba_head_dim: int = 64
    mamba_state: int = 128
    mamba_groups: int = 1
    mamba_conv_taps: int = 4
    mamba_chunk: int = 128
    # The scan a Mamba-2 layer runs: ops.ssd.ssd, or the policy's recording
    # wrapper of it (_resolve_scan).
    scan_fn: Callable = ssd
    # The convolution a Mamba-2 or a linear-attention layer runs:
    # ops.conv.conv, or the policy's recording wrapper of it (_resolve_conv).
    conv_fn: Callable = conv
    # Grouped-query heads: n_kv_heads k/v heads under n_heads query heads,
    # all d_model // n_heads wide (separate q_proj / k_proj / v_proj).
    # None: one fused qkv, as always. qk_norm "head": RMSNorm over each head.
    n_kv_heads: int | None = None
    # A head width of its own (None: d_model // n_heads): q and attn_out's
    # input are n_heads * head_dim wide, projections separate.
    head_dim: int | None = None
    # Sliding-window attention: query t sees keys t - window < s <= t
    # (None: every key up to its own). Set per layer by the core.
    window: int | None = None
    # "layer": the MoE router reads this layer's input as it arrives (before
    # the operator, un-normed); "ffn": the rows the experts read.
    moe_router_input: str = "ffn"
    # RMSNorm weights as offsets from one, x^ (1 + w): every norm of the
    # block but a linear-attention layer's gated one.
    norm_zero_centred: bool = False
    # The share of a head's lanes RoPE turns (a partial_rotary_factor).
    rope_share: float = 1.0
    # Gated attention: q_proj is twice as wide, a head's head_dim query
    # lanes then its head_dim gate lanes, and the attention's output is
    # multiplied by sigmoid(gate) before the output projection.
    attn_gate: bool = False
    # The linear-attention (Gated DeltaNet) mixer, _gdn_layer: gdn_key_heads
    # q/k heads of gdn_key_dim under gdn_value_heads value heads of
    # gdn_value_dim, a gdn_conv_taps-tap convolution over q, k and v, the
    # delta rule in chunks of gdn_chunk.
    gdn_key_heads: int = 4
    gdn_value_heads: int = 8
    gdn_key_dim: int = 64
    gdn_value_dim: int = 64
    gdn_conv_taps: int = 4
    gdn_chunk: int = 64
    # The rule a linear-attention layer runs: ops.gdn.gdn, or the policy's
    # recording wrapper of it (_resolve_delta_rule).
    rule_fn: Callable = gdn

    @nn.compact
    def __call__(self, x, cache=None, t=None, readout_idx=None,
                 n_valid=None):
        """Full mode (``cache=None``): x ``[B, T, d]`` -> ``[B, T, d]``.

        Decode mode: x is ONE position ``[B, 1, d]``; ``cache`` is this
        layer's state and ``t`` the write index. An attention layer's
        state is its ``(k, v)`` pair ``[B, W, Hkv, hd]`` (``Hkv`` =
        ``n_kv_heads``: grouped-query k/v are cached as they are, and the
        q heads of a group read the same rows) — of a windowed layer a
        ring of ``min(window, W)`` rows (:func:`_ring_cached`); a conv
        layer's is the last
        ``conv_taps - 1`` rows of ``B * u``, ``[B, conv_taps - 1, d]``
        (``n_valid``, prefill only: how many of x's rows are real — the
        state is taken from the rows before that; None: all of them).
        Attention runs q against the cache prefix (positions <= t)
        instead of recomputing the whole window — O(W) per step vs the
        window path's O(W^2). Returns ``(out, new_cache)``. Param
        names/creation order are identical in both modes (init always runs
        the full path), so one param tree serves both.

        Readout mode (``readout_idx`` set, final layer of the window
        path): x is the full window ``[B, W, d]`` but only row
        ``readout_idx`` is ever read by the heads, so k/v project over
        every row (earlier positions must still be attended) while the
        query, attention-output projection, and MLP run for the ONE
        readout row — the dead (W-1)/W of the final block's compute that
        the full path pays per actor step. Returns ``[B, 1, d]``. The
        row's attention is computed densely (a 1-row query is trivially
        dense; every backend computes the same causal function).

        With ``rope_theta`` set, q and k are rotated (after QK-norm) at
        their absolute positions in all three modes: rows ``0..T-1`` of a
        window, row ``readout_idx`` for the readout query, ``t + j`` in
        decode mode — the cache holds rotated keys."""
        B, T, _ = x.shape
        if self.op == "conv":
            return _conv_layer(self, x, cache, readout_idx, n_valid)
        if self.op == "mamba2":
            return _mamba_layer(self, x, cache, n_valid)
        if self.op == "gdn":
            return _gdn_layer(self, x, cache, n_valid)
        if self.op == "none":   # the FFN alone: nothing to cache
            if readout_idx is not None:
                x = jax.lax.dynamic_slice_in_dim(x, readout_idx, 1, axis=1)
            out = _block_ffn(self, x, x)
            return out if cache is None else (out, ())
        if self.op != "attention":
            raise ValueError(f"unknown layer operator {self.op!r} "
                             f"(attention | conv | mamba2 | gdn | none)")
        head_dim = self.head_dim or self.d_model // self.n_heads
        width = self.n_heads * head_dim     # of q and of attn_out's input
        # everything of the operator but its kernel: one part on the device
        with jax.named_scope(OP_PROJ):
            layer_in = x
            h = _block_norm(self, "ln_attn")(x)
            h = h.astype(self.compute_dtype)
            if self.n_kv_heads is None and self.head_dim is None:
                n_kv = self.n_heads
                qkv = _block_dense(self, 3 * self.d_model, "qkv")(h)
                q, k, v = jnp.split(qkv, 3, axis=-1)
            else:
                n_kv = self.n_kv_heads or self.n_heads
                q = _block_dense(self, width * (1 + self.attn_gate),
                                 "q_proj")(h)
                k = _block_dense(self, n_kv * head_dim, "k_proj")(h)
                v = _block_dense(self, n_kv * head_dim, "v_proj")(h)
            if self.qk_norm is True:
                # over the whole d_model-wide projection, before the heads
                q = _block_norm(self, "q_norm", "rms")(q).astype(
                    self.compute_dtype)
                k = _block_norm(self, "k_norm", "rms")(k).astype(
                    self.compute_dtype)
            gate = None
            if self.attn_gate:
                if self.n_kv_heads is None and self.head_dim is None:
                    raise ValueError("attn_gate needs separate projections "
                                     "(n_kv_heads or head_dim)")
                if self.qk_norm is True:
                    raise ValueError("attn_gate takes qk_norm false | "
                                     "\"head\"")
                # a head's query lanes, then its gate lanes
                q, gate = jnp.split(
                    q.reshape(B, T, self.n_heads, 2 * head_dim), 2, axis=-1)
                gate = gate.reshape(B, T, width)
            q = q.reshape(B, T, self.n_heads, head_dim)
            k, v = (a.reshape(B, T, n_kv, head_dim) for a in (k, v))
            if self.qk_norm == "head":
                # over each head's head_dim, one learned scale for all heads
                q = _block_norm(self, "q_norm", "rms")(q).astype(
                    self.compute_dtype)
                k = _block_norm(self, "k_norm", "rms")(k).astype(
                    self.compute_dtype)
            elif self.qk_norm not in (True, False):
                raise ValueError(f"unknown qk_norm {self.qk_norm!r} "
                                 f"(false | true | \"head\")")
            rope = self.rope_theta is not None
            if rope:
                k = apply_rope(k, 0 if t is None else t, self.rope_theta,
                               self.rope_share)
        if readout_idx is not None:
            with jax.named_scope(OP_PROJ):
                q_row = jax.lax.dynamic_slice_in_dim(q, readout_idx, 1,
                                                     axis=1)
                if rope:
                    q_row = apply_rope(q_row, readout_idx, self.rope_theta,
                                       self.rope_share)
            attn = dense_attention(q_row, k, v, causal=True,
                                   q_offset=readout_idx, window=self.window)
            with jax.named_scope(OP_PROJ):
                attn = attn.reshape(B, 1, width)
                if gate is not None:
                    attn = _gated(attn, jax.lax.dynamic_slice_in_dim(
                        gate, readout_idx, 1, axis=1))
                row_in = jax.lax.dynamic_slice_in_dim(x, readout_idx, 1,
                                                      axis=1)
                x = row_in + _block_dense(self, self.d_model, "attn_out")(
                    attn).astype(x.dtype)
            return _block_ffn(self, x, row_in)
        if rope:
            with jax.named_scope(OP_PROJ):
                q = apply_rope(q, 0 if t is None else t, self.rope_theta,
                               self.rope_share)
        if cache is None:
            attn = self.attn_fn(q, k, v, self.window)
            new_cache = None
        elif self.window is not None:
            attn, new_cache = _ring_cached(q, k, v, cache, t, self.window,
                                           n_valid)
        else:
            k_cache, v_cache = cache
            k_cache = jax.lax.dynamic_update_slice_in_dim(
                k_cache, k.astype(k_cache.dtype), t, axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(
                v_cache, v.astype(v_cache.dtype), t, axis=1)
            # Query j sits at absolute position t+j (T=1 per-step decode;
            # T=W prefill rebuilds the whole prefix in one dispatch) —
            # exactly dense_attention's offset-causal mask, so the cached
            # path shares the window path's attention code verbatim.
            attn = dense_attention(q, k_cache, v_cache, causal=True,
                                   q_offset=t)
            new_cache = (k_cache, v_cache)
        with jax.named_scope(OP_PROJ):
            attn = attn.reshape(B, T, width)
            if gate is not None:
                attn = _gated(attn, gate)
            x = x + _block_dense(self, self.d_model, "attn_out")(
                attn).astype(x.dtype)
        out = _block_ffn(self, x, layer_in)
        return out if cache is None else (out, new_cache)


def _gated(attn, gate):
    """``attn * sigmoid(gate)``, the product in float32."""
    return (attn.astype(jnp.float32) * jax.nn.sigmoid(
        gate.astype(jnp.float32))).astype(attn.dtype)


def _ring_cached(q, k, v, cache, t, window: int, n_valid):
    """A windowed layer's two cached modes -> ``(attn, new_cache)``. The
    cache is a ring: ``(k, v)`` of ``rows = min(window, W)`` rows, position
    ``p`` in row ``p % rows`` (keys rotated at their absolute positions,
    where the layer has RoPE, before they go in). Softmax does not care
    about the order of its keys, so a row's position is all a step needs.

    One decode step (``T == 1``, position ``t``): write row ``t % rows``,
    then attend every row under the positions the ring now holds — row
    ``s`` the newest ``p <= t`` with ``p % rows == s``, negative while
    nothing was written there. Prefill (``T > 1``): the rows are a
    sequence's FIRST ``T`` positions (``t = 0``: what the cache held is
    replaced, not read); windowed attention among them, then the ring takes
    the last ``rows`` of the ``n_valid`` real ones (None: all ``T``) —
    padding rows never enter, they would overwrite live ones."""
    k_cache, v_cache = cache
    rows, T = k_cache.shape[1], q.shape[1]
    slot = jnp.arange(rows)
    if T == 1:
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            k_cache, k.astype(k_cache.dtype), t % rows, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            v_cache, v.astype(v_cache.dtype), t % rows, axis=1)
        attn = dense_attention(q, k_cache, v_cache, causal=True, q_offset=t,
                               window=window,
                               kv_positions=t - jnp.mod(t - slot, rows))
        return attn, (k_cache, v_cache)
    attn = dense_attention(q, k, v, causal=True, window=window)
    n = T if n_valid is None else n_valid
    newest = jnp.clip((n - 1) - jnp.mod(n - 1 - slot, rows), 0, T - 1)
    return attn, (jnp.take(k, newest, axis=1).astype(k_cache.dtype),
                  jnp.take(v, newest, axis=1).astype(v_cache.dtype))


def _conv_layer(block: TransformerBlock, x, cache, readout_idx, n_valid):
    """A conv layer in ``block``'s param scope, in the block's three modes:
    ``x + conv_out(C * conv(B * u))``, then the FFN. No positions: the
    operator is causal by construction and sees ``conv_taps - 1`` rows
    back. (A plain function, like :func:`_block_ffn`.)"""
    B, T, d = x.shape
    back = block.conv_taps - 1
    w = block.param("conv_w", nn.initializers.lecun_normal(),
                    (block.conv_taps, d), jnp.float32)

    def in_proj(rows):
        with jax.named_scope(OP_PROJ):
            h = _block_norm(block, "ln_attn")(rows)
            return _block_dense(block, 3 * d, "conv_in")(
                h.astype(block.compute_dtype))

    def out_proj(x, y):
        with jax.named_scope(OP_PROJ):
            return x + _block_dense(block, d, "conv_out")(y).astype(x.dtype)

    if readout_idx is not None:
        # the one row needs its own and the conv_taps - 1 rows before it;
        # rows before the sequence's first have z = 0
        xp = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))
        rows = jax.lax.dynamic_slice_in_dim(xp, readout_idx, back + 1,
                                            axis=1)
        real = (readout_idx - back + jnp.arange(back + 1)) >= 0
        bcu = jnp.where(real[None, :, None], in_proj(rows), 0)
        y = _short_conv(bcu, w)[0][:, back:]
        return _block_ffn(block, out_proj(rows[:, back:], y),
                          rows[:, back:])
    y, zp = _short_conv(in_proj(x), w, cache)
    out = _block_ffn(block, out_proj(x, y), x)
    if cache is None:
        return out
    # zp row j is z row j - back: the state after n real rows is z rows
    # n - back .. n - 1
    n = T if n_valid is None else n_valid
    state = jax.lax.dynamic_slice_in_dim(zp, n, back, axis=1)
    return out, state.astype(cache.dtype)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """``softplus^-1`` of step sizes log-uniform over Mamba-2's published
    range (``time_step_min`` 0.001 .. ``time_step_max`` 0.1, floor 1e-4)."""
    lo, hi = jnp.log(0.001), jnp.log(0.1)
    dt = jnp.maximum(jnp.exp(lo + (hi - lo) * jax.random.uniform(key, shape)),
                     1e-4)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _a_log_init(key, shape, dtype=jnp.float32):
    """``log`` of decay rates uniform over Mamba-2's ``A_init_range``
    (1, 16): ``A = -exp(A_log)``."""
    return jnp.log(jax.random.uniform(key, shape, minval=1.0,
                                      maxval=16.0)).astype(dtype)


def _mamba_layer(block: TransformerBlock, x, cache, n_valid):
    """A Mamba-2 layer in ``block``'s param scope, ``x + out(norm_g(y *
    silu(z)))`` behind the layer's one norm, then the FFN if the layer has
    one. ``[z | xBC | dt] = in(norm(x))``; ``xBC = silu(conv(xBC) + b)``;
    ``(x, B, C) = split(xBC)``; ``dt = softplus(dt + dt_bias)`` and ``A =
    -exp(A_log)`` in float32; ``y`` the scan of ``ops/ssd.py``; ``norm_g``
    an RMSNorm over each of ``mamba_groups`` groups of the gated output.
    No positions: the scan orders the tokens.

    Full mode (``cache=None``). Cached modes: ``cache`` is ``(the
    convolution's last mamba_conv_taps - 1 rows of xBC, the [B, H, P, N]
    state in float32)``; one row continues from it in one step of the
    recurrence (O(1) in the position), several rows (prefill) run the
    chunked scan from it and leave the state after the ``n_valid`` real
    ones (rows past them get ``dt = 0``: they leave the state as it is).
    The readout row of a window needs the whole scan before it: the core
    runs a final Mamba-2 layer in full and slices."""
    Bsz, T, d = x.shape
    H, P = block.mamba_heads, block.mamba_head_dim
    G, N = block.mamba_groups, block.mamba_state
    inner, bc, back = H * P, G * N, block.mamba_conv_taps - 1
    if H % G:
        raise ValueError(f"mamba_groups {G} does not divide mamba_heads {H}")
    f32 = jnp.float32
    cd = block.compute_dtype
    lecun = nn.initializers.lecun_normal()
    weights = (
        block.param("mamba_in", lecun, (d, 2 * inner + 2 * bc + H), f32),
        block.param("mamba_conv_w", lecun,
                    (block.mamba_conv_taps, inner + 2 * bc), f32),
        block.param("mamba_conv_b", nn.initializers.normal(0.02),
                    (inner + 2 * bc,), f32),
        block.param("mamba_dt_bias", _dt_bias_init, (H,), f32),
        block.param("mamba_A_log", _a_log_init, (H,), f32),
        block.param("mamba_D", nn.initializers.ones, (H,), f32),
        block.param("mamba_norm", nn.initializers.ones, (inner,), f32),
        block.param("mamba_out", lecun, (inner, d), f32))
    eps = 1e-6 if block.norm_eps is None else float(block.norm_eps)

    def mix(h, weights, conv_rows, state, n_valid):
        """normed rows -> (the mixer's output, xBC with the rows before it,
        the state after the last real row)"""
        w_in, conv_w, conv_b, dt_bias, a_log, skip, scale, w_out = weights
        with jax.named_scope(OP_PROJ):
            z, xbc, dt = jnp.split(
                jnp.dot(h, w_in.astype(cd)),
                [inner, 2 * inner + 2 * bc], axis=-1)
            dt = jax.nn.softplus(dt.astype(f32) + dt_bias)
            if n_valid is not None:
                dt = jnp.where(jnp.arange(T)[None, :, None] < n_valid, dt,
                               0.0)
            a = -jnp.exp(a_log)
        xbc, padded = _mamba_conv(xbc, conv_w, conv_b, conv_rows,
                                  conv_fn=block.conv_fn)
        with jax.named_scope(OP_PROJ):
            xs, b_in, c_in = jnp.split(xbc, [inner, inner + bc], axis=-1)
            xs = xs.reshape(Bsz, T, H, P)
            b_in, c_in = (r.reshape(Bsz, T, G, N) for r in (b_in, c_in))
        if state is not None and T == 1:
            y, state = ssd_step(xs[:, 0], dt[:, 0], a, b_in[:, 0],
                                c_in[:, 0], skip, state)
            y = y[:, None]
        else:
            y, state = block.scan_fn(xs, dt, a, b_in, c_in, skip,
                                     block.mamba_chunk, state)
        # named (and kept) with the heads side by side in the lanes, as the
        # scan's kernels write it: a [..., H, 64] view between them and the
        # norm is turned T-minor and back, 0.4 GB a layer (PERF.md section
        # 6, PR 40)
        y = checkpoint_name(y.reshape(Bsz, T, inner), _SSD_OUT)
        with jax.named_scope(OP_PROJ):
            # the gate BEFORE the norm, the norm over each group's columns:
            # a group's lane-aligned slice at a time, no [..., G, inner / G]
            # view of the rows (that view splits the lanes, and XLA copies
            # 0.27 GB in float32 to make it, three times a layer)
            g = y.astype(f32) * nn.silu(z.astype(f32))
            g = jnp.concatenate(
                [cols * jax.lax.rsqrt(
                    jnp.mean(jnp.square(cols), -1, keepdims=True) + eps)
                 for cols in jnp.split(g, G, axis=-1)], axis=-1)
            y = (g * scale).astype(cd)
            return jnp.dot(y, w_out.astype(cd)), padded, state

    with jax.named_scope(OP_PROJ):
        h = _block_norm(block, "ln_attn")(x).astype(cd)
    if cache is None:
        # Full mode, the learner's: the mixer's inner activations (the
        # 10,304-wide projection, the convolution's rows, the gate and the
        # norm in float32: 1.7 GB a layer at 16,384 tokens) are made again
        # in the backward from the normed rows; of them only the scan's
        # output is kept, so that the backward runs the scan's backward
        # alone and never its forward a second time (ops/ssd.py).
        y, _, _ = jax.checkpoint(
            mix, policy=jax.checkpoint_policies.save_only_these_names(
                _SSD_OUT))(h, weights, None, None, None)
    else:
        y, padded, state = mix(h, weights, *cache, n_valid)
    with jax.named_scope(OP_PROJ):
        x_out = x + y.astype(x.dtype)
    out = _block_ffn(block, x_out, x)
    if cache is None:
        return out
    # padded row j is xBC row j - back: after n real rows the convolution
    # wants rows n - back .. n - 1
    n = T if n_valid is None else n_valid
    rows = jax.lax.dynamic_slice_in_dim(padded, n, back, axis=1)
    return out, (rows.astype(cache[0].dtype), state)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _normed_heads(a, heads: int, eps: float, mean: bool):
    """``a [..., heads * width]`` (float32) with each head's columns scaled
    by ``rsqrt(sum of their squares + eps)`` (``mean``: of their mean
    square): a head's lane-aligned slice at a time where a head is whole
    lane tiles, as the delta rule's kernels read and write them — no ``[...,
    heads, width]`` view of the rows (that view splits the lanes, and XLA
    copies 0.13 to 0.27 GB to make it, some thirty times a layer: PERF.md
    section 6, PR 43) —, through the view everywhere else. Jitted: a trunk's
    layers share ONE trace and one lowering of the slices (2.4 s of every
    process's start otherwise)."""
    def normed(cols):
        squares = jnp.square(cols)
        size = (jnp.mean if mean else jnp.sum)(squares, -1, keepdims=True)
        return cols * jax.lax.rsqrt(size + eps)

    width = a.shape[-1] // heads
    if width % 128:
        return normed(a.reshape(a.shape[:-1] + (heads, width))).reshape(
            a.shape)
    return jnp.concatenate(
        [normed(cols) for cols in jnp.split(a, heads, axis=-1)], axis=-1)


def _gdn_conv(qkv, w, state=None, conv_fn: Callable = conv):
    """A linear-attention layer's convolution: ``silu(conv(qkv))`` over q,
    k and v together, :func:`_mamba_conv` WITHOUT a bias under the scope
    ``relayrl_gdn_conv``; returns ``(out, qkv_padded)`` likewise."""
    return _mamba_conv(qkv, w, None, state, GDN_CONV_NAME, conv_fn)


def _gdn_a_log_init(key, shape, dtype=jnp.float32):
    """``log`` of decay rates uniform over (0, 16), Qwen3-Next's own: ``g =
    -exp(A_log) softplus(a + dt_bias)``."""
    return jnp.log(jax.random.uniform(key, shape, minval=1e-4,
                                      maxval=16.0)).astype(dtype)


def _gdn_layer(block: TransformerBlock, x, cache, n_valid):
    """A linear-attention (Gated DeltaNet) layer in ``block``'s param scope,
    ``x + out(norm_h(o) * silu(z))`` behind the layer's norm, then the FFN.
    ``[q | k | v | z] = in_qkvz(norm(x))``, ``[b | a] = in_ba(norm(x))``;
    ``[q | k | v] = silu(conv([q | k | v]))`` (no bias); ``beta =
    sigmoid(b)`` and ``g = -exp(A_log) softplus(a + dt_bias)`` in float32,
    one scalar a value head; ``q = q / |q| / sqrt(K)``, ``k = k / |k|`` a
    head; ``o`` the delta rule of ``ops/gdn.py``; ``norm_h`` an RMSNorm over
    each value head's width with a plain weight, BEFORE the gate. No
    positions: the rule orders the tokens.

    Full mode (``cache=None``). Cached modes: ``cache`` is the FIFTH kind,
    ``(the convolution's last gdn_conv_taps - 1 rows of [q | k | v], the
    [B, H, K, V] state in float32)``; one row continues from it in one step
    of the rule (O(1) in the position), several rows (prefill) run the
    chunked rule from it and leave the state after the ``n_valid`` real ones
    (rows past them get ``g = 0`` and ``beta = 0``: they leave the state as
    it is). The readout row of a window needs the whole rule before it: the
    core runs a final linear-attention layer in full and slices."""
    Bsz, T, d = x.shape
    Hk, H = block.gdn_key_heads, block.gdn_value_heads
    K, V = block.gdn_key_dim, block.gdn_value_dim
    kw, vw, back = Hk * K, H * V, block.gdn_conv_taps - 1
    if H % Hk:
        raise ValueError(f"gdn_key_heads {Hk} does not divide "
                         f"gdn_value_heads {H}")
    f32 = jnp.float32
    cd = block.compute_dtype
    lecun = nn.initializers.lecun_normal()
    weights = (
        block.param("gdn_in_qkvz", lecun, (d, 2 * kw + 2 * vw), f32),
        block.param("gdn_in_ba", lecun, (d, 2 * H), f32),
        block.param("gdn_conv_w", lecun,
                    (block.gdn_conv_taps, 2 * kw + vw), f32),
        block.param("gdn_dt_bias", nn.initializers.ones, (H,), f32),
        block.param("gdn_A_log", _gdn_a_log_init, (H,), f32),
        block.param("gdn_norm", nn.initializers.ones, (V,), f32),
        block.param("gdn_out", lecun, (vw, d), f32))
    eps = 1e-6 if block.norm_eps is None else float(block.norm_eps)

    def l2_normed(a, heads):
        # over a head's width, float32 (eps as the source's)
        return _normed_heads(a.astype(f32), heads, 1e-6, False)

    def mix(h, weights, conv_rows, state, n_valid):
        """normed rows -> (the mixer's output, [q | k | v] with the rows
        before it, the state after the last real row)"""
        w_qkvz, w_ba, conv_w, dt_bias, a_log, scale, w_out = weights
        with jax.named_scope(OP_PROJ):
            qkv, z = jnp.split(jnp.dot(h, w_qkvz.astype(cd)),
                               [2 * kw + vw], axis=-1)
            b_in, a_in = jnp.split(
                jnp.dot(h, w_ba.astype(cd), preferred_element_type=f32),
                2, axis=-1)
            beta = jax.nn.sigmoid(b_in)
            g = -jnp.exp(a_log) * jax.nn.softplus(a_in + dt_bias)
            if n_valid is not None:
                real = jnp.arange(T)[None, :, None] < n_valid
                beta, g = jnp.where(real, beta, 0.0), jnp.where(real, g, 0.0)
        qkv, padded = _gdn_conv(qkv, conv_w, conv_rows, block.conv_fn)
        with jax.named_scope(OP_PROJ):
            q, k, v = jnp.split(qkv, [kw, 2 * kw], axis=-1)
            q = (l2_normed(q, Hk) * K ** -0.5).astype(cd).reshape(
                Bsz, T, Hk, K)
            k = l2_normed(k, Hk).astype(cd).reshape(Bsz, T, Hk, K)
            v = v.reshape(Bsz, T, H, V)
        if T == 1:
            # one row is one step of the rule, from the cache's state or
            # (the row ``init`` traces) from nothing: no chunk to pad to
            if state is None:
                state = jnp.zeros((Bsz, H, K, V), f32)
            o, state = gdn_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                beta[:, 0], state)
            o = o[:, None]
        else:
            o, state = block.rule_fn(q, k, v, g, beta, block.gdn_chunk,
                                     state)
        # named (and kept) with the heads side by side in the lanes, as the
        # rule's kernels write it
        o = checkpoint_name(o.reshape(Bsz, T, vw), _GDN_OUT)
        with jax.named_scope(OP_PROJ):
            # the norm over each head's width BEFORE the gate, plain weight
            y = _normed_heads(o.astype(f32), H, eps, True) * jnp.tile(
                scale, H)
            y = y * nn.silu(z.astype(f32))
            return jnp.dot(y.astype(cd), w_out.astype(cd)), padded, state

    with jax.named_scope(OP_PROJ):
        layer_in = x
        h = _block_norm(block, "ln_attn")(x).astype(cd)
    if cache is None:
        # Full mode, the learner's: the mixer's inner activations (the
        # 12,288-wide projection, the convolution's rows, the normed q and
        # k, the gated norm in float32) are made again in the backward from
        # the normed rows; of them only the rule's output is kept — and,
        # where the rule runs as kernels, the solve's tiles their forward
        # wrote (67 MB a layer) —, so that the backward runs the rule's
        # backward alone and never its forward a second time (ops/gdn.py),
        # as _mamba_layer.
        y, _, _ = jax.checkpoint(
            mix, policy=jax.checkpoint_policies.save_only_these_names(
                _GDN_OUT, _GDN_SOLVE))(h, weights, None, None, None)
    else:
        y, padded, state = mix(h, weights, *cache, n_valid)
    with jax.named_scope(OP_PROJ):
        x_out = x + y.astype(x.dtype)
    out = _block_ffn(block, x_out, layer_in)
    if cache is None:
        return out
    # padded row j is [q | k | v] row j - back: after n real rows the
    # convolution wants rows n - back .. n - 1
    n = T if n_valid is None else n_valid
    rows = jax.lax.dynamic_slice_in_dim(padded, n, back, axis=1)
    return out, (rows.astype(cache[0].dtype), state)


def _embed_obs(parent: nn.Module, obs, d_model: int, max_seq_len: int,
               start=0, learned_positions: bool = True):
    """Obs embedding + positional table, built in the CALLER's param scope
    (layer names land flat: obs_embed / pos_embed) — the single source of
    truth shared by TransformerCore (full AND cached-decode modes, which
    differ only in the ``start`` position) and the pipeline family's
    _PPEmbed. With rotary positions (``learned_positions=False``) the
    blocks place the tokens and there is no ``pos_embed`` leaf."""
    _, T, _ = obs.shape
    with jax.named_scope(EMBED):
        x = nn.Dense(d_model, dtype=jnp.float32, name="obs_embed")(obs)
        if not learned_positions:
            return x
        pos = parent.param("pos_embed", nn.initializers.normal(0.02),
                           (max_seq_len, d_model), jnp.float32)
        return x + jax.lax.dynamic_slice_in_dim(pos, start, T, axis=0)[None]


def _readout_heads(x, mask, act_dim: int, d_model: int, has_critic: bool,
                   norm: str = "layer", norm_eps=None,
                   norm_zero_centred: bool = False):
    """Final norm (the arch's kind and epsilon, as the blocks') + pi/vf
    heads in the caller's scope (shared with _PPReadout; the vf optimizer
    partition keys off these exact `vf*` names)."""
    with jax.named_scope(HEADS):
        x = _norm(norm, norm_eps, "ln_final", norm_zero_centred)(x)
        logits = nn.Dense(act_dim, dtype=jnp.float32, name="pi_head")(x)
        if mask is not None:
            logits = jnp.where(mask > 0, logits, _MASK_FILL)
        if has_critic:
            # Shared-trunk actor-critic: unlike the MLP family's separate
            # vf_trunk, the critic reads the policy-shaped features, so the
            # vf optimizer partition (labels by `vf*` prefix) trains only
            # this head — a 2-layer MLP rather than a single linear probe to
            # give the vf steps real capacity.
            h = nn.Dense(d_model, dtype=jnp.float32, name="vf_head_up")(x)
            v = nn.Dense(1, dtype=jnp.float32, name="vf_head")(nn.tanh(h))
            v = jnp.squeeze(v, axis=-1)
        else:
            v = jnp.zeros(logits.shape[:-1], jnp.float32)
    return logits, v


class TransformerCore(nn.Module):
    """Obs sequence -> per-step (logits, v). Residual stream stays f32."""

    act_dim: int
    d_model: int
    n_layers: int
    n_heads: int
    mlp_ratio: int
    max_seq_len: int
    has_critic: bool
    attn_fn: Callable
    compute_dtype: Any
    moe_experts: int = 0
    moe_top_k: int = 2
    # TransformerBlock's arch fields, passed through as one dict
    block_kw: Mapping[str, Any] = flax.core.FrozenDict()
    # Per layer: its kind — an operator and an FFN ("full_attention" |
    # "sliding_attention" | "conv" | "linear_attention"; empty: full
    # attention everywhere) or ONE part ("mamba2" | "attention" | "ffn") —
    # and, in a MoE trunk, how
    # many leading layers keep the dense FFN.
    layer_types: tuple[str, ...] = ()
    moe_dense_layers: int = 0
    # the window of the "sliding_attention" layers
    sliding_window: int | None = None
    # Per layer under rotary positions: whether RoPE turns its q and k
    # (empty: every attention layer's). A layer left out sees no positions.
    rope_layers: tuple[bool, ...] = ()
    # "learned": a table added to the embedding (unless the blocks rotate:
    # block_kw's rope_theta); "none": no positional signal at all.
    positions: str = "learned"

    def layer_parts(self, i: int) -> tuple[str, bool]:
        """Layer ``i``'s (operator, whether an FFN follows it)."""
        kind = self.layer_types[i] if self.layer_types else "full_attention"
        if kind not in _LAYER_KINDS:
            raise ValueError(f"unknown layer type {kind!r} "
                             f"({' | '.join(_LAYER_KINDS)})")
        return _LAYER_KINDS[kind]

    def layer_op(self, i: int) -> str:
        return self.layer_parts(i)[0]

    def layer_window(self, i: int) -> int | None:
        """The window of layer ``i`` (None: a global layer, or a conv)."""
        if not self.layer_types or self.layer_types[i] != "sliding_attention":
            return None
        if not self.sliding_window or self.sliding_window < 1:
            raise ValueError(f"layer {i} is sliding_attention and the arch "
                             f"gives no sliding_window")
        return int(self.sliding_window)

    def layer_experts(self, i: int) -> int:
        if i < self.moe_dense_layers or not self.layer_parts(i)[1]:
            return 0
        return self.moe_experts

    @nn.compact
    def __call__(self, obs, mask=None, cache=None, t=None, readout_t=None,
                 n_valid=None):
        """Full mode: obs ``[B, T, D]`` -> (logits, v). Decode mode
        (``cache`` = tuple of per-layer states — a (k, v) pair for an
        attention layer (a ring of rows for a windowed one), the last rows
        of ``B * u`` for a conv layer, the convolution's rows and the
        state for a Mamba-2 layer, ``()`` for an FFN alone —,
        ``t`` = position; ``n_valid``: prefill's count of real rows):
        obs is ``[B, 1, D]``; returns ``((logits, v), new_cache)`` for the
        single position. Readout mode (``readout_t`` = dynamic row index):
        obs is a full window ``[B, W, D]`` but only position ``readout_t``
        is decoded — layers ``0..L-2`` run over every row (deeper layers
        attend all earlier positions' hidden states, so those are live),
        the final layer runs row-only (its other rows feed nothing), and
        the heads see the one row; returns ``(logits[B, A], v[B])``. Init
        always traces the full path, so all modes share one param tree."""
        decode = cache is not None
        kw = self.block_kw

        for what, per_layer in (("layer_types", self.layer_types),
                                ("rope_layers", self.rope_layers)):
            if per_layer and len(per_layer) != self.n_layers:
                raise ValueError(f"{what} names {len(per_layer)} layers, "
                                 f"n_layers is {self.n_layers}")

        def block_at(i: int) -> TransformerBlock:
            kw_i = kw
            if self.rope_layers and not self.rope_layers[i]:
                kw_i = {**kw, "rope_theta": None}   # no positions at all
            return TransformerBlock(
                self.d_model, self.n_heads, self.mlp_ratio, self.attn_fn,
                self.compute_dtype, moe_experts=self.layer_experts(i),
                moe_top_k=self.moe_top_k, op=self.layer_op(i),
                has_ffn=self.layer_parts(i)[1],
                window=self.layer_window(i), name=f"block_{i}", **kw_i)

        def heads(x, mask):
            return _readout_heads(x, mask, self.act_dim, self.d_model,
                                  self.has_critic, kw.get("norm", "layer"),
                                  kw.get("norm_eps"),
                                  kw.get("norm_zero_centred", False))

        x = _embed_obs(
            self, obs, self.d_model, self.max_seq_len,
            start=t if decode else 0,
            learned_positions=(self.positions == "learned"
                               and kw.get("rope_theta") is None))
        if readout_t is not None:
            idx = jnp.asarray(readout_t, jnp.int32)
            for i in range(self.n_layers - 1):
                x = block_at(i)(x)
            final = block_at(self.n_layers - 1)
            if (final.moe_experts > 0 and final.op == "attention"
                    or final.op in ("mamba2", "gdn")):
                # The MoE final block keeps its full-window pass (routing
                # is per token, so the sliced row is what a row-only pass
                # would give; the shortcut is simply not taken here). A
                # conv layer takes the row path whatever its FFN, as an FFN
                # alone does; a Mamba-2 layer's row needs the scan over
                # every row before it, a linear-attention layer's the rule.
                x = jax.lax.dynamic_slice_in_dim(final(x), idx, 1, axis=1)
            else:
                x = final(x, readout_idx=idx)
            mask_row = None
            if mask is not None:
                mask_row = jax.lax.dynamic_slice_in_dim(mask, idx, 1,
                                                        axis=1)
            logits, v = heads(x, mask_row)
            return logits[:, 0], v[:, 0]
        new_cache = []
        for i in range(self.n_layers):
            block = block_at(i)
            if decode:
                x, layer_cache = block(x, cache=cache[i], t=t,
                                       n_valid=n_valid)
                new_cache.append(layer_cache)
            else:
                x = block(x)
        out = heads(x, mask)
        return (out, tuple(new_cache)) if decode else out


def _as_btd(obs, mask):
    """Normalize step/evaluate inputs to [B, T, D] (+ mask [B, T, A])."""
    obs = jnp.asarray(obs)
    if obs.ndim == 1:          # [D] -> context of one
        obs, lead = obs[None, None], "scalar"
    elif obs.ndim == 2:        # [T, D]
        obs, lead = obs[None], "seq"
    else:                      # [B, T, D]
        lead = "batch"
    if mask is not None:
        mask = jnp.asarray(mask)
        while mask.ndim < 3:
            mask = mask[None]
    return obs, mask, lead


def _policy_from_apply(arch: Mapping[str, Any], init_params, apply_fn,
                       apply_row_fn=None) -> Policy:
    """Build the sequence-policy ABI (step/evaluate/mode/windowed variants)
    over any ``apply_fn(params, obs[B,T,D], mask) -> (logits[B,T,A],
    v[B,T])`` — shared by the plain and pipeline transformer families.

    ``apply_row_fn(params, obs[B,W,D], mask, idx) -> (logits[B,A], v[B])``
    is the optional readout-row-only forward for the window paths
    (step_window/mode_window): the full forward computes logits for every
    window row and reads one, so a family that can decode just the
    readout row (TransformerCore readout mode) skips the final layer's
    dead (W-1)/W — the per-step win every window-driven actor tier
    (vector batched step_window, serving sessions, the fused anakin scan)
    inherits from this one seam, which is also what keeps their bytes
    identical to each other. Families without a row decode (the pipeline
    family's staged apply) omit it and keep the full-forward readout."""

    def step(params, rng, obs, mask=None):
        obs, mask, lead = _as_btd(obs, mask)
        logits, v = apply_fn(params, obs, mask)
        logits_last, v_last = logits[:, -1], v[:, -1]
        act = jax.random.categorical(rng, logits_last, axis=-1)
        logp = _categorical_logp(logits_last, act)
        if lead != "batch":
            act, logp, v_last = act[0], logp[0], v_last[0]
        return act, {"logp_a": logp, "v": v_last}

    def evaluate(params, obs, act, mask=None):
        obs, mask, lead = _as_btd(obs, mask)
        act_b = jnp.asarray(act)
        while act_b.ndim < 2:  # scalar -> [1,1], [T] -> [1,T]
            act_b = act_b[None]
        logits, v = apply_fn(params, obs, mask)
        with jax.named_scope(HEADS):
            logp = _categorical_logp(logits, act_b)
            ent = _categorical_entropy(logits)
        if lead != "batch":
            logp, ent, v = logp[0], ent[0], v[0]
        if lead == "scalar":
            logp, ent, v = logp[0], ent[0], v[0]
        return logp, ent, v

    def mode(params, obs, mask=None):
        obs, mask, lead = _as_btd(obs, mask)
        logits, _ = apply_fn(params, obs, mask)
        act = jnp.argmax(logits[:, -1], axis=-1)
        return act if lead == "batch" else act[0]

    def _window_logits(params, window, t, mask):
        obs_b, mask_b, _ = _as_btd(window, mask)
        idx = jnp.clip(t - 1, 0, obs_b.shape[1] - 1)
        if apply_row_fn is not None:
            logits_r, v_r = apply_row_fn(params, obs_b, mask_b, idx)
            return logits_r[0], v_r[0]
        logits, v = apply_fn(params, obs_b, mask_b)
        return logits[0, idx], v[0, idx]

    def step_window(params, rng, window, t, mask=None):
        """Act from a right-zero-padded history window ``[W, obs_dim]``
        with ``t`` real rows: the readout position t-1 only attends
        positions < t (causal), so the zero padding is never seen and one
        fixed shape serves every history length — the actor-side fix for
        the train(full sequence)/serve(context-1) mismatch."""
        logits_t, v_t = _window_logits(params, window, t, mask)
        act = jax.random.categorical(rng, logits_t, axis=-1)
        return act, {"logp_a": _categorical_logp(logits_t, act), "v": v_t}

    def mode_window(params, window, t, mask=None):
        """Greedy readout from the history window (the deterministic-eval
        counterpart of step_window)."""
        logits_t, _ = _window_logits(params, window, t, mask)
        return jnp.argmax(logits_t, axis=-1)

    return Policy(arch=dict(arch), init_params=init_params, step=step,
                  evaluate=evaluate, mode=mode, step_window=step_window,
                  mode_window=mode_window)


# Arch keys that describe the model's block (TransformerBlock's fields of
# the same names; ``positions`` + ``rope_theta`` become its ``rope_theta``).
# An arch with none of them is the GPT-2 shaped block.
_BLOCK_ARCH_KEYS = ("norm", "norm_eps", "qk_norm", "use_bias", "ffn", "d_ff",
                    "moe_d_ff", "moe_norm_topk_prob", "moe_dispatch",
                    "n_kv_heads", "conv_taps", "head_dim",
                    "moe_router_input", "mamba_heads", "mamba_head_dim",
                    "mamba_state", "mamba_groups", "mamba_conv_taps",
                    "mamba_chunk", "norm_zero_centred", "rope_share",
                    "attn_gate", "gdn_key_heads", "gdn_value_heads",
                    "gdn_key_dim", "gdn_value_dim", "gdn_conv_taps",
                    "gdn_chunk")
# MoEMLP's fields by the arch key that sets each (block field ``moe_kw``)
_MOE_ARCH_KEYS = {"moe_router": "router", "moe_expert_bias": "expert_bias",
                  "moe_held": "held", "moe_routed_scaling": "routed_scaling",
                  "moe_shared_d_ff": "shared_d_ff",
                  "moe_shared_expert_gate": "shared_gate"}
# the core's own: what kind each layer is
_LAYER_ARCH_KEYS = ("layer_types", "moe_dense_layers", "sliding_window",
                    "rope_layers")
# ``layer_types`` entry -> (the layer's operator, whether an FFN follows)
_LAYER_KINDS = {"full_attention": ("attention", True),
                "sliding_attention": ("attention", True),
                "conv": ("conv", True),
                "mamba2": ("mamba2", False),
                "linear_attention": ("gdn", True),
                "attention": ("attention", False),
                "ffn": ("none", True)}


def _block_kwargs(arch: Mapping[str, Any]) -> dict:
    kw = {k: arch[k] for k in _BLOCK_ARCH_KEYS if k in arch}
    moe_kw = {field: arch[k] for k, field in _MOE_ARCH_KEYS.items()
              if k in arch}
    if "held" in moe_kw:
        moe_kw["held"] = tuple(int(a) for a in moe_kw["held"])
    if moe_kw:
        kw["moe_kw"] = flax.core.FrozenDict(moe_kw)
    positions = arch.get("positions", "learned")
    if positions == "rope":
        kw["rope_theta"] = float(arch.get("rope_theta", 10000.0))
    elif positions not in ("learned", "none"):
        raise ValueError(f"unknown positions {positions!r} "
                         f"(learned | rope | none)")
    return kw


def _make_core(arch: Mapping[str, Any], moe_experts: int = 0,
               attn_fn: Callable | None = None,
               scan_fn: Callable = ssd,
               rule_fn: Callable = gdn,
               conv_fn: Callable = conv) -> TransformerCore:
    """Arch -> TransformerCore module (shared by the policy builders and
    diagnostics like :func:`relayrl_tpu.models.moe.expert_utilization`,
    which re-applies the same module with captured intermediates)."""
    if attn_fn is None:
        attn_fn = _resolve_attention(arch)[0]
    return TransformerCore(
        act_dim=int(arch["act_dim"]),
        d_model=int(arch.get("d_model", 128)),
        n_layers=int(arch.get("n_layers", 2)),
        n_heads=int(arch.get("n_heads", 4)),
        mlp_ratio=int(arch.get("mlp_ratio", 4)),
        max_seq_len=int(arch.get("max_seq_len", 1024)),
        has_critic=bool(arch.get("has_critic", True)),
        attn_fn=attn_fn,
        compute_dtype=_compute_dtype(arch),
        moe_experts=moe_experts,
        moe_top_k=int(arch.get("moe_top_k", 2)),
        block_kw=flax.core.FrozenDict(
            {**_block_kwargs(arch), "scan_fn": scan_fn,
             "rule_fn": rule_fn, "conv_fn": conv_fn}),
        layer_types=tuple(arch.get("layer_types", ())),
        moe_dense_layers=int(arch.get("moe_dense_layers", 0)),
        sliding_window=arch.get("sliding_window"),
        rope_layers=tuple(bool(r) for r in arch.get("rope_layers", ())),
        positions=("none" if arch.get("positions") == "none" else "learned"),
    )


def _build_core_policy(arch: Mapping[str, Any], moe_experts: int = 0) -> Policy:
    obs_dim = int(arch["obs_dim"])
    attn_fn, attention_backends, score_area, attn_layout = (
        _resolve_attention(arch))
    scan_fn, scan_backends = _resolve_scan()
    rule_fn, gdn_backends = _resolve_delta_rule()
    conv_fn, conv_backends = _resolve_conv()
    core = _make_core(arch, moe_experts, attn_fn, scan_fn, rule_fn, conv_fn)

    def init_params(rng):
        return core.init(rng, jnp.zeros((1, 1, obs_dim), jnp.float32))

    head_dim = int(arch.get("head_dim", core.d_model // core.n_heads))
    n_kv_heads = int(arch.get("n_kv_heads", core.n_heads))
    conv_back = int(arch.get("conv_taps", 3)) - 1
    cache_dtype = core.compute_dtype

    def sized(key: str) -> int:     # the arch's, else the block's default
        return int(arch.get(key, getattr(TransformerBlock, key)))

    def init_cache(length: int, batch_size: int = 1):
        """Zeroed per-layer states for incremental decoding, five kinds
        side by side: a (k, v) pair ``[B, length, Hkv, hd]`` for a global
        attention layer, a ring ``[B, min(window, length), Hkv, hd]`` x 2
        for a windowed one (``_ring_cached``), the last ``conv_taps - 1``
        rows of ``B * u`` ``[B, conv_taps - 1, d]`` for a conv layer, and
        for a Mamba-2 layer the convolution's last ``mamba_conv_taps - 1``
        rows of ``xBC`` with the ``[B, H, P, N]`` state in float32 — whose
        size does not grow with ``length`` —, for a linear-attention layer
        the convolution's last ``gdn_conv_taps - 1`` rows of ``[q | k | v]``
        with the ``[B, H, K, V]`` state in float32, likewise. An FFN alone
        keeps nothing."""
        conv = (batch_size, conv_back, core.d_model)

        def kv_pair(i: int):
            rows = min(core.layer_window(i) or int(length), int(length))
            kv = (batch_size, rows, n_kv_heads, head_dim)
            return jnp.zeros(kv, cache_dtype), jnp.zeros(kv, cache_dtype)

        def mamba_state():
            heads, width = sized("mamba_heads"), sized("mamba_head_dim")
            state = sized("mamba_state")
            xbc = heads * width + 2 * sized("mamba_groups") * state
            return (jnp.zeros((batch_size, sized("mamba_conv_taps") - 1,
                               xbc), cache_dtype),
                    jnp.zeros((batch_size, heads, width, state),
                              jnp.float32))

        def gdn_state():
            heads, k_dim = sized("gdn_value_heads"), sized("gdn_key_dim")
            v_dim = sized("gdn_value_dim")
            qkv = 2 * sized("gdn_key_heads") * k_dim + heads * v_dim
            return (jnp.zeros((batch_size, sized("gdn_conv_taps") - 1, qkv),
                              cache_dtype),
                    jnp.zeros((batch_size, heads, k_dim, v_dim),
                              jnp.float32))

        def layer_cache(i: int):
            op = core.layer_op(i)
            if op == "conv":
                return jnp.zeros(conv, cache_dtype)
            if op == "mamba2":
                return mamba_state()
            if op == "gdn":
                return gdn_state()
            return () if op == "none" else kv_pair(i)

        return tuple(layer_cache(i) for i in range(core.n_layers))

    def step_cached(params, rng, cache, obs, t, mask=None):
        """One O(W) decode step: writes position ``t`` into the cache and
        samples the action for it. Numerics match ``step_window`` at the
        same position (tests/test_kv_cache.py)."""
        obs = jnp.asarray(obs)
        if obs.ndim == 1:                       # [D] -> [1,1,D]
            obs = obs[None, None]
        elif obs.ndim == 2:                     # [B,D] -> [B,1,D]
            obs = obs[:, None]
        mask_b = None
        if mask is not None:
            mask_b = jnp.asarray(mask)
            if mask_b.ndim == 1:                # [A] -> [1,1,A]
                mask_b = mask_b[None, None]
            elif mask_b.ndim == 2:              # [B,A] -> [B,1,A]
                mask_b = mask_b[:, None]
        (logits, v), new_cache = core.apply(params, obs, mask_b,
                                            cache=cache, t=t)
        logits_t, v_t = logits[:, 0], v[:, 0]
        act = jax.random.categorical(rng, logits_t, axis=-1)
        aux = {"logp_a": _categorical_logp(logits_t, act), "v": v_t}
        if obs.shape[0] == 1:
            act = act[0]
            aux = {k: a[0] for k, a in aux.items()}
        return act, aux, new_cache

    def prefill_cache(params, cache, window, n_valid=None):
        """Rebuild the whole cache from a padded window in ONE dispatch
        (post-hot-swap path): runs decode mode with T = W queries at
        t=0. Padding rows write garbage K/V beyond the real prefix, which
        later per-step decodes never attend (their causal mask stops at
        the current t) and overwrite in order. A conv layer's state and a
        Mamba-2 layer's have no positions to overwrite, and a windowed
        layer's ring would lose live rows to padding ones: all three are
        taken from the rows before ``n_valid``, the count of real rows
        (None: the whole window is real)."""
        window = jnp.asarray(window)
        if window.ndim == 2:
            window = window[None]
        _, new_cache = core.apply(params, window, None, cache=cache, t=0,
                                  n_valid=n_valid)
        return new_cache

    policy = _policy_from_apply(
        arch, init_params, core.apply,
        apply_row_fn=lambda params, obs, mask, idx: core.apply(
            params, obs, mask, readout_t=idx))
    import dataclasses as _dc

    evaluate_stats = None
    if moe_experts > 0:
        def evaluate_stats(params, obs, act, mask=None):
            """``evaluate`` plus the expert load of the same forward
            (``moe.load_extremes`` of the sown group sizes)."""
            from relayrl_tpu.models.moe import load_extremes

            stats = {}

            def apply_fn(params, obs, mask):
                out, state = core.apply(params, obs, mask,
                                        mutable=["intermediates"])
                stats.update(load_extremes(state["intermediates"]))
                return out

            out = _policy_from_apply(arch, init_params, apply_fn).evaluate(
                params, obs, act, mask)
            return (*out, stats)

    return _dc.replace(policy, init_cache=init_cache,
                       step_cached=step_cached,
                       prefill_cache=prefill_cache,
                       attention_backends=attention_backends,
                       attention_score_area_pct=score_area,
                       attention_layout=attn_layout,
                       scan_backends=scan_backends,
                       gdn_backends=gdn_backends,
                       conv_backends=conv_backends,
                       evaluate_stats=evaluate_stats)


@register_model("transformer_discrete")
def build_transformer_discrete(arch: Mapping[str, Any]) -> Policy:
    return _build_core_policy(arch)


@register_model("transformer_moe_discrete")
def build_transformer_moe_discrete(arch: Mapping[str, Any]) -> Policy:
    """Transformer whose FFNs are per-token top-k MoE layers (models/moe.py
    — NOT expert-choice, which is non-causal for policies); expert stacks
    shard over the mesh ``ep`` axis via the param rules. Same sequence ABI
    as transformer_discrete."""
    return _build_core_policy(arch, moe_experts=int(arch.get("moe_experts", 4)))


class _PPEmbed(nn.Module):
    """Input half of the pipeline transformer (stage-0-adjacent params);
    delegates to the shared :func:`_embed_obs` so names/math match
    TransformerCore exactly."""

    d_model: int
    max_seq_len: int

    @nn.compact
    def __call__(self, obs):
        return _embed_obs(self, obs, self.d_model, self.max_seq_len)


class _PPReadout(nn.Module):
    """Output half: delegates to the shared :func:`_readout_heads` (the vf
    optimizer partition keys off the same `vf*` names)."""

    act_dim: int
    d_model: int
    has_critic: bool

    @nn.compact
    def __call__(self, x, mask=None):
        return _readout_heads(x, mask, self.act_dim, self.d_model,
                              self.has_critic)


_PP_IO_KEYS = ("obs_embed", "pos_embed")


@register_model("transformer_pp_discrete")
def build_transformer_pp_discrete(arch: Mapping[str, Any]) -> Policy:
    """Pipeline-parallel transformer: identical math to
    ``transformer_discrete`` but the layer stack is STACKED on a leading
    axis (param subtree ``blocks``, sharded ``P("pp", ...)`` by the rules in
    parallel/sharding.py). With an ambient mesh whose ``pp`` axis > 1 the
    stack runs as a GPipe microbatch pipeline over ``pp``
    (:func:`relayrl_tpu.parallel.pipeline.pipeline_apply`); otherwise a
    plain ``lax.scan`` over layers — so the SAME arch config serves CPU
    actor hosts and the pipelined TPU learner (SURVEY.md §7.4 item 2).
    """
    new = [k for k in _BLOCK_ARCH_KEYS + _LAYER_ARCH_KEYS
           + tuple(_MOE_ARCH_KEYS) + ("positions", "rope_theta")
           if k in arch]
    if new:
        raise ValueError(
            f"transformer_pp_discrete builds the GPT-2 shaped block only "
            f"and does not take {new}; use transformer_discrete / "
            f"transformer_moe_discrete for these")
    obs_dim = int(arch["obs_dim"])
    d_model = int(arch.get("d_model", 128))
    n_layers = int(arch.get("n_layers", 2))
    n_micro = arch.get("pp_microbatches")
    attn_fn, attention_backends, score_area, attn_layout = (
        _resolve_attention(arch))
    block = TransformerBlock(
        d_model, int(arch.get("n_heads", 4)), int(arch.get("mlp_ratio", 4)),
        attn_fn, _compute_dtype(arch))
    embed = _PPEmbed(d_model, int(arch.get("max_seq_len", 1024)))
    readout = _PPReadout(int(arch["act_dim"]), d_model,
                         bool(arch.get("has_critic", True)))

    def init_params(rng):
        r_embed, r_read, r_blocks = jax.random.split(rng, 3)
        e = embed.init(r_embed, jnp.zeros((1, 1, obs_dim), jnp.float32))
        r = readout.init(r_read, jnp.zeros((1, 1, d_model), jnp.float32))
        stacked = jax.vmap(
            lambda k: block.init(k, jnp.zeros((1, 1, d_model), jnp.float32))
        )(jax.random.split(r_blocks, n_layers))
        return {"params": {**e["params"], **r["params"],
                           "blocks": stacked["params"]}}

    def _stage(local_blocks, h):
        return jax.lax.scan(
            lambda c, p: (block.apply({"params": p}, c), None),
            h, local_blocks)[0]

    def apply_fn(params, obs, mask=None):
        from relayrl_tpu.parallel.context import current_mesh

        inner = params["params"]
        x = embed.apply(
            {"params": {k: inner[k] for k in _PP_IO_KEYS}}, obs)
        mesh = current_mesh()
        if mesh is not None and mesh.shape.get("pp", 1) > 1:
            from relayrl_tpu.parallel.pipeline import pipeline_apply

            x = pipeline_apply(_stage, inner["blocks"], x, mesh,
                               n_microbatches=n_micro)
        else:
            x = _stage(inner["blocks"], x)
        ro = {k: v for k, v in inner.items()
              if k not in _PP_IO_KEYS + ("blocks",)}
        return readout.apply({"params": ro}, x, mask)

    import dataclasses as _dc

    return _dc.replace(_policy_from_apply(arch, init_params, apply_fn),
                       attention_backends=attention_backends,
                       attention_score_area_pct=score_area,
                       attention_layout=attn_layout)
