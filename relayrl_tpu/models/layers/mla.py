"""The operator ``"latent_attention"``: multi-head latent attention
(DeepSeek-V2's MLA, as Kimi Linear runs it) — causal softmax attention whose
keys and values are expanded from ONE compressed row a token.

``q = q_proj(u)`` as ``n_heads`` heads of ``qk_nope_head_dim +
qk_rope_head_dim`` — or, where the arch gives the query a low rank of its
own (``q_lora_rank``: DeepSeek-V3's), ``q = q_b(q_a_norm(q_a(u)))``,
``q_a_norm`` an RMSNorm over the rank's lanes; ``[c | k_pe] = kv_a(u)``
(``kv_lora_rank`` + ``qk_rope_head_dim`` lanes); ``[k_nope | v] =
kv_b(kv_a_norm(c))`` as ``n_heads`` heads of ``qk_nope_head_dim +
v_head_dim`` (``kv_a_norm`` an RMSNorm); head ``h``'s key is ``[k_nope_h |
k_pe]``, the ``k_pe`` lanes the SAME for every head; softmax of ``q_h . k_h
/ sqrt(qk_nope_head_dim + qk_rope_head_dim)``; ``attn_out`` over ``n_heads *
v_head_dim``. Under the trunk's ``positions: "rope"`` the shared ``k_pe``
lanes and each query's last ``qk_rope_head_dim`` lanes are rotated at their
absolute positions (:func:`_rotated`, under the part
``relayrl_latent_rope``): ``attention.apply_rope``'s half-split pairing, or
under ``rope_interleave`` the pairing of lanes ``(2i, 2i + 1)`` — a static
de-interleave of those lanes in q and k alike, then the half-split rotation,
nothing permuted back: a dot product does not see one permutation of both
its operands' lanes. Under any other ``positions`` no lane turns (Kimi
Linear: ``mla_use_nope``). q and k are one width and v another (192 / 128):
the arch's ``attention`` backends take that (``ops/flash.py``'s ``_mla``
kernels on a TPU, blockwise or dense elsewhere), recorded in
``Policy.attention_backends`` under the q / k width.

Three modes, one parameter tree, operator ``"attention"``'s:

* full (``cache=None``): ``x [B, T, d] -> [B, T, d]`` through the backend,
  keys and values expanded for every row;
* cached (``cache`` = this layer's ``(c, k_pe)``: the LATENT rows ``[B, W,
  kv_lora_rank]`` before their norm and the shared key lanes ``[B, W,
  qk_rope_head_dim]``, ALREADY rotated at their own positions where the layer
  rotates: a step turns the new row alone —
  576 numbers a token where the heads' keys and values would be 10,240;
  ``t`` the write index): x is one position, or a prefill's rows from
  position ``t``, query ``j`` at ``t + j``; every step expands the cache's
  rows through ``kv_b`` again (the weight-absorbed form, which never expands
  them, is ROADMAP's);
* readout (``readout_idx`` set): the latent rows over every row, the query,
  the output projection and the FFN for the ONE row the heads read.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from relayrl_tpu.models.layers.attention import apply_rope
from relayrl_tpu.models.layers.block import (
    block_dense,
    block_ffn,
    block_norm,
    block_residual,
)
from relayrl_tpu.ops.attention import dense_attention
from relayrl_tpu.ops.scopes import LATENT_ROPE, OP_PROJ

# as operator "attention": with a dense FFN; the core keeps a final layer
# with experts on its full-window pass
ROW_READOUT = True
# the attention entry and its records are operator "attention"'s
KERNELS = ()


def _rotated(cfg, a, start):
    """``a [B, L, heads, w]`` (or the shared ``[B, L, w]``), row j at absolute
    position ``start + j``: its LAST ``qk_rope_head_dim`` lanes rotated — or
    ``a`` as it came where the layer rotates none."""
    theta, pe = cfg["rope_theta"], cfg["qk_rope_head_dim"]
    if theta is None:
        return a
    with jax.named_scope(LATENT_ROPE):
        shared = a.ndim == 3
        if shared:
            a = a[:, :, None]
        lanes = a[..., a.shape[-1] - pe:]
        if cfg["rope_interleave"]:
            # pairs (2i, 2i + 1) -> (i, i + pe / 2): the half-split pairing
            lanes = jnp.concatenate([lanes[..., 0::2], lanes[..., 1::2]], -1)
        lanes = apply_rope(lanes, start, theta)
        if a.shape[-1] > pe:
            lanes = jnp.concatenate([a[..., :a.shape[-1] - pe], lanes], -1)
        return lanes[:, :, 0] if shared else lanes


def apply(block, x, cache, t, readout_idx, n_valid):
    B, T, _ = x.shape
    cfg, d, cd = block.cfg, block.d_model, block.compute_dtype
    H = cfg["n_heads"]
    rank, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    pe, vd = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    start = 0 if t is None else t

    def expand(c, k_pe):
        """Latent rows ``c [B, L, rank]`` and shared lanes ``k_pe [B, L,
        pe]`` -> ``(k [B, L, H, nope + pe], v [B, L, H, vd])``."""
        kv = block_dense(block, H * (nope + vd), "kv_b")(
            block_norm(block, "kv_a_norm", "rms")(c).astype(cd))
        k_nope, v = jnp.split(kv.reshape(c.shape[:2] + (H, nope + vd)),
                              [nope], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe[:, :, None].astype(cd),
                                      k_nope.shape[:3] + (pe,))], axis=-1)
        return k, v

    with jax.named_scope(OP_PROJ):
        layer_in = x
        h = block_norm(block, "ln_attn")(x).astype(cd)
        if cfg["q_lora_rank"] is None:
            q = block_dense(block, H * (nope + pe), "q_proj")(h)
        else:
            q = block_dense(block, H * (nope + pe), "q_b")(block_norm(
                block, "q_a_norm", "rms")(block_dense(
                    block, cfg["q_lora_rank"], "q_a")(h)).astype(cd))
        q = q.reshape(B, T, H, nope + pe)
        c, k_pe = jnp.split(block_dense(block, rank + pe, "kv_a")(h), [rank],
                            axis=-1)
    # the new rows' shared lanes, turned once, before anything keeps them
    k_pe = _rotated(cfg, k_pe, start)

    if readout_idx is not None:
        with jax.named_scope(OP_PROJ):
            k, v = expand(c, k_pe)
            q_row = jax.lax.dynamic_slice_in_dim(q, readout_idx, 1, axis=1)
        attn = dense_attention(_rotated(cfg, q_row, readout_idx), k, v,
                               causal=True, q_offset=readout_idx)
        with jax.named_scope(OP_PROJ):
            row_in = jax.lax.dynamic_slice_in_dim(x, readout_idx, 1, axis=1)
            x = block_residual(block, row_in, block_dense(
                block, d, "attn_out")(attn.reshape(B, 1, H * vd)),
                "ln_attn_out")
        return block_ffn(block, x, row_in)
    q = _rotated(cfg, q, start)
    if cache is None:
        with jax.named_scope(OP_PROJ):
            k, v = expand(c, k_pe)
        attn = block.fns["attention"](q, k, v, None)
        new_cache = None
    else:
        c_cache, pe_cache = cache
        c_cache = jax.lax.dynamic_update_slice_in_dim(
            c_cache, c.astype(c_cache.dtype), t, axis=1)
        pe_cache = jax.lax.dynamic_update_slice_in_dim(
            pe_cache, k_pe.astype(pe_cache.dtype), t, axis=1)
        with jax.named_scope(OP_PROJ):
            k, v = expand(c_cache, pe_cache)
        # query j sits at absolute position t + j, as operator "attention"'s
        attn = dense_attention(q, k, v, causal=True, q_offset=t)
        new_cache = (c_cache, pe_cache)
    with jax.named_scope(OP_PROJ):
        x = block_residual(
            block, x,
            block_dense(block, d, "attn_out")(attn.reshape(B, T, H * vd)),
            "ln_attn_out")
    out = block_ffn(block, x, layer_in)
    return out if cache is None else (out, new_cache)


def init_cache(cfg, d_model, batch, length, dtype, window):
    """Zeroed ``(c, k_pe)``: the latent rows and the shared key lanes."""
    return (jnp.zeros((batch, length, cfg["kv_lora_rank"]), dtype),
            jnp.zeros((batch, length, cfg["qk_rope_head_dim"]), dtype))
