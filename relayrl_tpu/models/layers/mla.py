"""The operator ``"latent_attention"``: multi-head latent attention
(DeepSeek-V2's MLA, as Kimi Linear runs it) — causal softmax attention whose
keys and values are expanded from ONE compressed row a token.

``q = q_proj(u)`` as ``n_heads`` heads of ``qk_nope_head_dim +
qk_rope_head_dim``; ``[c | k_pe] = kv_a(u)`` (``kv_lora_rank`` +
``qk_rope_head_dim`` lanes); ``[k_nope | v] = kv_b(kv_a_norm(c))`` as
``n_heads`` heads of ``qk_nope_head_dim + v_head_dim`` (``kv_a_norm`` an
RMSNorm); head ``h``'s key is ``[k_nope_h | k_pe]``, the ``k_pe`` lanes the
SAME for every head; softmax of ``q_h . k_h / sqrt(qk_nope_head_dim +
qk_rope_head_dim)``; ``attn_out`` over ``n_heads * v_head_dim``. No lane is
rotated (Kimi Linear: ``mla_use_nope``), and a trunk of ``positions:
"rope"`` is refused: the rotation of the ``qk_rope_head_dim`` lanes comes
with the first configuration that runs it. q and k are one width and v
another (192 / 128): the arch's ``attention`` backends take that
(``ops/flash.py``'s ``_mla`` kernels on a TPU, blockwise or dense
elsewhere), recorded in ``Policy.attention_backends`` under the q / k width.

Three modes, one parameter tree, operator ``"attention"``'s:

* full (``cache=None``): ``x [B, T, d] -> [B, T, d]`` through the backend,
  keys and values expanded for every row;
* cached (``cache`` = this layer's ``(c, k_pe)``: the LATENT rows ``[B, W,
  kv_lora_rank]`` before their norm and the shared key lanes ``[B, W,
  qk_rope_head_dim]`` —
  576 numbers a token where the heads' keys and values would be 10,240;
  ``t`` the write index): x is one position, or a prefill's rows from
  position ``t``; every step expands the cache's rows through ``kv_b`` again
  (the weight-absorbed form, which never expands them, is ROADMAP's);
* readout (``readout_idx`` set): the latent rows over every row, the query,
  the output projection and the FFN for the ONE row the heads read.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from relayrl_tpu.models.layers.block import (
    block_dense,
    block_ffn,
    block_norm,
    block_residual,
)
from relayrl_tpu.ops.attention import dense_attention
from relayrl_tpu.ops.scopes import OP_PROJ

# as operator "attention": with a dense FFN; the core keeps a final layer
# with experts on its full-window pass
ROW_READOUT = True
# the attention entry and its records are operator "attention"'s
KERNELS = ()


def apply(block, x, cache, t, readout_idx, n_valid):
    B, T, _ = x.shape
    cfg, d, cd = block.cfg, block.d_model, block.compute_dtype
    if cfg["rope_theta"] is not None:
        raise ValueError('latent attention rotates no lane: positions '
                         '"rope" is not built for it')
    H = cfg["n_heads"]
    rank, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    pe, vd = cfg["qk_rope_head_dim"], cfg["v_head_dim"]

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
        q = block_dense(block, H * (nope + pe), "q_proj")(h).reshape(
            B, T, H, nope + pe)
        c, k_pe = jnp.split(block_dense(block, rank + pe, "kv_a")(h), [rank],
                            axis=-1)

    if readout_idx is not None:
        with jax.named_scope(OP_PROJ):
            k, v = expand(c, k_pe)
            q_row = jax.lax.dynamic_slice_in_dim(q, readout_idx, 1, axis=1)
        attn = dense_attention(q_row, k, v, causal=True,
                               q_offset=readout_idx)
        with jax.named_scope(OP_PROJ):
            row_in = jax.lax.dynamic_slice_in_dim(x, readout_idx, 1, axis=1)
            x = block_residual(block, row_in, block_dense(
                block, d, "attn_out")(attn.reshape(B, 1, H * vd)),
                "ln_attn_out")
        return block_ffn(block, x, row_in)
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
