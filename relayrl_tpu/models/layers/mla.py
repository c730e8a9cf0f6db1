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
under ``rope_interleave`` the pairing of lanes ``(2i, 2i + 1)``. Which lanes
turn, and in what pairs, is decided on the WEIGHTS' COLUMNS at use
(:class:`_ColumnsApart`: ``q_b`` — or ``q_proj`` — and ``kv_a`` are stored
as published, ``[nope | pe]`` a head, the pairs side by side; their columns
are taken apart where the products are made), so the rows come out of the
matmuls as three products: the lanes that stand, and the first and the
second of every rotated pair. The rotation reads and writes the rotary lanes
alone, element by element on the two halves, and a head's query is put
together at its full width once, ``[nope | first | second]``, on its way to
the attention. That is the half-split order and nothing is permuted back: a
dot product does not see one permutation of both its operands' lanes, so the
scores, and the ``k_pe`` rows a cache holds, are what the turn of every
row's lanes gave. ``Policy.latent_rope`` records the form a kind of layer
ran (``"columns"``), one ``[latent_rope]`` line a kind. Under any other
``positions`` no lane turns (Kimi Linear: ``mla_use_nope``), the projections
are whole and there is no record. q and k are one width and v another (192 /
128): the arch's ``attention`` backends take that (``ops/flash.py``'s
``_mla`` kernels on a TPU, blockwise or dense elsewhere), recorded in
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

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

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


def _rope_record(arch):
    """The record of what a rotary latent layer's rotation ran as:
    ``Policy.latent_rope[(operator, "experts" | "dense")] = "columns"`` and
    one ``[latent_rope]`` line a distinct kind of layer and traced shape,
    with the MB of rows a making of the layer turns beside what turning
    every row's lanes walked (q whole, in and out). A layer that rotates
    nothing says nothing."""
    resolved: dict[tuple, str] = {}
    said = set()    # one line a kind and shape

    def say(block, B: int, T: int, dtype):
        kind = ("latent_attention",
                "experts" if block.moe_experts > 0 else "dense")
        resolved[kind] = "columns"
        if (kind, B, T) in said:
            return
        said.add((kind, B, T))
        cfg, dtype = block.cfg, jnp.dtype(dtype)
        H, pe = cfg["n_heads"], cfg["qk_rope_head_dim"]
        width = cfg["qk_nope_head_dim"] + pe
        shared = B * T * pe * dtype.itemsize / 1e6      # k's lanes, MB
        whole = H * shared * width / pe                 # q at every lane
        pairs = ("pairs (2i, 2i + 1)" if cfg["rope_interleave"]
                 else "pairs (i, i + half)")
        print(f"[latent_rope] {kind[0]}+{kind[1]} (from {block.name}) T={T} "
              f"heads={H} lanes {pe} of {width} {dtype.name}, {pairs} -> "
              f"columns: a making turns {H * shared:.1f} + {shared:.1f} MB "
              f"of rows, where every row's lanes were {whole:.1f} in + "
              f"{whole:.1f} out (platform {jax.default_backend()})",
              flush=True)

    return {"latent_rope": say}, {"latent_rope": resolved}


# the attention entry and its records are operator "attention"'s
KERNELS = (_rope_record,)


def _apart(w, heads: int, stand: int, turn: int, interleave: bool):
    """The last axis of ``w``, ``heads`` heads of ``[stand | turn]`` lanes
    as published -> ``(w [.., heads * stand], w [.., heads * turn / 2], w
    [.., heads * turn / 2])``: the lanes that stand, and the lanes that turn
    as the FIRST and the SECOND of each rotated pair, pair ``i`` of a head at
    lane ``i`` of the head in both — under ``interleave`` the head's lanes
    ``(2i, 2i + 1)``, else ``(i, i + turn / 2)``. Side by side the two are
    the half-split order. Reshapes and slices, whose cotangents are the
    same: what comes back to ``w`` is in the published order."""
    lead = w.shape[:-1]
    w = w.reshape(lead + (heads, stand + turn))
    turned = w[..., stand:]
    if interleave:
        turned = turned.reshape(lead + (heads, turn // 2, 2))
        pair = turned[..., 0], turned[..., 1]
    else:
        pair = turned[..., :turn // 2], turned[..., turn // 2:]
    return tuple(a.reshape(lead + (heads * a.shape[-1],))
                 for a in (w[..., :stand], *pair))


class _ColumnsApart(nn.Module):
    """``nn.Dense``'s parameters under its names (``kernel``, ``bias``: the
    tree, the seeds and the published column order are its) and its product
    as three — ``x`` times the columns that stand, times the first and times
    the second of the pairs that turn: :func:`_apart` of the weight's
    columns, 9.4 M numbers a making of ``q_b`` where the rows' lanes were
    3.3 M x 64. Three products, not three slices of one: a slice of a row's
    lanes is a copy on the chip, and the rotation rides the last product as
    its epilogue (PERF.md section 6, PR 63)."""

    heads: int
    stand: int
    turn: int
    interleave: bool
    dtype: Any
    use_bias: bool

    @nn.compact
    def __call__(self, x):
        width = self.heads * (self.stand + self.turn)
        kernel = self.param("kernel", nn.linear.default_kernel_init,
                            (x.shape[-1], width), jnp.float32)
        bias = (self.param("bias", nn.initializers.zeros_init(), (width,),
                           jnp.float32) if self.use_bias else None)
        x, kernel, bias = nn.dtypes.promote_dtype(x, kernel, bias,
                                                  dtype=self.dtype)
        apart = (self.heads, self.stand, self.turn, self.interleave)
        out = [jax.lax.dot_general(x, w, (((x.ndim - 1,), (0,)), ((), ())))
               for w in _apart(kernel, *apart)]
        if bias is not None:
            out = [y + b for y, b in zip(out, _apart(bias, *apart))]
        return out


def _rotated(cfg, first, second, start):
    """The rotary lanes as :class:`_ColumnsApart` emits them — the first and
    the second of each pair, ``[B, L, heads, qk_rope_head_dim / 2]`` each
    (or the shared ``[B, L, qk_rope_head_dim / 2]``), row j at absolute
    position ``start + j`` -> the two turned: ``attention.apply_rope``'s
    arithmetic, angles, cosines and sines in float32, on halves that were
    never one array. Side by side they are what ``apply_rope`` returns."""
    theta, half = cfg["rope_theta"], first.shape[-1]
    with jax.named_scope(LATENT_ROPE):
        inv_freq = 1.0 / (theta ** (
            jnp.arange(0, 2 * half, 2, dtype=jnp.float32) / (2 * half)))
        pos = jnp.asarray(start, jnp.float32) + jnp.arange(
            first.shape[1], dtype=jnp.float32)
        ang = pos[:, None] * inv_freq[None, :]              # [L, half]
        if first.ndim == 4:
            ang = ang[:, None]
        cos, sin = jnp.cos(ang)[None], jnp.sin(ang)[None]
        x1, x2 = first.astype(jnp.float32), second.astype(jnp.float32)
        return ((x1 * cos - x2 * sin).astype(first.dtype),
                (x2 * cos + x1 * sin).astype(first.dtype))


def apply(block, x, cache, t, readout_idx, n_valid):
    B, T, _ = x.shape
    cfg, d, cd = block.cfg, block.d_model, block.compute_dtype
    H = cfg["n_heads"]
    rank, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    pe, vd = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    start = 0 if t is None else t
    turns = cfg["rope_theta"] is not None

    def projection(name, heads, stand):
        """``name``'s product, whole — or where lanes turn its three, the
        rotary lanes apart."""
        if not turns:
            return block_dense(block, heads * (stand + pe), name)
        return _ColumnsApart(heads, stand, pe, cfg["rope_interleave"], cd,
                             block.use_bias, name=name)

    def query(q, rows, at):
        """A head's query at its full width, ``[B, rows, H, nope + pe]``:
        the ``rows`` rows of the projection from row ``at`` on, the rotary
        lanes turned at their positions ``at + j``."""
        if rows != T:
            q = [jax.lax.dynamic_slice_in_dim(a, at, rows, axis=1)
                 for a in q]
        if not turns:
            return q[0]
        q_nope, first, second = (a.reshape(B, rows, H, -1) for a in q)
        turned = _rotated(cfg, first, second, at)
        with jax.named_scope(OP_PROJ):
            return jnp.concatenate([q_nope, *turned], axis=-1)

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
            q = projection("q_proj", H, nope)(h)
        else:
            q = projection("q_b", H, nope)(block_norm(
                block, "q_a_norm", "rms")(block_dense(
                    block, cfg["q_lora_rank"], "q_a")(h)).astype(cd))
        if not turns:
            # (the operations in the order they always had: the lowered
            # text of a layer that rotates nothing is held, tier-1)
            q = [q.reshape(B, T, H, nope + pe)]
        kv = projection("kv_a", 1, rank)(h)
        if not turns:
            c, k_pe = jnp.split(kv, [rank], axis=-1)
    if turns:
        # the new rows' shared lanes, turned once, before anything keeps
        # them: in half-split order, as a cache has always held them
        block.fns["latent_rope"](block, B, T, cd)
        c, k_pe = kv[0], jnp.concatenate(
            _rotated(cfg, kv[1], kv[2], start), axis=-1)

    if readout_idx is not None:
        with jax.named_scope(OP_PROJ):
            k, v = expand(c, k_pe)
        attn = dense_attention(query(q, 1, readout_idx), k, v,
                               causal=True, q_offset=readout_idx)
        with jax.named_scope(OP_PROJ):
            row_in = jax.lax.dynamic_slice_in_dim(x, readout_idx, 1, axis=1)
            x = block_residual(block, row_in, block_dense(
                block, d, "attn_out")(attn.reshape(B, 1, H * vd)),
                "ln_attn_out")
        return block_ffn(block, x, row_in)
    q = query(q, T, start)
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
