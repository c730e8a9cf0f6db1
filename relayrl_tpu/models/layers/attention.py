"""The operator ``"attention"``: causal softmax attention over the time axis.

``n_heads`` query heads of ``head_dim`` (``d_model // n_heads`` unless the
arch gives heads a width of their own: q and the output projection are then
``n_heads * head_dim`` wide). One fused ``qkv`` projection in the GPT-2
shaped block; with ``n_kv_heads`` (grouped-query: that many k/v heads under
the query heads) or ``head_dim`` given, separate ``q_proj`` / ``k_proj`` /
``v_proj``. ``qk_norm``: an RMSNorm over the whole projection (``True``) or
over each head's width (``"head"``) before the heads attend. Under the
trunk's ``positions: "rope"``, q and k are rotated at their absolute
positions (after QK-norm; ``rope_share`` of a head's lanes); ``attn_gate``:
``q_proj`` is twice as wide, a head's query lanes then its gate lanes, and
the attention's output is multiplied by ``sigmoid(gate)`` before the output
projection. ``attn_scale``: the scores are ``attn_scale * q . k`` in place
of ``q . k / sqrt(head_dim)`` (Granite's ``attention_multiplier``); every
backend scales by the latter, so q is multiplied by the quotient of the two
once, in the compute type (Granite's 1/64 at head_dim 64: by 0.125, exact).
The block's ``window``: query ``t`` sees keys ``t - window < s <= t`` (None:
every key up to its own).

Four backends, the arch's ``attention``: ``"dense"`` (plain softmax, the
correctness anchor), ``"blockwise"`` (online softmax over k/v blocks of
``attention_block``), ``"flash"`` (the Pallas kernels of ``ops/flash.py`` on
a TPU, blockwise or dense elsewhere) and ``"ring"`` (over the mesh ``sp``
axis, :mod:`relayrl_tpu.parallel.ring`; without such a mesh, blockwise or
dense) — :func:`resolve` picks at trace time and records what it picked.

Three modes, one parameter tree (init traces the full one):

* full (``cache=None``): ``x [B, T, d] -> [B, T, d]`` through the backend;
* cached (``cache`` = this layer's ``(k, v)`` pair ``[B, W, Hkv, hd]``, keys
  rotated before they go in; ``t`` the write index): x is one position, or a
  prefill's ``W`` from position 0; q runs against the cache's prefix, O(W) a
  step where a window recompute is O(W^2); returns ``(out, new_cache)``. A
  windowed layer's pair is a ring of ``min(window, W)`` rows
  (:func:`_ring_cached`);
* readout (``readout_idx`` set, a window path's final layer): k and v
  project over every row, the query, the output projection and the FFN run
  for the ONE row the heads read; returns ``[B, 1, d]``. A one-row query is
  computed densely: every backend computes the same causal function.
"""

from __future__ import annotations

from typing import Any, Mapping

import jax
import jax.numpy as jnp

from relayrl_tpu.models.layers.block import (
    block_dense,
    block_ffn,
    block_norm,
    block_residual,
)
from relayrl_tpu.ops.attention import (
    blockwise_attention,
    cached_attention,
    dense_attention,
)
from relayrl_tpu.ops.cache_rows import write_row
from relayrl_tpu.ops.scopes import OP_PROJ

# with a dense FFN; a final layer with experts keeps its full-window pass
# (the core's rule)
ROW_READOUT = True
# (k, v) rows at their positions; a step at t masks every row after t, and a
# windowed layer's ring every row whose position is not yet written
CACHE_RESTARTS = "masked"
# the flash kernels' q and k/v block: few large grid steps, where the
# blockwise path's ``attention_block`` is a memory knob that wants small ones
FLASH_BLOCK = 1024


def resolve(arch: Mapping[str, Any]) -> tuple[dict, dict]:
    """Arch config -> ``({"attention": attn_fn}, records)``: the
    ``[B,T,H,D]x3 (+ a layer's window) -> [B,T,H,D]`` attention callable,
    and ``Policy``'s records of what it ran as.

    ``"flash"`` and ``"ring"`` pick their implementation at trace time
    from the platform, the sequence length and the ambient mesh, so the
    SAME arch config serves CPU actor hosts and the TPU learner. That
    choice is never silent: ``attention_backends`` maps every traced
    ``(T, head_dim, dtype)`` to the backend that was compiled for it
    (``dense`` / ``blockwise`` / ``flash_pallas`` / ``ring_flash_pallas``
    / ``ring_scan``), and each new entry prints one line naming the
    platform it was resolved on. ``attention_score_area_pct`` maps the
    shapes that run the Pallas flash kernels to the share of the T x T score
    matrix those compute (``ops.flash.score_area_pct``: how far the causal
    skip engages at that shape's tiling), and ``attention_layout`` to the
    operand layout they ran in (``ops.flash.lane_layout``: ``"2 heads a
    step"`` of the projections' own ``[B, T, H * D]``, or ``"head-major"``
    where the head axis is transposed out of the lanes); the line says
    both. A windowed layer's score area and layout are recorded under ``(T,
    head_dim, dtype, window)``, beside the global layers' of the same
    shape, and its line says ``window W`` (``attention_backends`` keeps the
    three-part key: a shape runs one backend whatever the mask).
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

    def flash_or_local(q, k, v, window=None):
        # Pallas kernel on TPU; off-TPU (CPU actor hosts, CI) the same
        # arch config resolves to the lax.scan blockwise path — the
        # heterogeneous-placement rule ring attention also follows.
        from relayrl_tpu.ops import flash

        T = q.shape[1]
        if (jax.default_backend() == "tpu"
                and T % min(FLASH_BLOCK, T) == 0):
            band = window if window is not None and window < T else None
            ran(q, "flash_pallas", flash.score_area_pct(
                T, *flash.tiling(T, True, FLASH_BLOCK, FLASH_BLOCK, band),
                True, band), k,
                flash.lane_layout(q.shape[2], k.shape[2], q.shape[3]),
                window)
            return flash.flash_attention(q, k, v, causal=True,
                                         block_q=FLASH_BLOCK,
                                         block_kv=FLASH_BLOCK,
                                         window=window)
        return local(q, k, v, window)

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

    kinds = {"dense": dense, "blockwise": blockwise,
             "flash": flash_or_local, "ring": ring_or_local}
    if kind not in kinds:
        raise ValueError(f"unknown attention kind {kind!r}")
    return {"attention": kinds[kind]}, {
        "attention_backends": resolved,
        "attention_score_area_pct": score_area,
        "attention_layout": layout}


KERNELS = (resolve,)


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


def _gated(attn, gate):
    """``attn * sigmoid(gate)``, the product in float32."""
    return (attn.astype(jnp.float32) * jax.nn.sigmoid(
        gate.astype(jnp.float32))).astype(attn.dtype)


def _ring_cached(q, k, v, cache, t, window: int, n_valid):
    """A windowed layer's two cached modes -> ``(attn, new_cache)``. The
    cache is a ring: ``(k, v)`` of ``rows = min(window, W)`` flat rows
    (``init_cache``), position ``p`` in row ``p % rows`` (keys rotated at
    their absolute positions, where the layer has RoPE, before they go in).
    Softmax does not care about the order of its keys, so a row's position
    is all a step needs.

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
    k_rows, v_rows = _flat_rows(k, k_cache), _flat_rows(v, v_cache)
    if T == 1:
        k_cache = write_row(k_cache, k_rows, t % rows)
        v_cache = write_row(v_cache, v_rows, t % rows)
        attn = cached_attention(q, k_cache, v_cache, k.shape[2], q_offset=t,
                                window=window,
                                kv_positions=t - jnp.mod(t - slot, rows))
        return attn, (k_cache, v_cache)
    attn = dense_attention(q, k, v, causal=True, window=window)
    n = T if n_valid is None else n_valid
    newest = jnp.clip((n - 1) - jnp.mod(n - 1 - slot, rows), 0, T - 1)
    return attn, (jnp.take(k_rows, newest, axis=1),
                  jnp.take(v_rows, newest, axis=1))


def _flat_rows(x, cache):
    """``x [B, T, Hkv, hd]`` as the cache keeps rows: ``[B, T, Hkv * hd]``
    of its type."""
    return x.reshape(*x.shape[:2], -1).astype(cache.dtype)


def apply(block, x, cache, t, readout_idx, n_valid):
    B, T, _ = x.shape
    cfg, d, cd = block.cfg, block.d_model, block.compute_dtype
    n_heads, qk_norm = cfg["n_heads"], cfg["qk_norm"]
    theta, share = cfg["rope_theta"], cfg["rope_share"]
    fused = cfg["n_kv_heads"] is None and cfg["head_dim"] is None
    head_dim = cfg["head_dim"] or d // n_heads
    width = n_heads * head_dim          # of q and of attn_out's input
    # everything of the operator but its kernel: one part on the device
    with jax.named_scope(OP_PROJ):
        layer_in = x
        h = block_norm(block, "ln_attn")(x)
        h = h.astype(cd)
        if fused:
            n_kv = n_heads
            qkv = block_dense(block, 3 * d, "qkv")(h)
            q, k, v = jnp.split(qkv, 3, axis=-1)
        else:
            n_kv = cfg["n_kv_heads"] or n_heads
            q = block_dense(block, width * (1 + cfg["attn_gate"]),
                            "q_proj")(h)
            k = block_dense(block, n_kv * head_dim, "k_proj")(h)
            v = block_dense(block, n_kv * head_dim, "v_proj")(h)
        if qk_norm is True:
            # over the whole d_model-wide projection, before the heads
            q = block_norm(block, "q_norm", "rms")(q).astype(cd)
            k = block_norm(block, "k_norm", "rms")(k).astype(cd)
        gate = None
        if cfg["attn_gate"]:
            if fused:
                raise ValueError("attn_gate needs separate projections "
                                 "(n_kv_heads or head_dim)")
            if qk_norm is True:
                raise ValueError("attn_gate takes qk_norm false | "
                                 "\"head\"")
            # a head's query lanes, then its gate lanes
            q, gate = jnp.split(
                q.reshape(B, T, n_heads, 2 * head_dim), 2, axis=-1)
            gate = gate.reshape(B, T, width)
        q = q.reshape(B, T, n_heads, head_dim)
        k, v = (a.reshape(B, T, n_kv, head_dim) for a in (k, v))
        if qk_norm == "head":
            # over each head's head_dim, one learned scale for all heads
            q = block_norm(block, "q_norm", "rms")(q).astype(cd)
            k = block_norm(block, "k_norm", "rms")(k).astype(cd)
        elif qk_norm not in (True, False):
            raise ValueError(f"unknown qk_norm {qk_norm!r} "
                             f"(false | true | \"head\")")
        if cfg["attn_scale"] is not None:
            q = q * jnp.asarray(cfg["attn_scale"] * head_dim ** 0.5, q.dtype)
        rope = theta is not None
        if rope:
            k = apply_rope(k, 0 if t is None else t, theta, share)
    if readout_idx is not None:
        with jax.named_scope(OP_PROJ):
            q_row = jax.lax.dynamic_slice_in_dim(q, readout_idx, 1, axis=1)
            if rope:
                q_row = apply_rope(q_row, readout_idx, theta, share)
        attn = dense_attention(q_row, k, v, causal=True,
                               q_offset=readout_idx, window=block.window)
        with jax.named_scope(OP_PROJ):
            attn = attn.reshape(B, 1, width)
            if gate is not None:
                attn = _gated(attn, jax.lax.dynamic_slice_in_dim(
                    gate, readout_idx, 1, axis=1))
            row_in = jax.lax.dynamic_slice_in_dim(x, readout_idx, 1, axis=1)
            x = block_residual(block, row_in, block_dense(
                block, d, "attn_out")(attn), "ln_attn_out")
        return block_ffn(block, x, row_in)
    if rope:
        with jax.named_scope(OP_PROJ):
            q = apply_rope(q, 0 if t is None else t, theta, share)
    if cache is None:
        attn = block.fns["attention"](q, k, v, block.window)
        new_cache = None
    elif block.window is not None:
        attn, new_cache = _ring_cached(q, k, v, cache, t, block.window,
                                       n_valid)
    else:
        k_cache, v_cache = cache
        # a decode step's one row goes where it lies, also under vmap
        k_cache = write_row(k_cache, _flat_rows(k, k_cache), t)
        v_cache = write_row(v_cache, _flat_rows(v, v_cache), t)
        # Query j sits at absolute position t+j (T=1 per-step decode;
        # T=W prefill rebuilds the whole prefix in one dispatch) —
        # exactly dense_attention's offset-causal mask, over the cache's
        # flat rows (a prefill goes through dense_attention itself).
        attn = cached_attention(q, k_cache, v_cache, n_kv, q_offset=t)
        new_cache = (k_cache, v_cache)
    with jax.named_scope(OP_PROJ):
        attn = attn.reshape(B, T, width)
        if gate is not None:
            attn = _gated(attn, gate)
        x = block_residual(block, x,
                           block_dense(block, d, "attn_out")(attn),
                           "ln_attn_out")
    out = block_ffn(block, x, layer_in)
    return out if cache is None else (out, new_cache)


def init_cache(cfg, d_model, batch, length, dtype, window):
    """Zeroed ``(k, v)`` ``[B, length, Hkv * hd]``, a position's heads side
    by side in one flat row (``ops.attention.cached_attention`` says why):
    grouped-query k/v are cached as they are, and the q heads of a group
    read the same rows; of a windowed layer, a ring of ``min(window,
    length)`` rows."""
    kv = (batch, min(window or length, length),
          (cfg["n_kv_heads"] or cfg["n_heads"])
          * (cfg["head_dim"] or d_model // cfg["n_heads"]))
    return jnp.zeros(kv, dtype), jnp.zeros(kv, dtype)
