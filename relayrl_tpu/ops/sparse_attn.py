"""Attention over a set of keys that a learned indexer picks for each query
(DeepSeek sparse attention): the indexer's scores with the exact selection,
and the softmax attention over the selected set, each in one of two forms.

For a query ``t`` and the keys ``s <= t``:

* **index scores** ``I[t, s] = sum_j w[t, j] relu(qi[t, j] . ki[s]) /
  sqrt(Hi) / sqrt(Di)`` — ``Hi`` small heads of ``Di`` over ONE key head
  (:func:`index_scores`, float32 sums of the operands' products);
* **selection** ``S_t``: the ``min(t + 1, topk)`` keys of largest ``I[t,
  s]``, ties to the lower index as ``lax.top_k`` breaks them
  (:func:`top_k_mask`). Exact, without a sort: the ``topk``-th largest score
  of a row is found by bisection over the scores' bits, one compare-and-
  count a bit, and the ties at it are taken in index order. No gradient
  passes;
* **attention** of every query head over ``S_t`` alone, grouped-query
  (:func:`masked_attention`, the operations of ``ops.attention.
  dense_attention`` with the selection as one more term of its mask), and
  ``p^``, the heads' mean probability of each selected key;
* **the indexer's loss**, row by row: ``KL(p^[t, .] || softmax_{S_t} I[t,
  .])`` with ``p^`` detached (:func:`index_kl`): what the indexer learns
  from, since the selection hands it no gradient.

:func:`sparse_attention` runs a whole sequence in tiles of ``chunk``
queries, each a ``jax.checkpoint`` (the backward makes a tile's scores,
selection and probabilities again; nothing ``[T, T]`` is ever kept — keeping
the selection alone, a bool a computed pair, read 1.16 GB more for one
threshold search less, ``rehearse_compile``, PR 47; the kernels keep each
row's threshold instead, 8 B a query), in
stages of a quarter of the sequence so that a tile meets the keys up to its
stage's end and not the whole row: 62.5% of the square at 4 stages, where
the causal half is 50% (8 stages compute 56% and read 7,820 samples/s at the
benchmark's shape, but each stage is a loop body of its own shape, forward
and backward, in every layer: the compiled update was 552 MB serialized for
371 at 4, more than the chip machine's compile cache would keep, and every
run compiled anew: PERF.md section 6, PR 47). A stage that ends at or before ``topk`` selects nothing:
every causal key is kept. The tile is no part of the function.

**Two forms of the attention over the selected set, picked by what the code
can observe** (:func:`backend`; no arch key, no environment variable, no
switch):

* ``masked_pallas`` — on a TPU, for shapes that tile (heads of whole lane
  tiles, a tile of queries that is a multiple of 128, the keys in whole
  blocks of 128: :func:`relayrl_tpu.ops.sparse_attn_pallas.fits`): the Pallas
  kernels of :mod:`relayrl_tpu.ops.sparse_attn_pallas`, one call a tile, the
  selection an int8 mask operand, a tile's scores and probabilities in VMEM
  and never in HBM, the key blocks past the tile's last query skipped from
  its position, a hand-written backward (``jax.custom_vjp``: one call a
  tile, ``sparse_attn_bwd``, all three gradients from one score tile).
  ``keye-vl2-policy.update`` runs them (PERF.md section 6, PR 48: 1,503 ms an
  update of masked-dense plain XLA at 2.1% of its roofline before them).
* ``masked_xla`` (:func:`masked_attention`) — everywhere else (CPU actor
  hosts, CI, the cached step's and the readout's few rows, a tile that does
  not tile) and the reference the kernels' tests hold them to: plain XLA,
  masked-dense, a ``[Hkv, H / Hkv, Tq, Tk]`` float32 score tile through HBM,
  backward by autodiff.

**Two forms of the indexer's scores and selection, picked the same way**
(:func:`index_backend`):

* ``select_pallas`` — on a TPU, for shapes that tile (whole query blocks, the
  keys in whole blocks, index heads of half a lane tile or more:
  :func:`relayrl_tpu.ops.index_pallas.fits`): the kernels of
  :mod:`relayrl_tpu.ops.index_pallas`. A block of queries' scores stay in VMEM
  from the matmuls that make them to the selection that reads them:
  ``index_kth`` searches the rows' thresholds (the same 32-step bisection over
  :func:`_ordered_bits`' order, each step a compare and count over VMEM
  scratch), ``index_select`` makes the scores again, compares once, settles
  the ties in index order and writes ``keep`` as the int8 tile the attention's
  kernels read (and the scores where the loss wants them), ``index_bwd`` is
  the scores' backward. A tile's checkpoint keeps the thresholds by name
  (:data:`KTH_NAME`, 128 KB a layer), so the search runs once an update and
  the recompute selects in one pass. ``keye-vl2-policy.update`` runs them
  (PERF.md section 6, PR 49: 418.5 ms an update of plain XLA at 3.0% of its
  roofline before them).
* ``bisect_select`` (:func:`index_scores` + :func:`top_k_mask`) — everywhere
  else, and the reference the kernels' tests hold them to: plain XLA, the 16
  heads' terms and 32 passes over a tile's bits through HBM.

The two rules are each their own: a shape may take one form's kernels and
the other's plain XLA. The loss (:func:`index_kl`) is plain XLA in both. The
three parts carry the names ``relayrl_index``, ``relayrl_sparse_attn`` and
``relayrl_loss`` onto the device (``ops/scopes.py``), the kernels under the
first two and under no deeper ``relayrl_`` name.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from relayrl_tpu.ops.attention import _NEG_INF
from relayrl_tpu.ops.scopes import INDEX, LOSS, SPARSE_ATTN

STAGES = 4  # of a sequence: a tile computes the keys up to its stage's end
PALLAS, XLA = "masked_pallas", "masked_xla"
# the name a tile's checkpoint keeps the kernels' log-sum-exp under
LSE_NAME = "sparse_attn_lse"
# ... and the indexer's kernels' thresholds (``ops/index_pallas.py``)
KTH_NAME = "index_kth_room"
SELECT_PALLAS, SELECT_XLA = "select_pallas", "bisect_select"


def index_scores(qi, ki, w):
    """``qi [Tq, Hi, Di]``, ``ki [Tk, Di]``, ``w [Tq, Hi]`` -> ``I [Tq, Tk]``
    float32 (every pair, causal or not)."""
    _, n_heads, width = qi.shape
    s = jnp.einsum("qhd,kd->hqk", qi, ki,
                   preferred_element_type=jnp.float32)
    # the heads' weighted sum on the vector unit, in float32: a matmul here
    # would round relu(s) to the MXU's operand precision
    weighted = jax.nn.relu(s) * w.astype(jnp.float32).T[:, :, None]
    return jnp.sum(weighted, axis=0) * (n_heads ** -0.5 * width ** -0.5)


def _ordered_bits(x):
    """float32 -> uint32 in the floats' total order (``-0.0`` below
    ``+0.0``, as ``lax.top_k`` sorts)."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def top_k_mask(scores, seen, k: int):
    """Bool ``[Tq, Tk]``: of each row's ``seen`` entries the ``k`` of
    largest ``scores`` (all of them where a row has no more than ``k``),
    ties to the lower index — the set ``lax.top_k`` returns, found without a
    sort: the k-th largest value by bisection over its 32 bits (one compare
    and count over the row a bit), then the ties at it in index order."""
    # an unseen entry sorts below every float (whose ordered bits are > 0)
    bits = jnp.where(seen, _ordered_bits(scores), jnp.uint32(0))

    def one_bit(i, kth):
        trial = kth | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        enough = jnp.sum(bits >= trial[:, None], axis=-1) >= k
        return jnp.where(enough, trial, kth)

    kth = jax.lax.fori_loop(0, 32, one_bit,
                            jnp.zeros(bits.shape[0], jnp.uint32))
    above = bits > kth[:, None]
    tied = (bits == kth[:, None]) & seen
    room = k - jnp.sum(above, axis=-1, dtype=jnp.int32)
    return above | (tied & (jnp.cumsum(tied, axis=-1, dtype=jnp.int32)
                            <= room[:, None]))


def masked_attention(q, k, v, keep):
    """``q [Tq, H, D]`` over ``k, v [Tk, Hkv, D]`` (q head ``j`` reads k/v
    head ``j // (H / Hkv)``), each query attending the keys ``keep [Tq,
    Tk]`` names -> ``(out [Tq, H, D], p^ [Tq, Tk])``: the softmax attention
    and the mean over the heads of its probabilities (float32). Scores in
    float32, the probabilities rounded to ``v``'s dtype for their product, as
    ``ops.attention.dense_attention`` does; k and v are not repeated for the
    heads of a group. (The group's heads as an axis of their own, not folded
    into the query axis as ``dense_attention`` folds them: the folded form
    read 4,558 ms an update for this form's 2,097 at the benchmark's shape,
    my chip runs, PR 47.)"""
    (tq, n_heads, width), n_kv = q.shape, k.shape[1]
    q = q.reshape(tq, n_kv, n_heads // n_kv, width)
    scale = 1.0 / jnp.sqrt(width).astype(jnp.float32)
    s = jnp.einsum("qhgd,khd->hgqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(keep[None, None], s, _NEG_INF), axis=-1)
    out = jnp.einsum("hgqk,khd->qhgd", p.astype(v.dtype), v)
    return out.reshape(tq, n_heads, width), jnp.mean(p, axis=(0, 1))


def backend(tq: int, tk: int, n_heads: int, n_kv: int, width: int) -> str:
    """``"masked_pallas"`` or ``"masked_xla"``: what :func:`attention` runs
    ``tq`` queries of ``n_heads`` heads of ``width`` over ``tk`` keys of
    ``n_kv`` heads as on this process's platform. The kernels on a TPU where
    the shapes tile (``sparse_attn_pallas.fits``: heads of whole lane tiles,
    a tile of queries and the keys in whole blocks), plain XLA everywhere
    else — CPU actor hosts, CI, the cached step's and the readout's few
    rows, a tile that does not divide. Platform and shape decide, nothing
    else: no arch key, no environment variable."""
    if jax.default_backend() != "tpu":
        return XLA
    from relayrl_tpu.ops import sparse_attn_pallas

    return PALLAS if sparse_attn_pallas.fits(tq, tk, n_heads, n_kv,
                                             width) else XLA


def index_backend(tq: int, tk: int, n_heads: int, width: int) -> str:
    """``"select_pallas"`` or ``"bisect_select"``: what :func:`indexer` runs
    ``tq`` queries of ``n_heads`` index heads of ``width`` over ``tk`` keys as
    on this process's platform — :func:`backend`'s rule for the indexer: the
    kernels of :mod:`relayrl_tpu.ops.index_pallas` on a TPU where the shapes
    tile (``index_pallas.fits``), :func:`index_scores` and
    :func:`top_k_mask` everywhere else. Platform and shape decide, nothing
    else."""
    if jax.default_backend() != "tpu":
        return SELECT_XLA
    from relayrl_tpu.ops import index_pallas

    return SELECT_PALLAS if index_pallas.fits(tq, tk, n_heads,
                                              width) else SELECT_XLA


def indexer(qi, ki, w, pos, topk: int, choose: bool, want_scores: bool):
    """The indexer of queries at the positions ``pos [Tq]``, as
    :func:`index_backend` says -> ``(keep [Tq, Tk] bool or int8, scores [Tq,
    Tk] float32 | None)``: each query's seen keys, of them the ``topk`` of
    largest index score where ``choose``; the scores where something reads
    them (``choose`` or ``want_scores``)."""
    seen = pos[:, None] >= jnp.arange(ki.shape[0])[None, :]
    if not (choose or want_scores):
        return seen, None
    with jax.named_scope(INDEX):
        if index_backend(qi.shape[0], ki.shape[0], qi.shape[1],
                         qi.shape[2]) == SELECT_PALLAS:
            from relayrl_tpu.ops.index_pallas import index_select

            return index_select(qi, ki, w, pos, topk, choose, want_scores)
        scores = index_scores(qi, ki, w)
        if not choose:
            return seen, scores
        return top_k_mask(jax.lax.stop_gradient(scores), seen, topk), scores


def attention(q, k, v, keep, pos, want_p_hat: bool = True,
              defer: bool = False):
    """:func:`masked_attention` of queries at the positions ``pos [Tq]``,
    as :func:`backend` says -> ``(out, p^, owed)``; ``p^`` is None unless
    ``want_p_hat``. ``keep`` names no key after its query's position and at
    least one key a query. ``defer``: where the kernels run, the caller
    settles their backward's ``delta`` over the ``out`` it assembles
    (``owed [Tq, H]``: ``sparse_attn_pallas.settle``); ``owed`` is None
    wherever nothing is owed."""
    if backend(q.shape[0], k.shape[0], q.shape[1], k.shape[1],
               q.shape[2]) == PALLAS:
        from relayrl_tpu.ops.sparse_attn_pallas import masked_attention_pallas

        return masked_attention_pallas(q, k, v, keep, pos, want_p_hat, defer)
    out, p_hat = masked_attention(q, k, v, keep.astype(bool))
    return out, p_hat if want_p_hat else None, None


def index_kl(p_hat, scores, keep):
    """``KL(p^[t, .] || softmax over the kept keys of scores[t, .])`` a
    row, ``[Tq]`` float32 (``p^`` sums to one over the kept keys and is
    zero elsewhere; ``0 log 0 = 0``)."""
    log_pi = jax.nn.log_softmax(jnp.where(keep, scores, _NEG_INF), axis=-1)
    # a kept key's log_pi is finite; where p^ is 0 the term is 0
    return jnp.sum(jax.scipy.special.xlogy(p_hat, p_hat)
                   - p_hat * jnp.where(keep, log_pi, 0.0), axis=-1)


def _rows(q, qi, w, pos, k, v, ki, topk: int, select: bool, loss: bool,
          defer: bool):
    """:func:`sparse_rows` and what its attention owes (:func:`attention`'s
    ``owed``; None unless ``defer`` and the kernels ran)."""
    keep, scores = indexer(qi, ki, w, pos, topk, select, loss)
    with jax.named_scope(SPARSE_ATTN):
        out, p_hat, owed = attention(q, k, v, keep, pos, loss, defer)
    with jax.named_scope(LOSS):
        kl = (index_kl(jax.lax.stop_gradient(p_hat), scores,
                       keep.astype(bool)) if loss
              else jnp.zeros(q.shape[0], jnp.float32))
        return out, kl, jnp.sum(keep, axis=-1, dtype=jnp.int32), owed


def sparse_rows(q, qi, w, pos, k, v, ki, topk: int, select: bool = True,
                loss: bool = True):
    """Queries at positions ``pos [Tq]`` against the key rows ``0 .. Tk -
    1`` -> ``(out [Tq, H, D], kl [Tq], kept [Tq])``: the attention over each
    query's selected keys, its row of the indexer's loss (zeros unless
    ``loss``) and how many keys it kept. ``select`` False: the caller knows
    that no row sees more than ``topk`` keys, and every causal key is
    kept."""
    return _rows(q, qi, w, pos, k, v, ki, topk, select, loss, False)[:3]


def stages(n_rows: int, chunk: int) -> tuple[int, int]:
    """``(tile, rows a stage)`` for a sequence of ``n_rows``: tiles of
    ``chunk`` queries (one tile where ``chunk`` does not divide the
    sequence), :data:`STAGES` stages of whole tiles where that many fit."""
    if chunk <= 0 or n_rows % chunk:
        return n_rows, n_rows
    per_stage = max(1, n_rows // chunk // STAGES) * chunk
    return chunk, per_stage if n_rows % per_stage == 0 else n_rows


def computed_pairs(n_rows: int, chunk: int) -> int:
    """The (query, key) pairs :func:`sparse_attention` computes at this
    shape: every tile against the keys up to its stage's end."""
    _, per_stage = stages(n_rows, chunk)
    return sum(per_stage * end
               for end in range(per_stage, n_rows + 1, per_stage))


def kept_pairs(n_rows: int, topk: int) -> int:
    """The (query, key) pairs of one sequence after the selection: query
    ``t`` keeps ``min(t + 1, topk)`` keys."""
    first = min(n_rows, topk)   # rows that see no more keys than they keep
    return first * (first + 1) // 2 + (n_rows - first) * topk


def _sequence(q, k, v, qi, ki, w, topk, chunk, loss):
    """One sequence, ``[T, ...]`` operands: the tiles of every stage."""
    n_rows = q.shape[0]
    tile, per_stage = stages(n_rows, chunk)
    outs = []
    for start in range(0, n_rows, per_stage):
        end = start + per_stage
        # of a tile's forward its backward wants the kernels' log-sum-exp
        # alone (``sparse_attn_pallas.settle``); the plain form keeps nothing
        rows = jax.checkpoint(
            functools.partial(_rows, topk=topk, select=end > topk, loss=loss,
                              defer=True),
            policy=jax.checkpoint_policies.save_only_these_names(
                LSE_NAME, KTH_NAME))

        def one_tile(args, end=end, rows=rows):
            return rows(*args, k[:end], v[:end], ki[:end])

        tiles = tuple(a[start:end].reshape(per_stage // tile, tile,
                                           *a.shape[1:])
                      for a in (q, qi, w, jnp.arange(n_rows)))
        outs.append(jax.tree_util.tree_map(
            lambda a: a.reshape(per_stage, *a.shape[2:]),
            jax.lax.map(one_tile, tiles)))
    out, kl, kept, owed = jax.tree_util.tree_map(
        lambda *a: jnp.concatenate(a), *outs)
    if owed is not None:
        from relayrl_tpu.ops.sparse_attn_pallas import settle

        out = settle(out, owed)
    return out, kl, kept


def sparse_attention(q, k, v, qi, ki, w, topk: int, chunk: int,
                     loss: bool = True):
    """Whole sequences from position 0: ``q [B, T, H, D]``, ``k, v [B, T,
    Hkv, D]``, the indexer's ``qi [B, T, Hi, Di]``, ``ki [B, T, Di]`` and
    ``w [B, T, Hi]`` -> ``(out [B, T, H, D], kl [B, T], kept [B, T])``."""
    return jax.vmap(functools.partial(_sequence, topk=topk, chunk=chunk,
                                      loss=loss))(q, k, v, qi, ki, w)
