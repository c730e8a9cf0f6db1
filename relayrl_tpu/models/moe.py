"""Mixture-of-experts FFN with per-token top-k routing (the ``ep`` family).

No counterpart exists in the reference (its only models are 2x128 MLPs —
SURVEY.md §2.5); this is a TPU-first capacity-scaling component: the
transformer block's FFN becomes E experts whose stacked weights shard over
the mesh ``ep`` axis (rule in parallel/sharding.py), so parameter capacity
scales with devices.

Routing is **per-token top-k**: each token's router picks its own experts
from its own features alone, so routing is exactly causal and IDENTICAL
between training batches and single-window actor serving — a hard
requirement for RL policies, where logp at step t must condition only on
history (capacity-competition schemes like expert-choice or token-dropping
leak future timesteps / sibling sequences into the gate and bias the
policy gradient). The router runs in float32 over all E experts. What it
reads is the layer's to say: by default the rows the experts read (the
block's normed FFN input); arch ``moe_router_input: "layer"`` hands it the
layer's own input instead, as it arrives — before the attention and before
any norm (SmallThinker's "router placed before attention": the choice is
known before the attention runs) — while the experts still read the normed
post-attention rows. Still one token's features alone. Three weightings
(arch ``moe_router`` and ``moe_norm_topk_prob``):

* softmax, ``True`` (the default) — top-k of the logits, softmax over the
  k chosen (equal to softmax over all E, top-k, renormalised);
* softmax, ``False`` — softmax over all E, top-k, the k probabilities used
  as they are (OLMoE's ``norm_topk_prob: false``);
* ``moe_router: "sigmoid"`` — a sigmoid score per expert; the k are chosen
  on ``score + moe_expert_bias`` (arch ``moe_expert_bias``: one float32 per
  expert, seeded non-zero; it enters the choice only, so its gradient is
  exactly zero and the optimizer never moves it) and weighted by their
  UNBIASED scores, divided by their sum + 1e-6 under ``moe_norm_topk_prob``
  (LFM2-MoE's router, whose ``routed_scaling_factor`` is 1).

Whatever the weighting, arch ``moe_routed_scaling`` multiplies the k weights
(Nemotron-H's ``routed_scaling_factor`` 2.5, after the normalisation; 1, the
default, multiplies nothing).

**Held experts.** A layer may be told which experts it holds: arch
``moe_held = [first, count]`` says this device holds experts
``first .. first + count - 1`` of the ``moe_experts`` the model has — the
share of one chip among the chips that divide a layer's experts. The
router still scores and chooses over all E (and normalises over the k
chosen of all E, held or not, so that the shares of all the chips add up
to the layer), the expert stacks are ``[count, ...]``, and the layer's
output is the sum over the chosen experts that are held: a token-slot
whose expert lives elsewhere adds nothing here — no stand-in for the
absent chips' work or traffic. Unset (or naming all E), every expert is
held and the layer is the plain sparse dispatch below: a full layer has no
dead rows to leave out.

Experts are two stacks, ``moe_w_up`` / ``moe_w_down`` — GELU (the default)
or squared ReLU (arch ``ffn: "relu2"``, ``down(relu(up(x))^2)``,
Nemotron-H's) — or gated, ``moe_w_gate`` beside them: SwiGLU (``ffn:
"swiglu"``, ``down(silu(gate(x)) * up(x))``) or ReGLU (``ffn: "reglu"``,
``down(relu(gate(x)) * up(x))``), of width ``moe_d_ff``.

**A shared expert** (arch ``moe_shared_d_ff``: its width; unset, none) is one
more FFN of the experts' kind that EVERY token takes, added once beside the
routed sum with weight 1 (``moe_shared_up`` / ``moe_shared_gate`` /
``moe_shared_down``, dense matmuls under the part ``relayrl_ffn``). It lies
outside the held share: every chip of a layer computes it alike, so the
shares of the chips that divide a layer add up to the layer with the shared
expert counted ONCE (``tests/test_nemotron_reference.py``). Arch
``moe_shared_expert_gate`` gives it a gate of its own, ``sigmoid(u w_s)``
with ``w_s [d]`` (``moe_shared_expert_gate``, no bias): one scalar a token
times the shared expert's output (Qwen3-Next's, Qwen2-MoE's), in float32
under the same part, outside the held share like the expert it gates.

**Experts in a latent** (arch ``moe_latent``: the width; unset, the residual
stream's own). The routed experts then work in a space narrower than the
stream: ONE down-projection of every token before the sort (``h = u W_dn``,
``moe_latent_down``, ``d -> latent``), the experts' stacks ``[E, latent,
ff]`` / ``[E, ff, latent]``, the weighted sum (and ``moe_routed_scaling``) in
the latent, ONE up-projection after the un-sort (``moe_latent_up``, ``latent
-> d``) — neither with a bias, a norm or an activation of its own
(Nemotron-3-Super's ``moe_latent_size`` 1024 under a stream of 4096). The
router and the shared expert read the full-width rows. Both projections are
dense matmuls under the part ``relayrl_moe_latent``; the sort, the row
buffers and the custom VJP below move ``latent``-wide rows. Every chip of a
layer computes the down-projection alike and the up-projection is linear, so
the shares of the chips that divide a layer still add up to the layer.

Dispatch is **sparse** on one device: the
N·k token-slots are sorted by expert, each expert's rows go through one
grouped matmul per weight stack (group sizes = a bincount of the chosen
experts), and the down output is un-sorted and summed per token with the
router weights. Every shape is static (N·k rows whatever the imbalance),
there is no capacity and so **no token-slot is ever dropped**: an expert
that every token picks gets all N rows, an expert nobody picks gets a group
of size 0. The FFN costs k/E of running every expert on every token. Both
permutations are gathers, forward and backward (a permutation's transpose
is the gather by its inverse), because a TPU scatter-add of [N·k, d] rows
is slower than the matmuls it serves.

With held experts the layer **works on the rows it holds**. Its row
buffers — the dispatched rows, up and gate in float32, the down output —
have **R rows, not N·k**: ``R = min(N·k, round_up(margin · N·k · held / E,
512))`` (:func:`row_buffer`), what even routing would send this device times
a margin (:data:`_ROW_MARGIN`, with the readings it was set from), in whole
row tiles of the grouped matmuls. The L live rows (the held experts' group
sizes, summed) stand in the order by expert and, inside an expert, by token,
and the layer walks that order in **``ceil(L / R)`` passes**, a loop whose
trip count the update computes: pass p takes rows ``[p R, (p + 1) R)``
through ONE copy of the grouped matmuls with pass-local group sizes and adds
its tokens' shares to ``y [N, d]`` in float32. One pass while the routing
stays under the margin — a learner that trains only the experts it holds
draws the routing towards them, update by update — and as many as it takes
beyond: a router that sends this device everything takes ``ceil(N·k / R)``.
Every live slot is computed exactly once whatever the routing, the guarantee
above unchanged: no capacity, no dropped slot, the same sums; a token's held
choices may lie in two passes, each adding its share. Token → row is an
R-row gather (``tokens[token_of_row]``; in the backward the output's
cotangent by the same index, times the row's router weight at R rows).

**How the order is made and how the rows come back: two walks, one rule.**
A layer takes one of them by :func:`held_form`, a function of its static
shapes ``(N, k, held, E)`` — no arch key, no knob —, and they share the
buffers, the passes, :data:`_shared_experts` and nothing of the index
arithmetic. Both make the same order (a stable sort by expert IS the order
by expert and token), so the same rows lie in the same passes.

* **Sorted** (:func:`_held_experts`; PR 35 / 36). ONE argsort of all N·k
  slots, choice-major, the absent experts' behind the held ones, and an
  N·k-element scatter that inverts it; a pass slices its R slots out of the
  sorted order. Row → token is ONE N·k-row gather from the R-row buffer and a
  weighted sum over k, a slot without a row in this pass reading row 0 and
  SELECTED away. PR 35 measured that against an R-row scatter-add into
  ``[N, d]``: 206–320 ns an added row where a gathered row costs 26–31, at
  k 4–6 where N·k is 2–4 R — which decided the gather for the cells of
  then (``lfm2-policy``, ``smallthinker-policy``).
* **Counted** (:func:`_counted_experts`; PR 58 / 59). Nothing is sorted and
  the only N·k-sized work is element-wise over ``top_idx``. The compaction
  (:func:`_compact`, once a forward): which of a token's k choices are held —
  at most ``h = min(k, held)``, a token's experts are distinct — and per
  place ``j < h`` that choice's expert, its place among the k and its
  weight, ``[N, h]`` each; then ONE cumulative count over ``[N, held]``
  gives each held choice's row: its expert's first row plus the tokens
  before it that chose that expert. A pass finds its R rows' (expert,
  token) by compares against those running counts (:func:`_first_past`:
  two levels of 128 and one R-row gather of a block's counts, 0.06–0.23 ms a
  call where a binary search's 14 dependent gathers read 1.0–5.7 and all
  R x N compares 0.19–0.99). Row → token is ONE R-row scatter-add into
  ``[N, d]``, the rows added at their tokens: 33–140 ns an added row at R
  rows, not PR 35's 206–320 at N·k. (Two other forms were built and taken
  out. A segmented sum over the token-ordered rows lost wherever R x d is
  large: XLA writes each shifted ``[R, d]`` float32 copy out. h N-row
  gathers ``rows[row(t, j)]`` selected by ``j < count[t]`` and summed won
  alone only at N·h / R <= 4, and the one cell that took them by that rule,
  ``keye-vl2-policy`` at N·h = 4 R, read 23,900–23,981 samples/s with them
  and 24,464–24,552 with the scatter-add, x 1.024 on each of five seeds:
  PERF.md section 6, PR 59's review round.) A row's weight gradient is
  added at its token's place (``[N, h]``, an R-element scatter-add) and goes
  back to the k choices element-wise (:func:`_at_choices`): no N·k
  ``slot_to_row``.

**The rule** (:func:`held_form`): counted where ``N·k >= 16 R`` — the
N·k-scale index work is what the layer spends its time on (top-22 of 512
with 8 held: N·k = 32 R, 124 ms of dispatch round 14 ms of grouped matmuls) —
or where 8 divides k; sorted elsewhere, and wherever the buffers are N·k
rows long (one token's: nothing to save, and an eager ``init`` of 24 more
programs). PR 58 ran every held layer counted: + 11.9 %, + 12.5 % and
+ 12.2 % of ``train_samples_per_s`` in the three cells the rule now names
(``nemotron3-super-policy``, and at k = 8 ``kimi-linear-policy`` and
``keye-vl2-policy``), + 0.1 % to + 1.1 % in the four at N·k / R = 2–8 with
k = 4, 6, 10 — and the counted program's longer text cost the two smallest
(N·k / R = 2 and 4) 1.7–2.4 s of warm set-up, + 15.9 % and + 7.8 % of
``setup_s`` as the driver read them: refused. Those four keep the sorted
walk, lowered text for text as it was (``tests/test_flash_tpu_compile.py``
pins two of them). The sorted walk before PR 59 ran a held layer's slots
token-major where 8 divides k (the plain branch's order, below), the slow
N·k-row gather-and-sum: 15.1–16.7 ms a call at 16,384 tokens and k = 8
where the choice-major shapes read 3.1–10.1. It is written choice-major
alone now, and at k = 8 that was measured too, in ``keye-vl2-policy.update``
(N·k = 4 R): 21,302–21,357 samples/s token-major, 23,412–23,478
choice-major, 24,464–24,552 counted — so 8 | k stays a condition, on that
one cell's reading (PERF.md section 6, PR 58 and 59).

**No arithmetic touches a row the kernels did not write**, on either walk:
what they leave in a pass's tail — rows past its live ones — is whatever the
buffer held, and ``0 x`` that is NaN where that is NaN. A row past the live
ones, and a slot or place without a row in this pass (which reads row 0, a
live row), is SELECTED away (``where``) before it is weighted or added, in
the forward's combine, in the tokens' gradient and in the router weights';
the stacks' gradients come from kernels that select their groups' rows
themselves; and only selected values are accumulated across passes. Forward
and backward are each a loop of their own under one ``jax.custom_vjp`` a
walk: the forward keeps the ``[N, d]`` tokens, the router weights, the
walk's index vectors and the stacks; the backward loop makes a pass's R-row
buffers again from the tokens and runs that pass's transpose, accumulating
the stacks' gradients, the tokens' and the router weights'. Differentiating
THROUGH the loop (or through a ``lax.cond`` that picked a buffer size) would
make every buffer of every pass a residual of the forward, written in full —
zero-filled — for the passes not taken: the N·k-row buffers this form exists
to avoid. A pass's experts, and their transpose, are each one inner
``jax.jit`` (:data:`_shared_experts`, :func:`_shared_experts_vjp`): the held
layers of a trunk share ONE trace and ONE lowering of the kernels — set-up
time, not speed: the compiled update inlines the calls, twelve Mosaic calls
a layer as before. Set-up is also why nothing stands between the layer's
call and those kernels that need not (ROADMAP 1.5: a warm ``build`` moves by
seconds with a frame more above a lowering): the rule is a plain call that
returns before anything is traced, and the policy's record of what each
layer shape ran as — one ``[moe]`` line a distinct shape, e.g. ``[moe]
slots=360448 rows=11264 held=8/512 k=22 -> counted``
(:func:`dispatch_form`, ``Policy.moe_backends``) — is written by the block
before it calls the layer (``layers/block.py``), not from inside; the layer
and the record take the branch from ONE resolution (:func:`layer_form`).
The layer sows ``row_passes`` (the trips its forward loop counted as it ran),
``row_buffer`` (R) and ``sorted_slots`` (the slots it put in expert order:
passes x R counted, N·k sorted and where every expert is held) beside
``expert_load``; the update reports ``moe_row_passes`` and
``moe_sorted_slots``.

The **dense** path — every expert on every token, a dense ``[N, E]`` weight
mask in the combine einsum, E/k times the FLOPs — is what GSPMD partitions:
each ``ep`` shard computes its own experts and the combine contracts over E
with a psum. The sparse path is a single-device program: its grouped
matmuls are Pallas calls, which GSPMD does not partition, so under an
``ep`` mesh every chip would have to hold every expert stack and ``ep``
would scale nothing (not measured: no multi-chip cell yet). The layer
therefore picks by what it can observe where it is traced: dense under an
ambient mesh (``parallel/context.py``) whose ``ep`` axis is larger than 1,
sparse everywhere else. The multi-chip follow-up (ROADMAP 2.7) is the
expert exchange under ``shard_map``: each chip running the held-experts
layer on its ``moe_held`` range — the piece that exists now — between an
all-to-all that brings it the token-slots of its experts and one that
takes the results back. Arch ``moe_dispatch`` (``"sparse"`` |
``"dense"``) overrides the pick for ``tests/test_moe.py``, which compares
the two paths (forward and every gradient); ``"sparse"`` under an ``ep``
mesh is refused. No benchmark cell takes the dense path.

No auxiliary load-balancing loss is applied (see
:func:`expert_utilization` for the rationale and the monitoring hook for
the gate-collapse failure mode that omission leaves open).

Shapes: tokens flatten to ``[N = B*T, d]``; expert stacks are
``moe_w_up`` / ``moe_w_gate [E, d, ff]`` and ``moe_w_down [E, ff, d]``
(``E`` = the held count where ``moe_held`` is set; ``d`` = ``moe_latent``
where that is set).
"""

from __future__ import annotations

import functools
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from relayrl_tpu.models.mlp import GATED_FFN, UNGATED_FFN
from relayrl_tpu.ops.scopes import (
    FFN,
    HELD_EXPERTS_NAME,
    MOE_ELEMENTWISE,
    MOE_LATENT,
    MOE_ROUTE,
    MOE_ROWS,
)

# What a checkpoint round the whole layer (the trunk's ``block_checkpoint``)
# keeps of an expert FFN by name, dearest first by device time a byte: the
# router's choice — its float32 logits and the experts a sigmoid router
# chose, so that no backward runs the score matmul or ``lax.top_k`` (a full
# sort of a token's scores on a TPU) a second time —, the shared expert's first products and
# the rows of the latent the routed experts read. Marks that lower to
# nothing under any other policy: nothing in this file lists them.
ROUTER_LOGITS = "relayrl_moe_logits"
ROUTER_CHOICE = "relayrl_moe_choice"
SHARED_UP = "relayrl_moe_shared_up"
SHARED_GATE = "relayrl_moe_shared_gate"
LATENT_ROWS = "relayrl_moe_latent_rows"
BLOCK_KEPT = (ROUTER_LOGITS, ROUTER_CHOICE, SHARED_UP, SHARED_GATE,
              LATENT_ROWS)

# Spread of the seeded ``moe_expert_bias`` (sigmoid router): against scores
# whose 4th and 5th lie ~0.02 apart it moves the choice of about every
# second token, and an expert's load by about a quarter.
_EXPERT_BIAS_STD = 0.02


def route(logits, k: int, norm_topk_prob: bool, router: str = "softmax",
          expert_bias=None):
    """Router logits ``[N, E]`` (float32) -> (weights ``[N, k]``, expert
    indices ``[N, k]``); a token's row depends on that token alone. The
    sigmoid router's choice carries a name (:data:`BLOCK_KEPT`): a checkpoint
    that lists it sorts once (a softmax router's weights are made from
    ``top_k``'s values too, and no trunk under ``block_checkpoint`` has one:
    nothing is named there)."""
    if router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        biased = scores if expert_bias is None else scores + expert_bias
        top_idx = checkpoint_name(jax.lax.top_k(biased, k)[1], ROUTER_CHOICE)
        top_w = jnp.take_along_axis(scores, top_idx, axis=-1)
        if norm_topk_prob:
            top_w = top_w / (top_w.sum(axis=-1, keepdims=True) + 1e-6)
        return top_w, top_idx
    if router != "softmax":
        raise ValueError(f"unknown moe_router {router!r} (softmax | sigmoid)")
    if norm_topk_prob:
        top_vals, top_idx = jax.lax.top_k(logits, k)
        return jax.nn.softmax(top_vals, axis=-1), top_idx
    return jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)


def _slots_3d(rows, n: int, choice_major: bool):
    """Slot-ordered rows ``[N*k, d]`` as ``[N, k, d]`` (slot = token * k +
    choice) or, ``choice_major``, ``[k, N, d]`` (slot = choice * N +
    token): a split of the leading axis either way."""
    lead = (-1, n) if choice_major else (n, -1)
    return rows.reshape(lead + rows.shape[-1:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch_rows(tokens, token_of_row, slot_to_row, choice_major=False):
    """``tokens[token_of_row]``: each token's row once per chosen expert,
    in expert order. Its transpose is a gather too: cotangent rows back in
    slot order (``slot_to_row``), the k of each token summed."""
    return _dispatch_fwd(tokens, token_of_row, slot_to_row, choice_major)[0]


def _dispatch_fwd(tokens, token_of_row, slot_to_row, choice_major=False):
    out = jnp.take(tokens, token_of_row, axis=0)
    return out, (slot_to_row, tokens.shape[0])


def _dispatch_bwd(choice_major, res, g):
    slot_to_row, n = res
    g = jnp.take(g, slot_to_row, axis=0)
    g = _slots_3d(g, n, choice_major).sum(axis=0 if choice_major else 1)
    return g, None, None


_dispatch_rows.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _unsort_rows(rows, slot_to_row, row_to_slot):
    """``rows[slot_to_row]``: expert-ordered rows back in slot order
    (token-major, k to a token); transpose = the gather by the inverse."""
    return _unsort_fwd(rows, slot_to_row, row_to_slot)[0]


def _unsort_fwd(rows, slot_to_row, row_to_slot):
    return jnp.take(rows, slot_to_row, axis=0), row_to_slot


def _unsort_bwd(row_to_slot, g):
    return jnp.take(g, row_to_slot, axis=0), None, None


_unsort_rows.defvjp(_unsort_fwd, _unsort_bwd)


def grouped_matmul(lhs, rhs, group_sizes):
    """``[m, k] x [E, k, n] -> [m, n]`` in ``lhs``'s dtype, float32
    accumulation: row block i of ``lhs`` (``group_sizes[i]`` rows) times
    ``rhs[i]``. On a TPU, where the shapes tile, the Pallas kernels of
    ``ops/grouped_matmul.py``; everywhere else (CPU actor hosts, CI, a
    handful of decode rows) XLA's ``ragged_dot`` — the same rule as
    ``attention: "flash"``."""
    if jax.default_backend() == "tpu":
        from relayrl_tpu.ops import grouped_matmul as kernels

        if kernels.fits(lhs.shape[0], rhs.shape[1], rhs.shape[2]):
            return kernels.gmm(lhs, rhs, group_sizes)
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=lhs.dtype)


def _activation(ffn: str, up, gate=None):
    """The experts' inner activation: ``act(gate) * up`` where the FFN is
    gated (``gate`` given), ``act(up)`` otherwise."""
    return (UNGATED_FFN[ffn](up) if gate is None
            else GATED_FFN[ffn](gate) * up)


def _experts(ffn, xs, stacks, group_sizes):
    """Expert-ordered rows ``xs [m, d]`` through their experts' FFN:
    ``stacks = (w_up, w_gate | None, w_down)``, float32 between the
    grouped matmuls. Rows past ``group_sizes.sum()`` are never written."""
    w_up, w_gate, w_down = stacks

    def up_proj(w):
        return grouped_matmul(xs, w, group_sizes).astype(jnp.float32)

    h = _activation(ffn, up_proj(w_up),
                    None if w_gate is None else up_proj(w_gate))
    return grouped_matmul(h.astype(xs.dtype), w_down, group_sizes)


# -- the held-experts layer: R-row buffers walked in ceil(live / R) passes ---

# Rows of a held layer's buffers over the rows even routing would send it
# (N*k * held / E). A learner that trains only the experts it holds draws the
# routing towards them: read on the chip every update over 20 s windows of the
# benchmark's two held cells (PERF.md section 6, PR 36), each layer's live
# rows stay at 0.76-1.28 of the even share for 40 updates (8 of 64 held) or
# 25 (16 of 64), pass 1.25 after 40-82 / 36-44 and stand at 1.4-2.3 / 1.5-1.9
# when the window ends after 90 / 55. At 2 a layer takes one pass through
# such a window on most seeds and a second for its last updates on the rest;
# against 1.25 the larger buffers cost 4 ms an update at one pass where a
# second walk cost 7 a layer (8 of 64 held; 13 where one cost 21, 16 of 64).
_ROW_MARGIN = 2.0
# ... rounded up to the grouped matmul kernels' row tile
# (``ops.grouped_matmul.TILING[0]``; not imported: module docstring there)
_ROW_TILE = 512


def row_buffer(n_slots: int, n_held: int, n_exp: int) -> int:
    """Rows of a held-experts layer's buffers: what the layer can observe
    (its slots, the share of the experts it holds) says how many."""
    rows = math.ceil(_ROW_MARGIN * n_slots * n_held / n_exp / _ROW_TILE)
    return min(n_slots, rows * _ROW_TILE)


# slots a buffer row (N*k / R) from which a held layer counts its rows
_COUNTED_SLOTS_A_ROW = 16


def held_form(n: int, k: int, n_held: int, n_exp: int) -> str:
    """Which of its two walks a held-experts layer of these static shapes
    takes (module docstring): ``"counted"`` or ``"sorted"``. Sorted where
    the buffers have a row a slot (``R = N*k``: a decode step, the one token
    a model's parameters are made at) — counting saves nothing there and is
    24 more eager programs an ``init``. Else counted

    * where 8 divides k: ``keye-vl2-policy.update`` (k = 8, N*k = 4 R)
      reads 24,464-24,552 samples/s counted against 23,412-23,478 sorted
      (and 21,302-21,357 on the token-major sorted walk it had before
      PR 59) — the one cell behind this condition: at k = 4, 6 and 10 and
      N*k / R = 2-8 the two walks draw, and ``kimi-linear-policy`` (k = 8)
      counts by the next condition too;
    * where ``N*k >= 16 R``: the N*k-scale index work — the argsort, the
      scatter that inverts it, the N*k-row gather — is what the layer
      spends its time on (top-22 of 512 with 8 held: N*k = 32 R).

    Elsewhere the two are a draw on the chip (+ 0.1 % to + 1.1 % in the four
    cells at N*k / R = 2-8) and the counted walk's longer program costs
    1.7-2.4 s of a warm set-up: sorted (PERF.md section 6, PR 58 and 59).
    Every reading behind the rule is at N = 16,384 tokens an update: the
    counted walk's search (:func:`_first_past`) compares R x (held * N / 128
    + 128) a pass, which with R growing as N is quadratic in the tokens
    (0.06-0.23 ms a call here), where the sort is N*k log N*k — an update of
    64k tokens or more reads the rule again before it trusts it."""
    rows = row_buffer(n * k, n_held, n_exp)
    if rows == n * k:
        return "sorted"
    counted = k % 8 == 0 or n * k >= _COUNTED_SLOTS_A_ROW * rows
    return "counted" if counted else "sorted"


class _Form(NamedTuple):
    """What a layer runs as (:func:`layer_form`): ``form`` — ``"dense"``,
    ``"plain"`` (the sparse dispatch, every expert held: one sort of all
    N*k slots), ``"sorted"`` or ``"counted"`` (held experts:
    :func:`held_form`); ``k`` of the experts there are; the experts held,
    ``first`` and ``n_held``; ``rows`` of the sparse dispatch's buffers (0
    dense)."""
    form: str
    k: int
    first: int
    n_held: int
    rows: int


def layer_form(n: int, top_k: int, n_exp: int, held, dispatch) -> _Form:
    """A layer's branch from its static shapes and the ambient mesh, ONE
    resolution for the layer itself (:meth:`MoEMLP.__call__`) and for the
    policy's record of it (:func:`dispatch_form`). The arguments are a
    block's own: N tokens, arch ``moe_top_k``, ``moe_experts``, ``moe_held``
    (None: all) and ``moe_dispatch`` (None: by the mesh)."""
    k = max(1, min(top_k, n_exp))
    first, n_held = held or (0, n_exp)
    if not (0 <= first and 0 < n_held and first + n_held <= n_exp):
        raise ValueError(f"moe_held {held} outside 0..{n_exp}")
    dispatch = _dispatch_of(dispatch)
    if dispatch == "dense":
        return _Form("dense", k, first, n_held, 0)
    if dispatch != "sparse":
        raise ValueError(f"unknown moe_dispatch {dispatch!r}")
    if _mesh_ep() > 1:
        raise ValueError(
            f"moe_dispatch 'sparse' under a mesh with ep={_mesh_ep()}: the "
            f"sparse dispatch is a single-device program (GSPMD does not "
            f"partition its Pallas calls over ep); leave moe_dispatch unset "
            f"and the layer takes the dense path, which GSPMD does "
            f"partition")
    if n_held == n_exp:
        return _Form("plain", k, first, n_held, n * k)
    return _Form(held_form(n, k, n_held, n_exp), k, first, n_held,
                 row_buffer(n * k, n_held, n_exp))


def dispatch_form(n: int, top_k: int, n_exp: int, held,
                  dispatch) -> tuple | None:
    """:func:`layer_form` as the policy records it (``Policy.moe_backends``,
    one ``[moe]`` line a distinct shape): ``(the record's key, the form, the
    shapes as the line says them)``; None for the dense dispatch, which
    walks no slots. ``layers/block.block_ffn`` asks before it calls the
    layer."""
    form, k, _, n_held, rows = layer_form(n, top_k, n_exp, held, dispatch)
    if form == "dense":
        return None
    return ((n * k, rows, n_held, n_exp, k), form,
            f"slots={n * k} rows={rows} held={n_held}/{n_exp} k={k}")


def _passes(live, rows: int):
    """Passes that cover ``live`` rows: ``ceil(live / rows)``."""
    return (live + (rows - 1)) // rows


def _pass_of(p, rows, load, row_to_slot, slot_to_row):
    """Pass ``p``'s share of the sorted order, rows ``[p R, (p + 1) R)``.
    From the rows' side: the slot of each row, the rows that are live and
    the pass-local group sizes. From the tokens' side: each slot's row in
    this pass's buffers and whether it has one (a slot of an absent expert,
    of another pass or of no pass points at row 0 and has none)."""
    slots = jax.lax.dynamic_slice(row_to_slot, (p * rows,), (rows,))
    ends = jnp.clip(jnp.cumsum(load) - p * rows, 0, rows)
    live = jnp.arange(rows) < ends[-1]
    row_of_slot = slot_to_row - p * rows
    has_row = (row_of_slot >= 0) & (row_of_slot < ends[-1])
    return (slots, live, jnp.diff(ends, prepend=0),
            jnp.where(has_row, row_of_slot, 0), has_row)


def _rows_of(tokens, slots):
    """``tokens [N, .]`` gathered to the rows of ``slots`` (R rows; slot =
    choice * N + token: the sorted walk's slots run choice-major)."""
    return tokens.at[slots % tokens.shape[0]].get(mode="promise_in_bounds")


def _rows_to_tokens(rows, row_of_slot, has_row, n, slot_weights=None):
    """Each token's sum over its k slots of the slot's row (times
    ``slot_weights`` where given), float32 ``[N, d]``: ONE N*k-row gather
    and a sum over k. A slot without a row in this pass reads row 0 and is
    SELECTED away — never multiplied by a zero weight: ``0 x`` whatever a
    row holds is NaN where that is NaN. The sum is written out choice by
    choice: as a reduction XLA first writes the gathered rows again in
    float32."""
    got = rows.at[row_of_slot].get(mode="promise_in_bounds")

    def choice(a, j):  # slot-ordered [N*k, .] -> choice j's [N, .]
        return jnp.take(_slots_3d(a, n, True), j, axis=0)

    def share(j):
        row = choice(got, j).astype(jnp.float32)
        if slot_weights is not None:
            row = choice(slot_weights[:, None], j) * row
        return jnp.where(choice(has_row[:, None], j), row, 0)

    return sum(share(j) for j in range(has_row.shape[0] // n))


# One trace and one lowering of the experts (and of their transpose) for
# all the held layers of a trunk that share its shapes: a layer calls them.
_shared_experts = jax.jit(_experts, static_argnums=0)


@functools.partial(jax.jit, static_argnums=0)
def _shared_experts_vjp(ffn, xs, stacks, group_sizes, d_out):
    """``(out, d xs, d stacks)`` of :func:`_experts` at ``d_out``."""
    # a vjp's name transforms wrap the first scope entered under them: this
    # one, so that the kernels keep the names a device trace finds them by
    # (ops/grouped_matmul.py)
    def experts(xs, stacks):
        with jax.named_scope(HELD_EXPERTS_NAME):
            return _experts(ffn, xs, stacks, group_sizes)

    out, transpose = jax.vjp(experts, xs, stacks)
    return (out, *transpose(d_out))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _held_experts(ffn, rows, tokens, top_w, stacks, load, row_to_slot,
                  slot_to_row):
    """The held-experts layer on the rows it holds, the SORTED walk (module
    docstring): ``(y [N, d] float32, the passes the loop ran)``.
    ``row_to_slot``: the choice-major slots sorted by held expert, absent
    experts' behind, padded to a whole number of ``rows``-row passes;
    ``slot_to_row``: its inverse."""
    return _held_fwd(ffn, rows, tokens, top_w, stacks, load, row_to_slot,
                     slot_to_row)[0]


def _held_fwd(ffn, rows, tokens, top_w, stacks, load, row_to_slot,
              slot_to_row):
    n, k = top_w.shape
    top_w_flat = top_w.T.reshape(-1)

    def one_pass(p, carry):
        y, ran = carry
        with jax.named_scope(MOE_ROUTE):
            slots, _, sizes, row_of_slot, has_row = _pass_of(
                p, rows, load, row_to_slot, slot_to_row)
        with jax.named_scope(MOE_ROWS):
            xs = _rows_of(tokens, slots)
        with jax.named_scope(MOE_ELEMENTWISE):  # round the kernels' names
            out = _shared_experts(ffn, xs, stacks, sizes)
        with jax.named_scope(MOE_ROWS):
            y = y + _rows_to_tokens(out, row_of_slot, has_row, n,
                                    top_w_flat)
        return y, ran + 1

    out = jax.lax.fori_loop(
        0, _passes(load.sum(), rows), one_pass,
        (jnp.zeros(tokens.shape, jnp.float32), jnp.int32(0)))
    return out, (tokens, top_w, stacks, load, row_to_slot, slot_to_row)


def _held_bwd(ffn, rows, res, g):
    """One loop of its own: pass p's R-row buffers again from the [N, d]
    tokens, then that pass's transpose. Differentiating THROUGH a loop (or
    a ``cond``) would make every buffer of every pass a residual of the
    forward, zero-filled where a pass did not run."""
    tokens, top_w, stacks, load, row_to_slot, slot_to_row = res
    g_y, _ = g  # the trip count is an integer: nothing comes back for it
    n, k = top_w.shape
    top_w_flat = top_w.T.reshape(-1)

    def one_pass(p, carry):
        d_tokens, d_weights, d_stacks = carry
        with jax.named_scope(MOE_ROUTE):
            slots, live, sizes, row_of_slot, has_row = _pass_of(
                p, rows, load, row_to_slot, slot_to_row)
        with jax.named_scope(MOE_ROWS):
            g_rows = _rows_of(g_y, slots)
            weights = top_w_flat.at[slots].get(mode="promise_in_bounds")
            xs = _rows_of(tokens, slots)
        with jax.named_scope(MOE_ELEMENTWISE):  # round the kernels' names
            d_out = (g_rows * weights[:, None]).astype(tokens.dtype)
            out, d_xs, d_pass = _shared_experts_vjp(ffn, xs, stacks, sizes,
                                                    d_out)
            # the tail's rows were never written: selected away, not masked
            d_w = jnp.where(live,
                            (out.astype(jnp.float32) * g_rows).sum(-1), 0)
        with jax.named_scope(MOE_ROWS):
            d_tokens = d_tokens + _rows_to_tokens(d_xs, row_of_slot, has_row,
                                                  n)
        with jax.named_scope(MOE_ELEMENTWISE):
            return (d_tokens,
                    jax.lax.dynamic_update_slice(d_weights, d_w,
                                                 (p * rows,)),
                    jax.tree_util.tree_map(jnp.add, d_stacks, d_pass))

    d_tokens, d_weights, d_stacks = jax.lax.fori_loop(
        0, _passes(load.sum(), rows), one_pass,
        (jnp.zeros(tokens.shape, jnp.float32),
         jnp.zeros(row_to_slot.shape, jnp.float32),
         jax.tree_util.tree_map(jnp.zeros_like, stacks)))
    with jax.named_scope(MOE_ROWS):
        # each slot's weight gradient is its row's (0 for a slot without one)
        d_top_w = d_weights.at[slot_to_row].get(mode="promise_in_bounds")
        d_top_w = d_top_w.reshape(k, n).T
    with jax.named_scope(MOE_ELEMENTWISE):
        return (d_tokens.astype(tokens.dtype), d_top_w.astype(top_w.dtype),
                d_stacks, None, None, None)


_held_experts.defvjp(_held_fwd, _held_bwd)


# -- the same layer, its rows COUNTED into expert order: nothing is sorted ----

class _Held(NamedTuple):
    """A forward's held choices (:func:`_compact`): ``count [N]``, a token's
    held choices, at most ``h = min(k, held)`` (its experts are distinct);
    ``[N, h]`` each, in the order of the k choices, the j-th held choice's
    local ``expert`` (-1 at the places past ``count``), its place among the
    k (``choice``) and its router ``weight``; ``running [held * N]``,
    expert-major: the rows, in the order by expert and, inside an expert, by
    token, up to and with (expert e, token t) — row r belongs to the first
    (e, t) whose count passes it; ``load [held]``."""
    count: Any
    expert: Any
    choice: Any
    weight: Any
    running: Any
    load: Any


def _compact(top_idx, top_w, held) -> _Held:
    """The held choices, element-wise over ``top_idx [N, k]`` (the only
    N*k-sized work of a held layer) and then by COUNTING — no sort: a held
    choice's row in the expert order is its expert's first row plus the
    tokens before this one that chose that expert."""
    first, n_held = held
    k = top_idx.shape[1]
    h = min(k, n_held)
    # choice-major [k, N]: the tokens in the lanes
    local = top_idx.T - first
    is_held = (local >= 0) & (local < n_held)
    seen = jnp.cumsum(is_held, axis=0, dtype=jnp.int32)  # up to and with c
    # [h, k, N]: choice c is the token's j-th held one (one c a place, or
    # none); written out as a loop over c and j it is k * h selects a table
    # and doubles the time an update takes to lower (PERF.md section 6)
    at = is_held & (seen == jnp.arange(1, h + 1)[:, None, None])

    def placed(per_choice, empty=0):  # [k, .] -> [N, h]
        return (jnp.where(at, per_choice - empty, 0).sum(1) + empty).T

    expert = placed(local, -1)
    # [N, held]: the tokens up to and with t that chose expert e ...
    chose = expert[..., None] == jnp.arange(n_held)
    upto = jnp.cumsum(chose.any(1), axis=0, dtype=jnp.int32)
    load = upto[-1]
    upto = upto + (jnp.cumsum(load) - load)      # ... and the experts < e
    return _Held(seen[-1], expert, placed(jnp.arange(k)[:, None]),
                 placed(top_w.T), upto.T.reshape(-1), load)


def _at_choices(per_held, count, choice, k: int):
    """``per_held [N, h]`` back at the k choices: ``[N, k]`` holding
    ``per_held[t, j]`` at ``choice[t, j]`` for ``j < count[t]`` and 0 at
    the choices that are not held."""
    here = ((jnp.arange(choice.shape[1]) < count[:, None])[..., None]
            & (choice[..., None] == jnp.arange(k)))              # [N, h, k]
    return jnp.where(here, per_held[..., None], 0).sum(1)


# entries a block of the search by compares (:func:`_first_past`)
_SEARCH_BLOCK = 128


def _first_past(running, at):
    """For each of ``at [R]`` the first index whose ``running`` count (non-
    decreasing) is larger — ``len(running)`` where none is. By compares, in
    two levels — the block of :data:`_SEARCH_BLOCK` entries, then the entry
    in it: R x (len / 128 + 128) compares and ONE R-row gather of a block's
    counts, where a binary search is ``log2 len`` dependent R-element
    gathers, 6-10 x the time (PERF.md section 6, PR 58)."""
    n = running.shape[0]
    blocks = -(-n // _SEARCH_BLOCK)
    counts = jnp.pad(running, (0, blocks * _SEARCH_BLOCK - n),
                     mode="edge").reshape(blocks, _SEARCH_BLOCK)
    block = (counts[:, -1][None, :] <= at[:, None]).sum(1, dtype=jnp.int32)
    mine = counts.at[jnp.minimum(block, blocks - 1)].get(
        mode="promise_in_bounds")                                # [R, 128]
    found = block * _SEARCH_BLOCK + (mine <= at[:, None]).sum(
        1, dtype=jnp.int32)
    return jnp.minimum(found, n)


class _Pass(NamedTuple):
    """A counted pass's R rows (:func:`_counted_pass`): each row's ``token``,
    its ``place`` among that token's held choices, its router ``weight`` and
    whether it is ``live`` (a row past the live ones points at token 0,
    place 0, weight 0); ``sizes [held]``, the pass-local groups."""
    token: Any
    place: Any
    weight: Any
    live: Any
    sizes: Any


def _counted_pass(p, rows: int, held: _Held) -> _Pass:
    """Pass ``p``'s share of the expert order, rows ``[p R, (p + 1) R)``."""
    n, h = held.expert.shape
    row = p * rows + jnp.arange(rows, dtype=jnp.int32)
    live = row < held.running[-1]
    at = _first_past(held.running, row)             # expert * N + token
    token = jnp.where(live, at % n, 0)
    mine = held.expert.at[token].get(
        mode="promise_in_bounds") == (at // n)[:, None]
    weight = held.weight.at[token].get(mode="promise_in_bounds")
    ends = jnp.clip(jnp.cumsum(held.load) - p * rows, 0, rows)
    return _Pass(token, jnp.where(mine, jnp.arange(h), 0).sum(1),
                 jnp.where(mine, weight, 0).sum(1), live,
                 jnp.diff(ends, prepend=0))


def _counted_to_tokens(y, rows, ix: _Pass, weighted):
    """``y [N, d]`` float32 plus each token's sum over its live rows of this
    pass (times their router weights where ``weighted``): the R rows added
    at their tokens. A row past the live ones — whatever the kernels left
    there — is SELECTED away, never multiplied by a zero weight: ``0 x`` is
    NaN where that is NaN."""
    x = rows.astype(jnp.float32)
    if weighted:
        x = x * ix.weight[:, None]
    return y.at[ix.token].add(jnp.where(ix.live[:, None], x, 0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _counted_experts(ffn, rows, held, tokens, top_w, top_idx, stacks):
    """The held-experts layer on the rows it holds, the COUNTED walk
    (module docstring): ``(y [N, d] float32, the passes the loop ran)``.
    ``held``: ``(first, count)`` of the experts ``top_idx`` chooses among."""
    return _counted_fwd(ffn, rows, held, tokens, top_w, top_idx, stacks)[0]


def _counted_fwd(ffn, rows, held, tokens, top_w, top_idx, stacks):
    with jax.named_scope(MOE_ROUTE):
        mine = _compact(top_idx, top_w, held)

    def one_pass(p, carry):
        y, ran = carry
        with jax.named_scope(MOE_ROUTE):
            ix = _counted_pass(p, rows, mine)
        with jax.named_scope(MOE_ROWS):
            xs = tokens.at[ix.token].get(mode="promise_in_bounds")
        with jax.named_scope(MOE_ELEMENTWISE):  # round the kernels' names
            out = _shared_experts(ffn, xs, stacks, ix.sizes)
        with jax.named_scope(MOE_ROWS):
            y = _counted_to_tokens(y, out, ix, True)
        return y, ran + 1

    out = jax.lax.fori_loop(
        0, _passes(mine.running[-1], rows), one_pass,
        (jnp.zeros(tokens.shape, jnp.float32), jnp.int32(0)))
    return out, (tokens, stacks, top_idx, mine)


def _counted_bwd(ffn, rows, held, res, g):
    """One loop of its own: pass p's R-row buffers again from the [N, d]
    tokens, then that pass's transpose. Differentiating THROUGH a loop (or
    a ``cond``) would make every buffer of every pass a residual of the
    forward, zero-filled where a pass did not run."""
    tokens, stacks, top_idx, mine = res
    g_y, _ = g  # the trip count is an integer: nothing comes back for it

    def one_pass(p, carry):
        d_tokens, d_weight, d_stacks = carry
        with jax.named_scope(MOE_ROUTE):
            ix = _counted_pass(p, rows, mine)
        with jax.named_scope(MOE_ROWS):
            g_rows = g_y.at[ix.token].get(mode="promise_in_bounds")
            xs = tokens.at[ix.token].get(mode="promise_in_bounds")
        with jax.named_scope(MOE_ELEMENTWISE):  # round the kernels' names
            d_out = (g_rows * ix.weight[:, None]).astype(tokens.dtype)
            out, d_xs, d_pass = _shared_experts_vjp(ffn, xs, stacks,
                                                    ix.sizes, d_out)
            # the tail's rows were never written: selected away, not masked
            d_w = jnp.where(ix.live,
                            (out.astype(jnp.float32) * g_rows).sum(-1), 0)
        with jax.named_scope(MOE_ROWS):
            d_tokens = _counted_to_tokens(d_tokens, d_xs, ix, False)
            # a row's weight gradient, at its token's place for it
            d_weight = d_weight.at[ix.token, ix.place].add(d_w)
        with jax.named_scope(MOE_ELEMENTWISE):
            return (d_tokens, d_weight,
                    jax.tree_util.tree_map(jnp.add, d_stacks, d_pass))

    d_tokens, d_weight, d_stacks = jax.lax.fori_loop(
        0, _passes(mine.running[-1], rows), one_pass,
        (jnp.zeros(tokens.shape, jnp.float32),
         jnp.zeros(mine.weight.shape, jnp.float32),
         jax.tree_util.tree_map(jnp.zeros_like, stacks)))
    with jax.named_scope(MOE_ELEMENTWISE):
        d_top_w = _at_choices(d_weight, mine.count, mine.choice,
                              top_idx.shape[1])
        return (d_tokens.astype(tokens.dtype),
                d_top_w.astype(mine.weight.dtype), None, d_stacks)


_counted_experts.defvjp(_counted_fwd, _counted_bwd)


def _mesh_ep() -> int:
    """Size of the ``ep`` axis of the mesh this trace runs under (1: none).
    A mesh can be ambient only where ``parallel/context.py`` is loaded;
    importing it here would load the whole ``parallel`` package (and
    Pallas with it) into a CPU actor that holds this arch."""
    import sys

    context = sys.modules.get("relayrl_tpu.parallel.context")
    mesh = context.current_mesh() if context else None
    return 1 if mesh is None else int(mesh.shape.get("ep", 1))


def _dispatch_of(dispatch: str | None) -> str:
    """Arch ``moe_dispatch``, or the pick by the ambient mesh's ``ep``
    axis (module docstring): ``"sparse"`` | ``"dense"``."""
    return dispatch or ("dense" if _mesh_ep() > 1 else "sparse")


def _shared_ffn(layer: "MoEMLP", xs, gated: bool, tokens):
    """The expert every token takes, ``[N, d]`` float32, in ``layer``'s
    param scope: dense matmuls, outside the dispatch and outside the held
    share; under ``shared_gate`` times ``sigmoid(tokens w_s)``, read from
    the float32 rows as the router reads them. (A plain function: a module
    method would be wrapped by flax.)"""
    def dense(features, name):
        return nn.Dense(features, dtype=layer.compute_dtype, use_bias=False,
                        name=name)

    with jax.named_scope(FFN):
        up = checkpoint_name(dense(layer.shared_d_ff, "moe_shared_up")(xs),
                             SHARED_UP)
        gate = (checkpoint_name(
            dense(layer.shared_d_ff, "moe_shared_gate")(xs), SHARED_GATE)
                if gated else None)
        h = _activation(layer.ffn, up.astype(jnp.float32),
                        None if gate is None else gate.astype(jnp.float32))
        out = dense(layer.d_model, "moe_shared_down")(
            h.astype(layer.compute_dtype)).astype(jnp.float32)
        if layer.shared_gate:
            out = out * jax.nn.sigmoid(nn.Dense(
                1, dtype=jnp.float32, use_bias=False,
                name="moe_shared_expert_gate")(tokens.astype(jnp.float32)))
        return out


class MoEMLP(nn.Module):
    """Per-token top-k MoE FFN over flattened tokens."""

    d_model: int
    d_ff: int
    n_experts: int
    top_k: int
    compute_dtype: Any
    norm_topk_prob: bool = True
    ffn: str = "gelu"
    dispatch: str | None = None     # None: by the ambient mesh's ep axis
    use_bias: bool = True      # the router's; the expert stacks have none
    router: str = "softmax"         # | "sigmoid" (module docstring)
    expert_bias: bool = False       # moe_expert_bias in the sigmoid choice
    # (first, count): the experts of n_experts this device holds; None: all
    held: tuple[int, int] | None = None
    routed_scaling: float = 1.0     # times the k weights, after normalising
    # width of the one shared expert every token takes (None: none)
    shared_d_ff: int | None = None
    # sigmoid(u w_s) times the shared expert's output (module docstring)
    shared_gate: bool = False
    # the routed experts' width where it is not the stream's (module
    # docstring, "Experts in a latent"); None: d_model
    latent: int | None = None

    @nn.compact
    def __call__(self, x, route_x=None):
        """``x [B, T, d]``: the rows the experts read. ``route_x``: the
        rows the router reads where they are not the same (the layer's
        un-normed input under ``moe_router_input: "layer"``); None: ``x``."""
        B, T, d = x.shape
        n = B * T
        n_exp = self.n_experts
        cd = self.compute_dtype
        tokens = x.reshape(n, d)

        # the branch taken below, as the policy's record says it
        form, k, first, n_held, rows = layer_form(
            n, self.top_k, n_exp, self.held, self.dispatch)
        partial = n_held < n_exp
        if self.latent is not None and (
                isinstance(self.latent, bool)
                or not isinstance(self.latent, int) or self.latent < 1):
            raise ValueError(
                f"moe_latent {self.latent!r}: the width the routed experts "
                f"work in, a whole number of at least 1 (unset: d_model)")
        width = self.latent or d

        # the layer's parts carry their names onto the device
        # (ops/scopes.py): the router and the sort, the row gathers, and the
        # element-wise passes round the grouped matmuls, which keep their own
        with jax.named_scope(MOE_ROUTE):
            routed = tokens if route_x is None else route_x.reshape(n, d)
            logits = checkpoint_name(
                nn.Dense(n_exp, dtype=jnp.float32, use_bias=self.use_bias,
                         name="moe_gate")(routed.astype(jnp.float32)),
                ROUTER_LOGITS)
            bias = None
            if self.expert_bias:
                # enters the choice only: zero gradient, never moved; seeded
                # non-zero so that the path is exercised
                bias = self.param("moe_expert_bias",
                                  nn.initializers.normal(_EXPERT_BIAS_STD),
                                  (n_exp,), jnp.float32)
            top_w, top_idx = route(logits, k, self.norm_topk_prob,
                                   self.router, bias)              # [N, k]
            if self.routed_scaling != 1.0:
                top_w = top_w * float(self.routed_scaling)

        # the rows the experts read: the tokens, or their latent
        rows_in = tokens
        if self.latent:
            with jax.named_scope(MOE_LATENT):
                rows_in = checkpoint_name(
                    nn.Dense(width, dtype=cd, use_bias=False,
                             name="moe_latent_down")(tokens.astype(cd)),
                    LATENT_ROWS)

        init = nn.initializers.lecun_normal(batch_axis=(0,))
        gated = self.ffn in GATED_FFN
        with jax.named_scope(MOE_ELEMENTWISE):  # the stacks' casts
            if gated:
                w_gate = self.param("moe_w_gate", init,
                                    (n_held, width, self.d_ff),
                                    jnp.float32).astype(cd)
            w_up = self.param("moe_w_up", init, (n_held, width, self.d_ff),
                              jnp.float32).astype(cd)
            w_down = self.param("moe_w_down", init,
                                (n_held, self.d_ff, width),
                                jnp.float32).astype(cd)

        # token-slots per (held) expert: the grouped matmuls' group sizes
        # and the load monitor (a compare-and-reduce; a scatter-add
        # serialises)
        with jax.named_scope(MOE_ROUTE):
            load = (top_idx[..., None] == first + jnp.arange(n_held)).sum(
                axis=(0, 1), dtype=jnp.int32)

        if form == "dense":
            weights = jnp.zeros((n, n_exp), jnp.float32).at[
                jnp.arange(n)[:, None], top_idx].set(top_w)      # [N, E]
            if partial:
                weights = weights[:, first:first + n_held]
            xs = rows_in.astype(cd)

            def up_proj(w):
                return jnp.einsum("nd,edf->enf", xs, w,
                                  preferred_element_type=jnp.float32)

            h = _activation(self.ffn, up_proj(w_up),
                            up_proj(w_gate) if gated else None)
            out = jnp.einsum("enf,efd->end", h.astype(cd), w_down,
                             preferred_element_type=jnp.float32)
            y = jnp.einsum("ne,end->nd", weights, out)       # psum over ep
            row_passes = jnp.int32(0)                # no row buffers here
            sorted_slots = jnp.int32(0)              # and no expert order
        else:
            stacks = (w_up, w_gate if gated else None, w_down)
            if form == "counted":
                with jax.named_scope(MOE_ELEMENTWISE):
                    xs = rows_in.astype(cd)
                # its parts are named inside, in both of its loops
                y, row_passes = _counted_experts(
                    self.ffn, rows, (first, n_held), xs, top_w, top_idx,
                    stacks)
                sorted_slots = row_passes * rows
            else:
                # slot s = token s // k, choice s % k; rows = slots by
                # expert. [N*k, d] rows split as [N, k, d] for the combine,
                # which a TPU tiles (8, 128) over (k, d): free at k = 8, a
                # relayout into padded tiles at k = 4 (25 ms an update in
                # lfm2-policy.update, PERF.md section 6). So where 8 does
                # not divide k the slots run choice-major, slot s = choice
                # s // N, token s % N, and the split is [k, N, d]. Both
                # orders are measured: choice-major at k = 8 costs
                # olmoe-policy.update 1.4% (1.7 ms an update, one
                # broadcast-select fusion half as long again: PERF.md
                # section 6). A held layer's sorted walk is written
                # choice-major alone: where 8 divides k it counts (or, its
                # buffers N*k rows long, walks one token's slots).
                choice_major = partial or k % 8 != 0
                sorted_slots = jnp.int32(n * k)
                with jax.named_scope(MOE_ROUTE):
                    expert_of_slot = (top_idx.T if choice_major else top_idx
                                      ).reshape(n * k)
                    if partial:
                        # the slots of absent experts sort behind the held
                        # ones: a tail no pass reaches (module docstring,
                        # "Held experts")
                        local = expert_of_slot - first
                        expert_of_slot = jnp.where(
                            (local >= 0) & (local < n_held), local, n_held)
                    row_to_slot = jnp.argsort(expert_of_slot, stable=True)
                    slot_to_row = jnp.zeros_like(row_to_slot).at[
                        row_to_slot].set(
                            jnp.arange(n * k, dtype=row_to_slot.dtype),
                            unique_indices=True)
                if partial:
                    # (the cast in each branch: where it stands in the
                    # program)
                    with jax.named_scope(MOE_ELEMENTWISE):
                        xs = rows_in.astype(cd)
                    with jax.named_scope(MOE_ROUTE):
                        padded = jnp.pad(row_to_slot, (0, -(n * k) % rows))
                    # its parts are named inside, in both of its loops
                    y, row_passes = _held_experts(
                        self.ffn, rows, xs, top_w, stacks, load, padded,
                        slot_to_row)
                else:
                    row_passes = jnp.int32(1)
                    with jax.named_scope(MOE_ROUTE):
                        token_of_row = (row_to_slot % n if choice_major
                                        else row_to_slot // k)
                    with jax.named_scope(MOE_ELEMENTWISE):
                        xs = rows_in.astype(cd)
                    # round the CALLS: a custom_vjp's backward carries the
                    # scopes of its call, not those opened in its forward
                    with jax.named_scope(MOE_ROWS):
                        xs = _dispatch_rows(xs, token_of_row, slot_to_row,
                                            choice_major)         # [N*k, d]
                    # what is not a kernel between the dispatch and its way
                    # back (the kernels keep their own innermost names)
                    with jax.named_scope(MOE_ELEMENTWISE):
                        out = _experts(self.ffn, xs, stacks, load)
                    with jax.named_scope(MOE_ROWS):
                        out = _unsort_rows(out, slot_to_row, row_to_slot)
                    with jax.named_scope(MOE_ELEMENTWISE):
                        out = _slots_3d(out, n, choice_major).astype(
                            jnp.float32)
                        y = (jnp.einsum("kn,knd->nd", top_w.T, out)
                             if choice_major
                             else jnp.einsum("nk,nkd->nd", top_w, out))

        # Monitoring hook: token-slots per held expert (sums to N*k where
        # every expert is held), and how the sparse dispatch walked them:
        # the rows of its buffers, the passes it took over them (1 over
        # N*k rows where every expert is held) and the slots it put in
        # expert order (passes x R where a held layer counts, N*k where one
        # sort orders them all). Inert unless applied with
        # mutable=["intermediates"] — the update's moe_load_max/min,
        # moe_held_slots, moe_row_passes, moe_sorted_slots and
        # expert_utilization() read it.
        self.sow("intermediates", "expert_load", load)
        self.sow("intermediates", "expert_slots", jnp.int32(n * k))
        self.sow("intermediates", "row_passes", row_passes)
        self.sow("intermediates", "row_buffer", jnp.int32(rows))
        self.sow("intermediates", "sorted_slots", sorted_slots)
        if self.latent:
            with jax.named_scope(MOE_LATENT):
                y = nn.Dense(d, dtype=cd, use_bias=False,
                             name="moe_latent_up")(y.astype(cd)).astype(
                                 jnp.float32)
        if self.shared_d_ff:
            y = y + _shared_ffn(self, tokens.astype(cd), gated, tokens)
        return y.reshape(B, T, d).astype(x.dtype)


def _sown(intermediates) -> dict:
    """``{layer: what its MoE layer sowed}`` of one applied forward."""
    return {layer: sub["moe"] for layer, sub in intermediates.items()
            if layer.startswith("block_") and "moe" in sub}


def _loads(intermediates) -> dict:
    """``{layer: ([held] token-slots per held expert, all N*k slots)}``
    from one applied forward's sown counts."""
    return {layer: (moe["expert_load"][0].astype(jnp.float32),
                    moe["expert_slots"][0].astype(jnp.float32))
            for layer, moe in _sown(intermediates).items()}


def _shares(intermediates) -> dict:
    """``{layer: [held] shares of ALL the layer's token-slots}``."""
    return {layer: load / jnp.maximum(slots, 1.0)
            for layer, (load, slots) in _loads(intermediates).items()}


def load_extremes(intermediates) -> dict:
    """``{"moe_load_max", "moe_load_min", "moe_held_slots",
    "moe_row_passes", "moe_sorted_slots"}``: the fullest and the emptiest
    held expert's share of all the token-slots, over every MoE layer of one
    applied forward (nothing is recomputed); the token-slots routed to held
    experts, the passes the sparse dispatch took over its row buffers and
    the slots it put in expert order, each summed over those layers. 1/E
    each at even load; max -> 1/k is the gate collapsing; held slots = N*k a
    layer where every expert is held; passes = 1 a layer unless a held-experts
    layer's router sent it more rows than its buffer has; sorted slots =
    passes x the buffer's rows in a held-experts layer that counts its rows
    (:func:`held_form`), N*k in one that sorts them and where every expert
    is held."""
    sown = _sown(intermediates).values()
    shares = jnp.stack(list(_shares(intermediates).values()))
    held = sum(load.sum() for load, _ in _loads(intermediates).values())
    passes = sum(moe["row_passes"][0] for moe in sown)
    ordered = sum(moe["sorted_slots"][0] for moe in sown)
    return {"moe_load_max": shares.max(), "moe_load_min": shares.min(),
            "moe_held_slots": held,
            "moe_row_passes": jnp.asarray(passes, jnp.float32),
            "moe_sorted_slots": jnp.asarray(ordered, jnp.float32)}


def expert_utilization(arch, params, obs, mask=None) -> dict:
    """Per-layer share of the token-slots per expert — the gate-collapse
    monitor.

    No auxiliary load-balancing loss is applied during training — by
    choice, not for want of a path: no token is ever dropped, so a collapsed
    gate is *correct*, just slow (its experts' groups grow and the others'
    shrink), and a balancing term would pull an RL policy's routing towards
    evenness at the cost of its return. (The path exists since PR 47: a
    trunk brings a loss of its own through ``Policy.own_loss`` — the rows
    come with ``evaluate_stats``, IMPALA's update adds their mean, any
    other algorithm refuses the policy — which is how a sparse-attention
    layer's indexer trains; a balancing loss would be sown the same way.)
    The standard top-k failure mode — the gate collapsing onto a
    few experts — is therefore something to MONITOR: the IMPALA update
    reports the extremes of this every update (``moe_load_max`` /
    ``moe_load_min``); call this on a representative batch for the whole
    distribution, and alarm when the max fraction nears 1/k.

    Returns ``{layer_name: [E] fractions summing to 1}`` (under
    ``moe_held``: the held experts' fractions of all the token-slots,
    summing to the held share).
    """
    from relayrl_tpu.models.transformer import _make_core

    core = _make_core(arch, moe_experts=int(arch.get("moe_experts", 4)))
    _, state = core.apply(params, jnp.asarray(obs), mask,
                          mutable=["intermediates"])
    return _shares(state["intermediates"])
