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
  (LFM2-MoE's router; its ``routed_scaling_factor`` is 1 and has no key).

**Held experts.** A layer may be told which experts it holds: arch
``moe_held = [first, count]`` says this device holds experts
``first .. first + count - 1`` of the ``moe_experts`` the model has — the
share of one chip among the chips that divide a layer's experts. The
router still scores and chooses over all E (and normalises over the k
chosen of all E, held or not, so that the shares of all the chips add up
to the layer), the expert stacks are ``[count, ...]``, and the layer's
output is the sum over the chosen experts that are held: a token-slot
whose expert lives elsewhere adds nothing here — no stand-in for the
absent chips' work or traffic. Unset, every expert is held and the layer
is what it was.

Experts are GELU (``moe_w_up`` / ``moe_w_down``, the default) or gated,
``moe_w_gate`` beside them: SwiGLU (arch ``ffn: "swiglu"``,
``down(silu(gate(x)) * up(x))``) or ReGLU (``ffn: "reglu"``,
``down(relu(gate(x)) * up(x))``), of width ``moe_d_ff``.

Dispatch is **sparse** on one device: the
N·k token-slots are sorted by expert, each expert's rows go through one
grouped matmul per weight stack (group sizes = a bincount of the chosen
experts), and the down output is un-sorted and summed per token with the
router weights. Every shape is static (N·k rows whatever the imbalance),
there is no capacity and so **no token-slot is ever dropped**: an expert
that every token picks gets all N rows, an expert nobody picks gets a group
of size 0. With held experts the buffers keep their N·k rows and the slots
of absent experts sort behind the held ones, as a tail no group covers:
the grouped matmuls visit (and cost) the held rows only, the row gathers
still move N·k rows, and what the kernels leave in the tail — rows they
never wrote — is masked to zero where it would be read (the un-sorted
output, the input cotangent). The layer's N·k-row buffers (the dispatched
rows, up and gate in float32, the un-sorted output) are then recomputed in
the backward from the ``[N, d]`` tokens (``jax.checkpoint`` round dispatch,
experts and combine) instead of kept: sized for every slot they are eight
times what the held slots need, 1.6 GB a layer at 65,536 slots of 2048.
The FFN costs k/E of running every expert on every token. Both
permutations are gathers, forward and backward (a permutation's transpose
is the gather by its inverse), because a TPU scatter-add of [N·k, d] rows
is slower than the matmuls it serves.

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
(``E`` = the held count where ``moe_held`` is set).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from relayrl_tpu.models.mlp import GATED_FFN

# Spread of the seeded ``moe_expert_bias`` (sigmoid router): against scores
# whose 4th and 5th lie ~0.02 apart it moves the choice of about every
# second token, and an expert's load by about a quarter.
_EXPERT_BIAS_STD = 0.02


def route(logits, k: int, norm_topk_prob: bool, router: str = "softmax",
          expert_bias=None):
    """Router logits ``[N, E]`` (float32) -> (weights ``[N, k]``, expert
    indices ``[N, k]``); a token's row depends on that token alone."""
    if router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        biased = scores if expert_bias is None else scores + expert_bias
        top_idx = jax.lax.top_k(biased, k)[1]
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _dispatch_rows(tokens, token_of_row, slot_to_row, live_slot=None,
                   choice_major=False):
    """``tokens[token_of_row]``: each token's row once per chosen expert,
    in expert order. Its transpose is a gather too: cotangent rows back in
    slot order (``slot_to_row``), the k of each token summed.
    ``live_slot`` (bool per slot, held-experts layers): the cotangent of a
    slot whose expert is not held is zero, whatever its row holds."""
    return _dispatch_fwd(tokens, token_of_row, slot_to_row, live_slot,
                         choice_major)[0]


def _dispatch_fwd(tokens, token_of_row, slot_to_row, live_slot=None,
                  choice_major=False):
    out = jnp.take(tokens, token_of_row, axis=0)
    return out, (slot_to_row, live_slot, tokens.shape[0])


def _dispatch_bwd(choice_major, res, g):
    slot_to_row, live_slot, n = res
    g = jnp.take(g, slot_to_row, axis=0)
    if live_slot is not None:
        g = jnp.where(live_slot[:, None], g, 0)
    g = _slots_3d(g, n, choice_major).sum(axis=0 if choice_major else 1)
    return g, None, None, None


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


def _mesh_ep() -> int:
    """Size of the ``ep`` axis of the mesh this trace runs under (1: none).
    A mesh can be ambient only where ``parallel/context.py`` is loaded;
    importing it here would load the whole ``parallel`` package (and
    Pallas with it) into a CPU actor that holds this arch."""
    import sys

    context = sys.modules.get("relayrl_tpu.parallel.context")
    mesh = context.current_mesh() if context else None
    return 1 if mesh is None else int(mesh.shape.get("ep", 1))


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

    @nn.compact
    def __call__(self, x, route_x=None):
        """``x [B, T, d]``: the rows the experts read. ``route_x``: the
        rows the router reads where they are not the same (the layer's
        un-normed input under ``moe_router_input: "layer"``); None: ``x``."""
        B, T, d = x.shape
        n = B * T
        n_exp = self.n_experts
        k = max(1, min(self.top_k, n_exp))
        cd = self.compute_dtype
        tokens = x.reshape(n, d)

        first, n_held = self.held or (0, n_exp)
        if not (0 <= first and 0 < n_held and first + n_held <= n_exp):
            raise ValueError(f"moe_held {self.held} outside 0..{n_exp}")
        partial = n_held < n_exp

        routed = tokens if route_x is None else route_x.reshape(n, d)
        logits = nn.Dense(n_exp, dtype=jnp.float32, use_bias=self.use_bias,
                          name="moe_gate")(routed.astype(jnp.float32))
        bias = None
        if self.expert_bias:
            # enters the choice only: zero gradient, never moved; seeded
            # non-zero so that the path is exercised
            bias = self.param("moe_expert_bias",
                              nn.initializers.normal(_EXPERT_BIAS_STD),
                              (n_exp,), jnp.float32)
        top_w, top_idx = route(logits, k, self.norm_topk_prob, self.router,
                               bias)                               # [N, k]

        init = nn.initializers.lecun_normal(batch_axis=(0,))
        gated = self.ffn in GATED_FFN
        if gated:
            w_gate = self.param("moe_w_gate", init, (n_held, d, self.d_ff),
                                jnp.float32).astype(cd)
        w_up = self.param("moe_w_up", init, (n_held, d, self.d_ff),
                          jnp.float32).astype(cd)
        w_down = self.param("moe_w_down", init, (n_held, self.d_ff, d),
                            jnp.float32).astype(cd)

        def act(up, gate=None):
            return GATED_FFN[self.ffn](gate) * up if gated else nn.gelu(up)

        # token-slots per (held) expert: the grouped matmuls' group sizes
        # and the load monitor (a compare-and-reduce; a scatter-add
        # serialises)
        load = (top_idx[..., None] == first + jnp.arange(n_held)).sum(
            axis=(0, 1), dtype=jnp.int32)

        dispatch = self.dispatch or ("dense" if _mesh_ep() > 1 else "sparse")
        if dispatch == "dense":
            weights = jnp.zeros((n, n_exp), jnp.float32).at[
                jnp.arange(n)[:, None], top_idx].set(top_w)      # [N, E]
            if partial:
                weights = weights[:, first:first + n_held]
            xs = tokens.astype(cd)

            def up_proj(w):
                return jnp.einsum("nd,edf->enf", xs, w,
                                  preferred_element_type=jnp.float32)

            h = act(up_proj(w_up), up_proj(w_gate) if gated else None)
            out = jnp.einsum("enf,efd->end", h.astype(cd), w_down,
                             preferred_element_type=jnp.float32)
            y = jnp.einsum("ne,end->nd", weights, out)       # psum over ep
        elif dispatch == "sparse":
            if _mesh_ep() > 1:
                raise ValueError(
                    f"moe_dispatch 'sparse' under a mesh with ep="
                    f"{_mesh_ep()}: the sparse dispatch is a single-device "
                    f"program (GSPMD does not partition its Pallas calls "
                    f"over ep); leave moe_dispatch unset and the layer "
                    f"takes the dense path, which GSPMD does partition")
            # slot s = token s // k, choice s % k; rows = slots by expert.
            # [N*k, d] rows split as [N, k, d] for the combine, which a TPU
            # tiles (8, 128) over (k, d): free at k = 8, a relayout into
            # padded tiles at k = 4 (25 ms an update in lfm2-policy.update,
            # PERF.md section 6). So where 8 does not divide k the slots run
            # choice-major, slot s = choice s // N, token s % N, and the
            # split is [k, N, d]. Both orders are measured: choice-major at
            # k = 8 costs olmoe-policy.update 1.4% (1.7 ms an update, one
            # broadcast-select fusion half as long again: PERF.md section 6).
            choice_major = k % 8 != 0
            expert_of_slot = (top_idx.T if choice_major else top_idx
                              ).reshape(n * k)
            live_slot = None
            if partial:
                # the slots of absent experts sort behind the held ones: a
                # tail of rows no group covers and no matmul visits
                local = expert_of_slot - first
                live_slot = (local >= 0) & (local < n_held)
                expert_of_slot = jnp.where(live_slot, local, n_held)
            row_to_slot = jnp.argsort(expert_of_slot, stable=True)
            slot_to_row = jnp.zeros_like(row_to_slot).at[row_to_slot].set(
                jnp.arange(n * k, dtype=row_to_slot.dtype),
                unique_indices=True)
            token_of_row = (row_to_slot % n if choice_major
                            else row_to_slot // k)

            def sparse(tokens, top_w, w_up, w_gate, w_down):
                xs = _dispatch_rows(tokens, token_of_row, slot_to_row,
                                    live_slot, choice_major)      # [N*k, d]

                def up_proj(w):
                    return grouped_matmul(xs, w, load).astype(jnp.float32)

                h = act(up_proj(w_up), up_proj(w_gate) if gated else None)
                out = grouped_matmul(h.astype(cd), w_down, load)  # [N*k, d]
                out = _unsort_rows(out, slot_to_row, row_to_slot)
                if partial:  # the tail's rows were never written
                    out = jnp.where(live_slot[:, None], out, 0)
                out = _slots_3d(out, n, choice_major).astype(jnp.float32)
                if choice_major:
                    return jnp.einsum("kn,knd->nd", top_w.T, out)
                return jnp.einsum("nk,nkd->nd", top_w, out)

            if partial:
                # every N*k-row buffer (the dispatched rows, up and gate in
                # float32, the un-sorted output) is sized for all the slots
                # whatever the held rows: recomputed in the backward from
                # the [N, d] tokens, not kept (module docstring)
                sparse = jax.checkpoint(sparse)
            y = sparse(tokens.astype(cd), top_w, w_up,
                       w_gate if gated else None, w_down)
        else:
            raise ValueError(f"unknown moe_dispatch {dispatch!r}")

        # Monitoring hook: token-slots per held expert (sums to N*k where
        # every expert is held). Inert unless applied with
        # mutable=["intermediates"] — the update's moe_load_max/min,
        # moe_held_slots and expert_utilization() read it.
        self.sow("intermediates", "expert_load", load)
        self.sow("intermediates", "expert_slots", jnp.int32(n * k))
        return y.reshape(B, T, d).astype(x.dtype)


def _loads(intermediates) -> dict:
    """``{layer: ([held] token-slots per held expert, all N*k slots)}``
    from one applied forward's sown counts."""
    return {layer: (sub["moe"]["expert_load"][0].astype(jnp.float32),
                    sub["moe"]["expert_slots"][0].astype(jnp.float32))
            for layer, sub in intermediates.items()
            if layer.startswith("block_") and "moe" in sub}


def _shares(intermediates) -> dict:
    """``{layer: [held] shares of ALL the layer's token-slots}``."""
    return {layer: load / jnp.maximum(slots, 1.0)
            for layer, (load, slots) in _loads(intermediates).items()}


def load_extremes(intermediates) -> dict:
    """``{"moe_load_max", "moe_load_min", "moe_held_slots"}``: the fullest
    and the emptiest held expert's share of all the token-slots, over every
    MoE layer of one applied forward (nothing is recomputed), and the
    token-slots routed to held experts, summed over those layers. 1/E each
    at even load; max -> 1/k is the gate collapsing; held slots = N*k a
    layer where every expert is held."""
    shares = jnp.stack(list(_shares(intermediates).values()))
    held = sum(load.sum() for load, _ in _loads(intermediates).values())
    return {"moe_load_max": shares.max(), "moe_load_min": shares.min(),
            "moe_held_slots": held}


def expert_utilization(arch, params, obs, mask=None) -> dict:
    """Per-layer share of the token-slots per expert — the gate-collapse
    monitor.

    No auxiliary load-balancing loss is applied during training (a
    deliberate omission: no token is ever dropped, so a collapsed gate is
    *correct*, just slow — its experts' groups grow and the others' shrink
    — and an aux loss would have to be plumbed through every algorithm's
    update). The standard top-k failure mode — the gate collapsing onto a
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
