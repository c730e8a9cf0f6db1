"""Decoder-only transformer sequence policy (the long-context model family).

No counterpart exists in the reference — its only models are 2x128 MLPs
(relayrl_framework/src/native/python/algorithms/REINFORCE/kernel.py:14-21)
and SURVEY.md §5.7 records long-context support as absent. This family is
the TPU-first addition: a causal trunk over the trajectory time axis, so the
policy conditions on history instead of a single observation.

**A trunk** is an observation embedding, ``n_layers`` layers on a float32
residual stream, a final norm and the pi / vf heads. A layer
(:class:`TransformerBlock`) is an operator and, after it, an FFN — or one of
the two alone, ``x + part(norm(x))`` — and the arch's ``layer_types`` names
each layer's kind (:data:`relayrl_tpu.models.layers.LAYER_KINDS`; without
it, attention and an FFN everywhere). What an operator is — its arch keys,
its three modes, the state a cached call keeps — is its module's in
:mod:`relayrl_tpu.models.layers` to say; this file asks that table and names
no operator. What every layer shares is described by the arch, not by knobs
(:mod:`relayrl_tpu.models.arch_keys`): ``norm`` (``"layer"`` | ``"rms"``,
``norm_zero_centred``: weights as offsets from one) with ``norm_eps``,
``use_bias``, ``ffn`` with ``d_ff``; in ``transformer_moe_discrete`` the FFN
of every layer past the first ``moe_dense_layers`` is the expert layer of
:mod:`relayrl_tpu.models.moe`. With none of these given it is the GPT-2
shaped block (LayerNorm, learned positions, biases, GELU FFN of ``mlp_ratio
* d_model``). ``positions``: ``"learned"`` (a table added to the embedding),
``"rope"`` (the attention layers rotate q and k by ``rope_theta`` — all of
them, or those ``rope_layers`` names; no table) or ``"none"`` (no positional
signal at all: recurrent layers order the tokens). Four scalars, each 1 (or
absent) unless the arch gives it and then nothing is traced for it:
``embed_multiplier`` on the embedded observation, ``residual_multiplier`` on
both halves of every layer, ``attn_scale`` in place of ``1 / sqrt(head_dim)``
and ``logit_divisor`` under the policy logits (Granite 4.0-H's four).

Sequence ABI: ``evaluate(params, obs[B,T,D], act[B,T], mask[B,T,A]) ->
(logp[B,T], ent[B,T], v[B,T])`` — same shapes the per-step MLP family
broadcasts to, so REINFORCE/PPO updates take this policy unchanged.
``step`` treats the second-to-last axis as time (``[T,D]`` or ``[B,T,D]``)
and returns the action at the last position; a bare ``[D]`` obs is a
context of one. ``step_window`` acts from a padded window (the final layer
runs for the readout row alone where its operator can); ``step_cached`` /
``prefill_cache`` continue from per-layer states (``init_cache``).
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp
import flax
from flax import linen as nn

from relayrl_tpu.models import layers
from relayrl_tpu.models.arch_keys import (
    BLOCK_KEYS,
    DECLARED,
    MOE_KEYS,
    OPERATOR_BASE,
    OPERATOR_KEYS,
    settings,
)
from relayrl_tpu.models.base import Policy, hold_params, register_model
from relayrl_tpu.models.layers.block import norm as _norm
from relayrl_tpu.models.mlp import (
    _MASK_FILL,
    _categorical_entropy,
    _categorical_logp,
    _compute_dtype,
)
from relayrl_tpu.ops.scopes import EMBED, HEADS, LOOP_PASS, OP_PROJ


class TransformerBlock(nn.Module):
    """One layer of a trunk: the operator ``op`` (a name of
    ``layers.OPERATORS``) and, where ``has_ffn``, the FFN half after it. The
    fields are what every layer shares; the operator's own settings are
    ``cfg`` (:func:`_operator_settings`) and the kernel entries it calls
    ``fns`` (``layers.resolve``)."""

    d_model: int
    mlp_ratio: int
    compute_dtype: Any
    op: str = "attention"
    cfg: Mapping[str, Any] = flax.core.FrozenDict()
    fns: Mapping[str, Callable] = flax.core.FrozenDict()
    has_ffn: bool = True
    # Sliding-window attention: query t sees keys t - window < s <= t
    # (None: every key up to its own). Set per layer by the core.
    window: int | None = None
    # >0: the FFN is a per-token top-k MoE of this many experts (models/
    # moe.py; weights shard over the mesh ``ep`` axis). 0 keeps the dense
    # mlp_up / mlp_down FFN.
    moe_experts: int = 0
    moe_top_k: int = 2
    # arch_keys.BLOCK_KEYS, by name
    norm: str = BLOCK_KEYS["norm"]
    norm_eps: float | None = BLOCK_KEYS["norm_eps"]
    norm_zero_centred: bool = BLOCK_KEYS["norm_zero_centred"]
    norm_sandwich: bool = BLOCK_KEYS["norm_sandwich"]
    residual_multiplier: float = BLOCK_KEYS["residual_multiplier"]
    use_bias: bool = BLOCK_KEYS["use_bias"]
    ffn: str = BLOCK_KEYS["ffn"]
    d_ff: int | None = BLOCK_KEYS["d_ff"]
    moe_d_ff: int | None = BLOCK_KEYS["moe_d_ff"]
    moe_norm_topk_prob: bool = BLOCK_KEYS["moe_norm_topk_prob"]
    moe_dispatch: str | None = BLOCK_KEYS["moe_dispatch"]
    moe_router_input: str = BLOCK_KEYS["moe_router_input"]
    # MoEMLP's further fields (arch_keys.MOE_KEYS)
    moe_kw: Mapping[str, Any] = flax.core.FrozenDict()

    @nn.compact
    def __call__(self, x, cache=None, t=None, readout_idx=None,
                 n_valid=None, restart=False):
        """The operator's ``apply`` (``layers``' interface): full mode ``x
        [B, T, d] -> [B, T, d]``; with ``cache`` (this layer's state) and
        ``t`` (the write index; ``n_valid``: a prefill's count of real rows)
        ``(out, new_cache)``; with ``readout_idx`` the one row ``[B, 1,
        d]``. Param names and creation order are identical in every mode
        (init always runs the full path), so one param tree serves all.

        ``restart``: the caller's cache may be a used one, and a call at
        ``t`` = 0 starts a new sequence over it. Rows at their positions
        need nothing for that; a state without positions (the operator's
        ``CACHE_RESTARTS`` is ``"zeroed"``) is read as zeros there — a
        select that fuses into the read the step makes anyway, no pass over
        the state of its own. Without it the program is the one a fresh
        cache always had."""
        op = layers.OPERATORS[self.op]
        if restart and getattr(op, "CACHE_RESTARTS", None) == "zeroed":
            with jax.named_scope(OP_PROJ):
                first = jnp.asarray(t) == 0
                cache = jax.tree.map(
                    lambda a: jnp.where(first, jnp.zeros((), a.dtype), a),
                    cache)
        return op.apply(self, x, cache, t, readout_idx, n_valid)


def _embed_obs(parent: nn.Module, obs, d_model: int, max_seq_len: int,
               start=0, learned_positions: bool = True,
               multiplier: float = 1.0):
    """Obs embedding + positional table, built in the CALLER's param scope
    (layer names land flat: obs_embed / pos_embed) — the single source of
    truth shared by TransformerCore (full AND cached-decode modes, which
    differ only in the ``start`` position) and the pipeline family's
    _PPEmbed. With rotary positions (``learned_positions=False``) the
    blocks place the tokens and there is no ``pos_embed`` leaf.
    ``multiplier`` (the arch's ``embed_multiplier``) scales the embedded
    observation, before any table is added."""
    _, T, _ = obs.shape
    with jax.named_scope(EMBED):
        x = nn.Dense(d_model, dtype=jnp.float32, name="obs_embed")(obs)
        if multiplier != 1.0:
            x = multiplier * x
        if not learned_positions:
            return x
        pos = parent.param("pos_embed", nn.initializers.normal(0.02),
                           (max_seq_len, d_model), jnp.float32)
        return x + jax.lax.dynamic_slice_in_dim(pos, start, T, axis=0)[None]


def _readout_heads(x, mask, act_dim: int, d_model: int, has_critic: bool,
                   norm: str = "layer", norm_eps=None,
                   norm_zero_centred: bool = False, normed: bool = False,
                   logit_divisor: float = 1.0):
    """Final norm (the arch's kind and epsilon, as the blocks') + pi/vf
    heads in the caller's scope (shared with _PPReadout; the vf optimizer
    partition keys off these exact `vf*` names). ``normed``: the rows come
    from the final norm already (a looped trunk's last pass).
    ``logit_divisor`` divides the policy logits (before a mask fills); the
    value is no logit and is not divided."""
    with jax.named_scope(HEADS):
        if not normed:
            x = _norm(norm, norm_eps, "ln_final", norm_zero_centred)(x)
        logits = nn.Dense(act_dim, dtype=jnp.float32, name="pi_head")(x)
        if logit_divisor != 1.0:
            logits = logits / logit_divisor
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
    mlp_ratio: int
    max_seq_len: int
    has_critic: bool
    compute_dtype: Any
    moe_experts: int = 0
    moe_top_k: int = 2
    # TransformerBlock's shared arch fields, the operators' settings by
    # operator, the kernel entries behind the policy's records
    block_kw: Mapping[str, Any] = flax.core.FrozenDict()
    op_cfg: Mapping[str, Mapping[str, Any]] = flax.core.FrozenDict()
    fns: Mapping[str, Callable] = flax.core.FrozenDict()
    # Per layer: its kind, a name of layers.LAYER_KINDS (empty: full
    # attention and an FFN everywhere) and, in a MoE trunk, how many leading
    # layers keep the dense FFN.
    layer_types: tuple[str, ...] = ()
    moe_dense_layers: int = 0
    # the window of the "sliding_attention" layers
    sliding_window: int | None = None
    # Per layer under rotary positions: whether RoPE turns its q and k
    # (empty: every attention layer's). A layer left out sees no positions.
    rope_layers: tuple[bool, ...] = ()
    # "learned": a table added to the embedding; "rope": the blocks rotate;
    # "none": no positional signal at all.
    positions: str = "learned"
    # How often the stack runs over its one parameter tree, and whether the
    # full mode checkpoints each block application (arch_keys.CORE_KEYS).
    loop_steps: int = 1
    block_checkpoint: bool = False
    # What multiplies the embedded observation and divides the policy logits.
    embed_multiplier: float = 1.0
    logit_divisor: float = 1.0

    def layer_parts(self, i: int) -> tuple[str, bool]:
        """Layer ``i``'s (operator, whether an FFN follows it)."""
        kind = self.layer_types[i] if self.layer_types else "full_attention"
        if kind not in layers.LAYER_KINDS:
            raise ValueError(f"unknown layer type {kind!r} "
                             f"({' | '.join(layers.LAYER_KINDS)})")
        return layers.LAYER_KINDS[kind]

    def layer_window(self, i: int) -> int | None:
        """The window of layer ``i`` (None: every other kind)."""
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
                 n_valid=None, restart=False):
        """Full mode: obs ``[B, T, D]`` -> (logits, v). Decode mode
        (``cache`` = tuple of states, one a pass and layer, pass-major, each
        its operator's; ``t`` = position; ``n_valid``: prefill's count of
        real rows; ``restart``: the cache may be a used one, which every
        block then reads at ``t`` = 0 as a new sequence's): obs is ``[B, 1,
        D]``; returns ``((logits, v), new_cache)`` for the single position. Readout mode (``readout_t`` =
        dynamic row index): obs is a full window ``[B, W, D]`` but only
        position ``readout_t`` is decoded — every layer but the last pass's
        last runs over every row (deeper layers attend all earlier
        positions' hidden states, so those are live), that one runs row-only
        (its other rows feed nothing), and the heads see the one row;
        returns ``(logits[B, A], v[B])``. Init always traces the full path,
        so all modes share one param tree.

        ``loop_steps`` > 1: the ``n_layers`` blocks run that many times over
        the SAME parameters, ``ln_final`` after every pass (its output is
        the next pass's input, and the heads read the last pass's without a
        second norm), positions the same at every pass; the full mode runs
        the passes as ONE body of a scan. ``block_checkpoint``: the full
        mode keeps a block application's input for the backward and makes
        the rest again there (``nn.remat``) — but for what is dearest to
        make again, which it keeps by name: the flash forward's output, a
        mixer's recurrence, output and input projection, the router's
        choice, the shared expert's first products and the latent's rows
        (the list and its order: where ``kept`` is built below;
        ``Policy.checkpoint_kept`` and the ``[checkpoint]`` lines say what
        each kind of layer held, in bytes)."""
        decode = cache is not None
        kw = self.block_kw
        S, L = self.loop_steps, self.n_layers

        for what, per_layer in (("layer_types", self.layer_types),
                                ("rope_layers", self.rope_layers)):
            if per_layer and len(per_layer) != L:
                raise ValueError(f"{what} names {len(per_layer)} layers, "
                                 f"n_layers is {L}")
        if S < 1:
            raise ValueError(f"loop_steps is {S}: at least one pass")
        if decode and len(cache) != S * L:
            raise ValueError(f"the cache holds {len(cache)} states; "
                             f"{S} passes of {L} layers keep {S * L}")
        idx = None
        if readout_t is not None:
            idx = jnp.asarray(readout_t, jnp.int32)
        # the learner's forward: every pass alike, and the one mode that is
        # differentiated
        full = not decode and idx is None
        kept = ()
        if self.block_checkpoint and full:
            # beside a block application's input, by name: the flash
            # forward's output and log-sum-exp and what a mixer's own
            # checkpoint keeps of its recurrence (``KEPT``) — the backward
            # then runs no forward kernel and no recurrence a second time
            # (PERF.md section 6, PR 50, PR 55) —, and what is dearest to
            # make again of the rest, in the order of device time a byte
            # (PR 61): a mixer's output and its input projection's product
            # (``recurrent.BLOCK_KEPT``), the router's choice, the shared
            # expert's first products and the latent's rows
            # (``moe.BLOCK_KEPT``). One static list: a name no value of a
            # layer carries keeps nothing there, and the dense FFN's
            # products have none (32 applications of it do not fit).
            from relayrl_tpu.models import moe
            from relayrl_tpu.ops import flash

            kept = (flash.OUT_NAME, flash.LSE_NAME,
                    *(name for op in layers.OPERATORS.values()
                      for name in getattr(op, "KEPT", ())),
                    *layers.recurrent.BLOCK_KEPT, *moe.BLOCK_KEPT)
        checked = {}    # kind of layer -> its checkpointed block class

        def block_at(i: int) -> TransformerBlock:
            op, has_ffn = self.layer_parts(i)
            cfg = self.op_cfg[op]
            if self.rope_layers and not self.rope_layers[i]:
                cfg = cfg.copy({"rope_theta": None})  # no positions at all
            block_cls = TransformerBlock
            if kept:
                # a policy a kind of layer, behind the record of what it
                # keeps (``Policy.checkpoint_kept``, ``[checkpoint]`` lines)
                kind = (op, "experts" if self.layer_experts(i)
                        else "dense" if has_ffn else "none")
                if kind not in checked:
                    policy, said = self.fns["checkpoint"](kind, kept)
                    checked[kind] = nn.remat(TransformerBlock,
                                             policy=policy), said
                block_cls = checked[kind][0]
            return block_cls(
                self.d_model, self.mlp_ratio, self.compute_dtype, op=op,
                cfg=cfg, fns=self.fns, has_ffn=has_ffn,
                window=self.layer_window(i),
                moe_experts=self.layer_experts(i), moe_top_k=self.moe_top_k,
                name=f"block_{i}", **kw)

        def final_norm():
            return _norm(kw["norm"], kw["norm_eps"], "ln_final",
                         kw["norm_zero_centred"])

        def whole_pass(_core, x, _):
            """One pass over every row, the scan's body: the L blocks, then
            the final norm. ``_core`` is this module as the scan lifts it,
            the parent of what is made here."""
            with jax.named_scope(LOOP_PASS):
                for i in range(L):
                    x = block_at(i)(x)
                with jax.named_scope(HEADS):
                    return final_norm()(x), None

        x = _embed_obs(
            self, obs, self.d_model, self.max_seq_len,
            start=t if decode else 0,
            learned_positions=self.positions == "learned",
            multiplier=self.embed_multiplier)
        new_cache = []
        if S > 1 and full:
            # ONE body in the program, run S times over the broadcast
            # parameters: 32 block applications written out are 0.8 GB of
            # executable at the benchmark's looped configuration, which no
            # compile cache keeps and the chip holds beside its state
            x, _ = nn.scan(whole_pass, variable_broadcast="params",
                           split_rngs={"params": False}, length=S)(
                               self, x, None)
        else:
            # one tree: every pass calls the same L modules; a cached pass
            # has its own states, the last pass of a readout its one row
            blocks = [block_at(i) for i in range(L)]
            end_of_pass = final_norm() if S > 1 else None
            # A final attention block with experts keeps its full-window
            # pass (routing is per token, so the sliced row is what a
            # row-only pass would give; the shortcut is simply not taken),
            # as does an operator whose row needs every row before it.
            last = blocks[-1]
            row_only = (layers.OPERATORS[last.op].ROW_READOUT and not (
                last.moe_experts > 0 and last.op == "attention"))
            for s in range(S):
                with (jax.named_scope(LOOP_PASS) if S > 1
                      else contextlib.nullcontext()):
                    for i, block in enumerate(blocks):
                        if decode:
                            x, layer_cache = block(
                                x, cache=cache[s * L + i], t=t,
                                n_valid=n_valid, restart=restart)
                            new_cache.append(layer_cache)
                        elif idx is None or (s, i) != (S - 1, L - 1):
                            x = block(x)
                        elif row_only:
                            x = block(x, readout_idx=idx)
                        else:
                            x = jax.lax.dynamic_slice_in_dim(block(x), idx,
                                                             1, axis=1)
                    if S > 1:
                        with jax.named_scope(HEADS):
                            x = end_of_pass(x)
        for _, said in checked.values():
            said()
        if idx is not None and mask is not None:
            mask = jax.lax.dynamic_slice_in_dim(mask, idx, 1, axis=1)
        logits, v = _readout_heads(
            x, mask, self.act_dim, self.d_model, self.has_critic,
            kw["norm"], kw["norm_eps"], kw["norm_zero_centred"],
            normed=S > 1, logit_divisor=self.logit_divisor)
        if idx is not None:
            return logits[:, 0], v[:, 0]
        return ((logits, v), tuple(new_cache)) if decode else (logits, v)


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


def _block_settings(arch: Mapping[str, Any]) -> dict:
    """The arch's values for ``TransformerBlock``'s shared fields."""
    kw = settings(BLOCK_KEYS, arch)
    moe_kw = {field: arch[k] for k, field in MOE_KEYS.items() if k in arch}
    if "held" in moe_kw:
        moe_kw["held"] = tuple(int(a) for a in moe_kw["held"])
    kw["moe_kw"] = flax.core.FrozenDict(moe_kw)
    return kw


def _operator_settings(arch: Mapping[str, Any]) -> dict:
    """Operator -> a block's ``cfg``: the arch's values for the operator's
    declared keys (and those of the operator it extends,
    ``arch_keys.OPERATOR_BASE``), beside what the trunk tells every layer —
    its head count and, under ``positions: "rope"``, the rotation's base
    (None: no rotation; the core clears it for the layers ``rope_layers``
    leaves out)."""
    positions = arch.get("positions", "learned")
    if positions not in ("learned", "rope", "none"):
        raise ValueError(f"unknown positions {positions!r} "
                         f"(learned | rope | none)")
    trunk = {"n_heads": int(arch.get("n_heads", 4)),
             "rope_theta": (float(arch.get("rope_theta", 10000.0))
                            if positions == "rope" else None)}
    return {op: {**settings(OPERATOR_KEYS.get(OPERATOR_BASE.get(op), {}),
                            arch), **settings(keys, arch), **trunk}
            for op, keys in OPERATOR_KEYS.items()}


def _make_core(arch: Mapping[str, Any], moe_experts: int = 0,
               fns: Mapping[str, Callable] | None = None) -> TransformerCore:
    """Arch -> TransformerCore module (shared by the policy builders and
    diagnostics like :func:`relayrl_tpu.models.moe.expert_utilization`,
    which re-applies the same module with captured intermediates)."""
    if fns is None:
        fns = layers.resolve(arch)[0]
    return TransformerCore(
        act_dim=int(arch["act_dim"]),
        d_model=int(arch.get("d_model", 128)),
        n_layers=int(arch.get("n_layers", 2)),
        mlp_ratio=int(arch.get("mlp_ratio", 4)),
        max_seq_len=int(arch.get("max_seq_len", 1024)),
        has_critic=bool(arch.get("has_critic", True)),
        compute_dtype=_compute_dtype(arch),
        moe_experts=moe_experts,
        moe_top_k=int(arch.get("moe_top_k", 2)),
        block_kw=flax.core.FrozenDict(_block_settings(arch)),
        op_cfg=flax.core.FrozenDict(_operator_settings(arch)),
        fns=flax.core.FrozenDict(fns),
        layer_types=tuple(arch.get("layer_types", ())),
        moe_dense_layers=int(arch.get("moe_dense_layers", 0)),
        sliding_window=arch.get("sliding_window"),
        rope_layers=tuple(bool(r) for r in arch.get("rope_layers", ())),
        positions=arch.get("positions", "learned"),
        loop_steps=int(arch.get("loop_steps", 1)),
        block_checkpoint=bool(arch.get("block_checkpoint", False)),
        embed_multiplier=float(arch.get("embed_multiplier", 1.0)),
        logit_divisor=float(arch.get("logit_divisor", 1.0)),
    )


def _build_core_policy(arch: Mapping[str, Any], moe_experts: int = 0) -> Policy:
    obs_dim = int(arch["obs_dim"])
    held = bool(arch.get("held_params", False))
    fns, records = layers.resolve(arch)
    core = _make_core(arch, moe_experts, fns)

    def init_params(rng):
        """The parameters as published, float32 — or, under the arch's
        ``held_params``, as a tier that only decodes the policy holds them
        (``base.hold_params``: under ``jit`` each cast fuses into the leaf's
        own initialiser, so the float32 tree is never whole). Such a policy
        refuses ``evaluate``, and the learner's build refuses the key
        (``base.apply_arch_overrides(..., learner=True)``)."""
        params = core.init(rng, jnp.zeros((1, 1, obs_dim), jnp.float32))
        return hold_params(policy, params) if held else params

    def init_cache(length: int, batch_size: int = 1):
        """Zeroed states for incremental decoding, one a pass and layer
        (``loop_steps * n_layers``, pass-major: a looped trunk's pass ``s``,
        layer ``l`` attends what pass ``s``, layer ``l`` wrote), each its
        operator's (``layers``): a (k, v) pair or a ring of rows, a
        convolution's last rows, a mixer's rows and float32 state — whose
        size does not grow with ``length`` —, nothing for an FFN alone."""
        def state(i: int):
            op = core.layer_parts(i)[0]
            return layers.OPERATORS[op].init_cache(
                core.op_cfg[op], core.d_model, batch_size, int(length),
                core.compute_dtype, core.layer_window(i))

        return tuple(state(i) for _ in range(core.loop_steps)
                     for i in range(core.n_layers))

    def step_cached(params, rng, cache, obs, t, mask=None, restart=False):
        """One O(W) decode step: writes position ``t`` into the cache and
        samples the action for it. Numerics match ``step_window`` at the
        same position (tests/test_kv_cache.py). ``restart`` (static): the
        cache is one an earlier sequence may have used, and a step at ``t``
        = 0 reads nothing of it (``Policy.cache_restarts``)."""
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
                                            cache=cache, t=t,
                                            restart=restart)
        logits_t, v_t = logits[:, 0], v[:, 0]
        act = jax.random.categorical(rng, logits_t, axis=-1)
        aux = {"logp_a": _categorical_logp(logits_t, act), "v": v_t}
        if obs.shape[0] == 1:
            act = act[0]
            aux = {k: a[0] for k, a in aux.items()}
        return act, aux, new_cache

    def prefill_cache(params, cache, window, n_valid=None, restart=False):
        """Rebuild the whole cache from a padded window in ONE dispatch
        (post-hot-swap path): runs decode mode with T = W queries at
        t=0. Padding rows write garbage K/V beyond the real prefix, which
        later per-step decodes never attend (their causal mask stops at
        the current t) and overwrite in order. A state without positions
        (a convolution's rows, a recurrence's) has nothing to overwrite,
        and a windowed layer's ring would lose live rows to padding ones:
        those are taken from the rows before ``n_valid``, the count of real
        rows (None: the whole window is real). ``restart``: ``cache`` is a
        used one (the fused tier rebuilds in place), whose state without
        positions the sequence must not continue from."""
        window = jnp.asarray(window)
        if window.ndim == 2:
            window = window[None]
        _, new_cache = core.apply(params, window, None, cache=cache, t=0,
                                  n_valid=n_valid, restart=restart)
        return new_cache

    policy = _policy_from_apply(
        arch, init_params, core.apply,
        apply_row_fn=lambda params, obs, mask, idx: core.apply(
            params, obs, mask, readout_t=idx))
    # the operator that brings a loss of its own, if a layer has one
    own = next((op for op in (layers.OPERATORS[core.layer_parts(i)[0]]
                              for i in range(core.n_layers))
                if hasattr(op, "OWN_LOSS")), None)
    own_loss = own and own.OWN_LOSS
    evaluate_stats = None
    if core.loop_steps > 1 and (moe_experts > 0 or own_loss):
        raise ValueError(
            "loop_steps > 1 over a trunk with experts or a loss of its own: "
            "what such a layer counts for the update (the expert load, the "
            "loss rows) is sown a layer, not a pass and layer")
    if moe_experts > 0 or own_loss:
        def evaluate_stats(params, obs, act, mask=None):
            """``evaluate`` plus what the same forward counted: the expert
            load (``moe.load_extremes`` of the sown group sizes) and, of a
            trunk that brings a loss (``Policy.own_loss``), its rows."""
            stats = {}

            def apply_fn(params, obs, mask):
                out, state = core.apply(params, obs, mask,
                                        mutable=["intermediates"])
                if moe_experts > 0:
                    from relayrl_tpu.models.moe import load_extremes

                    stats.update(load_extremes(state["intermediates"]))
                if own_loss:
                    stats.update(own.own_loss_stats(state["intermediates"],
                                                    obs.shape[1]))
                return out

            out = _policy_from_apply(arch, init_params, apply_fn).evaluate(
                params, obs, act, mask)
            return (*out, stats)

    restarts = all(
        getattr(layers.OPERATORS[core.layer_parts(i)[0]],
                "CACHE_RESTARTS", None) for i in range(core.n_layers))
    # (``init_params`` under ``held_params`` reads the cached step off this
    # name: the finished policy's)
    policy = dataclasses.replace(policy, init_cache=init_cache,
                                 step_cached=step_cached,
                                 prefill_cache=prefill_cache,
                                 cache_restarts=restarts,
                                 evaluate_stats=evaluate_stats,
                                 own_loss=own_loss, **records)
    if held:
        def evaluate(*_args, **_kwargs):
            raise ValueError(
                "held_params: this policy's parameters are a decode-only "
                "tier's (matmul weights at the compute type); evaluate is "
                "the learner's forward, whose master weights are float32 — "
                "build the learner's policy without the key")

        policy = dataclasses.replace(
            policy, evaluate=evaluate,
            evaluate_stats=evaluate_stats and evaluate)
    return policy


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
    new = [k for k in DECLARED if k in arch]
    if new:
        raise ValueError(
            f"transformer_pp_discrete builds the GPT-2 shaped block only "
            f"and does not take {new}; use transformer_discrete / "
            f"transformer_moe_discrete for these")
    obs_dim = int(arch["obs_dim"])
    d_model = int(arch.get("d_model", 128))
    n_layers = int(arch.get("n_layers", 2))
    n_micro = arch.get("pp_microbatches")
    fns, records = layers.resolve(arch, ("attention",))
    block = TransformerBlock(
        d_model, int(arch.get("mlp_ratio", 4)), _compute_dtype(arch),
        cfg=_operator_settings(arch)["attention"], fns=fns)
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

    return dataclasses.replace(
        _policy_from_apply(arch, init_params, apply_fn), **records)
