"""Model registry + the policy ABI.

The reference's model ABI is a TorchScript module exporting ``step(obs, mask)
-> (act, {logp_a, v})`` plus ``get_input_dim``/``get_output_dim``, validated
by a dummy forward on every load (reference: relayrl_framework/src/native/
python/algorithms/REINFORCE/kernel.py:99-143 and src/network/client/
agent_wrapper.rs:88-168). TorchScript ships code; JAX params are data-only,
so here the ABI is an **architecture config** (a JSON-able dict) resolved
through this registry into a :class:`Policy` — a bundle of pure functions
that run identically on the TPU learner and on CPU actor hosts (SURVEY.md
§7.4 item 2).

Arch config schema::

    {"kind": "<registry key>", "obs_dim": int, "act_dim": int, ...}
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from relayrl_tpu.models.arch_keys import DECLARED, TRUNK_KEYS

_REGISTRY: dict[str, Callable[[Mapping[str, Any]], "Policy"]] = {}


def register_model(kind: str):
    def deco(builder):
        _REGISTRY[kind] = builder
        return builder
    return deco


def build_policy(arch: Mapping[str, Any]) -> "Policy":
    kind = arch.get("kind")
    if kind not in _REGISTRY:
        raise ValueError(f"unknown model kind {kind!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[kind](arch)


# Model-shape hyperparams that algorithms forward verbatim from their
# hyperparam dict into the arch config when present, so any policy family
# (e.g. model_kind="transformer_discrete" with d_model/n_layers/attention)
# is reachable through the algorithm ctor without per-algorithm plumbing:
# the sequence trunk's own keys and every key its layers declare
# (models/arch_keys.py, where each is written once).
ARCH_PASSTHROUGH_KEYS = TRUNK_KEYS + DECLARED


def apply_arch_overrides(arch: dict, params: Mapping[str, Any],
                         learner: bool = False) -> dict:
    """Copy any present ARCH_PASSTHROUGH_KEYS from hyperparams into arch.

    Algorithms call this once, right before ``build_policy(self.arch)``,
    with ``learner=True``: a key that belongs to a tier that only decodes
    (``held_params``) is refused there. Sequence-model keys on a
    non-sequence kind almost always mean a forgotten ``model_kind`` — warn
    instead of silently training the default MLP with the overrides ignored.
    """
    copied = [k for k in ARCH_PASSTHROUGH_KEYS if k in params]
    for key in copied:
        arch[key] = params[key]
    if learner and arch.get("held_params"):
        raise ValueError(
            "held_params is a decode-only tier's key: init_params would "
            "hand the learner its matmul weights at the compute type, and "
            "it would train them as master weights. Build the learner's "
            "policy without it")
    kind = str(arch.get("kind", ""))
    if copied and (kind.startswith("mlp") or kind.startswith("cnn")):
        import warnings

        warnings.warn(
            f"model overrides {copied} have no effect on model kind "
            f"{kind!r} — did you forget model_kind="
            f"\"transformer_discrete\" (or another sequence kind)?",
            stacklevel=2)
    return arch


@dataclasses.dataclass(frozen=True)
class Policy:
    """Pure-function policy bundle.

    * ``init_params(rng) -> params``
    * ``step(params, rng, obs, mask) -> (act, aux)`` — sampling forward;
      ``aux`` always contains ``logp_a`` and ``v`` (v=0 without a critic),
      mirroring the reference's step ABI. Works on single obs ``[obs_dim]``
      or batches ``[..., obs_dim]``.
    * ``evaluate(params, obs, act, mask) -> (logp, entropy, v)`` — the
      learner-side forward for loss computation on ``[..., obs_dim]``.
    * ``mode(params, obs, mask) -> act`` — deterministic action (greedy).
    * ``step_window(params, rng, window, t, mask) -> (act, aux)`` —
      optional, sequence policies only: act from a fixed-size
      right-zero-padded observation window ``[W, obs_dim]`` whose first
      ``t`` rows are real. One jit signature regardless of history length
      (causal attention never attends past the read position, so the
      padding is inert). PolicyActor uses this to serve sequence policies
      with real context instead of context-1 per request.
    """

    arch: dict[str, Any]
    init_params: Callable
    step: Callable
    evaluate: Callable
    mode: Callable
    step_window: Callable | None = None
    mode_window: Callable | None = None
    # KV-cache incremental serving (sequence policies): ``init_cache(W,
    # batch_size) -> cache`` and ``step_cached(params, rng, cache, obs, t,
    # mask) -> (act, aux, new_cache)`` — O(W) per step vs step_window's
    # full-window recompute. Numerics identical to step_window while
    # t < W (PolicyActor falls back to the window path past that, and
    # replays the window to rebuild the cache after a model hot-swap).
    init_cache: Callable | None = None
    step_cached: Callable | None = None
    # ``prefill_cache(params, cache, window, n_valid=None) -> cache``
    # rebuilds the whole cache from the padded window in one dispatch (used
    # after hot-swaps); ``n_valid`` = the window's count of real rows, which
    # a state without positions (a short convolution's) is taken before.
    prefill_cache: Callable | None = None
    # Whether a new sequence may start over a used cache: under
    # ``step_cached(..., restart=True)`` (and ``prefill_cache`` likewise) a
    # step at ``t`` = 0 reads nothing an earlier sequence left there, for
    # every layer's state — rows at their positions, of which a step at
    # ``t`` reads rows <= ``t`` alone (attention's (k, v)), or a state
    # without positions that position 0 then reads as zeros (a convolution's
    # last rows, a recurrence's state: ``layers.CACHE_RESTARTS``). Without
    # the flag the programs are those of a fresh cache. The fused rollout's
    # scan carry holds a cache only where this is True: an in-scan episode
    # end clears nothing.
    cache_restarts: bool = False
    # Sequence policies: ``{(T, head_dim, dtype): backend}`` for every
    # attention shape traced so far (models/layers/attention.resolve fills
    # it at trace time) — which implementation a platform-dependent
    # ``attention`` config actually compiled to. None for other families.
    # (A windowed layer runs the backend of its shape: no key of its own.)
    attention_backends: Mapping[tuple, str] | None = None
    # Beside it, for the shapes that resolved to ``flash_pallas``: the share
    # (%) of the T x T score matrix the flash kernels compute at that shape's
    # tiling (``ops.flash.score_area_pct``; a causal kernel that skipped
    # everything above the diagonal would read 50 + 50 / T). A windowed
    # layer's entry is keyed ``(T, head_dim, dtype, window)``, beside the
    # global layers' of the same shape, here and in ``attention_layout``.
    attention_score_area_pct: Mapping[tuple, float] | None = None
    # ...and the operand layout they ran in (``ops.flash.lane_layout``):
    # ``"2 heads a step"`` of the projections' own ``[B, T, H * D]``
    # (head_dim 64), or ``"head-major"`` (``[B * H, T, D]``, the head axis
    # transposed out of the lanes round the kernels).
    attention_layout: Mapping[tuple, str] | None = None
    # Sequence policies with Mamba-2 layers: ``{(T, heads, head_dim, state,
    # dtype): "ssd_pallas" | "ssd_xla"}`` for every scan shape traced so far
    # (models/layers/recurrent.kernel) — whether ``ops/ssd.py`` ran the
    # Pallas kernels (a TPU, shapes that tile) or plain XLA. Empty for a
    # trunk without such layers, None for other families.
    scan_backends: Mapping[tuple, str] | None = None
    # Sequence policies with linear-attention layers: ``{(T, value heads,
    # key width, value width, dtype): "gdn_pallas" | "gdn_xla"}`` for every
    # delta-rule shape traced so far (models/layers/recurrent.kernel) —
    # whether ``ops/gdn.py`` ran the Pallas kernels (a TPU, shapes that
    # tile) or plain XLA. Empty for a trunk without such layers, None for
    # other families.
    gdn_backends: Mapping[tuple, str] | None = None
    # ...and with Kimi Delta Attention layers: ``{(T, heads, key width,
    # value width, dtype): "kda_pallas" | "kda_xla"}`` for every shape of
    # the delta rule under a decay a key lane traced so far — whether
    # ``ops/kda.py`` ran the Pallas kernels (a TPU, shapes that tile) or
    # plain XLA. Empty for a trunk without such layers, None for other
    # families.
    kda_backends: Mapping[tuple, str] | None = None
    # Sequence policies with latent-attention layers that rotate (``positions:
    # "rope"``): ``{("latent_attention", "experts" | "dense"): "columns"}``
    # for every kind of such layer traced so far (models/layers/mla.py) —
    # the nope / rope split and the rotation's pairing are taken on the
    # projections' columns at use, and the rotation walks the rotary lanes
    # alone. No entry for a latent layer that rotates nothing; None for
    # other families.
    latent_rope: Mapping[tuple, str] | None = None
    # Sequence policies with Mamba-2 or linear-attention layers: ``{(T,
    # columns, taps, continues from a cache's rows, dtype): "conv_pallas" |
    # "conv_xla"}`` for every shape of the mixers' depthwise convolution
    # traced so far (models/layers/recurrent.kernel) — whether
    # ``ops/conv.py`` ran the Pallas kernels (a TPU, a sequence's start,
    # shapes that tile) or plain XLA. Empty for a trunk without such
    # layers, None for other families.
    conv_backends: Mapping[tuple, str] | None = None
    # Sequence policies with sparse-attention layers: ``{(T, head_dim, index
    # heads, index head_dim, topk, dtype): "select_pallas+masked_pallas" |
    # "bisect_select+masked_xla" | ...}`` for every full-mode shape traced so
    # far (models/layers/sparse_attention.py) — whether ``ops/sparse_attn.py``
    # ran the indexer's scores and selection (before the ``+``) and the
    # attention over the selected keys (after it) as the Pallas kernels (a
    # TPU, shapes that tile) or as plain XLA. Empty for a trunk without such
    # layers, None for other families.
    index_backends: Mapping[tuple, str] | None = None
    # Sequence policies with expert layers: ``{(N*k slots, rows of the
    # buffers, held, experts, k): "counted" | "sorted" | "plain"}`` for
    # every shape of the sparse dispatch traced so far
    # (models/moe.dispatch_form) — a held-experts layer counts its held
    # choices into expert order where its shapes pay for it
    # (models/moe.held_form) and sorts all N*k slots with the absent
    # experts' behind where they do not; a layer that holds every expert
    # sorts all N*k. Empty for a trunk without experts (and for the dense
    # dispatch), None for other families.
    moe_backends: Mapping[tuple, str] | None = None
    # Sequence policies under ``block_checkpoint``: ``{(operator, "experts" |
    # "dense" | "none"): {name: bytes}}`` for every distinct kind of layer a
    # gradient was traced through so far (models/layers/block.py) — what the
    # checkpoint round that layer keeps by name beside the layer's input,
    # and the bytes each value holds at the traced shape (one application).
    # Empty without the key (and before a gradient), None for other families.
    checkpoint_kept: Mapping[tuple, Mapping[str, int]] | None = None
    # MoE and sparse-attention trunks: ``evaluate_stats(params, obs, act,
    # mask) -> (logp, entropy, v, stats)`` — ``evaluate`` plus what the same
    # forward counted (``moe_load_max`` / ``moe_load_min``:
    # models/moe.load_extremes; ``index_kept_pct``) for the
    # update's metrics. None for every other family.
    evaluate_stats: Callable | None = None
    # THE seam for a loss the model itself brings: the name an update
    # reports it under (``"IndexLoss"``: a sparse-attention trunk's indexers
    # learn from nothing else), None for a model without one. With it set,
    # ``evaluate_stats``' stats hold ``own_loss_rows`` ``[B, T]``, the term
    # row by row summed over the layers; an update adds its mean over the
    # valid rows to its loss (algorithms/impala.py), and an algorithm that
    # would drop it refuses the policy (``build_algorithm``).
    own_loss: str | None = None

    @property
    def input_dim(self) -> int:
        return int(self.arch["obs_dim"])

    @property
    def output_dim(self) -> int:
        return int(self.arch["act_dim"])

    # -- reference getter parity --
    def get_input_dim(self) -> int:
        return self.input_dim

    def get_output_dim(self) -> int:
        return self.output_dim


def validate_policy(policy: Policy, params) -> None:
    """Dummy-forward validation on load (ref: agent_wrapper.rs:88-168 runs a
    zero-obs ``step`` and asserts the output shape/aux dict)."""
    obs_shape = policy.arch.get("obs_shape") or (policy.input_dim,)
    obs = jnp.zeros(tuple(obs_shape), dtype=jnp.float32)
    mask = jnp.ones((policy.output_dim,), dtype=jnp.float32)
    act, aux = policy.step(params, jax.random.PRNGKey(0), obs, mask)
    if not isinstance(aux, dict) or "logp_a" not in aux:
        raise ValueError("policy step ABI violation: aux dict missing 'logp_a'")
    act_arr = np.asarray(act)
    if act_arr.ndim > 1:
        raise ValueError(f"policy step returned act of rank {act_arr.ndim} for single obs")


def held_dtypes(policy: Policy, params):
    """``params``' tree of the dtype a tier that only DECODES the policy
    holds each leaf at: the narrower float type where every use of the leaf
    in the cached step is a cast to it (a matmul weight under a bfloat16
    compute type), the leaf's own everywhere else (what is used in float32:
    norm scales, a recurrence's ``A_log`` / ``dt_bias`` / ``D``, a
    convolution's taps and bias, the observation embedding, the heads).
    Read off ``policy.step_cached`` as traced — the program such a tier
    compiles —, so a step's operands are the same numbers either way. Only
    the step's own equations are read: a leaf that a call takes whole (or a
    policy without a cached step) stays as published, which is always
    right, and a leaf already held is one the step no longer casts."""
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype), params)
    as_is = jax.tree.map(lambda x: np.dtype(x.dtype), shapes)
    if policy.step_cached is None or policy.init_cache is None:
        return as_is
    obs = jnp.zeros((policy.input_dim,), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda p: policy.step_cached(
        p, jax.random.PRNGKey(0), policy.init_cache(1), obs, 0))(shapes).jaxpr
    leaves, treedef = jax.tree.flatten(shapes)
    uses = {id(var): [] for var in jaxpr.invars}    # a Literal has no hash
    for eqn in jaxpr.eqns:
        for var in eqn.invars:
            if id(var) in uses:
                uses[id(var)].append(
                    np.dtype(eqn.params["new_dtype"])
                    if eqn.primitive.name == "convert_element_type" else None)
    for var in jaxpr.outvars:
        if id(var) in uses:
            uses[id(var)].append(None)

    def held(var, leaf):
        to = set(uses[id(var)])
        if len(to) != 1 or None in to:
            return np.dtype(leaf.dtype)
        to, = to
        narrower = (jnp.issubdtype(to, jnp.floating)
                    and jnp.issubdtype(leaf.dtype, jnp.floating)
                    and to.itemsize < leaf.dtype.itemsize)
        return to if narrower else np.dtype(leaf.dtype)

    return jax.tree.unflatten(treedef, [
        held(var, leaf) for var, leaf in zip(jaxpr.invars, leaves)])


def hold_params(policy: Policy, params, dtypes=None):
    """``params`` with each leaf at its :func:`held_dtypes` entry
    (``dtypes``: that tree, where the caller kept it), cast LEAF BY LEAF: a
    leaf already there is passed on as it is, and the published float32 tree
    is never whole on the device beside the held one."""
    if dtypes is None:
        dtypes = held_dtypes(policy, params)
    return jax.tree.map(
        lambda x, to: x if x.dtype == to else jnp.asarray(x).astype(to),
        params, dtypes)


def mlp_sizes(arch: Mapping[str, Any]) -> tuple[int, ...]:
    return tuple(int(h) for h in arch.get("hidden_sizes", (128, 128)))
