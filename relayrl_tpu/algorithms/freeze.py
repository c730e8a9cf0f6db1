"""Frozen-layer optimizer masks (``learner.freeze``): first-class
config for fine-tune-style runs that train the heads and upper blocks
over a frozen trunk.

``learner.freeze`` is a regex (or list of regexes) matched against
"/"-joined parameter leaf paths (e.g. ``params/block_0/qkv/kernel``);
the config loader validates it (``config/loader.normalize_freeze_spec``).
Matching leaves are partitioned to ``optax.set_to_zero()`` via
``optax.multi_transform`` — NOT ``optax.masked``, which passes raw
gradients through for unmasked leaves and silently moves the "frozen"
params (caught in PR 5). Frozen leaves are therefore
bit-identical across any number of updates, which is also what makes
them free on the wire: model-wire v2's delta encoder skips unchanged
leaves outright, so every frozen leaf lands in
``relayrl_wire_publish_bytes_saved_total`` on every publish.

Consumers: the on-policy family (IMPALA's single optimizer chain;
REINFORCE/PPO's pi/vf partition grows a third "freeze" label). The
chosen patterns + frozen-leaf accounting ride every checkpoint's JSON
extras (``freeze`` key) so a resume can verify the mask it restores
under (checkpoint/manager.py).
"""

from __future__ import annotations

import re
from typing import Any, Sequence

import jax


def leaf_path(path) -> str:
    """One KeyPath -> the "/"-joined string form patterns match against
    (flax dict trees yield e.g. ``params/block_0/qkv/kernel``)."""
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def freeze_info(params, patterns: Sequence[str]) -> dict[str, Any]:
    """Accounting for checkpoints/telemetry: which patterns, how many
    leaves/bytes they froze, and the frozen paths themselves (sorted) —
    the checkpoint extras surface (``extra["freeze"]``) and what the
    wire-v2 savings claim is audited against."""
    compiled = [re.compile(p) for p in patterns]
    frozen, total, frozen_bytes = [], 0, 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        total += 1
        name = leaf_path(path)
        if any(c.search(name) for c in compiled):
            frozen.append(name)
            frozen_bytes += getattr(leaf, "nbytes", 0)
    return {
        "patterns": list(patterns),
        "frozen_leaves": len(frozen),
        "total_leaves": total,
        "frozen_bytes": int(frozen_bytes),
        "frozen_paths": sorted(frozen),
    }


def freeze_labels(params, patterns: Sequence[str], base_labels=None):
    """Label pytree for ``optax.multi_transform``: frozen leaves get
    ``"freeze"``; the rest keep ``base_labels`` (an existing partition —
    REINFORCE/PPO's pi/vf labels) or ``"train"`` when None."""
    compiled = [re.compile(p) for p in patterns]

    def label(path, _leaf, base):
        name = leaf_path(path)
        if any(c.search(name) for c in compiled):
            return "freeze"
        return base

    if base_labels is None:
        return jax.tree_util.tree_map_with_path(
            lambda p, leaf: label(p, leaf, "train"), params)
    return jax.tree_util.tree_map_with_path(label, params, base_labels)


def masked_optimizer(tx, params, patterns: Sequence[str]):
    """Wrap a whole-tree optimizer so leaves matching ``patterns`` never
    move: ``multi_transform({train: tx, freeze: set_to_zero})``. No-op
    (returns ``tx``) with empty patterns, so call sites stay
    unconditional."""
    import optax

    patterns = tuple(patterns or ())
    if not patterns:
        return tx
    return optax.multi_transform(
        {"train": tx, "freeze": optax.set_to_zero()},
        freeze_labels(params, patterns))
