"""Convolutional (Atari-class) actor-critic policies.

The reference has no pixel models (its only kernels are 2×128 MLPs —
reference: relayrl_framework/src/native/python/algorithms/REINFORCE/
kernel.py:12-84), but the driver's north-star configs require a CNN pixel
policy for PPO Atari Pong and IMPALA Breakout (BASELINE.md). This is the
Nature-DQN trunk as a flax module: three convs + a 512 dense, shared
between the categorical policy head and the value head.

Compute notes (TPU): convs run in the configured compute dtype (bf16 feeds
the MXU's conv path); the trunk is shared between pi and vf heads (unlike
the MLP family's separate trunks) because conv features dominate FLOPs —
one trunk halves HBM traffic. Observations arrive as flat wire vectors and
are reshaped to ``(H, W, C)`` NHWC inside the module, so the transport/codec
layer stays rank-agnostic.
"""

from __future__ import annotations

from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp
from flax import linen as nn

from relayrl_tpu.models.base import Policy, register_model
from relayrl_tpu.models.mlp import (
    _MASK_FILL,
    _categorical_entropy,
    _categorical_logp,
    _compute_dtype,
)
from relayrl_tpu.ops.scopes import CONV, HEADS, OBS_PREP

# (features, kernel, stride) — the Nature-DQN trunk.
NATURE_CONV = ((32, 8, 4), (64, 4, 2), (64, 3, 1))

# TPU-native trunk: same geometry (kernels/strides/receptive field) as the
# Nature trunk, channel widths raised to MXU-lane multiples (64/128). The
# Nature widths are shape-hostile to a 128x128 systolic array — conv1's
# 32 output channels occupy <=25% of the lanes on ~40% of the FLOPs
# (docs/parallelism.md roofline section). This spec spends ~4x the
# arithmetic of NATURE_CONV but maps it where the MXU can actually retire
# it; pick it with ``conv_spec="tpu"`` in the arch/hyperparams.
TPU_CONV = ((64, 8, 4), (128, 4, 2), (128, 3, 1))

CONV_PRESETS = {"nature": NATURE_CONV, "tpu": TPU_CONV}


def resolve_conv_spec(spec) -> tuple:
    """Resolve a conv spec that may be a preset name ("nature"/"tpu") or an
    explicit ((features, kernel, stride), ...) sequence."""
    if isinstance(spec, str):
        try:
            return CONV_PRESETS[spec.lower()]
        except KeyError:
            raise ValueError(
                f"unknown conv preset {spec!r}; known: {sorted(CONV_PRESETS)}"
            ) from None
    return tuple(tuple(int(x) for x in row) for row in spec)


def validate_conv_spec(obs_shape, conv_spec) -> None:
    """Fail fast when a conv stack collapses the feature map to nothing
    (VALID padding): with the Nature trunk anything under ~36 px dies at
    the third layer, and the eventual failure is an opaque
    ZeroDivisionError inside the initializer. Raises with per-layer sizes
    so the user can shrink the spec or grow the frame."""
    h, w = int(obs_shape[0]), int(obs_shape[1])
    sizes = [(h, w)]
    for feat, kern, stride in conv_spec:
        h = (h - int(kern)) // int(stride) + 1
        w = (w - int(kern)) // int(stride) + 1
        sizes.append((h, w))
        if h <= 0 or w <= 0:
            raise ValueError(
                f"conv_spec {tuple(map(tuple, conv_spec))} collapses a "
                f"{obs_shape[0]}x{obs_shape[1]} frame to {h}x{w} (layer "
                f"sizes {sizes}); use a larger frame (Nature trunk needs "
                f">= 36 px) or a shallower conv_spec")


class ConvTrunk(nn.Module):
    obs_shape: Sequence[int]  # (H, W, C)
    conv_spec: Sequence[Sequence[int]] = NATURE_CONV
    dense: int = 512
    scale_obs: bool = True
    compute_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        # Accept flat wire obs [..., H*W*C] (the transport format) or
        # already-shaped [..., H, W, C]; run convs on [N, H, W, C].
        shape = tuple(self.obs_shape)
        flat_dim = shape[0] * shape[1] * shape[2]
        if x.shape[-1] == flat_dim:
            batch_shape = x.shape[:-1]
        elif x.shape[-3:] == shape:
            batch_shape = x.shape[:-3]
        else:
            raise ValueError(
                f"obs trailing shape {x.shape} matches neither ({flat_dim},) "
                f"nor {shape}")
        # the trunk's two parts on the device (ops/scopes.py): the frames'
        # way in, and the convolutions with their dense layer
        with jax.named_scope(OBS_PREP):
            x = (x.reshape((-1,) + shape) if batch_shape
                 else x.reshape((1,) + shape))
            x = x.astype(self.compute_dtype)
            if self.scale_obs:
                x = x / jnp.asarray(255.0, self.compute_dtype)
        with jax.named_scope(CONV):
            for i, (feat, kern, stride) in enumerate(self.conv_spec):
                x = nn.Conv(feat, (kern, kern), strides=(stride, stride),
                            padding="VALID", dtype=self.compute_dtype,
                            name=f"conv_{i}")(x)
                x = nn.relu(x)
            x = x.reshape(x.shape[0], -1)
            x = nn.relu(nn.Dense(self.dense, dtype=self.compute_dtype,
                                 name="trunk_dense")(x))
            if not batch_shape:
                return x[0]
            return x.reshape(*batch_shape, -1)


class ConvActorCritic(nn.Module):
    act_dim: int
    obs_shape: Sequence[int]
    conv_spec: Sequence[Sequence[int]] = NATURE_CONV
    dense: int = 512
    scale_obs: bool = True
    has_critic: bool = True
    compute_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, obs, mask=None):
        feats = ConvTrunk(self.obs_shape, self.conv_spec, self.dense,
                          self.scale_obs, self.compute_dtype,
                          name="trunk")(obs)
        with jax.named_scope(HEADS):
            logits = nn.Dense(self.act_dim, dtype=self.compute_dtype,
                              name="pi_head")(feats)
            logits = logits.astype(jnp.float32)
            if mask is not None:
                logits = jnp.where(mask > 0, logits, _MASK_FILL)
            if self.has_critic:
                v = nn.Dense(1, dtype=self.compute_dtype,
                             name="vf_head")(feats)
                v = jnp.squeeze(v.astype(jnp.float32), axis=-1)
            else:
                v = jnp.zeros(logits.shape[:-1], dtype=jnp.float32)
        return logits, v


@register_model("cnn_discrete")
def build_cnn_discrete(arch: Mapping[str, Any]) -> Policy:
    obs_shape = tuple(int(d) for d in arch["obs_shape"])
    if len(obs_shape) != 3:
        raise ValueError(f"cnn_discrete needs obs_shape (H, W, C), got {obs_shape}")
    conv_spec = resolve_conv_spec(arch.get("conv_spec", NATURE_CONV))
    validate_conv_spec(obs_shape, conv_spec)
    obs_dim = int(jnp.prod(jnp.array(obs_shape)))
    arch = dict(arch)
    arch.setdefault("obs_dim", obs_dim)
    # The policy's own copy states the default as resolved: the on-policy
    # learner reads it to tell a byte-frame stream before any data.
    arch.setdefault("scale_obs", True)
    if int(arch["obs_dim"]) != obs_dim:
        raise ValueError(
            f"obs_dim {arch['obs_dim']} != prod(obs_shape) {obs_dim}")

    module = ConvActorCritic(
        act_dim=int(arch["act_dim"]),
        obs_shape=obs_shape,
        conv_spec=conv_spec,
        dense=int(arch.get("dense", 512)),
        scale_obs=bool(arch["scale_obs"]),
        has_critic=bool(arch.get("has_critic", True)),
        compute_dtype=_compute_dtype(arch),
    )

    def init_params(rng):
        return module.init(rng, jnp.zeros((1, obs_dim), jnp.float32))

    def step(params, rng, obs, mask=None):
        logits, v = module.apply(params, obs, mask)
        act = jax.random.categorical(rng, logits, axis=-1)
        logp = _categorical_logp(logits, act)
        return act, {"logp_a": logp, "v": v}

    def evaluate(params, obs, act, mask=None):
        logits, v = module.apply(params, obs, mask)
        with jax.named_scope(HEADS):
            return (_categorical_logp(logits, act),
                    _categorical_entropy(logits), v)

    def mode(params, obs, mask=None):
        logits, _ = module.apply(params, obs, mask)
        return jnp.argmax(logits, axis=-1)

    return Policy(arch=arch, init_params=init_params, step=step,
                  evaluate=evaluate, mode=mode)
