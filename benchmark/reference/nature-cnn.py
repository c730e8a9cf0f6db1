"""Plain reference for ``nature-cnn``: Mnih et al. 2015's trunk (32x8x8/4,
64x4x4/2, 64x3x3/1, dense 512, ReLU, VALID padding, frames scaled by 1/255)
with a policy head and a value head, in float32 ``jax.lax`` /
``jax.numpy`` at precision "highest". Nothing from ``relayrl_tpu/models``;
it reads the system's parameter tree as data.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark import flops


def program_kwargs(cfg: dict) -> dict:
    """The configuration in the program's own hyper-parameter names."""
    return {"obs_shape": cfg["obs_shape"], "conv_spec": cfg["conv_spec"],
            "dense": cfg["dense"], "scale_obs": cfg["scale_obs"]}


def train_flops_per_sample(cfg: dict, seq_len: int) -> float:
    return flops.TRAIN_OVER_FWD * flops.cnn_fwd_flops(
        1, cfg["obs_shape"], cfg["conv_spec"], cfg["dense"], cfg["act_dim"])


def _dense(p, x):
    return x @ p["kernel"].astype(jnp.float32) + p["bias"].astype(
        jnp.float32)



def _forward_impl(p, frames, strides):
    x = frames
    for i, stride in enumerate(strides):
        layer = p["trunk"][f"conv_{i}"]
        x = jax.lax.conv_general_dilated(
            x, layer["kernel"].astype(jnp.float32), (stride, stride),
            "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST)
        x = jax.nn.relu(x + layer["bias"])
    x = jax.nn.relu(_dense(p["trunk"]["trunk_dense"],
                           x.reshape(x.shape[0], -1)))
    return (jax.nn.log_softmax(_dense(p["pi_head"], x), -1),
            _dense(p["vf_head"], x)[..., 0])


_forward = jax.jit(_forward_impl, static_argnums=2)


def forward(params, obs, cfg: dict):
    """``obs [..., H*W*C]`` (raw 0..255 values) -> (log-probabilities
    ``[..., act_dim]``, values ``[...]``)."""
    h, w, c = cfg["obs_shape"]
    lead = obs.shape[:-1]
    frames = jnp.asarray(obs, jnp.float32).reshape(-1, h, w, c)
    if cfg["scale_obs"]:
        frames = frames / 255.0
    strides = tuple(int(s) for _f, _k, s in cfg["conv_spec"])
    with jax.default_matmul_precision("highest"):
        logp, v = _forward(params["params"], frames, strides)
    return logp.reshape(*lead, -1), v.reshape(lead)
