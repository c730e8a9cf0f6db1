"""Operations and bytes of ``granite4h-micro-policy``'s rollout step, from its
shapes alone (beside ``flops_rollout.py``, which counts a GPT-2 keyed trunk;
the same rules: matmul terms only, 2 x multiply-adds, what a step NEEDS and
not what a program executes).

ONE NEW env step of one lane at position ``t`` (the ``t``-th observation of
the lane's episode, counted from 1) needs every weight matrix once for its
one new row:

* a ``mamba`` layer: the input projection ``2 d (2 inner + 2 N + H)``, the
  output projection ``2 inner d``, and the recurrence's two products over the
  ``[H, P, N]`` state — ``dt x (x) B`` into it and ``C . h`` out of it, ``2 H
  P N`` each (the decay's multiply and the convolution's four taps are
  element-wise and not counted) — whatever the position: a state-space step
  costs the same at every ``t``;
* an ``attention`` layer: q and the output projection ``2 d d`` each, k and
  v ``2 d (kv heads x head)`` each, and the new row's scores and values over
  the ``t`` keys it may see, ``4 d t``;
* every layer's SwiGLU: three matrices, ``6 d ff``;
* the observation embedding ``2 obs d``, the policy head ``2 d act`` and the
  value head the program emits beside every action (``2 d d + 2 d``).

Linear in ``t``, so the mean over the positions a window ran is the count at
their mean. :func:`ssm_step_bytes` is what the recurrence's step must MOVE:
every lane's float32 state read once and written once in each ``mamba``
layer. The state is the only operand of that step that does not fit on the
chip's fast memory beside its weights' tiles; its ``x``, ``B``, ``C`` and
``dt`` are a few KB a lane.
"""

from __future__ import annotations


def layer_counts(cfg: dict) -> tuple[int, int]:
    """(``mamba`` layers, ``attention`` layers) of ``layer_types``."""
    kinds = list(cfg["layer_types"])
    return kinds.count("mamba"), kinds.count("attention")


def rollout_flops_per_step(cfg: dict, t: float) -> float:
    """``cfg``: the configuration file; ``t``: keys the new row sees, 1 <= t
    <= ``positions_as_run`` (a mean over positions may be fractional)."""
    d, ff = int(cfg["hidden_size"]), int(cfg["shared_intermediate_size"])
    heads, width = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    state = int(cfg["mamba_d_state"])
    inner = heads * width
    kv = (int(cfg["num_key_value_heads"]) * d
          // int(cfg["num_attention_heads"]))
    obs, act = int(cfg["obs_dim"]), int(cfg["act_dim"])
    if not 1 <= t <= int(cfg["positions_as_run"]):
        raise ValueError(f"position {t} outside 1.."
                         f"{cfg['positions_as_run']}")
    n_mamba, n_attn = layer_counts(cfg)
    mamba = (2 * d * (2 * inner + 2 * int(cfg["mamba_n_groups"]) * state
                      + heads)
             + 2 * inner * d + 4 * heads * width * state)
    attention = 4 * d * d + 4 * d * kv + 4 * d * t
    mlp = 6 * d * ff
    embed_heads = 2 * obs * d + 2 * d * act + 2 * d * d + 2 * d
    return (n_mamba * mamba + n_attn * attention + (n_mamba + n_attn) * mlp
            + embed_heads)


def ssm_step_bytes(cfg: dict, lanes: int) -> float:
    """Bytes ONE scan step's recurrences must move over ``lanes`` lanes: the
    float32 ``[H, P, N]`` state of every ``mamba`` layer read once and
    written once."""
    state = (int(cfg["mamba_n_heads"]) * int(cfg["mamba_d_head"])
             * int(cfg["mamba_d_state"]) * 4)
    return 2.0 * lanes * layer_counts(cfg)[0] * state
