"""Operations ONE NEW env step of one lane needs from a decoder-only policy
trunk, from its shapes alone: what a step costs whatever implements it.

A step at position ``t`` (the ``t``-th observation of the lane's episode,
counted from 1) needs every weight matrix once for its one new row and the
scores of that row against the ``t`` keys it may see (itself included):

* per layer QKVO ``8 d^2``, the MLP ``4 d n_inner`` and the attention
  ``4 d t`` (q.k over ``t`` keys ``2 d t``, p.v the same);
* the observation embedding ``2 obs d``, the policy head ``2 d act`` and
  the value head the program emits beside every action
  (``vf_head_up`` ``2 d^2``, ``vf_head`` ``2 d``).

Matmul terms only (2 x multiply-adds), as ``benchmark/flops.py``. The count
does NOT depend on how the program gets there: a program that runs the whole
window of ``W`` rows again for every step (``runtime/anakin``'s scan over
``step_window`` today) executes some ``W`` times this and is credited with
this, so ``mfu_pct.rollout`` reads what share of the chip's peak went into
work a step needs. The count is linear in ``t``, so the mean over the
positions a window ran is the count at their mean.
"""

from __future__ import annotations


def rollout_flops_per_step(cfg: dict, t: float) -> float:
    """``cfg``: a GPT-2-keyed configuration file (``n_embd``, ``n_layer``,
    ``n_inner``, ``obs_dim``, ``act_dim``); ``t``: keys the new row sees,
    1 <= t <= ``n_positions`` (a mean over positions may be fractional)."""
    d, layers = int(cfg["n_embd"]), int(cfg["n_layer"])
    inner, obs, act = int(cfg["n_inner"]), int(cfg["obs_dim"]), int(
        cfg["act_dim"])
    if not 1 <= t <= int(cfg["n_positions"]):
        raise ValueError(f"position {t} outside 1..{cfg['n_positions']}")
    per_layer = 8 * d * d + 4 * d * inner + 4 * d * t
    embed_heads = 2 * obs * d + 2 * d * act + 2 * d * d + 2 * d
    return layers * per_layer + embed_heads
