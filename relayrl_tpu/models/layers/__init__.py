"""The sequence trunk's operators, one module each, behind one table.

A layer of a trunk is an operator and, after it, an FFN (dense, or the
expert layer of :mod:`relayrl_tpu.models.moe`) — or one of the two alone,
``x + part(norm(x))``. :data:`LAYER_KINDS` maps an arch's ``layer_types``
entry to the pair; :data:`OPERATORS` maps an operator's name to its module.
Every operator module exports the same interface, and ``models/
transformer.py`` asks the table and nothing else of this package:

* ``apply(block, x, cache, t, readout_idx, n_valid)`` — the layer, in
  ``block``'s param scope (every parameter is the block's own, so one tree
  serves every mode), in the modes the operator has: full (``cache=None``,
  ``x [B, T, d] -> [B, T, d]``), cached (``x`` one position, or a prefill's
  window with ``n_valid`` real rows, from position ``t``: returns ``(out,
  new_cache)``) and, where ``ROW_READOUT``, readout (``readout_idx`` set:
  ``x`` a whole window, the result its one row ``[B, 1, d]``). Its own
  settings are ``block.cfg``: the arch's values for the keys
  ``arch_keys.OPERATOR_KEYS`` declares under its name, beside the trunk's
  ``n_heads`` and a layer's ``rope_theta``. It ends in
  :func:`.block.block_ffn`;
* ``init_cache(cfg, d_model, batch, length, dtype, window)`` — the zeroed
  state a cached call continues from;
* ``ROW_READOUT`` — whether a final layer can run for the readout row alone
  (a recurrent mixer's row needs the whole recurrence before it);
* ``CACHE_RESTARTS`` (optional, absent: it cannot) — how a new sequence may
  start over a used state, where the caller says the cache is a used one
  (``restart``): ``"masked"`` — the state is rows at their positions and a
  step at ``t`` reads rows <= ``t`` alone (attention's ``(k, v)``), nothing
  to do — or ``"zeroed"`` — a state without positions (a convolution's last
  rows, a recurrence's state), which the block then reads as zeros at ``t``
  = 0 (``TransformerBlock.__call__``: one place, every such operator). What
  the fused rollout's scan carry needs of every layer
  (``Policy.cache_restarts``);
* ``KERNELS`` — for each kernel entry the operator calls, ``(arch) ->
  ({name: callable}, {Policy field: record})``: the callable a block finds
  in its ``fns`` under ``name``, behind the policy's record of what it ran
  as.

Adding an operator is one module here, its keys in ``arch_keys.py`` and one
line of each table below.
"""

from __future__ import annotations

from typing import Any, Mapping

from relayrl_tpu.models.layers import (
    attention,
    block,
    gdn,
    kda,
    mamba2,
    mla,
    recurrent,
    short_conv,
    sparse_attention,
)

OPERATORS = {"attention": attention, "conv": short_conv, "mamba2": mamba2,
             "gdn": gdn, "kda": kda, "latent_attention": mla,
             "sparse_attention": sparse_attention, "none": block}

# ``layer_types`` entry -> (the layer's operator, whether an FFN follows)
LAYER_KINDS = {"full_attention": ("attention", True),
               "sliding_attention": ("attention", True),   # ``sliding_window``
               "conv": ("conv", True),
               "mamba2": ("mamba2", False),
               "mamba": ("mamba2", True),      # ... and an FFN, its own norm
               "linear_attention": ("gdn", True),
               "kda": ("kda", True),
               "latent_attention": ("latent_attention", True),
               "sparse_attention": ("sparse_attention", True),
               "attention": ("attention", False),
               "ffn": ("none", True)}


def resolve(arch: Mapping[str, Any], operators=tuple(OPERATORS)
            ) -> tuple[dict, dict]:
    """One policy's kernel entries behind their records: ``(fns, records)``
    — what its blocks carry as ``fns`` and the ``Policy`` fields that say
    what each traced shape ran as —, each of ``operators``' kernels once
    (the two mixers share their convolution's)."""
    fns, records = {}, {}
    for kernel in dict.fromkeys(
            k for op in operators for k in OPERATORS[op].KERNELS):
        fn, record = kernel(arch)
        fns.update(fn)
        records.update(record)
    return fns, records
