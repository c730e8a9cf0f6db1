"""Driver ``update_indexed``: driver ``update_routed`` — its own ``drive``,
every call, phase and check of it, both comparisons with the reference — and
two more checks, for a model whose attention selects its keys by a learned
indexer that trains by a loss of its own.

Selection hands the indexer no gradient: if the update dropped the model's
own loss (``Policy.own_loss``), the indexer's gradients would be exactly
zero, every other check would still pass and the comparison with the
reference would compare two untrained indexers. So, outside the window, on
the parameters the window left and the reference sequence:

* ``index_loss``: the indexers' loss of the system's own forward
  (``policy.evaluate_stats``' ``own_loss_rows``, their mean) is finite, not
  negative, and within ``tolerance.index_loss_abs`` of the reference's
  ``index_loss`` on the same parameters (a KL of two distributions over the
  same 2,048 keys: what differs is the handful of keys the two selections
  disagree on and bfloat16's rounding of the scores);
* ``indexer_trained``: every sparse-attention layer's ``index_k_norm`` — a
  LayerNorm seeded at scale 1 and bias 0 exactly, which nothing but the
  indexers' loss reaches — has moved off its seed.

``update_routed.drive`` reaches the plain comparison through
``harness.reference_check`` by name and stands its own function there for
the length of the call; this driver first stands the plain comparison plus
its two checks under that name.
"""

from __future__ import annotations

import json

from benchmark import harness
from benchmark.drivers import update_routed


def indexer_checks(run: harness.Run, policy, params, obs_sample) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    obs = jnp.asarray(obs_sample, jnp.float32)
    act = jnp.zeros(obs.shape[:-1], jnp.int32)
    *_, stats = jax.jit(policy.evaluate_stats)(params, obs, act)
    got = float(jnp.mean(stats["own_loss_rows"]))
    want = float(run.reference.index_loss(params, obs, run.config))
    limit = float(run.config["tolerance"]["index_loss_abs"])
    run.notes["index_loss"] = {
        "system": got, "reference": want, "tolerance": limit,
        "kept_pct": float(stats["index_kept_pct"])}
    run.check("index_loss", bool(np.isfinite(got) and got >= 0.0
                                 and abs(got - want) <= limit),
              json.dumps(run.notes["index_loss"]))
    norms = [sub["index_k_norm"] for name, sub in params["params"].items()
             if name.startswith("block_") and "index_k_norm" in sub]
    moved = [float(jnp.abs(n["bias"]).max()) > 0.0
             and float(jnp.abs(n["scale"] - 1.0).max()) > 0.0 for n in norms]
    run.check("indexer_trained", bool(norms) and all(moved),
              f"index_k_norm off its seed in {sum(moved)} of {len(norms)} "
              f"layers")


def drive(run: harness.Run) -> None:
    plain = harness.reference_check

    def with_indexer(run, policy, params, obs_sample):
        plain(run, policy, params, obs_sample)
        indexer_checks(run, policy, params, obs_sample)

    # update_routed.drive takes what it finds under the name as the plain
    # comparison, calls it and then makes its own: all three run
    harness.reference_check = with_indexer
    try:
        update_routed.drive(run)
    finally:
        harness.reference_check = plain
