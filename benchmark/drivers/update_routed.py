"""Driver ``update_routed``: driver ``update`` — its own ``drive``, every
call, phase and check of it — and one more comparison with the plain
reference, for a model that routes each token to its top-k experts.

``harness.reference_check`` takes the largest difference over all tokens.
In a routed model that maximum is set by a handful of tokens whose k-th and
(k+1)-th router probabilities lie closer than the error bfloat16 leaves in
the router's input: the system picks another expert there than the float32
reference, and the token's output moves by a whole expert's share. A limit
wide enough for that one token passes an 8-bit trunk and a layer that drops
every token's k-th expert (PERF.md section 6, PR 27). So this driver
compares once more with a statistic those few tokens cannot set: the error
that all but ``1 - tolerance.routed.quantile`` of the tokens stay under,
held to limits a little above rounding. The largest difference stays under
``harness.reference_check``'s limits, which now bound what one token that
routes differently may do.

``update.drive`` reaches the comparison through ``harness.reference_check``
by name; this driver stands a function that makes both comparisons in its
place for the length of that call. (A hook for a configuration's own
comparison is a ``benchmark`` issue's to give the harness: PERF.md
section 7.)
"""

from __future__ import annotations

import json

from benchmark import harness
from benchmark.drivers import update


def routed_errors(logp_sys, v_sys, logp_ref, v_ref, quantile: float,
                  limits: tuple[float, float]) -> dict:
    """Each token's two errors as the harness normalises them, their
    ``quantile`` over the tokens, and ``flipped_tokens``: how many tokens
    lie above ``limits`` — as far as the outputs show, the tokens that went
    to another expert than the reference's."""
    import numpy as np

    logp_sys, v_sys, logp_ref, v_ref = (
        np.asarray(a, np.float64)
        for a in (logp_sys, v_sys, logp_ref, v_ref))
    spread = float(logp_ref.max() - logp_ref.min())
    err_logp = np.abs(logp_sys - logp_ref).max(-1) / max(1.0, spread)
    err_v = np.abs(v_sys - v_ref) / max(1.0, float(np.abs(v_ref).max()))
    return {"rel_dlogp": float(np.quantile(err_logp, quantile)),
            "rel_dv": float(np.quantile(err_v, quantile)),
            "tokens": int(err_v.size),
            "flipped_tokens": int(((err_logp > limits[0])
                                   | (err_v > limits[1])).sum())}


def routed_reference_check(run: harness.Run, policy, params,
                           obs_sample) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    act_dim = int(run.config["act_dim"])
    obs = jnp.asarray(obs_sample, jnp.float32)

    def system(params, obs):  # as harness.reference_check evaluates it
        def one(a):
            act = jnp.full(obs.shape[:-1], a, jnp.int32)
            logp, _ent, v = policy.evaluate(params, obs, act)
            return logp, v

        logp, v = jax.vmap(one)(jnp.arange(act_dim))
        return jnp.moveaxis(logp, 0, -1), v[0]

    tol = run.config["tolerance"]["routed"]
    got = routed_errors(
        *jax.jit(system)(params, obs),
        *run.reference.forward(params, obs, run.config),
        float(tol["quantile"]), (tol["logp_rel"], tol["value_rel"]))
    # one token that routes differently differs in one of its k slots
    got["routing_agreement"] = 1.0 - got["flipped_tokens"] / (
        got["tokens"] * int(run.config["num_experts_per_tok"]))
    got["tolerance"] = {k: tol[k] for k in (
        "quantile", "logp_rel", "value_rel")}
    run.notes["reference_routed"] = got
    ok = (np.isfinite(got["rel_dlogp"]) and np.isfinite(got["rel_dv"])
          and got["rel_dlogp"] <= tol["logp_rel"]
          and got["rel_dv"] <= tol["value_rel"])
    run.check("reference_routed", ok, json.dumps(got))


def drive(run: harness.Run) -> None:
    plain = harness.reference_check

    def both(run, policy, params, obs_sample):
        plain(run, policy, params, obs_sample)
        routed_reference_check(run, policy, params, obs_sample)

    harness.reference_check = both
    try:
        update.drive(run)
    finally:
        harness.reference_check = plain
