"""The wrong references of ``kimi-linear-policy``, each planted in the
program's place and held to the cell's own comparison.

    python benchmark/tests/controls_kimi_linear.py --seed <n> [--seconds <s>]

By hand, on the chip (``chiprun -- python benchmark/tests/controls_kimi_
linear.py --seed 5500000401``). It IS one run of ``kimi-linear-policy.update``
— ``benchmark/run.py``'s own ``main``, the same set-up, window, checks and
result line — and where that run compares the timed path's parameters with
the plain reference it goes on, once a control of :data:`CONTROLS`: the same
two functions that decide the cell's ``correct`` (``harness.reference_check``
and ``drivers/update_routed.routed_reference_check``, as they stand, under
the limits of ``configs/kimi-linear-policy.json``) are given a policy whose
``evaluate`` is the REFERENCE COMPUTED WRONGLY, on the same parameters and
the same sample, against the exact reference. A control is REFUSED when
either check fails: ``correct`` would be false had the program computed
that. One ``CONTROL`` line a control, the readings beside their limits (and,
for the record and for the next choice of a statistic, the routed errors at
:data:`QUANTILES`); all of them in
``benchmark/out/controls-kimi-linear.<seed>.json``.

What the limits are held to refuse is :data:`HELD`. ``bf16`` is read and
NOT held: the reference on bfloat16 operands with the rule's state, ``log
alpha`` and ``beta`` rounded every token differs from the exact reference by
what the program itself differs by (the program IS a bfloat16 computation,
and at seeded weights the decays forget a state's rounding within a few
tokens), so no limit that passes the program refuses it
(``configs/kimi-linear-policy.json``, ``tolerance.not_held``).

Exit code 0: the run's own checks passed, ``exact`` passed and every control
of ``HELD`` was refused; 1 otherwise. ``warm_cache`` is left out of the run's
own: the controls' programs compile new in a warm checkout, which is what
that check counts — and they push the update's executable out of the
machine's capped compile cache, so in a call that also measures the cell
this script runs LAST (the run after it reads ``warm_cache`` false and a
cold set-up). ``tests/test_kimi_linear_reference.py`` runs :func:`judge` at
a tiny size on the CPU.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

CELL = "kimi-linear-policy.update"
# name -> how ``reference.forward`` computes it
CONTROLS = {
    "exact": {},                                  # must pass: reads 0
    "scalar_decay": {"wrong": {"scalar_decay": True}},
    "rope": {"wrong": {"rope": True}},
    "no_latent_norm": {"wrong": {"no_latent_norm": True}},
    "bf16": {"wrong": {"bf16": True}},
    # the two 8-bit formats, the precisions below the bfloat16 the
    # configuration computes in: e4m3 the nearer (3 bits of mantissa), e5m2
    "float8_e4m3fn": {"operands": "float8_e4m3fn"},
    "float8_e5m2": {"operands": "float8_e5m2"},
}
HELD = ("scalar_decay", "rope", "no_latent_norm", "float8_e4m3fn",
        "float8_e5m2")
QUANTILES = (0.5, 0.75, 0.9, 0.95)


class _Planted:
    """A policy whose learner-side forward is ``outputs``: what the
    reference gave, computed as a control says, for the parameters and the
    sample both checks are handed."""

    def __init__(self, outputs):
        self.logp, self.v = outputs

    def evaluate(self, params, obs, act):
        import jax.numpy as jnp

        logp = jnp.take_along_axis(self.logp, act[..., None], -1)[..., 0]
        return logp, None, self.v


class _Bench:
    """What the two checks touch of a ``harness.Run``, a control's checks
    kept apart from the run's own."""

    def __init__(self, run, exact):
        self.config = run.config
        self.reference = self
        self.exact = exact
        self.notes: dict = {}
        self.checks: dict[str, bool] = {}

    def forward(self, params, obs, cfg):  # computed once, read by both
        return self.exact

    def check(self, name, ok, detail=""):
        self.checks[name] = bool(ok)
        return bool(ok)


def judge(run, params, obs_sample, controls=CONTROLS, plain=None) -> dict:
    """``{control: {"refused": ..., "checks": {"reference": ok,
    "reference_routed": ok}, "reference": readings, "reference_routed":
    readings, "by_quantile": {q: [logp, value]}}}``: each control's outputs in the program's place, through the
    cell's two checks, against the exact reference (``plain``:
    ``harness.reference_check`` where :func:`main` stands in its place)."""
    import jax.numpy as jnp

    from benchmark import harness
    from benchmark.drivers import update_routed

    obs = jnp.asarray(obs_sample, jnp.float32)
    exact = run.reference.forward(params, obs, run.config)
    out = {}
    limits = run.config["tolerance"]["routed"]
    limits = (limits["logp_rel"], limits["value_rel"])
    for name, how in controls.items():
        bench = _Bench(run, exact)
        planted = _Planted(exact if not how else run.reference.forward(
            params, obs, run.config, **how))
        (plain or harness.reference_check)(bench, planted, params,
                                           obs_sample)
        update_routed.routed_reference_check(bench, planted, params,
                                             obs_sample)
        by_quantile = {}
        for q in QUANTILES:
            got = update_routed.routed_errors(planted.logp, planted.v,
                                              *exact, q, limits)
            by_quantile[str(q)] = [got["rel_dlogp"], got["rel_dv"]]
        out[name] = {"refused": not all(bench.checks.values()),
                     "checks": bench.checks, **bench.notes,
                     "by_quantile": by_quantile}
    return out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--rehearsal", default=None,
                    help="tiny sizes on a CPU: the path, never a reading")
    args = ap.parse_args(argv)

    from benchmark import harness
    from benchmark import run as cell

    plain = harness.reference_check
    judged, checks = {}, {}

    def with_controls(run, policy, params, obs_sample):
        checks.update(own=run.checks)   # the run's own, filled to its end
        plain(run, policy, params, obs_sample)
        judged.update(judge(run, params, obs_sample, plain=plain))
        for name, got in judged.items():
            print("CONTROL %s %s %s" % (
                name, "REFUSED" if got["refused"] else "passed",
                json.dumps({k: v for k, v in got.items() if k != "refused"})),
                flush=True)
        path = os.path.join(harness.OUT_DIR,
                            f"controls-kimi-linear.{run.seed}.json")
        os.makedirs(harness.OUT_DIR, exist_ok=True)
        with open(path, "w") as f:
            json.dump(judged, f, indent=1)

    harness.reference_check = with_controls
    try:
        rc = cell.main(
            ["--workload", CELL, "--seed", str(args.seed), "--trace", "0"]
            + ([] if args.seconds is None
               else ["--seconds", str(args.seconds)])
            + ([] if args.rehearsal is None
               else ["--rehearsal", args.rehearsal]))
    finally:
        harness.reference_check = plain
    if args.rehearsal is not None:  # wide limits: the path alone
        return rc
    ok = (rc == 0 and bool(judged)
          and all(ok for name, ok in checks["own"].items()
                  if name != "warm_cache")
          and not judged["exact"]["refused"]
          and all(judged[name]["refused"] for name in HELD))
    print("CONTROLS " + ("held" if ok else "NOT HELD"), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
