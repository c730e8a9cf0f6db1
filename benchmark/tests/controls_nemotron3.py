"""The wrong references of ``nemotron3-super-policy``, each planted in the
program's place and held to the cell's own comparison.

    python benchmark/tests/controls_nemotron3.py --seed <n> [--seconds <s>]

By hand, on the chip. ``controls_kimi_linear.py``'s method and its
``judge``, unedited, for this cell's controls: it IS one run of
``nemotron3-super-policy.update`` — ``benchmark/run.py``'s own ``main`` — and
where that run compares the timed path's parameters with the plain reference
it goes on, once a control of :data:`CONTROLS`: the two functions that decide
the cell's ``correct`` are given a policy whose ``evaluate`` is the REFERENCE
COMPUTED WRONGLY, on the same parameters and the same sample, against the
exact reference. A control is REFUSED when either check fails. One
``CONTROL`` line a control with the routed errors at the script's quantiles,
one ``PROGRAM`` line with the timed program's own errors at the same
quantiles (what the limits are set over, seed by seed); all of them in
``benchmark/out/controls-nemotron3.<seed>.json``.

Held (:data:`HELD`): the experts fed the rows' first 1024 columns in place
of their down-projection, the 5 left out, a top-21 layer, float8 e5m2
operands — ISSUE 57's four —, and the state dropped at chunk ends, no shared
expert and float8 e4m3 beside them. ``bf16`` is read and not held: it is the
program's own precision.

Exit code 0: the run's own checks passed (``warm_cache`` left out: the
controls' programs compile new), ``exact`` passed and every control of
``HELD`` was refused; 1 otherwise. Run it LAST in a call: its reference
programs push the update's executable out of the machine's capped compile
cache.
"""

import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

CELL = "nemotron3-super-policy.update"
CONTROLS = {
    "exact": {},                                  # must pass: reads 0
    "latent": {"wrong": {"latent": False}},
    "scaling": {"wrong": {"scaling": 1.0}},
    "top_k": {"wrong": {"top_k": 21}},
    "shared": {"wrong": {"shared": False}},
    "carry": {"wrong": {"carry": False}},
    "bf16": {"operands": "bfloat16"},
    "float8_e4m3fn": {"operands": "float8_e4m3fn"},
    "float8_e5m2": {"operands": "float8_e5m2"},
}
HELD = ("latent", "scaling", "top_k", "shared", "carry", "float8_e4m3fn",
        "float8_e5m2")


def _kimi():
    spec = importlib.util.spec_from_file_location(
        "controls_kimi_linear", os.path.join(HERE, "controls_kimi_linear.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


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
    from benchmark.drivers import update_routed

    base = _kimi()
    plain = harness.reference_check
    judged, checks = {}, {}

    def with_controls(run, policy, params, obs_sample):
        checks.update(own=run.checks)   # the run's own, filled to its end
        plain(run, policy, params, obs_sample)
        judged.update(base.judge(run, params, obs_sample, CONTROLS, plain))
        for name, got in judged.items():
            print("CONTROL %s %s %s" % (
                name, "REFUSED" if got["refused"] else "passed",
                json.dumps({k: v for k, v in got.items() if k != "refused"})),
                flush=True)
        # the timed program's own errors, by the same quantiles: the cell's
        # second check at each of them (its notes hold the readings)
        program = {}
        tol = run.config["tolerance"]["routed"]
        for q in base.QUANTILES:
            run.config["tolerance"]["routed"] = {**tol, "quantile": q}
            bench = base._Bench(run, None)
            bench.reference = run.reference
            update_routed.routed_reference_check(bench, policy, params,
                                                 obs_sample)
            got = bench.notes["reference_routed"]
            program[str(q)] = [got["rel_dlogp"], got["rel_dv"]]
        run.config["tolerance"]["routed"] = tol
        judged["program"] = {"by_quantile": program,
                             "reference": run.notes.get("reference")}
        print("PROGRAM " + json.dumps(judged["program"]), flush=True)
        os.makedirs(harness.OUT_DIR, exist_ok=True)
        with open(os.path.join(harness.OUT_DIR,
                               f"controls-nemotron3.{run.seed}.json"),
                  "w") as f:
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
