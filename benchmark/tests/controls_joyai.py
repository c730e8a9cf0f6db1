"""The wrong references of ``joyai-flash-policy``, each planted in the
program's place and held to the cell's own comparison.

    python benchmark/tests/controls_joyai.py --seed <n> [--seconds <s>]

By hand, on the chip. ``controls_kimi_linear.py``'s method and its
``judge``, unedited, for this cell's controls: it IS one run of
``joyai-flash-policy.update`` — ``benchmark/run.py``'s own ``main`` — and
where that run compares the timed path's parameters with the plain reference
it goes on, once a control of :data:`CONTROLS`: the two functions that decide
the cell's ``correct`` are given a policy whose ``evaluate`` is the REFERENCE
COMPUTED WRONGLY, on the same parameters and the same sample, against the
exact reference. A control is REFUSED when either check fails. One
``CONTROL`` line a control with the routed errors at the script's quantiles,
one ``PROGRAM`` line with the timed program's own errors at the same
quantiles (what the limits are set over, seed by seed); all of them in
``benchmark/out/controls-joyai.<seed>.json``.

Held (:data:`HELD`): no lane rotated, the other pairing of the rotated lanes
(``(i, i + 32)`` where the model pairs ``(2i, 2i + 1)``), scores over
``sqrt(128)``, a top-7 layer and the two float8 operand formats. Read and
NOT held: ``bf16`` (the program's own precision) and ``no_q_norm`` — the
query's low-rank row without its RMSNorm differs from the exact reference by
less than the program itself does: at seeded weights that row's root mean
square is already near 1
(``configs/joyai-flash-policy.json``, ``tolerance.not_held``).

Exit code 0: the run's own checks passed (``warm_cache`` left out: the
controls' programs compile new), ``exact`` passed and every control of
``HELD`` was refused; 1 otherwise. Run it LAST in a call: its reference
programs push the update's executable out of the machine's capped compile
cache. ``tests/test_joyai_flash_reference.py`` runs ``judge`` over
:data:`CONTROLS` at a tiny size on the CPU.
"""

import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

CELL = "joyai-flash-policy.update"
CONTROLS = {
    "exact": {},                                  # must pass: reads 0
    "no_rope": {"wrong": {"no_rope": True}},
    "half_split": {"wrong": {"half_split": True}},
    "no_q_norm": {"wrong": {"no_q_norm": True}},
    "scale_128": {"wrong": {"scale_128": True}},
    "top7": {"wrong": {"top_k": 7}},
    "bf16": {"operands": "bfloat16"},
    "float8_e4m3fn": {"operands": "float8_e4m3fn"},
    "float8_e5m2": {"operands": "float8_e5m2"},
}
HELD = ("no_rope", "half_split", "scale_128", "top7", "float8_e4m3fn",
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
                               f"controls-joyai.{run.seed}.json"),
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
