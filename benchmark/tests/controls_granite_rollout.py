"""``granite4h-micro-policy.rollout``'s readings, seed by seed in one process:
the program's own distance from the plain reference (the lower reading of
each limit), the control's (the upper), and the wrong references.

    python benchmark/tests/controls_granite_rollout.py --seeds <a,b,...> [--seconds <s>]

By hand, on the chip: ``controls_rollout.py``'s method for this cell. For
each seed it IS one run of the cell through the driver's own ``roll`` and
``judge``, so ``PROGRAM`` lines hold what a run compares. Then each CONTROL:
the reference put in the program's place, computed another way, run over the
same observations and actions the reference lanes emitted, its ``logp_a`` and
``v`` put where the program's were, and the driver's own
``compare_with_reference`` judging them against the exact reference:

* ``bfloat16`` — both operands of every matmul of the 40 layers rounded to
  the configuration's own precision: must PASS (the program's distance is
  rounding);
* ``float8_e4m3fn`` / ``float8_e5m2`` — the nearest precisions below it: must
  be REFUSED;
* the wrong references (``forward(..., wrong=...)``): ``carry`` (a state
  carried over a reset), ``residual`` (the 0.22 left out), ``attn_scale``
  (1/sqrt(64) for 1/64), ``gate`` (the gate after the norm): each must be
  REFUSED.

Exit code 0: every run's own checks passed (``warm_cache`` left out: the
controls' programs compile new), every ``bfloat16`` passed and every other
control was refused. All lines also go to
``chiprun_out/bench/controls-granite-rollout.jsonl``. Run it LAST in a call.
"""

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

CELL = "granite4h-micro-policy.rollout"
PASSES = {"bfloat16": {"operands": "bfloat16"}}
HELD = {"float8_e4m3fn": {"operands": "float8_e4m3fn"},
        "float8_e5m2": {"operands": "float8_e5m2"},
        "carry": {"wrong": {"carry": True}},
        "residual": {"wrong": {"residual": 1.0}},
        "attn_scale": {"wrong": {"attn_scale": 0.125}},
        "gate": {"wrong": {"gate": "after"}}}


def control_readings(run, rolled: dict, **how) -> dict:
    """The control's ``logp_a`` / ``v`` in the program's place, judged by
    the driver's own comparison against the exact reference."""
    import numpy as np

    from benchmark.drivers import rollout

    cfg, tr = run.config, run.traffic
    horizon = int(tr["env_kwargs"]["horizon"])
    picked = rollout.reference_lanes(run.seed, int(tr["lanes"]),
                                     int(tr["reference_lanes"]))
    episodes = []
    for lane in picked:
        for ep in rollout.lane_episodes(rolled["frames"][lane], horizon,
                                        rolled["window"]):
            logp, v = (np.asarray(x)[0] for x in run.reference.forward(
                rolled["params"],
                rollout.episode_obs(ep, int(tr["window_size"])), cfg, **how))
            episodes.append(dict(ep, logp_a=logp[ep["at"], ep["act"]],
                                 v=v[ep["at"]]))
    got = rollout.compare_with_reference(
        run.reference.forward, rolled["params"], cfg, episodes,
        int(tr["window_size"]))
    tol = tr["tolerance"]
    got["refused"] = not (got["rel_dlogp"] <= tol["logp_rel"]
                          and got["rel_dv"] <= tol["value_rel"])
    return got


def run_cell(seed: int, seconds: float | None = None,
             rehearsal: str | None = None):
    """One run of the cell in this process through the driver's own ``roll``
    and ``judge``: ``(run, rolled, result line)``. ``rehearsal``: a file of
    tiny sizes, on a CPU."""
    from benchmark import harness
    from benchmark.drivers import rollout

    spec = harness.load_cell(CELL)
    if rehearsal is not None:
        with open(rehearsal) as f:
            tiny = json.load(f)[CELL]
        spec["config"] = {**spec["config"], **tiny["config"]}
        spec["traffic"] = {**spec["traffic"], **tiny["traffic"]}
    run = harness.Run(argparse.Namespace(
        workload=CELL, seed=seed, trace=0, rehearsal=rehearsal,
        seconds=seconds or spec["run_seconds"]), spec, time.monotonic())
    try:
        harness.start_run(run)
        rolled = rollout.roll(run)
        rollout.judge(run, rolled)
        line = harness.finish_run(run)
    finally:
        harness.clean_up(run, None)
    return run, rolled, line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--rehearsal", default=None,
                    help="tiny sizes on a CPU: the path, never a reading")
    args = ap.parse_args(argv)

    out_dir = os.path.join(REPO, "chiprun_out", "bench")
    os.makedirs(out_dir, exist_ok=True)
    ok = True
    with open(os.path.join(out_dir, "controls-granite-rollout.jsonl"),
              "a") as log:
        def say(kind: str, seed: int, what: dict) -> None:
            line = json.dumps({"kind": kind, "seed": seed, **what})
            print(f"{kind} {line}", flush=True)
            log.write(line + "\n")
            log.flush()

        for seed in (int(s) for s in args.seeds.split(",")):
            run, rolled, line = run_cell(seed, args.seconds, args.rehearsal)
            own = {k: v for k, v in line["checks"].items()
                   if k != "warm_cache"}
            say("PROGRAM", seed, {
                "checks_failed": [k for k, v in own.items() if not v],
                "rate": run.e2e["rollout_steps_per_s"],
                "memory_peak_bytes": run.memory_peak_bytes,
                **run.notes["reference"]})
            ok &= all(own.values())
            for name, how in {**PASSES, **HELD}.items():
                got = control_readings(run, rolled, **how)
                say("CONTROL", seed, {"control": name, **got})
                ok &= got["refused"] == (name in HELD)
            del rolled, run
            gc.collect()   # the next seed builds 11 GB again
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
