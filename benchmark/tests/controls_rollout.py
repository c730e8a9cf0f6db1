"""``gpt2m-policy.rollout``'s readings, seed by seed in one process: the
program's own distance from the plain reference (the lower reading of each
limit) and the control's (the upper).

    python benchmark/tests/controls_rollout.py --seeds <a,b,...> [--seconds <s>]

By hand, on the chip. For each seed it IS one run of the cell through the
driver's own ``roll`` and ``judge`` (a window of ``--seconds``, the cell's
own lanes, window and dispatches), so ``PROGRAM`` lines hold what a run
compares. Then the CONTROL: the reference put in the program's place and
computed in the nearest precision below the configuration's — the operands
of every matmul of the 24 blocks (activations and weights) rounded to
``float8_e4m3fn`` and ``float8_e5m2`` where the program computes them in
bfloat16, the float32 embedding and heads left as the configuration states
them. It is run over the same observations and actions the reference lanes
emitted, its ``logp_a`` and ``v`` are put where the program's were, and the
driver's own ``compare_with_reference`` judges them against the exact
reference. A control is REFUSED when it is over a limit. ``bf16`` (the
program's own precision, the blocks' operands rounded to bfloat16) is read
beside them and must pass.

Exit code 0: every run's own checks passed (``warm_cache`` left out: the
controls' programs compile new), every ``bf16`` passed and every float8
control was refused. All lines also go to
``chiprun_out/bench/controls-rollout.jsonl``. Run it LAST in a call.
``benchmark/tests/test_rollout_driver.py`` runs :func:`control_readings` at
a toy size on the CPU.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

CELL = "gpt2m-policy.rollout"
CONTROLS = ("bfloat16", "float8_e4m3fn", "float8_e5m2")
HELD = ("float8_e4m3fn", "float8_e5m2")


def reference_in(operands: str, config_name: str):
    """A fresh copy of the plain reference whose blocks round both operands
    of every ``_dense`` to ``operands`` and multiply in float32; embedding
    and heads stay exact."""
    import jax.numpy as jnp

    from benchmark import harness

    ref = harness.load_reference(config_name)
    exact, block = ref._dense, ref._block
    dtype = jnp.dtype(operands)

    def rounded(p, x):
        q = {"kernel": p["kernel"].astype(dtype), "bias": p["bias"]}
        return exact(q, x.astype(dtype).astype(jnp.float32))

    def block_rounded(p, x, **kw):
        ref._dense = rounded  # what ``_block`` finds while it is traced
        try:
            return block(p, x, **kw)
        finally:
            ref._dense = exact

    ref._block = block_rounded
    return ref


def control_readings(run, rolled: dict, operands: str) -> dict:
    """The control's ``logp_a`` / ``v`` in the program's place, judged by
    the driver's own comparison against the exact reference."""
    import numpy as np

    from benchmark.drivers import rollout

    cfg, tr = run.config, run.traffic
    horizon = int(tr["env_kwargs"]["horizon"])
    wrong = reference_in(operands, run.config_name)
    picked = rollout.reference_lanes(run.seed, int(tr["lanes"]),
                                     int(tr["reference_lanes"]))
    episodes = []
    for lane in picked:
        for ep in rollout.lane_episodes(rolled["frames"][lane], horizon,
                                        rolled["window"]):
            logp, v = (np.asarray(x)[0] for x in wrong.forward(
                rolled["params"],
                rollout.episode_obs(ep, int(tr["window_size"])), cfg))
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
    with open(os.path.join(out_dir, "controls-rollout.jsonl"), "a") as log:
        def say(kind: str, seed: int, what: dict) -> None:
            line = json.dumps({"kind": kind, "seed": seed, **what})
            print(f"{kind} {line}", flush=True)
            log.write(line + "\n")
            log.flush()

        for seed in (int(s) for s in args.seeds.split(",")):
            run, rolled, line = run_cell(seed, args.seconds, args.rehearsal)
            readings = {c: control_readings(run, rolled, c)
                        for c in CONTROLS}
            own = {k: v for k, v in line["checks"].items()
                   if k != "warm_cache"}
            say("PROGRAM", seed, {
                "checks_failed": [k for k, v in own.items() if not v],
                "rate": run.e2e["rollout_steps_per_s"],
                **run.notes["reference"]})
            for name, got in readings.items():
                say("CONTROL", seed, {"operands": name, **got})
            ok &= all(own.values()) and not readings["bfloat16"]["refused"]
            ok &= all(readings[c]["refused"] for c in HELD)
            del rolled, run
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
