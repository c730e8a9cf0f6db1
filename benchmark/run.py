"""One run of one benchmark cell.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process that holds the chip: loads, warms up the cell's own shapes,
measures for ``--seconds``, checks the outputs outside the window, prints
the contract's one JSON object as the last line of its standard output and
exits 0. Without a TPU (or with fewer chips than the cell asks for), with
an unknown cell or with one of the cell's files missing it exits non-zero
and prints no result.

``--rehearsal <file>`` is this program's own test entry: it overlays tiny
sizes from ``<file>`` (``benchmark/tests/rehearsal.json``) on the cell's
configuration and traffic so the whole path can run on a CPU. A rehearsal
never prints a metric.
"""

import time

T_START = time.monotonic()  # before any other import: set-up starts here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", default=None,
                    help="test entry: JSON file of tiny sizes per cell")
    args = ap.parse_args(argv)

    from benchmark import harness

    spec = harness.load_cell(args.workload)
    if args.seconds is None:
        args.seconds = spec["run_seconds"]
    if args.rehearsal is not None:
        with open(args.rehearsal) as f:
            tiny = json.load(f).get(args.workload, {})
        spec["config"] = {**spec["config"], **tiny.get("config", {})}
        spec["traffic"] = {**spec["traffic"], **tiny.get("traffic", {})}
    driver = harness.load_driver(spec["traffic"]["driver"])
    for m in spec["per_layer"]:  # a missing reader is refused before set-up
        harness.load_layer_metric(m["name"])

    run = harness.Run(args, spec, T_START)
    line = None
    try:
        harness.start_run(run)
        driver.drive(run)
        line = harness.finish_run(run)
    finally:
        harness.clean_up(run, line)
    if run.rehearsal:
        # names of what was read, never a value: a rate from a CPU must not
        # stand under a device metric's name
        notes = {k: v for k, v in line["notes"].items()
                 if k in ("reference", "attention_backends")}
        print(json.dumps({"rehearsal": True, "correct": line["correct"],
                          "checks": line["checks"], "notes": notes,
                          "read": sorted(line["metrics"]),
                          "device": run.device}), flush=True)
        return 0 if line["correct"] else 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
