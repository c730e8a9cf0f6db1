"""Run a list of benchmark runs one after another and keep every line.

    python benchmark/tests/measure.py <label> <cell>:<seed>:<seconds>:<trace> ...

By hand, on the chip (``chiprun -- python benchmark/tests/measure.py ...``):
each run is a new process, as in the driver's check. The parent never
touches jax, so the child owns the chip. Every run's last line (the
contract's JSON object), its ``setup_s`` line with the phases, its exit code
and its wall time go to ``chiprun_out/bench/<label>.jsonl``; each cell's
output file (with the trace layout of a traced run) is copied beside it; a
summary with the quartile spread of every metric is printed at the end.
"""

import json
import os
import shutil
import statistics
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def spread(values):
    """Distance between the first and third quartile over the median."""
    if len(values) < 3:
        return None
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / med if med else None


def main() -> int:
    label, runs = sys.argv[1], sys.argv[2:]
    out_dir = os.path.join(REPO, "chiprun_out", "bench")
    os.makedirs(out_dir, exist_ok=True)
    log = open(os.path.join(out_dir, f"{label}.jsonl"), "a")
    table: dict = {}
    for spec in runs:
        cell, seed, seconds, trace = spec.split(":")
        cmd = [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
               "--workload", cell, "--seed", seed, "--seconds", seconds,
               "--trace", trace]
        t0 = time.monotonic()
        proc = subprocess.run(cmd, cwd=REPO, capture_output=True, text=True)
        wall = time.monotonic() - t0
        lines = proc.stdout.strip().splitlines()
        rec = {"run": spec, "rc": proc.returncode, "wall_s": wall,
               "at": time.time(),
               "setup_line": next((ln for ln in lines
                                   if ln.startswith("benchmark: setup_s")),
                                  None)}
        try:
            rec["result"] = json.loads(lines[-1])
        except (IndexError, ValueError):
            rec["stdout_tail"] = lines[-15:]
            rec["stderr_tail"] = proc.stderr.strip().splitlines()[-25:]
        log.write(json.dumps(rec) + "\n")
        log.flush()
        src = os.path.join(REPO, "benchmark", "out", f"{cell}.json")
        if os.path.exists(src):
            shutil.copy(src, os.path.join(
                out_dir, f"{label}.{cell}.trace{trace}.last.json"))
        res = rec.get("result", {})
        print(f"== {spec} rc={proc.returncode} wall={wall:.1f}s "
              f"correct={res.get('correct')} failed={res.get('failed')}",
              flush=True)
        print("   " + str(rec["setup_line"]), flush=True)
        for name, m in res.get("metrics", {}).items():
            print(f"   {name} = {m['value']:.6g} {m['unit']}", flush=True)
            table.setdefault((cell, trace, name), []).append(m["value"])
        if "device" in res:
            print(f"   device {res['device']}", flush=True)
        if not res:
            print("   " + "\n   ".join(rec.get("stdout_tail", [])
                                       + rec.get("stderr_tail", [])),
                  flush=True)
        elif not res.get("correct"):
            print(f"   checks {res.get('checks')} notes {res.get('notes')}",
                  flush=True)
        if res.get("notes", {}).get("reference"):
            print(f"   reference {res['notes']['reference']}", flush=True)
        if "breakdown" in res:
            print(f"   breakdown {json.dumps(res['breakdown'])}", flush=True)
    print("== summary (median, quartile spread / median, n; first run "
          "of a cell left in)")
    for (cell, trace, name), values in sorted(table.items()):
        s = spread(values)
        print(f"   {cell} trace={trace} {name}: median "
              f"{statistics.median(values):.6g} spread "
              f"{'n/a' if s is None else f'{s:.4f}'} n={len(values)} "
              f"values {[round(v, 4) for v in values]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
