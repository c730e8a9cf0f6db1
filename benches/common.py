"""Shared bench harness utilities.

The reference's bench suite is criterion (relayrl_framework/benches/
network_benchmarks.rs, runtime_benchmarks.rs); these scripts reproduce its
measurement *shapes* (BASELINE.md) as standalone Python programs. Every
bench prints one JSON line per configuration:

    {"bench": ..., "config": {...}, "value": N, "unit": ...}

Run any file directly; ``--quick`` shrinks the grid for smoke runs.
All benches force CPU JAX unless RELAYRL_BENCH_TPU=1, so what they print
is a count or a CPU-host figure, never a device metric (the chip belongs
to one process at a time; ``chip_smoke.py`` at the repo root is the
quickest thing that runs on it).
"""

from __future__ import annotations

import json
import os
import socket
import statistics
import sys
import time

# Make `import relayrl_tpu` work for direct script invocation from either
# the repo root (`python benches/bench_X.py` — script dir, not cwd, lands
# on sys.path) or this directory — no PYTHONPATH needed.
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def setup_platform() -> None:
    """Pin the bench to CPU JAX. Forced (not setdefault): the ambient
    environment may point JAX_PLATFORMS at an accelerator these benches
    must not take from the process that owns it. The live config is
    updated too — valid as long as no backend has been initialized, which
    is the case at bench startup."""
    if os.environ.get("RELAYRL_BENCH_TPU") != "1":
        os.environ["JAX_PLATFORMS"] = "cpu"
        try:
            import jax

            jax.config.update("jax_platforms", "cpu")
        except Exception:
            pass


def quick() -> bool:
    return "--quick" in sys.argv


def bench_cwd() -> str:
    """Chdir into a throwaway dir with checkpointing disabled, so timed
    samples exclude orbax/model-file saves and no artifacts land in the
    repo (config auto-create + server model writes go to cwd)."""
    import tempfile

    from relayrl_tpu.config import default_config

    d = tempfile.mkdtemp(prefix="relayrl_bench_")
    cfg = default_config()
    cfg["learner"]["checkpoint_dir"] = ""
    cfg["learner"]["checkpoint_every_epochs"] = 1_000_000
    with open(os.path.join(d, "relayrl_config.json"), "w") as f:
        json.dump(cfg, f)
    os.chdir(d)
    return d


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def emit(bench: str, config: dict, value: float, unit: str) -> None:
    print(json.dumps({"bench": bench, "config": config,
                      "value": round(value, 6), "unit": unit}), flush=True)


def percentile_sorted(values, q: float):
    """Index-quantile over an ALREADY-SORTED sequence:
    ``values[min(len-1, int(q*len))]``, None when empty. The one
    convention the serving-latency rows use on both ends (per-agent
    digests in _soak_worker, fleet pooling in bench_soak) — keep it
    here so the two can never drift to different rank rules."""
    if not values:
        return None
    return values[min(len(values) - 1, int(q * len(values)))]


def load_results(path) -> list:
    """Load a committed ``benches/results/*.json`` file as a list of rows.

    The results directory holds TWO shapes (benches/README.md "results
    format"): NDJSON — one JSON object per line, the shape ``emit()``
    prints and most benches redirect into their results file (a plain
    ``json.load`` fails on these with "Extra data") — and single-document
    JSON (an object or a list, sometimes pretty-printed) from benches
    that assemble one summary. This loader is the ONE reader for both:
    single documents parse first (a pretty-printed object is many lines
    but one document); anything else parses per line. A list document
    returns as-is; an object document returns as ``[obj]``; every
    returned element is a parsed row.
    """
    with open(path) as f:
        text = f.read()
    try:
        doc = json.loads(text)
        return doc if isinstance(doc, list) else [doc]
    except json.JSONDecodeError:
        pass
    rows = []
    for lineno, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        try:
            rows.append(json.loads(line))
        except json.JSONDecodeError as e:
            raise ValueError(
                f"{path}:{lineno}: neither a JSON document nor NDJSON "
                f"({e})") from e
    return rows


def time_fn(fn, warmup: int = 3, iters: int = 20) -> dict:
    """Median/mean/p99 wall time of ``fn()`` in seconds."""
    for _ in range(warmup):
        fn()
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        samples.append(time.perf_counter() - t0)
    import math

    ordered = sorted(samples)
    # Correct order statistic: ceil(0.99 n) - 1 — the max for n < 100.
    p99_idx = min(len(ordered) - 1, math.ceil(0.99 * len(ordered)) - 1)
    return {
        "median_s": statistics.median(samples),
        "mean_s": statistics.fmean(samples),
        "p99_s": ordered[p99_idx],
    }


def time_chained(step_fn, state, iters: int, warmup: int = 3,
                 readback=None) -> float:
    """Seconds per iteration of a CHAINED jitted step, fenced by one host
    readback.

    ``step_fn(state) -> (state, observable)``: each call's input depends on
    the previous output, so the device must execute them sequentially.
    ``readback(observable) -> float`` (default: first metric leaf) forces
    completion of the WHOLE chain with a single host transfer; a
    per-iteration fence would bill every call a host round-trip.
    """
    import jax
    import jax.numpy as jnp

    def _fence(obs):
        if readback is not None:
            return readback(obs)
        return float(jnp.asarray(jax.tree.leaves(obs)[0]))

    obs = None
    for _ in range(warmup):
        state, obs = step_fn(state)
    _fence(obs)
    t0 = time.perf_counter()
    for _ in range(iters):
        state, obs = step_fn(state)
    _fence(obs)
    return (time.perf_counter() - t0) / iters


def age_attribution(snapshots: list[dict]) -> dict:
    """Data-age / model-age attribution block for bench rows (ISSUE 14):
    pool the ``relayrl_trace_*`` histograms across process snapshots
    (data age lives server-side, model age actor-side) into one
    ``{count, mean, p50, p95}`` summary per distribution. Histograms
    with zero samples report ``{"count": 0}`` — the schema is stable
    either way, which is what the soak smoke asserts.

    Pooling is :func:`relayrl_tpu.telemetry.aggregate.merge_snapshots`
    — the fleet plane's ONE merge implementation (ISSUE 15), so bench
    artifacts and the live ``/fleet`` endpoint can never disagree on
    merge semantics."""
    from relayrl_tpu.telemetry.aggregate import (
        merge_snapshots,
        snapshot_metric,
    )
    from relayrl_tpu.telemetry.top import histogram_quantile

    wanted = {
        "relayrl_trace_data_age_seconds": "data_age_s",
        "relayrl_trace_model_age_seconds": "model_age_s",
        "relayrl_trace_data_age_versions": "data_age_versions",
    }
    merged = merge_snapshots(snap or {} for snap in snapshots)
    out = {
        "trace_sampled": int(snapshot_metric(
            merged, "relayrl_trace_sampled_total") or 0),
        "trace_spans": int(snapshot_metric(
            merged, "relayrl_trace_spans_total") or 0),
    }
    by_name = {m["name"]: m for m in merged["metrics"]
               if m.get("kind") == "histogram"}
    for name, key in wanted.items():
        agg = by_name.get(name)
        if not agg or not agg["count"]:
            out[key] = {"count": 0}
            continue
        out[key] = {
            "count": int(agg["count"]),
            "mean": round(agg["sum"] / agg["count"], 6),
            "p50": round(histogram_quantile(agg, 0.5), 6),
            "p95": round(histogram_quantile(agg, 0.95), 6),
        }
    return out
