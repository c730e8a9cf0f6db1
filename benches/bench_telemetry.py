"""Telemetry hot-path cost: disabled (null) vs enabled metric operations.

The subsystem's design contract (ISSUE 4): instrumentation sites hold a
direct metric reference, so the DISABLED cost is one attribute call on a
shared null object, and the ENABLED cost is a threading.local read plus
a plain ``+=`` on a per-thread shard — no lock either way. This bench
measures both (plus histogram observe and snapshot aggregation) and
ASSERTS the contract so a regression that sneaks a lock or an allocation
into ``inc()`` fails loudly rather than shaving fleet throughput
silently.

Prints one JSON line per row; ``--write`` commits to
benches/results/telemetry.json.
"""

from __future__ import annotations

import json
import sys
import time

from common import quick, setup_platform  # noqa: E402

setup_platform()

# Generous ceilings on a noisy shared host — an order of magnitude above
# the measured numbers, tight enough to catch "someone added a lock /
# registry lookup to the hot path" (~10x regressions).
MAX_DISABLED_NS = 1500.0
MAX_ENABLED_COUNTER_NS = 3000.0
# Tracing plane (ISSUE 14): disabled span sites pay one attribute check
# on the shared null tracer; a live span record is a dict build + ring
# append + counter inc (journal off in-bench). Sampling draw is the
# per-trajectory stride decision.
MAX_TRACE_DISABLED_NS = 1500.0
MAX_TRACE_SPAN_NS = 30000.0
MAX_TRACE_DRAW_NS = 5000.0
# Program spans (telemetry/spans.py): with the profiler off a span is one
# context manager and two clock reads. ISSUE 24's budget is 1 us on the chip
# machine's host, and it is the ceiling of the spans without arguments (the
# per-trajectory ones: 658 and 841 ns there, PR 24). Keyword arguments add
# the dict the call builds (1,182 ns); those spans are per update or per
# queue item, and their ceiling sits a quarter above that reading. With a
# profiler session on a span also builds a TraceAnnotation.
MAX_PROGRAM_SPAN_OFF_NS = 1000.0
MAX_PROGRAM_SPAN_ARGS_OFF_NS = 1500.0
MAX_PROGRAM_SPAN_ON_NS = 25000.0
# Fleet aggregation plane (ISSUE 15): one snapshot-frame encode per
# process per fleet_interval_s (msgpack of a ~40-family registry), and
# one merge per proc per interval at the root. Both are off the hot
# path (emitter/tick threads), so the ceilings guard "per interval"
# scale, not per-op scale: a root merging 1000 procs at these ceilings
# spends <1 core-second per interval.
MAX_FRAME_ENCODE_US = 3000.0
MAX_MERGE_US_PER_PROC = 1000.0


def _best_ns_per_op(fn, n_ops: int, trials: int) -> float:
    best = float("inf")
    for _ in range(trials):
        t0 = time.perf_counter_ns()
        fn(n_ops)
        best = min(best, (time.perf_counter_ns() - t0) / n_ops)
    return best


def _loop_baseline(n_ops: int, trials: int) -> float:
    """Cost of the bare ``for _ in range(n)`` loop, subtracted from every
    row so the numbers are per-call, not per-iteration-plus-loop."""
    def body(n):
        for _ in range(n):
            pass
    return _best_ns_per_op(body, n_ops, trials)


def run() -> list[dict]:
    from relayrl_tpu.telemetry import NullRegistry, Registry

    n_ops = 200_000 if quick() else 1_000_000
    trials = 3 if quick() else 5
    base_ns = _loop_baseline(n_ops, trials)

    null_counter = NullRegistry().counter("relayrl_bench_total")
    reg = Registry(run_id="bench")
    counter = reg.counter("relayrl_bench_total")
    hist = reg.histogram("relayrl_bench_seconds")
    # A registry the size of the instrumented framework (~40 families)
    # so the snapshot row measures a realistic aggregation.
    for i in range(40):
        reg.counter(f"relayrl_bench_fam{i}_total").inc(i)

    def inc_null(n):
        inc = null_counter.inc
        for _ in range(n):
            inc()

    def inc_real(n):
        inc = counter.inc
        for _ in range(n):
            inc()

    def observe_real(n):
        observe = hist.observe
        for _ in range(n):
            observe(0.003)

    rows = []

    def row(name, ns, extra=None):
        entry = {"bench": "telemetry_hotpath",
                 "config": {"op": name, "n_ops": n_ops, "trials": trials},
                 "ns_per_op": round(ns, 1), "unit": "ns/op",
                 **(extra or {})}
        print(json.dumps(entry))
        rows.append(entry)
        return entry

    disabled_ns = _best_ns_per_op(inc_null, n_ops, trials) - base_ns
    enabled_ns = _best_ns_per_op(inc_real, n_ops, trials) - base_ns
    observe_ns = _best_ns_per_op(observe_real, n_ops, trials) - base_ns

    row("counter_inc_disabled", disabled_ns,
        {"ceiling_ns": MAX_DISABLED_NS})
    row("counter_inc_enabled", enabled_ns,
        {"ceiling_ns": MAX_ENABLED_COUNTER_NS})
    row("histogram_observe_enabled", observe_ns)

    n_snap = 200 if quick() else 1000
    t0 = time.perf_counter_ns()
    for _ in range(n_snap):
        reg.snapshot()
    snap_us = (time.perf_counter_ns() - t0) / n_snap / 1000.0
    entry = {"bench": "telemetry_snapshot",
             "config": {"metric_families": 42, "n_ops": n_snap},
             "us_per_snapshot": round(snap_us, 1), "unit": "us/snapshot"}
    print(json.dumps(entry))
    rows.append(entry)

    # -- tracing plane: disabled no-op vs live span record (ISSUE 14) --
    from relayrl_tpu import telemetry as telemetry_mod
    from relayrl_tpu.telemetry.trace import NULL_TRACER, Tracer

    # The tracer's own counters must be REAL metrics, or the span row
    # would measure a null-counter inc and flatter the result.
    telemetry_mod.set_registry(reg)
    tracer = Tracer(1.0, ring=4096, proc="bench", journal=False)

    def span_disabled(n):
        t = NULL_TRACER
        for _ in range(n):
            if t.enabled:
                t.span("traj", "x", "env", 0, 1)

    def span_enabled(n):
        span = tracer.span
        for _ in range(n):
            span("traj", "bench-1", "env", 1000, 2000, agent="a")

    def draw_enabled(n):
        sample = tracer.sample_traj
        for _ in range(n):
            sample(1000, 1)

    n_span = max(10_000, n_ops // 10)
    span_off_ns = _best_ns_per_op(span_disabled, n_ops, trials) - base_ns
    span_on_ns = _best_ns_per_op(span_enabled, n_span, trials) - base_ns
    draw_ns = _best_ns_per_op(draw_enabled, n_span, trials) - base_ns
    row("trace_span_disabled", span_off_ns,
        {"ceiling_ns": MAX_TRACE_DISABLED_NS})
    row("trace_span_record_enabled", span_on_ns,
        {"ceiling_ns": MAX_TRACE_SPAN_NS})
    row("trace_sample_draw_enabled", draw_ns,
        {"ceiling_ns": MAX_TRACE_DRAW_NS})

    # -- program spans: profiler off vs on (ISSUE 24) --
    import tempfile

    import jax

    from relayrl_tpu.telemetry.spans import span as program_span

    ledger = {"x_s": 0.0}

    def program_span_bare(n):
        for _ in range(n):
            with program_span("rl:bench.span"):
                pass

    def program_span_total(n):
        for _ in range(n):
            with program_span("rl:bench.span", ledger, "x_s"):
                pass

    def program_span_args(n):
        for _ in range(n):
            with program_span("rl:bench.span", ledger, "x_s", version=3,
                              bytes=4096):
                pass

    pspan_off = {fn.__name__: _best_ns_per_op(fn, n_span, trials) - base_ns
                 for fn in (program_span_bare, program_span_total,
                            program_span_args)}
    with tempfile.TemporaryDirectory() as trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            pspan_on = {
                fn.__name__: _best_ns_per_op(fn, 10_000, trials) - base_ns
                for fn in (program_span_total, program_span_args)}
        finally:
            jax.profiler.stop_trace()
    off_ceiling = {name: (MAX_PROGRAM_SPAN_ARGS_OFF_NS if name.endswith("args")
                          else MAX_PROGRAM_SPAN_OFF_NS)
                   for name in pspan_off}
    for name, ns in pspan_off.items():
        row(f"{name}_profiler_off", ns, {"ceiling_ns": off_ceiling[name]})
    for name, ns in pspan_on.items():
        row(f"{name}_profiler_on", ns,
            {"ceiling_ns": MAX_PROGRAM_SPAN_ON_NS})
    for name, ns in pspan_off.items():
        assert ns < off_ceiling[name], (
            f"{name} with the profiler off costs {ns:.0f} ns — over "
            f"{off_ceiling[name]}")
    assert max(pspan_on.values()) < MAX_PROGRAM_SPAN_ON_NS, (
        f"program span under a profiler session costs {pspan_on} ns — "
        f"over {MAX_PROGRAM_SPAN_ON_NS}")

    # The contract asserts (the CI teeth): disabled must stay an
    # attribute-call away from free, and the enabled increment must stay
    # lock-free cheap.
    assert counter.total() == n_ops * trials
    assert disabled_ns < MAX_DISABLED_NS, (
        f"disabled-path inc {disabled_ns:.0f}ns/op exceeds "
        f"{MAX_DISABLED_NS}ns — the null object gained real work")
    assert enabled_ns < MAX_ENABLED_COUNTER_NS, (
        f"enabled inc {enabled_ns:.0f}ns/op exceeds "
        f"{MAX_ENABLED_COUNTER_NS}ns — the shard hot path gained a "
        f"lock/lookup")
    assert span_off_ns < MAX_TRACE_DISABLED_NS, (
        f"trace-off span site {span_off_ns:.0f}ns/op exceeds "
        f"{MAX_TRACE_DISABLED_NS}ns — the null tracer gained real work")
    assert span_on_ns < MAX_TRACE_SPAN_NS, (
        f"span record {span_on_ns:.0f}ns/op exceeds "
        f"{MAX_TRACE_SPAN_NS}ns — the flight-recorder path regressed")
    assert draw_ns < MAX_TRACE_DRAW_NS, (
        f"sampling draw {draw_ns:.0f}ns/op exceeds {MAX_TRACE_DRAW_NS}ns")

    # -- fleet aggregation (ISSUE 15): frame encode + merge per proc --
    from relayrl_tpu.telemetry.aggregate import (
        encode_snapshot_frame,
        merge_snapshots,
        snapshot_section,
    )

    snap = reg.snapshot()
    n_frames = 200 if quick() else 2000
    t0 = time.perf_counter_ns()
    for i in range(n_frames):
        encode_snapshot_frame([snapshot_section(snap, "bench", "actor",
                                                1.0, i)])
    enc_us = (time.perf_counter_ns() - t0) / n_frames / 1000.0
    entry = {"bench": "fleet_aggregation",
             "config": {"op": "snapshot_frame_encode",
                        "metric_families": 42, "n_ops": n_frames},
             "us_per_frame": round(enc_us, 1), "unit": "us/frame",
             "ceiling_us": MAX_FRAME_ENCODE_US}
    print(json.dumps(entry))
    rows.append(entry)
    assert enc_us < MAX_FRAME_ENCODE_US, (
        f"snapshot-frame encode {enc_us:.0f}us exceeds "
        f"{MAX_FRAME_ENCODE_US}us — the fleet emitter got expensive")

    for n_procs in (8, 64):
        snaps = [snap] * n_procs
        n_merges = max(5, (50 if quick() else 200) // max(1, n_procs // 8))
        t0 = time.perf_counter_ns()
        for _ in range(n_merges):
            merge_snapshots(snaps)
        merge_us = (time.perf_counter_ns() - t0) / n_merges / 1000.0
        per_proc_us = merge_us / n_procs
        entry = {"bench": "fleet_aggregation",
                 "config": {"op": "merge_snapshots", "procs": n_procs,
                            "metric_families": 42, "n_ops": n_merges},
                 "us_per_merge": round(merge_us, 1),
                 "us_per_proc": round(per_proc_us, 1), "unit": "us/merge",
                 "ceiling_us_per_proc": MAX_MERGE_US_PER_PROC}
        print(json.dumps(entry))
        rows.append(entry)
        assert per_proc_us < MAX_MERGE_US_PER_PROC, (
            f"merge at {n_procs} procs costs {per_proc_us:.0f}us/proc, "
            f"exceeds {MAX_MERGE_US_PER_PROC}us — root tick cost "
            f"regressed")
    return rows


def main():
    rows = run()
    if "--write" in sys.argv:
        import os

        out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "results", "telemetry.json")
        with open(out, "w") as f:
            for entry in rows:
                f.write(json.dumps(entry) + "\n")


if __name__ == "__main__":
    main()
