"""What every cell shares: finding a cell's files by name, the run record,
set-up phases, compile counting, the traced sub-window, the comparison with
the plain reference, and the one JSON line.

Nothing here knows a configuration, a traffic mix, a driver or a per-layer
metric by name: each is a file of its own, found through ``BENCHMARK.json``
(see ``benchmark/README.md``). From the program the harness takes only the
public objects a driver builds and the spans the benchmark puts around its
calls into them; it imports none of ``bench.py``, ``benches/`` or
``chip_smoke.py``.
"""

from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import time

from benchmark.spans import Spans

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
OUT_DIR = os.path.join(HERE, "out")
PHASES = ("import", "backend_init", "native", "children", "build",
          "traffic_pool", "warmup")
# jax.random.PRNGKey takes what 32 signed bits hold; --seed may be larger.
PROGRAM_SEED_MOD = 2**31 - 1


class Refused(SystemExit):
    """A run that must not print a result: exit code 2 and one line why."""

    def __init__(self, why: str):
        print(f"benchmark: REFUSED {why}", file=sys.stderr, flush=True)
        super().__init__(2)


def say(msg: str) -> None:
    print(f"benchmark: {msg}", flush=True)


# --------------------------------------------------------------------------
# a cell's files, by name
# --------------------------------------------------------------------------

def _read_json(path: str, what: str) -> dict:
    if not os.path.isfile(path):
        raise Refused(f"{what} {os.path.relpath(path, REPO)!r} is missing")
    with open(path) as f:
        return json.load(f)


def _load_by_path(path: str, what: str):
    """Import a file whose name is a metric's or a configuration's name
    (dots and hyphens: not importable by module name)."""
    if not os.path.isfile(path):
        raise Refused(f"{what} {os.path.relpath(path, REPO)!r} is missing")
    name = "benchmark._by_path." + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, HERE))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str) -> dict:
    """Everything ``BENCHMARK.json`` says about one cell, with its files."""
    bench = _read_json(os.path.join(REPO, "BENCHMARK.json"), "file")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"unknown workload {workload!r}; BENCHMARK.json has "
                      f"{sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = _read_json(os.path.join(REPO, cfg_entry["file"]),
                        "configuration file")
    traffic = _read_json(
        os.path.join(HERE, "traffic", cell["traffic"] + ".json"),
        "traffic mix")

    def mine(metrics):
        return [m for m in metrics
                if "workloads" not in m or workload in m["workloads"]]

    return {
        "cell": cell, "config_name": cell["config"], "config": config,
        "traffic": traffic,
        "end_to_end": mine(bench["end_to_end"]),
        "per_layer": mine(bench["per_layer"]),
        "run_seconds": bench["run_seconds"],
    }


def load_driver(name: str):
    path = os.path.join(HERE, "drivers", name + ".py")
    if not os.path.isfile(path):
        raise Refused(f"driver 'benchmark/drivers/{name}.py' is missing")
    # by module name: a driver's child processes are started with
    # ``spawn`` and re-import their target function by it
    return importlib.import_module(f"benchmark.drivers.{name}")


def load_reference(config_name: str):
    return _load_by_path(
        os.path.join(HERE, "reference", config_name + ".py"),
        "plain reference")


def load_layer_metric(name: str):
    return _load_by_path(os.path.join(HERE, "layer_metrics", name + ".py"),
                         "per-layer metric reader")


def load_peaks(device_kind: str) -> dict:
    peaks = _read_json(os.path.join(HERE, "peaks.json"), "table of peaks")
    if device_kind not in peaks:
        raise Refused(f"no peaks on record for device_kind {device_kind!r}; "
                      f"add it to benchmark/peaks.json with its source")
    return peaks[device_kind]


# --------------------------------------------------------------------------
# compile counting (copied from chip_smoke.CompileCounter)
# --------------------------------------------------------------------------

class CompileCounter:
    """Compile requests vs persistent-cache hits, from jax's own events."""

    def __init__(self):
        from jax import monitoring

        self.requests = self.hits = 0
        monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def percentile_sorted(values, q: float):
    """Index-quantile over an ALREADY-SORTED sequence (copied from
    ``benches/common.percentile_sorted``): ``values[min(n-1, int(q*n))]``."""
    if not values:
        return None
    return values[min(len(values) - 1, int(q * len(values)))]


# --------------------------------------------------------------------------
# the run record
# --------------------------------------------------------------------------

class Run:
    """One run of one cell. Drivers fill it; per-layer readers read it."""

    def __init__(self, args, spec: dict, t_start: float):
        self.workload = args.workload
        self.seed = int(args.seed)
        self.program_seed = self.seed % PROGRAM_SEED_MOD
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.rehearsal = args.rehearsal is not None
        self.spec = spec
        self.config_name = spec["config_name"]
        self.config = spec["config"]
        self.traffic = spec["traffic"]
        self.t_start = t_start
        self.phase_s: dict[str, float] = {p: 0.0 for p in PHASES}
        self.spans = Spans()
        self.run_dir = os.path.join(OUT_DIR, f"run-{self.workload}")
        self.out_path = os.path.join(OUT_DIR, f"{self.workload}.json")
        self.compiles: CompileCounter | None = None
        self.cache_dir = ""
        self.cache_was_warm = False
        self.memory_peak_bytes = 0
        self.device: dict = {}
        self.peaks: dict = {}
        self.reference = None
        # filled by the driver
        self.window_s = 0.0
        self.setup_s = 0.0
        self.samples = 0            # valid timesteps consumed in the window
        self.updates = 0            # fenced updates in the window
        self.attempted = 0
        self.failed = 0
        self.counters: dict = {}    # driver-specific counts and byte sizes
        self.timings: dict = {}     # server.timings deltas (loop cells)
        self.stats: dict = {}       # server.stats deltas (loop cells)
        self.e2e: dict[str, float] = {}
        self.window_compile_requests = 0
        self.checks: dict[str, bool] = {}
        self.notes: dict = {}
        self.trace_reduced: dict | None = None
        self.memory_stats: dict = {}
        self.train_flops_per_sample = 0.0
        self.train_rate = 0.0       # valid timesteps per second of window

    # -- set-up phases ----------------------------------------------------
    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.monotonic()
        try:
            yield
        finally:
            self.phase_s[name] += time.monotonic() - t0

    def begin_window(self) -> float:
        """First instant of the measured window: set-up ends here."""
        now = time.monotonic()
        self.setup_s = now - self.t_start
        self._requests_at_window = self.compiles.requests
        named = sum(self.phase_s.values())
        say("setup_s %.3f phases %s other %.3f" % (
            self.setup_s,
            " ".join(f"{p}={self.phase_s[p]:.3f}" for p in PHASES),
            self.setup_s - named))
        return now

    def end_window(self, t0: float) -> None:
        self.window_s = time.monotonic() - t0
        self.window_compile_requests = (self.compiles.requests
                                        - self._requests_at_window)

    # -- the traced sub-window (``--trace 1`` only) ------------------------
    @contextlib.contextmanager
    def traced(self):
        """Profiler on for the enclosed block. The block itself sits in a
        ``host:window`` span, so profiler start and stop are outside what
        is counted as device idle time."""
        if not self.trace:
            yield
            return
        import jax

        trace_dir = os.path.join(self.run_dir, "trace")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # host spans come from spans.py
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        self.spans.traced = True
        try:
            with jax.profiler.TraceAnnotation("host:window"):
                yield
        finally:
            self.spans.traced = False
            jax.profiler.stop_trace()
        from benchmark import trace_reduce

        events = trace_reduce.load_events(trace_dir)
        if events is None:
            return
        self.notes["trace_layout"] = events["layout"]
        window = trace_reduce.host_window_ns(events, "host:window")
        events["host"] = [e for e in events["host"]
                          if e[0] != "host:window"]
        self.trace_reduced = trace_reduce.reduce_events(events, window)

    # -- checks -----------------------------------------------------------
    def check(self, name: str, ok: bool, detail: str = "") -> bool:
        self.checks[name] = bool(ok)
        if not ok:
            say(f"CHECK FAILED {name} {detail}")
        return bool(ok)


# --------------------------------------------------------------------------
# start-up and shut-down of a run
# --------------------------------------------------------------------------

def start_run(run: Run) -> None:
    """Backend, device, compile cache, run directory. Without a TPU (or
    with fewer chips than the cell asks for) the run is refused — except in
    a rehearsal, which never prints a metric."""
    with run.phase("import"):
        import jax

        import relayrl_tpu  # noqa: F401  (the program; absent => ImportError)
    with run.phase("backend_init"):
        devices = jax.devices()
    dev = devices[0]
    run.device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devices)}
    chips = int(run.spec["cell"]["chips"])
    if not run.rehearsal:
        if dev.platform != "tpu":
            raise Refused(f"no accelerator: jax found platform "
                          f"{dev.platform!r}; nothing was measured")
        if len(devices) < chips:
            raise Refused(f"cell asks for {chips} chip(s), jax found "
                          f"{len(devices)}")
        run.peaks = load_peaks(dev.device_kind)
        from relayrl_tpu.utils.compile_cache import resolve_compile_cache

        run.cache_dir = resolve_compile_cache()
        # Warm = an earlier run of this cell in THIS checkout ran to its end
        # with this cache directory: every program of the cell is then in
        # the cache under the keys this checkout's code produces. (A cache
        # directory shared with another checkout says nothing: a Mosaic
        # kernel's key holds the source locations it was traced from, so
        # other code, or the same code elsewhere, compiles anew.)
        try:
            with open(run.out_path) as f:
                run.cache_was_warm = (json.load(f).get("cache_complete_for")
                                      == run.cache_dir)
        except (OSError, ValueError):
            run.cache_was_warm = False
    run.compiles = CompileCounter()
    run.reference = load_reference(run.config_name)
    shutil.rmtree(run.run_dir, ignore_errors=True)
    os.makedirs(run.run_dir)
    say(f"device platform={dev.platform} device_kind={dev.device_kind!r} "
        f"count={len(devices)} cache={run.cache_dir or 'off'} "
        f"warm={run.cache_was_warm}")


def ensure_native(run: Run) -> str:
    """``native/librelayrl_native.so`` built once per checkout: only when
    it is absent or older than a source beside it, never in every run."""
    with run.phase("native"):
        native = os.path.join(REPO, "native")
        lib = os.path.join(native, "librelayrl_native.so")
        sources = [os.path.join(native, f) for f in os.listdir(native)
                   if f.endswith((".cc", ".h")) or f == "Makefile"]
        if os.path.exists(lib) and os.path.getmtime(lib) >= max(
                os.path.getmtime(s) for s in sources):
            return "current"
        if not (shutil.which("make") and shutil.which("g++")):
            if os.path.exists(lib):
                os.remove(lib)  # a stale binary must not decode the run
            return "no toolchain: python decode"
        out = subprocess.run(["make", "-B", "-C", native],
                             capture_output=True, text=True, timeout=600)
        if out.returncode != 0:
            raise Refused(f"native library did not build: "
                          f"{out.stderr[-500:]}")
        return "built"


def write_program_config(run: Run, departures: dict) -> str:
    """The program's config for this run, in the run directory: its
    defaults plus the configuration file's ``program_config`` departures
    (each listed there with its reason) plus the driver's own."""
    from relayrl_tpu.config import default_config

    cfg = default_config()

    def merge(dst: dict, src: dict) -> None:
        for k, v in src.items():
            if isinstance(v, dict) and isinstance(dst.get(k), dict):
                merge(dst[k], v)
            else:
                dst[k] = v

    merge(cfg, run.config.get("program_config", {}))
    merge(cfg, departures)
    path = os.path.join(run.run_dir, "relayrl_config.json")
    with open(path, "w") as f:
        json.dump(cfg, f, indent=1)
    return path


def on_tpu(tree) -> bool:
    import jax

    return all(d.platform == "tpu"
               for leaf in jax.tree_util.tree_leaves(tree)
               if isinstance(leaf, jax.Array) for d in leaf.devices())


def tree_checksum(tree) -> int:
    """crc32 over every leaf's bytes in path order: equal trees, equal
    numbers, on the learner and in an actor child."""
    import zlib

    import jax
    import numpy as np

    crc = 0
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in sorted(leaves, key=lambda kv: jax.tree_util.keystr(
            kv[0])):
        crc = zlib.crc32(np.ascontiguousarray(np.asarray(leaf)).tobytes(),
                         crc)
    return crc


def reference_check(run: Run, policy, params, obs_sample) -> None:
    """The system's action log-probabilities and values against the plain
    float32 reference, on a seeded sample at the published widths, outside
    the measured window. ``policy.evaluate`` is the learner-side forward;
    evaluating it for every action (the trunk does not depend on the
    action, so ``vmap`` computes it once) gives the normalised logits."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    act_dim = int(run.config["act_dim"])
    obs = jnp.asarray(obs_sample, jnp.float32)

    def system(params, obs):
        def one(a):
            act = jnp.full(obs.shape[:-1], a, jnp.int32)
            logp, _ent, v = policy.evaluate(params, obs, act)
            return logp, v

        logp, v = jax.vmap(one)(jnp.arange(act_dim))
        return jnp.moveaxis(logp, 0, -1), v[0]

    logp_sys, v_sys = jax.jit(system)(params, obs)
    logp_ref, v_ref = run.reference.forward(params, obs, run.config)
    err_logp = float(jnp.max(jnp.abs(logp_sys - logp_ref)))
    err_v = float(jnp.max(jnp.abs(v_sys - v_ref)))
    # errors are judged against the size of what is compared: the learner
    # has trained for the whole window, and its logits have grown with it
    spread = float(jnp.max(logp_ref) - jnp.min(logp_ref))
    v_scale = float(jnp.max(jnp.abs(v_ref)))
    rel_logp = err_logp / max(1.0, spread)
    rel_v = err_v / max(1.0, v_scale)
    tol = run.config["tolerance"]
    run.notes["reference"] = {
        "max_abs_dlogp": err_logp, "max_abs_dv": err_v,
        "logp_range": spread, "v_max_abs": v_scale,
        "rel_dlogp": rel_logp, "rel_dv": rel_v,
        "sample_shape": list(obs.shape),
        "tolerance": {k: tol[k] for k in ("logp_rel", "value_rel")}}
    ok = (np.isfinite(err_logp) and np.isfinite(err_v)
          and rel_logp <= tol["logp_rel"] and rel_v <= tol["value_rel"]
          # no spread at all would mean nothing was compared
          and spread > 0)
    run.check("reference", ok, json.dumps(run.notes["reference"]))


def finish_run(run: Run) -> dict:
    """Memory, per-layer readers, the warm-cache rule, the result line."""
    import jax

    # This runtime accounts a program's temporaries apart from the buffers:
    # ``peak_bytes_in_use`` is parameters, optimizer state and staged
    # batches; ``peak_bytes_reserved`` is what the largest program set aside
    # while it ran, and equals ``memory_analysis()``'s temporaries for it
    # (gpt2m-policy.update: 8.64 GB read, 8.70 GB compiled; nature-cnn: 4.28
    # and 4.28; PERF.md section 2). The chip holds both at once, so its peak
    # is their sum, on the fullest chip; the two parts are printed beside it.
    peak, parts = 0, (0, 0)
    for d in jax.devices():
        try:
            stats = dict(d.memory_stats() or {})
        except Exception:  # the CPU backend of a rehearsal reports none
            stats = {}
        run.memory_stats = run.memory_stats or stats  # first chip's, kept
        in_use = stats.get("peak_bytes_in_use", 0)
        reserved = stats.get("peak_bytes_reserved", 0)
        if in_use + reserved >= peak:
            peak, parts = in_use + reserved, (in_use, reserved)
    run.memory_peak_bytes = int(peak)
    device = dict(run.device, memory_peak_bytes=int(peak),
                  peak_bytes_in_use=int(parts[0]),
                  peak_bytes_reserved=int(parts[1]))

    run.check("compiles_in_window", run.window_compile_requests == 0,
              f"{run.window_compile_requests} compile requests inside the "
              f"measured window")
    if run.cache_was_warm:
        run.check("warm_cache", run.compiles.requests == run.compiles.hits,
                  f"{run.compiles.requests} requests, {run.compiles.hits} "
                  f"hits in a checkout whose cache was warm")
    say(f"compiles: {run.compiles.requests} requests, {run.compiles.hits} "
        f"from the persistent cache, {run.window_compile_requests} inside "
        f"the window")

    if run.trace:
        metrics = {}
        for m in run.spec["per_layer"]:
            value = load_layer_metric(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        if run.trace_reduced is not None:
            device["busy_s"] = run.trace_reduced["busy_s"]
            device["window_s"] = run.trace_reduced["window_s"]
    else:
        run.e2e["setup_s"] = run.setup_s
        metrics = {m["name"]: {"value": float(run.e2e[m["name"]]),
                               "unit": m["unit"]}
                   for m in run.spec["end_to_end"]}
    line = {"correct": all(run.checks.values()),
            "attempted": int(run.attempted), "failed": int(run.failed),
            "metrics": metrics, "device": device}
    if run.trace and run.trace_reduced is not None:
        line["breakdown"] = {
            "device_ops": run.trace_reduced["device_ops"],
            "idle_gaps": run.trace_reduced["idle_gaps"]}
    line["checks"] = run.checks
    line["phases"] = {k: round(v, 4) for k, v in run.phase_s.items()}
    line["notes"] = {k: v for k, v in run.notes.items()
                     if k != "trace_layout"}
    line["notes"]["memory_stats"] = run.memory_stats
    line["notes"]["window"] = {"window_s": run.window_s,
                               "updates": run.updates,
                               "samples": run.samples, **run.e2e}
    if "compared" in run.notes:
        # each number a driver compared, beside its limit: last in the line
        line["compared"] = line["notes"].pop("compared")
    return line


def clean_up(run: Run, line: dict | None) -> None:
    """Remove what the run wrote, except the compile cache and the one
    output file — so the twelfth run of a check starts as the second."""
    shutil.rmtree(run.run_dir, ignore_errors=True)
    if line is None:
        return
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(run.out_path, "w") as f:
        json.dump({"workload": run.workload, "seed": run.seed,
                   "trace": run.trace, "setup_s": run.setup_s,
                   "phases": run.phase_s, "result": line,
                   "cache_complete_for": run.cache_dir or None,
                   "trace_layout": run.notes.get("trace_layout")}, f,
                  indent=1)
