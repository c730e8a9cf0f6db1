"""jaxlint (relayrl_tpu.analysis) — rule units, suppression/baseline
mechanics, CLI contract, and the repo-wide lint gate.

Layout mirrors docs/static_analysis.md: every rule has at least one
positive (fires) and one negative (stays silent) snippet; the gate test
at the bottom is the CI hook — it fails the suite the moment a new
non-baselined finding lands anywhere in the framework tree.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

from relayrl_tpu.analysis import (
    all_rules,
    analyze_paths,
    analyze_source,
    apply_baseline,
    load_baseline,
    main,
    rules_by_code,
    write_baseline,
)

pytestmark = pytest.mark.jaxlint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "relayrl_tpu")
BASELINE = os.path.join(PKG, "analysis", "baseline.json")

# Everything the gate covers: the package plus every committed harness
# that ships with the framework.
GATE_PATHS = [
    PKG,
    os.path.join(REPO, "examples"),
    os.path.join(REPO, "scripts"),
    os.path.join(REPO, "tests"),  # tests/drills/ included
]


def codes(src: str) -> list[str]:
    return [f.rule for f in analyze_source(textwrap.dedent(src), "x.py")]


class TestRegistry:
    def test_at_least_eight_rules(self):
        assert len(all_rules()) >= 8

    def test_codes_unique_and_described(self):
        by_code = rules_by_code()  # raises on duplicates
        for code, rule in by_code.items():
            assert code and rule.name and rule.description, code


class TestPrngKeyReuse:
    def test_positive_reuse(self):
        assert codes("""
            import jax
            def f(rng):
                a = jax.random.normal(rng, (3,))
                b = jax.random.uniform(rng, (3,))
                return a + b
        """) == ["JAX01"]

    def test_positive_reuse_in_loop(self):
        assert "JAX01" in codes("""
            import jax
            def f(rng, n):
                out = []
                for _ in range(n):
                    out.append(jax.random.normal(rng, (3,)))
                return out
        """)

    def test_negative_split_chain(self):
        assert codes("""
            import jax
            def f(rng):
                rng, sub = jax.random.split(rng)
                a = jax.random.normal(sub, (3,))
                rng, sub = jax.random.split(rng)
                return a + jax.random.uniform(sub, (3,))
        """) == []

    def test_negative_loop_with_resplit(self):
        assert codes("""
            import jax
            def f(rng, n):
                out = []
                for _ in range(n):
                    rng, sub = jax.random.split(rng)
                    out.append(jax.random.normal(sub, (3,)))
                return out
        """) == []

    def test_negative_prngkey_int_seed_is_not_a_key(self):
        # PRNGKey(seed) consumes an INT, not a key — a seeded loop of
        # fresh keys (test/bench idiom) must not flag.
        assert codes("""
            import jax
            def f(policy, params, obs):
                for seed in range(5):
                    policy.step(params, jax.random.PRNGKey(seed), obs)
        """) == []

    def test_negative_two_lambdas_each_binding_rng(self):
        # lambda params are fresh bindings — no cross-lambda reuse
        assert codes("""
            import jax
            f = lambda rng: jax.random.normal(rng, (3,))
            g = lambda rng: jax.random.uniform(rng, (3,))
        """) == []

    def test_negative_comprehension_iteration_var(self):
        # the canonical `for k in jax.random.split(rng, n)` fan-out
        assert codes("""
            import jax
            def f(rng, n):
                keys = jax.random.split(rng, n)
                a = [jax.random.normal(k, (3,)) for k in keys]
                b = [jax.random.uniform(k, (3,)) for k in keys]
                return a, b
        """) == []

    def test_positive_reuse_inside_one_lambda(self):
        assert "JAX01" in codes("""
            import jax
            f = lambda rng: (jax.random.normal(rng, (3,))
                             + jax.random.uniform(rng, (3,)))
        """)

    def test_negative_branches_use_key_once_each(self):
        assert codes("""
            import jax
            def f(rng, greedy):
                if greedy:
                    return jax.random.categorical(rng, None)
                else:
                    return jax.random.normal(rng, (3,))
        """) == []


class TestHostSyncInJit:
    def test_positive_numpy_and_cast(self):
        got = codes("""
            import jax
            import numpy as np
            @jax.jit
            def f(x):
                y = np.asarray(x)
                return float(y)
        """)
        assert got.count("JAX02") == 2

    def test_positive_item_in_scan_body(self):
        assert "JAX02" in codes("""
            import jax
            def body(c, x):
                c = c + x.item()
                return c, x
            def g(xs):
                return jax.lax.scan(body, 0.0, xs)
        """)

    def test_negative_trace_time_static_casts(self):
        # float(len(x)) / int(x.shape[0]) are static under trace — legal
        assert codes("""
            import jax
            @jax.jit
            def f(x):
                scale = float(len(x))
                n = int(x.shape[0])
                return x * scale / n
        """) == []

    def test_negative_jnp_and_host_code(self):
        assert codes("""
            import jax
            import jax.numpy as jnp
            import numpy as np
            @jax.jit
            def f(x):
                return jnp.asarray(x) * 2
            def host(v):
                return float(np.asarray(v))  # not traced: fine
        """) == []


class TestPrintInJit:
    def test_positive(self):
        assert "JAX03" in codes("""
            import jax
            @jax.jit
            def f(x):
                print(x)
                return x
        """)

    def test_negative_debug_print_and_host_print(self):
        assert codes("""
            import jax
            @jax.jit
            def f(x):
                jax.debug.print("x={x}", x=x)
                return x
            def host():
                print("hello")
        """) == []


class TestUntraceableArgNoStatic:
    def test_positive_str_param(self):
        assert "JAX04" in codes("""
            import jax
            def f(x, mode: str):
                return x
            g = jax.jit(f)
        """)

    def test_negative_with_static_argnames(self):
        assert codes("""
            import jax
            def f(x, mode: str):
                return x
            g = jax.jit(f, static_argnames=("mode",))
        """) == []

    def test_negative_method_does_not_shadow_wrapped_function(self):
        # jit wraps the module-level `loss`; the same-named method's
        # str param must not be attributed to it
        assert codes("""
            import jax
            def loss(x):
                return x
            g = jax.jit(loss)
            class Trainer:
                def loss(self, x, mode: str):
                    return x
        """) == []

    def test_negative_pytree_dict_batch_is_traceable(self):
        # dict batches are pytrees — the learner's own signature.
        assert codes("""
            import jax
            from typing import Mapping
            def update(state, batch: Mapping[str, jax.Array]):
                return state
            g = jax.jit(update, donate_argnums=0)
        """) == []


class TestMissingDonate:
    def test_positive_update_name(self):
        assert "JAX05" in codes("""
            import jax
            def train_step(state, batch):
                return state
            step = jax.jit(train_step)
        """)

    def test_positive_target_name(self):
        assert "JAX05" in codes("""
            import jax
            class A:
                def setup(self, run):
                    self._update = jax.jit(run)
        """)

    def test_negative_with_donate(self):
        assert codes("""
            import jax
            def train_step(state, batch):
                return state
            step = jax.jit(train_step, donate_argnums=0)
        """) == []

    def test_negative_non_update_name(self):
        assert codes("""
            import jax
            def evaluate(params, obs):
                return obs
            ev = jax.jit(evaluate)
        """) == []


class TestUntimedJitDispatch:
    def test_positive(self):
        assert "JAX06" in codes("""
            import jax, time
            def g(x): return x
            f = jax.jit(g)
            def bench(x):
                t0 = time.perf_counter()
                y = f(x)
                return y, time.perf_counter() - t0
        """)

    def test_negative_with_block(self):
        assert codes("""
            import jax, time
            def g(x): return x
            f = jax.jit(g)
            def bench(x):
                t0 = time.perf_counter()
                y = jax.block_until_ready(f(x))
                return y, time.perf_counter() - t0
        """) == []

    def test_negative_np_asarray_host_fence(self):
        assert codes("""
            import jax, time
            import numpy as np
            def g(x): return x
            f = jax.jit(g)
            def bench(x):
                t0 = time.perf_counter()
                y = np.asarray(f(x))
                return y, time.perf_counter() - t0
        """) == []

    def test_negative_float_host_fence(self):
        # A host readback of a value that depends on the chain fences
        # it (chip_smoke.py phase A checks both fences on the chip).
        assert codes("""
            import jax, time
            def g(x): return x
            f = jax.jit(g)
            def bench(x):
                t0 = time.perf_counter()
                y = f(x)
                float(y)
                return time.perf_counter() - t0
        """) == []


class TestBlockingUnderLock:
    def test_positive_sleep(self):
        assert "CONC01" in codes("""
            import time, threading
            lock = threading.Lock()
            def f():
                with lock:
                    time.sleep(1.0)
        """)

    def test_positive_recv_under_attr_lock(self):
        assert "CONC01" in codes("""
            class T:
                def f(self, sock):
                    with self._pub_lock:
                        return sock.recv()
        """)

    def test_negative_sleep_outside_lock(self):
        assert codes("""
            import time
            def f(lock):
                with lock:
                    x = 1
                time.sleep(0.1)
                return x
        """) == []

    def test_positive_thread_join_under_lock(self):
        assert "CONC01" in codes("""
            class T:
                def f(self):
                    with self._lock:
                        self._listener_thread.join()
        """)

    def test_negative_string_and_path_join_under_lock(self):
        # str.join / os.path.join are not blocking I/O
        assert codes("""
            import os
            def f(lock, items):
                with lock:
                    name = ", ".join(items)
                    return os.path.join("a", name)
        """) == []

    def test_negative_nested_def_not_executed_under_lock(self):
        assert codes("""
            import time
            def f(lock):
                with lock:
                    def cb():
                        time.sleep(1.0)
                return cb
        """) == []


class TestWallClockLatency:
    def test_positive_inline_interval(self):
        assert "TEL01" in codes("""
            import time
            def f(hist, work):
                t0 = time.time()
                work()
                hist.observe(time.time() - t0)
        """)

    def test_positive_named_interval_through_set(self):
        assert "TEL01" in codes("""
            import time
            def f(gauge, work):
                start = time.time()
                work()
                elapsed = time.time() - start
                gauge.set(elapsed)
        """)

    def test_negative_monotonic_interval(self):
        assert codes("""
            import time
            def f(hist, work):
                t0 = time.monotonic()
                work()
                hist.observe(time.monotonic() - t0)
        """) == []

    def test_negative_wall_timestamp_not_interval(self):
        # recording the wall clock itself is the cross-host-timestamp
        # use case the convention keeps time.time() for
        assert codes("""
            import time
            def f(gauge):
                gauge.set(time.time())
        """) == []


class TestBareExcept:
    def test_positive(self):
        assert "CONC02" in codes("""
            def f():
                try:
                    pass
                except:
                    pass
        """)

    def test_negative_typed(self):
        assert codes("""
            def f():
                try:
                    pass
                except Exception:
                    pass
        """) == []


class TestModuleLevelDeviceTouch:
    def test_positive_module_scope(self):
        assert "IMP01" in codes("""
            import jax
            DEVICES = jax.devices()
        """)

    def test_positive_config_update_in_class_body(self):
        assert "IMP01" in codes("""
            import jax
            class Cfg:
                jax.config.update("jax_enable_x64", True)
        """)

    def test_negative_inside_function(self):
        assert codes("""
            import jax
            def devices():
                return jax.devices()
        """) == []

    def test_negative_exempt_init(self):
        src = "import jax\nD = jax.devices()\n"
        assert [f.rule for f in
                analyze_source(src, "pkg/__init__.py")] == []


class TestSuppression:
    BAD = "import jax\nD = jax.devices()\n"

    def test_same_line(self):
        src = ("import jax\n"
               "D = jax.devices()  # jaxlint: disable=IMP01\n")
        assert analyze_source(src, "x.py") == []

    def test_line_above_and_slug(self):
        src = ("import jax\n"
               "# jaxlint: disable=module-level-device-touch\n"
               "D = jax.devices()\n")
        assert analyze_source(src, "x.py") == []

    def test_disable_all(self):
        src = ("import jax\n"
               "D = jax.devices()  # jaxlint: disable=all\n")
        assert analyze_source(src, "x.py") == []

    def test_wrong_code_does_not_suppress(self):
        src = ("import jax\n"
               "D = jax.devices()  # jaxlint: disable=JAX01\n")
        assert [f.rule for f in analyze_source(src, "x.py")] == ["IMP01"]

    def test_inline_disable_does_not_leak_to_next_line(self):
        src = ("import jax\n"
               "D = jax.devices()  # jaxlint: disable=IMP01\n"
               "E = jax.devices()\n")
        got = analyze_source(src, "x.py")
        assert [(f.rule, f.line) for f in got] == [("IMP01", 3)]

    def test_above_line_disable_requires_comment_only_line(self):
        # a CODE line above with a trailing disable covers itself only
        src = ("import jax\n"
               "x = 1  # jaxlint: disable=IMP01\n"
               "E = jax.devices()\n")
        assert [f.rule for f in analyze_source(src, "x.py")] == ["IMP01"]

    def test_trailing_reason_still_suppresses(self):
        # the documented style pairs every disable with a reason
        src = ("import jax\n"
               "D = jax.devices()  # jaxlint: disable=IMP01 - entry "
               "script, backend already up\n")
        assert analyze_source(src, "x.py") == []


class TestEngineMechanics:
    def test_syntax_error_is_a_parse_finding(self):
        got = analyze_source("def broken(:\n", "x.py")
        assert [f.rule for f in got] == ["PARSE"]

    def test_paths_relative_to_scan_root_parent(self, tmp_path):
        pkg = tmp_path / "mypkg"
        pkg.mkdir()
        (pkg / "m.py").write_text("import jax\nD = jax.devices()\n")
        findings = analyze_paths([str(pkg)])
        assert [f.path for f in findings] == ["mypkg/m.py"]

    def test_file_arg_under_cwd_keys_like_directory_scan(self, tmp_path,
                                                         monkeypatch):
        # A per-file run from the repo root must produce the same baseline
        # key as the directory scan, or baselined findings resurface.
        pkg = tmp_path / "mypkg"
        pkg.mkdir()
        (pkg / "m.py").write_text("import jax\nD = jax.devices()\n")
        monkeypatch.chdir(tmp_path)
        by_dir = analyze_paths([str(pkg)])
        by_file = analyze_paths(["mypkg/m.py"])
        by_dot = analyze_paths(["."])
        assert [f.key() for f in by_file] == [f.key() for f in by_dir]
        assert [f.key() for f in by_dot] == [f.key() for f in by_dir]

    def test_keys_anchor_at_repo_root_regardless_of_cwd(self, tmp_path,
                                                        monkeypatch):
        # with a repo marker present, a scan from a SUBDIRECTORY must
        # produce the same baseline keys as one from the repo root
        (tmp_path / "pyproject.toml").write_text("")
        pkg = tmp_path / "mypkg"
        pkg.mkdir()
        (pkg / "m.py").write_text("import jax\nD = jax.devices()\n")
        monkeypatch.chdir(tmp_path)
        from_root = analyze_paths(["mypkg"])
        monkeypatch.chdir(pkg)
        from_subdir = analyze_paths(["."])
        by_abs = analyze_paths([str(pkg / "m.py")])
        assert [f.path for f in from_root] == ["mypkg/m.py"]
        assert [f.key() for f in from_subdir] == [f.key() for f in from_root]
        assert [f.key() for f in by_abs] == [f.key() for f in from_root]

    def test_baseline_roundtrip_match_and_stale(self, tmp_path):
        pkg = tmp_path / "p"
        pkg.mkdir()
        (pkg / "m.py").write_text("import jax\nD = jax.devices()\n")
        findings = analyze_paths([str(pkg)])
        assert len(findings) == 1
        bl = tmp_path / "baseline.json"
        write_baseline(bl, findings)
        new, matched, stale = apply_baseline(findings, load_baseline(bl))
        assert (new, matched, stale) == ([], 1, [])
        # fix the code -> the entry goes stale, nothing is new
        (pkg / "m.py").write_text("import jax\n")
        new, matched, stale = apply_baseline(
            analyze_paths([str(pkg)]), load_baseline(bl))
        assert new == [] and matched == 0 and len(stale) == 1

    def test_baseline_count_absorbs_exactly_n(self, tmp_path):
        pkg = tmp_path / "p"
        pkg.mkdir()
        # two IDENTICAL lines -> one baseline key with count=2
        (pkg / "m.py").write_text(
            "import jax\nD = jax.devices()\nD = jax.devices()\n")
        findings = analyze_paths([str(pkg)])
        assert len(findings) == 2
        bl = tmp_path / "b.json"
        write_baseline(bl, findings)
        data = json.loads(bl.read_text())
        assert data["findings"][0]["count"] == 2
        # a third copy of the same line is NEW
        (pkg / "m.py").write_text(
            "import jax\n" + "D = jax.devices()\n" * 3)
        new, matched, _ = apply_baseline(
            analyze_paths([str(pkg)]), load_baseline(bl))
        assert matched == 2 and len(new) == 1


class TestCli:
    def test_list_rules_exits_zero(self, capsys):
        assert main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for code in ("JAX01", "CONC01", "IMP01"):
            assert code in out

    def test_unknown_select_exits_two(self, capsys):
        assert main(["--select", "NOPE99", str(PKG)]) == 2

    def test_missing_path_exits_two(self):
        assert main(["/no/such/dir-jaxlint"]) == 2

    def test_new_finding_exits_one_then_baselined_zero(self, tmp_path,
                                                       capsys):
        bad = tmp_path / "bad.py"
        bad.write_text("import jax\nD = jax.devices()\n")
        bl = tmp_path / "b.json"
        assert main([str(bad), "--baseline", str(bl)]) == 1
        assert main([str(bad), "--baseline", str(bl),
                     "--write-baseline"]) == 0
        assert main([str(bad), "--baseline", str(bl)]) == 0

    # ISSUE 17 wall re-fit: subprocess CLI round-trip; still runs in
    # scripts/check.sh stage 2 (no marker filter there).
    @pytest.mark.slow
    def test_scoped_write_baseline_needs_explicit_path(self, tmp_path,
                                                       capsys):
        bad = tmp_path / "bad.py"
        bad.write_text("import jax\nD = jax.devices()\n")
        # --write-baseline without an explicit --baseline PATH is always
        # refused: any scan covers only a slice of the gate's scope, so
        # writing it to the shared default would drop grandfathered
        # entries from everywhere else.
        assert main([str(bad), "--write-baseline"]) == 2
        assert main([str(bad), "--select", "IMP01",
                     "--write-baseline"]) == 2
        assert main(["--write-baseline"]) == 2
        # explicit --baseline path -> allowed
        bl = tmp_path / "b.json"
        assert main([str(bad), "--select", "IMP01", "--baseline", str(bl),
                     "--write-baseline"]) == 0
        assert bl.is_file()

    def test_corrupt_baseline_exits_two_with_diagnostic(self, tmp_path,
                                                        capsys):
        bad = tmp_path / "bad.py"
        bad.write_text("import jax\nD = jax.devices()\n")
        bl = tmp_path / "broken.json"
        bl.write_text("{not json")
        assert main([str(bad), "--baseline", str(bl)]) == 2
        assert "cannot read baseline" in capsys.readouterr().err

    def test_hidden_and_venv_dirs_are_pruned(self, tmp_path):
        pkg = tmp_path / "proj"
        (pkg / ".venv" / "lib").mkdir(parents=True)
        (pkg / "src").mkdir()
        (pkg / ".venv" / "lib" / "vendored.py").write_text(
            "import jax\nD = jax.devices()\n")
        (pkg / "src" / "m.py").write_text("import jax\nD = jax.devices()\n")
        findings = analyze_paths([str(pkg)])
        assert [f.path for f in findings] == ["proj/src/m.py"]

    def test_json_format(self, tmp_path, capsys):
        bad = tmp_path / "bad.py"
        bad.write_text("import jax\nD = jax.devices()\n")
        assert main([str(bad), "--no-baseline", "--format", "json"]) == 1
        payload = json.loads(capsys.readouterr().out)
        assert payload["new"][0]["rule"] == "IMP01"


class TestRepoGate:
    """The CI gate: the framework tree must be clean modulo the
    committed baseline. A finding here means either fix the code,
    suppress it with a reasoned `# jaxlint: disable=...`, or (for
    pre-existing debt only) regenerate the baseline."""

    def test_framework_tree_has_no_new_findings(self):
        findings = analyze_paths(GATE_PATHS)
        baseline = load_baseline(BASELINE) if os.path.isfile(BASELINE) else {}
        new, _matched, _stale = apply_baseline(findings, baseline)
        assert not new, "new jaxlint findings:\n" + "\n".join(
            f.format() for f in new)

    def test_package_gate_via_module_invocation(self):
        # The exact invocation CI and the docs use, end to end.
        proc = subprocess.run(
            [sys.executable, "-m", "relayrl_tpu.analysis", PKG],
            cwd=REPO, capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_module_invocation_fails_on_new_finding(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("import jax\nD = jax.devices()\n")
        proc = subprocess.run(
            [sys.executable, "-m", "relayrl_tpu.analysis", str(bad)],
            cwd=REPO, capture_output=True, text=True, timeout=120)
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "IMP01" in proc.stdout
