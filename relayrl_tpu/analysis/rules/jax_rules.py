"""JAX hazard rules: the silent-throughput-killer class.

Every rule here targets a failure mode that produces *wrong numbers or
slow programs without an exception*: reused PRNG keys correlate samples,
host syncs inside traced code serialize the dispatch pipeline, prints
inside jit fire once at trace time, untraceable args retrace per call,
missing donation doubles live buffers, and timing without
``block_until_ready`` measures dispatch latency instead of compute.
"""

from __future__ import annotations

import ast
import re
from typing import Iterator

from relayrl_tpu.analysis.engine import (
    JIT_WRAPPERS,
    ModuleInfo,
    Rule,
    qualname,
    walk_skip_nested_functions as _walk_skip_nested_functions,
)

# jax.random calls that *produce* keys (assigning their result creates a
# fresh key; passing a key to them still consumes it).
_KEY_MAKERS = frozenset({
    "jax.random.PRNGKey",
    "jax.random.key",
    "jax.random.split",
    "jax.random.fold_in",
    "jax.random.clone",
})

_TIMING_CALLS = frozenset({
    "time.time", "time.time_ns",
    "time.perf_counter", "time.perf_counter_ns",
    "time.monotonic", "time.monotonic_ns",
})


def _first_key_arg(call: ast.Call) -> str | None:
    """The PRNG key operand of a ``jax.random.*`` call: first positional
    arg, or the ``key=`` keyword — only when it is a bare Name (attribute
    keys live across methods; tracking them needs flow analysis a linter
    should not pretend to have)."""
    if call.args and isinstance(call.args[0], ast.Name):
        return call.args[0].id
    for kw in call.keywords:
        if kw.arg == "key" and isinstance(kw.value, ast.Name):
            return kw.value.id
    return None


class PrngKeyReuse(Rule):
    """A PRNG key consumed by two ``jax.random.*`` calls yields
    *correlated* randomness — exploration noise that repeats, dropout
    masks equal to sampling masks. JAX never warns; the learning curve
    just quietly degrades."""

    code = "JAX01"
    name = "prng-key-reuse"
    description = ("PRNG key passed to more than one jax.random call "
                   "without an intervening split/fold_in")

    # Subtrees that bind their own names: consumption inside them must
    # not leak into the enclosing scope (two lambdas each taking `rng`,
    # or two comprehensions reusing the iteration variable `k`, are zero
    # reuse). Each is scanned as its own scope below.
    _OWN_SCOPE = (ast.Lambda, ast.ListComp, ast.SetComp, ast.DictComp,
                  ast.GeneratorExp)

    def check(self, module: ModuleInfo) -> Iterator[tuple[ast.AST, str]]:
        scopes: list[ast.AST] = [module.tree]
        scopes += [n for n in ast.walk(module.tree)
                   if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
        reported: set[tuple[int, int, str]] = set()
        for scope in scopes:
            body = scope.body if hasattr(scope, "body") else []
            findings: list[tuple[ast.AST, str]] = []
            self._scan_block(module, body, {}, findings, reported)
            yield from findings
        # lambda/comprehension bodies, each as an isolated scope
        for node in ast.walk(module.tree):
            if isinstance(node, self._OWN_SCOPE):
                findings = []
                self._process_expr(module, node, {}, findings, reported,
                                   enter_scope=True)
                yield from findings

    # state: name -> ("alive", line) fresh key | ("used", line) consumed
    def _scan_block(self, module: ModuleInfo, stmts, state: dict,
                    findings: list, reported: set) -> dict:
        for stmt in stmts:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue  # separate scope, visited on its own
            if isinstance(stmt, ast.If):
                s1 = self._scan_block(module, stmt.body, dict(state),
                                      findings, reported)
                s2 = self._scan_block(module, stmt.orelse, dict(state),
                                      findings, reported)
                state = self._merge(s1, s2)
            elif isinstance(stmt, (ast.For, ast.AsyncFor, ast.While)):
                # Two passes: a consume-without-resplit inside a loop body
                # is a reuse across iterations the first pass can't see.
                inner = self._scan_block(module, stmt.body, dict(state),
                                         findings, reported)
                self._scan_block(module, stmt.body, dict(inner),
                                 findings, reported)
                state = self._merge(state, inner)
                state = self._scan_block(module, stmt.orelse, state,
                                         findings, reported)
            elif isinstance(stmt, ast.Try):
                state = self._scan_block(module, stmt.body, state,
                                         findings, reported)
                for h in stmt.handlers:
                    state = self._scan_block(module, h.body, state,
                                             findings, reported)
                state = self._scan_block(module, stmt.orelse, state,
                                         findings, reported)
                state = self._scan_block(module, stmt.finalbody, state,
                                         findings, reported)
            elif isinstance(stmt, (ast.With, ast.AsyncWith)):
                for item in stmt.items:
                    self._process_expr(module, item.context_expr, state,
                                       findings, reported)
                state = self._scan_block(module, stmt.body, state,
                                         findings, reported)
            else:
                self._process_stmt(module, stmt, state, findings, reported)
        return state

    @staticmethod
    def _merge(s1: dict, s2: dict) -> dict:
        out = {}
        for name in set(s1) | set(s2):
            v1, v2 = s1.get(name), s2.get(name)
            if v1 is None or v2 is None:
                continue  # dropped/opaque in one branch: be conservative
            used = [v for v in (v1, v2) if v[0] == "used"]
            out[name] = min(used) if used else v1
        return out

    def _walk_expr(self, node, top: bool = False):
        """Expression walk that stays in the current binding scope."""
        if not top and isinstance(node, self._OWN_SCOPE + (
                ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            return
        yield node
        for child in ast.iter_child_nodes(node):
            yield from self._walk_expr(child)

    def _process_expr(self, module, expr, state, findings, reported,
                      enter_scope: bool = False):
        calls = [n for n in self._walk_expr(expr, top=enter_scope)
                 if isinstance(n, ast.Call)]
        calls.sort(key=lambda c: (c.lineno, c.col_offset))
        for call in calls:
            resolved = module.resolved_call(call)
            if not resolved or not resolved.startswith("jax.random."):
                continue
            if resolved in ("jax.random.PRNGKey", "jax.random.key"):
                continue  # argument is an int seed, not a key
            key = _first_key_arg(call)
            if key is None:
                continue
            prior = state.get(key)
            if prior is not None and prior[0] == "used":
                mark = (call.lineno, call.col_offset, key)
                if mark not in reported:
                    reported.add(mark)
                    findings.append((call, (
                        f"PRNG key `{key}` is reused here (already "
                        f"consumed by a jax.random call on line "
                        f"{prior[1]}); derive fresh keys with "
                        f"`jax.random.split` — reuse silently correlates "
                        f"the two sample streams")))
            else:
                state[key] = ("used", call.lineno)

    def _process_stmt(self, module, stmt, state, findings, reported):
        self._process_expr(module, stmt, state, findings, reported)
        targets: list[ast.expr] = []
        if isinstance(stmt, ast.Assign):
            targets = stmt.targets
            value = stmt.value
        elif isinstance(stmt, (ast.AnnAssign, ast.AugAssign)):
            targets = [stmt.target]
            value = stmt.value
        else:
            return
        fresh = (isinstance(value, ast.Call)
                 and module.resolved_call(value) in _KEY_MAKERS)
        for target in targets:
            elts = target.elts if isinstance(target, ast.Tuple) else [target]
            for el in elts:
                if isinstance(el, ast.Starred):
                    el = el.value
                if isinstance(el, ast.Name):
                    if fresh:
                        state[el.id] = ("alive", stmt.lineno)
                    else:
                        state.pop(el.id, None)


class HostSyncInJit(Rule):
    """Host<->device round-trips inside traced code either fail at trace
    time (``float()`` on a tracer) or — worse — silently pin the value to
    host numpy and fall out of the compiled program."""

    code = "JAX02"
    name = "host-sync-in-jit"
    description = ("host numpy / float() / .item() call inside a "
                   "jit-traced function")

    _CASTS = frozenset({"float", "int", "bool", "complex"})
    _SYNC_ATTRS = frozenset({"item", "tolist"})

    def check(self, module: ModuleInfo) -> Iterator[tuple[ast.AST, str]]:
        seen: set[tuple[int, int]] = set()
        for fn in module.traced_functions:
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                mark = (node.lineno, node.col_offset)
                if mark in seen:
                    continue
                msg = self._diagnose(module, node)
                if msg:
                    seen.add(mark)
                    yield node, msg

    def _diagnose(self, module: ModuleInfo, call: ast.Call) -> str | None:
        resolved = module.resolved_call(call)
        if resolved and (resolved.startswith("numpy.")
                         or resolved == "numpy"):
            return (f"host numpy call `{qualname(call.func)}` inside a "
                    f"traced function — use jax.numpy; host ops force a "
                    f"sync and fall out of the compiled program")
        # Only bare-Name cast arguments are flagged: `float(len(x))` and
        # `float(x.shape[0])` are trace-time statics (legal under jit),
        # and attribute args are usually static hyperparams — precision
        # over recall.
        if (resolved in self._CASTS and len(call.args) == 1
                and isinstance(call.args[0], ast.Name)):
            return (f"`{resolved}()` on a traced value forces a host "
                    f"sync (or a trace-time error) inside jit; keep the "
                    f"value on device or move the cast outside the "
                    f"traced function")
        if (isinstance(call.func, ast.Attribute)
                and call.func.attr in self._SYNC_ATTRS
                and not call.args):
            return (f"`.{call.func.attr}()` inside a traced function "
                    f"synchronizes host and device; compute on-device "
                    f"and convert outside the jit boundary")
        return None


class PrintInJit(Rule):
    """``print`` in traced code fires once, at trace time, with tracer
    reprs — not per step with values. ``jax.debug.print`` is the
    intended tool."""

    code = "JAX03"
    name = "print-in-jit"
    description = "python print() inside a jit-traced function"

    def check(self, module: ModuleInfo) -> Iterator[tuple[ast.AST, str]]:
        seen: set[tuple[int, int]] = set()
        for fn in module.traced_functions:
            for node in ast.walk(fn):
                if (isinstance(node, ast.Call)
                        and module.resolved_call(node) == "print"
                        and (node.lineno, node.col_offset) not in seen):
                    seen.add((node.lineno, node.col_offset))
                    yield node, (
                        "print() inside a traced function executes once "
                        "at trace time with tracer values; use "
                        "jax.debug.print(...) for per-step output")


class UntraceableArgNoStatic(Rule):
    """A jitted function whose signature declares a value jax cannot
    trace (str/bytes/Callable) needs ``static_argnums``/
    ``static_argnames`` — otherwise every call raises, or retraces when
    smuggled through as a weak type."""

    code = "JAX04"
    name = "untraceable-arg-no-static"
    description = ("jit-wrapped function takes str/bytes/Callable "
                   "parameters without static_argnums/static_argnames")

    _UNTRACEABLE = frozenset({
        "str", "bytes", "Callable", "callable",
        "typing.Callable", "collections.abc.Callable",
    })

    def check(self, module: ModuleInfo) -> Iterator[tuple[ast.AST, str]]:
        # A bare Name handed to jax.jit refers to a module-level (or
        # local) function — NOT a same-named method somewhere else in the
        # file. Prefer the module-level def; fall back to a name that is
        # unique across the module; skip ambiguous names entirely rather
        # than checking the wrong signature.
        top = {n.name: n for n in module.tree.body
               if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
        by_name: dict[str, list] = {}
        for n in ast.walk(module.tree):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                by_name.setdefault(n.name, []).append(n)
        defs = dict(top)
        for name, nodes in by_name.items():
            if name not in defs and len(nodes) == 1:
                defs[name] = nodes[0]
        for call, wrapped, _target in module.jit_calls:
            if not isinstance(wrapped, ast.Name):
                continue
            fn = defs.get(wrapped.id)
            if fn is None or self._has_static_kwarg(call):
                continue
            bad = self._untraceable_params(module, fn)
            if bad:
                yield call, self._message(wrapped.id, bad)
        for fn in defs.values():
            dec_call = module.jit_decorator_call(fn)
            plain_jit = any(module.is_jit_decorator(d)
                            and not isinstance(d, ast.Call)
                            for d in fn.decorator_list)
            if dec_call is None and not plain_jit:
                continue
            if dec_call is not None and self._has_static_kwarg(dec_call):
                continue
            bad = self._untraceable_params(module, fn)
            if bad:
                yield fn, self._message(fn.name, bad)

    @staticmethod
    def _has_static_kwarg(call: ast.Call) -> bool:
        names = {kw.arg for kw in call.keywords}
        return bool(names & {"static_argnums", "static_argnames"})

    def _untraceable_params(self, module: ModuleInfo, fn) -> list[str]:
        bad = []
        params = list(fn.args.posonlyargs) + list(fn.args.args) + list(
            fn.args.kwonlyargs)
        for p in params:
            if p.arg in ("self", "cls") or p.annotation is None:
                continue
            ann = p.annotation
            if isinstance(ann, ast.Subscript):
                ann = ann.value
            if module.resolve(qualname(ann)) in self._UNTRACEABLE:
                bad.append(p.arg)
        return bad

    @staticmethod
    def _message(fn_name: str, bad: list[str]) -> str:
        return (f"jit of `{fn_name}` takes untraceable parameter(s) "
                f"{', '.join(repr(b) for b in bad)} — mark them with "
                f"static_argnums/static_argnames or hoist them out of "
                f"the traced signature")


class MissingDonate(Rule):
    """Train-step/update functions carry the full optimizer + param state
    through every call; without ``donate_argnums`` XLA keeps input AND
    output buffers live across the update — on TPU that halves the
    largest fittable model."""

    code = "JAX05"
    name = "missing-donate"
    description = ("jit of a *train_step*/*update* function without "
                   "donate_argnums/donate_argnames")

    _NAME_RE = re.compile(r"(train_step|update)", re.IGNORECASE)

    def check(self, module: ModuleInfo) -> Iterator[tuple[ast.AST, str]]:
        for call, wrapped, target in module.jit_calls:
            if self._has_donate(call):
                continue
            label = None
            if isinstance(wrapped, ast.Name) and self._NAME_RE.search(
                    wrapped.id):
                label = wrapped.id
            elif target and self._NAME_RE.search(target.split(".")[-1]):
                label = target
            if label:
                yield call, self._message(label)
        for fn in ast.walk(module.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if not self._NAME_RE.search(fn.name):
                continue
            dec_call = module.jit_decorator_call(fn)
            plain = any(module.is_jit_decorator(d)
                        and not isinstance(d, ast.Call)
                        for d in fn.decorator_list)
            if plain or (dec_call is not None
                         and not self._has_donate(dec_call)):
                yield fn, self._message(fn.name)

    @staticmethod
    def _has_donate(call: ast.Call) -> bool:
        names = {kw.arg for kw in call.keywords}
        return bool(names & {"donate_argnums", "donate_argnames"})

    @staticmethod
    def _message(label: str) -> str:
        return (f"jit of `{label}` has no donate_argnums — the old "
                f"state buffers stay live across the update, doubling "
                f"peak memory for the largest training state")


class UntimedJitDispatch(Rule):
    """Jitted calls return before the device finishes (async dispatch);
    a wall-clock pair around one measures *enqueue* latency. Every such
    measurement needs a ``block_until_ready`` before the second
    timestamp."""

    code = "JAX06"
    name = "untimed-jit-dispatch"
    description = ("jitted call timed with time.*() pairs but no "
                   "block_until_ready in the function")

    def check(self, module: ModuleInfo) -> Iterator[tuple[ast.AST, str]]:
        for fn in ast.walk(module.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if self._has_block(module, fn):
                continue
            timings: list[tuple[int, int]] = []
            jit_calls: list[ast.Call] = []
            for node in _walk_skip_nested_functions(fn):
                if not isinstance(node, ast.Call):
                    continue
                resolved = module.resolved_call(node)
                if resolved in _TIMING_CALLS:
                    timings.append((node.lineno, node.col_offset))
                elif self._is_jitted_dispatch(module, node):
                    jit_calls.append(node)
            if len(timings) < 2 or not jit_calls:
                continue
            first, last = min(timings), max(timings)
            for call in jit_calls:
                pos = (call.lineno, call.col_offset)
                if first < pos < last:
                    yield call, (
                        "jitted call timed without block_until_ready — "
                        "dispatch is async, so this measures enqueue "
                        "latency, not device compute; call "
                        "jax.block_until_ready(result) before the "
                        "closing timestamp")
                    break  # one report per function is enough

    @staticmethod
    def _has_block(module: ModuleInfo, fn: ast.AST) -> bool:
        """True when the function contains an explicit fence:
        ``block_until_ready``, or a ``float(...)`` / ``np.asarray(...)``
        host readback of a non-constant value — the documented
        alternative on platforms where block_until_ready returns at
        dispatch (docs/static_analysis.md JAX06). Any such call anywhere
        in the function counts: this rule deliberately trades recall for
        precision (an incidental float() on host data will mask a real
        unfenced measurement, but a fence-looking call must never be
        flagged — suppression fatigue kills linters faster than missed
        findings do)."""
        for node in ast.walk(fn):
            if isinstance(node, ast.Attribute) and node.attr == (
                    "block_until_ready"):
                return True
            if isinstance(node, ast.Name) and node.id == "block_until_ready":
                return True
            if not (isinstance(node, ast.Call) and node.args
                    and not isinstance(node.args[0], ast.Constant)):
                continue
            if (isinstance(node.func, ast.Name)
                    and node.func.id == "float"):
                return True
            if module.resolved_call(node) in ("numpy.asarray",
                                              "numpy.array"):
                return True
        return False

    @staticmethod
    def _is_jitted_dispatch(module: ModuleInfo, call: ast.Call) -> bool:
        target = qualname(call.func)
        if target and target in module.jitted_callables:
            return True
        # inline dispatch: jax.jit(f)(x)
        return (isinstance(call.func, ast.Call)
                and module.resolved_call(call.func) in JIT_WRAPPERS)


RULES = [
    PrngKeyReuse,
    HostSyncInJit,
    PrintInJit,
    UntraceableArgNoStatic,
    MissingDonate,
    UntimedJitDispatch,
]
