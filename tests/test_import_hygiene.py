"""Import hygiene: a torch-free server and agent, and a package graph whose
arrows point one way.

BASELINE.md constraint: zero torch/CUDA imports in the training server.

The reference's learner is PyTorch end to end; this framework's entire
compute path is JAX/XLA, and the driver's north-star config explicitly
requires the server to run torch-free. A stray ``import torch`` anywhere
on the server path would cost ~1 GB RSS and seconds of import time per
process (torch IS installed in this environment, so the import would
succeed silently — only this test notices). Run in a subprocess so other
tests' imports can't contaminate ``sys.modules``.

The package graph: :data:`LAYERS` declares the packages of ``relayrl_tpu/``
bottom to top, and a package imports only itself and what stands below it
— at module level or inside a function, in any form. A cycle of packages
survives import time only behind a function-level import, so those count.
"""

import ast
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PKG = "relayrl_tpu"

#: The packages of ``relayrl_tpu/``, bottom to top (docs/architecture.md
#: draws them). ``utils`` stands above ``telemetry`` because
#: ``utils/logger.py`` mirrors its rows into the registry.
LAYERS = (
    "_native", "telemetry", "utils", "types", "config", "ops", "parallel",
    "models", "data", "checkpoint", "faults", "guardrails", "envs",
    "transport", "algorithms", "runtime", "rlhf", "relay", "analysis",
)


def _run(code: str) -> str:
    import importlib.util

    # Self-check against vacuity: without torch installed, sys.modules can
    # never contain it and the guard would pass while proving nothing.
    assert importlib.util.find_spec("torch") is not None, \
        "hygiene test vacuous: torch not installed in this environment"
    env = dict(os.environ)
    # Repo root ONLY: the ambient PYTHONPATH may carry accelerator plugin
    # site dirs — this test is about OUR import graph, on the CPU backend.
    env["PYTHONPATH"] = _REPO
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout


def test_server_path_is_torch_free(tmp_cwd):
    stdout = _run(
        "import os, sys\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "from relayrl_tpu.runtime.server import TrainingServer\n"
        "srv = TrainingServer('REINFORCE', obs_dim=4, act_dim=2,\n"
        "                     env_dir='.', start=False,\n"
        "                     hyperparams={'hidden_sizes': [8]})\n"
        "bad = sorted(m for m in sys.modules\n"
        "             if m == 'torch' or m.startswith('torch.'))\n"
        "print('TORCH_MODULES', bad)\n")
    assert "TORCH_MODULES []" in stdout, stdout


def test_agent_path_is_torch_free(tmp_cwd):
    stdout = _run(
        "import os, sys\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "import numpy as np\n"
        # The REAL agent entry point: importing runtime.agent pulls in the
        # whole agent-side transport graph at module level, so a stray
        # torch import anywhere on the actor path is caught here.
        "import relayrl_tpu.runtime.agent  # noqa: F401\n"
        "from relayrl_tpu.runtime.policy_actor import PolicyActor\n"
        "from relayrl_tpu.algorithms import build_algorithm\n"
        "alg = build_algorithm('REINFORCE', obs_dim=4, act_dim=2,\n"
        "                      env_dir='.', hidden_sizes=[8])\n"
        "actor = PolicyActor(alg.bundle())\n"
        "actor.request_for_action(np.zeros(4, np.float32))\n"
        "bad = sorted(m for m in sys.modules\n"
        "             if m == 'torch' or m.startswith('torch.'))\n"
        "print('TORCH_MODULES', bad)\n")
    assert "TORCH_MODULES []" in stdout, stdout


def _imported_packages(node: ast.AST, package: list[str]):
    """The top-level packages of ``relayrl_tpu`` one import statement names,
    absolute or relative, ``from relayrl_tpu import x`` included.
    ``package`` is the dotted path of the package the importing module
    lives in, as a list."""
    if isinstance(node, ast.Import):
        targets = [(a.name.split("."), ()) for a in node.names]
    elif isinstance(node, ast.ImportFrom):
        module = node.module.split(".") if node.module else []
        base = package[:len(package) - node.level + 1] if node.level else []
        targets = [(base + module, [a.name for a in node.names])]
    else:
        return
    for path, names in targets:
        if path[:1] != [_PKG]:
            continue
        # ``from relayrl_tpu import x, y``: the names are the packages.
        yield from (path[1:2] or names)


def _upward_imports(package: str) -> list[str]:
    rank = {name: i for i, name in enumerate(LAYERS)}
    found = []
    for dirpath, _, files in sorted(os.walk(os.path.join(_REPO, _PKG, package))):
        for fname in sorted(f for f in files if f.endswith(".py")):
            path = os.path.join(dirpath, fname)
            rel = os.path.relpath(path, _REPO)
            with open(path, encoding="utf-8") as fh:
                tree = ast.parse(fh.read(), filename=rel)
            inside = rel.split(os.sep)[:-1]
            for node in ast.walk(tree):
                for target in _imported_packages(node, inside):
                    # A module of the root (``relayrl_tpu/x.py``) or an
                    # unlisted package has no rank: the second test below
                    # holds the list complete.
                    if rank.get(target, -1) > rank[package]:
                        found.append((rel, node.lineno, target))
    return [f"{rel}:{line}: {package} imports {target}, which stands above it"
            for rel, line, target in sorted(found)]


@pytest.mark.parametrize("package", LAYERS)
def test_package_imports_point_down(package):
    assert os.path.isdir(os.path.join(_REPO, _PKG, package)), (
        f"LAYERS names {package!r}, which relayrl_tpu/ does not hold")
    found = _upward_imports(package)
    assert not found, "\n".join(found)


def test_every_package_has_a_layer():
    root = os.path.join(_REPO, _PKG)
    held = sorted(d for d in os.listdir(root)
                  if os.path.isfile(os.path.join(root, d, "__init__.py")))
    assert held == sorted(LAYERS)
