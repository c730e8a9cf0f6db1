"""BASELINE.md constraint: zero torch/CUDA imports in the training server.

The reference's learner is PyTorch end to end; this framework's entire
compute path is JAX/XLA, and the driver's north-star config explicitly
requires the server to run torch-free. A stray ``import torch`` anywhere
on the server path would cost ~1 GB RSS and seconds of import time per
process (torch IS installed in this environment, so the import would
succeed silently — only this test notices). Run in a subprocess so other
tests' imports can't contaminate ``sys.modules``.
"""

import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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
