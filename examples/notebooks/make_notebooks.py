"""Author + execute the reference's 12-notebook example matrix.

The reference ships 12 Jupyter notebooks — REINFORCE with/without
baseline x {cartpole, mountain_car, lunar_lander} x {zmq, grpc}
(reference: examples/ tree, loop at examples/README.md:125-152). This
script builds the same matrix against this framework's API and executes
each notebook for real (nbclient), committing genuine cell outputs the
way the reference commits notebook outputs.

    python examples/notebooks/make_notebooks.py              # build + run all
    python examples/notebooks/make_notebooks.py --only cartpole   # substring
    python examples/notebooks/make_notebooks.py --no-execute # author only

Notebook names are `{env}_reinforce_{baseline|nobaseline}_{zmq|grpc}`.

Budgets are example-sized (a minute or two per notebook on a CPU host):
cartpole/lunarlander cells show a rising return at that budget;
mountain_car is annotated `wiring` — its sparse -1/step reward needs
exploration help no plain policy-gradient example gets (the reference's
committed mountain_car outputs are flat at -200 for the same reason).
Long-budget learning evidence lives in examples/golden/.
"""

from __future__ import annotations

import argparse
import os
import tempfile
import time
from pathlib import Path

import nbformat
from nbformat.v4 import new_code_cell, new_markdown_cell, new_notebook

HERE = Path(__file__).resolve().parent

ENVS = {
    "cartpole": dict(env_id="CartPole-v1", obs_dim=4, act_dim=2,
                     episodes=150, max_steps=500, expects="learning",
                     ref_dir="classic_control/cartpole"),
    "mountaincar": dict(env_id="MountainCar-v0", obs_dim=2, act_dim=3,
                        episodes=60, max_steps=200, expects="wiring",
                        ref_dir="classic_control/mountain_car"),
    "lunarlander": dict(env_id="LunarLander-v3", obs_dim=8, act_dim=4,
                        episodes=120, max_steps=400, expects="learning",
                        ref_dir="box2d/lunar_lander"),
}

EXPECTS_NOTE = {
    "learning": "At this example budget the sampled return should trend "
                "upward (long-budget curves live in `examples/golden/`).",
    "wiring": "MountainCar's -1/step reward is silent until the flag is "
              "reached, which plain REINFORCE at example budget essentially "
              "never does — the reference's committed mountain_car outputs "
              "are flat at -200 for the same reason. This notebook "
              "demonstrates the distributed wiring on a third env family; "
              "expect a flat curve.",
}


def build(env_key: str, baseline: bool, transport: str) -> nbformat.NotebookNode:
    e = ENVS[env_key]
    algo = "REINFORCE " + ("with" if baseline else "without") + " baseline"
    ref_nb = (f"/root/reference/examples/REINFORCE_"
              f"{'with' if baseline else 'without'}_baseline/{e['ref_dir']}/"
              f"{transport}/*.ipynb")
    title = f"# {algo} — {e['env_id']} — {transport}\n"
    nb = new_notebook(metadata={
        "kernelspec": {"display_name": "Python 3", "language": "python",
                       "name": "python3"},
        "language_info": {"name": "python"},
    })
    nb.cells.append(new_markdown_cell(
        f"{title}\n"
        f"One cell of the reference's 12-notebook example matrix, rebuilt "
        f"against the TPU-native framework (counterpart: `{ref_nb}`, loop "
        f"shape from the reference's `examples/README.md:125-152`). The "
        f"actor below is an ordinary CPU host process; the learner inside "
        f"`TrainingServer` is a jitted JAX update (TPU when available).\n\n"
        f"{EXPECTS_NOTE[e['expects']]}"))

    nb.cells.append(new_code_cell(
        "import os\n"
        "import socket\n\n"
        "# One kernel hosts the learner and the actor loop, so both run on\n"
        "# the backend JAX finds (JAX_PLATFORMS=cpu keeps them on the CPU).\n"
        "from relayrl_tpu.envs import make\n"
        "from relayrl_tpu.runtime.agent import (\n"
        "    Agent, coerce_env_action, greedy_episodes)\n"
        "from relayrl_tpu.runtime.server import TrainingServer\n\n"
        "def free_port():\n"
        "    with socket.socket() as s:\n"
        "        s.bind((\"127.0.0.1\", 0))\n"
        "        return s.getsockname()[1]\n"))

    if transport == "zmq":
        addr = (
            "addrs = {name: f\"tcp://127.0.0.1:{free_port()}\"\n"
            "         for name in (\"agent_listener\", \"trajectory\", "
            "\"model\")}\n"
            "server_addrs = dict(agent_listener_addr=addrs[\"agent_listener\"],\n"
            "                    trajectory_addr=addrs[\"trajectory\"],\n"
            "                    model_pub_addr=addrs[\"model\"])\n"
            "agent_addrs = dict(agent_listener_addr=addrs[\"agent_listener\"],\n"
            "                   trajectory_addr=addrs[\"trajectory\"],\n"
            "                   model_sub_addr=addrs[\"model\"])\n")
    else:
        addr = (
            "port = free_port()\n"
            "server_addrs = dict(bind_addr=f\"127.0.0.1:{port}\")\n"
            "agent_addrs = dict(server_addr=f\"127.0.0.1:{port}\")\n")
    nb.cells.append(new_code_cell(addr))

    nb.cells.append(new_code_cell(
        f"server = TrainingServer(\n"
        f"    \"REINFORCE\", obs_dim={e['obs_dim']}, act_dim={e['act_dim']},\n"
        f"    server_type=\"{transport}\", env_dir=\".\",\n"
        f"    hyperparams={{\"with_vf_baseline\": {baseline}}},\n"
        f"    **server_addrs)\n"))

    nb.cells.append(new_code_cell(
        "# One kernel hosts both the server and the actor loop below, so\n"
        "# let the learner pre-compile its update shapes while we sleep\n"
        "# (otherwise the first XLA compile competes with the busy actor\n"
        "# loop for CPU and the policy never hot-swaps mid-run).\n"
        "server.wait_warmup()\n"))

    nb.cells.append(new_code_cell(
        f"agent = Agent(server_type=\"{transport}\", seed=0, **agent_addrs)\n"
        f"env = make(\"{e['env_id']}\")\n"))

    nb.cells.append(new_code_cell(
        f"returns = []\n"
        f"for ep in range({e['episodes']}):\n"
        f"    obs, _ = env.reset(seed=ep)\n"
        f"    ep_ret, reward = 0.0, 0.0\n"
        f"    terminated = truncated = False\n"
        f"    for _ in range({e['max_steps']}):\n"
        f"        record = agent.request_for_action(obs, reward=reward)\n"
        f"        obs, reward, terminated, truncated, _ = env.step(\n"
        f"            coerce_env_action(record.act))\n"
        f"        ep_ret += float(reward)\n"
        f"        if terminated or truncated:\n"
        f"            break\n"
        f"    time_limited = not terminated\n"
        f"    agent.flag_last_action(reward, truncated=time_limited,\n"
        f"                           final_obs=obs if time_limited else None)\n"
        f"    returns.append(ep_ret)\n"
        f"    if (ep + 1) % 25 == 0:\n"
        f"        recent = returns[-25:]\n"
        f"        print(f\"episode {{ep + 1:4d}}  avg(last 25) = \"\n"
        f"              f\"{{sum(recent) / len(recent):8.1f}}  model v\"\n"
        f"              f\"{{agent.model_version}}\")\n"))

    nb.cells.append(new_code_cell(
        "import matplotlib\n"
        "matplotlib.use(\"Agg\")\n"
        "import matplotlib.pyplot as plt\n"
        "import numpy as np\n\n"
        "w = max(5, len(returns) // 10)\n"
        "roll = np.convolve(returns, np.ones(w) / w, mode=\"valid\")\n"
        "fig, ax = plt.subplots(figsize=(7, 3.2))\n"
        "ax.plot(returns, alpha=0.35, label=\"episode return\")\n"
        "ax.plot(range(w - 1, len(returns)), roll, "
        "label=f\"rolling mean ({w})\")\n"
        "ax.set_xlabel(\"episode\")\n"
        "ax.set_ylabel(\"return\")\n"
        "ax.legend()\n"
        "fig.tight_layout()\n"
        "plt.show()\n"))

    nb.cells.append(new_code_cell(
        "import time\n\n"
        "# Tail episodes may still be in socket buffers: wait for the\n"
        "# ingest count, then drain the learner, before reading stats.\n"
        f"deadline = time.time() + 10\n"
        f"while (server.stats[\"trajectories\"] < {e['episodes']}\n"
        f"       and time.time() < deadline):\n"
        f"    time.sleep(0.05)\n"
        f"server.drain()\n"
        "greedy = greedy_episodes(agent.actor, env, episodes=5,\n"
        f"                         max_steps={e['max_steps']})\n"
        "print(f\"greedy eval over 5 episodes: \"\n"
        "      f\"{sum(greedy) / len(greedy):.1f}  (per-episode: \"\n"
        "      f\"{[round(g, 1) for g in greedy]})\")\n"
        "print(f\"final model version: {agent.model_version};  server \"\n"
        "      f\"updates: {server.stats['updates']};  trajectories: \"\n"
        "      f\"{server.stats['trajectories']}\")\n"
        "agent.disable_agent()\n"
        "server.disable_server()\n"))
    return nb


def cells() -> dict[str, tuple[str, bool, str]]:
    out = {}
    for env_key in ENVS:
        for baseline in (True, False):
            for transport in ("zmq", "grpc"):
                tag = "baseline" if baseline else "nobaseline"
                name = f"{env_key}_reinforce_{tag}_{transport}"
                out[name] = (env_key, baseline, transport)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", help="substring filter on notebook name")
    ap.add_argument("--no-execute", action="store_true")
    ap.add_argument("--out", default=str(HERE), metavar="DIR",
                    help="output directory (default: alongside this script; "
                         "tests point it elsewhere so an authoring run can't "
                         "clobber the committed executed notebooks)")
    args = ap.parse_args()
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    from nbclient import NotebookClient

    selected = {name: spec for name, spec in cells().items()
                if not args.only or args.only in name}
    if not selected:
        raise SystemExit(f"--only {args.only!r} matches none of: "
                         f"{', '.join(cells())}")
    for name, (env_key, baseline, transport) in selected.items():
        nb = build(env_key, baseline, transport)
        path = out_dir / f"{name}.ipynb"
        if not args.no_execute:
            t0 = time.time()
            print(f"== executing {name} ...", flush=True)
            # Kernel gets the repo on sys.path (committed notebooks assume
            # the package is installed, like the reference's) and a scratch
            # cwd so run artifacts (relayrl_config.json, logs/) don't land
            # in the repo.
            repo = str(HERE.parent.parent)
            os.environ["PYTHONPATH"] = (
                repo + os.pathsep + os.environ.get("PYTHONPATH", ""))
            with tempfile.TemporaryDirectory() as scratch:
                client = NotebookClient(
                    nb, timeout=900,
                    resources={"metadata": {"path": scratch}})
                client.execute()
            print(f"   done in {time.time() - t0:.0f}s", flush=True)
        nbformat.write(nb, path)
        print(f"   wrote {path}", flush=True)


if __name__ == "__main__":
    main()
