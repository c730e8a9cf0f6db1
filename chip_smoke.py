"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py

One process holds the chip and drives the main path once through the entry
points a user calls — CPU actor processes -> zmq -> ingest -> jitted update
on the TPU -> publish -> hot-swap — plus the other device programs the repo
has (the widest transformer through ``build_algorithm``, the flash kernels
alone at the benchmark's shapes against XLA attention, the fused anakin
rollout, a served batch, the expert layer's grouped-matmul kernels against
XLA's own, the Mamba-2 scan's, the gated delta rule's and the mixers'
convolution's kernels against their plain forms, the rotated latent layer
against the benchmark's plain reference of it). It checks what comes out, fails on the first thing
that is wrong (non-zero exit, one ``chip_smoke: FAIL`` line saying why; a
phase's own exception is never caught), and ends with ONE JSON line:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Without an accelerator it exits non-zero before running any phase and prints
no result. It uses the chips it finds: on a multi-chip host the learner of
phase A is sharded over all of them and the check says so.

This measures nothing — the fence timings it prints answer "which fence is
sound on this machine", they are not a benchmark.

Actor children are started with ``spawn`` and re-import this module, so the
module level imports nothing that touches jax.
"""

from __future__ import annotations

import functools
import json
import multiprocessing as mp
import os
import queue as queue_mod
import shutil
import socket
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")
RUN_DIR = os.path.join(OUT_DIR, "run")
WALL_LIMIT_S = 1150.0  # the driver allows 1200 s, compilation included

# Phase A: a 256x256 bf16 MLP (BASELINE.md's REINFORCE shape) under the
# closed loop.
A_ACTORS = 2
A_UPDATES = 12   # past learner.checkpoint_every_epochs (10): one orbax
#                  save happens while the persistent compile cache is on
# Phase B: docs/parallelism.md's transformer_flash_computebound shape
# (d1024, head_dim 128), through the normal seam.
B_ARCH = dict(model_kind="transformer_discrete", d_model=1024, n_layers=4,
              n_heads=8, max_seq_len=1024, attention="flash")
B_OBS, B_ACT, B_T, B_TRAJ = 64, 18, 1024, 4
B_UPDATES = 3
FENCE_CHAIN = 20
# Phase F: the held-experts layer alone, (cell, tokens, the layer's widths).
_LFM2 = dict(d=2048, ff=1536, k=4, n_held=8, ffn="swiglu", router="sigmoid")
F_SHAPES = [("lfm2-policy", 8192, _LFM2), ("lfm2-policy", 16384, _LFM2),
            ("smallthinker-policy", 16384,
             dict(d=2560, ff=768, k=6, n_held=16, ffn="reglu",
                  router="softmax"))]


def say(msg: str) -> None:
    """This script's own lines: stdout, and a log beside the run directory
    (the epoch tables the learner prints can push them out of a tail)."""
    print(f"chip_smoke: {msg}", flush=True)
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "smoke.log"), "a") as f:
        f.write(f"{time.strftime('%H:%M:%S')} {msg}\n")


def fail(msg: str):
    """First wrong thing ends the run. SystemExit unwinds through every
    ``finally`` below, which is where started processes are stopped."""
    say(f"FAIL {msg}")
    sys.exit(1)


def check(cond, msg: str) -> None:
    if not cond:
        fail(msg)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# --------------------------------------------------------------------------
# children: CPU hosts, and they prove it
# --------------------------------------------------------------------------

def _child_platform(tag: str, queue) -> None:
    """First thing a child does: pin the CPU BEFORE importing jax, then
    report the platform jax actually gave it."""
    from relayrl_tpu.utils.hostpin import pin_cpu

    pin_cpu()
    import jax

    queue.put((tag, "platform", jax.devices()[0].platform))


def actor_child(idx: int, config_path: str, workdir: str, addrs: dict,
                stop, queue) -> None:
    tag = f"actor-{idx}"
    _child_platform(tag, queue)
    os.makedirs(workdir, exist_ok=True)
    os.chdir(workdir)
    from relayrl_tpu.envs import CartPoleEnv
    from relayrl_tpu.runtime.agent import Agent, run_gym_loop

    agent = Agent(config_path=config_path, server_type="zmq", seed=idx,
                  **addrs)
    env = CartPoleEnv()
    episodes = 0
    deadline = time.monotonic() + 600
    # Play until the parent has its updates AND this actor has hot-swapped
    # at least once (model_version >= 1 is the swap, seen from here).
    while (not (stop.is_set() and agent.model_version >= 1)
           and time.monotonic() < deadline):
        episodes += len(run_gym_loop(agent, env, episodes=2, max_steps=500))
    queue.put((tag, "done", {"model_version": agent.model_version,
                             "episodes": episodes}))
    agent.disable_agent()


def remote_child(config_path: str, workdir: str, addrs: dict, episodes: int,
                 queue) -> None:
    tag = "remote-0"
    _child_platform(tag, queue)
    os.makedirs(workdir, exist_ok=True)
    os.chdir(workdir)
    from relayrl_tpu.envs import CartPoleEnv
    from relayrl_tpu.runtime.agent import run_gym_loop
    from relayrl_tpu.runtime.inference import RemoteActorClient

    client = RemoteActorClient(config_path=config_path, server_type="zmq",
                               seed=7, identity=tag, **addrs)
    returns = run_gym_loop(client, CartPoleEnv(), episodes=episodes,
                           max_steps=200)
    queue.put((tag, "done", {"model_version": client.model_version,
                             "episodes": len(returns),
                             "steps": int(sum(returns))}))
    client.disable_agent()


class Children:
    """Every process this script starts, so that each one is stopped."""

    def __init__(self):
        self.ctx = mp.get_context("spawn")  # never fork once jax is live
        self.queue = self.ctx.Queue()
        self.procs: list = []

    def start(self, target, *args) -> None:
        p = self.ctx.Process(target=target, args=(*args, self.queue),
                             daemon=True)
        p.start()
        self.procs.append(p)

    def check_alive(self) -> None:
        dead = [(p.name, p.exitcode) for p in self.procs
                if p.exitcode not in (None, 0)]
        check(not dead, f"child process died: {dead}")

    def expect(self, tags: set[str], kind: str, timeout: float) -> dict:
        """One ``kind`` message from each of ``tags`` — a loud failure
        with a timeout, never a wait: a child that initialises the TPU
        backend while this process holds the chip hangs or dies."""
        got: dict = {}
        deadline = time.monotonic() + timeout
        while set(got) != tags:
            self.check_alive()
            left = deadline - time.monotonic()
            check(left > 0, f"no {kind!r} report from "
                            f"{sorted(tags - set(got))} within {timeout:.0f}s")
            try:
                tag, k, payload = self.queue.get(timeout=min(left, 1.0))
            except queue_mod.Empty:
                continue
            if k == kind and tag in tags:
                got[tag] = payload
        return got

    def stop_all(self) -> None:
        for p in self.procs:
            p.join(timeout=20)
        for p in self.procs:
            if p.is_alive():
                p.terminate()
                p.join(timeout=5)
            if p.is_alive():
                p.kill()
                p.join(timeout=5)
        self.procs = []


# --------------------------------------------------------------------------
# set-up
# --------------------------------------------------------------------------

def rebuild_native() -> str:
    """``native/librelayrl_native.so`` is git-ignored, and the server's
    ingest decodes through whatever binary it finds — so a stale one in the
    working tree would run on the chip. Rebuild it from ``native/*.cc`` in
    this run, or make sure there is none."""
    native_dir = os.path.join(REPO, "native")
    lib = os.path.join(native_dir, "librelayrl_native.so")
    if shutil.which("make") and shutil.which("g++"):
        t0 = time.monotonic()
        out = subprocess.run(["make", "-B", "-C", native_dir],
                             capture_output=True, text=True, timeout=600)
        if out.returncode != 0:
            print(out.stderr[-2000:], flush=True)
            fail(f"native library did not build (make rc={out.returncode})")
        return f"built from native/*.cc in {time.monotonic() - t0:.0f}s"
    if os.path.exists(lib):
        os.remove(lib)
        return "no toolchain: stale binary removed"
    return "no toolchain, no binary"


def write_config() -> str:
    """The config this run uses, in the run directory: defaults plus these
    overrides. (``ConfigLoader`` reads cwd when given no path, and the repo
    root can hold an untracked ``relayrl_config.json``.)"""
    from relayrl_tpu.config import default_config

    cfg = default_config()
    cfg["learner"]["precision"] = "bfloat16"
    cfg["max_traj_length"] = B_T  # phase B's 1024 bucket (default cap 1000)
    path = os.path.join(RUN_DIR, "relayrl_config.json")
    with open(path, "w") as f:
        json.dump(cfg, f, indent=1)
    return path


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


def on_tpu(tree) -> bool:
    import jax

    return all(d.platform == "tpu"
               for leaf in jax.tree_util.tree_leaves(tree)
               if isinstance(leaf, jax.Array) for d in leaf.devices())


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def phase_a_and_d(config_path: str, n_devices: int):
    """A: closed loop — TrainingServer on the chip(s), spawned CPU actors.
    D: the same server answers RemoteActorClient requests from a CPU child.
    Returns the learner's final bundle (phase C swaps to it)."""
    import jax

    from relayrl_tpu.runtime.server import TrainingServer

    server_addrs = {
        "agent_listener_addr": f"tcp://127.0.0.1:{free_port()}",
        "trajectory_addr": f"tcp://127.0.0.1:{free_port()}",
        "model_pub_addr": f"tcp://127.0.0.1:{free_port()}",
        "serving_addr": f"tcp://127.0.0.1:{free_port()}",
    }
    agent_addrs = {
        "agent_listener_addr": server_addrs["agent_listener_addr"],
        "trajectory_addr": server_addrs["trajectory_addr"],
        "model_sub_addr": server_addrs["model_pub_addr"],
    }
    remote_addrs = {**agent_addrs, "serving_addr": server_addrs["serving_addr"]}

    t0 = time.monotonic()
    server = TrainingServer(
        "IMPALA", obs_dim=4, act_dim=2, server_type="zmq",
        env_dir=os.path.join(RUN_DIR, "server"), config_path=config_path,
        serving=True,
        hyperparams={"hidden_sizes": [256, 256], "seed_salt": 0},
        **server_addrs)
    children = Children()
    try:
        server.wait_warmup(timeout=600)  # raises if the update won't compile
        say(f"A: server up, warmup {server.timings['warmup_s']:.1f}s "
            f"(bucket shapes {server.algorithm.buffer.buckets})")
        check(server.inference is not None, "A: serving plane did not start")

        stop = children.ctx.Event()
        tags = {f"actor-{i}" for i in range(A_ACTORS)}
        for i in range(A_ACTORS):
            children.start(actor_child, i, config_path,
                           os.path.join(RUN_DIR, f"actor-{i}"), agent_addrs,
                           stop)
        platforms = children.expect(tags, "platform", timeout=180)
        check(set(platforms.values()) == {"cpu"},
              f"A: actor children not on cpu: {platforms}")

        deadline = time.monotonic() + 420
        while server.stats["updates"] < A_UPDATES:
            check(time.monotonic() < deadline,
                  f"A: {server.stats['updates']} updates after 420s "
                  f"(stats {server.stats})")
            check(server._learner_thread.is_alive(), "A: learner thread died")
            children.check_alive()
            time.sleep(0.2)
        stop.set()
        done = children.expect(tags, "done", timeout=120)
        check(all(d["model_version"] >= 1 for d in done.values()),
              f"A: an actor never hot-swapped: {done}")
        children.stop_all()

        # D: one served batch or more, from a thin client on a CPU host.
        children.start(remote_child, config_path,
                       os.path.join(RUN_DIR, "remote-0"), remote_addrs, 3)
        rp = children.expect({"remote-0"}, "platform", timeout=180)
        check(rp["remote-0"] == "cpu", f"D: remote child not on cpu: {rp}")
        served = children.expect({"remote-0"}, "done", timeout=180)["remote-0"]
        children.stop_all()

        check(server.drain(timeout=120), "A: server did not drain")
        stats = dict(server.stats)
        params = server.algorithm.state.params
        check(on_tpu(params), "A: learner params are not on tpu devices")
        spans = {len(x.sharding.device_set)
                 for x in jax.tree_util.tree_leaves(params)}
        check(spans == {n_devices},
              f"A: param shardings span {spans} devices, expected "
              f"{{{n_devices}}}")
        check(stats["updates"] >= 3, f"A: updates {stats['updates']} < 3")
        for key in ("dropped", "dropped_nonfinite", "learner_errors",
                    "publish_errors", "warmup_failed"):
            check(stats[key] == 0, f"A: stats[{key!r}] = {stats[key]}")
        check(server._ckpt_consecutive_failures == 0,
              "A: a periodic checkpoint failed")
        server.algorithm._ckpt_mgr.wait()  # the async orbax save lands
        ckpt_dir = os.path.join(RUN_DIR, "server", "checkpoints")
        steps = sorted(d for d in os.listdir(ckpt_dir) if d.isdigit())
        check(bool(steps), f"A: no orbax checkpoint step under {ckpt_dir}")

        acct = server.inference.accounting()
        check(served["episodes"] == 3 and served["steps"] > 0,
              f"D: remote client did not finish its episodes: {served}")
        check(served["model_version"] >= 1,
              f"D: served actions came from version "
              f"{served['model_version']} (install_params never ran)")
        check(on_tpu(server.inference.params),
              "D: serving params are not on tpu devices")
        bundle = server.algorithm.bundle()
        say(f"A: ok — {stats['updates']} updates from "
            f"{stats['trajectories']} trajectories, actors {done}, params on "
            f"tpu over {n_devices} device(s), dropped 0, "
            f"warmup/learner/publish errors 0, orbax steps {steps} with the "
            f"compile cache on, decode path {server.ingest_decoder}, "
            f"{time.monotonic() - t0:.0f}s")
        say(f"D: ok — remote client {served}, service at version "
            f"{server.inference.version}, accounting {acct}")
        return bundle
    finally:
        children.stop_all()
        server.disable_server()


def synthetic_episode(rng, length: int):
    import numpy as np

    from relayrl_tpu.types.action import ActionRecord

    return [ActionRecord(
        obs=rng.standard_normal(B_OBS).astype(np.float32),
        act=np.int64(rng.integers(B_ACT)), rew=float(rng.random()),
        data={"logp_a": np.float32(-2.89), "v": np.float32(0.0)},
        done=(i == length - 1)) for i in range(length)]


def phase_b(config_path: str) -> None:
    """The d1024·L4·T1024·head_dim 128 transformer through
    ``build_algorithm`` and the exact calls ``server._process_one`` makes:
    accumulate -> stage_batch -> train_on_batch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from relayrl_tpu.algorithms import build_algorithm
    from relayrl_tpu.models import build_policy

    t0 = time.monotonic()
    algo = build_algorithm(
        "IMPALA", obs_dim=B_OBS, act_dim=B_ACT, config_path=config_path,
        env_dir=os.path.join(RUN_DIR, "phase_b"), traj_per_epoch=B_TRAJ,
        bucket_lengths=[B_T], seed_salt=0, **B_ARCH)
    check(algo.arch["precision"] == "bfloat16", "B: not the bf16 trunk")
    before = jax.device_get(algo.state.params["params"]["block_0"]["qkv"]
                            ["kernel"])
    rng = np.random.default_rng(0)
    losses = []
    while len(losses) < B_UPDATES:
        batch = algo.accumulate(synthetic_episode(rng, B_T))
        if batch is None:
            continue
        staged = algo.stage_batch(batch)
        metrics = algo.train_on_batch(staged)
        losses.append(metrics["LossTotal"])  # reading it fences the update
    algo.inflight.drain()
    check(all(np.isfinite(losses)), f"B: non-finite loss {losses}")
    after = jax.device_get(algo.state.params["params"]["block_0"]["qkv"]
                           ["kernel"])
    check(np.all(np.isfinite(after)) and not np.array_equal(before, after),
          "B: parameters did not change (or went non-finite)")
    check(on_tpu(algo.state.params), "B: params are not on tpu devices")
    key = (B_T, B_ARCH["d_model"] // B_ARCH["n_heads"], "bfloat16")
    resolved = dict(algo.policy.attention_backends)
    check(resolved.get(key) == "flash_pallas",
          f"B: attention at {key} resolved to {resolved.get(key)!r}, "
          f"not the Pallas kernel ({resolved})")
    text = algo._update.lower(algo.state, staged).compile().as_text()
    n_mosaic = text.count("tpu_custom_call")
    check(n_mosaic > 0, "B: no Mosaic custom call in the compiled update")

    # Agreement with the reference on a small input: the same parameters
    # through the same network with dense softmax attention.
    dense = build_policy({**algo.arch, "attention": "dense"})
    obs = jnp.asarray(rng.standard_normal((1, B_T, B_OBS)), jnp.float32)
    act = jnp.asarray(rng.integers(0, B_ACT, (1, B_T)), jnp.int32)
    logp_k, _, _ = jax.jit(algo.policy.evaluate)(algo.state.params, obs, act)
    logp_d, _, _ = jax.jit(dense.evaluate)(algo.state.params, obs, act)
    err = float(jnp.max(jnp.abs(logp_k - logp_d)))
    # bf16 trunk: 8 mantissa bits through 4 layers; logp is O(3).
    check(np.isfinite(err) and err < 0.1,
          f"B: flash vs dense logp differ by {err}")
    say(f"B: ok — {len(losses)} updates, LossTotal {losses}, attention "
        f"{resolved}, {n_mosaic} Mosaic custom calls in the compiled update, "
        f"flash-vs-dense max |dlogp| {err:.1e}, {time.monotonic() - t0:.0f}s")

    # The fence question (docs/operations.md, jaxlint JAX06): the same chain
    # of updates, fenced three ways. Straight through the jitted update — the
    # in-flight window would fence for us.
    state = algo.state  # settled: the in-flight window was drained above
    walls = {}
    for name in ("no_fence", "block_until_ready", "host_readback"):
        t1 = time.perf_counter()
        for _ in range(FENCE_CHAIN):
            state, m = algo._update(state, staged)
        if name == "block_until_ready":
            jax.block_until_ready(state)
        elif name == "host_readback":
            float(m["LossTotal"])
        walls[name] = time.perf_counter() - t1
        jax.block_until_ready(state)  # settle before the next variant
    algo.state = state
    sound = walls["block_until_ready"] > 0.8 * walls["host_readback"]
    say(f"fence: {FENCE_CHAIN} chained phase-B updates — dispatch only "
        f"{walls['no_fence'] * 1e3:.0f} ms, jax.block_until_ready(state) "
        f"{walls['block_until_ready'] * 1e3:.0f} ms, host readback "
        f"{walls['host_readback'] * 1e3:.0f} ms => block_until_ready "
        f"{'FENCES' if sound else 'does NOT fence'} on this machine")
    check(walls["host_readback"] > 2 * walls["no_fence"],
          "fence: host readback returned as fast as dispatch")


def phase_b_default_buckets(config_path: str) -> None:
    """``learner.bucket_lengths`` as shipped ([64, 256, 1000]) through
    ``warmup()`` for the transformer policy — what a default server's
    learner thread compiles first."""
    from relayrl_tpu.algorithms import build_algorithm

    t0 = time.monotonic()
    algo = build_algorithm(
        "IMPALA", obs_dim=B_OBS, act_dim=B_ACT, config_path=config_path,
        env_dir=os.path.join(RUN_DIR, "phase_b_buckets"), seed_salt=0,
        model_kind="transformer_discrete", d_model=256, n_layers=2,
        n_heads=8, max_seq_len=1024, attention="flash")
    buckets = algo.buffer.buckets
    n = algo.warmup()
    check(n == len(buckets), f"B': warmup compiled {n} of {buckets}")
    resolved = dict(algo.policy.attention_backends)
    for t in buckets:
        check(resolved.get((t, 32, "bfloat16")) == "flash_pallas",
              f"B': bucket {t} resolved to "
              f"{resolved.get((t, 32, 'bfloat16'))!r} ({resolved})")
    say(f"B': ok — default buckets {buckets} compiled through warmup() as "
        f"{resolved}, {time.monotonic() - t0:.0f}s")


def differ(a, b) -> float:
    """Largest difference between two device arrays, as a share of the
    largest entry of the second."""
    import jax.numpy as jnp

    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.abs(a - b).max() / jnp.abs(b).max())


def phase_b_kernels() -> None:
    """The two flash kernels alone at the shapes the benchmark's
    transformer cells run — (B*H, T, D) = (128, 1024, 64), one block a head
    walked in causal strips, (64, 4096, 128), a 4 x 4 grid a head with
    strips on its diagonal, (64, 8192, 64) grouped, 32 q heads over 8
    k/v heads that reach the kernels unrepeated (lfm2-policy.update), and
    (28, 16384, 128) over 4 k/v heads under a window of 4096, the band
    kernels (smallthinker-policy.update's windowed layers) —
    bfloat16, forward and all three gradients
    under a random cotangent, against the XLA paths on the same inputs in
    float32 (``dense_attention`` at T 1024, ``blockwise_attention`` at
    T 4096; the grouped shape against ``dense_attention`` one k/v head and
    its four q heads at a time; the band shape against ``dense_attention``
    with the band mask one q head at a time, dk and dv summed over a
    group). The kernels round p and ds to bfloat16 for their second
    matmuls and the results once: a few units in the last place of the
    largest entry, phase E's limit."""
    import jax
    import jax.numpy as jnp

    from relayrl_tpu.ops import flash
    from relayrl_tpu.ops.attention import blockwise_attention, dense_attention

    t0 = time.monotonic()
    said = []
    def blockwise(q, k, v):
        return blockwise_attention(q, k, v, 512, causal=True)

    for (B, T, H, D), h_kv, reference, window in (
            ((8, 1024, 16, 64), 16, dense_attention, None),
            ((4, 4096, 16, 128), 16, blockwise, None),
            ((2, 8192, 32, 64), 8, dense_attention, None),
            ((1, 16384, 28, 128), 4,
             functools.partial(dense_attention, window=4096), 4096)):
        q, k, v, do = (
            jax.random.normal(key, (B, T, heads, D), jnp.bfloat16)
            for key, heads in zip(jax.random.split(jax.random.PRNGKey(T), 4),
                                  (H, h_kv, h_kv, H)))

        @jax.jit
        def kernel_side(q, k, v, do):
            out, vjp = jax.vjp(functools.partial(
                flash.flash_attention, window=window), q, k, v)
            return (out, *vjp(do))

        @jax.jit
        def xla_side(q, k, v, do):
            out, vjp = jax.vjp(reference, *(
                x.astype(jnp.float32) for x in (q, k, v)))
            return (out, *vjp(do.astype(jnp.float32)))

        def xla_in_parts(q, k, v, do, at_once):
            """One k/v head and ``at_once`` of its q heads at a time (T x T
            float32 scores of every head at once do not fit at T 8192, nor
            a group's seven at T 16384); dk and dv summed over a group."""
            G = H // h_kv
            outs, dqs, dks, dvs = [], [], [], []
            for h in range(h_kv):
                dk = dv = 0.0
                for j in range(h * G, (h + 1) * G, at_once):
                    part = xla_side(q[:, :, j:j + at_once], k[:, :, h:h + 1],
                                    v[:, :, h:h + 1], do[:, :, j:j + at_once])
                    outs.append(part[0])
                    dqs.append(part[1])
                    dk, dv = dk + part[2], dv + part[3]
                dks.append(dk)
                dvs.append(dv)
            return [jnp.concatenate(x, axis=2)
                    for x in (outs, dqs, dks, dvs)]

        area = flash.score_area_pct(T, *flash.tiling(T), True, window)
        errs = dict(zip(("out", "dq", "dk", "dv"), map(
            differ, kernel_side(q, k, v, do),
            xla_side(q, k, v, do) if h_kv == H else xla_in_parts(
                q, k, v, do, 1 if window else H // h_kv))))
        for what, err in errs.items():
            check(err <= 2.0 ** -6,
                  f"B\": flash {what} at {(B * H, T, D)} differs from "
                  f"XLA's by {err:.3g} of its largest entry (limit 2^-6)")
        said.append(f"{(B * H, T, D)} k/v heads {h_kv} of {H} "
                    f"window {window} tiling "
                    f"{flash.tiling(T)} score area "
                    f"{area:g}% "
                    f"{json.dumps({w: round(e, 6) for w, e in errs.items()})}")
    say(f"B\": ok — flash fwd / bwd kernels against XLA attention in "
        f"float32: {'; '.join(said)}, {time.monotonic() - t0:.0f}s")


def phase_c(bundle) -> None:
    """One fused rollout: CartPole-JAX, 64 lanes x unroll 32, MLP, in this
    process, with a parameter swap over the model wire between windows."""
    import jax
    import numpy as np

    from relayrl_tpu.runtime.anakin import AnakinActorHost
    from relayrl_tpu.transport.modelwire import ModelWireEncoder
    from relayrl_tpu.types.model_bundle import ModelBundle

    t0 = time.monotonic()
    sent = []
    zeros = jax.tree_util.tree_map(np.zeros_like, bundle.params)
    host = AnakinActorHost(
        ModelBundle(version=0, arch=bundle.arch, params=zeros),
        "CartPole-v1", num_envs=64, unroll_length=32,
        on_send=lambda lane, payload: sent.append(len(payload)))
    try:
        outs = [host.rollout() for _ in range(2)]
        frame, _info = ModelWireEncoder().encode(
            bundle.version, bundle.arch, bundle.params)
        check(host.swap_from_wire(bundle.version, frame) is not None,
              "C: the wire swap installed nothing")
        outs += [host.rollout() for _ in range(2)]
    finally:
        host.close()
    check(host.version == bundle.version, "C: swap did not take")
    check(on_tpu(host.params), "C: swapped params are not on tpu devices")
    check(on_tpu(host._carry), "C: the scan carry is not on tpu devices")
    check(all(o["steps"] == 64 * 32 for o in outs), f"C: window sizes {outs}")
    episodes = sum(len(r) for r in host.episode_returns)
    check(episodes > 0 and sent, "C: no episode finished / nothing emitted")
    rets = [r for lane in host.episode_returns for r in lane]
    check(all(np.isfinite(rets)) and min(rets) >= 1.0,
          "C: CartPole returns are not finite positive step counts")
    say(f"C: ok — 4 windows of 64x32, swap v0->v{bundle.version} between "
        f"them, {episodes} episodes, {len(sent)} frames emitted "
        f"({outs[0]['wire']}), {time.monotonic() - t0:.0f}s")


def phase_e() -> None:
    """The expert layer's grouped-matmul kernels where they run: the
    hand-written ``custom_vjp`` of ``ops/grouped_matmul.gmm`` (forward,
    d_lhs through the transposed stack, d_rhs through ``tgmm``) at
    ``olmoe-policy.update``'s shapes — 16,384 tokens x top-8 rows of 2048
    through 64 experts of width 1024 — under a random router's load and
    under one with empty groups, a group of one row and a group that takes
    half the rows. Against the formulas written out with XLA's own
    operations: the value and d_lhs = g W_e^T are ``lax.ragged_dot``
    forwards, d_rhs[e] = x_e^T g_e a plain matmul over the group's rows,
    for a handful of groups. Both
    sides accumulate in float32 and round once to bfloat16, so they may
    differ by the order of the sums: a few units in the last place of the
    largest entry.

    And at ``nemotron-twotower-policy.update``'s (PR 39): a 12,288-row
    buffer of 2688 through 8 held experts of width 1856 = 14.5 x 128, which
    no multiple of 128 divides, up (the irregular last tile on the result's
    axis, on the contracted axis in d_lhs) and down (1856 contracted)."""
    phase_e_at(16384 * 8, 2048, 1024, 64)
    phase_e_at(12288, 2688, 1856, 8)
    phase_e_at(12288, 1856, 2688, 8)


def phase_e_at(m: int, k: int, n: int, n_exp: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from relayrl_tpu.ops import grouped_matmul as kernels

    t0 = time.monotonic()
    check(kernels.fits(m, k, n), f"E: the kernels do not tile {(m, k, n)}")
    rng = np.random.default_rng(0)
    half = n_exp // 2
    skewed = np.zeros(n_exp, np.int64)
    skewed[half // 8], skewed[half // 4] = m // 2, 1
    rest = m - int(skewed.sum())
    # the first half: all empty but the two above
    skewed[half:] = rng.multinomial(rest, np.ones(half) / half)
    loads = {"random router": (rng.multinomial(m, np.ones(n_exp) / n_exp),
                               sorted({0, 1, half - 1, half, n_exp - 1})),
             "empty groups, 1 row, half the rows": (
                 skewed, sorted({half // 8, half // 4, half + half // 4,
                                 n_exp - 1}))}
    key = jax.random.split(jax.random.PRNGKey(0), 3)
    lhs = jax.random.normal(key[0], (m, k), jnp.bfloat16)
    rhs = jax.random.normal(key[1], (n_exp, k, n), jnp.bfloat16) / 32
    g = jax.random.normal(key[2], (m, n), jnp.bfloat16)

    # the gigabyte operands are arguments: closed over, they would be
    # constants of the compiled program (minutes of compile, PR 27)
    @jax.jit
    def kernel_side(lhs, rhs, g, sizes):
        out, vjp = jax.vjp(lambda a, b: kernels.gmm(a, b, sizes), lhs, rhs)
        return (out, *vjp(g))

    @jax.jit
    def xla_side(lhs, rhs, g, sizes):
        def ragged(a, b):
            return jax.lax.ragged_dot(a, b, sizes,
                                      preferred_element_type=a.dtype)

        return ragged(lhs, rhs), ragged(g, rhs.swapaxes(1, 2))

    worst = {"value": 0.0, "d_lhs": 0.0, "d_rhs": 0.0}
    for name, (load, groups) in loads.items():
        check(int(load.sum()) == m, f"E: load {name!r} sums to {load.sum()}")
        sizes = jnp.asarray(load, jnp.int32)
        value, d_lhs, d_rhs = kernel_side(lhs, rhs, g, sizes)
        errs = dict(zip(("value", "d_lhs"), map(
            differ, (value, d_lhs), xla_side(lhs, rhs, g, sizes))))
        start = np.concatenate([[0], np.cumsum(load)])
        errs["d_rhs"] = max(
            differ(d_rhs[e], jnp.einsum(
                "mk,mn->kn", lhs[start[e]:start[e + 1]],
                g[start[e]:start[e + 1]],
                preferred_element_type=jnp.float32).astype(d_rhs.dtype))
            for e in groups)
        for what, err in errs.items():
            worst[what] = max(worst[what], err)
            check(err <= 2.0 ** -6,
                  f"E: {what} under {name!r} differs from XLA's by "
                  f"{err:.3g} of its largest entry (limit 2^-6)")
        check(bool(jnp.isfinite(d_rhs.astype(jnp.float32)).all())
              and not bool(d_rhs[np.flatnonzero(load == 0)].any()),
              f"E: d_rhs under {name!r}: non-finite, or an empty group's "
              f"is not zero")
    say(f"E: ok — gmm / d_lhs / d_rhs kernels against lax.ragged_dot and "
        f"per-group matmuls at [{m}, {k}] x [{n_exp}, {k}, {n}], two "
        f"loads; largest relative difference "
        f"{json.dumps({w: round(v, 6) for w, v in worst.items()})}, "
        f"{time.monotonic() - t0:.0f}s")


def forced_logits(rng, n: int, k: int, n_exp: int, held: tuple[int, int],
                  live: int, empty_last: bool = False):
    """Router logits ``[n, n_exp]`` (numpy float32) whose top-k sends
    exactly ``live`` of the ``n * k`` slots to the held experts ``held =
    (first, count)`` (none to the last of them under ``empty_last``), some
    experts more popular than others. (``tests/test_moe.py`` walks the same
    crossings with it on the CPU.)"""
    import numpy as np

    first, count = held
    mine = np.arange(first, first + count - bool(empty_last))
    absent = np.setdiff1d(np.arange(n_exp), np.arange(first, first + count))
    per_token = np.full(n, live // n)
    per_token[rng.permutation(n)[:live % n]] += 1
    assert per_token.max() <= min(k, len(mine))
    assert k - per_token.min() <= len(absent)

    def pick(experts, how_many):  # weighted draws without replacement
        keys = rng.random((n, len(experts))) ** (
            1.0 / rng.uniform(0.3, 3.0, len(experts)))
        chosen = np.zeros((n, n_exp), bool)
        chosen[:, experts] = (-keys).argsort(1).argsort(1) < how_many[:, None]
        return chosen

    chosen = pick(mine, per_token) | pick(absent, k - per_token)
    return np.where(chosen, rng.uniform(1.0, 3.0, (n, n_exp)),
                    rng.uniform(-5.0, -4.0, (n, n_exp))).astype(np.float32)


def phase_f() -> None:
    """The held-experts layer's walk where it runs (``models/moe.py``,
    "Held experts"): the layer alone, the Pallas kernels under it, at
    ``lfm2-policy``'s widths (8 of 64 SwiGLU experts of 1536 held, top-4 by
    a sigmoid router, 8,192 tokens — the reference comparison's sequence —
    and 16,384, an update's) and at ``smallthinker-policy``'s (16 of 64
    ReGLU experts of 768, top-6, 16,384 tokens), with a router made to
    send the layer an exact number of live rows L: the crossing of its
    R-row buffer (R - 513, R - 1, R, R + 1, R + 511, R + 513), of the
    second (2R - 1, 2R, 2R + 1), three passes, every slot (``ceil(N k /
    R)`` passes) and a last held expert of no rows. One compiled program a
    shape walks them all — the trip count is the update's to compute.
    Output and every gradient (tokens, the router's rows, the three
    stacks) against the DENSE path of the same layer in float32 at the
    highest matmul precision (a ReGLU layer's gradients: in bfloat16, see
    below), NaN-free, the reported trip counts as ``ceil(L / R)``. Limit
    2^-6 as phases B" and E: of the largest entry
    for what belongs to one token, of the norm for the stacks' gradients."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from relayrl_tpu.models import moe

    t0 = time.monotonic()
    n_exp = 64
    said = []
    for cell, n, s in F_SHAPES:
        d, k, n_held = s["d"], s["k"], s["n_held"]
        rows = moe.row_buffer(n * k, n_held, n_exp)

        def layer(dtype, dispatch):
            return moe.MoEMLP(d, s["ff"], n_exp, k, dtype, ffn=s["ffn"],
                              dispatch=dispatch, use_bias=False,
                              router=s["router"],
                              expert_bias=s["router"] == "sigmoid",
                              held=(0, n_held))

        def program(dtype, dispatch):
            def loss(params, x, route_x, g):
                y, state = layer(dtype, dispatch).apply(
                    params, x.astype(dtype), route_x,
                    mutable=["intermediates"])
                sown = state["intermediates"]
                return (jnp.sum(y.astype(jnp.float32) * g),
                        (y, sown["row_passes"][0],
                         sown["expert_load"][0]))
            return jax.jit(jax.value_and_grad(loss, (0, 1, 2),
                                              has_aux=True))

        key = jax.random.split(jax.random.PRNGKey(n + d), 6)
        x = jax.random.normal(key[0], (1, n, d), jnp.float32)
        g = jax.random.normal(key[1], (1, n, d), jnp.float32)
        # bfloat16 tokens on both sides: the layer's input as the trunk
        # hands it over
        x = x.astype(jnp.bfloat16).astype(jnp.float32)

        def stack(key, fan_in, fan_out):
            return jax.random.normal(key, (n_held, fan_in, fan_out),
                                     jnp.float32) / fan_in ** 0.5

        # the router reads its own rows: the logits, through an identity
        params = {"moe_gate": {"kernel": jnp.eye(d, n_exp)},
                  "moe_w_gate": stack(key[2], d, s["ff"]),
                  "moe_w_up": stack(key[3], d, s["ff"]),
                  "moe_w_down": stack(key[4], s["ff"], d)}
        if s["router"] == "sigmoid":
            params["moe_expert_bias"] = 0.02 * jax.random.normal(
                key[5], (n_exp,), jnp.float32)
        params = {"params": params}
        compact = program(jnp.bfloat16, "sparse")
        with jax.default_matmul_precision("highest"):
            dense = program(jnp.float32, "dense")
        # ReLU's derivative is a step: where bfloat16 rounding moves a gate
        # pre-activation across zero a whole row's term comes or goes, and
        # against float32 the gate stack's gradient read 0.033 of its norm
        # apart for that alone (PR 36). A ReGLU layer's gradients are held
        # to the dense path in the layer's own precision, its output to
        # float32 like the others.
        dense_own = (program(jnp.bfloat16, "dense") if s["ffn"] == "reglu"
                     else None)
        cases = [(rows + off, False) for off in (-513, -1, 0, 1, 511, 513)]
        cases += [(2 * rows + off, False) for off in (-1, 0, 1)]
        cases += [(3 * rows - 7, False), (n * k, False), (rows + 1, True)]
        # a layer that holds a quarter of the experts has, at a margin of
        # 2, buffers of half the slots: two passes are all there are
        cases = list(dict.fromkeys(c for c in cases if c[0] <= n * k))
        rng = np.random.default_rng(n + k)
        worst, walked = {False: 0.0, True: 0.0}, []
        for live, empty_last in cases:
            route_x = np.zeros((1, n, d), np.float32)
            route_x[0, :, :n_exp] = forced_logits(
                rng, n, k, n_exp, (0, n_held), live, empty_last)
            route_x = jnp.asarray(route_x)
            (_, (y, passes, load)), grads = compact(params, x, route_x, g)
            with jax.default_matmul_precision("highest"):
                (_, (y_d, _, load_d)), grads_d = dense(params, x, route_x, g)
            if dense_own is not None:
                _, grads_d = dense_own(params, x, route_x, g)
            what = f"F: {cell} N {n} R {rows} L {live}"
            check(int(load.sum()) == live == int(load_d.sum()),
                  f"{what}: the router sent {int(load.sum())} live rows")
            check(not empty_last or int(load[-1]) == 0,
                  f"{what}: the last held expert was to stay empty")
            check(int(passes) == -(-live // rows),
                  f"{what}: {int(passes)} passes reported, "
                  f"{-(-live // rows)} expected")
            pairs = [("y", y, y_d)] + [
                (jax.tree_util.keystr(path), a, b) for (path, a), b in zip(
                    jax.tree_util.tree_flatten_with_path(grads)[0],
                    jax.tree_util.tree_leaves(grads_d))]
            for name, a, b in pairs:
                check(bool(jnp.isfinite(a.astype(jnp.float32)).all()),
                      f"{what}: {name} is not finite")
                if not float(jnp.abs(b).max()):  # the expert bias: no way
                    check(not float(jnp.abs(a).max()),  # back to it
                          f"{what}: {name} should be zero")
                    continue
                # what belongs to one token (the output, the tokens' and
                # the router rows' gradients) by its largest difference: a
                # dropped or doubled row shows there in full; a stack's
                # gradient, a sum over thousands of rows, by the norm of
                # the difference.
                stack = "moe_w_" in name
                err = (float(jnp.linalg.norm((a - b).astype(jnp.float32))
                             / jnp.linalg.norm(b)) if stack
                       else differ(a, b))
                worst[stack] = max(worst[stack], err)
                check(err <= 2.0 ** -6,
                      f"{what}: {name} differs from the dense path's by "
                      f"{err:.3g} of its "
                      f"{'norm' if stack else 'largest entry'} "
                      f"(limit 2^-6)")
            walked.append(f"{live}:{int(passes)}")
        said.append(f"{cell} N {n} R {rows} (L:passes {' '.join(walked)}; "
                    f"largest difference {worst[False]:.4g} of the largest "
                    f"entry, per token; {worst[True]:.4g} of the norm, the "
                    f"stacks' gradients)")
    say(f"F: ok — the held-experts layer's walk against its dense path in "
        f"float32, output and every gradient, NaN-free: {'; '.join(said)}, "
        f"{time.monotonic() - t0:.0f}s")


def both_ways(fn, args, cotangents, chunk: int):
    """``fn(*args[:-1], chunk=chunk, state=args[-1])``'s two results and,
    under ``cotangents`` of both, the gradient of every argument: one jitted
    program (phases G and H, a kernel form and its plain form each)."""
    import jax

    @jax.jit
    def run(args, cotangents):
        out, vjp = jax.vjp(
            lambda *a: fn(*a[:-1], chunk=chunk, state=a[-1]), *args)
        return (*out, *vjp(cotangents))
    return run(args, cotangents)


def phase_g() -> None:
    """The Mamba-2 scan at ``nemotron-twotower-policy.update``'s shape —
    two 8192-token episodes, 64 heads of 64, a state of 128, 8 groups,
    chunks of 128, bfloat16, from a carried state — through the Pallas
    kernels (``ops/ssd_pallas.py``: ``ssd_fwd``, and under a random
    cotangent of both results ``ssd_states`` + ``ssd_bwd``) and through the
    plain form (``ops/ssd.ssd_xla``) on the same operands: ``y``, the last
    state and the gradients of all six arguments and of the carried state,
    each within 2^-6 of the plain form's largest entry (both round their
    matmuls' operands to bfloat16; phases B" / E's limit). ``ssd()`` itself
    has to pick the kernels here, and says so."""
    import jax
    import jax.numpy as jnp

    from relayrl_tpu.ops import ssd as scan

    t0 = time.monotonic()
    b, T, H, P, G, N, chunk = 2, 8192, 64, 64, 8, 128, 128
    check(scan.backend(T, H, P, G, N, chunk) == scan.PALLAS,
          f"G: ssd() would run {scan.backend(T, H, P, G, N, chunk)} at heads "
          f"{H} x {P}, groups {G}, state {N}, chunk {chunk} on a TPU")
    keys = jax.random.split(jax.random.PRNGKey(40), 9)
    lo = jnp.bfloat16
    args = (jax.random.normal(keys[0], (b, T, H, P), lo),
            jax.random.uniform(keys[1], (b, T, H), jnp.float32, 0.001, 0.1),
            -jax.random.uniform(keys[2], (H,), jnp.float32, 1.0, 16.0),
            jax.random.normal(keys[3], (b, T, G, N), lo),
            jax.random.normal(keys[4], (b, T, G, N), lo),
            jax.random.normal(keys[5], (H,), jnp.float32),
            jax.random.normal(keys[6], (b, H, P, N), jnp.float32))
    cotangents = (jax.random.normal(keys[7], (b, T, H, P), lo),
                  jax.random.normal(keys[8], (b, H, P, N), jnp.float32))

    names = ("y", "last", "dx", "ddt", "dA", "dB", "dC", "dD", "dstate")
    errs = dict(zip(names, map(
        differ, both_ways(scan.ssd, args, cotangents, chunk),
        both_ways(scan.ssd_xla, args, cotangents, chunk))))
    for what, err in errs.items():
        check(err <= 2.0 ** -6,
              f"G: the scan kernels' {what} differs from the plain form's "
              f"by {err:.3g} of its largest entry (limit 2^-6)")
    say(f"G: ok — ssd_fwd / ssd_states / ssd_bwd against the plain form at "
        f"{(b, T, H, P)} state {N} groups {G} chunk {chunk} bfloat16: "
        f"{json.dumps({w: round(e, 6) for w, e in errs.items()})}, "
        f"{time.monotonic() - t0:.0f}s")


def phase_h() -> None:
    """The gated delta rule at ``qwen3next-policy.update``'s shape — two
    8192-token episodes, 32 value heads over 16 key heads of 128, chunks of
    64, bfloat16, from a carried state — through the Pallas kernels
    (``ops/gdn_pallas.py``: ``gdn_fwd``, and under a random cotangent of
    both results ``gdn_states`` + ``gdn_bwd``) and through the plain form
    (``ops/gdn.gdn_xla``) on the same operands: ``o``, the last state and
    the gradients of all five arguments and of the carried state, each
    within 2^-6 of the plain form's largest entry (both round their
    matmuls' operands to bfloat16; phases E-G's limit). ``gdn()`` itself
    has to pick the kernels here, and says so."""
    import jax
    import jax.numpy as jnp

    from relayrl_tpu.ops import gdn as rule

    t0 = time.monotonic()
    b, T, Hk, H, K, V, chunk = 2, 8192, 16, 32, 128, 128, 64
    check(rule.backend(T, H, Hk, K, V, chunk) == rule.PALLAS,
          f"H: gdn() would run {rule.backend(T, H, Hk, K, V, chunk)} at heads "
          f"{Hk} x {K} under {H} x {V}, chunk {chunk} on a TPU")
    keys = jax.random.split(jax.random.PRNGKey(43), 8)
    lo = jnp.bfloat16

    def unit(key, scale):    # as the mixer's L2 norm leaves q and k
        a = jax.random.normal(key, (b, T, Hk, K), jnp.float32)
        return (a / jnp.linalg.norm(a, axis=-1, keepdims=True)
                * scale).astype(lo)

    args = (unit(keys[0], K ** -0.5), unit(keys[1], 1.0),
            jax.random.normal(keys[2], (b, T, H, V), lo),
            # log decays from a state that spans chunks to one that forgets
            # within a few tokens
            -jax.random.uniform(keys[3], (b, T, H), jnp.float32, 1e-3, 2.0),
            jax.random.uniform(keys[4], (b, T, H), jnp.float32),
            jax.random.normal(keys[5], (b, H, K, V), jnp.float32))
    cotangents = (jax.random.normal(keys[6], (b, T, H, V), lo),
                  jax.random.normal(keys[7], (b, H, K, V), jnp.float32))

    names = ("o", "last", "dq", "dk", "dv", "dg", "dbeta", "dstate")
    errs = dict(zip(names, map(
        differ, both_ways(rule.gdn, args, cotangents, chunk),
        both_ways(rule.gdn_xla, args, cotangents, chunk))))
    for what, err in errs.items():
        check(err <= 2.0 ** -6,
              f"H: the delta rule's kernels' {what} differs from the plain "
              f"form's by {err:.3g} of its largest entry (limit 2^-6)")
    say(f"H: ok — gdn_fwd / gdn_states / gdn_bwd against the plain form at "
        f"{(b, T, Hk, K)} | {(H, V)} chunk {chunk} bfloat16: "
        f"{json.dumps({w: round(e, 6) for w, e in errs.items()})}, "
        f"{time.monotonic() - t0:.0f}s")


def phase_i() -> None:
    """The mixers' convolution at the two cells' shapes — two 8192-token
    episodes of 6,144 columns with a bias (``nemotron-twotower-policy``) and
    of 8,192 without (``qwen3next-policy``), 4 taps, bfloat16, from a
    sequence's start — through the Pallas kernels (``ops/conv_pallas.py``:
    ``conv_fwd``, and under a random cotangent ``conv_bwd``) and through the
    plain form (``ops/conv.conv_xla``) on the same operands: the output
    within one bfloat16 step (2^-8 of the largest entry) on no more than one
    entry in a thousand and equal everywhere else, the gradients of the
    rows, the taps and the bias within 2^-6 of the plain form's largest
    entry (autodiff of the plain form rounds every tap's term of ``dx`` to
    bfloat16; the kernel rounds their float32 sum once). ``conv()`` itself
    has to pick the kernels here, and says so."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from relayrl_tpu.ops import conv as cv
    from relayrl_tpu.ops.scopes import MAMBA_CONV_NAME

    t0 = time.monotonic()
    said = []
    for C, has_bias in ((6144, True), (8192, False)):
        check(cv.backend(8192, C, 4) == cv.PALLAS,
              f"I: conv() would run {cv.backend(8192, C, 4)} at 8192 rows of "
              f"{C} columns on a TPU")
        keys = jax.random.split(jax.random.PRNGKey(44 + C), 4)
        x, dy = (jax.random.normal(k, (2, 8192, C), jnp.bfloat16)
                 for k in keys[:2])
        args = (x, 0.5 * jax.random.normal(keys[2], (4, C), jnp.float32)) + (
            (0.1 * jax.random.normal(keys[3], (C,), jnp.float32),)
            if has_bias else ())

        def ways(fn):
            @jax.jit
            def run(args, dy):
                out, vjp = jax.vjp(
                    lambda x, w, bias=None: fn(x, w, bias), *args)
                return (out, *vjp(dy))
            return run(args, dy)

        got = ways(lambda *a: cv.conv(*a, None, MAMBA_CONV_NAME))
        want = ways(cv.conv_xla)
        out, ref = (np.asarray(a[0], np.float32) for a in (got, want))
        unequal = float((out != ref).mean())
        errs = dict(zip(("out", "dx", "dw", "dbias"), map(differ, got, want)))
        check(unequal <= 1e-3 and errs["out"] <= 2.0 ** -8,
              f"I: the convolution kernel's output differs from the plain "
              f"form's on {unequal:.3g} of the entries, by {errs['out']:.3g} "
              f"of the largest at most (limits 1e-3, 2^-8)")
        for what, err in errs.items():
            check(err <= 2.0 ** -6,
                  f"I: the convolution kernels' {what} differs from the "
                  f"plain form's by {err:.3g} of its largest entry (limit "
                  f"2^-6)")
        said.append(f"{C} columns, bias {has_bias}: unequal outputs "
                    f"{unequal:.3g}, "
                    f"{json.dumps({w: round(e, 6) for w, e in errs.items()})}")
    say(f"I: ok — conv_fwd / conv_bwd against the plain form at (2, 8192, C) "
        f"4 taps bfloat16: {'; '.join(said)}, {time.monotonic() - t0:.0f}s")


def phase_j() -> None:
    """The delta rule under a decay a key lane at
    ``kimi-linear-policy.update``'s shape — one 16,384-token episode, 32
    heads of 128, chunks of 64, bfloat16, from a carried state — through the
    Pallas kernels (``ops/kda_pallas.py``: ``kda_fwd``, and under a random
    cotangent of both results ``kda_states`` + ``kda_bwd``) and through the
    plain form (``ops/kda.kda_xla``) on the same operands: ``o``, the last
    state and the six cotangents, each within 2^-6 of the plain form's
    largest entry. Twice: at the decays the policy is seeded with
    (``exp(A_log)`` uniform over (0, 16) a head: all but a head or two
    forget a state within a token or two, so a wrong state handed from chunk
    to chunk would not show), and at decays near 1 (``g`` in [-0.05, 0]),
    where the state carried in reaches every chunk's output. ``kda()``
    itself has to pick the kernels here, and says so."""
    import jax
    import jax.numpy as jnp

    from relayrl_tpu.ops import kda as rule

    t0 = time.monotonic()
    b, T, H, K, V, chunk = 1, 16384, 32, 128, 128, 64
    check(rule.backend(T, H, K, V, chunk) == rule.PALLAS,
          f"J: kda() would run {rule.backend(T, H, K, V, chunk)} at {H} heads "
          f"of {K} x {V}, chunk {chunk} on a TPU")
    keys = jax.random.split(jax.random.PRNGKey(45), 9)
    lo = jnp.bfloat16

    def unit(key, scale):    # as the mixer's L2 norm leaves q and k
        a = jax.random.normal(key, (b, T, H, K), jnp.float32)
        return (a / jnp.linalg.norm(a, axis=-1, keepdims=True)
                * scale).astype(lo)

    lanes = jax.random.uniform(keys[3], (b, T, H, K), jnp.float32, 1e-3, 1.0)
    rates = jax.random.uniform(keys[8], (H, 1), jnp.float32, 1e-4, 16.0)
    names = ("o", "last", "dq", "dk", "dv", "dg", "dbeta", "dstate")
    said = []
    for what, g in (("seeded", -rates * lanes), ("near 1", -0.05 * lanes)):
        args = (unit(keys[0], K ** -0.5), unit(keys[1], 1.0),
                jax.random.normal(keys[2], (b, T, H, V), lo), g,
                jax.random.uniform(keys[4], (b, T, H), jnp.float32),
                jax.random.normal(keys[5], (b, H, K, V), jnp.float32))
        cotangents = (jax.random.normal(keys[6], (b, T, H, V), lo),
                      jax.random.normal(keys[7], (b, H, K, V), jnp.float32))
        errs = dict(zip(names, map(
            differ, both_ways(rule.kda, args, cotangents, chunk),
            both_ways(rule.kda_xla, args, cotangents, chunk))))
        for name, err in errs.items():
            check(err <= 2.0 ** -6,
                  f"J: at decays {what} the kernels' {name} differs from the "
                  f"plain form's by {err:.3g} of its largest entry (limit "
                  f"2^-6)")
        said.append(f"decays {what} "
                    f"{json.dumps({w: round(e, 6) for w, e in errs.items()})}")
    say(f"J: ok — kda_fwd / kda_states / kda_bwd against the plain form at "
        f"{(b, T, H, K)} chunk {chunk} bfloat16: {'; '.join(said)}, "
        f"{time.monotonic() - t0:.0f}s")


def phase_k() -> None:
    """The rotated latent layer at ``joyai-flash-policy``'s widths — 32 heads,
    a query rank of 1536, a latent row of 512 + 64, q / k 192 lanes and v
    128, theta 32e6 on interleaved pairs, a dense SwiGLU FFN of 7168 behind
    it, bfloat16 — as a one-layer trunk through ``build_policy``, held to
    the benchmark's plain reference of the same layer
    (``benchmark/reference/joyai-flash-policy.py``, float32 "highest", the
    pairs turned in place) on the same seeded tree, T 2,048. Full: every
    row's value and its action's log-probability through the ``_mla`` flash kernels
    (``Policy.attention_backends`` has to say ``flash_pallas``). Cached: a
    prefill of the first 2,040 rows, then eight steps through the ``(c,
    k_pe)`` cache, ``k_pe`` stored already rotated — each step's value
    against the reference's row. Limits 0.05: one layer of bfloat16
    rounding, no routing to flip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from relayrl_tpu.models import build_policy

    t0 = time.monotonic()
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from benchmark import harness

    reference = harness.load_reference("joyai-flash-policy")
    cfg = {**harness.load_cell("joyai-flash-policy.update")["config"],
           "num_hidden_layers": 1, "positions_as_run": 2048}
    T, pre = cfg["positions_as_run"], 2040
    kwargs = {k: v for k, v in reference.program_kwargs(cfg).items()
              if not k.startswith("moe_") and k != "block_checkpoint"}
    kwargs["kind"] = "transformer_discrete"
    del kwargs["model_kind"]
    policy = build_policy({"obs_dim": cfg["obs_dim"],
                           "act_dim": cfg["act_dim"], "has_critic": True,
                           "precision": "bfloat16", **kwargs})
    params = jax.jit(policy.init_params)(jax.random.PRNGKey(62))
    rng = np.random.default_rng(62)
    obs = jnp.asarray(rng.standard_normal((1, T, cfg["obs_dim"])),
                      jnp.float32)
    act = jnp.asarray(rng.integers(0, cfg["act_dim"], (1, T)), jnp.int32)
    logp_ref, v_ref = reference.forward(params, obs, cfg)
    logp_ref = jnp.take_along_axis(logp_ref, act[..., None], -1)[..., 0]
    logp, _ent, v = jax.jit(policy.evaluate)(params, obs, act)
    ran = dict(policy.attention_backends or {}).get((T, 192, "bfloat16"))
    check(ran == "flash_pallas",
          f"K: the latent layer's full mode ran {ran!r} at T {T}, q / k 192")
    d_logp = float(jnp.abs(logp - logp_ref).max())
    d_v = float(jnp.abs(v - v_ref).max())
    check(d_logp <= 0.05 and d_v <= 0.05,
          f"K: full mode differs from the reference's layer by {d_logp:.3g} "
          f"(log-probabilities) / {d_v:.3g} (values), limit 0.05")
    # the other pairing is another function of the same tree: told apart
    halves = reference.forward(params, obs, cfg, wrong={"half_split": True})
    apart = float(jnp.abs(v - halves[1]).max())
    check(apart > 2 * d_v,
          f"K: the half-split reference is as near ({apart:.3g}) as the "
          f"interleaved one ({d_v:.3g})")
    padded = np.asarray(obs[0]).copy()
    padded[pre:] = 0.0
    cache = policy.prefill_cache(params, policy.init_cache(T),
                                 jnp.asarray(padded), pre)
    step = jax.jit(policy.step_cached)
    d_step = 0.0
    for t in range(pre, T):
        _, aux, cache = step(params, jax.random.PRNGKey(t), cache,
                             obs[0, t], t)
        d_step = max(d_step, abs(float(aux["v"]) - float(v_ref[0, t])))
    check(d_step <= 0.05,
          f"K: cached steps {pre}..{T - 1} differ from the reference's rows "
          f"by {d_step:.3g} (values), limit 0.05")
    say(f"K: ok — the rotated latent layer (32 heads, q / k 192, v 128, "
        f"theta 32e6 interleaved) against the plain reference at T {T} "
        f"bfloat16: full {d_logp:.4f} / {d_v:.4f} through {ran}, "
        f"half-split apart by {apart:.4f}, cached steps {d_step:.4f}, "
        f"{time.monotonic() - t0:.0f}s")


# --------------------------------------------------------------------------

def phase_l() -> None:
    """The fused rollout's cached scan and a model swap's rebuild at
    ``gpt2m-policy.rollout``'s own size — 64 lanes of a 1,024-row window, 24
    layers' keys and values in the scan carry (6.44 GB) — where only the
    chip can say that both programs fit beside each other. Six dispatches
    under one seeded tree, ``maybe_swap`` to another mid-episode, two more,
    a swap back, two more: the first LAUNCH after each swap rebuilds every
    lane's cache from its ring (``runtime/anakin.make_cache_rebuild``; the
    second rebuild finds the program compiled, so its time is the
    rebuild's own). The host keeps one window in flight, so the first
    ``rollout()`` after a swap still returns a window of the parameters
    before it and the new ones' first window is the SECOND after the swap.
    Two lanes' emitted ``logp_a``
    and ``v`` are held to ``policy.evaluate`` over the lane's observations,
    at the learner's own shape, under the parameters that were installed
    when the step was LAUNCHED (limit 0.05 of max(1, range): bfloat16
    rounding reads under 0.02, PERF.md section 6), and the steps after the
    swap ALSO against the old parameters, which has to read far over the
    limit — a cache left as the old parameters wrote it would pass the one
    and fail the other the wrong way round. Not one of ``run``'s phases
    (its programs are the benchmark cell's, which every check of a PR
    runs): ``chiprun -- python -c "import chip_smoke;
    chip_smoke.phase_l()"``."""
    import jax
    import numpy as np

    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from benchmark import harness
    from benchmark.drivers.rollout import policy_arch
    from relayrl_tpu import telemetry
    from relayrl_tpu.models import build_policy
    from relayrl_tpu.runtime.anakin import AnakinActorHost
    from relayrl_tpu.types.model_bundle import ModelBundle

    t0 = time.monotonic()
    spec = harness.load_cell("gpt2m-policy.rollout")
    cfg, tr = spec["config"], spec["traffic"]
    arch = policy_arch(cfg, harness.load_reference(
        spec["config_name"]).program_kwargs(cfg))
    policy = build_policy(arch)
    make = jax.jit(policy.init_params)
    old, new = (jax.block_until_ready(make(jax.random.PRNGKey(s)))
                for s in (2**31 + 67, 67))
    lanes, unroll = int(tr["lanes"]), int(tr["unroll_length"])
    width = int(tr["window_size"])
    telemetry.set_registry(telemetry.Registry(run_id="chip-smoke-l"))
    host = AnakinActorHost(
        ModelBundle(version=0, arch=arch, params=old), tr["env"],
        num_envs=lanes, unroll_length=unroll, window_size=width,
        max_traj_length=int(tr["max_traj_length"]), seed=67,
        **tr["env_kwargs"])
    # every window as the host's emit is handed it, in the order emitted:
    # the constructor's first, never the one a last call leaves in flight
    windows, emit = [], host._emit_columnar

    def kept(window):
        windows.append(window)
        return emit(window)

    host._emit_columnar = kept
    stats = jax.devices()[0].memory_stats
    try:
        before = [host.rollout()["dispatch_s"] for _ in range(6)]
        peak_before = stats()["peak_bytes_in_use"]
        check(host.maybe_swap(ModelBundle(version=1, arch=arch, params=new)),
              "L: the swap installed nothing")
        after = [host.rollout()["dispatch_s"] for _ in range(2)]
        peak_after = stats()["peak_bytes_in_use"]
        check(host.maybe_swap(ModelBundle(version=2, arch=arch, params=old)),
              "L: the swap back installed nothing")
        back = [host.rollout()["dispatch_s"] for _ in range(2)]
    finally:
        host.close()
    counted: dict = {}     # summed over a name's label sets (the cache
    for m in telemetry.get_registry().snapshot()["metrics"]:   # gauge's kinds)
        if "value" in m:
            counted[m["name"]] = counted.get(m["name"], 0) + m["value"]
    reads = counted.__getitem__

    cache_bytes = lanes * sum(
        int(np.prod(x.shape)) * x.dtype.itemsize for x in
        jax.tree.leaves(jax.eval_shape(lambda: policy.init_cache(width))))
    check(reads("relayrl_actor_cache_rebuilds_total") == 2,
          f"L: {reads('relayrl_actor_cache_rebuilds_total')} rebuilds")
    check(reads("relayrl_actor_cached_steps_total")
          == reads("relayrl_actor_env_steps_total") == 10 * lanes * unroll,
          "L: not every step was a cached step")
    check(reads("relayrl_actor_cache_bytes") == cache_bytes,
          f"L: the gauge reads {reads('relayrl_actor_cache_bytes')}, "
          f"{lanes} x init_cache({width}) is {cache_bytes}")
    check(on_tpu(host._carry), "L: the scan carry is not on tpu devices")

    check(reads("relayrl_actor_windows_in_flight") == 1,
          "L: close() did not leave the one window in flight where it was")
    evaluate = jax.jit(policy.evaluate)
    # one window is in flight ahead of the host: a swap after call k first
    # shows in the window call k+1 LAUNCHES, which call k+2 returns
    swap_at, back_at, worst, control = 7 * unroll, 9 * unroll, 0.0, np.inf
    for lane in (0, lanes - 1):
        obs = np.zeros((1, width, int(cfg["obs_dim"])), np.float32)
        act = np.zeros((1, width), np.int32)
        got = {k: np.concatenate([w["aux"][k][lane] for w in windows])
               for k in ("logp_a", "v")}
        n = len(got["v"])
        obs[0, :n] = np.concatenate([w["obs"][lane] for w in windows])
        act[0, :n] = np.concatenate([w["act"][lane] for w in windows])
        check(np.all(np.isfinite(got["logp_a"]) & np.isfinite(got["v"])),
              f"L: lane {lane} emitted a non-finite step")

        def distance(params, rows):
            logp, _ent, v = (np.asarray(x)[0] for x in
                             evaluate(params, obs, act))
            return max(
                float(np.max(np.abs(got["logp_a"][rows] - logp[rows])))
                / max(1.0, float(np.ptp(logp[rows]))),
                float(np.max(np.abs(got["v"][rows] - v[rows])))
                / max(1.0, float(np.max(np.abs(v[rows])))))

        worst = max(worst, distance(old, slice(0, swap_at)),
                    distance(new, slice(swap_at, back_at)),
                    distance(old, slice(back_at, n)))
        control = min(control, distance(old, slice(swap_at, back_at)),
                      distance(new, slice(back_at, n)))
    check(worst <= 0.05, f"L: emitted logp_a / v are {worst:.4f} from "
          f"policy.evaluate under the installed parameters (limit 0.05)")
    check(control > 0.05, f"L: the steps after a swap are {control:.4f} "
          f"from the parameters it replaced (the control has to read over "
          f"0.05)")
    say(f"L: ok — {lanes} x {width} cached, {cache_bytes / 1e9:.2f} GB of "
        f"cache in the carry; dispatches before the swap "
        f"{[round(1e3 * d, 1) for d in before]} ms (host clock, a call's "
        f"launch until the window it returns is ready: what is LEFT of a "
        f"window's device time), the one that launched the rebuild "
        f"{1e3 * after[0]:.1f} ms (its program compiles), the next "
        f"{1e3 * after[1]:.1f} ms; after the swap back "
        f"{1e3 * back[0]:.1f} ms (the rebuild alone beside a dispatch) and "
        f"{1e3 * back[1]:.1f} ms; "
        f"peak_bytes_in_use {peak_before / 1e9:.3f} GB before the swap "
        f"(two trees of parameters held), {peak_after / 1e9:.3f} GB after "
        f"the rebuild; distance from evaluate {worst:.4f}, control "
        f"{control:.4f}, {time.monotonic() - t0:.0f}s")


def main() -> None:
    t_start = time.monotonic()
    import jax
    import jaxlib

    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    from importlib import metadata

    versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__}
    for pkg in ("libtpu", "flax", "optax", "orbax-checkpoint"):
        try:
            versions[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            versions[pkg] = "not installed"
    say(f"device platform={dev['platform']} device_kind={dev['kind']!r} "
        f"count={dev['count']} versions={versions}")
    if dev["platform"] != "tpu":
        say(f"FAIL no accelerator: jax found platform {dev['platform']!r}; "
            f"no phase was run")
        sys.exit(2)

    # A hang must not outlive the driver's limit with children attached.
    def _overrun():
        say(f"FAIL wall limit {WALL_LIMIT_S:.0f}s exceeded")
        for p in mp.active_children():
            p.kill()
        os._exit(3)

    watchdog = threading.Timer(WALL_LIMIT_S, _overrun)
    watchdog.daemon = True
    watchdog.start()

    run(dev, t_start)
    watchdog.cancel()
    print(json.dumps({"ok": True, "device": dev}), flush=True)


def run(dev: dict, t_start: float) -> None:
    """Every phase, in a run directory of its own."""
    shutil.rmtree(RUN_DIR, ignore_errors=True)
    os.makedirs(RUN_DIR)
    os.chdir(RUN_DIR)
    say(f"native library: {rebuild_native()}")

    from relayrl_tpu.utils.compile_cache import ENV_VAR, resolve_compile_cache

    cache_dir = resolve_compile_cache()
    compiles = CompileCounter()
    say(f"compile cache: {cache_dir} "
        f"({ENV_VAR} {'set' if os.environ.get(ENV_VAR) else 'not set'}, "
        f"{len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0} "
        f"entries at start)")
    config_path = write_config()

    bundle = phase_a_and_d(config_path, dev["count"])
    phase_b(config_path)
    phase_b_default_buckets(config_path)
    phase_b_kernels()
    phase_c(bundle)
    phase_e()
    phase_f()
    phase_g()
    phase_h()
    phase_i()
    phase_j()
    phase_k()

    say(f"compiles: {compiles.requests} requests, {compiles.hits} served by "
        f"the persistent cache, {compiles.requests - compiles.hits} compiled "
        f"new")
    say(f"wall {time.monotonic() - t_start:.0f}s")


if __name__ == "__main__":
    main()
