"""Driver ``loop``: the closed actor -> learner -> actor loop on one host.

This process holds the chip and runs a ``TrainingServer`` over zmq. The
program's own vector actor tier (``VectorAgent``) runs in CPU child
processes, started with ``spawn`` BEFORE this process touches the chip so
that their imports overlap the learner's warm-up, each pinned to cores the
parent does not use. The children step a synthetic environment of the
configuration's frame shape (``traffic_gen.SyntheticEnv``), sample from the
policy they hold, ship unrolls of ``traj_len`` steps and hot-swap every
model the server publishes.

The module level imports nothing that touches jax: the children re-import
it.
"""

from __future__ import annotations

import multiprocessing as mp
import os
import queue as queue_mod
import socket
import time

from benchmark import harness, traffic_gen

CHILD_TIMEOUT_S = 240.0


# --------------------------------------------------------------------------
# the actor child
# --------------------------------------------------------------------------

def actor_child(idx: int, cores: list, config: dict, traffic: dict,
                seed: int, config_path: str, workdir: str, addrs: dict,
                stop, final_version, out) -> None:
    tag = f"actor-{idx}"
    if cores:
        os.sched_setaffinity(0, cores)
    # one core, one thread: XLA's CPU thread pool must not oversubscribe it
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_cpu_multi_thread_eigen=false").strip()
    from relayrl_tpu.utils.hostpin import pin_cpu

    pin_cpu()
    import jax
    import numpy as np

    out.put((tag, "platform", jax.devices()[0].platform))
    os.makedirs(workdir, exist_ok=True)
    os.chdir(workdir)
    from relayrl_tpu.runtime.agent import VectorAgent

    lanes = int(traffic["lanes_per_process"])
    env = traffic_gen.SyntheticEnv(config, traffic, lanes, seed + 1 + idx)
    agent = VectorAgent(
        num_envs=lanes, config_path=config_path, server_type="zmq",
        handshake_timeout_s=CHILD_TIMEOUT_S, seed=(seed + idx) % (2**31 - 1),
        identity=f"bench-{tag}", host_mode="vector", start=False, **addrs)
    sent = {"n": 0}
    inner_emit = agent.emit_lane

    def emit_lane(lane, payload, _stamps=None):
        sent["n"] += 1
        return inner_emit(lane, payload, _stamps=_stamps)

    agent.emit_lane = emit_lane
    agent.enable_agent()
    installs: list = []  # (version, t_monotonic, frame bytes)
    inner_swap = agent.host.swap_from_wire

    def swap_from_wire(version, blob):
        installed = inner_swap(version, blob)
        if installed is not None:
            installs.append((int(agent.host.version), time.monotonic(),
                             len(blob)))
        return installed

    agent.host.swap_from_wire = swap_from_wire

    obs = env.observe()
    rewards = np.zeros(lanes, np.float32)
    steps = 0
    announced = False
    deadline = time.monotonic() + 900.0
    while not stop.is_set() and time.monotonic() < deadline:
        records = agent.request_for_actions(obs, rewards=rewards)
        actions = np.asarray([int(np.asarray(r.act).reshape(-1)[0])
                              for r in records])
        obs, rewards = env.step(actions)
        steps += lanes
        if not announced and installs:
            out.put((tag, "swapped", installs[0][0]))
            announced = True
    # the learner drains, then says which version is its last: hold it
    t_end = time.monotonic() + 60.0
    while final_version.value < 0 and time.monotonic() < t_end:
        time.sleep(0.01)
    while (agent.model_version < final_version.value
           and time.monotonic() < t_end):
        time.sleep(0.01)
    out.put((tag, "done", {
        "installs": installs, "sent": sent["n"], "steps": steps,
        "version": int(agent.model_version),
        "checksum": harness.tree_checksum(agent.host.params)}))
    agent.disable_agent()


# --------------------------------------------------------------------------
# the parent
# --------------------------------------------------------------------------

class Children:
    """Every process this driver starts, so that each one is stopped."""

    def __init__(self):
        self.ctx = mp.get_context("spawn")  # never fork once jax is live
        self.queue = self.ctx.Queue()
        self.procs: list = []
        self.reports: dict = {}  # kind -> {child tag: payload}

    def start(self, *args) -> None:
        p = self.ctx.Process(target=actor_child, args=(*args, self.queue),
                             daemon=True)
        p.start()
        self.procs.append(p)

    def expect(self, tags: set, kind: str, timeout: float) -> dict:
        """Every child's report of ``kind``; reports of other kinds that
        arrive meanwhile are kept for their own ``expect``."""
        got = self.reports.setdefault(kind, {})
        deadline = time.monotonic() + timeout
        while not tags <= set(got):
            dead = [(p.name, p.exitcode) for p in self.procs
                    if p.exitcode not in (None, 0)]
            if dead:
                raise RuntimeError(f"actor child died: {dead}")
            left = deadline - time.monotonic()
            if left <= 0:
                raise RuntimeError(f"no {kind!r} report from "
                                   f"{sorted(tags - set(got))} in "
                                   f"{timeout:.0f}s")
            try:
                tag, k, payload = self.queue.get(timeout=min(left, 0.5))
            except queue_mod.Empty:
                continue
            self.reports.setdefault(k, {})[tag] = payload
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


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _core_plan(traffic: dict) -> tuple[list, list]:
    """Parent keeps the first ``parent_cores`` cores it may run on (learner,
    staging, publisher and transport threads); each child gets one of the
    rest, round robin."""
    cores = sorted(os.sched_getaffinity(0))
    keep = min(int(traffic["parent_cores"]), max(1, len(cores) - 1))
    rest = cores[keep:] or cores
    n = int(traffic["actor_processes"])
    return cores[:keep], [[rest[i % len(rest)]] for i in range(n)]


def _lags_ms(dispatched: list, installs_by_actor: dict, t0: float,
             t1: float) -> tuple[list, int]:
    """For every version whose update the learner dispatched inside the
    window and every actor: the time from that dispatch to the actor's
    first install of that version or a newer one (a version the publisher
    coalesced away is held once its successor is)."""
    lags, missing = [], 0
    for version, t_call in dispatched:
        if not t0 <= t_call <= t1:
            continue
        for installs in installs_by_actor.values():
            held = [t for v, t, _n in installs if v >= version and t >= t_call]
            if held:
                lags.append(1e3 * (min(held) - t_call))
            else:
                missing += 1
    return sorted(lags), missing


def _await_dispatch(probe, timeout: float) -> int:
    """Index of the learner's next dispatched update; returns within a
    millisecond of ``train_on_batch`` handing it to the device."""
    k = len(probe.dispatched)
    deadline = time.monotonic() + timeout
    while len(probe.dispatched) == k:
        if time.monotonic() > deadline:
            raise RuntimeError(f"no update dispatched in {timeout:.0f}s")
        time.sleep(0.001)
    return k


def drive(run: harness.Run) -> None:
    cfg, tr = run.config, run.traffic
    steps, per_update = int(tr["traj_len"]), int(tr["traj_per_update"])
    n_actors = int(tr["actor_processes"])
    say = harness.say
    say(f"native library: {harness.ensure_native(run)}")

    children = Children()
    server = None
    try:
        with run.phase("children"):
            # the config file must exist before a child reads it; jax is
            # already imported here but the server is not yet built
            config_path = harness.write_program_config(
                run, {"max_traj_length": steps})
            ports = {k: f"tcp://127.0.0.1:{_free_port()}" for k in
                     ("agent_listener_addr", "trajectory_addr",
                      "model_pub_addr")}
            agent_addrs = {
                "agent_listener_addr": ports["agent_listener_addr"],
                "trajectory_addr": ports["trajectory_addr"],
                "model_sub_addr": ports["model_pub_addr"]}
            parent_cores, child_cores = _core_plan(tr)
            stop = children.ctx.Event()
            final_version = children.ctx.Value("q", -1)
            for i in range(n_actors):
                children.start(i, child_cores[i], cfg, tr, run.seed,
                               config_path,
                               os.path.join(run.run_dir, f"actor-{i}"),
                               agent_addrs, stop, final_version)
            os.sched_setaffinity(0, parent_cores)
        tags = {f"actor-{i}" for i in range(n_actors)}

        with run.phase("import"):
            import jax
            import numpy as np

            from relayrl_tpu.runtime.server import TrainingServer

        with run.phase("build"):
            server = TrainingServer(
                cfg["algorithm"]["name"], obs_dim=int(cfg["obs_dim"]),
                act_dim=int(cfg["act_dim"]), server_type="zmq",
                env_dir=os.path.join(run.run_dir, "server"),
                config_path=config_path, serving=False,
                hyperparams={
                    "traj_per_epoch": per_update, "bucket_lengths": [steps],
                    "seed": run.program_seed, "seed_salt": 0,
                    **cfg["algorithm"]["hyperparams"],
                    **run.reference.program_kwargs(cfg)},
                **ports)
            algo = server.algorithm
        from benchmark.instrument import LearnerProbe

        probe = LearnerProbe(run, algo)
        run.train_flops_per_sample = run.reference.train_flops_per_sample(
            cfg, steps)
        before = harness.tree_checksum(
            jax.tree_util.tree_leaves(algo.state.params)[0])

        with run.phase("warmup"):
            server.wait_warmup(timeout=600)
            platforms = children.expect(tags, "platform", CHILD_TIMEOUT_S)
            children.expect(tags, "swapped", CHILD_TIMEOUT_S)
            warm = int(tr["warm_updates"])
            deadline = time.monotonic() + CHILD_TIMEOUT_S
            while algo.inflight.fenced_count < warm:
                if time.monotonic() > deadline:
                    raise RuntimeError(
                        f"{server.stats['updates']} updates after "
                        f"{CHILD_TIMEOUT_S:.0f}s: {dict(server.stats)}")
                time.sleep(0.02)
            # The loop starts with some ten updates at the learner's own
            # limit before it settles at the actors' pace (PERF.md, Findings
            # PR 23). The window opens once the learner thread has waited
            # for data for ``steady_idle_s`` in all, or after
            # ``steady_wait_s`` if it never does: then the learner is the
            # limit, and that is the steady state.
            idle0 = server.timings["learner_idle_s"]
            deadline = time.monotonic() + float(tr["steady_wait_s"])
            while (server.timings["learner_idle_s"] - idle0
                   < float(tr["steady_idle_s"])
                   and time.monotonic() < deadline):
                time.sleep(0.02)

        if run.trace:
            with run.traced():
                time.sleep(float(tr["trace_seconds"]))
            run.spans.reset()

        def snapshot():
            return (probe.mark(), dict(server.timings), dict(server.stats),
                    server._publisher.published if server._publisher else 0)

        # Both edges of the window lie on a dispatch of the learner thread:
        # the window holds whole updates only, and a learner that stalls at
        # either edge keeps it open until its next dispatch, so the rate
        # falls. (Not on a fence: the in-flight window fences late under
        # load and all at once when the learner idles, so the count of
        # fenced updates between two instants swings by two of 24.)
        k0 = _await_dispatch(probe, CHILD_TIMEOUT_S)
        m0, timings0, stats0, pub0 = snapshot()
        now0 = run.begin_window()
        time.sleep(run.seconds)
        k1 = _await_dispatch(probe, CHILD_TIMEOUT_S)
        run.end_window(now0)
        m1, timings1, stats1, pub1 = snapshot()

        # -- after the window: stop, drain, agree on the last version -----
        stop.set()
        drained = server.drain(timeout=120)
        last = int(algo.dispatched_version)
        final_version.value = last
        done = children.expect(tags, "done", 120.0)
        children.stop_all()
        learner_sum = harness.tree_checksum(algo.bundle().params)
        stats_end = dict(server.stats)

        probe.fill(run, m0, m1)
        # The window runs from dispatch k0 to dispatch k1 on the learner
        # thread's own stamps; its work is the updates k0+1 .. k1, gathered
        # and staged wholly inside it and all fenced by the drain above.
        t0, t1 = probe.dispatched[k0][1], probe.dispatched[k1][1]
        run.window_s = t1 - t0
        run.updates = k1 - k0
        run.samples = sum(probe.valid_per_update[k0 + 1:k1 + 1])
        run.timings = {k: timings1[k] - timings0.get(k, 0.0)
                       for k in timings1}
        run.stats = {k: stats1[k] - stats0.get(k, 0) for k in stats1
                     if isinstance(stats1[k], (int, float))}
        run.counters["publishes"] = pub1 - pub0
        lags, missing = _lags_ms(probe.dispatched,
                                 {t: d["installs"] for t, d in done.items()},
                                 t0, t1)
        frames = [n for d in done.values() for v, t, n in d["installs"]
                  if t0 <= t <= t1]
        run.counters.update(
            model_lag_samples=len(lags), model_lag_missing=missing,
            publish_bytes_mean=(sum(frames) / len(frames)) if frames else 0,
            actor_steps=sum(d["steps"] for d in done.values()))
        run.train_rate = run.samples / run.window_s
        run.e2e["train_samples_per_s"] = run.train_rate
        for q in (50, 95):
            run.e2e[f"model_lag_p{q}_ms"] = (
                harness.percentile_sorted(lags, q / 100) or 0.0)
        stamps = [t for _v, t in probe.dispatched[k0:k1 + 1]]
        run.notes["loop"] = {
            "model_lag_samples": len(lags), "model_lag_missing": missing,
            "model_lag_ms": {q: harness.percentile_sorted(lags, q / 100)
                             for q in (5, 25, 50, 75, 95)},
            "updates": run.updates, "publishes": pub1 - pub0,
            "update_interval_ms": [round(1e3 * (b - a)) for a, b in
                                   zip(stamps, stamps[1:])],
            "decoder": server.ingest_decoder,
            "parent_cores": parent_cores, "child_cores": child_cores}
        say(f"model lag over {len(lags)} (version, actor) samples, "
            f"{missing} never held; {run.updates} updates fenced in the "
            f"window; decode path {server.ingest_decoder}")

        failures = ("dropped", "dropped_nonfinite", "learner_errors",
                    "publish_errors")
        run.attempted = run.stats.get("trajectories", 0) + run.stats.get(
            "dropped", 0)
        run.failed = sum(int(run.stats.get(k, 0)) for k in failures)

        # -- correctness ---------------------------------------------------
        run.check("children_on_cpu", set(platforms.values()) == {"cpu"},
                  str(platforms))
        run.check("drained", bool(drained))
        run.check("no_errors", all(stats_end.get(k, 0) == 0 for k in
                                   failures + ("warmup_failed",)),
                  str({k: stats_end.get(k) for k in failures}))
        sent = sum(d["sent"] for d in done.values())
        seen = stats_end["trajectories"] + stats_end["dropped"]
        run.check("every_trajectory_accounted", sent == seen,
                  f"children sent {sent}, server counted {seen}")
        run.check("lag_complete", missing == 0 and len(lags) > 0,
                  f"{missing} (version, actor) pairs never held")
        held = {t: (d["version"], d["checksum"]) for t, d in done.items()}
        run.check("actors_hold_learner_params",
                  all(v == last and c == learner_sum
                      for v, c in held.values()),
                  f"learner v{last} crc {learner_sum}, actors {held}")
        losses = [float(v) for v in dict(probe.last_metrics).values()] \
            if probe.last_metrics is not None else [float("nan")]
        run.check("finite_losses", bool(np.all(np.isfinite(losses))),
                  str(losses))
        after = harness.tree_checksum(
            jax.tree_util.tree_leaves(algo.state.params)[0])
        run.check("params_changed", before != after)
        if not run.rehearsal:
            run.check("params_on_tpu", harness.on_tpu(algo.state.params))
        harness.reference_check(
            run, algo.policy, algo.state.params,
            traffic_gen.obs_sample(cfg, int(tr["reference_sequences"]),
                                   steps, run.seed))
    finally:
        children.stop_all()
        if server is not None:
            server.disable_server()
