"""Driver ``loop_replay``: the closed loop at a rate the learner sets.

This process holds the chip and runs a ``TrainingServer`` as
``drivers/loop.py`` does. What feeds it differs: CPU child processes that
REPLAY recorded unrolls through a ``VectorAgent``'s own send path (lanes,
spool, the ``#r`` report, the transport) instead of stepping an environment
and a policy. Each child makes its pool from ``--seed`` with ``traffic_gen``,
builds for every unroll the records a ``VectorActorHost`` builds for those
steps and serialises them ONCE, before the window, with the program's own
encoder (``types.trajectory.serialize_actions``): the bytes on the wire are
the bytes an actor ships. It subscribes to the model, installs every
published version and stamps what it sends with the version it holds.

**Held back, never dropped** (ISSUE 65's ruling). Over zmq the program
cannot hold a sender back (PUSH has no back-channel), so the hold is this
driver's, and its clock is the one acknowledgement every actor already
gets, the model version: :func:`credit_allowance`. The server's admission
then never sheds, and a shed of any kind fails the run.

The module level imports nothing that touches jax: the children re-import
it.
"""

from __future__ import annotations

import os
import sys
import threading
import time

from benchmark import harness, traffic_gen
from benchmark.drivers import loop

CHILD_TIMEOUT_S = loop.CHILD_TIMEOUT_S
WAIT_POLL_S = 0.0005  # a held process looks at its version this often


# --------------------------------------------------------------------------
# the credit rule and the recorded unrolls (pure: benchmark/tests hold them)
# --------------------------------------------------------------------------

def credit_allowance(version_now: int, version_first: int,
                     credit_updates: int, per_update: int, processes: int,
                     index: int) -> int:
    """How many unrolls process ``index`` of ``processes`` may have sent in
    all: its share of (versions gone by since its first send +
    ``credit_updates``) x ``per_update``. A version is an update consumed,
    whatever publishes were coalesced, so versions that skip count by their
    number. The shares of all processes sum to the whole exactly."""
    total = (max(0, int(version_now) - int(version_first))
             + int(credit_updates)) * int(per_update)
    return total // processes + (1 if index < total % processes else 0)


def unroll_records(obs, act, rew, aux: dict) -> list:
    """The records ``VectorActorHost._step`` builds for one lane over these
    steps, with each reward handed back to the step that earned it as the
    lane's next request does (``update_reward`` on a reward that is not 0)."""
    import numpy as np

    from relayrl_tpu.runtime.policy_actor import normalize_obs
    from relayrl_tpu.types.action import ActionRecord

    obs = normalize_obs(np.asarray(obs))
    act = np.asarray(act, np.int32)  # what the jitted sample returns
    aux = {k: np.asarray(aux[k], np.float32) for k in sorted(aux)}
    records = []
    for t in range(len(act)):
        record = ActionRecord(
            obs=obs[t], act=act[t], mask=None, rew=0.0,
            data={k: np.asarray(v[t]) for k, v in aux.items()}, done=False)
        if rew[t]:
            record.update_reward(float(rew[t]))
        records.append(record)
    return records


def encoded_pool(config: dict, traffic: dict, seed: int) -> list:
    """``pool_unrolls`` distinct unrolls of ``traj_len`` steps from the
    seed, each as the one wire frame an actor ships for it."""
    from relayrl_tpu.types.trajectory import serialize_actions

    pool = traffic_gen.decoded_pool(
        config, {"pool_trajectories": traffic["pool_unrolls"],
                 "traj_len": traffic["traj_len"]}, seed)
    return [serialize_actions(unroll_records(
        d.columns["o"], d.columns["a"], d.columns["r"], d.aux))
        for d in pool]


def clipped_s(intervals, t0: float, t1: float) -> float:
    """Seconds of ``intervals`` [(a, b), ...] that lie inside [t0, t1]."""
    return sum(max(0.0, min(b, t1) - max(a, t0)) for a, b in intervals)


# --------------------------------------------------------------------------
# the replay child
# --------------------------------------------------------------------------

def replay_child(idx: int, cores: list, config: dict, traffic: dict,
                 seed: int, config_path: str, workdir: str, addrs: dict,
                 stop, final_version, out) -> None:
    tag = f"replay-{idx}"
    if cores:
        os.sched_setaffinity(0, cores)
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_cpu_multi_thread_eigen=false").strip()
    from relayrl_tpu.utils.hostpin import pin_cpu

    pin_cpu()
    import jax

    out.put((tag, "platform", jax.devices()[0].platform))
    os.makedirs(workdir, exist_ok=True)
    os.chdir(workdir)
    from relayrl_tpu.runtime.agent import VectorAgent

    lanes = int(traffic["lanes_per_process"])
    per_update = int(traffic["traj_per_update"])
    processes = int(traffic["replay_processes"])
    credit = int(traffic["credit_updates"])
    pool = encoded_pool(config, traffic, seed + 1 + idx)
    agent = VectorAgent(
        num_envs=lanes, config_path=config_path, server_type="zmq",
        handshake_timeout_s=CHILD_TIMEOUT_S, seed=(seed + idx) % (2**31 - 1),
        identity=f"bench-{tag}", host_mode="vector", start=False, **addrs)
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

    version_first = int(agent.model_version)
    sent, waits, held_since, announced = 0, [], None, False
    deadline = time.monotonic() + 900.0
    while not stop.is_set() and time.monotonic() < deadline:
        if not announced and installs:
            out.put((tag, "swapped", installs[0][0]))
            announced = True
        if sent < credit_allowance(agent.model_version, version_first,
                                   credit, per_update, processes, idx):
            if held_since is not None:
                waits.append((held_since, time.monotonic()))
                held_since = None
            # born now: the data's age is its time in the relay
            agent.emit_lane(sent % lanes, pool[sent % len(pool)],
                            _stamps=(time.monotonic_ns(), None))
            sent += 1
            continue
        if held_since is None:
            held_since = time.monotonic()
        time.sleep(WAIT_POLL_S)
    if held_since is not None:
        waits.append((held_since, time.monotonic()))
    out.put((tag, "stopped", sent))
    # the learner drains, then says which version is its last: hold it
    t_end = time.monotonic() + 60.0
    while final_version.value < 0 and time.monotonic() < t_end:
        time.sleep(0.01)
    while (agent.model_version < final_version.value
           and time.monotonic() < t_end):
        time.sleep(0.01)
    out.put((tag, "done", {
        "installs": installs, "sent": sent, "waits": waits,
        "frame_bytes": sum(map(len, pool)) / len(pool),
        "version_first": version_first, "version": int(agent.model_version),
        "checksum": harness.tree_checksum(agent.host.params)}))
    agent.disable_agent()


# --------------------------------------------------------------------------
# the parent
# --------------------------------------------------------------------------

class ReplayChildren(loop.Children):
    def start(self, *args) -> None:
        p = self.ctx.Process(target=replay_child, args=(*args, self.queue),
                             daemon=True)
        p.start()
        self.procs.append(p)


class QueueSampler(threading.Thread):
    """Depth of the server's raw and decoded queues, every ``period``
    seconds: ``samples`` holds ``(t_monotonic, raw, decoded)``."""

    def __init__(self, server, period: float):
        super().__init__(name="bench-queue-sampler", daemon=True)
        self.server, self.period = server, period
        self.samples: list = []
        self._halt = threading.Event()

    def run(self) -> None:
        server = self.server
        while not self._halt.wait(self.period):
            self.samples.append((time.monotonic(), server._ingest.qsize(),
                                 server._decoded.qsize()))

    def halt(self) -> None:
        self._halt.set()
        self.join(timeout=5)


def _core_plan(traffic: dict) -> tuple[list, list]:
    """The parent (learner, staging, publisher and transport threads) keeps
    the first ``parent_cores`` cores it may run on; each replay process gets
    one of the next, round robin."""
    cores = sorted(os.sched_getaffinity(0))
    n = int(traffic["replay_processes"])
    keep = min(int(traffic["parent_cores"]), max(1, len(cores) - 1))
    rest = cores[keep:keep + n] or cores
    return cores[:keep], [[rest[i % len(rest)]] for i in range(n)]


def _sheds(server) -> dict:
    guard = server.guardrails
    if guard is None or guard.admission is None:
        return {}
    return dict(guard.admission.accounting()["sheds"])


def drive(run: harness.Run) -> None:
    cfg, tr = run.config, run.traffic
    steps, per_update = int(tr["traj_len"]), int(tr["traj_per_update"])
    n_procs = int(tr["replay_processes"])
    say = harness.say
    say(f"native library: {harness.ensure_native(run)}")

    children = ReplayChildren()
    server = sampler = None
    try:
        with run.phase("children"):
            config_path = harness.write_program_config(
                run, {"max_traj_length": steps,
                      **tr.get("program_config", {})})
            ports = {k: f"tcp://127.0.0.1:{loop._free_port()}" for k in
                     ("agent_listener_addr", "trajectory_addr",
                      "model_pub_addr")}
            agent_addrs = {
                "agent_listener_addr": ports["agent_listener_addr"],
                "trajectory_addr": ports["trajectory_addr"],
                "model_sub_addr": ports["model_pub_addr"]}
            parent_cores, child_cores = _core_plan(tr)
            stop = children.ctx.Event()
            final_version = children.ctx.Value("q", -1)
            for i in range(n_procs):
                children.start(i, child_cores[i], cfg, tr, run.seed,
                               config_path,
                               os.path.join(run.run_dir, f"replay-{i}"),
                               agent_addrs, stop, final_version)
            os.sched_setaffinity(0, parent_cores)
        tags = {f"replay-{i}" for i in range(n_procs)}

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
            sampler = QueueSampler(server, float(tr["queue_sample_s"]))
            sampler.start()
            platforms = children.expect(tags, "platform", CHILD_TIMEOUT_S)
            children.expect(tags, "swapped", CHILD_TIMEOUT_S)
            # The loop is at its steady rate from the first update: the
            # senders burst their credit at once and stand held from then
            # on (PERF.md section 6, PR 65: the credit stands BEFORE the
            # server's PULL socket, its queues hold a tenth of it). So the
            # window opens a fixed ``settle_updates`` after the warm ones,
            # the same work from every seed; ``saturated`` holds the run
            # to it.
            first = int(tr["warm_updates"]) + int(tr["settle_updates"])
            deadline = time.monotonic() + CHILD_TIMEOUT_S
            while algo.inflight.fenced_count < first:
                if time.monotonic() > deadline:
                    raise RuntimeError(
                        f"{server.stats['updates']} updates after "
                        f"{CHILD_TIMEOUT_S:.0f}s: {dict(server.stats)}")
                time.sleep(0.02)

        if run.trace:
            with run.traced():
                time.sleep(float(tr["trace_seconds"]))
            run.spans.reset()

        def snapshot():
            return (probe.mark(), dict(server.timings), dict(server.stats),
                    server._publisher.published if server._publisher else 0,
                    _sheds(server))

        # both edges on a dispatch of the learner thread: drivers/loop.py
        k0 = loop._await_dispatch(probe, CHILD_TIMEOUT_S)
        m0, timings0, stats0, pub0, sheds0 = snapshot()
        now0 = run.begin_window()
        time.sleep(run.seconds)
        k1 = loop._await_dispatch(probe, CHILD_TIMEOUT_S)
        run.end_window(now0)
        m1, timings1, stats1, pub1, sheds1 = snapshot()

        # -- after the window: stop, count, drain, agree on the version ---
        stop.set()
        stopped = children.expect(tags, "stopped", 60.0)
        sent = sum(stopped.values())

        def counted():
            return server.stats["trajectories"] + server.stats["dropped"]

        deadline = time.monotonic() + 60.0  # what the wire still holds
        while counted() < sent and time.monotonic() < deadline:
            time.sleep(0.02)
        drained = server.drain(timeout=120)
        sampler.halt()
        last = int(algo.dispatched_version)
        final_version.value = last
        done = children.expect(tags, "done", 120.0)
        children.stop_all()
        learner_sum = harness.tree_checksum(algo.bundle().params)
        stats_end = dict(server.stats)
        sheds_end = _sheds(server)

        probe.fill(run, m0, m1)
        t0, t1 = probe.dispatched[k0][1], probe.dispatched[k1][1]
        run.window_s = t1 - t0
        run.updates = k1 - k0
        run.samples = sum(probe.valid_per_update[k0 + 1:k1 + 1])
        run.timings = {k: timings1[k] - timings0.get(k, 0.0)
                       for k in timings1}
        run.stats = {k: stats1[k] - stats0.get(k, 0) for k in stats1
                     if isinstance(stats1[k], (int, float))}
        sheds = {k: sheds1[k] - sheds0.get(k, 0) for k in sheds1}
        lags, missing = loop._lags_ms(
            probe.dispatched, {t: d["installs"] for t, d in done.items()},
            t0, t1)
        frames = [n for d in done.values() for v, t, n in d["installs"]
                  if t0 <= t <= t1]
        frame_bytes = (sum(d["frame_bytes"] for d in done.values())
                       / len(done))
        depths = sorted(raw + dec for t, raw, dec in sampler.samples
                        if t0 <= t <= t1)
        held = {t: clipped_s(d["waits"], t0, t1) for t, d in done.items()}
        run.counters.update(
            publishes=pub1 - pub0,
            model_lag_samples=len(lags), model_lag_missing=missing,
            publish_bytes_mean=(sum(frames) / len(frames)) if frames else 0,
            ingest_wire_bytes=run.stats.get("trajectories", 0) * frame_bytes,
            credit_wait_s_mean=sum(held.values()) / len(held),
            queue_depth_median=(depths[len(depths) // 2] if depths
                                else None))
        run.train_rate = run.samples / run.window_s
        run.e2e["train_samples_per_s"] = run.train_rate
        for q in (50, 95):
            run.e2e[f"model_lag_p{q}_ms"] = (
                harness.percentile_sorted(lags, q / 100) or 0.0)
        stamps = [t for _v, t in probe.dispatched[k0:k1 + 1]]
        idle_share = run.timings["learner_idle_s"] / run.window_s
        run.notes["loop"] = {
            "model_lag_samples": len(lags), "model_lag_missing": missing,
            "model_lag_ms": {q: harness.percentile_sorted(lags, q / 100)
                             for q in (5, 25, 50, 75, 95)},
            "updates": run.updates, "publishes": pub1 - pub0,
            "update_interval_ms": [round(1e3 * (b - a)) for a, b in
                                   zip(stamps, stamps[1:])],
            "decoder": server.ingest_decoder,
            "parent_cores": parent_cores, "child_cores": child_cores}
        run.notes["replay"] = {
            "sent": sent, "counted": counted(), "frame_bytes": frame_bytes,
            "sheds": sheds_end, "learner_idle_share": idle_share,
            "learner_idle_limit": float(tr["saturated_idle_share"]),
            "held_share_limit": float(tr["saturated_held_share"]),
            "credit_wait_share": {t: s / run.window_s
                                  for t, s in sorted(held.items())},
            "queue_depth": {q: harness.percentile_sorted(depths, q / 100)
                            for q in (5, 50, 95)},
            "versions": {t: (d["version_first"], d["version"])
                         for t, d in sorted(done.items())}}
        say(f"{run.updates} updates in the window, learner idle "
            f"{100 * idle_share:.2f}%; sent {sent}, counted {counted()}; "
            f"decode path {server.ingest_decoder}")

        failures = ("dropped", "dropped_nonfinite", "learner_errors",
                    "publish_errors")
        run.attempted = run.stats.get("trajectories", 0) + run.stats.get(
            "dropped", 0) + sum(sheds.values())
        run.failed = (sum(int(run.stats.get(k, 0)) for k in failures)
                      + sum(sheds.values()))

        # -- correctness ---------------------------------------------------
        run.check("children_on_cpu", set(platforms.values()) == {"cpu"},
                  str(platforms))
        run.check("drained", bool(drained))
        run.check("no_errors", all(stats_end.get(k, 0) == 0 for k in
                                   failures + ("warmup_failed",)),
                  str({k: stats_end.get(k) for k in failures}))
        # the senders offered more than the system took (each stood held at
        # its credit for ``saturated_held_share`` of the window or more) and
        # the learner thread had data (it waited under
        # ``saturated_idle_share`` of the window): the traffic file has the
        # readings both limits were set from
        idle_limit = float(tr["saturated_idle_share"])
        held_limit = float(tr["saturated_held_share"])
        held_min = min(held.values()) / run.window_s
        run.check("saturated",
                  idle_share < idle_limit and held_min >= held_limit,
                  f"learner thread waited for data {idle_share:.4f} of the "
                  f"window, limit {idle_limit}; the least-held process "
                  f"stood at its credit {held_min:.4f}, limit {held_limit}")
        run.check("nothing_dropped",
                  stats_end["dropped"] == 0 and not any(sheds_end.values()),
                  f"dropped {stats_end['dropped']}, sheds {sheds_end}")
        run.check("every_trajectory_accounted", sent == counted(),
                  f"children sent {sent}, server counted {counted()}")
        run.check("lag_complete", missing == 0 and len(lags) > 0,
                  f"{missing} (version, process) pairs never held")
        held_params = {t: (d["version"], d["checksum"])
                       for t, d in done.items()}
        run.check("actors_hold_learner_params",
                  all(v == last and c == learner_sum
                      for v, c in held_params.values()),
                  f"learner v{last} crc {learner_sum}, "
                  f"processes {held_params}")
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
        ref = run.notes.get("reference", {})
        tol = ref.get("tolerance", {})
        # each number compared beside its limit: last in the result line
        # and last on standard error
        run.notes["compared"] = {
            "rel_dlogp": [ref.get("rel_dlogp"), tol.get("logp_rel")],
            "rel_dv": [ref.get("rel_dv"), tol.get("value_rel")],
            "learner_idle_share": [idle_share, idle_limit],
            "held_share_min": [held_min, held_limit],
            "dropped": [stats_end["dropped"], 0],
            "sheds": [sum(sheds_end.values()), 0],
            "sent_less_counted": [sent - counted(), 0],
            "model_lag_missing": [missing, 0]}
        for name, (value, limit) in run.notes["compared"].items():
            print(f"compared {name} {value} limit {limit}", file=sys.stderr,
                  flush=True)
    finally:
        if sampler is not None:
            sampler.halt()
        children.stop_all()
        if server is not None:
            server.disable_server()
