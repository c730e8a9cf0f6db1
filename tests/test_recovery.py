"""End-to-end crash recovery (ISSUE 6 tentpole): durable actor spool,
idempotent ingest, and the learner/actor SIGKILL drills.

Unit layer: TrajectorySpool retention/disk/breaker semantics and the
SequenceLedger dedup window + sidecar persistence.

Drill layer (all three transports): a real TrainingServer subprocess
(tests/drills/_chaos_server.py) is SIGKILLed mid-training while a live Agent
keeps stepping; the respawned server resumes from orbax + the ingest-
ledger sidecar, the agent heals (breaker probe / zmq socket monitor /
native heartbeat), replays its spool, and the final sequence accounting
proves zero loss and zero double-training: every sequence number the
actor ever assigned is accepted exactly once on the surviving line of
history, replay surplus lands in the duplicate counter, and the model
version the actor holds advances monotonically across the crash.
"""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from relayrl_tpu import faults, telemetry
from relayrl_tpu.runtime.spool import SequenceLedger, TrajectorySpool
from tests._util import free_port

DRILLS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "drills")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_planes():
    faults.reset_for_tests()
    telemetry.reset_for_tests()
    yield
    faults.reset_for_tests()
    telemetry.reset_for_tests()


class TestTrajectorySpool:
    def test_bounded_eviction_keeps_newest(self):
        spool = TrajectorySpool(send_fn=None, max_entries=3)
        for i in range(6):
            spool.send(b"p%d" % i, "a")
        assert spool.depth == 3
        assert [seq for _, seq, _ in spool._entries] == [4, 5, 6]
        assert spool.sent_counts() == {"a": 6}

    def test_byte_bound_evicts(self):
        spool = TrajectorySpool(send_fn=None, max_entries=100,
                                max_bytes=1 << 16)
        big = b"x" * 30_000
        for _ in range(5):
            spool.send(big, "a")
        assert spool.depth <= 2

    def test_disk_spool_survives_process_death(self, tmp_path):
        """The actor-crash half of durability: a NEW spool over the same
        directory restores the retained window AND continues the seq
        space (no reused sequence numbers — reuse would alias distinct
        trajectories in the server's dedup window)."""
        d = str(tmp_path)
        spool = TrajectorySpool(send_fn=None, max_entries=10,
                                directory=d, name="worker0")
        for i in range(4):
            spool.send(b"payload-%d" % i, "lane0")
        spool.send(b"other", "lane1")
        spool.close()  # process "crash" (file already flushed per append)

        reborn = TrajectorySpool(send_fn=None, max_entries=10,
                                 directory=d, name="worker0")
        assert reborn.depth == 5
        assert reborn.sent_counts() == {"lane0": 4, "lane1": 1}
        assert reborn.send(b"new", "lane0") == 5  # continues, not reuses
        sent = []
        reborn.send_fn = lambda p, tagged: sent.append((p, tagged))
        assert reborn.replay() == 6
        assert (b"payload-0", "lane0#s1") in sent

    def test_disk_spool_tolerates_torn_tail(self, tmp_path):
        d = str(tmp_path)
        spool = TrajectorySpool(send_fn=None, directory=d, name="t")
        spool.send(b"whole", "a")
        spool.close()
        path = os.path.join(d, "t.spool")
        with open(path, "ab") as f:
            f.write(b"\x00\x00\x00\xffTORN")  # half a record
        reborn = TrajectorySpool(send_fn=None, directory=d, name="t")
        assert reborn.depth == 1  # the whole record, not the torn one
        # The torn bytes must be TRUNCATED before appends resume:
        # records written after a surviving torn tail would be
        # unreachable to the NEXT load (it stops at the first torn
        # record) — the double-crash case.
        reborn.send(b"second-life", "a")
        reborn.close()
        third = TrajectorySpool(send_fn=None, directory=d, name="t")
        assert third.depth == 2
        assert third.sent_counts() == {"a": 2}

    def test_breaker_opens_then_heal_replays(self):
        """Dead-server shape: sends fail → breaker opens (actor stops
        paying wire timeouts) → server returns → the half-open probe
        send succeeds → the spool auto-replays the outage window."""
        from relayrl_tpu.transport.retry import CircuitBreaker, RetryPolicy

        alive = {"up": False}
        delivered = []

        def send_fn(payload, tagged):
            if not alive["up"]:
                raise ConnectionError("server down")
            delivered.append((payload, tagged))

        spool = TrajectorySpool(
            send_fn=send_fn, max_entries=100,
            retry=RetryPolicy(base_delay_s=0.001, max_delay_s=0.002,
                              deadline_s=0.01, max_attempts=2),
            breaker=CircuitBreaker("t", failure_threshold=2,
                                   reset_timeout_s=0.05))
        spool.send(b"a", "x")
        spool.send(b"b", "x")  # second failure opens the breaker
        assert spool.breaker.state == "open"
        spool.send(b"c", "x")  # buffered without touching the wire
        assert not delivered and spool.depth == 3
        alive["up"] = True
        time.sleep(0.06)  # half-open window
        spool.send(b"d", "x")  # probe succeeds → closes → auto-replay
        assert spool.breaker.state == "closed"
        payloads = [p for p, _ in delivered]
        assert payloads.count(b"a") >= 1 and payloads.count(b"c") >= 1
        assert set(payloads) == {b"a", b"b", b"c", b"d"}


class TestSequenceLedger:
    def test_monotonic_accept_and_dup_drop(self):
        led = SequenceLedger(window=64)
        assert all(led.accept("a", s) for s in (1, 2, 3))
        assert not led.accept("a", 2)  # replay
        assert led.accept("b", 1)      # independent per-agent space
        assert led.total_duplicates() == 1
        assert led.counts()["a"] == {"max_seq": 3, "accepted": 3,
                                     "contiguous": True}

    def test_out_of_order_within_window(self):
        led = SequenceLedger(window=16)
        assert led.accept("a", 5)
        assert led.accept("a", 3)  # late but inside the window
        assert not led.accept("a", 3)
        assert led.counts()["a"]["contiguous"] is False  # 1,2,4 missing

    def test_below_window_treated_as_duplicate(self):
        led = SequenceLedger(window=4)
        assert led.accept("a", 100)
        assert not led.accept("a", 95)  # <= 100 - 4: conservatively dup
        assert led.accept("a", 97)

    def test_sidecar_roundtrip(self, tmp_path):
        led = SequenceLedger(window=32)
        for s in (1, 2, 4):
            led.accept("a", s)
        led.accept("a", 2)  # a duplicate, for the counter
        path = str(tmp_path / "ledger.json")
        led.save(path)
        back = SequenceLedger.load(path)
        assert back.window == 32
        assert back.total_duplicates() == 1
        assert not back.accept("a", 4)  # still deduped after restore
        assert back.accept("a", 3)      # still open after restore

    def test_retract_reopens_seq(self):
        led = SequenceLedger(window=16)
        assert led.accept("a", 1)
        led.retract("a", 1)  # queue-full downstream: loss, not dedup
        assert led.accept("a", 1)
        assert led.counts()["a"]["accepted"] == 1


class TestIdempotentIngestLive:
    def test_replay_never_double_trains_zmq(self, tmp_cwd):
        """In-process loop: an Agent ships episodes, then force-replays
        its whole spool window twice. The server's trajectory counter
        must count each unique episode ONCE; the surplus lands in the
        duplicate counter."""
        from relayrl_tpu.runtime.agent import Agent
        from relayrl_tpu.runtime.server import TrainingServer

        addrs = {
            "agent_listener_addr": f"tcp://127.0.0.1:{free_port()}",
            "trajectory_addr": f"tcp://127.0.0.1:{free_port()}",
            "model_pub_addr": f"tcp://127.0.0.1:{free_port()}",
        }
        worker_addrs = {
            "agent_listener_addr": addrs["agent_listener_addr"],
            "trajectory_addr": addrs["trajectory_addr"],
            "model_sub_addr": addrs["model_pub_addr"],
        }
        server = TrainingServer(
            "REINFORCE", obs_dim=4, act_dim=2, env_dir=str(tmp_cwd),
            hyperparams={"traj_per_epoch": 100, "hidden_sizes": [16, 16]},
            **addrs)
        try:
            agent = Agent(server_type="zmq", handshake_timeout_s=30,
                          seed=0, probe=False, **worker_addrs)
            try:
                rng = np.random.default_rng(0)
                n_episodes = 6
                for _ in range(n_episodes):
                    for _ in range(3):
                        agent.request_for_action(
                            rng.standard_normal(4).astype(np.float32))
                    agent.flag_last_action(1.0, terminated=True)
                assert agent.spool is not None
                assert agent.spool.replay() == n_episodes
                agent.spool.replay()  # and again
                deadline = time.monotonic() + 30
                while (server.ingest_accounting()["duplicates"]
                       < 2 * n_episodes and time.monotonic() < deadline):
                    time.sleep(0.05)
                server.drain(timeout=30)
                acct = server.ingest_accounting()
                row = acct["agents"][agent.transport.identity]
                assert row == {"max_seq": n_episodes,
                               "accepted": n_episodes, "contiguous": True}
                assert acct["duplicates"] == 2 * n_episodes
                assert server.stats["trajectories"] == n_episodes
            finally:
                agent.disable_agent()
        finally:
            server.disable_server()


def _spawn_server(scratch: str, transport: str, addrs: dict,
                  resume: bool) -> subprocess.Popen:
    cfg = {
        "algorithm": "REINFORCE", "obs_dim": 6, "act_dim": 3,
        "hyperparams": {"traj_per_epoch": 4, "hidden_sizes": [16, 16],
                        "with_vf_baseline": False},
        "server_type": transport, "scratch": scratch,
        "checkpoint_every": 1, "resume": resume,
        "status_path": os.path.join(scratch, "status.json"),
        **addrs,
    }
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    return subprocess.Popen(
        [sys.executable, os.path.join(DRILLS, "_chaos_server.py"),
         json.dumps(cfg)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)


def _read_status(scratch: str) -> dict | None:
    try:
        with open(os.path.join(scratch, "status.json")) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None


def _wait_status(scratch: str, proc: subprocess.Popen, pred,
                 timeout_s: float, what: str) -> dict:
    deadline = time.monotonic() + timeout_s
    status = None
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            out, _ = proc.communicate()
            raise AssertionError(
                f"chaos server died waiting for {what} "
                f"(rc={proc.returncode}):\n{out[-3000:]}")
        status = _read_status(scratch)
        if status is not None and pred(status):
            return status
        time.sleep(0.1)
    raise AssertionError(f"timed out waiting for {what}; last={status}")


def _drive_episodes(agent, rng, n: int, steps: int = 4) -> None:
    for _ in range(n):
        for _ in range(steps):
            agent.request_for_action(
                rng.standard_normal(6).astype(np.float32))
        agent.flag_last_action(1.0, terminated=True)


def _transport_addrs(transport: str) -> tuple[dict, dict]:
    """(server-side, agent-side) address overrides on fresh fixed ports
    (fixed so the RESTARTED server binds where the agent reconnects)."""
    if transport in ("native", "grpc"):
        port = free_port()
        return ({"bind_addr": f"127.0.0.1:{port}"},
                {"server_addr": f"127.0.0.1:{port}"})
    ports = [free_port() for _ in range(3)]
    return ({"agent_listener_addr": f"tcp://127.0.0.1:{ports[0]}",
             "trajectory_addr": f"tcp://127.0.0.1:{ports[1]}",
             "model_pub_addr": f"tcp://127.0.0.1:{ports[2]}"},
            {"agent_listener_addr": f"tcp://127.0.0.1:{ports[0]}",
             "trajectory_addr": f"tcp://127.0.0.1:{ports[1]}",
             "model_sub_addr": f"tcp://127.0.0.1:{ports[2]}"})


def _require_transport(transport: str) -> None:
    if transport == "native":
        from relayrl_tpu.transport.native_backend import native_available

        if not native_available():
            pytest.skip("native .so unavailable")
    if transport == "grpc":
        pytest.importorskip("grpc")


# ISSUE 17 wall re-fit: the drill is transport-agnostic above the wire;
# zmq stays in the fast tier, the grpc/native twins ride the slow tier
# (same convention as the columnar SIGKILL trio in PR 14).
@pytest.mark.parametrize(
    "transport",
    ["zmq",
     pytest.param("grpc", marks=pytest.mark.slow),
     pytest.param("native", marks=pytest.mark.slow)])
def test_learner_sigkill_resume_zero_loss_zero_dup(transport, tmp_path,
                                                   tmp_cwd):
    """THE learner crash drill: SIGKILL the training server mid-run,
    restart it with resume, and assert (a) sequence accounting — every
    trajectory the actor sent is accepted exactly once on the surviving
    line of history (contiguous, max_seq == actor's sent count), with
    replay surplus visible as duplicates, and (b) model-version
    continuity — the version the actor holds strictly advances across
    the crash (orbax restores the version counter; wire-v2 keyframes
    resync the fleet)."""
    _require_transport(transport)
    scratch = str(tmp_path)
    server_addrs, agent_addrs = _transport_addrs(transport)
    proc = _spawn_server(scratch, transport, server_addrs, resume=False)
    agent = None
    try:
        _wait_status(scratch, proc, lambda s: True, 120, "server up")
        from relayrl_tpu.runtime.agent import Agent

        extra = {"heartbeat_s": 1.0} if transport == "native" else {}
        agent = Agent(server_type=transport, handshake_timeout_s=60,
                      seed=0, probe=False, **agent_addrs, **extra)
        rng = np.random.default_rng(0)
        # Phase 1: train until at least one checkpoint (version > 0 and
        # a ledger sidecar on disk) so the resume has a base.
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            _drive_episodes(agent, rng, 2)
            status = _read_status(scratch)
            if (status and status["version"] >= 2
                    and status["accounting"]["agents"]):
                break
            time.sleep(0.1)
        status = _read_status(scratch)
        assert status and status["version"] >= 2, (
            f"no training before the kill in 120 s: {status}")
        v_before = status["version"]
        agent_v_before = agent.model_version

        # Phase 2: SIGKILL. No shutdown path runs — the drill.
        proc.kill()
        proc.wait(timeout=30)

        # Phase 3: the actor keeps playing into the outage (sends fail
        # into the spool / the zmq pipe; the breaker keeps the env loop
        # fast).
        _drive_episodes(agent, rng, 8)
        sent_during_outage = agent.spool.sent_counts()[
            agent.transport.identity]

        # Phase 4: restart with resume; the agent must heal on its own
        # (breaker probe / socket monitor / heartbeat redial) and the
        # fleet must train PAST the pre-kill version (continuity).
        proc = _spawn_server(scratch, transport, server_addrs, resume=True)
        # the dead server's status file is still there: the event is the
        # NEW process's first write of it (restored, warmed up, serving)
        _wait_status(scratch, proc, lambda s: s["pid"] == proc.pid, 180,
                     "the restarted server's own status")
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            _drive_episodes(agent, rng, 2)
            assert proc.poll() is None, (
                f"restarted server died (rc={proc.returncode}):\n"
                f"{proc.communicate()[0][-3000:]}")
            status = _read_status(scratch)
            if (status and status["version"] > v_before
                    and agent.model_version > agent_v_before):
                break
            time.sleep(0.1)
        assert status and status["version"] > v_before, (
            f"server never trained past the crash in 180 s: {status} "
            f"<= {v_before}")
        assert agent.model_version > agent_v_before, (
            "actor never resynced to the post-crash model line")

        # Phase 5: belt-and-braces full replay, then the accounting
        # assertion — the heart of the drill.
        agent.spool.replay()
        ident = agent.transport.identity
        sent_total = agent.spool.sent_counts()[ident]
        assert sent_total >= sent_during_outage

        def recovered(s):
            row = s["accounting"]["agents"].get(ident)
            return (row is not None and row["max_seq"] == sent_total
                    and row["contiguous"])

        status = _wait_status(scratch, proc, recovered, 120,
                              "zero-loss accounting")
        row = status["accounting"]["agents"][ident]
        assert row["accepted"] == sent_total, (
            f"double-training or loss: {row} vs sent={sent_total}")
        # The replay after recovery re-sent already-accepted sequences:
        # the dedup ledger must show them as duplicates, not train them.
        assert status["accounting"]["duplicates"] >= 1
        # Recovery left its breadcrumbs in the server telemetry.
        names = {m["name"] for m in status["telemetry"]["metrics"]}
        assert "relayrl_server_duplicate_trajectories_total" in names
    finally:
        if agent is not None:
            agent.disable_agent()
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.communicate(timeout=60)
            except subprocess.TimeoutExpired:
                proc.kill()


_ACTOR_LOOP = """
import json, sys, time
import numpy as np
from relayrl_tpu.runtime.agent import Agent

cfg = json.loads(sys.argv[1])
agent = Agent(server_type="native", handshake_timeout_s=60, seed=1,
              probe=False, server_addr=cfg["server_addr"])
rng = np.random.default_rng(1)
print("actor-ready", flush=True)
while True:
    for _ in range(4):
        agent.request_for_action(rng.standard_normal(6).astype(np.float32))
    agent.flag_last_action(1.0, terminated=True)
"""


def test_actor_sigkill_reap_and_replacement_recovers(tmp_cwd):
    """The actor crash drill (native reaping plane): SIGKILL a live
    actor process → the kernel-closed connection unregisters it; a
    replacement joins and training throughput recovers (updates keep
    advancing past the churn)."""
    _require_transport("native")
    from relayrl_tpu.runtime.server import TrainingServer

    port = free_port()
    server = TrainingServer(
        "REINFORCE", obs_dim=6, act_dim=3, env_dir=str(tmp_cwd),
        hyperparams={"traj_per_epoch": 4, "hidden_sizes": [16, 16]},
        server_type="native", bind_addr=f"127.0.0.1:{port}")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO

    def spawn_actor():
        return subprocess.Popen(
            [sys.executable, "-c", _ACTOR_LOOP,
             json.dumps({"server_addr": f"127.0.0.1:{port}"})],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, cwd=str(tmp_cwd))

    def registry_size():
        with server._registry_lock:
            return len(server.agent_ids)

    victim = spawn_actor()
    try:
        deadline = time.monotonic() + 120
        while ((registry_size() < 1 or server.stats["updates"] < 1)
               and time.monotonic() < deadline):
            assert victim.poll() is None, victim.communicate()[0][-2000:]
            time.sleep(0.1)
        assert registry_size() >= 1 and server.stats["updates"] >= 1
        updates_at_kill = server.stats["updates"]

        victim.kill()  # SIGKILL: kernel closes the sockets
        victim.wait(timeout=30)
        deadline = time.monotonic() + 60
        while registry_size() > 0 and time.monotonic() < deadline:
            time.sleep(0.1)
        assert registry_size() == 0, "dead actor never reaped"

        replacement = spawn_actor()
        try:
            deadline = time.monotonic() + 120
            while ((registry_size() < 1
                    or server.stats["updates"] <= updates_at_kill)
                   and time.monotonic() < deadline):
                assert replacement.poll() is None, (
                    replacement.communicate()[0][-2000:])
                time.sleep(0.1)
            assert registry_size() >= 1, "replacement never registered"
            assert server.stats["updates"] > updates_at_kill, (
                "training did not recover after the churn")
        finally:
            replacement.kill()
            replacement.wait(timeout=30)
    finally:
        if victim.poll() is None:
            victim.kill()
            victim.wait(timeout=30)
        server.disable_server()
