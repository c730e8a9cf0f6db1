"""The RLHF dataflow scheduler: generate → score → update as decoupled
stages over the existing tiers (ISSUE 13 tentpole; the MindSpeed RL /
RLAX disaggregated pattern).

Stage map — every stage rides machinery that already exists:

* **generate** — a :class:`GenerationStage` steps ``rlhf.lanes``
  TokenGen lanes through ONE batched jitted policy dispatch per round.
  Sequence (transformer) policies run the vector tier's vmapped
  ``step_window`` path (``runtime/vector_actor.py`` — generation through
  this stage is BIT-identical to a local ``PolicyActor`` at the same
  seed + params version, the lock tests/test_rlhf.py holds);
  ``rlhf.generation_tier: "anakin"`` moves generation INSIDE the fused
  scan (:class:`FusedGenerationStage` — TokenGen as pure JAX in the
  ``lax.scan`` with the rolling-window carry, ``lanes × unroll`` tokens
  per device dispatch instead of one per-step round-trip); thin-client
  generation via the serving plane serves sequence policies too since
  serving v2 — the service holds each lane's rolling window in its
  session table, capacity bounded by ``serving.max_sessions`` (size it
  to the lane count; an evicted lane resyncs from its client mirror,
  it does not fail). Behavior policy
  evidence is recorded per token at generation time: ``logp_a`` (the
  V-trace numerator's denominator) already rides every record's aux;
  the stage adds ``bver``, the params version the token was sampled
  under.
* **score** — completed generations are WITHHELD from the wire (the
  ``VectorAgent.send_interceptor`` seam) and handed to a
  :class:`ScoreStage` thread, which batches them into one jitted scorer
  dispatch, writes the terminal reward into the episode's marker
  record, and re-injects via ``VectorAgent.emit_lane`` — sequence
  numbers are assigned at emission, so the spool's at-least-once window
  only ever holds FINAL (scored) bytes and a crash replay can never
  deliver an unscored episode.
* **update** — the unmodified training server: scored episodes flow
  through spool/seq-dedup/columnar ingest into the IMPALA learner,
  whose V-trace correction (``ops/vtrace.py``) importance-weights each
  token from its recorded behavior log-prob back to the current policy
  — the off-policy lag between ``bver`` and the learner's version is
  exactly what it exists for. ``learner.freeze`` masks
  (``algorithms/freeze.py``) make the fine-tune recipe first-class.

Telemetry: ``relayrl_rlhf_generated_tokens_total``,
``relayrl_rlhf_scored_episodes_total``,
``relayrl_rlhf_stage_seconds{stage=generate|score|emit}``, and
``relayrl_rlhf_lag_versions`` (behavior-vs-actor-held version distance
observed at emission). docs/observability.md has the catalog;
docs/operations.md the runbook.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable

import numpy as np

from relayrl_tpu.envs.scorers import make_scorer
from relayrl_tpu.telemetry.core import LAG_BUCKETS
from relayrl_tpu.types.columnar import (
    DecodedTrajectory,
    encode_columnar_frame,
    is_columnar_frame,
    parse_frame,
)
from relayrl_tpu.types.trajectory import (
    deserialize_actions,
    serialize_actions,
)


def extract_generation(records, prompt_len: int):
    """Serialized-episode records → ``(tokens[i32], gen_len, marker)``.

    ``records`` is one episode as shipped by an actor tier: real steps
    (obs = the pre-action token context window, act = the token) plus
    the trailing terminal marker from ``flag_last_action``. The full
    generated sequence is the LAST real step's context with its action
    written at the final write position — observations are recorded
    before the action lands, so only the last token is missing from the
    last observation. Token values are small integers, exact in the
    float32 the wire normalizes observations to."""
    real = [r for r in records if r.act is not None]
    if not real:
        raise ValueError("episode has no real steps to score")
    marker = records[-1] if records[-1].act is None else None
    gen_len = len(real)
    last = real[-1]
    tokens = np.asarray(last.obs).astype(np.int32).reshape(-1).copy()
    write = int(prompt_len) + gen_len - 1
    if write >= tokens.shape[0]:
        raise ValueError(
            f"generation of {gen_len} tokens overflows the context window "
            f"({tokens.shape[0]} with prompt_len {prompt_len})")
    tokens[write] = int(np.asarray(last.act).reshape(-1)[0])
    return tokens, gen_len, marker


def extract_generation_frame(dt: DecodedTrajectory, prompt_len: int):
    """Columnar twin of :func:`extract_generation`: one decoded frame
    (the anakin tier ships whole episodes as contiguous columnar frames,
    markers pre-folded) → ``(tokens[i32], gen_len)``. The terminal
    marker is folded into the frame (``n_records == n_steps + 1``), so
    there is no marker object to patch — the score lands on ``r[-1]``
    directly, which is exactly where the server's native decoder folds a
    scored marker's reward."""
    if dt.n_steps < 1:
        raise ValueError("frame has no real steps to score")
    if dt.n_records != dt.n_steps + 1:
        raise ValueError(
            f"frame is not one terminated episode (n_steps {dt.n_steps}, "
            f"n_records {dt.n_records}) — the score stage patches the "
            f"folded terminal reward, which a mid-episode chunk lacks")
    gen_len = int(dt.n_steps)
    tokens = np.asarray(
        dt.columns["o"][-1]).astype(np.int32).reshape(-1).copy()
    write = int(prompt_len) + gen_len - 1
    if write >= tokens.shape[0]:
        raise ValueError(
            f"generation of {gen_len} tokens overflows the context window "
            f"({tokens.shape[0]} with prompt_len {prompt_len})")
    tokens[write] = int(np.asarray(dt.columns["a"][-1]).reshape(-1)[0])
    return tokens, gen_len


class ScoreStage:
    """Decoupled scoring: batches completed generations into one scorer
    dispatch, assigns the terminal reward, re-emits.

    ``submit`` runs on the generation thread and BLOCKS when
    ``max_queue`` episodes are parked (bounded hand-off = backpressure:
    a slow scorer throttles generation instead of growing unbounded —
    the pipeline/serving precedent). The worker gathers up to ``batch``
    episodes, waiting ``linger_s`` after the first for siblings (size-
    or-linger close, the dynamic-batching shape), scores them in ONE
    ``score_batch_np`` dispatch (short batches are padded with repeats
    of row 0 — inert under vmap, sliced off), patches each episode's
    terminal marker reward, and hands the re-serialized bytes to
    ``emit_fn(lane, payload)``.
    """

    def __init__(self, scorer, prompt_len: int, emit_fn: Callable,
                 batch: int = 8, linger_s: float = 0.02,
                 max_queue: int = 256, version_fn: Callable | None = None):
        from relayrl_tpu import telemetry

        self.scorer = scorer
        self.prompt_len = int(prompt_len)
        self.emit_fn = emit_fn
        self.batch = max(1, int(batch))
        self.linger_s = max(0.0, float(linger_s))
        self.version_fn = version_fn
        self._q: queue.Queue = queue.Queue(maxsize=max(1, int(max_queue)))
        self._stop = threading.Event()
        self._error: BaseException | None = None
        self.scored: list[float] = []  # per-episode scores, arrival order
        self._scored_lock = threading.Lock()
        reg = telemetry.get_registry()
        self._m_scored = reg.counter(
            "relayrl_rlhf_scored_episodes_total",
            "completed generations scored and re-emitted")
        self._m_score_s = reg.histogram(
            "relayrl_rlhf_stage_seconds",
            "wall seconds per stage dispatch on the RLHF dataflow",
            labels={"stage": "score"})
        self._m_emit_s = reg.histogram(
            "relayrl_rlhf_stage_seconds",
            "wall seconds per stage dispatch on the RLHF dataflow",
            labels={"stage": "emit"})
        self._m_lag = reg.histogram(
            "relayrl_rlhf_lag_versions",
            "behavior version vs actor-held version at emission "
            "(tokens sampled N publishes behind the model they train)",
            buckets=LAG_BUCKETS)
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="rlhf-score")
        self._thread.start()

    def submit(self, lane: int, payload: bytes) -> None:
        # Bounded put in a re-checking loop, NOT one blocking put: if the
        # worker dies while the queue is full, nothing ever drains it —
        # a single q.put() would block the generation thread forever
        # (inside the host lock, wedging model swaps too) instead of
        # surfacing the worker's error.
        while True:
            if self._error is not None:
                raise RuntimeError("score stage died") from self._error
            if self._stop.is_set():
                raise RuntimeError("score stage is closed")
            try:
                self._q.put((lane, payload), timeout=0.5)
                return
            except queue.Full:
                continue

    def _gather(self):
        """One batch: block for the first episode, then linger for
        siblings up to ``batch``."""
        try:
            first = self._q.get(timeout=0.1)
        except queue.Empty:
            return []
        out = [first]
        deadline = time.monotonic() + self.linger_s
        while len(out) < self.batch:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            try:
                out.append(self._q.get(timeout=remaining))
            except queue.Empty:
                break
        return out

    def _score_batch(self, episodes):
        """(lane, records, tokens, gen_len, marker) rows → scores [n]."""
        n = len(episodes)
        batched = getattr(self.scorer, "score_batch_np", None)
        if batched is None:
            return [float(self.scorer.score_np(tok, self.prompt_len, gl))
                    for (_l, _r, tok, gl, _m) in episodes]
        width = self.batch if n <= self.batch else n
        tokens = np.stack(
            [episodes[i % n][2] for i in range(width)])  # pad: repeat rows
        gen_lens = np.asarray(
            [episodes[i % n][3] for i in range(width)], np.int32)
        scores = batched(tokens, self.prompt_len, gen_lens)
        return [float(s) for s in scores[:n]]

    def _loop(self) -> None:
        try:
            while not (self._stop.is_set() and self._q.empty()):
                batch = self._gather()
                if not batch:
                    continue
                from relayrl_tpu.telemetry import trace as trace_mod

                tracer = trace_mod.get_tracer()
                trace_id = tracer.sample_id("rlhf")
                t0_ns = time.monotonic_ns() if trace_id else 0
                t0 = time.monotonic()
                episodes = []
                for lane, payload in batch:
                    if is_columnar_frame(payload):
                        # Anakin-tier generation: one whole episode per
                        # frame, markers pre-folded. The decoded frame
                        # stands in for the record list; the marker slot
                        # is None (the terminal reward lives in r[-1]).
                        dt = parse_frame(payload)
                        tokens, gen_len = extract_generation_frame(
                            dt, self.prompt_len)
                        episodes.append((lane, dt, tokens, gen_len, None))
                    else:
                        records = deserialize_actions(payload)
                        tokens, gen_len, marker = extract_generation(
                            records, self.prompt_len)
                        episodes.append(
                            (lane, records, tokens, gen_len, marker))
                scores = self._score_batch(episodes)
                self._m_score_s.observe(time.monotonic() - t0)
                if trace_id:
                    t1_ns = time.monotonic_ns()
                    tracer.span("rlhf", trace_id, "score", t0_ns, t1_ns,
                                episodes=len(episodes))
                t1 = time.monotonic()
                held = (int(self.version_fn())
                        if self.version_fn is not None else None)
                for (lane, records, _tok, _gl, marker), score in zip(
                        episodes, scores):
                    if isinstance(records, DecodedTrajectory):
                        # Columnar patch: the marker is folded, so the
                        # score IS the terminal row's reward (the
                        # terminal record's own rew is always masked to
                        # 0 — "the reward rides the marker" — and
                        # update_reward REPLACES, so folded terminal =
                        # 0 + score). ``u`` stays untouched: u[-1]=0
                        # mirrors the per-record fold exactly.
                        r_col = np.array(records.columns["r"], copy=True)
                        r_col[-1] = r_col.dtype.type(score)
                        records.columns = dict(records.columns)
                        records.columns["r"] = r_col
                        if held is not None:
                            bvers = records.aux.get("bver")
                            if bvers is not None:
                                for bver in np.asarray(
                                        bvers).reshape(-1).tolist():
                                    self._m_lag.observe(
                                        max(0, held - int(bver)))
                        payload_out = encode_columnar_frame(records)
                    else:
                        if marker is not None:
                            marker.update_reward(float(score))
                        else:  # defensive: episode ended without a marker
                            records[-1].update_reward(
                                records[-1].rew + float(score))
                        if held is not None:
                            for r in records:
                                bver = (r.data or {}).get("bver")
                                if bver is not None:
                                    self._m_lag.observe(
                                        max(0, held - int(bver)))
                        payload_out = serialize_actions(records)
                    self.emit_fn(lane, payload_out)
                    self._m_scored.inc()
                    with self._scored_lock:
                        self.scored.append(float(score))
                self._m_emit_s.observe(time.monotonic() - t1)
                if trace_id:
                    tracer.span("rlhf", trace_id, "emit", t1_ns,
                                time.monotonic_ns(),
                                episodes=len(episodes))
        except BaseException as e:  # surfaced on the next submit/close
            self._error = e
            print(f"[rlhf] score stage died: {e!r}", flush=True)

    def scored_snapshot(self) -> list[float]:
        with self._scored_lock:
            return list(self.scored)

    def close(self, timeout_s: float = 30.0) -> None:
        """Drain-and-stop: everything submitted before close() is scored
        and emitted (the flush contract a final spool replay relies
        on)."""
        self._stop.set()
        self._thread.join(timeout=timeout_s)
        if self._error is not None:
            raise RuntimeError("score stage died") from self._error


class GenerationStage:
    """The generate stage: one batched policy dispatch per round across
    ``lanes`` TokenGen lanes (scorer=None — the decoupled mode; rewards
    are the score stage's job), stamping each record with the behavior
    version ``bver``. Works against anything exposing the batched
    actor-host surface (``request_for_actions`` / per-lane
    ``flag_last_action`` / ``version``): a raw
    :class:`~relayrl_tpu.runtime.vector_actor.VectorActorHost` (the
    bit-identity tests), a live :class:`~relayrl_tpu.runtime.agent.
    VectorAgent`, or the scheduler's remote-lane adapter."""

    def __init__(self, host, venv, seed: int | None = None):
        from relayrl_tpu import telemetry

        self.host = host
        self.venv = venv
        self.obs, _ = venv.reset(seed=seed)
        self.episodes_started = venv.num_envs
        self.episodes_done = 0
        self.tokens_generated = 0
        reg = telemetry.get_registry()
        self._m_tokens = reg.counter(
            "relayrl_rlhf_generated_tokens_total",
            "tokens generated (one per lane per batched dispatch)")
        self._m_gen_s = reg.histogram(
            "relayrl_rlhf_stage_seconds",
            "wall seconds per stage dispatch on the RLHF dataflow",
            labels={"stage": "generate"})

    def run_round(self) -> int:
        """One token per lane: dispatch, stamp ``bver``, step the envs,
        flag finished lanes (terminal reward 0.0 — the score stage owns
        it). Returns the number of episodes that completed."""
        from relayrl_tpu.runtime.agent import coerce_env_action

        t0 = time.monotonic()
        records = self.host.request_for_actions(self.obs)
        bver = np.int32(self.host.version)
        for r in records:
            # The version the batch's single params read served — the
            # V-trace lag evidence. Stamped before the episode's flush
            # (records live in the lane trajectory until the terminal
            # marker ships them).
            r.data["bver"] = bver
        actions = [coerce_env_action(r.act) for r in records]
        self.obs, _rews, terms, truncs, _infos = self.venv.step(actions)
        done = 0
        for lane in range(self.venv.num_envs):
            if terms[lane] or truncs[lane]:
                self.host.flag_last_action(lane, 0.0, terminated=True)
                done += 1
        self._m_tokens.inc(self.venv.num_envs)
        gen_dt = time.monotonic() - t0
        self._m_gen_s.observe(gen_dt)
        if done:
            # Trace draw at EPISODE granularity only (this round closed
            # at least one generation) — a per-token draw would churn
            # the sampling lock and, at rate 1.0, flood the flight
            # recorder with one span per token across all lanes.
            from relayrl_tpu.telemetry import trace as trace_mod

            tracer = trace_mod.get_tracer()
            if tracer.enabled:
                trace_id = tracer.sample_id("rlhf")
                if trace_id:
                    now_ns = time.monotonic_ns()
                    tracer.span("rlhf", trace_id, "generate",
                                now_ns - int(gen_dt * 1e9), now_ns,
                                lanes=self.venv.num_envs,
                                episodes=done)
        self.tokens_generated += self.venv.num_envs
        self.episodes_done += done
        self.episodes_started += done  # autoreset: a new one began
        return done


class FusedGenerationStage:
    """Anakin-tier generate stage (``rlhf.generation_tier: "anakin"``):
    generation happens INSIDE the fused scan — TokenGen runs as pure JAX
    in the ``lax.scan`` with the rolling-window carry, so one
    ``rollout()`` dispatch produces ``lanes × unroll_length`` tokens
    with zero per-token host round-trips. ``bver`` is stamped at unstack
    (``record_bver=True`` — the whole window is one model version by
    construction) and ``logp_a`` rides each record's aux as everywhere
    else, so the per-token behavior evidence the V-trace correction and
    the lag histogram read is identical to the vector tier's. Episodes
    still leave through the interceptor seam (withheld → scored →
    re-injected); this object only drives rollouts and keeps the pacing
    loop's accounting surface (``host``/``episodes_done``/
    ``run_round``/``tokens_generated``)."""

    def __init__(self, agent):
        from relayrl_tpu import telemetry

        self.agent = agent
        self.host = agent.host
        self.episodes_done = 0
        self.tokens_generated = 0
        reg = telemetry.get_registry()
        self._m_tokens = reg.counter(
            "relayrl_rlhf_generated_tokens_total",
            "tokens generated (one per lane per batched dispatch)")
        self._m_gen_s = reg.histogram(
            "relayrl_rlhf_stage_seconds",
            "wall seconds per stage dispatch on the RLHF dataflow",
            labels={"stage": "generate"})

    def run_round(self) -> int:
        """One fused window: ``lanes × unroll_length`` tokens in a
        single device dispatch. Returns completed episodes (TokenGen
        ends every episode as ``terminated``, so in-scan autoreset
        starts the next prompt without leaving the device)."""
        t0 = time.monotonic()
        stats = self.agent.rollout()
        self._m_tokens.inc(int(stats["steps"]))
        self._m_gen_s.observe(time.monotonic() - t0)
        self.tokens_generated += int(stats["steps"])
        done = int(stats["episodes"])
        self.episodes_done += done
        return done


class _RemoteLanes:
    """Thin-client generation tier: N ``RemoteActorClient`` lanes against
    the serving plane, adapted to the batched actor-host surface the
    GenerationStage drives. Sequence policies serve through the
    service's per-session window table (serving v2) — keep
    ``serving.max_sessions`` at or above the lane count so steady-state
    generation never cycles through eviction/resync.

    The N round-trips fire CONCURRENTLY (one worker per lane): serial
    requests would cost N x the round-trip per token AND present the
    service's size-or-linger batcher with batch-of-1 forever — in-flight
    overlap is exactly the concurrency the dynamic batching was built
    for. Each client has its own lock, so cross-client concurrency is
    safe; per-lane episode assembly stays on its lane's worker."""

    def __init__(self, clients):
        import concurrent.futures

        self.clients = clients
        self._pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=len(clients), thread_name_prefix="rlhf-remote")

    @property
    def version(self) -> int:
        return max(c.version for c in self.clients)

    def request_for_actions(self, obs, masks=None, rewards=None):
        futures = [self._pool.submit(c.request_for_action, obs[i])
                   for i, c in enumerate(self.clients)]
        return [f.result() for f in futures]

    def close(self) -> None:
        self._pool.shutdown(wait=True)

    def flag_last_action(self, lane: int, reward: float = 0.0,
                         truncated: bool = False, final_obs=None,
                         terminated: bool | None = None, final_mask=None):
        self.clients[lane].flag_last_action(
            reward, truncated=truncated, final_obs=final_obs,
            terminated=terminated, final_mask=final_mask)


class RlhfScheduler:
    """Wires the three stages against a live training server.

    ``server_type``/``addr_overrides`` point at the server exactly like
    an Agent's; the learner side (algorithm, ``learner.freeze``,
    V-trace knobs) is the server's config — this object is purely the
    actor-plane orchestrator. ``scorer`` overrides the config-resolved
    one (any object with ``score_np``/``score_batch_np``); ``rng_keys``
    feeds the vector host's per-lane key override (bit-identity locks).
    """

    def __init__(
        self,
        config_path: str | None = None,
        server_type: str = "zmq",
        seed: int = 0,
        identity: str | None = None,
        lanes: int | None = None,
        scorer=None,
        generation_tier: str | None = None,
        rng_keys=None,
        handshake_timeout_s: float = 60.0,
        **addr_overrides,
    ):
        from relayrl_tpu.config import ConfigLoader
        from relayrl_tpu.envs import SyncVectorEnv, TokenGenEnv

        self.config = ConfigLoader(None, config_path)
        p = self.config.get_rlhf_params()
        self.params = p
        self.lanes = int(lanes if lanes is not None else p["lanes"])
        self.tier = str(generation_tier or p["generation_tier"])
        self.prompt_len = p["prompt_len"]
        self.scorer = scorer if scorer is not None else self._make_scorer(p)

        # Env lanes run scorer-less: the terminal reward is the score
        # stage's to assign (the whole point of the decoupled dataflow).
        # The anakin tier has no host-side envs at all — TokenGen runs
        # as pure JAX inside the fused scan.
        if self.tier == "anakin":
            self.venv = None
        else:
            def env_fn():
                return TokenGenEnv(vocab_size=p["vocab_size"],
                                   prompt_len=p["prompt_len"],
                                   max_new_tokens=p["max_new_tokens"],
                                   scorer=None)

            self.venv = SyncVectorEnv([env_fn for _ in range(self.lanes)])

        if self.tier == "remote":
            from relayrl_tpu.runtime.inference import RemoteActorClient

            base = identity or f"rlhf-{seed}"
            clients = []
            for k in range(self.lanes):
                client = RemoteActorClient(
                    config_path=config_path, server_type=server_type,
                    seed=seed + k, identity=f"{base}.lane{k}",
                    handshake_timeout_s=handshake_timeout_s,
                    **addr_overrides)
                # Interpose the score stage on this lane's episode flow
                # (the VectorAgent seam, client-shaped): the original
                # sender becomes the stage's emit target.
                clients.append(client)
            self.agent = None
            self._clients = clients
            host = _RemoteLanes(clients)
            sends = [c.trajectory._on_send for c in clients]
            for k, c in enumerate(clients):
                c.trajectory._on_send = (
                    lambda payload, _k=k: self._withhold(_k, payload))
            self._emit = lambda lane, payload: sends[lane](payload)
            version_fn = lambda: host.version  # noqa: E731
        elif self.tier == "anakin":
            from relayrl_tpu.runtime.agent import VectorAgent

            # Fused generation: TokenGen-v0 inside the scan, whole
            # episodes shipped as columnar frames (the anakin default),
            # bver stamped at unstack. The interceptor seam is the SAME
            # one the vector tier uses — withheld episodes come back
            # through emit_lane with spool seqs assigned at emission, so
            # the at-least-once window only ever holds scored bytes.
            self.agent = VectorAgent(
                num_envs=self.lanes, server_type=server_type, seed=seed,
                identity=identity, host_mode="anakin",
                unroll_length=p["generation_unroll"],
                jax_env="TokenGen-v0",
                jax_env_kwargs={"vocab_size": p["vocab_size"],
                                "prompt_len": p["prompt_len"],
                                "max_new_tokens": p["max_new_tokens"]},
                record_bver=True,
                handshake_timeout_s=handshake_timeout_s,
                send_interceptor=self._withhold, rng_keys=rng_keys,
                config_path=config_path, **addr_overrides)
            self._clients = []
            host = self.agent.host
            self._emit = self.agent.emit_lane
            version_fn = lambda: self.agent.host.version  # noqa: E731
        else:
            from relayrl_tpu.runtime.agent import VectorAgent

            self.agent = VectorAgent(
                num_envs=self.lanes, server_type=server_type, seed=seed,
                identity=identity, host_mode="vector",
                handshake_timeout_s=handshake_timeout_s,
                send_interceptor=self._withhold, rng_keys=rng_keys,
                config_path=config_path, **addr_overrides)
            self._clients = []
            host = self.agent.host
            self._emit = self.agent.emit_lane
            version_fn = lambda: self.agent.host.version  # noqa: E731

        self.score_stage = ScoreStage(
            self.scorer, prompt_len=p["prompt_len"], emit_fn=self._emit,
            batch=p["score_batch"], max_queue=p["score_queue"],
            version_fn=version_fn)
        self.generation = (FusedGenerationStage(self.agent)
                           if self.tier == "anakin"
                           else GenerationStage(host, self.venv, seed=seed))

    def _make_scorer(self, p: dict):
        if p["scorer"] == "reward_model":
            return make_scorer(
                "reward_model", vocab_size=p["vocab_size"],
                context_len=p["prompt_len"] + p["max_new_tokens"],
                d_model=p["rm_d_model"], n_layers=p["rm_n_layers"],
                seed=p["rm_seed"])
        return make_scorer("programmatic", vocab_size=p["vocab_size"])

    def _withhold(self, lane: int, payload: bytes):
        self.score_stage.submit(lane, payload)
        return None  # the stage re-injects via emit after scoring

    # -- driving --
    def run(self, episodes: int, deadline_s: float = 300.0) -> dict:
        """Generate until ``episodes`` generations have been scored and
        emitted (or the deadline passes), pacing against the learner:
        once ``rlhf.max_episodes_per_version`` episodes completed under
        one held model version, generation waits (bounded by
        ``rlhf.pace_timeout_s``) for a newer swap before continuing — a
        fast actor host can outrun the learner 10-30x, and V-trace's
        clipped-rho correction tolerates bounded lag rather than making
        free throughput of unbounded lag. Returns run stats including
        the arrival-ordered score curve."""
        pace = int(self.params.get("max_episodes_per_version", 0))
        pace_timeout = float(self.params.get("pace_timeout_s", 5.0))
        deadline = time.monotonic() + deadline_s
        pace_version = self.generation.host.version
        pace_done = self.generation.episodes_done
        while (len(self.score_stage.scored_snapshot()) < episodes
               and time.monotonic() < deadline):
            held = self.generation.host.version
            if held != pace_version:
                pace_version, pace_done = held, self.generation.episodes_done
            elif (pace and
                  self.generation.episodes_done - pace_done >= pace):
                # Staleness bound hit: wait (briefly) for a newer swap.
                # A timeout WITHOUT a swap falls through to exactly one
                # liveness round and re-enters this wait — the anchor
                # does NOT advance, so a stalled learner gets a trickle
                # of fresh episodes (the crash-drill heartbeat) instead
                # of an unbounded pile-up of stale ones.
                wait_until = min(deadline,
                                 time.monotonic() + pace_timeout)
                while (self.generation.host.version == pace_version
                       and time.monotonic() < wait_until):
                    time.sleep(0.005)
                held = self.generation.host.version
                if held != pace_version:
                    pace_version = held
                    pace_done = self.generation.episodes_done
            self.generation.run_round()
        scores = self.score_stage.scored_snapshot()
        return {
            "episodes_scored": len(scores),
            "scores": scores,
            "tokens_generated": self.generation.tokens_generated,
        }

    def flush(self, timeout_s: float = 30.0) -> None:
        """Finish any open lane episodes are NOT flushed (mid-generation
        tokens stay local); everything already terminal is scored and
        emitted."""
        self.score_stage.close(timeout_s=timeout_s)

    def close(self) -> None:
        try:
            self.score_stage.close()
        finally:
            if self.agent is not None:
                self.agent.disable_agent()
            host = self.generation.host
            if hasattr(host, "close"):
                host.close()  # remote tier: drain the lane worker pool
            for c in self._clients:
                c.disable_agent()
