"""Driver ``rollout``: the fused actor tier alone, at a rate the chip sets.

This process holds the chip and runs ONE ``AnakinActorHost``
(``relayrl_tpu/runtime/anakin.py``): ``lanes`` on-device environments and a
sequence policy stepped together, ``unroll_length`` env steps a lane in one
dispatch of ``jit(vmap(scan(env.step . policy.step_window)))``, then one
``device_get`` of the window and the host's columnar emit. Closed loop: the
next ``host.rollout()`` is called as soon as the last returns. No learner, no
transport, no model swap: ``on_send`` is this file's :class:`Sink`, which
keeps each payload's lane and bytes and does nothing else inside the window.

The policy's parameters are made on the device from ``--seed`` in one jitted
call, float32 as the learner publishes them; the arch is the configuration's
(``reference.program_kwargs``) with the compute type its ``program_config``
names, built as ``algorithms/impala.py`` builds it.

``correct`` is decided from what the timed window itself emitted, after it
has closed (:func:`account`, :func:`compare_with_reference`): every step
the window dispatched is read back from the sink's frames through the
program's own columnar decoder, and for ``reference_lanes`` lanes every
emitted ``logp_a`` and ``v`` of the window is held against the plain
float32 reference run once over the observations that lane emitted since its
episode began. The traffic file has the readings the limits stand on.
"""

from __future__ import annotations

import sys
import time

from benchmark import harness
from benchmark.flops_rollout import rollout_flops_per_step


class Sink:
    """``on_send(lane, payload)``: keeps what the host ships."""

    def __init__(self):
        self.payloads: list[tuple[int, bytes]] = []

    def __call__(self, lane: int, payload) -> None:
        self.payloads.append((int(lane), bytes(payload)))


def policy_arch(cfg: dict, program_kwargs: dict) -> dict:
    """The arch ``IMPALA._setup`` builds for this configuration: kind, the
    two ends' sizes, a critic, the learner's compute type, then the
    program's own pass-through of its hyper-parameter names."""
    from relayrl_tpu.models.base import apply_arch_overrides

    arch = {"kind": program_kwargs["model_kind"],
            "obs_dim": int(cfg["obs_dim"]), "act_dim": int(cfg["act_dim"]),
            "has_critic": True,
            "precision": str(cfg["program_config"]["learner"]["precision"])}
    return apply_arch_overrides(arch, program_kwargs)


def expected_emitted_steps(dispatched: int, horizon: int, chunk: int) -> int:
    """Steps of one lane that have left the host as frames after it was
    dispatched ``dispatched`` steps since it was built: whole episodes at
    once (an episode's end flushes), and of the running episode every whole
    ``chunk`` but the one a next step has not yet pushed out
    (``AnakinActorHost._append_segment`` flushes a full chunk when the
    step after it arrives). ``horizon`` is a multiple of ``chunk``."""
    episodes, rest = divmod(dispatched, horizon)
    return episodes * horizon + (chunk * ((rest - 1) // chunk) if rest else 0)


def decode_sink(payloads, lanes: int) -> list[list]:
    """The sink's payloads as ``DecodedTrajectory`` frames a lane, in the
    order they were shipped, through the program's own decoder."""
    from relayrl_tpu.types.columnar import parse_frame

    frames: list[list] = [[] for _ in range(lanes)]
    for lane, payload in payloads:
        frames[lane].append(parse_frame(payload))
    return frames


def lane_steps(lane_frames: list, horizon: int) -> dict:
    """One lane's frames end to end as columns, each step with the index
    it has among the steps the lane was dispatched since the host was
    built. The index is read from the environment's phase feature
    (``obs[-1] = t / horizon``): a frame starts at the first index not yet
    held whose phase is its first row's, so a frame that never came leaves
    a gap and does not shift what follows. ``in_order``: no gap, no repeat,
    and every frame's rows run on by one."""
    import numpy as np

    if not lane_frames:
        empty = np.zeros(0)
        return {"idx": empty.astype(np.int64), "obs": empty, "act": empty,
                "logp_a": empty, "v": empty, "in_order": True}
    idx, pos, in_order = [], 0, True
    for f in lane_frames:
        phase = np.rint(f.columns["o"][:, -1].astype(np.float64)
                        * horizon).astype(np.int64)
        start = pos + int(phase[0] - pos) % horizon
        rows = start + np.arange(len(phase))
        in_order &= start == pos and np.array_equal(phase, rows % horizon)
        idx.append(rows)
        pos = start + len(phase)
    return {"idx": np.concatenate(idx), "in_order": bool(in_order),
            "obs": np.concatenate([f.columns["o"] for f in lane_frames]),
            "act": np.concatenate([f.columns["a"] for f in lane_frames]),
            "logp_a": np.concatenate([f.aux["logp_a"] for f in lane_frames]),
            "v": np.concatenate([f.aux["v"] for f in lane_frames])}


def account(frames: list[list], dispatched: int, window: tuple[int, int],
            horizon: int, chunk: int, act_dim: int) -> dict:
    """What the sink's frames hold, lane by lane, against what the host
    was dispatched. ``dispatched``: steps a lane since the host was built;
    ``window``: the half-open range of those steps the measured window
    dispatched. Every lane's frames must be ``chunk`` steps each, in order
    (:func:`lane_steps`) and ``expected_emitted_steps`` in all, and every
    step of the window must be among them, finite, its action one of
    ``act_dim`` and its log-probability not above 0."""
    import numpy as np

    want = expected_emitted_steps(dispatched, horizon, chunk)
    p0, p1 = window
    out = {"lanes_short": 0, "lanes_out_of_order": 0, "frames_off_size": 0,
           "window_steps_missing": 0, "nonfinite_steps": 0,
           "actions_out_of_range": 0, "logp_positive": 0,
           "frames": sum(len(f) for f in frames), "steps_wanted": want}
    for lane_frames in frames:
        got = lane_steps(lane_frames, horizon)
        seen = (got["idx"] >= p0) & (got["idx"] < p1)
        act, logp, v = got["act"][seen], got["logp_a"][seen], got["v"][seen]
        out["frames_off_size"] += sum(f.n_steps != chunk
                                      for f in lane_frames)
        out["lanes_short"] += len(got["idx"]) != want
        out["lanes_out_of_order"] += not got["in_order"]
        out["window_steps_missing"] += (p1 - p0) - len(
            np.unique(got["idx"][seen]))
        out["nonfinite_steps"] += int(np.sum(
            ~(np.isfinite(logp) & np.isfinite(v))))
        out["actions_out_of_range"] += int(np.sum((act < 0)
                                                  | (act >= act_dim)))
        out["logp_positive"] += int(np.sum(logp > 0))
    return out


def lane_episodes(lane_frames: list, horizon: int, window: tuple[int, int]
                  ) -> list[dict]:
    """The episodes of one lane that hold steps of ``window``: for each the
    rows the lane emitted from the episode's first step on, each at its
    place in the episode, and which of them lie in the window."""
    import numpy as np

    got = lane_steps(lane_frames, horizon)
    p0, p1 = window
    episodes = []
    for start in range((p0 // horizon) * horizon, p1, horizon):
        mine = (got["idx"] >= start) & (got["idx"] < start + horizon)
        at = got["idx"][mine] - start
        episodes.append({
            "at": at, "obs": got["obs"][mine], "act": got["act"][mine],
            "logp_a": got["logp_a"][mine], "v": got["v"][mine],
            "in_window": (at + start >= p0) & (at + start < p1)})
    return episodes


def episode_obs(ep: dict, width: int):
    """An episode's emitted observations at their places in one
    ``[1, width, obs_dim]`` array; rows the lane has not emitted are zeros,
    which no earlier row attends."""
    import numpy as np

    obs = np.zeros((1, width, ep["obs"].shape[-1]), np.float32)
    obs[0, ep["at"]] = ep["obs"]
    return obs


def compare_with_reference(forward, params, cfg: dict, episodes: list[dict],
                           width: int) -> dict:
    """Emitted ``logp_a`` / ``v`` of the episodes' window rows against
    ``forward`` (the plain reference: float32, "highest"; causal, so one
    pass over an episode's observations gives every step). Every pass has
    one shape (:func:`episode_obs`; ``width``: the lanes' window), so a warm
    cache serves it whatever the window's length was. Judged as
    ``harness.reference_check`` judges: the widest difference over max(1,
    the range of the reference's log-probabilities) and over max(1,
    max |v|)."""
    import numpy as np

    err_logp = err_v = v_scale = 0.0
    lo, hi, steps = np.inf, -np.inf, 0
    for ep in episodes:
        mine = ep["in_window"]
        rows = ep["at"][mine]
        if not len(rows):
            continue
        logp_ref, v_ref = (np.asarray(x)[0] for x in forward(
            params, episode_obs(ep, width), cfg))
        err_logp = max(err_logp, float(np.max(np.abs(
            ep["logp_a"][mine] - logp_ref[rows, ep["act"][mine]]))))
        err_v = max(err_v, float(np.max(np.abs(ep["v"][mine]
                                               - v_ref[rows]))))
        lo = min(lo, float(np.min(logp_ref[rows])))
        hi = max(hi, float(np.max(logp_ref[rows])))
        v_scale = max(v_scale, float(np.max(np.abs(v_ref[rows]))))
        steps += len(rows)
    spread = hi - lo if steps else 0.0
    return {"max_abs_dlogp": err_logp, "max_abs_dv": err_v,
            "logp_range": spread, "v_max_abs": v_scale,
            "rel_dlogp": err_logp / max(1.0, spread),
            "rel_dv": err_v / max(1.0, v_scale), "steps_compared": steps}


def _dispatch(run, host, log: list) -> None:
    with run.spans.span("rollout"):
        out = host.rollout()
    log.append((out["dispatch_s"], out["encode_s"]))


def _dispatch_for(run, host, log: list, seconds: float) -> None:
    """Closed loop for ``seconds``, then to the end of the dispatch that is
    running: both edges lie on a dispatch boundary."""
    t_end = time.monotonic() + seconds
    while time.monotonic() < t_end:
        _dispatch(run, host, log)


def reference_lanes(seed: int, lanes: int, n: int) -> list[int]:
    """The lanes whose emitted steps go through the reference: a sample
    drawn from the seed (every lane runs the same length today, so the
    longest is in it whichever is drawn)."""
    import numpy as np

    return sorted(np.random.default_rng(seed).choice(
        lanes, size=n, replace=False).tolist())


def roll(run: harness.Run) -> dict:
    """Set-up, the traced sub-window, the measured window and the cool
    dispatches after it. Fills the run's rate and counters and returns what
    :func:`judge` reads: the host's parameters, the sink's frames decoded,
    the window's range of a lane's steps and the steps a lane was
    dispatched in all."""
    with run.phase("import"):
        import jax
        import numpy as np

        from relayrl_tpu.models import build_policy
        from relayrl_tpu.runtime.anakin import AnakinActorHost
        from relayrl_tpu.types.model_bundle import ModelBundle

    cfg, tr = run.config, run.traffic
    lanes, unroll = int(tr["lanes"]), int(tr["unroll_length"])
    chunk = int(tr["max_traj_length"])
    horizon = int(tr["env_kwargs"]["horizon"])
    if horizon % chunk or chunk % unroll:
        raise harness.Refused(
            f"horizon {horizon}, max_traj_length {chunk}, unroll_length "
            f"{unroll}: each must be a multiple of the next")
    sink = Sink()
    with run.phase("build"):
        arch = policy_arch(cfg, run.reference.program_kwargs(cfg))
        policy = build_policy(arch)
        params = jax.block_until_ready(jax.jit(policy.init_params)(
            jax.random.PRNGKey(run.program_seed)))
        host = AnakinActorHost(
            ModelBundle(version=0, arch=arch, params=params), tr["env"],
            num_envs=lanes, unroll_length=unroll, max_traj_length=chunk,
            on_send=sink, seed=run.program_seed,
            columnar_wire=bool(tr["columnar_wire"]),
            async_emit=bool(tr["async_emit"]),
            emit_coalesce_frames=int(tr["emit_coalesce_frames"]),
            window_size=int(tr["window_size"]),
            record_bver=bool(tr["record_bver"]), **tr["env_kwargs"])
    # bytes the window brings back a dispatch: counted where the host's
    # ``rollout`` looks its jitted window producer up, on the instance
    d2h_bytes: list[int] = []
    produce = getattr(host, "_rollout_fn", None)

    def counted(params, explore, carry):
        carry, window = produce(params, explore, carry)
        d2h_bytes.append(sum(int(x.nbytes) for x in
                             jax.tree_util.tree_leaves(window)))
        return carry, window

    if produce is not None:
        host._rollout_fn = counted

    log: list = []  # (dispatch_s, encode_s) a dispatch, in order
    try:
        with run.phase("warmup"):
            for _ in range(int(tr["warm_dispatches"])):
                _dispatch(run, host, log)
        if run.trace:
            with run.traced():
                _dispatch_for(run, host, log, float(tr["trace_seconds"]))
        run.spans.reset()

        n0 = len(log)
        t0 = run.begin_window()
        _dispatch_for(run, host, log, run.seconds)
        run.end_window(t0)
        n1 = len(log)

        # -- after the window: push its last rows out of the host --------
        for _ in range(int(tr["cool_dispatches"])):
            _dispatch(run, host, log)
        try:
            emit_error = "" if host.flush_emits() else "emits not drained"
        except RuntimeError as e:
            emit_error = repr(e)
    finally:
        host.close()

    dispatches = n1 - n0
    steps = lanes * unroll * dispatches
    window = (unroll * n0, unroll * n1)  # a lane's steps, half open
    run.updates, run.samples, run.attempted = dispatches, steps, steps
    run.e2e["rollout_steps_per_s"] = steps / run.window_s
    # keys the new row of each window step sees: its place in its episode
    mean_keys = float(np.mean(np.arange(*window) % horizon + 1))
    run.counters.update(
        rollout_dispatches=dispatches,
        rollout_dispatch_s=sum(d for d, _ in log[n0:n1]),
        rollout_emit_s=sum(e for _, e in log[n0:n1]),
        d2h_bytes_per_dispatch=(sum(d2h_bytes[n0:n1]) / dispatches
                                if len(d2h_bytes) == len(log) else 0.0),
        # a configuration with other keys than GPT-2's counts its own step
        rollout_flops_per_step=getattr(
            run.reference, "rollout_flops_per_step",
            rollout_flops_per_step)(cfg, mean_keys),
        rollout_mean_keys=mean_keys)
    run.notes["rollout"] = {
        "dispatches": dispatches, "lane_steps_window": list(window),
        "lane_steps_dispatched": unroll * len(log),
        "dispatch_ms": [round(1e3 * d, 1) for d, _ in log[n0:n1]],
        "emit_ms": [round(1e3 * e, 1) for _, e in log[n0:n1]],
        "frame_bytes": (sum(len(p) for _, p in sink.payloads)
                        / max(1, len(sink.payloads)))}
    return {"params": host.params, "emit_error": emit_error,
            "frames": decode_sink(sink.payloads, lanes), "window": window,
            "lane_steps_dispatched": unroll * len(log)}


def judge(run: harness.Run, rolled: dict) -> None:
    """Everything ``correct`` depends on, outside the window, from what the
    window emitted."""
    import numpy as np

    cfg, tr = run.config, run.traffic
    lanes, horizon = int(tr["lanes"]), int(tr["env_kwargs"]["horizon"])
    frames, window = rolled["frames"], rolled["window"]
    dispatches = run.updates
    seen = account(frames, rolled["lane_steps_dispatched"], window, horizon,
                   int(tr["max_traj_length"]), int(cfg["act_dim"]))
    run.failed = seen["window_steps_missing"] + seen["nonfinite_steps"]
    run.notes["rollout"].update(seen)
    run.check("enough_dispatches", dispatches >= int(tr["min_dispatches"]),
              f"{dispatches} dispatches in the window, "
              f"{tr['min_dispatches']} wanted")
    if not run.rehearsal:
        run.check("params_on_tpu", harness.on_tpu(rolled["params"]))
    run.check("no_emit_error", not rolled["emit_error"],
              rolled["emit_error"])
    accounted = not any(seen[k] for k in (
        "lanes_short", "lanes_out_of_order", "frames_off_size",
        "window_steps_missing"))
    run.check("every_step_accounted", accounted, str(seen))
    in_range = not any(seen[k] for k in (
        "nonfinite_steps", "actions_out_of_range", "logp_positive"))
    run.check("outputs_in_range", in_range, str(seen))

    picked = reference_lanes(run.seed, lanes, int(tr["reference_lanes"]))
    episodes = [ep for lane in picked
                for ep in lane_episodes(frames[lane], horizon, window)]
    t_ref = time.monotonic()
    ref = compare_with_reference(run.reference.forward, rolled["params"],
                                 cfg, episodes, int(tr["window_size"]))
    tol = tr["tolerance"]
    ref.update(lanes=picked, seconds=time.monotonic() - t_ref,
               tolerance={k: tol[k] for k in ("logp_rel", "value_rel")})
    run.notes["reference"] = ref
    wanted = len(picked) * (window[1] - window[0])
    run.check("reference",
              bool(np.isfinite(ref["rel_dlogp"])
                   and np.isfinite(ref["rel_dv"])
                   and ref["rel_dlogp"] <= tol["logp_rel"]
                   and ref["rel_dv"] <= tol["value_rel"]
                   and ref["steps_compared"] == wanted
                   and ref["logp_range"] > 0), str(ref))
    harness.say(f"{dispatches} dispatches of {lanes} x "
                f"{tr['unroll_length']} in {run.window_s:.3f} s; "
                f"{seen['frames']} frames read back; reference "
                f"{ref['steps_compared']} steps in {ref['seconds']:.2f} s")
    # each number compared beside its limit: last in the result line and
    # last on standard error
    run.notes["compared"] = {
        "rel_dlogp": [ref["rel_dlogp"], tol["logp_rel"]],
        "rel_dv": [ref["rel_dv"], tol["value_rel"]],
        "compiles_in_window": [run.window_compile_requests, 0],
        "reference_steps_short": [wanted - ref["steps_compared"], 0],
        "window_steps_missing": [seen["window_steps_missing"], 0],
        "lanes_short": [seen["lanes_short"], 0],
        "lanes_out_of_order": [seen["lanes_out_of_order"], 0],
        "frames_off_size": [seen["frames_off_size"], 0],
        "nonfinite_steps": [seen["nonfinite_steps"], 0],
        "actions_out_of_range": [seen["actions_out_of_range"], 0],
        "logp_positive": [seen["logp_positive"], 0]}
    for name, (value, limit) in run.notes["compared"].items():
        print(f"compared {name} {value} limit {limit}", file=sys.stderr,
              flush=True)


def drive(run: harness.Run) -> None:
    judge(run, roll(run))
