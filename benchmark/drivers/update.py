"""Driver ``update``: decoded trajectories from a seeded pool go through
exactly the calls ``server._process_one`` makes for each one —
``accumulate`` -> (when a batch is full) ``stage_batch`` ->
``train_on_batch`` -> the in-flight window — in one thread, closed loop.
Bypasses transport, decode and publish; the epoch log the server defers
behind the fence is not written.
"""

from __future__ import annotations

import time

from benchmark import harness, traffic_gen
from benchmark.instrument import LearnerProbe


def _feed(algo, pool, i: int) -> tuple[int, bool]:
    """One trajectory through the learner's calls; returns the next index
    and whether this one completed a batch (an update was dispatched)."""
    batch = algo.accumulate(pool[i % len(pool)])
    if batch is not None:
        algo.train_on_batch(algo.stage_batch(batch))
    return i + 1, batch is not None


def _feed_for(run, algo, pool, i: int, seconds: float) -> int:
    """Feed for ``seconds``, then on to the next update boundary, then
    fence everything: the window holds whole updates only, so its rate is
    not quantised by an update cut in half at the end (one update is up to
    1.4% of a 20 s window here)."""
    t_end = time.monotonic() + seconds
    dispatched = False
    while time.monotonic() < t_end or not dispatched:
        i, dispatched = _feed(algo, pool, i)
    with run.spans.span("drain"):
        algo.inflight.drain()
    return i


def drive(run: harness.Run) -> None:
    with run.phase("import"):
        import jax
        import numpy as np

        from relayrl_tpu.algorithms import build_algorithm

    cfg, tr = run.config, run.traffic
    steps, per_update = int(tr["traj_len"]), int(tr["traj_per_update"])
    with run.phase("build"):
        config_path = harness.write_program_config(
            run, {"max_traj_length": steps})
        algo = build_algorithm(
            cfg["algorithm"]["name"], obs_dim=int(cfg["obs_dim"]),
            act_dim=int(cfg["act_dim"]), config_path=config_path,
            env_dir=run.run_dir, traj_per_epoch=per_update,
            bucket_lengths=[steps], seed=run.program_seed, seed_salt=0,
            **cfg["algorithm"]["hyperparams"],
            **run.reference.program_kwargs(cfg))
        jax.block_until_ready(algo.state.params)
    with run.phase("traffic_pool"):
        pool = traffic_gen.decoded_pool(cfg, tr, run.seed)
    probe = LearnerProbe(run, algo)
    run.train_flops_per_sample = run.reference.train_flops_per_sample(
        cfg, steps)
    before = harness.tree_checksum(
        jax.tree_util.tree_leaves(algo.state.params)[0])

    i = 0
    with run.phase("warmup"):
        while algo.inflight.dispatch_count < int(tr["warm_updates"]):
            i, _ = _feed(algo, pool, i)
        algo.inflight.drain()
        warm_loss = float(probe.last_metrics["LossTotal"])

    if run.trace:
        with run.traced():
            i = _feed_for(run, algo, pool, i, float(tr["trace_seconds"]))
        run.spans.reset()

    m0 = probe.mark()
    t0 = run.begin_window()
    i = _feed_for(run, algo, pool, i, run.seconds)
    run.end_window(t0)
    m1 = probe.mark()
    probe.fill(run, m0, m1)

    run.attempted = i  # trajectories offered, warm-up included
    run.failed = int(algo.dropped_nonfinite)
    run.train_rate = run.samples / run.window_s
    run.e2e["train_samples_per_s"] = run.train_rate
    stamps = [t for _v, t in probe.dispatched if t >= t0]
    run.notes["update_interval_ms"] = [
        round(1e3 * (b - a)) for a, b in zip(stamps, stamps[1:])]

    # -- correctness, outside the window ---------------------------------
    last_loss = float(probe.last_metrics["LossTotal"])
    run.check("finite_losses", bool(np.isfinite(warm_loss)
                                    and np.isfinite(last_loss)),
              f"LossTotal {warm_loss} .. {last_loss}")
    after = harness.tree_checksum(
        jax.tree_util.tree_leaves(algo.state.params)[0])
    run.check("params_changed", before != after)
    if not run.rehearsal:
        run.check("params_on_tpu", harness.on_tpu(algo.state.params))
    run.check("all_fenced", algo.inflight.pending == 0
              and run.updates == m1["dispatched"] - m0["dispatched"])
    want = cfg.get("expect_attention_backend")
    if want and not run.rehearsal:
        backends = dict(algo.policy.attention_backends or {})
        key = (steps, int(cfg["n_embd"]) // int(cfg["n_head"]), "bfloat16")
        run.notes["attention_backends"] = {str(k): v
                                           for k, v in backends.items()}
        staged = algo.stage_batch(
            algo.mh_zero_batch(per_update, steps))
        text = algo._update.lower(algo.state, staged).compile().as_text()
        n_mosaic = text.count("tpu_custom_call")
        run.notes["mosaic_calls_in_update"] = n_mosaic
        run.check("attention_backend",
                  backends.get(key) == want and n_mosaic > 0,
                  f"{key} -> {backends.get(key)!r}, {n_mosaic} Mosaic calls")
    harness.reference_check(
        run, algo.policy, algo.state.params,
        traffic_gen.obs_sample(cfg, int(tr["reference_sequences"]), steps,
                               run.seed))
