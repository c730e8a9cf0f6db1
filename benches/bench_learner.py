"""Learner update throughput per algorithm (steps/s of the jitted update).

The reference publishes no learner numbers (BASELINE.md); its learner is a
single serialized stdio pipe into CPU torch. This bench times each
algorithm's pure jitted update on fixed batches — the number that scales
with chips — and, for the three flagship model families (MLP,
transformer-flash, CNN-pixel), reports MFU from analytic matmul/conv FLOP
counts against the chip's peak bf16 rate on a TPU (the perf evidence must
cover the non-MLP families; a CPU run prints rates and no utilization).
Runs on CPU by default; RELAYRL_BENCH_TPU=1 to target the real chip.
"""

import os
import sys

import numpy as np

from common import emit, quick, setup_platform, time_chained

setup_platform()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

ON_TPU = os.environ.get("RELAYRL_BENCH_TPU") == "1"

# --profile=DIR (or RELAYRL_BENCH_PROFILE=DIR): capture one jax.profiler
# trace per benched family under DIR before timing starts.
PROFILE_DIR = os.environ.get("RELAYRL_BENCH_PROFILE", "")
for _arg in list(sys.argv[1:]):
    if _arg.startswith("--profile="):
        PROFILE_DIR = _arg.split("=", 1)[1]
        sys.argv.remove(_arg)


def chip_peak_flops():
    from bench import _chip_peak_flops  # repo root, on sys.path via common

    return _chip_peak_flops(jax.devices()[0].device_kind)


# -- analytic FLOPs per jitted update (matmul/conv terms only; elementwise
#    and V-trace scans are noise next to them). IMPALA's update runs one
#    policy.evaluate inside the fused loss, so fwd+bwd ~= 3x fwd. --

def mlp_fwd_flops(n_tokens, obs, act, hidden):
    dims = [obs] + list(hidden)
    trunk = sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))
    # mlp family: separate pi/vf trunks, both live in evaluate()
    return n_tokens * (2 * trunk + 2 * hidden[-1] * (act + 1))


def transformer_fwd_flops(n_tokens, seq_len, obs, act, d_model, n_layers,
                          ffn_mult=4):
    # per token per layer: QKVO projections 8 d^2 + MLP 2*(2 d * ffn d)
    # + causal attention matmuls ~2 d T (QK^T and AV over ~T/2 keys each)
    per_layer = (8 * d_model * d_model
                 + 4 * ffn_mult * d_model * d_model
                 + 2 * d_model * seq_len)
    embed_heads = 2 * obs * d_model + 2 * d_model * (act + 1)
    return n_tokens * (n_layers * per_layer + embed_heads)


def cnn_fwd_flops(n_frames, obs_shape, conv_spec, dense, act):
    h, w, c = obs_shape
    per_frame = 0
    for feat, kern, stride in conv_spec:
        h = (h - kern) // stride + 1
        w = (w - kern) // stride + 1
        per_frame += 2 * h * w * feat * (kern * kern * c)
        c = feat
    per_frame += 2 * (h * w * c) * dense + 2 * dense * (act + 1)
    return n_frames * per_frame


def onpolicy_batch(B, T, obs_dim, act_dim, rng):
    return {
        "obs": rng.standard_normal((B, T, obs_dim)).astype(np.float32),
        "act": rng.integers(0, act_dim, (B, T)).astype(np.int32),
        "act_mask": np.ones((B, T, act_dim), np.float32),
        "rew": rng.standard_normal((B, T)).astype(np.float32),
        "val": np.zeros((B, T), np.float32),
        "logp": np.full((B, T), -1.0, np.float32),
        "valid": np.ones((B, T), np.float32),
        "last_val": np.zeros((B,), np.float32),
    }


def offpolicy_batch(B, obs_dim, act_dim, discrete, rng):
    return {
        "obs": rng.standard_normal((B, obs_dim)).astype(np.float32),
        "act": (rng.integers(0, act_dim, B).astype(np.int32) if discrete
                else rng.uniform(-1, 1, (B, act_dim)).astype(np.float32)),
        "rew": rng.standard_normal(B).astype(np.float32),
        "obs2": rng.standard_normal((B, obs_dim)).astype(np.float32),
        "mask2": np.ones((B, act_dim), np.float32),
        "done": (rng.random(B) < 0.05).astype(np.float32),
    }


def bench_algo(name, make_state_update, batch, flops_per_update=None,
               detail=None, trials=None, updates_per_call=1):
    state, update = make_state_update()
    # donate_argnums=0: the production jit config (every algorithms/*.py
    # update donates its state), so the recorded updates/s measures the
    # in-place-buffer path the server actually runs (jaxlint JAX05).
    # Each consumer below hands the chain a fresh copy of `state` —
    # donation invalidates the caller's buffers after the first call.
    jitted = jax.jit(update, donate_argnums=0)

    def fresh_state():
        return jax.tree.map(
            lambda x: jnp.copy(x) if isinstance(x, jax.Array) else x, state)

    device_batch = {k: jnp.asarray(v) for k, v in batch.items()}
    if PROFILE_DIR:
        # One traced update per family under --profile=DIR: the
        # jax.profiler trace (TensorBoard profile plugin / perfetto)
        # shows where the update's time goes on the chip — the tracing
        # tier SURVEY §5.1 maps tokio-console/flamegraph to.
        from relayrl_tpu.utils.profiling import trace

        def run_once():
            out = jitted(fresh_state(), device_batch)
            # Host readback of the output: the trace window cannot
            # close before the device work has run.
            float(np.asarray(jax.tree.leaves(out)[0]).reshape(-1)[0])

        run_once()  # compile OUTSIDE the trace window
        fam = (detail or {}).get("family", name).replace("/", "_")
        with trace(os.path.join(PROFILE_DIR, f"{name}_{fam}")):
            run_once()  # steady-state device step only
    # Multiple trials with the raw spread recorded: a single number is
    # not comparable across runs without its variance. Canonical value =
    # best trial.
    trials = trials if trials is not None else (1 if quick() else 3)
    dts = [time_chained(lambda s: jitted(s, device_batch), fresh_state(),
                        iters=10 if quick() else 30)
           for _ in range(trials)]
    dt = min(dts)
    k = updates_per_call  # dispatch fusion: one call = k updates
    config = {"algorithm": name, "platform": jax.default_backend(),
              **(detail or {})}
    if trials > 1:
        config["trials_updates_per_sec"] = [round(k / d, 2) for d in dts]
    if flops_per_update:
        config["analytic_flops_per_update"] = float(flops_per_update)
        if jax.default_backend() == "tpu":  # a utilization is a chip number
            config["mfu"] = round(
                k * flops_per_update / dt / chip_peak_flops(), 4)
    emit("learner_update", config, k / dt, "updates/s")


def _pipeline_episode(n, obs_dim, act_dim, seed):
    from relayrl_tpu.types.action import ActionRecord

    rng = np.random.default_rng(seed)
    return [ActionRecord(
        obs=rng.standard_normal(obs_dim).astype(np.float32),
        act=np.int64(rng.integers(act_dim)), rew=float(rng.random()),
        data={"logp_a": np.float32(-0.69), "v": np.float32(0.0)},
        done=(i == n - 1)) for i in range(n)]


def bench_pipeline():
    """Learner-thread blocked time per epoch: the synchronous chain
    (fence every update + gather/serialize the publish inline) vs the
    pipelined hot path (bounded in-flight dispatch window, latest-wins
    publisher thread, device prefetch). Same algorithm, same trajectory
    stream — the learning math is identical (tests/test_learner_pipeline
    proves bit-identical params); only where the host waits moves."""
    import tempfile
    import time

    from relayrl_tpu.algorithms import build_algorithm
    from relayrl_tpu.runtime.pipeline import ModelPublisher

    obs_dim, act_dim, tpe = 16, 4, 8
    epochs = 8 if quick() else 24
    episodes = [_pipeline_episode(48, obs_dim, act_dim, seed=s)
                for s in range(epochs * tpe)]

    def run(mode):
        algo = build_algorithm(
            "REINFORCE", obs_dim=obs_dim, act_dim=act_dim,
            traj_per_epoch=tpe, hidden_sizes=[64, 64], seed_salt=0,
            with_vf_baseline=True,
            max_inflight_updates=0 if mode == "sync" else 2,
            logger_kwargs={"output_dir": tempfile.mkdtemp()})
        algo.warmup()
        publisher = None
        if mode == "pipelined":
            publisher = ModelPublisher(lambda s: s.to_bundle().to_bytes())
        publish_wait = 0.0
        t_loop = time.monotonic()
        for ep in episodes:
            batch = algo.accumulate(ep)
            if batch is None:
                continue
            if mode == "pipelined":
                algo.train_on_batch(algo.stage_batch(batch))
                publisher.submit(algo.snapshot_for_publish())
            else:
                algo.train_on_batch(batch)  # window 0: fenced at dispatch
                t0 = time.monotonic()
                algo.bundle().to_bytes()    # inline gather + serialize
                publish_wait += time.monotonic() - t0
        loop_s = time.monotonic() - t_loop  # learner-thread wall time
        algo.inflight.drain()               # fence stragglers (outside loop)
        if publisher is not None:
            publisher.drain(timeout=60)
            publisher.stop()
        blocked = algo.inflight.device_wait_s + publish_wait
        return blocked, loop_s

    for mode in ("sync", "pipelined"):
        blocked, loop_s = run(mode)
        emit("learner_pipeline",
             {"algorithm": "REINFORCE", "mode": mode, "epochs": epochs,
              "traj_per_epoch": tpe, "obs_dim": obs_dim, "act_dim": act_dim,
              "hidden_sizes": [64, 64],
              "learner_thread_s_per_epoch": round(loop_s / epochs, 6)},
             blocked / epochs * 1e3, "blocked_ms/epoch")


def bench_pipeline_sharded():
    """The same blocked-time split on a SHARDED learner: REINFORCE after
    ``enable_multihost`` over a dp mesh (single-process — the collectives
    compile into the update either way), sync chain vs the pipelined
    multichip dispatch the broadcast loop now runs (mesh-aware
    ``stage_batch`` prefetch, in-flight window, collective
    ``snapshot_for_publish`` gather into the publisher thread). The dp
    extent adapts to the bench host (gcd of device count and
    traj_per_epoch; 1 device still exercises the sharded code path).
    tests/test_multichip_pipeline.py proves the two modes bit-identical;
    this row records what the overlap buys the learner thread."""
    import math
    import tempfile
    import time

    from relayrl_tpu.algorithms import build_algorithm
    from relayrl_tpu.parallel import make_mesh
    from relayrl_tpu.runtime.pipeline import ModelPublisher

    obs_dim, act_dim, tpe = 16, 4, 8
    epochs = 8 if quick() else 24
    dp = math.gcd(len(jax.devices()), tpe)
    mesh = make_mesh({"dp": dp}, jax.devices()[:dp])
    episodes = [_pipeline_episode(48, obs_dim, act_dim, seed=s)
                for s in range(epochs * tpe)]

    def run(mode):
        algo = build_algorithm(
            "REINFORCE", obs_dim=obs_dim, act_dim=act_dim,
            traj_per_epoch=tpe, hidden_sizes=[64, 64], seed_salt=0,
            with_vf_baseline=True,
            max_inflight_updates=0 if mode == "sync" else 2,
            logger_kwargs={"output_dir": tempfile.mkdtemp()})
        algo.enable_multihost(mesh)
        algo.warmup()  # single-process: the collective-warmup guard passes
        publisher = None
        if mode == "pipelined":
            publisher = ModelPublisher(lambda s: s.to_bundle().to_bytes())
        publish_wait = 0.0
        t_loop = time.monotonic()
        for ep in episodes:
            batch = algo.accumulate(ep)
            if batch is None:
                continue
            if mode == "pipelined":
                algo.train_on_batch(algo.stage_batch(batch))
                publisher.submit(algo.snapshot_for_publish())
            else:
                algo.train_on_batch(batch)  # window 0: fenced at dispatch
                t0 = time.monotonic()
                algo.bundle().to_bytes()    # inline gather + serialize
                publish_wait += time.monotonic() - t0
        loop_s = time.monotonic() - t_loop
        algo.inflight.drain()
        if publisher is not None:
            publisher.drain(timeout=60)
            publisher.stop()
        blocked = algo.inflight.device_wait_s + publish_wait
        return blocked, loop_s

    for mode in ("sync", "pipelined"):
        blocked, loop_s = run(mode)
        emit("learner_pipeline",
             {"algorithm": "REINFORCE", "mode": f"sharded_{mode}",
              "mesh": {"dp": dp}, "epochs": epochs, "traj_per_epoch": tpe,
              "obs_dim": obs_dim, "act_dim": act_dim,
              "hidden_sizes": [64, 64],
              "learner_thread_s_per_epoch": round(loop_s / epochs, 6)},
             blocked / epochs * 1e3, "blocked_ms/epoch")


def main():
    from relayrl_tpu.algorithms.reinforce import (
        ReinforceState, make_optimizers, make_reinforce_update)
    from relayrl_tpu.algorithms.dqn import DQNState, make_dqn_update
    from relayrl_tpu.algorithms.sac import SACState, make_sac_update
    from relayrl_tpu.algorithms.impala import ImpalaState, make_impala_update
    from relayrl_tpu.models import build_policy
    from relayrl_tpu.models.q_networks import (
        DiscreteQNet, SquashedGaussianActor, TwinQNet)
    import optax

    rng = np.random.default_rng(0)
    B, T, OBS, ACT = 64, 128, 32, 8

    def mk_reinforce():
        arch = {"kind": "mlp_discrete", "obs_dim": OBS, "act_dim": ACT,
                "hidden_sizes": [128, 128], "has_critic": True}
        policy = build_policy(arch)
        params = policy.init_params(jax.random.PRNGKey(0))
        tx_pi, tx_vf = make_optimizers(params, 3e-4, 1e-3)
        state = ReinforceState(params=params, pi_opt_state=tx_pi.init(params),
                               vf_opt_state=tx_vf.init(params),
                               rng=jax.random.PRNGKey(1), step=jnp.int32(0))
        update = make_reinforce_update(policy, 3e-4, 1e-3, 20, 0.99, 0.95, True)
        return state, update

    def mk_impala():
        arch = {"kind": "mlp_discrete", "obs_dim": OBS, "act_dim": ACT,
                "hidden_sizes": [128, 128], "has_critic": True}
        policy = build_policy(arch)
        params = policy.init_params(jax.random.PRNGKey(0))
        tx = optax.chain(optax.clip_by_global_norm(40.0), optax.adam(3e-4))
        state = ImpalaState(params=params, opt_state=tx.init(params),
                            rng=jax.random.PRNGKey(1), step=jnp.int32(0))
        update = make_impala_update(policy, 3e-4, 0.99, 0.5, 0.01, 1.0, 1.0,
                                    40.0)
        return state, update

    def mk_dqn():
        module = DiscreteQNet(act_dim=ACT, hidden_sizes=(128, 128))
        params = module.init(jax.random.PRNGKey(0),
                             jnp.zeros((1, OBS), jnp.float32))
        tx = optax.adam(1e-3)
        state = DQNState(params=params,
                         target_params=jax.tree.map(jnp.copy, params),
                         opt_state=tx.init(params), step=jnp.int32(0))
        return state, make_dqn_update(module, 0.99, 1e-3, 0.995, True)

    def mk_sac():
        actor = SquashedGaussianActor(act_dim=ACT, hidden_sizes=(128, 128))
        critic = TwinQNet(hidden_sizes=(128, 128))
        a = actor.init(jax.random.PRNGKey(0), jnp.zeros((1, OBS)))
        c = critic.init(jax.random.PRNGKey(1), jnp.zeros((1, OBS)),
                        jnp.zeros((1, ACT)))
        log_alpha = jnp.float32(np.log(0.2))
        state = SACState(
            actor_params=a, critic_params=c,
            target_critic_params=jax.tree.map(jnp.copy, c),
            log_alpha=log_alpha,
            actor_opt_state=optax.adam(3e-4).init(a),
            critic_opt_state=optax.adam(3e-4).init(c),
            alpha_opt_state=optax.adam(3e-4).init(log_alpha),
            rng=jax.random.PRNGKey(2), step=jnp.int32(0))
        return state, make_sac_update(actor, critic, 1.0, 0.99, 3e-4, 3e-4,
                                      3e-4, 0.995, -float(ACT))

    # Full shape config on every row so per-family numbers are comparable
    # across runs.
    mlp_shape = {"B": B, "T": T, "obs_dim": OBS, "act_dim": ACT,
                 "hidden_sizes": [128, 128]}
    bench_algo("REINFORCE", mk_reinforce, onpolicy_batch(B, T, OBS, ACT, rng),
               detail={"family": "mlp", **mlp_shape, "train_vf_iters": 20})
    bench_algo("IMPALA", mk_impala, onpolicy_batch(B, T, OBS, ACT, rng),
               flops_per_update=3 * mlp_fwd_flops(B * T, OBS, ACT, [128, 128]),
               detail={"family": "mlp", **mlp_shape})
    bench_algo("DQN", mk_dqn, offpolicy_batch(256, OBS, ACT, True, rng),
               detail={"family": "mlp", "batch_size": 256, "obs_dim": OBS,
                       "act_dim": ACT, "hidden_sizes": [128, 128]})
    bench_algo("SAC", mk_sac, offpolicy_batch(256, OBS, ACT, False, rng),
               detail={"family": "mlp", "batch_size": 256, "obs_dim": OBS,
                       "act_dim": ACT, "hidden_sizes": [128, 128]})

    # Dispatch fusion (updates_per_dispatch=K): tiny off-policy batches
    # on the chip are dominated by per-dispatch latency (benches/README
    # learner commentary) — one lax.scan dispatch carrying K sequential
    # updates amortizes it. Same math as K unfused calls
    # (tests/test_offpolicy.py::TestDispatchFusion).
    K = 8

    def mk_dqn_fused():
        state, update = mk_dqn()

        def fused(s, stacked):
            s2, ms = jax.lax.scan(lambda ss, b: update(ss, b), s, stacked)
            # last update's metrics: same output contract as one update
            # (the harness fences on a scalar leaf)
            return s2, jax.tree.map(lambda x: x[-1], ms)

        return state, fused

    single = offpolicy_batch(256, OBS, ACT, True, rng)
    stacked = {key: np.stack([v] * K) for key, v in single.items()}
    bench_algo("DQN-fused", mk_dqn_fused, stacked, updates_per_call=K,
               detail={"family": "mlp", "batch_size": 256, "obs_dim": OBS,
                       "act_dim": ACT, "hidden_sizes": [128, 128],
                       "updates_per_dispatch": K})

    # Pipelined vs synchronous learner-thread blocked time (the ISSUE-2
    # acceptance metric): same math, different overlap.
    bench_pipeline()
    # ...and the same split on the sharded (multichip broadcast-loop)
    # learner: the dispatch window + publish gather over a dp mesh.
    bench_pipeline_sharded()

    # -- flagship non-MLP families: transformer-flash and CNN-pixel, both
    #    through the IMPALA update (the async-fleet north star for big
    #    models; one fused fwd+bwd over [B, T]) with analytic-FLOP MFU --
    if ON_TPU and not quick():
        t_B, t_T, t_d, t_L = 8, 1024, 256, 4
        c_B, c_T = 16, 32
    else:  # CPU smoke: same code path, laptop-sized shapes
        t_B, t_T, t_d, t_L = 2, 128, 64, 2
        c_B, c_T = 2, 8

    def mk_impala_for(arch):
        policy = build_policy(arch)
        params = policy.init_params(jax.random.PRNGKey(0))
        tx = optax.chain(optax.clip_by_global_norm(40.0), optax.adam(3e-4))
        state = ImpalaState(params=params, opt_state=tx.init(params),
                            rng=jax.random.PRNGKey(1), step=jnp.int32(0))
        update = make_impala_update(policy, 3e-4, 0.99, 0.5, 0.01, 1.0, 1.0,
                                    40.0)
        return state, update

    # "flash" resolves per backend: Pallas kernel on TPU, the lax.scan
    # blockwise path elsewhere (models/transformer.py heterogeneous rule).
    t_arch = {"kind": "transformer_discrete", "obs_dim": 64, "act_dim": 18,
              "d_model": t_d, "n_layers": t_L, "n_heads": 8,
              "max_seq_len": t_T, "has_critic": True,
              "attention": "flash",
              "attention_block": min(256, t_T), "precision": "bfloat16"}
    bench_algo(
        "IMPALA", lambda: mk_impala_for(t_arch),
        onpolicy_batch(t_B, t_T, 64, 18, rng),
        flops_per_update=3 * transformer_fwd_flops(
            t_B * t_T, t_T, 64, 18, t_d, t_L),
        detail={"family": "transformer_flash" if ON_TPU else "transformer",
                "B": t_B, "T": t_T, "d_model": t_d, "n_layers": t_L,
                "n_heads": 8, "head_dim": t_d // 8})

    # Compute-bound transformer demo shape (docs/parallelism.md roofline):
    # head_dim = d_model/heads = 128 fills the MXU's 128 lanes (the
    # serving default d=256/H=8 gives head_dim 32 -> <=25% lane occupancy,
    # the shape bound behind the 13.6% MFU row), and the per-layer weight
    # reuse over 4096 tokens puts arithmetic intensity ~4x the v5e ridge.
    if ON_TPU and not quick():
        big_arch = {"kind": "transformer_discrete", "obs_dim": 64,
                    "act_dim": 18, "d_model": 1024, "n_layers": 4,
                    "n_heads": 8, "max_seq_len": 1024, "has_critic": True,
                    "attention": "flash", "attention_block": 256,
                    "precision": "bfloat16"}
        bench_algo(
            "IMPALA", lambda: mk_impala_for(big_arch),
            onpolicy_batch(4, 1024, 64, 18, rng),
            flops_per_update=3 * transformer_fwd_flops(
                4 * 1024, 1024, 64, 18, 1024, 4),
            detail={"family": "transformer_flash_computebound", "B": 4,
                    "T": 1024, "d_model": 1024, "n_layers": 4,
                    "n_heads": 8, "head_dim": 128})

    from relayrl_tpu.models.cnn import NATURE_CONV

    obs_shape = (84, 84, 4) if ON_TPU and not quick() else (36, 36, 2)
    conv_spec = NATURE_CONV
    c_obs = int(np.prod(obs_shape))
    c_arch = {"kind": "cnn_discrete", "obs_shape": obs_shape,
              "obs_dim": c_obs, "act_dim": 18, "conv_spec": conv_spec,
              "dense": 512, "has_critic": True, "precision": "bfloat16"}
    bench_algo(
        "IMPALA", lambda: mk_impala_for(c_arch),
        onpolicy_batch(c_B, c_T, c_obs, 18, rng),
        flops_per_update=3 * cnn_fwd_flops(
            c_B * c_T, obs_shape, conv_spec, 512, 18),
        detail={"family": "cnn_pixel", "B": c_B, "T": c_T,
                "obs_shape": list(obs_shape),
                "conv_spec": [list(s) for s in conv_spec], "dense": 512})

    # TPU-native trunk (conv_spec="tpu"): Nature geometry with channel
    # widths at MXU-lane multiples (64/128/128) — ~4x the FLOPs, but they
    # land where the systolic array can retire them, so MFU (not
    # updates/s) is the number to compare against the cnn_pixel row
    # (docs/parallelism.md CNN roofline: Nature's 32-channel conv1 caps
    # lane occupancy at <=25% on ~40% of its FLOPs).
    if ON_TPU and not quick():
        from relayrl_tpu.models.cnn import TPU_CONV

        tpu_cnn_arch = dict(c_arch, conv_spec=TPU_CONV)
        bench_algo(
            "IMPALA", lambda: mk_impala_for(tpu_cnn_arch),
            onpolicy_batch(c_B, c_T, c_obs, 18, rng),
            flops_per_update=3 * cnn_fwd_flops(
                c_B * c_T, obs_shape, TPU_CONV, 512, 18),
            detail={"family": "cnn_pixel_tpu_trunk", "B": c_B, "T": c_T,
                    "obs_shape": list(obs_shape),
                    "conv_spec": [list(s) for s in TPU_CONV], "dense": 512})

        # Batch-scaling lever (docs/parallelism.md CNN roofline: "bigger
        # frame batch — more M rows per conv" is lever #1 for the
        # lane-starved Nature shape): same trunk, 4x the frames per
        # update. MFU here vs the B=16 row isolates how much of the
        # 4.9% was M-dimension starvation vs the 32-channel lane cap.
        bench_algo(
            "IMPALA", lambda: mk_impala_for(c_arch),
            onpolicy_batch(64, c_T, c_obs, 18, rng),
            flops_per_update=3 * cnn_fwd_flops(
                64 * c_T, obs_shape, conv_spec, 512, 18),
            detail={"family": "cnn_pixel_b64", "B": 64, "T": c_T,
                    "obs_shape": list(obs_shape),
                    "conv_spec": [list(s) for s in conv_spec],
                    "dense": 512})
        bench_algo(
            "IMPALA", lambda: mk_impala_for(tpu_cnn_arch),
            onpolicy_batch(64, c_T, c_obs, 18, rng),
            flops_per_update=3 * cnn_fwd_flops(
                64 * c_T, obs_shape, TPU_CONV, 512, 18),
            detail={"family": "cnn_pixel_tpu_trunk_b64", "B": 64, "T": c_T,
                    "obs_shape": list(obs_shape),
                    "conv_spec": [list(s) for s in TPU_CONV], "dense": 512})


if __name__ == "__main__":
    main()
