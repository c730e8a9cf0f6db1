"""Headline benchmark: REINFORCE learner steps/sec/chip on TPU.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

``vs_baseline`` compares against a faithful torch-CPU implementation of the
reference's learner epoch (one policy-gradient step + ``train_vf_iters``
value MSE steps — relayrl_framework/src/native/python/algorithms/REINFORCE/
REINFORCE.py:97-125) on the same data: the reference publishes no numbers
(BASELINE.md), and its learner is CPU PyTorch, so "reference-shaped torch on
this host's CPU" is the honest stand-in baseline.

This measures the chip or nothing: without a TPU it exits non-zero before
it times anything and prints no metric line.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

# Peak dense bf16 FLOP/s of one chip, keyed by the exact
# ``jax.devices()[0].device_kind`` string the chip reports (``chip_smoke.py``
# prints it). A device that is not here is an error, not a silently
# dropped ``mfu``. Source: Google Cloud documentation, "TPU v5e" —
# 197 TFLOP/s bf16 per chip.
_CHIP_PEAK_FLOPS = {
    "TPU v5 lite": 197e12,
}


def _require_tpu() -> str:
    """The device this process will measure, or exit: no fallback."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench: no TPU (jax found platform {dev.platform!r}, "
            f"{dev.device_kind!r}); nothing measured")
    return dev.device_kind


def _chip_peak_flops(device_kind: str) -> float:
    try:
        return _CHIP_PEAK_FLOPS[device_kind]
    except KeyError:
        raise SystemExit(
            f"bench: no peak FLOP/s on record for device_kind "
            f"{device_kind!r}; add it to _CHIP_PEAK_FLOPS with its source"
        ) from None


def best_of(trials: int, timed_once) -> float:
    """Max rate over ``trials`` runs of ``timed_once() -> rate``, used
    SYMMETRICALLY for the jax and torch sides."""
    return max(timed_once() for _ in range(trials))


# Bench shape: 64 trajectories × 256 steps (the north-star configs feed a
# v4-8 learner from 64 actors; one epoch batch per update).
B, T, OBS, ACT = 64, 256, 128, 18
HIDDEN = [256, 256]
VF_ITERS = 80
WARMUP, ITERS = 3, 20


def _batch(rng):
    return {
        "obs": rng.standard_normal((B, T, OBS)).astype(np.float32),
        "act": rng.integers(0, ACT, (B, T)).astype(np.int32),
        "act_mask": np.ones((B, T, ACT), np.float32),
        "rew": rng.standard_normal((B, T)).astype(np.float32),
        "val": rng.standard_normal((B, T)).astype(np.float32),
        "logp": rng.standard_normal((B, T)).astype(np.float32),
        "valid": np.ones((B, T), np.float32),
        "last_val": np.zeros((B,), np.float32),
    }


def _analytic_flops_per_update() -> float:
    """Matmul FLOPs of one compiled epoch update.

    The pi and vf losses each call the full actor-critic apply, but XLA
    dead-code-eliminates the trunk whose outputs the loss doesn't touch,
    so the live compute is: policy step = fwd+bwd over the pi trunk+head
    (~3x fwd) + one diagnostic fwd; value phase = train_vf_iters grad steps
    over the vf trunk+head (~3x fwd each) + 2 diagnostic fwds. Elementwise
    ops (activations, GAE scan, Adam) are negligible next to the matmuls.
    """
    n = B * T
    dims = [OBS] + list(HIDDEN)
    trunk = sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))
    pi_fwd = n * (trunk + 2 * HIDDEN[-1] * ACT)
    vf_fwd = n * (trunk + 2 * HIDDEN[-1] * 1)
    return 4.0 * pi_fwd + (3.0 * VF_ITERS + 2.0) * vf_fwd


def bench_jax(warmup: int = WARMUP, iters: int = ITERS,
              cost_check: bool = True, trials: int = 3) -> tuple[float, float]:
    """Returns (epoch_updates_per_sec, mfu).

    MFU = analytic matmul FLOPs of one epoch update x updates/s / chip
    peak bf16 FLOP/s. XLA's cost_analysis is logged as a cross-check only
    when ``cost_check`` — it counts the vf fori_loop body once, and the
    AOT lower().compile() it requires duplicates the jit compile."""
    import jax
    import jax.numpy as jnp
    import optax

    from relayrl_tpu.algorithms.reinforce import (
        ReinforceState,
        _param_labels,
        make_reinforce_update,
    )
    from relayrl_tpu.models import build_policy

    arch = {"kind": "mlp_discrete", "obs_dim": OBS, "act_dim": ACT,
            "hidden_sizes": HIDDEN, "has_critic": True, "precision": "bfloat16"}
    policy = build_policy(arch)
    params = policy.init_params(jax.random.PRNGKey(0))
    labels = _param_labels(params)
    tx_pi = optax.multi_transform(
        {"pi": optax.adam(3e-4), "vf": optax.set_to_zero()}, labels)
    tx_vf = optax.multi_transform(
        {"pi": optax.set_to_zero(), "vf": optax.adam(1e-3)}, labels)
    state = ReinforceState(params=params, pi_opt_state=tx_pi.init(params),
                           vf_opt_state=tx_vf.init(params),
                           rng=jax.random.PRNGKey(1), step=jnp.int32(0))
    update = jax.jit(
        make_reinforce_update(policy, 3e-4, 1e-3, VF_ITERS, 0.99, 0.95,
                              with_baseline=True),
        donate_argnums=0)

    rng = np.random.default_rng(0)
    batch = {k: jnp.asarray(v) for k, v in _batch(rng).items()}

    flops_per_update = _analytic_flops_per_update()
    if cost_check:
        try:
            # Cross-check only: XLA's cost analysis counts a fori_loop body
            # ONCE, so it undercounts the 80 vf iterations ~27x; log it for
            # comparison but use the analytic count for MFU.
            cost = update.lower(state, batch).compile().cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0]
            print(f"bench: xla cost_analysis flops={cost.get('flops'):.3e} "
                  f"(loop body counted once), analytic={flops_per_update:.3e}",
                  file=sys.stderr)
        except Exception as exc:  # cost analysis is backend-dependent
            print(f"bench: cost_analysis unavailable ({exc!r})",
                  file=sys.stderr)

    for _ in range(warmup):
        state, metrics = update(state, batch)
    # Host readback of a value that depends on the whole donated-state
    # chain: it cannot return before the last update has run. (On the
    # v5e jax.block_until_ready fences just as well — chip_smoke.py times
    # the same chain under both and they agree.)
    float(metrics["LossPi"])

    def one_trial():
        nonlocal state, metrics
        t0 = time.perf_counter()
        for _ in range(iters):
            state, metrics = update(state, batch)
        float(metrics["LossPi"])  # forces all ITERS sequential updates
        return iters / (time.perf_counter() - t0)

    ups = best_of(trials, one_trial)
    peak = _chip_peak_flops(jax.devices()[0].device_kind)
    return ups, flops_per_update * ups / peak


def bench_transformer(warmup: int = 2, iters: int = 8) -> dict:
    """Secondary headline: the transformer-flash family through the
    IMPALA update, so the chip evidence covers a non-MLP family.
    Returns {updates_per_sec, mfu, shape}; a failure here fails the
    bench."""
    import jax
    import jax.numpy as jnp
    import optax

    from relayrl_tpu.algorithms.impala import ImpalaState, make_impala_update
    from relayrl_tpu.models import build_policy

    t_B, t_T, t_d, t_L = 8, 1024, 256, 4
    arch = {"kind": "transformer_discrete", "obs_dim": 64, "act_dim": 18,
            "d_model": t_d, "n_layers": t_L, "n_heads": 8,
            "max_seq_len": t_T, "has_critic": True, "attention": "flash",
            "attention_block": 256, "precision": "bfloat16"}
    policy = build_policy(arch)
    params = policy.init_params(jax.random.PRNGKey(0))
    tx = optax.chain(optax.clip_by_global_norm(40.0), optax.adam(3e-4))
    state = ImpalaState(params=params, opt_state=tx.init(params),
                        rng=jax.random.PRNGKey(1), step=jnp.int32(0))
    # donate_argnums=0 matches the MLP headline jit above and the
    # production jit in algorithms/impala.py — without it XLA keeps the
    # old transformer state alive across every update (jaxlint JAX05).
    update = jax.jit(
        make_impala_update(policy, 3e-4, 0.99, 0.5, 0.01, 1.0, 1.0, 40.0),
        donate_argnums=0)

    rng = np.random.default_rng(0)
    batch = {
        "obs": jnp.asarray(rng.standard_normal((t_B, t_T, 64)).astype(np.float32)),
        "act": jnp.asarray(rng.integers(0, 18, (t_B, t_T)).astype(np.int32)),
        "act_mask": jnp.ones((t_B, t_T, 18), jnp.float32),
        "rew": jnp.asarray(rng.standard_normal((t_B, t_T)).astype(np.float32)),
        "val": jnp.zeros((t_B, t_T), jnp.float32),
        "logp": jnp.full((t_B, t_T), -1.0, jnp.float32),
        "valid": jnp.ones((t_B, t_T), jnp.float32),
        "last_val": jnp.zeros((t_B,), jnp.float32),
    }
    for _ in range(warmup):
        state, metrics = update(state, batch)
    float(jax.tree_util.tree_leaves(metrics)[0])  # host fence (see bench_jax)

    def one_trial():
        nonlocal state, metrics
        t0 = time.perf_counter()
        for _ in range(iters):
            state, metrics = update(state, batch)
        float(jax.tree_util.tree_leaves(metrics)[0])
        return iters / (time.perf_counter() - t0)

    ups = best_of(2, one_trial)
    # analytic fwd FLOPs (see benches/bench_learner.transformer_fwd_flops);
    # IMPALA's fused fwd+bwd ~= 3x fwd
    tokens = t_B * t_T
    per_layer = 8 * t_d * t_d + 16 * t_d * t_d + 2 * t_d * t_T
    fwd = tokens * (t_L * per_layer + 2 * 64 * t_d + 2 * t_d * 19)
    peak = _chip_peak_flops(jax.devices()[0].device_kind)
    return {"updates_per_sec": round(ups, 2),
            "B": t_B, "T": t_T, "d_model": t_d, "n_layers": t_L,
            "mfu": round(3 * fwd * ups / peak, 4)}


def bench_torch_reference(iters: int = 3, trials: int = 3) -> float:
    """Reference-shaped learner epoch in torch on CPU: one pg step +
    VF_ITERS value steps over the same flattened step set."""
    import torch

    torch.manual_seed(0)
    torch.set_num_threads(max(1, (torch.get_num_threads())))

    class MLP(torch.nn.Module):
        def __init__(self, out):
            super().__init__()
            layers, prev = [], OBS
            for h in HIDDEN:
                layers += [torch.nn.Linear(prev, h), torch.nn.Tanh()]
                prev = h
            layers += [torch.nn.Linear(prev, out)]
            self.net = torch.nn.Sequential(*layers)

        def forward(self, x):
            return self.net(x)

    pi, vf = MLP(ACT), MLP(1)
    pi_opt = torch.optim.Adam(pi.parameters(), lr=3e-4)
    vf_opt = torch.optim.Adam(vf.parameters(), lr=1e-3)

    rng = np.random.default_rng(0)
    raw = _batch(rng)
    obs = torch.from_numpy(raw["obs"].reshape(B * T, OBS))
    act = torch.from_numpy(raw["act"].reshape(B * T)).long()
    adv = torch.from_numpy(raw["rew"].reshape(B * T))
    ret = torch.from_numpy(raw["val"].reshape(B * T))

    def epoch():
        logp = torch.log_softmax(pi(obs), dim=-1).gather(1, act[:, None]).squeeze(1)
        loss_pi = -(logp * adv).mean()
        pi_opt.zero_grad(); loss_pi.backward(); pi_opt.step()
        for _ in range(VF_ITERS):
            loss_v = ((vf(obs).squeeze(-1) - ret) ** 2).mean()
            vf_opt.zero_grad(); loss_v.backward(); vf_opt.step()

    epoch()  # warmup

    def one_trial():
        t0 = time.perf_counter()
        for _ in range(iters):
            epoch()
        return iters / (time.perf_counter() - t0)

    return best_of(trials, one_trial)


def profile_stages(epochs: int = 6) -> dict:
    """Per-stage timing breakdown of the pipelined learner hot path
    (``--profile``): decode → assemble → H2D → device → publish, seconds
    per epoch, appended to the bench JSON so a headline regression can
    be attributed to a stage instead of re-derived from scratch. Each stage is timed in isolation with an explicit
    fence where the work is asynchronous (device dispatch, H2D), so the
    numbers are attributable even though the production path overlaps
    them on purpose."""
    import tempfile

    import jax

    from relayrl_tpu.algorithms import build_algorithm
    from relayrl_tpu.types.action import ActionRecord
    from relayrl_tpu.types.trajectory import (
        deserialize_actions,
        serialize_actions,
    )

    obs_dim, act_dim, tpe, ep_len = 32, 8, 8, 128
    rng = np.random.default_rng(0)
    payloads = []
    for s in range(epochs * tpe):
        payloads.append(serialize_actions([
            ActionRecord(
                obs=rng.standard_normal(obs_dim).astype(np.float32),
                act=np.int64(rng.integers(act_dim)), rew=float(rng.random()),
                data={"logp_a": np.float32(-0.69), "v": np.float32(0.0)},
                done=(i == ep_len - 1))
            for i in range(ep_len)]))

    algo = build_algorithm(
        "REINFORCE", obs_dim=obs_dim, act_dim=act_dim, traj_per_epoch=tpe,
        hidden_sizes=[128, 128], with_vf_baseline=True, seed_salt=0,
        logger_kwargs={"output_dir": tempfile.mkdtemp()})
    algo.warmup()

    # Publish split: serialize_s (host gather + wire encode — what
    # model-wire v2 shrinks with delta frames) vs socket_s (the PUB send
    # itself) — separately attributable so a wire-format change shows up
    # in the headline profile instead of hiding inside one bucket. A
    # real zmq PUB/SUB pair on loopback, drained off-thread, keeps the
    # socket number honest.
    import threading

    import zmq

    from relayrl_tpu.transport.base import MODEL_TOPIC, pack_model_frame
    from relayrl_tpu.transport.modelwire import ModelWireEncoder

    ctx = zmq.Context.instance()
    pub = ctx.socket(zmq.PUB)
    pub_port = pub.bind_to_random_port("tcp://127.0.0.1")
    sub = ctx.socket(zmq.SUB)
    sub.connect(f"tcp://127.0.0.1:{pub_port}")
    sub.setsockopt(zmq.SUBSCRIBE, b"")
    stop_drain = threading.Event()

    def _drain():
        poller = zmq.Poller()
        poller.register(sub, zmq.POLLIN)
        while not stop_drain.is_set():
            if dict(poller.poll(50)):
                sub.recv_multipart()

    drainer = threading.Thread(target=_drain, daemon=True)
    drainer.start()
    wire_enc = ModelWireEncoder()  # production default: v2, delta frames

    stages = {"decode_s": 0.0, "assemble_s": 0.0, "h2d_s": 0.0,
              "device_s": 0.0, "publish_s": 0.0, "serialize_s": 0.0,
              "socket_s": 0.0}
    for raw in payloads:
        t0 = time.perf_counter()
        episode = deserialize_actions(raw)
        stages["decode_s"] += time.perf_counter() - t0

        t0 = time.perf_counter()
        batch = algo.accumulate(episode)
        stages["assemble_s"] += time.perf_counter() - t0
        if batch is None:
            continue

        t0 = time.perf_counter()
        staged = jax.block_until_ready(algo.stage_batch(batch))
        stages["h2d_s"] += time.perf_counter() - t0

        t0 = time.perf_counter()
        metrics = algo.train_on_batch(staged)
        jax.block_until_ready(metrics.device)
        stages["device_s"] += time.perf_counter() - t0

        t0 = time.perf_counter()
        snap = algo.snapshot_for_publish()
        frame, _info = wire_enc.encode(snap.version, snap.arch,
                                       snap.host_params())
        dt = time.perf_counter() - t0
        stages["serialize_s"] += dt
        stages["publish_s"] += dt

        t0 = time.perf_counter()
        pub.send_multipart([MODEL_TOPIC,
                            pack_model_frame(snap.version, frame)])
        dt = time.perf_counter() - t0
        stages["socket_s"] += dt
        stages["publish_s"] += dt  # legacy total: serialize + socket

    stop_drain.set()
    drainer.join(timeout=2)
    pub.close(linger=0)
    sub.close(linger=0)
    return {
        "epochs": epochs, "traj_per_epoch": tpe, "episode_len": ep_len,
        "obs_dim": obs_dim, "act_dim": act_dim,
        "per_epoch_ms": {k[:-2]: round(v / epochs * 1e3, 3)
                         for k, v in stages.items()},
    }


def main():
    device_kind = _require_tpu()
    _chip_peak_flops(device_kind)  # an unknown chip fails before timing
    from relayrl_tpu.utils.compile_cache import resolve_compile_cache

    print(f"bench: device {device_kind!r}, compile cache at "
          f"{resolve_compile_cache()}", file=sys.stderr, flush=True)
    jax_sps, mfu = bench_jax()
    torch_sps = bench_torch_reference()
    result = {
        "metric": "learner_steps_per_sec_chip",
        "value": round(jax_sps, 3),
        "unit": (f"epoch_updates/s (B=64,T=256,obs=128,act=18,vf_iters=80,"
                 f"platform=tpu,device_kind={device_kind})"),
        "vs_baseline": round(jax_sps / torch_sps, 2),
        "mfu": round(mfu, 4),
        "transformer_flash": bench_transformer(),
    }
    if "--profile" in sys.argv:
        # Per-stage breakdown (decode/assemble/H2D/device/publish) rides
        # in the same JSON line so a headline regression points at a
        # stage, not just a number.
        result["stage_profile"] = profile_stages()
    print(json.dumps(result))


if __name__ == "__main__":
    main()
