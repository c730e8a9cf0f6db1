"""Flash-kernel autotune: (block_q, block_kv) x head-dim on the chip.

The serving-shape transformer_flash row (B=8, T=1024, d_model=256,
8 heads -> head_dim 32) sits far below the kernel's best rate at
head_dim 128 (benches/results/learner_tpu.json, attention.json — captures
that predate the current code). Two levers, measured separately here:

* block shape — the [block_q, D] x [D, block_kv] score matmul and the
  [block_q, block_kv] x [block_kv, D] value matmul change arithmetic
  intensity and grid-step count with the block pair; the committed
  default (1024, 1024) was picked at D in {64, 128} and may be wrong
  at small D.
* head_dim — the MXU contracts 128 lanes; D=32 quarter-fills every
  matmul's contraction depth, capping attainable MFU at ~D/128 of
  peak BEFORE softmax overhead. The sweep's D axis quantifies exactly
  what a model config buys by choosing fewer, wider heads at fixed
  d_model (e.g. 2x128 instead of 8x32 at d_model=256 — same param
  count, same FLOPs, 4x the contraction depth).

Emits one JSON line per (T, D, block_q, block_kv) with fwd and
fwd+bwd TFLOP/s + fraction-of-peak; picks the winner per (T, D).
Each cell is bracketed by a fixed control cell (default clamped blocks,
compiled once per shape) and ranked by the ``fwd_vs_ctrl`` ratio, so a
chip whose throughput drifts during the sweep cannot drown the block
signal; ``ctrl_spread`` flags brackets whose two controls disagree.
Chip-only by default (the Pallas interpreter would sweep for hours and
measure nothing); CPU smoke via --quick uses tiny shapes in interpret
mode to prove the harness runs everywhere.

Run: RELAYRL_BENCH_TPU=1 python benches/bench_flash_autotune.py
Artifact (with --write): benches/results/flash_autotune.json
"""

from __future__ import annotations

import itertools
import json
import os
import sys

import time

from common import emit, quick, setup_platform

setup_platform()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def attention_flops(B: int, T: int, H: int, D: int, causal: bool) -> float:
    """Matmul FLOPs only (QK^T + PV), the standard flash accounting."""
    full = 4.0 * B * H * T * T * D
    return full / 2 if causal else full


# A pre/post control disagreement above this excludes the cell from
# winner ranking: the observed throttle modes sit ~2.3x apart, so a
# clean bracket reads ~1.0x and a straddled one ~2.3x — 1.25 separates
# them with margin for ordinary timer jitter.
CTRL_SPREAD_MAX = 1.25


def sweep():
    from relayrl_tpu.ops.flash import flash_attention

    on_tpu = jax.default_backend() == "tpu"
    if quick():
        shapes = [(2, 256, 2, 32)]
        blocks = [128, 256]
        peak = None
    else:
        if not on_tpu:
            print("flash autotune needs a TPU backend "
                  "(RELAYRL_BENCH_TPU=1 + live chip); --quick for the "
                  "CPU harness smoke", file=sys.stderr)
            return []
        # serving shape (8 heads x 32) and its wide-head re-spec
        # (2 x 128) at the same d_model=256, plus the compute-bound
        # reference point D=128 at bigger T.
        shapes = [(8, 1024, 8, 32), (8, 1024, 4, 64), (8, 1024, 2, 128),
                  (4, 2048, 2, 128)]
        blocks = [128, 256, 512, 1024]
        from bench_learner import chip_peak_flops

        peak = chip_peak_flops()

    rows = []
    for B, T, H, D in shapes:
        key = jax.random.PRNGKey(0)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (B, T, H, D), jnp.bfloat16)
        k = jax.random.normal(kk, (B, T, H, D), jnp.bfloat16)
        v = jax.random.normal(kv, (B, T, H, D), jnp.bfloat16)
        flops_fwd = attention_flops(B, T, H, D, causal=True)
        best = None
        iters = 3 if quick() else 10

        def make_chain(step, x0):
            """Compile one jitted fori_loop of ``iters`` chained
            applications and warm it. Compiled ONCE and reused — a fresh
            lambda per timing would recompile every call (jax.jit caches
            by callable identity), which would be the whole sweep
            budget."""
            chain = jax.jit(lambda x: jax.lax.fori_loop(
                0, iters, lambda i, y: step(y), x))
            float(jnp.sum(chain(x0)[0, 0, 0].astype(jnp.float32)))
            return chain

        def time_chain(chain, x0):
            """Fenced by ONE host readback of a value that depends on
            the whole chain."""
            t0 = time.perf_counter()
            float(jnp.sum(chain(x0)[0, 0, 0].astype(jnp.float32)))
            return (time.perf_counter() - t0) / iters

        # Drift control: every cell is bracketed by a fixed reference
        # cell (default clamped blocks, compiled once per shape):
        # ``fwd_vs_ctrl`` is the cell's speed relative to the control —
        # chip-global drift cancels in the ratio — and ``ctrl_spread``
        # (pre/post disagreement) flags cells whose bracket straddled a
        # change; spread > CTRL_SPREAD_MAX excludes a cell from winner
        # ranking. All compilation happens BEFORE the pre/post bracket so
        # the bracket spans only the four timed runs. Rank blocks by
        # fwd_vs_ctrl.
        ctrl_b = min(1024, T)
        try:
            ctrl_chain = make_chain(
                lambda qq: jnp.tanh(flash_attention(
                    qq, k, v, causal=True, block_q=ctrl_b,
                    block_kv=ctrl_b, interpret=not on_tpu)),
                q)
        except Exception as e:
            emit("flash_autotune", {
                "B": B, "T": T, "H": H, "D": D, "ctrl_block": ctrl_b,
                "error": "control: " + repr(e)[:200]}, 0.0, "TFLOP/s")
            continue

        for bq, bkv in itertools.product(blocks, blocks):
            if T % bq or T % bkv:
                continue
            try:
                fwd_chain = make_chain(
                    lambda qq, bq=bq, bkv=bkv: jnp.tanh(flash_attention(
                        qq, k, v, causal=True, block_q=bq, block_kv=bkv,
                        interpret=not on_tpu)),
                    q)

                grad = jax.jit(jax.grad(
                    lambda qq, kk, vv, bq=bq, bkv=bkv: jnp.sum(
                        flash_attention(qq, kk, vv, causal=True, block_q=bq,
                                        block_kv=bkv, interpret=not on_tpu
                                        ).astype(jnp.float32)),
                    argnums=(0, 1, 2)))

                def bwd_step(qq):
                    dq, dk, dv = grad(qq, k, v)
                    return jnp.tanh(dq + dk + dv)

                bwd_chain = make_chain(bwd_step, q)

                ctrl_pre = time_chain(ctrl_chain, q)
                dt_f = time_chain(fwd_chain, q)
                dt_g = time_chain(bwd_chain, q)
                ctrl_post = time_chain(ctrl_chain, q)
            except Exception as e:
                emit("flash_autotune", {
                    "B": B, "T": T, "H": H, "D": D, "block_q": bq,
                    "block_kv": bkv, "error": repr(e)[:200]}, 0.0, "TFLOP/s")
                continue
            ctrl_dt = min(ctrl_pre, ctrl_post)
            row = {
                "B": B, "T": T, "H": H, "D": D,
                "block_q": bq, "block_kv": bkv,
                "fwd_tflops": round(flops_fwd / dt_f / 1e12, 2),
                # bwd with recompute: dq pass + dkv pass redo the score
                # matmul — 2.5x fwd matmul FLOPs for the VJP, 3.5x for
                # the fwd+bwd chain timed here
                "fwdbwd_tflops": round(3.5 * flops_fwd / dt_g / 1e12, 2),
                # drift-normalized ranking metric + bracket quality
                "fwd_vs_ctrl": round(ctrl_dt / dt_f, 3),
                "ctrl_spread": round(
                    max(ctrl_pre, ctrl_post) / min(ctrl_pre, ctrl_post), 3),
            }
            if peak:
                row["fwd_frac_peak"] = round(flops_fwd / dt_f / peak, 4)
            emit("flash_autotune", dict(row), row["fwd_tflops"], "TFLOP/s")
            if row["ctrl_spread"] > CTRL_SPREAD_MAX:
                continue  # bracket straddled a mode flip; ratio untrusted
            if best is None or row["fwd_vs_ctrl"] > best["fwd_vs_ctrl"]:
                best = row
        if best is not None:
            best["winner"] = True
            emit("flash_autotune_best", dict(best), best["fwd_tflops"],
                 "TFLOP/s")
            rows.append(best)
    return rows


def main():
    rows = sweep()
    if "--write" in sys.argv and rows:
        out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "results", "flash_autotune.json")
        os.makedirs(os.path.dirname(out), exist_ok=True)
        with open(out, "w") as f:
            json.dump({"bench": "flash_autotune", "winners": rows}, f,
                      indent=1)


if __name__ == "__main__":
    main()
