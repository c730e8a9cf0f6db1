"""Attention backend shootout on the live JAX backend (TPU when present).

Compares the three single-device attention tiers on production-shaped
inputs (bf16, BTHD layout):

* ``dense``     — materializes the [Tq, Tk] score matrix (ops/attention.py)
* ``blockwise`` — lax.scan online softmax, O(T * block) memory
* ``flash``     — fused Pallas TPU kernel (ops/flash.py)

Reports forward latency and a train-shaped fwd+bwd latency (grad of a
scalar loss through the op) per backend, plus achieved TFLOP/s using the
analytic 4*B*H*T^2*D causal attention FLOP count (x2.5 for fwd+bwd).

Unlike the transport benches this one WANTS the accelerator: it runs on
whatever backend is live and records it. CPU runs are valid for shape
comparisons but the headline is the chip.
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

try:  # standalone from benches/ (the directory convention) ...
    from common import emit
except ImportError:  # ... or as a module from the repo root
    from benches.common import emit


def attention_flops(B, T, H, D, causal=True):
    # Two matmuls (QK^T and PV), 2*T*T*D MACs each -> 4*T^2*D flops per
    # (batch, head); causal halves the useful triangle.
    f = 4.0 * B * H * T * T * D
    return f / 2 if causal else f


def main() -> None:
    quick = "--quick" in sys.argv
    shapes = ([(2, 512, 4, 64, 128)] if quick
              # (B, T, H, D, block): the trajectory-shaped config and a
              # long-context one where the dense score matrix stops fitting
              # on-chip (see benches/README.md for the committed numbers).
              else [(8, 2048, 8, 64, 256), (2, 8192, 8, 64, 512)])
    for shape in shapes:
        run_shape(*shape, quick=quick)


def run_shape(B, T, H, D, block, quick=False) -> None:
    platform = jax.default_backend()
    FLASH_BLOCK = 1024

    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((B, T, H, D)),
                           jnp.bfloat16) for _ in range(3))

    from relayrl_tpu.ops.attention import blockwise_attention, dense_attention
    from relayrl_tpu.ops.flash import flash_attention

    backends = {
        "dense": lambda q, k, v: dense_attention(q, k, v, causal=True),
        "blockwise": lambda q, k, v: blockwise_attention(
            q, k, v, block_size=block, causal=True),
        # Flash takes its own (kernel-scale) block: grid-step count
        # dominates kernel wall time, unlike the scan path whose block is
        # a memory/fusion knob.
        "flash": lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=FLASH_BLOCK, block_kv=FLASH_BLOCK),
    }
    if platform == "tpu":
        # Ring cost model: the same chunk kernels the sp ring runs, all
        # local — fused-flash minus these rows is the per-device price of
        # chunking (state-carry HBM traffic + per-call overhead), with ICI
        # deliberately excluded. Forward-only (the ring backward is its
        # own two-pass schedule).
        from relayrl_tpu.parallel.ring_flash import chunked_flash_local

        for n in (2, 4):
            backends[f"flash_chunked{n}"] = (
                lambda q, k, v, n=n: chunked_flash_local(
                    q, k, v, n_chunks=n, causal=True))
    else:
        backends.pop("flash")  # interpreter mode would dominate the chart

    flops_fwd = attention_flops(B, T, H, D)
    cfg = {"B": B, "T": T, "H": H, "D": D, "block": block,
           "flash_block": FLASH_BLOCK,
           "dtype": "bfloat16", "platform": platform}

    import time

    iters = 5 if quick else (10 if T > 4096 else 30)

    def timed_chain(step, x0):
        """One jitted fori_loop of ``iters`` chained applications (each
        input depends on the previous output), closed by ONE host readback:
        a single dispatch, so per-call dispatch cost amortizes away, and a
        host read of a value depending on the whole chain cannot return
        early."""
        chain = jax.jit(lambda x: jax.lax.fori_loop(
            0, iters, lambda i, y: step(y), x))
        float(jnp.sum(chain(x0)[0, 0, 0].astype(jnp.float32)))  # warmup
        t0 = time.perf_counter()
        float(jnp.sum(chain(x0)[0, 0, 0].astype(jnp.float32)))
        return (time.perf_counter() - t0) / iters

    for name, fn in backends.items():
        fwd = jax.jit(lambda qq, fn=fn: fn(qq, k, v))
        dt = timed_chain(lambda qq: fwd(qq), q)
        emit(f"attention_fwd_{name}", cfg, dt * 1e3, "ms")
        emit(f"attention_fwd_{name}_tflops", cfg,
             flops_fwd / dt / 1e12, "TFLOP/s")

        if name.startswith("flash_chunked"):
            continue  # fwd-only cost model (no VJP on the chunk helper)

        grad = jax.jit(jax.grad(
            lambda qq, kk, vv, fn=fn: jnp.sum(
                fn(qq, kk, vv).astype(jnp.float32)), argnums=(0, 1, 2)))
        # Full backward: differentiate w.r.t. q, k AND v (grad through q
        # alone would let XLA dead-code-eliminate the dk/dv work) and chain
        # through the sum of all three so none is pruned; tanh keeps the
        # timed programs NaN/inf-free.
        def bwd_step(qq):
            dq, dk, dv = grad(qq, k, v)
            return jnp.tanh(dq + dk + dv)

        dt = timed_chain(bwd_step, q)
        emit(f"attention_fwdbwd_{name}", cfg, dt * 1e3, "ms")
        emit(f"attention_fwdbwd_{name}_tflops", cfg,
             2.5 * flops_fwd / dt / 1e12, "TFLOP/s")


if __name__ == "__main__":
    main()
