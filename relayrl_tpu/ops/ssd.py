"""The Mamba-2 state-space recurrence in its chunked ("SSD") form.

Per head ``h`` (``H`` heads of width ``P``, a state of ``N`` columns, the
``G`` groups of ``B`` and ``C`` shared by ``H / G`` heads each, head ``h``
reading group ``h // (H / G)``)::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        S  [P, N]
    y_t = S_t C_t + D x_t

with ``dt_t > 0`` one step size a head and token, ``A < 0`` and ``D`` one
scalar a head. Nothing else under ``ops/`` recurs over T
(:mod:`relayrl_tpu.ops.recurrence` is V-trace's scalar recursion).

:func:`ssd` evaluates it a chunk of ``chunk`` tokens at a time (Dao & Gu
2024, "Transformers are SSMs", the block decomposition of section 6):

* **inside a chunk**, the quadratic form: ``y_i += sum_{j <= i} (C_i . B_j)
  exp(cs_i - cs_j) dt_j x_j`` with ``cs`` the running sum of ``dt A``
  inside the chunk — ``[chunk, chunk]`` score tiles (``C B^T`` a group,
  scores times ``x`` a head), the work the MXU takes;
* **a chunk's own state**, ``sum_j exp(cs_last - cs_j) dt_j x_j (x) B_j``,
  one matmul a head over the chunk;
* **across chunks**, ``S_{c+1} = exp(cs_last,c) S_c + state_c``: a
  multiply-add on the heads' ``[P, N]`` states, the only part sequential in
  T (64 steps at T 8192), no Python loop over the chunks in the trace;
* **the carried state's part**, ``y_i += exp(cs_i) (S_c C_i)``, one matmul
  a head.

Exponentials are taken of non-positive sums only: ``cs_i - cs_j`` is masked
to ``-inf`` above the diagonal BEFORE the exponential, so nothing overflows
whatever ``dt A`` is, and a decay that underflows is the zero it stands
for. Step sizes, ``A``, the sums, the decays and the carried state are
float32; the matmuls' operands are ``x``'s dtype (the compute dtype) with
float32 accumulation, as the flash kernels round their probabilities.

``T`` need not be a multiple of ``chunk``: the call pads on the right with
``dt = 0`` rows, which leave the state as it is (decay 1, nothing added),
so ``last_state`` is the state after the real rows. Right-padded episodes
need nothing: the recurrence is causal, a real row never sees a later one
(``tests/test_ssd.py``). A caller that wants the state after its first
``n`` rows zeroes ``dt`` from row ``n`` on (``models/layers/mamba2.py``'s
prefill).

**Two forms of the same five lines, picked by what the code can observe**
(:func:`backend`; no arch key, no environment variable, no switch):

* ``ssd_pallas`` — on a TPU, for shapes that tile (eight heads of a group
  a grid step, whole heads a 128-lane block, a chunk of 128 or 256, a state
  of whole lane tiles: :func:`relayrl_tpu.ops.ssd_pallas.fits`) and at
  least one whole chunk of rows: the Pallas kernels of
  :mod:`relayrl_tpu.ops.ssd_pallas`, a grid over (sequence, eight heads,
  chunk) with the chunk axis sequential, the score tiles and the carried
  state in VMEM, a hand-written backward
  (``jax.custom_vjp``: the chunk-start states made again by one kernel, the
  reverse sweep by another). ``nemotron-twotower-policy.update`` runs them
  (PERF.md section 6, PR 40: 91.9 ms an update of plain XLA at 5.4 % of its
  roofline before them).
* ``ssd_xla`` (:func:`ssd_xla`) — everywhere else (CPU actor hosts, CI, a
  shape that does not tile, the single row ``init`` traces) and the
  reference the kernels' tests hold them to: plain XLA, every chunk at once, **one group a step** of a ``lax.map``
  (a group's ``H / G`` heads together with the group's own ``B`` and
  ``C``: the score tiles of every head and chunk and the per-chunk states
  are 0.27 GB each in bfloat16 and 0.27 to 0.54 GB in float32 a layer at
  16,384 tokens, and a dozen such arrays live at once in the backward; a
  group's are an eighth), the chunks' states carried by ONE ``lax.scan``,
  **backward by autodiff under ``jax.checkpoint``** (a group's
  intermediates are made again from its arguments, which is all the
  forward keeps).

Both sit under one named scope, ``relayrl_ssd`` (``ops/scopes.py``), and no
deeper ``relayrl_`` name: the benchmark's ``ssd_ms`` / ``ssd_roofline`` read
the exact scope. ``models/layers/mamba2.KERNELS`` records which form a
policy's scans ran as (``Policy.scan_backends``) and prints one ``[scan]``
line a shape.

:func:`ssd_step` is the recurrence's one step, what a cached decode runs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from relayrl_tpu.ops.scopes import SSD_NAME

# what a scan ran as (``backend``; ``Policy.scan_backends``)
PALLAS, XLA = "ssd_pallas", "ssd_xla"


@functools.partial(jax.checkpoint, static_argnums=(1,))
def _group(args, chunk: int):
    """One group's heads over whole chunks (``T % chunk == 0``): ``x [b, T,
    R, P]``, ``dt [b, T, R]`` float32, ``A, D [R]``, the group's ``B, C [b,
    T, N]``, ``state [b, R, P, N]`` float32 -> ``(y, last state)``."""
    x, dt, A, B, C, D, state = args
    b, T, R, P = x.shape
    N = B.shape[-1]
    c, cd, f32 = T // chunk, x.dtype, jnp.float32
    mm = functools.partial(jnp.einsum, preferred_element_type=f32)

    # z = (sequence, chunk)
    xc = x.reshape(b * c, chunk, R, P)
    dtc = dt.reshape(b * c, chunk, R)
    Bc, Cc = (a.reshape(b * c, chunk, N) for a in (B, C))
    # cs[z, r, i]: the sum of dt A over the chunk's rows 0..i (<= 0)
    cs = jnp.cumsum(dtc * A, axis=1).transpose(0, 2, 1)
    xdt = xc.astype(f32) * dtc[..., None]               # dt_j x_j

    # inside a chunk: (C_i . B_j) exp(cs_i - cs_j) on and under the diagonal
    cb = mm("zin,zjn->zij", Cc, Bc)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(causal, cs[..., :, None] - cs[..., None, :],
                              -jnp.inf))
    y = mm("zrij,zjrp->zirp", (cb[:, None] * decay).astype(cd),
           xdt.astype(cd))

    # a chunk's own state, as it stands after the chunk's last row
    to_end = jnp.exp(cs[..., -1:] - cs).transpose(0, 2, 1)      # [z, j, r]
    own = mm("zjrp,zjn->zrpn", (xdt * to_end[..., None]).astype(cd), Bc)

    # across chunks: the state each chunk starts from
    def carry(s, step):
        through, own_c = step
        return through[..., None, None] * s + own_c, s

    def by_chunk(a):  # [b * c, ...] -> [c, b, ...]
        return jnp.moveaxis(a.reshape((b, c) + a.shape[1:]), 1, 0)

    last, start = jax.lax.scan(
        carry, state, (by_chunk(jnp.exp(cs[..., -1])), by_chunk(own)))
    start = jnp.moveaxis(start, 0, 1).reshape(own.shape)
    carried = mm("zin,zrpn->zirp", Cc, start.astype(cd))
    y = y + carried * jnp.exp(cs).transpose(0, 2, 1)[..., None]

    y = y.reshape(b, T, R, P) + D[:, None] * x.astype(f32)
    return y.astype(cd), last


def ssd_xla(x, dt, A, B, C, D, chunk: int = 128, state=None):
    """:func:`ssd` as plain XLA, one group a step of a ``lax.map``: every
    backend takes it, and the kernels' tests hold them to it."""
    with jax.named_scope(SSD_NAME):
        b, T, H, P = x.shape
        G, N = B.shape[2:]
        R, f32 = H // G, jnp.float32
        if state is None:
            state = jnp.zeros((b, H, P, N), f32)
        pad = -T % chunk
        if pad:
            x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (
                a.ndim - 2)) for a in (x, dt, B, C))
        # group g's heads (g R .. g R + R - 1) with group g's B and C, the
        # group axis in front: one group a step of the map
        by_group = (
            jnp.moveaxis(x.reshape(b, T + pad, G, R, P), 2, 0),
            jnp.moveaxis(dt.astype(f32).reshape(b, T + pad, G, R), 2, 0),
            A.astype(f32).reshape(G, R),
            jnp.moveaxis(B, 2, 0), jnp.moveaxis(C, 2, 0),
            D.astype(f32).reshape(G, R),
            jnp.moveaxis(state.astype(f32).reshape(b, G, R, P, N), 1, 0))
        y, last = jax.lax.map(lambda group: _group(group, chunk), by_group)
        y = jnp.moveaxis(y, 0, 2).reshape(b, T + pad, H, P)
        return y[:, :T], jnp.moveaxis(last, 0, 1).reshape(b, H, P, N)


def backend(T: int, H: int, P: int, G: int, N: int, chunk: int) -> str:
    """``"ssd_pallas"`` or ``"ssd_xla"``: what :func:`ssd` runs a scan of
    these shapes as on this process's platform. The kernels on a TPU where
    the shapes tile (``ssd_pallas.fits``) and there is a whole chunk of
    rows, plain XLA everywhere else — CPU actor hosts, CI, a shape that does
    not tile, a scan shorter than a chunk (the one row ``init`` traces: the
    kernels' grid would be one step of padding, and their lowering a second
    of every process's start). Platform and shape decide, nothing else: no
    arch key, no environment variable."""
    if jax.default_backend() != "tpu" or T < chunk:
        return XLA
    from relayrl_tpu.ops import ssd_pallas

    return PALLAS if ssd_pallas.fits(H, P, G, N, chunk) else XLA


def ssd(x, dt, A, B, C, D, chunk: int = 128, state=None):
    """``x [b, T, H, P]``, step sizes ``dt [b, T, H]`` (positive, as they
    enter the recurrence), ``A [H]`` (negative), ``B, C [b, T, G, N]``,
    ``D [H]``, ``state [b, H, P, N]`` float32 (None: zeros, a sequence's
    start) -> ``(y [b, T, H, P]`` in ``x``'s dtype, ``last_state [b, H, P,
    N]`` float32``)``, as :func:`backend` says."""
    if backend(*x.shape[1:], *B.shape[2:], chunk) == PALLAS:
        from relayrl_tpu.ops.ssd_pallas import ssd_pallas

        return ssd_pallas(x, dt, A, B, C, D, chunk, state)
    return ssd_xla(x, dt, A, B, C, D, chunk, state)


def ssd_step(x, dt, A, B, C, D, state):
    """One step of the recurrence, what :func:`ssd` computes at ``T = 1``:
    ``x [b, H, P]``, ``dt [b, H]``, ``B, C [b, G, N]``, ``state [b, H, P,
    N]`` float32 -> ``(y [b, H, P], new state)``."""
    with jax.named_scope(SSD_NAME):
        f32 = jnp.float32
        rep = x.shape[1] // B.shape[1]
        Bh, Ch = (jnp.repeat(a.astype(f32), rep, axis=1) for a in (B, C))
        dt = dt.astype(f32)
        xf = x.astype(f32)
        state = (jnp.exp(dt * A.astype(f32))[..., None, None] * state
                 + (dt[..., None] * xf)[..., None] * Bh[:, :, None])
        y = jnp.einsum("bhpn,bhn->bhp", state, Ch) + D.astype(f32)[
            :, None] * xf
        return y.astype(x.dtype), state
