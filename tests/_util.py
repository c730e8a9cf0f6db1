"""Shared test helpers (imported as ``_util`` — conftest adds tests/ to
sys.path via rootdir)."""

import socket


def free_port() -> int:
    """An ephemeral localhost port (bound momentarily, then released)."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def xplane_events(trace_dir) -> dict:
    """{name: [(line index, start_ns, duration_ns, stats)]} of the program's
    spans (``host:`` / ``rl:``) on the host planes of the newest xplane
    under ``trace_dir``."""
    import glob
    import os

    import jax

    path = max(glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                      "*", "*.xplane.pb")),
               key=os.path.getmtime)
    events: dict = {}
    n = 0
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            n += 1
            for ev in line.events:
                if ev.name.startswith(("host:", "rl:")):
                    events.setdefault(ev.name, []).append(
                        (n, ev.start_ns, ev.duration_ns, dict(ev.stats)))
    return events


def burn_cpu(seconds: float) -> None:
    """Keep the calling thread on a CPU for ``seconds`` of ITS CPU time."""
    import time

    end = time.thread_time() + seconds
    while time.thread_time() < end:
        sum(range(1000))


def zmq_addr_pair() -> tuple[dict, dict]:
    """``(server_addrs, agent_addrs)`` for one zmq plane on fresh ephemeral
    ports: the server binds ``model_pub_addr``, agents (and relays, as
    their upstream) subscribe to it as ``model_sub_addr``."""
    server = {
        "agent_listener_addr": f"tcp://127.0.0.1:{free_port()}",
        "trajectory_addr": f"tcp://127.0.0.1:{free_port()}",
        "model_pub_addr": f"tcp://127.0.0.1:{free_port()}",
    }
    agent = {"agent_listener_addr": server["agent_listener_addr"],
             "trajectory_addr": server["trajectory_addr"],
             "model_sub_addr": server["model_pub_addr"]}
    return server, agent


# Float model outputs (``logp_a``, ``v``) of two differently shaped XLA
# programs — a batched dispatch against a single one, a vmapped host against
# a plain actor — agree to float32 rounding, not bit for bit: XLA promises no
# bit equality across shapes, and these tests have read a last-place
# difference (-1.0039216 vs -1.0039217, one ulp = 1.2e-7 relative). A few ulp
# of an O(1) float32: rtol 1e-6, and atol 1e-6 for values that cancel towards
# zero from O(1) terms. Never looser. Everything that is not a float model
# output — actions, rng keys, observations, rewards, flags, framing, seq and
# dedup accounting — compares exactly.
MODEL_OUTPUT_RTOL = 1e-6
MODEL_OUTPUT_ATOL = 1e-6


def assert_aux_equal(got, want, what=""):
    """One aux value of an action record: floats under the tolerance above,
    anything else (ints, bools, bytes, strings) exactly."""
    import numpy as np

    got_a, want_a = np.asarray(got), np.asarray(want)
    assert got_a.dtype == want_a.dtype and got_a.shape == want_a.shape, what
    if want_a.dtype.kind == "f":
        np.testing.assert_allclose(got_a, want_a, rtol=MODEL_OUTPUT_RTOL,
                                   atol=MODEL_OUTPUT_ATOL, err_msg=str(what))
    else:
        assert np.array_equal(got_a, want_a), what


def assert_episode_payloads_match(got: bytes, want: bytes, what=""):
    """Two serialized episodes decode to the same records: same length, every
    field but the float aux values exactly equal (observations, actions,
    masks, rewards, done / truncated / reward_updated flags, aux keys and
    integer aux), float aux values under the model-output tolerance."""
    import numpy as np

    from relayrl_tpu.types.trajectory import deserialize_actions

    got_recs, want_recs = deserialize_actions(got), deserialize_actions(want)
    assert len(got_recs) == len(want_recs), what
    for t, (g, w) in enumerate(zip(got_recs, want_recs)):
        at = (what, t)
        for field in ("obs", "act", "mask"):
            gv, wv = getattr(g, field), getattr(w, field)
            assert (gv is None) == (wv is None), (at, field)
            if wv is not None:
                gv, wv = np.asarray(gv), np.asarray(wv)
                assert gv.dtype == wv.dtype and np.array_equal(gv, wv), \
                    (at, field)
        assert (g.rew, g.done, g.truncated, g.reward_updated) == \
            (w.rew, w.done, w.truncated, w.reward_updated), at
        assert set(g.data or {}) == set(w.data or {}), at
        for k in (w.data or {}):
            assert_aux_equal(g.data[k], w.data[k], (at, k))


def band_attention_oracle(q, k, v, window):
    """Softmax attention under the sliding-window mask (query t sees keys
    ``t - window < s <= t``), written out from positions: the anchor of the
    windowed ops and kernels, nothing of theirs (not even their mask).
    ``q [B, T, H, D]``, ``k`` / ``v`` ``[B, T, Hkv, D]``, grouped k/v
    repeated."""
    import jax
    import jax.numpy as jnp

    k, v = (jnp.repeat(a, q.shape[2] // k.shape[2], axis=2) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (q.shape[-1] ** 0.5)
    back = jnp.arange(q.shape[1])[:, None] - jnp.arange(k.shape[1])[None, :]
    s = jnp.where((back >= 0) & (back < window), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


def kernel_calls(jaxpr) -> list:
    """``[(kernel name, number of results)]`` of every ``pallas_call`` in a
    jaxpr, inner jaxprs included."""
    import jax

    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append((eqn.params["name"], len(eqn.outvars)))
        for inner in jax.core.jaxprs_in_params(eqn.params):
            found += kernel_calls(inner)
    return found


def without_symbol_counters(text: str) -> str:
    """A lowered module's text less the counters the lowering gives its
    private functions (``@_where_38`` -> ``@_where``): they move with what a
    process lowered before and with a ``checkpoint_name`` no policy lists,
    not with what the program is."""
    import re

    return re.sub(r"@(\w+?)_\d+\b", r"@\1", text)
