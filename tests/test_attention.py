"""Attention ops + ring attention (sp) + transformer policy tests.

Ring attention runs on the 8-virtual-CPU-device mesh from conftest; the
correctness anchor is dense attention on the unsharded sequence.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _util import band_attention_oracle
from relayrl_tpu.models import build_policy, validate_policy
from relayrl_tpu.ops.attention import blockwise_attention, dense_attention
from relayrl_tpu.parallel import (
    make_mesh,
    make_ring_attention,
    make_ring_flash_attention,
    use_mesh,
)

B, T, H, D = 2, 32, 4, 16


def _qkv(seed=0, t=T):
    rng = np.random.default_rng(seed)
    shape = (B, t, H, D)
    return tuple(
        jnp.asarray(rng.standard_normal(shape), jnp.float32) for _ in range(3)
    )


class TestDenseAttention:
    def test_causal_ignores_future(self):
        q, k, v = _qkv()
        out = dense_attention(q, k, v, causal=True)
        # Changing the future of the KV stream must not change position t.
        k2 = k.at[:, T // 2:].set(99.0)
        v2 = v.at[:, T // 2:].set(-99.0)
        out2 = dense_attention(q, k2, v2, causal=True)
        np.testing.assert_allclose(
            out[:, : T // 2], out2[:, : T // 2], rtol=1e-6)
        assert not np.allclose(out[:, T // 2:], out2[:, T // 2:])

    def test_first_position_is_v0(self):
        q, k, v = _qkv()
        out = dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out[:, 0], v[:, 0], rtol=1e-5)


class TestCachedAttention:
    """``ops.attention.cached_attention``: a cache's flat rows ``[B, L,
    Hkv * D]`` read without splitting the lanes into heads (one query row)
    against :func:`dense_attention` over the same rows split into heads."""

    @staticmethod
    def _rows(kv_heads, length=12, v_width=D, seed=0):
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, length, kv_heads, D)),
                        jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, length, kv_heads, v_width)),
                        jnp.float32)
        return q, k, v

    @pytest.mark.parametrize("kv_heads", [H, 2, 1])
    @pytest.mark.parametrize("t", [0, 5, 11])
    def test_one_row_equals_dense_attention(self, kv_heads, t):
        from relayrl_tpu.ops.attention import cached_attention

        q, k, v = self._rows(kv_heads)
        want = dense_attention(q, k, v, causal=True, q_offset=t)
        got = cached_attention(q, k.reshape(B, 12, -1), v.reshape(B, 12, -1),
                               kv_heads, q_offset=t)
        assert got.shape == want.shape == (B, 1, H, D)
        np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)

    def test_a_ring_s_positions_and_window(self):
        from relayrl_tpu.ops.attention import cached_attention

        q, k, v = self._rows(2, length=8)
        # a ring of 8 rows at step 10 under a window of 5: row s holds the
        # newest position <= 10 congruent to s
        pos = 10 - jnp.mod(10 - jnp.arange(8), 8)
        kw = dict(q_offset=10, window=5, kv_positions=pos)
        want = dense_attention(q, k, v, causal=True, **kw)
        got = cached_attention(q, k.reshape(B, 8, -1), v.reshape(B, 8, -1),
                               2, **kw)
        np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
        empty = jnp.where(jnp.arange(8) > 2, -1, jnp.arange(8))
        got = cached_attention(q, k.reshape(B, 8, -1), v.reshape(B, 8, -1),
                               2, q_offset=2, kv_positions=empty)
        want = dense_attention(q, k[:, :3], v[:, :3], causal=True,
                               q_offset=2)
        np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)

    def test_values_of_a_width_of_their_own_and_a_prefill(self):
        from relayrl_tpu.ops.attention import cached_attention

        q, k, v = self._rows(2, v_width=8)
        got = cached_attention(q, k.reshape(B, 12, -1), v.reshape(B, 12, -1),
                               2, q_offset=7)
        np.testing.assert_allclose(
            got, dense_attention(q, k, v, causal=True, q_offset=7),
            atol=2e-6, rtol=0)
        # more rows than one: dense_attention itself, over the split rows
        rng = np.random.default_rng(3)
        q4 = jnp.asarray(rng.standard_normal((B, 12, H, D)), jnp.float32)
        got = cached_attention(q4, k.reshape(B, 12, -1),
                               v.reshape(B, 12, -1), 2)
        assert (got == dense_attention(q4, k, v, causal=True)).all()

    def test_masked_rows_are_not_read(self):
        """Rows after ``t`` may hold anything finite (another episode's
        tail: the fused rollout does not zero a cache at a reset), and a
        NaN key there is never read."""
        from relayrl_tpu.ops.attention import cached_attention

        q, k, v = self._rows(2)
        k_rows, v_rows = k.reshape(B, 12, -1), v.reshape(B, 12, -1)
        want = cached_attention(q, k_rows, v_rows, 2, q_offset=4)
        got = cached_attention(q, k_rows.at[:, 5:].set(jnp.nan),
                               v_rows.at[:, 5:].set(1e30), 2, q_offset=4)
        assert (got == want).all()


class TestBlockwiseAttention:
    @pytest.mark.parametrize("block", [4, 8, 32])
    def test_matches_dense(self, block):
        q, k, v = _qkv()
        ref = dense_attention(q, k, v, causal=True)
        out = blockwise_attention(q, k, v, block_size=block, causal=True)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)

    def test_non_causal(self):
        q, k, v = _qkv()
        ref = dense_attention(q, k, v, causal=False)
        out = blockwise_attention(q, k, v, block_size=8, causal=False)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)

    def test_rejects_ragged_blocks(self):
        q, k, v = _qkv()
        with pytest.raises(ValueError, match="not divisible"):
            blockwise_attention(q, k, v, block_size=5)

    def test_grad_matches_dense(self):
        q, k, v = _qkv(3)

        def loss_dense(q, k, v):
            return jnp.sum(dense_attention(q, k, v) ** 2)

        def loss_block(q, k, v):
            return jnp.sum(blockwise_attention(q, k, v, block_size=8) ** 2)

        g_ref = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        g_blk = jax.grad(loss_block, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ref, g_blk):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


class TestRingAttention:
    @pytest.mark.parametrize("spec", [
        {"dp": 1, "sp": 8}, {"dp": 2, "sp": 4}, {"dp": 1, "sp": 2},
    ])
    def test_matches_dense(self, spec):
        n = spec.get("dp", 1) * spec.get("sp", 1)
        mesh = make_mesh({**{"dp": 1, "fsdp": 1, "tp": 1, "sp": 1}, **spec},
                         jax.devices()[:n])
        q, k, v = _qkv()
        ref = dense_attention(q, k, v, causal=True)
        out = jax.jit(make_ring_attention(mesh))(q, k, v)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)

    def test_non_causal_matches(self):
        mesh = make_mesh({"dp": 1, "fsdp": 1, "tp": 1, "sp": 4},
                         jax.devices()[:4])
        q, k, v = _qkv(1)
        ref = dense_attention(q, k, v, causal=False)
        out = jax.jit(make_ring_attention(mesh, causal=False))(q, k, v)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)

    def test_grad_flows_through_ring(self):
        mesh = make_mesh({"dp": 1, "fsdp": 1, "tp": 1, "sp": 4},
                         jax.devices()[:4])
        q, k, v = _qkv(2)
        ring = make_ring_attention(mesh)

        def loss_ring(q, k, v):
            return jnp.sum(ring(q, k, v) ** 2)

        def loss_dense(q, k, v):
            return jnp.sum(dense_attention(q, k, v) ** 2)

        g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
        g_ref = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ring, g_ref):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


class TestRingFlashAttention:
    """The Pallas-chunk ring (parallel/ring_flash.py), interpret mode on
    the CPU mesh; anchors are dense attention on the unsharded sequence
    and the scan ring it accelerates."""

    @pytest.mark.parametrize("spec", [
        {"dp": 1, "sp": 2}, {"dp": 2, "sp": 4}, {"dp": 1, "sp": 4},
    ])
    def test_matches_dense(self, spec):
        n = spec.get("dp", 1) * spec.get("sp", 1)
        mesh = make_mesh({**{"dp": 1, "fsdp": 1, "tp": 1, "sp": 1}, **spec},
                         jax.devices()[:n])
        q, k, v = _qkv(t=64)  # chunk of 64/sp tiles by 8
        ref = dense_attention(q, k, v, causal=True)
        out = jax.jit(make_ring_flash_attention(mesh, interpret=True))(
            q, k, v)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)

    def test_non_causal_matches(self):
        mesh = make_mesh({"dp": 1, "fsdp": 1, "tp": 1, "sp": 4},
                         jax.devices()[:4])
        q, k, v = _qkv(1, t=64)
        ref = dense_attention(q, k, v, causal=False)
        out = jax.jit(make_ring_flash_attention(
            mesh, causal=False, interpret=True))(q, k, v)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)

    def test_matches_scan_ring(self):
        mesh = make_mesh({"dp": 1, "fsdp": 1, "tp": 1, "sp": 4},
                         jax.devices()[:4])
        q, k, v = _qkv(2, t=64)
        scan = jax.jit(make_ring_attention(mesh))(q, k, v)
        flash = jax.jit(make_ring_flash_attention(mesh, interpret=True))(
            q, k, v)
        np.testing.assert_allclose(flash, scan, rtol=1e-5, atol=1e-6)

    def test_grad_matches_dense(self):
        mesh = make_mesh({"dp": 1, "fsdp": 1, "tp": 1, "sp": 4},
                         jax.devices()[:4])
        q, k, v = _qkv(3, t=64)
        ring = make_ring_flash_attention(mesh, interpret=True)

        g_ring = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(ring(q, k, v) ** 2),
            argnums=(0, 1, 2)))(q, k, v)
        g_ref = jax.grad(
            lambda q, k, v: jnp.sum(dense_attention(q, k, v) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ring, g_ref):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("causal,n", [(True, 2), (True, 4), (False, 2)])
    def test_chunked_local_matches_dense(self, causal, n):
        # The single-device ring cost model must agree with dense — it
        # runs the exact chunk kernels and mode schedule the sharded
        # ring uses.
        from relayrl_tpu.parallel.ring_flash import chunked_flash_local

        q, k, v = _qkv(4, t=64)
        ref = dense_attention(q, k, v, causal=causal)
        out = jax.jit(lambda q, k, v: chunked_flash_local(
            q, k, v, n_chunks=n, causal=causal, interpret=True))(q, k, v)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)

    def test_untileable_chunk_raises(self):
        # T=32 over sp=8 leaves 4-row chunks (< the 8-row tile): the
        # builder must refuse so callers fall back to the scan ring (the
        # transformer "ring" path checks pick_chunk_block first).
        from relayrl_tpu.parallel.ring_flash import pick_chunk_block

        assert pick_chunk_block(4) is None
        assert pick_chunk_block(64) == 64
        assert pick_chunk_block(3 * 8) == 8
        assert pick_chunk_block(4096) == 1024
        mesh = make_mesh({"dp": 1, "fsdp": 1, "tp": 1, "sp": 8},
                         jax.devices()[:8])
        q, k, v = _qkv()  # T=32
        with pytest.raises(Exception, match="does not tile"):
            jax.jit(make_ring_flash_attention(mesh, interpret=True))(q, k, v)


ARCH = {
    "kind": "transformer_discrete",
    "obs_dim": 8,
    "act_dim": 5,
    "d_model": 32,
    "n_layers": 2,
    "n_heads": 2,
    "max_seq_len": 64,
    "has_critic": True,
}


class TestTransformerPolicy:
    def test_abi_validates(self):
        policy = build_policy(ARCH)
        params = policy.init_params(jax.random.PRNGKey(0))
        validate_policy(policy, params)

    def test_evaluate_shapes(self):
        policy = build_policy(ARCH)
        params = policy.init_params(jax.random.PRNGKey(0))
        obs = jnp.zeros((3, 16, 8))
        act = jnp.zeros((3, 16), jnp.int32)
        logp, ent, v = policy.evaluate(params, obs, act)
        assert logp.shape == ent.shape == v.shape == (3, 16)

    def test_evaluate_single_transition(self):
        """evaluate on a bare [D] obs + scalar act returns scalars (the
        [..., obs_dim] contract of the Policy ABI)."""
        policy = build_policy(ARCH)
        params = policy.init_params(jax.random.PRNGKey(0))
        logp, ent, v = policy.evaluate(
            params, jnp.zeros((8,)), jnp.int32(1))
        assert logp.shape == ent.shape == v.shape == ()

    def test_step_uses_history(self):
        """Same final obs, different history => different logits."""
        policy = build_policy(ARCH)
        params = policy.init_params(jax.random.PRNGKey(0))
        rng = jax.random.PRNGKey(1)
        obs_a = jnp.zeros((8, 8)).at[-1].set(1.0)
        obs_b = jnp.ones((8, 8)).at[-1].set(1.0)
        _, aux_a = policy.step(params, rng, obs_a)
        _, aux_b = policy.step(params, rng, obs_b)
        assert not np.allclose(aux_a["v"], aux_b["v"])

    def test_action_mask_respected(self):
        policy = build_policy(ARCH)
        params = policy.init_params(jax.random.PRNGKey(0))
        obs = jnp.ones((4, 8))
        mask = jnp.zeros((4, 5)).at[:, 2].set(1.0)
        for seed in range(5):
            act, _ = policy.step(params, jax.random.PRNGKey(seed), obs, mask)
            assert int(act) == 2

    @pytest.mark.parametrize("attention", ["blockwise", "ring"])
    def test_attention_variants_match_dense(self, attention):
        """All backends define the same function on one device."""
        dense = build_policy({**ARCH, "attention": "dense"})
        other = build_policy(
            {**ARCH, "attention": attention, "attention_block": 8})
        params = dense.init_params(jax.random.PRNGKey(0))
        obs = jnp.asarray(
            np.random.default_rng(0).standard_normal((2, 16, 8)), jnp.float32)
        act = jnp.zeros((2, 16), jnp.int32)
        ref = dense.evaluate(params, obs, act)
        out = other.evaluate(params, obs, act)
        for a, b in zip(ref, out):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)

    def test_sequence_parallel_reinforce_update(self):
        """Full REINFORCE epoch update with a ring-attention transformer,
        compiled over a dp=2 x sp=4 mesh with the time axis sharded, matches
        the single-device dense-attention update."""
        import optax

        from relayrl_tpu.algorithms.reinforce import (
            ReinforceState,
            make_optimizers,
            make_reinforce_update,
        )
        from relayrl_tpu.parallel import (
            make_sharded_update,
            place_batch,
            place_state,
        )

        mesh = make_mesh({"dp": 2, "fsdp": 1, "tp": 1, "sp": 4},
                         jax.devices()[:8])
        dense = build_policy({**ARCH, "attention": "dense"})
        ring = build_policy({**ARCH, "attention": "ring"})
        params = dense.init_params(jax.random.PRNGKey(0))
        tx_pi, tx_vf = make_optimizers(params, 3e-4, 1e-3)
        state = ReinforceState(
            params=params, pi_opt_state=tx_pi.init(params),
            vf_opt_state=tx_vf.init(params), rng=jax.random.PRNGKey(1),
            step=jnp.int32(0))

        rng = np.random.default_rng(0)
        Bb, Tt = 4, 16
        batch = {
            "obs": rng.standard_normal((Bb, Tt, 8)).astype(np.float32),
            "act": rng.integers(0, 5, (Bb, Tt)).astype(np.int32),
            "act_mask": np.ones((Bb, Tt, 5), np.float32),
            "rew": rng.standard_normal((Bb, Tt)).astype(np.float32),
            "val": np.zeros((Bb, Tt), np.float32),
            "logp": np.zeros((Bb, Tt), np.float32),
            "valid": np.ones((Bb, Tt), np.float32),
            "last_val": np.zeros((Bb,), np.float32),
        }

        def make(policy):
            return make_reinforce_update(
                policy, pi_lr=3e-4, vf_lr=1e-3, train_vf_iters=2,
                gamma=0.99, lam=0.95, with_baseline=True)

        ref_state, ref_metrics = jax.jit(make(dense))(
            state, {k: jnp.asarray(v) for k, v in batch.items()})

        sharded = make_sharded_update(make(ring), mesh, state,
                                      donate_state=False, shard_time=True)
        out_state, out_metrics = sharded(
            place_state(state, mesh),
            place_batch(batch, mesh, shard_time=True))

        for key in ref_metrics:
            np.testing.assert_allclose(
                float(out_metrics[key]), float(ref_metrics[key]),
                rtol=1e-3, atol=1e-5, err_msg=key)
        assert int(out_state.step) == 1

    def test_ring_policy_under_mesh(self):
        """transformer evaluate with attention=ring inside an sp mesh,
        jitted, matches the dense single-device result."""
        mesh = make_mesh({"dp": 2, "fsdp": 1, "tp": 1, "sp": 4},
                         jax.devices()[:8])
        dense = build_policy({**ARCH, "attention": "dense"})
        ring = build_policy({**ARCH, "attention": "ring"})
        params = dense.init_params(jax.random.PRNGKey(0))
        obs = jnp.asarray(
            np.random.default_rng(1).standard_normal((2, 16, 8)), jnp.float32)
        act = jnp.zeros((2, 16), jnp.int32)
        ref = dense.evaluate(params, obs, act)
        with use_mesh(mesh):
            out = jax.jit(ring.evaluate)(params, obs, act)
        for a, b in zip(ref, out):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
        # ...and each policy says what its attention actually ran as.
        assert set(dense.attention_backends.values()) == {"dense"}
        assert "ring_scan" in ring.attention_backends.values()


class TestStepWindow:
    """Actor-side history window (train/serve context parity fix)."""

    def test_padded_window_matches_unpadded_sequence(self):
        # Right-zero padding past t must be inert: causal attention at the
        # readout position t-1 never attends positions >= t.
        policy = build_policy(ARCH)
        params = policy.init_params(jax.random.PRNGKey(0))
        rng = np.random.default_rng(3)
        t, W = 5, 12
        seq = rng.standard_normal((t, 8)).astype(np.float32)
        window = np.zeros((W, 8), np.float32)
        window[:t] = seq
        key = jax.random.PRNGKey(7)
        act_w, aux_w = policy.step_window(params, key, window, t)
        act_s, aux_s = policy.step(params, key, seq)
        assert int(act_w) == int(act_s)
        np.testing.assert_allclose(float(aux_w["logp_a"]),
                                   float(aux_s["logp_a"]), rtol=1e-5)
        np.testing.assert_allclose(float(aux_w["v"]), float(aux_s["v"]),
                                   rtol=1e-5)

    def test_actor_serves_with_context(self):
        from relayrl_tpu.runtime.policy_actor import PolicyActor
        from relayrl_tpu.types.model_bundle import ModelBundle

        policy = build_policy({**ARCH, "actor_context": 8})
        params = policy.init_params(jax.random.PRNGKey(0))
        actor = PolicyActor(ModelBundle(version=1, arch={**ARCH,
                                                         "actor_context": 8},
                                        params=params))
        rng = np.random.default_rng(0)
        for i in range(11):  # overflow the 8-window: rolling path runs
            actor.request_for_action(rng.standard_normal(8))
        assert actor._window_len == 8
        # Window holds the newest observations, oldest dropped.
        actor.flag_last_action(0.0, terminated=True)
        assert actor._window_len == 0 and not actor._window.any()

    def test_history_changes_action_distribution(self):
        # Same current obs, different history -> different logp through
        # the actor path (context is actually used at serving time).
        policy = build_policy(ARCH)
        params = policy.init_params(jax.random.PRNGKey(0))
        key = jax.random.PRNGKey(5)
        obs = np.ones((8,), np.float32)
        W = 16
        w1 = np.zeros((W, 8), np.float32)
        w2 = np.zeros((W, 8), np.float32)
        w1[0], w1[1] = 1.0, obs
        w2[0], w2[1] = -3.0, obs
        _, aux1 = policy.step_window(params, key, w1, 2)
        _, aux2 = policy.step_window(params, key, w2, 2)
        assert abs(float(aux1["v"]) - float(aux2["v"])) > 1e-6

    def test_actor_context_exceeding_model_rejected(self):
        from relayrl_tpu.runtime.policy_actor import PolicyActor
        from relayrl_tpu.types.model_bundle import ModelBundle

        import pytest

        arch = {**ARCH, "actor_context": ARCH["max_seq_len"] + 1}
        policy = build_policy(arch)
        params = policy.init_params(jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="max_seq_len"):
            PolicyActor(ModelBundle(version=1, arch=arch, params=params))

    def test_deterministic_action_uses_window(self):
        from relayrl_tpu.runtime.policy_actor import PolicyActor
        from relayrl_tpu.types.model_bundle import ModelBundle

        policy = build_policy(ARCH)
        params = policy.init_params(jax.random.PRNGKey(0))
        actor = PolicyActor(ModelBundle(version=1, arch=dict(ARCH),
                                        params=params))
        rng = np.random.default_rng(1)
        for _ in range(3):
            actor.deterministic_action(rng.standard_normal(8))
        assert actor._window_len == 3  # greedy eval advances history too


# -- the block the arch describes: RoPE, QK-norm, RMSNorm, SwiGLU, no bias ---
# (models/arch_keys.py: BLOCK_KEYS, the operators'; every default is the GPT-2 shaped
# block, pinned in tests/test_olmoe_reference.py)

MODERN = {"norm": "rms", "norm_eps": 1e-5, "positions": "rope",
          "rope_theta": 10000.0, "qk_norm": True, "use_bias": False,
          "ffn": "swiglu", "d_ff": 48}
BLOCK_VARIANTS = {
    "rope": {"positions": "rope"},
    "rope_qknorm": {"positions": "rope", "qk_norm": True},
    "modern_dense": MODERN,
    "modern_moe": {**MODERN, "kind": "transformer_moe_discrete",
                   "moe_experts": 4, "moe_top_k": 2, "moe_d_ff": 16,
                   "moe_norm_topk_prob": False},
}


class TestRope:
    def _x(self, t=12, seed=0):
        return jnp.asarray(np.random.default_rng(seed).standard_normal(
            (2, t, 2, 16)), jnp.float32)

    @pytest.mark.parametrize("start", [0, 3, 7])
    def test_shifted_start_shifts_nothing_but_positions(self, start):
        from relayrl_tpu.models.layers.attention import apply_rope

        x = self._x()
        whole = apply_rope(x, 0, 10000.0)
        tail = apply_rope(x[:, start:], start, 10000.0)
        np.testing.assert_allclose(tail, whole[:, start:], atol=1e-5)
        # a traced start (cached decode) is the same rotation
        traced = jax.jit(lambda a, s: apply_rope(a, s, 10000.0))(
            x[:, start:], jnp.int32(start))
        np.testing.assert_allclose(traced, tail, atol=1e-5)

    def test_is_a_rotation_and_scores_are_relative(self):
        from relayrl_tpu.models.layers.attention import apply_rope

        q, k = self._x(seed=1), self._x(seed=2)
        np.testing.assert_allclose(
            jnp.linalg.norm(apply_rope(q, 5, 10000.0), axis=-1),
            jnp.linalg.norm(q, axis=-1), rtol=1e-5)

        def scores(shift):
            return jnp.einsum("bqhd,bkhd->bhqk",
                              apply_rope(q, shift, 10000.0),
                              apply_rope(k, shift, 10000.0))

        # q.k depends on the distance between the two positions only
        np.testing.assert_allclose(scores(0), scores(9), atol=2e-4)
        assert not np.allclose(
            scores(0), jnp.einsum("bqhd,bkhd->bhqk", q, k), atol=1e-2)

    def test_position_zero_is_the_identity(self):
        from relayrl_tpu.models.layers.attention import apply_rope

        x = self._x(t=1)
        np.testing.assert_allclose(apply_rope(x, 0, 10000.0), x, atol=1e-7)


class TestBlockArch:
    @pytest.mark.parametrize("name", sorted(BLOCK_VARIANTS))
    def test_parameter_tree_follows_the_arch(self, name):
        arch = {**ARCH, **BLOCK_VARIANTS[name]}
        params = build_policy(arch).init_params(
            jax.random.PRNGKey(0))["params"]
        rope = arch.get("positions") == "rope"
        assert ("pos_embed" in params) == (not rope)
        block = params["block_0"]
        assert ("q_norm" in block) == bool(arch.get("qk_norm"))
        if arch.get("norm") == "rms":
            assert set(block["ln_attn"]) == {"scale"}
            assert set(params["ln_final"]) == {"scale"}
        else:
            assert set(block["ln_attn"]) == {"scale", "bias"}
        assert ("bias" in block["qkv"]) == arch.get("use_bias", True)
        if "moe_experts" in arch:
            assert block["moe"]["moe_w_gate"].shape == (4, 32, 16)
            assert "bias" not in block["moe"]["moe_gate"]
        elif arch.get("ffn") == "swiglu":
            assert block["mlp_gate"]["kernel"].shape == (32, 48)
            assert block["mlp_down"]["kernel"].shape == (48, 32)

    @pytest.mark.parametrize("name", sorted(BLOCK_VARIANTS))
    def test_full_window_equals_readout_row(self, name):
        # the learner's full forward and the actor tiers' readout-row
        # forward place q and k at the same absolute positions
        policy = build_policy({**ARCH, **BLOCK_VARIANTS[name]})
        params = policy.init_params(jax.random.PRNGKey(1))
        rng = np.random.default_rng(4)
        W = 10
        window = rng.standard_normal((W, 8)).astype(np.float32)
        act = jnp.zeros((1, W), jnp.int32)
        logp, _, v = policy.evaluate(params, window[None], act)
        for t in (1, 4, W):
            padded = window.copy()
            padded[t:] = 0.0  # the rows after the readout are never seen
            _, aux = policy.step_window(
                params, jax.random.PRNGKey(0), padded, t)
            np.testing.assert_allclose(float(aux["v"]), float(v[0, t - 1]),
                                       atol=2e-5)

    @pytest.mark.parametrize("key,value", [
        ("norm", "batch"), ("positions", "alibi"), ("ffn", "relu")])
    def test_unknown_value_refused(self, key, value):
        with pytest.raises(ValueError, match=value):
            build_policy({**ARCH, key: value}).init_params(
                jax.random.PRNGKey(0))

    def test_pipeline_family_refuses_the_new_keys(self):
        with pytest.raises(ValueError, match="GPT-2 shaped block"):
            build_policy({**ARCH, "kind": "transformer_pp_discrete",
                          "positions": "rope"})

    def test_norm_eps_reaches_every_norm(self):
        # a large epsilon changes the output; the default is flax's 1e-6
        base = build_policy(ARCH)
        params = base.init_params(jax.random.PRNGKey(0))
        obs = 1e-3 * jnp.ones((1, 4, 8))
        act = jnp.zeros((1, 4), jnp.int32)
        same = build_policy({**ARCH, "norm_eps": 1e-6})
        other = build_policy({**ARCH, "norm_eps": 1e-2})
        np.testing.assert_array_equal(base.evaluate(params, obs, act)[2],
                                      same.evaluate(params, obs, act)[2])
        assert not np.allclose(base.evaluate(params, obs, act)[2],
                               other.evaluate(params, obs, act)[2])


class TestGroupedQueryAttention:
    """k/v with fewer heads than q: q head j reads k/v head j // G. The
    anchor is the same op on k/v repeated G times along the head axis."""

    @staticmethod
    def _grouped(h_kv, seed=3):
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
        k, v = (jnp.asarray(rng.standard_normal((B, T, h_kv, D)),
                            jnp.float32) for _ in range(2))
        rep = lambda a: jnp.repeat(a, H // h_kv, axis=2)
        return q, k, v, rep(k), rep(v)

    @pytest.mark.parametrize("h_kv", [1, 2, 4])
    @pytest.mark.parametrize("fn", ["dense", "blockwise"])
    def test_matches_repeated_kv(self, fn, h_kv):
        q, k, v, k_rep, v_rep = self._grouped(h_kv)
        attn = dense_attention if fn == "dense" else (
            lambda q, k, v: blockwise_attention(q, k, v, 8))
        np.testing.assert_allclose(attn(q, k, v),
                                   dense_attention(q, k_rep, v_rep),
                                   atol=2e-6, rtol=2e-6)

    @pytest.mark.parametrize("fn", ["dense", "blockwise"])
    def test_grads_sum_over_the_group(self, fn):
        q, k, v, k_rep, v_rep = self._grouped(2)
        attn = dense_attention if fn == "dense" else (
            lambda q, k, v: blockwise_attention(q, k, v, 8))
        loss = lambda f: lambda q, k, v: jnp.sum(jnp.sin(f(q, k, v)))
        dq, dk, dv = jax.grad(loss(attn), (0, 1, 2))(q, k, v)
        wq, wk, wv = jax.grad(loss(dense_attention), (0, 1, 2))(
            q, k_rep, v_rep)
        group = lambda a: a.reshape(B, T, 2, H // 2, D).sum(3)
        np.testing.assert_allclose(dq, wq, atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(dk, group(wk), atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(dv, group(wv), atol=1e-5, rtol=1e-5)

    def test_offset_row_reads_a_grouped_cache(self):
        # the cached / readout modes: one query row at position 5 against
        # a grouped k/v prefix
        q, k, v, k_rep, v_rep = self._grouped(2)
        row = dense_attention(q[:, 5:6], k, v, q_offset=5)
        np.testing.assert_allclose(
            row, dense_attention(q, k_rep, v_rep)[:, 5:6], atol=2e-6)

    def test_heads_that_do_not_group_are_refused(self):
        q, k, v, _, _ = self._grouped(2)
        with pytest.raises(ValueError, match="do not group"):
            dense_attention(q, k[:, :, :1].repeat(3, 2), v)


class TestSlidingWindow:
    """``window``: query t sees keys s with t - window < s <= t. The anchor
    is softmax under a mask written out from positions
    (``_util.band_attention_oracle``), nothing of the op's own."""

    _oracle = staticmethod(band_attention_oracle)

    @pytest.mark.parametrize("window", [1, 5, 8, 13, T, T + 9])
    @pytest.mark.parametrize("h_kv", [H, 1])
    @pytest.mark.parametrize("fn", ["dense", "blockwise"])
    def test_matches_the_band_mask(self, fn, h_kv, window):
        q, k, v, _, _ = TestGroupedQueryAttention._grouped(h_kv)
        attn = (dense_attention if fn == "dense" else
                lambda q, k, v, window: blockwise_attention(
                    q, k, v, 8, window=window))
        np.testing.assert_allclose(attn(q, k, v, window=window),
                                   self._oracle(q, k, v, window),
                                   atol=2e-6, rtol=2e-6)

    @pytest.mark.parametrize("fn", ["dense", "blockwise"])
    def test_grads_match_the_band_mask(self, fn):
        q, k, v, _, _ = TestGroupedQueryAttention._grouped(2)
        attn = (functools.partial(dense_attention, window=6)
                if fn == "dense" else
                lambda q, k, v: blockwise_attention(q, k, v, 8, window=6))
        loss = lambda f: lambda q, k, v: jnp.sum(jnp.sin(f(q, k, v)))
        got = jax.grad(loss(attn), (0, 1, 2))(q, k, v)
        want = jax.grad(loss(functools.partial(self._oracle, window=6)),
                        (0, 1, 2))(q, k, v)
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, atol=1e-5, rtol=1e-5)

    def test_a_window_of_the_sequence_is_the_causal_call(self):
        q, k, v = _qkv()
        np.testing.assert_array_equal(dense_attention(q, k, v, window=T),
                                      dense_attention(q, k, v))

    def test_keys_at_given_positions_in_any_order(self):
        # a ring cache: the rows of k/v are not in order, and some are empty
        q, k, v, _, _ = TestGroupedQueryAttention._grouped(2)
        order = np.random.default_rng(0).permutation(T)
        empty = jnp.concatenate([jnp.asarray(order), jnp.full((3,), -1)])
        pad = lambda a: jnp.concatenate(
            [a[:, order], 7.0 * jnp.ones_like(a[:, :3])], axis=1)
        row = dense_attention(q[:, 20:21], pad(k), pad(v), q_offset=20,
                              window=6, kv_positions=empty)
        np.testing.assert_allclose(
            row, self._oracle(q, k, v, 6)[:, 20:21], atol=2e-6)

    def test_a_window_needs_the_causal_mask(self):
        q, k, v = _qkv()
        for attn in (dense_attention,
                     lambda *a, **kw: blockwise_attention(*a, 8, **kw)):
            with pytest.raises(ValueError, match="causal"):
                attn(q, k, v, causal=False, window=4)


# A SmallThinker-shaped trunk: a global NoPE layer, then windowed RoPE
# layers; heads of a width of their own (3 x 16 = 48 under d_model 32) over
# one k/v head.
SLIDING = {"norm": "rms", "positions": "rope", "use_bias": False,
           "ffn": "reglu", "d_ff": 48, "n_layers": 3, "n_heads": 3,
           "n_kv_heads": 1, "head_dim": 16,
           "layer_types": ["full_attention", "sliding_attention",
                           "sliding_attention"],
           "sliding_window": 4, "rope_layers": [0, 1, 1]}


class TestWindowedLayers:
    def test_parameter_tree_follows_the_arch(self):
        params = build_policy({**ARCH, **SLIDING}).init_params(
            jax.random.PRNGKey(0))["params"]
        assert "pos_embed" not in params       # no table under rope
        block = params["block_1"]
        assert block["q_proj"]["kernel"].shape == (32, 48)
        assert block["k_proj"]["kernel"].shape == (32, 16)
        assert block["attn_out"]["kernel"].shape == (48, 32)
        assert block["mlp_gate"]["kernel"].shape == (32, 48)

    @pytest.mark.parametrize("attention", ["dense", "blockwise", "flash",
                                           "ring"])
    def test_every_backend_computes_the_band(self, attention):
        # off-TPU and without a mesh every kind resolves to an XLA path
        arch = {**ARCH, **SLIDING, "attention_block": 4}
        dense = build_policy({**arch, "attention": "dense"})
        other = build_policy({**arch, "attention": attention})
        params = dense.init_params(jax.random.PRNGKey(0))
        obs = jnp.asarray(np.random.default_rng(1).standard_normal(
            (2, 16, 8)), jnp.float32)
        act = jnp.zeros((2, 16), jnp.int32)
        for a, b in zip(dense.evaluate(params, obs, act),
                        other.evaluate(params, obs, act)):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)

    def test_the_window_and_the_missing_positions_are_in_the_output(self):
        # a wider window, or RoPE on the global layer, is another model
        base = {**ARCH, **SLIDING}
        policy = build_policy(base)
        params = policy.init_params(jax.random.PRNGKey(0))
        obs = jnp.asarray(np.random.default_rng(1).standard_normal(
            (1, 12, 8)), jnp.float32)
        act = jnp.zeros((1, 12), jnp.int32)
        v = policy.evaluate(params, obs, act)[2]
        for other in ({"sliding_window": 6}, {"rope_layers": [1, 1, 1]},
                      {"rope_layers": [0, 0, 1]}):
            v2 = build_policy({**base, **other}).evaluate(
                params, obs, act)[2]
            assert not np.allclose(v, v2, atol=1e-4), other
        # rows inside the first window see every earlier key either way
        v6 = build_policy({**base, "sliding_window": 6}).evaluate(
            params, obs, act)[2]
        np.testing.assert_allclose(v[:, :4], v6[:, :4], atol=1e-5)

    def test_a_nope_global_layer_sees_no_order_among_earlier_rows(self):
        # one global layer without positions: the last row's output is the
        # same whatever order the rows before it came in
        arch = {**ARCH, **SLIDING, "n_layers": 1,
                "layer_types": ["full_attention"], "rope_layers": [0]}
        policy = build_policy(arch)
        params = policy.init_params(jax.random.PRNGKey(0))
        obs = np.random.default_rng(2).standard_normal((1, 9, 8)).astype(
            np.float32)
        shuffled = obs.copy()
        shuffled[0, :8] = obs[0, 7::-1]
        act = jnp.zeros((1, 9), jnp.int32)
        np.testing.assert_allclose(
            policy.evaluate(params, obs, act)[2][0, -1],
            policy.evaluate(params, shuffled, act)[2][0, -1], atol=1e-5)

    @pytest.mark.parametrize("last", ["sliding_attention", "full_attention"])
    def test_full_window_equals_readout_row(self, last):
        arch = {**ARCH, **SLIDING}
        arch["layer_types"] = arch["layer_types"][:2] + [last]
        policy = build_policy(arch)
        params = policy.init_params(jax.random.PRNGKey(1))
        W = 10
        window = np.random.default_rng(4).standard_normal((W, 8)).astype(
            np.float32)
        _, _, v = policy.evaluate(params, window[None],
                                  jnp.zeros((1, W), jnp.int32))
        for t in (1, 5, W):
            _, aux = policy.step_window(params, jax.random.PRNGKey(0),
                                        jnp.asarray(window), t)
            np.testing.assert_allclose(float(aux["v"]), float(v[0, t - 1]),
                                       atol=1e-4, err_msg=f"t={t}")

    @pytest.mark.parametrize("over,match", [
        ({"layer_types": ["full_attention", "local", "conv"]},
         "unknown layer type"),
        ({"sliding_window": None}, "no sliding_window"),
        ({"rope_layers": [0, 1]}, "rope_layers names 2 layers"),
        ({"ffn": "geglu"}, "unknown ffn"),
    ])
    def test_what_the_arch_cannot_mean_is_refused(self, over, match):
        with pytest.raises(ValueError, match=match):
            build_policy({**ARCH, **SLIDING, **over}).init_params(
                jax.random.PRNGKey(0))

    @pytest.mark.parametrize("key,value", [
        ("head_dim", 16), ("sliding_window", 4), ("rope_layers", [1, 1]),
        ("moe_router_input", "layer")])
    def test_pipeline_family_refuses_the_keys(self, key, value):
        with pytest.raises(ValueError, match="GPT-2 shaped block"):
            build_policy({**ARCH, "kind": "transformer_pp_discrete",
                          key: value})

    def test_ring_attention_refuses_a_window_under_a_mesh(self):
        mesh = make_mesh({"dp": 2, "fsdp": 1, "tp": 1, "sp": 4},
                         jax.devices()[:8])
        ring = build_policy({**ARCH, **SLIDING, "n_kv_heads": 3,
                             "attention": "ring"})
        params = ring.init_params(jax.random.PRNGKey(0))
        with use_mesh(mesh), pytest.raises(ValueError, match="no window"):
            jax.jit(ring.evaluate)(params, jnp.zeros((2, 16, 8)),
                                   jnp.zeros((2, 16), jnp.int32))
