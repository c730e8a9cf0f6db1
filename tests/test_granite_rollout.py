"""A trunk of Mamba-2 + FFN layers beside attention layers (Granite 4.0-H's
shape, ``benchmark/configs/granite4h-micro-policy.json``) through the path the
fused actor tier runs it on: the full forward against the benchmark's plain
reference, a cached step that starts a sequence over a USED cache, the fused
scan across in-scan resets against the window program, the swap's rebuild, and
parameters held as the step uses them. Toy sizes, seeded, CPU, float32 unless
a test says otherwise.
"""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_anakin import _counted, registry  # noqa: F401 (a fixture)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OBS, ACT, W = 6, 4, 8   # JaxRecall(horizon=8, n_cues=4): obs_dim 6, 4 actions


def _by_path(rel: str, name: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def reference():
    """The benchmark's reference file, imported where it lies (no copy)."""
    return _by_path("benchmark/reference/granite4h-micro-policy.py",
                    "granite4h_reference")


@pytest.fixture(scope="module")
def tiny_cfg():
    """The configuration file cut to a toy: d_model 64, two periods of 3
    ``mamba`` : 1 ``attention``, each of the four scalars at a value that is
    not its default (and not Granite's)."""
    with open(os.path.join(
            REPO, "benchmark/configs/granite4h-micro-policy.json")) as f:
        cfg = json.load(f)
    return dict(
        cfg, hidden_size=64, num_hidden_layers=8,
        layer_types=(["mamba"] * 3 + ["attention"]) * 2,
        num_attention_heads=4, num_key_value_heads=2,
        shared_intermediate_size=128, mamba_n_heads=4, mamba_d_head=32,
        mamba_d_state=16, mamba_chunk_size=4, positions_as_run=W,
        attention="dense", residual_multiplier=0.3,
        attention_multiplier=0.1, embedding_multiplier=5.0,
        logits_scaling=3.0, obs_dim=OBS, act_dim=ACT)


def _arch(reference, cfg, precision="float32", **more):
    from relayrl_tpu.models.base import apply_arch_overrides

    kw = reference.program_kwargs(cfg)
    arch = apply_arch_overrides(
        {"kind": kw["model_kind"], "obs_dim": OBS, "act_dim": ACT,
         "has_critic": True, "precision": precision}, kw)
    return {**arch, **more}


def _all_logp(policy, params, obs):
    """Normalised log-probabilities of every action and the values, from the
    learner's ``evaluate``."""
    def every_action(params, obs):
        logp, _, v = jax.vmap(lambda a: policy.evaluate(
            params, obs, jnp.full(obs.shape[:-1], a, jnp.int32)))(
                jnp.arange(ACT))
        return jnp.moveaxis(logp, 0, -1), v[0]

    return jax.jit(every_action)(params, obs)


class TestTrunkAgainstTheReference:
    def test_full_forward(self, reference, tiny_cfg):
        from relayrl_tpu.models import build_policy

        arch = _arch(reference, tiny_cfg)
        assert arch["layer_types"][:4] == ["mamba"] * 3 + ["full_attention"]
        for key, value in (("residual_multiplier", 0.3), ("attn_scale", 0.1),
                           ("embed_multiplier", 5.0), ("logit_divisor", 3.0)):
            assert arch[key] == value
        policy = build_policy({**arch, "held_params": False})
        params = policy.init_params(jax.random.PRNGKey(3))
        obs = jax.random.normal(jax.random.PRNGKey(1), (2, W, OBS))
        logp, v = _all_logp(policy, params, obs)
        logp_ref, v_ref = reference.forward(params, obs, tiny_cfg)
        np.testing.assert_allclose(logp, logp_ref, atol=2e-5, rtol=0)
        np.testing.assert_allclose(v, v_ref, atol=2e-5, rtol=0)

    @pytest.mark.parametrize("wrong", [
        {"carry": True}, {"residual": 1.0}, {"attn_scale": 0.125},
        {"gate": "after"}], ids=lambda w: next(iter(w)))
    def test_a_wrong_reference_reads_apart(self, reference, tiny_cfg, wrong):
        """Each control of ``controls_granite_rollout`` is another
        function: the comparison can tell."""
        from relayrl_tpu.models import build_policy

        policy = build_policy(_arch(reference, tiny_cfg, held_params=False))
        params = policy.init_params(jax.random.PRNGKey(3))
        obs = jax.random.normal(jax.random.PRNGKey(1), (1, W, OBS))
        logp_ref, v_ref = reference.forward(params, obs, tiny_cfg)
        logp, v = reference.forward(params, obs, tiny_cfg, wrong=wrong)
        assert float(jnp.max(jnp.abs(logp - logp_ref))) > 1e-3
        assert float(jnp.max(jnp.abs(v - v_ref))) > 1e-3

    def test_the_parent_is_refused_in_build(self, reference, tiny_cfg,
                                            monkeypatch):
        """A program whose models do not take this configuration's keys
        (the parent of the PR that brought it) exits at once, in ``build``."""
        from relayrl_tpu.models import base

        monkeypatch.setattr(base, "ARCH_PASSTHROUGH_KEYS", tuple(
            k for k in base.ARCH_PASSTHROUGH_KEYS
            if k not in ("residual_multiplier", "held_params")))
        with pytest.raises(SystemExit, match="REFUSED.*held_params"):
            reference.program_kwargs(tiny_cfg)

    def test_the_scalars_default_to_nothing_traced(self):
        """With none of the four keys an arch's lowered step is what it was:
        no multiply by one, no division by one."""
        from relayrl_tpu.models import build_policy

        arch = {"kind": "transformer_discrete", "obs_dim": OBS,
                "act_dim": ACT, "d_model": 16, "n_layers": 1, "n_heads": 2,
                "max_seq_len": W}
        texts = []
        for more in ({}, {"residual_multiplier": 1.0, "attn_scale": None,
                          "embed_multiplier": 1.0, "logit_divisor": 1.0}):
            policy = build_policy({**arch, **more})
            params = policy.init_params(jax.random.PRNGKey(0))
            texts.append(jax.jit(policy.step).lower(
                params, jax.random.PRNGKey(0), jnp.zeros((W, OBS)),
                None).as_text())
        assert texts[0] == texts[1]


# one layer kind each whose state has no positions, behind an attention layer
RESTART_TRUNKS = {
    "mamba2": {"layer_types": ["mamba", "full_attention"], "mamba_heads": 2,
               "mamba_head_dim": 16, "mamba_state": 8, "mamba_chunk": 4},
    "gdn": {"layer_types": ["linear_attention", "full_attention"],
            "gdn_key_heads": 2, "gdn_value_heads": 2, "gdn_key_dim": 8,
            "gdn_value_dim": 8, "gdn_chunk": 4},
    "kda": {"layer_types": ["kda", "full_attention"], "kda_heads": 2,
            "kda_head_dim": 8, "kda_chunk": 4},
    "conv": {"layer_types": ["conv", "full_attention"]},
}


def _restart_policy(name):
    from relayrl_tpu.models import build_policy

    return build_policy({
        "kind": "transformer_discrete", "obs_dim": OBS, "act_dim": ACT,
        "d_model": 16, "n_layers": 2, "n_heads": 2, "max_seq_len": W,
        "positions": "none", "norm": "rms", **RESTART_TRUNKS[name]})


@pytest.mark.parametrize("name", list(RESTART_TRUNKS))
def test_a_cached_sequence_starts_over_a_used_cache(name):
    """``step_cached(restart=True)`` from ``t`` = 0 over the cache another
    sequence left — its recurrent state, its convolution's rows, its keys
    and values — gives the full forward's rows, as from a zeroed cache:
    position 0 reads a state without positions as zeros
    (``layers.CACHE_RESTARTS``). Without the flag the step is the one a
    fresh cache always had and carries the used state on."""
    import functools

    policy = _restart_policy(name)
    assert policy.cache_restarts
    params = policy.init_params(jax.random.PRNGKey(2))
    used, fresh = (jax.random.normal(jax.random.PRNGKey(k), (W, OBS))
                   for k in (5, 6))
    step = jax.jit(functools.partial(policy.step_cached, restart=True))
    cache = policy.init_cache(W)
    for t in range(W):
        _, _, cache = step(params, jax.random.PRNGKey(t), cache, used[t], t)
    assert all(float(jnp.max(jnp.abs(x))) > 0
               for x in jax.tree.leaves(cache))
    want_logp, want_v = _all_logp(policy, params, fresh[None])
    _, carried, _ = policy.step_cached(params, jax.random.PRNGKey(0), cache,
                                       fresh[0], 0)
    assert abs(float(carried["v"]) - float(want_v[0, 0])) > 1e-3
    for t in range(W):
        act, aux, cache = step(params, jax.random.PRNGKey(t), cache,
                               fresh[t], t)
        np.testing.assert_allclose(aux["logp_a"], want_logp[0, t, act],
                                   atol=1e-4, rtol=0)
        np.testing.assert_allclose(aux["v"], want_v[0, t], atol=1e-4, rtol=0)


@pytest.mark.parametrize("name", ["mamba2", "conv"])
def test_a_prefill_replaces_a_used_state(name):
    """``prefill_cache`` over a used cache (what the swap's rebuild hands
    it) leaves what it leaves over a zeroed one."""
    policy = _restart_policy(name)
    params = policy.init_params(jax.random.PRNGKey(2))
    window = jax.random.normal(jax.random.PRNGKey(7), (W, OBS))
    used = jax.tree.map(lambda x: jnp.full_like(x, 3.0),
                        policy.init_cache(W))
    got = policy.prefill_cache(params, used, window, n_valid=5,
                               restart=True)
    want = policy.prefill_cache(params, policy.init_cache(W), window,
                                n_valid=5)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        # rows past n_valid of a (k, v) pair hold the padding rows' in both
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=0)


def _keep_windows(host) -> list:
    from tests.test_anakin import TestFusedCachedScan

    return TestFusedCachedScan._keep_windows(host)


def _assert_windows_agree(got, want, atol=1e-4):
    from tests.test_anakin import TestFusedCachedScan

    TestFusedCachedScan._assert_windows_agree(got, want, atol)


class TestFusedScanOverAStateWithoutPositions:
    """The fused host on a ``mamba`` + ``attention`` trunk, held as
    ``tests/test_anakin.py::TestFusedCachedScan`` holds the attention trunk:
    against the SAME host on the window program."""

    LANES = 3

    @pytest.fixture(scope="class")
    def arch(self, reference, tiny_cfg):
        cfg = dict(tiny_cfg, num_hidden_layers=2,
                   layer_types=["mamba", "attention"])
        return _arch(reference, cfg)

    @staticmethod
    def _bundle(arch, seed=0, version=0):
        from relayrl_tpu.models import build_policy
        from relayrl_tpu.types.model_bundle import ModelBundle

        params = build_policy({**arch, "held_params": False}).init_params(
            jax.random.PRNGKey(seed))
        return ModelBundle(version=version, arch=arch, params=params)

    def _host(self, monkeypatch, arch, cached: bool, unroll: int, **kw):
        from relayrl_tpu.runtime import anakin

        with monkeypatch.context() as m:
            if not cached:
                m.setattr(anakin, "carry_holds_cache", lambda *a: False)
            host = anakin.AnakinActorHost(
                kw.pop("bundle", None) or self._bundle(arch), "Recall-v0",
                num_envs=self.LANES, unroll_length=unroll, seed=5,
                horizon=W, n_cues=ACT, **kw)
        assert len(host._carry) == (7 if cached else 6)
        return host

    def test_cached_steps_equal_the_window_programs_across_resets(
            self, tmp_cwd, monkeypatch, registry, arch):
        """Two dispatches of 20 steps at a horizon of 8: five in-scan resets
        a lane. The same actions and episode ends, ``logp_a`` / ``v`` within
        1e-4, and the resets counted."""
        windows = {}
        for cached in (True, False):
            host = self._host(monkeypatch, arch, cached, unroll=20)
            assert host.policy.cache_restarts
            windows[cached] = _keep_windows(host)
            host.rollout()
            host.rollout()
        _assert_windows_agree(windows[True], windows[False])
        ends = sum(int(w["term"].sum()) for w in windows[True])
        assert ends == self.LANES * 5
        assert _counted("relayrl_actor_state_resets_total") == 2 * ends
        assert _counted("relayrl_actor_cached_steps_total") == (
            2 * self.LANES * 20)

    def test_a_reset_reads_nothing_of_the_state_it_starts_over(
            self, tmp_cwd, monkeypatch, arch):
        """After a dispatch that ends every lane's episode the Mamba-2
        states and rows are made 1e3 and the (k, v) rows poisoned as
        ``test_anakin`` poisons them: the next episodes read what an
        untouched twin reads."""
        hosts = [self._host(monkeypatch, arch, True, unroll=W)
                 for _ in range(2)]
        windows = [_keep_windows(h) for h in hosts]
        for host in hosts:
            host.rollout()
            assert np.all(np.asarray(host._carry[5]) == 0)  # all reset
        *rest, cache = hosts[0]._carry
        layer_types = arch["layer_types"]
        hosts[0]._carry = (*rest, tuple(
            (jnp.full_like(a, np.nan), jnp.full_like(b, 1e30))
            if kind == "full_attention"
            else (jnp.full_like(a, 1e3), jnp.full_like(b, 1e3))
            for kind, (a, b) in zip(layer_types, cache)))
        for host in hosts:
            host.rollout()
        _assert_windows_agree(windows[0], windows[1], atol=1e-6)

    def test_a_swap_rebuilds_state_and_rows_to_what_prefill_gives(
            self, tmp_cwd, monkeypatch, registry, arch):
        """``maybe_swap`` mid-episode, with the constructor's window in
        flight (5 rows of 8 in every ring, the carry that window returns):
        after the next launch's rebuild every lane's state and rows are what
        ``prefill_cache`` makes of the lane's ring under the NEW parameters,
        and the windows launched after the swap equal the window program's
        after the same swap."""
        windows = {}
        for cached in (True, False):
            host = self._host(monkeypatch, arch, cached, unroll=5)
            windows[cached] = _keep_windows(host)
            assert host.maybe_swap(self._bundle(arch, seed=1, version=1))
            if cached:
                rebuilt = host._rebuild_fn(host.params, host._carry)
                win, wlen, cache = rebuilt[4:]
                for lane in range(self.LANES):
                    want = host.policy.prefill_cache(
                        host.params, host.policy.init_cache(W), win[lane],
                        n_valid=wlen[lane])
                    got = jax.tree.map(lambda x: x[lane], cache)
                    for a, b in zip(jax.tree.leaves(got),
                                    jax.tree.leaves(want)):
                        np.testing.assert_allclose(a, b, atol=1e-5, rtol=0)
                assert int(wlen[0]) == 5
            host.rollout()
            host.rollout()
        _assert_windows_agree(windows[True], windows[False])
        assert _counted("relayrl_actor_cache_rebuilds_total") == 1

    def test_the_cache_gauge_splits_rows_from_state(self, tmp_cwd,
                                                    monkeypatch, registry,
                                                    arch):
        host = self._host(monkeypatch, arch, True, unroll=2)
        one = host.policy.init_cache(W)
        rows = sum(x.nbytes for kind, layer in zip(arch["layer_types"], one)
                   if kind == "full_attention" for x in layer)
        state = sum(x.nbytes for x in jax.tree.leaves(one)) - rows
        assert rows > 0 and state > 0
        assert _counted("relayrl_actor_cache_bytes",
                        kind="rows") == self.LANES * rows
        assert _counted("relayrl_actor_cache_bytes",
                        kind="state") == self.LANES * state
        assert host._cache_bytes == self.LANES * (rows + state)


class TestHeldParameters:
    """The host holds a matmul weight at the compute type and what is used
    in float32 in float32; a dispatch from them is the dispatch from float32
    parameters cast at use."""

    @pytest.fixture(scope="class")
    def arch(self, reference, tiny_cfg):
        cfg = dict(tiny_cfg, num_hidden_layers=2,
                   layer_types=["mamba", "attention"])
        return _arch(reference, cfg, precision="bfloat16")

    CAST = ("mamba_in", "mamba_out", "q_proj", "k_proj", "v_proj",
            "attn_out", "mlp_up", "mlp_gate", "mlp_down")

    def _assert_held(self, params):
        leaves = jax.tree_util.tree_flatten_with_path(params)[0]
        assert leaves
        for path, leaf in leaves:
            name = jax.tree_util.keystr(path)
            cast = any(f"'{k}'" in name for k in self.CAST)
            assert leaf.dtype == (jnp.bfloat16 if cast else jnp.float32), name

    def test_init_params_returns_the_held_form_under_the_key(self, arch):
        from relayrl_tpu.models import build_policy

        assert arch["held_params"] is True
        held = jax.jit(build_policy(arch).init_params)(jax.random.PRNGKey(4))
        self._assert_held(held)
        published = jax.jit(build_policy(
            {**arch, "held_params": False}).init_params)(
                jax.random.PRNGKey(4))
        assert all(x.dtype == jnp.float32
                   for x in jax.tree.leaves(published))
        # the same numbers, rounded once
        for a, b in zip(jax.tree.leaves(held), jax.tree.leaves(published)):
            np.testing.assert_array_equal(a, b.astype(a.dtype))

    def test_the_host_holds_and_steps_as_if_cast_at_use(self, tmp_cwd,
                                                        monkeypatch,
                                                        registry, arch):
        from relayrl_tpu.models import base, build_policy
        from relayrl_tpu.runtime import anakin
        from relayrl_tpu.types.model_bundle import ModelBundle

        published = build_policy({**arch, "held_params": False}).init_params(
            jax.random.PRNGKey(4))
        bundle = ModelBundle(version=0, arch=arch, params=published)

        def host(hold: bool):
            with monkeypatch.context() as m:
                if not hold:   # the parent's host: float32, cast at use
                    m.setattr(anakin, "held_dtypes", lambda p, tree: None)
                return anakin.AnakinActorHost(
                    bundle, "Recall-v0", num_envs=3, unroll_length=W + 4,
                    seed=5, horizon=W, n_cues=ACT)

        held, plain = host(True), host(False)
        self._assert_held(held.params)
        assert all(x.dtype == jnp.float32
                   for x in jax.tree.leaves(plain.params))
        nbytes = {h: sum(x.nbytes for x in jax.tree.leaves(h.params))
                  for h in (held, plain)}
        assert _counted("relayrl_actor_param_bytes") == nbytes[plain]  # last
        matrices = sum(x.nbytes for x in jax.tree.leaves(held.params)
                       if x.dtype == jnp.bfloat16)
        assert nbytes[held] == nbytes[plain] - matrices
        assert matrices > 0.8 * nbytes[held]    # all but halves
        windows = [_keep_windows(h)
                   for h in (held, plain)]
        for h in (held, plain):
            h.rollout()
        _assert_windows_agree(windows[0], windows[1], atol=1e-6)
        # a swap installs held too, and a tree already held passes through
        assert held.maybe_swap(ModelBundle(version=1, arch=arch,
                                           params=published))
        self._assert_held(held.params)
        again = base.hold_params(held.policy, held.params)
        assert all(a is b for a, b in zip(jax.tree.leaves(again),
                                          jax.tree.leaves(held.params)))

    def test_a_learner_refuses_the_key(self, arch):
        """``held_params`` changes what ``init_params`` returns: the
        learner's build refuses it, and the policy refuses the learner's
        forward, so no one trains bfloat16 master weights unasked."""
        from relayrl_tpu.models import base, build_policy

        from relayrl_tpu.algorithms import build_algorithm

        with pytest.raises(ValueError, match="held_params"):
            build_algorithm("IMPALA", obs_dim=OBS, act_dim=ACT,
                            model_kind="transformer_discrete",
                            **{k: v for k, v in arch.items()
                               if k in base.ARCH_PASSTHROUGH_KEYS})
        assert base.apply_arch_overrides(   # an actor's arch passes
            {}, {"held_params": True}) == {"held_params": True}
        policy = build_policy(arch)
        params = policy.init_params(jax.random.PRNGKey(4))
        with pytest.raises(ValueError, match="held_params"):
            policy.evaluate(params, jnp.zeros((1, W, OBS)),
                            jnp.zeros((1, W), jnp.int32))

    @pytest.mark.parametrize("handshake", ["host_arrays", "device_arrays"])
    def test_wire_deltas_reach_a_host_that_holds(self, tmp_cwd, arch,
                                                 handshake):
        """A wire-v2 delta's base is the PUBLISHED float32 tree, which a
        host that cast its matmul weights no longer has. Built from a
        handshake bundle's host arrays (``ModelBundle.from_bytes``: the
        deployed path) it kept them for the decoder's seed and the first
        delta applies; built from device arrays it has the held tree alone,
        whose manifest is not the publisher's: the first delta asks for a
        keyframe ONCE, the keyframe installs, and deltas apply from there.
        Every install is held, and was cast before the gate."""
        from relayrl_tpu.models import build_policy
        from relayrl_tpu.runtime.anakin import AnakinActorHost
        from relayrl_tpu.transport import modelwire as mw
        from relayrl_tpu.types.model_bundle import ModelBundle

        make = build_policy({**arch, "held_params": False}).init_params
        trees = [jax.tree.map(np.asarray, make(jax.random.PRNGKey(k)))
                 for k in (4, 5, 6, 7)]
        first = (trees[0] if handshake == "host_arrays"
                 else jax.tree.map(jnp.asarray, trees[0]))
        host = AnakinActorHost(
            ModelBundle(version=0, arch=arch, params=first), "Recall-v0",
            num_envs=2, unroll_length=2, seed=5, horizon=W, n_cues=ACT)
        enc = mw.ModelWireEncoder(keyframe_interval=100, small_model_bytes=0)
        enc.encode(0, arch, trees[0])           # the handshake's keyframe
        delta1, info = enc.encode(1, arch, trees[1])
        assert info["kind"] == "delta"
        installed = 0
        if handshake == "host_arrays":
            assert host.swap_from_wire(1, delta1) is not None
            installed = 1
        else:
            with pytest.raises(mw.WireBaseMismatch):
                host.swap_from_wire(1, delta1)
            assert host.version == 0
            enc.force_keyframe()
            key2, info = enc.encode(2, arch, trees[2])
            assert info["kind"] == "keyframe"
            assert host.swap_from_wire(2, key2) is not None
            installed = 2
        delta, info = enc.encode(3, arch, trees[3])
        assert info["kind"] == "delta"
        assert host.swap_from_wire(3, delta) is not None
        assert host.version == 3 and installed
        assert host._wire_decoder.resyncs == (handshake == "device_arrays")
        self._assert_held(host.params)
        for held, want in zip(jax.tree.leaves(host.params),
                              jax.tree.leaves(trees[3])):
            np.testing.assert_array_equal(held, want.astype(held.dtype))
        host.rollout()      # the rebuild and a window under the new tree
        host.close()

    def test_a_float32_policy_is_held_as_published(self, tmp_cwd):
        """Nothing is cast at use under a float32 compute type: the host's
        leaves are the bundle's own."""
        from relayrl_tpu.runtime.anakin import AnakinActorHost
        from tests.test_anakin import _recall_bundle

        bundle = _recall_bundle()
        host = AnakinActorHost(bundle, "Recall-v0", num_envs=2,
                               unroll_length=2, horizon=8, n_cues=4)
        assert all(a is b for a, b in zip(jax.tree.leaves(host.params),
                                          jax.tree.leaves(bundle.params)))
