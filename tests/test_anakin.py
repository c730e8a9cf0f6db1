"""The fused Anakin rollout engine (runtime/anakin.py): window unstack
wire semantics, swap gates, cross-process determinism, config knobs, the
networked VectorAgent anakin tier end-to-end on zmq, and THE acceptance
drill — a chaos-style learner SIGKILL/restart with anakin actors, zero
loss through the spool/dedup plane.
"""

import json
import os
import signal
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from _util import free_port

pytestmark = pytest.mark.anakin

DRILLS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "drills")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bundle(obs_dim=4, act_dim=2, seed=0, version=0):
    """Deterministic MLP bundle (no algorithm state, so two processes
    building it get bit-identical params)."""
    from relayrl_tpu.models import build_policy
    from relayrl_tpu.types.model_bundle import ModelBundle

    arch = {"kind": "mlp_discrete", "obs_dim": obs_dim, "act_dim": act_dim,
            "hidden_sizes": [16]}
    policy = build_policy(arch)
    params = policy.init_params(jax.random.PRNGKey(seed))
    return ModelBundle(version=version, arch=arch, params=params)


def _seq_bundle(obs_dim=4, act_dim=2, max_seq_len=8, seed=0, version=0,
                uniform=False):
    """Deterministic windowed-transformer bundle (a ``step_window``
    sequence policy — the fused scan's rolling-window carry path).

    A seeded initialisation is NOT a random policy: this one answers the
    pole's lean and holds it up for 52–85 steps (measured, PR 45), and how
    long depends on the initialisers' bytes, which differ by jax version.
    ``uniform`` zeroes the action head, so every logit is 0 and sampling is
    uniform whatever those bytes are: a random policy's CartPole episodes
    (11–34 steps at the tests' seeds). The value head still reads the
    window's features, so ``v`` differs row by row as before."""
    from relayrl_tpu.models import build_policy
    from relayrl_tpu.types.model_bundle import ModelBundle

    arch = {"kind": "transformer_discrete", "obs_dim": obs_dim,
            "act_dim": act_dim, "d_model": 16, "n_layers": 1, "n_heads": 2,
            "max_seq_len": max_seq_len}
    policy = build_policy(arch)
    params = policy.init_params(jax.random.PRNGKey(seed))
    if uniform:
        inner = dict(params["params"])
        inner["pi_head"] = jax.tree_util.tree_map(np.zeros_like,
                                                  inner["pi_head"])
        params = {**params, "params": inner}
    return ModelBundle(version=version, arch=arch, params=params)


class TestUnstackWireSemantics:
    def test_episode_stream_matches_live_loop_shape(self, tmp_cwd):
        """Each shipped episode ends in a terminal marker carrying the
        final step's reward; every non-terminal record holds the reward
        its own action earned with the live path's ``reward_updated``
        side channel; the final action record keeps rew=0 (its reward
        rides the marker, exactly like ``flag_last_action``)."""
        from relayrl_tpu.runtime.anakin import AnakinActorHost
        from relayrl_tpu.types.trajectory import deserialize_actions

        sent: list[tuple[int, bytes]] = []
        host = AnakinActorHost(
            _bundle(), "CartPole-v1", num_envs=4, unroll_length=64,
            columnar_wire=False,  # this suite pins the per-record fallback
            on_send=lambda lane, p: sent.append((lane, p)), seed=2)
        host.rollout()
        assert {lane for lane, _ in sent} == {0, 1, 2, 3}
        for _, payload in sent:
            acts = deserialize_actions(payload)
            marker, steps = acts[-1], acts[:-1]
            assert marker.done and marker.act is None
            assert marker.rew == 1.0  # CartPole: every step pays 1.0
            assert not marker.truncated  # random policy falls, not times out
            assert marker.obs is None  # genuine terminal: no bootstrap obs
            for rec in steps[:-1]:
                assert rec.rew == 1.0 and rec.reward_updated
                assert rec.obs.shape == (4,) and rec.obs.dtype == np.float32
                assert set(rec.data) == {"logp_a", "v"}
            assert steps[-1].rew == 0.0 and not steps[-1].reward_updated

    def test_truncation_ships_bootstrap_obs(self, tmp_cwd):
        """A time-limit ending must ship truncated=True plus the
        pre-reset observation (the value bootstrap needs the successor
        state), with terminated-beats-truncated precedence preserved."""
        from relayrl_tpu.envs.jax import JaxCartPole
        from relayrl_tpu.runtime.anakin import AnakinActorHost
        from relayrl_tpu.types.trajectory import deserialize_actions

        sent: list[bytes] = []
        host = AnakinActorHost(
            _bundle(), JaxCartPole(max_steps=5), num_envs=2,
            unroll_length=40, columnar_wire=False,
            on_send=lambda lane, p: sent.append(p),
            seed=0)
        host.rollout()
        truncated_markers = terminal_markers = 0
        for payload in sent:
            marker = deserialize_actions(payload)[-1]
            assert marker.done
            if marker.truncated:
                truncated_markers += 1
                assert marker.obs is not None and marker.obs.shape == (4,)
            else:
                terminal_markers += 1
                assert marker.obs is None
        # max_steps=5 under a random policy: overwhelmingly time limits.
        assert truncated_markers >= 5

    def test_episode_returns_match_shipped_rewards(self, tmp_cwd):
        """The host's per-lane episode accounting equals the sum of
        rewards on the wire for each shipped episode."""
        from relayrl_tpu.runtime.anakin import AnakinActorHost
        from relayrl_tpu.types.trajectory import deserialize_actions

        per_lane: dict[int, list[bytes]] = {}
        host = AnakinActorHost(
            _bundle(), "CartPole-v1", num_envs=3, unroll_length=50,
            columnar_wire=False,
            on_send=lambda lane, p: per_lane.setdefault(lane, []).append(p),
            seed=5)
        host.rollout()
        host.rollout()
        for lane, payloads in per_lane.items():
            wire_returns = [
                sum(a.rew for a in deserialize_actions(p))
                for p in payloads]
            # completed episodes only (a window can end mid-episode, and
            # max_traj_length can split one episode into chunks — CartPole
            # under the default 1000-cap never splits here)
            assert wire_returns == pytest.approx(
                host.episode_returns[lane][:len(wire_returns)])

    def test_run_anakin_loop_returns_per_lane(self, tmp_cwd):
        from relayrl_tpu.runtime.anakin import AnakinActorHost, run_anakin_loop

        host = AnakinActorHost(_bundle(), "CartPole-v1", num_envs=2,
                               unroll_length=60, seed=1)
        returns = run_anakin_loop(host, windows=2)
        assert len(returns) == 2
        assert all(len(lane_returns) >= 1 for lane_returns in returns)
        assert all(r >= 1.0 for lane in returns for r in lane)


class TestSwapGates:
    def test_swap_between_windows_and_stale_rejection(self, tmp_cwd):
        from relayrl_tpu.runtime.anakin import AnakinActorHost

        host = AnakinActorHost(_bundle(version=3), "CartPole-v1",
                               num_envs=2, unroll_length=8, seed=0)
        host.rollout()
        assert not host.maybe_swap(_bundle(version=3))  # stale: same ver
        newer = _bundle(seed=9, version=7)
        assert host.maybe_swap(newer)
        assert host.version == 7
        host.rollout()  # next window runs on the new params
        with pytest.raises(ValueError, match="arch"):
            host.maybe_swap(_bundle(obs_dim=4, act_dim=3, version=9))

    def test_swap_from_bytes_roundtrip(self, tmp_cwd):
        from relayrl_tpu.runtime.anakin import AnakinActorHost

        host = AnakinActorHost(_bundle(version=0), "CartPole-v1",
                               num_envs=1, unroll_length=4, seed=0)
        assert host.swap_from_bytes(_bundle(seed=4, version=2).to_bytes())
        assert host.version == 2

    def test_env_model_dim_mismatch_raises(self, tmp_cwd):
        from relayrl_tpu.runtime.anakin import AnakinActorHost

        with pytest.raises(ValueError, match="obs_dim"):
            AnakinActorHost(_bundle(obs_dim=6), "CartPole-v1",
                            num_envs=1, unroll_length=4)

    def test_kv_cache_only_policy_refused(self, tmp_cwd, monkeypatch):
        """Sequence policies run fused now; the one remaining refusal is
        KV-cache-only policies (``step_cached`` without ``step_window``),
        and its message must name the tiers that DO serve them."""
        import dataclasses

        from relayrl_tpu.models import build_policy
        from relayrl_tpu.runtime import anakin as anakin_mod

        def cache_only(arch):
            return dataclasses.replace(build_policy(arch),
                                       step_window=None, mode_window=None)

        monkeypatch.setattr(anakin_mod, "build_policy", cache_only)
        with pytest.raises(ValueError, match="KV-cache"):
            anakin_mod.AnakinActorHost(_seq_bundle(), "CartPole-v1",
                                       num_envs=1, unroll_length=4,
                                       validate=False)

    def test_window_size_clamps_to_model_context(self, tmp_cwd):
        """``window_size`` narrows the scan-carry ring but can never
        widen past the model's positional table."""
        from relayrl_tpu.runtime.anakin import AnakinActorHost

        wide = AnakinActorHost(_seq_bundle(max_seq_len=8), "CartPole-v1",
                               num_envs=1, unroll_length=4,
                               window_size=512, seed=0)
        assert wide._window_size == 8
        narrow = AnakinActorHost(_seq_bundle(max_seq_len=8), "CartPole-v1",
                                 num_envs=1, unroll_length=4,
                                 window_size=0, seed=0)
        assert narrow._window_size == 1


_DETERMINISM_SCRIPT = """
import hashlib, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
from relayrl_tpu.models import build_policy
from relayrl_tpu.types.model_bundle import ModelBundle
from relayrl_tpu.runtime.anakin import AnakinActorHost

arch = {"kind": "mlp_discrete", "obs_dim": 4, "act_dim": 2,
        "hidden_sizes": [16]}
policy = build_policy(arch)
bundle = ModelBundle(version=0, arch=arch,
                     params=policy.init_params(jax.random.PRNGKey(0)))
h = hashlib.sha256()
host = AnakinActorHost(bundle, "CartPole-v1", num_envs=4, unroll_length=32,
                       on_send=lambda lane, p: h.update(p), seed=123)
host.rollout()
host.rollout()
h.update(repr(host.episode_returns).encode())
print("WINDOW_SHA", h.hexdigest())
"""


def test_cross_process_determinism(tmp_path):
    """Same seed ⇒ byte-identical trajectory windows across two FRESH
    processes: the fused rollout (policy sampling, env dynamics, in-scan
    autoresets, unstacker, wire codec) is a pure function of
    (params, seed). This is the determinism half of the golden
    acceptance; the numpy-parity half lives in tests/test_jax_envs.py."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    digests = []
    for _ in range(2):
        out = subprocess.run([sys.executable, "-c", _DETERMINISM_SCRIPT],
                             capture_output=True, text=True, timeout=300,
                             env=env, cwd=str(tmp_path))
        assert out.returncode == 0, out.stderr[-2000:]
        digests.append(out.stdout.split("WINDOW_SHA")[1].strip())
    assert digests[0] == digests[1]


class TestFusedSequenceRollout:
    def test_window_helpers_agree(self):
        """``push_window`` (host numpy rule) and ``window_advance``
        (its functional scan-carry twin) are ONE rule: identical ring
        bytes + length at every step through fill, roll, and past
        capacity."""
        import jax.numpy as jnp

        from relayrl_tpu.runtime.policy_actor import (push_window,
                                                      window_advance)

        rng = np.random.default_rng(0)
        win_np = np.zeros((4, 3), np.float32)
        win_jx = jnp.zeros((4, 3), jnp.float32)
        len_np, len_jx = 0, jnp.int32(0)
        adv = jax.jit(window_advance)
        for step in range(11):
            obs = rng.standard_normal(3).astype(np.float32)
            len_np, rolled = push_window(win_np, len_np, obs)
            win_jx, len_jx = adv(win_jx, len_jx, obs)
            np.testing.assert_array_equal(win_np, np.asarray(win_jx))
            assert len_np == int(len_jx)
            assert rolled == (step >= 4)

    def test_fused_sequence_ships_episodes(self, tmp_cwd):
        """A windowed transformer runs INSIDE the scan: per-record wire
        episodes carry f32 obs plus the logp_a/v aux, and ``record_bver``
        stamps the behavior version on every step (the RLHF V-trace
        evidence)."""
        from relayrl_tpu import telemetry
        from relayrl_tpu.runtime.anakin import AnakinActorHost
        from relayrl_tpu.types.trajectory import deserialize_actions

        telemetry.reset_for_tests()
        telemetry.set_registry(telemetry.Registry(run_id="fused-seq"))
        sent: list[bytes] = []
        host = AnakinActorHost(
            _seq_bundle(max_seq_len=8, version=3, uniform=True),
            "CartPole-v1",
            num_envs=4, unroll_length=64, columnar_wire=False,
            record_bver=True,
            on_send=lambda lane, p: sent.append(p), seed=2)
        host.rollout()
        assert len(sent) >= 4
        for payload in sent:
            acts = deserialize_actions(payload)
            marker, steps = acts[-1], acts[:-1]
            assert marker.done and marker.act is None
            for rec in steps:
                assert rec.obs.dtype == np.float32
                assert set(rec.data) == {"logp_a", "v", "bver"}
                assert int(rec.data["bver"]) == 3
        names = {m["name"]
                 for m in telemetry.get_registry().snapshot()["metrics"]}
        telemetry.reset_for_tests()
        assert "relayrl_actor_window_size" in names


class _JaxVectorTwin:
    """Gym-like vector facade over the SAME on-device env stream the
    fused host scans: identical key derivation (the ``0x0E74`` env-root
    fold, one 2N reset split into init + carry keys) and the identical
    ``step_autoreset`` composition — so a vector-tier host driven through
    the REAL ``run_vector_gym_loop`` replays the fused scan's exact
    observation/reward/done stream on the host side."""

    def __init__(self, env, num_envs: int, seed: int):
        from relayrl_tpu.envs.jax.base import step_autoreset

        self.env = env
        self.num_envs = int(num_envs)
        env_root = jax.random.fold_in(jax.random.PRNGKey(seed), 0x0E74)
        reset_keys = jax.random.split(env_root, 2 * num_envs)
        self._init_keys = reset_keys[:num_envs]
        self._keys = reset_keys[num_envs:]
        self._states = None
        self._reset_fn = jax.jit(jax.vmap(env.reset))
        self._step_fn = jax.jit(jax.vmap(
            lambda k, s, a: step_autoreset(env, k, s, a)))

    def reset(self, seed=None):
        self._states, obs = self._reset_fn(self._init_keys)
        return np.asarray(obs), [{} for _ in range(self.num_envs)]

    def step(self, actions):
        import jax.numpy as jnp

        acts = jnp.asarray(np.asarray(actions))
        (self._keys, self._states, obs, rew, term, trunc,
         stepped) = self._step_fn(self._keys, self._states, acts)
        term, trunc = np.asarray(term), np.asarray(trunc)
        stepped = np.asarray(stepped)
        # run_vector_gym_loop's contract: the pre-reset observation rides
        # the per-lane info dict for the time-limit bootstrap.
        infos = [({"final_observation": stepped[i]}
                  if (term[i] or trunc[i]) else {})
                 for i in range(self.num_envs)]
        return np.asarray(obs), np.asarray(rew), term, trunc, infos


class TestFusedSequenceCrossTierParity:
    """THE acceptance golden: the fused sequence scan ships episodes
    BYTE-identical to the vector-tier ``step_window`` path at the same
    seed + params — across in-scan autoreset boundaries (the rolling
    window must reset, never leak between episodes), through genuine
    terminations AND time-limit truncations (the bootstrap ``final_obs``
    marker), in both wire forms."""

    # max_steps=18 against random-policy CartPole episode lengths
    # (``_seq_bundle(uniform=True)``: uniform sampling by construction)
    # gives every run BOTH ending kinds (pole falls < 18 / time limit at
    # 18) while the W=8 ring still rolls well past capacity.
    N, UNROLL, SEED, MAX_STEPS = 2, 150, 3, 18

    def _run_fused(self, columnar: bool):
        from relayrl_tpu.envs.jax import JaxCartPole
        from relayrl_tpu.runtime.anakin import AnakinActorHost

        per_lane: dict[int, list[bytes]] = {k: [] for k in range(self.N)}
        host = AnakinActorHost(
            _seq_bundle(max_seq_len=8, uniform=True),
            JaxCartPole(max_steps=self.MAX_STEPS),
            num_envs=self.N, unroll_length=self.UNROLL,
            columnar_wire=columnar,
            on_send=lambda lane, p: per_lane[lane].append(p),
            seed=self.SEED)
        host.rollout()
        return per_lane

    def _run_vector(self):
        from relayrl_tpu.envs.jax import JaxCartPole
        from relayrl_tpu.runtime.vector_actor import (VectorActorHost,
                                                      run_vector_gym_loop)

        per_lane: dict[int, list[bytes]] = {k: [] for k in range(self.N)}
        host = VectorActorHost(
            _seq_bundle(max_seq_len=8, uniform=True), num_envs=self.N,
            on_send=lambda lane, p: per_lane[lane].append(p),
            seed=self.SEED)
        twin = _JaxVectorTwin(JaxCartPole(max_steps=self.MAX_STEPS),
                              self.N, self.SEED)
        run_vector_gym_loop(host, twin, steps=self.UNROLL)
        return per_lane

    def test_per_record_wire_bytes_identical(self, tmp_cwd):
        from relayrl_tpu.types.trajectory import deserialize_actions

        fused = self._run_fused(columnar=False)
        vector = self._run_vector()
        markers = []
        for lane in range(self.N):
            # Enough episodes that the W=8 ring rolled and reset across
            # several in-scan autoreset boundaries.
            assert len(fused[lane]) >= 2, "need autoreset boundaries"
            assert fused[lane] == vector[lane], (
                f"lane {lane}: fused scan bytes diverged from the "
                f"vector step_window tier")
            markers += [deserialize_actions(p)[-1] for p in fused[lane]]
        # The stream crossed both ending kinds (truncation ships the
        # bootstrap obs; termination ships none).
        assert any(m.truncated for m in markers)
        assert any(not m.truncated for m in markers)

    def test_columnar_frames_decode_identical_to_vector_tier(self,
                                                             tmp_cwd):
        """The columnar wire form of the SAME contract: a fused frame
        parses into exactly the DecodedTrajectory the native decoder
        produces from the vector tier's per-record payload."""
        from relayrl_tpu.types.columnar import (NativeDecoder,
                                                native_codec_available,
                                                parse_frame)

        if not native_codec_available():
            pytest.skip("native codec unavailable")
        fused = self._run_fused(columnar=True)
        vector = self._run_vector()
        dec = NativeDecoder()
        for lane in range(self.N):
            assert len(fused[lane]) == len(vector[lane]) >= 2
            for frame, payload in zip(fused[lane], vector[lane]):
                a = parse_frame(frame, agent_id="x")
                b = dec.decode(payload, agent_id="x")
                assert (a.n_steps, a.n_records, a.marker_truncated) == \
                    (b.n_steps, b.n_records, b.marker_truncated)
                assert set(a.columns) == set(b.columns)
                for k in a.columns:
                    assert a.columns[k].dtype == b.columns[k].dtype, k
                    assert a.columns[k].tobytes() == \
                        b.columns[k].tobytes(), k
                assert set(a.aux) == set(b.aux)
                for k in a.aux:
                    assert a.aux[k].tobytes() == b.aux[k].tobytes(), k
                assert (a.final_obs is None) == (b.final_obs is None)
                if a.final_obs is not None:
                    assert a.final_obs.tobytes() == b.final_obs.tobytes()


def _recall_bundle(seed=0, version=0, n_cues=4, window=8, obs_dim=None):
    """A two-layer windowed transformer (float32) for ``JaxRecall(n_cues)``
    (or any env of ``obs_dim``) at a context of ``window`` rows."""
    from relayrl_tpu.models import build_policy
    from relayrl_tpu.types.model_bundle import ModelBundle

    arch = {"kind": "transformer_discrete",
            "obs_dim": obs_dim or n_cues + 2, "act_dim": n_cues,
            "d_model": 16, "n_layers": 2, "n_heads": 2,
            "max_seq_len": window}
    params = build_policy(arch).init_params(jax.random.PRNGKey(seed))
    return ModelBundle(version=version, arch=arch, params=params)


def _counted(name: str, **labels):
    """A metric's value, summed over its label sets that match ``labels``
    (``relayrl_actor_cache_bytes`` has one a kind)."""
    from relayrl_tpu import telemetry

    return sum(m["value"]
               for m in telemetry.get_registry().snapshot()["metrics"]
               if m["name"] == name and all(
                   m["labels"].get(k) == v for k, v in labels.items()))


@pytest.fixture
def registry():
    from relayrl_tpu import telemetry

    telemetry.reset_for_tests()
    telemetry.set_registry(telemetry.Registry(run_id="fused-cached"))
    yield
    telemetry.reset_for_tests()


class TestFusedCachedScan:
    """Where no episode can outgrow the window the scan's carry holds
    each lane's decode cache and a step is ``policy.step_cached`` for the
    one new row (``runtime/anakin.carry_holds_cache``). Held here, at toy
    size in float32, against the SAME host built on the window program —
    ``make_fused_rollout(..., cached=False)``, which the host builds when
    the rule says no; the tests make it say no — as the process tier's
    pair is held (``tests/test_kv_cache.py``): the same action at the same
    key, ``logp_a`` and ``v`` to float rounding, not to the byte."""

    LANES, W, CUES = 3, 8, 4

    def _host(self, monkeypatch, cached: bool, unroll: int, sink=None,
              columnar=True, bundle=None):
        from relayrl_tpu.runtime import anakin

        with monkeypatch.context() as m:
            if not cached:
                m.setattr(anakin, "carry_holds_cache", lambda *a: False)
            host = anakin.AnakinActorHost(
                bundle or _recall_bundle(), "Recall-v0",
                num_envs=self.LANES, unroll_length=unroll, seed=5,
                columnar_wire=columnar, on_send=sink,
                horizon=self.W, n_cues=self.CUES)
        assert len(host._carry) == (7 if cached else 6)
        return host

    @staticmethod
    def _keep_windows(host) -> list:
        """The stacked window of every LAUNCH from here on, as the host
        looks its producer up: the constructor's window is in flight
        already and is not among them, and the last one kept is the one a
        last ``rollout()`` left in flight."""
        windows, produce = [], host._rollout_fn

        def kept(params, explore, carry):
            carry, window = produce(params, explore, carry)
            windows.append(jax.device_get(window))
            return carry, window

        host._rollout_fn = kept
        return windows

    @staticmethod
    def _assert_windows_agree(got, want, atol=1e-4):
        assert len(got) == len(want)
        for a, b in zip(got, want):
            for k in ("obs", "act", "rew", "term", "trunc", "final_obs"):
                np.testing.assert_array_equal(a[k], b[k], err_msg=k)
            for k in ("logp_a", "v"):
                assert np.all(np.isfinite(a["aux"][k])), k
                np.testing.assert_allclose(a["aux"][k], b["aux"][k],
                                           atol=atol, rtol=0, err_msg=k)

    @pytest.mark.parametrize("columnar", [True, False],
                             ids=["columnar", "records"])
    def test_cached_steps_equal_the_window_programs(self, tmp_cwd,
                                                    monkeypatch, registry,
                                                    columnar):
        """Two dispatches of 20 steps at a horizon of 8: five in-scan
        resets a lane, none on a dispatch's edge. Step for step the same
        actions and episode boundaries, ``logp_a`` / ``v`` within 1e-4,
        and the same number of payloads of the same sizes on the wire."""
        sent = {True: [], False: []}
        windows = {}
        for cached in (True, False):
            host = self._host(
                monkeypatch, cached, unroll=20, columnar=columnar,
                sink=lambda lane, p, _c=cached: sent[_c].append(
                    (lane, len(p))))
            windows[cached] = self._keep_windows(host)
            host.rollout()
            host.rollout()
            assert (host._cache_bytes > 0) == cached
        self._assert_windows_agree(windows[True], windows[False])
        assert sum(int(w["term"].sum()) for w in windows[True]) == \
            self.LANES * 5
        assert sent[True] == sent[False] and sent[True]
        steps = 2 * self.LANES * 20
        assert _counted("relayrl_actor_env_steps_total") == 2 * steps
        # the window-program host counts none
        assert _counted("relayrl_actor_cached_steps_total") == steps

    def test_a_reset_reads_nothing_of_the_cache_it_starts_over(
            self, tmp_cwd, monkeypatch):
        """The cache is not zeroed at an in-scan reset (decision 2 of the
        change that brought it): a cached step at ``t`` masks every row
        after ``t``, and the new episode overwrites them in order. After a
        dispatch that ends every lane's episode, every key row is made NaN
        and every value row 1e30 (a masked score is replaced, so a NaN
        there is never read; a masked value row is multiplied by a weight
        of exactly 0, which a NaN would survive and a finite row does
        not — rows a lane has written are finite). The next episodes read
        what an untouched twin reads."""
        hosts = [self._host(monkeypatch, True, unroll=self.W)
                 for _ in range(2)]
        windows = [self._keep_windows(h) for h in hosts]
        for host in hosts:
            host.rollout()
            # the carry the window in flight returns: its episodes ended too
            assert np.all(np.asarray(host._carry[5]) == 0)  # all reset
        *rest, cache = hosts[0]._carry
        hosts[0]._carry = (*rest, tuple(
            (jax.numpy.full_like(k, np.nan), jax.numpy.full_like(v, 1e30))
            for k, v in cache))
        for host in hosts:
            host.rollout()
        self._assert_windows_agree(windows[0], windows[1])

    def test_a_swap_rebuilds_the_cache_from_the_ring(self, tmp_cwd,
                                                     monkeypatch, registry):
        """``maybe_swap`` mid-episode, with the constructor's window in
        flight (5 rows of 8 in every ring, the carry that window returns):
        the next window LAUNCHED equals the window program's after the same
        swap — the keys and values of the first five rows are the NEW
        parameters' — and the rebuild is counted once. A stale bundle
        rebuilds nothing."""
        windows = {}
        for cached in (True, False):
            host = self._host(monkeypatch, cached, unroll=5)
            windows[cached] = self._keep_windows(host)
            assert np.all(np.asarray(host._carry[5]) == 5)
            assert host.maybe_swap(_recall_bundle(seed=1, version=1))
            assert host._cache_version == 0     # no launch since the swap
            host.rollout()      # launches the first window of version 1
            assert host._cache_version == (1 if cached else 0)
            assert not host.maybe_swap(_recall_bundle(seed=2, version=1))
            host.rollout()
            host.rollout()
        self._assert_windows_agree(windows[True], windows[False])
        assert _counted("relayrl_actor_cache_rebuilds_total") == 1
        # the swap changed what is emitted: the check can tell
        stale = self._host(monkeypatch, True, unroll=5)
        kept = self._keep_windows(stale)
        stale.rollout()
        assert not np.allclose(kept[0]["aux"]["v"],
                               windows[True][0]["aux"]["v"], atol=1e-4)

    @staticmethod
    def _rule_cases():
        from relayrl_tpu.envs.jax import (JaxCartPole, JaxRecall,
                                          JaxTokenGen)

        class NoStatedLimit(JaxCartPole):
            def __init__(self):
                super().__init__(max_steps=6)
                self.max_episode_steps = None

        return {
            "cartpole-outgrows-window": (
                lambda: JaxCartPole(max_steps=18), 4, 2, False),
            "no-stated-limit": (NoStatedLimit, 4, 2, False),
            "recall-fits-window": (
                lambda: JaxRecall(horizon=8, n_cues=4), 6, 4, True),
            "tokengen-fits-window": (
                lambda: JaxTokenGen(vocab_size=4, prompt_len=2,
                                    max_new_tokens=6), 8, 4, True),
        }

    @pytest.mark.parametrize("case", [
        "cartpole-outgrows-window", "no-stated-limit",
        "recall-fits-window", "tokengen-fits-window"])
    def test_the_rule_reads_the_episode_limit(self, tmp_cwd, registry, case):
        """Cached iff the environment states a limit on an episode's steps
        no longer than the window: the window program keeps the six
        carry leaves it had and a gauge of 0 bytes."""
        from relayrl_tpu.runtime.anakin import AnakinActorHost

        make_env, obs_dim, act_dim, cached = self._rule_cases()[case]
        host = AnakinActorHost(
            _recall_bundle(n_cues=act_dim, obs_dim=obs_dim), make_env(),
            num_envs=2, unroll_length=12, seed=1)
        assert host._window_size == 8
        assert len(host._carry) == (7 if cached else 6)
        assert (host._rebuild_fn is not None) == cached
        assert (_counted("relayrl_actor_cache_bytes") > 0) == cached
        out = host.rollout()
        assert _counted("relayrl_actor_cached_steps_total") == (
            out["steps"] if cached else 0)

    def test_a_state_without_positions_rides_the_carry_too(self, tmp_cwd):
        """A trunk with a layer whose state has no positions (a
        convolution's last rows) reads that state as zeros at position 0,
        so a new episode may start over a used cache and the rule takes it
        (``Policy.cache_restarts``; ``tests/test_granite_rollout.py`` holds
        such trunks to the window program across resets). A trunk with a
        layer whose operator does not declare the restart — a latent
        layer's compressed rows — keeps the window program, whatever the
        environment states."""
        from relayrl_tpu.envs.jax import JaxRecall
        from relayrl_tpu.models import build_policy
        from relayrl_tpu.runtime.anakin import carry_holds_cache

        arch = dict(_recall_bundle().arch)
        env = JaxRecall(horizon=8, n_cues=4)
        assert carry_holds_cache(build_policy(arch), env, 8)
        assert not carry_holds_cache(build_policy(arch), env, 7)
        mixed = build_policy({**arch, "layer_types": ["conv",
                                                      "full_attention"]})
        assert mixed.step_cached is not None and mixed.cache_restarts
        assert carry_holds_cache(mixed, env, 8)
        assert not carry_holds_cache(mixed, env, 7)
        latent = build_policy({**arch, "layer_types": ["latent_attention",
                                                       "full_attention"]})
        assert latent.step_cached is not None
        assert not latent.cache_restarts
        assert not carry_holds_cache(latent, env, 8)

    def test_the_carry_holds_lanes_times_init_cache_and_the_same_window(
            self, tmp_cwd, monkeypatch):
        """A count, no time: the cache in the carry is ``lanes`` times
        ``policy.init_cache(W)``, byte for byte of shape, and the window a
        dispatch hands back has the window program's keys, shapes and
        dtypes."""
        hosts = {c: self._host(monkeypatch, c, unroll=4)
                 for c in (True, False)}
        one = hosts[True].policy.init_cache(self.W)
        per_lane = sum(x.nbytes for x in jax.tree.leaves(one))
        assert hosts[True]._cache_bytes == self.LANES * per_lane > 0
        assert hosts[False]._cache_bytes == 0
        assert jax.tree.map(lambda x: (self.LANES, *x.shape), one) == \
            jax.tree.map(lambda x: x.shape, hosts[True]._carry[6])
        shapes = {c: jax.eval_shape(h._rollout_fn, h.params,
                                    h._explore_kwargs, h._carry)[1]
                  for c, h in hosts.items()}
        assert jax.tree.structure(shapes[True]) == \
            jax.tree.structure(shapes[False])
        assert set(shapes[True]) == {"obs", "act", "rew", "term", "trunc",
                                     "final_obs", "aux"}
        assert jax.tree.leaves(shapes[True]) == \
            jax.tree.leaves(shapes[False])


class TestWindowInFlight:
    """The host keeps one window in flight ahead of itself
    (``AnakinActorHost.rollout``): window k+1 is launched before window k
    is waited for, fetched and emitted. Held here by the ORDER of the
    host's own calls, by what a window carries from its launch across a
    swap, by the account of launched steps at ``close()``, and by where
    the cache rebuild runs — no time is read."""

    STEPS = 2 * 4   # lanes x unroll of every host below

    @staticmethod
    def _host(kind: str, monkeypatch, **kw):
        from relayrl_tpu.runtime import anakin

        if kind == "mlp":
            return anakin.AnakinActorHost(
                kw.pop("bundle", None) or _bundle(), "CartPole-v1",
                num_envs=2, unroll_length=4, seed=3, **kw)
        with monkeypatch.context() as m:
            if kind == "window":
                m.setattr(anakin, "carry_holds_cache", lambda *a: False)
            host = anakin.AnakinActorHost(
                kw.pop("bundle", None) or _recall_bundle(), "Recall-v0",
                num_envs=2, unroll_length=4, seed=3, horizon=8, n_cues=4,
                **kw)
        assert (host._rebuild_fn is not None) == (kind == "cached")
        return host

    @staticmethod
    def _log_launches(host, events: list | None = None) -> list:
        """What each launch from here on was passed and handed back; with
        ``events``, ``("launch", id of the window)`` into it a launch."""
        launches, produce = [], host._rollout_fn
        events = [] if events is None else events

        def logged(params, explore, carry):
            new, window = produce(params, explore, carry)
            launches.append({"params": params, "carry_in": carry,
                             "carry_out": new, "window": window,
                             "done_ns": time.monotonic_ns()})
            events.append(("launch", id(window)))
            return new, window

        host._rollout_fn = logged
        return launches

    @staticmethod
    def _keep_emitted(host, monkeypatch) -> list:
        """Every window the host emits, as its emit was handed it, with
        the production stamp the host held then."""
        emitted = []
        for name in ("_emit_columnar", "_unstack"):
            emit = getattr(host, name)

            def kept(w, _emit=emit):
                emitted.append({"window": w, "born_ns": host._window_born_ns})
                return _emit(w)

            monkeypatch.setattr(host, name, kept)
        return emitted

    @pytest.mark.parametrize("kind", ["mlp", "window", "cached"])
    def test_the_next_window_is_launched_before_the_last_is_fetched(
            self, tmp_cwd, monkeypatch, kind):
        """The constructor launches window 0. Call k of ``rollout()``
        launches window k+1, THEN waits for window k and fetches it: the
        window fetched is never the newest launched. One path over the
        feed-forward step, the window program and the cached one."""
        host = self._host(kind, monkeypatch)
        first = host._in_flight[0].window
        assert len(host._in_flight) == 1
        events: list = []
        launches = self._log_launches(host, events)
        for kind, name in (("wait", "block_until_ready"),
                           ("get", "device_get")):
            def logged(x, _kind=kind, _fn=getattr(jax, name)):
                events.append((_kind, id(x)))
                return _fn(x)

            monkeypatch.setattr(jax, name, logged)
        for _ in range(3):
            host.rollout()
            assert len(host._in_flight) == 1
        monkeypatch.undo()
        launched = [first] + [rec["window"] for rec in launches]
        assert len(launched) == 4
        want = []
        for k in range(3):
            want += [("launch", id(launched[k + 1])),
                     ("wait", id(launched[k])), ("get", id(launched[k]))]
        assert events == want
        assert host._in_flight[0].window is launched[3]
        # every launch consumed the carry the launch before it returned
        for before, after in zip(launches, launches[1:]):
            assert after["carry_in"] is before["carry_out"]
        host.close()

    @pytest.mark.parametrize("wire", ["columnar", "records", "async"])
    def test_a_window_carries_the_version_and_stamp_of_its_launch(
            self, tmp_cwd, monkeypatch, wire):
        """``swap_from_wire`` between two ``rollout()`` calls: the call
        after the swap still returns a window of the OLD version, stamped
        as such (it was in flight when the swap landed); the new version
        first shows one window later, in the window that call launched.
        Every emitted window's ``bver`` is the version whose parameters
        its launch was passed, and its ``born_ns`` lies inside its own
        launch."""
        from relayrl_tpu.transport.modelwire import ModelWireEncoder

        built_ns = time.monotonic_ns()
        host = self._host(
            "cached", monkeypatch, bundle=_recall_bundle(version=3),
            record_bver=True, columnar_wire=wire != "records",
            async_emit=wire == "async")
        launches = self._log_launches(host)
        emitted = self._keep_emitted(host, monkeypatch)
        by_version = {3: host.params}
        first_call_ns = time.monotonic_ns()
        host.rollout()
        newer = _recall_bundle(seed=1, version=7)
        frame, _info = ModelWireEncoder().encode(
            7, newer.arch, jax.tree.map(np.asarray, newer.params))
        assert host.swap_from_wire(7, frame) is not None
        by_version[7] = host.params
        assert host.version == 7 and by_version[7] is not by_version[3]
        for _ in range(3):
            host.rollout()
        assert host.flush_emits()
        bver = [np.unique(e["window"]["aux"]["bver"]).tolist()
                for e in emitted]
        assert bver == [[3], [3], [7], [7]]
        # windows 1.. were launched under the log: what each was passed
        for rec, e, version in zip(launches, emitted[1:], [3, 7, 7]):
            assert rec["params"] is by_version[version]
            assert np.unique(e["window"]["aux"]["bver"]).tolist() == [version]
        assert launches[3]["params"] is by_version[7]   # the one in flight
        # the stamp is the launch's: after the launch before, before its own
        # (the constructor's window: the first call's, which takes it)
        done = [built_ns] + [rec["done_ns"] for rec in launches]
        born = [e["born_ns"] for e in emitted]
        assert built_ns < first_call_ns <= born[0] < done[1]
        for k in (1, 2, 3):
            assert done[k - 1] <= born[k] < done[k]
        host.close()

    @staticmethod
    def _short_episodes(sent: list, **kw):
        """Two lanes of CartPole cut at 5 steps under windows of 4: an
        episode ends inside every window from the second on, and every
        payload the host ships lands in ``sent``."""
        from relayrl_tpu.envs.jax import JaxCartPole
        from relayrl_tpu.runtime.anakin import AnakinActorHost

        return AnakinActorHost(
            _bundle(), JaxCartPole(max_steps=5), num_envs=2, unroll_length=4,
            seed=3, on_send=lambda lane, p: sent.append((lane, bytes(p))),
            **kw)

    @pytest.mark.parametrize("wire", ["columnar", "records", "async"])
    def test_the_window_in_flight_outlives_close_and_no_frame_has_a_gap(
            self, tmp_cwd, monkeypatch, registry, wire):
        """``close()`` leaves the window in flight where it is, and the
        first ``rollout()`` of a host enabled again returns it: across the
        agent's disable/enable cycle the FRAMES that reach the wire, decoded,
        are those of a twin that never closed — no step missing, every
        episode's end in its place, the same returns — and at every close
        emitted + in flight = launched."""
        from relayrl_tpu.types.columnar import parse_frame
        from relayrl_tpu.types.trajectory import deserialize_actions

        def gauge():
            return _counted("relayrl_actor_windows_in_flight")

        def account(host, launched):
            assert len(host._in_flight) == gauge() == 1
            assert (_counted("relayrl_actor_env_steps_total") + self.STEPS
                    == launched * self.STEPS)

        kw = {"columnar_wire": wire != "records",
              "async_emit": wire == "async"}
        twin_sent: list = []
        twin = self._short_episodes(twin_sent, **kw)
        for _ in range(6):
            twin.rollout()
        assert twin.flush_emits()
        twin.close()
        from relayrl_tpu import telemetry
        telemetry.set_registry(telemetry.Registry(run_id="in-flight"))

        sent: list = []
        host = self._short_episodes(sent, **kw)
        assert gauge() == 1
        launches = self._log_launches(host)
        for _ in range(3):
            host.rollout()
            assert gauge() == 1
        host.close()                    # windows 0-2 emitted, 3 in flight
        held = host._in_flight[0]
        account(host, launched=4)
        host.close()                    # and again: nothing to do
        account(host, launched=4)
        host.start_emitter()            # the agent's enable
        host.rollout()
        assert launches[2]["window"] is held.window     # the one returned
        for _ in range(2):
            host.rollout()
        assert host.flush_emits()
        host.close()
        assert len(launches) + 1 == 7
        account(host, launched=7)

        def decoded(payloads):
            if wire == "records":
                return [(lane, [(r.obs, r.act, r.rew, r.done, r.truncated)
                                for r in deserialize_actions(p)])
                        for lane, p in payloads]
            frames = [(lane, parse_frame(p)) for lane, p in payloads]
            return [(lane, f.n_steps, f.n_records, f.marker_truncated,
                     f.columns, f.aux, f.final_obs) for lane, f in frames]

        assert sent == twin_sent and len(sent) >= 6
        jax.tree.map(np.testing.assert_array_equal,
                     decoded(sent), decoded(twin_sent))
        assert host.episode_returns == twin.episode_returns
        assert sum(map(len, host.episode_returns)) >= 6

    def test_a_wait_that_raises_leaves_both_windows_in_flight(
            self, tmp_cwd, monkeypatch, registry):
        """The wait for a window raises (an interrupt, a device error): the
        call raises, nothing was dropped — both windows stay in flight —
        and the next call launches none and returns the window the last one
        waited for, so the stream is a twin's that never failed."""
        twin_sent: list = []
        twin = self._short_episodes(twin_sent)
        for _ in range(3):
            twin.rollout()
        sent: list = []
        host = self._short_episodes(sent)
        launches = self._log_launches(host)
        host.rollout()
        emitted = _counted("relayrl_actor_env_steps_total")
        wait = jax.block_until_ready

        def fails_once(x):
            monkeypatch.setattr(jax, "block_until_ready", wait)
            raise KeyboardInterrupt

        monkeypatch.setattr(jax, "block_until_ready", fails_once)
        with pytest.raises(KeyboardInterrupt):
            host.rollout()
        assert len(host._in_flight) == 2 == len(launches)
        assert _counted("relayrl_actor_windows_in_flight") == 2
        assert _counted("relayrl_actor_env_steps_total") == emitted
        waited_for = host._in_flight[0]
        host.rollout()
        assert len(launches) == 2
        assert [w is waited_for for w in host._in_flight] == [False]
        host.rollout()
        assert len(launches) == 3 and len(host._in_flight) == 1
        assert sent == twin_sent and sent

    def test_a_launch_behind_a_finished_window_observes_its_gap(
            self, tmp_cwd, monkeypatch, registry):
        """One observation a launch of ``rollout()``: above 0 where the
        window in flight had already finished when the call came (the
        device had nothing queued), which a wait before the call makes
        certain here."""
        from relayrl_tpu import telemetry

        def gap():
            return next(
                m for m in telemetry.get_registry().snapshot()["metrics"]
                if m["name"] == "relayrl_actor_rollout_launch_gap_seconds")

        host = self._host("mlp", monkeypatch)
        assert gap()["count"] == 0
        jax.block_until_ready(host._in_flight[0].window)
        host.rollout()
        first = gap()
        assert first["count"] == 1 and first["sum"] > 0
        host.rollout()
        assert gap()["count"] == 2
        host.close()

    def test_the_rebuild_runs_once_a_swap_ahead_of_the_first_new_launch(
            self, tmp_cwd, monkeypatch, registry):
        """After a swap the caches are rebuilt once, at the next LAUNCH and
        under the lock with it: on the newest carry — what the window in
        flight returns — and under the new parameters, which the launch
        right behind it is passed. No launch before it saw them; no launch
        after it rebuilds again."""
        host = self._host("cached", monkeypatch)
        events: list = []
        launches = self._log_launches(host, events)
        rebuilds, rebuild = [], host._rebuild_fn

        def logged(params, carry):
            assert host._lock.locked()
            new = rebuild(params, carry)
            rebuilds.append({"params": params, "carry_in": carry,
                             "carry_out": new})
            events.append(("rebuild", None))
            return new

        host._rebuild_fn = logged
        host.rollout()
        old = host.params
        assert host.maybe_swap(_recall_bundle(seed=1, version=1))
        assert not rebuilds                      # a swap launches nothing
        for _ in range(3):
            host.rollout()
        assert [kind for kind, _ in events] == [
            "launch", "rebuild", "launch", "launch", "launch"]
        assert len(rebuilds) == 1
        assert _counted("relayrl_actor_cache_rebuilds_total") == 1
        assert launches[0]["params"] is old
        assert rebuilds[0]["params"] is host.params is not old
        assert rebuilds[0]["carry_in"] is launches[0]["carry_out"]
        assert launches[1]["carry_in"] is rebuilds[0]["carry_out"]
        assert all(rec["params"] is host.params for rec in launches[1:])
        host.close()


class TestConfigKnobs:
    def test_actor_params_anakin(self, tmp_path):
        from relayrl_tpu.config import ConfigLoader

        path = tmp_path / "cfg.json"
        path.write_text(json.dumps({"actor": {
            "host_mode": "anakin", "num_envs": 8,
            "unroll_length": 128, "jax_env": "Pendulum-v1"}}))
        params = ConfigLoader(None, str(path)).get_actor_params()
        assert params["host_mode"] == "anakin"
        assert params["unroll_length"] == 128
        assert params["jax_env"] == "Pendulum-v1"

    def test_actor_params_anakin_defaults_and_clamps(self, tmp_path):
        from relayrl_tpu.config import ConfigLoader

        path = tmp_path / "cfg.json"
        path.write_text(json.dumps({"actor": {
            "host_mode": "warp", "unroll_length": "bogus",
            "jax_env": None}}))
        params = ConfigLoader(None, str(path)).get_actor_params()
        assert params["host_mode"] == "process"  # unknown mode degrades
        assert params["unroll_length"] == 32
        assert params["jax_env"] == "CartPole-v1"

    def test_actor_window_size_clamps(self, tmp_path):
        from relayrl_tpu.config import ConfigLoader

        counter = iter(range(100))

        def load(actor):
            path = tmp_path / f"cfg{next(counter)}.json"
            path.write_text(json.dumps({"actor": actor}))
            return ConfigLoader(None, str(path)).get_actor_params()

        assert load({})["window_size"] is None  # defer to model context
        assert load({"window_size": 12})["window_size"] == 12
        assert load({"window_size": -3})["window_size"] == 1
        assert load({"window_size": "bogus"})["window_size"] is None


class TestNetworkedAnakinZmq:
    # ISSUE 17 wall re-fit: live-zmq anakin e2e rides the slow tier; the
    # fast tier keeps cross-process determinism + the unstacker contract.
    @pytest.mark.slow
    def test_lanes_register_stream_and_hot_swap(self, tmp_cwd):
        """The networked anakin tier against a live zmq TrainingServer:
        N logical lanes register over one connection, every lane's
        trajectories arrive attributed and dedup-accounted, the learner
        trains, and the published model hot-swaps back into the fused
        host (version advances between windows)."""
        from relayrl_tpu.runtime.agent import VectorAgent
        from relayrl_tpu.runtime.server import TrainingServer

        addrs = {
            "agent_listener_addr": f"tcp://127.0.0.1:{free_port()}",
            "trajectory_addr": f"tcp://127.0.0.1:{free_port()}",
            "model_pub_addr": f"tcp://127.0.0.1:{free_port()}",
        }
        agent_addrs = {
            "agent_listener_addr": addrs["agent_listener_addr"],
            "trajectory_addr": addrs["trajectory_addr"],
            "model_sub_addr": addrs["model_pub_addr"],
        }
        server = TrainingServer(
            "REINFORCE", obs_dim=4, act_dim=2, env_dir=str(tmp_cwd),
            hyperparams={"traj_per_epoch": 4, "hidden_sizes": [16],
                         "with_vf_baseline": True},
            **addrs)
        try:
            agent = VectorAgent(
                num_envs=4, server_type="zmq", handshake_timeout_s=30,
                seed=0, probe=False, host_mode="anakin",
                jax_env="CartPole-v1", unroll_length=32,
                identity="anakin-e2e", **agent_addrs)
            try:
                assert agent.host_mode == "anakin"
                v0 = agent.model_version
                deadline = time.monotonic() + 60
                while time.monotonic() < deadline:
                    agent.rollout()
                    if (agent.model_version > v0
                            and server.stats["updates"] >= 2):
                        break
                assert agent.model_version > v0, \
                    "fused host never hot-swapped a published model"
                server.drain(timeout=30)
                acct = server.ingest_accounting()
                lane_rows = {aid: row for aid, row in acct["agents"].items()
                             if aid.startswith("anakin-e2e.lane")}
                assert len(lane_rows) == 4  # every lane attributed
                for aid, row in lane_rows.items():
                    assert row["accepted"] >= 1 and row["contiguous"], (
                        aid, row)
                # guard rails of the anakin surface
                with pytest.raises(RuntimeError, match="rollout"):
                    agent.request_for_actions(np.zeros((4, 4), np.float32))
                with pytest.raises(RuntimeError, match="in-scan"):
                    agent.flag_last_action(0, 1.0)
            finally:
                agent.disable_agent()
        finally:
            server.disable_server()


def _read_status(scratch: str) -> dict | None:
    try:
        with open(os.path.join(scratch, "status.json")) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None


def _wait_status(scratch, proc, pred, timeout_s, what) -> dict:
    deadline = time.monotonic() + timeout_s
    status = None
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            out, _ = proc.communicate()
            raise AssertionError(
                f"chaos server died waiting for {what} "
                f"(rc={proc.returncode}):\n{out[-3000:]}")
        status = _read_status(scratch)
        if status is not None and pred(status):
            return status
        time.sleep(0.1)
    raise AssertionError(f"timed out waiting for {what}; last={status}")


# The fused-sequence drill trains a REINFORCE transformer: episodes must
# fit the positional table, so the env truncates at 48 and the bucket is
# 64 (carried in hyperparams — the subprocess scratch config has no
# learner section). The agent-side window (16) is narrower than the
# truncation horizon, so the scan ring genuinely rolls AND resets
# through the outage.
_SEQ_DRILL_HP = {
    "traj_per_epoch": 4, "model_kind": "transformer_discrete",
    "d_model": 16, "n_layers": 1, "n_heads": 2, "max_seq_len": 64,
    "bucket_lengths": [64], "with_vf_baseline": False,
}


@pytest.mark.slow  # ISSUE 17 wall re-fit: SIGKILL mechanism covered fast by test_recovery's zmq drill
@pytest.mark.parametrize("policy_kind", ["mlp", "sequence"])
def test_learner_sigkill_restart_with_anakin_actors_zero_loss(
        tmp_path, tmp_cwd, policy_kind):
    """The acceptance drill: SIGKILL the learner mid-run while a fused
    anakin host keeps producing windows INTO the outage (the env lives
    on the actor's device — env-steps never stop), restart with resume,
    and assert zero loss / zero double-train per LANE through the
    existing spool → replay → sequence-dedup plane, plus model-version
    continuity across the crash. Runs twice: the MLP scan and the
    fused-sequence (rolling-window transformer) scan — the spool/replay
    plane must be policy-shape-agnostic."""
    scratch = str(tmp_path)
    ports = [free_port() for _ in range(3)]
    server_addrs = {"agent_listener_addr": f"tcp://127.0.0.1:{ports[0]}",
                    "trajectory_addr": f"tcp://127.0.0.1:{ports[1]}",
                    "model_pub_addr": f"tcp://127.0.0.1:{ports[2]}"}
    agent_addrs = {"agent_listener_addr": f"tcp://127.0.0.1:{ports[0]}",
                   "trajectory_addr": f"tcp://127.0.0.1:{ports[1]}",
                   "model_sub_addr": f"tcp://127.0.0.1:{ports[2]}"}

    hyperparams = (dict(_SEQ_DRILL_HP) if policy_kind == "sequence"
                   else {"traj_per_epoch": 4, "hidden_sizes": [16, 16],
                         "with_vf_baseline": False})
    agent_env_kwargs = ({"jax_env_kwargs": {"max_steps": 48},
                         "window_size": 16}
                        if policy_kind == "sequence" else {})

    def spawn(resume: bool) -> subprocess.Popen:
        cfg = {
            "algorithm": "REINFORCE", "obs_dim": 4, "act_dim": 2,
            "hyperparams": hyperparams,
            "server_type": "zmq", "scratch": scratch,
            "checkpoint_every": 1, "resume": resume,
            "status_path": os.path.join(scratch, "status.json"),
            **server_addrs,
        }
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = REPO
        return subprocess.Popen(
            [sys.executable, os.path.join(DRILLS, "_chaos_server.py"),
             json.dumps(cfg)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)

    proc = spawn(resume=False)
    agent = None
    try:
        _wait_status(scratch, proc, lambda s: True, 120, "server up")
        from relayrl_tpu.runtime.agent import VectorAgent

        agent = VectorAgent(
            num_envs=2, server_type="zmq", handshake_timeout_s=60,
            seed=0, probe=False, host_mode="anakin",
            jax_env="CartPole-v1", unroll_length=16,
            identity="anakin-chaos", **agent_env_kwargs, **agent_addrs)
        # Phase 1: train until a checkpoint base exists.
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            agent.rollout()
            status = _read_status(scratch)
            if (status and status["version"] >= 2
                    and status["accounting"]["agents"]):
                break
            time.sleep(0.05)
        status = _read_status(scratch)
        assert status and status["version"] >= 2, "no training before kill"
        v_before = status["version"]
        agent_v_before = agent.model_version

        # Phase 2: SIGKILL — no shutdown path.
        proc.kill()
        proc.wait(timeout=30)

        # Phase 3: the fused host keeps rolling into the outage; windows
        # land in the spool (zmq PUSH is fire-and-forget into a dead pipe,
        # the spool retains them).
        for _ in range(6):
            agent.rollout()
        sent_during_outage = dict(agent.spool.sent_counts())
        assert sum(sent_during_outage.values()) > 0

        # Phase 4: restart with resume; the agent heals and trains past
        # the pre-kill version.
        proc = spawn(resume=True)
        _wait_status(scratch, proc, lambda s: True, 120, "server restart")
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            agent.rollout()
            status = _read_status(scratch)
            if (status and status["version"] > v_before
                    and agent.model_version > agent_v_before):
                break
            time.sleep(0.05)
        assert status["version"] > v_before, (
            f"server never trained past the crash: {status['version']} "
            f"<= {v_before}")
        assert agent.model_version > agent_v_before, (
            "fused host never resynced to the post-crash model line")

        # Phase 5: full replay, then per-LANE zero-loss accounting.
        agent.spool.replay()
        sent_counts = agent.spool.sent_counts()
        lane_ids = [aid for aid in sent_counts
                    if aid.startswith("anakin-chaos.lane")]
        assert len(lane_ids) == 2

        def recovered(s):
            rows = s["accounting"]["agents"]
            return all(
                rows.get(aid, {}).get("max_seq") == sent_counts[aid]
                and rows[aid]["contiguous"] for aid in lane_ids)

        status = _wait_status(scratch, proc, recovered, 120,
                              "zero-loss accounting for every lane")
        for aid in lane_ids:
            row = status["accounting"]["agents"][aid]
            assert row["accepted"] == sent_counts[aid], (
                f"loss or double-train on {aid}: {row} "
                f"vs sent={sent_counts[aid]}")
        assert status["accounting"]["duplicates"] >= 1  # replay surplus
    finally:
        if agent is not None:
            agent.disable_agent()
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.communicate(timeout=60)
            except subprocess.TimeoutExpired:
                proc.kill()
