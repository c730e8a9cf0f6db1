"""RLHF workload plane (ISSUE 13): scorers, the freeze mask, the
generate→score→update scheduler, and the acceptance locks.

Lock inventory (the ISSUE's acceptance criteria):

* generation through the scheduler is BIT-identical to a local
  ``step_window`` actor at the same seed + params version
  (TestGenerationBitIdentity — byte-equal wire payloads);
* frozen leaves are bit-identical before/after N updates under the
  ``learner.freeze`` mask, round-trip through checkpoint resume, and
  are skipped (counted in ``publish_bytes_saved``) by the wire-v2 delta
  encoder (TestFreezeMask);
* the SIGKILL chaos drill on the new plane: learner killed mid-run →
  spool replay → accepted == max_seq == sent per lane, zero loss, zero
  double-train, and the reward run still converges
  (test_chaos_learner_sigkill_rlhf_plane).
"""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from relayrl_tpu import telemetry
from tests._util import (
    assert_episode_payloads_match,
    free_port,
    zmq_addr_pair,
)

pytestmark = pytest.mark.rlhf

DRILLS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "drills")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.reset_for_tests()
    yield
    telemetry.reset_for_tests()


# ---------------------------------------------------------------------------
# scorers
# ---------------------------------------------------------------------------

class TestScorers:
    def test_programmatic_counts_successor_chain(self):
        from relayrl_tpu.envs.scorers import ProgrammaticScorer

        sc = ProgrammaticScorer(vocab_size=6)
        # prompt [2, 3]; generated [4, 5, 1, 0]: 4=3+1 hit, 5=4+1 hit,
        # 1 != 5+1 (=0 mod 6 is EOS anyway) miss, 0 is EOS (never counts)
        tokens = np.array([2, 3, 4, 5, 1, 0, 0], np.int32)
        assert sc.score_np(tokens, 2, 4) == 2.0
        # the same window scored as jax, bit-equal
        import jax.numpy as jnp

        assert float(sc.score_jax(jnp.asarray(tokens), 2, 4)) == 2.0
        # batch path agrees with singles
        batch = sc.score_batch_np(np.stack([tokens, tokens]), 2,
                                  np.array([4, 2]))
        assert batch[0] == 2.0 and batch[1] == sc.score_np(tokens, 2, 2)

    def test_reward_model_frozen_and_deterministic(self):
        from relayrl_tpu.envs.scorers import RewardModelScorer

        a = RewardModelScorer(vocab_size=6, context_len=8, seed=11)
        b = RewardModelScorer(vocab_size=6, context_len=8, seed=11)
        tokens = np.array([1, 2, 3, 4, 5, 0, 0, 0], np.int32)
        s = a.score_np(tokens, 2, 3)
        assert s == b.score_np(tokens, 2, 3), "same (shape, seed) must agree"
        assert -1.0 < s < 1.0, "tanh-squashed score"
        # batch path returns the identical bits as the single path
        batch = a.score_batch_np(np.stack([tokens, tokens]), 2,
                                 np.array([3, 3]))
        assert batch[0] == np.float32(s) == batch[1]
        # params are FROZEN: scoring never mutates them
        import jax

        before = jax.tree_util.tree_leaves(a.params)[0].copy()
        a.score_np(tokens, 2, 5)
        np.testing.assert_array_equal(
            before, jax.tree_util.tree_leaves(a.params)[0])

    def test_make_scorer_unknown_name(self):
        from relayrl_tpu.envs.scorers import make_scorer

        with pytest.raises(ValueError, match="programmatic"):
            make_scorer("nope")

    def test_tokengen_rm_parity_both_planes(self):
        """The RM-scored env: numpy twin and JAX twin pay the SAME
        reward bits at the terminal (both planes call one compiled
        scorer program)."""
        import jax
        import jax.numpy as jnp

        from relayrl_tpu.envs import TokenGenEnv, make_jax
        from relayrl_tpu.envs.scorers import RewardModelScorer

        rm = RewardModelScorer(vocab_size=5, context_len=6, seed=2)
        kwargs = dict(vocab_size=5, prompt_len=2, max_new_tokens=4,
                      scorer=rm)
        jenv = make_jax("TokenGen-v0", **kwargs)
        nenv = TokenGenEnv(**kwargs)
        nenv.reset(seed=0)
        step = jax.jit(jenv.step)
        key = jax.random.PRNGKey(9)
        rng = np.random.default_rng(9)
        terminals = 0
        key, sub = jax.random.split(key)
        state, _ = jenv.reset(sub)
        for _ in range(60):
            nenv._tokens = np.asarray(state.tokens, np.int32).copy()
            nenv._t = int(state.t)
            action = int(rng.integers(5))
            state, _obs, jrew, jterm, _tr = step(state, jnp.int32(action))
            _nobs, nrew, nterm, _nt, _ = nenv.step(action)
            assert np.float32(float(jrew)) == np.float32(nrew)
            assert bool(jterm) == nterm
            if bool(jterm):
                terminals += 1
                key, sub = jax.random.split(key)
                state, _ = jenv.reset(sub)
        assert terminals >= 5

    def test_jax_env_refuses_host_only_scorer(self):
        from relayrl_tpu.envs import make_jax

        with pytest.raises(ValueError, match="score_jax"):
            make_jax("TokenGen-v0", scorer=lambda tok, p, g: 0.0)


# ---------------------------------------------------------------------------
# score stage
# ---------------------------------------------------------------------------

def _generate_episode(seed: int, vocab=6, prompt_len=2, max_new=5):
    """One scorer-less TokenGen episode through a real PolicyActor
    (MLP), returning (payload bytes, actor)."""
    from relayrl_tpu.envs import TokenGenEnv
    from relayrl_tpu.runtime.policy_actor import PolicyActor
    from relayrl_tpu.types.model_bundle import ModelBundle
    from relayrl_tpu.models import build_policy
    import jax

    arch = {"kind": "mlp_discrete", "obs_dim": prompt_len + max_new,
            "act_dim": vocab, "hidden_sizes": [16], "has_critic": True}
    params = build_policy(arch).init_params(jax.random.PRNGKey(seed))
    sent = []
    actor = PolicyActor(ModelBundle(version=1, arch=arch, params=params),
                        on_send=sent.append, seed=seed)
    env = TokenGenEnv(vocab_size=vocab, prompt_len=prompt_len,
                      max_new_tokens=max_new, scorer=None)
    obs, _ = env.reset(seed=seed)
    for _ in range(max_new):
        rec = actor.request_for_action(obs)
        obs, _rew, term, _tr, _ = env.step(int(np.asarray(rec.act)))
        if term:
            actor.flag_last_action(0.0, terminated=True)
            break
    assert sent, "episode never shipped"
    return sent[0], env


def _fused_generation_frames(seed=0, vocab=6, prompt_len=2, max_new=6,
                             lanes=2, unroll=24):
    """Fused-scan TokenGen episodes as columnar frames through a real
    AnakinActorHost — the anakin generation tier's wire form (ISSUE 20):
    whole episodes, per-token logp_a/v aux, bver stamped at unstack."""
    import jax

    from relayrl_tpu.models import build_policy
    from relayrl_tpu.runtime.anakin import AnakinActorHost
    from relayrl_tpu.types.model_bundle import ModelBundle

    ctx = prompt_len + max_new
    arch = {"kind": "transformer_discrete", "obs_dim": ctx,
            "act_dim": vocab, "d_model": 16, "n_layers": 1, "n_heads": 2,
            "max_seq_len": ctx}
    policy = build_policy(arch)
    bundle = ModelBundle(version=2, arch=arch,
                         params=policy.init_params(jax.random.PRNGKey(seed)))
    sent: list[tuple[int, bytes]] = []
    host = AnakinActorHost(
        bundle, "TokenGen-v0", num_envs=lanes, unroll_length=unroll,
        columnar_wire=True, record_bver=True,
        on_send=lambda lane, p: sent.append((lane, p)), seed=seed,
        vocab_size=vocab, prompt_len=prompt_len, max_new_tokens=max_new)
    host.rollout()
    assert sent, "fused generation never shipped an episode"
    return sent


class TestScoreStage:
    def test_extract_generation_reconstructs_tokens(self):
        from relayrl_tpu.rlhf.scheduler import extract_generation
        from relayrl_tpu.types.trajectory import deserialize_actions

        payload, env = _generate_episode(0)
        records = deserialize_actions(payload)
        tokens, gen_len, marker = extract_generation(records, 2)
        # the env's own final buffer IS the ground truth
        np.testing.assert_array_equal(tokens, env._tokens)
        assert gen_len == env._t
        assert marker is not None and marker.act is None

    def test_scores_patch_marker_and_preserve_steps(self):
        from relayrl_tpu.rlhf.scheduler import ScoreStage
        from relayrl_tpu.types.trajectory import deserialize_actions

        payload, env = _generate_episode(1)

        class FixedScorer:
            def score_np(self, tokens, prompt_len, gen_len):
                return 7.25

        emitted = []
        stage = ScoreStage(FixedScorer(), prompt_len=2,
                           emit_fn=lambda lane, p: emitted.append((lane, p)),
                           batch=4)
        stage.submit(3, payload)
        stage.close()
        assert len(emitted) == 1 and emitted[0][0] == 3
        out = deserialize_actions(emitted[0][1])
        inp = deserialize_actions(payload)
        assert out[-1].act is None and out[-1].rew == 7.25
        assert inp[-1].rew == 0.0
        # every non-reward field of every record survives byte-for-byte
        for a, b in zip(inp[:-1], out[:-1]):
            np.testing.assert_array_equal(a.obs, b.obs)
            np.testing.assert_array_equal(a.act, b.act)
            assert a.rew == b.rew and a.done == b.done
        assert stage.scored_snapshot() == [7.25]

    def test_batched_scoring_pads_and_slices(self):
        """A partial batch pads with repeated rows (inert) — scores for
        the real rows must equal the single-path scores."""
        from relayrl_tpu.rlhf.scheduler import ScoreStage
        from relayrl_tpu.envs.scorers import ProgrammaticScorer
        from relayrl_tpu.types.trajectory import deserialize_actions

        sc = ProgrammaticScorer(vocab_size=6)
        payloads = [_generate_episode(s)[0] for s in range(3)]
        emitted = []
        stage = ScoreStage(sc, prompt_len=2,
                           emit_fn=lambda lane, p: emitted.append(p),
                           batch=8)  # > submissions: forced padding
        for i, p in enumerate(payloads):
            stage.submit(i, p)
        stage.close()
        assert len(emitted) == 3
        for src, out_bytes in zip(payloads, emitted):
            from relayrl_tpu.rlhf.scheduler import extract_generation

            records = deserialize_actions(src)
            tokens, gen_len, _ = extract_generation(records, 2)
            expected = sc.score_np(tokens, 2, gen_len)
            out = deserialize_actions(out_bytes)
            assert out[-1].rew == expected

    def test_extract_generation_frame_reconstructs_tokens(self, tmp_cwd):
        """The columnar twin of extract_generation: the full token
        buffer comes back from the LAST observation row plus the final
        action (the env never materializes the terminal row), and every
        generated slot equals the action column that wrote it."""
        from relayrl_tpu.rlhf.scheduler import extract_generation_frame
        from relayrl_tpu.types.columnar import parse_frame

        for _lane, frame in _fused_generation_frames():
            dt = parse_frame(frame)
            tokens, gen_len = extract_generation_frame(dt, 2)
            assert gen_len == dt.n_steps >= 1
            assert tokens.dtype == np.int32
            first = np.asarray(dt.columns["o"][0]).astype(np.int32)
            np.testing.assert_array_equal(tokens[:2], first[:2])
            acts = np.asarray(dt.columns["a"], np.int32).reshape(-1)
            for i in range(gen_len):
                assert int(tokens[2 + i]) == int(acts[i]), i

    def test_score_stage_patches_columnar_frame(self, tmp_cwd):
        """A fused-tier columnar frame flows through the SAME stage:
        the terminal reward cell is replaced with the score, every other
        column/aux byte survives, and the submitted frame is never
        mutated in place."""
        from relayrl_tpu.rlhf.scheduler import ScoreStage
        from relayrl_tpu.types.columnar import parse_frame

        _lane, frame = _fused_generation_frames()[0]

        class FixedScorer:
            def score_np(self, tokens, prompt_len, gen_len):
                return 7.25

        emitted = []
        stage = ScoreStage(FixedScorer(), prompt_len=2,
                           emit_fn=lambda lane, p: emitted.append((lane, p)),
                           batch=4)
        stage.submit(3, frame)
        stage.close()
        assert len(emitted) == 1 and emitted[0][0] == 3
        out = parse_frame(emitted[0][1])
        inp = parse_frame(frame)
        assert out.columns["r"][-1] == np.float32(7.25)
        assert inp.columns["r"][-1] == 0.0  # scorer-less env, unmutated
        np.testing.assert_array_equal(out.columns["r"][:-1],
                                      inp.columns["r"][:-1])
        for k in ("o", "a", "t", "u", "x"):
            assert out.columns[k].tobytes() == inp.columns[k].tobytes(), k
        assert set(out.aux) == set(inp.aux) >= {"logp_a", "bver"}
        for k in inp.aux:
            assert out.aux[k].tobytes() == inp.aux[k].tobytes(), k
        assert stage.scored_snapshot() == [7.25]


# ---------------------------------------------------------------------------
# freeze mask (acceptance lock: frozen leaves bit-identical + wire skip)
# ---------------------------------------------------------------------------

class TestFreezeMask:
    def test_normalize_spec_validates(self):
        from relayrl_tpu.config.loader import normalize_freeze_spec

        assert normalize_freeze_spec(None) == ()
        assert normalize_freeze_spec("") == ()
        assert normalize_freeze_spec("a.*b") == ("a.*b",)
        assert normalize_freeze_spec(["x", "y"]) == ("x", "y")
        with pytest.raises(ValueError, match="not a valid regex"):
            normalize_freeze_spec("[")
        with pytest.raises(ValueError, match="non-empty"):
            normalize_freeze_spec([""])

    def test_loader_validates_freeze_at_load(self, tmp_path):
        from relayrl_tpu.config import ConfigLoader

        p = tmp_path / "relayrl_config.json"
        p.write_text(json.dumps({"learner": {"freeze": "["}}))
        with pytest.warns(UserWarning, match="invalid learner.freeze"):
            loader = ConfigLoader(None, p, create_if_missing=False)
            assert loader.get_learner_params()["freeze"] is None
        p2 = tmp_path / "ok.json"
        p2.write_text(json.dumps({"learner": {"freeze": ["params/pi"]}}))
        loader = ConfigLoader(None, p2, create_if_missing=False)
        assert loader.get_learner_params()["freeze"] == ["params/pi"]

    @staticmethod
    def _leaf_map(params):
        import jax

        from relayrl_tpu.algorithms.freeze import leaf_path

        return {leaf_path(p): np.asarray(leaf).tobytes()
                for p, leaf in jax.tree_util.tree_leaves_with_path(params)}

    @staticmethod
    def _drive_epochs(algo, obs_dim, act_dim, epochs):
        from relayrl_tpu.types.action import ActionRecord

        rng = np.random.default_rng(0)
        for _ in range(epochs * algo.traj_per_epoch):
            ep = [ActionRecord(
                obs=rng.standard_normal(obs_dim).astype(np.float32),
                act=np.int64(rng.integers(act_dim)), rew=float(rng.random()),
                data={"logp_a": np.float32(-1.0), "v": np.float32(0.0)},
                done=(i == 3)) for i in range(4)]
            algo.receive_trajectory(ep)

    # Wall re-fit convention: REINFORCE is the fast per-algorithm
    # representative; the IMPALA/PPO twins ride the slow tier.
    @pytest.mark.parametrize("algo_name,extra", [
        pytest.param("IMPALA", {}, marks=pytest.mark.slow),
        ("REINFORCE", {"with_vf_baseline": True, "train_vf_iters": 2}),
        pytest.param("PPO", {"train_iters": 1, "minibatch_count": 2},
                     marks=pytest.mark.slow),
    ])
    def test_frozen_leaves_bit_identical_after_updates(self, algo_name,
                                                       extra, tmp_cwd):
        """THE mask lock, on every family that takes the knob: frozen
        leaves byte-equal after N real updates, trainable leaves moved."""
        import re
        import tempfile

        from relayrl_tpu.algorithms import build_algorithm

        pattern = r"params/(obs_embed|pos_embed|block_0)/"
        algo = build_algorithm(
            algo_name, obs_dim=6, act_dim=4, traj_per_epoch=2, seed_salt=0,
            model_kind="transformer_discrete", d_model=16, n_layers=2,
            n_heads=2, max_seq_len=8, bucket_lengths=[8],
            freeze=pattern,
            logger_kwargs={"output_dir": tempfile.mkdtemp()}, **extra)
        info = algo.freeze_info
        assert 0 < info["frozen_leaves"] < info["total_leaves"]
        before = self._leaf_map(algo.state.params)
        self._drive_epochs(algo, 6, 4, epochs=2)
        import jax

        jax.block_until_ready(algo.state.params)
        after = self._leaf_map(algo.state.params)
        rx = re.compile(pattern)
        moved = 0
        for name, buf in before.items():
            if rx.search(name):
                assert after[name] == buf, f"frozen leaf moved: {name}"
            else:
                moved += int(after[name] != buf)
        assert moved > 0, "no trainable leaf moved — update inert?"
        assert algo.version >= 2

    def test_checkpoint_roundtrip_and_mask_guard(self, tmp_cwd):
        """The mask rides checkpoint extras; resume under the same mask
        continues with leaves still frozen; resume under a DIFFERENT
        mask refuses with a pointed error."""
        import tempfile

        from relayrl_tpu.algorithms import build_algorithm
        from relayrl_tpu.checkpoint.manager import (
            checkpoint_algorithm,
            restore_algorithm,
        )

        pattern = r"params/block_0/"

        def build(freeze):
            kwargs = {"freeze": freeze} if freeze else {}
            return build_algorithm(
                "IMPALA", obs_dim=6, act_dim=4, traj_per_epoch=2,
                seed_salt=0, model_kind="transformer_discrete", d_model=16,
                n_layers=2, n_heads=2, max_seq_len=8, bucket_lengths=[8],
                logger_kwargs={"output_dir": tempfile.mkdtemp()}, **kwargs)

        algo = build(pattern)
        self._drive_epochs(algo, 6, 4, epochs=1)
        ckpt_dir = str(tmp_cwd / "ckpts")
        checkpoint_algorithm(algo, ckpt_dir, wait=True)
        extra = algo._ckpt_mgr.read_extra(algo._ckpt_mgr.latest_step())
        assert extra["freeze"]["patterns"] == [pattern]
        assert extra["freeze"]["frozen_leaves"] == \
            algo.freeze_info["frozen_leaves"]

        resumed = build(pattern)
        restore_algorithm(resumed, ckpt_dir)
        frozen_before = {k: v for k, v in
                         self._leaf_map(resumed.state.params).items()
                         if "block_0" in k}
        self._drive_epochs(resumed, 6, 4, epochs=1)
        import jax

        jax.block_until_ready(resumed.state.params)
        for name, buf in self._leaf_map(resumed.state.params).items():
            if "block_0" in name:
                assert frozen_before[name] == buf, name

        with pytest.raises(ValueError, match="learner.freeze"):
            restore_algorithm(build(None), ckpt_dir)

    def test_wire_v2_skips_frozen_leaves(self):
        """The savings surface: consecutive updates under the mask
        produce delta frames that OMIT every frozen leaf, and the
        publisher-side publish_bytes_saved counter grows by their
        bytes."""
        import re
        import tempfile

        import jax

        from relayrl_tpu.algorithms import build_algorithm
        from relayrl_tpu.algorithms.freeze import leaf_path
        from relayrl_tpu.transport import modelwire as mw
        from relayrl_tpu.types.model_bundle import leaf_manifest

        telemetry.set_registry(telemetry.Registry(run_id="freeze-wire"))
        pattern = r"params/(obs_embed|pos_embed|block_0)/"
        algo = build_algorithm(
            "IMPALA", obs_dim=6, act_dim=4, traj_per_epoch=2, seed_salt=0,
            model_kind="transformer_discrete", d_model=16, n_layers=2,
            n_heads=2, max_seq_len=8, bucket_lengths=[8], freeze=pattern,
            logger_kwargs={"output_dir": tempfile.mkdtemp()})
        enc = mw.ModelWireEncoder(keyframe_interval=10**9, compress="auto",
                                  small_model_bytes=0)
        params0 = jax.device_get(algo.state.params)
        manifest, leaves = leaf_manifest(params0)
        rx = re.compile(pattern)
        frozen_idx = {i for i, (p, _l) in enumerate(
            jax.tree_util.tree_leaves_with_path(params0)) if rx.search(
                leaf_path(p))}
        assert frozen_idx
        enc.encode(1, algo.arch, params0)  # keyframe seeds the base
        for v in range(2, 5):
            self._drive_epochs(algo, 6, 4, epochs=1)
            frame, info = enc.encode(v, algo.arch,
                                     jax.device_get(algo.state.params))
            assert info["kind"] == "delta"
            _k, hdr, _p = mw.parse_frame(frame)
            shipped = {entry[0] for entry in hdr["leaves"]}
            assert not (shipped & frozen_idx), (
                "a frozen leaf landed on the wire")
        snap = telemetry.get_registry().snapshot()
        saved = [m["value"] for m in snap["metrics"]
                 if m["name"] == "relayrl_wire_publish_bytes_saved_total"]
        frozen_bytes = sum(leaves[i].nbytes for i in frozen_idx)
        assert saved and saved[0] >= 3 * frozen_bytes


# ---------------------------------------------------------------------------
# generation bit-identity (acceptance lock)
# ---------------------------------------------------------------------------

class TestGenerationBitIdentity:
    def test_scheduler_generation_equals_local_step_window_actor(self):
        """A batch-of-1 GenerationStage (the scheduler's generate stage
        over a VectorActorHost, rng_keys pinned to the actor's key)
        produces the episode payloads of a local PolicyActor driving the
        same env stream through step_window: decoded, the same tokens,
        observations, rewards, flags and ``bver`` exactly, and the same
        logp/v to float32 rounding — the host's vmapped batch-of-1 and the
        actor's plain call are two XLA program shapes
        (``_util.MODEL_OUTPUT_RTOL``)."""
        import jax

        from relayrl_tpu.envs import SyncVectorEnv, TokenGenEnv
        from relayrl_tpu.models import build_policy
        from relayrl_tpu.rlhf.scheduler import GenerationStage
        from relayrl_tpu.runtime.policy_actor import PolicyActor
        from relayrl_tpu.runtime.vector_actor import VectorActorHost
        from relayrl_tpu.types.model_bundle import ModelBundle

        vocab, prompt_len, max_new = 6, 2, 5
        ctx = prompt_len + max_new
        arch = {"kind": "transformer_discrete", "obs_dim": ctx,
                "act_dim": vocab, "d_model": 16, "n_layers": 1,
                "n_heads": 2, "max_seq_len": max_new, "has_critic": True}
        params = build_policy(arch).init_params(jax.random.PRNGKey(42))
        bundle = ModelBundle(version=7, arch=arch, params=params)

        def env_fn():
            return TokenGenEnv(vocab_size=vocab, prompt_len=prompt_len,
                               max_new_tokens=max_new, scorer=None)

        # -- scheduler path: GenerationStage over a batch-of-1 host --
        stage_payloads = []
        host = VectorActorHost(
            bundle, num_envs=1,
            on_send=lambda lane, p: stage_payloads.append(p),
            rng_keys=np.asarray(jax.random.PRNGKey(0))[None],
            validate=False)
        venv = SyncVectorEnv([env_fn])
        stage = GenerationStage(host, venv, seed=123)
        rounds = 0
        while len(stage_payloads) < 6 and rounds < 200:
            stage.run_round()
            rounds += 1
        assert len(stage_payloads) >= 6

        # -- local actor path: PolicyActor + the same env stream --
        actor_payloads = []
        actor = PolicyActor(bundle, on_send=actor_payloads.append, seed=0,
                            validate=False)
        assert actor._window_fn is not None, "must exercise step_window"
        env = env_fn()
        episode = 0
        obs, _ = env.reset(seed=123)  # SyncVectorEnv lane-0 seeding
        while len(actor_payloads) < len(stage_payloads):
            rec = actor.request_for_action(obs)
            # the scheduler stamps the behavior version on every record
            rec.data["bver"] = np.int32(actor.version)
            obs, _rew, term, _tr, _ = env.step(int(np.asarray(rec.act)))
            if term:
                actor.flag_last_action(0.0, terminated=True)
                episode += 1
                # SyncVectorEnv autoreset seeding: base + lane + N*episode
                obs, _ = env.reset(seed=123 + episode)
        for n, (got, want) in enumerate(zip(stage_payloads, actor_payloads)):
            assert_episode_payloads_match(got, want, f"episode {n}")


# ---------------------------------------------------------------------------
# live plane (in-process server)
# ---------------------------------------------------------------------------

def _write_rlhf_config(path, vocab=6, prompt_len=2, max_new=6, lanes=4,
                       freeze=None, extra=None):
    cfg = {
        "max_traj_length": 64,
        "learner": {"checkpoint_dir": "", "checkpoint_every_epochs":
                    1_000_000, "bucket_lengths": [8]},
        # Spool window sized for the chaos drill's volume (the PR 6
        # rule: window >= episode rate x (outage + replay time) — RLHF
        # episodes are short, so thousands of seqs per lane per run;
        # the 512-entry default would evict the in-flight-at-kill
        # window before phase 5 replays it).
        "actor": {"spool_entries": 32768, "spool_bytes": 268435456},
        "rlhf": {"vocab_size": vocab, "prompt_len": prompt_len,
                 "max_new_tokens": max_new, "scorer": "programmatic",
                 "lanes": lanes, "score_batch": lanes,
                 # Bounded staleness with a fast stall-trickle: the
                 # chaos drill generates through a learner outage at
                 # ~one round per pace_timeout.
                 "max_episodes_per_version": 32, "pace_timeout_s": 1.0},
    }
    if freeze:
        cfg["learner"]["freeze"] = freeze
    if extra:
        for k, v in extra.items():
            cfg.setdefault(k, {}).update(v)
    with open(path, "w") as f:
        json.dump(cfg, f)
    return str(path)


_TRANSFORMER_HP = {
    "traj_per_epoch": 8, "model_kind": "transformer_discrete",
    "d_model": 16, "n_layers": 2, "n_heads": 2, "max_seq_len": 8,
    "lr": 3e-3, "seed_salt": 0,
    # Episodes are <= max_new_tokens + 1 steps; the bucket must stay
    # within the transformer's positional table (max_seq_len) — the
    # default 64/256/1000 buckets would pad past it and fail every
    # update. Carried in hyperparams so subprocess drills (whose
    # scratch config lacks the test's learner section) agree.
    "bucket_lengths": [8],
}


def _every_sent_episode_accepted(server, sched, lanes, timeout_s=120):
    """Wait for what the assertions after it read, not for a clock: the
    learner has trained twice and every episode the lanes sent has reached
    the ledger (``drain`` cannot see bytes still in a socket's buffer, so an
    accounting read straight after it is a race under a busy host). Returns
    the accounting; past the deadline it fails saying which was short."""
    sent = sched.agent.spool.sent_counts()
    deadline = time.monotonic() + timeout_s
    while True:
        agents = server.ingest_accounting()["agents"]
        landed = (len(agents) == lanes and all(
            row["max_seq"] == sent.get(lane) for lane, row in agents.items()))
        if landed and server.stats["updates"] >= 2:
            break
        assert time.monotonic() < deadline, (
            f"after {timeout_s} s: updates={server.stats['updates']} (want "
            f">= 2), sent={sent}, ledger={agents}")
        time.sleep(0.05)
    assert server.drain(timeout=60), "the learner's queues never emptied"
    return server.ingest_accounting()


class TestLivePlane:
    def test_generate_score_update_over_live_zmq(self, tmp_cwd):
        """The dataflow against a real TrainingServer: a transformer
        IMPALA learner (V-trace over the recorded behavior logp) trains
        on score-stage-assigned rewards, every lane's episodes are
        accepted exactly once, and the rlhf metric family is live."""
        from relayrl_tpu.rlhf.scheduler import RlhfScheduler
        from relayrl_tpu.runtime.server import TrainingServer

        config_path = _write_rlhf_config(tmp_cwd / "relayrl_config.json")
        addrs, agent_addrs = zmq_addr_pair()
        telemetry.set_registry(telemetry.Registry(run_id="rlhf-live"))
        server = TrainingServer(
            "IMPALA", obs_dim=8, act_dim=6, env_dir=str(tmp_cwd),
            hyperparams=dict(_TRANSFORMER_HP), config_path=config_path,
            **addrs)
        sched = None
        try:
            sched = RlhfScheduler(config_path=config_path,
                                  server_type="zmq", seed=0,
                                  identity="rlhf-live",
                                  handshake_timeout_s=60, **agent_addrs)
            stats = sched.run(episodes=64, deadline_s=120)
            assert stats["episodes_scored"] >= 64, (
                f"{stats['episodes_scored']} of 64 episodes scored in 120 s")
            sched.flush()
            acct = _every_sent_episode_accepted(server, sched, lanes=4)
            sent = sched.agent.spool.sent_counts()
            for lane_id, row in acct["agents"].items():
                assert row["accepted"] == row["max_seq"] == sent[lane_id]
                assert row["contiguous"]
            names = {m["name"]
                     for m in telemetry.get_registry().snapshot()["metrics"]}
            for metric in ("relayrl_rlhf_generated_tokens_total",
                           "relayrl_rlhf_scored_episodes_total",
                           "relayrl_rlhf_stage_seconds",
                           "relayrl_rlhf_lag_versions"):
                assert metric in names, metric
        finally:
            if sched is not None:
                sched.close()
            server.disable_server()

    def test_fused_generation_tier_anakin(self, tmp_cwd):
        """ISSUE 20 acceptance: ``rlhf.generation_tier:
        "anakin"`` moves TokenGen INSIDE the fused scan. The live locks:
        FusedGenerationStage drives whole rollout windows, withheld
        episodes come back score-patched as columnar frames, the
        transformer IMPALA learner trains on them (per-token logp_a +
        bver intact for V-trace), and the per-lane zero-loss accounting
        holds on the same spool plane."""
        from relayrl_tpu.rlhf.scheduler import (FusedGenerationStage,
                                                RlhfScheduler)
        from relayrl_tpu.runtime.server import TrainingServer

        config_path = _write_rlhf_config(
            tmp_cwd / "relayrl_config.json",
            extra={"rlhf": {"generation_tier": "anakin"}})
        addrs, agent_addrs = zmq_addr_pair()
        telemetry.set_registry(telemetry.Registry(run_id="rlhf-fused"))
        server = TrainingServer(
            "IMPALA", obs_dim=8, act_dim=6, env_dir=str(tmp_cwd),
            hyperparams=dict(_TRANSFORMER_HP), config_path=config_path,
            **addrs)
        sched = None
        try:
            sched = RlhfScheduler(config_path=config_path,
                                  server_type="zmq", seed=0,
                                  identity="rlhf-fused",
                                  handshake_timeout_s=60, **agent_addrs)
            assert isinstance(sched.generation, FusedGenerationStage)
            assert sched.venv is None  # no host-side envs at all
            stats = sched.run(episodes=64, deadline_s=180)
            assert stats["episodes_scored"] >= 64
            # lanes x unroll tokens per round, counted by the stage
            assert stats["tokens_generated"] >= 128
            sched.flush()
            acct = _every_sent_episode_accepted(server, sched, lanes=4)
            sent = sched.agent.spool.sent_counts()
            for lane_id, row in acct["agents"].items():
                assert row["accepted"] == row["max_seq"] == sent[lane_id]
                assert row["contiguous"]
            names = {m["name"]
                     for m in telemetry.get_registry().snapshot()["metrics"]}
            for metric in ("relayrl_rlhf_generated_tokens_total",
                           "relayrl_rlhf_scored_episodes_total",
                           "relayrl_rlhf_stage_seconds"):
                assert metric in names, metric
        finally:
            if sched is not None:
                sched.close()
            server.disable_server()

    @pytest.mark.slow
    def test_remote_generation_tier_mlp(self, tmp_cwd):
        """(slow: spins a serving plane + thin clients — the fast suite
        keeps the vector-tier live test; run with ``-m rlhf``.)

        Thin-client generation where the serving contracts allow it:
        an MLP token policy served by the InferenceService; the score
        stage patches rewards on the client-side episodes exactly as on
        the vector tier."""
        from relayrl_tpu.rlhf.scheduler import RlhfScheduler
        from relayrl_tpu.runtime.server import TrainingServer

        config_path = _write_rlhf_config(
            tmp_cwd / "relayrl_config.json", lanes=2,
            extra={"serving": {"enabled": True, "max_batch": 4,
                               "batch_timeout_ms": 2.0},
                   "server": {"inference_server":
                              {"host": "127.0.0.1",
                               "port": str(free_port())}}})
        addrs, agent_addrs = zmq_addr_pair()
        server = TrainingServer(
            "IMPALA", obs_dim=8, act_dim=6, env_dir=str(tmp_cwd),
            hyperparams={"traj_per_epoch": 4, "hidden_sizes": [16],
                         "seed_salt": 0},
            config_path=config_path, **addrs)
        sched = None
        try:
            sched = RlhfScheduler(config_path=config_path,
                                  server_type="zmq", seed=0,
                                  identity="rlhf-remote", lanes=2,
                                  generation_tier="remote",
                                  handshake_timeout_s=60, **agent_addrs)
            stats = sched.run(episodes=8, deadline_s=120)
            assert stats["episodes_scored"] >= 8
            sched.flush()
            server.drain(timeout=60)
            acct = server.ingest_accounting()
            assert len(acct["agents"]) == 2
            total = sum(r["accepted"] for r in acct["agents"].values())
            assert total >= 8
            for row in acct["agents"].values():
                assert row["accepted"] == row["max_seq"]
        finally:
            if sched is not None:
                sched.close()
            server.disable_server()

    def test_sequence_policies_are_servable(self):
        """Serving v2 flipped the old refusal: sequence policies build an
        InferenceService with a session window (ctx from max_seq_len), so
        the RLHF generation tier can sit behind the serving plane."""
        import jax

        from relayrl_tpu.models import build_policy
        from relayrl_tpu.runtime.inference import InferenceService
        from relayrl_tpu.types.model_bundle import ModelBundle

        arch = {"kind": "transformer_discrete", "obs_dim": 4, "act_dim": 3,
                "d_model": 16, "n_layers": 1, "n_heads": 2,
                "max_seq_len": 8, "has_critic": True}
        params = build_policy(arch).init_params(jax.random.PRNGKey(0))
        svc = InferenceService(ModelBundle(version=1, arch=arch,
                                           params=params))
        try:
            assert svc.ctx == 8
        finally:
            svc.stop()


# ---------------------------------------------------------------------------
# chaos drill (acceptance lock)
# ---------------------------------------------------------------------------

def _spawn_rlhf_server(scratch: str, addrs: dict,
                       resume: bool) -> subprocess.Popen:
    cfg = {
        "algorithm": "IMPALA", "obs_dim": 8, "act_dim": 6,
        "hyperparams": dict(_TRANSFORMER_HP),
        "server_type": "zmq", "scratch": scratch,
        "checkpoint_every": 2, "resume": resume,
        # One seq per (short) episode — thousands per lane per drill;
        # the dedup window must keep late replays re-acceptable for the
        # whole run (the columnar-drill sizing precedent).
        "dedup_window": 32768,
        "status_path": os.path.join(scratch, "status.json"),
        **addrs,
    }
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    return subprocess.Popen(
        [sys.executable, os.path.join(DRILLS, "_chaos_server.py"),
         json.dumps(cfg)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)


def _read_status(scratch: str):
    try:
        with open(os.path.join(scratch, "status.json")) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None


@pytest.mark.slow
def test_chaos_learner_sigkill_rlhf_plane(tmp_path, tmp_cwd):
    """(slow: a multi-phase subprocess drill, ~1-3 min — the fast suite
    covers the plane's correctness via TestLivePlane; run this one with
    ``pytest -m rlhf`` or ``-m slow``.)

    THE drill on the new plane: SIGKILL the IMPALA learner mid-run
    while the scheduler keeps generating and scoring (episodes land in
    the spool), restart with resume, replay — per-lane accounting must
    read accepted == max_seq == sent (zero loss, zero double-train),
    the actor-held model version must advance across the crash, and the
    reward run must still converge (the scored curve improves over its
    random-start baseline)."""
    from relayrl_tpu.rlhf.scheduler import RlhfScheduler

    scratch = str(tmp_path)
    addrs, agent_addrs = zmq_addr_pair()
    server_addrs = {k: addrs[k] for k in
                    ("agent_listener_addr", "trajectory_addr",
                     "model_pub_addr")}
    config_path = _write_rlhf_config(tmp_cwd / "relayrl_config.json",
                                     lanes=4)
    proc = _spawn_rlhf_server(scratch, server_addrs, resume=False)
    sched = None
    try:
        deadline = time.monotonic() + 120
        while _read_status(scratch) is None:
            assert proc.poll() is None, proc.communicate()[0][-3000:]
            assert time.monotonic() < deadline, "server never came up"
            time.sleep(0.2)
        sched = RlhfScheduler(config_path=config_path, server_type="zmq",
                              seed=0, identity="rlhf-chaos",
                              handshake_timeout_s=120, **agent_addrs)
        # Phase 1: train past a checkpoint so resume has a base.
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            sched.run(episodes=len(sched.score_stage.scored_snapshot()) + 16,
                      deadline_s=30)
            status = _read_status(scratch)
            if status and status["version"] >= 4:
                break
        status = _read_status(scratch)
        assert status and status["version"] >= 4, "no training before kill"
        v_before = status["version"]
        agent_v_before = sched.agent.model_version

        # Phase 2: SIGKILL, no shutdown path.
        proc.kill()
        proc.wait(timeout=30)

        # Phase 3: generation + scoring continue into the outage; scored
        # episodes land in the spool window.
        sched.run(episodes=len(sched.score_stage.scored_snapshot()) + 24,
                  deadline_s=60)

        # Phase 4: restart with resume; the agent heals and trains past
        # the pre-kill version.
        proc = _spawn_rlhf_server(scratch, server_addrs, resume=True)
        deadline = time.monotonic() + 240
        healed = False
        while time.monotonic() < deadline:
            sched.run(episodes=len(sched.score_stage.scored_snapshot()) + 8,
                      deadline_s=30)
            status = _read_status(scratch)
            if (status and status["version"] > v_before
                    and sched.agent.model_version > agent_v_before):
                healed = True
                break
        assert healed, (
            f"never trained past the crash: server "
            f"{status and status['version']} vs {v_before}, actor "
            f"{sched.agent.model_version} vs {agent_v_before}")

        # Phase 5: belt-and-braces replay + the accounting assertion.
        sched.flush()
        sched.agent.spool.replay()
        sent = sched.agent.spool.sent_counts()
        deadline = time.monotonic() + 120
        ok = False
        while time.monotonic() < deadline:
            status = _read_status(scratch)
            rows = (status or {}).get("accounting", {}).get("agents", {})
            if rows and all(
                    rows.get(lane, {}).get("accepted") == count
                    and rows.get(lane, {}).get("max_seq") == count
                    and rows.get(lane, {}).get("contiguous")
                    for lane, count in sent.items()):
                ok = True
                break
            time.sleep(0.3)
        assert ok, f"zero-loss accounting never settled: {rows} vs {sent}"
        assert status["accounting"]["duplicates"] >= 1, (
            "the replay should have produced deduped duplicates")

        # Phase 6: the reward run still converges — the scored curve's
        # final window beats its random-start window.
        scores = sched.score_stage.scored_snapshot()
        assert len(scores) >= 60
        first = float(np.mean(scores[:20]))
        last = float(np.mean(scores[-20:]))
        assert last > first - 0.25, (
            f"reward collapsed across the crash: {first:.2f} -> {last:.2f}")
    finally:
        if sched is not None:
            try:
                sched.close()
            except RuntimeError:
                pass
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.communicate(timeout=60)
            except subprocess.TimeoutExpired:
                proc.kill()


# ---------------------------------------------------------------------------
# config + top
# ---------------------------------------------------------------------------

class TestConfigAndTop:
    def test_get_rlhf_params_clamps(self, tmp_path):
        from relayrl_tpu.config import ConfigLoader

        p = tmp_path / "relayrl_config.json"
        p.write_text(json.dumps({"rlhf": {
            "vocab_size": "junk", "prompt_len": -3, "lanes": 0,
            "scorer": "nope", "generation_tier": "warp",
            "generation_unroll": 0}}))
        loader = ConfigLoader(None, p, create_if_missing=False)
        params = loader.get_rlhf_params()
        assert params["vocab_size"] == 8
        assert params["prompt_len"] == 1
        assert params["lanes"] == 1
        assert params["scorer"] == "programmatic"
        assert params["generation_tier"] == "vector"
        assert params["generation_unroll"] == 1

    def test_generation_unroll_default_bounds_burst(self):
        from relayrl_tpu.config import ConfigLoader

        # The fused tier's burst size: one dispatch emits
        # lanes x generation_unroll same-version tokens, so the default
        # must stay near the episode budget (max_new_tokens), NOT the
        # rollout tier's unroll_length (32) — the measured failure mode
        # is triple-digit train-time version lag and a reward collapse.
        params = ConfigLoader(None, None).get_rlhf_params()
        assert params["generation_unroll"] <= params["max_new_tokens"]

    def test_generation_tier_anakin_accepted(self, tmp_path):
        from relayrl_tpu.config import ConfigLoader

        p = tmp_path / "relayrl_config.json"
        p.write_text(json.dumps({"rlhf": {"generation_tier": "anakin"}}))
        loader = ConfigLoader(None, p, create_if_missing=False)
        assert loader.get_rlhf_params()["generation_tier"] == "anakin"

    def test_unknown_rlhf_key_warns(self, tmp_path):
        from relayrl_tpu.config import ConfigLoader

        p = tmp_path / "relayrl_config.json"
        p.write_text(json.dumps({"rlhf": {"vocab_sizes": 8}}))
        with pytest.warns(UserWarning, match="rlhf.vocab_sizes"):
            ConfigLoader(None, p, create_if_missing=False)

    def test_small_model_bytes_knob(self, tmp_path):
        from relayrl_tpu.config import ConfigLoader

        p = tmp_path / "relayrl_config.json"
        p.write_text(json.dumps({"transport": {"small_model_bytes": 0}}))
        loader = ConfigLoader(None, p, create_if_missing=False)
        assert loader.get_transport_params()["small_model_bytes"] == 0
        p2 = tmp_path / "b.json"
        p2.write_text(json.dumps({"transport": {}}))
        loader = ConfigLoader(None, p2, create_if_missing=False)
        assert loader.get_transport_params()["small_model_bytes"] is None

    def test_top_renders_rlhf_section(self):
        from relayrl_tpu.telemetry.top import render

        snapshot = {
            "enabled": True, "run_id": "r", "uptime_s": 1.0,
            "mono_ns": 10**9,
            "metrics": [
                {"name": "relayrl_rlhf_generated_tokens_total",
                 "kind": "counter", "value": 1234, "labels": {}},
                {"name": "relayrl_rlhf_stage_seconds", "kind": "histogram",
                 "labels": {"stage": "generate"}, "count": 10,
                 "buckets": [0.1, 1.0], "counts": [5, 5, 0], "sum": 2.0},
            ],
        }
        text = render(snapshot)
        assert "-- rlhf" in text
        assert "generated_tokens_total: 1.2k" in text
        assert "stage=generate" in text
