"""Dynamics-parity goldens: each on-device JAX env vs its numpy built-in,
plus the in-scan autoreset and the unified env registry.

Parity contract (see the precision note in ``envs/jax/base.py``): every
discrete field — rewards where integral, terminated/truncated flags, step
counters, and Recall's ENTIRE observation — must match the numpy twin
EXACTLY; continuous observations must match to float32 precision
(``atol=rtol=2e-6``) per step. Full float bitwise equality between the
two planes is not physically achievable on this backend: XLA contracts
mul+add chains into FMAs and its cos/sin differ from libm's by 1 ulp
(both measured — see the probe test), so the goldens pin the strongest
true invariant instead: per-step agreement from IDENTICAL injected
states, so errors never compound, across termination, truncation, and
autoreset boundaries. Byte-exact reproducibility WITHIN the JAX plane is
pinned separately (tests/test_anakin.py cross-process determinism).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from relayrl_tpu.envs import CartPoleEnv, PendulumEnv, RecallEnv, list_envs

pytestmark = pytest.mark.anakin
from relayrl_tpu.envs.jax import (
    JAX_ENVS,
    make_jax,
    step_autoreset,
)

ATOL = RTOL = 2e-6  # float32-grade per-step agreement


def test_xla_float_parity_bound_probe():
    """The evidence for the parity contract above: XLA's jitted float32
    math agrees with numpy's to ~1 ulp but NOT bitwise (FMA contraction
    + transcendental implementations). If this ever starts failing, the
    backend's float behavior changed and the golden tolerances need a
    fresh look."""
    xs = np.linspace(-3.2, 3.2, 4001, dtype=np.float32)
    jit_cos = np.asarray(jax.jit(jnp.cos)(xs))
    ulp = np.abs(jit_cos.view(np.int32).astype(np.int64)
                 - np.cos(xs).view(np.int32).astype(np.int64)).max()
    assert ulp <= 4, f"XLA cos drifted {ulp} ulp from libm"


class TestCartPoleParity:
    def test_per_step_dynamics_across_boundaries(self):
        """400 steps of per-step injected parity under a fixed action
        stream: before every step the numpy twin is set to the JAX env's
        exact state, both step, and all five return fields are compared.
        Episodes end by termination (pole falls under random actions) and
        the JAX lane autoresets in the same call chain the fused rollout
        uses, so the comparison crosses many episode boundaries."""
        jenv = make_jax("CartPole-v1")
        nenv = CartPoleEnv()
        nenv.reset(seed=0)  # state is overwritten by injection below
        step = jax.jit(jenv.step)
        rng = np.random.default_rng(7)
        key = jax.random.PRNGKey(7)
        key, sub = jax.random.split(key)
        state, _ = jenv.reset(sub)
        episodes = 0
        for _ in range(400):
            nenv._state = np.asarray(state.state, np.float64).copy()
            nenv._t = int(state.t)
            action = int(rng.integers(2))
            state, jobs, jrew, jterm, jtrunc = step(state, jnp.int32(action))
            nobs, nrew, nterm, ntrunc, _ = nenv.step(action)
            np.testing.assert_allclose(np.asarray(jobs), nobs,
                                       atol=ATOL, rtol=RTOL)
            assert float(jrew) == nrew == 1.0
            assert bool(jterm) == nterm and bool(jtrunc) == ntrunc
            if bool(jterm) or bool(jtrunc):
                episodes += 1
                key, sub = jax.random.split(key)
                state, _ = jenv.reset(sub)
        assert episodes >= 5, "golden never crossed an episode boundary"

    def test_truncation_flag_parity(self):
        """Time-limit endings: a short max_steps forces truncation; the
        flag must fire on the same step with the same independent-flags
        semantics as the numpy twin (both-true is representable)."""
        jenv = make_jax("CartPole-v1", max_steps=6)
        nenv = CartPoleEnv(max_steps=6)
        nenv.reset(seed=1)
        step = jax.jit(jenv.step)
        state, _ = jenv.reset(jax.random.PRNGKey(1))
        for i in range(6):
            nenv._state = np.asarray(state.state, np.float64).copy()
            nenv._t = int(state.t)
            action = i % 2
            state, _, _, jterm, jtrunc = step(state, jnp.int32(action))
            _, _, nterm, ntrunc, _ = nenv.step(action)
            assert bool(jterm) == nterm and bool(jtrunc) == ntrunc
        assert bool(jtrunc), "max_steps=6 must truncate on step 6"

    def test_reset_distribution(self):
        """Seeded resets land in CartPole's U(-0.05, 0.05) init box and
        differ across keys (the PRNG streams are necessarily different
        between the planes; the CONTRACT is the distribution)."""
        jenv = make_jax("CartPole-v1")
        a = np.asarray(jenv.reset(jax.random.PRNGKey(0))[1])
        b = np.asarray(jenv.reset(jax.random.PRNGKey(1))[1])
        assert np.abs(a).max() <= 0.05 and np.abs(b).max() <= 0.05
        assert not np.array_equal(a, b)
        # same key ⇒ same init, the reproducibility half
        c = np.asarray(jenv.reset(jax.random.PRNGKey(0))[1])
        np.testing.assert_array_equal(a, c)


class TestPendulumParity:
    def test_per_step_dynamics_and_reward(self):
        jenv = make_jax("Pendulum-v1", max_steps=25)
        nenv = PendulumEnv(max_steps=25)
        nenv.reset(seed=0)
        step = jax.jit(jenv.step)
        rng = np.random.default_rng(3)
        key = jax.random.PRNGKey(3)
        key, sub = jax.random.split(key)
        state, _ = jenv.reset(sub)
        truncations = 0
        for _ in range(120):
            nenv._theta = float(np.float32(state.theta))
            nenv._theta_dot = float(np.float32(state.theta_dot))
            nenv._t = int(state.t)
            action = np.float32(rng.uniform(-2.5, 2.5))  # incl. clip range
            state, jobs, jrew, jterm, jtrunc = step(
                state, jnp.asarray([action]))
            nobs, nrew, nterm, ntrunc, _ = nenv.step([action])
            np.testing.assert_allclose(np.asarray(jobs), nobs,
                                       atol=ATOL, rtol=RTOL)
            np.testing.assert_allclose(float(jrew), nrew,
                                       atol=ATOL, rtol=RTOL)
            assert not bool(jterm) and not nterm  # pendulum never terminates
            assert bool(jtrunc) == ntrunc
            if bool(jtrunc):
                truncations += 1
                key, sub = jax.random.split(key)
                state, _ = jenv.reset(sub)
        assert truncations >= 3

    def test_obs_is_cos_sin_thetadot(self):
        jenv = make_jax("Pendulum-v1")
        _, obs = jenv.reset(jax.random.PRNGKey(0))
        obs = np.asarray(obs)
        assert obs.shape == (3,)
        assert abs(obs[0] ** 2 + obs[1] ** 2 - 1.0) < 1e-5


class TestRecallParity:
    def test_full_bitwise_parity(self):
        """Recall's observation is integer-derived (one-hot, flag, and a
        power-of-two phase division), so here the parity claim is the
        full one: obs, reward, and flags are ALL bit-equal to the numpy
        twin, across several episodes with injected cues."""
        horizon, n_cues = 8, 3
        jenv = make_jax("Recall-v0", horizon=horizon, n_cues=n_cues)
        nenv = RecallEnv(horizon=horizon, n_cues=n_cues)
        nenv.reset(seed=0)
        step = jax.jit(jenv.step)
        rng = np.random.default_rng(11)
        key = jax.random.PRNGKey(11)
        key, sub = jax.random.split(key)
        state, jobs = jenv.reset(sub)
        # reset obs parity for the injected cue
        nenv._cue, nenv._t = int(state.cue), 0
        np.testing.assert_array_equal(np.asarray(jobs), nenv._obs())
        for _ in range(5 * horizon):
            nenv._cue, nenv._t = int(state.cue), int(state.t)
            action = int(rng.integers(n_cues))
            state, jobs, jrew, jterm, jtrunc = step(state, jnp.int32(action))
            nobs, nrew, nterm, ntrunc, _ = nenv.step(action)
            np.testing.assert_array_equal(np.asarray(jobs), nobs)
            assert float(jrew) == nrew
            assert bool(jterm) == nterm and bool(jtrunc) == ntrunc
            if bool(jterm):
                key, sub = jax.random.split(key)
                state, jobs = jenv.reset(sub)
                nenv._cue, nenv._t = int(state.cue), 0
                np.testing.assert_array_equal(np.asarray(jobs), nenv._obs())

    def test_memoryless_cap_and_query_reward(self):
        """The task's defining property carries over: only the query step
        pays, and it pays iff the action matches the episode's cue."""
        jenv = make_jax("Recall-v0", horizon=4, n_cues=2)
        state, _ = jenv.reset(jax.random.PRNGKey(0))
        cue = int(state.cue)
        step = jax.jit(jenv.step)
        rewards = []
        for t in range(4):
            state, _, rew, term, _ = step(state, jnp.int32(cue))
            rewards.append(float(rew))
        assert rewards == [0.0, 0.0, 0.0, 1.0] and bool(term)


class TestInScanAutoreset:
    def test_lanes_never_leave_device(self):
        """The fused composition: 600 scanned steps cross many episode
        boundaries; each boundary hands back the NEXT episode's reset
        observation (inside CartPole's init box) while the pre-reset
        observation rides final_obs — and the scanned flags exactly match
        a step-by-step replay of the same program."""
        env = make_jax("CartPole-v1")

        def body(c, _):
            key, state, obs = c
            (key, state, obs, rew, term, trunc,
             final_obs) = step_autoreset(env, key, state, jnp.int32(1))
            return (key, state, obs), {"obs": obs, "rew": rew,
                                       "term": term, "trunc": trunc,
                                       "final_obs": final_obs}

        key = jax.random.PRNGKey(5)
        rkey, ikey = jax.random.split(key)
        state, obs = env.reset(ikey)
        _, w = jax.jit(lambda c: jax.lax.scan(body, c, None, length=600))(
            (rkey, state, obs))
        term = np.asarray(w["term"])
        obs_w = np.asarray(w["obs"])
        final = np.asarray(w["final_obs"])
        assert term.sum() >= 10, "constant-push cartpole must fall often"
        done_idx = np.flatnonzero(term)
        # At a boundary t the emitted obs row is ALREADY the next
        # episode's reset (inside the init box) — the SyncVectorEnv
        # autoreset convention — while final_obs[t] is the fallen state
        # (outside it).
        for t in done_idx:
            assert np.abs(obs_w[t]).max() <= 0.05
            assert np.abs(final[t]).max() > 0.05
        assert bool((np.asarray(w["rew"]) == 1.0).all())

    def test_fixed_seed_reproducibility(self):
        """Same carry seed ⇒ identical scanned window, byte for byte —
        the in-process half of the determinism contract (the
        cross-process half lives in tests/test_anakin.py)."""
        env = make_jax("Recall-v0", horizon=8, n_cues=2)

        def run(seed):
            def body(c, _):
                key, state, obs = c
                (key, state, obs, rew, *_rest) = step_autoreset(
                    env, key, state, jnp.int32(0))
                return (key, state, obs), obs

            key = jax.random.PRNGKey(seed)
            rkey, ikey = jax.random.split(key)
            state, obs = env.reset(ikey)
            return np.asarray(jax.jit(
                lambda c: jax.lax.scan(body, c, None, length=64))(
                    (rkey, state, obs))[1])

        np.testing.assert_array_equal(run(9), run(9))
        assert not np.array_equal(run(9), run(10))


class TestGridWorldParity:
    def test_full_bitwise_parity(self):
        """All-integer dynamics (int32 positions, clamped moves,
        integral rewards): obs, reward, and BOTH flags are bit-equal to
        the numpy twin across injected states, terminations (goal
        reached), and time-limit truncations."""
        from relayrl_tpu.envs import GridWorldEnv

        jenv = make_jax("GridWorld-v0", size=4, max_steps=10)
        nenv = GridWorldEnv(size=4, max_steps=10)
        nenv.reset(seed=0)
        step = jax.jit(jenv.step)
        rng = np.random.default_rng(5)
        key = jax.random.PRNGKey(5)
        key, sub = jax.random.split(key)
        state, jobs = jenv.reset(sub)
        assert np.asarray(jobs).dtype == np.int32
        terms = truncs = 0
        for _ in range(400):
            nenv._pos = np.asarray(state.pos, np.int32).copy()
            nenv._t = int(state.t)
            action = int(rng.integers(4))
            state, jobs, jrew, jterm, jtrunc = step(state, jnp.int32(action))
            nobs, nrew, nterm, ntrunc, _ = nenv.step(action)
            np.testing.assert_array_equal(np.asarray(jobs), nobs)
            assert np.asarray(jobs).dtype == nobs.dtype == np.int32
            assert float(jrew) == nrew
            assert bool(jterm) == nterm and bool(jtrunc) == ntrunc
            terms += bool(jterm)
            truncs += bool(jtrunc) and not bool(jterm)
            if bool(jterm) or bool(jtrunc):
                key, sub = jax.random.split(key)
                state, jobs = jenv.reset(sub)
        assert terms >= 3 and truncs >= 3, (terms, truncs)

    def test_reset_distribution_excludes_goal(self):
        jenv = make_jax("GridWorld-v0", size=3)
        for i in range(32):
            state, obs = jenv.reset(jax.random.PRNGKey(i))
            assert not bool(np.all(np.asarray(state.pos) == 2)), i
            np.testing.assert_array_equal(np.asarray(obs),
                                          np.asarray(state.pos))
        # same key ⇒ same start, the reproducibility half
        a = np.asarray(jenv.reset(jax.random.PRNGKey(0))[1])
        b = np.asarray(jenv.reset(jax.random.PRNGKey(0))[1])
        np.testing.assert_array_equal(a, b)

    def test_goal_pays_exactly_once(self):
        from relayrl_tpu.envs.jax.gridworld import GridWorldState

        jenv = make_jax("GridWorld-v0", size=3, max_steps=20)
        step = jax.jit(jenv.step)
        # one cell left of the goal: move right -> terminal, reward 1.0
        state = GridWorldState(pos=jnp.array([2, 1], jnp.int32),
                               t=jnp.int32(0))
        state, obs, rew, term, trunc = step(state, jnp.int32(3))
        assert float(rew) == 1.0 and bool(term) and not bool(trunc)
        np.testing.assert_array_equal(np.asarray(obs), [2, 2])
        # stepping at a border clamps and pays nothing
        state = GridWorldState(pos=jnp.array([0, 0], jnp.int32),
                               t=jnp.int32(0))
        state, obs, rew, term, _ = step(state, jnp.int32(0))  # up at top
        assert float(rew) == 0.0 and not bool(term)
        np.testing.assert_array_equal(np.asarray(obs), [0, 0])


class TestBanditParity:
    def test_full_bitwise_parity(self):
        """All-integer dynamics (context one-hot, target-arm residue,
        0/1 reward): obs, reward, and BOTH flags are bit-equal to the
        numpy twin across injected contexts. Every step is an episode
        (one-step bandit), so this is also the densest autoreset
        exercise in the battery."""
        from relayrl_tpu.envs import BanditEnv

        jenv = make_jax("Bandit-v0", n_contexts=5, n_arms=3)
        nenv = BanditEnv(n_contexts=5, n_arms=3)
        nenv.reset(seed=0)
        step = jax.jit(jenv.step)
        rng = np.random.default_rng(2)
        key = jax.random.PRNGKey(2)
        hits = 0
        for i in range(64):
            key, sub = jax.random.split(key)
            state, jobs = jenv.reset(sub)
            nenv._ctx = int(state.ctx)
            np.testing.assert_array_equal(np.asarray(jobs), nenv._obs())
            assert np.asarray(jobs).dtype == np.int32
            action = int(rng.integers(3))
            _state, jobs, jrew, jterm, jtrunc = step(state,
                                                     jnp.int32(action))
            nobs, nrew, nterm, ntrunc, _ = nenv.step(action)
            np.testing.assert_array_equal(np.asarray(jobs), nobs)
            assert float(jrew) == nrew
            assert bool(jterm) == nterm is True
            assert bool(jtrunc) == ntrunc is False
            hits += int(nrew)
        assert 0 < hits < 64, "need both rewarded and unrewarded pulls"

    def test_target_arm_is_learnable_mapping(self):
        """The contract the fast-regression signal rests on: the correct
        arm is a deterministic function of the context, identical in
        both planes."""
        from relayrl_tpu.envs import BanditEnv
        from relayrl_tpu.envs.jax.bandit import BanditState

        jenv = make_jax("Bandit-v0", n_contexts=6, n_arms=4,
                        mult=3, shift=1)
        nenv = BanditEnv(n_contexts=6, n_arms=4, mult=3, shift=1)
        step = jax.jit(jenv.step)
        for ctx in range(6):
            target = nenv.target_arm(ctx)
            state = BanditState(ctx=jnp.int32(ctx))
            _s, _o, rew, _t, _x = step(state, jnp.int32(target))
            assert float(rew) == 1.0, (ctx, target)
            wrong = (target + 1) % 4
            _s, _o, rew, _t, _x = step(state, jnp.int32(wrong))
            assert float(rew) == 0.0


class TestTokenGenParity:
    def test_full_bitwise_parity_programmatic(self):
        """TokenGen with the all-integer programmatic scorer: obs
        (the token context window), reward (a count, integral in
        float32), and flags bit-equal to the numpy twin from injected
        states, across EOS endings and max_new_tokens endings."""
        from relayrl_tpu.envs import TokenGenEnv
        from relayrl_tpu.envs.scorers import ProgrammaticScorer

        scorer = ProgrammaticScorer(vocab_size=6)
        kwargs = dict(vocab_size=6, prompt_len=2, max_new_tokens=5,
                      scorer=scorer)
        jenv = make_jax("TokenGen-v0", **kwargs)
        nenv = TokenGenEnv(**kwargs)
        nenv.reset(seed=0)
        step = jax.jit(jenv.step)
        rng = np.random.default_rng(4)
        key = jax.random.PRNGKey(4)
        key, sub = jax.random.split(key)
        state, jobs = jenv.reset(sub)
        assert np.asarray(jobs).dtype == np.int32
        eos_ends = budget_ends = 0
        scored = 0.0
        for _ in range(300):
            nenv._tokens = np.asarray(state.tokens, np.int32).copy()
            nenv._t = int(state.t)
            action = int(rng.integers(6))
            state, jobs, jrew, jterm, jtrunc = step(state,
                                                    jnp.int32(action))
            nobs, nrew, nterm, ntrunc, _ = nenv.step(action)
            np.testing.assert_array_equal(np.asarray(jobs), nobs)
            assert float(jrew) == nrew
            assert bool(jterm) == nterm and bool(jtrunc) == ntrunc is False
            if bool(jterm):
                scored += float(jrew)
                # A terminal whose final action is NOT EOS can only be
                # the max_new_tokens budget ending — the second
                # termination type the parity must cover.
                eos_ends += int(action == 0)
                budget_ends += int(action != 0)
                key, sub = jax.random.split(key)
                state, jobs = jenv.reset(sub)
        assert eos_ends >= 3, "never saw an EOS ending"
        assert budget_ends >= 3, "never saw a max_new_tokens ending"
        assert scored > 0, "random play never hit a successor token"

    def test_prompt_excludes_eos_and_reset_reproducible(self):
        jenv = make_jax("TokenGen-v0", vocab_size=8, prompt_len=3,
                        max_new_tokens=4)
        for i in range(16):
            state, obs = jenv.reset(jax.random.PRNGKey(i))
            prompt = np.asarray(state.tokens)[:3]
            assert np.all(prompt >= 1) and np.all(prompt < 8)
            assert np.all(np.asarray(state.tokens)[3:] == 0)
        a = np.asarray(jenv.reset(jax.random.PRNGKey(0))[1])
        b = np.asarray(jenv.reset(jax.random.PRNGKey(0))[1])
        np.testing.assert_array_equal(a, b)

    def test_scorerless_mode_pays_zero(self):
        """The decoupled-dataflow contract: scorer=None means the env
        NEVER pays reward — the score stage owns it."""
        jenv = make_jax("TokenGen-v0", vocab_size=6, prompt_len=2,
                        max_new_tokens=3)
        state, _ = jenv.reset(jax.random.PRNGKey(0))
        step = jax.jit(jenv.step)
        for tok in (3, 4, 0):  # incl. an EOS terminal
            state, _obs, rew, _term, _tr = step(state, jnp.int32(tok))
            assert float(rew) == 0.0


class TestRegistry:
    def test_jax_registry_covers_builtins(self):
        assert set(JAX_ENVS) == {"CartPole-v1", "Pendulum-v1", "Recall-v0",
                                 "GridWorld-v0", "Bandit-v0", "TokenGen-v0"}

    def test_list_envs_has_both_planes(self):
        known = list_envs()
        assert known["builtin"] == sorted(known["builtin"])
        assert "CartPole-v1" in known["jax"]

    def test_make_jax_unknown_id_lists_registry(self):
        with pytest.raises(ValueError, match="CartPole-v1"):
            make_jax("NoSuchEnv-v0")

    def test_make_error_message_lists_both_planes(self):
        from relayrl_tpu.envs import make

        with pytest.raises(ValueError, match="on-device"):
            make("NoSuchEnv-v0")

    def test_make_jax_forwards_kwargs(self):
        env = make_jax("Recall-v0", horizon=16, n_cues=4)
        assert env.horizon == 16 and env.obs_dim == 6


# id -> (constructor arguments, the limit the environment then states)
EPISODE_LIMITS = {
    "CartPole-v1": ({"max_steps": 12}, 12),
    "Pendulum-v1": ({"max_steps": 9}, 9),
    "Recall-v0": ({"horizon": 8, "n_cues": 4}, 8),
    "GridWorld-v0": ({"size": 4, "max_steps": 11}, 11),
    "Bandit-v0": ({}, 1),
    "TokenGen-v0": ({"vocab_size": 6, "max_new_tokens": 5}, 5),
}


class TestEpisodeLimit:
    def test_every_registered_env_has_a_case(self):
        assert set(EPISODE_LIMITS) == set(JAX_ENVS)

    def test_the_base_class_states_no_limit(self):
        from relayrl_tpu.envs.jax import JaxEnv

        assert JaxEnv.max_episode_steps is None

    @pytest.mark.parametrize("env_id", sorted(EPISODE_LIMITS))
    def test_no_episode_of_a_random_policy_outruns_the_stated_limit(
            self, env_id):
        """``max_episode_steps`` is what the fused rollout sizes a
        sequence policy's history by (``runtime/anakin.carry_holds_cache``):
        300 autoreset steps of random actions a lane, four lanes, and no
        run of steps between two episode ends is longer than the limit."""
        kwargs, limit = EPISODE_LIMITS[env_id]
        env = make_jax(env_id, **kwargs)
        assert env.max_episode_steps == limit
        space = env.action_space

        def act(key):
            if hasattr(space, "n"):
                return jax.random.randint(key, (), 0, space.n)
            return jax.random.uniform(key, space.shape, jnp.float32,
                                      space.low, space.high)

        def lane(key):
            k_reset, k_env, k_act = jax.random.split(key, 3)
            state, _obs = env.reset(k_reset)

            def body(carry, k):
                ekey, state = carry
                ekey, state, _o, _r, term, trunc, _f = step_autoreset(
                    env, ekey, state, act(k))
                return (ekey, state), jnp.logical_or(term, trunc)

            _, done = jax.lax.scan(body, (k_env, state),
                                   jax.random.split(k_act, 300))
            return done

        done = np.asarray(jax.jit(jax.vmap(lane))(
            jax.random.split(jax.random.PRNGKey(11), 4)))
        assert done.any()
        for row in done:
            ends = np.flatnonzero(row)
            runs = np.diff(np.concatenate([[-1], ends]))
            assert runs.max() <= limit
            assert len(row) - 1 - ends[-1] < limit  # nor the open tail
