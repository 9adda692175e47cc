"""Native C++ envpool: 3-way dynamics parity (C++ vs NumPy fallback vs JAX
envs) and the pooled ES backend end-to-end."""

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp

from estorch_tpu import ES, NS_ES, MLPPolicy, PooledAgent
from estorch_tpu.parallel import single_device_mesh
from estorch_tpu.envs import CartPole, Pendulum
from estorch_tpu.envs.native_pool import NativeEnvPool, _NumpyPool


@pytest.fixture(scope="module")
def native_available():
    pool = NativeEnvPool("cartpole", 1)
    ok = pool.is_native
    pool.close()
    if not ok:
        pytest.skip("C++ envpool unavailable (no compiler)")


class TestPoolParity:
    def test_cartpole_cpp_matches_jax_env(self, native_available):
        """Same start state + actions → identical trajectories (C++ vs JAX)."""
        pool = NativeEnvPool("cartpole", 4, n_threads=2, seed=0)
        obs = pool.reset()
        env = CartPole()
        jstate = jnp.asarray(obs)  # state == obs for cartpole
        rng = np.random.default_rng(3)
        for t in range(30):
            acts = rng.integers(0, 2, (4, 1)).astype(np.float32)
            cobs, crew, cdone = pool.step(acts)
            for i in range(4):
                js, jobs_, jrew, jdone = env.step(jstate[i], jnp.int32(int(acts[i, 0])))
                if cdone[i]:
                    # C++ auto-resets; just check the done flag agreed
                    assert bool(jdone)
                else:
                    np.testing.assert_allclose(
                        cobs[i], np.asarray(jobs_), rtol=1e-4, atol=1e-5,
                        err_msg=f"step {t} env {i}",
                    )
                jstate = jstate.at[i].set(js if not cdone[i] else jnp.asarray(cobs[i]))
        pool.close()

    def test_pendulum_cpp_matches_jax_env(self, native_available):
        pool = NativeEnvPool("pendulum", 2, seed=5)
        obs = pool.reset()
        env = Pendulum()
        # recover (th, thdot) from obs
        states = [jnp.array([np.arctan2(o[1], o[0]), o[2]]) for o in obs]
        rng = np.random.default_rng(1)
        for t in range(25):
            acts = rng.uniform(-2, 2, (2, 1)).astype(np.float32)
            cobs, crew, _ = pool.step(acts)
            for i in range(2):
                s, o, r, _ = env.step(states[i], jnp.asarray(acts[i]))
                states[i] = s
                np.testing.assert_allclose(cobs[i], np.asarray(o), rtol=1e-3, atol=1e-4)
                np.testing.assert_allclose(crew[i], float(r), rtol=1e-3, atol=1e-4)
        pool.close()

    def test_numpy_fallback_matches_cpp_dynamics(self, native_available):
        """C++ and the NumPy fallback step identically from the same state."""
        cpp = NativeEnvPool("cartpole", 8, seed=0)
        npy = _NumpyPool(0, 8, seed=0)
        obs_c = cpp.reset()
        npy.reset()
        npy.state = obs_c.copy()  # align states (reset RNGs differ)
        acts = np.ones((8, 1), np.float32)
        oc, rc, dc = cpp.step(acts)
        on, rn, dn = npy.step(acts)
        live = ~dc
        np.testing.assert_allclose(oc[live], on[live], rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(dc, dn)
        cpp.close()

    def test_auto_reset_keeps_envs_alive(self, native_available):
        pool = NativeEnvPool("cartpole", 16, seed=2)
        pool.reset()
        done_seen = False
        for _ in range(300):
            obs, rew, done = pool.step(np.zeros((16, 1), np.float32))
            done_seen = done_seen or bool(done.any())
            # auto-reset: post-done observations are fresh (within bounds)
            assert np.all(np.abs(obs[done, 0]) <= 0.05 + 1e-6)
        assert done_seen
        pool.close()

    def test_failed_rebuild_never_loads_the_stale_library(
            self, native_available, monkeypatch):
        """A libenvpool.so older than the tracked envpool.cpp whose
        rebuild fails must NOT be loaded (it steps some other version of
        the envs): no library, and the NumPy pool takes over."""
        import subprocess

        from estorch_tpu.envs import native_pool

        def no_compiler(*a, **k):
            raise subprocess.CalledProcessError(2, "make")

        monkeypatch.setattr(native_pool, "_stale", lambda path: True)
        monkeypatch.setattr(native_pool.subprocess, "run", no_compiler)
        assert native_pool.os.path.exists(native_pool._LIB_PATH)
        assert native_pool._load_library() is None

    def test_unknown_env_rejected(self):
        with pytest.raises(ValueError, match="unknown env"):
            NativeEnvPool("humanoid", 4)

    def test_thread_count_invariance(self, native_available):
        """1-thread and 8-thread pools produce identical trajectories."""
        a = NativeEnvPool("pendulum", 32, n_threads=1, seed=9)
        b = NativeEnvPool("pendulum", 32, n_threads=8, seed=9)
        oa, ob = a.reset(), b.reset()
        np.testing.assert_array_equal(oa, ob)
        for _ in range(10):
            acts = np.full((32, 1), 0.5, np.float32)
            oa, ra, _ = a.step(acts)
            ob, rb, _ = b.step(acts)
            np.testing.assert_array_equal(oa, ob)
            np.testing.assert_array_equal(ra, rb)
        a.close()
        b.close()


class TestSanitizers:
    """SURVEY §5 race detection: the pool's thread team under TSan/ASan."""

    @staticmethod
    def _sanitizer_supported(flag: str) -> bool:
        """Probe the toolchain, NOT our code: skip only when the sanitizer
        runtime itself is unavailable; a compile error in our sources must
        FAIL the test, not skip it."""
        import subprocess
        import tempfile

        with tempfile.NamedTemporaryFile("w", suffix=".cpp") as f:
            f.write("int main(){return 0;}\n")
            f.flush()
            probe = subprocess.run(
                ["g++", flag, "-o", "/dev/null", f.name],
                capture_output=True, timeout=60,
            )
        return probe.returncode == 0

    @pytest.mark.parametrize("target,binary,flag", [
        ("tsan", "stress_tsan", "-fsanitize=thread"),
        ("asan", "stress_asan", "-fsanitize=address"),
    ])
    def test_sanitizer_stress_clean(self, target, binary, flag):
        import os
        import subprocess

        if not self._sanitizer_supported(flag):
            pytest.skip(f"toolchain lacks {flag}")
        native = os.path.join(os.path.dirname(__file__), "..", "estorch_tpu", "native")
        build = subprocess.run(
            ["make", "-C", native, target], capture_output=True, timeout=180
        )
        assert build.returncode == 0, (
            f"{target} build failed:\n{build.stderr.decode(errors='replace')[-2000:]}"
        )
        run = subprocess.run(
            [os.path.join(native, binary)], capture_output=True, timeout=600
        )
        assert run.returncode == 0, (
            f"{target} stress failed:\n{run.stderr.decode(errors='replace')[-2000:]}"
        )
        assert b"stress: OK" in run.stdout


class TestPooledBackend:
    def _make(self, cls=ES, **extra):
        kw = dict(
            policy=MLPPolicy,
            agent=PooledAgent,
            optimizer=optax.adam,
            population_size=32,
            sigma=0.1,
            seed=0,
            policy_kwargs={"action_dim": 2, "hidden": (16,)},
            agent_kwargs={"env_name": "cartpole", "horizon": 100},
            optimizer_kwargs={"learning_rate": 3e-2},
            table_size=1 << 16,
        )
        kw.update(extra)
        return cls(**kw)

    def test_backend_detected_and_trains(self):
        es = self._make()
        assert es.backend == "pooled"
        es.train(5, verbose=False)
        assert len(es.history) == 5
        assert es.history[-1]["env_steps"] > 0

    def test_learning_on_pooled_cartpole(self):
        es = self._make()
        es.train(10, verbose=False)
        first = es.history[0]["reward_mean"]
        last = es.history[-1]["reward_mean"]
        assert last > first, (first, last)

    def test_pooled_update_matches_device_offsets(self):
        """The pooled path must use the exact offsets the update regenerates:
        member_params(i) equals the i-th row of the materialized thetas."""
        es = self._make()
        pair_offs = es.engine.core.all_pair_offsets(es.state)
        thetas = es.engine._materialize(
            es.state.params_flat, es.state.sigma, pair_offs
        )
        for i in (0, 1, 7):
            np.testing.assert_allclose(
                np.asarray(es.engine.member_params(es.state, i)),
                np.asarray(thetas[i]),
                rtol=1e-6, atol=1e-7,
            )

    def test_double_buffer_learns_and_counts_steps(self):
        """The overlapped path must behave like a working evaluator: learning
        happens, step accounting is sane, shapes match."""
        es = self._make(agent_kwargs={"env_name": "cartpole", "horizon": 100,
                                      "double_buffer": True})
        es.train(8, verbose=False)
        first, last = es.history[0], es.history[-1]
        assert last["reward_mean"] > first["reward_mean"], (first, last)
        assert 0 < last["env_steps"] <= 32 * 100

    def test_double_buffer_matches_sync_given_same_pools(self):
        """With identical env streams, DB evaluation must equal the sync
        path member-for-member (same thetas, same pools, same seeds)."""
        import jax.numpy as jnp

        a = self._make(agent_kwargs={"env_name": "cartpole", "horizon": 60,
                                     "double_buffer": True})
        pair_offs = a.engine.core.all_pair_offsets(a.state)
        thetas = a.engine._materialize(a.state.params_flat, a.state.sigma, pair_offs)
        db = a.engine._evaluate_double_buffered(thetas)

        # rebuild the same half-pools and replay through the sync algorithm
        from estorch_tpu.envs.native_pool import NativeEnvPool

        ref_fit = np.zeros(32, np.float32)
        for lo, seed in ((0, 0), (16, 10_007)):
            pool = NativeEnvPool("cartpole", 16, seed=seed)
            obs = pool.reset()
            alive = np.ones(16, bool)
            for _ in range(60):
                acts = np.asarray(
                    a.engine._batch_actions(thetas[lo:lo + 16], jnp.asarray(obs))
                )
                obs, rew, done = pool.step(acts)
                ref_fit[lo:lo + 16] += rew * alive
                alive &= ~done
                if not alive.any():
                    break
            pool.close()
        np.testing.assert_allclose(db.fitness, ref_fit, rtol=1e-5, atol=1e-6)

    def test_ns_es_on_pooled(self):
        es = self._make(cls=NS_ES, meta_population_size=2, k=3)
        es.train(2, verbose=False)
        assert len(es.archive) == 2 + 2
        assert es.history[-1]["archive_size"] == 4

    def test_vbn_on_pooled(self):
        es = self._make(
            policy_kwargs={"action_dim": 2, "hidden": (16,), "use_vbn": True},
        )
        es.train(1, verbose=False)
        assert "vbn_stats" in es._frozen


class TestGymVecPool:
    """Arbitrary gymnasium envs on the pooled path via the gym: prefix —
    device-batched inference for MuJoCo-class envs without MJX."""

    def test_pool_interface_over_gym_env(self):
        from estorch_tpu.envs.gym_vec_pool import make_pool

        pool = make_pool("gym:CartPole-v1", 6, seed=0)
        assert pool.obs_shape == (4,) and pool.discrete and pool.n_actions == 2
        obs = pool.reset()
        assert obs.shape == (6, 4)
        obs, rew, done = pool.step(np.ones((6, 1), np.float32))
        assert rew.shape == (6,) and done.shape == (6,)
        pool.close()

    def test_resets_draw_fresh_initial_states(self):
        """Regression: reseeding every reset would evaluate identical starts
        each generation; only the FIRST reset pins the seed."""
        from estorch_tpu.envs.gym_vec_pool import make_pool

        pool = make_pool("gym:CartPole-v1", 4, seed=0)
        a = pool.reset()
        b = pool.reset()
        assert not np.array_equal(a, b)
        pool.close()
        # determinism across pools still holds (same seed, same sequence)
        p1 = make_pool("gym:CartPole-v1", 4, seed=0)
        c = p1.reset()
        np.testing.assert_array_equal(a, c)
        p1.close()

    def test_pooled_es_on_gym_env(self):
        """Full pooled training over a gymnasium env (device-batched
        forwards, gym.vector stepping, psum update)."""
        es = self._mk_gym_es()
        es.train(4, verbose=False)
        assert es.backend == "pooled"
        first = es.history[0]["reward_mean"]
        last = es.history[-1]["reward_mean"]
        assert last > first, (first, last)

    def test_pooled_es_on_gym_mujoco(self):
        """MuJoCo (HalfCheetah) through the pooled path — BASELINE config 2's
        env with device-batched inference."""
        es = ES(
            policy=MLPPolicy, agent=PooledAgent, optimizer=optax.adam,
            population_size=8, sigma=0.05, seed=0,
            policy_kwargs={"action_dim": 6, "hidden": (16,), "discrete": False},
            agent_kwargs={"env_name": "gym:HalfCheetah-v5", "horizon": 30},
            optimizer_kwargs={"learning_rate": 1e-2},
            table_size=1 << 14,
            mesh=single_device_mesh(),
        )
        es.train(1, verbose=False)
        assert np.isfinite(es.history[0]["reward_mean"])
        assert es.history[0]["env_steps"] == 8 * 30  # cheetah never terminates

    @staticmethod
    def _mk_gym_es():
        return ES(
            policy=MLPPolicy, agent=PooledAgent, optimizer=optax.adam,
            population_size=16, sigma=0.1, seed=0,
            policy_kwargs={"action_dim": 2, "hidden": (16,)},
            agent_kwargs={"env_name": "gym:CartPole-v1", "horizon": 100},
            optimizer_kwargs={"learning_rate": 3e-2},
            table_size=1 << 16,
        )

    def test_env_kwargs_reach_gym_make(self):
        """env_kwargs forward to gym.make — HalfCheetah with x-position in
        the observation (the BC the novelty locomotion family needs) grows
        obs_dim 17 → 18, consistently in spec probe AND pool."""
        from estorch_tpu.envs.gym_vec_pool import make_pool, pool_env_spec

        kw = {"exclude_current_positions_from_observation": False}
        spec = pool_env_spec("gym:HalfCheetah-v5", kw)
        assert spec["obs_dim"] == 18
        pool = make_pool("gym:HalfCheetah-v5", 2, seed=0, env_kwargs=kw)
        assert pool.obs_dim == 18
        pool.close()

    def test_env_kwargs_rejected_for_native(self):
        from estorch_tpu.envs.gym_vec_pool import make_pool

        with pytest.raises(ValueError, match="native"):
            make_pool("cartpole", 2, env_kwargs={"x": 1})

    def test_bc_indices_slice_the_final_obs(self):
        """bc_indices=(0,) → 1-dim BC everywhere the pooled path reports
        one: member evaluation, center evaluation, batched held-out eval."""
        es = ES(
            policy=MLPPolicy, agent=PooledAgent, optimizer=optax.adam,
            population_size=8, sigma=0.1, seed=0,
            policy_kwargs={"action_dim": 2, "hidden": (8,)},
            agent_kwargs={"env_name": "cartpole", "horizon": 30,
                          "bc_indices": (0,)},
            optimizer_kwargs={"learning_rate": 1e-2},
            table_size=1 << 14,
            mesh=single_device_mesh(),
        )
        assert es.engine.bc_dim == 1
        ev = es.engine.evaluate(es.state)
        assert np.asarray(ev.bc).shape == (8, 1)
        c = es.engine.evaluate_center(es.state)
        assert np.asarray(c.bc).shape == (1,)
        det = es.evaluate_policy(n_episodes=3, return_details=True)
        assert det["bc"].shape == (3, 1)
        es.engine.pool.close()
        es.engine.center_pool.close()


class TestPong84ConvPath:
    """The Atari-config machinery (conv policy + pooled pixel env) end to
    end, using the bundled pong84 C++ env in place of ALE (BASELINE config 5
    stand-in)."""

    def test_pong84_env_semantics(self, native_available):
        pool = NativeEnvPool("pong84", 4, n_threads=2, seed=0)
        obs = pool.reset()
        assert obs.shape == (4, 84 * 84)
        assert pool.obs_shape == (84, 84, 1)
        assert pool.discrete and pool.n_actions == 3
        # pixels are binary {0, 1}
        assert set(np.unique(obs)).issubset({0.0, 1.0})
        # a still agent eventually concedes points (negative rewards), and
        # play CONTINUES past a point (multi-rally episodes, ALE-style)
        conceded = np.zeros(4)
        won = np.zeros(4)
        dones = np.zeros(4, bool)
        for _ in range(2000):
            _, r, d = pool.step(np.zeros((4, 1), np.float32))
            conceded += (r < 0)
            won += (r > 0)
            dones |= d
        # structural (not statistical): a still agent concedes far more than
        # the tracker does, and play continues past single points
        assert conceded.sum() > won.sum()
        assert np.any(conceded > 1)
        # first-to-21 match: no env may report done before conceding 21
        # (a still agent can still WIN points off tracker spin, so count
        # conceded, not net)
        for i in range(4):
            if dones[i]:
                assert conceded[i] >= 21
        pool.close()

    def test_pong84_match_runs_to_21(self, native_available):
        """done fires exactly at the 21st CONCEDED point (the still agent
        may also score a few off tracker spin — those don't end matches)."""
        pool = NativeEnvPool("pong84", 1, n_threads=1, seed=3)
        pool.reset()
        conceded, steps = 0, 0
        done = False
        while not done and steps < 60_000:
            _, r, d = pool.step(np.zeros((1, 1), np.float32))
            conceded += int(r[0] < 0.0)
            done = bool(d[0])
            steps += 1
        assert done, "match never ended"
        assert conceded == 21
        pool.close()

    def test_naturecnn_es_on_pong84(self, native_available):
        """Full conv rollout: NatureCNN population through the pooled path."""
        from estorch_tpu import NatureCNN
        from estorch_tpu.parallel import single_device_mesh

        es = ES(
            policy=NatureCNN,
            agent=PooledAgent,
            optimizer=optax.adam,
            population_size=4,
            sigma=0.05,
            seed=0,
            policy_kwargs={"action_dim": 3, "use_vbn": False},
            agent_kwargs={"env_name": "pong84", "horizon": 40},
            optimizer_kwargs={"learning_rate": 1e-2},
            table_size=1 << 21,  # NatureCNN ~1.7M params needs a larger table
            mesh=single_device_mesh(),  # pop 4 need not divide the 8-dev mesh
        )
        es.train(2, verbose=False)
        assert es.backend == "pooled"
        assert len(es.history) == 2
        assert es.history[-1]["env_steps"] > 0
        assert np.isfinite(es.history[-1]["reward_mean"])
