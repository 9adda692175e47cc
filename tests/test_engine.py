"""Sharded generation engine tests (SURVEY.md §4 'Distributed without a pod').

The key invariants of the broadcast-free design:
- the update computed on an 8-device mesh equals the 1-device update up to
  psum reduction order;
- the same seed gives the same trajectory (exact determinism on one mesh);
- the split evaluate→weights→update path (novelty family) reproduces the
  fused generation_step.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from estorch_tpu.envs import CartPole
from estorch_tpu.ops import centered_rank, make_noise_table, make_param_spec
from estorch_tpu.parallel import (
    EngineConfig,
    ESEngine,
    pairs_per_device,
    population_mesh,
    single_device_mesh,
)


def _mlp_setup():
    def init_params(key):
        k1, k2 = jax.random.split(key)
        return {
            "w1": jax.random.normal(k1, (4, 16)) * 0.5,
            "b1": jnp.zeros(16),
            "w2": jax.random.normal(k2, (16, 2)) * 0.5,
            "b2": jnp.zeros(2),
        }

    def apply(params, obs):
        h = jnp.tanh(obs @ params["w1"] + params["b1"])
        return h @ params["w2"] + params["b2"]

    params = init_params(jax.random.PRNGKey(0))
    flat, spec = make_param_spec(params)
    return flat, spec, apply


@pytest.fixture(scope="module")
def setup():
    flat, spec, apply = _mlp_setup()
    env = CartPole()
    table = make_noise_table(1 << 18, seed=0)
    cfg = EngineConfig(population_size=32, sigma=0.1, horizon=100, eval_chunk=8)
    opt = optax.adam(3e-2)
    return dict(flat=flat, spec=spec, apply=apply, env=env, table=table, cfg=cfg, opt=opt)


def _engine(s, mesh):
    return ESEngine(s["env"], s["apply"], s["spec"], s["table"], s["opt"], s["cfg"], mesh)


class TestShardingEquivalence:
    def test_8dev_equals_1dev(self, setup, devices8):
        e8 = _engine(setup, population_mesh())
        e1 = _engine(setup, single_device_mesh())
        s8 = e8.init_state(setup["flat"], jax.random.PRNGKey(7))
        s1 = e1.init_state(setup["flat"], jax.random.PRNGKey(7))
        for gen in range(4):
            s8, m8 = e8.generation_step(s8)
            s1, m1 = e1.generation_step(s1)
            np.testing.assert_array_equal(
                np.asarray(m8["fitness"]), np.asarray(m1["fitness"]),
                err_msg=f"fitness diverged at gen {gen}",
            )
            np.testing.assert_allclose(
                np.asarray(s8.params_flat), np.asarray(s1.params_flat),
                rtol=2e-5, atol=1e-6, err_msg=f"params diverged at gen {gen}",
            )

    def test_same_seed_exact_determinism(self, setup):
        e = _engine(setup, population_mesh())
        sa = e.init_state(setup["flat"], jax.random.PRNGKey(3))
        sb = e.init_state(setup["flat"], jax.random.PRNGKey(3))
        for _ in range(3):
            sa, _ = e.generation_step(sa)
            sb, _ = e.generation_step(sb)
        np.testing.assert_array_equal(np.asarray(sa.params_flat), np.asarray(sb.params_flat))

    def test_different_seed_differs(self, setup):
        e = _engine(setup, population_mesh())
        sa = e.init_state(setup["flat"], jax.random.PRNGKey(3))
        sb = e.init_state(setup["flat"], jax.random.PRNGKey(4))
        sa, _ = e.generation_step(sa)
        sb, _ = e.generation_step(sb)
        assert not np.array_equal(np.asarray(sa.params_flat), np.asarray(sb.params_flat))


class TestSplitPath:
    def test_split_equals_fused(self, setup):
        """evaluate → centered_rank → apply_weights == generation_step."""
        e = _engine(setup, population_mesh())
        s0 = e.init_state(setup["flat"], jax.random.PRNGKey(11))
        fused_state, fused_metrics = e.generation_step(s0)

        ev = e.evaluate(s0)
        np.testing.assert_array_equal(
            np.asarray(ev.fitness), np.asarray(fused_metrics["fitness"])
        )
        weights = centered_rank(jnp.asarray(ev.fitness))
        split_state, _ = e.apply_weights(s0, weights)
        np.testing.assert_allclose(
            np.asarray(split_state.params_flat), np.asarray(fused_state.params_flat),
            rtol=1e-6, atol=1e-7,
        )
        assert int(split_state.generation) == int(fused_state.generation) == 1

    def test_center_eval_is_deterministic(self, setup):
        e = _engine(setup, population_mesh())
        s0 = e.init_state(setup["flat"], jax.random.PRNGKey(11))
        r1 = e.evaluate_center(s0)
        r2 = e.evaluate_center(s0)
        assert float(r1.total_reward) == float(r2.total_reward)
        assert r1.bc.shape == (setup["env"].bc_dim,)


class TestMeshValidation:
    def test_odd_population_rejected(self):
        with pytest.raises(ValueError, match="even"):
            pairs_per_device(65, 8)

    def test_indivisible_pairs_padded(self, setup, devices8):
        """Regression for the old hard-error case: 17 pairs over 8 devices
        used to raise "use a population that is a multiple of 2·n_devices";
        now the population is ghost-padded (zero-weighted, clamped rows)
        and trains IDENTICALLY to the same population on one device —
        padding must be unobservable in fitness, steps, and the update."""
        assert pairs_per_device(34, 8) == 3  # ceil(17/8): padded pairs
        cfg = EngineConfig(population_size=34, sigma=0.1, horizon=30)
        e8 = ESEngine(setup["env"], setup["apply"], setup["spec"],
                      setup["table"], setup["opt"], cfg, population_mesh())
        e1 = ESEngine(setup["env"], setup["apply"], setup["spec"],
                      setup["table"], setup["opt"], cfg, single_device_mesh())
        s8 = e8.init_state(setup["flat"], jax.random.PRNGKey(7))
        s1 = e1.init_state(setup["flat"], jax.random.PRNGKey(7))
        for gen in range(2):
            s8, m8 = e8.generation_step(s8)
            s1, m1 = e1.generation_step(s1)
            assert m8["fitness"].shape == (34,)
            np.testing.assert_array_equal(
                np.asarray(m8["fitness"]), np.asarray(m1["fitness"]),
                err_msg=f"padded fitness diverged at gen {gen}")
            assert int(m8["steps"]) == int(m1["steps"])
            np.testing.assert_allclose(
                np.asarray(s8.params_flat), np.asarray(s1.params_flat),
                rtol=2e-5, atol=1e-6,
                err_msg=f"padded update diverged at gen {gen}")

    def test_member_reconstruction_matches_eval_perturbation(self, setup):
        """member_params(i) must be exactly the θ the engine evaluated for i."""
        e = _engine(setup, single_device_mesh())
        s0 = e.init_state(setup["flat"], jax.random.PRNGKey(2))
        ev = e.evaluate(s0)
        # re-evaluate member 5's reconstructed params by hand: same fitness
        from estorch_tpu.envs.rollout import make_rollout

        theta5 = e.member_params(s0, 5)
        # rollout key: pair 2 (member 5 = pair 2, sign -) shares the pair key
        import estorch_tpu.parallel.engine as eng_mod

        okey, rkey = eng_mod._gen_keys(s0)
        pair_keys = jax.random.split(rkey, setup["cfg"].population_size // 2)
        rollout = make_rollout(setup["env"], setup["apply"], setup["cfg"].horizon)
        res = rollout(setup["spec"].unravel(theta5), pair_keys[5 // 2])
        assert float(res.total_reward) == float(ev.fitness[5])


    @pytest.mark.parametrize("case,over", [
        ("sigma_decay", {"sigma_decay": 0.5, "sigma_min": 0.02}),
        ("episodes2", {"episodes_per_member": 2}),
        ("bf16", {"compute_dtype": "bfloat16"}),
    ], ids=["sigma_decay", "episodes2", "bf16"])
    def test_unmirrored_members_match_their_own_rollout(self, setup, case,
                                                        over):
        """The materialised body on an UNMIRRORED run, member by member:
        ``member_params(state, i)`` rolled out alone, with member i's own
        key, is ``fitness[i]`` — under an annealed σ, over two episodes a
        member, and in bf16."""
        import estorch_tpu.parallel.engine as eng_mod
        from estorch_tpu.envs.rollout import make_rollout

        cfg = EngineConfig(population_size=32, sigma=0.1, horizon=100,
                           eval_chunk=2, mirrored=False, **over)
        e = ESEngine(setup["env"], setup["apply"], setup["spec"],
                     setup["table"], setup["opt"], cfg, population_mesh())
        assert e.forward_form == "materialised"
        s = e.init_state(setup["flat"], jax.random.PRNGKey(3))
        if case == "sigma_decay":
            s, _ = e.generation_step(s)  # σ is 0.05 from here on
            assert float(s.sigma) == pytest.approx(0.05)
        fitness = np.asarray(e.evaluate(s).fitness)
        assert len(set(fitness.tolist())) > 1, "every member scored the same"

        apply, cast = setup["apply"], lambda tree: tree
        if case == "bf16":
            apply = eng_mod._bf16_io_apply(apply)
            cast = lambda tree: eng_mod._cast_leaves(tree, jnp.bfloat16)
        rollout = make_rollout(setup["env"], apply, cfg.horizon)
        _, rkey = eng_mod._gen_keys(s)
        keys = jax.random.split(rkey, cfg.population_size)
        # 4 members a device in 2 chunks: both chunks, first and last device
        for i in (0, 3, 13, 31):
            params = cast(setup["spec"].unravel(e.member_params(s, i)))
            if case == "episodes2":
                want = np.mean([float(rollout(params, k).total_reward)
                                for k in jax.random.split(keys[i], 2)])
            else:
                want = float(rollout(params, keys[i]).total_reward)
            assert want == float(fitness[i]), (case, i)


class TestSigmaAnnealing:
    def test_sigma_decays_with_floor(self, setup):
        cfg = EngineConfig(
            population_size=32, sigma=0.1, horizon=20, eval_chunk=8,
            sigma_decay=0.5, sigma_min=0.02,
        )
        e = ESEngine(setup["env"], setup["apply"], setup["spec"], setup["table"],
                     setup["opt"], cfg, population_mesh())
        s = e.init_state(setup["flat"], jax.random.PRNGKey(0))
        sigmas = [float(s.sigma)]
        for _ in range(4):
            s, _ = e.generation_step(s)
            sigmas.append(float(np.asarray(s.sigma)))
        np.testing.assert_allclose(sigmas, [0.1, 0.05, 0.025, 0.02, 0.02], rtol=1e-6)

    def test_member_reconstruction_uses_state_sigma(self, setup):
        cfg = EngineConfig(
            population_size=32, sigma=0.1, horizon=20, eval_chunk=8,
            sigma_decay=0.5,
        )
        e = ESEngine(setup["env"], setup["apply"], setup["spec"], setup["table"],
                     setup["opt"], cfg, single_device_mesh())
        s = e.init_state(setup["flat"], jax.random.PRNGKey(0))
        s, _ = e.generation_step(s)  # sigma now 0.05
        theta = np.asarray(e.member_params(s, 0))
        # exact reconstruction with the DECAYED state sigma
        offs = e.all_pair_offsets(s)
        eps = np.asarray(setup["table"].slice(offs[0], setup["spec"].dim))
        expected = np.asarray(s.params_flat) + float(np.asarray(s.sigma)) * eps
        np.testing.assert_allclose(theta, expected, rtol=1e-6, atol=1e-7)

    def test_default_no_decay_keeps_sigma(self, setup):
        e = _engine(setup, population_mesh())
        s = e.init_state(setup["flat"], jax.random.PRNGKey(0))
        s, _ = e.generation_step(s)
        assert float(np.asarray(s.sigma)) == pytest.approx(setup["cfg"].sigma)


class TestUnmirroredSampling:
    """Reference's plain ES: independent noise per member, no antithetic
    pairs (mirroring is the opt-in of BASELINE config 3)."""

    def _engine(self, setup, mesh, pop=32):
        cfg = EngineConfig(
            population_size=pop, sigma=0.1, horizon=100, eval_chunk=8,
            mirrored=False,
        )
        return ESEngine(setup["env"], setup["apply"], setup["spec"],
                        setup["table"], setup["opt"], cfg, mesh)

    def test_learns_cartpole(self, setup):
        e = self._engine(setup, population_mesh())
        s = e.init_state(setup["flat"], jax.random.PRNGKey(0))
        first = None
        for _ in range(10):
            s, m = e.generation_step(s)
            mean = float(np.asarray(m["fitness"]).mean())
            first = mean if first is None else first
        assert mean > first + 15, (first, mean)

    def test_8dev_equals_1dev(self, setup, devices8):
        e8 = self._engine(setup, population_mesh())
        e1 = self._engine(setup, single_device_mesh())
        s8 = e8.init_state(setup["flat"], jax.random.PRNGKey(5))
        s1 = e1.init_state(setup["flat"], jax.random.PRNGKey(5))
        for _ in range(3):
            s8, m8 = e8.generation_step(s8)
            s1, m1 = e1.generation_step(s1)
        np.testing.assert_array_equal(
            np.asarray(m8["fitness"]), np.asarray(m1["fitness"])
        )
        np.testing.assert_allclose(
            np.asarray(s8.params_flat), np.asarray(s1.params_flat),
            rtol=2e-5, atol=1e-6,
        )

    def test_member_reconstruction(self, setup):
        e = self._engine(setup, single_device_mesh())
        s = e.init_state(setup["flat"], jax.random.PRNGKey(2))
        ev = e.evaluate(s)
        # member 3's reconstructed theta re-rolls to its recorded fitness
        from estorch_tpu.envs.rollout import make_rollout
        import estorch_tpu.parallel.engine as eng_mod

        theta3 = e.member_params(s, 3)
        _, rkey = eng_mod._gen_keys(s)
        keys = jax.random.split(rkey, 32)
        rollout = make_rollout(setup["env"], setup["apply"], 100)
        res = rollout(setup["spec"].unravel(theta3), keys[3])
        assert float(res.total_reward) == float(ev.fitness[3])

    def test_odd_population_allowed(self, setup):
        """No pair structure -> odd populations are legal when they divide
        the mesh (single device here)."""
        cfg = EngineConfig(population_size=7, sigma=0.1, horizon=10, mirrored=False)
        e = ESEngine(setup["env"], setup["apply"], setup["spec"], setup["table"],
                     setup["opt"], cfg, single_device_mesh())
        s = e.init_state(setup["flat"], jax.random.PRNGKey(0))
        s, m = e.generation_step(s)
        assert np.asarray(m["fitness"]).shape == (7,)


class TestEpisodesPerMember:
    def test_multi_episode_fitness_and_steps(self, setup):
        cfg = EngineConfig(population_size=16, sigma=0.1, horizon=50,
                           episodes_per_member=3)
        e = ESEngine(setup["env"], setup["apply"], setup["spec"], setup["table"],
                     setup["opt"], cfg, single_device_mesh())
        s = e.init_state(setup["flat"], jax.random.PRNGKey(1))
        ev = e.evaluate(s)
        assert ev.fitness.shape == (16,)
        # 3 episodes per member: total alive steps must exceed the
        # single-episode engine's for the same seed
        cfg1 = EngineConfig(population_size=16, sigma=0.1, horizon=50)
        e1 = ESEngine(setup["env"], setup["apply"], setup["spec"], setup["table"],
                      setup["opt"], cfg1, single_device_mesh())
        ev1 = e1.evaluate(e1.init_state(setup["flat"], jax.random.PRNGKey(1)))
        assert int(ev.steps) > int(ev1.steps)

    def test_multi_episode_fitness_is_exact_episode_mean(self, setup):
        """Member fitness must equal the mean of its episode returns,
        replayed manually with the same keys."""
        from estorch_tpu.envs.rollout import make_rollout
        import estorch_tpu.parallel.engine as eng_mod

        cfg = EngineConfig(population_size=4, sigma=0.1, horizon=40,
                           episodes_per_member=3)
        e = ESEngine(setup["env"], setup["apply"], setup["spec"], setup["table"],
                     setup["opt"], cfg, single_device_mesh())
        s = e.init_state(setup["flat"], jax.random.PRNGKey(7))
        ev = e.evaluate(s)

        member = 1
        theta = e.member_params(s, member)
        _, rkey = eng_mod._gen_keys(s)
        pair_keys = jax.random.split(rkey, 2)  # population 4 → 2 pairs
        member_key = pair_keys[member // 2]
        rollout = make_rollout(setup["env"], setup["apply"], 40)
        rets = [
            float(rollout(setup["spec"].unravel(theta), k).total_reward)
            for k in jax.random.split(member_key, 3)
        ]
        np.testing.assert_allclose(
            float(np.asarray(ev.fitness)[member]), np.mean(rets), rtol=1e-6
        )


class TestMinimumPopulation:
    def test_population_of_two(self, setup):
        """One antithetic pair — the smallest legal population — must run."""
        cfg = EngineConfig(population_size=2, sigma=0.1, horizon=20)
        e = ESEngine(setup["env"], setup["apply"], setup["spec"], setup["table"],
                     setup["opt"], cfg, single_device_mesh())
        s = e.init_state(setup["flat"], jax.random.PRNGKey(0))
        s, m = e.generation_step(s)
        assert np.asarray(m["fitness"]).shape == (2,)
        assert int(s.generation) == 1


class _NaNBombEnv:
    """Continuous-action toy env whose reward is NaN whenever action[0]
    exceeds a threshold — so the perturbation's SIGN decides which members
    fail, deterministically for a fixed seed.  Episode = 5 steps."""

    obs_dim = 4
    action_dim = 2
    discrete = False
    bc_dim = 2

    def reset(self, key):
        del key
        return jnp.int32(0), jnp.zeros(4, jnp.float32)

    def step(self, state, action):
        reward = 1.0 - jnp.sum(action**2)
        reward = jnp.where(action[0] > 0.05, jnp.nan, reward)
        nstate = state + 1
        return nstate, jnp.zeros(4, jnp.float32), reward, nstate >= 5

    def behavior(self, state, obs):
        del state
        return obs[:2]


class TestNaNFitnessMasking:
    """VERDICT round-1 weak #1: the fused device path must not promote a
    NaN-fitness member to the top rank — it must match the host backend's
    drop-and-renormalize semantics (utils/fault.py)."""

    def _engine(self, setup, mesh):
        cfg = EngineConfig(population_size=32, sigma=0.1, horizon=8, eval_chunk=8)
        return ESEngine(_NaNBombEnv(), setup["apply"], setup["spec"],
                        setup["table"], setup["opt"], cfg, mesh)

    def test_fused_update_matches_host_renormalization(self, setup):
        from estorch_tpu.utils.fault import rank_weights_with_failures

        e = self._engine(setup, single_device_mesh())
        s0 = e.init_state(setup["flat"], jax.random.PRNGKey(9))
        ev = e.evaluate(s0)
        fit = np.asarray(ev.fitness)
        # the seed must actually produce a mixed population or the test is vacuous
        assert np.isnan(fit).any(), "seed produced no NaN members — adjust threshold"
        assert np.isfinite(fit).sum() >= 2

        fused_state, m = e.generation_step(s0)
        assert int(m["n_valid"]) == int(np.isfinite(fit).sum())
        assert np.isfinite(np.asarray(fused_state.params_flat)).all()

        # split path with the HOST weighting = the required semantics
        w = rank_weights_with_failures(fit)
        split_state, _ = e.apply_weights(s0, jnp.asarray(w))
        np.testing.assert_allclose(
            np.asarray(fused_state.params_flat),
            np.asarray(split_state.params_flat),
            rtol=1e-6, atol=1e-7,
        )

    def test_nan_member_contributes_zero_weight(self, setup):
        """Sanity on the weights themselves: re-derive them in-program and
        check the NaN members got exactly 0."""
        from estorch_tpu.ops import centered_rank_safe

        e = self._engine(setup, population_mesh())
        s0 = e.init_state(setup["flat"], jax.random.PRNGKey(9))
        fit = np.asarray(e.evaluate(s0).fitness)
        w, _ = centered_rank_safe(jnp.asarray(fit))
        w = np.asarray(w)
        assert (w[~np.isfinite(fit)] == 0.0).all()
        assert abs(w.sum()) < 1e-4  # still centered over survivors

    @pytest.mark.slow
    def test_all_invalid_generation_raises_via_api(self, setup):
        """Backend parity: host/pooled raise when <2 members survive; the
        device path must too (ES.train acts on the n_valid metric)."""
        import optax as _optax

        from estorch_tpu import ES
        from estorch_tpu.envs.agent import JaxAgent
        from estorch_tpu.models import MLPPolicy

        class _AlwaysNaN(_NaNBombEnv):
            def step(self, state, action):
                nstate, obs, _, done = _NaNBombEnv.step(self, state, action)
                return nstate, obs, jnp.float32(jnp.nan), done

        es = ES(
            MLPPolicy, JaxAgent(_AlwaysNaN(), horizon=5), _optax.adam,
            policy_kwargs={"action_dim": 2, "hidden": (8,), "discrete": False},
            optimizer_kwargs={"learning_rate": 1e-2},
            population_size=16, sigma=0.1, seed=0,
        )
        flat_before = np.asarray(es.state.params_flat).copy()
        gen_before = int(es.state.generation)
        with pytest.raises(RuntimeError, match="valid fitness"):
            es.train(1, verbose=False)
        # state must be rolled back — a catcher that checkpoints es.state
        # must not persist the dead-generation update
        np.testing.assert_array_equal(np.asarray(es.state.params_flat), flat_before)
        assert int(es.state.generation) == gen_before

    def test_all_finite_metrics_report_full_population(self, setup):
        # a HEALTHY env (module fixture's CartPole, not the NaN bomb):
        # every member must count as valid
        cartpole_engine = _engine(setup, population_mesh())
        s = cartpole_engine.init_state(setup["flat"], jax.random.PRNGKey(0))
        _, m = cartpole_engine.generation_step(s)
        assert int(m["n_valid"]) == setup["cfg"].population_size


class TestLearning:
    def test_cartpole_learns(self, setup):
        """Fitness must rise substantially within a few generations (smoke =
        BASELINE config 1, scaled down for CI speed)."""
        e = _engine(setup, population_mesh())
        s = e.init_state(setup["flat"], jax.random.PRNGKey(0))
        first_mean = None
        for gen in range(10):
            s, m = e.generation_step(s)
            mean = float(np.asarray(m["fitness"]).mean())
            if first_mean is None:
                first_mean = mean
        assert mean > first_mean + 20, (first_mean, mean)


def test_driver_entry_point_dry_run(devices8, capsys):
    """``__graft_entry__.dryrun_multichip`` is what the driver calls to see
    every forward form of the engine compile and run over a mesh; nothing
    else in this suite runs it."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "__graft_entry__.py")
    spec = importlib.util.spec_from_file_location("graft_entry", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.dryrun_multichip(len(devices8))
    out = capsys.readouterr().out
    for mode in ("mirrored", "unmirrored", "pair_shared", "lowrank",
                 "recurrent", "obsnorm", "obsnorm_lowrank",
                 "recurrent_lowrank"):
        assert f"dryrun_multichip(8)[{mode}]" in out, mode
    assert "dryrun_multichip(8): OK" in out
