"""Recurrent-policy support (device path): carry threading through the
compiled rollout scan, learning on a memory probe, option guards.

The reference has no recurrent machinery — its user-owned
``agent.rollout`` loop (SURVEY.md §3.3) lets torch users thread hidden
state by hand.  Here the episode loop is a compiled ``lax.scan``
(envs/rollout.py), so the framework threads the carry; these tests pin
that contract end to end.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from estorch_tpu import ES, JaxAgent, MLPPolicy, RecurrentPolicy
from estorch_tpu.envs import RecallEnv
from estorch_tpu.envs.rollout import make_rollout


def _make_es(policy, pk, **over):
    kw = dict(
        policy=policy,
        agent=JaxAgent,
        optimizer=optax.adam,
        population_size=128,
        sigma=0.1,
        policy_kwargs=pk,
        agent_kwargs={"env": RecallEnv(), "horizon": 16},
        optimizer_kwargs={"learning_rate": 5e-2},
        seed=0,
    )
    kw.update(over)
    return ES(**kw)


RECURRENT_PK = {"action_dim": 1, "hidden": (8,), "gru_size": 8,
                "discrete": False}


class TestRecurrentPolicyModule:
    def test_apply_returns_out_and_carry(self):
        mod = RecurrentPolicy(**RECURRENT_PK)
        obs = jnp.zeros((1,))
        h0 = mod.carry_init()
        assert h0.shape == (8,)
        variables = mod.init(jax.random.PRNGKey(0), obs, h0)
        out, h1 = mod.apply(variables, obs, h0)
        assert out.shape == (1,)
        assert h1.shape == (8,)

    def test_carry_accumulates_history(self):
        """Identical observations at t>0 must still produce different
        outputs when the histories differ — that is what the carry is for."""
        mod = RecurrentPolicy(**RECURRENT_PK)
        h0 = mod.carry_init()
        variables = mod.init(jax.random.PRNGKey(0), jnp.zeros((1,)), h0)
        _, h_pos = mod.apply(variables, jnp.ones((1,)), h0)
        _, h_neg = mod.apply(variables, -jnp.ones((1,)), h0)
        zero = jnp.zeros((1,))
        out_pos, _ = mod.apply(variables, zero, h_pos)
        out_neg, _ = mod.apply(variables, zero, h_neg)
        assert not np.allclose(np.asarray(out_pos), np.asarray(out_neg))


class TestCarryThreading:
    def test_rollout_threads_and_resets_carry(self):
        """A hand-built 'policy' whose carry counts its own invocations:
        after a horizon-H rollout the count must be H (threading), and a
        second rollout must start from 0 again (reset per episode)."""
        env = RecallEnv()
        seen = {}

        def policy_apply(params, obs, h):
            seen["h"] = h
            return jnp.zeros((1,)), h + 1.0

        # deliberately the LEGACY zero-arg form: make_rollout must keep
        # accepting it (inspect-based detection in envs/rollout.py)
        rollout = make_rollout(env, policy_apply, horizon=5,
                               carry_init=lambda: jnp.zeros(()))
        res = rollout({}, jax.random.PRNGKey(0))
        assert int(res.steps) == 5
        # trace-time check: the carry entered the scan as the carry slot
        assert seen["h"].shape == ()

        # the carry VALUE is observable through the action: emit h as the
        # action, reward = clip(h)*sign -> with sign=+1 total = 0+1+1+1+1
        # (h clips at 1 from step 2 on)
        def emit_h(params, obs, h):
            return h[None], h + 1.0

        rollout2 = make_rollout(env, emit_h, horizon=5,
                                carry_init=lambda params=None: jnp.zeros(()))
        for key in range(4):
            res2 = rollout2({}, jax.random.PRNGKey(key))
            sign = float(env.reset(jax.random.PRNGKey(key))[0][0])
            assert float(res2.total_reward) == pytest.approx(4.0 * sign)


class TestRecurrentTraining:
    @pytest.mark.slow
    def test_learns_memory_task_where_memoryless_cannot(self):
        """RecallEnv: the ±1 signal is visible only at t=0; reward is
        action*signal each step.  Memoryless expected return caps at ~1
        (the first step); the recurrent policy must blow through that."""
        # pop 256 / 80 gens: converges to the ceiling (16.0) on seeds 0-2;
        # pop 128 / 60 gens was measured NOT enough (stalls ~3)
        es = _make_es(RecurrentPolicy, RECURRENT_PK, population_size=256)
        es.train(80, verbose=False)
        ev = es.evaluate_policy(n_episodes=64, seed=9)
        assert ev["mean"] > 8.0, f"recurrent policy failed to learn: {ev}"

        base = _make_es(MLPPolicy,
                        {"action_dim": 1, "hidden": (8, 8), "discrete": False})
        base.train(60, verbose=False)
        ev0 = base.evaluate_policy(n_episodes=64, seed=9)
        assert ev0["mean"] < 4.0, f"memoryless should cap near 1: {ev0}"

    @pytest.mark.slow
    def test_bf16_recurrent_runs_and_learns(self):
        es = _make_es(RecurrentPolicy, RECURRENT_PK,
                      compute_dtype="bfloat16")
        es.train(25, verbose=False)
        assert es.history[-1]["reward_mean"] > es.history[0]["reward_mean"]

    def test_bf16_legacy_zero_arg_carry_init(self):
        """ADVICE regression: the engine's bf16 carry wrapper used to call
        ``base_carry_init(params)`` unconditionally, so a legacy zero-arg
        ``carry_init`` worked in f32 but raised TypeError under
        compute_dtype='bfloat16'.  It must run (and cast the carry) in
        both dtypes."""
        import optax as _optax

        from estorch_tpu.envs import CartPole
        from estorch_tpu.ops import make_noise_table, make_param_spec
        from estorch_tpu.parallel import (EngineConfig, ESEngine,
                                          single_device_mesh)

        def init_params(key):
            return {
                "w": jax.random.normal(key, (4, 8)) * 0.5,
                "wo": jnp.zeros((8, 2)),
            }

        def apply(params, obs, h):
            h_new = jnp.tanh(obs @ params["w"] + h)
            return h_new @ params["wo"], h_new

        flat, spec = make_param_spec(init_params(jax.random.PRNGKey(0)))
        for dtype in ("float32", "bfloat16"):
            eng = ESEngine(
                CartPole(), apply, spec, make_noise_table(1 << 16, seed=0),
                _optax.sgd(1e-2),
                EngineConfig(population_size=8, sigma=0.1, horizon=10,
                             compute_dtype=dtype),
                single_device_mesh(),
                carry_init=lambda: jnp.zeros((8,)),  # legacy zero-arg form
            )
            state = eng.init_state(flat, jax.random.PRNGKey(1))
            state, metrics = eng.generation_step(state)
            assert np.isfinite(float(np.asarray(metrics["fitness"]).mean()))

    @pytest.mark.slow
    def test_mirrored_off_and_episodes_per_member(self):
        es = _make_es(RecurrentPolicy, RECURRENT_PK, mirrored=False,
                      episodes_per_member=2, population_size=64)
        es.train(2, verbose=False)
        assert np.isfinite(es.history[-1]["reward_mean"])


class TestRecurrentLowRank:
    """Recurrent × low_rank (round-4 verdict next #7): factored noise over
    the whole recurrent tree — trunk, cell gates, head — with per-episode
    materialization (ops/lowrank.py tree form)."""

    def test_tree_spec_factors_cell_kernels(self):
        es = _make_es(RecurrentPolicy, RECURRENT_PK, low_rank=1)
        spec = es.engine.lr_spec
        assert hasattr(spec, "treedef")
        # every 2-D kernel where rank-1 saves must be factored — the GRU
        # gate kernels included (the whole point of the recurrent form)
        assert len(spec.lr_leaves) >= 6  # trunk + 6 gru gates + head, minus
        # any no-saving shapes
        assert spec.noise_dim < es.engine.spec.dim  # the O(dim) state shrank

    @pytest.mark.slow
    def test_trains_and_split_equals_fused(self):
        from estorch_tpu.utils.fault import rank_weights_with_failures

        es = _make_es(RecurrentPolicy, RECURRENT_PK, low_rank=1,
                      population_size=32)
        ev = es.engine.evaluate(es.state)
        w = rank_weights_with_failures(np.asarray(ev.fitness))
        split_state, _ = es.engine.apply_weights(es.state, w)

        es2 = _make_es(RecurrentPolicy, RECURRENT_PK, low_rank=1,
                       population_size=32)
        fused_state, _ = es2.engine.generation_step(es2.state)
        np.testing.assert_array_equal(
            np.asarray(split_state.params_flat),
            np.asarray(fused_state.params_flat),
        )

    def test_member_params_match_evaluated_member(self):
        """member_params(i) must rebuild exactly the θ_i the rollout saw."""
        es = _make_es(RecurrentPolicy, RECURRENT_PK, low_rank=1,
                      population_size=16)
        res = es.engine.evaluate(es.state)
        fitness = np.asarray(res.fitness)
        i = int(np.argmax(fitness))
        theta = es.engine.member_params(es.state, i)

        okey, rkey = jax.random.fold_in(
            jax.random.fold_in(es.state.key, es.state.generation), 0
        ), jax.random.fold_in(
            jax.random.fold_in(es.state.key, es.state.generation), 1
        )
        pair_keys = jax.random.split(rkey, 8)
        key_i = jnp.repeat(pair_keys, 2, axis=0)[i]
        rollout = make_rollout(es.env, es._policy_apply, 16,
                               carry_init=es.module.carry_init)
        res_i = rollout(es._spec.unravel(theta), key_i)
        assert float(res_i.total_reward) == pytest.approx(
            fitness[i], abs=1e-4
        )

    @pytest.mark.slow
    def test_lstm_low_rank_trains(self):
        pk = dict(RECURRENT_PK, cell="lstm")
        es = _make_es(RecurrentPolicy, pk, low_rank=1, population_size=32)
        es.train(2, verbose=False)
        assert np.isfinite(es.history[-1]["reward_mean"])

    @pytest.mark.slow
    def test_bf16_runs(self):
        es = _make_es(RecurrentPolicy, RECURRENT_PK, low_rank=1,
                      population_size=32, compute_dtype="bfloat16")
        es.train(1, verbose=False)
        assert np.isfinite(es.history[-1]["reward_mean"])


class TestRecurrentPooled:
    """The pooled path threads the carry host-side across the generation's
    env-step loop (parallel/pooled.py) — one stacked (population, …) carry
    updated by the same batched forward that computes actions."""

    def _pooled_es(self, **over):
        from estorch_tpu import PooledAgent

        kw = dict(
            policy=RecurrentPolicy,
            agent=PooledAgent,
            optimizer=optax.adam,
            population_size=16,
            sigma=0.1,
            policy_kwargs={"action_dim": 2, "hidden": (8,), "gru_size": 8,
                           "discrete": True},
            agent_kwargs={"env_name": "cartpole", "horizon": 32},
            optimizer_kwargs={"learning_rate": 1e-2},
            seed=0,
        )
        kw.update(over)
        return ES(**kw)

    @pytest.mark.slow
    def test_trains_and_is_finite(self):
        es = self._pooled_es()
        es.train(2, verbose=False)
        assert np.isfinite(es.history[-1]["reward_mean"])
        ev = es.evaluate_policy(n_episodes=2)
        assert np.isfinite(ev["mean"])

    def test_carry_changes_actions(self):
        """Same observation, different carries -> different policy output:
        the carry genuinely reaches the pooled batched forward."""
        es = self._pooled_es()
        eng = es.engine
        assert eng.recurrent
        pair_offs = eng.core.all_pair_offsets(es.state)
        thetas = eng._materialize(es.state.params_flat, es.state.sigma,
                                  pair_offs)
        obs = jnp.ones((16, 4))
        h0 = eng._carries(16)
        _, h1 = eng._batch_actions(thetas, obs, h0)
        # after one distinct step the carries must differ from start
        assert not np.allclose(np.asarray(h1), np.asarray(h0))
        # logits path: argmax may coincide, so compare carries after a
        # second step from the two different carry states
        _, h2a = eng._batch_actions(thetas, obs, h1)
        _, h2b = eng._batch_actions(thetas, obs, h0)
        assert not np.allclose(np.asarray(h2a), np.asarray(h2b))

    def test_double_buffer_runs(self):
        es_a = self._pooled_es()
        es_b = self._pooled_es(
            agent_kwargs={"env_name": "cartpole", "horizon": 32,
                          "double_buffer": True},
        )
        ra = es_a.engine.evaluate(es_a.state)
        rb = es_b.engine.evaluate(es_b.state)
        assert ra.fitness.shape == rb.fitness.shape
        assert np.isfinite(ra.fitness).all() and np.isfinite(rb.fitness).all()


class TestRecurrentPredict:
    def test_predict_carry_roundtrip(self):
        es = _make_es(RecurrentPolicy, RECURRENT_PK)
        out, h = es.predict(jnp.ones((1,)))
        assert out.shape == (1,) and h.shape == (8,)
        out2, h2 = es.predict(jnp.zeros((1,)), carry=h)
        assert h2.shape == (8,)

    def test_predict_zero_arg_carry_init_module(self):
        """ADVICE regression: predict() used to call
        ``self.module.carry_init(p)`` unconditionally; a custom recurrent
        module with the historical zero-arg ``carry_init()`` worked in
        the rollout path but broke in predict.  Both paths share the
        compat contract now."""

        class LegacyCarryPolicy(RecurrentPolicy):
            def carry_init(self):  # historical zero-arg form
                return super().carry_init(None)

        es = _make_es(LegacyCarryPolicy, RECURRENT_PK, population_size=32)
        out, h = es.predict(jnp.ones((1,)))
        assert out.shape == (1,) and h.shape == (8,)
        es.train(1, verbose=False)  # rollout path agrees
        assert np.isfinite(es.history[-1]["reward_mean"])


class TestLSTMCore:
    @pytest.mark.slow
    def test_lstm_carry_is_tuple_and_trains(self):
        pk = {**RECURRENT_PK, "cell": "lstm"}
        mod = RecurrentPolicy(**pk)
        c0 = mod.carry_init()
        assert isinstance(c0, tuple) and len(c0) == 2
        es = _make_es(RecurrentPolicy, pk, population_size=64)
        es.train(3, verbose=False)
        assert np.isfinite(es.history[-1]["reward_mean"])

    @pytest.mark.slow
    def test_lstm_learns_memory_task(self):
        pk = {**RECURRENT_PK, "cell": "lstm"}
        es = _make_es(RecurrentPolicy, pk, population_size=256)
        es.train(80, verbose=False)
        ev = es.evaluate_policy(n_episodes=64, seed=9)
        assert ev["mean"] > 8.0, f"LSTM policy failed to learn: {ev}"

    def test_bad_cell_rejected(self):
        with pytest.raises(ValueError, match="cell"):
            _make_es(RecurrentPolicy, {**RECURRENT_PK, "cell": "rnn"})

    @pytest.mark.slow
    def test_lstm_bf16_runs(self):
        pk = {**RECURRENT_PK, "cell": "lstm"}
        es = _make_es(RecurrentPolicy, pk, population_size=32,
                      compute_dtype="bfloat16")
        es.train(2, verbose=False)
        assert np.isfinite(es.history[-1]["reward_mean"])


class TestRecurrentVision:
    """RecurrentNatureCNN on the pooled pixel-pong path: conv trunk + GRU
    memory over real 84×84 observations."""

    def test_shapes_and_carry(self):
        from estorch_tpu import RecurrentNatureCNN

        mod = RecurrentNatureCNN(action_dim=3, gru_size=32)
        obs = jnp.zeros((84, 84, 1), jnp.float32)
        h0 = mod.carry_init()
        assert h0.shape == (32,)
        variables = mod.init(jax.random.PRNGKey(0), obs, h0)
        out, h1 = mod.apply(variables, obs, h0)
        assert out.shape == (3,) and h1.shape == (32,)

    @pytest.mark.slow
    def test_pooled_pong_trains(self):
        from estorch_tpu import PooledAgent, RecurrentNatureCNN

        es = ES(
            policy=RecurrentNatureCNN,
            agent=PooledAgent,
            optimizer=optax.adam,
            population_size=16,
            sigma=0.05,
            policy_kwargs={"action_dim": 3, "gru_size": 32},
            agent_kwargs={"env_name": "pong84", "horizon": 48},
            optimizer_kwargs={"learning_rate": 1e-2},
            seed=0,
        )
        es.train(1, verbose=False)
        assert np.isfinite(es.history[-1]["reward_mean"])
        assert es.engine.recurrent


class TestStackedAndLearnedCarry:
    """Round-5 ROADMAP item 6: stacked recurrent cells and a LEARNED
    episode-start carry.  ``carry0_*`` are ordinary params — perturbed by
    ES noise, moved by the update — and ``carry_init(params)`` reads the
    member's values at episode start (envs/rollout.py passes the member's
    perturbed tree).  The reference has no recurrent machinery at all
    (SURVEY.md §3.3), so both are beyond-parity extensions."""

    def test_stacked_carry_structure(self):
        for cell in ("gru", "lstm"):
            pk = dict(RECURRENT_PK, cell=cell, n_layers=2)
            mod = RecurrentPolicy(**pk)
            h0 = mod.carry_init()
            assert isinstance(h0, tuple) and len(h0) == 2
            obs = jnp.zeros((1,))
            v = mod.init(jax.random.PRNGKey(0), obs, h0)
            _, h1 = mod.apply(v, obs, h0)
            assert (jax.tree_util.tree_structure(h1)
                    == jax.tree_util.tree_structure(h0))
            # layer 0 keeps the historic single-layer submodule name (so
            # existing checkpoints/goldens stay valid); layer 1 is suffixed
            assert cell in v["params"] and f"{cell}_1" in v["params"]

    @pytest.mark.slow
    def test_stacked_trains(self):
        es = _make_es(RecurrentPolicy, dict(RECURRENT_PK, n_layers=2),
                      population_size=32)
        es.train(2, verbose=False)
        assert np.isfinite(es.history[-1]["reward_mean"])

    def test_learned_carry_params_exist_and_are_read(self):
        mod = RecurrentPolicy(**dict(RECURRENT_PK, learned_carry=True))
        obs = jnp.zeros((1,))
        v = mod.init(jax.random.PRNGKey(0), obs, mod.carry_init())
        assert "carry0_0" in v["params"]
        p = dict(v["params"])
        p["carry0_0"] = jnp.full((8,), 0.5)
        np.testing.assert_array_equal(np.asarray(mod.carry_init(p)),
                                      np.full((8,), 0.5))
        # variables-dict form and the zero-arg shape donor both work
        np.testing.assert_array_equal(np.asarray(mod.carry_init({"params": p})),
                                      np.full((8,), 0.5))
        assert np.all(np.asarray(mod.carry_init()) == 0)

    @pytest.mark.slow
    def test_learned_carry_trains_and_moves(self):
        es = _make_es(RecurrentPolicy,
                      dict(RECURRENT_PK, learned_carry=True),
                      population_size=64)
        c0 = np.asarray(
            es._spec.unravel(es.state.params_flat)["carry0_0"]).copy()
        es.train(3, verbose=False)
        c1 = np.asarray(es._spec.unravel(es.state.params_flat)["carry0_0"])
        assert np.isfinite(es.history[-1]["reward_mean"])
        # the learned carry is a real parameter: the update moved it
        assert not np.allclose(c0, c1)

    @pytest.mark.slow
    def test_learned_carry_split_equals_fused(self):
        from estorch_tpu.utils.fault import rank_weights_with_failures

        pk = dict(RECURRENT_PK, learned_carry=True)
        es = _make_es(RecurrentPolicy, pk, population_size=32)
        ev = es.engine.evaluate(es.state)
        w = rank_weights_with_failures(np.asarray(ev.fitness))
        split_state, _ = es.engine.apply_weights(es.state, w)
        es2 = _make_es(RecurrentPolicy, pk, population_size=32)
        fused_state, _ = es2.engine.generation_step(es2.state)
        np.testing.assert_array_equal(np.asarray(split_state.params_flat),
                                      np.asarray(fused_state.params_flat))

    @pytest.mark.slow
    def test_learned_carry_low_rank_is_dense_leaf(self):
        es = _make_es(RecurrentPolicy,
                      dict(RECURRENT_PK, learned_carry=True),
                      low_rank=1, population_size=32)
        # identify carry0_0's leaf INDEX (shape alone would collide with
        # same-shaped biases) and assert that exact leaf gets dense noise
        tree = es._spec.unravel(es.state.params_flat)
        paths = jax.tree_util.tree_flatten_with_path(tree)[0]
        carry_idx = [i for i, (path, _) in enumerate(paths)
                     if any(getattr(k, "key", None) == "carry0_0"
                            for k in path)]
        assert len(carry_idx) == 1
        dense_idx = {i for i, _, _, _ in es.engine.lr_spec.dense_leaves}
        assert carry_idx[0] in dense_idx  # exact dense noise, never dropped
        es.train(1, verbose=False)
        assert np.isfinite(es.history[-1]["reward_mean"])

    @pytest.mark.slow
    def test_lstm_stacked_learned_bf16_trains(self):
        pk = dict(RECURRENT_PK, cell="lstm", n_layers=2, learned_carry=True)
        es = _make_es(RecurrentPolicy, pk, population_size=32,
                      compute_dtype="bfloat16")
        es.train(1, verbose=False)
        assert np.isfinite(es.history[-1]["reward_mean"])

    def test_pooled_rejects_learned_carry(self):
        from estorch_tpu import PooledAgent

        with pytest.raises(ValueError, match="learned_carry"):
            ES(
                policy=RecurrentPolicy,
                agent=PooledAgent,
                optimizer=optax.adam,
                population_size=8,
                sigma=0.1,
                policy_kwargs={"action_dim": 2, "hidden": (8,),
                               "gru_size": 8, "discrete": True,
                               "learned_carry": True},
                agent_kwargs={"env_name": "cartpole", "horizon": 8},
                optimizer_kwargs={"learning_rate": 1e-2},
                seed=0,
            )

    @pytest.mark.slow
    def test_learned_carry_composes_with_obs_norm(self):
        """obs_norm packs the rollout's params as (tree, obs_stats); the
        engine's carry_init wrapper must read the learned carry from the
        PARAMS half (parallel/engine.py rollout_carry_init)."""
        es = _make_es(RecurrentPolicy,
                      dict(RECURRENT_PK, learned_carry=True),
                      population_size=32, obs_norm=True)
        es.train(2, verbose=False)
        assert np.isfinite(es.history[-1]["reward_mean"])
