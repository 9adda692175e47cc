"""IW-ES: importance-weighted sample reuse (algo/iwes.py + engine programs).

Anchors: λ against a direct Gaussian-density-ratio oracle on materialized
member params; the combined update against a dense hand-built estimator;
the ESS guard's fallback to vanilla ES; end-to-end learnability.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from conftest import materialised

from estorch_tpu import ES, IW_ES, JaxAgent, MLPPolicy
from estorch_tpu.envs import CartPole


def _make(cls=IW_ES, n_pop=16, seed=7, **kw):
    base = dict(
        policy=MLPPolicy,
        agent=JaxAgent,
        optimizer=optax.adam,
        population_size=n_pop,
        sigma=0.1,
        seed=seed,
        policy_kwargs={"action_dim": 2, "hidden": (8,)},
        agent_kwargs={"env": CartPole(), "horizon": 50},
        optimizer_kwargs={"learning_rate": 1e-2},
        table_size=1 << 15,
    )
    base.update(kw)
    return cls(**base)


class TestRatios:
    @pytest.mark.slow
    def test_lambda_matches_density_ratio_oracle(self):
        """λ from engine noise_stats must equal the direct Gaussian density
        ratio computed from each materialized old member."""
        es = _make()
        es.train(1, verbose=False)
        prev_st = es.state  # snapshot a REAL state (the ring keeps only a
        es.train(1, verbose=False)  # minimal record; member_params needs it)
        st = es.state
        entry = (prev_st.params_flat, float(np.asarray(prev_st.sigma)),
                 es.engine.all_pair_offsets(prev_st), None)
        lam, d_vec, c, old_offsets = es._ratios(entry, st)

        dim = es._spec.dim
        s_old = float(np.asarray(prev_st.sigma))
        s_new = float(np.asarray(st.sigma))
        center_old = np.asarray(prev_st.params_flat)
        center_new = np.asarray(st.params_flat)
        want = np.zeros(es.population_size)
        for i in range(es.population_size):
            theta = np.asarray(es.engine.member_params(prev_st, i))
            e_old = (theta - center_old) / s_old
            e_new = (theta - center_new) / s_new
            log_ratio = dim * np.log(s_old / s_new) + 0.5 * (
                e_old @ e_old - e_new @ e_new
            )
            want[i] = log_ratio
        want = np.exp(want - want.max())  # _ratios shifts by max too
        np.testing.assert_allclose(lam, want, rtol=2e-3, atol=2e-4)

    def test_identity_move_gives_uniform_lambda(self):
        """θ_new == θ_old and equal σ → every λ identical → ESS == n."""
        es = _make()
        es.train(1, verbose=False)  # populate state only
        st = es.state
        entry = (st.params_flat, float(np.asarray(st.sigma)),
                 es.engine.all_pair_offsets(st), None)
        lam, d_vec, c, _ = es._ratios(entry, st)
        np.testing.assert_allclose(lam, lam[0])
        ess = lam.sum() ** 2 / (lam**2).sum()
        assert ess == pytest.approx(es.population_size)


class TestUpdate:
    @pytest.mark.slow
    def test_reuse_update_matches_dense_oracle(self):
        """engine.apply_weights_reuse == hand-built combined estimator on
        materialized noise, run through the same optax transform."""
        es = _make(n_pop=16)
        es.train(1, verbose=False)
        prev_st = es.state
        prev_fit = np.asarray(es.engine.evaluate(prev_st).fitness)
        es.train(1, verbose=False)
        st = es.state

        ev = es.engine.evaluate(st)
        fitness = np.asarray(ev.fitness)
        entry = (prev_st.params_flat, float(np.asarray(prev_st.sigma)),
                 es.engine.all_pair_offsets(prev_st), prev_fit)
        lam, d_vec, c, old_offsets = es._ratios(entry, st)
        new_st, gnorm = es._reuse_update(
            st, fitness, [(prev_fit, lam, d_vec, c, old_offsets)]
        )

        # ---- oracle ----
        from estorch_tpu.utils.fault import rank_weights_with_failures

        n = 16
        sigma_new = float(np.asarray(st.sigma))
        w_all = rank_weights_with_failures(np.concatenate([fitness, prev_fit]))
        w_fresh, w_old = w_all[:n], w_all[n:]
        lam_t = lam * n / lam.sum()

        center = np.asarray(st.params_flat)
        grad = np.zeros_like(center)
        okey = jax.random.fold_in(jax.random.fold_in(st.key, st.generation), 0)
        from estorch_tpu.ops.noise import sample_pair_offsets

        offs = np.asarray(
            sample_pair_offsets(okey, n // 2, es.table.size, es._spec.dim)
        )
        for i in range(n):
            eps = np.asarray(es.table.slice(int(offs[i // 2]), es._spec.dim))
            s = 1.0 if i % 2 == 0 else -1.0
            grad += w_fresh[i] * s * eps
        d_np = np.asarray(d_vec)
        for i in range(n):
            theta = np.asarray(es.engine.member_params(prev_st, i))
            eps_new = (theta - center) / sigma_new
            grad += w_old[i] * lam_t[i] * eps_new
        grad /= 2 * n * sigma_new

        opt = optax.adam(1e-2)
        updates, _ = opt.update(
            -jnp.asarray(grad), st.opt_state, st.params_flat
        )
        want = np.asarray(optax.apply_updates(st.params_flat, updates))
        np.testing.assert_allclose(
            np.asarray(new_st.params_flat), want, rtol=1e-4, atol=1e-5
        )

    def test_ess_guard_falls_back_to_vanilla(self):
        """A huge center move collapses λ → ESS guard skips reuse and the
        generation must be recorded as non-reused."""
        es = _make(optimizer_kwargs={"learning_rate": 5.0})  # violent moves
        es.train(3, verbose=False)
        assert not any(r["reused_prev"] for r in es.history[1:])
        # with a tame lr the same seed settles into reuse within a few gens
        es2 = _make()
        es2.train(6, verbose=False)
        assert any(r["reused_prev"] for r in es2.history)
        assert all(r["ess"] >= 0.0 for r in es2.history)

    def test_pair_shared_forward_is_equivalent(self):
        """IW_ES runs whichever forward the engine resolves; since the
        decomposition behind the pair-shared one is an exact identity at
        f32, the whole reuse trajectory must match the materialised
        forward — offsets, fitness, ESS decisions, and the combined
        update."""
        es_std = materialised(_make())
        es_dec = _make()
        assert es_dec.engine.forward_form == "pair_shared"
        es_std.train(5, verbose=False)
        es_dec.train(5, verbose=False)
        assert ([r["reused_prev"] for r in es_std.history]
                == [r["reused_prev"] for r in es_dec.history])
        np.testing.assert_allclose(
            np.asarray(es_std.state.params_flat),
            np.asarray(es_dec.state.params_flat),
            rtol=0, atol=1e-6,
        )

    @pytest.mark.slow
    def test_never_reusing_warns_once_with_heuristic(self):
        """20+ consecutive ESS rejections → one RuntimeWarning naming the
        lr ≲ σ/√dim fix; reuse-friendly runs stay silent."""
        import warnings

        es = _make(optimizer_kwargs={"learning_rate": 5.0})
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            es.train(IW_ES.DRY_WARN_AFTER + 3, verbose=False)
        msgs = [w for w in caught if issubclass(w.category, RuntimeWarning)
                and "ESS guard" in str(w.message)]
        assert len(msgs) == 1, [str(w.message) for w in caught]
        assert "sigma/sqrt(dim)" in str(msgs[0].message)
        assert not any(r["reused_prev"] for r in es.history)

        es2 = _make()  # tame lr: reuses, so no warning even over many gens
        with warnings.catch_warnings(record=True) as caught2:
            warnings.simplefilter("always")
            es2.train(IW_ES.DRY_WARN_AFTER + 3, verbose=False)
        assert not [w for w in caught2
                    if issubclass(w.category, RuntimeWarning)
                    and "ESS guard" in str(w.message)]
        assert any(r["reused_prev"] for r in es2.history)

    @pytest.mark.slow
    def test_multi_generation_window(self):
        """reuse_window=3: the ring fills, multiple generations are admitted
        once moves settle, and effective_samples scales with reused_gens."""
        es = _make(reuse_window=3)
        es.train(12, verbose=False)
        gens = [r["reused_gens"] for r in es.history]
        assert max(gens) >= 2, gens  # at least one update used 2+ old gens
        for r in es.history:
            assert r["effective_samples"] == 16 * (1 + r["reused_gens"])
        assert np.isfinite(es.history[-1]["reward_mean"])

    @pytest.mark.slow
    def test_window_mesh_invariance(self):
        from estorch_tpu.parallel.mesh import population_mesh

        es8 = _make(reuse_window=2)
        es1 = _make(reuse_window=2, mesh=population_mesh(jax.devices()[:1]))
        es8.train(4, verbose=False)
        es1.train(4, verbose=False)
        np.testing.assert_allclose(
            np.asarray(es8.state.params_flat),
            np.asarray(es1.state.params_flat),
            rtol=0, atol=1e-6,
        )

    def test_records_have_iw_fields(self):
        es = _make()
        es.train(2, verbose=False)
        r0, r1 = es.history
        assert r0["reused_prev"] is False  # nothing to reuse at gen 0
        assert r0["effective_samples"] == 16
        assert "ess" in r1

    def test_mesh_invariance(self):
        from estorch_tpu.parallel.mesh import population_mesh

        es8 = _make()
        es1 = _make(mesh=population_mesh(jax.devices()[:1]))
        es8.train(3, verbose=False)
        es1.train(3, verbose=False)
        np.testing.assert_allclose(
            np.asarray(es8.state.params_flat),
            np.asarray(es1.state.params_flat),
            rtol=0, atol=1e-6,
        )

    def test_unmirrored(self):
        es = _make(mirrored=False)
        es.train(3, verbose=False)
        assert np.isfinite(es.history[-1]["reward_mean"])

    def test_rejected_combinations(self):
        with pytest.raises(ValueError, match="low_rank"):
            _make(low_rank=1)
        import torch

        class P(torch.nn.Module):
            def __init__(self):
                super().__init__()
                self.lin = torch.nn.Linear(2, 2)

            def forward(self, x):
                return self.lin(x)

        class A:
            def rollout(self, policy):
                return 0.0

        with pytest.raises(ValueError, match="device"):
            IW_ES(P, A, torch.optim.Adam, population_size=4)


class TestLearnability:
    def test_cartpole_improves(self):
        """Learnability and reuse are naturally antagonistic (fast learning
        = big center moves = collapsed λ, the guard correctly disables
        reuse) — so this asserts improvement only; reuse firing is pinned
        by test_ess_guard_falls_back_to_vanilla's small-step regime."""
        es = _make(n_pop=32, seed=0,
                   agent_kwargs={"env": CartPole(), "horizon": 200},
                   optimizer_kwargs={"learning_rate": 3e-2})
        es.train(12, verbose=False)
        first = es.history[0]["reward_mean"]
        best = max(r["reward_mean"] for r in es.history)
        assert best > first + 40.0, (first, best)
