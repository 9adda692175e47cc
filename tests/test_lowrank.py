"""Low-rank perturbations (ops/lowrank.py + engine low_rank path).

Covers: noise statistics (zero-mean, unit variance of E entries), the
update reduction vs a direct dense oracle, forward equivalence vs a
materialized dense perturbation, mirrored-pair antithesis, 8-dev == 1-dev
invariance, member_params consistency, and end-to-end learnability.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from estorch_tpu import ES, JaxAgent, MLPPolicy
from estorch_tpu.envs import CartPole, Pendulum
from estorch_tpu.ops.lowrank import (
    lowrank_tree_noise,
    lowrank_tree_weighted_sum,
    make_lowrank_spec,
)


def _mlp_params(key, dims=(6, 8, 3)):
    """MLPPolicy-shaped param tree {dense_0.., head: {kernel, bias}}."""
    names = [f"dense_{i}" for i in range(len(dims) - 2)] + ["head"]
    params = {}
    for i, name in enumerate(names):
        k1, key = jax.random.split(key)
        params[name] = {
            "kernel": jax.random.normal(k1, (dims[i], dims[i + 1])),
            "bias": jnp.zeros((dims[i + 1],)),
        }
    return params


class TestSpec:
    def test_layout_and_dims(self):
        params = _mlp_params(jax.random.key(0), dims=(6, 8, 3))
        spec = make_lowrank_spec(params, rank=2)
        # kernels: (6+8)*2 + (8+3)*2 = 50; biases: 8 + 3 = 11
        assert spec.noise_dim == 50 + 11
        vec = jnp.arange(spec.noise_dim, dtype=jnp.float32)
        unpacked = spec.unpack(vec)
        (a, b), nb = unpacked["dense_0"]["kernel"], unpacked["dense_0"]["bias"]
        assert a.shape == (6, 2) and b.shape == (8, 2) and nb.shape == (8,)
        (a, b), nb = unpacked["head"]["kernel"], unpacked["head"]["bias"]
        assert a.shape == (8, 2) and b.shape == (3, 2) and nb.shape == (3,)
        # the MLP case of the tree spec keeps the layout its runs have
        # always drawn: kernels in layer order (A then B), then biases
        assert float(unpacked["dense_0"]["kernel"][0][0, 0]) == 0.0
        assert float(unpacked["dense_0"]["kernel"][1][0, 0]) == 12.0
        assert float(a[0, 0]) == 28.0 and float(b[0, 0]) == 44.0
        assert float(unpacked["dense_0"]["bias"][0]) == 50.0
        assert float(nb[0]) == 58.0
        # a leading batch axis (one row per pair) unpacks leaf by leaf
        batched = spec.unpack(jnp.stack([vec, vec + 1.0]))
        assert batched["head"]["kernel"][1].shape == (2, 3, 2)
        np.testing.assert_array_equal(batched["head"]["bias"][1], nb + 1.0)

    def test_tree_spec_lays_noise_out_in_leaf_order_by_default(self):
        from estorch_tpu.ops.lowrank import make_lowrank_tree_spec

        params = _mlp_params(jax.random.key(0), dims=(6, 8, 3))
        tree = make_lowrank_tree_spec(params, 2)
        mlp = make_lowrank_spec(params, rank=2)
        assert tree.noise_dim == mlp.noise_dim == 61
        assert type(tree) is type(mlp)
        # leaf order: dense_0/bias first; the MLP order: dense_0/kernel
        assert tree.dense_leaves[0][-1] == 0 and mlp.lr_leaves[0][3] == 0
        with pytest.raises(ValueError, match="low_rank"):
            make_lowrank_tree_spec(params, 0)

    def test_dense_fallback_when_rank_not_low(self):
        """rank ≥ min(m, n) layers get exact dense noise (same size, exact
        Gaussian) instead of a fake low-rank factorization."""
        params = _mlp_params(jax.random.key(0), dims=(6, 8, 3))
        spec = make_lowrank_spec(params, rank=3)  # head is 8x3 → dense
        # leaves in tree order: dense_0/{bias, kernel}, head/{bias, kernel}
        assert [l[0] for l in spec.lr_leaves] == [1]
        assert [l[0] for l in spec.dense_leaves] == [0, 2, 3]
        # dense_0: (6+8)*3 = 42; head dense: 8*3 = 24; biases: 8+3 = 11
        assert spec.noise_dim == 42 + 24 + 11
        unpacked = spec.unpack(jnp.arange(spec.noise_dim, dtype=jnp.float32))
        e, nb = unpacked["head"]["kernel"], unpacked["head"]["bias"]
        assert not isinstance(e, tuple)
        assert e.shape == (8, 3) and nb.shape == (3,)

    def test_unit_variance_entries(self):
        """Dense E entries must be ~N(0,1)-moment-matched for σ to keep its
        full-rank meaning."""
        params = _mlp_params(jax.random.key(0), dims=(32, 32, 16))
        spec = make_lowrank_spec(params, rank=4)
        vals = []
        for s in range(200):
            noise = jax.random.normal(jax.random.key(s), (spec.noise_dim,))
            dense = lowrank_tree_noise(spec, noise)
            vals.append(np.asarray(dense["dense_0"]["kernel"]).ravel())
        flat = np.concatenate(vals)
        assert abs(flat.mean()) < 0.01
        assert abs(flat.var() - 1.0) < 0.05


class TestUpdateReduction:
    def test_weighted_sum_matches_dense_oracle(self):
        params = _mlp_params(jax.random.key(1), dims=(5, 7, 2))
        spec = make_lowrank_spec(params, rank=1)
        k = 9
        noise = jax.random.normal(jax.random.key(2), (k, spec.noise_dim))
        w = jax.random.normal(jax.random.key(3), (k,))
        got = lowrank_tree_weighted_sum(spec, noise, w)
        # oracle: materialize every member's dense tree and sum
        for name in ("dense_0", "head"):
            want_k = sum(
                float(w[i]) * np.asarray(lowrank_tree_noise(spec, noise[i])[name]["kernel"])
                for i in range(k)
            )
            np.testing.assert_allclose(
                np.asarray(got[name]["kernel"]), want_k, rtol=1e-5, atol=1e-5
            )
            want_b = sum(
                float(w[i]) * np.asarray(lowrank_tree_noise(spec, noise[i])[name]["bias"])
                for i in range(k)
            )
            np.testing.assert_allclose(
                np.asarray(got[name]["bias"]), want_b, rtol=1e-5, atol=1e-5
            )


class TestForward:
    def test_lowrank_apply_matches_materialized_dense(self):
        """mlp_lowrank_apply == MLPPolicy.apply with W + c·dense(E)."""
        from estorch_tpu.models.decomposed import mlp_lowrank_apply

        module = MLPPolicy(action_dim=3, hidden=(8,), discrete=True)
        obs = jax.random.normal(jax.random.key(0), (6,))
        variables = module.init(jax.random.key(1), obs)
        params = variables["params"]
        spec = make_lowrank_spec(params, rank=2)
        noise = jax.random.normal(jax.random.key(2), (spec.noise_dim,))
        c = 0.13

        got = mlp_lowrank_apply(module, params, spec.unpack(noise), c, obs)

        dense = lowrank_tree_noise(spec, noise)
        perturbed = jax.tree_util.tree_map(
            lambda p, e: p + c * e, params, dense
        )
        want = module.apply({"params": perturbed}, obs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def _make_es(n_pop=16, seed=7, rank=1, **kw):
    return ES(
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
        low_rank=rank,
        **kw,
    )


class TestEngineIntegration:
    def test_trains_and_history_sane(self):
        es = _make_es()
        es.train(2, verbose=False)
        assert len(es.history) == 2
        assert np.isfinite(es.history[-1]["reward_mean"])

    def test_mesh_invariance(self):
        """8 virtual devices must produce the identical update as 1."""
        from estorch_tpu.parallel.mesh import population_mesh

        es8 = _make_es()
        mesh1 = population_mesh(jax.devices()[:1])
        es1 = _make_es(mesh=mesh1)
        es8.train(2, verbose=False)
        es1.train(2, verbose=False)
        np.testing.assert_allclose(
            np.asarray(es8.state.params_flat),
            np.asarray(es1.state.params_flat),
            rtol=0, atol=1e-6,
        )

    def test_member_params_match_evaluated_member(self):
        """member_params(i) must rebuild exactly the θ_i the rollout saw:
        evaluate member i's reconstructed params and compare fitness."""
        es = _make_es(n_pop=16)
        res = es.engine.evaluate(es.state)
        fitness = np.asarray(res.fitness)
        i = int(np.argmax(fitness))
        theta = es.engine.member_params(es.state, i)

        from estorch_tpu.envs.rollout import make_rollout

        okey, rkey = jax.random.fold_in(
            jax.random.fold_in(es.state.key, es.state.generation), 0
        ), jax.random.fold_in(
            jax.random.fold_in(es.state.key, es.state.generation), 1
        )
        pair_keys = jax.random.split(rkey, 8)
        key_i = jnp.repeat(pair_keys, 2, axis=0)[i]
        rollout = make_rollout(es.env, es._policy_apply, 50)
        res_i = rollout(es._spec.unravel(theta), key_i)
        assert float(res_i.total_reward) == pytest.approx(fitness[i], abs=1e-4)

    def test_unmirrored_mode(self):
        es = _make_es(mirrored=False)
        es.train(2, verbose=False)
        assert np.isfinite(es.history[-1]["reward_mean"])

    def test_rejected_on_host_and_pooled(self):
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

        with pytest.raises(ValueError, match="low_rank"):
            ES(P, A, torch.optim.Adam, population_size=4, low_rank=1)

        from estorch_tpu import PooledAgent

        with pytest.raises(ValueError, match="low_rank"):
            ES(
                policy=MLPPolicy,
                agent=PooledAgent,
                optimizer=optax.adam,
                population_size=16,
                policy_kwargs={"action_dim": 2, "hidden": (8,)},
                agent_kwargs={"env_name": "cartpole", "horizon": 20},
                optimizer_kwargs={"learning_rate": 1e-2},
                table_size=1 << 15,
                low_rank=1,
            )

    def test_needs_a_policy_with_a_perturbed_forward(self):
        """low_rank is a property of the search distribution, not a forward
        any module can run: one without a perturbed form (models/
        perturbed.py) and without a carry is refused at construction."""
        import flax.linen as nn

        class TwoLayer(nn.Module):
            @nn.compact
            def __call__(self, obs):
                return nn.Dense(2)(nn.tanh(nn.Dense(8)(obs)))

        with pytest.raises(ValueError, match="perturbed forward"):
            ES(policy=TwoLayer, agent=JaxAgent, optimizer=optax.adam,
               population_size=16, sigma=0.1,
               agent_kwargs={"env": CartPole(), "horizon": 50},
               optimizer_kwargs={"learning_rate": 1e-2},
               table_size=1 << 15, low_rank=1)

    def test_learnability_pendulum(self):
        """Rank-1 ES must still learn: Pendulum mean return improves."""
        env = Pendulum()
        es = ES(
            policy=MLPPolicy,
            agent=JaxAgent,
            optimizer=optax.adam,
            population_size=256,
            sigma=0.1,
            seed=0,
            policy_kwargs={"action_dim": 1, "hidden": (16, 16),
                           "discrete": False, "action_scale": 2.0},
            agent_kwargs={"env": env, "horizon": 100},
            optimizer_kwargs={"learning_rate": 3e-2},
            table_size=1 << 17,
            low_rank=1,
        )
        es.train(15, verbose=False)
        first = es.history[0]["reward_mean"]
        last = max(r["reward_mean"] for r in es.history)
        # calibration: full-rank ES on this exact budget reaches ~+60; the
        # hyperscale claim is rank-1 ≈ full-rank, not rank-1 ≫ full-rank
        assert last > first + 40.0, (first, last)


class TestTreeSpec:
    """Generic pytree low-rank form (recurrent policies, round-5)."""

    def _params(self):
        key = jax.random.key(0)
        k1, k2, k3, k4 = jax.random.split(key, 4)
        return {
            "dense": {"kernel": jax.random.normal(k1, (12, 16)),
                      "bias": jax.random.normal(k2, (16,))},
            "cell": {"gate": {"kernel": jax.random.normal(k3, (16, 16))}},
            "head": {"kernel": jax.random.normal(k4, (16, 2))},
        }

    def test_layout(self):
        from estorch_tpu.ops.lowrank import make_lowrank_tree_spec

        p = self._params()
        spec = make_lowrank_tree_spec(p, 1)
        # factored: (12,16) and (16,16); dense: bias (1-D) and head
        # ((16+2)·1 ≥ 16·2 is false → 18 < 32, so head factors too)
        assert len(spec.lr_leaves) == 3
        assert len(spec.dense_leaves) == 1
        assert spec.noise_dim == (12 + 16) + (16 + 16) + (16 + 2) + 16

    def test_noise_tree_matches_perturb(self):
        from estorch_tpu.ops.lowrank import (lowrank_tree_noise,
                                             lowrank_tree_perturb,
                                             make_lowrank_tree_spec)

        p = self._params()
        spec = make_lowrank_tree_spec(p, 2)
        vec = jax.random.normal(jax.random.key(1), (spec.noise_dim,))
        noise = lowrank_tree_noise(spec, vec)
        pert = lowrank_tree_perturb(spec, p, vec, 0.3)
        jax.tree_util.tree_map(
            lambda w, e, t: np.testing.assert_allclose(
                np.asarray(w + 0.3 * e), np.asarray(t), rtol=1e-6
            ),
            p, noise, pert,
        )
        # factored kernel really is rank-2
        assert np.linalg.matrix_rank(np.asarray(noise["cell"]["gate"]["kernel"]),
                                     tol=1e-5) <= 2

    def test_weighted_sum_matches_dense_oracle(self):
        from estorch_tpu.ops.lowrank import (lowrank_tree_noise,
                                             lowrank_tree_weighted_sum,
                                             make_lowrank_tree_spec)

        p = self._params()
        spec = make_lowrank_tree_spec(p, 1)
        k = 5
        mat = jax.random.normal(jax.random.key(2), (k, spec.noise_dim))
        w = jax.random.normal(jax.random.key(3), (k,))
        got = lowrank_tree_weighted_sum(spec, mat, w)
        want = None
        for i in range(k):
            dense = lowrank_tree_noise(spec, mat[i])
            scaled = jax.tree_util.tree_map(lambda e: w[i] * e, dense)
            want = scaled if want is None else jax.tree_util.tree_map(
                jnp.add, want, scaled
            )
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-5
            ),
            got, want,
        )
