"""Cross-backend × algorithm integration matrix.

Every algorithm on every backend, two generations each — the seams where
integration breaks hide (novelty-on-pooled, NSRA-on-host, gym-pool
variants). Asserts the contract every combination must honor: records
complete, fitness finite, state advances, novelty bookkeeping consistent.
"""

import numpy as np
import optax
import pytest
import torch

from estorch_tpu import ES, NS_ES, NSR_ES, NSRA_ES, JaxAgent, MLPPolicy, PooledAgent
from estorch_tpu.envs import CartPole

ALGOS = {
    "ES": (ES, {}),
    "NS_ES": (NS_ES, {"meta_population_size": 2, "k": 3}),
    "NSR_ES": (NSR_ES, {"meta_population_size": 2, "k": 3}),
    "NSRA_ES": (NSRA_ES, {"meta_population_size": 2, "k": 3, "weight": 0.7}),
}


class _TorchMLP(torch.nn.Module):
    def __init__(self):
        super().__init__()
        self.net = torch.nn.Sequential(
            torch.nn.Linear(4, 8), torch.nn.Tanh(), torch.nn.Linear(8, 2)
        )

    def forward(self, x):
        return self.net(x)


class _QuadAgent:
    def rollout(self, policy):
        with torch.no_grad():
            v = torch.nn.utils.parameters_to_vector(policy.parameters())
            r = -float(((v - 0.1) ** 2).sum())
        self.last_episode_steps = 1
        return r, v[:2].numpy()


BACKENDS = {
    "device": dict(
        policy=MLPPolicy,
        agent=JaxAgent,
        optimizer=optax.adam,
        policy_kwargs={"action_dim": 2, "hidden": (8,)},
        agent_kwargs={"env": CartPole(), "horizon": 30},
        optimizer_kwargs={"learning_rate": 1e-2},
    ),
    "pooled-native": dict(
        policy=MLPPolicy,
        agent=PooledAgent,
        optimizer=optax.adam,
        policy_kwargs={"action_dim": 2, "hidden": (8,)},
        agent_kwargs={"env_name": "cartpole", "horizon": 30},
        optimizer_kwargs={"learning_rate": 1e-2},
    ),
    "pooled-gym": dict(
        policy=MLPPolicy,
        agent=PooledAgent,
        optimizer=optax.adam,
        policy_kwargs={"action_dim": 2, "hidden": (8,)},
        agent_kwargs={"env_name": "gym:CartPole-v1", "horizon": 30},
        optimizer_kwargs={"learning_rate": 1e-2},
    ),
    "host": dict(
        policy=_TorchMLP,
        agent=_QuadAgent,
        optimizer=torch.optim.Adam,
        optimizer_kwargs={"lr": 1e-2},
    ),
}


@pytest.mark.parametrize("backend", sorted(BACKENDS))
@pytest.mark.parametrize("algo", sorted(ALGOS))
def test_algo_backend_combination(backend, algo):
    cls, extra = ALGOS[algo]
    kw = dict(BACKENDS[backend])
    kw.update(extra)
    es = cls(population_size=16, sigma=0.05, seed=0, table_size=1 << 14, **kw)
    es.train(2, verbose=False)

    assert len(es.history) == 2
    for rec in es.history:
        assert np.isfinite(rec["reward_mean"])
        assert np.isfinite(rec["grad_norm"])
    assert es.generation == 2
    if algo != "ES":
        # archive: meta seeds + one BC per generation; meta states intact
        assert len(es.archive) == 2 + 2
        assert len(es.meta_states) == 2
        assert "novelty_mean" in es.history[-1]
    if algo == "NSRA_ES":
        assert 0.0 <= es.history[-1]["nsra_weight"] <= 1.0
    if backend.startswith("pooled"):
        es.engine.pool.close()
        es.engine.center_pool.close()


@pytest.mark.parametrize("backend", sorted(BACKENDS))
def test_unmirrored_backends(backend):
    """mirrored=False (the reference's plain per-member sampling) must run
    on every backend — round-1 VERDICT next-round #7."""
    kw = dict(BACKENDS[backend])
    es = ES(population_size=16, sigma=0.05, seed=0, table_size=1 << 14,
            mirrored=False, **kw)
    es.train(2, verbose=False)
    assert len(es.history) == 2
    for rec in es.history:
        assert np.isfinite(rec["reward_mean"])
        assert np.isfinite(rec["grad_norm"])
    if backend.startswith("pooled"):
        es.engine.pool.close()
        es.engine.center_pool.close()


@pytest.mark.parametrize("backend", sorted(BACKENDS))
def test_sigma_decay_backends(backend):
    """sigma_decay anneals identically on every backend."""
    kw = dict(BACKENDS[backend])
    es = ES(population_size=16, sigma=0.05, seed=0, table_size=1 << 14,
            sigma_decay=0.5, sigma_min=0.02, **kw)
    es.train(2, verbose=False)
    assert es.history[0]["sigma"] == pytest.approx(0.05)
    assert es.history[1]["sigma"] == pytest.approx(0.025)
    sig = float(np.asarray(es.state.sigma))
    assert sig == pytest.approx(0.02)  # floored
    if backend.startswith("pooled"):
        es.engine.pool.close()
        es.engine.center_pool.close()


@pytest.mark.parametrize("backend", ["device", "pooled-native"])
def test_bf16_compute_dtype_backends(backend):
    """bf16 responsibility is split between engine.py (obs/output shim) and
    the param-cast at each builder (engine._member_cast / pooled
    materialize) — lock in that both halves stay wired on both backends."""
    kw = dict(BACKENDS[backend])
    es = ES(population_size=16, sigma=0.05, seed=0, table_size=1 << 14,
            compute_dtype="bfloat16", **kw)
    es.train(2, verbose=False)
    assert len(es.history) == 2
    for rec in es.history:
        assert np.isfinite(rec["reward_mean"])
    assert str(es.state.params_flat.dtype) == "float32"  # master stays f32
    if backend.startswith("pooled"):
        es.engine.pool.close()
        es.engine.center_pool.close()


def test_iwes_in_algo_matrix_on_device():
    """IW_ES honors the same record/state contract as the other algorithms
    on its (only) backend."""
    from estorch_tpu import IW_ES

    kw = dict(BACKENDS["device"])
    es = IW_ES(population_size=16, sigma=0.05, seed=0, table_size=1 << 14, **kw)
    es.train(2, verbose=False)
    assert len(es.history) == 2
    for rec in es.history:
        assert np.isfinite(rec["reward_mean"])
        assert np.isfinite(rec["grad_norm"])
        assert "reused_prev" in rec and "ess" in rec
    assert es.generation == 2


@pytest.mark.parametrize("mode", ["pair_shared", "materialised", "low_rank"])
def test_engine_modes_run_all_algorithms(mode):
    """Every forward form of the replicated engine composes with the
    novelty family (they all sit behind _eval_local), not just vanilla ES."""
    over = {"pair_shared": dict(),
            "materialised": dict(mirrored=False),
            "low_rank": dict(low_rank=1)}[mode]
    from estorch_tpu import NSR_ES

    kw = dict(BACKENDS["device"])
    es = NSR_ES(population_size=16, sigma=0.05, seed=0, table_size=1 << 14,
                meta_population_size=2, k=3, **kw, **over)
    assert es.engine.forward_form == mode
    es.train(2, verbose=False)
    assert len(es.history) == 2
    assert np.isfinite(es.history[-1]["reward_mean"])


@pytest.mark.parametrize("mode", ["obs_norm", "recurrent"])
def test_round3_modes_run_novelty_family(mode):
    """obs_norm and recurrent policies compose with the novelty family's
    split path (stats refresh / carry threading live below _eval_local and
    apply_weights, which NS/NSR/NSRA share with vanilla ES)."""
    from estorch_tpu import NSR_ES, RecurrentPolicy

    kw = dict(BACKENDS["device"])
    over = {}
    if mode == "obs_norm":
        over["obs_norm"] = True
    else:
        kw["policy"] = RecurrentPolicy
        kw["policy_kwargs"] = {"action_dim": 2, "hidden": (8,),
                               "gru_size": 8}
    es = NSR_ES(population_size=16, sigma=0.05, seed=0, table_size=1 << 14,
                meta_population_size=2, k=3, **kw, **over)
    es.train(2, verbose=False)
    assert len(es.history) == 2
    assert np.isfinite(es.history[-1]["reward_mean"])
    if mode == "obs_norm":
        for st in es.meta_states:
            assert st.obs_stats is not None


def test_iwes_rejects_obs_norm():
    """Buffered generations' fitness was measured under older running
    stats — the density ratio's fixed-f(θ) assumption breaks, so the
    combination must fail loudly, not bias silently."""
    from estorch_tpu import IW_ES

    kw = dict(BACKENDS["device"])
    with pytest.raises(ValueError, match="obs_norm"):
        IW_ES(population_size=16, sigma=0.05, seed=0, table_size=1 << 14,
              obs_norm=True, **kw)


def test_iwes_recurrent_composes():
    """IW_ES's density-ratio reuse involves only params/noise/fitness —
    forward-shape agnostic, so the recurrent standard forward composes."""
    from estorch_tpu import IW_ES, RecurrentPolicy

    kw = dict(BACKENDS["device"])
    kw["policy"] = RecurrentPolicy
    kw["policy_kwargs"] = {"action_dim": 2, "hidden": (8,), "gru_size": 8}
    es = IW_ES(population_size=16, sigma=0.05, seed=0, table_size=1 << 14,
               **kw)
    es.train(2, verbose=False)
    assert np.isfinite(es.history[-1]["reward_mean"])
    assert "reused_prev" in es.history[-1]


def test_recurrent_lowrank_runs_novelty_family():
    """Round-5 composition: factored noise over the recurrent tree
    (per-episode materialization) lives below _eval_local/_local_grad,
    which the novelty family shares with vanilla ES."""
    from estorch_tpu import NSR_ES, RecurrentPolicy

    kw = dict(BACKENDS["device"])
    kw["policy"] = RecurrentPolicy
    kw["policy_kwargs"] = {"action_dim": 2, "hidden": (8,), "gru_size": 8}
    es = NSR_ES(population_size=16, sigma=0.05, seed=0, table_size=1 << 14,
                meta_population_size=2, k=3, low_rank=1, **kw)
    es.train(2, verbose=False)
    assert np.isfinite(es.history[-1]["reward_mean"])


def test_iwes_rejects_low_rank_as_ill_posed():
    """IW reuse under low_rank is not pending work — the drifted reused
    perturbation generally has no rank-r preimage, so no factor-space
    importance ratio exists; the combination must fail loudly."""
    from estorch_tpu import IW_ES

    kw = dict(BACKENDS["device"])
    with pytest.raises(ValueError, match="ill-posed"):
        IW_ES(population_size=16, sigma=0.05, seed=0, table_size=1 << 14,
              low_rank=1, **kw)
