"""TokenScoreEnv (envs/sequence.py): an episode is one forward over a
token sequence, recognised by make_rollout as a whole-episode env."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import lm_tiny
from estorch_tpu import ES, JaxAgent
from estorch_tpu.envs import TokenScoreEnv, make_rollout
from estorch_tpu.models import HybridLM


@pytest.fixture(scope="module")
def env():
    return TokenScoreEnv(**lm_tiny.ENV)


def test_the_key_picks_a_sequence_of_the_seeded_corpus(env):
    corpus = np.asarray(env.corpus())
    assert corpus.shape == (4, 21) and corpus.dtype == np.int32
    assert corpus.min() >= 0 and corpus.max() < 64
    np.testing.assert_array_equal(corpus, np.asarray(
        TokenScoreEnv(**lm_tiny.ENV).corpus()))
    assert not np.array_equal(corpus, np.asarray(
        TokenScoreEnv(**{**lm_tiny.ENV, "seed": 1}).corpus()))
    rows = set()
    for i in range(16):
        row, tokens = env.reset(jax.random.PRNGKey(i))
        np.testing.assert_array_equal(tokens, corpus[int(row)])
        rows.add(int(row))
    assert len(rows) > 1
    # common random numbers: one key, one sequence (both signs of a pair)
    a, b = env.reset(jax.random.PRNGKey(3)), env.reset(jax.random.PRNGKey(3))
    np.testing.assert_array_equal(a[1], b[1])


def test_score_is_mean_log_likelihood_and_probe_logits(env):
    logp = -jnp.arange(20, dtype=jnp.float32)
    last = jnp.arange(64, dtype=jnp.float32)
    fitness, bc, steps = env.score(None, None, (logp, last))
    assert float(fitness) == pytest.approx(-9.5)
    np.testing.assert_array_equal(bc, np.arange(32) * 2.0)
    assert int(steps) == 21 == env.default_horizon
    assert env.bc_dim == 32 and env.whole_episode


def test_make_rollout_calls_the_policy_once_over_the_sequence(env):
    calls = []

    def policy(params, tokens):
        calls.append(tokens.shape)
        return (jnp.full((20,), params), jnp.zeros((64,)) + params)

    rollout = make_rollout(env, policy, horizon=21)
    res = rollout(jnp.float32(-2.0), jax.random.PRNGKey(0))
    assert calls == [(21,)]
    assert float(res.total_reward) == -2.0 and int(res.steps) == 21
    assert res.bc.shape == (32,)
    with pytest.raises(ValueError, match="carry"):
        make_rollout(env, policy, 21, carry_init=lambda: 0.0)
    with pytest.raises(NotImplementedError):
        env.step(None, None)


@pytest.mark.parametrize("low_rank", [0, 1])
def test_es_trains_a_language_model_on_the_replicated_engine(env, low_rank):
    """The normal path on one device: full-rank noise takes the
    materialised forward, low_rank the perturbed one
    (``HybridLM.perturbed_apply``); a record's env_steps are the tokens
    passed through the model."""
    es = ES(policy=HybridLM, agent=JaxAgent, optimizer=optax.adam,
            population_size=8, sigma=0.02, policy_kwargs=lm_tiny.TINY,
            agent_kwargs={"env": env}, low_rank=low_rank,
            optimizer_kwargs={"learning_rate": 1e-2}, table_size=1 << 18,
            device=jax.devices()[0])
    assert es.engine.forward_form == ("low_rank" if low_rank
                                      else "materialised")
    before = np.asarray(es.state.params_flat).copy()
    es.train(2, verbose=False)
    assert [r["env_steps"] for r in es.history] == [8 * 21] * 2
    assert all(np.isfinite(r["reward_mean"]) for r in es.history)
    assert -4.3 < es.history[0]["reward_mean"] < -4.0   # about -log(64)
    after = np.asarray(es.state.params_flat)
    assert np.isfinite(after).all() and np.abs(after - before).max() > 0
    assert es.obs.counters.get("tokens_per_generation") == 8 * 21
