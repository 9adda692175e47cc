"""Decomposed population forward: z = x@W + c(x@E) must be EXACTLY the
materialized-weights path (it is a reordering, not an approximation),
across feature combinations — in the form the engine runs it: pair-shared
(mirrored runs, chosen by the engine).  Which form a run takes is the
engine's rule (``ESEngine.forward_form``), pinned here facet by facet."""

import re

import numpy as np
import optax
import pytest

import flax.linen as nn
import jax
from conftest import materialised

from estorch_tpu import ES, NS_ES, JaxAgent, MLPPolicy, RecurrentPolicy
from estorch_tpu.envs import CartPole, Pendulum


def _pair(**over):
    kw = dict(
        policy=MLPPolicy,
        agent=JaxAgent,
        optimizer=optax.adam,
        population_size=32,
        sigma=0.1,
        seed=0,
        policy_kwargs={"action_dim": 2, "hidden": (16,)},
        agent_kwargs={"env": CartPole(), "horizon": 60},
        optimizer_kwargs={"learning_rate": 2e-2},
        table_size=1 << 16,
    )
    kw.update(over)
    return ES(**kw)


class TwoLayer(nn.Module):
    """A policy that is not an MLPPolicy: no decomposed form to offer."""

    action_dim: int

    @nn.compact
    def __call__(self, obs):
        return nn.Dense(self.action_dim)(nn.tanh(nn.Dense(8)(obs)))


def _assert_equivalent(a, b, gens=3, exact=True, params_atol=1e-3):
    """``exact`` asserts tight float tolerance (the decomposition reorders
    IEEE sums, so bitwise equality would be flaky by construction — observed
    bit-identical today, but a near-tie argmax flip under a last-ulp logit
    difference is allowed to move one fitness value).  ``params_atol``
    loosens only the non-exact params check: in bf16 a rounding-induced
    argmax flip changes one member's whole fitness, which moves that
    member's rank weight and compounds through the update — the
    trajectories stay close (reward assert), not identical."""
    a.train(gens, verbose=False)
    b.train(gens, verbose=False)
    for ra, rb in zip(a.history, b.history):
        tol = 1e-6 if exact else 5e-2
        assert ra["reward_mean"] == pytest.approx(rb["reward_mean"], rel=tol, abs=1.0)
    pa = np.asarray(a.state.params_flat)
    pb = np.asarray(b.state.params_flat)
    if exact:
        np.testing.assert_allclose(pa, pb, rtol=1e-4, atol=1e-5)
    else:
        np.testing.assert_allclose(pa, pb, rtol=1e-3, atol=params_atol)


CONTINUOUS = dict(
    policy_kwargs={"action_dim": 1, "hidden": (16,), "discrete": False,
                   "action_scale": 2.0},
    agent_kwargs={"env": Pendulum(), "horizon": 40},
)

# case → (ES keywords, exact?, params_atol)
PAIR_CASES = {
    "f32": ({}, True, None),
    # bf16 admits a near-tie argmax flip between the two orderings
    "bf16": ({"compute_dtype": "bfloat16"}, False, 0.1),
    "obs_norm": ({"obs_norm": True}, True, None),
    # 32 members over the suite's 8 devices: 4 a device, 2 chunks of one pair
    "chunks": ({"eval_chunk": 2}, True, None),
    # continuous rewards accumulate transcendental terms: rounding shows
    "episodes": ({"episodes_per_member": 2, **CONTINUOUS}, False, 1e-3),
}


class TestPairSharedEquivalence:
    """The engine's choice for a mirrored MLP against the materialized
    path it replaces."""

    @pytest.mark.parametrize("case", sorted(PAIR_CASES))
    def test_matches_materialised(self, case):
        over, exact, atol = PAIR_CASES[case]
        pair = _pair(**over)
        assert pair.engine.forward_form == "pair_shared"
        if case == "chunks":
            assert pair.engine.members_local // pair.engine.eval_chunk >= 2
        _assert_equivalent(materialised(_pair(**over)), pair,
                           exact=exact, **({} if atol is None
                                           else {"params_atol": atol}))

    def test_matches_materialised_ns_es(self):
        def make():
            kw = dict(
                policy=MLPPolicy, agent=JaxAgent, optimizer=optax.adam,
                population_size=16, sigma=0.1, seed=7,
                policy_kwargs={"action_dim": 2, "hidden": (8,)},
                agent_kwargs={"env": CartPole(), "horizon": 50},
                optimizer_kwargs={"learning_rate": 1e-2},
                table_size=1 << 15, meta_population_size=2, k=3)
            return NS_ES(**kw)

        a, b = materialised(make()), make()
        assert b.engine.forward_form == "pair_shared"
        a.train(3, verbose=False)
        b.train(3, verbose=False)
        for ra, rb in zip(a.history, b.history):
            assert ra["reward_mean"] == pytest.approx(rb["reward_mean"],
                                                      rel=1e-6)
        for sa, sb in zip(a.meta_states, b.meta_states):
            np.testing.assert_allclose(np.asarray(sa.params_flat),
                                       np.asarray(sb.params_flat),
                                       rtol=1e-4, atol=1e-5)


ARRAY = re.compile(r"\b[a-z]+[0-9]+\[([0-9,]+)\]")


class TestPairSharedStructure:
    def test_program_holds_noise_per_pair_not_per_member(self):
        """CPU compile at a small population: ε is gathered and unraveled
        once per PAIR, and the noise dot is pair-batched ``[pairs, 2, ·]``.
        Sizes chosen so no other array can be mistaken for the noise: 24
        members, 12 pairs, dim 4·20+20+20·3+3 = 163."""
        members, pairs, hidden = 24, 12, 20
        es = _pair(population_size=members, device=jax.devices()[:1],
                   policy_kwargs={"action_dim": 3, "hidden": (hidden,)},
                   agent_kwargs={"env": CartPole(), "horizon": 5})
        dim = es._spec.dim
        assert dim == 163 and es.engine.noise_rows_per_generation == pairs
        text = es.engine._generation_step.lower(es.state).compile().as_text()
        shapes = {tuple(int(d) for d in m.split(","))
                  for m in ARRAY.findall(text)}
        assert (pairs, dim) in shapes, "no [pairs, dim] noise slab"
        assert (members, dim) not in shapes, "a [members, dim] array exists"
        assert (pairs, 4, hidden) in shapes, "ε kernels are not per pair"
        assert not {s for s in shapes if s[0] == members and len(s) == 3
                    and s[1:] in ((4, hidden), (hidden, 3))}, (
            "a per-member weight or noise tensor exists")
        dots = [ln for ln in text.splitlines()
                if re.search(r" = \S+ dot\(", ln)]
        assert any(re.search(rf"\[{pairs},2,{hidden}\]\S* dot\(", ln)
                   for ln in dots), "no pair-batched [pairs, 2, ·] noise dot"

    @pytest.mark.parametrize("name,over,form", [
        ("mirrored_mlp", {}, "pair_shared"),
        # what the forward computes in, how often and on what input does
        # not enter the rule
        ("bf16", {"compute_dtype": "bfloat16"}, "pair_shared"),
        ("episodes2", {"episodes_per_member": 2}, "pair_shared"),
        ("obs_norm", {"obs_norm": True}, "pair_shared"),
        ("unmirrored", {"mirrored": False}, "materialised"),
        ("recurrent", {"policy": RecurrentPolicy,
                       "policy_kwargs": {"action_dim": 2, "hidden": (8,),
                                         "gru_size": 8}}, "materialised"),
        ("vbn", {"policy_kwargs": {"action_dim": 2, "hidden": (16,),
                                   "use_vbn": True}}, "materialised"),
        ("not_an_mlp_policy", {"policy": TwoLayer,
                               "policy_kwargs": {"action_dim": 2}},
         "materialised"),
        ("low_rank", {"low_rank": 1}, "low_rank"),
        # 6 members a device (8 devices) in chunks of 3: a pair must not
        # straddle two chunks
        ("odd_chunk", {"population_size": 48, "eval_chunk": 3},
         "materialised"),
    ])
    def test_selection_rule(self, name, over, form):
        es = _pair(agent_kwargs={"env": CartPole(), "horizon": 5}, **over)
        assert es.engine.forward_form == form
        rows = es.population_size // 2 if form == "pair_shared" \
            else es.population_size
        assert es.engine.noise_rows_per_generation == rows
        cfg = es.run_manifest()["config"]
        assert cfg["forward_form"] == form
        assert cfg["noise_rows_per_generation"] == rows
        gauges = es.obs.counters.snapshot()
        assert gauges["forward_form"] == form
        assert gauges["noise_rows_per_generation"] == rows
        if name == "odd_chunk":
            assert es.engine.eval_chunk == 3
            es.train(1, verbose=False)  # the form it resolves to still runs
