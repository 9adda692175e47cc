"""The contract between a policy and the engine that runs it (PR 43).

A model states what it is ONCE (``models/perturbed.py::PolicyDeclaration``,
its ``declaration()``), an engine reports what it resolved at build ONCE
(``build_facts()``), and ``ES`` carries both to the gauges and to
``run_manifest()["config"]`` without naming a field of either.  Moving the
seam moved nothing: the literals of ``policy_contract_parent.py`` were read
at the parent commit of PR 43, those of ``policy_seam_parent.py`` at the
parent of PR 56, when the partition rules and the kernels' rules left
``parallel/`` for the declaration.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import optax
import pytest

import cca_moe_tiny
import delta_moe_tiny
import gated_window_moe_tiny
import indexed_moe_tiny
import lm_tiny
import loop_tiny
import moe_tiny
import sambay_tiny
import window_moe_tiny
from policy_contract_parent import PARENT
from policy_seam_parent import BUILDS

from estorch_tpu import ES, JaxAgent, MLPPolicy
from estorch_tpu.envs import CartPole, TokenScoreEnv
from estorch_tpu.models import (CCAMoELM, DeltaMoELM, GatedWindowMoELM,
                                HybridLM, IndexedMoELM, LoopedLM, MoELM,
                                SambaYLM, WindowMoELM)
from estorch_tpu.models import (cca_moe_lm, delta_moe_lm, gated_window_moe_lm,
                                hybrid_lm, indexed_moe_lm, looped_lm, moe_lm,
                                sambay_lm, window_moe_lm)
from estorch_tpu.models.perturbed import PolicyDeclaration, declaration_of
from estorch_tpu.ops import kernel_facts
from estorch_tpu.ops.pallas_attention import attention_facts
from estorch_tpu.ops.pallas_combine import combine_facts
from estorch_tpu.ops.pallas_delta import delta_facts
from estorch_tpu.ops.pallas_head import head_facts
from estorch_tpu.ops.pallas_scan import scan_facts
from estorch_tpu.parallel.engine import MANIFEST_BUILD_FACTS
from estorch_tpu.parallel.mesh import (DEFAULT_PARTITION_RULES,
                                       hyperscale_mesh,
                                       partition_rules_to_json)
from estorch_tpu.parallel.sharded import OUTPUT_REDUCTIONS, ShardedESEngine

# name -> (model, its tiny sizes, devices of the mesh, width of ``model``)
SEQUENCE_MODELS = {
    "hybrid": (HybridLM, lm_tiny, 4, 2),
    "looped": (LoopedLM, loop_tiny, 1, 1),
    "moe": (MoELM, moe_tiny, 1, 1),
    "sambay": (SambaYLM, sambay_tiny, 1, 1),
    "indexed_moe": (IndexedMoELM, indexed_moe_tiny, 1, 1),
    # added after the seam moved: no literal of the parent's to hold it to
    "cca_moe": (CCAMoELM, cca_moe_tiny, 1, 1),
    "window_moe": (WindowMoELM, window_moe_tiny, 1, 1),
    "delta_moe": (DeltaMoELM, delta_moe_tiny, 1, 1),
    "gated_window_moe": (GatedWindowMoELM, gated_window_moe_tiny, 1, 1),
}


@functools.lru_cache(maxsize=None)
def build(name):
    """One of the eleven builds the parents' literals were read from (built
    once: the tests read it and step nothing)."""
    devices = jax.devices()
    if name in SEQUENCE_MODELS:
        policy, tiny, n_devices, shards = SEQUENCE_MODELS[name]
        return ES(policy=policy, agent=JaxAgent, optimizer=optax.adam,
                  population_size=8, sigma=0.02, policy_kwargs=tiny.TINY,
                  agent_kwargs={"env": TokenScoreEnv(**tiny.ENV)},
                  optimizer_kwargs={"learning_rate": 1e-2},
                  shard_params=True, model_shards=shards, low_rank=1,
                  noise_mode="table", table_size=1 << 18,
                  compute_dtype="bfloat16", device=devices[:n_devices])
    sharded = {"mlp_sharded": dict(shard_params=True, model_shards=2),
               "mlp_replicated": {}}[name]
    return ES(policy=MLPPolicy, agent=JaxAgent, optimizer=optax.adam,
              population_size=8, sigma=0.1,
              policy_kwargs={"action_dim": 2, "hidden": (16, 16)},
              agent_kwargs={"env": CartPole(), "horizon": 20},
              optimizer_kwargs={"learning_rate": 1e-2},
              device=devices[:4], **sharded)


def sized(engine) -> dict:
    """What the engine sized from the policy's statements at build."""
    out = {k: getattr(engine, k) for k in (
        "pair_chunk", "eval_chunk", "n_eval_chunks", "signs_in_turn")
        if hasattr(engine, k)}
    if isinstance(engine, ShardedESEngine):
        out.update(
            float32_leaves_kept=sum(
                d == jnp.float32 for d in engine._leaf_dtypes),
            factored_leaves=len(engine._factored),
            selection_bytes=engine._selection_bytes)
    return out


@pytest.mark.parametrize("name", sorted(PARENT))
def test_manifest_gauges_and_sizes_are_the_parents(name, devices8):
    """(i) Every key and value of ``run_manifest()["config"]``, every gauge
    and every chunk size, for the five sequence models through the sharded
    engine's perturbed form and the MLP through both device engines."""
    es = build(name)
    want = PARENT[name]
    config = es.run_manifest()["config"]
    rules = config.pop("partition_rules", None)
    # stated since PR 46, when the head's rule left the attention's: on
    # these CPU meshes no kernel may be traced, which is every head's reason
    assert set(MANIFEST_BUILD_FACTS) <= set(config)
    assert config.pop("head_form_why") == (
        None if config["head_form"] is None
        else "the devices are 'cpu', not TPUs")
    # stated since PR 51, when the expert layer's combine got its two
    # forms: the scatter-add on these CPU meshes, nothing without experts
    kernels = dict(declaration_of(es.module).kernels)
    combine = "xla" if combine_facts in kernels else None
    assert config.pop("combine_form") == combine
    # stated since PR 53, when the gated delta rule got its two forms: the
    # XLA form on these CPU meshes, nothing without a linear layer
    delta = "xla" if delta_facts in kernels else None
    assert config.pop("delta_form") == delta
    # stated since PR 57, when a step of the causal kernel learned to hold
    # several heads: no kind of layer is in the kernel on these CPU meshes
    assert config.pop("attention_heads_a_step") is None
    assert sorted(config) == sorted(want["config"])
    assert config == want["config"]
    # since PR 56: the model's own rules, then the general four
    assert rules == (partition_rules_to_json(
        declaration_of(es.module).partition_rules + DEFAULT_PARTITION_RULES)
        if es._shard_params else None)
    gauges = es.obs.counters.snapshot()
    assert gauges.pop("combine_form", None) == combine
    assert gauges.pop("delta_form", None) == delta
    assert sorted(gauges) == sorted(want["gauges"])
    assert gauges == want["gauges"]
    assert sized(es.engine) == want["sized"]


@pytest.mark.parametrize("name", sorted(BUILDS))
def test_the_declarations_rules_and_kernels_moved_nothing(name, devices8):
    """(i, PR 56) EVERY key and value of ``run_manifest()["config"]``
    (``partition_rules`` apart, which now lists the model's own rules ahead
    of the general four), every gauge and every chunk size of the nine
    sequence models' builds and the MLP's two are what they were when
    ``parallel/mesh.py`` held every model's rules in one list and
    ``parallel/sharded.py`` evaluated every kernel's rule itself."""
    es = build(name)
    want = BUILDS[name]
    config = es.run_manifest()["config"]
    rules = config.pop("partition_rules", None)
    assert set(MANIFEST_BUILD_FACTS) <= set(config)
    # stated since PR 57 (the heads a grid step of the causal kernel holds,
    # by layer kind): nothing where no kind is in the kernel, and no gauge
    assert config.pop("attention_heads_a_step") is None
    assert config == want["config"]
    assert es.obs.counters.snapshot() == want["gauges"]
    assert sized(es.engine) == want["sized"]
    assert (rules is not None) == es._shard_params
    # a kernel rule's reason is a sentence: in the manifest, never a gauge
    assert not set(want["gauges"]) & kernel_facts.SENTENCES


# ------------------------------------------------- a model declares itself

# what each model states at its tiny sizes: the kernels it calls with the
# widths it calls them with (``(rule, widths)``; the attention's widths are
# ``(head widths, key heads, ((layer kind, band), ...), query heads)``: one
# count, or one a kind), and the fields the engine's chunk rule and the
# records read
STATED = {
    "hybrid": dict(
        partition_rules=hybrid_lm.PARTITION_RULES,
        kernels=((attention_facts, (8, 2, None, 4)), (head_facts, (32,)))),
    "looped": dict(
        partition_rules=looped_lm.PARTITION_RULES,
        kernels=((attention_facts, (8, 2, None, 4)), (head_facts, (32,))),
        leaf_rows={"head/kernel": 8},
        facts={"loop_steps": 4, "layer_applications_per_token": 8}),
    "moe": dict(
        partition_rules=moe_lm.PARTITION_RULES,
        kernels=((attention_facts, ((8, 4, 6), None, None, 4)),
                 (head_facts, (32,)),
                 (combine_facts, (32,))),
        leaf_rows={"head/kernel": 8}, outputs=("expert_load",),
        facts={"experts_held": 4, "experts_total": 16,
               "experts_per_token": 3, "mtp_depth": 1}),
    "sambay": dict(
        partition_rules=sambay_lm.PARTITION_RULES,
        kernels=((attention_facts, (
            (4, 0, 8), 4,
            (("window", 5), ("full_kv", None), ("cross", None)), 8)),
            (head_facts, (32,)), (scan_facts, (64, 4))),
        facts={"layer_kinds": "mamba,window,mamba_mem,full_kv,gmu,cross",
               "window": 5, "scan_chunk": 4, "kv_shared_by": 1,
               "memory_shared_by": 1}),
    "indexed_moe": dict(
        partition_rules=indexed_moe_lm.PARTITION_RULES,
        kernels=((attention_facts, (8, 2, (("selected", None),), 4)),
                 (head_facts, (32,)), (combine_facts, (32,))),
        leaf_rows={"head/kernel": 8},
        outputs=("expert_load", "selected_pairs"),
        facts={"experts_held": 4, "experts_total": 16,
               "experts_per_token": 3, "mtp_depth": 0, "sparse_topk": 6,
               "index_heads": 2, "index_head_dim": 8,
               "position_streams": 3}),
    # ONE expert a token over two shares reaches the EXPERTS' stacked
    # leaves; the head-mixing convolution's stack sees every position
    "cca_moe": dict(
        partition_rules=cca_moe_lm.PARTITION_RULES,
        kernels=((attention_facts, (8, 2, None, 8)), (head_facts, (32,)),
                 (combine_facts, (32,))),
        outputs=("expert_load",),
        leaf_rows_per_token=lambda lm: dict.fromkeys(lm.expert_leaves,
                                                     1.25 / 2),
        facts={"experts_held": 2, "experts_total": 4,
               "experts_per_token": 1, "mtp_depth": 0, "latent_q_width": 64,
               "latent_kv_width": 16, "conv_taps": 4, "router_hidden": 16}),
    # two kinds of attention layer, each with its band; the routes are
    # taken ahead of attention, which the declaration need not say
    "window_moe": dict(
        partition_rules=window_moe_lm.PARTITION_RULES,
        kernels=((attention_facts, (8, 2, (("window", 6), ("global", None)),
                                    6)),
                 (head_facts, (32,)), (combine_facts, (32,))),
        leaf_rows={"head/kernel": 8}, outputs=("expert_load",),
        facts={"experts_held": 4, "experts_total": 16,
               "experts_per_token": 3, "mtp_depth": 0, "sliding_window": 6,
               "window_layers": 2, "global_layers": 1}),
    # two kinds of MIXER and one kind of attention layer: no band to state;
    # the delta rule's chunk and the form its inverse takes are facts
    "delta_moe": dict(
        partition_rules=delta_moe_lm.PARTITION_RULES,
        kernels=((attention_facts, (16, 2, None, 4)), (head_facts, (32,)),
                 (combine_facts, (32,)), (delta_facts, (8, 8, 8))),
        leaf_rows={"head/kernel": 8}, outputs=("expert_load",),
        facts={"experts_held": 4, "experts_total": 16,
               "experts_per_token": 3, "mtp_depth": 0, "linear_layers": 3,
               "full_layers": 1, "delta_chunk": 8,
               "delta_inverse": "blocks of 8 by the finite product "
                                "(I - A)(I + A^2)(I + A^4)..., merged in "
                                "pairs"}),
    # two kinds of attention layer that differ in their HEAD COUNT too: the
    # attention's rule reads the key heads and the widths, which the kinds
    # share; each kind's heads are facts
    "gated_window_moe": dict(
        partition_rules=gated_window_moe_lm.PARTITION_RULES,
        kernels=((attention_facts, (8, 2, (("sliding", 6), ("full", None)),
                                    (6, 4))),
                 (head_facts, (32,)), (combine_facts, (32,))),
        leaf_rows={"head/kernel": 8}, outputs=("expert_load",),
        facts={"experts_held": 4, "experts_total": 16,
               "experts_per_token": 3, "mtp_depth": 0, "sliding_window": 6,
               "dense_layers": 1, "sliding_layers": 2, "full_layers": 2,
               "sliding_heads": 6, "full_heads": 4}),
}


@pytest.mark.parametrize("name", sorted(SEQUENCE_MODELS))
def test_a_model_declares_itself_once(name):
    policy, tiny, _, _ = SEQUENCE_MODELS[name]
    lm = policy(**tiny.TINY)
    stated = declaration_of(lm)
    assert stated == lm.declaration()
    # the leaves it names are the model's own lists (the benchmark's
    # tolerance scripts read the same properties)
    leaves = {field: tuple(getattr(lm, field, ())) for field in (
        "stacked_leaves", "dense_noise_leaves", "float32_leaves")}
    fields = dict(STATED[name])
    per_token = fields.pop("leaf_rows_per_token", lambda lm: dict.fromkeys(
        leaves["stacked_leaves"], 3 * 1.25 / 4))(lm)
    selection = getattr(lm, "selection_bytes", None)
    assert stated == PolicyDeclaration(
        **leaves, leaf_rows_per_token=per_token, selection_bytes=selection,
        **fields)
    assert set(stated.outputs) <= set(OUTPUT_REDUCTIONS)
    if selection is not None:
        assert stated.selection_bytes(21) == 21 * 21 + 4 * 2 * 8 * 21
    # every rule it names is one ops/kernel_facts.py collects the names of
    scope = kernel_facts.BuildScope("cpu", 1, None, (False, "a CPU"), 21, 2)
    assert set(kernel_facts.resolve(scope, stated.kernels)) <= set(
        kernel_facts.FACT_NAMES)


def test_a_module_that_states_nothing_gets_the_defaults(devices8):
    """(ii) The MLP declares nothing: the default declaration, and an
    engine that resolves no form of a sequence model."""
    assert declaration_of(MLPPolicy(action_dim=2, hidden=(16, 16))) == (
        PolicyDeclaration())
    assert declaration_of(object()) == PolicyDeclaration()
    es = build("mlp_sharded")
    engine = es.engine
    assert engine.policy == PolicyDeclaration()
    assert engine.kernel_facts == {}
    assert not set(engine.build_facts()) & set(kernel_facts.FACT_NAMES)
    # the manifest has every kernel's names all the same, each ``None``
    config = es.run_manifest()["config"]
    assert [config[k] for k in kernel_facts.FACT_NAMES] == [None] * 9
    # and its rules are the general ones alone
    assert engine.partition_rules == DEFAULT_PARTITION_RULES


# ------------------------------------------- what the policy returns, named

def _engine_with(es, policy):
    """``es``'s sharded engine rebuilt from another declaration."""
    lr_apply, lr_spec = es._perturbed_form(
        jax.ShapeDtypeStruct((es._spec.dim,), jnp.float32))
    return ShardedESEngine(
        es.env, es._policy_apply, es._spec, es.table, es.optimizer,
        es.config, hyperscale_mesh(model_shards=1,
                                   devices=jax.devices()[:1]),
        noise_mode="table", perturbed_apply=lr_apply, lowrank_spec=lr_spec,
        policy=policy)


@pytest.fixture(scope="module")
def moe_es():
    return build("moe")


def test_an_output_without_a_reduction_is_refused_at_build(moe_es):
    """(iii) The engine reduces a policy's outputs by NAME: one it has no
    reduction for stops the build, and the message lists the names it
    knows."""
    stated = declaration_of(moe_es.module)
    with pytest.raises(ValueError) as refused:
        _engine_with(moe_es, dataclasses.replace(
            stated, outputs=("expert_load", "router_entropy")))
    assert "('expert_load', 'router_entropy')" in str(refused.value)
    assert "['expert_load', 'selected_pairs']" in str(refused.value)


@pytest.mark.parametrize("outputs, returned", [
    ((), "argument 2 is longer"),
    (("expert_load", "selected_pairs"), "argument 2 is shorter"),
])
def test_a_tuple_of_another_length_is_refused_at_trace_time(
        moe_es, outputs, returned):
    """(iv) ``MoELM`` returns (score, behaviour, expert load): declared
    with fewer or more outputs, the generation program does not trace;
    nothing is read by position and found to be something else."""
    engine = _engine_with(moe_es, dataclasses.replace(
        declaration_of(moe_es.module), outputs=outputs))
    with pytest.raises(ValueError, match=returned):
        engine._generation_step.lower(moe_es.state, moe_es.table.data)


def test_the_declared_outputs_reach_the_metrics_by_name(moe_es):
    engine = _engine_with(moe_es, declaration_of(moe_es.module))
    _, metrics = jax.eval_shape(
        engine._generation_step, moe_es.state, moe_es.table.data)
    assert metrics["expert_load"].shape == (
        moe_es.module.n_routed_experts,)
    assert "selected_pairs" not in metrics
