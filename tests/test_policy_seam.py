"""The engine learns of a policy from its declaration alone (PR 56): a model
states how its own leaves are cut (``PolicyDeclaration.partition_rules``,
beside its ``param_shapes``) and which hand-written kernels it calls with
which widths (``kernels``); ``parallel/`` names no model's leaf and no
kernel.  Moving the two moved no leaf's ``PartitionSpec``: the literals of
``policy_seam_parent.py`` were read at the parent commit, when
``parallel/mesh.py`` held every model's rules in ONE first-match list.
"""

import ast
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import PartitionSpec as P

import loop_tiny
import seam_snapshots as snap
from policy_seam_parent import RULES_JSON, SHARDINGS
from test_policy_contract import SEQUENCE_MODELS

from estorch_tpu import ES, JaxAgent
from estorch_tpu.envs import TokenScoreEnv
from estorch_tpu.models import LoopedLM
from estorch_tpu.models.perturbed import declaration_of
from estorch_tpu.parallel.mesh import (DEFAULT_PARTITION_RULES, MODEL_AXIS,
                                       hyperscale_mesh, match_partition_rules,
                                       partition_rules_from_json,
                                       partition_rules_to_json,
                                       sharding_summary, unmatched_leaves)
from estorch_tpu.parallel.sharded import ShardedESEngine

PARALLEL = os.path.join(snap.ROOT, "estorch_tpu", "parallel")


@pytest.fixture(scope="module")
def published():
    return snap.published()


# ------------------------------------------ (i) no leaf is cut otherwise

@pytest.mark.parametrize("key", sorted(SHARDINGS))
def test_every_leaf_is_cut_as_the_parent_cut_it(key, published, devices8):
    """Every parameter leaf and every optimiser-state leaf of a published
    configuration, on a mesh of one device, of a ``model`` axis of 4 and of
    ``(2, 4)``: the model's own rules ahead of the general four resolve to
    the ``PartitionSpec`` the parent's global list resolved to."""
    name, mesh = key.split(" ")
    model, optimizer = published[name]
    rules = model.declaration().partition_rules + DEFAULT_PARTITION_RULES
    got = snap.digest(snap.leaf_specs(
        model, optimizer, tuple(int(n) for n in mesh.split("x")), rules))
    want = SHARDINGS[key]
    assert got["specs"] == want["specs"]
    assert (got["leaves"], got["sha256"]) == (want["leaves"], want["sha256"])


@pytest.mark.parametrize("name", sorted({k.split(" ")[0] for k in SHARDINGS}))
def test_a_models_own_rules_name_every_leaf_it_has(name, published):
    """A model's table is SUFFICIENT at its published size: no leaf is left
    to the general rules, whose suffixes (``bias``, ``scale``, ``kernel``)
    would cut a ``dt_bias`` or a ``norm_scale`` wrongly and in silence, and
    none reaches the catch-all."""
    model, _ = published[name]
    own = model.declaration().partition_rules
    assert own and unmatched_leaves(own, model.param_shapes()) == {}
    # rules over the one axis leaves are cut over, and nothing else
    assert {axis for _, spec in own for axis in spec} <= {None, MODEL_AXIS}


@pytest.mark.parametrize("name", sorted(SEQUENCE_MODELS))
def test_a_manifest_the_parent_wrote_still_loads(name, devices8):
    """``run_manifest()["config"]["partition_rules"]`` of a parent's run
    (its global list) goes through ``partition_rules_from_json`` and cuts
    this model's leaves as the model's own rules do now; what a run writes
    today round-trips too."""
    policy, tiny, _, _ = SEQUENCE_MODELS[name]
    lm = policy(**tiny.TINY)
    parents = partition_rules_from_json(RULES_JSON)
    assert len(parents) == 52 and parents[-1] == DEFAULT_PARTITION_RULES[-1]
    ours = lm.declaration().partition_rules + DEFAULT_PARTITION_RULES
    assert partition_rules_from_json(partition_rules_to_json(ours)) == ours
    mesh = hyperscale_mesh(2, 4, devices8)
    shapes = lm.param_shapes()
    assert (sharding_summary(shapes, match_partition_rules(
        parents, shapes, mesh)) == sharding_summary(
            shapes, match_partition_rules(ours, shapes, mesh)))


# ------------------------- (ii) a policy's rules are consulted before any

def _looped_es(devices):
    return ES(policy=LoopedLM, agent=JaxAgent, optimizer=optax.adam,
              population_size=8, sigma=0.02, policy_kwargs=loop_tiny.TINY,
              agent_kwargs={"env": TokenScoreEnv(**loop_tiny.ENV)},
              optimizer_kwargs={"learning_rate": 1e-2}, shard_params=True,
              model_shards=2, low_rank=1, noise_mode="table",
              table_size=1 << 18, compute_dtype="bfloat16",
              device=devices[:2])


def _engine_with(es, policy, **over):
    lr_apply, lr_spec = es._perturbed_form(
        jax.ShapeDtypeStruct((es._spec.dim,), jnp.float32))
    return ShardedESEngine(
        es.env, es._policy_apply, es._spec, es.table, es.optimizer,
        es.config, es.mesh, noise_mode="table", perturbed_apply=lr_apply,
        lowrank_spec=lr_spec, policy=policy, **over)


def test_each_engine_cuts_by_its_own_policys_rules(devices8):
    """Two declarations whose rules DISAGREE on ``attn/q``: each engine
    cuts the leaf as its own policy says (no other policy's rule can come
    first: there is no shared list), a leaf neither names goes by the
    general rules, and rules the caller passes replace the policy's."""
    es = _looped_es(devices8)
    stated = declaration_of(es.module)
    by_rows = dataclasses.replace(stated, partition_rules=(
        (r"attn/q$", P(MODEL_AXIS, None)),))
    by_columns = dataclasses.replace(stated, partition_rules=(
        (r"attn/q$", P(None, MODEL_AXIS)),))
    rows, columns = (_engine_with(es, policy).sharding_report()
                     for policy in (by_rows, by_columns))
    assert rows["layer_00/attn/q"] == "PartitionSpec('model', None)"
    assert columns["layer_00/attn/q"] == "PartitionSpec(None, 'model')"
    assert es.engine.sharding_report()["layer_00/attn/q"] == (
        "PartitionSpec(None, 'model')")
    # what neither names: the general rules, the same for both
    assert rows["head/kernel"] == columns["head/kernel"] == (
        "PartitionSpec(None, 'model')")
    assert "catch-all" in rows["layer_00/attn/o"]
    for engine_rules, policy in ((_engine_with(es, by_rows), by_rows),
                                 (es.engine, stated)):
        assert engine_rules.partition_rules == (
            policy.partition_rules + DEFAULT_PARTITION_RULES)
    # the caller's rules (``ES(partition_rules=)``) are used AS GIVEN
    strict = ((r".*", P()),)
    passed = _engine_with(es, by_rows, partition_rules=strict)
    assert passed.partition_rules == strict
    assert passed.sharding_report()["layer_00/attn/q"].startswith(
        "PartitionSpec(None, None)")


# ------------------------------------------ (iii) the arrow stays one-way

KERNEL_RULE_MODULES = re.compile(
    r"(^|\.)pallas_(attention|head|scan|combine|delta)$")
MAY_IMPORT = {"kernel_scope", "traced_why"}     # the engine's ONE question


def _imports(path):
    """``[(module, name)]`` of every import in the file, function-local
    ones among them."""
    with open(path) as f:
        tree = ast.parse(f.read())
    found = []
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            found += [(node.module or "", a.name) for a in node.names]
        elif isinstance(node, ast.Import):
            found += [(a.name, "") for a in node.names]
    return found


@pytest.mark.parametrize("name", ["sharded.py", "engine.py", "mesh.py"])
def test_parallel_knows_no_model_and_no_kernel(name):
    """``parallel/`` imports no sequence model, no block of one, and of the
    five kernels' modules only where kernels may be traced
    (``kernel_scope``, ``traced_why``); it holds no kernel fact's name and
    ``mesh.py`` no model's leaf: a model PR edits ``models/``, a kernel PR
    ``ops/`` and the models that call the kernel."""
    path = os.path.join(PARALLEL, name)
    for module, imported in _imports(path):
        assert not re.search(r"(_lm|lm_blocks)$", module), (module, imported)
        assert not re.search(r"(_lm|lm_blocks)$", imported), (module,
                                                             imported)
        if KERNEL_RULE_MODULES.search(module):
            assert imported in MAY_IMPORT, (module, imported)
        assert not KERNEL_RULE_MODULES.search(imported), (module, imported)
    with open(path) as f:
        text = f.read()
    for word in ("attention_form", "head_form", "scan_form", "combine_form",
                 "delta_form"):
        assert word not in text, word
    if name == "mesh.py":
        for leaf in ("mamba/", "delta/", "moe/", "attn/", "index"):
            assert leaf not in text, leaf
