"""The stage vocabulary of a generation (obs/trace.py) as the compiled
programs carry it, and ``Telemetry.phase`` as a span in the profiler's
trace (docs/observability.md, "Stages inside the generation program").

No wall-clock assertion anywhere: the xdist workers share the CPU.
"""

import contextlib
import re
import sys

import jax
import jax.numpy as jnp
import optax
import pytest

from estorch_tpu import ES, JaxAgent, MLPPolicy
from estorch_tpu.envs import CartPole
from estorch_tpu.models.perturbed import declaration_of
from estorch_tpu.obs.spans import Telemetry
from estorch_tpu.obs.trace import (ATTN, DENSE, DIFF, DISPATCH, ENV, EXIT,
                                   EXPERT, GATHER, GMU, GRAD, HEAD, INDEX,
                                   MIX, NOISE, PERTURB, POLICY, PART_PREFIX,
                                   RANK, ROPE, ROUTE, SAMPLE, SCOPE_PREFIX,
                                   SELECT, SSM, STAGES, UPDATE, annotate,
                                   part, stage, trace)
from estorch_tpu.ops.pallas_attention import attention_facts

# the stages of every generation program; a sequence model nests more
# inside es.policy (DENSE, SSM, ATTN, HEAD; a looped one ROPE and EXIT; a
# sparse-expert one ROPE, ROUTE, DISPATCH and EXPERT; one with gated memory
# units and differential attention GMU and DIFF; one whose attention reads a
# learned selection of keys INDEX and SELECT; one whose attention is computed
# inside a compressed latent MIX)
GENERATION_STAGES = STAGES[:9]
EXPERT_STAGES = {ROUTE, DISPATCH, EXPERT}
SAMBAY_STAGES = {GMU, DIFF}
INDEXED_STAGES = {INDEX, SELECT}
LATENT_STAGES = {MIX}

SCOPE = re.compile(r"(?<![A-Za-z0-9_])" + re.escape(SCOPE_PREFIX)
                   + r"([a-z_]+)")
MATMUL = re.compile(r" = \S+ (?:dot|convolution)\(.*op_name=\"([^\"]*)\"")
PHASES = ("dispatch", "device", "host_sync", "record")

# every form runs every stage; what differs is where the stage's work is
FORMS = {
    "standard": {},
    "noise_dma": {},  # the row kernels, interpreted (conftest.dma_gather)
    "unmirrored": {"mirrored": False},
    "low_rank": {"low_rank": 1},
    "obs_norm": {"obs_norm": True},
    "sharded_program": {"shard_params": True},
    "sharded_table": {"shard_params": True, "noise_mode": "table"},
}


def _es(**over):
    kw = dict(
        policy=MLPPolicy, agent=JaxAgent, optimizer=optax.adam,
        population_size=16, sigma=0.05, seed=0,
        policy_kwargs={"action_dim": 2, "hidden": (8,)},
        agent_kwargs={"env": CartPole(), "horizon": 10},
        optimizer_kwargs={"learning_rate": 1e-2}, table_size=1 << 14)
    kw.update(over)
    return ES(**kw)


@pytest.fixture
def keyed_by_source():
    """The suite's persistent compile cache keys programs WITHOUT their
    metadata, so an entry written before a scope existed would be served
    with the old ``op_name``s (the hazard docs/observability.md names).
    With the metadata in the key this test compiles what it reads."""
    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    jax.config.update(flag, True)
    yield
    jax.config.update(flag, before)


@pytest.mark.parametrize("form", sorted(FORMS))
def test_compiled_generation_names_every_stage(form, keyed_by_source,
                                               dma_gather):
    with dma_gather() if form == "noise_dma" else contextlib.nullcontext():
        es = _es(**FORMS[form])
    engine = es.engine
    if form in ("standard", "noise_dma"):
        assert engine.noise_gather_form == (
            "dma" if form == "noise_dma" else "slice")
    args = (es.state,)
    if getattr(engine, "noise_mode", None) == "table":
        args += (engine.table.data,)
    text = engine._generation_step.lower(*args).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    found = {s for name in names for s in SCOPE.findall(name)}
    assert found == set(GENERATION_STAGES), (
        f"{form}: stages missing {set(GENERATION_STAGES) - found}, unknown "
        f"{found - set(GENERATION_STAGES)}")
    # the matmuls of the rollout's while body are the forward's or the
    # physics'; the only others are the update's second pass over the noise
    matmuls = [SCOPE.findall(m) for line in text.splitlines()
               for m in MATMUL.findall(line)]
    assert matmuls, f"{form}: no dot or convolution in the compiled text"
    for stack in matmuls:
        assert stack, f"{form}: a dot outside every stage"
        assert GRAD in stack or POLICY in stack or ENV in stack, stack
    assert any(POLICY in stack for stack in matmuls)


# the nine sequence models on the sharded engine's perturbed form: what
# each is built from, the stages its forward does NOT name, the layers it
# nests inside es.policy, and the parts it names that are no leaf's
SEQUENCE_MODELS = {
    "sequence": dict(policy="HybridLM", tiny="lm_tiny", devices=4,
                     model_shards=2,
                     absent=({ROPE, EXIT} | EXPERT_STAGES | SAMBAY_STAGES
                             | INDEXED_STAGES | LATENT_STAGES),
                     inner=(DENSE, SSM, ATTN, HEAD)),
    "looped": dict(policy="LoopedLM", tiny="loop_tiny", devices=1,
                   model_shards=1,
                   absent=({SSM} | EXPERT_STAGES | SAMBAY_STAGES
                           | INDEXED_STAGES | LATENT_STAGES),
                   inner=(DENSE, ATTN, HEAD, ROPE, EXIT)),
    "expert": dict(policy="MoELM", tiny="moe_tiny", devices=1,
                   model_shards=1,
                   absent=({SSM, EXIT} | SAMBAY_STAGES | INDEXED_STAGES
                           | LATENT_STAGES),
                   inner=(DENSE, ATTN, HEAD, ROPE, ROUTE, DISPATCH, EXPERT)),
    # its three kinds of attention say which they are: parts of es.attn
    "sambay": dict(policy="SambaYLM", tiny="sambay_tiny", devices=1,
                   model_shards=1,
                   absent=({ROPE, EXIT} | EXPERT_STAGES | INDEXED_STAGES
                           | LATENT_STAGES),
                   inner=(DENSE, SSM, ATTN, HEAD, GMU, DIFF),
                   more_parts={"window": ATTN, "full": ATTN, "cross": ATTN}),
    # its indexer's three projections are parts of es.index; its one kind
    # of attention, over the selection, says which it is
    "indexed": dict(policy="IndexedMoELM", tiny="indexed_moe_tiny",
                    devices=1, model_shards=1,
                    absent={SSM, EXIT} | SAMBAY_STAGES | LATENT_STAGES,
                    inner=(DENSE, ATTN, HEAD, ROPE, ROUTE, DISPATCH, EXPERT,
                           INDEX, SELECT),
                    more_parts={"selected": ATTN}),
    # what it does to q, k and v inside the latent are parts of es.mix (the
    # convolutions' leaves among them, which are no matrices of a tree); its
    # router's state scale is a vector, a part of es.route beside the
    # router's matrices
    "latent": dict(policy="CCAMoELM", tiny="cca_moe_tiny", devices=1,
                   model_shards=1, held=2,
                   absent={SSM, EXIT} | SAMBAY_STAGES | INDEXED_STAGES,
                   inner=(DENSE, ATTN, HEAD, ROPE, ROUTE, DISPATCH, EXPERT,
                          MIX),
                   more_parts={"conv_time": MIX, "conv_head": MIX,
                               "qk_mean": MIX, "value_shift": MIX,
                               "qk_norm": MIX, "router_state": ROUTE}),
    # its two kinds of attention say which they are; its router sits AHEAD
    # of the attention, under the same es.route
    "windowed": dict(policy="WindowMoELM", tiny="window_moe_tiny", devices=1,
                     model_shards=1,
                     absent=({SSM, EXIT} | SAMBAY_STAGES | INDEXED_STAGES
                             | LATENT_STAGES),
                     inner=(DENSE, ATTN, HEAD, ROPE, ROUTE, DISPATCH, EXPERT),
                     more_parts={"window": ATTN, "global": ATTN}),
    # the gated delta rule names its parts beneath es.ssm (the conv's taps,
    # the decay's leaves and the gated norm's weights are no matrices of a
    # tree; the triangular solve and the chain over chunks multiply no leaf
    # at all); the attention of its ONE kind of full layer names none
    "delta": dict(policy="DeltaMoELM", tiny="delta_moe_tiny", devices=1,
                  model_shards=1,
                  absent=({EXIT} | SAMBAY_STAGES | INDEXED_STAGES
                          | LATENT_STAGES),
                  inner=(DENSE, SSM, ATTN, HEAD, ROPE, ROUTE, DISPATCH,
                         EXPERT),
                  more_parts={"conv": SSM, "decay": SSM, "solve": SSM,
                              "carry": SSM, "gate": SSM}),
    # its two kinds of attention (of different head counts) say which they
    # are; the gate a head is a part of es.dense by its leaf's key, which
    # the sigmoid and the product on the context carry too
    "gated": dict(policy="GatedWindowMoELM", tiny="gated_window_moe_tiny",
                  devices=1, model_shards=1,
                  absent=({SSM, EXIT} | SAMBAY_STAGES | INDEXED_STAGES
                          | LATENT_STAGES),
                  inner=(DENSE, ATTN, HEAD, ROPE, ROUTE, DISPATCH, EXPERT),
                  more_parts={"sliding": ATTN, "full": ATTN}),
}
# the models whose lowered text is read (an expert layer's grouped matmul
# keeps its name there and not in the compiled program's)
ROUTED = ("expert", "indexed", "latent", "windowed", "delta", "gated")
PART = re.compile(r"(?<![A-Za-z0-9_])" + re.escape(PART_PREFIX)
                  + r"([A-Za-z0-9_.]+)")


def _sequence_es(case):
    import importlib

    from estorch_tpu import models
    from estorch_tpu.envs import TokenScoreEnv

    tiny = importlib.import_module(case["tiny"])
    return ES(policy=getattr(models, case["policy"]), agent=JaxAgent,
              optimizer=optax.adam, population_size=8, sigma=0.02,
              policy_kwargs=tiny.TINY,
              agent_kwargs={"env": TokenScoreEnv(**tiny.ENV)},
              optimizer_kwargs={"learning_rate": 1e-2}, shard_params=True,
              model_shards=case["model_shards"], low_rank=1,
              noise_mode="table", table_size=1 << 18,
              device=jax.devices()[:case["devices"]])


def _multiplied_leaves(module) -> dict:
    """``{part: the stage it sits beneath}`` of every parameter leaf a
    forward of ``module`` multiplies: the matrices of its tree (the conv
    taps are added up, not multiplied by), named by their key, a module's
    one ``kernel`` / ``embedding`` by the module's, a shared or routed
    expert's with that path element in front."""
    leaves = jax.tree_util.tree_flatten_with_path(module.param_shapes())[0]
    own_stage = {"router": ROUTE, "exit_gate": EXIT, "head": HEAD,
                 "embed": POLICY, "index_q": INDEX, "index_k": INDEX,
                 "index_w": INDEX, "router_down": ROUTE}
    parts = {}
    for path, leaf in leaves:
        keys = [str(k.key) for k in path]
        if (len(leaf.shape) < 2 or keys[-1].startswith("conv")
                or keys[-1] == "A_log"):        # a table of decay rates
            continue
        if "router_mlp" in keys:        # a router's MLP is one part
            parts["router_mlp"] = ROUTE
            continue
        if keys[-1] in ("kernel", "embedding"):
            keys = keys[:-1]
        words = [k for k in keys[:-1] if k in ("shared", "experts")]
        name = ".".join(words + [keys[-1]])
        parts[name] = (EXPERT if "experts" in words
                       else own_stage.get(name, DENSE))
    return parts


@pytest.mark.parametrize("model", sorted(SEQUENCE_MODELS))
def test_model_names_its_layers_inside_the_policy_stage(model,
                                                        keyed_by_source):
    """The sharded engine's perturbed form on each sequence model: every
    stage of a generation but the ones the model has no work for, its
    layers' stages nested inside es.policy, the rank-r corrections under
    es.perturb; every leaf a forward multiplies a part beneath its stage,
    and no part moves an operation to another stage."""
    from benchmark import stage_reduce

    case = SEQUENCE_MODELS[model]
    es = _sequence_es(case)
    engine = es.engine
    lowered = engine._generation_step.lower(es.state, engine.table.data)
    if model in ROUTED:
        text = lowered.as_text(debug_info=True)
        names = re.findall(r'loc\("(jit\([^"]*)"', text)
    else:
        text = lowered.compile().as_text()
        names = re.findall(r'op_name="([^"]*)"', text)
    found = {s for name in names for s in SCOPE.findall(name)}
    want = set(STAGES) - case["absent"]
    assert found == want, (want - found, found - want)
    for inner in case["inner"]:
        stacks = [SCOPE.findall(n) for n in names
                  if SCOPE_PREFIX + inner in n]
        # (the compiler shortens a few names to their last scope, e.g.
        # "es.attn/reduce_max": those carry no outer stage at all; the
        # lowered text of the expert model shortens none)
        nested = [st for st in stacks if POLICY in st]
        assert stacks and len(nested) > len(stacks) // 2, inner
        assert model not in ROUTED or len(nested) == len(stacks), inner
        assert all(st.index(POLICY) < st.index(inner) for st in nested), inner
    stacks = [(tuple(SCOPE.findall(n)), n) for n in names if SCOPE.findall(n)]
    if model in ROUTED:
        # the grouped matmuls sit under es.expert, their per-(member,
        # expert) corrections one deeper, the sort under es.dispatch
        assert any(st[-1] == EXPERT and "ragged_dot" in n for st, n in stacks)
        assert any(st[-2:] == (EXPERT, PERTURB) for st, _ in stacks)
        assert any(st[-1] == DISPATCH and "sort" in n for st, n in stacks)
        assert any(st[-1] == DISPATCH and "scatter" in n for st, n in stacks)
        assert any(st[-1] == ROUTE and "top_k" in n for st, n in stacks)
        assert es.obs.counters.get("experts_held") == case.get("held", 4)
    if model == "latent":
        # the head-mixing convolution's batched products under es.mix, their
        # per-(tap, head) corrections one deeper; the router's MLP and the
        # choice of ONE expert under es.route; the tied table read at the
        # head, transposed, in its own part
        assert any(st[-1] == MIX and "dot_general" in n for st, n in stacks)
        assert any(st[-2:] == (MIX, PERTURB) for st, _ in stacks)
        assert any(st[-1] == ROUTE and "erf" in n for st, n in stacks)
        assert any(st[-2:] == (ROUTE, PERTURB) for st, _ in stacks)
        assert any(st[-1] == HEAD and PART.findall(n) == ["embed"]
                   for st, n in stacks)
        assert es.obs.counters.get("conv_taps") == 4
    if model == "windowed":
        # the router's choice is made BEFORE the attention it sits beside:
        # the first top_k of the program comes ahead of the first softmax
        # of an attention part (the lowered text is in program order)
        first = {key: next(i for i, n in enumerate(names) if hit(n))
                 for key, hit in (
                     ("route", lambda n: SCOPE.findall(n)[-1:] == [ROUTE]
                      and "top_k" in n),
                     ("attn", lambda n: ATTN in SCOPE.findall(n)
                      and PART.findall(n) == ["global"]))}
        assert first["route"] < first["attn"], first
        # only the window layers turn: es.rope under no part of.global
        assert any(st[-1] == ROPE and ("sin" in n or "cos" in n)
                   for st, n in stacks)
        assert es.obs.counters.get("sliding_window") == 6
    if model == "gated":
        # the gate a head: its projection's matmul under es.dense in the
        # part of its leaf, and the sigmoid on the context in the same
        # part; the kinds' tables and rotations under es.rope (a partial
        # rotation puts the turned half back beside the rest of the head)
        assert any(st[-1] == DENSE and "dot_general" in n
                   and PART.findall(n) == ["head_gate"] for st, n in stacks)
        assert any(st[-1] == DENSE and "logistic" in n
                   and PART.findall(n) == ["head_gate"] for st, n in stacks)
        assert any(st[-1] == ROPE and "cos" in n for st, n in stacks)
        assert any(st[-1] == ROPE and "concatenate" in n for st, n in stacks)
        assert es.obs.counters.get("sliding_heads") == 6
        assert es.obs.counters.get("full_heads") == 4
    if model == "delta":
        # the chain over the chunks is a loop under es.ssm in the part
        # of.carry, the triangular system's products in of.solve; the
        # shared expert's one-column gate is a part of es.dense
        assert any(st[-1] == SSM and "while" in n
                   and PART.findall(n) == ["carry"] for st, n in stacks)
        assert any(st[-1] == SSM and "dot_general" in n
                   and PART.findall(n) == ["solve"] for st, n in stacks)
        assert any(st[-1] == DENSE and PART.findall(n) == ["shared_gate"]
                   for st, n in stacks)
        assert es.obs.counters.get("delta_chunk") == 8
    if model == "indexed":
        # the score product of every index head against the ONE key head
        # under es.index, the bisection's loop and the prefix count under
        # es.select, and the selection the attention reads
        assert any(st[-1] == INDEX and "dot_general" in n for st, n in stacks)
        assert any(st[-1] == SELECT and "while" in n for st, n in stacks)
        assert any(st[-1] == SELECT and "cumsum" in n for st, n in stacks)
        assert any(st[-2:] == (INDEX, PERTURB) for st, _ in stacks)
        assert es.obs.counters.get("sparse_topk") == 6
    if model not in ROUTED:
        # the projections are matmuls under es.dense or es.head; the
        # corrections are nested one deeper, under es.perturb
        matmuls = [SCOPE.findall(m) for line in text.splitlines()
                   for m in MATMUL.findall(line)]
        assert any(st[-1] == DENSE for st in matmuls)
        assert any(st[-1] == HEAD for st in matmuls)
        assert any(st[-1] == PERTURB and DENSE in st for st, _ in stacks)
    if model == "looped":
        # the rotation's sines and cosines, and the gate's sigmoid (its exp)
        assert any(st[-1] == ROPE and ("sin" in n or "cos" in n)
                   for st, n in stacks)
        assert any(st[-1] == EXIT and n.endswith("exp") for st, n in stacks)
        assert es.obs.counters.get("loop_steps") == 4
        assert es.obs.counters.get("layer_applications_per_token") == 8

    # ---- the parts: every multiplied leaf, beneath its stage
    parted = [(".".join(PART.findall(n)), n) for n in names
              if PART.search(n)]
    leaves = {**_multiplied_leaves(es.module), **case.get("more_parts", {})}
    assert {p for p, _ in parted} == set(leaves), (
        set(leaves) - {p for p, _ in parted},
        {p for p, _ in parted} - set(leaves))
    for part_name, beneath in leaves.items():
        last = PART_PREFIX + part_name.split(".")[-1]
        assert any(beneath in SCOPE.findall(n[:n.rindex(last)])
                   for p, n in parted if p == part_name), (part_name, beneath)
    if model == "sambay":
        # the scan is a loop under es.ssm; the two maps are subtracted under
        # es.diff and the memory multiplied under es.gmu
        assert any(st[-1] == SSM and "while" in n for st, n in stacks)
        assert any(st[-1] == DIFF and n.endswith("sub") for st, n in stacks)
        assert any(st[-1] == GMU and n.endswith("mul") for st, n in stacks)
        assert es.obs.counters.get("kv_shared_by") == 1
    if model in ("sequence", "sambay"):     # the tied head reads the embedding, too
        assert any(p == "embed" and HEAD in SCOPE.findall(n)
                   for p, n in parted)
    # an unfused correction keeps es.perturb INSIDE its part
    assert any(n[n.rindex(PART_PREFIX):].count(SCOPE_PREFIX + PERTURB)
               for _, n in parted)
    # a part is no stage: with or without it a name stack books alike
    for _, n in parted:
        bare = "/".join(c for c in n.split("/") if not PART.search(c))
        assert stage_reduce.stage_of(n) == stage_reduce.stage_of(bare), n
        assert stage_reduce.stage_of(n) != stage_reduce.UNSCOPED, n


@pytest.mark.parametrize("model", sorted(SEQUENCE_MODELS))
def test_parts_are_metadata_only(model, monkeypatch):
    """A model's perturbed forward lowered with the parts and with
    ``part`` a no-op is the same program, text for text (the StableHLO
    text leaves the locations out): a part adds no operation."""
    import importlib

    from estorch_tpu import models
    from estorch_tpu.models import (cca_moe_lm, delta_moe_lm,
                                    gated_window_moe_lm, hybrid_lm,
                                    indexed_moe_lm, lm_blocks, looped_lm,
                                    moe_lm, perturbed, sambay_lm,
                                    window_moe_lm)

    case = SEQUENCE_MODELS[model]
    tiny = importlib.import_module(case["tiny"])
    module = getattr(models, case["policy"])(**tiny.TINY)
    shapes = module.param_shapes()
    spec = perturbed.lowrank_spec_for(module, shapes, 1)
    tokens = jax.ShapeDtypeStruct((tiny.ENV["seq_len"],), jnp.int32)
    row = jax.ShapeDtypeStruct((spec.noise_dim,), jnp.float32)

    def forward(params, noise_row, toks):
        return module.perturbed_apply(params, spec.unpack(noise_row), 0.02,
                                      toks)

    def lowered():
        # a fresh function each time: jit's cache would hand back the first
        return jax.jit(lambda *a: forward(*a)).lower(shapes, row, tokens)

    with_parts = lowered()
    assert PART_PREFIX in with_parts.as_text(debug_info=True)
    for mod in (lm_blocks, perturbed, hybrid_lm, looped_lm, moe_lm,
                sambay_lm, indexed_moe_lm, cca_moe_lm, window_moe_lm,
                delta_moe_lm, gated_window_moe_lm):
        monkeypatch.setattr(mod, "part",
                            lambda name: contextlib.nullcontext())
    without = lowered()
    assert PART_PREFIX not in without.as_text(debug_info=True)
    assert with_parts.as_text() == without.as_text()


# ---- compiled for a described TPU v5e: Mosaic runs, nothing executes ----
# (the ONE file of the suite that loads the TPU's compiler: a second file
# could land on another xdist worker, which could not load it as well)


@pytest.fixture(scope="module")
def v5e_chip(v5e_2x2):
    """One chip of the described v5e 2x2."""
    return v5e_2x2[0]


@pytest.fixture(scope="module")
def v5e_2x2():
    """The four chips of a described v5e 2x2: devices to compile for, with
    no chip attached.  The persistent compile cache is off meanwhile (an
    entry written for a TPU cannot be read back here and warns at every
    read)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2",
            chips_per_host_bounds=(2, 2, 1), num_slices=1)
    except Exception as e:  # no libtpu here, or its lock is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield list(topo.devices)
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def test_dma_form_books_its_kernels_to_noise_and_grad(v5e_chip):
    """On a TPU mesh the engine resolves ``noise_gather_form`` to "dma" by
    itself, and the two Mosaic custom calls of the compiled generation
    program sit under es.noise (the evaluation's gather) and es.grad (the
    update's weighted sum): the device trace books them to those stages,
    not to ``unscoped``."""
    from jax.sharding import NamedSharding, PartitionSpec

    from estorch_tpu.models.decomposed import mlp_decomposed_apply
    from estorch_tpu.parallel import ESEngine
    from estorch_tpu.parallel.mesh import population_mesh

    es = _es(compute_dtype="bfloat16", table_size=1 << 16)  # the pieces
    assert es.engine.noise_gather_form == "slice"  # a CPU mesh
    mesh = population_mesh([v5e_chip])
    engine = ESEngine(
        es.env, es._policy_apply, es._spec, es.table, es.optimizer,
        es.config, mesh,
        decomposed_apply=lambda shared, noise, c, obs: mlp_decomposed_apply(
            es.module, shared, noise, c, obs))
    assert engine.forward_form == "pair_shared"
    assert engine.noise_gather_form == "dma"
    replicated = NamedSharding(mesh, PartitionSpec())
    state = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=replicated),
        es.state)
    text = engine._generation_step.lower(state).compile().as_text()
    kernels = [SCOPE.findall(name) for line in text.splitlines()
               if "tpu_custom_call" in line
               for name in re.findall(r'op_name="([^"]*)"', line)]
    assert sorted(stack[-1] for stack in kernels) == [GRAD, NOISE], kernels


@pytest.mark.parametrize("case,form", [
    ("f32_tileable_table", "dma"),
    ("dim_past_the_vmem_budget", "slice"),
    ("table_not_whole_tiles", "slice"),
    ("bf16_table", "slice"),
    ("low_rank", "slice"),
    ("update_only", "dma"),
])
def test_gather_rule_on_a_tpu_mesh(case, form, v5e_chip):
    """``ESEngine.noise_gather_form`` on a mesh of TPU devices, each arm of
    the rule (platform, table dtype and tiling, ``spec.dim``, ``low_rank``)
    — what a chip run resolves, with nothing compiled."""
    from estorch_tpu.ops import make_noise_table, make_param_spec
    from estorch_tpu.parallel import ESEngine
    from estorch_tpu.parallel.engine import NOISE_KERNEL_MAX_DIM
    from estorch_tpu.parallel.mesh import population_mesh

    es = _es(low_rank=1 if case == "low_rank" else 0, table_size=1 << 16)
    assert es.engine.noise_gather_form == "slice"  # a CPU mesh
    spec, table, env = es._spec, es.table, es.env
    if case == "dim_past_the_vmem_budget":
        _, spec = make_param_spec(
            {"w": jnp.zeros((NOISE_KERNEL_MAX_DIM + 1,), jnp.float32)})
    elif case == "table_not_whole_tiles":
        table = make_noise_table((1 << 16) + 8, seed=0)
    elif case == "bf16_table":
        table = make_noise_table(1 << 16, seed=0, dtype=jnp.bfloat16)
    elif case == "update_only":  # the pooled path's core engine
        env = None
    lowrank = {}
    if case == "low_rank":
        lr_apply, lr_spec = es._perturbed_form(es.state.params_flat)
        lowrank = {"lowrank_apply": lr_apply, "lowrank_spec": lr_spec}
    engine = ESEngine(env, es._policy_apply, spec, table, es.optimizer,
                      es.config, population_mesh([v5e_chip]), **lowrank)
    assert engine.noise_gather_form == form


@pytest.mark.parametrize("dim,rows", [(75018, 5120), (166673, 2048)],
                         ids=["humanoid2d", "synth376"])
@pytest.mark.parametrize("kernel", ["gather_bf16", "gather_f32", "sum"])
def test_row_kernels_compile_for_the_v5e_at_the_cells_widths(
        kernel, dim, rows, v5e_chip):
    """Mosaic accepts both row kernels at the two one-chip cells' widths
    (aligned DMA windows, VMEM within the scoped limit): what interpret
    mode cannot show, at no chip time."""
    from jax.sharding import SingleDeviceSharding

    from estorch_tpu.ops.pallas_noise import (gather_noise_rows,
                                              weighted_noise_sum)

    one = SingleDeviceSharding(v5e_chip)
    table = jax.ShapeDtypeStruct((1 << 25,), jnp.float32, sharding=one)
    offs = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one)
    if kernel == "sum":
        w = jax.ShapeDtypeStruct((rows,), jnp.float32, sharding=one)
        compiled = jax.jit(lambda t, o, w: weighted_noise_sum(
            t, o, w, dim=dim, interpret=False)).lower(table, offs, w).compile()
    else:
        dtype = jnp.bfloat16 if kernel == "gather_bf16" else jnp.float32
        compiled = jax.jit(lambda t, o: gather_noise_rows(
            t, o, dim=dim, dtype=dtype, interpret=False)).lower(
                table, offs).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("heads, shared", [(16, 0), (32, 64), (32, 128)],
                         ids=["looped", "latent", "shared128"])
def test_attention_kernel_compiles_for_the_v5e_at_the_looped_cells_shapes(
        heads, shared, dtype, v5e_chip):
    """Mosaic accepts the attention kernel at ``ouro-2.6b-es-4k-1chip``'s
    shapes (16 heads of 128 as column blocks of a [4096, 2048] array, the
    kernel's own blocks, one pair's two signs through ``vmap``), with
    nothing else in the program: no score tensor, no copy; and at
    ``joyai-flash-es-4k-1chip``'s (32 heads of 128 with a second score
    term of 64, two heads a lane block, against ONE key part; keys and
    values column blocks of ONE ``[4096, 32 · 256]`` array), where the
    only other operations lay that key part out as ``[k, 0 | 0, k]``."""
    from jax.sharding import SingleDeviceSharding

    from estorch_tpu.ops.pallas_attention import causal_attention

    def operand(width):
        return jax.ShapeDtypeStruct(
            (1, 2, 4096, width), dtype,
            sharding=SingleDeviceSharding(v5e_chip))

    # latent attention's kv_b writes each head's key with its values
    # beside it, and the kernel reads both out of that one array
    beside = shared == 64
    parts = (operand(heads * 128),
             operand(heads * (256 if beside else 128)),
             None if beside else operand(heads * 128)) + (
        (operand(heads * shared), operand(shared)) if shared else ())
    text = jax.jit(jax.vmap(jax.vmap(lambda *parts: causal_attention(
        *parts, num_heads=heads, num_kv_heads=heads, head_dim=128,
        scale=(128 + shared) ** -0.5, interpret=False)))).lower(
            *parts).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert "f32[" not in text.split("ENTRY")[1] or dtype == jnp.float32
    assert shared or " copy(" not in text.split("ENTRY")[1]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_attention_kernel_compiles_for_the_v5e_at_a_differential_pairs_shapes(
        dtype, v5e_chip):
    """Mosaic accepts the attention kernel at ``phi4-flash-es-8k-1chip``'s
    shapes: 40 score heads of 64 over 20 key heads and ten value blocks of
    128, two heads a column block, one member of 8,192 positions in blocks
    of 1,024 (the cell evaluates a pair's signs in turn).  q and v reach the
    call as they were handed in (a bitcast, a prefetch: no transposing copy,
    no copy of the values a map); the key is laid out ``[k₁, 0 | 0, k₂]``
    before it."""
    from jax.sharding import SingleDeviceSharding

    from estorch_tpu.ops.pallas_attention import causal_attention

    def operand(width):
        return jax.ShapeDtypeStruct(
            (1, 8192, width), dtype, sharding=SingleDeviceSharding(v5e_chip))

    text = jax.jit(jax.vmap(lambda q, k, v: causal_attention(
        q, k, v, num_heads=40, num_kv_heads=20, head_dim=64, value_dim=128,
        scale=0.125, interpret=False, paired=True))).lower(
            operand(2560), operand(1280), operand(1280)).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    entry = text.split("ENTRY")[1]
    call, = [line for line in entry.splitlines()
             if "tpu_custom_call" in line]
    q_operand, _, v_operand = re.search(
        r"custom-call\(([^)]*)\)", call).group(1).split(", ")
    by_name = {line.split(" = ")[0].strip(): line
               for line in entry.splitlines() if " = " in line}
    assert " bitcast(%q" in by_name[q_operand]
    assert re.search(r" (bitcast|copy-done)\(", by_name[v_operand])
    assert "select" in entry     # the padded key


@pytest.mark.parametrize("hidden, vocab, tied, dtype", [
    (2048, 49152, False, jnp.bfloat16), (2048, 49152, False, jnp.float32),
    (2048, 16160, False, jnp.bfloat16), (2048, 100352, True, jnp.bfloat16),
    (8192, 32768, False, jnp.bfloat16), (4096, 32768, True, jnp.float32),
    # zaya1-es-8k-1chip: the first cell that RUNS the tied layout, its
    # 32,784 rows a short last vocabulary tile
    (2048, 32784, True, jnp.bfloat16),
], ids=["looped", "looped_f32", "tail", "tied", "widest_bf16", "widest_f32",
        "tied_tail"])
def test_head_kernel_compiles_for_the_v5e_at_the_cells_shapes(
        hidden, vocab, tied, dtype, v5e_chip):
    """Mosaic accepts the head's kernel at the one-chip sequence cells'
    shapes (one pair's two signs x 4,096 positions x hidden 2,048 through
    the engine's ``vmap``s, tiles of the kernel's own, the scoped-VMEM limit
    it asks for): ``ouro-2.6b-es-4k-1chip``'s ``[2048, 49152]`` head,
    ``joyai-flash-es-4k-1chip``'s ``[2048, 16160]`` (a short last tile,
    masked) and a tied ``[100352, 2048]`` embedding read transposed
    (granite's, were it on one chip); and at the widest rows its rule
    admits, 16 KiB of hidden state (row tiles of 512).  ONE custom call
    holds all rows, and ``W`` reaches it as the un-batched parameter it
    is."""
    from jax.sharding import SingleDeviceSharding

    from estorch_tpu.models import lm_blocks
    from estorch_tpu.ops.pallas_attention import kernel_scope

    def on_chip(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=SingleDeviceSharding(v5e_chip))

    w_shape = (vocab, hidden) if tied else (hidden, vocab)

    def scores(h, tokens, w, a, b):
        def pair(hp, tp, ap, bp):
            return jax.vmap(lambda hs, sign: lm_blocks.score_next_tokens(
                hs, tp, w, (ap, bp), 0.002 * sign, 512,
                8.0 if tied else None, leaf="embed" if tied else "head",
                transposed=tied)[0])(hp, jnp.asarray([1.0, -1.0]))
        return jax.vmap(pair)(h, tokens, a, b)

    with kernel_scope(interpret=False):
        text = jax.jit(scores).lower(
            on_chip((1, 2, 4096, hidden), dtype),
            on_chip((1, 4096), jnp.int32),
            on_chip(w_shape, dtype), on_chip((1, w_shape[0], 1), jnp.float32),
            on_chip((1, w_shape[1], 1), jnp.float32)).compile().as_text()
    entry = text.split("ENTRY")[1]
    calls = [line for line in entry.splitlines()
             if "tpu_custom_call" in line]
    assert len(calls) == 1 and "next_token_scores" in calls[0]
    layout = "bf16" if dtype == jnp.bfloat16 else "f32"
    assert f"{layout}[{w_shape[0]},{w_shape[1]}]{{1,0}}" in calls[0]
    # no logits block, and no stack of W, anywhere in the program
    assert not re.search(rf"f32\[[\d,]*512,{vocab}\]", text)
    assert not re.search(rf"\[\d+,{w_shape[0]},{w_shape[1]}\]", text)


def _looped_engine_on(devices, model_shards, head_dim, length, latent=False,
                      population_size=4):
    """A small looped model's sharded engine on a mesh of described TPU
    ``devices``: its pieces from an ES built on the CPU, as the engine of
    a chip run would get them.  ``latent``: a small sparse-expert model
    with latent attention instead, heads ``head_dim`` + 64 shared wide."""
    from estorch_tpu.envs import TokenScoreEnv
    from estorch_tpu.models import LoopedLM, MoELM
    from estorch_tpu.parallel.mesh import hyperscale_mesh
    from estorch_tpu.parallel.sharded import ShardedESEngine

    sizes = dict(vocab_size=256, hidden_size=128, intermediate_size=256,
                 num_attention_heads=2, attention_block=128, head_block=128)
    es = _es(
        policy=MoELM if latent else LoopedLM,
        population_size=population_size, sigma=0.02,
        policy_kwargs=dict(
            layer_types=("moe",), moe_intermediate_size=64, q_lora_rank=32,
            kv_lora_rank=32, qk_nope_head_dim=head_dim, qk_rope_head_dim=64,
            v_head_dim=head_dim, n_routed_experts=4, **sizes) if latent
        else dict(
            layer_types=("full_attention",), num_key_value_heads=1,
            head_dim=head_dim, total_ut_steps=2, **sizes),
        agent_kwargs={"env": TokenScoreEnv(
            vocab_size=256, seq_len=length, corpus_sequences=4)},
        shard_params=True, low_rank=1, noise_mode="table",
        compute_dtype="bfloat16", table_size=1 << 18,
        device=jax.devices()[:1])
    assert es.engine.kernel_facts["attention_form"] == "xla"  # a CPU mesh
    lr_apply, lr_spec = es._perturbed_form(
        jax.ShapeDtypeStruct((es._spec.dim,), jnp.float32))
    engine = ShardedESEngine(
        es.env, es._policy_apply, es._spec, es.table, es.optimizer,
        es.config, hyperscale_mesh(model_shards=model_shards, devices=devices),
        partition_rules=es._partition_rules, noise_mode="table",
        perturbed_apply=lr_apply, lowrank_spec=lr_spec,
        policy=declaration_of(es.module))
    return es, engine


@pytest.mark.parametrize("n_devices, model_shards, head_dim, length, form", [
    (1, 1, 128, 256, "kernel"),
    (1, 1, 64, 256, "xla"),     # granite's head: half a lane tile
    (1, 1, 128, 200, "xla"),    # no block of the kernel divides it
    # granite's mesh: the centre gathered, whole members on each chip
    (4, 2, 128, 256, "kernel"),
    (4, 2, 64, 256, "xla"),     # and granite's heads on it
    (4, 1, 128, 256, "xla"),    # nothing to gather: the centre split
])
def test_attention_rule_on_a_tpu_mesh(n_devices, model_shards, head_dim,
                                      length, form, v5e_2x2):
    """``ShardedESEngine.attention_form`` on meshes of TPU devices, each
    arm of the rule (whole members on a chip: one device, or several with
    the centre gathered; ``head_dim``; the sequence) — what a chip run
    resolves, with nothing compiled."""
    _, engine = _looped_engine_on(v5e_2x2[:n_devices], model_shards,
                                  head_dim, length)
    assert engine.kernel_facts["attention_form"] == form


@pytest.mark.parametrize("n_devices, model_shards, head_dim, length, form", [
    (1, 1, 128, 512, "kernel"),
    (1, 1, 128, 256, "xla"),    # no row tile of the head's divides it
    # the head's rule is its own: heads the attention's kernel turns away
    (1, 1, 64, 512, "kernel"),
    (4, 2, 128, 512, "kernel"),  # granite's mesh: the centre gathered
    (4, 2, 64, 512, "kernel"),   # granite's mesh and granite's heads
    (4, 2, 64, 256, "xla"),
    (4, 1, 128, 512, "xla"),     # the centre split: no whole member a chip
])
def test_head_rule_on_a_tpu_mesh(n_devices, model_shards, head_dim, length,
                                 form, v5e_2x2):
    """``ShardedESEngine.head_form`` on meshes of TPU devices: the kernel
    wherever Mosaic kernels may be traced (one device, or several with the
    centre gathered) and the head's own shapes fit (hidden 128 here),
    whatever form the attention takes; the XLA form on every other."""
    _, engine = _looped_engine_on(v5e_2x2[:n_devices], model_shards,
                                  head_dim, length)
    assert engine.kernel_facts["head_form"] == form
    assert engine.kernels_traced == (n_devices == 1 or model_shards == 2)


def test_on_a_2x2_mesh_a_chips_head_call_holds_its_own_members(v5e_2x2):
    """The generation program of a small looped model with granite's heads
    (64 wide, values of 64) compiled for a described v5e 2x2, ``(pop 2,
    model 2)``, the centre gathered, eight pairs: Mosaic compiles the
    head's kernel inside the engine's ``shard_map`` over the pairs; ONE
    call, under es.head inside es.policy, whose rows are the two pairs of
    ONE chip x 2 signs x 512 positions (a replicated call would hold all
    eight pairs' 8,192) and whose ``W`` is the whole gathered leaf; the
    attention beside it stays in the XLA form; and the partition adds no
    collective: what crosses the chips before the evaluation is the
    centre's gather, as without a kernel."""
    es, engine = _looped_engine_on(v5e_2x2, 2, 64, 512, population_size=16)
    assert (engine.centre_form, engine.kernel_facts["attention_form"],
            engine.kernel_facts["head_form"]) == (
        "gathered", "xla", "kernel")
    assert "4 TPU devices, whole members on each" in engine.kernel_facts[
        "head_form_why"]
    assert (engine.pair_chunk, engine.n_pair_chunks) == (8, 1)
    text = _compiled_generation(es, engine)
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    # the one Mosaic call of the program: no attention kernel beside it
    assert len(calls) == 1 and "next_token_scores" in calls[0]
    assert "bf16[2048,128]" in calls[0] and "bf16[128,256]" in calls[0]
    assert "8192" not in calls[0]
    name, = re.findall(r'op_name="([^"]*)"', calls[0])
    assert SCOPE.findall(name)[-2:] == [POLICY, HEAD], name
    assert PART.findall(name) == ["head"], name
    moved = _collectives_by_computation(text)
    assert not moved["in a loop"], moved["in a loop"]
    assert not [c for cs in moved.values() for c in cs if 512 in c[2]]


@pytest.mark.parametrize("latent", [False, True], ids=["looped", "latent"])
def test_kernel_form_books_the_heads_kernel_to_head(latent, v5e_chip):
    """On a one-device TPU mesh, over 512 positions, the engine's scope
    gives the head its kernel too: the compiled generation program holds
    the Mosaic call ``next_token_scores`` under es.head inside es.policy,
    in the part of the head's leaf, where the XLA form's logits were: the
    device trace books it to ``loop.head_share`` / ``moe.head_share`` and
    to ``part.head_flops_util`` (two calls for the latent model: the main
    head and the MTP head)."""
    es, engine = _looped_engine_on([v5e_chip], 1, 128, 512, latent=latent)
    assert (engine.kernel_facts["attention_form"],
            engine.kernel_facts["head_form"]) == ("kernel", "kernel")
    state = jax.tree_util.tree_map(
        lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
        es.state, engine.state_shardings)
    table = jax.ShapeDtypeStruct(es.table.data.shape, es.table.data.dtype,
                                 sharding=engine._repl)
    text = engine._generation_step.lower(state, table).compile().as_text()
    heads = [name for line in text.splitlines()
             if "tpu_custom_call" in line and "next_token_scores" in line
             for name in re.findall(r'op_name="([^"]*)"', line)]
    assert len(heads) == (2 if latent else 1), heads
    for name in heads:
        assert SCOPE.findall(name)[-2:] == [POLICY, HEAD], name
        assert PART.findall(name) == ["head"], name


@pytest.mark.parametrize("latent", [False, True], ids=["looped", "latent"])
def test_kernel_form_books_its_kernel_to_attn(latent, v5e_chip):
    """On a one-device TPU mesh the engine takes the attention kernel by
    itself, and the Mosaic custom call of the compiled generation program
    sits under es.attn inside es.policy, where the XLA form's score
    fusions were: the device trace books it to ``loop.attn_share``
    (``moe.attn_share`` for latent attention, whose widths 128 + 64 shared
    the rule is told by the model)."""
    es, engine = _looped_engine_on([v5e_chip], 1, 128, 256, latent=latent)
    assert engine.kernel_facts["attention_form"] == "kernel"
    state = jax.tree_util.tree_map(
        lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
        es.state, engine.state_shardings)
    table = jax.ShapeDtypeStruct(es.table.data.shape, es.table.data.dtype,
                                 sharding=engine._repl)
    text = engine._generation_step.lower(state, table).compile().as_text()
    # (a sparse-expert program's grouped matmuls are custom calls too)
    kernels = [(name, SCOPE.findall(name)) for line in text.splitlines()
               if "tpu_custom_call" in line and "ragged" not in line
               for name in re.findall(r'op_name="([^"]*)"', line)]
    assert kernels and all(
        stack[-2:] == [POLICY, ATTN] and "causal_attention" in name
        for name, stack in kernels), kernels


def _sambay_engine_on(chip, **policy_over):
    """A small SambaY decoder's ES on the CPU and its sharded engine on the
    one-device mesh of ``chip``, built as ``algo/es.py`` builds it."""
    from estorch_tpu.envs import TokenScoreEnv
    from estorch_tpu.models import SambaYLM
    from estorch_tpu.parallel.mesh import hyperscale_mesh
    from estorch_tpu.parallel.sharded import ShardedESEngine

    es = _es(
        policy=SambaYLM, population_size=4, sigma=0.02,
        policy_kwargs={**dict(
            vocab_size=256, hidden_size=256, intermediate_size=256,
            num_attention_heads=4, num_key_value_heads=2, published_layers=8,
            layer_indices=(0, 1, 4, 5, 6, 7), sliding_window=64,
            mamba_d_state=4, mamba_dt_rank=8, scan_chunk=16,
            attention_block=128, head_block=128), **policy_over},
        agent_kwargs={"env": TokenScoreEnv(
            vocab_size=256, seq_len=256, corpus_sequences=4)},
        shard_params=True, low_rank=1, noise_mode="table",
        compute_dtype="bfloat16", table_size=1 << 18,
        device=jax.devices()[:1])
    lr_apply, lr_spec = es._perturbed_form(
        jax.ShapeDtypeStruct((es._spec.dim,), jnp.float32))
    engine = ShardedESEngine(
        es.env, es._policy_apply, es._spec, es.table, es.optimizer,
        es.config, hyperscale_mesh(model_shards=1, devices=[chip]),
        partition_rules=es._partition_rules, noise_mode="table",
        perturbed_apply=lr_apply, lowrank_spec=lr_spec,
        policy=declaration_of(es.module))
    return es, engine


def _collectives_by_computation(compiled_text) -> dict:
    """``{computation name: [(kind, dtype, shape)]}`` of a compiled program,
    and under ``"in a loop"`` those of every computation a ``while`` body
    reaches."""
    import re

    from conftest import collectives

    blocks = re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \([^\n]*\) -> )",
                      compiled_text)
    found, calls = {}, {}
    for block in blocks:
        head = block.split("(", 1)[0].split()
        name = head[-1].lstrip("%") if head else ""
        found[name] = collectives(block)
        calls[name] = set(re.findall(
            r"(?:calls|body|condition|to_apply)=%([\w.\-]+)", block))
    reach = set(re.findall(r"body=%([\w.\-]+)", compiled_text))
    todo = list(reach)
    while todo:
        for callee in calls.get(todo.pop(), ()):
            if callee not in reach:
                reach.add(callee)
                todo.append(callee)
    assert reach, "the program has no loop"
    found["in a loop"] = [c for name in reach for c in found.get(name, [])]
    return found


def test_a_gathered_centre_crosses_the_chips_once_in_the_compute_dtype(
        centre_form, v5e_2x2, monkeypatch):
    """The generation program of a small looped model compiled for a
    described v5e 2x2, ``(pop 2, model 2)``, eight pairs in two chunks.
    ``gathered``: every factored leaf the rules split is all-gathered ONCE,
    whole, in bfloat16 (the cast stays in front of the gather), no float32
    gather of a whole leaf, and NOTHING crosses the chips inside a loop:
    the chunk scan evaluates whole members.  ``split``, the same engine
    with no room on the chip: the loop holds the tensor-parallel forward's
    collectives over activations."""
    from estorch_tpu.parallel import sharded

    length = 384  # no leaf has a side of it
    one = _looped_engine_on(v5e_2x2[:1], 1, 128, length,
                            population_size=16)[1]
    # a budget of one pair's widest activations: a pair a chip a chunk
    monkeypatch.setattr(sharded, "ACTIVATION_BUDGET_BYTES",
                        2 * 4 * one._widest_activation())
    es, engine = _looped_engine_on(v5e_2x2, 2, 128, length,
                                   population_size=16)
    assert engine.centre_form == centre_form
    # a pair a chip at full width, or two a ``pop`` shard at half of it
    assert (engine.pair_chunk, engine.n_pair_chunks) == (4, 2)
    moved = _collectives_by_computation(_compiled_generation(es, engine))
    everywhere = [c for name, cs in moved.items() if name != "in a loop"
                  for c in cs]
    whole = {shape for shape in engine.leaf_shapes if len(shape) == 2}
    split_leaves = sorted(
        engine.leaf_shapes[i] for i, _, _, _, _ in engine.lr_spec.lr_leaves
        if not engine._param_sharding_leaves[i].is_fully_replicated)
    assert len(split_leaves) >= 6
    gathers = [(dtype, shape) for kind, dtype, shape in everywhere
               if kind == "all-gather" and shape in whole]
    if centre_form == "split":
        assert not gathers
        assert any(length in shape for _, _, shape in moved["in a loop"])
        return
    assert sorted(shape for dtype, shape in gathers
                  if dtype == "bf16") == split_leaves
    assert not [g for g in gathers if g[0] != "bf16"]
    assert not moved["in a loop"], moved["in a loop"]
    assert not [c for c in everywhere if length in c[2]]


def _compiled_generation(es, engine) -> str:
    state = jax.tree_util.tree_map(
        lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
        es.state, engine.state_shardings)
    table = jax.ShapeDtypeStruct(es.table.data.shape, es.table.data.dtype,
                                 sharding=engine._repl)
    return engine._generation_step.lower(state, table).compile().as_text()


def test_kernel_form_books_a_differential_pairs_kernel_by_its_kind(v5e_chip):
    """A small SambaY decoder with differential heads of 64 on a
    one-device TPU mesh: the rule takes its pairs (two score heads a column
    block over ONE value block of 128), the engine says which kind of
    attention layer took which form, and the compiled generation program
    holds the Mosaic call under es.attn inside es.policy in the parts of
    the two full-causal kinds, ``of.full`` and ``of.cross``, and none in
    ``of.window``, whose calls stay in the XLA form: the device trace books
    each to ``sambay.full_attn_share`` / ``sambay.window_attn_share`` as
    before."""
    es, engine = _sambay_engine_on(v5e_chip)
    assert dict(es.module.declaration().kernels)[attention_facts][0] == (
        64, 0, 128)
    assert es.engine.kernel_facts["attention_form_by_kind"] == (
        "window:xla,full_kv:xla,cross:xla")        # a CPU mesh
    assert engine.kernel_facts["attention_form"] == "kernel"
    assert engine.kernel_facts["attention_form_why"] == (
        "one TPU device, two score heads a column block, whole row blocks; "
        "layers with a window of 64 in the XLA form")
    assert engine.kernel_facts["attention_form_by_kind"] == (
        "window:xla,full_kv:kernel,cross:kernel")
    text = _compiled_generation(es, engine)
    kernels = [name for line in text.splitlines()
               if "tpu_custom_call" in line and "causal_attention" in line
               for name in re.findall(r'op_name="([^"]*)"', line)]
    assert sorted(PART.findall(name)[0] for name in kernels) == [
        "cross", "full"], kernels
    for name in kernels:
        assert SCOPE.findall(name)[-2:] == [ATTN, ATTN], name
        assert SCOPE.findall(name)[0] == POLICY, name
    # the windowed layer's scores are XLA's: float32, under its own part
    assert any(PART_PREFIX + "window" in line and "f32[" in line
               and "exponential" in line for line in text.splitlines())


@pytest.mark.parametrize("d_inner, d_state, length, a_batched", [
    (5120, 16, 8192, True), (5120, 16, 8192, False), (640, 8, 512, True),
], ids=["sambay_cell", "centre", "narrow_blocks"])
def test_scan_kernel_compiles_for_the_v5e_at_the_cells_shapes(
        d_inner, d_state, length, a_batched, v5e_chip):
    """Mosaic accepts the selective scan's kernel at
    ``phi4-flash-es-8k-1chip``'s shapes (one member of 8,192 steps x 5,120
    channels x 16 states in float32 through the engine's pair x sign
    ``vmap``s, ``A`` a perturbed leaf and so batched, or not) and at a width
    only the narrowest channel block divides, eight states.  ONE custom
    call; ``x``, ``Δ`` and ``y`` reach and leave it as they are (no padded
    or transposed copy of a ``[T, d_inner]`` operand anywhere in the
    program)."""
    from jax.sharding import SingleDeviceSharding

    from estorch_tpu.ops import pallas_scan

    def on_chip(*shape):
        return jax.ShapeDtypeStruct((1, 1) + shape, jnp.float32,
                                    sharding=SingleDeviceSharding(v5e_chip))

    def scan(*operands):
        return pallas_scan.selective_scan(*operands, interpret=False)

    axes = (0, 0, 0 if a_batched else None, 0, 0)
    a = (on_chip(d_inner, d_state) if a_batched else jax.ShapeDtypeStruct(
        (d_inner, d_state), jnp.float32,
        sharding=SingleDeviceSharding(v5e_chip)))
    compiled = jax.jit(jax.vmap(jax.vmap(scan, in_axes=axes),
                                in_axes=axes)).lower(
        on_chip(length, d_inner), on_chip(length, d_inner), a,
        on_chip(length, d_state), on_chip(length, d_state)).compile()
    text = compiled.as_text()
    entry = text.split("ENTRY")[1]
    calls = [line for line in entry.splitlines()
             if "tpu_custom_call" in line]
    assert len(calls) == 1 and "selective_scan" in calls[0]
    wide = [line for line in entry.splitlines()
            if re.search(rf"= f32\[(1,1,)?{length},{d_inner}\]\S* "
                         r"(copy|transpose|pad|fusion)\(", line)]
    assert wide == [], wide
    # the program holds its operands and y, and nothing as large beside them
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 4 * length * d_inner // 8


@pytest.mark.parametrize("tokens, hidden, held, rows", [
    (16384, 2560, 16, 30720), (32768, 2048, 8, 20480),
    (16384, 2048, 16, 20480), (65536, 2048, 16, 40960),
], ids=["smallthinker", "zaya1", "keye", "joyai"])
def test_combine_kernel_compiles_for_the_v5e_at_the_cells_shapes(
        tokens, hidden, held, rows, v5e_chip):
    """Mosaic accepts the combine's kernel at the four expert cells' shapes
    (the merged members' float32 token rows, a pass of ``rows`` rows over
    ``held`` experts; the tokens and the weights of a pass as scalars: 327
    KB of SMEM at the widest).  ONE custom call, ``y`` aliased in and out:
    the program holds no second ``[tokens, hidden]`` buffer, and nothing
    beside its operands but the run bounds."""
    from jax.sharding import SingleDeviceSharding

    from estorch_tpu.ops import pallas_combine

    def on_chip(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=SingleDeviceSharding(v5e_chip))

    def combine(y, out, w, token, key, first):
        return pallas_combine.combine_rows(y, out, w, token, key, first,
                                           held=held, interpret=False)

    compiled = jax.jit(combine, donate_argnums=0).lower(
        on_chip((tokens, hidden)), on_chip((rows, hidden)), on_chip((rows,)),
        on_chip((rows,), jnp.int32), on_chip((rows,), jnp.int32),
        on_chip((), jnp.bool_)).compile()
    entry = compiled.as_text().split("ENTRY")[1]
    calls = [line for line in entry.splitlines()
             if "tpu_custom_call" in line]
    assert len(calls) == 1 and "combine_rows" in calls[0]
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == 4 * tokens * hidden
    assert memory.temp_size_in_bytes < 4 * tokens * hidden // 64


def test_kernel_form_books_the_scans_kernel_to_ssm(v5e_chip):
    """A small SambaY decoder (``d_inner`` 512 over 256 steps: one channel
    block, one time chunk) on a one-device TPU mesh: the engine resolves
    ``scan_form`` to "kernel" by itself, and the compiled generation
    program holds the Mosaic call ``selective_scan`` once a Mamba layer
    under es.ssm inside es.policy, where the device trace books it
    (``sambay.ssm_share``, ``sambay.ssm_hbm_util``); no ``while`` loop is
    left under es.ssm."""
    es, engine = _sambay_engine_on(v5e_chip)
    assert es.engine.kernel_facts["scan_form"] == "xla"            # a CPU mesh
    assert (engine.kernel_facts["attention_form"],
            engine.kernel_facts["scan_form"]) == ("kernel", "kernel")
    text = _compiled_generation(es, engine)
    kernels = [name for line in text.splitlines()
               if "tpu_custom_call" in line and "selective_scan" in line
               for name in re.findall(r'op_name="([^"]*)"', line)]
    assert len(kernels) == 2, kernels
    for name in kernels:
        assert SCOPE.findall(name)[0] == POLICY, name
        assert SCOPE.findall(name)[-1] == SSM, name
    assert not [line for line in text.splitlines()
                if " while(" in line and SCOPE.findall(line)[-1:] == [SSM]]


def test_a_scan_the_rule_refuses_stays_a_loop_on_the_chip(v5e_chip):
    """The same decoder with 24 states a channel: attention and head in
    their kernels, the scans in the ``lax.scan`` (a ``while`` under
    es.ssm), and the engine says so."""
    es, engine = _sambay_engine_on(v5e_chip, mamba_d_state=24)
    assert (engine.kernel_facts["attention_form"],
            engine.kernel_facts["scan_form"]) == ("kernel", "xla")
    text = _compiled_generation(es, engine)
    assert not [line for line in text.splitlines()
                if "tpu_custom_call" in line and "selective_scan" in line]
    assert [line for line in text.splitlines()
            if " while(" in line and SCOPE.findall(line)[-1:] == [SSM]]


# ------------------------------------------ the gated delta rule's kernels

# the delta rule's own parts of es.ssm, and every part of it
RULE_PARTS = ("solve", "carry")
SSM_PARTS = ("conv", "decay", "gate") + RULE_PARTS


def _delta_es(**policy_over):
    """A linear and a full layer with linear heads of 128 over 128
    positions in chunks of 64 (the widths the delta rule's kernels take:
    one tile of two chunks), as ``algo/es.py`` builds it on the CPU."""
    from estorch_tpu.envs import TokenScoreEnv
    from estorch_tpu.models import DeltaMoELM

    return _es(
        policy=DeltaMoELM, population_size=4, sigma=0.02,
        policy_kwargs={**dict(
            layer_types=("linear", "full"), vocab_size=256, hidden_size=128,
            moe_intermediate_size=32, shared_expert_intermediate_size=32,
            linear_num_key_heads=1, linear_num_value_heads=2,
            linear_key_head_dim=128, linear_value_head_dim=128,
            num_attention_heads=2, num_key_value_heads=1, head_dim=128,
            num_experts=4, num_experts_per_tok=2, behaviour_positions=16,
            attention_block=128, head_block=128, delta_chunk=64),
            **policy_over},
        agent_kwargs={"env": TokenScoreEnv(
            vocab_size=256, seq_len=128, corpus_sequences=4)},
        shard_params=True, low_rank=1, noise_mode="table",
        compute_dtype="bfloat16", table_size=1 << 18,
        device=jax.devices()[:1])


def _delta_engine_on(chip, **policy_over):
    """:func:`_delta_es` and its sharded engine on the one-device mesh of
    ``chip``, built as ``algo/es.py`` builds it."""
    from estorch_tpu.parallel.mesh import hyperscale_mesh
    from estorch_tpu.parallel.sharded import ShardedESEngine

    es = _delta_es(**policy_over)
    lr_apply, lr_spec = es._perturbed_form(
        jax.ShapeDtypeStruct((es._spec.dim,), jnp.float32))
    engine = ShardedESEngine(
        es.env, es._policy_apply, es._spec, es.table, es.optimizer,
        es.config, hyperscale_mesh(model_shards=1, devices=[chip]),
        partition_rules=es._partition_rules, noise_mode="table",
        perturbed_apply=lr_apply, lowrank_spec=lr_spec,
        policy=declaration_of(es.module))
    return es, engine


def _ssm_parts(names) -> list:
    """The parts, ``[]`` where it names none, of every operation whose
    innermost stage is es.ssm."""
    return [PART.findall(n) for n in names
            if SCOPE.findall(n)[-1:] == [SSM]]


def test_kernel_form_books_the_delta_rule_to_its_two_parts(
        kernel_attention, keyed_by_source):
    """The delta case at widths that fit, in a kernel scope under the
    interpreter: the engine says ``delta_form`` "kernel" (gauge and
    manifest too), BOTH ``of.solve`` and ``of.carry`` hold operations
    beneath es.ssm (``benchmark/layers/gdn.py`` returns nothing for a
    program without ``of.solve``, and divides the rule's two rooflines by
    the seconds of the two), no operation of es.ssm is without a part, and
    no ``while`` of a ``lax.scan`` over the chunks is left in ``of.carry``
    but the interpreter's grid loop."""
    with kernel_attention():
        es = _delta_es()
    engine = es.engine
    assert (engine.kernel_facts["delta_form"],
            es.obs.counters.get("delta_form"),
            es.run_manifest()["config"]["delta_form"]) == ("kernel",) * 3
    text = engine._generation_step.lower(
        es.state, engine.table.data).as_text(debug_info=True)
    names = re.findall(r'loc\("(jit\([^"]*)"', text)
    parts = _ssm_parts(names)
    assert parts and all(len(p) == 1 and p[0] in SSM_PARTS for p in parts), (
        sorted({tuple(p) for p in parts}))
    for part in RULE_PARTS:
        # (the lowered text names a jitted call by its function)
        kernel = {"solve": "solve_chunks", "carry": "chain_chunks"}[part]
        assert any(p == [part] and kernel in n for p, n in zip(
            parts, (n for n in names if SCOPE.findall(n)[-1:] == [SSM])))
    # the pad and the head-major moveaxis of the XLA form's ``chunked()``
    # are not run: no transpose of es.ssm outside the decay rows' (solve)
    assert not [n for n in names if SCOPE.findall(n)[-1:] == [SSM]
                and "transpose" in n and PART.findall(n) != ["solve"]]


def test_the_delta_case_at_tiny_widths_says_xla():
    """The suite's tiny model (heads of 8 in chunks of 8) builds the XLA
    form whatever the scope, and says so."""
    es = _sequence_es(SEQUENCE_MODELS["delta"])
    assert (es.engine.kernel_facts["delta_form"],
            es.obs.counters.get("delta_form"),
            es.run_manifest()["config"]["delta_form"]) == ("xla",) * 3


@pytest.mark.parametrize("length, nk, nv, dk, dv, chunk", [
    (16384, 16, 32, 128, 128, 64), (4096, 4, 4, 256, 128, 128),
    (4096, 2, 8, 128, 256, 16)], ids=["qwen3next", "wide_keys",
                                      "wide_values"])
def test_delta_kernels_compile_for_the_v5e_at_the_cells_shapes(
        length, nk, nv, dk, dv, chunk, v5e_chip):
    """Mosaic accepts the two kernels at the Qwen3-Next cell's shapes
    (16,384 positions, 16 key and 32 value heads of 128 x 128, chunks of
    64) and at the rule's edges (heads of two lane blocks, one and four
    value heads a key head, the widest and the narrowest chunk) under the
    engine's ``vmap`` of one member: TWO custom calls, no ``[T,
    nv·dv]``-sized copy, pad or transpose beside them (q, k, v stay ``[T,
    heads · dim]``), and nothing held but ``W``, ``U`` and the decay
    rows."""
    from jax.sharding import SingleDeviceSharding

    from estorch_tpu.ops import pallas_delta


    def on_chip(*shape):
        return jax.ShapeDtypeStruct((1,) + shape, jnp.float32,
                                    sharding=SingleDeviceSharding(v5e_chip))

    def rule(q, k, v, g, beta):
        rows = pallas_delta.decay_rows(g, beta, nk, chunk)
        w, u = pallas_delta.solve_chunks(
            k.reshape(length, nk, dk), v.reshape(length, nv, dv), rows,
            chunk=chunk, interpret=False)
        return pallas_delta.chain_chunks(
            q.reshape(length, nk, dk), k.reshape(length, nk, dk), w, u,
            rows, chunk=chunk, interpret=False)

    compiled = jax.jit(jax.vmap(rule)).lower(
        on_chip(length, nk * dk), on_chip(length, nk * dk),
        on_chip(length, nv * dv), on_chip(length, nv),
        on_chip(length, nv)).compile()
    entry = compiled.as_text().split("ENTRY")[1]
    calls = [line for line in entry.splitlines()
             if "tpu_custom_call" in line]
    assert len(calls) == 2
    assert "delta_solve" in calls[0] and "delta_chain" in calls[1]
    wide = [line for line in entry.splitlines()
            if re.search(rf"= f32\[(1,)?{length},({nk * dk}|{nv * dv})\]\S* "
                         r"(copy|transpose|pad|fusion)\(", line)]
    assert wide == [], wide
    # W and U, and the decay rows in their tiles: nothing else is held
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 2.2 * 4 * length * nv * max(dk, dv)


def test_kernel_form_books_the_delta_rules_kernels_on_the_chip(v5e_chip):
    """The same small decoder on a one-device TPU mesh: the engine resolves
    ``delta_form`` to "kernel" by itself, and the compiled generation
    program holds the Mosaic calls ``delta_solve`` under es.ssm in the part
    ``of.solve`` and ``delta_chain`` in ``of.carry``, where the device
    trace books them (``gdn.solve_share``, ``gdn.carry_share``, the rule's
    two rooflines); no ``while`` of the XLA form's chain is left under
    es.ssm."""
    es, engine = _delta_engine_on(v5e_chip)
    assert es.engine.kernel_facts["delta_form"] == "xla"           # a CPU mesh
    assert engine.kernel_facts["delta_form"] == "kernel"
    text = _compiled_generation(es, engine)
    for kernel, part in (("delta_solve", "solve"), ("delta_chain", "carry")):
        names = [name for line in text.splitlines()
                 if "tpu_custom_call" in line and kernel in line
                 for name in re.findall(r'op_name="([^"]*)"', line)]
        assert len(names) == 1, (kernel, names)
        assert SCOPE.findall(names[0])[0] == POLICY, names
        assert SCOPE.findall(names[0])[-1] == SSM, names
        assert PART.findall(names[0]) == [part], names
    assert not [line for line in text.splitlines()
                if " while(" in line and SCOPE.findall(line)[-1:] == [SSM]]


def test_a_chunk_the_rule_refuses_stays_in_xla_on_the_chip(v5e_chip):
    """The same decoder in chunks of 8 (the inverse's base alone): the
    rule's XLA form, a ``while`` under es.ssm, and the engine says so; its
    attention and head keep their kernels."""
    es, engine = _delta_engine_on(v5e_chip, delta_chunk=8)
    assert (engine.kernel_facts["attention_form"],
            engine.kernel_facts["delta_form"]) == ("kernel", "xla")
    text = _compiled_generation(es, engine)
    assert not [line for line in text.splitlines()
                if "tpu_custom_call" in line and "delta_" in line]
    assert [line for line in text.splitlines()
            if " while(" in line and SCOPE.findall(line)[-1:] == [SSM]]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_attention_kernel_compiles_for_the_v5e_under_a_selection(dtype,
                                                                  v5e_chip):
    """Mosaic accepts the attention kernel at ``keye-vl2-es-16k-1chip``'s
    shapes: 32 query heads of 128 over 4 key heads, one member of 16,384
    positions in blocks of 1,024 (the cell evaluates a pair's signs in
    turn), the ``[T, T]`` int8 selection one more operand read a tile at a
    time; nothing else in the program, no copy of the selection."""
    from jax.sharding import SingleDeviceSharding

    from estorch_tpu.ops.pallas_attention import causal_attention

    def operand(width, kind=dtype):
        return jax.ShapeDtypeStruct(
            (1, 16384, width), kind, sharding=SingleDeviceSharding(v5e_chip))

    text = jax.jit(jax.vmap(lambda q, k, v, chosen: causal_attention(
        q, k, v, selected=chosen, num_heads=32, num_kv_heads=4, head_dim=128,
        scale=128 ** -0.5, interpret=False))).lower(
            operand(4096), operand(512), operand(512),
            operand(16384, jnp.int8)).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    entry = text.split("ENTRY")[1]
    assert " copy(" not in entry and "s8[1,16384,16384]" in entry


def test_kernel_form_books_the_selected_attention_by_its_kind(v5e_chip):
    """A small decoder with a learned selection of keys on a one-device TPU
    mesh: the rule takes its heads of 128, the engine says that the selected
    attention took the kernel, and the compiled generation program holds
    the Mosaic call under es.attn inside es.policy in the part
    ``of.selected``, one a layer, beside the head's: the device trace books
    it to ``dsa.attn_share``."""
    from estorch_tpu.envs import TokenScoreEnv
    from estorch_tpu.models import IndexedMoELM
    from estorch_tpu.parallel.mesh import hyperscale_mesh
    from estorch_tpu.parallel.sharded import ShardedESEngine

    es = _es(
        policy=IndexedMoELM, population_size=4, sigma=0.02,
        policy_kwargs=dict(
            layer_types=("moe", "moe"), vocab_size=256, hidden_size=128,
            moe_intermediate_size=64, num_attention_heads=2,
            num_key_value_heads=1, head_dim=128, num_experts=4,
            num_experts_per_tok=2, indexer_num_heads=2, indexer_head_dim=64,
            topk=64, mrope_section=(16, 24, 24), attention_block=128,
            index_block=128, head_block=128),
        agent_kwargs={"env": TokenScoreEnv(
            vocab_size=256, seq_len=512, corpus_sequences=4)},
        shard_params=True, low_rank=1, noise_mode="table",
        compute_dtype="bfloat16", table_size=1 << 18,
        device=jax.devices()[:1])
    assert dict(es.module.declaration().kernels)[attention_facts][0] == 128
    assert es.engine.kernel_facts["attention_form_by_kind"] == (
        "selected:xla")                                        # a CPU mesh
    lr_apply, lr_spec = es._perturbed_form(
        jax.ShapeDtypeStruct((es._spec.dim,), jnp.float32))
    engine = ShardedESEngine(
        es.env, es._policy_apply, es._spec, es.table, es.optimizer,
        es.config, hyperscale_mesh(model_shards=1, devices=[v5e_chip]),
        partition_rules=es._partition_rules, noise_mode="table",
        perturbed_apply=lr_apply, lowrank_spec=lr_spec,
        policy=declaration_of(es.module))
    assert (engine.kernel_facts["attention_form"],
            engine.kernel_facts["head_form"]) == ("kernel", "kernel")
    assert engine.kernel_facts["attention_form_why"] == (
        "one TPU device, whole column blocks, whole row blocks")
    assert engine.kernel_facts["attention_form_by_kind"] == "selected:kernel"
    assert engine.kernel_facts["attention_heads_a_step"].startswith(
        "selected:")
    state = jax.tree_util.tree_map(
        lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
        es.state, engine.state_shardings)
    table = jax.ShapeDtypeStruct(es.table.data.shape, es.table.data.dtype,
                                 sharding=engine._repl)
    text = engine._generation_step.lower(state, table).compile().as_text()
    kernels = [name for line in text.splitlines()
               if "tpu_custom_call" in line and "causal_attention" in line
               for name in re.findall(r'op_name="([^"]*)"', line)]
    assert len(kernels) == 2, kernels
    for name in kernels:
        assert PART.findall(name) == ["selected"], name
        assert SCOPE.findall(name)[0] == POLICY, name
        assert SCOPE.findall(name)[-1] == ATTN, name
    # the index scores and the choice are XLA's, under their own stages
    assert any(SCOPE_PREFIX + INDEX in line and "f32[" in line
               for line in text.splitlines())
    assert any(SCOPE_PREFIX + SELECT in line for line in text.splitlines())


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_attention_kernel_compiles_for_the_v5e_at_the_latents_shapes(
        dtype, v5e_chip):
    """Mosaic accepts the attention kernel at ``zaya1-es-8k-1chip``'s shapes:
    8 query heads over 2 key-value heads of 128 (the latent: q ``[8192,
    1024]``, k and v ``[8192, 256]``), 8,192 positions in blocks of 1,024,
    two pairs' two signs through the engine's ``vmap``s, under the caller's
    scale; nothing else in the program."""
    from jax.sharding import SingleDeviceSharding

    from estorch_tpu.ops.pallas_attention import causal_attention

    def operand(width):
        return jax.ShapeDtypeStruct(
            (2, 2, 8192, width), dtype,
            sharding=SingleDeviceSharding(v5e_chip))

    text = jax.jit(jax.vmap(jax.vmap(lambda q, k, v: causal_attention(
        q, k, v, num_heads=8, num_kv_heads=2, head_dim=128,
        scale=128 ** -0.5, interpret=False)))).lower(
            operand(1024), operand(256), operand(256)).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert " copy(" not in text.split("ENTRY")[1]


def test_kernel_form_books_the_latents_attention_and_the_tied_head(v5e_chip):
    """A small decoder with attention inside a latent on a one-device TPU
    mesh: the rule takes its 8 / 2 heads of 128 and the tied head's hidden
    128 over 512 positions, and the compiled generation program holds one
    Mosaic call ``causal_attention`` a layer under es.attn inside es.policy
    and ONE ``next_token_scores`` under es.head in the part ``of.embed``
    (the tied layout): the device trace books them to ``cca.attn_share``
    and ``cca.head_share``.  The latent's mixing stays XLA's, under
    es.mix."""
    from estorch_tpu.envs import TokenScoreEnv
    from estorch_tpu.models import CCAMoELM
    from estorch_tpu.parallel.mesh import hyperscale_mesh
    from estorch_tpu.parallel.sharded import ShardedESEngine

    es = _es(
        policy=CCAMoELM, population_size=4, sigma=0.02,
        policy_kwargs=dict(
            layer_types=("hybrid", "hybrid"), vocab_size=256,
            hidden_size=128, moe_intermediate_size=64, num_attention_heads=8,
            num_key_value_heads=2, head_dim=128, router_hidden_size=16,
            num_experts=2, expert_group_size=2, behaviour_positions=64,
            attention_block=128, head_block=128),
        agent_kwargs={"env": TokenScoreEnv(
            vocab_size=256, seq_len=512, corpus_sequences=4)},
        shard_params=True, low_rank=1, noise_mode="table",
        compute_dtype="bfloat16", table_size=1 << 18,
        device=jax.devices()[:1])
    assert (es.engine.kernel_facts["attention_form"],
            es.engine.kernel_facts["head_form"]) == ("xla", "xla")
    lr_apply, lr_spec = es._perturbed_form(
        jax.ShapeDtypeStruct((es._spec.dim,), jnp.float32))
    engine = ShardedESEngine(
        es.env, es._policy_apply, es._spec, es.table, es.optimizer,
        es.config, hyperscale_mesh(model_shards=1, devices=[v5e_chip]),
        partition_rules=es._partition_rules, noise_mode="table",
        perturbed_apply=lr_apply, lowrank_spec=lr_spec,
        policy=declaration_of(es.module))
    assert (engine.kernel_facts["attention_form"],
            engine.kernel_facts["head_form"]) == ("kernel", "kernel")
    assert engine.kernel_facts["attention_form_by_kind"] == "causal:kernel"
    assert (es.engine.kernel_facts["combine_form"],
            engine.kernel_facts["combine_form"]) == ("xla", "kernel")
    state = jax.tree_util.tree_map(
        lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
        es.state, engine.state_shardings)
    table = jax.ShapeDtypeStruct(es.table.data.shape, es.table.data.dtype,
                                 sharding=engine._repl)
    text = engine._generation_step.lower(state, table).compile().as_text()

    def calls(kernel):
        return [name for line in text.splitlines()
                if "tpu_custom_call" in line and kernel in line
                for name in re.findall(r'op_name="([^"]*)"', line)]

    kernels = calls("causal_attention")
    assert len(kernels) == 2, kernels
    for name in kernels:
        assert SCOPE.findall(name)[0] == POLICY, name
        assert SCOPE.findall(name)[-1] == ATTN, name
    heads = calls("next_token_scores")
    assert len(heads) == 1, heads
    assert SCOPE.findall(heads[0])[-2:] == [POLICY, HEAD], heads
    assert PART.findall(heads[0]) == ["embed"], heads
    assert any(SCOPE_PREFIX + MIX in line and "f32[" in line
               for line in text.splitlines())
    # the expert layers' combine in its kernel, one call a layer (the
    # loop's passes all go through it), where the scatter-add was: the
    # device trace books it to ``cca.dispatch_share``
    combines = calls("combine_rows")
    assert len(combines) == 2, combines
    for name in combines:
        assert SCOPE.findall(name)[0] == POLICY, name
        assert SCOPE.findall(name)[-1] == DISPATCH, name
    assert not [line for line in text.splitlines()
                if " scatter(" in line and "f32[2048,128]" in line]


@pytest.mark.parametrize("window", [None, 4096], ids=["global", "band4096"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_attention_kernel_compiles_for_the_v5e_in_groups_of_seven(
        dtype, window, v5e_chip):
    """Mosaic accepts the attention kernel at ``smallthinker-es-16k-1chip``'s
    shapes, a grouping no other cell has: 28 query heads over 4 key-value
    heads of 128 (groups of SEVEN: q ``[16384, 3584]``, k and v ``[16384,
    512]``), 16,384 positions in blocks of 1,024, one member at a time as the
    cell's chunk of one pair with its signs in turn evaluates them; nothing
    else in the program.  The global layer's call, and the window layers'
    under their band of 4,096 keys (a key axis of five steps, three folds
    in the body)."""
    from jax.sharding import SingleDeviceSharding

    from estorch_tpu.ops.pallas_attention import causal_attention

    def operand(width):
        return jax.ShapeDtypeStruct(
            (1, 1, 16384, width), dtype,
            sharding=SingleDeviceSharding(v5e_chip))

    text = jax.jit(jax.vmap(jax.vmap(lambda q, k, v: causal_attention(
        q, k, v, num_heads=28, num_kv_heads=4, head_dim=128,
        scale=128 ** -0.5, interpret=False, window=window)))).lower(
            operand(3584), operand(512), operand(512)).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert " copy(" not in text.split("ENTRY")[1]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_attention_kernel_compiles_for_the_v5e_under_half_a_block_of_band(
        dtype, v5e_chip):
    """Mosaic accepts the attention kernel at the sliding layers of
    ``laguna-xs2-es-16k-1chip``, PUBLISHED shapes no other cell has: 64
    query heads over 8 key-value heads of 128 (q ``[16384, 8192]``, k and v
    ``[16384, 1024]``) under a band of 512 keys, half of the kernel's block
    at 16,384 positions, which ``band_block`` makes the block itself: a
    grid of (64 heads, 32 query blocks) with no key axis, the previous and
    the own key block both in the step, inside the default scoped VMEM (the
    compile is the check); no float32 score array and no copy beside it in
    the program, one member at a time as the cell evaluates them."""
    from jax.sharding import SingleDeviceSharding

    from estorch_tpu.ops.pallas_attention import (band_block, call_form,
                                                  causal_attention, fits)

    assert fits(128, 0, 128, None, 16384)
    assert band_block(512, 16384) == 512
    assert call_form("kernel", 512, 16384) == "kernel"

    def operand(width):
        return jax.ShapeDtypeStruct(
            (1, 1, 16384, width), dtype,
            sharding=SingleDeviceSharding(v5e_chip))

    compiled = jax.jit(jax.vmap(jax.vmap(lambda q, k, v: causal_attention(
        q, k, v, num_heads=64, num_kv_heads=8, head_dim=128,
        scale=128 ** -0.5, interpret=False, window=512)))).lower(
            operand(8192), operand(1024), operand(1024)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert " copy(" not in text.split("ENTRY")[1]
    assert compiled.memory_analysis().temp_size_in_bytes == 0


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_attention_kernel_compiles_for_the_v5e_at_heads_of_256(dtype,
                                                               v5e_chip):
    """Mosaic accepts the attention kernel at ``qwen3next-es-16k-1chip``'s
    shapes, which no other cell has: 16 query heads over 2 key-value heads
    of 256 (TWO lane blocks a head, groups of EIGHT: q ``[16384, 4096]``, k
    and v ``[16384, 512]``), 16,384 positions, one member at a time as the
    cell's chunk of one pair with its signs in turn evaluates them; nothing
    else in the program."""
    from jax.sharding import SingleDeviceSharding

    from estorch_tpu.ops.pallas_attention import causal_attention, fits

    assert fits(256, 0, 256, None, 16384)

    def operand(width):
        return jax.ShapeDtypeStruct(
            (1, 1, 16384, width), dtype,
            sharding=SingleDeviceSharding(v5e_chip))

    text = jax.jit(jax.vmap(jax.vmap(lambda q, k, v: causal_attention(
        q, k, v, num_heads=16, num_kv_heads=2, head_dim=256,
        scale=256 ** -0.5, interpret=False)))).lower(
            operand(4096), operand(512), operand(512)).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert " copy(" not in text.split("ENTRY")[1]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("cell, length, heads, kv_heads", [
    ("laguna", 16384, 48, 8), ("zaya1", 8192, 8, 2)])
def test_attention_kernel_compiles_for_the_v5e_a_groups_heads_a_step(
        cell, length, heads, kv_heads, dtype, v5e_chip):
    """Mosaic accepts the causal kernel in the form :func:`step_heads` and
    :func:`diagonal_sub` give it at two cells' PUBLISHED shapes that no
    other case compiles: the full layers of ``laguna-xs2-es-16k-1chip`` (48
    query heads over 8 key-value heads of 128, groups of SIX, 16,384
    positions) and ``zaya1-es-8k-1chip``'s (8 over 2, groups of four, 8,192
    positions), one member at a time; the step's heads, their running
    max, sum and accumulator and the float32 tile fit the VMEM the call asks
    for (the compile is the check: a refusal is a tier-1 failure, not a chip
    minute); nothing else in the program."""
    from jax.sharding import SingleDeviceSharding

    from estorch_tpu.ops.pallas_attention import (STEP_VMEM_LIMIT,
                                                  causal_attention,
                                                  key_block, kernel_block,
                                                  step_heads,
                                                  step_vmem_bytes)

    def operand(width):
        return jax.ShapeDtypeStruct(
            (1, 1, length, width), dtype,
            sharding=SingleDeviceSharding(v5e_chip))

    itemsize, group = jnp.dtype(dtype).itemsize, heads // kv_heads
    block = kernel_block(length)
    took = step_heads(group, 128, 128, itemsize, block,
                      key_block(block, 128, itemsize))
    assert took > 1 and heads % took == 0
    assert step_vmem_bytes(took, 128, 128, itemsize, block,
                           block) <= STEP_VMEM_LIMIT
    compiled = jax.jit(jax.vmap(jax.vmap(lambda q, k, v: causal_attention(
        q, k, v, num_heads=heads, num_kv_heads=kv_heads, head_dim=128,
        scale=128 ** -0.5, interpret=False)))).lower(
            operand(heads * 128), operand(kv_heads * 128),
            operand(kv_heads * 128)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert " copy(" not in text.split("ENTRY")[1]
    assert compiled.memory_analysis().temp_size_in_bytes == 0


@pytest.mark.parametrize("window, by_kind, parts", [
    (512, "window:kernel,global:kernel", ["global", "window", "window"]),
    (128, "window:kernel,global:kernel", ["global", "window", "window"]),
    (192, "window:xla,global:kernel", ["global"])])
def test_kernel_form_books_each_kind_of_layer_by_its_part(v5e_chip, window,
                                                          by_kind, parts):
    """A small decoder with a global and two window layers on a one-device
    TPU mesh: the rule takes its 14 / 2 heads of 128 (groups of seven) over
    1,536 positions, three of the kernel's blocks of 512.  Under a band of
    512 keys (one block: the band's rule, ``pallas_attention.call_form``)
    the compiled generation program holds THREE Mosaic calls
    ``causal_attention`` under es.attn inside es.policy, the global
    layer's in the part ``of.global`` and the window layers' in
    ``of.window``, and under 128 keys too (a quarter of a block, whole
    128-lane rows that divide the sequence: the band is the block, both key
    blocks in one grid step); under 192 keys only the global layer's, and
    the window layers' float32 scores are XLA's: the device trace books them to
    ``swa.global_attn_share`` and ``swa.window_attn_share`` either way.
    The head takes its kernel beside them."""
    from estorch_tpu.envs import TokenScoreEnv
    from estorch_tpu.models import WindowMoELM
    from estorch_tpu.parallel.mesh import hyperscale_mesh
    from estorch_tpu.parallel.sharded import ShardedESEngine

    es = _es(
        policy=WindowMoELM, population_size=4, sigma=0.02,
        policy_kwargs=dict(
            layer_types=("global", "window", "window"), vocab_size=256,
            hidden_size=128, moe_ffn_hidden_size=64,
            sliding_window_size=window,
            num_attention_heads=14, num_key_value_heads=2, head_dim=128,
            moe_num_primary_experts=2, expert_group_size=2,
            moe_num_active_primary_experts=2, behaviour_positions=64,
            attention_block=128, head_block=128),
        agent_kwargs={"env": TokenScoreEnv(
            vocab_size=256, seq_len=1536, corpus_sequences=4)},
        shard_params=True, low_rank=1, noise_mode="table",
        compute_dtype="bfloat16", table_size=1 << 18,
        device=jax.devices()[:1])
    assert es.engine.kernel_facts["attention_form_by_kind"] == (
        "window:xla,global:xla")
    lr_apply, lr_spec = es._perturbed_form(
        jax.ShapeDtypeStruct((es._spec.dim,), jnp.float32))
    engine = ShardedESEngine(
        es.env, es._policy_apply, es._spec, es.table, es.optimizer,
        es.config, hyperscale_mesh(model_shards=1, devices=[v5e_chip]),
        partition_rules=es._partition_rules, noise_mode="table",
        perturbed_apply=lr_apply, lowrank_spec=lr_spec,
        policy=declaration_of(es.module))
    assert (engine.kernel_facts["attention_form"],
            engine.kernel_facts["head_form"]) == ("kernel", "kernel")
    assert engine.kernel_facts["attention_form_by_kind"] == by_kind
    # a group's SEVEN heads share a grid step in every kernel kind's calls:
    # the causal grid's, the band's of one block and the band as the block
    assert engine.kernel_facts["attention_heads_a_step"] == (
        "global:7" if window == 192 else "window:7,global:7")
    assert engine.build_facts()["attention_heads_a_step"] == (
        engine.kernel_facts["attention_heads_a_step"])
    assert engine.kernel_facts["attention_form_why"].endswith(
        f"layers with a window of {window} in the "
        + ("XLA form" if window == 192 else "kernel"))
    state = jax.tree_util.tree_map(
        lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
        es.state, engine.state_shardings)
    table = jax.ShapeDtypeStruct(es.table.data.shape, es.table.data.dtype,
                                 sharding=engine._repl)
    text = engine._generation_step.lower(state, table).compile().as_text()

    def calls(kernel):
        return [name for line in text.splitlines()
                if "tpu_custom_call" in line and kernel in line
                for name in re.findall(r'op_name="([^"]*)"', line)]

    kernels = calls("causal_attention")
    assert sorted(PART.findall(name)[0] for name in kernels) == parts, kernels
    for name in kernels:
        assert SCOPE.findall(name)[0] == POLICY, kernels
        assert SCOPE.findall(name)[-1] == ATTN, kernels
    heads = calls("next_token_scores")
    assert len(heads) == 1 and PART.findall(heads[0]) == ["head"], heads
    # where the window layers stay in the XLA form their scores are XLA's,
    # under es.attn in their own part; in the kernel no float32 [.., T]
    # score array of theirs is left
    banded = [n for line in text.splitlines() if "f32[" in line
              for n in re.findall(r'op_name="([^"]*)"', line)
              if PART.findall(n) == ["window"] and ATTN in SCOPE.findall(n)]
    assert bool(banded) == (window == 192)


@pytest.mark.parametrize("use", ["context", "decorator"])
def test_stage_scopes_a_name_stack(use):
    assert len(set(STAGES)) == len(STAGES) == 23
    assert (SAMPLE, NOISE, PERTURB, POLICY, ENV, GATHER, RANK, GRAD,
            UPDATE, DENSE, SSM, ATTN, HEAD, ROPE, EXIT, ROUTE, DISPATCH,
            EXPERT, GMU, DIFF, INDEX, SELECT, MIX) == STAGES
    if use == "context":
        def f(x):
            with stage(NOISE):
                return x * 2.0
    else:
        @stage(NOISE)
        def f(x):
            return x * 2.0
    text = jax.jit(f).lower(1.0).as_text(debug_info=True)
    assert SCOPE_PREFIX + NOISE in text
    with pytest.raises(ValueError, match="unknown stage"):
        stage("forward")


def test_part_scopes_a_name_stack_beneath_a_stage():
    def f(x):
        with stage(DENSE), part("shared"), part("gate"):
            return x * 2.0

    text = jax.jit(f).lower(1.0).as_text(debug_info=True)
    assert (SCOPE_PREFIX + DENSE + "/" + PART_PREFIX + "shared/"
            + PART_PREFIX + "gate") in text
    # a part's prefix is its own: no stage scope can be read out of one
    assert not SCOPE.findall(PART_PREFIX + DENSE)
    assert not PART_PREFIX.startswith(SCOPE_PREFIX)
    for bad in ("", "a/b", "x y", ".gate", "gate.", "es.dense/x"):
        with pytest.raises(ValueError, match="parameter leaf"):
            part(bad)


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    path = max(trace_dir.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    events = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats)
                if "generation" in stats:
                    events.append((e.start_ns, e.start_ns + e.duration_ns,
                                   e.name, int(stats["generation"])))
    return sorted(events)


def test_phases_are_spans_in_the_profilers_trace(tmp_path):
    es = _es()
    es.train(1, verbose=False)      # compile outside the trace
    first = es.obs.generation
    nested = Telemetry()
    with trace(str(tmp_path)):
        es.train(2, verbose=False)
        with nested.phase("update"):
            with nested.phase("obsnorm_merge"):
                pass
    events = _host_events(tmp_path)
    ours = [e for e in events if e[2] in PHASES]
    # dispatch, device, host_sync, record of each generation, in that
    # order, none overlapping the next, each carrying its generation
    assert [e[2] for e in ours] == list(PHASES) * 2
    assert [e[3] for e in ours] == [first] * 4 + [first + 1] * 4
    for a, b in zip(ours, ours[1:]):
        assert a[1] <= b[0], (a, b)
    # the records are what they were: same keys, nested names joined by "/"
    for rec in es.history[-2:]:
        assert set(rec["phases"]) == set(PHASES)
        assert all(v >= 0.0 for v in rec["phases"].values())
    assert set(nested.take_phases()) == {"update", "update/obsnorm_merge"}
    outer, = [e for e in events if e[2] == "update"]
    inner, = [e for e in events if e[2] == "update/obsnorm_merge"]
    assert outer[0] <= inner[0] and inner[1] <= outer[1]


@pytest.mark.parametrize("profiler", ["importable", "not_importable"])
def test_phase_records_with_no_profiler_running(profiler, monkeypatch):
    if profiler == "not_importable":
        monkeypatch.setitem(sys.modules, "jax", None)   # ``import jax`` raises
        with pytest.raises(ImportError):
            annotate("dispatch")
    obs = Telemetry()
    for _ in range(2):      # the second phase takes the swapped-in no-op
        with obs.phase("dispatch"):
            with obs.phase("lookup"):
                pass
    assert [e["name"] for e in obs.recorder.events()
            if e["kind"] == "span"] == ["dispatch/lookup", "dispatch"] * 2
    assert obs.hists.snapshot()["phase/dispatch"]["count"] == 2
    phases = obs.take_phases()
    assert set(phases) == {"dispatch", "dispatch/lookup"}
    assert phases["dispatch"] >= phases["dispatch/lookup"] >= 0.0
