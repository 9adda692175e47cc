"""Param-sharded hyperscale ES engine — no tree of the STATE ever whole on
one device (the forward's compute-dtype copy of the centre is, where it
fits: ``centre_form`` below).

The fused engine (parallel/engine.py) replicates the full param tree on
every device, so the largest trainable policy is capped by one chip's HBM
(ROADMAP open item 1).  This engine implements the "Evolution Strategies
at the Hyperscale" recipe (PAPERS.md, arxiv 2511.16652) on a 2-D
``(pop, model)`` mesh (parallel/mesh.py):

- **Sharded state.**  Params and optimizer state live as TREES whose
  leaves are sharded over ``model`` per regex partition rules
  (:func:`~estorch_tpu.parallel.mesh.match_partition_rules`); optax's
  param-shaped subtrees resolve through the SAME rules, so
  adam's moments shard exactly like the weights they smooth.
- **In-program noise.**  ε is generated inside the jitted program, keyed
  on ``(key, generation, row, leaf)`` (ops/noise.py ``program_noise``):
  threefry is counter-based, so every mesh shape computes identical
  values while each device materializes only its shard of each (chunked)
  noise block — ε never exists host-side or whole on one device.  With
  ``config.low_rank`` the 2-D leaves where factoring saves draw
  ``A·Bᵀ/√r`` factors instead (ops/lowrank.py
  ``lowrank_program_factors``) and the update einsums the factors — no
  dense E anywhere.  ``noise_mode="table"`` instead slices the classic
  HBM table per leaf (same values as the replicated engine — the
  numerical-parity mode the sharded A/B gates on).
- **Donated on-chip generations.**  ``generation_step`` is ONE jitted
  program with ``donate_argnums=(0,)`` and ``out_shardings`` equal to
  the input state shardings: sample→eval→update runs in place, and the
  only param-sized traffic per generation is the psum'd update GSPMD
  inserts for the weighted-noise contraction — never a replicated tree.

Global-view but for ONE region (``jit`` + ``NamedSharding`` constraints;
the exception, a ``shard_map`` around the evaluation of a chunk's pairs in
the ``gathered`` centre form, is below): the program is written against
full logical shapes and GSPMD partitions it, which is what makes the
numerics mesh-shape invariant (values identical on (1, N), (N, 1), or
(a, b) meshes up to f32 reduction order — the forward's contractions over
model-sharded dims and the update psum may reassociate, so cross-path
comparisons are ``allclose`` at f32, not bit-equal; docs/sharding.md).

Two evaluation bodies, chosen by the engine from what it observes
(``forward_form``; no option):

- ``perturbed``: low-rank table noise on a policy with a perturbed forward
  (models/perturbed.py).  No member's weights are ever built: the centre
  enters the member ``vmap`` un-batched (cast to the compute dtype once a
  generation), the factor rows of each antithetic pair are sliced from the
  table at the pair's offset and batched over pairs, and both signs of a
  pair read one factor row.  Every projection is one population-wide
  matmul plus a rank-r correction; the update is one contraction per leaf
  over the pairs' factors, sharded like the leaf.  This is the form a model
  too wide for a perturbed copy per member needs.  Where its centre lies
  is ``centre_form`` (:func:`centre_form_why`, decided at build from what
  a chip holds, no option):

  - ``gathered``, where the whole compute-dtype centre fits a chip beside
    the chip's share of the state: one all-gather a leaf a generation, of
    the leaf already cast; the pairs split over EVERY chip of the mesh;
    each chip evaluates whole members, so no collective runs between two
    projections and no float32 sum is reassociated across chips.  The
    split is made by hand: a chunk's pairs are evaluated under a
    ``shard_map`` over the pair axes, the gathered centre whole inside it,
    so that what is traced there sees a chip's own pairs and nothing names
    a mesh axis.  That is what a hand-written kernel needs (left to GSPMD
    a ``pallas_call`` is replicated, every chip running every member), and
    why the policy's Mosaic kernels may be traced on such a mesh.
  - ``split``, where it does not (and on a ``model`` axis of 1, where
    there is nothing to gather): the centre stays sharded over ``model``
    like the state, the pairs over ``pop``, and GSPMD runs every
    projection tensor-parallel (all-gathers of split inputs, combines of
    row-split projections' partial products).

  The state, the optimizer, the update and the emitted best member are
  sharded over ``model`` in both.  Where the policy's Pallas kernels may be
  traced follows from the same observations: TPU devices and whole members
  on a chip, which is a mesh of one device or the ``gathered`` form
  (``ops.pallas_attention.traced_why`` has the rule).  Which kernels those
  are and what each one's own shapes then decide is the policy's and the
  kernels' to say (:meth:`ShardedESEngine._resolve_kernel_facts`).
- ``materialised``: ``leaf[None] + σ·s·ε`` per member of a chunk, for
  full-rank noise and in-program low-rank noise on small trees.

Scope: feedforward device-native envs (whole-episode sequence envs
included), one episode per member.  obs_norm and recurrent carries stay on
the replicated engine (their machinery assumes a replicated flat vector);
the ctor rejects them loudly.
"""

from __future__ import annotations

import contextlib
import logging
import math
import time
from typing import Any, Callable, NamedTuple

import chex
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..envs.rollout import make_rollout
from ..models.perturbed import PolicyDeclaration
from ..obs.spans import NULL_TELEMETRY
from ..obs.trace import (GATHER, GRAD, NOISE, PERTURB, RANK, SAMPLE, UPDATE,
                         stage)
from ..ops.gradient import fold_mirrored_weights
from ..ops.lowrank import (lowrank_program_factors, lowrank_program_leaf_noise,
                           lowrank_tree_noise, lowrank_tree_weighted_sum)
from ..ops.noise import (NoiseTable, leaf_noise_keys, program_noise,
                         row_noise_key, sample_pair_offsets)
from ..ops.kernel_facts import BuildScope, resolve as resolve_kernel_facts
from ..ops.pallas_attention import kernel_scope, traced_why
from ..ops.params import ParamSpec
from ..ops.ranks import centered_rank_safe
from .engine import (EngineConfig, _bf16_io_apply, _bf16_obs,
                     _choose_eval_chunk, _gen_keys)
from .mesh import (DEFAULT_PARTITION_RULES, MODEL_AXIS, POP_AXIS,
                   _leaf_path_name, match_partition_rules, padded_count,
                   sharding_summary)

NOISE_MODES = ("program", "table")
# perturbed form: a chunk's widest activation (float32 [chunk · positions a
# leaf is applied to at once, the leaf's output width], a device's share)
# is held under this many bytes
ACTIVATION_BUDGET_BYTES = 256 * 2**20
# a chip's memory where the platform does not say (the CPU's virtual
# devices, a described TPU): a v5e's 16 GB, which the centre form's rule
# holds the gathered centre against
CHIP_MEMORY_BYTES = 16 * 10**9


def centre_form_why(forward_form: str, model_shards: int, centre_bytes: int,
                    held_bytes: int, chip_bytes: int) -> tuple[str, str]:
    """``("gathered" | "split", why)``: the layout of the centre the
    perturbed form's forward reads, on a ``(pop, model)`` mesh whose
    ``model`` axis is ``model_shards`` wide.  ``gathered``: every chip holds
    the WHOLE centre in its compute dtypes (one all-gather a leaf a
    generation) and evaluates whole members, the pairs split over every
    chip of the mesh, so that no collective runs between two projections.
    It is taken when, and only when, ALL hold: the form is ``perturbed``
    (the materialised form builds members' weights, a shard a chip); the
    ``model`` axis is wider than 1 (else every leaf is whole already and
    there is nothing to gather); ``centre_bytes``, the whole centre in its
    compute dtypes, fits a chip of ``chip_bytes`` beside the ``held_bytes``
    it holds anyway (its share of the float32 state and of the best member,
    a chunk's activation budget).  Otherwise ``split``: the centre stays
    sharded over ``model`` like the state and GSPMD runs every projection
    tensor-parallel, the form for a centre one chip cannot hold.  ``why``
    names the first condition that fails (the engine logs it; the run
    manifest and a gauge carry it)."""
    failed = [why for ok, why in (
        (forward_form == "perturbed",
         f"the {forward_form} form builds members' weights, a shard a chip"),
        (model_shards > 1,
         "the mesh's model axis is 1: every leaf is whole on its chip"),
        (centre_bytes + held_bytes <= chip_bytes,
         f"the centre in its compute dtypes, {centre_bytes} bytes, does not "
         f"fit a chip of {chip_bytes} bytes beside the {held_bytes} it "
         "holds (state, best member, a chunk's activations)"),
    ) if not ok]
    if failed:
        return "split", failed[0]
    return "gathered", (
        f"the centre in its compute dtypes, {centre_bytes} bytes, fits a "
        f"chip of {chip_bytes} bytes beside the {held_bytes} it holds: "
        f"whole members on each of the mesh's chips, no {MODEL_AXIS!r} axis "
        "in the forward")


def _chip_memory_bytes(device) -> int:
    """What the runtime lets a program use of ``device``'s memory, where
    the platform reports it; ``CHIP_MEMORY_BYTES`` where it does not."""
    try:
        stats = device.memory_stats()
    except jax.errors.JaxRuntimeError:  # a described device, not attached
        stats = None
    return int((stats or {}).get("bytes_limit", CHIP_MEMORY_BYTES))


def _bytes_per_chip(shapes, shardings) -> int:
    """Bytes ONE device holds of a tree of ``shapes`` under ``shardings``."""
    return sum(
        math.prod(sh.shard_shape(tuple(x.shape))) * jnp.dtype(x.dtype).itemsize
        for x, sh in zip(
            jax.tree_util.tree_leaves(shapes),
            jax.tree_util.tree_leaves(
                shardings, is_leaf=lambda s: isinstance(s, NamedSharding))))


def _held_expert_load(engine, load, alive):
    load = load.reshape(engine.members_padded, -1)
    return jnp.where(alive[:, None], load, 0).sum(axis=0)


def _member_selected_pairs(engine, chosen, alive):
    return chosen.reshape(
        engine.members_padded)[: engine.config.population_size]


# What a policy may return after ``(score, behaviour)``, by the name it
# declares (``PolicyDeclaration.outputs``): how the perturbed form reduces a
# value ``[(chunks,) pairs, signs, ...]`` over the members (``alive``: the
# real ones, the first) into ``metrics[name]``
OUTPUT_REDUCTIONS = {
    # pairs per held expert, over the real members
    "expert_load": _held_expert_load,
    # a member's count, summed over its layers, fits int32; the
    # population's need not: the host adds the members up
    "selected_pairs": _member_selected_pairs}


def _rng_scope(partitionable: bool):
    """Program-mode dispatch/trace scope: the partitionable threefry
    implementation, without which GSPMD cannot shard in-program normal()
    generation — each device would materialize every FULL noise block as
    a temp, the exact replicate this engine exists to avoid (measured:
    ~1.9× the replicated path's per-device peak at 900k params; with the
    flag it drops well under).  Scoped, not global: the flag changes the
    random stream, and the legacy stream is load-bearing everywhere else
    (the noise table's values are pinned by goldens; table-mode parity
    with the replicated engine needs legacy fold_in/split).  The jit
    trace cache keys on the config, so every dispatch of a program-mode
    computation must re-enter this scope."""
    if partitionable:
        return jax.threefry_partitionable(True)
    return contextlib.nullcontext()


class ShardedESState(NamedTuple):
    """Training state whose params/opt_state leaves are device-sharded.

    Unlike :class:`~estorch_tpu.parallel.engine.ESState` the params are a
    TREE (sharding is per-leaf, per the partition rules), not a flat
    vector.  ``params_flat`` gathers for host-side consumers (best-member
    snapshots, bundle export, inspection) — it materializes the full
    vector on the default device, so it is an inspection API, not a
    training-path one.
    """

    params: Any  # pytree, leaves sharded per partition rules
    opt_state: Any  # optax state, param-shaped subtrees sharded likewise
    key: jax.Array  # replicated PRNG key (folded with generation)
    generation: jax.Array  # () int32, replicated
    sigma: jax.Array  # () float32, replicated

    @property
    def params_flat(self) -> jax.Array:
        """Gathered flat center vector (ravel_pytree order — identical to
        the replicated path's ``ParamSpec`` layout)."""
        return ravel_pytree(self.params)[0]


class ShardedESEngine:
    """Param-sharded twin of :class:`~estorch_tpu.parallel.engine.ESEngine`.

    Same ``generation_step(state) -> (state, metrics)`` protocol (fitness /
    steps / grad_norm / n_valid / update_finite), so ``ES.train`` drives it
    unchanged.
    """

    telemetry = NULL_TELEMETRY

    def __init__(
        self,
        env: Any,
        policy_apply: Callable[..., Any],
        spec: ParamSpec,
        table: NoiseTable | None,
        optimizer: optax.GradientTransformation,
        config: EngineConfig,
        mesh: Mesh,
        partition_rules=None,
        noise_mode: str = "program",
        perturbed_apply: Callable[..., Any] | None = None,
        lowrank_spec=None,
        policy: PolicyDeclaration = PolicyDeclaration(),
        telemetry=None,
    ):
        # the ES's hub from the first line on: what this constructor, then
        # ``init_state`` and ``compile`` span lands in it (obs/spans.py)
        if telemetry is not None:
            self.telemetry = telemetry
        if config.obs_norm:
            raise ValueError(
                "obs_norm is a replicated-engine option; the sharded "
                "path's noise/state layout replaces it (docs/sharding.md)"
            )
        if config.episodes_per_member != 1:
            raise ValueError(
                "episodes_per_member is a replicated-engine option for now")
        if env is None:
            raise ValueError(
                "the sharded engine fuses eval+update on-chip; it has no "
                "update-only mode (use ESEngine for the pooled path)")
        if noise_mode not in NOISE_MODES:
            raise ValueError(
                f"noise_mode must be one of {NOISE_MODES}, got {noise_mode!r}")
        if noise_mode == "table":
            if table is None:
                raise ValueError("noise_mode='table' needs a NoiseTable")
            if config.low_rank and (perturbed_apply is None
                                    or lowrank_spec is None):
                raise ValueError(
                    "low_rank rows from the table need a policy with a "
                    "perturbed forward and its noise layout "
                    "(models/perturbed.py; ES builds both); without one, "
                    "low_rank noise is generated in-program "
                    "(noise_mode='program')"
                )
        missing = {POP_AXIS, MODEL_AXIS} - set(mesh.axis_names)
        if missing:
            raise ValueError(
                f"sharded engine needs a ({POP_AXIS!r}, {MODEL_AXIS!r}) "
                f"mesh (parallel/mesh.py::hyperscale_mesh); {mesh.axis_names} "
                f"is missing {sorted(missing)}"
            )

        self.env = env
        self.policy_apply = policy_apply
        self.spec = spec
        self.table = table
        self.optimizer = optimizer
        self.config = config
        self.mesh = mesh
        self.noise_mode = noise_mode
        # which evaluation body the generation program runs, resolved once
        # from what the engine observes (run manifest + telemetry gauges)
        self.forward_form = (
            "perturbed" if noise_mode == "table" and config.low_rank
            else "materialised")
        self.lr_spec = lowrank_spec if self.forward_form == "perturbed" else None
        # what the policy states of itself, each field read where it counts
        self.policy = policy
        # what it returns after what the env scores it names; the perturbed
        # form reduces each into the metrics by that name
        if set(policy.outputs) - set(OUTPUT_REDUCTIONS):
            raise ValueError(
                f"the policy declares the outputs {policy.outputs}; this "
                f"engine can reduce {sorted(OUTPUT_REDUCTIONS)} over members")
        # a model whose attention reads a learned selection of keys
        # (models/indexed_moe_lm.py): the bytes of the selection's
        # temporaries ONE member holds over the horizon, which the chunk
        # rule counts
        self._selection_bytes = (
            0 if policy.selection_bytes is None
            else int(policy.selection_bytes(config.horizon)))
        # the Pallas kernels compile through Mosaic on the chip this mesh
        # is made of; anywhere else only the interpreter can run them
        self._pallas_interpret = mesh.devices.flat[0].platform != "tpu"
        self._dtype = (jnp.bfloat16 if config.compute_dtype == "bfloat16"
                       else jnp.float32)
        self.n_devices = int(mesh.devices.size)
        self.mesh_shape = "x".join(str(n) for n in mesh.devices.shape)
        axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        self.pop_shards = int(axis_sizes[POP_AXIS])
        self.model_shards = int(axis_sizes[MODEL_AXIS])
        self.bc_dim = int(env.bc_dim)

        # ---- param-tree layout (tree_flatten order == ravel order) ----
        params_shape = jax.eval_shape(
            spec.unravel, jax.ShapeDtypeStruct((spec.dim,), jnp.float32))
        self._params_shape = params_shape
        leaves, self._treedef = jax.tree_util.tree_flatten(params_shape)
        self.leaf_paths = [
            _leaf_path_name(path) for path, _ in
            jax.tree_util.tree_flatten_with_path(params_shape)[0]]
        self.leaf_shapes = [tuple(int(d) for d in l.shape) for l in leaves]
        self.leaf_sizes = [math.prod(s) if s else 1 for s in self.leaf_shapes]
        offs, pos = [], 0
        for sz in self.leaf_sizes:
            offs.append(pos)
            pos += sz
        self.leaf_flat_offsets = offs  # table-mode: leaf start within a row
        # the dtype each leaf has in the copy the forward reads: the
        # compute dtype, but float32 for the leaves the model names (a
        # router: a rounding of its scores picks another expert)
        keep = set(policy.float32_leaves)
        if keep - set(self.leaf_paths):
            raise ValueError("float32_leaves names no leaf: "
                             f"{sorted(keep - set(self.leaf_paths))}")
        self._leaf_dtypes = [jnp.float32 if path in keep else self._dtype
                             for path in self.leaf_paths]

        # low_rank: which leaves draw factored noise — the SAME
        # (m+n)·r < m·n save-or-dense rule as ops/lowrank.py specs
        self._factored: dict[int, tuple[int, int]] = {}
        if config.low_rank:
            r = int(config.low_rank)
            # but for the 2-D leaves no matmul reads, which the model names
            dense = set(policy.dense_noise_leaves)
            for i, shape in enumerate(self.leaf_shapes):
                if (len(shape) == 2 and self.leaf_paths[i] not in dense
                        and r * (shape[0] + shape[1]) < shape[0] * shape[1]):
                    self._factored[i] = (shape[0], shape[1])

        # ---- partition rules → shardings (params + optax state): the
        # caller's, else the policy's own for the leaves it names, tried
        # before the general rules of a policy that states none
        self.partition_rules = tuple(
            partition_rules if partition_rules is not None
            else policy.partition_rules + DEFAULT_PARTITION_RULES)
        self.param_shardings = match_partition_rules(
            self.partition_rules, params_shape, mesh)
        opt_shape = jax.eval_shape(optimizer.init, params_shape)
        self.opt_shardings = match_partition_rules(
            self.partition_rules, opt_shape, mesh, log_unmatched=False)
        self._repl = NamedSharding(mesh, P())
        self.state_shardings = ShardedESState(
            params=self.param_shardings,
            opt_state=self.opt_shardings,
            key=self._repl,
            generation=self._repl,
            sigma=self._repl,
        )
        self._param_sharding_leaves = jax.tree_util.tree_leaves(
            self.param_shardings,
            is_leaf=lambda x: isinstance(x, NamedSharding))
        # member/row-batched noise blocks: pop axis on the batch dim, the
        # leaf's own spec on the rest
        self._batched_shardings = [
            NamedSharding(mesh, P(POP_AXIS, *sh.spec))
            for sh in self._param_sharding_leaves
        ]

        # ---- the layout of the centre the perturbed form's forward reads:
        # "gathered" | "split", resolved once, here, from the form, the mesh
        # and what a chip holds (run manifest + telemetry gauges)
        self.centre_bytes = sum(
            size * jnp.dtype(dtype).itemsize
            for size, dtype in zip(self.leaf_sizes, self._leaf_dtypes))
        self.centre_form, self.centre_form_why = centre_form_why(
            self.forward_form, self.model_shards, self.centre_bytes,
            # the float32 state's share, the best member the program emits
            # and the one the host holds, a chunk's widest activations
            _bytes_per_chip(opt_shape, self.opt_shardings)
            + 3 * self.param_bytes_per_chip + ACTIVATION_BUDGET_BYTES,
            _chip_memory_bytes(mesh.devices.flat[0]))
        gathered = self.centre_form == "gathered"
        # what the pairs of a chunk are split over, and the shardings of
        # the compute-dtype centre: every chip of the mesh and whole leaves
        # where the centre is gathered; ``pop`` and the state's own where
        # it stays split
        self._pair_axes = (POP_AXIS, MODEL_AXIS) if gathered else POP_AXIS
        self._pair_shards = self.n_devices if gathered else self.pop_shards
        self._centre_shardings = (
            [self._repl] * len(self.leaf_shapes) if gathered
            else self._param_sharding_leaves)
        if self.forward_form == "perturbed":
            logging.getLogger(__name__).info(
                "centre_form %s (%s)", self.centre_form, self.centre_form_why)
        self._resolve_kernel_facts()

        # ---- population layout (ghost-padded like the replicated path) --
        cfg = config
        if cfg.mirrored:
            if cfg.population_size % 2 != 0:
                raise ValueError(
                    "mirrored sampling needs an even population, got "
                    f"{cfg.population_size}")
            self.rows_global = cfg.population_size // 2
        else:
            self.rows_global = cfg.population_size
        self.members_padded = padded_count(cfg.population_size, self.pop_shards)
        per_shard = self.members_padded // self.pop_shards
        req = max(1, cfg.eval_chunk // self.pop_shards) if cfg.eval_chunk > 0 else 0
        chunk_per_shard = _choose_eval_chunk(req, per_shard)
        self.eval_chunk = chunk_per_shard * self.pop_shards
        self.n_eval_chunks = self.members_padded // self.eval_chunk
        # update reduction chunking over noise rows (the rows are padded to
        # what the perturbed form splits its pairs over)
        self.rows_padded = padded_count(self.rows_global, self._pair_shards)
        rows_per_shard = self.rows_padded // self.pop_shards
        greq = max(1, cfg.grad_chunk // self.pop_shards) if cfg.grad_chunk > 0 else 0
        gchunk_per_shard = _choose_eval_chunk(greq, rows_per_shard)
        self.grad_chunk = gchunk_per_shard * self.pop_shards
        self.n_grad_chunks = self.rows_padded // self.grad_chunk

        # noise-table rows (or in-program rows) the evaluation reads per
        # generation, and the floats of one row
        self.noise_rows_per_generation = self.rows_global
        self.noise_dim = (self.lr_spec.noise_dim if self.lr_spec is not None
                          else spec.dim)

        bf16 = self._dtype == jnp.bfloat16
        if self.forward_form == "perturbed":
            self._size_pair_chunks()

            def packed_apply(packed, obs):
                shared, noise, c = packed
                out = perturbed_apply(shared, noise, c,
                                      _bf16_obs(obs) if bf16 else obs)
                return jax.tree_util.tree_map(
                    lambda o: o.astype(jnp.float32), out)

            self._rollout = self._in_kernel_scope(
                make_rollout(env, packed_apply, cfg.horizon))
        else:
            self._rollout = self._in_kernel_scope(make_rollout(
                env, _bf16_io_apply(policy_apply) if bf16 else policy_apply,
                cfg.horizon))

        # metrics shardings: scalars/vectors replicated, the in-program
        # best-member tree sharded exactly like the params it perturbs
        metrics_shardings = {
            "fitness": self._repl, "bc": self._repl, "steps": self._repl,
            "grad_norm": self._repl, "n_valid": self._repl,
            "update_finite": self._repl, "sigma": self._repl,
            "best_theta": self.param_shardings,
        }
        if self.forward_form == "perturbed":
            metrics_shardings.update(
                dict.fromkeys(policy.outputs, self._repl))
        # table mode threads the table as a replicated OPERAND, not a
        # closure: a closed-over array lowers as an embedded HLO constant
        # — at table size that bloats the module past the persistent
        # cache's 2 GB proto ceiling and re-uploads per compile
        if noise_mode == "table":
            self._generation_step = jax.jit(
                self._generation_body,
                donate_argnums=(0,),
                in_shardings=(self.state_shardings, self._repl),
                out_shardings=(self.state_shardings, metrics_shardings),
            )
        else:
            self._generation_step = jax.jit(
                lambda state: self._generation_body(state, None),
                donate_argnums=(0,),
                in_shardings=(self.state_shardings,),
                out_shardings=(self.state_shardings, metrics_shardings),
            )
        self._compiled_facts: dict | None = None
        # a best member that replaces an older one is copied INTO the older
        # one's (donated) buffers; ``keep_unused``: the donated tree is read
        # by nothing, and pruned it would alias nothing
        self._copy_into = jax.jit(
            lambda new, old: jax.tree_util.tree_map(jnp.copy, new),
            donate_argnums=(1,), keep_unused=True,
            out_shardings=self.param_shardings)
        self._copy_into_compiled = None

    def _resolve_kernel_facts(self):
        """What the policy's hand-written kernels report of this engine's
        programs, resolved once, at build (run manifest + telemetry
        gauges).  ONE question is the engine's, whether Mosaic kernels may
        be traced at all (``pallas_attention.traced_why``: TPU devices and
        whole members on a chip); the scope it opens around the policy's
        trace says that and nothing more, and each call site then takes its
        form from its own shapes while it is traced.  Which kernels the
        policy calls, with which widths, is the policy's to state
        (``PolicyDeclaration.kernels``), and what each then reports its own
        module's rule: the engine hands every rule what only it observed
        and names none."""
        scope = self._build_scope()
        self.kernels_traced, self.kernels_traced_why = scope.traced
        # {gauge / manifest name: value}, in the order the policy names its
        # kernels; {} for a policy that calls none
        self.kernel_facts = resolve_kernel_facts(scope, self.policy.kernels)
        if self.kernel_facts:
            logging.getLogger(__name__).info(
                "kernels traced: %s (%s); %s", self.kernels_traced,
                self.kernels_traced_why, self.kernel_facts)

    def _build_scope(self) -> BuildScope:
        """What this engine observed, for the kernels' rules."""
        platform = self.mesh.devices.flat[0].platform
        return BuildScope(
            platform=platform, n_devices=self.n_devices,
            centre_form=self.centre_form,
            traced=traced_why(platform, self.n_devices, self.centre_form),
            horizon=self.config.horizon,
            itemsize=jnp.dtype(self._dtype).itemsize)

    def _in_kernel_scope(self, rollout):
        """``rollout`` traced where this engine may trace Mosaic kernels:
        the policy's call sites learn of it by the scope open while they
        are traced, and take their XLA forms with none."""
        if not self.kernels_traced:
            return rollout

        def scoped(*args):
            with kernel_scope(self._pallas_interpret):
                return rollout(*args)

        return scoped

    # ------------------------------------------------------------- noise

    def _row_noise(self, i: int, leaf_key, offsets, rows: jax.Array,
                   table_data=None) -> jax.Array:
        """(k, *leaf_shape) noise for leaf ``i`` over row indices ``rows``.

        program mode: generated from the (key, generation, row, leaf)
        chain; table mode: the leaf's slice of each row's table window
        (``table_data`` is the traced operand) — value-identical to the
        replicated engine's ε."""
        shape = self.leaf_shapes[i]
        if self.noise_mode == "table":
            size, loff = self.leaf_sizes[i], self.leaf_flat_offsets[i]
            data = table_data

            def one(row):
                start = offsets[row] + loff
                return jax.lax.dynamic_slice(data, (start,), (size,)).reshape(shape)

            return jax.vmap(one)(rows)
        if i in self._factored:
            m, n = self._factored[i]
            r = int(self.config.low_rank)

            def one(row):
                return lowrank_program_leaf_noise(
                    r, m, n, row_noise_key(leaf_key, row))

            return jax.vmap(one)(rows)
        return jax.vmap(lambda row: program_noise(leaf_key, row, shape))(rows)

    def _leaf_keys(self, okey):
        if self.noise_mode == "table":
            return [None] * len(self.leaf_shapes)
        return leaf_noise_keys(okey, len(self.leaf_shapes))

    def _offsets(self, okey):
        if self.noise_mode != "table":
            return None
        return sample_pair_offsets(
            okey, self.rows_global, self.table.size, self.noise_dim)

    def all_pair_offsets(self, state: ShardedESState) -> jax.Array:
        """Table mode: this generation's per-PAIR (mirrored) or per-MEMBER
        offsets, the same derivation the program performs, so an outside
        evaluator (the benchmark's reference) perturbs with the same
        noise."""
        if self.noise_mode != "table":
            raise ValueError("program-mode noise has no table offsets")
        return self._offsets(_gen_keys(state)[0])

    # ------------------------------------------------------------- eval

    def _member_rows_signs(self, ids: jax.Array):
        if self.config.mirrored:
            rows = jnp.minimum(ids // 2, self.rows_global - 1)
            signs = jnp.where(ids % 2 == 0, 1.0, -1.0).astype(jnp.float32)
        else:
            rows = jnp.minimum(ids, self.rows_global - 1)
            signs = jnp.ones(ids.shape, jnp.float32)
        return rows, signs

    def _eval_chunk_body(self, state, offsets, leaf_keys, member_keys, ids,
                         table_data):
        """Evaluate one chunk of (global) member ids: build the chunk's
        perturbed trees leaf-by-leaf (each block sharded (pop, *rule)) and
        vmap the rollout over members."""
        with stage(SAMPLE):
            rows, signs = self._member_rows_signs(ids)
            keys = jnp.take(member_keys, rows, axis=0)
        with stage(PERTURB):
            scale = state.sigma * signs  # (chunk,)
        leaves = jax.tree_util.tree_leaves(state.params)
        theta_leaves = []
        for i, leaf in enumerate(leaves):
            with stage(NOISE):
                eps = self._row_noise(
                    i, leaf_keys[i], offsets, rows, table_data)
                eps = jax.lax.with_sharding_constraint(
                    eps, self._batched_shardings[i])
            with stage(PERTURB):
                b = scale.reshape((ids.shape[0],) + (1,) * leaf.ndim)
                theta_leaves.append(
                    (leaf[None] + b * eps).astype(self._leaf_dtypes[i]))
        theta = jax.tree_util.tree_unflatten(self._treedef, theta_leaves)
        res = jax.vmap(self._rollout, in_axes=(0, 0))(theta, keys)
        return res.total_reward, res.bc, res.steps

    def _widest_activation(self) -> int:
        """Floats of the widest activation ONE member holds on a device:
        over the factored leaves, the positions the leaf is applied to at
        once times its output width, over ``model`` where the centre stays
        split (a member is whole on its chip where it is gathered).  A leaf
        sees the whole
        horizon unless the policy runs it in blocks of positions
        (``leaf_rows``: an untied head is ``[head_block, vocab]``, never
        ``[horizon, vocab]``).  A stacked expert leaf sees the rows routed
        to its experts (``leaf_rows_per_token`` a position), whole on every
        device: its expert axis is what ``model`` divides."""
        horizon = self.config.horizon
        across = 1 if self.centre_form == "gathered" else self.model_shards
        policy = self.policy
        return max([
            min(horizon, policy.leaf_rows.get(self.leaf_paths[i], horizon))
            * -(-n // across)
            for i, _, n, _, _ in self.lr_spec.lr_leaves] + [
            math.ceil(horizon * policy.leaf_rows_per_token.get(
                self.leaf_paths[i], 1.0)) * n
            for i, _, _, n, _, _ in self.lr_spec.stacked_leaves] or [1])

    def _size_pair_chunks(self):
        """Perturbed form: antithetic pairs (unmirrored: members) per
        evaluation chunk.  ``eval_chunk`` members when the caller set it;
        otherwise as many as keep a device's share of the chunk's widest
        activation (:meth:`_widest_activation`, float32; or, where they are
        more, the bytes of a learned selection of keys and of the index
        scores it is chosen from, as the model states them:
        ``selection_bytes``) under
        ``ACTIVATION_BUDGET_BYTES``.  A chunk holds whole pairs and a
        multiple of the rows' shards (``pop_shards``; every device of the
        mesh where the centre is gathered), with one exception: where ONE
        member's widest activation is over the budget by itself, a chunk is
        one pair whose two signs are evaluated in turn (``signs_in_turn``:
        half the activations of a pair at once, the pair still reads one
        factor row)."""
        cfg = self.config
        per_row = 2 if cfg.mirrored else 1
        rows_per_shard = self.rows_padded // self._pair_shards
        self.signs_in_turn = False
        if cfg.eval_chunk > 0:
            req = max(1, cfg.eval_chunk // (per_row * self._pair_shards))
        else:
            per_member = max(4 * self._widest_activation(),
                             self._selection_bytes)
            req = max(1, ACTIVATION_BUDGET_BYTES // (per_member * per_row))
            self.signs_in_turn = (cfg.mirrored
                                  and per_member > ACTIVATION_BUDGET_BYTES)
        rows_chunk_per_shard = _choose_eval_chunk(req, rows_per_shard)
        self.pair_chunk = rows_chunk_per_shard * self._pair_shards
        self.n_pair_chunks = self.rows_padded // self.pair_chunk
        # members evaluated at once, and how often
        in_turn = 2 if self.signs_in_turn else 1
        self.eval_chunk = self.pair_chunk * per_row // in_turn
        self.n_eval_chunks = self.n_pair_chunks * in_turn
        self.members_padded = self.rows_padded * per_row

    def _eval_all_perturbed(self, state, center, noise_rows, rkey):
        """Evaluate every member without building any member's weights:
        ``center`` (the compute-dtype copy of the params: sharded like them
        in the ``split`` centre form, whole on every chip in ``gathered``)
        enters un-batched, ``noise_rows [rows_padded, noise_dim]`` are
        unpacked per chunk into factor trees batched over pairs, and the two
        signs of a pair read the one tree.  The pairs of a chunk are split
        over ``pop`` and left to GSPMD in the ``split`` form
        (``vmap(spmd_axis_name=)``); in ``gathered`` they are partitioned
        over every chip of the mesh by hand (a ``shard_map`` over the pair
        axes, the centre whole inside it), which is what lets a Mosaic
        kernel in the policy run a chip's own members."""
        cfg = self.config
        with stage(SAMPLE):
            member_keys = jax.random.split(rkey, self.rows_global)
            keys = jnp.take(member_keys, self._padded_rows(), axis=0)
            signs = (jnp.asarray([1.0, -1.0], jnp.float32) if cfg.mirrored
                     else jnp.ones((1,), jnp.float32))

        def eval_pairs(center, sigma, noise_c, keys_c, spmd_axis_name=None):
            """The pairs whose rows are ``noise_c``, every member of them
            whole where this is traced."""
            with stage(NOISE):
                noise_tree = self.lr_spec.unpack(noise_c)

            def pair_eval(noise_p, key):
                def sign_eval(sign):
                    with stage(PERTURB):
                        c = sigma * sign
                    return self._rollout((center, noise_p, c), key)

                if self.signs_in_turn:
                    return jax.lax.map(sign_eval, signs)
                return jax.vmap(sign_eval)(signs)

            if spmd_axis_name is None and keys_c.shape[0] == 1:
                # ONE pair on this chip: evaluated as it is, not under a
                # ``vmap`` of one.  XLA drops an axis of one from some
                # operations and keeps it on others, and the mixed shapes
                # cost the published four-chip program 0.63 GiB of
                # temporaries a chip and 23 s of compile (PERF.md, PR 46)
                res = jax.tree_util.tree_map(
                    lambda x: x[None], pair_eval(jax.tree_util.tree_map(
                        lambda x: x[0], noise_tree), keys_c[0]))
            else:
                res = jax.vmap(pair_eval, spmd_axis_name=spmd_axis_name)(
                    noise_tree, keys_c)
            # strict: a policy that returns more or fewer things than it
            # declares is refused here, while the program is traced
            return (res.total_reward, res.bc, res.steps, dict(zip(
                self.policy.outputs, res.extras or (), strict=True)))

        if self.centre_form == "gathered":
            # whole members on every chip: the chunk's pairs are
            # partitioned over the chips by hand, so that what is traced
            # inside sees a chip's OWN pairs and a hand-written kernel runs
            # on them (left to GSPMD a ``pallas_call`` would be replicated:
            # every chip scoring every member).  The centre and sigma enter
            # whole, as they lie; nothing inside names a mesh axis, so no
            # collective is added
            pairs = P(self._pair_axes)
            chunk_body = jax.shard_map(
                lambda noise_c, keys_c, center, sigma: eval_pairs(
                    center, sigma, noise_c, keys_c),
                mesh=self.mesh, in_specs=(pairs, pairs, P(), P()),
                out_specs=pairs, check_vma=False)
        else:
            pair_rows = NamedSharding(self.mesh, P(self._pair_axes, None))

            def chunk_body(noise_c, keys_c, center, sigma):
                with stage(NOISE):
                    noise_c = jax.lax.with_sharding_constraint(
                        noise_c, pair_rows)
                return eval_pairs(center, sigma, noise_c, keys_c,
                                  spmd_axis_name=self._pair_axes)

        if self.n_pair_chunks == 1:
            f, bc, st, outputs = chunk_body(noise_rows, keys, center,
                                            state.sigma)
        else:
            n, k = self.n_pair_chunks, self.pair_chunk
            _, (f, bc, st, outputs) = jax.lax.scan(
                lambda _, xs: (0, chunk_body(*xs, center, state.sigma)), 0,
                (noise_rows.reshape(n, k, self.noise_dim),
                 keys.reshape((n, k) + keys.shape[1:])))
        # (chunks, pairs, signs) is member order: member 2k+s is pair k
        f = f.reshape(self.members_padded)
        bc = bc.reshape(self.members_padded, self.bc_dim)
        st = st.reshape(self.members_padded)
        with stage(GATHER):
            alive = jnp.arange(self.members_padded) < cfg.population_size
            steps = jnp.where(alive, st, 0).sum()
            out = {name: OUTPUT_REDUCTIONS[name](self, outputs[name], alive)
                   for name in self.policy.outputs}
            return (f[: cfg.population_size], bc[: cfg.population_size],
                    steps, out)

    def _noise_rows(self, offsets, table_data):
        """Perturbed form: every pair's ``noise_dim`` floats, sliced from
        the table at the pair's offset (ghost rows repeat the last): the
        one read of the noise, shared by evaluation, update and best-member
        reconstruction.  Small (rows × noise_dim), so it is replicated."""
        with stage(NOISE):
            rows = jax.vmap(lambda o: jax.lax.dynamic_slice(
                table_data, (o,), (self.noise_dim,)))(
                    offsets[self._padded_rows()])
            if self.centre_form == "gathered":
                # said, not left to propagation: the chunks' rows are then
                # slices a chip already has, and the update reads the same
                rows = jax.lax.with_sharding_constraint(rows, self._repl)
            return rows

    def _padded_rows(self):
        """Row index per padded row: ghost rows repeat the last real one."""
        return jnp.minimum(jnp.arange(self.rows_padded, dtype=jnp.int32),
                           self.rows_global - 1)

    def _eval_all(self, state, offsets, leaf_keys, rkey, table_data):
        cfg = self.config
        # rollout keys: one per PAIR when mirrored (common random numbers
        # across the ± twins), one per member otherwise — the replicated
        # engine's exact keying, so table-mode fitness matches it
        with stage(SAMPLE):
            member_keys = jax.random.split(rkey, self.rows_global)
            ids = jnp.arange(self.members_padded, dtype=jnp.int32)
        if self.n_eval_chunks == 1:
            f, bc, st = self._eval_chunk_body(
                state, offsets, leaf_keys, member_keys, ids, table_data)
        else:
            def body(_, ids_c):
                return 0, self._eval_chunk_body(
                    state, offsets, leaf_keys, member_keys, ids_c, table_data)

            _, (f, bc, st) = jax.lax.scan(
                body, 0, ids.reshape(self.n_eval_chunks, self.eval_chunk))
            f = f.reshape(self.members_padded)
            bc = bc.reshape(self.members_padded, self.bc_dim)
            st = st.reshape(self.members_padded)
        # no explicit collective here (GSPMD places them); the stage keeps
        # the replicated engine's name for the same work: ghost masking and
        # the global views
        with stage(GATHER):
            alive = jnp.arange(self.members_padded) < cfg.population_size
            steps = jnp.where(alive, st, 0).sum()
            return (f[: cfg.population_size], bc[: cfg.population_size],
                    steps)

    # ------------------------------------------------------------- update

    @stage(GRAD)
    def _weighted_factor_sum(self, state, noise_rows, weights):
        """Perturbed form: grad tree from the generation's noise rows, one
        contraction per leaf over the pairs' factors (ΔW = Σ w·A·Bᵀ/√r),
        constrained to the leaf's own sharding so that no device ever holds
        a whole leaf of it."""
        cfg = self.config
        row_w = fold_mirrored_weights(weights) if cfg.mirrored else weights
        pad = self.rows_padded - self.rows_global
        if pad:
            row_w = jnp.concatenate([row_w, jnp.zeros((pad,), row_w.dtype)])
        denom = jnp.float32(cfg.population_size) * state.sigma
        tree = lowrank_tree_weighted_sum(self.lr_spec, noise_rows, row_w)
        return jax.tree_util.tree_map(
            lambda g, sh: jax.lax.with_sharding_constraint(g, sh) / denom,
            tree, self.param_shardings)

    @stage(GRAD)
    def _weighted_noise_sum(self, state, offsets, leaf_keys, weights,
                            table_data):
        """grad tree = Σ_rows w_row · ε_row / (population · σ), chunked
        over rows; each leaf's accumulator stays sharded like the leaf —
        the contraction over the pop-sharded chunk axis is the ONE psum'd
        param-sized transfer of the generation."""
        cfg = self.config
        if cfg.mirrored:
            row_w = fold_mirrored_weights(weights)  # (rows_global,)
        else:
            row_w = weights
        pad = self.rows_padded - self.rows_global
        rows = jnp.arange(self.rows_padded, dtype=jnp.int32)
        rows = jnp.minimum(rows, self.rows_global - 1)
        if pad:
            row_w = jnp.concatenate([row_w, jnp.zeros((pad,), row_w.dtype)])
        leaves = jax.tree_util.tree_leaves(state.params)
        rank = int(cfg.low_rank) if cfg.low_rank else 0

        def chunk_contrib(i, leaf_key, rows_c, w_c):
            if rank and i in self._factored:
                m, n = self._factored[i]

                def factors(row):
                    return lowrank_program_factors(
                        rank, m, n, row_noise_key(leaf_key, row))

                a, b = jax.vmap(factors)(rows_c)  # (k, m, r), (k, n, r)
                return jnp.einsum(
                    "kmr,knr->mn", a * w_c[:, None, None], b
                ) / jnp.sqrt(jnp.float32(rank))
            eps = self._row_noise(i, leaf_key, offsets, rows_c, table_data)
            eps = jax.lax.with_sharding_constraint(
                eps, self._batched_shardings[i])
            return jnp.tensordot(w_c, eps, axes=1)

        if self.n_grad_chunks == 1:
            acc = [
                jax.lax.with_sharding_constraint(
                    chunk_contrib(i, leaf_keys[i], rows, row_w),
                    self._param_sharding_leaves[i])
                for i in range(len(leaves))
            ]
        else:
            rows_cs = rows.reshape(self.n_grad_chunks, self.grad_chunk)
            w_cs = row_w.reshape(self.n_grad_chunks, self.grad_chunk)

            def body(acc, xs):
                rows_c, w_c = xs
                new = [
                    jax.lax.with_sharding_constraint(
                        acc[i] + chunk_contrib(i, leaf_keys[i], rows_c, w_c),
                        self._param_sharding_leaves[i])
                    for i in range(len(acc))
                ]
                return new, None

            acc0 = [
                jax.lax.with_sharding_constraint(
                    jnp.zeros(self.leaf_shapes[i], jnp.float32),
                    self._param_sharding_leaves[i])
                for i in range(len(leaves))
            ]
            acc, _ = jax.lax.scan(body, acc0, (rows_cs, w_cs))
        denom = jnp.float32(cfg.population_size) * state.sigma
        grad_leaves = [a / denom for a in acc]
        return jax.tree_util.tree_unflatten(self._treedef, grad_leaves)

    # ------------------------------------------------------------- body

    @stage(UPDATE)
    def _finish_update(self, state: ShardedESState, grad, n_valid):
        """Weight decay + optax step + σ annealing + the in-program
        anomaly rollback, from the grad tree."""
        cfg = self.config
        if cfg.weight_decay > 0.0:
            grad = jax.tree_util.tree_map(
                lambda g, p: g - cfg.weight_decay * p, grad, state.params)
        gnorm = jnp.sqrt(sum(
            jnp.sum(jnp.square(g)) for g in jax.tree_util.tree_leaves(grad)))
        neg = jax.tree_util.tree_map(jnp.negative, grad)
        updates, new_opt_state = self.optimizer.update(
            neg, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        new_sigma = state.sigma
        if cfg.sigma_decay != 1.0:
            new_sigma = jnp.maximum(
                state.sigma * cfg.sigma_decay, cfg.sigma_min)
        # In-program anomaly rollback: donation destroys the caller's
        # pre-step buffers, so the restore the replicated path's ES.train
        # does host-side ("reject instead of training on poison",
        # docs/resilience.md) happens HERE — a rejected generation emits
        # the input state unchanged (same generation → the deterministic
        # re-run contract holds) and ES.train only counts/announces it.
        # The gate reads the GRADIENT (and the valid count), known before
        # any leaf is stepped, so each leaf is stepped and selected in
        # place; gating on the stepped params would hold a second whole
        # state (params + moments) until the last leaf was checked.
        ok = jnp.logical_and(jnp.isfinite(gnorm), n_valid >= 2)

        def keep(new, old):
            return jax.tree_util.tree_map(
                lambda n, o: jnp.where(ok, n, o), new, old)

        new_state = ShardedESState(
            params=keep(new_params, state.params),
            opt_state=keep(new_opt_state, state.opt_state),
            key=state.key,
            generation=jnp.where(ok, state.generation + 1, state.generation),
            sigma=jnp.where(ok, new_sigma, state.sigma),
        )
        # reported, not gated on: finite gradients through optax give
        # finite params; if they ever do not, ES.train counts the
        # generation rejected and stops after its limit of repeats
        params_finite = jnp.array(True)
        for leaf in jax.tree_util.tree_leaves(new_state.params):
            params_finite = jnp.logical_and(
                params_finite, jnp.isfinite(leaf).all())
        update_finite = jnp.logical_and(jnp.isfinite(gnorm), params_finite)
        return new_state, gnorm, update_finite

    def _generation_body(self, state: ShardedESState, table_data):
        with stage(SAMPLE):
            okey, rkey = _gen_keys(state)
            offsets = self._offsets(okey)
            leaf_keys = self._leaf_keys(okey)
        perturbed = self.forward_form == "perturbed"
        if perturbed:
            # the pairs' rows, read once for evaluation, update and best
            noise_rows = self._noise_rows(offsets, table_data)
            # cast first, then laid out as the centre form says: a gathered
            # leaf crosses the chips in its compute dtype
            with stage(PERTURB):
                center = jax.tree_util.tree_unflatten(self._treedef, [
                    jax.lax.with_sharding_constraint(x.astype(dtype), sh)
                    for x, dtype, sh in zip(
                        jax.tree_util.tree_leaves(state.params),
                        self._leaf_dtypes, self._centre_shardings)])
            fitness, bc, steps, outputs = self._eval_all_perturbed(
                state, center, noise_rows, rkey)
        else:
            outputs = {}
            fitness, bc, steps = self._eval_all(
                state, offsets, leaf_keys, rkey, table_data)
        with stage(RANK):
            weights, n_valid = centered_rank_safe(fitness)
        if perturbed:
            grad = self._weighted_factor_sum(state, noise_rows, weights)
        else:
            grad = self._weighted_noise_sum(
                state, offsets, leaf_keys, weights, table_data)
        new_state, gnorm, update_finite = self._finish_update(
            state, grad, n_valid)
        # In-program best-member reconstruction: ES.train snapshots the
        # generation's best θ on improvement; with the pre-step center
        # donated it cannot be rebuilt host-side afterwards, so the
        # program emits it — sharded like the params (per-device cost =
        # one extra param shard; the host gathers only on improvement).
        with stage(RANK):
            safe_fit = jnp.where(jnp.isfinite(fitness), fitness, -jnp.inf)
            best_rows, best_signs = self._member_rows_signs(
                jnp.argmax(safe_fit)[None])
        if perturbed:
            with stage(NOISE):
                best_eps = jax.tree_util.tree_leaves(lowrank_tree_noise(
                    self.lr_spec, noise_rows[best_rows[0]]))
        best_leaves = []
        for i, leaf in enumerate(jax.tree_util.tree_leaves(state.params)):
            with stage(NOISE):
                eps = best_eps[i] if perturbed else self._row_noise(
                    i, leaf_keys[i], offsets, best_rows, table_data)[0]
            with stage(PERTURB):
                best_leaves.append(jax.lax.with_sharding_constraint(
                    leaf + state.sigma * best_signs[0] * eps,
                    self._param_sharding_leaves[i]))
        metrics = {
            "fitness": fitness,
            "bc": bc,
            "steps": steps,
            "grad_norm": gnorm,
            "n_valid": n_valid,
            "update_finite": update_finite,
            # pre-step σ for the record: ES.train logs prev_state.sigma on
            # the replicated path; that buffer is donated here
            "sigma": state.sigma,
            "best_theta": jax.tree_util.tree_unflatten(
                self._treedef, best_leaves),
        }
        metrics.update(outputs)
        return new_state, metrics

    # ------------------------------------------------------------- public

    def init_state(self, params_flat: jax.Array, key: jax.Array) -> ShardedESState:
        with self.telemetry.phase("setup/init_state"):
            return self._init_state(params_flat, key)

    def _init_state(self, params_flat, key) -> ShardedESState:
        chex.assert_shape(params_flat, (self.spec.dim,))
        chex.assert_tree_all_finite(params_flat)
        # place leaf by leaf, each slice straight onto its shards: the flat
        # vector is never replicated over the mesh and no second whole
        # tree exists anywhere (a host vector is read where it lies)
        on_host = isinstance(params_flat, np.ndarray)
        flat = params_flat if on_host else jnp.asarray(params_flat)
        leaves, at = [], 0
        for shape, size, sharding in zip(self.leaf_shapes, self.leaf_sizes,
                                         self._param_sharding_leaves):
            part = (flat[at:at + size] if on_host else
                    jax.lax.dynamic_slice(flat, (jnp.int32(at),), (size,)))
            leaves.append(jax.device_put(
                part.reshape(shape).astype(jnp.float32), sharding))
            at += size
        params = jax.tree_util.tree_unflatten(self._treedef, leaves)
        # init the optimizer state ON the mesh: out_shardings places the
        # param-shaped moments without a replicated round-trip
        opt_state = jax.jit(
            self.optimizer.init, out_shardings=self.opt_shardings)(params)
        return ShardedESState(
            params=params,
            opt_state=opt_state,
            key=jax.device_put(key, self._repl),
            generation=jax.device_put(jnp.int32(0), self._repl),
            sigma=jax.device_put(jnp.float32(self.config.sigma), self._repl),
        )

    def compile(self, state: ShardedESState) -> float:
        """AOT-compile the donated generation program; returns seconds.

        The compile ledger entry carries XLA's own per-device argument/
        output/temp byte sizes (``memory_analysis``) — with sharded
        inputs those ARE shard sizes, which is how the bench A/B and the
        acceptance test state per-device peak bytes."""
        from ..obs.profile.costmodel import compiled_cost_facts

        obs = self.telemetry
        with obs.phase("setup/compile"):
            t0 = time.perf_counter()
            args = ((state, self.table.data) if self.noise_mode == "table"
                    else (state,))
            with _rng_scope(self.noise_mode == "program"):
                with obs.phase("lower"):
                    lowered = self._generation_step.lower(*args)
                with obs.phase("acquire"):
                    compiled = lowered.compile()
            dt = time.perf_counter() - t0
            with obs.phase("facts"):
                self._compiled_facts = compiled_cost_facts(compiled)
                obs.compile_event("generation_step_sharded", dt,
                                  compiled=compiled, first_call=True)
            # built here, called as built: the first best member that
            # replaces another may come generations later, and nothing is
            # to compile then
            with obs.phase("copy_into"):
                self._copy_into_compiled = self._copy_into.lower(
                    state.params, state.params).compile()
        return dt

    def keep_best(self, best_theta, held=None):
        """The tree to hold on the mesh for a generation's best member
        (``metrics["best_theta"]``).  The first is the program's own output.
        One that replaces an older tree ``held`` is copied into ``held``'s
        buffers, so that the held tree and the program's next output both
        stay where they were: on a chip nearly full of state a generation's
        time depends on where its param-sized output lands (two levels 1.6%
        apart, flipping at every new best: PERF.md §6, PR 31)."""
        if held is None:
            return best_theta
        copy = self._copy_into_compiled or self._copy_into
        return copy(best_theta, held)

    @property
    def param_bytes_per_chip(self) -> int:
        """Float32 bytes of the centre one device holds, from the resolved
        shardings (optimizer moments are param-shaped multiples of it)."""
        return _bytes_per_chip(
            [jax.ShapeDtypeStruct(shape, jnp.float32)
             for shape in self.leaf_shapes], self._param_sharding_leaves)

    @property
    def centre_bytes_per_chip(self) -> int:
        """Bytes of the compute-dtype centre one device holds while the
        perturbed form's forward runs: the whole of it where the centre is
        gathered, the device's shard where it stays split."""
        return _bytes_per_chip(
            [jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in
             zip(self.leaf_shapes, self._leaf_dtypes)],
            self._centre_shardings)

    # what this engine itself resolves at build, by attribute, under the
    # names of its gauges and manifest entries (engine.py::build_fact_gauges)
    BUILD_FACTS = (
        "forward_form", "noise_rows_per_generation", "mesh_shape",
        "param_bytes_per_chip", "centre_form", "centre_form_why",
        "centre_bytes_per_chip")

    def build_facts(self) -> dict:
        """The engine's own facts and, under the names their rules give
        them, what the policy's kernels report (``kernel_facts``)."""
        return {**{name: getattr(self, name) for name in self.BUILD_FACTS},
                **self.kernel_facts}

    def memory_facts(self) -> dict:
        """XLA per-device byte facts of the compiled generation program
        ({} before :meth:`compile` or when the jax version hides them)."""
        return dict(self._compiled_facts or {})

    def generation_step(self, state: ShardedESState):
        """Fused sharded ES generation: (new_state, metrics)."""
        if self.noise_mode == "table":
            return self._generation_step(state, self.table.data)
        with _rng_scope(True):
            return self._generation_step(state)

    def member_params(self, state: ShardedESState, member_index: int) -> jax.Array:
        """One member's flat θ (ravel order) — host convenience for
        best-member snapshots (reference's ``best_policy``).

        Computed EAGERLY on the default device from the gathered center:
        the same ``(key, generation, row, leaf)`` noise functions as the
        in-program paths (so the reconstruction is exact), but outside
        the mesh program — a one-member gather is inspection traffic, and
        keeping it off the mesh sidesteps GSPMD resharding of a
        scalar-indexed program for no training-path benefit."""
        with _rng_scope(self.noise_mode == "program"):
            return self._member_params_eager(state, member_index)

    def _member_params_eager(self, state, member_index):
        okey, _ = _gen_keys(state)
        offsets = self._offsets(okey)
        leaf_keys = self._leaf_keys(okey)
        idx = int(member_index)
        if self.config.mirrored:
            row, sign = idx // 2, (1.0 if idx % 2 == 0 else -1.0)
        else:
            row, sign = idx, 1.0
        row = jnp.int32(row)
        table_data = self.table.data if self.noise_mode == "table" else None
        if self.forward_form == "perturbed":
            dense = jax.tree_util.tree_leaves(lowrank_tree_noise(
                self.lr_spec, self.table.slice(offsets[row], self.noise_dim)))
        flats = []
        for i, leaf in enumerate(jax.tree_util.tree_leaves(state.params)):
            eps = dense[i] if self.forward_form == "perturbed" else (
                self._row_noise(
                    i, leaf_keys[i], offsets, row[None], table_data)[0])
            flats.append(
                (jax.device_get(leaf) + jax.device_get(
                    state.sigma * sign * eps)).reshape(-1))
        return jnp.asarray(np.concatenate(flats))

    def sharding_report(self) -> dict[str, str]:
        """{leaf path: resolved spec} — what the rules did, incl. any
        divisibility fallbacks (manifests, tests, docs examples) — and,
        under ``centre_form``, how the forward's copy of the centre lies."""
        report = sharding_summary(self._params_shape, self.param_shardings,
                                  self.partition_rules)
        report["centre_form"] = (
            f"{self.centre_form}: {self.centre_form_why}; "
            f"{self.centre_bytes_per_chip} bytes of it a chip")
        return report
