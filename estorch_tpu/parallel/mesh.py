"""Device mesh helpers: population data-parallelism + parameter sharding.

The reference's distributed runtime is ``torch.distributed`` gather/broadcast
over ``n_proc`` CPU processes (SURVEY.md §2 item 7).  The TPU-native
equivalent is a 1-D ``jax.sharding.Mesh`` over the available chips with a
single named axis ``POP_AXIS``: each device evaluates its population shard
and the update travels through one ``lax.psum`` riding ICI.  On multi-slice
deployments the same axis spans slices — XLA routes the reduction
hierarchically (ICI within a slice, DCN across) without code changes.

The hyperscale path (parallel/sharded.py, "Evolution Strategies at the
Hyperscale", PAPERS.md arxiv 2511.16652) adds a second axis ``MODEL_AXIS``:
a 2-D ``(pop, model)`` mesh where parameter leaves are sharded over
``model`` per regex partition rules (:func:`match_partition_rules`, the
fmengine/EasyLM idiom) and the population is sharded
over ``pop``, so neither the param tree nor any member's perturbation
ever exists whole on one device.
"""

from __future__ import annotations

import re
from typing import Any, Sequence

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

POP_AXIS = "pop"
MODEL_AXIS = "model"


def _auto_mesh(shape: tuple[int, ...], names: tuple[str, ...],
               devices: Sequence[jax.Device]) -> Mesh:
    """Every mesh here has ``Auto`` axes: the engines place data with
    ``shard_map``, ``with_sharding_constraint`` and ``NamedSharding``
    operands, which ``jax.make_mesh``'s default ``Explicit`` axes refuse
    (sharding would have to ride every array's type instead)."""
    return jax.make_mesh(shape, names, (AxisType.Auto,) * len(names),
                         devices=devices)


def population_mesh(devices: Sequence[jax.Device] | None = None) -> Mesh:
    """1-D mesh over ``devices`` (default: all) with the population axis."""
    devs = list(devices) if devices is not None else jax.devices()
    return _auto_mesh((len(devs),), (POP_AXIS,), devs)


def single_device_mesh(device: jax.Device | None = None) -> Mesh:
    dev = device if device is not None else jax.devices()[0]
    return _auto_mesh((1,), (POP_AXIS,), [dev])


def hyperscale_mesh(
    pop_shards: int | None = None,
    model_shards: int | None = None,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """2-D ``(pop, model)`` mesh for the param-sharded engine.

    Defaults: ``model`` spans every device (maximum per-device memory
    reduction — the hyperscale regime this mesh exists for) and ``pop``
    is the co-factor.  ``pop_shards × model_shards`` must equal the
    device count when both are given.
    """
    devs = list(devices) if devices is not None else jax.devices()
    n = len(devs)
    if pop_shards is None and model_shards is None:
        pop_shards, model_shards = 1, n
    elif pop_shards is None:
        pop_shards = n // int(model_shards)
    elif model_shards is None:
        model_shards = n // int(pop_shards)
    pop_shards, model_shards = int(pop_shards), int(model_shards)
    if pop_shards * model_shards != n:
        raise ValueError(
            f"mesh shape ({pop_shards}, {model_shards}) needs "
            f"{pop_shards * model_shards} devices, got {n}"
        )
    return _auto_mesh((pop_shards, model_shards), (POP_AXIS, MODEL_AXIS),
                      devs)


def pairs_per_device(population_size: int, n_devices: int) -> int:
    """PADDED antithetic pairs each device owns.

    The population is laid out device-major: device d owns pairs
    [d·k, (d+1)·k) and members [2·d·k, 2·(d+1)·k), so an all_gather of
    per-device fitness reproduces the global member order.

    Pair counts that do not divide the device count are PADDED UP to the
    next multiple: the engine evaluates the padded tail as zero-weighted
    ghost members (clamped noise rows, masked out of the ranking and the
    update — parallel/engine.py), so any even population runs on any
    mesh.  Historically this hard-errored ("use a population that is a
    multiple of 2·n_devices"); the regression test for that case now
    asserts training works.
    """
    if population_size % 2 != 0:
        raise ValueError(f"population_size must be even (mirrored sampling), got {population_size}")
    n_pairs = population_size // 2
    return -(-n_pairs // n_devices)  # ceil division: padded pairs per device


def padded_count(n: int, n_shards: int) -> int:
    """``n`` rounded up to the next multiple of ``n_shards``."""
    return -(-int(n) // int(n_shards)) * int(n_shards)


# ---------------------------------------------------------------------------
# regex partition rules  (the `match_partition_rules` idiom)
# ---------------------------------------------------------------------------

# Default rules for the bundled policy families (models/policies.py):
# conv kernels shard their output-channel dim, dense kernels their output
# dim, 1-D vectors (biases, scales, learned carries) shard outright, and
# everything else replicates.  The trailing catch-all makes the defaults
# total over ANY tree; strict user rule sets omit it and get the
# unmatched-leaf error instead.
# The sequence models' leaves (models/hybrid_lm.py, models/looped_lm.py;
# models/moe_lm.py's own follow in MOE_LM_PARTITION_RULES) come first.  Projections are column- then row-parallel in pairs
# (in_z/in_x/in_dt → out_proj, gate/up → down, q/k/v → o), so one
# all-reduce closes each pair; Mamba heads, their conv channels, dt,
# A_log, D and the gated norm go by head; the embedding by vocabulary row
# and an untied head by vocabulary column; B and C (one group, read by
# every head), the block norms and a looped model's exit gate (one
# column) replicate.
HYBRID_LM_PARTITION_RULES = (
    (r"embed/embedding$", P(MODEL_AXIS, None)),
    (r"mamba/(in_z|in_x|in_dt)$", P(None, MODEL_AXIS)),
    (r"mamba/conv_x_kernel$", P(None, None, MODEL_AXIS)),
    (r"mamba/(conv_x_bias|A_log|D|dt_bias|norm_scale)$", P(MODEL_AXIS)),
    (r"mamba/(in_bc|conv_bc_kernel|conv_bc_bias)$", P()),
    (r"mamba/out_proj$", P(MODEL_AXIS, None)),
    (r"attn/(q|k|v)$", P(None, MODEL_AXIS)),
    (r"attn/o$", P(MODEL_AXIS, None)),
    (r"mlp/(gate|up)$", P(None, MODEL_AXIS)),
    (r"mlp/down$", P(MODEL_AXIS, None)),
    (r"(norm[1-4]|final_norm)/scale$", P()),
    (r"head/kernel$", P(None, MODEL_AXIS)),
    (r"exit_gate/(kernel|bias)$", P()),
)

# A sparse-expert model with latent attention (models/moe_lm.py).  The
# STACKED expert leaves ``[experts, m, n]`` shard their EXPERT axis: a
# device holds whole experts, as expert parallelism does.  Latent
# attention's up-projections go by head (column-parallel, closed by the
# row-parallel ``attn/o`` above); its two down-projections are narrow and
# feed a norm over their whole width, so they replicate, as do the norms,
# the router and its selection bias (every device routes every token).
# The shared expert is a gated FFN like ``mlp``.
MOE_LM_PARTITION_RULES = (
    (r"experts/(gate|up|down)$", P(MODEL_AXIS, None, None)),
    (r"shared/(gate|up)$", P(None, MODEL_AXIS)),
    (r"shared/down$", P(MODEL_AXIS, None)),
    (r"attn/(q_b|kv_b)$", P(None, MODEL_AXIS)),
    (r"attn/(q_a|kv_a)$", P()),
    (r"(q_norm|kv_norm|embed_norm|hidden_norm)/scale$", P()),
    (r"moe/(router|router_bias)$", P()),
    (r"mtp/eh$", P(None, MODEL_AXIS)),
)

# A decoder of Mamba-1, differential attention and gated memory units
# (models/sambay_lm.py).  Its fused projections are column-parallel
# (``in_proj``, ``qkv``, and the cross layers' ``q``, which the rule for
# ``attn/(q|k|v)`` above already names), closed by the row-parallel
# ``out_proj`` / ``attn/o`` above; Mamba-1's channels, their conv taps,
# ``dt_proj``'s columns, ``A_log``'s rows, ``dt_bias`` and ``D`` go by
# channel (the last three by the Mamba-2 rule above, which names them);
# ``x_proj`` contracts the channels into Δ's rank, B and C, which every
# channel reads (row-parallel, one all-reduce); a gated
# memory unit is a column- then row-parallel pair whose gate product is by
# channel, like the memory it multiplies.  The differential λ vectors and
# the norm over a head pair's values are a head wide and replicate, as do
# the LayerNorms' biases.
SAMBAY_LM_PARTITION_RULES = (
    (r"mamba/in_proj$", P(None, MODEL_AXIS)),
    (r"mamba/conv_kernel$", P(None, None, MODEL_AXIS)),
    (r"mamba/conv_bias$", P(MODEL_AXIS)),
    (r"mamba/x_proj$", P(MODEL_AXIS, None)),
    (r"mamba/dt_proj$", P(None, MODEL_AXIS)),
    (r"attn/qkv$", P(None, MODEL_AXIS)),
    (r"attn/(qkv_bias|q_bias)$", P(MODEL_AXIS)),
    (r"attn/(o_bias|subln|lambda_[qk][12])$", P()),
    (r"gmu/gmu_in$", P(None, MODEL_AXIS)),
    (r"gmu/gmu_out$", P(MODEL_AXIS, None)),
    (r"(norm[1-4]|final_norm)/bias$", P()),
)

# A sparse-expert decoder whose attention reads a learned selection of keys
# (models/indexed_moe_lm.py).  Its q, k, v, o, router and stacked experts go
# by the rules above that name them.  The indexer's query projection goes by
# index head (column-parallel); its ONE key head, that key's LayerNorm and
# the per-head weights ``index_w`` (16 columns) are read whole by every
# index head and replicate, as the per-head norms of q and k do.
INDEXED_MOE_LM_PARTITION_RULES = (
    (r"indexer/index_q$", P(None, MODEL_AXIS)),
    (r"indexer/(index_k|index_w)$", P()),
    (r"indexer/index_norm/(scale|bias)$", P()),
    (r"k_norm/scale$", P()),
)

# A sparse-expert decoder whose attention is computed inside a compressed
# latent (models/cca_moe_lm.py).  Its q, k, v, o, embedding, stacked experts
# and selection bias go by the rules above that name them.  The head-mixing
# convolution's STACKED ``[taps · heads, d, d]`` matrices shard their
# (tap, head) axis, as the experts shard theirs; the depthwise taps, both
# convolutions' biases and the temperatures are a few thousand values that
# every head's slice reads and replicate.  The router (a down-projection
# into a norm over its whole width, a state's scale, a three-matrix MLP 256
# wide) replicates, as the one-matrix routers do: every device routes every
# token.
CCA_MOE_LM_PARTITION_RULES = (
    (r"attn/conv_head$", P(MODEL_AXIS, None, None)),
    (r"attn/(conv_time|conv_time_bias|conv_head_bias|temperature)$", P()),
    (r"moe/(router_down|router_down_bias|router_state)$", P()),
    (r"moe/router_norm/scale$", P()),
    (r"moe/router_mlp/[wb][123]$", P()),
)

# A sparse-expert decoder of gated-delta-rule and gated full-attention
# layers (models/delta_moe_lm.py).  Its k, v, o, norms, router, shared and
# stacked experts go by the rules above that name them; the full layers'
# ``q`` (each head's query and gate side by side) by the rule for
# ``attn/(q|k|v)``.  The linear mixer's fused ``[q | k | v | z]`` projection
# and the conv over ``[q | k | v]`` go by column (a layout, not a cut by
# head: GSPMD moves what the split into parts needs), closed by the
# row-parallel ``out_proj``; ``A_log`` and ``dt_bias`` by value head; the
# narrow ``[b | a]`` projection, the gated norm's one head of weights and
# the shared expert's one-column gate replicate.
DELTA_MOE_LM_PARTITION_RULES = (
    (r"delta/in_proj_qkvz$", P(None, MODEL_AXIS)),
    (r"delta/conv$", P(None, None, MODEL_AXIS)),
    (r"delta/(A_log|dt_bias)$", P(MODEL_AXIS)),
    (r"delta/(in_proj_ba|norm_scale)$", P()),
    (r"delta/out_proj$", P(MODEL_AXIS, None)),
    (r"moe/shared_gate$", P()),
)

# A sparse-expert decoder whose two kinds of attention layer differ in
# their head count, with a gate a head (models/gated_window_moe_lm.py).  Its
# q, k, v, o (as wide as the layer's own heads), norms, dense FFN, router,
# shared and stacked experts go by the rules above that name them.  The
# gate's projection is one column a head, 48 or 64 of them: it replicates,
# as the other narrow projections do.
GATED_WINDOW_MOE_LM_PARTITION_RULES = (
    (r"attn/head_gate$", P()),
)

CATCH_ALL = r".*"

DEFAULT_PARTITION_RULES = (
        HYBRID_LM_PARTITION_RULES + MOE_LM_PARTITION_RULES
        + SAMBAY_LM_PARTITION_RULES + INDEXED_MOE_LM_PARTITION_RULES
        + CCA_MOE_LM_PARTITION_RULES + DELTA_MOE_LM_PARTITION_RULES
        + GATED_WINDOW_MOE_LM_PARTITION_RULES) + (
    (r"conv[^/]*/kernel$", P(None, None, None, MODEL_AXIS)),
    (r"kernel$", P(None, MODEL_AXIS)),
    (r"(bias|scale|embedding|carry0[^/]*)$", P(MODEL_AXIS)),
    (CATCH_ALL, P()),
)


def _leaf_path_name(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return "/".join(parts)


def _fit_spec_to_shape(spec: P, shape, mesh: Mesh) -> P:
    """Drop sharded dims the leaf cannot honor.

    Two fallbacks, both per-dim and both toward replication: a spec
    longer than the leaf's rank keeps only its first ``ndim`` entries,
    and a dim whose size does not divide its mesh-axis extent is
    replicated (jax requires even shards; padding a *parameter* would
    change the optimization problem, so replication is the honest
    fallback — the rule-author sees it via :func:`sharding_summary`).
    """
    ndim = len(shape)
    entries = list(spec)[:ndim]
    entries += [None] * (ndim - len(entries))
    out = []
    for dim, axis in zip(shape, entries):
        if axis is None:
            out.append(None)
            continue
        names = axis if isinstance(axis, tuple) else (axis,)
        extent = 1
        for nm in names:
            extent *= dict(zip(mesh.axis_names, mesh.devices.shape))[nm]
        out.append(axis if dim % extent == 0 else None)
    return P(*out)


def match_partition_rules(rules, tree: Any, mesh: Mesh,
                          log_unmatched: bool = True) -> Any:
    """Pytree of ``NamedSharding`` from ``(regex, PartitionSpec)`` rules.

    Each leaf's '/'-joined tree path is matched against the rules in
    order (``re.search``); the first hit wins.  Scalar leaves (rank 0 or
    a single element) always replicate.  A leaf NO rule matches raises —
    the rule-coverage check that keeps a partial rule set from silently
    replicating a 100M-param leaf.  Works on arrays and
    ``ShapeDtypeStruct``s (so optimizer-state shardings come from
    ``jax.eval_shape`` without materializing anything): optax states
    embed param-shaped subtrees under the same leaf names, so ONE rule
    set covers params and optimizer state.

    A leaf that only the catch-all ``(".*", P())`` matched is replicated
    as before, and named with its bytes in one warning (``log_unmatched``;
    :func:`sharding_summary` says the same per leaf): a rule set written
    for other models must not replicate a wide leaf in silence.
    """
    compiled = [(re.compile(pat), spec) for pat, spec in rules]
    fell_through = unmatched_leaves(rules, tree) if log_unmatched else {}
    if fell_through:
        import logging

        logging.getLogger(__name__).warning(
            "partition rules: only the catch-all %r matched %d leaves, "
            "which therefore replicate on every device (%d bytes each "
            "device): %s", CATCH_ALL, len(fell_through),
            sum(fell_through.values()),
            ", ".join(f"{k} ({v} B)" for k, v in fell_through.items()))

    def leaf_sharding(path, leaf):
        name = _leaf_path_name(path)
        shape = tuple(getattr(leaf, "shape", ()))
        size = 1
        for d in shape:
            size *= d
        if len(shape) == 0 or size == 1:
            return NamedSharding(mesh, P())  # never partition scalars
        for pat, spec in compiled:
            if pat.search(name) is not None:
                return NamedSharding(mesh, _fit_spec_to_shape(spec, shape, mesh))
        raise ValueError(
            f"no partition rule matched param leaf '{name}' "
            f"(shape {shape}); add a rule (a trailing ('.*', P()) "
            "replicates unmatched leaves explicitly)"
        )

    return jax.tree_util.tree_map_with_path(leaf_sharding, tree)


def unmatched_leaves(rules, tree: Any) -> dict[str, int]:
    """{leaf path: bytes} of the non-scalar leaves that no rule but the
    catch-all ``(".*", P())`` matched: the leaves a rule set written for
    other models replicates without having been asked to."""
    named = [re.compile(pat) for pat, _ in rules if pat != CATCH_ALL]
    out: dict[str, int] = {}

    def visit(path, leaf):
        name = _leaf_path_name(path)
        shape = tuple(getattr(leaf, "shape", ()))
        size = 1
        for d in shape:
            size *= d
        if size > 1 and not any(p.search(name) for p in named):
            out[name] = size * int(leaf.dtype.itemsize)

    jax.tree_util.tree_map_with_path(visit, tree)
    return out


def sharding_summary(tree: Any, shardings: Any,
                     rules=None) -> dict[str, str]:
    """{leaf path: spec} — what the rules actually resolved to (incl.
    divisibility fallbacks), for logs/manifests and the coverage tests.
    Given the ``rules``, a leaf that only the catch-all matched says so
    and gives its bytes."""
    out: dict[str, str] = {}
    fell_through = unmatched_leaves(rules, tree) if rules else {}

    def visit(path, leaf, sh):
        name = _leaf_path_name(path)
        out[name] = str(sh.spec)
        if name in fell_through:
            out[name] += (f" (catch-all: no rule names this leaf; "
                          f"{fell_through[name]} bytes replicated)")

    jax.tree_util.tree_map_with_path(visit, tree, shardings)
    return out


def partition_rules_to_json(rules) -> list:
    """Serializable form of a rule set: [[pattern, [dim entries]], ...]
    where a dim entry is an axis name, a list of axis names, or None.
    Round-trips through :func:`partition_rules_from_json` (the config-
    serialization contract the tests pin)."""
    out = []
    for pat, spec in rules:
        entries = []
        for axis in spec:
            if isinstance(axis, tuple):
                entries.append(list(axis))
            else:
                entries.append(axis)
        out.append([pat, entries])
    return out


def partition_rules_from_json(data) -> tuple:
    rules = []
    for pat, entries in data:
        axes = tuple(
            tuple(e) if isinstance(e, list) else e for e in entries
        )
        rules.append((str(pat), P(*axes)))
    return tuple(rules)
