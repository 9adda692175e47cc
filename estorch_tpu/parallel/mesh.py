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

from ..models.perturbed import MODEL_AXIS  # the axis a policy's rules name

POP_AXIS = "pop"


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

# The rules of a policy that states none (models/policies.py's families),
# and the LAST rules tried for one that states its own
# (``PolicyDeclaration.partition_rules``, written beside the model's
# ``param_shapes``: no table here names a model's leaves): conv kernels shard
# their output-channel dim, dense kernels their output dim, 1-D vectors
# (biases, scales, learned carries) shard outright, and everything else
# replicates.  The trailing catch-all makes the defaults total over ANY
# tree; strict user rule sets omit it and get the unmatched-leaf error
# instead.
CATCH_ALL = r".*"

DEFAULT_PARTITION_RULES = (
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
