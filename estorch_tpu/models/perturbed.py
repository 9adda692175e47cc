"""The perturbed-dense primitive: ``x @ (W + c·E)`` without forming the sum.

Every ES member evaluates the centre ``W`` plus its own ``c·E`` (``c =
σ·sign``).  Materialised, that is one weight matrix per member.  Here ``W``
enters un-batched, so under a ``vmap`` over members (or over antithetic
pairs and their two signs) the shared term ``x @ W`` of every projection is
ONE population-wide matmul, and only the correction is per member:

- ``E = A·Bᵀ/√r`` (``noise = (A, B)``, ops/lowrank.py):
  ``x@W + (c/√r)·((x@A)@Bᵀ)``, O((m+n)·r) per row instead of O(m·n);
- dense ``E`` (``noise`` an array; small leaves where factoring would not
  save): ``x@W + c·(x@E)``.

A stack of experts ``W [E, m, n]`` takes the grouped form
(:func:`perturbed_grouped_dense`): rows sorted by expert go through ONE
grouped matmul against the stack, whichever members they belong to.  The
correction is two dense products against the factors of every (member,
expert) laid side by side, ``x @ A_all`` and ``· @ B_allᵀ``, between which
each row keeps the columns of its own pair (a one-hot choice): no row
fetches a copy of its factors.  A stack of one matrix a HEAD that every
row passes (a per-head convolution's taps) is a batched matmul
(:func:`perturbed_headwise_dense`).

The embedding lookup and the tied head take the same factors:
``E[tok] + c·A[tok]·Bᵀ/√r`` and ``h@Eᵀ + c·(h@B)@Aᵀ/√r``.  Both dots
accumulate in float32 and the sum is formed in float32; callers cast once.
The corrections carry the ``es.perturb`` stage scope (obs/trace.py); which
leaf a product reads is the CALLER's to say (``part``: ``lm_blocks.dense``
for every projection, the models for their heads), but for the embedding
lookup, which has one name everywhere and says ``of.embed`` itself.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from ..obs.trace import PERTURB, part, stage

F32 = jnp.float32


def is_factored(noise) -> bool:
    """``(A, B)`` factors, as opposed to a dense noise array."""
    return isinstance(noise, (tuple, list))


def _outer(xa, b):
    """``xa @ bᵀ`` for ``xa [..., r]``, ``b [n, r]``: for the small ranks
    ES uses, a sum of ``r`` broadcast products (it fuses into the add that
    consumes it; a K=1 matmul would not)."""
    b = b.astype(F32)
    r = b.shape[-1]
    if r > 4:
        return jnp.dot(xa, b.T, preferred_element_type=F32)
    out = xa[..., 0:1] * b[:, 0]
    for k in range(1, r):
        out = out + xa[..., k:k + 1] * b[:, k]
    return out


def perturbed_dense(x, w, noise, c, transposed: bool = False):
    """float32 ``x @ (W + c·E)``; ``W`` is ``[m, n]`` (``transposed``: the
    product is with ``Wᵀ``, ``x`` is ``[..., n]``, as a tied head reads an
    embedding).  ``noise`` is ``None`` (the centre alone), ``(A [m, r],
    B [n, r])`` or a dense ``[m, n]`` array; ``c`` a scalar."""
    contract = ((x.ndim - 1,), (1 if transposed else 0,))
    y = jnp.tensordot(x, w, axes=contract, preferred_element_type=F32)
    if noise is None:
        return y
    with stage(PERTURB):
        if not is_factored(noise):
            return y + c * jnp.tensordot(
                x, noise.astype(x.dtype), axes=contract,
                preferred_element_type=F32)
        a, b = (noise[1], noise[0]) if transposed else noise
        r = a.shape[-1]
        xa = jnp.dot(x, a.astype(x.dtype), preferred_element_type=F32)
        scale = c / jnp.sqrt(jnp.asarray(r, F32))
        return y + scale * _outer(xa, b)


def leaf_columns(w, noise, groups: int, cols: slice):
    """``(w, noise)`` of the leaf ``w [m, groups · width]`` cut to the
    columns ``cols`` of each group's ``width`` (a head's), its noise cut
    alike (a factor pair's ``B [groups · width, r]`` by rows, a dense one
    by columns): ``x @ (W + c·E)`` cut by columns IS the product with the
    cut leaf, column for column.  A projection whose output is split
    before its readers is computed a part at a time, so that each part
    leaves its matmul in the layout its reader wants; cut AFTER the
    matmul, XLA writes the whole output once in one layout, then cuts it
    and transposes the parts (PERF.md, PR 34: 0.065 s a generation)."""
    def cut(x, axis):
        shape = x.shape
        x = x.reshape(*shape[:axis], groups, -1, *shape[axis + 1:])
        x = x[(slice(None),) * (axis + 1) + (cols,)]
        return x.reshape(*shape[:axis], -1, *shape[axis + 1:])

    if noise is not None:
        noise = ((noise[0], cut(noise[1], 0)) if is_factored(noise)
                 else cut(noise, 1))
    return cut(w, 1), noise


def perturbed_grouped_dense(x, w, group_sizes, noise, c, row_expert,
                            row_member):
    """float32 rows ``x[i] @ (W[e_i] + c[m_i]·E[m_i, e_i])`` of a stack of
    experts ``W [E, m, n]``, for rows ``x [R, m]`` SORTED by expert:
    expert ``e``'s rows are the ``group_sizes[e]`` after those of the
    experts before it; rows past ``sum(group_sizes)`` come out as whatever
    the correction alone gives (the caller masks them).  The centre's
    product is one grouped matmul (``jax.lax.ragged_dot``; on a TPU a
    kernel whose work follows the rows in the groups, not ``E`` × rows)
    shared by every member whose rows are among ``x``.  ``noise``: ``None``
    or ``(A [M, E, m, r], B [M, E, n, r])``, one factor pair per (member,
    expert); ``c [M]``; ``row_expert``, ``row_member`` ``[R]`` say whose
    pair corrects each row.

    The correction gathers no factor row by row (``A[m_i, e_i]`` would be
    a ``[R, m, r]`` copy out of ``M·E`` distinct pairs, read with the MXU
    idle: PERF.md, PR 45).  ``x @ A`` is ONE product against all ``M·E·r``
    columns (operands in ``x``'s dtype, float32 sums, as
    :func:`perturbed_dense`); a row keeps its own pair's ``r`` of them
    times ``c[m_i]/√r`` and zeros elsewhere; that ``[R, M·E·r]`` goes
    against all of ``B`` in float32 at ``HIGHEST``: a sum with ``r``
    non-zero terms a row, neither side rounded.  Both are dense products
    under ``es.perturb`` and the caller's part, not grouped matmuls."""
    y = jax.lax.ragged_dot(x, w, group_sizes, preferred_element_type=F32)
    if noise is None:
        return y
    with stage(PERTURB):
        a, b = noise
        members, held, _, r = a.shape
        pairs = members * held
        xa = jnp.einsum("im,jmr->ijr", x,
                        a.reshape(pairs, -1, r).astype(x.dtype),
                        preferred_element_type=F32)
        own = (row_member * held + row_expert)[:, None] == jnp.arange(
            pairs, dtype=row_expert.dtype)
        scale = jnp.repeat(c, held) / jnp.sqrt(jnp.asarray(r, F32))
        xa = jnp.where(own[..., None], xa * scale[:, None], 0.0)
        return y + jnp.einsum("ijr,jnr->in", xa,
                              b.reshape(pairs, -1, r).astype(F32),
                              precision=jax.lax.Precision.HIGHEST)


def perturbed_headwise_dense(x, w, noise, c):
    """float32 ``x[:, h] @ (W[h] + c·E[h])`` HEAD-MAJOR, ``[H, T, n]``, for
    ``x [T, H, m]`` against a stack ``W [H, m, n]`` of one matrix a head:
    every row passes EVERY matrix of the stack with its own slice (a
    batched matmul; nothing is routed, so no sort and no groups).  The
    output keeps the batched product's own order, heads first: the caller
    sums its terms and turns the sum once.  ``noise``: ``None``, ``(A [H,
    m, r], B [H, n, r])`` one factor pair a matrix (a stacked leaf of
    ops/lowrank.py), or a dense ``[H, m, n]`` array; ``c`` a scalar."""
    y = jnp.einsum("thm,hmn->htn", x, w, preferred_element_type=F32)
    if noise is None:
        return y
    with stage(PERTURB):
        if not is_factored(noise):
            return y + c * jnp.einsum("thm,hmn->htn", x,
                                      noise.astype(x.dtype),
                                      preferred_element_type=F32)
        a, b = noise
        xa = jnp.einsum("thm,hmr->htr", x, a.astype(x.dtype),
                        preferred_element_type=F32)
        scale = c / jnp.sqrt(jnp.asarray(a.shape[-1], F32))
        return y + scale * jnp.einsum("htr,hnr->htn", xa, b.astype(F32))


def perturbed_embed(tokens, table, noise, c):
    """float32 rows ``(E + c·A·Bᵀ/√r)[tokens]``: the lookup reads the
    centre's rows and the factor ``A``'s rows, never a perturbed table."""
    with part("embed"):
        rows = jnp.take(table, tokens, axis=0).astype(F32)
        if noise is None:
            return rows
        with stage(PERTURB):
            if not is_factored(noise):
                return rows + c * jnp.take(noise, tokens, axis=0).astype(F32)
            a, b = noise
            scale = c / jnp.sqrt(jnp.asarray(a.shape[-1], F32))
            return rows + scale * _outer(
                jnp.take(a, tokens, axis=0).astype(F32), b)


def perturbed_leaf(w, noise, c):
    """float32 ``w + c·e`` for the small leaves no matmul reads (norm
    scales, conv taps, per-head scalars): materialised per member."""
    w = w.astype(F32)
    if noise is None:
        return w
    with stage(PERTURB):
        return w + c * noise.astype(F32)


# the mesh axis a policy's partition rules may name: the one parameter
# leaves are cut over (parallel/mesh.py builds its meshes with it)
MODEL_AXIS = "model"


@dataclasses.dataclass(frozen=True)
class PolicyDeclaration:
    """What a policy states of itself, once, for the engine that runs it and
    for the run's records (:func:`declaration_of`).  Every field has the
    value of a policy that states nothing: an MLP, a conv or recurrent
    policy.  A sequence model builds its own in ONE place, its
    ``declaration()``, from its sizes, beside the ``param_shapes`` that
    name its leaves; the param-sharded engine reads the fields where it
    cuts the state, sizes chunks and reports its build
    (parallel/sharded.py), ``lowrank_spec_for`` where it lays out the
    noise, ``ES`` merges ``facts`` into the gauges and
    ``run_manifest()["config"]``.  Nothing under ``parallel/`` knows a
    model's leaves or a kernel's name: both are said here.

    - ``partition_rules``: ``(regex, PartitionSpec over MODEL_AXIS)`` pairs
      for the leaves THIS model names, first match wins
      (``parallel/mesh.py::match_partition_rules``); the engine tries them
      before the general rules of a policy that states none
      (``DEFAULT_PARTITION_RULES``).  Rules for the leaves of shared blocks
      are models/lm_blocks.py's, composed by each model in the order it
      needs;
    - ``kernels``: ``(rule, widths)`` pairs, one a hand-written kernel the
      forward calls: ``rule`` the ``*_facts`` function of the kernel's own
      module (``ops/pallas_*.py``), ``widths`` what this model calls it
      with, plain tuples so that two declarations compare equal.  The
      engine reports ``rule(scope, *widths)`` of each at build
      (ops/kernel_facts.py) and knows none by name;
    - ``leaf_rows``: ``{leaf path: positions per application}`` of the
      leaves a sequence does NOT pass whole (an untied head run in blocks);
    - ``leaf_rows_per_token``: ``{stacked leaf path: rows of the leaf's
      input per position}`` (an expert sees the pairs routed to it);
    - ``stacked_leaves``: leaves whose leading axis indexes experts (or a
      per-head convolution's (tap, head) matrices), one factor pair a
      matrix of the stack; ``dense_noise_leaves``: 2-D leaves no matmul
      reads, dense noise whatever the factoring rule says of their shape;
    - ``float32_leaves``: leaves the forward reads in float32 whatever the
      compute dtype;
    - ``selection_bytes``: ``horizon -> bytes`` of the temporaries ONE
      member's learned selection of keys holds, for the chunk rule;
    - ``outputs``: the names, in order, of what the policy returns after
      ``(score, behaviour)``; the engine reduces each by its name;
    - ``facts``: what the model itself adds to the gauges and the manifest.
    """

    partition_rules: tuple = ()
    kernels: tuple = ()
    leaf_rows: dict = dataclasses.field(default_factory=dict)
    leaf_rows_per_token: dict = dataclasses.field(default_factory=dict)
    stacked_leaves: tuple = ()
    dense_noise_leaves: tuple = ()
    float32_leaves: tuple = ()
    selection_bytes: Callable[[int], int] | None = None
    outputs: tuple = ()
    facts: dict = dataclasses.field(default_factory=dict)


def declaration_of(module) -> PolicyDeclaration:
    """The module's ``declaration()``; the defaults for one that has none."""
    own = getattr(module, "declaration", None)
    return PolicyDeclaration() if own is None else own()


def perturbed_forward(module):
    """``(params, noise, c, obs) -> policy output`` of ``params + c·noise``
    for a module that has such a form (``noise`` as
    ``LowRankTreeSpec.unpack`` gives it), else ``None``.  A module brings
    its own as ``perturbed_apply``; the MLP's is models/decomposed.py's."""
    own = getattr(module, "perturbed_apply", None)
    if own is not None:
        return own
    from .decomposed import mlp_lowrank_apply, supports_decomposed

    if not supports_decomposed(module):
        return None

    def mlp_apply(params, noise, c, obs):
        return mlp_lowrank_apply(module, params, noise, c, obs)

    return mlp_apply


def lowrank_spec_for(module, params, rank: int):
    """The module's noise layout: the tree spec (ops/lowrank.py); an MLP
    keeps the kernels-then-biases order its runs have always drawn."""
    from ..ops.lowrank import make_lowrank_spec, make_lowrank_tree_spec
    from .decomposed import supports_decomposed

    if supports_decomposed(module):
        return make_lowrank_spec(params, rank)
    policy = declaration_of(module)
    return make_lowrank_tree_spec(
        params, rank, stacked=policy.stacked_leaves,
        dense=policy.dense_noise_leaves)
