"""Low-rank perturbations: per-layer E = A·Bᵀ/√r noise (ES at hyperscale).

The classic estimator perturbs every weight independently: ε_i is a full
(dim,) table slice, so noise memory/bandwidth per member is O(dim) and the
per-step forward must touch an O(m·n) noise matrix per layer.  The low-rank
family (PAPERS.md "Evolution Strategies at the Hyperscale") replaces each
layer's kernel noise with

    E = A @ Bᵀ / √r,     A ~ N(0,1)^(m×r),  B ~ N(0,1)^(n×r)

whose entries remain zero-mean unit-variance (E[AᵢₖBⱼₖ]=0, Var=r·(1/r)=1),
while the per-member noise state shrinks from Σ m·n to Σ (m+n)·r — at
Humanoid-MLP size (376→256→256→17, r=1) that is ~166k → ~2.4k floats, the
difference between HBM-resident populations of 10k and 700k members — and
the forward's noise term drops from O(m·n) to O((m+n)·r) per step:

    x @ (W + c·A Bᵀ/√r) = x@W + (c/√r)·((x@A) @ Bᵀ)

Layers where factoring would not actually save noise floats
((m+n)·r ≥ m·n — e.g. a 16×1 continuous head at any rank, or a small
square layer at high rank) fall back to exact dense Gaussian noise: the
fallback is exact AND no larger.  Bias noise is always dense (biases are
already O(n)).

The rank-weighted update never materializes any member's E_i:

    ΔW = Σ_i w_i A_i Bᵀ_i / √r = einsum('imr,inr->mn', w·A, B)/√r

one MXU contraction per layer over the whole population.  This is an
APPROXIMATION of isotropic-Gaussian ES (the search distribution is no
longer Gaussian in weight space); the hyperscale paper's result is that
the estimator's performance matches full ES as layer dims grow.

Sampling rides the same shared-noise-table machinery as the full-rank path
(ops/noise.py): one table offset per member/pair, A‖B‖dense‖bias noise
unpacked from a single contiguous (noise_dim,) slice — workers never
exchange noise, exactly as the reference's seed-passing protocol intends
(SURVEY.md §2.8).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class LowRankTreeSpec:
    """Static layout of one member's noise vector over ANY param pytree
    (leaf index = position in ``jax.tree_util.tree_flatten``).

    ``lr_leaves``: (leaf_index, m, n, a_off, b_off): 2-D leaves where
    factoring saves ((m+n)·rank < m·n) carry factors A (m, r) and B (n, r)
    at those offsets.  ``stacked_leaves``: (leaf_index, e, m, n, a_off,
    b_off): leaves ``[e, m, n]`` whose leading axis indexes experts (the
    caller names them) carry ONE factor pair per expert, A (e, m, r) and
    B (e, n, r): ``E[k] = A[k]·B[k]ᵀ/√r``, independent across experts as
    the entries of a dense E would be.  ``dense_leaves``: (leaf_index,
    shape, size, off): every other leaf (biases, conv taps, per-head
    scalars, norm scales, a 16×1 head at any rank) carries exact dense
    noise, which is exact AND no larger.  Offsets are assigned in
    ``order`` (default: leaf order).
    """

    rank: int
    noise_dim: int
    treedef: Any
    lr_leaves: tuple
    dense_leaves: tuple
    stacked_leaves: tuple = ()

    @property
    def n_leaves(self) -> int:
        return (len(self.lr_leaves) + len(self.dense_leaves)
                + len(self.stacked_leaves))

    def unpack(self, noise_vec: jax.Array) -> Any:
        """(noise_dim,) slice → the params' pytree with ``(A, B)`` at each
        factored leaf and the dense noise array at every other: what a
        perturbed forward (models/perturbed.py) reads.  ``noise_vec`` may
        carry leading batch axes (one row per pair)."""
        r = self.rank
        lead = noise_vec.shape[:-1]
        leaves = [None] * self.n_leaves
        for i, m, n, a_off, b_off in self.lr_leaves:
            leaves[i] = (
                noise_vec[..., a_off:a_off + m * r].reshape(lead + (m, r)),
                noise_vec[..., b_off:b_off + n * r].reshape(lead + (n, r)))
        for i, e, m, n, a_off, b_off in self.stacked_leaves:
            leaves[i] = (
                noise_vec[..., a_off:a_off + e * m * r].reshape(
                    lead + (e, m, r)),
                noise_vec[..., b_off:b_off + e * n * r].reshape(
                    lead + (e, n, r)))
        for i, shape, size, off in self.dense_leaves:
            leaves[i] = noise_vec[..., off:off + size].reshape(lead + shape)
        return jax.tree_util.tree_unflatten(self.treedef, leaves)


def make_lowrank_tree_spec(params: Any, rank: int, order=None,
                           stacked=(), dense=()) -> LowRankTreeSpec:
    """Layout from ANY param pytree (arrays or ``ShapeDtypeStruct``s).
    ``order``: the leaf indices in the order their noise is laid out.
    ``stacked``: the '/'-joined paths of the leaves ``[e, m, n]`` whose
    leading axis indexes experts (a model's ``stacked_leaves``): each is
    factored per expert where that saves, by the 2-D rule on ``(m, n)``.
    ``dense``: the paths of 2-D leaves that take dense noise whatever the
    rule says of their shape (a model's ``dense_noise_leaves``: a leaf no
    matmul reads, such as Mamba-1's ``A_log [d_inner, d_state]``, is a
    table of independent scalars, and a rank-r product is no model of
    those)."""
    if rank < 1:
        raise ValueError(f"low_rank must be >= 1, got {rank}")
    leaves, treedef = jax.tree_util.tree_flatten(params)
    stacked_at, dense_at = set(), set()
    if stacked or dense:
        paths = ["/".join(str(getattr(k, "key", k)) for k in path)
                 for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]]
        stacked_at = {paths.index(p) for p in stacked}
        dense_at = {paths.index(p) for p in dense}
    lr_leaves, dense_leaves, stacked_leaves = [], [], []
    off = 0
    for i in (range(len(leaves)) if order is None else order):
        shape = tuple(int(d) for d in leaves[i].shape)
        if (i in stacked_at and len(shape) == 3
                and rank * (shape[1] + shape[2]) < shape[1] * shape[2]):
            e, m, n = shape
            stacked_leaves.append((i, e, m, n, off, off + e * m * rank))
            off += e * (m + n) * rank
            continue
        # low-rank only where it actually SAVES: (m+n)·r < m·n (this also
        # implies r < min(m, n), since mn/(m+n) < min(m, n)); otherwise the
        # factors would cost more noise floats than exact dense Gaussian,
        # an approximation strictly worse than the thing it approximates
        if (len(shape) == 2 and i not in dense_at
                and rank * (shape[0] + shape[1]) < shape[0] * shape[1]):
            m, n = shape
            lr_leaves.append((i, m, n, off, off + m * rank))
            off += (m + n) * rank
        else:
            size = 1
            for s in shape:
                size *= s
            dense_leaves.append((i, shape, size, off))
            off += size
    return LowRankTreeSpec(
        rank=rank, noise_dim=off, treedef=treedef,
        lr_leaves=tuple(sorted(lr_leaves)),
        dense_leaves=tuple(sorted(dense_leaves)),
        stacked_leaves=tuple(sorted(stacked_leaves)),
    )


def make_lowrank_spec(params: Any, rank: int) -> LowRankTreeSpec:
    """The MLP case ({name: {kernel, bias}}): the tree spec with the noise
    laid out kernels first (layer order), then biases: the layout the
    MLP runs have always drawn from the table, so their noise is
    unchanged."""
    from ..models.decomposed import _ordered_dense_names

    paths = [tuple(str(k.key) for k in path) for path, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]]
    names = _ordered_dense_names(params)
    order = ([paths.index((n, "kernel")) for n in names]
             + [paths.index((n, "bias")) for n in names])
    return make_lowrank_tree_spec(params, rank, order=order)


def lowrank_program_factors(rank: int, m: int, n: int, key: jax.Array):
    """In-program (A, B) factors for one leaf/row — the sharded path's
    table-free twin of :meth:`LowRankTreeSpec.unpack` (parallel/sharded.py):
    instead of unpacking factors from a table slice, they are generated
    from the (key, generation, row, leaf) chain (ops/noise.py).  Same
    statistics (entries of A·Bᵀ/√r are zero-mean unit-variance), same
    savings (the update einsum never materializes dense E)."""
    return (
        jax.random.normal(jax.random.fold_in(key, 0), (m, rank), jnp.float32),
        jax.random.normal(jax.random.fold_in(key, 1), (n, rank), jnp.float32),
    )


def lowrank_program_leaf_noise(rank: int, m: int, n: int, key: jax.Array) -> jax.Array:
    """Dense E = A·Bᵀ/√r from in-program factors (the eval-side form; the
    update side keeps the factors and einsums them — no dense E)."""
    a, b = lowrank_program_factors(rank, m, n, key)
    return (a @ b.T) / jnp.sqrt(jnp.float32(rank))


def lowrank_tree_noise(spec: LowRankTreeSpec, noise_vec: jax.Array) -> Any:
    """Materialize the DENSE noise pytree one member's slice represents:
    snapshot/debug path (member_params) and the recurrent policies'
    once-per-episode perturbation, not the per-step hot path."""
    r = spec.rank
    scale = 1.0 / jnp.sqrt(jnp.float32(r))
    leaves = [None] * spec.n_leaves
    for i, m, n, a_off, b_off in spec.lr_leaves:
        a = noise_vec[a_off:a_off + m * r].reshape(m, r)
        b = noise_vec[b_off:b_off + n * r].reshape(n, r)
        leaves[i] = (a @ b.T) * scale
    for i, e, m, n, a_off, b_off in spec.stacked_leaves:
        a = noise_vec[a_off:a_off + e * m * r].reshape(e, m, r)
        b = noise_vec[b_off:b_off + e * n * r].reshape(e, n, r)
        leaves[i] = jnp.einsum("emr,enr->emn", a, b) * scale
    for i, shape, size, off in spec.dense_leaves:
        leaves[i] = noise_vec[off:off + size].reshape(shape)
    return jax.tree_util.tree_unflatten(spec.treedef, leaves)


def lowrank_tree_perturb(
    spec: LowRankTreeSpec, params: Any, noise_vec: jax.Array, scale
) -> Any:
    """``params + scale · dense(noise_vec)``: one member's perturbed tree.
    Recurrent cells thread a carry through the episode scan, so their
    forward is not restructured around the factors; the dense perturbation
    is materialized ONCE PER EPISODE (amortized over the horizon) while the
    noise STATE stays O(noise_dim) and the update stays factored."""
    noise = lowrank_tree_noise(spec, noise_vec)
    return jax.tree_util.tree_map(lambda w, e: w + scale * e, params, noise)


def lowrank_tree_weighted_sum(
    spec: LowRankTreeSpec, noise_mat: jax.Array, weights: jax.Array
) -> Any:
    """Σ_i w_i · dense(noise_i) as a pytree, without materializing any
    member's dense noise.

    ``noise_mat``: (k, noise_dim) stacked member/pair slices; ``weights``:
    (k,) rank weights (mirrored: already pair-folded w⁺−w⁻, exact because
    a pair shares ONE slice, so ±E share (A, B) and fold like full-rank
    noise).  One MXU contraction per factored leaf."""
    r = spec.rank
    k = noise_mat.shape[0]
    scale = 1.0 / jnp.sqrt(jnp.float32(r))
    leaves = [None] * spec.n_leaves
    for i, m, n, a_off, b_off in spec.lr_leaves:
        a = noise_mat[:, a_off:a_off + m * r].reshape(k, m, r)
        b = noise_mat[:, b_off:b_off + n * r].reshape(k, n, r)
        leaves[i] = jnp.einsum("kmr,knr->mn", a * weights[:, None, None], b) * scale
    for i, e, m, n, a_off, b_off in spec.stacked_leaves:
        a = noise_mat[:, a_off:a_off + e * m * r].reshape(k, e, m, r)
        b = noise_mat[:, b_off:b_off + e * n * r].reshape(k, e, n, r)
        leaves[i] = jnp.einsum(
            "kemr,kenr->emn", a * weights[:, None, None, None], b) * scale
    for i, shape, size, off in spec.dense_leaves:
        e = noise_mat[:, off:off + size]
        leaves[i] = (weights @ e).reshape(shape)
    return jax.tree_util.tree_unflatten(spec.treedef, leaves)
