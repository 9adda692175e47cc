"""Shared noise table and antithetic (mirrored) perturbation sampling.

TPU-native replacement for the reference's per-member ``torch.randn_like``
noise draw (reference: ``estorch/estorch.py``, upstream path — see SURVEY.md
§2 item 8; the mount was empty, so no line numbers).  Instead of generating
fresh Gaussian noise per population member — and shipping it (or its seed)
between processes — we keep ONE immutable float32 table in HBM and address
it with per-member integer offsets.  This is the OpenAI-ES shared-noise-table
design and the `north_star` of BASELINE.json:

- noise never crosses the wire: every device derives identical offsets from a
  shared PRNG key, so the update is reconstructed locally and reduced with a
  single ``lax.psum``;
- a member's noise is one contiguous span of the table, addressed by an
  integer, instead of Python-loop RNG.  How whole rows LEAVE the table is
  the engine's choice (``ESEngine.noise_gather_form``): ``NoiseTable.slice``
  under ``vmap`` is the portable form and the oracle, but XLA lowers it on
  the TPU to a sequential loop that copies one unaligned row per step
  (3-5% of the HBM peak), so on a TPU mesh the pair-shared evaluation and
  the update move rows by DMA instead (ops/pallas_noise.py);
- antithetic pairs (mirrored sampling, Salimans et al. 2017 §2) share an
  offset with flipped sign, halving table reads and variance.

All functions are pure and jit/shard_map compatible.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

DEFAULT_TABLE_SIZE = 1 << 25  # 32M floats = 128 MiB of HBM; OpenAI-ES used 250M.


@dataclasses.dataclass(frozen=True)
class NoiseTable:
    """An immutable shared Gaussian noise table.

    ``data`` lives in HBM (or host RAM under the CPU backend).  ``size`` is
    static so slice shapes stay known to XLA.
    """

    data: jax.Array  # (size,) float32, ~N(0, 1)
    seed: int
    size: int

    def slice(self, offset: jax.Array, dim: int) -> jax.Array:
        """Noise vector of length ``dim`` starting at ``offset`` (traced ok).
        The definition of a noise row; many rows at once on a TPU are
        ops/pallas_noise.py::gather_noise_rows, bit-identical to this."""
        return jax.lax.dynamic_slice(self.data, (offset,), (dim,))


def _tree_flatten(t: NoiseTable):
    return (t.data,), (t.seed, t.size)


def _tree_unflatten(aux, children):
    (data,) = children
    seed, size = aux
    return NoiseTable(data=data, seed=seed, size=size)


jax.tree_util.register_pytree_node(NoiseTable, _tree_flatten, _tree_unflatten)


def make_noise_table(
    size: int = DEFAULT_TABLE_SIZE, seed: int = 0, dtype=jnp.float32
) -> NoiseTable:
    """Build the shared table once, deterministically from ``seed``.

    Every host/device that calls this with the same ``(size, seed)`` holds a
    bit-identical table — the precondition for broadcast-free updates.
    Generated in one XLA call (threefry is counter-based, so this is
    reproducible across backends).
    """
    key = jax.random.key(seed)
    data = jax.random.normal(key, (size,), dtype=dtype)
    return NoiseTable(data=data, seed=seed, size=size)


def sample_pair_offsets(
    key: jax.Array, n_pairs: int, table_size: int, dim: int
) -> jax.Array:
    """Uniform offsets for ``n_pairs`` antithetic pairs, each in [0, size-dim].

    Deterministic in ``key``: all devices compute the identical offset vector
    and slice their own shard — this replaces the reference's parameter
    broadcast entirely (BASELINE.json north_star).
    """
    if dim > table_size:
        raise ValueError(
            f"parameter dim {dim} exceeds noise table size {table_size}; "
            "grow the table (noise_table_size) to at least a few times dim"
        )
    return jax.random.randint(key, (n_pairs,), 0, table_size - dim + 1, dtype=jnp.int32)


def pair_signs(population_size: int) -> jax.Array:
    """Signs (+1, -1, +1, -1, ...) for mirrored sampling.

    Member ``2k`` evaluates ``θ + σ·ε_k``; member ``2k+1`` evaluates
    ``θ - σ·ε_k``.  ``population_size`` must be even.
    """
    if population_size % 2 != 0:
        raise ValueError(f"mirrored sampling needs an even population, got {population_size}")
    return jnp.where(jnp.arange(population_size) % 2 == 0, 1.0, -1.0).astype(jnp.float32)


def member_offsets(pair_offsets: jax.Array) -> jax.Array:
    """Expand per-pair offsets to per-member offsets: (n_pairs,) → (2*n_pairs,)."""
    return jnp.repeat(pair_offsets, 2)


# ---------------------------------------------------------------------------
# in-program noise (the hyperscale sharded path, parallel/sharded.py)
# ---------------------------------------------------------------------------
#
# The table above is the SECOND of three noise representations; at
# param-sharded scale even the table is a liability (128 MiB replicated
# HBM, and table offsets address the FLAT param vector — a layout a
# sharded tree no longer has).  The third representation generates ε
# inside the jitted program, keyed on (key, generation, row, leaf):
# threefry is counter-based, so the values are identical on every mesh
# shape and no ε buffer ever exists host-side or whole on one device —
# under GSPMD each device computes exactly its shard of each normal()
# (the same no-materialization idea as ops/pallas_noise.py's weighted sum,
# moved from DMA engines into the RNG).  These three helpers
# define THE keying contract in one place so the eval-side perturbation
# and the update-side reduction can never diverge.


def leaf_noise_keys(gen_key: jax.Array, n_leaves: int) -> list[jax.Array]:
    """Per-leaf base keys for one generation's in-program noise.

    ``gen_key`` is the per-generation offset stream key (engine
    ``_gen_keys``); leaf ``i`` of the param tree (tree_flatten order)
    draws from ``fold_in(gen_key, i)``.  Static count → a Python list,
    resolved at trace time."""
    return [jax.random.fold_in(gen_key, i) for i in range(n_leaves)]


def row_noise_key(leaf_key: jax.Array, row: jax.Array) -> jax.Array:
    """Key for noise row ``row`` (pair index when mirrored, member index
    otherwise) of one leaf — the (key, generation, row, leaf) chain's
    last link.  ``row`` may be traced (vmapped over chunks)."""
    return jax.random.fold_in(leaf_key, row)


def program_noise(leaf_key: jax.Array, row: jax.Array, shape) -> jax.Array:
    """One leaf's ε for one noise row, generated in-program: ~N(0,1),
    deterministic in (leaf_key, row), identical on any mesh."""
    return jax.random.normal(row_noise_key(leaf_key, row), shape, jnp.float32)


# ---------------------------------------------------------------------------
# scenario parameter streams (estorch_tpu/scenarios, docs/scenarios.md)
# ---------------------------------------------------------------------------

SCENARIO_STREAM_SALT = 0x5CE7A2  # disjoint from every training stream: the
# engine folds the STATE key with (generation, 0|1) and the rollout key
# with member/center/probe indices; scenario draws fold a FRESH key built
# from the distribution's own integer seed, salted so a user reusing one
# seed integer for both ES and the distribution still gets disjoint streams


def scenario_variant_key(seed: int, variant) -> jax.Array:
    """THE ``(seed, variant)`` key for scenario-parameter draws.

    ``variant`` may be traced (the in-program assignment path draws it
    from the member's rollout key) or a Python int (host-side concrete
    draws for manifests and the sequential bench leg) — threefry is
    counter-based, so both spellings produce identical parameters.
    Deterministic in ``(seed, variant)`` alone: the same variant draws
    the same physics constants in every generation, member, process, and
    mesh shape, which is what makes a scenario REPLAYABLE."""
    base = jax.random.fold_in(jax.random.PRNGKey(seed), SCENARIO_STREAM_SALT)
    return jax.random.fold_in(base, variant)


@partial(jax.jit, static_argnames=("dim",))
def member_noise(table: NoiseTable, offsets: jax.Array, signs: jax.Array, dim: int) -> jax.Array:
    """Materialize signed noise rows for a batch of members: (n, dim).

    Only used for small batches (tests, chunked gradient accumulation).
    The engine's own evaluation gathers its rows inside ``_eval_local``
    (parallel/engine.py) and DOES hold a whole chunk's noise at once —
    with ``eval_chunk=0`` the whole local shard's: one row per member, or
    one per antithetic pair in the pair-shared form; only ``low_rank``
    avoids a ``(rows, dim)`` slab.
    """
    rows = jax.vmap(lambda o: table.slice(o, dim))(offsets)
    return rows * signs[:, None]
