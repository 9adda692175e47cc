"""Forward-only Pallas TPU kernels for the chunked gated delta rule
(models/delta_moe_lm.py::gated_delta_rule has the algebra): a chunk's
triangular system and the state carried over the chunks stay in VMEM.

The XLA form writes every intermediate of a chunk to HBM and reads it back
(``K Kᵀ``, the ``[chunk, chunk]`` decay tiles, ``A``, each level of the
blocked inverse: 134 MB each a member and layer at 16,384 positions of 32
value heads), multiplies tiles of 8 to 64 rows as batched float32 matmuls,
and carries the ``[key_dim, value_dim]`` float32 state of every head across
HBM at every step of a ``lax.scan`` over the chunks: 3.1 µs a (chunk, value
head) at 0.014 of the MXU's peak (PERF.md §5, PR 52).  Here the same terms
are computed by two kernels, one under each of the rule's two parts of
``es.ssm``, a TILE of 128 positions at a time: the tile's chunks (two of 64)
are the diagonal blocks of ONE ``[128, 128]`` matrix and masks keep them
apart, so that every product fills the MXU's array and every float32 tile
its registers' lanes.

- :func:`solve_chunks` (``of.solve``): grid ``(key heads, blocks of
  tiles)``, every step its own.  A tile of a key head: ``K Kᵀ`` once, then
  for each of the head's value heads the decay tile ``e^{γ_i - γ_j}``, ``A =
  tril₋(diag β (K Kᵀ ∘ decay))`` inside each chunk, ``T = (I + A)⁻¹``, ``W
  = T diag β (K ∘ e^γ)`` and ``U = T diag β V``.  Writes ``W [T, nv·dk]``
  and ``U [T, nv·dv]``.
- :func:`chain_chunks` (``of.carry``): grid ``(key heads, blocks of
  tiles)``, the blocks innermost and in turn.  The states ``S [dk, dv]`` of
  a key head's value heads live in VMEM scratch from the head's first chunk
  to its last (zeroed at its first block); a tile: ``Q Kᵀ`` and the decay
  tile once (computed again from ``γ``, not read back), then its chunks in
  turn, a value head's ``V' = U - W S``, ``O = (Q ∘ e^γ) S + (Q Kᵀ ∘ decay)
  V'``, ``S' = e^{γ_C} S + (K ∘ e^{γ_C - γ})ᵀ V'``; ``W`` and ``Q ∘ e^γ``
  meet the state in one product.

No head-major copy: ``q`` and ``k`` are read as ``[T, nk·dk]`` and ``v``,
``W``, ``U``, ``o`` as ``[T, nv·…]`` in blocks of :data:`BLOCK_ROWS` rows at
column block = key head (a key head's value heads lie side by side).  What
a (tile, value head) needs of ``g`` and ``β`` is one ROW each (``γ``, the
running sum of ``g`` inside each chunk, and ``β``: :func:`decay_rows` lays
them ``[nk, tiles, 2·rep, 128]``, 2 MB a sequence); where a term scales
rows (``diag β``, ``e^γ``) the kernel turns the row into a column through
the identity's mask and a lane sum.

The triangular inverse is the XLA form's blocked one with MASKS in place of
slices: the :data:`INVERSE_BASE`-row diagonal blocks of ``A`` (a mask) by
the finite product ``(I - D)(I + D²)(I + D⁴)``, whose terms stay bounded at
8 rows whatever the keys, then a level at a time ``X ← X - X B X`` with
``B`` the level's sub-diagonal blocks of ``A`` (a mask), of which only the
SECOND block of each pair has rows, so half the rows are multiplied: ten
products a (tile, value head) at chunk 64, the XLA form's values up to the
order of a float32 sum (on the v5e the two forms agreed to the last bit:
PERF.md §6, PR 53).

What bounds the kernels (Mosaic's schedule for a described v5e, PERF.md §6,
PR 53): a float32 product at ``HIGHEST`` is six passes of the MXU, and a
pass streams its left operand's rows through the array at one ``[8, 128]``
register every two cycles, whatever the tile holds; the schedule issues 0.46
of those a cycle.  So the cost is the ROWS streamed (1,824 registers a tile
and key head in the solve, 1,056 in the chain, at two value heads a key
head), not the tiles' useful entries: the merge levels' half is what the
masks give back, and the value heads' products are written side by side
because the schedule follows program order and overlaps one head's pass
with the other's pushes.

Precision is the XLA form's: float32 operands, state and accumulation,
every product at ``HIGHEST`` (Mosaic's ``contract_precision<fp32>``); the
decay enters only as ``e^{γ_i - γ_j}`` with ``i >= j``, ``e^γ`` and
``e^{γ_C - γ}``.  Padding (a last short chunk, the tiles that fill a last
block) has ``β = 0``, ``g = 0``, ``k = 0``: it writes nothing and the state
passes through.

ES takes no gradient: there is no ``custom_vjp`` and nothing is saved.
Members enter through ``vmap`` (the batching rule of ``pallas_call`` puts
them in front of the grid; a head's first block zeroes the state for every
member).

``interpret`` is a required argument, as in ops/pallas_attention.py.  Which
form a program takes is observed, not configured (:func:`delta_form`; a
model names :func:`delta_facts` in its declaration and the run's records say):
``delta_moe_lm.gated_delta_rule`` takes the kernels inside an engine's
``pallas_attention.kernel_scope`` where its shapes fit (:func:`fits`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# positions the kernels compute at once: their chunks are the diagonal
# blocks of one [TILE, TILE] matrix (the MXU's array, a register's lanes)
TILE = 128
F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
# the diagonal blocks of a chunk's triangular system that are inverted by
# the finite product (I - A)(I + A²)(I + A⁴)…; larger ones are assembled
# from their halves.  8: the product's terms grow at most C(6, 3) = 20-fold
# before they cancel, whatever the keys (at 64 they reach 1e17).  Both
# forms of the rule invert these blocks (delta_moe_lm.unit_lower_inverse)
INVERSE_BASE = 8
# positions of a grid step: four tiles.  A step's blocks (k, v, W, U of two
# value heads of 128: 1.75 MiB, two buffers each) stay well inside the
# default scoped VMEM, and a sequence of 16,384 is 32 steps a key head; on
# the v5e 256 and 1,024 rows read within 1% of 512 (PERF.md §6, PR 53)
BLOCK_ROWS = 512
# the chunks the kernels take: a power of two of INVERSE_BASE-row blocks,
# at least two (a merge level moves whole 8-row sublane tiles), in a tile
CHUNKS = (16, 32, 64, 128)
# the widest head whose two states and blocks were sized above
HEAD_DIM_MAX = 256


# --------------------------------------------------------------------------
# the rule
# --------------------------------------------------------------------------


def fits(key_dim: int, value_dim: int, chunk: int, length: int) -> bool:
    """The shapes the kernels take: heads of whole 128-lane blocks (a head
    is a column block of ``[T, heads · dim]``), a chunk the masked blocked
    inverse takes (:data:`CHUNKS`), a sequence of at least one position
    (whole chunks or a padded last one: the padding writes nothing)."""
    return (0 < key_dim <= HEAD_DIM_MAX and key_dim % LANES == 0
            and 0 < value_dim <= HEAD_DIM_MAX and value_dim % LANES == 0
            and chunk in CHUNKS and length > 0)


def delta_form(traced: bool, key_dim: int, value_dim: int, chunk: int,
               length: int) -> str:
    """``"kernel"`` or ``"xla"`` for the gated delta rule of a program's
    linear layers, over sequences of ``length`` positions in chunks of
    ``chunk`` with heads of ``key_dim`` and ``value_dim``.  The rule's OWN
    form, whatever forms the kernels beside it take: the kernels when, and
    only when, Mosaic kernels may be ``traced`` in the program
    (``pallas_attention.traced_why`` has that rule: TPU devices and whole
    members on a chip) and the shapes fit (:func:`fits`).  What
    ``delta_moe_lm.gated_delta_rule`` does while it is traced, said once at
    build."""
    return ("kernel" if traced and fits(key_dim, value_dim, chunk, length)
            else "xla")


# what :func:`delta_facts` answers for (ops/kernel_facts.py collects them)
FACTS = ("delta_form",)


def delta_facts(scope, key_dim: int, value_dim: int, chunk: int) -> dict:
    """What an engine's build reports of a model's gated delta rule, as
    the model names it in ``PolicyDeclaration.kernels``: a key head's
    width, a value head's, the chunk; ``scope``
    (``ops.kernel_facts.BuildScope``) has whether kernels may be traced
    and the sequence length.  :func:`delta_form`'s answer under its name."""
    return {"delta_form": delta_form(scope.traced[0], key_dim, value_dim,
                                     chunk, scope.horizon)}


def block_rows(length: int) -> int:
    """Positions of a grid step for a sequence of ``length``: whole tiles,
    :data:`BLOCK_ROWS` or the whole (padded) sequence where it is
    shorter."""
    return min(BLOCK_ROWS, -(-length // TILE) * TILE)


def inverse_products(chunk: int) -> int:
    """Products of one masked blocked inverse: two a doubling of the finite
    product's exponents up to :data:`INVERSE_BASE`, two a merge level.
    Chunk 64: 4 + 6 = 10."""
    doublings = INVERSE_BASE.bit_length() - 2
    levels = (chunk // INVERSE_BASE).bit_length() - 1
    return 2 * doublings + 2 * levels


# --------------------------------------------------------------------------
# what the kernels declare
# --------------------------------------------------------------------------


def _padded_length(length: int) -> int:
    """``length`` in whole blocks of :func:`block_rows`: the positions the
    grid computes."""
    rows = block_rows(length)
    return -(-length // rows) * rows


def solve_cost(length: int, chunk: int, key_heads: int, value_heads: int,
               key_dim: int, value_dim: int) -> pl.CostEstimate:
    """What ONE call of :func:`solve_chunks` does, by the DENSE ``[P, P]``
    tiles (``P`` = :data:`TILE`) it multiplies: a product against a
    triangular or block-diagonal tile costs the whole tile here
    (``benchmark/costs_gdn.py`` counts what the mathematics needs).  A
    (tile, key head): ``K Kᵀ``, ``2 P² dk``.  A (tile, value head): the
    inverse's :func:`inverse_products` products of ``2 P³``, ``W`` ``2 P²
    dk``, ``U`` ``2 P² dv``; ``P² + P`` exponentials (the decay tile and
    ``e^γ``).  Bytes, float32: ``k``, ``v`` and the rows read, ``W`` and
    ``U`` written, once each."""
    n, p = _padded_length(length) // TILE, TILE
    return pl.CostEstimate(
        flops=n * (key_heads * 2 * p * p * key_dim + value_heads * (
            inverse_products(chunk) * 2 * p ** 3
            + 2 * p * p * (key_dim + value_dim))),
        transcendentals=n * value_heads * (p * p + p),
        bytes_accessed=4 * n * p * (
            key_heads * key_dim + value_heads * (
                2 * value_dim + key_dim + 2)))


def chain_cost(length: int, chunk: int, key_heads: int, value_heads: int,
               key_dim: int, value_dim: int) -> pl.CostEstimate:
    """What ONE call of :func:`chain_chunks` does, by dense tiles.  A
    (tile, key head): ``Q Kᵀ``, ``2 P² dk``.  A (chunk, value head): ``W
    S``, ``(Q ∘ e^γ) S`` and ``(K ∘ e^{γ_C - γ})ᵀ V'``, ``2 L dk dv`` each,
    and ``(Q Kᵀ ∘ decay) V'``, ``2 L² dv``; a (tile, value head) ``P² + 2
    P`` exponentials and one more a chunk.  Bytes, float32: ``q``, ``k``,
    ``W``, ``U`` and the rows read, ``o`` written, once each."""
    n, p, l = _padded_length(length) // TILE, TILE, chunk
    chunks = n * (p // l)
    return pl.CostEstimate(
        flops=(n * key_heads * 2 * p * p * key_dim + chunks * value_heads * (
            3 * 2 * l * key_dim * value_dim + 2 * l * l * value_dim)),
        transcendentals=value_heads * (n * (p * p + 2 * p) + chunks),
        bytes_accessed=4 * n * p * (
            2 * key_heads * key_dim + value_heads * (
                2 * value_dim + key_dim + 2)))


# --------------------------------------------------------------------------
# inside a kernel: values of one tile
# --------------------------------------------------------------------------


def _dot(a, b, lhs: int = 1, rhs: int = 0):
    """float32 ``a @ b`` at ``HIGHEST``, contracting axis ``lhs`` of ``a``
    with axis ``rhs`` of ``b`` (``rhs=1``: ``a @ bᵀ``; ``lhs=0``: ``aᵀ @
    b``)."""
    return jax.lax.dot_general(
        a, b, (((lhs,), (rhs,)), ((), ())), precision=HIGHEST,
        preferred_element_type=F32)


def _indices():
    """``(row, col)`` index tiles ``[TILE, TILE]``."""
    return (jax.lax.broadcasted_iota(jnp.int32, (TILE, TILE), 0),
            jax.lax.broadcasted_iota(jnp.int32, (TILE, TILE), 1))


def _same_block(row, col, size: int):
    """Where ``row`` and ``col`` lie in one ``size``-row diagonal block
    (``size`` a power of two)."""
    shift = size.bit_length() - 1
    return (row >> shift) == (col >> shift)


def _column(values, where):
    """A column ``[TILE, 1]`` of the row ``values [1, TILE]``: row ``i``
    takes the ONE entry ``where`` marks in it (the identity: the row as a
    column), by the mask and a lane sum (exact: one term a row)."""
    return jnp.sum(jnp.where(where, values, 0.0), axis=1, keepdims=True)


def _decay_tile(gamma, gamma_col, visible):
    """``e^{γ_i - γ_j}`` where ``visible`` (``i >= j`` in one chunk), 0
    elsewhere."""
    return jnp.where(
        visible, jnp.exp(jnp.where(visible, gamma_col - gamma, 0.0)), 0.0)


def _unit_lower_inverses(tiles, row, col, chunk: int):
    """``(I + A)⁻¹`` of each ``a [TILE, TILE]`` of ``tiles``, strictly
    lower-triangular inside its ``chunk``-row diagonal blocks and 0 outside
    them: the XLA form's blocked inverse
    (``delta_moe_lm.unit_lower_inverse``) of every chunk of a tile at once,
    its slices as masks.  The tiles go through each product side by side:
    they are independent, and in this order Mosaic's schedule overlaps one
    tile's product with the other's."""
    powers = [-jnp.where(_same_block(row, col, INVERSE_BASE), a, 0.0)
              for a in tiles]
    inverses = [jnp.where(row == col, 1.0, 0.0) + p for p in powers]
    reached = 2                 # exponents below this are in the product
    while reached < INVERSE_BASE:
        powers = [_dot(p, p) for p in powers]
        inverses = [x + _dot(x, p) for x, p in zip(inverses, powers)]
        reached *= 2
    size = INVERSE_BASE
    while size < chunk:
        # the blocks under the diagonal that join two inverted blocks of
        # ``size`` rows (a tile holds nothing above the diagonal).  Only
        # the SECOND block of a pair has rows in ``X B X``: they alone are
        # multiplied (whole sublane tiles: the split moves nothing)
        joins = _same_block(row, col, 2 * size) & ~_same_block(row, col,
                                                                size)
        pairs = [x.reshape(TILE // (2 * size), 2, size, TILE)
                 for x in inverses]
        seconds = [x[:, 1].reshape(TILE // 2, TILE) for x in pairs]
        halves = [_dot(x, jnp.where(joins, a, 0.0))
                  for x, a in zip(seconds, tiles)]
        belows = [_dot(h, x) for h, x in zip(halves, inverses)]
        inverses = [
            jnp.stack([x[:, 0], x[:, 1] - below.reshape(x[:, 1].shape)],
                      axis=1).reshape(TILE, TILE)
            for x, below in zip(pairs, belows)]
        size *= 2
    return inverses


def _for_each_tile(body, tiles: int):
    """``body(n)`` for the tiles of a block in turn."""
    def step(n, carry):
        body(n)
        return carry

    jax.lax.fori_loop(0, tiles, step, 0)


def _solve_kernel(k_ref, v_ref, rows_ref, w_ref, u_ref, *, chunk: int,
                  rep: int):
    dk, dv = k_ref.shape[1], v_ref.shape[1] // rep
    row, col = _indices()
    chunks = _same_block(row, col, chunk)
    visible, below, eye = chunks & (row >= col), chunks & (row > col), (
        row == col)

    def one_tile(w_ref, u_ref, n):
        at = pl.ds(pl.multiple_of(n * TILE, TILE), TILE)
        k = k_ref[at, :]
        kk = _dot(k, k, rhs=1)                              # K Kᵀ
        rows = rows_ref[n]                                  # [2·rep, TILE]
        gammas = [_column(rows[r:r + 1], eye) for r in range(rep)]
        betas = [_column(rows[rep + r:rep + r + 1], eye) for r in range(rep)]
        inverses = _unit_lower_inverses([
            jnp.where(below, beta * kk * _decay_tile(
                rows[r:r + 1], gamma, visible), 0.0)
            for r, (gamma, beta) in enumerate(zip(gammas, betas))],
            row, col, chunk)
        for r, (gamma, beta, inverse) in enumerate(zip(gammas, betas,
                                                       inverses)):
            k_in = k * (beta * jnp.exp(gamma))
            v_in = v_ref[at, r * dv:(r + 1) * dv] * beta
            w_ref[at, r * dk:(r + 1) * dk] = _dot(inverse, k_in)
            u_ref[at, r * dv:(r + 1) * dv] = _dot(inverse, v_in)

    # (the output Refs are parameters, not closed-over names: a store is a
    # write THROUGH them, which esguard's R03 would read as a trace-time
    # mutation of a closure)
    _for_each_tile(functools.partial(one_tile, w_ref, u_ref),
                   k_ref.shape[0] // TILE)


def _chain_kernel(q_ref, k_ref, w_ref, u_ref, rows_ref, o_ref, s_ref, *,
                  chunk: int, rep: int):
    dk, dv = k_ref.shape[1], o_ref.shape[1] // rep
    row, col = _indices()
    visible, eye = _same_block(row, col, chunk) & (row >= col), row == col
    # the last position of a row's chunk: where γ_C stands in the row of γ
    closes = col == (row | (chunk - 1))

    @pl.when(pl.program_id(1) == 0)
    def _a_heads_first_block():
        s_ref[...] = jnp.zeros(s_ref.shape, F32)

    def one_tile(o_ref, s_ref, n):
        first = pl.multiple_of(n * TILE, TILE)
        q, k = q_ref[pl.ds(first, TILE), :], k_ref[pl.ds(first, TILE), :]
        qk = _dot(q, k, rhs=1)                              # Q Kᵀ
        rows = rows_ref[n]
        heads = range(rep)
        gammas = [rows[r:r + 1] for r in heads]
        columns = [_column(gamma, eye) for gamma in gammas]
        insides = [qk * _decay_tile(gamma, column, visible)
                   for gamma, column in zip(gammas, columns)]
        q_ins = [q * jnp.exp(column) for column in columns]
        k_outs = [k * jnp.exp(_column(gamma, closes) - column)
                  for gamma, column in zip(gammas, columns)]
        for j in range(TILE // chunk):          # the tile's chunks in turn
            rows_j = slice(j * chunk, (j + 1) * chunk)
            at = pl.ds(first + j * chunk, chunk)
            # (the heads go through each product side by side: they are
            # independent, and Mosaic's schedule follows this order)
            states = [s_ref[r] for r in heads]
            boths = [_dot(jnp.concatenate([
                w_ref[at, r * dk:(r + 1) * dk], q_ins[r][rows_j]], axis=0),
                states[r]) for r in heads]                  # W S, Q̃ S
            freshes = [u_ref[at, r * dv:(r + 1) * dv] - boths[r][:chunk]
                       for r in heads]                      # V'
            for r in heads:
                o_ref[at, r * dv:(r + 1) * dv] = boths[r][chunk:] + _dot(
                    insides[r][rows_j, rows_j], freshes[r])
            for r in heads:
                # (a [1, 1] is broadcast along the lanes first: Mosaic
                # does not broadcast along sublanes and lanes at once)
                total = gammas[r][:, (j + 1) * chunk - 1:(j + 1) * chunk]
                s_ref[r] = (
                    jnp.exp(jnp.broadcast_to(total, (1, dv))) * states[r]
                    + _dot(k_outs[r][rows_j], freshes[r], lhs=0))

    _for_each_tile(functools.partial(one_tile, o_ref, s_ref),
                   k_ref.shape[0] // TILE)


# --------------------------------------------------------------------------
# the calls
# --------------------------------------------------------------------------


def decay_rows(g, beta, key_heads: int, chunk: int):
    """``[nk, tiles, 2·rep, TILE]`` float32 of ``g, beta [T, nv]``: for key
    head ``h`` and tile ``n`` of :data:`TILE` positions the rows ``γ`` (the
    running sum of ``g`` inside each CHUNK) of its ``rep`` value heads,
    then their ``β``.  The sequence is padded to whole blocks of
    :func:`block_rows` with ``g = 0``, ``β = 0``."""
    t, nv = g.shape
    rep = nv // key_heads
    padded = _padded_length(t)

    def chunked(x):
        return _padded(x.astype(F32), padded).reshape(
            padded // chunk, chunk, key_heads, rep)

    both = jnp.stack([jnp.cumsum(chunked(g), axis=1), chunked(beta)])
    both = both.reshape(2, padded // TILE, TILE, key_heads, rep)
    both = jnp.transpose(both, (3, 1, 0, 4, 2))     # [nk, n, 2, rep, TILE]
    return both.reshape(key_heads, padded // TILE, 2 * rep, TILE)


def _padded(x, length: int):
    """``x [T, …]`` with zero rows up to ``length``."""
    pad = length - x.shape[0]
    return x if not pad else jnp.pad(
        x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))


def _specs(rows: int, rep: int, dk: int, dv: int):
    """The block of a grid step ``(key head i, block j)`` of ``[T, nk·dk]``,
    of ``[T, nv·dk]``, of ``[T, nv·dv]`` and of the decay rows."""
    def wide(width):
        return pl.BlockSpec((rows, width), lambda i, j: (j, i))

    return (wide(dk), wide(rep * dk), wide(rep * dv), pl.BlockSpec(
        (None, rows // TILE, 2 * rep, TILE), lambda i, j: (i, j, 0, 0)))


def _checked(q, k, values, rows, chunk: int):
    """``(T in whole blocks, nk, rep, dk, dv)`` of ``q, k [T, nk, dk]``,
    values of the shape ``values = (T, nv, dv)`` and the decay rows."""
    t, nk, dk = k.shape
    nv, dv = values[1:]
    padded = _padded_length(t)
    if (q.shape != k.shape or values[0] != t or nv % nk
            or not fits(dk, dv, chunk, t)
            or rows.shape != (nk, padded // TILE, 2 * (nv // nk), TILE)):
        raise ValueError(
            f"q {q.shape}, k {k.shape}, values {values} and rows "
            f"{rows.shape} in chunks of {chunk} are not [T, nk, dk] twice, "
            f"[T, nv, dv] and what decay_rows lays, with heads of whole "
            f"{LANES}-lane blocks and a chunk among {CHUNKS}")
    return padded, nk, nv // nk, dk, dv


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def solve_chunks(k, v, rows, *, chunk: int, interpret: bool):
    """``(W [T', nv·dk], U [T', nv·dv])`` float32 of every chunk's
    triangular system: ``k [T, nk, dk]``, ``v [T, nv, dv]``, ``rows`` as
    :func:`decay_rows` lays them; ``T'`` is ``T`` in whole blocks."""
    padded, nk, rep, dk, dv = _checked(k, k, v.shape, rows, chunk)
    t, nv, block = k.shape[0], nk * rep, block_rows(k.shape[0])
    keys, wide_k, wide_v, row_spec = _specs(block, rep, dk, dv)
    return pl.pallas_call(
        functools.partial(_solve_kernel, chunk=chunk, rep=rep),
        grid=(nk, padded // block),
        in_specs=[keys, wide_v, row_spec],
        out_specs=[wide_k, wide_v],
        out_shape=[jax.ShapeDtypeStruct((padded, nv * dk), F32),
                   jax.ShapeDtypeStruct((padded, nv * dv), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        cost_estimate=solve_cost(t, chunk, nk, nv, dk, dv),
        name="delta_solve",
        interpret=interpret,
    )(_padded(k.astype(F32).reshape(t, nk * dk), padded),
      _padded(v.astype(F32).reshape(t, nv * dv), padded), rows)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def chain_chunks(q, k, w, u, rows, *, chunk: int, interpret: bool):
    """``o [T', nv·dv]`` float32 of the chain over the chunks from a zero
    state: ``q, k [T, nk, dk]``, ``w`` and ``u`` as :func:`solve_chunks`
    wrote them, ``rows`` as :func:`decay_rows` lays them."""
    t, nk, dk = k.shape
    nv = w.shape[1] // dk
    dv = u.shape[1] // nv
    padded, _, rep, _, _ = _checked(q, k, (t, nv, dv), rows, chunk)
    if w.shape != (padded, nv * dk) or u.shape != (padded, nv * dv):
        raise ValueError(
            f"w {w.shape} and u {u.shape} are not what solve_chunks writes "
            f"for k {k.shape}: [{padded}, nv·dk] and [{padded}, nv·dv]")
    block = block_rows(t)
    keys, wide_k, wide_v, row_spec = _specs(block, rep, dk, dv)
    return pl.pallas_call(
        functools.partial(_chain_kernel, chunk=chunk, rep=rep),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=(nk, padded // block),
            in_specs=[keys, keys, wide_k, wide_v, row_spec],
            out_specs=wide_v,
            scratch_shapes=[pltpu.VMEM((rep, dk, dv), F32)],  # the states
        ),
        out_shape=jax.ShapeDtypeStruct((padded, nv * dv), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=chain_cost(t, chunk, nk, nv, dk, dv),
        name="delta_chain",
        interpret=interpret,
    )(_padded(q.astype(F32).reshape(t, nk * dk), padded),
      _padded(k.astype(F32).reshape(t, nk * dk), padded), w, u, rows)
