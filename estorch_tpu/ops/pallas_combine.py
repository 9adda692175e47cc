"""A Pallas TPU kernel for the expert layer's COMBINE: the routed rows a
pass of the experts wrote, each times its route's weight, added back into
the rows of the tokens they came from, every token's row written ONCE.

The XLA form (models/lm_blocks.py::_experts_of_members) is ``y.at[token]
.add(out · w)``: a scatter-add whose indices may repeat and lie in no order,
which XLA:TPU runs as one read-modify-write a row, in turn (0.23 to 0.33 µs
a row whatever its width: 74 GB/s of the v5e's 819 in the cell with the
widest rows; PERF.md §5, PR 49).  What that form does not use: the pass's
rows are SORTED, by held expert and, inside an expert, by token (the stable
sort over pair index ``token · k + kk``), so the key ``row_expert · tokens +
token`` ascends over the pass.  The rows one expert sends a tile of
consecutive tokens are therefore ONE CONTIGUOUS RUN of the pass's rows, and
a tile's whole input is ``held`` such runs, whose bounds one
``searchsorted`` on the key gives for every (expert, tile) at once.

Grid over tiles of consecutive tokens.  A step holds its tile ``[tile,
hidden]`` float32 in VMEM (zeros in a pass that starts a layer, what the
passes before it left otherwise), walks the held experts in order, copies
each one's run from HBM in chunks of a fixed row count (8-row aligned, the
next experts' first chunks in flight while this one's rows are added), adds
``w[r] · out[r]`` into row ``token[r] − tile start`` for the run's rows in
turn, and writes the tile once.  The adds are float32, in expert order and,
inside an expert, in token order: the order the scatter-add's updates are
listed in, so on the same operands the two forms agree to the last bit
(on the v5e at the shapes timed below, PERF.md §6, PR 51; on the CPU
wherever the products are exact: the interpreter's program may contract
the multiply-add).  Tokens and weights are read as scalars (scalar
prefetch), the rows as ``[1, hidden]`` vectors at a dynamic sublane.

``y`` is aliased in and out: a second pass of the expert loop adds into
what the first left and no second ``[tokens, hidden]`` buffer exists.  A
pass that starts a layer (``first``) does not read ``y`` at all.

ES takes no gradient: there is no ``custom_vjp`` and nothing is saved.

``interpret`` is a required argument, as in ops/pallas_attention.py.  Which
form a program takes is observed, not configured (:func:`combine_form`; a
model names :func:`combine_facts` in its declaration and the run's records say):
``lm_blocks.routed_experts`` takes the kernel inside an engine's
``pallas_attention.kernel_scope`` where its shapes fit (:func:`fits`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
# consecutive tokens of a grid step.  On the v5e, one pass at three expert
# cells' shapes and at eight of the fourth's calls in one, 20 calls chained,
# against the scatter-add on the same operands, equal to the last bit
# (PERF.md §6, PR 51; a pass that starts a layer / one that adds to
# another's): [16384, 2560] from 30,720 rows over 16 experts 0.951 / 1.292
# ms (the scatter-add 10.240); [32768, 2048] from 20,480 over 8 0.853 /
# 1.459 (3.963); [16384, 2048] from 20,480 over 16 0.649 / 0.945 (2.962);
# [65536, 2048] from 40,960 over 16 1.892 / 2.852 (7.897).  Tiles of 1,024
# gain 0.4 to 13% more on the first kind of pass and would take 21 MiB for
# the tile's two copies; tiles of 256 lose 10 to 25%
TOKEN_TILE = 512
# the scoped-VMEM limit the call asks for, past the default 16 MiB: a
# [512, 2560] float32 tile is 5 MiB and the output's pipeline holds two,
# beside the slots of row chunks (the v5e has 128 MiB)
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
# bytes of one float32 token row the tile's two pipelined copies have room
# for under that limit
ROW_BYTES_MAX = 16 * 1024
# the experts whose first chunks are in flight at once: a chunk is 0.3 to
# 0.7 MB and its rows are added in half a microsecond (11 bundles a row),
# so the copies hide behind one another.  2 in flight lose 4 to 6% of a
# pass that starts a layer; 8 and 16 gain nothing there and lose 10 to 30%
# of a pass that first reads its tile (the tile's copy queues behind them)
DEPTH = 4
# the most rows of a chunk: a longer run (a router that sends a tile's
# tokens to few experts) takes further chunks, one at a time
CHUNK_ROWS_MAX = 128


# --------------------------------------------------------------------------
# the rule
# --------------------------------------------------------------------------


def fits(hidden: int, tokens: int) -> bool:
    """The shapes the kernel takes: float32 rows of whole 128-lane blocks,
    narrow enough for a tile of :data:`TOKEN_TILE` of them to be held
    twice in VMEM, and a member's tokens a whole number of such tiles (the
    merged members' are then too)."""
    return (hidden > 0 and hidden % LANES == 0
            and hidden * 4 <= ROW_BYTES_MAX
            and tokens > 0 and tokens % TOKEN_TILE == 0)


def combine_form(traced: bool, hidden: int, length: int) -> str:
    """``"kernel"`` or ``"xla"`` for the combine of a program's expert
    layers, over sequences of ``length`` tokens of ``hidden`` floats.  The
    combine's OWN rule, whatever forms the kernels beside it take: the
    kernel when, and only when, Mosaic kernels may be ``traced`` in the
    program (``pallas_attention.traced_why`` has that rule: TPU devices and
    whole members on a chip) and the shapes fit (:func:`fits`).  What
    ``lm_blocks.routed_experts`` does while it is traced, said once at
    build."""
    return "kernel" if traced and fits(hidden, length) else "xla"


# what :func:`combine_facts` answers for (ops/kernel_facts.py collects them)
FACTS = ("combine_form",)


def combine_facts(scope, hidden: int) -> dict:
    """What an engine's build reports of the combine of a model's expert
    layers, as the model names it in ``PolicyDeclaration.kernels``:
    ``hidden``, the floats of a token's row the combine adds the routed
    rows into; ``scope`` (``ops.kernel_facts.BuildScope``) has whether
    kernels may be traced and the sequence length.  :func:`combine_form`'s
    answer under its name."""
    return {"combine_form": combine_form(scope.traced[0], hidden,
                                         scope.horizon)}


def chunk_rows(rows: int, held: int, tiles: int) -> int:
    """Rows of one chunk of a run, for a pass of ``rows`` rows over
    ``held`` experts and ``tiles`` token tiles: a pass's share of a run
    (the capacity's margin over what a uniform router sends is in
    ``rows``) and the 8-row alignment's slack, whole sublane tiles, at
    least 16 and no more than the pass or :data:`CHUNK_ROWS_MAX`.  On the
    v5e 16 rows fewer cost 25 to 60% of a pass (one run in three then
    takes a second chunk, which nothing hides) and 16 more 6 to 19% (read
    and not used)."""
    want = -(-rows // (held * tiles)) + SUBLANES
    return min(rows, CHUNK_ROWS_MAX,
               max(2 * SUBLANES, -(-want // SUBLANES) * SUBLANES))


# --------------------------------------------------------------------------
# the kernel
# --------------------------------------------------------------------------


def combine_cost(rows: int, tokens: int, hidden: int, held: int,
                 tile: int, chunk: int) -> pl.CostEstimate:
    """What ONE call of the kernel does at the least, from its grid and
    blocks: the declaration ``pallas_call`` hands XLA.  FLOPs: a multiply
    and an add a float of a routed row.  Bytes: one chunk of float32 rows
    an expert and tile (a run longer than a chunk reads more, by the rows
    it holds), every token's float32 row written once (a pass that does not
    start a layer reads them too: the kernel learns which it is when it
    runs), and four bytes a row each of tokens and weights, as the run
    bounds."""
    tiles = tokens // tile
    return pl.CostEstimate(
        flops=2 * rows * hidden, transcendentals=0,
        bytes_accessed=(held * tiles * chunk * hidden * 4
                        + tokens * hidden * 4
                        + 2 * rows * 4 + (held * tiles + 2) * 4))


def _combine_kernel(first_ref, bounds_ref, tok_ref, w_ref, y_hbm, rows_hbm,
                    o_ref, buf, sem, y_sem, *, held: int, tiles: int,
                    tile: int, chunk: int, rows: int, depth: int):
    j = pl.program_id(0)
    base = j * tile

    def run(e):
        """Expert ``e``'s run in this tile: ``(its first row, the row past
        its last, the 8-row tile its first row lies in)`` of the pass."""
        lo, hi = bounds_ref[e * tiles + j], bounds_ref[e * tiles + j + 1]
        return lo, hi, (lo // SUBLANES) * SUBLANES

    def fetch(e, c, slot):
        """Chunk ``c`` of expert ``e``'s run into ``slot``: ``(the copy,
        the pass row its first row is)``; a chunk that would pass the end
        of the pass starts earlier."""
        at = pl.multiple_of(
            jnp.minimum(run(e)[2] + c * chunk, rows - chunk), SUBLANES)
        return pltpu.make_async_copy(
            rows_hbm.at[pl.ds(at, chunk), :], buf.at[slot],
            sem.at[slot]), at

    def add(e, c, slot, at):
        """The rows of chunk ``c`` of expert ``e``'s run, in turn."""
        lo, hi, floor = run(e)

        def one(tile_ref, r, carry):
            # (the output Ref is a parameter, not a closed-over name, as
            # in ops/pallas_scan.py: the add is a write THROUGH it)
            i = tok_ref[r] - base
            tile_ref[pl.ds(i, 1), :] += (
                w_ref[r] * buf[slot, pl.ds(r - at, 1), :])
            return carry

        jax.lax.fori_loop(jnp.maximum(lo, floor + c * chunk),
                          jnp.minimum(hi, at + chunk),
                          functools.partial(one, o_ref), 0)

    # the first chunks of the first ``depth`` experts' runs, all in flight
    for e in range(depth):
        fetch(e, 0, e)[0].start()

    @pl.when(first_ref[0] != 0)
    def _starts_a_layer():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(first_ref[0] == 0)
    def _adds_to_a_pass():
        copy = pltpu.make_async_copy(
            y_hbm.at[pl.ds(pl.multiple_of(base, SUBLANES), tile), :], o_ref,
            y_sem.at[0])
        copy.start()
        copy.wait()

    def expert(e, carry):
        slot = jax.lax.rem(e, depth)
        copy, at = fetch(e, 0, slot)
        copy.wait()
        add(e, 0, slot, at)

        # the slot is read: it takes the first chunk of a later expert
        @pl.when(e + depth < held)
        def _later():
            fetch(e + depth, 0, slot)[0].start()

        _, hi, floor = run(e)

        def further(c, carry):
            copy, at = fetch(e, c, depth)
            copy.start()
            copy.wait()
            add(e, c, depth, at)
            return carry

        jax.lax.fori_loop(1, pl.cdiv(hi - floor, chunk), further, 0)
        return carry

    jax.lax.fori_loop(0, held, expert, 0)


def combine_rows(y, rows, w, token, key, first, *, held: int,
                 interpret: bool, tile: int | None = None,
                 chunk: int | None = None):
    """``y`` with ``w[r] · rows[r]`` added into row ``token[r]`` for every
    row ``r`` of a pass whose ``key[r] < held · tokens``, float32 ``[tokens,
    hidden]``, written in place of ``y``.

    ``rows [R, hidden]`` float32, ``R`` a multiple of 8; ``w [R]`` float32;
    ``token [R]`` int32, each inside ``y``; ``key [R]`` int32 ASCENDING:
    ``expert · tokens + token`` of a row that counts, ``held · tokens`` of
    one that does not (those last); ``first``: whether ``y`` is all zeros
    (a bool scalar, traced: the kernel then does not read it).

    ``tile``, ``chunk``: tokens of a grid step and rows of a run's chunk,
    :data:`TOKEN_TILE` and :func:`chunk_rows` where not given."""
    tokens, hidden = y.shape
    n_rows = rows.shape[0]
    tile = tile or TOKEN_TILE
    if tokens % tile or tile % SUBLANES or n_rows % SUBLANES:
        raise ValueError(f"{tokens} tokens in tiles of {tile}, a pass of "
                         f"{n_rows} rows: not whole tiles of {SUBLANES}")
    tiles = tokens // tile
    chunk = chunk or chunk_rows(n_rows, held, tiles)
    depth = min(held, DEPTH)
    bounds = jnp.searchsorted(
        key, jnp.arange(held * tiles + 1, dtype=jnp.int32) * tile,
        side="left", method="compare_all").astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # first, bounds, token, w
        grid=(tiles,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),   # y stays in HBM
                  pl.BlockSpec(memory_space=pl.ANY)],  # as the pass's rows
        out_specs=pl.BlockSpec((tile, hidden), lambda j, *_: (j, 0)),
        scratch_shapes=[
            # a ring of first chunks, and one slot for further chunks
            pltpu.VMEM((depth + 1, chunk, hidden), jnp.float32),
            pltpu.SemaphoreType.DMA((depth + 1,)),
            pltpu.SemaphoreType.DMA((1,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_combine_kernel, held=held, tiles=tiles,
                          tile=tile, chunk=chunk, rows=n_rows,
                          depth=depth),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tokens, hidden), jnp.float32),
        # operand 4 (after the four prefetched) is y
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=combine_cost(n_rows, tokens, hidden, held, tile,
                                   chunk),
        name="combine_rows",
        interpret=interpret,
    )(jnp.asarray(first, jnp.int32).reshape(1), bounds,
      token.astype(jnp.int32), w.astype(jnp.float32), y, rows)
