"""Pallas TPU kernels that move ε out of the HBM noise table by DMA.

A noise row is ``table[o : o + dim]`` at an ARBITRARY offset ``o`` of a 1-D
f32 table the chip tiles by 1024.  The pure-JAX paths read it with
``NoiseTable.slice`` under ``vmap``, which XLA lowers on the TPU to a
sequential loop copying one unaligned row per iteration into one sublane
of every tile of a 2-D array: 24-37 GB/s on a v5e whose HBM moves 819
(PERF.md).  Here rows leave the table as whole aligned windows, many bytes
in flight, and are realigned on the chip.

What Mosaic dictates (learned by compiling for the v5e, not by reading):
a DMA out of HBM must start and end on the array's tiling — (8, 128) for
the 2-D f32 view, i.e. 1024-float boundaries.  So every kernel here

1. views the table as ``(size/128, 128)`` (a bitcast, no copy),
2. DMAs the ALIGNED window of rows that contains the wanted span
   (:func:`_window`: start rounded down to a multiple of 8 rows, clamped
   so the window never runs off the table), and
3. realigns in VMEM (:func:`_shifted`): one dynamic lane rotate, a select
   against the next row, one dynamic sublane rotate — after which flat
   element k of the result is flat element k of the wanted span.

Two kernels share that front end.  Both move WHOLE rows, on one DMA
schedule (:func:`_this_step_row`: a few window buffers, the next rows' DMAs
in flight under this row's realignment), and are what a generation of the
replicated engine runs on a TPU mesh (``ESEngine.noise_gather_form ==
"dma"``):

- :func:`gather_noise_rows` — the evaluation's pass: ``(n, dim)`` rows,
  cast to the compute dtype in VMEM and written to a ``(n, rows, 128)``
  slab through a pipelined output block; bit-identical to
  ``table.slice(o, dim).astype(dtype)``.
- :func:`weighted_noise_sum` — the update's pass, Σ_k w_k·ε_k: each row is
  FMA'd (f32, VPU) into a VMEM accumulator that is only written back at
  the end; no ``(chunk, dim)`` block is ever materialized.

``interpret`` is a required argument: the engine derives it from the
platform of the mesh it runs on (never true on a TPU mesh), tests pass
``True``.  Nothing here consults ``jax.default_backend()``.

Relation to the param-sharded path: these kernels make TABLE noise
never-materialized by streaming DMA; the sharded engine
(parallel/sharded.py) takes the same no-materialization goal one step
further by deleting the table — ε is generated in-program from the
(key, generation, row, leaf) chain (ops/noise.py program family) under
partitionable threefry, so each device's RNG emits exactly its shard of
each noise block straight into the scaled-add/FMA.  Same design
pressure, moved from the DMA engines into the bit generator; these
kernels remain the replicated engine's path.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
TILE = LANES * SUBLANES  # floats per (8, 128) f32 tile: the DMA alignment
ROW_SLOTS = 3  # window buffers of the row kernels: two rows' DMAs in flight
# under a third's realignment (on the v5e the weighted sum is 13% faster
# than with two, a fourth adds nothing, and several rows a grid step add
# under 1.5%: PERF.md)


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _round_up(a: int, b: int) -> int:
    return _cdiv(a, b) * b


def _table_rows(table_data: jax.Array, window_rows: int) -> int:
    """Rows of the (rows, 128) table view, after checking the table can be
    viewed that way and holds at least one DMA window."""
    size = int(table_data.shape[0])
    if size % TILE != 0:
        raise ValueError(
            f"the row kernels view the table as (size/128, 128) "
            f"f32 tiles; table size {size} is not a multiple of {TILE}")
    rows = size // LANES
    if rows < window_rows:
        raise ValueError(
            f"noise table of {size} floats is smaller than one DMA window "
            f"({window_rows * LANES} floats); use a larger table_size")
    return rows


def _window(start, t_rows: int, window_rows: int):
    """(first table row, flat shift) of the aligned DMA window holding the
    span that begins at flat table index ``start``: the row is a multiple
    of 8 (the HBM tiling) and clamped so ``row + window_rows`` stays on
    the table — near the table's end the shift simply grows."""
    row = jnp.minimum((start // TILE) * SUBLANES, t_rows - window_rows)
    return pl.multiple_of(row, SUBLANES), start - row * LANES


def _shifted(x: jax.Array, shift, rows_out: int) -> jax.Array:
    """Rows ``[0, rows_out)`` of ``x`` (R, 128) advanced by ``shift`` flat
    elements: ``out.ravel()[k] == x.ravel()[k + shift]``.  ``shift`` is a
    traced scalar; the caller guarantees the span it asks for lies inside
    ``x`` (wrapped-around elements only ever land past it)."""
    n_rows = x.shape[0]
    lane_shift = jax.lax.rem(shift, LANES)
    row_shift = shift // LANES
    # a[i, j] = x[i, (j + lane_shift) % 128]
    a = pltpu.roll(x, jax.lax.rem(LANES - lane_shift, LANES), 1)
    a_next = pltpu.roll(a, n_rows - 1, 0)  # a_next[i] = a[i + 1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    b = jnp.where(lane < LANES - lane_shift, a, a_next)
    c = pltpu.roll(b, jax.lax.rem(n_rows - row_shift, n_rows), 0)
    return c[:rows_out, :]


def _vmem_limit(window_rows: int) -> int | None:
    """Scoped-VMEM request for a kernel whose largest live arrays are a
    few window-sized buffers (the slots, the accumulator or the output
    block's two, the realignment's temporaries): the 16 MiB default up to
    ~0.4M floats per window, more (of the v5e's 128 MiB) past it."""
    need = (ROW_SLOTS + 6) * window_rows * LANES * 4
    return None if need <= (16 << 20) else min(need, 96 << 20)


# --------------------------------------------------------------------------
# whole noise rows: table[o_k : o_k + dim] for many k
# --------------------------------------------------------------------------


def _row_geometry(table_data: jax.Array, dim: int, sublanes: int):
    """(table rows, window rows, output rows) of the (·, 128) views a row
    of ``dim`` floats needs: the output rounded up to the output dtype's
    sublane tile, the window one f32 tile longer so that any span
    ``[shift, shift + dim)`` with shift < TILE lies inside it."""
    rows_out = _round_up(_cdiv(dim, LANES), sublanes)
    window_rows = rows_out + SUBLANES
    return _table_rows(table_data, window_rows), window_rows, rows_out


def rows_fit_dma(table_data: jax.Array, dim: int) -> bool:
    """Whether the row kernels can serve rows of ``dim`` floats from this
    table: f32, whole (8, 128) tiles, at least one DMA window long."""
    size = int(table_data.shape[0])
    # the longest window: output rows rounded to bf16's 16-sublane tile
    window_rows = _round_up(_cdiv(dim, LANES), 2 * SUBLANES) + SUBLANES
    return (table_data.dtype == jnp.float32 and size % TILE == 0
            and size >= window_rows * LANES)


def row_kernel_cost(n: int, window_rows: int, rows_out: int,
                    out_itemsize: int, summed: bool) -> pl.CostEstimate:
    """What one call of a row kernel over ``n`` noise rows does, from its
    geometry: the declaration ``pallas_call`` hands XLA (a profiler's
    trace carries it as the custom call's ``flops`` and
    ``bytes_accessed``).  Bytes: every row's aligned f32 window read from
    the table, plus what is written: the ``(n, rows_out, 128)`` slab in
    the rows' dtype for the gather; ONE f32 ``(rows_out, 128)`` sum, with
    a multiply and an add a float and row, where ``summed``."""
    read = n * window_rows * LANES * 4
    written = (1 if summed else n) * rows_out * LANES * out_itemsize
    return pl.CostEstimate(
        flops=2 * n * rows_out * LANES if summed else 0,
        transcendentals=0, bytes_accessed=read + written)


def _this_step_row(offs_ref, table_ref, buf, sem, t_rows: int, n: int,
                   rows_out: int) -> jax.Array:
    """Grid step ``i`` of a row kernel over ``n`` rows: noise row ``i``,
    realigned, as ``(rows_out, 128)`` f32.  Its aligned window landed in
    slot ``i % slots`` while earlier rows were consumed; the windows of the
    next ``slots − 1`` rows are in flight when this returns."""
    slots, window_rows = buf.shape[0], buf.shape[1]
    i = pl.program_id(0)

    def dma(r):
        first, _ = _window(offs_ref[r], t_rows, window_rows)
        slot = jax.lax.rem(r, slots)
        return pltpu.make_async_copy(
            table_ref.at[pl.ds(first, window_rows), :],
            buf.at[slot],
            sem.at[slot],
        )

    @pl.when(i == 0)
    def _prime():
        for r in range(min(slots - 1, n)):
            dma(r).start()

    # row i − 1 has been consumed: its slot takes row i + slots − 1
    @pl.when(i + slots - 1 < n)
    def _prefetch():
        dma(i + slots - 1).start()

    dma(i).wait()
    _, shift = _window(offs_ref[i], t_rows, window_rows)
    return _shifted(buf[jax.lax.rem(i, slots)], shift, rows_out)


def _row_scratch(table_data: jax.Array, window_rows: int):
    return [
        pltpu.VMEM((ROW_SLOTS, window_rows, LANES), table_data.dtype),
        pltpu.SemaphoreType.DMA((ROW_SLOTS,)),
    ]


def _gather_kernel(t_rows: int, n: int, rows_out: int):
    def kernel(offs_ref, table_ref, out_ref, buf, sem):
        row = _this_step_row(offs_ref, table_ref, buf, sem, t_rows, n,
                             rows_out)
        out_ref[0] = row.astype(out_ref.dtype)

    return kernel


@partial(jax.jit, static_argnames=("dim", "dtype", "interpret"))
def gather_noise_rows(
    table_data: jax.Array,  # (table_size,) float32 — NoiseTable.data
    offsets: jax.Array,  # (n,) int32 row offsets
    dim: int,
    dtype,  # the rows' dtype: the cast happens in VMEM, after the DMA
    interpret: bool,
) -> jax.Array:
    """``(n, dim)`` noise rows, ``table[o : o + dim].astype(dtype)`` bit for
    bit (ops/noise.py::NoiseTable.slice), moved by DMA.

    The kernel writes a ``(n, rows_out, 128)`` slab whose row k, read
    flat, is noise row k followed by padding up to the tile; the caller
    gets its ``[:, :dim]``."""
    n = int(offsets.shape[0])
    dtype = jnp.dtype(dtype)
    if n == 0:
        return jnp.zeros((0, dim), dtype)
    t_rows, window_rows, rows_out = _row_geometry(
        table_data, dim, SUBLANES * 4 // dtype.itemsize)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # offsets
        grid=(n,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],  # table stays in HBM
        out_specs=pl.BlockSpec((1, rows_out, LANES), lambda i, *_: (i, 0, 0)),
        scratch_shapes=_row_scratch(table_data, window_rows),
    )
    out = pl.pallas_call(
        _gather_kernel(t_rows, n, rows_out),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, rows_out, LANES), dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(window_rows)),
        cost_estimate=row_kernel_cost(n, window_rows, rows_out,
                                      dtype.itemsize, summed=False),
        interpret=interpret,
    )(offsets.astype(jnp.int32), table_data.reshape(t_rows, LANES))
    return out.reshape(n, rows_out * LANES)[:, :dim]


# --------------------------------------------------------------------------
# update reduction: Σ_k w_k · table[o_k : o_k + dim]
# --------------------------------------------------------------------------


def _weighted_sum_kernel(t_rows: int, n: int, rows_out: int):
    def kernel(offs_ref, w_ref, table_ref, out_ref, buf, sem):
        @pl.when(pl.program_id(0) == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        row = _this_step_row(offs_ref, table_ref, buf, sem, t_rows, n,
                             rows_out)
        out_ref[...] += w_ref[pl.program_id(0)] * row

    return kernel


@partial(jax.jit, static_argnames=("dim", "interpret"))
def weighted_noise_sum(
    table_data: jax.Array,  # (table_size,) float32 — NoiseTable.data
    offsets: jax.Array,  # (n,) int32 row offsets
    weights: jax.Array,  # (n,) float32 weight per row
    dim: int,
    interpret: bool,
) -> jax.Array:
    """Streamed Σ_k w_k·ε_k: one DMA per noise row, zero materialization.

    Drop-in for ops/gradient.py::rank_weighted_noise_sum (same contract);
    the FMA is f32 on the VPU.  VMEM cost is ~4·dim floats (the window
    buffers and the accumulator, which is only written back at the end)
    plus the realignment temporaries, so it suits dims up to ~1M params.
    Callers with larger dims should keep the chunked pure-JAX path.
    """
    n = int(offsets.shape[0])
    if n == 0:
        return jnp.zeros((dim,), table_data.dtype)
    t_rows, window_rows, rows_out = _row_geometry(table_data, dim, SUBLANES)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # offsets, weights
        grid=(n,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],  # table stays in HBM
        out_specs=pl.BlockSpec((rows_out, LANES), lambda i, *_: (0, 0)),
        scratch_shapes=_row_scratch(table_data, window_rows),
    )
    out = pl.pallas_call(
        _weighted_sum_kernel(t_rows, n, rows_out),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows_out, LANES), table_data.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(window_rows)),
        cost_estimate=row_kernel_cost(n, window_rows, rows_out, 4,
                                      summed=True),
        interpret=interpret,
    )(
        offsets.astype(jnp.int32),
        weights.astype(table_data.dtype),
        table_data.reshape(t_rows, LANES),
    )
    return out.reshape(-1)[:dim]

