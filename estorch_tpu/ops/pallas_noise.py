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

Three kernels share that front end.  Two move WHOLE rows, on one DMA
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

The third never materializes a member's ε at all:

- :func:`population_noise_matvec` — the per-member noise term of the
  decomposed forward, y_i = c_i·(x_i @ E_i), with E_i = the member's table
  slice viewed as a (d, h) matrix.  Grid over (members × row-blocks); the
  realigned block W is a (rows, 128) FLAT view of E_i, contracted on the
  MXU as ``A_i @ W`` with a small per-member coefficient matrix A_i built
  from x_i outside the kernel.  That contraction can only express E's
  columns when they line up with lanes: h a multiple of 128, or a divisor
  of it.  Any other width (a 10-unit head) takes the gathered einsum — a
  choice made from the static shape alone, identical on every platform.

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

from ..obs.trace import NOISE, stage

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
            f"the streamed-noise kernels view the table as (size/128, 128) "
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
        interpret=interpret,
    )(
        offsets.astype(jnp.int32),
        weights.astype(table_data.dtype),
        table_data.reshape(t_rows, LANES),
    )
    return out.reshape(-1)[:dim]


# --------------------------------------------------------------------------
# decomposed-forward noise term: y_i = c_i · (x_i @ E_i)
# --------------------------------------------------------------------------


def lanes_regular(h: int) -> bool:
    """True when a (d, h) matrix stored flat lines its columns up with the
    128 lanes of a (rows, 128) view — the widths the streamed matvec
    kernel can contract (see the module docstring)."""
    return h % LANES == 0 or LANES % h == 0


def _pick_row_block(d: int, h: int, budget_floats: int = 64 * 1024) -> int:
    """Largest divisor of d whose B·h-float DMA fits the per-buffer budget
    (256 KiB: two buffers plus the realignment temporaries stay far inside
    scoped VMEM whatever the layer's size)."""
    best = 1
    for b in range(1, d + 1):
        if d % b == 0 and b * h <= budget_floats:
            best = b
    return best


def _noise_matvec_kernel(t_rows: int, window_rows: int, k_rows: int,
                         block_floats: int, layer_offset: int):
    def kernel(offs_ref, a_ref, table_ref, y_ref, buf, sem):
        i = pl.program_id(0)  # member
        k = pl.program_id(1)  # row block (inner axis)
        n_i = pl.num_programs(0)
        n_k = pl.num_programs(1)

        def window(member, blk):
            return _window(
                offs_ref[member] + layer_offset + blk * block_floats,
                t_rows, window_rows)

        def dma(slot, member, blk):
            first, _ = window(member, blk)
            return pltpu.make_async_copy(
                table_ref.at[pl.ds(first, window_rows), :],
                buf.at[slot],
                sem.at[slot],
            )

        step = i * n_k + k

        @pl.when(step == 0)
        def _warmup():
            dma(0, 0, 0).start()

        # prefetch the NEXT grid step's block (possibly the next member's
        # first block) while this one is consumed
        nxt = step + 1

        @pl.when(nxt < n_i * n_k)
        def _prefetch():
            dma(jax.lax.rem(nxt, 2), nxt // n_k, jax.lax.rem(nxt, n_k)).start()

        @pl.when(k == 0)
        def _init():
            y_ref[...] = jnp.zeros_like(y_ref)

        slot = jax.lax.rem(step, 2)
        dma(slot, i, k).wait()
        _, shift = window(i, k)
        w = _shifted(buf[slot], shift, k_rows)  # flat view of the E block
        y_ref[0] += jnp.dot(a_ref[0, 0], w,
                            preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)

    return kernel


def _matvec_coefficients(x: jax.Array, d: int, h: int, block_rows: int,
                         m_rows: int, k_rows: int) -> jax.Array:
    """(n, n_blocks, m_rows, k_rows) coefficient matrices A with
    ``A[i, b] @ W == `` the lane-layout of ``x[i, block b] @ E_block`` for
    W the (k_rows, 128) flat view of that E block.

    h = g·128: row q of W holds columns [128·(q%g), …) of E row q//g, so
    A[m, q] = x[q//g]·[q%g == m] and output row m is y[128m : 128(m+1)].
    128 = g·h: row q of W holds E rows q·g … q·g+g-1 side by side, so
    A[m, q] = x[q·g + m] and y[j] = Σ_m out[m, m·h + j].
    Rows/columns past the block are zero, so whatever the window holds
    beyond the block (the next leaf's noise) contributes nothing."""
    n = x.shape[0]
    n_blocks = d // block_rows
    xb = x.reshape(n, n_blocks, block_rows)
    if h % LANES == 0:
        g = h // LANES
        a = jnp.einsum("nbr,mk->nbmrk", xb, jnp.eye(g, dtype=x.dtype))
        a = a.reshape(n, n_blocks, g, block_rows * g)
    else:
        g = LANES // h
        q = _cdiv(block_rows, g)
        xb = jnp.pad(xb, ((0, 0), (0, 0), (0, q * g - block_rows)))
        a = xb.reshape(n, n_blocks, q, g).transpose(0, 1, 3, 2)
    return jnp.pad(a, ((0, 0), (0, 0), (0, m_rows - a.shape[2]),
                       (0, k_rows - a.shape[3])))


@partial(
    jax.jit,
    static_argnames=("d", "h", "layer_offset", "interpret", "block_rows"),
)
def population_noise_matvec(
    table_data: jax.Array,  # (table_size,) float32
    offsets: jax.Array,  # (n,) int32 — each member's flat-ε start offset
    c: jax.Array,  # (n,) float32 — σ·sign per member
    x: jax.Array,  # (n, d) float32 — the layer's input batch
    layer_offset: int,  # this layer's kernel start WITHIN the member ε vector
    d: int,
    h: int,
    interpret: bool,
    block_rows: int | None = None,
) -> jax.Array:
    """y[i] = c[i] · (x[i] @ E_i) with E_i streamed from the table.

    ``E_i = table[offsets[i]+layer_offset : …+d·h]`` viewed row-major as
    (d, h) — exactly the layout ops/params.py's unravel gives a Dense
    kernel, so this reproduces models/decomposed.py's noise term without
    materializing any member's noise tree.  Widths that are not
    :func:`lanes_regular` gather their (small) E_i instead.
    """
    n = int(x.shape[0])
    dtype = table_data.dtype
    offsets = offsets.astype(jnp.int32)
    if not lanes_regular(h):
        e = jax.vmap(lambda o: jax.lax.dynamic_slice(
            table_data, (o + layer_offset,), (d * h,)))(offsets)
        return c[:, None].astype(dtype) * jnp.einsum(
            "nd,ndh->nh", x.astype(dtype), e.reshape(n, d, h),
            precision=jax.lax.Precision.HIGHEST)
    if block_rows is None:
        block_rows = _pick_row_block(d, h)
    if d % block_rows != 0:
        raise ValueError(f"block_rows {block_rows} must divide d {d}")
    n_blocks = d // block_rows
    block_floats = block_rows * h
    g = h // LANES if h % LANES == 0 else LANES // h
    m_rows = _round_up(g, SUBLANES)
    k_rows = _round_up(_cdiv(block_floats, LANES), SUBLANES)
    window_rows = k_rows + SUBLANES
    t_rows = _table_rows(table_data, window_rows)
    a = _matvec_coefficients(x.astype(dtype), d, h, block_rows, m_rows, k_rows)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # offsets
        grid=(n, n_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, m_rows, k_rows),
                         lambda i, k, *_: (i, k, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),  # table stays in HBM
        ],
        out_specs=pl.BlockSpec((1, m_rows, LANES), lambda i, k, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, window_rows, LANES), dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out = pl.pallas_call(
        _noise_matvec_kernel(t_rows, window_rows, k_rows, block_floats,
                             layer_offset),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, m_rows, LANES), dtype),
        interpret=interpret,
    )(offsets, a, table_data.reshape(t_rows, LANES))
    if h % LANES == 0:
        y = out[:, :g, :].reshape(n, h)
    else:
        y = jnp.einsum("nmmj->nj", out[:, :g, :].reshape(n, g, g, h))
    return c[:, None].astype(dtype) * y


# --------------------------------------------------------------------------
# full streamed MLP forward (population-batched)
# --------------------------------------------------------------------------


def flat_layer_offsets(params) -> dict[str, dict[str, int]]:
    """Each leaf's start offset within the ravel_pytree flat vector.

    ravel_pytree flattens in tree order (sorted dict keys), each leaf
    row-major — the layout every table slice is unraveled with, so these
    offsets address a member's ε exactly like spec.unravel does.
    """
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    offsets: dict[str, dict[str, int]] = {}
    pos = 0
    for path, leaf in flat:
        layer = path[0].key
        name = path[1].key
        offsets.setdefault(layer, {})[name] = pos
        pos += int(leaf.size)
    return offsets


def mlp_streamed_apply(
    module,
    shared_params,
    table_data: jax.Array,
    offsets: jax.Array,  # (n,) member ε start offsets
    c: jax.Array,  # (n,) σ·sign
    obs: jax.Array,  # (n, obs_dim) population observation batch
    layer_offsets: dict[str, dict[str, int]],
    interpret: bool,
) -> jax.Array:
    """Population-batched MLPPolicy forward, weights (shared + c·ε) with ε
    streamed from the table.

    The shared-W term of every layer is one dense (n, d) @ (d, h) matmul
    (MXU); the noise term streams through :func:`population_noise_matvec`;
    bias noise is a tiny (n, h) gather.  Bit-for-bit this reorders the same
    contractions as models/decomposed.py::mlp_decomposed_apply, which the
    tests pin to float tolerance.
    """
    from ..models.decomposed import _ordered_dense_names

    names = _ordered_dense_names(shared_params)
    x = obs
    for name in names:
        w = shared_params[name]["kernel"]
        b = shared_params[name]["bias"]
        d, h = int(w.shape[0]), int(w.shape[1])
        # the forward reads eps itself: the streaming kernel and the bias
        # gather are this form's noise stage, inside the policy's
        with stage(NOISE):
            noise_term = population_noise_matvec(
                table_data, offsets, c, x,
                layer_offset=layer_offsets[name]["kernel"],
                d=d, h=h, interpret=interpret,
            )
            # bias noise: h floats per member — a tiny gather, not worth a
            # DMA
            bias_off = layer_offsets[name]["bias"]
            nb = jax.vmap(
                lambda o: jax.lax.dynamic_slice(
                    table_data, (o + bias_off,), (h,))
            )(offsets)
        x = x @ w + noise_term + b + c[:, None] * nb
        if name != "head":
            x = module.activation(x)
    if not module.discrete:
        x = jnp.tanh(x) * module.action_scale
    return x
