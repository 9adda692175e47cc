"""A forward-only Pallas TPU kernel for Mamba-1's selective scan: the state
``[d_state, channels]`` lives in vector registers and VMEM from the first
step of a sequence to its last and never reaches HBM.

The XLA form (models/sambay_lm.py::selective_scan) is a ``lax.scan`` whose
carry, the float32 state, and whose decay operand cross HBM at every step:
1.1 µs a step at 8,192 x 5,120 x 16, a twelfth of the HBM roofline of the
scan's own operands (PERF.md, PR 37 to 39).  Here a block of channels keeps
its state in registers over a chunk of steps and in VMEM scratch between
chunks; what crosses HBM is ``Δ``, ``x`` and ``y`` once each, and ``B`` and
``C`` once per channel block.

    h_t = exp(Δ_t ⊙ A) ⊙ h_{t-1} + (Δ_t ⊙ x_t) ⊗ B_t,    y_t = h_t C_t

ES takes no gradient: there is no ``custom_vjp`` and nothing is saved.

Grid ``(channel blocks, time chunks)``, time innermost and sequential; the
state is zeroed at a channel block's first chunk.  Layout, chosen by
reading the bundles Mosaic schedules for a described v5e (PERF.md, PR 40):
a block is 1,024 channels, and ONE step's 1,024 values of ``Δ``, ``x`` or
``y`` are one vector register, ``[8 lane groups, 128 lanes]``: a row of the
``[chunk, 1024]`` block as it lies in VMEM, read by one strided load and
reshaped for free.  The state is ``d_state`` such registers, one a STATE,
and ``A`` another ``d_state``: 32 of the 64 there are, resident over the
chunk's loop.  So nothing is broadcast along sublanes, and ``y_t`` is a sum
of ``d_state`` registers, not a reduce inside one; ``B_t`` and ``C_t`` are
``2 · d_state`` SCALARS a step, read from SMEM (the two ``[T · d_state]``
arrays flattened by XLA, 0.5 MB each) and splat: no ``[T, d_state, 128]``
copy exists anywhere.  With the states in the sublanes instead (the layout
the XLA form carries) every step pays sublane broadcasts of ``Δ`` and
``Δx`` and a sublane reduce of ``y``: 63 bundles a step and 1,024 channels
against 38.5 here, of which the multiplies, adds, exponential pushes and
splats fill 97% of the four vector slots.

Precision, the same as the XLA form's: float32 ``Δ``, decay, state and
``y``; one exponential per (step, channel, state); the ``d_state`` terms of
``y_t`` summed in float32, in the order of the states.  The exponential is
the chip's ``2^z``, which Mosaic's own ``exp(z)`` reaches by a rounded
multiply with ``log2 e``; here that factor is multiplied into ``A`` once a
chunk (``exp(Δ·A) = 2^(Δ·(A·log2 e))``: the same two roundings in another
order, one multiply a (step, channel, state) fewer).

Members enter through ``vmap`` (the batching rule of ``pallas_call`` puts
them in front of the grid); ``a`` may be batched or not.

``interpret`` is a required argument, as in ops/pallas_attention.py.  Which
form a program takes is observed, not configured (:func:`scan_form`; a
model names :func:`scan_facts` in its declaration and the run's records say):
``sambay_lm.selective_scan`` takes the kernel inside an engine's
``pallas_attention.kernel_scope`` where its shapes fit (:func:`fits`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# channels of a block, largest first: 1,024 are the eight lane groups of
# one vector register, so that a state of a block is ONE register a step;
# a narrower block (for a width 1,024 does not divide) fills part of one
CHANNEL_BLOCKS = (1024, 512, 256, 128)
# steps of a grid step (the [chunk, 1024] float32 blocks of Δ, x and y, two
# buffers each, are 6 MiB of VMEM; B's and C's chunks 32 KiB of SMEM each)
# and steps unrolled an iteration of its loop.  By Mosaic's schedule for a
# described v5e (PERF.md, PR 40) a step of 1,024 channels x 16 states is
# 44.8 bundles at 4 unrolled, 38.5 at 8, 37.6 at 16 (twice the code); the
# chunk does not enter the loop's schedule
TIME_CHUNK = 256
UNROLL = 8
# the states whose registers (the state's and A's, two a state) stay
# resident beside the step's temporaries in the 64 there are
STATES_MAX = 16
_LOG2_E = 1.4426950408889634


# --------------------------------------------------------------------------
# the rule
# --------------------------------------------------------------------------


def channel_block(d_inner: int) -> int | None:
    """The kernel's channel block for ``d_inner`` channels: the largest of
    :data:`CHANNEL_BLOCKS` that divides them, ``None`` where none does."""
    return next((b for b in CHANNEL_BLOCKS if d_inner % b == 0), None)


def fits(d_inner: int, d_state: int, length: int) -> bool:
    """The shapes the kernel takes: channels in whole 128-lane blocks, no
    more states than stay in registers, a sequence of whole time chunks."""
    return (channel_block(d_inner) is not None
            and 0 < d_state <= STATES_MAX
            and length > 0 and length % TIME_CHUNK == 0)


def scan_form(traced: bool, d_inner: int, d_state: int, length: int) -> str:
    """``"kernel"`` or ``"xla"`` for the selective scans of a program, over
    sequences of ``length`` steps with ``d_inner`` channels of ``d_state``
    states.  The scan's OWN rule, whatever form the model's attention
    takes: the kernel when, and only when, Mosaic kernels may be
    ``traced`` in the program (``pallas_attention.traced_why`` has that
    rule: TPU devices and whole members on a chip) and the scan's shapes
    fit (:func:`fits`).  What ``sambay_lm.selective_scan`` does while it is
    traced, said once at build."""
    return "kernel" if traced and fits(d_inner, d_state, length) else "xla"


# what :func:`scan_facts` answers for (ops/kernel_facts.py collects them)
FACTS = ("scan_form",)


def scan_facts(scope, d_inner: int, d_state: int) -> dict:
    """What an engine's build reports of a model's selective scans, as the
    model names them in ``PolicyDeclaration.kernels``; ``scope``
    (``ops.kernel_facts.BuildScope``) has whether kernels may be traced
    and the sequence length.  :func:`scan_form`'s answer under its name."""
    return {"scan_form": scan_form(scope.traced[0], d_inner, d_state,
                                   scope.horizon)}


# --------------------------------------------------------------------------
# the kernel
# --------------------------------------------------------------------------


def scan_cost(length: int, d_inner: int, d_state: int) -> pl.CostEstimate:
    """What ONE call of the kernel does: the declaration ``pallas_call``
    hands XLA (a profiler's trace carries it as the custom call's ``flops``
    and ``bytes_accessed``).  FLOPs per (step, channel, state): ``Δ·A``,
    the decay's product, ``(Δx)·B``, the sum, ``h·C`` and its sum, six;
    and ``Δ·x``, one per (step, channel).  Transcendentals: one
    exponential per (step, channel, state).  Bytes, float32: ``Δ``, ``x``
    and ``y`` ``[T, d_inner]`` and ``B``, ``C`` ``[T, d_state]`` ONCE each,
    the scan's least (``B`` and ``C`` are fetched again by every channel
    block, and ``A``'s ``d_inner · d_state`` are not counted): what
    ``benchmark/costs_sambay.scan_bytes_per_sequence`` counts.  ``vmap``
    scales all three by the members in front of the grid."""
    return pl.CostEstimate(
        flops=length * d_inner * (6 * d_state + 1),
        transcendentals=length * d_inner * d_state,
        bytes_accessed=4 * length * (3 * d_inner + 2 * d_state))


def _scan_kernel(dt_ref, x_ref, a_ref, b_ref, c_ref, y_ref, h_ref, *,
                 chunk: int, unroll: int, states: int):
    block = a_ref.shape[1]
    groups = block // LANES

    def row(ref, i):
        """Row ``i`` of a ``[rows, block]`` ref as one register's ``[lane
        groups, 128]``: a strided load, and a reshape that moves nothing."""
        return ref[pl.ds(i, 1), :].reshape(groups, LANES)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        h_ref[...] = jnp.zeros(h_ref.shape, jnp.float32)

    # exp(Δ·A) as 2^(Δ·(A·log2 e)): the factor Mosaic's exp multiplies by
    # at every (step, channel, state), once a chunk instead
    a = [row(a_ref, n) * _LOG2_E for n in range(states)]

    def steps(y_ref, g, h):
        # (the output Ref is a parameter, not a closed-over name: the
        # store below is a write THROUGH it, which esguard's R03 would
        # read as a trace-time mutation of a closure)
        first = pl.multiple_of(g * unroll, unroll)
        h = list(h)
        for t in (first + u for u in range(unroll)):
            dt = row(dt_ref, t)
            dtx = dt * row(x_ref, t)
            y = None
            for n in range(states):
                # B_t[n] and C_t[n]: scalars out of SMEM, splat
                h[n] = (jnp.exp2(dt * a[n]) * h[n]
                        + b_ref[0, t * states + n] * dtx)
                term = c_ref[0, t * states + n] * h[n]
                y = term if y is None else y + term
            y_ref[pl.ds(t, 1), :] = y.reshape(1, block)
        return tuple(h)

    h = jax.lax.fori_loop(0, chunk // unroll,
                          functools.partial(steps, y_ref),
                          tuple(row(h_ref, n) for n in range(states)))
    for n in range(states):
        h_ref[pl.ds(n, 1), :] = h[n].reshape(1, block)


@functools.partial(jax.jit, static_argnames=(
    "interpret", "block_d", "chunk", "unroll"))
def selective_scan(
    x: jax.Array,      # [T, d_inner]
    delta: jax.Array,  # [T, d_inner], after the softplus
    a: jax.Array,      # [d_inner, d_state], negative
    b: jax.Array,      # [T, d_state]
    c: jax.Array,      # [T, d_state]
    *,
    interpret: bool,
    block_d: int | None = None,
    chunk: int | None = None,
    unroll: int | None = None,
) -> jax.Array:
    """Mamba-1's recurrence ``h_t = exp(Δ_t ⊙ A) ⊙ h_{t-1} + (Δ_t ⊙ x_t) ⊗
    B_t``, ``y_t = h_t C_t`` from a zero state: ``y [T, d_inner]`` float32
    (everything is computed in float32 whatever the operands' dtype).

    ``block_d``, ``chunk``, ``unroll``: the channels of a block, the steps
    of a grid step and the steps unrolled; :func:`channel_block` of
    ``d_inner``, :data:`TIME_CHUNK` and :data:`UNROLL` where not given (no
    more than there are).  ``block_d`` is whole 128-lane groups; on the
    chip ``chunk`` is a multiple of 8 too (:func:`scan_form` is where an
    engine asks)."""
    t, d = x.shape
    n = a.shape[1]
    block_d = min(block_d or channel_block(d) or d, d)
    chunk = min(chunk or TIME_CHUNK, t)
    unroll = min(unroll or UNROLL, chunk)
    if (delta.shape != (t, d) or a.shape != (d, n) or b.shape != (t, n)
            or c.shape != (t, n)):
        raise ValueError(
            f"x {x.shape}, delta {delta.shape}, a {a.shape}, b {b.shape}, c "
            f"{c.shape} are not [T, D], [T, D], [D, N], [T, N], [T, N]")
    if block_d % LANES or d % block_d or t % chunk or chunk % unroll:
        raise ValueError(
            f"{d} channels x {t} steps are not whole blocks of {block_d} "
            f"channels ({LANES}-lane groups) and chunks of {chunk} steps, "
            f"{unroll} unrolled")
    f32 = jnp.float32
    wide = pl.BlockSpec((chunk, block_d), lambda i, j: (j, i))
    # a chunk's B or C, flat, as the last two dimensions of its block (so
    # that members in front of the grid leave the block whole)
    scalars = pl.BlockSpec((None, 1, chunk * n), lambda i, j: (j, 0, 0),
                           memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_scan_kernel, chunk=chunk, unroll=unroll,
                          states=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=(d // block_d, t // chunk),
            in_specs=[wide, wide,
                      pl.BlockSpec((n, block_d), lambda i, j: (0, i)),
                      scalars, scalars],
            out_specs=wide,
            scratch_shapes=[pltpu.VMEM((n, block_d), f32)],  # the state
        ),
        out_shape=jax.ShapeDtypeStruct((t, d), f32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=scan_cost(t, d, n),
        name="selective_scan",
        interpret=interpret,
    )(delta.astype(f32), x.astype(f32), a.astype(f32).T,
      b.astype(f32).reshape(t // chunk, 1, chunk * n),
      c.astype(f32).reshape(t // chunk, 1, chunk * n))
