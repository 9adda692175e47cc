"""A forward-only Pallas TPU kernel for causal attention with an online
softmax: a score tile lives in VMEM from Q·Kᵀ to P·V and never reaches HBM.

The XLA form (models/lm_blocks.py::causal_attention) computes Q·Kᵀ + mask,
the softmax's max/exp/sum and P·V as separate fusions over float32 score
tiles held in HBM, and is bound by moving them (0.93 of the v5e's HBM peak
at 0.15 of its MXU's: PERF.md).  Here the scores of one ``(block_q,
block_k)`` tile are made, exponentiated and contracted with V where they
lie; what crosses HBM is q and the context once and k, v once per query
block that sees them.

ES takes no gradient: there is no ``custom_vjp`` and nothing is saved.

Layout: no transposes around the kernel.  q is read as the 2-D array
``[T, heads·head_dim]`` and k, v as ``[T, kv_heads·head_dim]`` that the
projections produce, in blocks of ``(block, head_dim)`` whose column-block
index IS the head (query head ``j`` reads key/value column block ``j //
(heads / kv_heads)``), and the context is written straight into ``[T,
heads·head_dim]``, the layout the output projection reads.

Grid ``(heads, query blocks, key blocks)``, the key axis innermost and
sequential; running max, sum and a float32 accumulator in VMEM scratch.
Causal by construction: a key block beyond the query block's last row is
skipped (``pl.when``) AND not fetched (its index map clamps to the last
visible block, and a block whose index did not change is not copied
again); the mask is applied only inside tiles the diagonal crosses.
Members enter through ``vmap`` (the batching rule of ``pallas_call`` puts
them in front of the grid).

Precision, the same as the XLA form's: operands in the dtype handed in
(bfloat16 in the cells), float32 scores, float32 max / sum / accumulator,
probabilities cast to the operands' dtype for P·V with float32
accumulation, ONE divide at the end.  Nothing is approximated or dropped;
what differs from the XLA form is the order of the float32 sums and that
the un-normalised probabilities are what is rounded to bfloat16.

What Mosaic dictated (learned by compiling for the v5e, not by reading):
a block's last two dimensions must be divisible by 8 and 128 or span the
array, so a head is a column block only where ``head_dim % 128 == 0``
(granite's heads of 64 are refused at lowering: two heads a block, split in
VMEM, is ROADMAP R4's); the float32 score tile, its exponential and the
bfloat16 probabilities of a block pair live on the kernel's stack in scoped
VMEM, 16 MiB by default: blocks of 1024 x 1024 fit, 2048 x 2048 ask for
24.6 MiB and are refused.  Around the kernel XLA keeps q, k, v and the
context in the row-major ``(8, 128)`` tiling the custom call states; the
rotation before it prefers positions in the lanes, so one transposing copy
of q and one of k precede each call (0.012 s a generation in the looped
cell against 0.526 s saved: PERF.md, PR 32).

``interpret`` is a required argument, as in ops/pallas_noise.py: the engine
derives it from the platform of the mesh it runs on (never true on a TPU
mesh), tests pass ``True``.  Nothing here consults
``jax.default_backend()``.

Which form a program takes is the ENGINE's decision, made once at build
from what it observes (:func:`attention_form`), and told to the model
function while the engine traces it (:func:`kernel_scope`): a call outside
an engine's trace takes the XLA form.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# the kernel's square blocks, largest first: multiples of the 128 lanes.
# On the v5e at the looped cell's shapes (2 members x 16 heads x 4,096
# positions x 128) a call takes 1.52 ms at 1024 x 1024, 2.54 ms at 512 x
# 512, 1.73 at 512 queries x 1024 keys, 2.75 at 1024 x 512, 3.20 at 256 x
# 512 (PERF.md, PR 32): a grid step costs about as much as the MXU work of
# a 512 tile, and a block's rows are 256-byte pieces of a [T, heads · 128]
# array, so few large steps beat many small ones although 10 of 16 tiles of
# 1024 hold more masked scores than 36 of 64 of 512.
BLOCKS = (1024, 512, 256, 128)

# contract the last dimension of both operands: q [bq, hd] · k [bk, hd]ᵀ
_QK_DIMS = (((1,), (1,)), ((), ()))


# --------------------------------------------------------------------------
# the rule, and how the engine tells the model function
# --------------------------------------------------------------------------


def kernel_block(length: int) -> int | None:
    """The kernel's block (queries and keys alike) for a sequence of
    ``length`` positions: the largest of :data:`BLOCKS` that divides it,
    ``None`` where none does."""
    return next((b for b in BLOCKS if length % b == 0), None)


def attention_form(platform: str, n_devices: int, head_dim: int,
                   length: int) -> str:
    """``"kernel"`` or ``"xla"`` for a program on a mesh of ``n_devices``
    devices of ``platform`` that runs attention with heads of ``head_dim``
    over ``length`` positions.  The kernel is taken when, and only when,
    ALL hold: the devices are TPUs; there is one of them, so the
    attention's operands are whole on it (under GSPMD an unwrapped
    ``pallas_call`` would be replicated, not partitioned); a head is a
    whole number of 128-lane column blocks; the sequence is a whole number
    of the kernel's blocks (:func:`kernel_block`)."""
    fits = head_dim % LANES == 0 and kernel_block(length) is not None
    return ("kernel" if platform == "tpu" and n_devices == 1 and fits
            else "xla")


_SCOPE: contextvars.ContextVar = contextvars.ContextVar(
    "attention_kernel_interpret", default=None)


@contextlib.contextmanager
def kernel_scope(interpret: bool):
    """While a policy is traced inside, ``lm_blocks.causal_attention`` takes
    the kernel (under the Pallas interpreter where ``interpret``).  The
    engine that resolved ``attention_form == "kernel"`` opens it around its
    own trace of the policy; nothing else does.  The scope acts at TRACE
    time and is no part of a ``jax.jit`` cache key: a jitted function
    traced outside it keeps the XLA form if called inside it later."""
    token = _SCOPE.set(bool(interpret))
    try:
        yield
    finally:
        _SCOPE.reset(token)


def scoped_interpret() -> bool | None:
    """``interpret`` of the enclosing :func:`kernel_scope`, or ``None``
    outside one (the XLA form)."""
    return _SCOPE.get()


# --------------------------------------------------------------------------
# the kernel
# --------------------------------------------------------------------------


def _last_visible(i, block_q: int, block_k: int):
    """Index of the last key block the rows of query block ``i`` see."""
    return ((i + 1) * block_q - 1) // block_k


def _attention_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                      scale: float, block_q: int, block_k: int):
    i, j = pl.program_id(1), pl.program_id(2)
    last = _last_visible(i, block_q, block_k)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def fold(masked: bool):
        """This key block into the running max, sum and accumulator."""
        v = v_ref[...]
        s = jax.lax.dot_general(q_ref[...], k_ref[...], _QK_DIMS,
                                preferred_element_type=jnp.float32) * scale
        if masked:
            rows = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            cols = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(cols <= rows, s, -jnp.inf)
        # every row sees key 0, which block 0 holds: after the first block
        # the running max is finite, so exp(-inf - max) is 0, never NaN
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next[:, :1])
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_next
        acc_ref[...] = alpha[:, :1] * acc_ref[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    # a tile needs the mask where its last key lies beyond its first row
    crosses = (j + 1) * block_k - 1 > i * block_q
    visible = j <= last

    @pl.when(jnp.logical_and(visible, crosses))
    def _diagonal():
        fold(masked=True)

    @pl.when(jnp.logical_and(visible, jnp.logical_not(crosses)))
    def _below():
        fold(masked=False)

    @pl.when(j == last)
    def _finish():
        o_ref[...] = (acc_ref[...] / l_ref[...][:, :1]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "num_heads", "num_kv_heads", "head_dim", "scale", "block_q", "block_k",
    "interpret"))
def causal_attention(
    q: jax.Array,  # [T, num_heads · head_dim], rotated, compute dtype
    k: jax.Array,  # [T, num_kv_heads · head_dim], rotated, compute dtype
    v: jax.Array,  # [T, num_kv_heads · head_dim], compute dtype
    *,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    scale: float,
    interpret: bool,
    block_q: int | None = None,
    block_k: int | None = None,
) -> jax.Array:
    """The context ``softmax(scale · q kᵀ + causal mask) v`` per head,
    ``[T, num_heads · head_dim]`` in q's dtype, with grouped heads (query
    head ``j`` reads key/value head ``j // (num_heads / num_kv_heads)``).

    ``block_q``, ``block_k``: rows of a query and of a key block;
    :func:`kernel_block` of ``T`` where not given (the whole sequence where
    it has none, which only the interpreter runs).  On the chip
    ``head_dim`` and the blocks must be multiples of 128
    (:func:`attention_form` is where an engine asks); under the interpreter
    any sizes with ``T % block == 0`` run."""
    t = q.shape[0]
    own = kernel_block(t) or t
    block_q, block_k = min(block_q or own, t), min(block_k or own, t)
    if t % block_q or t % block_k:
        raise ValueError(
            f"sequence of {t} positions is not a whole number of "
            f"({block_q}, {block_k}) blocks")
    if num_heads % num_kv_heads:
        raise ValueError("query heads must be a multiple of key/value heads")
    if (q.shape != (t, num_heads * head_dim)
            or k.shape != (t, num_kv_heads * head_dim) or v.shape != k.shape):
        raise ValueError(
            f"q {q.shape}, k {k.shape}, v {v.shape} are not [T, heads · "
            f"{head_dim}] of {num_heads} and {num_kv_heads} heads")
    group = num_heads // num_kv_heads

    def kv_block(h, i, j):
        # beyond the diagonal: the block already there, so nothing moves
        return jnp.minimum(j, _last_visible(i, block_q, block_k)), h // group

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(num_heads, t // block_q, t // block_k),
        in_specs=[
            pl.BlockSpec((block_q, head_dim), lambda h, i, j: (i, h)),
            pl.BlockSpec((block_k, head_dim), kv_block),
            pl.BlockSpec((block_k, head_dim), kv_block),
        ],
        out_specs=pl.BlockSpec((block_q, head_dim), lambda h, i, j: (i, h)),
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),  # running max
            pltpu.VMEM((block_q, LANES), jnp.float32),  # running sum
            pltpu.VMEM((block_q, head_dim), jnp.float32),  # accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_attention_kernel, scale=scale, block_q=block_q,
                          block_k=block_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="causal_attention",
        interpret=interpret,
    )(q, k, v)
