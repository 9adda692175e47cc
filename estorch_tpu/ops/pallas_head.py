"""A forward-only Pallas TPU kernel for the next-token head: the float32
logits of a row tile x vocabulary tile live in VMEM from the matmul to the
log-softmax's reduce and never reach HBM.

The XLA form (models/lm_blocks.py::score_next_tokens) projects float32
logits ``[members, block, vocab]``, re-lays them out (the matmul writes
positions in the sublanes, the reduces want the two signs of a pair there),
reduces them in two passes (max, then exp-sum) and gathers the target's
logit: per block the logits are written once, copied once and read three
times, all through HBM (PERF.md, PR 35: 0.151 s of 0.289 s in the looped
cell).  Here a tile ``s = h @ W_tile + (c/√r)·(h A) ⊗ Bᵀ_tile`` is made,
folded into a running max ``m`` and sum ``l = l·exp(m_old − m) + Σ exp(s −
m)``, and the target's logit is picked where ``tile offset + lane ==
target`` (a compare-and-sum in the tile: no gather on logits).  What leaves
the kernel is ``picked − (m + log l)``, one float32 a row: the online
softmax of ops/pallas_attention.py with the vocabulary in the place of the
keys and nothing to contract the probabilities with.

ES takes no gradient: there is no ``custom_vjp`` and nothing is saved.

Grid ``(row tiles, vocabulary tiles)``, the vocabulary axis innermost and
sequential; ``m``, ``l`` and the picked logit in VMEM scratch.  The hidden
width is contracted whole (no K loop): a row tile's ``[rows, hidden]`` stays
in VMEM while every vocabulary tile of ``W`` streams past it, so ``W`` is
read once per ROW TILE and the kernel's intensity on ``W`` is the row tile's
height in FLOP/B against the v5e's ridge of 240: rows of 512 or more, or
the kernel would be HBM-bound where the XLA matmul is not.

Both head layouts: an untied ``[hidden, vocab]`` kernel and a tied
``[vocab, hidden]`` embedding read transposed (the last dimension of both
operands contracted, as attention's ``q kᵀ``).  ANY vocabulary: the last
tile of one that is not a whole number of tiles (16,160 = 15.78 x 1024) is
fetched short and its tail masked to ``-inf`` before the max, by the iota
the pick uses; ``W`` is never padded.

Members do not enter through ``pallas_call``'s batching rule: the core is a
``custom_vmap`` that MERGES them into the row axis (as
``lm_blocks._expert_core`` grows its set of members), so that ``W``, which
no ``vmap`` of the engine batches, stays ONE un-batched operand and is never
broadcast (201 MB a member in the looped cell).  A row tile never straddles
two members: each reads the one ``Bᵀ`` of its member.

Precision, the same as the XLA form's: operands in the dtype handed in
(bfloat16 in the cells), float32 accumulation, the correction, the scaling,
max, sum and log in float32.  Nothing is approximated and no vocabulary
entry is dropped; what differs is the order of the float32 sums (and, on
the chip, that XLA runs a rank-1 ``(h A) ⊗ Bᵀ`` as a default-precision
product where the kernel multiplies in float32: against float32 logits of
the same operands the kernel is off by 8e-6, the XLA form by 2e-3; PERF.md,
PR 36).

``interpret`` is a required argument, as in ops/pallas_attention.py.  Which
form a program takes is observed, not configured (:func:`head_form_why`; a
model names :func:`head_facts` in its declaration and the run's records say):
``lm_blocks.score_next_tokens`` takes the kernel inside an engine's
``pallas_attention.kernel_scope`` where its shapes fit (:func:`fits`),
whatever form the attention beside it takes.  The engine opens the scope
where a member is whole on its chip (``pallas_attention.traced_why`` has the
rule): one TPU device, or several with the centre gathered, where it
partitions the members over the chips itself, so that a chip's call holds
its own members' rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# rows and vocabulary entries of a tile.  On the v5e, a call of 2 members x
# 4,096 rows x hidden 2,048 in bfloat16 (PERF.md, PR 36; the XLA form 17.99
# ms and 5.24 ms): against [2048, 49152] 9.43 ms at 512 x 1024 (0.89 of the
# MXU's peak), 9.31 at 1024 x 1024, 9.08 at 512 x 2048, 9.05 at 1024 x
# 2048, 10.06 at 1024 x 512, 10.23 at 512 x 512, 11.55 at 1024 x 256, 10.97
# at 2048 x 1024; against [2048, 16160], whose last tile is short and
# masked in a branch of its own, 3.27 ms at 512 x 1024 (0.84), 3.19 at 512
# x 2048, 3.49 at 1024 x 512, 3.57 at 512 x 512, but 5.02 at 1024 x 1024,
# 4.31 at 1024 x 2048 and 5.25 at 2048 x 512: with the two branches, rows
# of 1024 run slow (masking EVERY tile instead: 3.25 at 1024 x 1024, 3.32
# at 512 x 1024).  A wide vocabulary tile spreads the per-step work on the
# running max, sum and pick (lane-replicated [rows, 128] scratch) over more
# logits; 2048 entries gain 1 to 4% more and would halve the hidden width
# that fits.  W is re-read once per ROW tile, 16 times a call at 512 rows
# (3.2 GB, 3.9 ms at the HBM peak, under the MXU's 8.4): rows of 256 would
# be HBM-bound
ROW_TILE = 512
VOCAB_TILE = 1024
# the scoped-VMEM limit the call asks for, past the default 16 MiB: a
# [512, 2048] bfloat16 row tile and a [2048, 1024] weight tile,
# double-buffered, are 12 MiB before the float32 logits tile, its
# exponential and the masks (the v5e has 128 MiB)
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
# bytes of ONE row of hidden states (contracted whole, no K loop) the
# pipelined blocks have room for under that limit: bfloat16 8192 wide,
# float32 4096; past that Mosaic refuses the call (compiled for a
# described v5e)
ROW_BYTES_MAX = 16 * 1024

# contract the last dimension of both operands: h [rows, hidden] with a
# tied embedding's rows [vocab tile, hidden]
_NT_DIMS = (((1,), (1,)), ((), ()))
_NN_DIMS = (((1,), (0,)), ((), ()))


# --------------------------------------------------------------------------
# the rule
# --------------------------------------------------------------------------


def fits(hidden: int, rows: int, itemsize: int) -> bool:
    """The shapes the kernel takes: a hidden width of whole 128-lane
    blocks, narrow enough in ``itemsize``-byte operands to be contracted
    whole from VMEM, and a member's positions a whole number of row
    tiles."""
    return (hidden > 0 and hidden % LANES == 0
            and hidden * itemsize <= ROW_BYTES_MAX and rows > 0
            and rows % ROW_TILE == 0)


def head_form_why(traced: tuple[bool, str], hidden: int, length: int,
                  itemsize: int) -> tuple[str, str]:
    """``("kernel" | "xla", why)`` for the next-token head of a program,
    scoring sequences of ``length`` positions from hidden states ``hidden``
    wide in ``itemsize``-byte operands.  The head's OWN rule, whatever form
    the model's attention takes: the kernel when, and only when, Mosaic
    kernels may be traced in the program (``traced``, the answer of
    ``pallas_attention.traced_why``: TPU devices and whole members on a
    chip, so ``W`` and a member's hidden states are whole where the call
    runs) and the head's shapes fit (:func:`fits`).  What
    ``lm_blocks.score_next_tokens`` does while it is traced, said once at
    build; ``why`` names what decided (the engine logs it and the run
    manifest carries it)."""
    may, where = traced
    if not may:
        return "xla", where
    if not fits(hidden, length, itemsize):
        return "xla", (
            f"hidden states {hidden} wide in {itemsize}-byte operands over "
            f"{length} positions: not whole {LANES}-lane blocks of at most "
            f"{ROW_BYTES_MAX} bytes a row over whole row tiles of {ROW_TILE}")
    return "kernel", (f"{where}; a hidden width of whole {LANES}-lane "
                      f"blocks, whole row tiles of {ROW_TILE}")


# what :func:`head_facts` answers for (ops/kernel_facts.py collects them)
FACTS = ("head_form", "head_form_why")


def head_facts(scope, hidden: int) -> dict:
    """What an engine's build reports of a model's next-token head, as the
    model names it in ``PolicyDeclaration.kernels``: ``hidden``, the width
    the head contracts; ``scope`` (``ops.kernel_facts.BuildScope``) has
    whether kernels may be traced, the sequence length and the compute
    dtype's item size.  :func:`head_form_why`'s answer under its names."""
    form, why = head_form_why(scope.traced, hidden, scope.horizon,
                              scope.itemsize)
    return {"head_form": form, "head_form_why": why}


# --------------------------------------------------------------------------
# the kernel
# --------------------------------------------------------------------------


def head_cost(rows: int, hidden: int, vocab: int, rank: int,
              block_rows: int, block_vocab: int,
              itemsize: int) -> pl.CostEstimate:
    """What ONE call of the kernel does, from its grid and blocks: the
    declaration ``pallas_call`` hands XLA (a profiler's trace carries it as
    the custom call's ``flops`` and ``bytes_accessed``).  FLOPs: the logits
    matmul ``2 · rows · hidden · vocab`` and the rank-r correction's ``2 ·
    rows · rank · vocab`` (the columns of a short last tile that lie past
    the vocabulary are multiplied too and not counted).  Transcendentals:
    one exponential a logit, one a row and tile for the rescaling, one
    logarithm a row.  Bytes: the hidden states once, ``W`` once per ROW
    TILE (it streams past each), as the float32 factor ``Bᵀ`` of the
    tile's member; the targets, the scaled ``h A`` and the scores, four
    bytes a row each."""
    row_tiles = rows // block_rows
    vocab_tiles = -(-vocab // block_vocab)
    return pl.CostEstimate(
        flops=2 * rows * vocab * (hidden + rank),
        transcendentals=rows * (vocab + vocab_tiles + 1),
        bytes_accessed=(
            rows * hidden * itemsize
            + row_tiles * hidden * vocab * itemsize
            + row_tiles * rank * vocab * 4 + rows * rank * 4
            + rows * 4 + rows * 4))


def _head_kernel(h_ref, w_ref, tgt_ref, *refs, vocab: int, block_vocab: int,
                 transposed: bool, rank: int, logits_scaling):
    # with a correction: the scaled h A [rows, r] and Bᵀ [r, vocab tile]
    *factors, o_ref, m_ref, l_ref, p_ref = refs
    j = pl.program_id(1)
    last = pl.num_programs(1) - 1

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        p_ref[...] = jnp.zeros(p_ref.shape, jnp.float32)

    def fold(masked: bool):
        """This vocabulary tile into the running max, sum and pick."""
        s = jax.lax.dot_general(
            h_ref[...], w_ref[...], _NT_DIMS if transposed else _NN_DIMS,
            preferred_element_type=jnp.float32)
        if factors:
            xs, bt = factors[0][...], factors[1][...]
            if rank > 4:
                s = s + jnp.dot(xs, bt, preferred_element_type=jnp.float32)
            else:
                # a sum of r broadcast products, as perturbed._outer: a
                # multiply-add a logit on the VPU under the MXU's product
                for k in range(rank):
                    s = s + xs[:, k:k + 1] * bt[k:k + 1, :]
        if logits_scaling is not None:
            s = s / logits_scaling
        cols = j * block_vocab + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        if masked:
            # past the vocabulary the short tile holds whatever was there
            s = jnp.where(cols < vocab, s, -jnp.inf)
        p_ref[...] += jnp.sum(jnp.where(cols == tgt_ref[...], s, 0.0),
                              axis=1, keepdims=True)
        # tile 0 holds entry 0: after it the running max is finite, so
        # exp(-inf - max) is 0, never NaN
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        l_ref[...] = jnp.exp(m_prev - m_next) * l_ref[...] + jnp.sum(
            jnp.exp(s - m_next[:, :1]), axis=1, keepdims=True)
        m_ref[...] = m_next

    if vocab % block_vocab == 0:
        fold(masked=False)
    else:
        @pl.when(j < last)
        def _whole():
            fold(masked=False)

        @pl.when(j == last)
        def _short():
            fold(masked=True)

    @pl.when(j == last)
    def _finish():
        o_ref[...] = (p_ref[...] - m_ref[...] - jnp.log(l_ref[...]))[:, :1]


def _score_members(h, targets, xs, bt, w, *, transposed: bool,
                   logits_scaling, interpret: bool, block_rows: int | None,
                   block_vocab: int | None):
    """:func:`score_rows` for a set of members, written out: ``(h [M, T,
    hidden], targets [M, T], xs [M, T, r] | None, bt [M, r, vocab] | None,
    w) -> [M, T]``."""
    members, t, hidden = h.shape
    vocab = w.shape[0] if transposed else w.shape[1]
    rank = 0 if xs is None else xs.shape[-1]
    block_rows = min(block_rows or ROW_TILE, t)
    block_vocab = min(block_vocab or VOCAB_TILE, vocab)
    if t % block_rows:
        raise ValueError(f"a member's {t} positions are not a whole number "
                         f"of row tiles of {block_rows}")
    if w.shape != ((vocab, hidden) if transposed else (hidden, vocab)):
        raise ValueError(f"w {w.shape} does not contract h's {hidden}")
    rows, per_member = members * t, t // block_rows

    operands = [h.reshape(rows, hidden), w, targets.reshape(rows, 1)]
    in_specs = [
        pl.BlockSpec((block_rows, hidden), lambda i, j: (i, 0)),
        pl.BlockSpec((block_vocab, hidden), lambda i, j: (j, 0))
        if transposed else
        pl.BlockSpec((hidden, block_vocab), lambda i, j: (0, j)),
        pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0)),
    ]
    if rank:
        operands += [xs.reshape(rows, rank), bt]
        in_specs += [
            pl.BlockSpec((block_rows, rank), lambda i, j: (i, 0)),
            # a row tile lies in ONE member: that member's factor
            pl.BlockSpec((None, rank, block_vocab),
                         lambda i, j: (i // per_member, 0, j)),
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(rows // block_rows, pl.cdiv(vocab, block_vocab)),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_rows, LANES), jnp.float32),  # running max
            pltpu.VMEM((block_rows, LANES), jnp.float32),  # running sum
            pltpu.VMEM((block_rows, LANES), jnp.float32),  # target's logit
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _head_kernel, vocab=vocab, block_vocab=block_vocab,
            transposed=transposed, rank=rank, logits_scaling=logits_scaling),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=head_cost(rows, hidden, vocab, rank, block_rows,
                                block_vocab, h.dtype.itemsize),
        name="next_token_scores",
        interpret=interpret,
    )(*operands)
    return out.reshape(members, t)


@functools.lru_cache(maxsize=None)
def _core(transposed: bool, logits_scaling, interpret: bool,
          block_rows: int | None, block_vocab: int | None):
    """:func:`_score_members`, ``custom_vmap``'d so that a ``vmap`` over
    more members (pairs, signs) GROWS the set, the row axis of ONE call,
    instead of batching the call: ``w``, which those ``vmap``s do not
    batch, is then never broadcast to a copy a member."""
    impl = functools.partial(
        _score_members, transposed=transposed, logits_scaling=logits_scaling,
        interpret=interpret, block_rows=block_rows, block_vocab=block_vocab)
    core = jax.custom_batching.custom_vmap(impl)

    @core.def_vmap
    def rule(axis_size, in_batched, h, targets, xs, bt, w):
        if in_batched[4]:
            # each member its own weights (the materialised form): no W to
            # share, the members go through the call's own batching rule
            axes = jax.tree_util.tree_map(lambda b: 0 if b else None,
                                          in_batched)
            return jax.vmap(impl, in_axes=axes)(h, targets, xs, bt, w), True

        def merged(x, batched):
            if not batched:
                x = jnp.broadcast_to(x, (axis_size,) + x.shape)
            return x.reshape((axis_size * x.shape[1],) + x.shape[2:])

        h, targets, xs, bt = jax.tree_util.tree_map(
            merged, (h, targets, xs, bt), tuple(in_batched[:4]))
        out = core(h, targets, xs, bt, w)
        return out.reshape((axis_size, -1) + out.shape[1:]), True

    return core


def score_rows(h, w, targets, xs=None, bt=None, *, transposed: bool = False,
               logits_scaling=None, interpret: bool,
               block_rows: int | None = None,
               block_vocab: int | None = None):
    """``log softmax(s)[targets] [T]`` float32 of the logits ``s = h @ W +
    xs @ bt`` (``/ logits_scaling`` where given), which exist a tile at a
    time in VMEM only.  ``h [T, hidden]`` in the compute dtype; ``w
    [hidden, vocab]``, or ``[vocab, hidden]`` read ``transposed`` (a tied
    embedding); ``targets [T]`` int32, each inside the vocabulary; the
    rank-r correction as ``xs [T, r]`` float32 (``(c/√r) · h A``, the scale
    folded in) and ``bt [r, vocab]`` float32, or neither for the centre
    alone.  Under ``vmap`` the members become rows of ONE call (the
    module's text): ``h``, ``targets``, ``xs``, ``bt`` may each be batched
    or not; a batched ``w`` falls back to a call a member.

    ``block_rows``, ``block_vocab``: rows and vocabulary entries of a tile;
    :data:`ROW_TILE` and :data:`VOCAB_TILE` where not given (no more than
    there are).  A
    vocabulary that is not a whole number of tiles is masked in its last."""
    if (xs is None) != (bt is None):
        raise ValueError("a correction needs xs AND bt")
    core = _core(bool(transposed),
                 None if logits_scaling is None else float(logits_scaling),
                 bool(interpret), block_rows, block_vocab)
    if xs is not None:
        xs, bt = xs[None], bt[None]
    return core(h[None], targets.astype(jnp.int32)[None], xs, bt, w)[0]
