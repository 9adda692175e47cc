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
heads·value_dim]``, the layout the output projection reads.  Where ONE
projection writes each head's key with its values beside it (latent
attention's ``kv_b``: ``[T, heads·(128 + 128)]``), the kernel reads both
out of that array, key column block ``2g`` and values ``2g + 1``
(``v=None``): no k and no v is cut from it, and XLA then has the
projection write the kernel's row-major tiling itself.

Heads HALF a lane block wide come two a block (``paired``): a
differential PAIR as arXiv 2410.05258 publishes it, ``q [T, H/2, 2, 64]``
two query parts side by side, ``k [T, G/2, 2, 64]`` two key heads, ONE
value block of 128 a key pair.  Score head ``h = (pair p, map m, g)`` of
the grid reads q's column block ``p · group + g`` as the projection wrote
it, both maps of it, against key column block ``2p + m`` of the layout
``[T, pairs, 2, 128] = [k₁, 0 | 0, k₂]``, and the pair's ONE value block;
its context goes to column block ``h`` of ``[T, pairs · 2 · group · 128]``,
map 1's ``group`` heads and then map 2's, where
``lm_blocks.differential_combine`` reads it.  Exact (the other map's lanes
meet zeros; the MXU contracts 128 deep whatever the width), no transposing
copy of q, no copy of the values a map, and the body below is the
single-term one unchanged: only index maps differ.  The padded key costs 42
MB a member and layer at 8,192 positions x 20 key heads in bfloat16.

The score may have a SECOND term: ``s = q kᵀ + q_shared k_sharedᵀ``, each
head's second query part against ONE key part ``[T, width]`` that every
head reads (latent attention's rotated 64, which the XLA form broadcasts
to ``[T, heads, 64]``; DeepSeek-V3 writes the score as this sum).  In the
tile the parts are put side by side in VMEM and contracted once, 256
deep, so the MXU adds the terms where it accumulates; mask, running max
and sum, P·V and the divide are the single-term kernel's.  A shared width
that is not whole 128-lane blocks packs TWO heads a block: column block
``h // 2`` of ``q_shared [T, heads·64]`` holds heads ``2⌊h/2⌋`` and the
next, and the key part is laid out ``[T, 256] = [k, 0 | 0, k]``, whose
column block ``h % 2`` picks head ``h``'s half by its zeros.  Exact (the
other head's lanes meet zeros), no padded copy of q (+8.4 MB a member and
layer), and the v5e's MXU contracts 128 deep whatever the width.  The
term is a branch at TRACE time: without a shared part the traced kernel is
the single-term one, operand for operand.

The tile may be masked by a SELECTION of keys (``selected [T, T]`` int8,
``lm_blocks.select_keys``: a learned sparse attention's choice, the same
for every head): one more operand, read a ``(block_q, block_k)`` tile at a
time beside q and k, and ``s = where(selected, s, -inf)`` in EVERY visible
tile, beside the causal mask in the tiles the diagonal crosses.  A row may
then have selected nothing in the first key blocks it sees: its running
max is still ``-inf`` when the next block arrives, and the body keeps max
``-inf``, sum 0 and accumulator 0 for it (the exponentials are taken
against 0 where the max is ``-inf``) instead of ``exp(-inf - -inf)``.  A
branch at TRACE time, as the shared term and the pair are: without a
selection the traced kernel is the one above, operand for operand.  The
kernel multiplies every visible pair and masks; skipping the key blocks no
row of a query block selected is ROADMAP R13's.

The visible keys may be a BAND (``window``: query ``t`` sees ``(t - window,
t]``, a sliding-window layer).  The grid's key axis is then only as long as
the band, the most key blocks a query block sees (five of 1,024 under 4,096
keys, whatever the sequence), its step ``j`` key block ``first visible +
j``; the band's mask, ``cols > rows - window``, is applied in the tiles its
edge crosses, beside the diagonal's in the last.  With an aligned band a
query block's LAST rows see nothing in the first block it is handed, so a
banded call takes the selection's guard against ``exp(-inf - -inf)``.  A
band NARROWER than a block is the block itself, its two key blocks folded in
ONE grid step with no key axis (the last section of this file).  A branch at
TRACE time: without a window the traced kernel is the one above, operand
for operand.  Which calls take it is :func:`call_form`'s rule.

Grid ``(heads / heads a step, query blocks, key blocks)``, the key axis
innermost and sequential; running max, sum and a float32 accumulator in VMEM
scratch, a head of the step its own columns of each.  A STEP HOLDS SEVERAL
QUERY HEADS where the heads are plain (values apart, no shared part, no
pairs) and come in key-value groups: :func:`step_heads` of the group's
heads, contiguous columns ``(block_q, heads · width)`` of q and of the
context (a group's heads ARE contiguous in ``[T, heads · 128]``) over ONE
key and value block, fetched once for them; the body folds them one after
another in a loop at dynamic lane offsets.  What that buys is the grid's
steps, not the body: a head of 16,384 positions is 256 steps of which 120
lie beyond the diagonal, skipped and still paid (0.16 µs each), and the body
is scheduled no tighter for unrolled heads (PERF.md §6, PR 57, has Mosaic's
bundle counts and the kernel-only table).  The step's VMEM (a head's
lane-replicated max and sum, its accumulator, two copies of its q and
context: 2.5 MiB a head at 1,024 rows of 128 lanes beside the 10 MiB of ONE
head's float32 tile, exponential and probabilities) is asked for with
``vmem_limit_bytes``, 64 of the v5e's 128 MiB, as the head's and the
combine's kernels ask.  A branch at TRACE time: a call the rule leaves one
head a step traces the kernel above, operand for operand.

Causal by construction: a key block beyond the query block's last row is
skipped (``pl.when``) AND not fetched (its index map clamps to the last
visible block, and a block whose index did not change is not copied
again); the mask is applied only inside tiles the diagonal crosses, and
where the blocks are square such a tile is made by sub-tiles of a QUARTER of
the block (:func:`diagonal_sub`): the six of its sixteen above the diagonal
hold no visible pair and are never multiplied, masked or exponentiated, the
four on the diagonal carry the mask, the six below none (3,515 bundles of
Mosaic's schedule where the masked tile takes 5,001 and a tile below the
diagonal 4,817: mostly the mask's iota, compare and select and the dead
sub-tiles' exponentials, not the products).
Members enter through ``vmap`` (the batching rule of ``pallas_call`` puts
them in front of the grid).

Precision, the same as the XLA form's: operands in the dtype handed in
(bfloat16 in the cells), float32 scores, float32 max / sum / accumulator,
probabilities cast to the operands' dtype for P·V with float32
accumulation, ONE divide at the end.  Nothing is approximated or dropped;
what differs from the XLA form is the order of the float32 sums and that
the un-normalised probabilities are what is rounded to bfloat16.  Several
heads a step change no number of any head; the diagonal's sub-tiles change
the order of P·V's float32 partial sums inside that tile and nothing else.

What Mosaic dictated (learned by compiling for the v5e, not by reading):
a block's last two dimensions must be divisible by 8 and 128 or span the
array, so a head is a column block only where ``head_dim % 128 == 0``;
heads of 64 come two a block, which this kernel takes where the pair's
values are ONE block of 128 (``paired`` above, and the shared 64 below:
the split is the key's zeros, in HBM), and refuses where each head of 64
has values of 64 of its own, half a block of context (granite's grouped
heads: split in VMEM, ROADMAP R4's); the float32 score tile, its exponential and the
bfloat16 probabilities of a block pair live on the kernel's stack in scoped
VMEM, 16 MiB by default: blocks of 1024 x 1024 fit, 2048 x 2048 ask for
24.6 MiB and are refused.  The second term changes none of that: Mosaic
never holds two float32 ``[1024, 1024]`` products (it compiles the sum of
two products and the one 256-deep product alike, in bfloat16 and float32,
at every block pair that fits without it; the call's pipelined blocks are
9.31 MiB against 8.95 MiB, the two new operands' buffers; 1024 queries x
2048 keys fits too, 14.9 MiB, and multiplies a fifth more masked scores).
Around the kernel XLA keeps q, k, v and the
context in the row-major ``(8, 128)`` tiling the custom call states; the
rotation before it prefers positions in the lanes, so one transposing copy
of q and one of k precede each call (0.012 s a generation in the looped
cell against 0.526 s saved: PERF.md, PR 32), and a projection whose output
is CUT before the kernel (latent attention's q into its two parts) is
written positions-in-lanes and transposed after the cut (PERF.md, PR 34).

``interpret`` is a required argument, as in ops/pallas_noise.py: the engine
derives it from the platform of the mesh it runs on (never true on a TPU
mesh), tests pass ``True``.  Nothing here consults
``jax.default_backend()``.

Where a kernel may be traced at all is the ENGINE's decision, made once at
build from what it observes (:func:`traced_why`: TPU devices and whole
members on a chip) and told to the model function while the engine traces
it (:func:`kernel_scope`): a call outside an engine's trace takes the XLA
form.  Inside the scope a CALL of the core decides by its own shapes
(:func:`fits`; the head and the scan by theirs, so an attention the shapes
turn away stays in the XLA form beside a head in its kernel), and a call
with a ``window`` by its band too: the kernel where the band spans at least
one of its blocks or can be the block itself, the XLA form under any other
(:func:`call_form` has the rule; ``lm_blocks.attention_core`` and
:func:`attention_facts` both read it).  Nothing in a program depends on what
is SAID of it: a model names :func:`attention_facts` in its declaration's
``kernels`` with the widths, key heads and bands it calls the core with,
and an engine's build evaluates it once for the run's records
(``attention_form``, its reason, ``attention_form_by_kind``;
ops/kernel_facts.py), knowing neither this module's name nor the model's.
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
# With the second score term, at the sparse-expert cell's shapes (2 members
# x 32 heads x 4,096 x (128 + 64 shared), values 128; PERF.md, PR 34): 3.92
# ms at 1024 x 1024 as one 256-deep product (4.01 as the sum of two
# products, 4.15 with q's shared part zero-padded to 128 lanes a head), 4.41
# at 512 x 1024, 5.96 at 512 x 512, 6.53 at 1024 x 512, 7.23 at 2048 x 512;
# the single-term kernel on the same 32 heads 3.02 ms (16 heads: 1.45), so
# the second term costs what 128 more of contraction depth cost the MXU at
# its peak (0.17 TFLOP in 0.90 ms); the XLA form 12.34 ms.
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


def key_block(own: int, width: int, itemsize: int) -> int:
    """The key block of a call whose sequence takes blocks of ``own`` and
    whose heads (or values) are ``width`` wide in operands of ``itemsize``
    bytes: ``own``, but 512 for float32 heads of two lane blocks and more
    under blocks of 1,024: beside the score tile the pipelined copies of a
    ``(1024, 256)`` float32 key and value block pass the 16 MiB of scoped
    VMEM by 76 KiB (compiled for the v5e), key blocks of 512 fit.  No
    bfloat16 call and no narrower head is changed by it."""
    narrow = itemsize == 4 and width >= 2 * LANES and own > 512
    return 512 if narrow else own


def traced_why(platform: str, n_devices: int,
               centre_form: str | None = None) -> tuple[bool, str]:
    """``(may Mosaic kernels be traced here?, why)`` for the policy's
    forward in a program on a mesh of ``n_devices`` devices of
    ``platform`` whose perturbed form reads its centre as ``centre_form``
    says (``parallel/sharded.py::centre_form_why``).  THE rule of the four
    kernels' scope (:func:`kernel_scope`), said once, here: the devices are
    TPUs, and a member is WHOLE on its chip, which it is on a mesh of one
    device and, on a mesh of several, where the centre is ``"gathered"``:
    every chip then holds the whole compute-dtype centre and the engine
    partitions the members over the chips by hand (a ``shard_map`` over the
    pairs), so a ``pallas_call`` inside runs a chip's own members.  Where
    the centre stays ``"split"`` over the mesh's ``model`` axis the
    operands are not whole on a chip, and under GSPMD an unwrapped
    ``pallas_call`` would be replicated, not partitioned: no kernel there.
    The answer says nothing of any one kernel: each call site takes its
    kernel inside the scope where its own shapes fit (:func:`fits` here,
    ``pallas_head.fits``, ``pallas_scan.fits``)."""
    if platform != "tpu":
        return False, f"the devices are {platform!r}, not TPUs"
    if n_devices == 1:
        return True, "one TPU device"
    if centre_form != "gathered":
        return False, (f"{n_devices} devices on the mesh and the centre "
                       f"{centre_form}: a member's operands are not whole "
                       "on a chip")
    return True, (f"{n_devices} TPU devices, whole members on each (the "
                  "centre gathered, the pairs partitioned by hand)")


def _pair(head: int, shared: int, value: int, kv_heads: int | None) -> bool:
    """Two score heads of half a lane block side by side over ONE value
    block, an even number of key heads: differential attention's pair."""
    return (2 * head == value == LANES and not shared
            and kv_heads is not None and kv_heads % 2 == 0)


def _shape_failures(head: int, shared: int, value: int,
                    kv_heads: int | None, length: int) -> list[str]:
    """The conditions on an attention's shapes that fail, each as a
    sentence (:func:`attention_form_why` has them in words)."""
    return [why for ok, why in (
        (value % LANES == 0
         and (head % LANES == 0 or _pair(head, shared, value, kv_heads)),
         f"a head's own part is {head} wide and its values {value}, over "
         f"{kv_heads} key heads: not whole {LANES}-lane column blocks, nor "
         "pairs of half a block that read one value block"),
        (shared % LANES == 0 or shared == LANES // 2,
         f"the shared part is {shared} wide: neither whole {LANES}-lane "
         "blocks nor half of one"),
        (kernel_block(length) is not None,
         f"no block of {BLOCKS} divides {length} positions"),
    ) if not ok]


def fits(head: int, shared: int, value: int, kv_heads: int | None,
         length: int) -> bool:
    """The shapes ONE call of the core takes the kernel at, inside a
    scope: the width and block conditions of :func:`attention_form_why`,
    read off the call's own operands (``kv_heads``: the key heads of a call
    whose heads come in pairs, ``None`` for any other)."""
    return not _shape_failures(head, shared, value, kv_heads, length)


def attention_form(platform: str, n_devices: int, widths, length: int,
                   window: int | None = None,
                   kv_heads: int | None = None,
                   centre_form: str | None = None) -> str:
    """``"kernel"`` or ``"xla"``: :func:`attention_form_why` without its
    reason."""
    return attention_form_why(platform, n_devices, widths, length, window,
                              kv_heads, centre_form)[0]


def attention_form_why(platform: str, n_devices: int, widths, length: int,
                       window: int | None = None,
                       kv_heads: int | None = None,
                       centre_form: str | None = None) -> tuple[str, str]:
    """``("kernel" | "xla", why)`` for a program on a mesh of ``n_devices``
    devices of ``platform`` that runs attention over ``length`` positions
    with heads of ``widths``, as the model states them: one width (an
    ``int``) for heads scored and summed at it, or ``(a head's own
    query/key part, a shared part, the value width)`` where every head
    also scores a second part against ONE key all heads read (0: none).
    These are what the kernel's column blocks are cut by.  The kernel is
    taken when, and only when, ALL hold: Mosaic kernels may be traced in
    the program (:func:`traced_why`: TPU devices and whole members on a
    chip, by ``centre_form`` on a mesh of several); a head's values are
    whole numbers of 128-lane column blocks, and its own part is too, OR
    is half of one with values of ONE block and an even number of key
    heads ``kv_heads``: two score heads a block that read one value block,
    which is what a differential PAIR is and how such a model hands its
    heads to the core (``attention_core(paired=True)``); the shared part
    is a whole number of blocks or half of one (two heads a block); the
    sequence is a whole number of the kernel's blocks
    (:func:`kernel_block`).  ``why`` names the first of these that fails
    (the engine logs it and the run manifest carries it).  The shape
    conditions are the ones a CALL of the core decides by inside the scope
    (:func:`fits`): an attention they turn away stays in the XLA form
    beside a head or a scan in its kernel.

    ``window``: the band of the model's windowed layers, if it has any.
    It decides nothing here: inside the program's scope a CALL with a
    window takes the kernel or the XLA form by the band against the
    kernel's block (:func:`call_form`) and every other call the kernel;
    ``why`` says which."""
    return _form_why(traced_why(platform, n_devices, centre_form), widths,
                     length, window, kv_heads)


def _form_why(traced: tuple[bool, str], widths, length: int,
              window: int | None, kv_heads: int | None) -> tuple[str, str]:
    """:func:`attention_form_why` from ``traced``, the answer of
    :func:`traced_why` (an engine's build has it already)."""
    head, shared, value = _parts_of(widths)
    traced, where = traced
    failed = ([] if traced else [where]) + _shape_failures(
        head, shared, value, kv_heads, length)
    if failed:
        return "xla", failed[0]
    return "kernel", "{}, {}, whole row blocks{}".format(
        where,
        "two score heads a column block"
        if _pair(head, shared, value, kv_heads) else "whole column blocks",
        "" if window is None else
        f"; layers with a window of {window} in the " + (
            "kernel" if call_form("kernel", window, length, _pair(
                head, shared, value, kv_heads)) == "kernel" else "XLA form"))


def call_form(form: str, window: int | None, length: int,
              paired: bool = False) -> str:
    """The form ONE call of the core over ``length`` positions takes where
    the kernel may be traced and the call's shapes fit it (``form`` is
    ``"kernel"``; anything else stays what it is, the XLA form).  THE rule
    of a call with a ``window``, said once, here (``attention_core`` and
    :func:`attention_facts`' ``attention_form_by_kind`` both read it), a rule of
    shapes: the kernel where the band spans at least ONE of the kernel's
    blocks (``window >= kernel_block(length)``: the grid's key axis follows
    the band; a window of the sequence or more is plain causal attention),
    AND where a narrower band can be the block itself (:func:`band_block`:
    whole 128-lane rows that divide the sequence, heads not ``paired``),
    both of its key blocks folded in one grid step (the module's text has
    the measured times).  Any other band keeps the XLA form: the kernel
    multiplies whole tiles, two of 1,024 for half a tile of visible pairs
    under the SambaY cell's paired band of 512 keys."""
    spans = (window is None or window >= (kernel_block(length) or length)
             or band_block(window, length, paired) is not None)
    return "kernel" if form == "kernel" and spans else "xla"


_SCOPE: contextvars.ContextVar = contextvars.ContextVar(
    "attention_kernel_interpret", default=None)


@contextlib.contextmanager
def kernel_scope(interpret: bool):
    """While a policy is traced inside, Mosaic kernels may be traced
    (under the Pallas interpreter where ``interpret``), and that is all it
    says: ``lm_blocks``' ``attention_core``, ``score_next_tokens`` and
    ``routed_experts`` and ``sambay_lm.selective_scan`` each take their
    kernel where the call's own shapes fit (:func:`fits`, and ``fits`` of
    ``pallas_head``, ``pallas_combine``, ``pallas_scan``), else XLA.  The
    engine opens it around its own trace of the policy where
    :func:`traced_why` says so; nothing else does.  The scope acts at TRACE
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


# a step of several heads asks Mosaic for this much of the v5e's 128 MiB of
# VMEM (the default 16 MiB of scoped VMEM holds ONE head's float32 tile, its
# exponential and the probabilities, about 10 MiB at 1,024 x 1,024, and each
# head of the step adds 1.5 MiB of scratch and 1 MiB of q and context), as
# ops/pallas_head.py and ops/pallas_combine.py ask; and the rule keeps a
# step, by its own count, within this much of it
STEP_VMEM_LIMIT = 64 << 20
STEP_VMEM_BYTES = 48 << 20
# the most heads a step holds: a grid step's cost is already an eighth
STEP_MOST_HEADS = 8


def step_vmem_bytes(heads: int, head_dim: int, value_dim: int,
                    itemsize: int, block_q: int, block_k: int) -> int:
    """The VMEM a grid step of ``heads`` query heads holds, counted from
    its shapes: the float32 score tile, its exponential and the
    probabilities in the operands' dtype on the stack (one head's at a
    time: the heads are a loop); a head's running max and sum
    (lane-replicated ``[block_q, 128]`` float32) and accumulator; the
    pipeline's two copies of q and of the context, of the step's key and
    value blocks and of a selection's int8 tile (counted whether the call
    has one or not: the rule does not read it)."""
    stack = block_q * block_k * (4 + 4 + itemsize)
    scratch = heads * block_q * (2 * LANES + value_dim) * 4
    blocks = 2 * itemsize * (heads * block_q * (head_dim + value_dim)
                             + block_k * (head_dim + value_dim))
    return stack + scratch + blocks + 2 * block_q * block_k


def step_heads(group: int, head_dim: int, value_dim: int, itemsize: int,
               block_q: int, block_k: int) -> int:
    """How many query heads ONE grid step of the causal kernel holds for a
    call of plain heads (values apart, no shared part, no pairs) in
    key-value groups of ``group``: the largest divisor of the group, at
    most :data:`STEP_MOST_HEADS`, whose step is within
    :data:`STEP_VMEM_BYTES` by :func:`step_vmem_bytes`.  A step's heads
    are contiguous columns of q and of the context and read ONE key-value
    head, fetched once for them; 256 grid steps a head at 16,384 positions
    (120 of them beyond the diagonal, skipped and still paid) become 256 a
    step's heads.  A function of the call's shapes alone: 6 of `laguna`'s
    group of 6, 7 of 7, 8 of 8 (heads of 128 and of 256 in bfloat16), 4 of
    8 float32 heads of 256, 1 where heads have no groups."""
    return max((h for h in range(1, min(group, STEP_MOST_HEADS) + 1)
                if group % h == 0 and step_vmem_bytes(
                    h, head_dim, value_dim, itemsize, block_q,
                    block_k) <= STEP_VMEM_BYTES), default=1)


def diagonal_sub(block_q: int, block_k: int) -> int:
    """The width of the sub-tiles a tile on the diagonal is made by: a
    QUARTER of the block where the blocks are square and the quarter is
    whole 128-lane blocks (256 of 1,024: ten of the tile's sixteen sub-tiles
    are multiplied, masked (the four on the diagonal alone) and
    exponentiated; the six above it hold no visible pair and are never
    made), else half of it (128 of 256); 0 (the whole tile, masked) for
    unequal blocks and for blocks of 128.  On the v5e (PERF.md §6, PR 57)
    the masked tile is 5,001 bundles of Mosaic's schedule and the sub-tiled
    one 3,556 in halves, 3,515 in quarters, 3,498 in eighths, most of it the
    mask's iota, compare and select and the dead sub-tiles' exponentials; a
    call at 16,384 positions reads the same in all three (26.12 ms for
    26.95), the latent heads' 256-deep scores at 4,096 positions 1.816 ms in
    halves and 1.743 in quarters and eighths (2.007 masked)."""
    if block_q != block_k:
        return 0
    return next((block_k // n for n in (4, 2)
                 if block_k % n == 0 and block_k // n % LANES == 0), 0)


def _last_visible(i, block_q: int, block_k: int):
    """Index of the last key block the rows of query block ``i`` see."""
    return ((i + 1) * block_q - 1) // block_k


def _first_visible(i, block_q: int, block_k: int, window: int | None):
    """Index of the first key block the rows of query block ``i`` see
    under a band of ``window`` keys: the block that holds the oldest key of
    the block's first row, ``i · block_q - window + 1``; block 0 without a
    band.  ``i``: a Python ``int`` or a traced grid index."""
    if window is None:
        return 0
    oldest = i * block_q - window + 1
    return (max(oldest, 0) if isinstance(i, int)
            else jnp.maximum(oldest, 0)) // block_k


def _band_blocks(length: int, block_q: int, block_k: int,
                 window: int | None) -> list[int]:
    """How many key blocks each query block sees, first visible to last:
    the tiles the kernel computes (:func:`attention_cost`); the most of
    them is the length of the grid's key axis."""
    return [_last_visible(i, block_q, block_k) + 1
            - _first_visible(i, block_q, block_k, window)
            for i in range(length // block_q)]


def attention_cost(length: int, num_heads: int, num_kv_heads: int,
                   head_dim: int, value_dim: int, shared_dim: int,
                   block_q: int, block_k: int, itemsize: int,
                   paired: bool = False,
                   selected: bool = False,
                   window: int | None = None,
                   sub: int = 0) -> pl.CostEstimate:
    """What ONE call of the kernel does, from its grid and blocks: the
    declaration ``pallas_call`` hands XLA (the scheduler reads it, and a
    profiler's trace carries it as the custom call's ``flops`` and
    ``bytes_accessed``, which XLA cannot count for a Mosaic body).  FLOPs:
    the tiles the kernel COMPUTES (key blocks up to the query block's last
    row; the grid steps beyond are skipped and not counted), each ``2 ·
    block_q · block_k · (head_dim + shared_dim + value_dim)``: the widths
    the model states, although the MXU contracts a shared 64 as 128 deep
    and multiplies the masked half of a diagonal tile too (and a pair's
    64-wide score heads as 128 deep: two heads a block).  ``sub``: the
    square tile on the diagonal, one a query block, is multiplied by
    sub-tiles of ``sub`` that hold a visible pair alone, ``n (n + 1) / 2``
    of its ``n²`` (ten of sixteen in quarters), products and exponentials
    alike; 0: whole.
    Transcendentals: a tile's exponentials, one a score and one a row for
    the rescaling.  Bytes: q, k, v, the shared parts and the context ONCE
    each (the algorithm's least; k and v are fetched again for every query
    block that sees them, once a grid step whatever heads it holds;
    ``paired``: ONE value block a pair of key heads; ``selected``: the int8
    tiles of the selection the kernel computes under, once, although every
    step fetches them again).  ``vmap`` scales all three by the members in
    front of the grid."""
    tiles = sum(_band_blocks(length, block_q, block_k, window))
    # of each query block's diagonal tile the sub-tiles above the diagonal
    # are not made, (n - 1) / 2n of it (none where the tile is whole)
    n = block_k // sub if sub else 1
    scores = (tiles * 2 * n - length // block_q * (n - 1)) * (
        block_q * block_k) // (2 * n)
    value_heads = num_kv_heads // 2 if paired else num_kv_heads
    elements = length * (
        num_heads * (head_dim + shared_dim + value_dim)        # q, q_shared, out
        + num_kv_heads * head_dim + value_heads * value_dim    # k, v
        + shared_dim)                                          # k_shared
    return pl.CostEstimate(
        flops=2 * num_heads * scores * (head_dim + shared_dim + value_dim),
        transcendentals=num_heads * (scores + tiles * block_q),
        bytes_accessed=elements * itemsize
        + (tiles * block_q * block_k if selected else 0))


def _attention_kernel(q_ref, k_ref, v_ref, *refs, scale: float, block_q: int,
                      block_k: int, selected: bool = False,
                      window: int | None = None, heads: int = 1,
                      sub: int = 0):
    # with a shared score term: each head's second query part, the one key;
    # with a selection: its tile, last of the operands
    *shared, o_ref, m_ref, l_ref, acc_ref = refs
    sel_ref = shared.pop() if selected else None
    i, j = pl.program_id(1), pl.program_id(2)
    last = _last_visible(i, block_q, block_k)
    first_step = j == 0
    if window is not None:
        # the key axis is as long as the band: its steps are the key
        # blocks from the first one the query block sees
        j = _first_visible(i, block_q, block_k, window) + j

    @pl.when(first_step)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def cut(ref, h):
        """Head ``h``'s columns of a block that holds the step's heads side
        by side, whole lane blocks each (the block itself where the step
        holds one).  ``h``: a Python ``int`` or a loop's index."""
        if heads == 1:
            return ref
        width = ref.shape[1] // heads
        if isinstance(h, int):
            return ref.at[:, h * width:(h + 1) * width]
        return ref.at[:, pl.ds(pl.multiple_of(h * width, LANES), width)]

    # sub-blocks of a square tile the diagonal crosses: only the sub-tiles
    # that hold a visible pair are made (`diagonal_sub`)
    n = block_k // sub if sub else 0

    def stack(*parts):
        # the parts that hold rows, one under another
        parts = [x for x in parts if x is not None]
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, 0)

    def diagonal_scores(q, k):
        """``q kᵀ`` of the tile on the diagonal, key sub-block ``c`` against
        the rows from its first on (the rows above it see none of its keys:
        ``-inf`` with no product, no mask and no exponential made of it),
        the causal mask inside the sub-tile on the diagonal alone."""
        own = (jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 1)
               <= jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 0))
        tile = []
        for c in range(n):
            lo, hi = c * sub, (c + 1) * sub
            s = jax.lax.dot_general(q[lo:], k[lo:hi], _QK_DIMS,
                                    preferred_element_type=jnp.float32)
            tile.append(stack(
                jnp.full((lo, sub), -jnp.inf) if lo else None,
                jnp.where(own, s[:sub], -jnp.inf),
                s[sub:] if hi < block_k else None))
        return jnp.concatenate(tile, axis=1) * scale

    def diagonal_context(p, v):
        """``p v`` of the tile on the diagonal: a key sub-block's values
        against the rows that see it (``p`` is 0 above the diagonal)."""
        terms = [[] for _ in range(n)]      # a query sub-block's
        for c in range(n):
            lo, hi = c * sub, (c + 1) * sub
            product = jnp.dot(p[lo:, lo:hi].astype(v.dtype), v[lo:hi],
                              preferred_element_type=jnp.float32)
            for a in range(c, n):
                terms[a].append(product[(a - c) * sub:(a - c + 1) * sub])
        return stack(*(sum(t[1:], t[0]) for t in terms))

    def fold(masked: bool, edge: bool = False):
        """This key block into the running max, sum and accumulator of
        every head of the step."""
        sub_tiles = bool(sub) and masked and not edge

        def head(h):
            # the step's heads read its ONE key and value block
            v = v_ref[...]
            q, k = cut(q_ref, h)[...], k_ref[...]
            if shared:
                # q kᵀ + q_shared k_sharedᵀ as ONE contraction over the parts
                # side by side: the MXU sums both terms where it accumulates
                qs_ref, ks_ref = shared
                q = jnp.concatenate([q, qs_ref[...]], axis=1)
                k = jnp.concatenate([k, ks_ref[...]], axis=1)
            if sub_tiles:
                s = diagonal_scores(q, k)
            else:
                s = jax.lax.dot_general(
                    q, k, _QK_DIMS,
                    preferred_element_type=jnp.float32) * scale
                if masked:
                    rows = i * block_q + jax.lax.broadcasted_iota(
                        jnp.int32, s.shape, 0)
                    cols = j * block_k + jax.lax.broadcasted_iota(
                        jnp.int32, s.shape, 1)
                    seen = cols <= rows
                    if edge:
                        seen = jnp.logical_and(seen, cols > rows - window)
                    s = jnp.where(seen, s, -jnp.inf)
            if selected:
                s = jnp.where(sel_ref[...].astype(jnp.int32) != 0, s,
                              -jnp.inf)
            # without a band or a selection every row sees key 0, which
            # block 0 holds: after the first block the running max is
            # finite, so exp(-inf - max) is 0, never NaN
            m_prev = cut(m_ref, h)[...]
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            m_base = m_next
            if selected or window is not None:
                # a row that has seen no key yet (it selected none so far;
                # the band's first block holds none of a late row's keys):
                # max -inf, and its exponentials are taken against 0 (all
                # of them 0)
                m_base = jnp.where(m_next == -jnp.inf, 0.0, m_next)
            alpha = jnp.exp(m_prev - m_base)
            p = jnp.exp(s - m_base[:, :1])
            cut(l_ref, h)[...] = alpha * cut(l_ref, h)[...] + jnp.sum(
                p, axis=1, keepdims=True)
            cut(m_ref, h)[...] = m_next
            cut(acc_ref, h)[...] = alpha[:, :1] * cut(acc_ref, h)[...] + (
                diagonal_context(p, v) if sub_tiles else jnp.dot(
                    p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32))

        if heads == 1:
            head(0)
        else:
            # a LOOP over the step's heads, each at its own lane offset:
            # at 1,024 x 1,024 Mosaic schedules unrolled heads no tighter
            # than one (4,920 to 5,140 bundles a head for 4,817) and takes
            # six times as long to compile them (PERF.md, PR 57)
            def one(h, carry):
                head(h)
                return carry

            jax.lax.fori_loop(0, heads, one, 0)

    # a tile needs the mask where its last key lies beyond its first row
    crosses = (j + 1) * block_k - 1 > i * block_q
    visible = j <= last
    if window is not None:
        # and the band's where its first key is too old for its last row
        # (both masks there: the diagonal may cross the same tile)
        edge = j * block_k <= (i + 1) * block_q - 1 - window

        @pl.when(jnp.logical_and(visible, edge))
        def _edge():
            fold(masked=True, edge=True)

        visible = jnp.logical_and(visible, jnp.logical_not(edge))

    @pl.when(jnp.logical_and(visible, crosses))
    def _diagonal():
        fold(masked=True)

    @pl.when(jnp.logical_and(visible, jnp.logical_not(crosses)))
    def _below():
        fold(masked=False)

    @pl.when(j == last)
    def _finish():
        for h in range(heads):
            cut(o_ref, h)[...] = (cut(acc_ref, h)[...]
                                  / cut(l_ref, h)[...][:, :1]).astype(
                                      o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "num_heads", "num_kv_heads", "head_dim", "value_dim", "scale", "block_q",
    "block_k", "interpret", "paired", "window", "heads_a_step", "sub"))
def causal_attention(
    q: jax.Array,  # [T, num_heads · head_dim], rotated, compute dtype
    k: jax.Array,  # [T, num_kv_heads · head_dim], rotated, compute dtype
    v: jax.Array | None,  # [T, num_kv_heads · value_dim]; None: beside k
    q_shared: jax.Array | None = None,  # [T, num_heads · shared width]
    k_shared: jax.Array | None = None,  # [T, shared width]: ONE key part
    selected: jax.Array | None = None,  # [T, T] int8: the keys a query sees
    *,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    scale: float,
    interpret: bool,
    value_dim: int | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    paired: bool = False,
    window: int | None = None,
    heads_a_step: int | None = None,
    sub: int | None = None,
) -> jax.Array:
    """The context ``softmax(scale · s + causal mask) v`` per head, ``[T,
    num_heads · value_dim]`` in q's dtype, with grouped heads (query head
    ``j`` reads key/value head ``j // (num_heads / num_kv_heads)``) whose
    values are ``value_dim`` wide (``head_dim`` where not given).  The
    score ``s`` is ``q kᵀ``, and with a shared part ``q kᵀ + q_shared
    k_sharedᵀ``: a second contraction of each head's ``q_shared`` part
    with the ONE key part every head reads (latent attention's rotated
    part), summed in float32 where the tile lies.  Without one the traced
    kernel is the single-term one.

    ``v=None``: ``k`` is ``[T, num_kv_heads · (head_dim + value_dim)]``
    and holds each head's key with its values beside it, as ONE projection
    wrote them (latent attention's ``kv_b``); the kernel reads both out of
    that array, so no k and no v is cut from it first.  The two widths
    must be equal (the key is column block ``2g``, the values ``2g + 1``).

    ``paired``: the heads come in PAIRS that share a column block, as
    differential attention publishes them: ``q [T, num_heads/2 · 2 ·
    head_dim]`` holds query head pair ``j``'s two heads side by side (its
    two softmax maps), ``k [T, num_kv_heads/2 · 2 · head_dim]`` key pair
    ``p``'s two heads, and ``v [T, num_kv_heads/2 · value_dim]`` ONE value
    block a key pair, read by both of its heads; query pair ``j`` reads
    key pair ``j // (num_heads / num_kv_heads)``, its head ``m`` key head
    ``m`` of it.  The context is ``[T, num_kv_heads/2 · 2 · group ·
    value_dim]``: per key pair, head 0 of its ``group`` query pairs and
    then head 1 of them (where ``lm_blocks.differential_combine`` reads
    it).  Grid head ``h = (pair p, map m, g)`` reads q column block ``p ·
    group + g`` as it lies, both heads of it, against key column block
    ``2p + m`` of the layout ``[T, pairs, 2, 2 · head_dim] = [k₀, 0 | 0,
    k₁]``, built here: the other head's lanes meet zeros, so the one
    contraction over the block IS head ``m``'s score, exactly, and the
    kernel's body is the single-term one (as the shared 64 below).  On
    the chip a pair is one 128-lane block: ``head_dim`` 64, ``value_dim``
    a multiple of 128.

    ``selected [T, T]`` int8: query ``t`` sees the keys ``s <= t`` with
    ``selected[t, s] != 0`` alone (plain heads); every head reads the one
    selection.  An empty row gives NaN, as a softmax over nothing does
    (``lm_blocks.select_keys`` always selects a query's own key).

    ``window``: query ``t`` sees the keys ``(t - window, t]`` alone (no
    selection with it).  The grid's key axis is as long as the band (five
    blocks of 1,024 under 4,096 keys) from the query block's first visible
    one, the band's mask in the tiles its edge crosses.  A band NARROWER
    than the block that :func:`band_block` takes is the block itself, both
    key blocks in one grid step (:func:`_band_in_one_step`; plain heads,
    no blocks given).  ``window >= T`` traces as a call without one.

    ``block_q``, ``block_k``: rows of a query and of a key block; where
    none is given :func:`kernel_block` of ``T`` (all of it where it has
    none: the interpreter's).  On the chip widths and blocks are multiples
    of 128, the shared width 64 too (:func:`attention_form`).

    ``heads_a_step``, ``sub``: the query heads ONE grid step holds and the
    width of the sub-tiles a tile on the diagonal is made by (0: the whole
    masked tile); where not given, as for the blocks, the rule's
    (:func:`step_heads`, :func:`diagonal_sub`: functions of the call's
    shapes; ONE head a step for heads in pairs, with a shared part or with
    their values beside their keys).  Several heads a step are plain heads,
    a divisor of the key-value group; sub-tiles cut a square block in
    several."""
    t = q.shape[0]
    value_dim = value_dim or head_dim
    own = kernel_block(t) or t
    fold = None if block_q or block_k else band_block(window, t, paired)
    block_q = min(block_q or fold or own, t)
    block_k = min(block_k or fold or key_block(
        own, max(head_dim, value_dim), q.dtype.itemsize), t)
    if t % block_q or t % block_k:
        raise ValueError(
            f"sequence of {t} positions is not a whole number of "
            f"({block_q}, {block_k}) blocks")
    if num_heads % num_kv_heads:
        raise ValueError("query heads must be a multiple of key/value heads")
    beside = v is None
    if beside and value_dim != head_dim:
        raise ValueError(
            f"values beside their keys are column blocks of one width; "
            f"got {head_dim} and {value_dim}")
    shared = q_shared is not None
    if shared != (k_shared is not None):
        raise ValueError("a shared score term needs q_shared AND k_shared")
    if paired and (beside or shared or num_kv_heads % 2):
        raise ValueError(
            "heads in pairs are an even number of key heads with their "
            "values apart and no shared part; got "
            f"{num_kv_heads} key heads, v {None if beside else v.shape}, "
            f"q_shared {q_shared.shape if shared else None}")
    if window is not None and window < 1:
        raise ValueError(f"a band holds at least a query's own key; got "
                         f"a window of {window}")
    if window is not None and window >= t:
        window = None
    choose = selected is not None
    if choose and (beside or shared or paired or window is not None
                   or selected.shape != (t, t)):
        raise ValueError(
            "a selection is [T, T] over heads of one width with their "
            f"values apart, no shared part, no pairs and no window; got "
            f"{selected.shape} over {t} positions")
    k_width = head_dim + value_dim if beside else head_dim
    value_heads = num_kv_heads // 2 if paired else num_kv_heads
    if (q.shape != (t, num_heads * head_dim)
            or k.shape != (t, num_kv_heads * k_width)
            or not (beside or v.shape == (t, value_heads * value_dim))):
        raise ValueError(
            f"q {q.shape}, k {k.shape}, v {None if beside else v.shape} are "
            f"not [T, {num_heads} · {head_dim}], [T, {num_kv_heads} · "
            f"{k_width}] and [T, {value_heads} · {value_dim}]")
    group = num_heads // num_kv_heads
    if fold and not (beside or shared):
        return _band_in_one_step(q, k, v, num_heads, num_kv_heads, head_dim,
                                 value_dim, scale, fold, interpret)

    def kv_row(i, j):
        # step j of a query block's key axis: its first visible key block
        # (block 0 without a band) and the ones after it; beyond the
        # diagonal: the block already there, so nothing moves
        if window is not None:
            j = _first_visible(i, block_q, block_k, window) + j
        return jnp.minimum(j, _last_visible(i, block_q, block_k))

    plain = not (beside or shared or paired)
    heads = heads_a_step or (step_heads(
        group, head_dim, value_dim, q.dtype.itemsize, block_q, block_k)
        if plain else 1)
    if sub is None:
        sub = diagonal_sub(block_q, block_k)
    if heads > 1 and not (plain and group % heads == 0):
        raise ValueError(
            f"{heads} heads a step: plain heads alone, a divisor of the "
            f"key-value group of {group}")
    if sub and (block_q != block_k or block_k % sub or block_k == sub):
        raise ValueError(
            f"sub-tiles of {sub} cut a square tile into several; got "
            f"blocks ({block_q}, {block_k})")
    # a step holds `heads` query heads of ONE key-value head, contiguous
    # columns of q and of the context; the group's next steps read the same
    # key and value block (`steps` a group: the block is not copied again)
    steps = group // heads

    def kv_block(h, i, j):
        return kv_row(i, j), h // steps

    def key_beside(h, i, j):
        return kv_row(i, j), 2 * (h // group)

    def values_beside(h, i, j):
        return kv_row(i, j), 2 * (h // group) + 1

    # the column block of q and of v that grid step h reads
    q_width, k_width, own_q, own_v = heads * head_dim, head_dim, (
        lambda h: h), (lambda h: h // steps)
    if paired:
        # two heads a column block: [k₀, 0 | 0, k₁] a key pair, whose
        # column block 2p + m picks head m's half of q's block by its
        # zeros; h = (pair p, map m, g) reads q's block p · group + g as it
        # lies and the pair's ONE value block.  The layout is a select over
        # whole blocks of lanes (reshaped to [.., 2, head_dim], XLA
        # transposes k to cut it at the 64-wide heads, and back)
        mine = (jnp.arange(2 * head_dim) // head_dim
                == jnp.arange(2)[:, None])                  # [map, lane]
        k = jnp.where(mine, k.reshape(t, value_heads, 1, 2 * head_dim),
                      0).reshape(t, num_kv_heads * 2 * head_dim)
        q_width, own_q, own_v = 2 * head_dim, (
            lambda h: h // (2 * group) * group + h % group), (
            lambda h: h // (2 * group))
        k_width = q_width

    operands = [q, k, k if beside else v]
    in_specs = [
        pl.BlockSpec((block_q, q_width), lambda h, i, j: (i, own_q(h))),
        pl.BlockSpec((block_k, k_width), key_beside if beside else kv_block),
        pl.BlockSpec((block_k, value_dim),
                     values_beside if beside
                     else lambda h, i, j: (kv_row(i, j), own_v(h))),
    ]
    # from the widths the model states, before a shared 64 is packed below
    cost = attention_cost(
        t, num_heads, num_kv_heads, head_dim, value_dim,
        k_shared.shape[-1] if shared else 0, block_q, block_k,
        q.dtype.itemsize, paired, choose, window, sub)
    if shared:
        width = k_shared.shape[-1]
        if (q_shared.shape != (t, num_heads * width)
                or k_shared.shape != (t, width)):
            raise ValueError(
                f"q_shared {q_shared.shape} and k_shared {k_shared.shape} "
                f"are not [T, {num_heads} · width] and [T, width]")
        if width % LANES == 0:
            # a head's part is a column block, the key's the one block
            q_col, k_col = (lambda h: h), (lambda h: 0)
        else:
            # two heads a column block (latent attention's 64 in 128
            # lanes): block h // 2 of q_shared holds heads 2⌊h/2⌋ and the
            # next, and the key is laid out [k, 0 | 0, k], whose column
            # block h % 2 picks head h's half by its zeros: exact, no
            # padded copy of q, and the MXU contracts 128 deep whatever
            # the width
            if num_heads % 2:
                raise ValueError(
                    f"a shared part of {width} (not whole 128-lane blocks) "
                    f"packs two heads a block: {num_heads} heads are odd")
            zero = jnp.zeros_like(k_shared)
            k_shared = jnp.concatenate(
                [k_shared, zero, zero, k_shared], axis=1)
            width, q_col, k_col = 2 * width, (lambda h: h // 2), (
                lambda h: h % 2)
        operands += [q_shared, k_shared]
        in_specs += [
            pl.BlockSpec((block_q, width), lambda h, i, j: (i, q_col(h))),
            pl.BlockSpec((block_k, width),
                         lambda h, i, j: (kv_row(i, j), k_col(h))),
        ]

    if choose:
        # the tile of the selection this step scores under; beyond the
        # diagonal the tile already there, as for k and v
        operands.append(selected)
        in_specs.append(pl.BlockSpec(
            (block_q, block_k), lambda h, i, j: (i, kv_row(i, j))))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        # the key axis: the most key blocks a query block sees (all of them
        # without a band: its last query block sees every one)
        grid=(num_heads // heads, t // block_q,
              max(_band_blocks(t, block_q, block_k, window))),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_q, heads * value_dim),
                               lambda h, i, j: (i, h)),
        scratch_shapes=[    # a head of the step its own columns of each
            pltpu.VMEM((block_q, heads * LANES), jnp.float32),  # running max
            pltpu.VMEM((block_q, heads * LANES), jnp.float32),  # running sum
            pltpu.VMEM((block_q, heads * value_dim), jnp.float32),  # accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_attention_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, selected=choose, window=window,
                          heads=heads, sub=sub),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, num_heads * value_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            **({} if heads == 1 else {"vmem_limit_bytes": STEP_VMEM_LIMIT})),
        cost_estimate=cost,
        name="causal_attention",
        interpret=interpret,
    )(*operands)


# --------------------------------------------------------------------------
# a band narrower than the kernel's block: both key blocks in ONE grid step
# --------------------------------------------------------------------------
# Under a band of w keys in blocks of w a query block sees exactly two key
# blocks, so the key axis leaves the grid.  Measured on the v5e at the
# sliding layers of laguna-xs2-es-16k-1chip, ONE member and layer (16,384
# positions x 64 heads x 128 over 8 key heads, band 512, bfloat16; PERF.md
# §6, PR 55): the XLA form 17.4 ms (20.5 in the cell); the grid above called
# with blocks of 512: 7.94 ms (512 x 1,024: 8.04; 1,024 x 1,024: 8.44), a
# grid step a tile with the running max, sum and accumulator carried; the
# band as the block, ONE head a step: two masked folds 3.55 ms, the two
# products selected into the one visible tile 3.50, only the visible
# 128-wide sub-tiles multiplied 3.40 (3.69 by query sub-blocks: short
# products); a key-value group's EIGHT heads a step: 3.12 (the selected tile,
# a loop over the heads), 3.04 (unrolled), and 2.20 ms with the visible
# sub-tiles alone and the heads unrolled, which is what is below: 0.62 of the
# MXU's peak by the visible pairs.  (The rule's helpers live down here so
# that no line of the kernel above moves: a Mosaic call's cache key carries
# its callers' lines.)


def _parts_of(widths) -> tuple:
    """``(a head's own part, the shared part, the value width)`` of heads
    as a model states them: one width, or the three."""
    return (widths, 0, widths) if isinstance(widths, int) else tuple(widths)


def heads_in_pairs(widths, kv_heads: int | None) -> bool:
    """Whether heads of ``widths`` (as a model states them, see
    :func:`attention_form_why`) over ``kv_heads`` key heads reach the core
    two a column block (``attention_core(paired=True)``): what
    :func:`call_form` asks of a call, said of a model."""
    return _pair(*_parts_of(widths), kv_heads)


# what :func:`attention_facts` answers for: the names of an engine's
# gauges and manifest entries (ops/kernel_facts.py collects the kernels')
FACTS = ("attention_form", "attention_form_why", "attention_form_by_kind",
         "attention_heads_a_step")


def attention_facts(scope, widths, kv_heads: int | None = None,
                    windows: tuple | None = None,
                    query_heads: int | tuple | None = None) -> dict:
    """What an engine's build reports of a model's attention, as the model
    names it in ``PolicyDeclaration.kernels``: ``widths`` and ``kv_heads``
    as :func:`attention_form_why` reads them, ``windows`` the ``(attention
    layer kind, the band of its calls | None)`` pairs in layer order (one
    kind, ``"causal"``, no band, where it states none), ``query_heads`` the
    query heads of its calls (one number, or one a kind in ``windows``'
    order).  ``scope`` is the engine's (``ops.kernel_facts.BuildScope``):
    whether kernels may be traced, the sequence length, the compute dtype's
    item size.  ``attention_form`` and its reason by
    :func:`attention_form_why`'s rule; ``attention_form_by_kind``,
    ``"<kind>:<form>,…"``, the form the calls of each kind take by
    :func:`call_form`'s; ``attention_heads_a_step``, ``"<kind>:<heads>,…"``
    over the kinds in the kernel form, the query heads ONE grid step of
    their calls holds (:func:`call_heads`), ``None`` where no kind is or the
    model states no ``query_heads``."""
    windows = windows or (("causal", None),)
    form, why = _form_why(
        scope.traced, widths, scope.horizon,
        next((band for _, band in windows if band is not None), None),
        kv_heads)
    paired = heads_in_pairs(widths, kv_heads)
    forms = [call_form(form, band, scope.horizon, paired)
             for _, band in windows]
    if not isinstance(query_heads, tuple):
        query_heads = (query_heads,) * len(windows)
    steps = [f"{kind}:" + str(call_heads(widths, kv_heads, heads, band,
                                         scope.horizon, scope.itemsize))
             for (kind, band), took, heads in zip(windows, forms, query_heads)
             if took == "kernel" and heads]
    return {"attention_form": form, "attention_form_why": why,
            "attention_form_by_kind": ",".join(
                f"{kind}:{took}" for (kind, _), took in zip(windows, forms)),
            "attention_heads_a_step": ",".join(steps) or None}


def call_heads(widths, kv_heads: int | None, query_heads: int,
               window: int | None, length: int, itemsize: int) -> int:
    """The query heads ONE grid step holds in a kernel call over ``length``
    positions of ``query_heads`` heads of ``widths`` over ``kv_heads``
    key-value heads (as a model states them) under a band of ``window``:
    what :func:`causal_attention` takes for such a call, said of a model.
    :func:`band_heads` where the band is the block itself,
    :func:`step_heads` for every other call of plain heads, 1 for heads in
    pairs, with a shared part or with their values beside their keys."""
    head, shared, value = _parts_of(widths)
    if shared or not kv_heads or _pair(head, shared, value, kv_heads):
        return 1
    group = query_heads // kv_heads
    block = band_block(window, length)
    if block:
        return band_heads(group, block, head, itemsize)
    own = kernel_block(length) or length
    return step_heads(group, head, value, itemsize, own,
                      key_block(own, max(head, value), itemsize))


def band_block(window: int | None, length: int,
               paired: bool = False) -> int | None:
    """The block of a call over ``length`` positions whose band of
    ``window`` keys is NARROWER than the kernel's block
    (:func:`kernel_block`) and can be the block itself: a whole number of
    128-lane rows that divides the sequence, over heads that are whole
    column blocks (not ``paired``).  ``None`` for every other call: no
    band, a band of a block or more (the grid's key axis follows it), a
    band that is no block (the XLA form, :func:`call_form`)."""
    own = kernel_block(length) or length
    if (window is None or paired or window >= own or window % LANES
            or length % window):
        return None
    return window


# rows of a query sub-block and keys of a key sub-block inside a band's
# block: the MXU's own width, so a sub-tile is one pass of one array
SUB = LANES
# the most bytes of q a grid step of a narrow band holds: a key-value group's
# heads share the step (k and v fetched once for them) up to this much
BAND_Q_BYTES = 1 << 20


def band_heads(group: int, block: int, head_dim: int, itemsize: int) -> int:
    """How many query heads of a key-value group of ``group`` ONE grid step
    of a narrow band holds: the largest divisor of the group whose q block
    ``[block, heads · head_dim]`` is within :data:`BAND_Q_BYTES` (all 8 of
    the cell's bfloat16 group at a band of 512; 4 in float32)."""
    most = max(1, BAND_Q_BYTES // (block * head_dim * itemsize))
    return max(d for d in range(1, group + 1) if group % d == 0 and d <= most)


def _band_kernel(q_ref, k_prev_ref, k_ref, v_prev_ref, v_ref, o_ref, *,
                 scale: float, heads: int):
    """Query block ``i`` of ``heads`` query heads of ONE key-value head
    under a band as wide as the block: it sees key blocks ``i - 1`` and
    ``i`` and no others, so both are folded HERE and nothing is carried
    between grid steps.  In the tiles' own indices the two masks are
    complements, the same in every step (own block: ``cols <= rows``, the
    diagonal; previous block: ``cols > rows``, the band's edge), so a row's
    visible scores are exactly ONE tile's worth: column sub-block ``c`` of
    that tile is the PREVIOUS block's product for the query rows before
    sub-block ``c``, the OWN block's for the rows after it, and the select
    of the two inside it.  Only those rows are multiplied (five of eight
    sub-tiles at four sub-blocks), each key sub-block one pass of the MXU
    against all the rows that see it; max, exp and sum are taken once over
    the visible tile (every row sees its own key: no ``-inf`` max); P·V
    goes the same way, a key sub-block's values against the rows that see
    it.  The heads of the step are unrolled: the scheduler overlaps one
    head's products with another's exponentials."""
    block, hd, vd = k_ref.shape[0], k_ref.shape[1], v_ref.shape[1]
    n = block // SUB
    rows = jax.lax.broadcasted_iota(jnp.int32, (SUB, SUB), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (SUB, SUB), 1)
    own = cols <= rows
    zero = jnp.zeros((SUB, SUB), jnp.float32)

    def stack(*parts):
        # the parts that hold rows, one under another
        parts = [x for x in parts if x is not None]
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, 0)

    def head(h, banded):
        q = q_ref[:, h * hd:(h + 1) * hd]
        tile = []
        for c in range(n):
            lo, hi = c * SUB, (c + 1) * SUB
            # own keys c against the rows from lo on, the first SUB of them
            # under the diagonal
            s_own = jax.lax.dot_general(q[lo:], k_ref[lo:hi], _QK_DIMS,
                                        preferred_element_type=jnp.float32)
            below = s_own[SUB:] if hi < block else None
            if banded:
                # previous keys c against the rows before hi, the last SUB
                # of them past the band's edge
                s_prev = jax.lax.dot_general(
                    q[:hi], k_prev_ref[lo:hi], _QK_DIMS,
                    preferred_element_type=jnp.float32)
                above, edge = s_prev[:lo] if lo else None, s_prev[lo:]
            else:
                above = jnp.full((lo, SUB), -jnp.inf) if lo else None
                edge = -jnp.inf
            tile.append(stack(above, jnp.where(own, s_own[:SUB], edge),
                              below))
        s = jnp.concatenate(tile, axis=1) * scale
        p = jnp.exp(s - jnp.max(s, axis=1, keepdims=True))
        total = jnp.sum(p, axis=1, keepdims=True)
        context = [[] for _ in range(n)]    # a query sub-block's terms
        for c in range(n):
            lo, hi = c * SUB, (c + 1) * SUB
            diagonal = p[lo:hi, lo:hi]
            seen = stack(jnp.where(own, diagonal, zero),
                         p[hi:, lo:hi] if hi < block else None)
            product = jnp.dot(seen.astype(v_ref.dtype), v_ref[lo:hi],
                              preferred_element_type=jnp.float32)
            for a in range(c, n):
                context[a].append(product[(a - c) * SUB:(a - c + 1) * SUB])
            if banded:
                seen = stack(p[:lo, lo:hi] if lo else None,
                             jnp.where(own, zero, diagonal))
                product = jnp.dot(seen.astype(v_ref.dtype), v_prev_ref[lo:hi],
                                  preferred_element_type=jnp.float32)
                for a in range(c + 1):
                    context[a].append(product[a * SUB:(a + 1) * SUB])
        o_ref[:, h * vd:(h + 1) * vd] = (
            stack(*(sum(terms[1:], terms[0]) for terms in context))
            / total).astype(o_ref.dtype)

    @pl.when(pl.program_id(1) == 0)
    def _first():
        # no previous block: the own block's triangle alone
        for h in range(heads):
            head(h, banded=False)

    @pl.when(pl.program_id(1) > 0)
    def _banded():
        for h in range(heads):
            head(h, banded=True)


def _band_in_one_step(q, k, v, num_heads: int, num_kv_heads: int,
                      head_dim: int, value_dim: int, scale: float, block: int,
                      interpret: bool) -> jax.Array:
    """:func:`causal_attention` under a band of ``block`` keys in blocks of
    ``block`` (:func:`band_block`): grid ``(heads / heads a step, query
    blocks)``, no key axis; a step holds :func:`band_heads` query heads of
    one key-value head, contiguous columns of q and of the context.  k and
    v are each handed in TWICE, as the previous and as the own block (two
    index maps over one array; block 0's previous clamps to itself and is
    not read)."""
    t, group = q.shape[0], num_heads // num_kv_heads
    heads = band_heads(group, block, head_dim, q.dtype.itemsize)
    steps = group // heads      # grid steps a key-value head

    def previous(g, i):
        return jnp.maximum(i - 1, 0), g // steps

    def own(g, i):
        return i, g // steps

    return pl.pallas_call(
        functools.partial(_band_kernel, scale=scale, heads=heads),
        grid=(num_heads // heads, t // block),
        in_specs=[
            pl.BlockSpec((block, heads * head_dim), lambda g, i: (i, g)),
            pl.BlockSpec((block, head_dim), previous),
            pl.BlockSpec((block, head_dim), own),
            pl.BlockSpec((block, value_dim), previous),
            pl.BlockSpec((block, value_dim), own),
        ],
        out_specs=pl.BlockSpec((block, heads * value_dim),
                               lambda g, i: (i, g)),
        out_shape=jax.ShapeDtypeStruct((t, num_heads * value_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        cost_estimate=attention_cost(
            t, num_heads, num_kv_heads, head_dim, value_dim, 0, block, block,
            q.dtype.itemsize, window=block),
        name="causal_attention",
        interpret=interpret,
    )(q, k, k, v, v)
