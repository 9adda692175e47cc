"""The pieces the NINE sequence models are built from
(models/hybrid_lm.py, models/looped_lm.py, models/moe_lm.py,
models/sambay_lm.py, models/indexed_moe_lm.py, models/cca_moe_lm.py,
models/window_moe_lm.py, models/delta_moe_lm.py,
models/gated_window_moe_lm.py): ONE RMSNorm (and its
zero-centred reading, ``1 + w``), ONE LayerNorm, ONE gated FFN whose
gate's activation is the MODEL's (SiLU where it states none, ReLU for a
ReGLU model), ONE causal attention core (full,
banded or over a learned SELECTION of keys), ONE indexer that makes such a
selection, ONE differential combine, ONE depthwise causal conv, the mixing
of q, k and v inside a compressed latent (a per-head convolution, the q-k
mean, the value shift, the L2 scale), ONE expert layer (routed by sigmoid
scores or by a softmax, of ONE matrix or of an MLP over a carried state,
with or without a selection bias, renormalised or not) and ONE
next-token scorer, each on the perturbed-dense primitive
(models/perturbed.py), so that an optimisation of one is measured on every
model that calls it.  The attention core, the scorer and the expert layer's
combine have TWO forms each of one algorithm, and the rule that picks is
below.

The attention's core (:func:`attention_core`: rotated parts -> context)
takes its operands in PARTS: per head a query and a key of one width and
values of another, and optionally a second query part per head with ONE
key part that every head reads.  The score is the sum of the two
contractions, ``q kᵀ + q_shared k_sharedᵀ``: the dot of the concatenated
parts, which is how DeepSeek-V3 writes latent attention's score (heads
128 + 64 wide where they are scored, the 64 rotated and its key shared,
128 where they are summed; the other two models' heads are one width with
no shared part).  The values may come BESIDE their keys in one array, as
latent attention's ``kv_b`` writes them (``v=None``): the kernel reads
both where they lie, the XLA form cuts them apart.  A model makes its own
parts (:func:`causal_attention`
is the plain q/k/v/o form two of them share).  With a ``window`` a query
sees the keys ``(t - window, t]`` and no others: both forms skip the key
blocks wholly outside that band as they skip those above the diagonal.  A
CALL with a window takes the kernel where the band spans at least one of the
kernel's blocks or, narrower, can be the block itself, and the XLA form under
any other (``pallas_attention.call_form`` has the rule), and the model's other
calls follow the rule below.
With ``selected`` (``[T, T]`` int8, :func:`select_keys`) a query sees the
keys its row of the selection marks and no others: a mask made from the
DATA, different for every member, layer and sequence, which BOTH forms
read: the XLA form beside its causal mask, the kernel as one more operand
of a tile.  A selection marks no future key, and ``topk >= T`` marks every
visible one: full causal attention, bit for bit.
Differential attention (arXiv 2410.05258) is ONE call of the core with both
softmax maps as heads of it (``paired``: the heads handed in as the PAIRS
the projections write, two score heads of half a lane block side by side
over ONE value block, which the kernel reads where they lie and the XLA
form orders itself), then :func:`differential_combine`.  The core
has TWO forms of one algorithm, same mathematics, same tiles, same
precision:

- ``"xla"``: block-causal einsums and a softmax, whose float32 score
  tiles XLA holds in HBM; a shared part is concatenated onto q and k, the
  key's broadcast to every head.  It runs anywhere: the CPU path, every
  test's oracle, and what a mesh takes whose chips hold no whole member.
- ``"kernel"``: ops/pallas_attention.py, an online softmax whose scores
  never leave VMEM; the shared part is a second contraction inside the
  tile, its key read by every head from the one ``[T, width]`` array.

Which one a program takes is not an option of a model or of ``ES``.  The
ENGINE resolves once at build, from what it observes, where Mosaic kernels
may be traced at all (``ops.pallas_attention.traced_why``, the one place
that says it: TPU devices and a member WHOLE on its chip, which is one
device on the mesh, or several with the centre gathered and the members
partitioned over them by hand) and opens ``pallas_attention.kernel_scope``
around its trace of the policy there.  Inside that scope
:func:`attention_core` takes the kernel for a call whose own shapes fit
(``pallas_attention.fits``: a head's values whole numbers of 128-lane
column blocks, and its own query/key part too, or half of one with values
of ONE block and an even number of key heads (a pair); a shared part of 64
or a multiple of 128; the sequence a whole number of the kernel's blocks;
and, of a call with a ``window``, a band of at least one of those blocks
or one that can be the block: ``call_form``), and the XLA form otherwise and
everywhere else, so ``apply`` outside an engine is the XLA form.  The run's
records say which form that is (``attention_form``, its reason, and
``attention_form_by_kind`` where the attention layers are of several kinds):
the model names the kernel's own rule, ``pallas_attention.attention_facts``,
in its declaration's ``kernels`` (``perturbed.PolicyDeclaration``) with what
it calls the core with: the widths the kernel's column blocks are cut by,
one ``int`` for heads of one width or ``(a head's own part, the shared part,
the value width)``; its key heads; and each kind's band.  The engine
evaluates it at build with what it observed (ops/kernel_facts.py) and
carries the answer to the gauges and the manifest
(``ShardedESEngine.kernel_facts``): the same conditions, said of the model.

The next-token scorer (:func:`score_next_tokens`: hidden states and the
head's leaf -> each next token's log-probability) takes the leaf itself,
its noise, ``c`` and whether it is read transposed (a tied embedding), not
a closure, because its two forms multiply it differently:

- ``"xla"``: float32 logits ``[block, vocab]`` a block of ``head_block``
  positions through ``perturbed_dense``, ``logsumexp`` and a gather: the
  logits are written, re-laid out and re-read through HBM.  It runs
  anywhere, as the attention's XLA form does.
- ``"kernel"``: ops/pallas_head.py: a row tile x vocabulary tile of logits
  (the matmul, the rank-r correction, the scaling) is folded into a running
  max and sum and the target's logit picked where it lies in VMEM; the
  members under the ``vmap``s around it become rows of ONE call, so ``W``
  is never copied a member; any vocabulary (a short last tile is masked).

It takes the kernel inside the SAME ``kernel_scope`` where its own shapes
fit (``pallas_head.fits``: a hidden width of whole 128-lane blocks and at
most 16 KiB a row, the sequence a whole number of the kernel's row tiles)
and the noise is factored or none, whatever form the attention beside it
takes: a model whose heads the attention's kernel turns away (64 wide with
values of 64) scores in the head's kernel all the same.  Which, and why,
is in the run's records (``head_form``, ``head_form_why``: the model's
declaration names ``pallas_head.head_facts`` with the width the head
contracts).  The last position's logits (the behaviour) are the one-row XLA
matmul in both forms.

One more kernel is taken inside that scope, by a model and not by this
module: Mamba-1's selective scan (``sambay_lm.selective_scan`` asks
``pallas_attention.scoped_interpret()`` as the dispatches here do;
ops/pallas_scan.py, ``scan_form`` in the records by its ``scan_facts``).

The indexer (:func:`select_keys`: DeepSeek-V3.2's sparse attention, whose
``sa_config`` keys a configuration carries) scores every visible key of a
query with a few narrow heads against ONE key head, ``I[t, s] = Σ_j w[t, j]
· relu(qI[t, j] · kI[s])`` (:func:`index_scores`, ``es.index``), and keeps
the ``min(t + 1, topk)`` largest, ties to the lower index
(:func:`choose_keys`, ``es.select``): a block of queries at a time against
the keys up to the block's end, the k-th largest score of a row found by
bisection on the float's bits (32 counting passes, no sort), exactly.

The expert layer (:func:`routed_experts`) is told which experts it holds:
it routes over all of them (:func:`route`: sigmoid scores and a selection
bias, or a softmax over all experts), computes what its own experts
give for the (token, k) pairs routed to them and leaves the rest out.
The two are separate functions: a model routes from the state its experts
read (:func:`routed_ffn`) or from another one, the layer's input ahead of
attention (models/window_moe_lm.py), and the gate's activation is the
model's, as the dense FFN's.  One
implementation: the pairs of ALL members under the ``vmap``s around it are
sorted by expert together, so that the centre's stacked ``[E, m, n]``
leaves go through one grouped matmul whose work follows the rows routed,
and only the rank-r correction is per (member, expert).  Static shapes and
no drop: the sorted rows are taken ``capacity`` at a time, as many times as
there are rows.  What closes a pass, the COMBINE (each routed row times its
route's weight, added into its token's row: float32 rows, weights and sums,
every routed pair, in the pass's row order), has TWO forms:

- ``"xla"``: ``y.at[token].add(out · w)``, a scatter-add, which XLA:TPU runs
  as one read-modify-write a row, in turn.  It runs anywhere, as the
  attention's XLA form does, and is what the materialised form (each member
  its own weights, a ``vmap`` of the layer) always takes.
- ``"kernel"``: ops/pallas_combine.py, over tiles of consecutive tokens.  A
  pass's rows ascend by ``held expert · tokens + token`` (the stable sort,
  and no expert twice a token), so what one expert sends a tile is ONE
  contiguous run of the pass's rows: a tile copies its ``held`` runs, adds
  them in the same order, and is written once, in place of ``y``.

It takes the kernel inside the SAME ``kernel_scope`` where its own shapes fit
(``pallas_combine.fits``: token rows of whole 128-lane blocks, a member's
tokens a whole number of the kernel's tiles), whatever forms the kernels
beside it take.  Which is in the run's records (``combine_form``: a model
with an expert layer names ``pallas_combine.combine_facts`` with the width
of a token's row).

The leaves these blocks name are cut over a mesh's ``model`` axis by the
rules at the end of this file (``DECODER_PARTITION_RULES``,
``EXPERT_PARTITION_RULES``); a model composes them with the rules of the
leaves only it has, beside its ``param_shapes``, into its declaration's
``partition_rules`` (docs/sharding.md).

Functions, not a base class: a model hands in its own ``dense`` (the
``(p, noise, c, name, x) -> x @ (p[name] + c·noise[name])`` of the class,
which a subclass may replace) and its sizes.  What only one model has stays
with it: the Mamba-2 mixer there, the pass loop and the exit gate here,
the gated delta rule, its gated norm and the attention's output gate in
models/delta_moe_lm.py.
Rotary positions are an optional argument of the one attention.

Precision as in the models' own text: matmul operands in the dtype of the
parameters handed in, float32 accumulation; norms, rotation, softmax and
log-softmax in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import functools

import numpy as np
from jax.sharding import PartitionSpec as P

from ..obs.trace import (ATTN, DENSE, DIFF, DISPATCH, EXPERT, HEAD, INDEX,
                         MIX, PERTURB, ROPE, ROUTE, SELECT, part, stage)
from ..ops import pallas_attention, pallas_combine, pallas_head
from .perturbed import (F32, MODEL_AXIS, is_factored, perturbed_dense,
                        perturbed_grouped_dense, perturbed_headwise_dense,
                        perturbed_leaf)

# rows the expert layer takes at a time, over what a uniform router sends
# its held experts: one pass nearly always, and the loop takes the rest
EXPERT_CAPACITY_MARGIN = 1.25


def layer_name(i: int) -> str:
    return f"layer_{i:02d}"


def refuse_unwritten(model, only: dict) -> None:
    """A model's ``__post_init__``: ``only`` maps a published key that has
    ONE form written here to that form's value; any other value raises,
    naming the key and the form."""
    for name, value in only.items():
        if getattr(model, name) != value:
            raise ValueError(f"{name} = {getattr(model, name)!r} is not "
                             f"written: the one form is {value!r}")


def draw_tree(shapes, key, value_of):
    """One float32 array per leaf of ``shapes``: ``value_of(name, key_i,
    shape)``, ``name`` the last key of the leaf's path and ``key_i =
    fold_in(key, i)`` in tree order (a model's ``init``, under one jit)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    return jax.tree_util.tree_unflatten(treedef, [
        value_of(str(path[-1].key), jax.random.fold_in(key, i), leaf.shape)
        for i, (path, leaf) in enumerate(leaves)])


def rmsnorm(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def zero_centred_rmsnorm(x, w, eps):
    """:func:`rmsnorm` whose weight is stored about ZERO: ``x · rsqrt(mean
    x² + eps) · (1 + w)`` (Qwen3-Next's and Gemma's convention; ``w = 0`` is
    the bare normalisation)."""
    return rmsnorm(x, 1.0 + w, eps)


def layernorm(x, scale, bias, eps):
    """float32 LayerNorm with bias over the last axis."""
    x = x.astype(F32)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale + bias


def causal_conv(x, taps, bias):
    """Depthwise causal conv over time: ``y_t = Σ_k taps[k]·x_{t-(K-1-k)} +
    bias`` (``taps [K, 1, C]``, the last tap multiplies the current step, as
    torch's ``Conv1d(padding=K-1)[..., :T]`` does)."""
    k_taps, t = taps.shape[0], x.shape[0]
    padded = jnp.pad(x, ((k_taps - 1, 0), (0, 0)))
    y = bias
    for k in range(k_taps):
        y = y + taps[k, 0] * padded[k:k + t]
    return y


def dense(p, noise, c, name, x, bias: str | None = None,
          under: str = DENSE):
    """float32 ``x @ (p[name] + c·noise[name])`` under ``es.dense``, the
    part ``of.<name>``: every projection of the nine models says here
    which leaf it multiplies (obs/trace.py).  ``bias``: the key of a bias
    leaf of ``p`` (perturbed like any small leaf), added to the product.
    ``under``: the stage of a projection that belongs to another one (the
    indexer's, ``es.index``)."""
    with stage(under), part(name):
        y = perturbed_dense(
            x, p[name], None if noise is None else noise[name], c)
        if bias is None:
            return y
        return y + perturbed_leaf(
            p[bias], None if noise is None else noise[bias], c)


def subtree(noise, *path):
    """``noise[path[0]][path[1]]…``, or ``None`` for the centre alone."""
    for k in path:
        if noise is None:
            return None
        noise = noise[k]
    return noise


def gated_mlp(dense, p, noise, c, u, activation=jax.nn.silu):
    """``down(activation(gate u) ⊙ up u)``, float32 out; the gate's
    ``activation`` is the model's (SiLU; ``jax.nn.relu``: ReGLU)."""
    dtype = u.dtype
    gate = dense(p, noise, c, "gate", u)
    up = dense(p, noise, c, "up", u)
    with stage(DENSE), part("down"):    # the operand ``down`` multiplies
        act = (activation(gate) * up).astype(dtype)
    return dense(p, noise, c, "down", act)


def yarn_inv_freq(head_dim: int, theta: float, scaling: dict):
    """YaRN's ``inv_freq [head_dim/2]`` float32 and the factor its tables
    are multiplied by (arXiv 2309.00071, the ``rope_type`` ``"yarn"`` of a
    published ``rope_scaling`` / ``rope_parameters`` group).  With ``D =
    head_dim`` (the ROTATED width), ``f_i = theta^(-2i/D)`` and ``L =
    original_max_position_embeddings``:

        low  = floor(D ln(L / (beta_fast · 2π)) / (2 ln theta))   (>= 0)
        high = ceil (D ln(L / (beta_slow · 2π)) / (2 ln theta))   (<= D - 1)
        r_i  = clip((i - low) / max(high - low, 0.001), 0, 1)
        inv_freq_i = (f_i / factor) · r_i + f_i · (1 - r_i)

    so the pairs that turn more than ``beta_fast`` times inside ``L`` keep
    their frequency, those that turn less than ``beta_slow`` times are
    slowed ``factor`` times, and the ones between are blended.  The second
    value is ``attention_factor``, ``0.1 ln(factor) + 1`` where the group
    gives none: cos and sin are both multiplied by it, so a score of two
    rotated parts grows by its square.  Computed on the host from the
    group's numbers (``beta_fast`` 32 and ``beta_slow`` 1 where absent)."""
    d, factor = head_dim, float(scaling["factor"])
    span = scaling["original_max_position_embeddings"]

    def turns_at(rotations):
        return d * np.log(span / (rotations * 2 * np.pi)) / (
            2 * np.log(theta))

    low = max(np.floor(turns_at(scaling.get("beta_fast") or 32)), 0)
    high = min(np.ceil(turns_at(scaling.get("beta_slow") or 1)), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 0.001), 0, 1)
    freq = float(theta) ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    attention_factor = scaling.get("attention_factor")
    if attention_factor is None:
        attention_factor = 0.1 * np.log(factor) + 1.0
    return (jnp.asarray(freq / factor * ramp + freq * (1 - ramp), F32),
            float(attention_factor))


def rotary_tables(length: int, head_dim: int, theta: float,
                  positions=None, sections=None, scaling=None):
    """``(cos, sin) [T, head_dim/2]`` float32 of positions ``0 … T-1``:
    ``inv_freq_i = theta^(-2i/head_dim)``.  ``positions [streams, T]`` with
    ``sections`` (M-RoPE, arXiv 2409.12191): frequency pair ``i`` turns by
    the position stream its section names, the first ``sections[0]`` pairs
    by stream 0, the next ``sections[1]`` by stream 1, …; the sections add
    up to ``head_dim/2``.  Streams that all hold ``0 … T-1`` give the
    tables of no ``positions``, bit for bit.

    ``scaling``: a published rope-scaling group (``rope_type`` and its
    numbers).  ``None`` and ``rope_type`` ``"default"`` are the path above,
    bit for bit: the program of a model that states no scaling is what it
    was.  ``"yarn"``: the frequencies of :func:`yarn_inv_freq` (its text has
    the formula: a per-frequency blend of ``f_i`` and ``f_i / factor``) and
    BOTH tables times its ``attention_factor``.  Any other type raises.
    One table a KIND of layer: a model whose kinds differ in ``theta``, in
    the rotated width or in the scaling calls this once a kind."""
    kind = (scaling or {}).get("rope_type", "default")
    if kind not in ("default", "yarn"):
        raise ValueError(f"rope_type {kind!r} is not written: 'default' "
                         "(no scaling) or 'yarn'")
    with stage(ROPE):
        if kind == "yarn":
            inv_freq, factor = yarn_inv_freq(head_dim, theta, scaling)
        else:
            factor = None
            inv_freq = 1.0 / (theta ** (
                jnp.arange(0, head_dim, 2, dtype=F32) / head_dim))
        if positions is None:
            at = jnp.arange(length, dtype=F32)[:, None]
        else:
            if (sum(sections) != head_dim // 2
                    or positions.shape != (len(sections), length)):
                raise ValueError(
                    f"sections {tuple(sections)} over positions "
                    f"{positions.shape} are not {head_dim // 2} frequency "
                    f"pairs over [streams, {length}]")
            stream = np.repeat(np.arange(len(sections)), sections)
            at = positions.astype(F32)[stream].T        # [T, head_dim/2]
        angle = at * inv_freq[None, :]
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        return (cos, sin) if factor is None else (cos * factor, sin * factor)


def rotate(x, cos, sin, interleaved: bool = False,
           rotary_dim: int | None = None):
    """Rotary embedding of ``x [T, heads, head_dim]`` float32.  Frequency
    ``i`` turns the pair ``(x_i, x_{i+d/2})`` in the halves convention
    (``x·cos + rotate_half(x)·sin``) and the pair ``(x_{2i}, x_{2i+1})``
    when ``interleaved``; each pair stays where it was.  ``rotary_dim``
    (a ``partial_rotary_factor``): the LEADING ``rotary_dim`` of each head
    turn, by the ``rotary_dim / 2`` frequency pairs of ``cos`` and ``sin``,
    the convention inside that slice; the rest of the head stays as it is.
    ``None``, or the whole head: the program of no ``rotary_dim``."""
    if rotary_dim is not None and rotary_dim != x.shape[-1]:
        return jnp.concatenate(
            [rotate(x[..., :rotary_dim], cos, sin, interleaved),
             x[..., rotary_dim:]], axis=-1)
    half = x.shape[-1] // 2
    x1, x2 = ((x[..., 0::2], x[..., 1::2]) if interleaved
              else (x[..., :half], x[..., half:]))
    cos, sin = cos[:, None, :], sin[:, None, :]
    y1, y2 = x1 * cos - x2 * sin, x2 * cos + x1 * sin
    if interleaved:
        return jnp.stack([y1, y2], axis=-1).reshape(x.shape)
    return jnp.concatenate([y1, y2], axis=-1)


def causal_attention(dense, p, noise, c, u, *, num_heads: int,
                     num_kv_heads: int, head_dim: int, scale: float,
                     block: int, rotary=None):
    """Causal attention with grouped heads from the four projections
    ``q``, ``k``, ``v``, ``o`` of ``p``, heads of ONE width.  ``rotary``:
    ``(cos, sin)`` of :func:`rotary_tables`, applied to queries and keys;
    ``None``: no positional encoding.  The core is :func:`attention_core`."""
    dtype, t = u.dtype, u.shape[0]
    nq, nkv, hd = num_heads, num_kv_heads, head_dim

    def rotated(x, heads):
        if rotary is None:
            return x
        with stage(ROPE):
            return rotate(x.reshape(t, heads, hd), *rotary)

    q = rotated(dense(p, noise, c, "q", u), nq).astype(dtype)
    k = rotated(dense(p, noise, c, "k", u), nkv).astype(dtype)
    v = dense(p, noise, c, "v", u).astype(dtype)
    ctx = attention_core(q, k, v, num_heads=nq, num_kv_heads=nkv,
                         scale=scale, block=block)
    return dense(p, noise, c, "o", ctx)


def attention_core(q, k, v, *, num_heads: int, num_kv_heads: int,
                   scale: float, block: int, q_shared=None, k_shared=None,
                   window: int | None = None, paired: bool = False,
                   selected=None):
    """``context [T, heads · value width]`` of causal attention with
    grouped heads: ``q [T, heads(, ·) qk width]``, ``k [T, kv heads(, ·)
    qk width]`` and ``v [T, kv heads(, ·) value width]`` in the compute
    dtype, heads split or not.  The value width may differ from the
    query/key width.  ``scale`` is ONE number of the program, the same for
    every head; a scale that is learned, or a head's own (a temperature),
    arrives folded into ``k`` (:func:`l2_scale`), the one way.  ``v=None``: ``k [T, kv heads(, ·) qk width + value
    width]`` holds each head's key with its values beside it, as one
    projection wrote them.  ``q_shared [T, heads(, ·) shared width]`` and
    ``k_shared [T, shared width]``: a second part of every head's query
    and ONE key part that every head reads (latent attention's rotated
    part); the score is ``q kᵀ + q_shared k_sharedᵀ``, the dot of the
    concatenated parts.  Block-causal: query block ``i`` is scored against
    the keys ``[0, end of block i)`` and no others, so (n+1)/(2n) of the
    ``[T, T]`` score tiles of ``n`` blocks are computed, and a masked score
    (``exp(-inf) = 0``) exists only inside the diagonal tile.  ``window``:
    query ``t`` sees the keys ``(t - window, t]`` alone; query block ``i``
    is then scored against the key blocks that hold ``(start of block i -
    window, end of block i)`` and no others, so a window of one block costs
    two key blocks a query block however long the sequence, and masked
    scores exist in the first and the last of them.  ``selected [T, T]``
    int8 (:func:`select_keys`): query ``t`` sees the keys ``s`` with
    ``selected[t, s] != 0`` alone, beside the causal mask (a selection
    marks no future key; heads of one width, values apart, no window).

    ``paired``: the heads come in PAIRS, as differential attention
    publishes them: ``q [T, heads/2, 2, qk width]`` a diff-head's two maps
    side by side, ``k [T, kv heads/2, 2, qk width]`` a key pair's two
    heads, ``v [T, kv heads/2, value width]`` ONE value block a key pair,
    read by both of its heads; diff-head ``j`` reads key pair ``j //
    (heads / kv heads)``, its map ``m`` key head ``m`` of it.  The context
    is that of the ``heads`` score heads ordered (key pair, map, group)
    over the ``kv heads`` key heads (pair, map), each with its pair's
    values: what :func:`differential_combine` reads.  The kernel reads the
    pairs where they lie (a pair is one of its column blocks); the XLA
    form makes that order of q and a copy of the values a map first.

    Inside an engine's ``pallas_attention.kernel_scope`` a call whose
    widths and length fit (``pallas_attention.fits``), and whose band, if
    it has a ``window``, spans at least one of the kernel's blocks or can
    be the block (``pallas_attention.call_form``), is the Pallas kernel
    (scores in VMEM, the shared part a second contraction in the tile: the
    key part is never broadcast, the band a key axis as long as itself); a
    call of other shapes or under any other band, and any call anywhere
    else, is the XLA form below, in blocks of ``block``, which
    concatenates the shared parts onto q and k, the key's broadcast to
    every head (the module's text has the rule).  The XLA form's loop over
    blocks is unrolled: the program grows with ``T / block``, so a much
    longer sequence should raise the block, not the count."""
    dtype, t = q.dtype, q.shape[0]
    nq, nkv = num_heads, num_kv_heads
    hd = q.size // (t * nq)
    if selected is not None and (
            v is None or q_shared is not None or window is not None
            or paired or selected.shape != (t, t)):
        raise ValueError(
            "a selection of keys is [T, T] over heads of one width with "
            "their values apart, no shared part, no window and no pairs")
    # the call's own widths: a head's values (ONE block a key pair where
    # the heads come in pairs; beside the keys where none are handed in)
    # and the part scored against the shared key
    vd = (k.size // (t * nkv) - hd if v is None
          else v.size // (t * (nkv // 2 if paired else nkv)))
    shared = 0 if q_shared is None else k_shared.shape[-1]
    # inside a scope, the kernel where these fit and a band, if there is
    # one, spans a block of it or can be the block
    form = pallas_attention.call_form(
        "kernel" if pallas_attention.fits(
            hd, shared, vd, nkv if paired else None, t) else "xla",
        window, t, paired)
    interpret = (pallas_attention.scoped_interpret() if form == "kernel"
                 else None)
    value_heads = nkv
    if paired and interpret is None:
        # the score heads of one key head (pair, map) side by side, and a
        # pair's values once a map
        q = q.reshape(t, nkv // 2, nq // nkv, 2, hd).transpose(0, 1, 3, 2, 4)
        v = jnp.broadcast_to(v.reshape(t, nkv // 2, 1, vd),
                             (t, nkv // 2, 2, vd))
    elif paired:
        value_heads = nkv // 2      # the kernel reads ONE block a key pair
    if v is None and (interpret is None or k.size != 2 * t * nkv * hd):
        # cut the values from beside the keys: the XLA form's einsums
        # read them apart (and the kernel's column blocks are one width)
        kv = k.reshape(t, nkv, -1)
        k, v = kv[..., :hd], kv[..., hd:]
    if interpret is not None:
        with stage(ATTN):
            return pallas_attention.causal_attention(
                q.reshape(t, nq * hd), k.reshape(t, -1),
                None if v is None else v.reshape(t, value_heads * vd),
                None if q_shared is None else q_shared.reshape(t, -1),
                k_shared, num_heads=nq, num_kv_heads=nkv, head_dim=hd,
                value_dim=vd, scale=scale, interpret=interpret,
                paired=paired, selected=selected, window=window)
    if q_shared is not None:
        with stage(ROPE):
            dr = k_shared.shape[-1]
            q = jnp.concatenate([q.reshape(t, nq, hd),
                                 q_shared.reshape(t, nq, dr)], axis=-1)
            k = jnp.concatenate(
                [k.reshape(t, nkv, hd),
                 jnp.broadcast_to(k_shared[:, None], (t, nkv, dr))], axis=-1)
            hd += dr
    # query head j reads key/value head j // (nq / nkv)
    qh = q.reshape(t, nkv, nq // nkv, hd)
    kh, vh = k.reshape(t, nkv, hd), v.reshape(t, nkv, vd)
    block = min(block, t)
    ctx = []
    for start in range(0, t, block):
        stop = min(start + block, t)
        # the first key block any query of this block sees
        first = (0 if window is None
                 else max(0, (start - window + 1) // block * block))
        q_b = qh[start:stop]
        if ctx:
            # one block at a time: left free, the TPU scheduler runs
            # every block's softmax before the first P·V and holds all
            # their float32 scores at once (T²/2 of them)
            q_b, _ = jax.lax.optimization_barrier((q_b, ctx[-1]))
        with stage(ATTN):
            s = jnp.einsum("qkgd,skd->kgqs", q_b, kh[first:stop],
                           preferred_element_type=F32) * scale
            keys = (jnp.arange(stop) if first == 0
                    else jnp.arange(first, stop))[None, :]
            queries = jnp.arange(start, stop)[:, None]
            mask = keys <= queries
            if window is not None:
                mask = mask & (keys > queries - window)
            if selected is not None:
                mask = mask & (selected[start:stop, first:stop] != 0)
            s = jnp.where(mask, s, -jnp.inf)
            prob = jax.nn.softmax(s, axis=-1).astype(dtype)
            ctx.append(jnp.einsum(
                "kgqs,skd->qkgd", prob, vh[first:stop],
                preferred_element_type=F32).astype(dtype))
    return jnp.concatenate(ctx).reshape(t, nq * vd)


# ------------------------------------------- mixing inside a compressed latent
# (CCA, arXiv 2510.04476: attention whose q, k and v live in a latent
# narrower than the residual and are mixed there, over time and across
# each other, before the scores; each piece alone, under es.mix and a part)

def head_conv(x, w, noise, c, bias, taps: int):
    """Causal convolution over time that MIXES the channels of each head,
    float32 ``[T, heads, width]``: ``y_t[h] = Σ_j x_{t-(taps-1-j)}[h] ·
    (C_j[h] + c·E_j[h]) + bias[h]``, zero before the sequence, the last tap
    the current position's (as :func:`causal_conv`).  ``x [T, heads,
    width]`` in the compute dtype; ``w [taps · heads, width, width]`` the
    (tap, head) matrices stacked tap-major (``w[j · heads + h] = C_j[h]``),
    a stacked leaf whose ``noise`` is one factor pair a matrix
    (ops/lowrank.py) or a dense array; ``bias [heads · width]`` float32, as
    a member reads it.  Written as ``taps`` head-wise matmuls on shifted
    inputs (``perturbed.perturbed_headwise_dense``): the centre's product
    is shared by the members and the correction books to ``es.perturb``."""
    t, heads, width = x.shape
    with stage(MIX), part("conv_head"):
        # head-major while the taps add up, as the batched products are
        y = bias.reshape(heads, 1, width)
        for j in range(taps):
            back = taps - 1 - j
            x_j = x if back == 0 else jnp.pad(
                x, ((back, 0), (0, 0), (0, 0)))[:t]
            of_tap = slice(j * heads, (j + 1) * heads)
            y = y + perturbed_headwise_dense(
                x_j, w[of_tap],
                None if noise is None else (
                    tuple(f[of_tap] for f in noise) if is_factored(noise)
                    else noise[of_tap]), c)
        return y.transpose(1, 0, 2)


def qk_mean(q, k, q_before, k_before):
    """``(q + ½(q̃ + repeat(k̃)), k + ½(mean over its group of q̃ + k̃))``
    float32: what the convolutions made of q ``[T, heads, width]`` and k
    ``[T, kv heads, width]``, each with the mean of BOTH projections'
    values from before the convolutions (``q̃``, ``k̃``) across the
    query-group boundary: query head ``i`` pairs with key head ``i //
    (heads / kv heads)``, as it does in the scores."""
    t, nq, width = q_before.shape
    nkv = k_before.shape[1]
    with stage(MIX), part("qk_mean"):
        of_group = q_before.reshape(t, nkv, nq // nkv, width).mean(axis=2)
        return (q + 0.5 * (q_before + jnp.repeat(k_before, nq // nkv, axis=1)),
                k + 0.5 * (of_group + k_before))


def value_shift(v):
    """``v [T, kv heads, width]`` with the LAST half of its heads read from
    the position before (zeros at position 0): the first half of the value
    heads are projections of ``u_t``, the others of ``u_{t-1}``."""
    t, nkv = v.shape[:2]
    with stage(MIX), part("value_shift"):
        before = jnp.pad(v[:, nkv // 2:], ((1, 0), (0, 0), (0, 0)))[:t]
        return jnp.concatenate([v[:, :nkv // 2], before], axis=1)


def l2_scale(x, scale, eps: float):
    """``√width · scale · x / ‖x‖₂`` over the last axis, float32: ``x /
    √(mean x² + eps) · scale``, an RMSNorm whose weight is ONE number a
    head.  ``scale`` broadcasts against ``x [T, heads, width]`` (``[heads,
    1]``: a learned temperature a head; ``1.0``: none).  Scores of two such
    vectors are ``width · scale_q · scale_k · cos``: bounded, whatever the
    projections' norms."""
    with stage(MIX), part("qk_norm"):
        return rmsnorm(x, scale, eps)


# ---------------------------------------------------- the learned selection

def index_scores(q_i, k_i, w, first_query: int):
    """``I [rows, keys]`` float32 of a block of queries against the keys up
    to the block's end: ``I[t, s] = Σ_j w[t, j] · relu(q_i[t, j] ·
    k_i[s])`` over the indexer's heads ``j``, ``-inf`` where ``s > t``.
    ``q_i [rows, heads, width]`` and the ONE key head ``k_i [keys, width]``
    in the compute dtype (float32 accumulation), ``w [rows, heads]``
    float32; the block's first query is position ``first_query``, key ``s``
    position ``s``.  A positive scale of a row of ``w`` changes no order."""
    with stage(INDEX):
        dots = jnp.einsum("qhd,sd->qhs", q_i, k_i,
                          preferred_element_type=F32)
        scores = jnp.sum(jax.nn.relu(dots) * w[:, :, None], axis=1)
        queries = first_query + jnp.arange(q_i.shape[0])[:, None]
        return jnp.where(jnp.arange(k_i.shape[0])[None, :] <= queries,
                         scores, -jnp.inf)


def _ordered_bits(x):
    """int32 whose signed order is the float32 order of ``x`` (``-0.0``
    read as ``0.0``, as a comparison of floats reads it)."""
    bits = jax.lax.bitcast_convert_type(
        jnp.where(x == 0.0, 0.0, x).astype(F32), jnp.int32)
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


def _prefix_count(flags):
    """Inclusive count of the set ``flags [rows, n]`` along a row, int32:
    lanes of 128 against a triangle on the MXU (exact: small whole numbers
    in float32), the blocks' totals by a short cumulative sum."""
    rows, n = flags.shape
    lane = 128 if n % 128 == 0 else n
    f = flags.reshape(rows, n // lane, lane)
    upto = jnp.arange(lane)[:, None] <= jnp.arange(lane)[None, :]
    inside = jnp.einsum("rbl,lm->rbm", f.astype(jnp.bfloat16),
                        upto.astype(jnp.bfloat16),
                        preferred_element_type=F32).astype(jnp.int32)
    before = jnp.cumsum(inside[..., -1], axis=1) - inside[..., -1]
    return (inside + before[..., None]).reshape(rows, n)


def choose_keys(scores, topk: int, first_query: int):
    """``(selected [rows, keys] int8, how many int32)``: per row of
    ``scores`` (:func:`index_scores`: ``-inf`` at the future keys) its
    ``min(t + 1, topk)`` largest, ``t = first_query + row``; among equal
    scores the lower index.  Exact and without a sort: the k-th largest
    value of a row is found bit by bit (32 passes that count the scores at
    or above a candidate, on an int32 whose order is the float's), the
    scores above it are taken, and of those equal to it the first few
    that fill the row."""
    with stage(SELECT):
        rows = scores.shape[0]
        want = jnp.minimum(first_query + jnp.arange(rows) + 1,
                           topk).astype(jnp.int32)[:, None]
        key = _ordered_bits(scores)
        low = jnp.int32(-2**31)

        def one_bit(i, kth):
            # ``kth`` holds the bits found so far of the k-th largest key,
            # as an unsigned pattern (sign bit flipped)
            trial = kth | jnp.left_shift(jnp.int32(1), 31 - i)
            enough = jnp.sum(key >= (trial ^ low), axis=1, keepdims=True,
                             dtype=jnp.int32) >= want
            return jnp.where(enough, trial, kth)

        kth = jax.lax.fori_loop(
            0, 32, one_bit, jnp.zeros((rows, 1), jnp.int32)) ^ low
        above, equal = key > kth, key == kth
        room = want - jnp.sum(above, axis=1, keepdims=True, dtype=jnp.int32)
        chosen = above | (equal & (_prefix_count(equal) <= room))
        return chosen.astype(jnp.int8), jnp.sum(chosen, dtype=jnp.int32)


def select_keys(q_i, k_i, w, *, topk: int, block: int):
    """``(selected [T, T] int8, selected pairs int32)``: the keys each
    query of a sequence attends to, ``selected[t, s] = 1`` for the ``min(t
    + 1, topk)`` keys ``s <= t`` of largest index score (the same for every
    attention head), 0 elsewhere; what both forms of
    :func:`attention_core` read as ``selected``.  ``q_i [T, heads,
    width]``, ``k_i [T, width]`` (rotated, compute dtype), ``w [T, heads]``
    float32.  ``block`` queries at a time against the keys up to the
    block's end (:func:`index_scores`, :func:`choose_keys`), one block
    after the other: the float32 scores of a block are ``[heads, block,
    T]`` at the end of the sequence."""
    t = q_i.shape[0]
    block = min(block, t)
    rows, count = [], jnp.int32(0)
    for start in range(0, t, block):
        stop = min(start + block, t)
        q_b = q_i[start:stop]
        if rows:
            # one block at a time, as the attention's XLA form
            q_b, _ = jax.lax.optimization_barrier((q_b, rows[-1]))
        chosen, n = choose_keys(
            index_scores(q_b, k_i[:stop], w[start:stop], start), topk, start)
        with stage(SELECT):
            rows.append(jnp.pad(chosen, ((0, 0), (0, t - stop))))
            count = count + n
    with stage(SELECT):
        return jnp.concatenate(rows), count


def differential_combine(ctx, lam, gamma, *, pairs: int, group: int,
                         lambda_init: float, eps: float):
    """Differential attention's combine (arXiv 2410.05258):
    ``RMSNorm(A₁v - λ·A₂v; γ, eps) · (1 - lambda_init)``, float32 ``[T,
    pairs · group · value width]``, from ONE call of :func:`attention_core`
    whose heads were both softmax maps: ``ctx [T, pairs · 2 · group · value
    width]`` holds, per key/value pair, map 1's ``group`` heads and then map
    2's (key head ``2p + m`` is map ``m`` of pair ``p``, and each value pair
    is read by both).  ``lam`` a scalar, ``gamma [value width]``."""
    t = ctx.shape[0]
    with stage(DIFF):
        maps = ctx.astype(F32).reshape(t, pairs, 2, group, -1)
        out = rmsnorm(maps[:, :, 0] - lam * maps[:, :, 1], gamma, eps)
        return (out * (1.0 - lambda_init)).reshape(t, -1)


# ------------------------------------------------------- the expert layer

def route(p, noise, c, u, *, top_k: int, scaling: float,
          scoring: str = "sigmoid", logits=None, renormalise: bool = True):
    """``(experts [T, top_k] int32, weights [T, top_k] float32)`` of the
    tokens ``u [T, hidden]`` float32 over ALL the experts the router
    ``p["router"] [hidden, experts]`` scores, held here or not.
    ``scoring`` ``"sigmoid"`` (DeepSeek-V3): ``s = sigmoid(u W_r)``, the
    ``top_k`` of ``s + bias`` (ties to the lower index), weights ``s`` at
    the chosen (the selection bias ``p["router_bias"]`` enters the choice
    only).  ``"softmax"`` (Qwen3-MoE): ``s = softmax(u W_r)`` over all
    experts, the ``top_k`` of ``s``.  Under either, the bias is read where
    ``p`` holds a ``router_bias`` and the choice is by ``s`` alone where a
    model builds none.  ``logits [T, experts]``
    float32: the scores of a router that is more than one matrix
    (:func:`state_router`), in place of ``u W_r``.  Renormalised to sum
    ``scaling``, or with ``renormalise=False`` ``scaling · s`` at the
    chosen as they are (at ``top_k`` 1 a renormalised weight is 1 whatever
    the router says).  All in float32, the matmul at ``highest``
    precision: a rounding of the scores picks another expert."""
    if scoring not in ("sigmoid", "softmax"):
        raise ValueError(f"scoring {scoring!r}: 'sigmoid' or 'softmax'")
    def scored(z):
        return (jax.nn.sigmoid(z) if scoring == "sigmoid"
                else jax.nn.softmax(z, axis=-1))

    with stage(ROUTE), jax.default_matmul_precision("highest"):
        if logits is None:
            with part("router"):
                s = scored(perturbed_dense(
                    u.astype(F32), p["router"].astype(F32),
                    None if noise is None else noise["router"], c))
        else:
            s = scored(logits)
        picked_by = s
        if "router_bias" in p:
            picked_by = s + perturbed_leaf(
                p["router_bias"],
                None if noise is None else noise["router_bias"], c)
        _, experts = jax.lax.top_k(picked_by, top_k)
        w = jnp.take_along_axis(s, experts, axis=-1)
        if not renormalise:
            return experts, scaling * w
        return experts, scaling * w / (w.sum(axis=-1, keepdims=True) + 1e-20)


def state_router(p, noise, c, u, below, eps: float):
    """``(logits [T, experts], state [T, router width])`` float32 of a
    router that is an MLP over a state carried from layer to layer (ZAYA1,
    arXiv 2511.17127): ``r = u W_dn + b_dn + γ ⊙ below``; ``logits = W₃
    gelu(W₂ gelu(W₁ rmsnorm(r) + b₁) + b₂) + b₃`` (the exact GELU, by
    ``erf``); ``r`` is handed to the layer above AFTER γ's term is added.
    ``p``: ``router_down``, ``router_down_bias``, ``router_state`` (γ),
    ``router_norm/scale`` and ``router_mlp/{w1, b1, w2, b2, w3, b3}``;
    ``below [T, router width]`` float32, zeros under the first layer.  All
    in float32 at ``highest`` precision, as :func:`route`, which takes the
    logits."""
    def leaf(*path):
        w = p
        for k in path:
            w = w[k]
        return w, subtree(noise, *path)

    def affine(x, weight, bias):
        w, w_noise = leaf(*weight)
        b, b_noise = leaf(*bias)
        return (perturbed_dense(x, w.astype(F32), w_noise, c)
                + perturbed_leaf(b, b_noise, c))

    with stage(ROUTE), jax.default_matmul_precision("highest"):
        with part("router_down"):
            r = affine(u.astype(F32), ("router_down",),
                       ("router_down_bias",))
        with part("router_state"):
            r = r + perturbed_leaf(*leaf("router_state"), c) * below
        with part("router_mlp"):
            x = rmsnorm(r, perturbed_leaf(*leaf("router_norm", "scale"), c),
                        eps)
            for i in (1, 2):
                x = jax.nn.gelu(affine(x, ("router_mlp", f"w{i}"),
                                       ("router_mlp", f"b{i}")),
                                approximate=False)
            return affine(x, ("router_mlp", "w3"), ("router_mlp", "b3")), r


def routed_ffn(moe, noise, c, u, dtype, *, top_k: int, scaling: float,
               first_held: int, total: int, scoring: str = "sigmoid",
               activation=jax.nn.silu):
    """An expert layer's routed part of the float32 tokens ``u``:
    :func:`route` over all experts, then :func:`routed_experts` on the
    tokens in the compute ``dtype`` for the experts ``moe["experts"]``
    holds; ``(the held experts' sum [T, hidden], pairs per held expert)``.
    A model whose router reads ANOTHER state than its experts (the layer's
    input, ahead of attention) calls the two functions itself."""
    # (the older form is asked for as it always was: what stands in for
    # ``route`` in a rehearsal takes the arguments it had then)
    experts, weights = route(
        moe, noise, c, u, top_k=top_k, scaling=scaling,
        **({} if scoring == "sigmoid" else {"scoring": scoring}))
    return routed_experts(
        moe["experts"], subtree(noise, "experts"), c, u.astype(dtype),
        experts, weights, first_held=first_held, total=total,
        activation=activation)


def expert_capacity(pairs: int, held: int, total: int) -> int:
    """Rows the expert layer takes at a time for ``pairs`` (token, k)
    pairs routed over ``total`` experts of which ``held`` are here: what a
    uniform router sends here and ``EXPERT_CAPACITY_MARGIN`` over it, a
    whole number of 512-row tiles (of 8 rows where that is more than
    all), and never more than all the pairs."""
    want = -(-int(pairs * held * EXPERT_CAPACITY_MARGIN) // total)
    tile = 512 if want >= 512 else 8
    return min(-(-want // tile) * tile, -(-pairs // 8) * 8)


def routed_experts(p, noise, c, u, experts, weights, *, first_held: int,
                   total: int, activation=jax.nn.silu):
    """``(Σ_k weights_k · expert_{experts_k}(u) over the pairs whose expert
    is HELD here [T, hidden] float32, pairs per held expert [held]
    int32)``: the held experts are ``first_held … first_held + held - 1``
    of ``total``, ``held`` the leading axis of the stacked ``p["gate"]``,
    ``p["up"] [held, hidden, width]`` and ``p["down"] [held, width,
    hidden]`` (gated: ``down(activation(gate u) ⊙ up u)``, the gate's
    ``activation`` the model's, SiLU where it states none).  ``experts``
    and ``weights`` are the routes of :func:`route`, from whichever state
    the model's router reads.  What the other experts would have added is
    left out.  ``held == total`` is the uncut layer.

    Under the engine's ``vmap``s over members the centre ``p`` is not
    batched, and the pairs of every member are handled together (the
    module's text): one sort, one grouped matmul a leaf and pass, and a
    combine whose form is picked here, by the module's rule."""
    interpret = pallas_attention.scoped_interpret()
    if interpret is not None and not pallas_combine.fits(u.shape[-1],
                                                         u.shape[0]):
        interpret = None
    core = _expert_core(int(first_held), int(total), activation, interpret)
    c = jnp.asarray(c, F32).reshape(1)
    noise = None if noise is None else {
        n: tuple(f[None] for f in noise[n]) for n in ("gate", "up", "down")}
    y, load = core(u[None], experts[None], weights[None], c,
                   {n: p[n] for n in ("gate", "up", "down")}, noise)
    return y[0], load[0]


@functools.lru_cache(maxsize=None)
def _expert_core(first_held: int, total: int, activation,
                 combine: bool | None = None):
    """The expert layer over a set of members, ``custom_vmap``'d so that a
    ``vmap`` over more members (pairs, signs) GROWS the set instead of
    batching the sort and the grouped matmul: ``(u [M, T, hidden], experts
    [M, T, K], weights [M, T, K], c [M], centre {gate, up, down}, noise
    {name: (A [M, E, m, r], B [M, E, n, r])} | None) -> (y [M, T, hidden],
    load [M, E])``; ``activation``: the experts' gate's; ``combine``: the
    form of the combine, ``None`` the scatter-add, else the kernel (under
    the Pallas interpreter where true)."""

    def members(combine):
        def impl(u, experts, weights, c, centre, noise):
            return _experts_of_members(u, experts, weights, c, centre, noise,
                                       first_held, total, activation, combine)
        return impl

    core = jax.custom_batching.custom_vmap(members(combine))

    @core.def_vmap
    def rule(axis_size, in_batched, u, experts, weights, c, centre, noise):
        if any(jax.tree_util.tree_leaves(in_batched[4])):
            # each member its own weights (the materialised form): no
            # centre to share, the members go one by one, and a batched
            # combine would be a kernel call a member: the scatter-add
            axes = jax.tree_util.tree_map(lambda b: 0 if b else None,
                                          in_batched)
            return jax.vmap(members(None), in_axes=axes)(
                u, experts, weights, c, centre, noise), (True, True)

        def merged(x, batched):
            if not batched:
                x = jnp.broadcast_to(x, (axis_size,) + x.shape)
            return x.reshape((axis_size * x.shape[1],) + x.shape[2:])

        u, experts, weights, c, noise = jax.tree_util.tree_map(
            merged, (u, experts, weights, c, noise),
            (*in_batched[:4], in_batched[5]))
        y, load = core(u, experts, weights, c, centre, noise)
        return ((y.reshape((axis_size, -1) + y.shape[1:]),
                 load.reshape((axis_size, -1) + load.shape[1:])),
                (True, True))

    return core


def _experts_of_members(u, experts, weights, c, centre, noise, first_held,
                        total, activation, combine: bool | None = None):
    """:func:`_expert_core` written out: sort the pairs by held expert
    (the others last), then ``capacity`` sorted rows at a time: gather the
    tokens, the gated FFN as grouped matmuls with each row's (member,
    expert) correction, add the weighted rows back into their tokens' rows
    (the combine).  The combine has TWO forms of one sum, float32 rows,
    weights and adds, every routed pair, in the pass's row order:
    ``combine`` ``None`` is the scatter-add, which runs anywhere; else the
    kernel of ops/pallas_combine.py (``combine``: its ``interpret``), which
    reads what the sort already gives (a pass's rows ascend by ``held
    expert · tokens + token``, so the rows an expert sends a tile of
    consecutive tokens are one contiguous run) and writes each token's row
    once, in place.  :func:`routed_experts` picks, by the module's rule."""
    n_members, t, hidden = u.shape
    k, held = experts.shape[-1], centre["gate"].shape[0]
    pairs, tokens = n_members * t * k, n_members * t
    cap = expert_capacity(pairs, held, total)
    with stage(DISPATCH):
        local = experts.reshape(pairs) - first_held
        group = jnp.where((local >= 0) & (local < held), local,
                          held).astype(jnp.int32)
        order = jnp.argsort(group, stable=True).astype(jnp.int32)
        load = (group.reshape(n_members, t * k, 1)
                == jnp.arange(held, dtype=jnp.int32)).sum(
                    axis=1, dtype=jnp.int32)
        sizes_all = load.sum(axis=0)
        ends = jnp.cumsum(sizes_all)
        starts, routed = ends - sizes_all, ends[-1]
        # a window past the last pair reads pairs nobody holds
        order_p = jnp.concatenate([order, jnp.full((cap,), pairs, jnp.int32)])
        group_p = jnp.concatenate(
            [group[order], jnp.full((cap,), held, jnp.int32)])
        x_flat, w_flat = u.reshape(tokens, hidden), weights.reshape(pairs)

    def grouped(name, x, sizes, row_expert, row_member):
        with part(name):
            return perturbed_grouped_dense(
                x, centre[name], sizes,
                None if noise is None else noise[name], c, row_expert,
                row_member)

    def one_pass(carry):
        start, y = carry
        with stage(DISPATCH):
            pair = jax.lax.dynamic_slice(order_p, (start,), (cap,))
            row_expert = jnp.minimum(
                jax.lax.dynamic_slice(group_p, (start,), (cap,)), held - 1)
            valid = start + jnp.arange(cap, dtype=jnp.int32) < routed
            token = jnp.minimum(pair // k, tokens - 1)
            row_member = token // t
            sizes = (jnp.clip(ends - start, 0, cap)
                     - jnp.clip(starts - start, 0, cap)).astype(jnp.int32)
            x = jnp.take(x_flat, token, axis=0)
            w = jnp.where(valid, jnp.take(
                w_flat, jnp.minimum(pair, pairs - 1)), 0.0)
        # the routed experts' leaves read ``experts.gate`` … in a trace
        with stage(EXPERT), part("experts"):
            gate = grouped("gate", x, sizes, row_expert, row_member)
            up = grouped("up", x, sizes, row_expert, row_member)
            with part("down"):
                act = (activation(gate) * up).astype(x.dtype)
            out = grouped("down", act, sizes, row_expert, row_member)
        with stage(DISPATCH):
            # the rows past the routed ones land nowhere
            if combine is None:
                y = y.at[jnp.where(valid, token, tokens)].add(
                    out * w[:, None], mode="drop")
            else:
                key = jnp.where(valid, row_expert * tokens + token,
                                held * tokens)
                y = pallas_combine.combine_rows(
                    y, out, w, token, key, start == 0, held=held,
                    interpret=combine)
        return start + cap, y

    _, y = jax.lax.while_loop(
        lambda carry: carry[0] < routed, one_pass,
        (jnp.int32(0), jnp.zeros((tokens, hidden), F32)))
    return y.reshape(n_members, t, hidden), load


def score_next_tokens(h, tokens, w, noise, c, block: int,
                      logits_scaling=None, *, leaf: str,
                      transposed: bool = False):
    """``(log p(tokens[t+1] | tokens[:t+1]) [T-1], the last position's
    logits [vocab])`` float32 from the hidden states ``h [T, hidden]`` and
    the head ``w + c·noise``: ``w [hidden, vocab]``, or a tied embedding
    ``[vocab, hidden]`` read ``transposed``; ``noise`` its ``(A, B)``
    factors, a dense array or ``None`` (models/perturbed.py).  The logits
    are divided by ``logits_scaling`` where the model has one; the ``[T,
    vocab]`` logits never exist.  ``leaf`` is the key of the leaf ``w``
    (``"head"``, a tied ``"embed"``): the logits, their log-softmax and
    the picked scores are that part of ``es.head`` (obs/trace.py).

    TWO forms of one algorithm (the module's text has the rule).  Inside
    an engine's ``pallas_attention.kernel_scope``, where the shapes fit
    (``pallas_head.fits``) and the noise is factored or none, the Pallas
    kernel of ops/pallas_head.py: a tile of logits is made, reduced and
    picked from in VMEM, all ``T`` rows in tiles of the kernel's own.
    Anywhere else the XLA form: float32 logits ``[block, vocab]`` a block
    of ``block`` positions, ``logsumexp`` and a gather.  The last
    position's logits are the one-row XLA matmul in both."""

    def scaled(y):
        return y if logits_scaling is None else y / logits_scaling

    def project(h_b):
        return perturbed_dense(h_b, w, noise, c, transposed)

    def last_logits():
        with stage(HEAD), part(leaf):
            return scaled(project(h[-1:])[0])

    t = tokens.shape[0]
    interpret = pallas_attention.scoped_interpret()
    if (interpret is not None
            and pallas_head.fits(h.shape[-1], t, h.dtype.itemsize)
            and (noise is None or is_factored(noise))):
        # the target of position t is token t+1; the last position's is
        # scored against token 0 and left out
        targets = jnp.pad(tokens[1:], (0, 1))
        with stage(HEAD), part(leaf):
            xs = bt = None
            if noise is not None:
                with stage(PERTURB):
                    a, b = (noise[1], noise[0]) if transposed else noise
                    xs = jnp.dot(h, a.astype(h.dtype),
                                 preferred_element_type=F32) * (
                        c / jnp.sqrt(jnp.asarray(a.shape[-1], F32)))
                    bt = b.astype(F32).T
            logp = pallas_head.score_rows(
                h, w, targets, xs, bt, transposed=transposed,
                logits_scaling=logits_scaling, interpret=interpret)
        return logp[:t - 1], last_logits()

    block = min(block, t)
    n_blocks = -(-t // block)
    pad = n_blocks * block - t
    # the target of position t is token t+1; the last position has none
    targets = jnp.pad(tokens[1:], (0, pad + 1))
    hp = jnp.pad(h, ((0, pad), (0, 0)))

    def score(xs):
        h_b, tgt_b = xs
        with stage(HEAD), part(leaf):
            logits = scaled(project(h_b))
            lse = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(
                logits, tgt_b[:, None], axis=-1)[:, 0]
            return picked - lse

    logp = jax.lax.map(score, (
        hp.reshape(n_blocks, block, -1),
        targets.reshape(n_blocks, block)))
    last = last_logits()
    return logp.reshape(-1)[:t - 1], last


# --------------------------------------------------------------------------
# partition rules of the leaves the blocks above name
# --------------------------------------------------------------------------
# ``(regex, PartitionSpec)`` pairs, first match wins
# (parallel/mesh.py::match_partition_rules).  A model lists these and the
# rules of its own leaves in its declaration's ``partition_rules``, in the
# order IT needs; the engine tries a model's list before the general rules.

# A decoder's frame.  Projections are column- then row-parallel in pairs
# (q/k/v -> o, gate/up -> down), so one all-reduce closes each pair; the
# embedding goes by vocabulary row and an untied head by vocabulary column;
# the block norms replicate.
DECODER_PARTITION_RULES = (
    (r"embed/embedding$", P(MODEL_AXIS, None)),
    (r"attn/(q|k|v)$", P(None, MODEL_AXIS)),
    (r"attn/o$", P(MODEL_AXIS, None)),
    (r"mlp/(gate|up)$", P(None, MODEL_AXIS)),
    (r"mlp/down$", P(MODEL_AXIS, None)),
    (r"(norm[1-4]|final_norm)/scale$", P()),
    (r"head/kernel$", P(None, MODEL_AXIS)),
)

# The expert layer (:func:`routed_experts`).  The STACKED expert leaves
# ``[experts, m, n]`` shard their EXPERT axis: a device holds whole experts,
# as expert parallelism does.  The shared expert is a gated FFN like
# ``mlp``.  The one-matrix router and its selection bias replicate: every
# device routes every token.
EXPERT_PARTITION_RULES = (
    (r"experts/(gate|up|down)$", P(MODEL_AXIS, None, None)),
    (r"shared/(gate|up)$", P(None, MODEL_AXIS)),
    (r"shared/down$", P(MODEL_AXIS, None)),
    (r"moe/(router|router_bias)$", P()),
)
