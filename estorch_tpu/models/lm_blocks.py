"""The pieces both sequence models are built from (models/hybrid_lm.py,
models/looped_lm.py): ONE RMSNorm, ONE gated SiLU FFN, ONE causal
attention and ONE blocked next-token scorer, each on the perturbed-dense
primitive (models/perturbed.py), so that an optimisation of one is
measured on both models.

The attention's core (rotated q, k, v -> context) has TWO forms of one
algorithm, same mathematics, same tiles, same precision:

- ``"xla"``: block-causal einsums and a softmax, whose float32 score
  tiles XLA holds in HBM.  It runs anywhere: the CPU path, every test's
  oracle, and what a mesh of several devices takes.
- ``"kernel"``: ops/pallas_attention.py, an online softmax whose scores
  never leave VMEM.

Which one a program takes is not an option of a model or of ``ES``: the
ENGINE resolves it once at build from what it observes
(``ShardedESEngine.attention_form``, by the one rule
``ops.pallas_attention.attention_form``: TPU devices, ONE device on the
mesh so the operands are whole on it, ``head_dim % 128 == 0``, the
sequence a whole number of the kernel's blocks) and opens
``pallas_attention.kernel_scope`` around its trace of the policy.
:func:`causal_attention` takes the kernel inside that scope and the XLA
form everywhere else, so ``apply`` outside an engine is the XLA form.
``ES`` hands the engine the model's ``head_dim``, as it hands it
``leaf_rows``.

Functions, not a base class: a model hands in its own ``dense`` (the
``(p, noise, c, name, x) -> x @ (p[name] + c·noise[name])`` of the class,
which a subclass may replace) and its sizes.  What only one model has stays
with it: the Mamba-2 mixer there, the pass loop and the exit gate here.
Rotary positions are an optional argument of the one attention.

Precision as in the models' own text: matmul operands in the dtype of the
parameters handed in, float32 accumulation; norms, rotation, softmax and
log-softmax in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..obs.trace import ATTN, DENSE, HEAD, ROPE, stage
from ..ops import pallas_attention
from .perturbed import F32, perturbed_dense


def layer_name(i: int) -> str:
    return f"layer_{i:02d}"


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


def dense(p, noise, c, name, x):
    """float32 ``x @ (p[name] + c·noise[name])`` under ``es.dense``."""
    with stage(DENSE):
        return perturbed_dense(
            x, p[name], None if noise is None else noise[name], c)


def subtree(noise, *path):
    """``noise[path[0]][path[1]]…``, or ``None`` for the centre alone."""
    for k in path:
        if noise is None:
            return None
        noise = noise[k]
    return noise


def gated_mlp(dense, p, noise, c, u):
    """``down(silu(gate u) ⊙ up u)``, float32 out."""
    dtype = u.dtype
    gate = dense(p, noise, c, "gate", u)
    up = dense(p, noise, c, "up", u)
    with stage(DENSE):
        act = (jax.nn.silu(gate) * up).astype(dtype)
    return dense(p, noise, c, "down", act)


def rotary_tables(length: int, head_dim: int, theta: float):
    """``(cos, sin) [T, head_dim/2]`` float32 of positions ``0 … T-1``:
    ``inv_freq_i = theta^(-2i/head_dim)``."""
    with stage(ROPE):
        inv_freq = 1.0 / (theta ** (
            jnp.arange(0, head_dim, 2, dtype=F32) / head_dim))
        angle = jnp.arange(length, dtype=F32)[:, None] * inv_freq[None, :]
        return jnp.cos(angle), jnp.sin(angle)


def rotate(x, cos, sin):
    """Rotary embedding in the halves convention (``x·cos +
    rotate_half(x)·sin``) of ``x [T, heads, head_dim]`` float32."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def causal_attention(dense, p, noise, c, u, *, num_heads: int,
                     num_kv_heads: int, head_dim: int, scale: float,
                     block: int, rotary=None):
    """Causal attention with grouped heads, block-causal: query block
    ``i`` is scored against the keys ``[0, end of block i)`` and no
    others, so (n+1)/(2n) of the ``[T, T]`` score tiles of ``n`` blocks
    are computed, and a masked score (``exp(-inf) = 0``) exists only
    inside the diagonal tile.  ``rotary``: ``(cos, sin)`` of
    :func:`rotary_tables`, applied to queries and keys; ``None``: no
    positional encoding.

    Inside an engine's ``pallas_attention.kernel_scope`` the core is the
    Pallas kernel (its own blocks, scores in VMEM); anywhere else the XLA
    form below, in blocks of ``block`` (the module's text has the rule).
    The XLA form's loop over blocks is unrolled: the program grows with
    ``T / block``, so a much longer sequence should raise the block, not
    the count."""
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
    interpret = pallas_attention.scoped_interpret()
    if interpret is not None:
        with stage(ATTN):
            ctx = pallas_attention.causal_attention(
                q.reshape(t, nq * hd), k.reshape(t, nkv * hd), v,
                num_heads=nq, num_kv_heads=nkv, head_dim=hd, scale=scale,
                interpret=interpret)
        return dense(p, noise, c, "o", ctx)
    # query head j reads key/value head j // (nq / nkv)
    qh = q.reshape(t, nkv, nq // nkv, hd)
    kh, vh = k.reshape(t, nkv, hd), v.reshape(t, nkv, hd)
    block = min(block, t)
    ctx = []
    for start in range(0, t, block):
        stop = min(start + block, t)
        q_b = qh[start:stop]
        if ctx:
            # one block at a time: left free, the TPU scheduler runs
            # every block's softmax before the first P·V and holds all
            # their float32 scores at once (T²/2 of them)
            q_b, _ = jax.lax.optimization_barrier((q_b, ctx[-1]))
        with stage(ATTN):
            s = jnp.einsum("qkgd,skd->kgqs", q_b, kh[:stop],
                           preferred_element_type=F32) * scale
            mask = (jnp.arange(stop)[None, :]
                    <= jnp.arange(start, stop)[:, None])
            s = jnp.where(mask, s, -jnp.inf)
            prob = jax.nn.softmax(s, axis=-1).astype(dtype)
            ctx.append(jnp.einsum(
                "kgqs,skd->qkgd", prob, vh[:stop],
                preferred_element_type=F32).astype(dtype))
    ctx = jnp.concatenate(ctx).reshape(t, nq * hd)
    return dense(p, noise, c, "o", ctx)


def score_next_tokens(h, tokens, project, block: int, logits_scaling=None):
    """``(log p(tokens[t+1] | tokens[:t+1]) [T-1], the last position's
    logits [vocab])`` float32 from the hidden states ``h [T, hidden]``, in
    blocks of ``block`` positions so that the ``[T, vocab]`` logits never
    exist.  ``project(h_block)`` is the model's head matmul (tied or not),
    float32; the logits are divided by ``logits_scaling`` where the model
    has one."""

    def scaled(y):
        return y if logits_scaling is None else y / logits_scaling

    t = tokens.shape[0]
    block = min(block, t)
    n_blocks = -(-t // block)
    pad = n_blocks * block - t
    # the target of position t is token t+1; the last position has none
    targets = jnp.pad(tokens[1:], (0, pad + 1))
    hp = jnp.pad(h, ((0, pad), (0, 0)))

    def score(xs):
        h_b, tgt_b = xs
        with stage(HEAD):
            logits = scaled(project(h_b))
            lse = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(
                logits, tgt_b[:, None], axis=-1)[:, 0]
            return picked - lse

    logp = jax.lax.map(score, (
        hp.reshape(n_blocks, block, -1),
        targets.reshape(n_blocks, block)))
    with stage(HEAD):
        last = scaled(project(h[-1:])[0])
    return logp.reshape(-1)[:t - 1], last
