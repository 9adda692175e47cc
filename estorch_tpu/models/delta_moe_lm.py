"""A sparse-expert decoder whose token mixers are of two kinds, a LINEAR one
with a matrix state corrected by the delta rule and a gated full-attention
one, as an ES policy: Qwen3-Next (``config.json`` keys ``linear_num_key_heads``,
``linear_num_value_heads``, ``linear_key_head_dim``, ``linear_value_head_dim``,
``linear_conv_kernel_dim``, ``full_attention_interval``,
``partial_rotary_factor``, ``shared_expert_intermediate_size``).  Per token
sequence ``[T]``, with ``ZNorm(x; w) = x · rsqrt(mean x² + eps) · (1 + w)``
(a ZERO-CENTRED RMSNorm: ``lm_blocks.zero_centred_rmsnorm``):

    x = E[tokens]
    each layer:
      a = ZNorm(x; g1)
      a ``linear`` layer (Gated DeltaNet, arXiv 2412.06464; ``nk`` key heads,
      ``nv`` value heads of ``dk`` and ``dv``; value head j reads key head
      ``j // (nv / nk)``):
        [q | k | v | z] = a W_qkvz          hidden -> nk·dk + nk·dk + nv·dv + nv·dv
        [b | a'] = a W_ba                   hidden -> nv + nv
        [q | k | v] <- silu(causal depthwise conv, ``linear_conv_kernel_dim``
                            taps, no bias, over the channels of [q | k | v])
        beta_t = sigmoid(b_t);  g_t = -exp(A_log) · softplus(a'_t + dt_bias)
        q_t <- q_t / √(Σ q_t² + 1e-6) / √dk;  k_t <- k_t / √(Σ k_t² + 1e-6)
        S_0 = 0 [dk, dv];  S~ = exp(g_t) S_{t-1}
        S_t = S~ + k_t ⊗ (beta_t (v_t - S~ᵀ k_t));  o_t = S_tᵀ q_t
        y_t = RMSNorm_dv(o_t; w_n) ⊙ silu(z_t)      a value head; w_n NOT
                                                    zero-centred
        h = x + [y] W_o
      a ``full`` layer (``num_attention_heads`` over ``num_key_value_heads``
      heads of ``head_dim``):
        [q | gate] a head = a W_q           each head's first ``head_dim`` the
                                            query, its last the gate
        k, v = a W_k, a W_v
        q <- ZNorm(q; w_q), k <- ZNorm(k; w_k) a head; the first
            ``partial_rotary_factor · head_dim`` of each head rotated (halves)
        h = x + (softmax(q kᵀ / √head_dim, causal) v ⊙ sigmoid(gate)) W_o
      b = ZNorm(h; g2)
      p = softmax(b W_r) over ALL experts;  S = the ``num_experts_per_tok``
          largest;  w_e = p_e / Σ_{e' in S} p_e'           (``norm_topk_prob``)
      x = h + Σ_{e in S, e held here} w_e · FFN_e(b)
            + sigmoid(b w_s) · FFN_shared(b)               (SwiGLU both)
    h = ZNorm(x; g_final);  score_t = log p(tokens[t+1] | …) from h W_head
    behaviour = the head's logits averaged over the last
                ``behaviour_positions`` positions

The delta rule runs in its CHUNKED form (:func:`gated_delta_rule`): a
state ``S`` is not only written and decayed, as Mamba-2's is, but CORRECTED
by what it already holds for the key, so the rows of a chunk depend on each
other through a unit lower-triangular system.  Inside a chunk of
``delta_chunk`` positions, with ``γ`` the decay's running sum:
``A = tril₋(diag β (K Kᵀ ∘ e^{γ_i - γ_j}))``, ``T = (I + A)⁻¹``
(:func:`unit_lower_inverse`), ``W = T diag β (K ∘ e^γ)``, ``U = T diag β V``;
across chunks, with the incoming state ``S``: ``V' = U - W S``, ``O = (Q ∘
e^γ) S + (Q Kᵀ ∘ e^{γ_i - γ_j} ∘ [j ≤ i]) V'``, ``S' = e^{γ_C} S + (K ∘
e^{γ_C - γ})ᵀ V'``.  All of it float32, its products at ``HIGHEST``
precision, in two forms of the same terms: the kernels of
ops/pallas_delta.py (a chunk's system and the carried state in VMEM, ``q``,
``k``, ``v``, ``o`` left ``[T, heads · dim]``) inside an engine's
``pallas_attention.kernel_scope`` where the shapes fit (``pallas_delta.fits``:
heads of whole 128-lane blocks, a chunk of 16 to 128; which is in the run's
records, ``delta_form``, by ``pallas_delta.delta_facts`` in ``declaration()``),
and an XLA form (batched products over head-major chunks, ``lax.scan`` over
the chunks) everywhere else: the CPU, a call outside a scope, other shapes.
Nothing of ``HybridLM._ssd`` is shared but the idea of a chunk, whose ``C Bᵀ
∘ decay`` product has no counterpart here.

The expert layer is told which experts it holds, as ``MoELM``'s: the router
scores ``num_experts · expert_group_size`` experts, this program holds the
``num_experts`` of share ``expert_group_rank`` and leaves out what the others
would have added; the shared expert is whole on every share.  Routers,
``A_log`` and ``dt_bias`` stay float32 in the copy the forward reads
(``float32_leaves``).

Every size is a constructor argument under its published key; the published
values live in the benchmark's configuration file only.  ``layer_types`` is
derived there from ``full_attention_interval`` (``full`` where ``(l + 1) mod
interval = 0``).  Precision as ``lm_blocks`` states; the delta rule's gate,
decay, state and gated norm in float32.

As an ES policy the module maps ``tokens [T]`` to ``(score [T-1], the head's
logits averaged over the last ``behaviour_positions`` positions [vocab],
(token, k) pairs per held expert summed over the layers [held])``.  Left out:
the MTP module, biases, a rope scaling (``rope_scaling`` is null and anything
else is refused; ``lm_blocks.rotary_tables(scaling=)`` is where one lives), an
un-normalised top-k.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from ..obs.trace import ATTN, DENSE, HEAD, ROPE, SSM, part, stage
from ..ops import (pallas_attention, pallas_combine, pallas_delta,
                   pallas_head)
from . import lm_blocks
from .lm_blocks import layer_name, subtree, zero_centred_rmsnorm
from .perturbed import (F32, MODEL_AXIS, PolicyDeclaration, leaf_columns,
                        perturbed_dense, perturbed_embed, perturbed_leaf)

# How this model's leaves (``param_shapes``) are cut over a mesh's ``model``
# axis: the decoder's frame and the expert layer (models/lm_blocks.py: k, v,
# o, norms, router, shared and stacked experts, and the full layers' ``q``,
# each head's query and gate side by side), and the linear mixer's own.  Its
# fused ``[q | k | v | z]`` projection and the conv over ``[q | k | v]`` go
# by column (a layout, not a cut by head: GSPMD moves what the split into
# parts needs), closed by the row-parallel ``out_proj``; ``A_log`` and
# ``dt_bias`` by value head; the narrow ``[b | a]`` projection, the gated
# norm's one head of weights, the per-head norms of q and k and the shared
# expert's one-column gate replicate.  ``A_log``, ``dt_bias`` and
# ``norm_scale`` are named HERE because the general rules would cut them by
# their suffixes.
PARTITION_RULES = (
    lm_blocks.DECODER_PARTITION_RULES + lm_blocks.EXPERT_PARTITION_RULES + (
        (r"(q_norm|k_norm)/scale$", P()),
        (r"delta/in_proj_qkvz$", P(None, MODEL_AXIS)),
        (r"delta/conv$", P(None, None, MODEL_AXIS)),
        (r"delta/(A_log|dt_bias)$", P(MODEL_AXIS)),
        (r"delta/(in_proj_ba|norm_scale)$", P()),
        (r"delta/out_proj$", P(MODEL_AXIS, None)),
        (r"moe/shared_gate$", P()),
    ))

LINEAR_LAYER, FULL_LAYER = "linear", "full"
EXPERT_LEAVES = ("gate", "up", "down")
HIGHEST = jax.lax.Precision.HIGHEST
# the diagonal blocks of a chunk's triangular system that the finite
# product inverts, in both forms of the rule (ops/pallas_delta.py has why 8)
INVERSE_BASE = pallas_delta.INVERSE_BASE


def _mm(a, b):
    """Batched float32 ``a @ b`` at ``HIGHEST``."""
    return jnp.matmul(a, b, precision=HIGHEST, preferred_element_type=F32)


def _product_inverse(a):
    """``(I + A)⁻¹ = (I - A)(I + A²)(I + A⁴)…`` of strictly lower-triangular
    ``A [..., n, n]``: ``A`` is nilpotent, so the product is EXACT once the
    exponents reach ``n``."""
    n = a.shape[-1]
    power = -a
    inverse = jnp.eye(n, dtype=F32) + power
    reached = 2                 # exponents below this are in the product
    while reached < n:
        power = _mm(power, power)
        inverse = inverse + _mm(inverse, power)
        reached *= 2
    return inverse


def unit_lower_inverse(a, base: int = INVERSE_BASE):
    """``(I + A)⁻¹`` float32 of strictly lower-triangular ``A [..., n, n]``
    (what lies on or above the diagonal of ``a`` must be 0).  Diagonal
    blocks of ``base`` rows by the finite product, then pairs of inverted
    blocks merged, level by level: ``[[M₁, 0], [B, M₂]]⁻¹ = [[M₁⁻¹, 0],
    [-M₂⁻¹ B M₁⁻¹, M₂⁻¹]]``.  ``n`` that is not ``base · 2^k`` takes the
    product whole."""
    n = a.shape[-1]
    blocks = n // base
    if n <= base or n % base or blocks & (blocks - 1):
        return _product_inverse(a)

    def blocks_of(size, row, col):
        """``[..., pairs, size, size]``: block ``(2p + row, 2p + col)`` of
        ``a`` cut into ``size``-row blocks, for every pair ``p`` (plain
        slices, stacked: a strided diagonal of the reshaped array trips
        XLA's algebraic simplifier under a ``vmap``)."""
        return jnp.stack([
            a[..., (p + row) * size:(p + row + 1) * size,
              (p + col) * size:(p + col + 1) * size]
            for p in range(0, n // size, 2)], axis=-3)

    inverse = _product_inverse(jnp.stack([
        a[..., i * base:(i + 1) * base, i * base:(i + 1) * base]
        for i in range(blocks)], axis=-3))
    size = base
    while size < n:
        first, second = inverse[..., 0::2, :, :], inverse[..., 1::2, :, :]
        below = -_mm(_mm(second, blocks_of(size, 1, 0)), first)
        inverse = jnp.concatenate([
            jnp.concatenate([first, jnp.zeros_like(first)], axis=-1),
            jnp.concatenate([below, second], axis=-1)], axis=-2)
        size *= 2
    return inverse[..., 0, :, :]


def gated_delta_rule(q, k, v, g, beta, chunk: int):
    """``o [T, nv, dv]`` float32 of the gated delta rule ``S~ = e^{g_t}
    S_{t-1}``, ``S_t = S~ + k_t ⊗ (β_t (v_t - S~ᵀ k_t))``, ``o_t = S_tᵀ
    q_t`` from ``S_0 = 0``, in chunks of ``chunk`` positions (the module's
    text has the algebra).  ``q, k [T, nk, dk]`` (normalised, ``q`` scaled),
    ``v [T, nv, dv]``, ``g [T, nv]`` (``<= 0``), ``beta [T, nv]``, all
    float32; value head ``j`` reads key head ``j // (nv / nk)``.  The decay
    enters only as ``e^{γ_i - γ_j}`` with ``i >= j``, ``e^γ`` and ``e^{γ_C
    - γ}``: nothing grows.  The padding of a last short chunk has ``β = 0``,
    ``g = 0`` and ``k = 0``: it writes nothing and the state passes
    through.  Parts of ``es.ssm``: ``of.solve`` (``K Kᵀ``, the triangular
    inverse, ``W``, ``U``) and ``of.carry`` (the chain over the chunks:
    ``V'``, ``O``, ``S'``).  Inside a kernel scope, where the shapes fit,
    the kernels of ops/pallas_delta.py compute the same terms
    (:func:`_rule_in_kernels`); this XLA form anywhere else."""
    t, nk, dk = q.shape
    nv, dv = v.shape[1:]
    interpret = pallas_attention.scoped_interpret()
    if interpret is not None and pallas_delta.fits(dk, dv, chunk, t):
        return _rule_in_kernels(q, k, v, g, beta, chunk, interpret)
    rep = nv // nk
    length = min(chunk, t)
    n = -(-t // length)
    pad = n * length - t

    def chunked(x, *heads):
        """``[n, nk(, rep), length, …]``: head-major inside a chunk."""
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        x = x.reshape(n, length, *heads, *x.shape[2:])
        return jnp.moveaxis(x, 1, len(heads) + 1)

    qc, kc = chunked(q, nk), chunked(k, nk)             # [n, nk, L, dk]
    vc = chunked(v, nk, rep)                            # [n, nk, rep, L, dv]
    gc, bc = chunked(g, nk, rep), chunked(beta, nk, rep)    # [n, nk, rep, L]
    causal = jnp.tril(jnp.ones((length, length), bool))
    with part("solve"):
        gamma = jnp.cumsum(gc, axis=-1)
        grown = jnp.exp(gamma)                          # e^γ, <= 1
        seg = gamma[..., :, None] - gamma[..., None, :]
        decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
        kk = _mm(kc, jnp.swapaxes(kc, -1, -2))          # [n, nk, L, L]
        a = jnp.where(jnp.tril(causal, -1),
                      bc[..., :, None] * kk[:, :, None] * decay, 0.0)
        inverse = unit_lower_inverse(a)                 # [n, nk, rep, L, L]
        k_in = kc[:, :, None] * (bc * grown)[..., None]
        w = _mm(inverse, k_in)                          # [n, nk, rep, L, dk]
        u = _mm(inverse, vc * bc[..., None])            # [n, nk, rep, L, dv]
    with part("carry"):
        total = gamma[..., -1]                          # [n, nk, rep]
        q_in = qc[:, :, None] * grown[..., None]
        k_out = kc[:, :, None] * jnp.exp(
            total[..., None] - gamma)[..., None]
        qk = _mm(qc, jnp.swapaxes(kc, -1, -2))          # [n, nk, L, L]
        inside = qk[:, :, None] * decay                 # j <= i only

        def one_chunk(state, xs):
            w_n, u_n, q_n, k_n, total_n = xs
            fresh = u_n - _mm(w_n, state)               # V'
            out = _mm(q_n, state)
            state = (jnp.exp(total_n)[..., None, None] * state
                     + _mm(jnp.swapaxes(k_n, -1, -2), fresh))
            return state, (fresh, out)

        _, (fresh, out) = jax.lax.scan(
            one_chunk, jnp.zeros((nk, rep, dk, dv), F32),
            (w, u, q_in, k_out, total))
        out = out + _mm(inside, fresh)                  # [n, nk, rep, L, dv]
    out = jnp.moveaxis(out, 3, 1).reshape(n * length, nv, dv)
    return out[:t]


def _rule_in_kernels(q, k, v, g, beta, chunk: int, interpret: bool):
    """:func:`gated_delta_rule` in the kernels of ops/pallas_delta.py: the
    same terms a tile at a time, ``K Kᵀ``, the decay tile, ``A``, the
    inverse and the state in VMEM; ``q``, ``k``, ``v`` and ``o`` stay
    ``[T, heads · dim]`` (no head-major copy).  Every operation is in one of
    the rule's two parts."""
    t, nv, dv = v.shape
    with part("solve"):
        rows = pallas_delta.decay_rows(g, beta, q.shape[1], chunk)
        w, u = pallas_delta.solve_chunks(k, v, rows, chunk=chunk,
                                         interpret=interpret)
    with part("carry"):
        out = pallas_delta.chain_chunks(q, k, w, u, rows, chunk=chunk,
                                        interpret=interpret)
        return out[:t].reshape(t, nv, dv)


@dataclasses.dataclass(frozen=True)
class DeltaMoELM:
    layer_types: Sequence[str]
    vocab_size: int
    hidden_size: int
    moe_intermediate_size: int
    shared_expert_intermediate_size: int
    linear_num_key_heads: int = 2
    linear_num_value_heads: int = 4
    linear_key_head_dim: int = 8
    linear_value_head_dim: int = 8
    linear_conv_kernel_dim: int = 4
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 8
    partial_rotary_factor: float = 0.25
    num_experts: int = 8               # held HERE
    expert_group_size: int = 1         # chips that share a layer's experts
    expert_group_rank: int = 0         # which share this program holds
    num_experts_per_tok: int = 2
    norm_topk_prob: bool = True
    behaviour_positions: int = 512
    rope_theta: float = 10000.0
    rope_scaling: Any = None
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    init_std: float = 0.02
    attention_block: int = 512
    head_block: int = 512
    delta_chunk: int = 64

    is_recurrent = False
    use_vbn = False

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        bad = set(self.layer_types) - {LINEAR_LAYER, FULL_LAYER}
        if bad or not self.layer_types:
            raise ValueError(
                f"layer_types holds {sorted(bad)}; a layer is "
                f"{LINEAR_LAYER!r} (the gated delta rule) or {FULL_LAYER!r} "
                "(gated softmax attention)")
        lm_blocks.refuse_unwritten(self, {
            "norm_topk_prob": True, "rope_scaling": None,
            "tie_word_embeddings": False})
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of key heads")
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError("value heads of the linear layers must be a "
                             "multiple of their key heads")
        if self.rotary_dim < 2 or self.rotary_dim % 2 or (
                self.rotary_dim > self.head_dim):
            raise ValueError(
                f"partial_rotary_factor {self.partial_rotary_factor} of "
                f"head_dim {self.head_dim} is {self.rotary_dim}: the "
                "rotation turns pairs inside the head")
        if self.linear_conv_kernel_dim < 1 or self.delta_chunk < 1:
            raise ValueError("linear_conv_kernel_dim and delta_chunk must "
                             "be >= 1")
        if not 0 <= self.expert_group_rank < self.expert_group_size:
            raise ValueError(
                f"expert_group_rank {self.expert_group_rank} is not one of "
                f"the {self.expert_group_size} shares")
        if self.num_experts_per_tok > self.experts_total:
            raise ValueError("more experts per token than experts")
        if self.behaviour_positions < 1:
            raise ValueError("behaviour_positions must be >= 1, got "
                             f"{self.behaviour_positions}")

    # ------------------------------------------------------------ sizes

    @property
    def experts_total(self) -> int:
        """Experts the router scores: every share's."""
        return self.num_experts * self.expert_group_size

    @property
    def first_expert_held(self) -> int:
        return self.num_experts * self.expert_group_rank

    @property
    def rotary_dim(self) -> int:
        """The leading part of a head that turns."""
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    def _layer_shapes(self, kind: str) -> dict:
        h, d = self.hidden_size, self.head_dim
        nq, nkv = self.num_attention_heads, self.num_key_value_heads
        e, w = self.num_experts, self.moe_intermediate_size
        ws, nv = self.shared_expert_intermediate_size, (
            self.linear_num_value_heads)
        conv = 2 * self.key_dim + self.value_dim
        mixer = {
            "delta": {"in_proj_qkvz": (h, conv + self.value_dim),
                      "in_proj_ba": (h, 2 * nv),
                      "conv": (self.linear_conv_kernel_dim, 1, conv),
                      "A_log": (nv,), "dt_bias": (nv,),
                      "norm_scale": (self.linear_value_head_dim,),
                      "out_proj": (self.value_dim, h)}
        } if kind == LINEAR_LAYER else {
            "attn": {"q": (h, nq * 2 * d), "k": (h, nkv * d),
                     "v": (h, nkv * d), "o": (nq * d, h),
                     "q_norm": {"scale": (d,)}, "k_norm": {"scale": (d,)}}}
        return {
            "norm1": {"scale": (h,)}, "norm2": {"scale": (h,)}, **mixer,
            "moe": {"router": (h, self.experts_total),
                    "shared": {"gate": (h, ws), "up": (h, ws),
                               "down": (ws, h)},
                    "shared_gate": (h, 1),
                    "experts": {"gate": (e, h, w), "up": (e, h, w),
                                "down": (e, w, h)}}}

    def param_shapes(self) -> dict:
        """The parameter tree as shapes (float32)."""
        h = self.hidden_size
        tree: dict[str, Any] = {
            "embed": {"embedding": (self.vocab_size, h)},
            "head": {"kernel": (h, self.vocab_size)},
            "final_norm": {"scale": (h,)}}
        for i, kind in enumerate(self.layer_types):
            tree[layer_name(i)] = self._layer_shapes(kind)
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s, F32), tree,
            is_leaf=lambda s: isinstance(s, tuple))

    def _layers(self) -> list[str]:
        return [layer_name(i) for i in range(len(self.layer_types))]

    @property
    def stacked_leaves(self) -> tuple:
        """The leaves whose leading axis indexes experts: one factor pair
        per expert (ops/lowrank.py)."""
        return tuple(f"{p}/moe/experts/{n}" for p in self._layers()
                     for n in EXPERT_LEAVES)

    @property
    def float32_leaves(self) -> tuple:
        """Leaves the forward reads in float32 whatever the compute dtype:
        the routers (a discrete choice) and what the decay is made of
        (``e^{-exp(A_log) · softplus(· + dt_bias)}`` over thousands of
        positions)."""
        return tuple(
            [f"{p}/moe/router" for p in self._layers()]
            + [f"{p}/delta/{n}" for p, kind in zip(self._layers(),
                                                   self.layer_types)
               if kind == LINEAR_LAYER for n in ("A_log", "dt_bias")])

    def declaration(self) -> PolicyDeclaration:
        """What the engine that runs this model and the run's records read
        of it, stated once (models/perturbed.py::PolicyDeclaration)."""
        rows = (self.num_experts_per_tok * lm_blocks.EXPERT_CAPACITY_MARGIN
                / self.expert_group_size)
        full = FULL_LAYER in self.layer_types
        return PolicyDeclaration(
            partition_rules=PARTITION_RULES,
            kernels=(
                # the full layers' heads, scored and summed at one width
                *([(pallas_attention.attention_facts,
                    (self.head_dim, self.num_key_value_heads, None,
                     self.num_attention_heads))]
                  if full else []),
                (pallas_head.head_facts, (self.hidden_size,)),
                (pallas_combine.combine_facts, (self.hidden_size,)),
                # a key head's width, a value head's, the chunk
                *([(pallas_delta.delta_facts,
                    (self.linear_key_head_dim, self.linear_value_head_dim,
                     self.delta_chunk))]
                  if LINEAR_LAYER in self.layer_types else [])),
            leaf_rows={"head/kernel": self.head_block},
            leaf_rows_per_token=dict.fromkeys(self.stacked_leaves, rows),
            stacked_leaves=self.stacked_leaves,
            float32_leaves=self.float32_leaves,
            outputs=("expert_load",),
            facts={"experts_held": self.num_experts,
                   "experts_total": self.experts_total,
                   "experts_per_token": self.num_experts_per_tok,
                   "mtp_depth": 0,
                   "linear_layers": self.layer_types.count(LINEAR_LAYER),
                   "full_layers": self.layer_types.count(FULL_LAYER),
                   "delta_chunk": self.delta_chunk,
                   "delta_inverse": (
                       f"blocks of {INVERSE_BASE} by the finite product "
                       "(I - A)(I + A^2)(I + A^4)..., merged in pairs")})

    # ------------------------------------------------------------- init

    def init(self, key, tokens=None) -> dict:
        """``{"params": tree}``, drawn in ONE jitted program: matrices, conv
        taps and embedding normal ``init_std``; the zero-centred norm
        weights 0 and the gated norm's 1; ``A_log`` and ``dt_bias`` so that
        a step's decay ``e^g`` lies between 0.9 and 0.999, log-uniform in
        ``1 - e^g`` over the heads (under the released code's ``A ~ U(0,
        16)``, ``dt_bias`` 1 most heads forget within a step)."""
        del tokens  # flax's signature; the shapes come from the sizes
        return {"params": jax.jit(self._draw)(key)}

    def _draw(self, key):
        def value_of(name, k, shape):
            if name in ("scale", "A_log"):
                return jnp.zeros(shape, F32)
            if name == "norm_scale":
                return jnp.ones(shape, F32)
            if name == "dt_bias":
                rate = jnp.exp(jax.random.uniform(
                    k, shape, F32, math.log(1e-3), math.log(1e-1)))
                return rate + jnp.log(-jnp.expm1(-rate))
            return self.init_std * jax.random.normal(k, shape, F32)

        return lm_blocks.draw_tree(self.param_shapes(), key, value_of)

    # ------------------------------------------------------------ apply

    def apply(self, variables, tokens):
        """flax's calling convention: ``apply({"params": p}, tokens)`` is
        the policy output of the centre."""
        return self.perturbed_apply(variables["params"], None, 0.0, tokens)

    def perturbed_apply(self, params, noise, c, tokens):
        """The policy output of ``params + c·noise`` for one sequence
        ``tokens [T]``: ``(log p(tokens[t+1] | …) [T-1], the head's logits
        averaged over the last ``behaviour_positions`` positions [vocab],
        pairs per held expert [held])``."""
        t = tokens.shape[0]
        dtype = params["embed"]["embedding"].dtype
        rotary = (lm_blocks.rotary_tables(t, self.rotary_dim, self.rope_theta)
                  if FULL_LAYER in self.layer_types else None)
        kernel, k_noise = params["head"]["kernel"], subtree(
            noise, "head", "kernel")

        x = perturbed_embed(tokens, params["embed"]["embedding"],
                            subtree(noise, "embed", "embedding"), c)
        load = jnp.zeros((self.num_experts,), jnp.int32)
        for name, kind in zip(self._layers(), self.layer_types):
            x, n_pairs = self._layer(params[name], subtree(noise, name), c,
                                     x, kind, rotary, dtype)
            load = load + n_pairs
        h = self._norm(params, noise, c, "final_norm", x).astype(dtype)
        score, _ = lm_blocks.score_next_tokens(
            h, tokens, kernel, k_noise, c, self.head_block, leaf="head")
        with stage(HEAD), part("head"):
            last = jnp.mean(perturbed_dense(
                h[-self.behaviour_positions:], kernel, k_noise, c), axis=0)
        return score, last, load

    # ----------------------------------------------------------- layers

    # a subclass that replaces ``_dense`` changes every 2-D projection of
    # both mixers and of the shared expert

    @staticmethod
    def _dense(p, noise, c, name, x):
        return lm_blocks.dense(p, noise, c, name, x)

    def _columns(self, p, noise, c, name, x, groups, cols):
        """``_dense`` with the leaf ``name`` cut to the columns ``cols`` of
        each of its ``groups``: a projection whose output is split before
        its readers leaves its matmul a part at a time
        (``perturbed.leaf_columns``)."""
        w, w_noise = leaf_columns(p[name], subtree(noise, name), groups, cols)
        return self._dense({name: w},
                           None if noise is None else {name: w_noise}, c,
                           name, x)

    def _norm(self, p, noise, c, name, y):
        """float32 zero-centred RMSNorm of ``y`` over its last axis by the
        perturbed ``p[name]["scale"]``."""
        return zero_centred_rmsnorm(y, perturbed_leaf(
            p[name]["scale"], subtree(noise, name, "scale"), c),
            self.rms_norm_eps)

    def _layer(self, p, noise, c, x, kind, rotary, dtype):
        """One decoder layer: ``(x + mixer + the shared and the held
        experts' part, pairs per held expert)``."""
        a = self._norm(p, noise, c, "norm1", x).astype(dtype)
        if kind == LINEAR_LAYER:
            x = x + self._delta(p["delta"], subtree(noise, "delta"), c, a)
        else:
            x = x + self._attention(p["attn"], subtree(noise, "attn"), c, a,
                                    rotary)
        b = self._norm(p, noise, c, "norm2", x)
        moe, m_noise = p["moe"], subtree(noise, "moe")
        routed, load = self._routed(moe, m_noise, c, b, dtype)
        shared = self._shared(moe, m_noise, c, b.astype(dtype))
        return x + shared + routed, load

    def _routed(self, moe, noise, c, b, dtype):
        """``(the held experts' part of the float32 ``b``, pairs per held
        expert)``: routed over ALL experts from the state the experts
        read."""
        return lm_blocks.routed_ffn(
            moe, noise, c, b, dtype, top_k=self.num_experts_per_tok,
            scaling=1.0, scoring="softmax",
            first_held=self.first_expert_held, total=self.experts_total)

    def _shared(self, moe, noise, c, u):
        """The shared expert, whole on every share, scaled a token by
        ``sigmoid(u w_s)``."""
        with part("shared"):    # its leaves read ``shared.gate`` … in a trace
            y = lm_blocks.gated_mlp(self._dense, moe["shared"],
                                    subtree(noise, "shared"), c, u)
        opened = self._dense(moe, noise, c, "shared_gate", u)
        with stage(DENSE), part("shared_gate"):
            return self._shared_scale(opened) * y

    @staticmethod
    def _shared_scale(opened):
        return jax.nn.sigmoid(opened)

    # ------------------------------------------------- the linear mixer

    def _delta(self, p, noise, c, u):
        """Gated DeltaNet of ``u [T, hidden]`` (compute dtype)."""
        dtype, t = u.dtype, u.shape[0]
        nk, nv = self.linear_num_key_heads, self.linear_num_value_heads
        dk, dv = self.linear_key_head_dim, self.linear_value_head_dim
        conv_width = 2 * self.key_dim + self.value_dim
        qkv = self._columns(p, noise, c, "in_proj_qkvz", u, 1,
                            slice(0, conv_width))
        z = self._columns(p, noise, c, "in_proj_qkvz", u, 1,
                          slice(conv_width, None))
        ba = self._dense(p, noise, c, "in_proj_ba", u)

        def leaf(name):
            return perturbed_leaf(p[name], subtree(noise, name), c)

        with stage(SSM):
            with part("conv"):
                qkv = self._conv(qkv, leaf("conv"))
            with part("decay"):
                beta = jax.nn.sigmoid(ba[:, :nv])
                g = self._decay(ba[:, nv:], leaf("A_log"), leaf("dt_bias"))
                q = self._unit(qkv[:, :self.key_dim].reshape(t, nk, dk)) * (
                    1.0 / math.sqrt(dk))
                k = self._unit(
                    qkv[:, self.key_dim:2 * self.key_dim].reshape(t, nk, dk))
                v = qkv[:, 2 * self.key_dim:].reshape(t, nv, dv)
            o = self._rule(q, k, v, g, beta)
            with part("gate"):
                y = self._gated_norm(o, leaf("norm_scale"),
                                     z.reshape(t, nv, dv))
        return self._dense(p, noise, c, "out_proj",
                           y.reshape(t, nv * dv).astype(dtype))

    @staticmethod
    def _conv(qkv, taps):
        """``silu`` of the causal depthwise conv over time, no bias."""
        return jax.nn.silu(lm_blocks.causal_conv(qkv, taps, 0.0))

    @staticmethod
    def _decay(a, a_log, dt_bias):
        """``g = -exp(A_log) · softplus(a' + dt_bias)`` a value head."""
        return -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)

    @staticmethod
    def _unit(x):
        """``x / √(Σ x² + 1e-6)`` over the last axis: a head's L2 norm."""
        return x * jax.lax.rsqrt(
            jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    def _rule(self, q, k, v, g, beta):
        return gated_delta_rule(q, k, v, g, beta, self.delta_chunk)

    def _gated_norm(self, o, scale, z):
        """``RMSNorm(o; scale) ⊙ silu(z)`` a value head, float32."""
        return lm_blocks.rmsnorm(o, scale, self.rms_norm_eps) * jax.nn.silu(z)

    # --------------------------------------------------- the full mixer

    def _attention(self, p, noise, c, u, rotary):
        """Gated grouped-query attention of ``u [T, hidden]`` (compute
        dtype): q/k norms a head, the leading ``rotary_dim`` of a head
        turned, the context times ``sigmoid`` of the gate the query
        projection wrote beside each head's query."""
        dtype, t = u.dtype, u.shape[0]
        nq, nkv, d = (self.num_attention_heads, self.num_key_value_heads,
                      self.head_dim)

        def head_part(y, name, heads):
            y = self._norm(p, noise, c, name, y.reshape(t, heads, d))
            with stage(ROPE):
                return lm_blocks.rotate(
                    y, *rotary, rotary_dim=self.rotary_dim).astype(dtype)

        q = head_part(self._columns(p, noise, c, "q", u, nq, slice(0, d)),
                      "q_norm", nq)
        gate = self._columns(p, noise, c, "q", u, nq, slice(d, None))
        k = head_part(self._dense(p, noise, c, "k", u), "k_norm", nkv)
        v = self._dense(p, noise, c, "v", u).astype(dtype)
        with stage(ATTN):
            ctx = lm_blocks.attention_core(
                q, k, v, num_heads=nq, num_kv_heads=nkv,
                scale=1.0 / math.sqrt(d), block=self.attention_block)
        with stage(DENSE), part("o"):   # the operand ``o`` multiplies
            ctx = (ctx.astype(F32) * self._output_gate(gate)).astype(dtype)
        return self._dense(p, noise, c, "o", ctx)

    @staticmethod
    def _output_gate(gate):
        return jax.nn.sigmoid(gate)
