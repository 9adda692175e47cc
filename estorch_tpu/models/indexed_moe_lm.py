"""A sparse-expert decoder whose attention reads a LEARNED SELECTION of keys,
as an ES policy: grouped-query attention under DeepSeek-V3.2's sparse
attention indexer (the ``sa_config`` keys of Keye-VL-2.0's ``config.json``,
``model_type`` ``KeyeVL2``), Qwen3-MoE's expert layer (softmax router, no
shared expert) and three position streams (M-RoPE).  Per token sequence
``[T]`` (``u`` the normed residual):

    x = E[tokens]
    each layer:   x += attn(rmsnorm₁ x);   x += moe(rmsnorm₂ x)
    attn(u):  q = u W_q -> [T, heads, d];  k = u W_k, v = u W_v -> [T, kv heads, d]
              q <- rmsnorm(q; γ_q), k <- rmsnorm(k; γ_k)    per head, over its d
              q, k rotated (halves convention), d/2 frequency pairs
              inv_freq_i = θ^(-2i/d); pair i turns by the position stream
              its section names (``mrope_section``: temporal, height,
              width); for text the three streams are the token's index
      indexer: qI = u W_qI -> [T, index heads, dI];  kI = layernorm(u W_kI) -> [T, dI]
               (ONE key head);  w = u W_w -> [T, index heads]
               qI, kI rotated over their dI by the temporal stream
               I[t, s] = Σ_j w[t, j] · relu(qI[t, j] · kI[s])     for s <= t
      select:  S_t = the min(t + 1, topk) keys s <= t of largest I[t, s],
               ties to the lower s (the same S_t for every head; for
               t < topk every visible key)
      score_h[t, s] = q_h[t] · k_{h // group}[s] / √d  for s in S_t, -inf elsewhere
      P = softmax_s;  ctx = P v;  out = ctx W_o
    moe(u):   p = softmax(u W_r) over ALL experts, float32
              idx = the ``num_experts_per_tok`` largest (ties to the lower index)
              g = p[idx] / Σ p[idx]                           (``norm_topk_prob``)
              y = Σ_{k: idx_k held here} g_k · expert_{idx_k}(u)   (gated SiLU)
              no shared expert, no selection bias
    h = rmsnorm_final(x);  score_t = log p(tokens[t+1] | …) from h W_head (untied)
    behaviour = the head's logits averaged over the last
                ``behaviour_positions`` positions

The selection is a third kind of mask, made from the data and not from
positions: ``[T, T]``, different for every member, layer and sequence
(``lm_blocks.select_keys``: int8, what both forms of
``lm_blocks.attention_core`` read).  The expert layer is told which
experts it holds, as ``MoELM``'s: the router scores ``num_experts ·
expert_group_size`` experts, this program holds the ``num_experts`` of
share ``expert_group_rank`` and leaves out what the others would have
added.  The routers, the indexer's LayerNorm and ``W_w`` stay float32 in
the copy the forward reads (``float32_leaves``): each decides a discrete
choice.

Every size is a constructor argument under its published key (the
``sa_config`` keys and ``mrope_section`` flat); the published values live
in the benchmark's configuration file only.  Precision as ``lm_blocks``
states: matmul operands in the dtype of the parameters handed in, float32
accumulation; residual stream, norms, rotation, softmax, the router, ``w``
and log-softmax in float32.

As an ES policy the module maps ``tokens [T]`` (and optional position ids
``[3, T]``; ``TokenScoreEnv`` gives none and the three streams are the
index) to ``(score [T-1], the head's logits averaged over the last
``behaviour_positions`` positions [vocab], (token, k) pairs per held expert
summed over the layers [held], (query, key) pairs selected summed over the
layers)``; ``TokenScoreEnv`` scores the first two, the engine sums the
others into its records.  The mean over positions is ``MoELM``'s, for its
reason: a token's choice of experts is discrete.  Left out: the vision
tower beside the language model, a shared expert, more than one indexer
key head, un-normalised routing weights, dense layers (``mlp_only_layers``
is empty).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from ..obs.trace import ATTN, HEAD, INDEX, ROPE, part, stage
from ..ops import pallas_attention, pallas_combine, pallas_head
from . import lm_blocks
from .lm_blocks import layer_name, rmsnorm, subtree
from .perturbed import (F32, MODEL_AXIS, PolicyDeclaration, perturbed_dense,
                        perturbed_embed, perturbed_leaf)

# How this model's leaves (``param_shapes``) are cut over a mesh's ``model``
# axis: the decoder's frame and the expert layer (models/lm_blocks.py), and
# the indexer's own.  Its query projection goes by index head
# (column-parallel); its ONE key head, that key's LayerNorm and the per-head
# weights ``index_w`` (16 columns) are read whole by every index head and
# replicate, as the per-head norms of q and k do.
PARTITION_RULES = (
    lm_blocks.DECODER_PARTITION_RULES + lm_blocks.EXPERT_PARTITION_RULES + (
        (r"(q_norm|k_norm)/scale$", P()),
        (r"indexer/index_q$", P(None, MODEL_AXIS)),
        (r"indexer/(index_k|index_w)$", P()),
        (r"indexer/index_norm/(scale|bias)$", P()),
    ))

MOE_LAYER = "moe"
EXPERT_LEAVES = ("gate", "up", "down")


@dataclasses.dataclass(frozen=True)
class IndexedMoELM:
    layer_types: Sequence[str]
    vocab_size: int
    hidden_size: int
    moe_intermediate_size: int
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 8
    num_experts: int = 8               # held HERE
    expert_group_size: int = 1         # chips that share a layer's experts
    expert_group_rank: int = 0         # which share this program holds
    num_experts_per_tok: int = 2
    norm_topk_prob: bool = True
    n_shared_experts: int = 0
    indexer_num_heads: int = 2
    indexer_head_dim: int = 8
    indexer_num_kv_heads: int = 1
    topk: int = 8
    mrope_section: Sequence[int] = (2, 1, 1)
    behaviour_positions: int = 512
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    init_std: float = 0.02
    attention_block: int = 512
    index_block: int = 512
    head_block: int = 512

    is_recurrent = False
    use_vbn = False

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        object.__setattr__(self, "mrope_section", tuple(self.mrope_section))
        bad = set(self.layer_types) - {MOE_LAYER}
        if bad or not self.layer_types:
            raise ValueError(f"layer_types holds {sorted(bad)}; every layer "
                             f"is {MOE_LAYER!r} (mlp_only_layers is empty)")
        lm_blocks.refuse_unwritten(self, {
            "indexer_num_kv_heads": 1, "n_shared_experts": 0,
            "norm_topk_prob": True, "attention_bias": False,
            "tie_word_embeddings": False})
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of key heads")
        if sum(self.mrope_section) * 2 != self.head_dim:
            raise ValueError(
                f"mrope_section {self.mrope_section} must add up to the "
                f"{self.head_dim // 2} frequency pairs of a head")
        if self.indexer_head_dim % 2:
            raise ValueError(f"indexer_head_dim {self.indexer_head_dim} "
                             "must be even: the rotation turns pairs")
        if self.topk < 1:
            raise ValueError(f"topk must be >= 1, got {self.topk}")
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

    def selection_bytes(self, length: int) -> int:
        """Bytes of the selection's temporaries ONE member holds over a
        sequence of ``length`` positions (parallel/sharded.py's chunk rule
        reads it): the int8 ``[T, T]`` selection of a layer and the float32
        index scores of the last block of queries, ``[index heads, block,
        T]``."""
        return length * length + 4 * self.indexer_num_heads * min(
            self.index_block, length) * length

    def _layer_shapes(self) -> dict:
        h, d = self.hidden_size, self.head_dim
        nq, nkv = self.num_attention_heads, self.num_key_value_heads
        hi, di = self.indexer_num_heads, self.indexer_head_dim
        e, w = self.num_experts, self.moe_intermediate_size
        return {
            "norm1": {"scale": (h,)}, "norm2": {"scale": (h,)},
            "attn": {"q": (h, nq * d), "k": (h, nkv * d), "v": (h, nkv * d),
                     "o": (nq * d, h), "q_norm": {"scale": (d,)},
                     "k_norm": {"scale": (d,)}},
            "indexer": {"index_q": (h, hi * di), "index_k": (h, di),
                        "index_norm": {"scale": (di,), "bias": (di,)},
                        "index_w": (h, hi)},
            "moe": {"router": (h, self.experts_total),
                    "experts": {"gate": (e, h, w), "up": (e, h, w),
                                "down": (e, w, h)}}}

    def param_shapes(self) -> dict:
        """The parameter tree as shapes (float32)."""
        h = self.hidden_size
        tree: dict[str, Any] = {
            "embed": {"embedding": (self.vocab_size, h)},
            "head": {"kernel": (h, self.vocab_size)},
            "final_norm": {"scale": (h,)}}
        for i in range(len(self.layer_types)):
            tree[layer_name(i)] = self._layer_shapes()
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
        the routers, and of the indexer its key's LayerNorm and ``W_w``."""
        return tuple(f"{p}/{n}" for p in self._layers() for n in (
            "moe/router", "indexer/index_norm/scale",
            "indexer/index_norm/bias", "indexer/index_w"))

    def declaration(self) -> PolicyDeclaration:
        """What the engine that runs this model and the run's records read
        of it, stated once (models/perturbed.py::PolicyDeclaration)."""
        # rows a stacked expert leaf is applied to per position: the (token,
        # k) pairs routed to the held experts, with the layer's margin
        rows = (self.num_experts_per_tok * lm_blocks.EXPERT_CAPACITY_MARGIN
                / self.expert_group_size)
        return PolicyDeclaration(
            partition_rules=PARTITION_RULES,
            kernels=(
                # heads scored and summed at one width; one kind of
                # attention layer, over a selection of keys, no band
                (pallas_attention.attention_facts,
                 (self.head_dim, self.num_key_value_heads,
                  (("selected", None),), self.num_attention_heads)),
                (pallas_head.head_facts, (self.hidden_size,)),
                # the token rows the expert layer's combine adds into
                (pallas_combine.combine_facts, (self.hidden_size,))),
            # the head runs in blocks of ``head_block`` positions
            leaf_rows={"head/kernel": self.head_block},
            leaf_rows_per_token=dict.fromkeys(self.stacked_leaves, rows),
            stacked_leaves=self.stacked_leaves,
            float32_leaves=self.float32_leaves,
            selection_bytes=self.selection_bytes,
            # after what the env scores: the pairs per held expert, then
            # the (query, key) pairs selected
            outputs=("expert_load", "selected_pairs"),
            # the sparse-expert facts under MoELM's names (no MTP module)
            facts={"experts_held": self.num_experts,
                   "experts_total": self.experts_total,
                   "experts_per_token": self.num_experts_per_tok,
                   "mtp_depth": 0,
                   "sparse_topk": self.topk,
                   "index_heads": self.indexer_num_heads,
                   "index_head_dim": self.indexer_head_dim,
                   "position_streams": len(self.mrope_section)})

    # ------------------------------------------------------------- init

    def init(self, key, tokens=None) -> dict:
        """``{"params": tree}``, drawn in ONE jitted program: matrices and
        embedding normal ``init_std``, norm scales 1, the LayerNorm's bias
        0."""
        del tokens  # flax's signature; the shapes come from the sizes
        return {"params": jax.jit(self._draw)(key)}

    def _draw(self, key):
        def value_of(name, k, shape):
            if name == "scale":
                return jnp.ones(shape, F32)
            if name == "bias":
                return jnp.zeros(shape, F32)
            return self.init_std * jax.random.normal(k, shape, F32)

        return lm_blocks.draw_tree(self.param_shapes(), key, value_of)

    # ------------------------------------------------------------ apply

    def apply(self, variables, tokens, positions=None):
        """flax's calling convention: ``apply({"params": p}, tokens)`` is
        the policy output of the centre."""
        return self.perturbed_apply(variables["params"], None, 0.0, tokens,
                                    positions)

    def perturbed_apply(self, params, noise, c, tokens, positions=None):
        """The policy output of ``params + c·noise`` for one sequence
        ``tokens [T]``: ``(log p(tokens[t+1] | …) [T-1], the head's logits
        averaged over the last ``behaviour_positions`` positions [vocab],
        pairs per held expert [held], selected (query, key) pairs)``.
        ``positions [3, T]``: the temporal, height and width stream of
        every token; ``None``: text, all three the token's index."""
        t = tokens.shape[0]
        dtype = params["embed"]["embedding"].dtype
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(t, dtype=jnp.int32), (len(self.mrope_section), t))
        rotary = lm_blocks.rotary_tables(
            t, self.head_dim, self.rope_theta, positions, self.mrope_section)
        # the indexer turns its whole width by the temporal stream
        index_rotary = lm_blocks.rotary_tables(
            t, self.indexer_head_dim, self.rope_theta, positions[:1],
            (self.indexer_head_dim // 2,))
        kernel, k_noise = params["head"]["kernel"], subtree(
            noise, "head", "kernel")

        x = perturbed_embed(tokens, params["embed"]["embedding"],
                            subtree(noise, "embed", "embedding"), c)
        load = jnp.zeros((self.num_experts,), jnp.int32)
        selected = jnp.int32(0)
        for name in self._layers():
            x, n_pairs, n_selected = self._layer(
                params[name], subtree(noise, name), c, x, rotary,
                index_rotary, dtype)
            load, selected = load + n_pairs, selected + n_selected
        h = self._norm(params, noise, c, "final_norm", x).astype(dtype)
        score, _ = lm_blocks.score_next_tokens(
            h, tokens, kernel, k_noise, c, self.head_block, leaf="head")
        with stage(HEAD), part("head"):
            last = jnp.mean(perturbed_dense(
                h[-self.behaviour_positions:], kernel, k_noise, c), axis=0)
        return score, last, load, selected

    # ----------------------------------------------------------- layers

    # a subclass that replaces ``_dense`` changes every 2-D projection of
    # the attention and the indexer

    @staticmethod
    def _dense(p, noise, c, name, x, under=lm_blocks.DENSE):
        return lm_blocks.dense(p, noise, c, name, x, under=under)

    def _norm(self, p, noise, c, name, y):
        """float32 RMSNorm of ``y`` by the perturbed ``p[name]["scale"]``."""
        return rmsnorm(y, perturbed_leaf(
            p[name]["scale"], subtree(noise, name, "scale"), c),
            self.rms_norm_eps)

    def _layer(self, p, noise, c, x, rotary, index_rotary, dtype):
        """One decoder layer: ``(x + attn + moe, pairs per held expert,
        selected pairs)``."""
        u = self._norm(p, noise, c, "norm1", x)
        chosen, n_selected = self._select(
            p["indexer"], subtree(noise, "indexer"), c, u, dtype,
            index_rotary)
        x = x + self._attention(p["attn"], subtree(noise, "attn"), c,
                                u.astype(dtype), rotary, chosen)
        routed, load = self._routed(
            p["moe"], subtree(noise, "moe"), c,
            self._norm(p, noise, c, "norm2", x), dtype)
        return x + routed, load, n_selected

    def _routed(self, moe, noise, c, u, dtype):
        return lm_blocks.routed_ffn(
            moe, noise, c, u, dtype, top_k=self.num_experts_per_tok,
            scaling=1.0, scoring="softmax",
            first_held=self.first_expert_held, total=self.experts_total)

    def _index_weights(self, p, noise, c, u):
        """``w [T, index heads]`` float32 from the float32 normed state."""
        with stage(INDEX), part("index_w"), jax.default_matmul_precision(
                "highest"):
            return perturbed_dense(u.astype(F32), p["index_w"].astype(F32),
                                   subtree(noise, "index_w"), c)

    def _index_parts(self, p, noise, c, u, dtype, index_rotary):
        """``(qI [T, index heads, dI], kI [T, dI])`` rotated, compute
        dtype, from the normed state ``u`` in the compute dtype."""
        t = u.shape[0]
        q_i = self._dense(p, noise, c, "index_q", u, INDEX).reshape(
            t, self.indexer_num_heads, self.indexer_head_dim)
        k_i = self._dense(p, noise, c, "index_k", u, INDEX)
        with stage(INDEX), part("index_k"):
            k_i = lm_blocks.layernorm(
                k_i,
                perturbed_leaf(p["index_norm"]["scale"],
                               subtree(noise, "index_norm", "scale"), c),
                perturbed_leaf(p["index_norm"]["bias"],
                               subtree(noise, "index_norm", "bias"), c),
                self.rms_norm_eps)
        with stage(ROPE):
            q_i = lm_blocks.rotate(q_i, *index_rotary)
            k_i = lm_blocks.rotate(k_i[:, None], *index_rotary)[:, 0]
        return q_i.astype(dtype), k_i.astype(dtype)

    def _select(self, p, noise, c, u, dtype, index_rotary):
        """``(selected [T, T] int8, selected pairs)`` of the float32 normed
        state ``u``."""
        q_i, k_i = self._index_parts(p, noise, c, u.astype(dtype), dtype,
                                     index_rotary)
        return lm_blocks.select_keys(
            q_i, k_i, self._index_weights(p, noise, c, u), topk=self.topk,
            block=self.index_block)

    def _attention(self, p, noise, c, u, rotary, selected):
        """Grouped-query attention of ``u [T, hidden]`` (compute dtype)
        over the ``selected`` keys."""
        dtype, t = u.dtype, u.shape[0]
        nq, nkv, d = (self.num_attention_heads, self.num_key_value_heads,
                      self.head_dim)

        def head_part(name, heads):
            y = self._dense(p, noise, c, name, u).reshape(t, heads, d)
            y = self._norm(p, noise, c, name + "_norm", y)
            with stage(ROPE):
                return lm_blocks.rotate(y, *rotary).astype(dtype)

        q, k = head_part("q", nq), head_part("k", nkv)
        v = self._dense(p, noise, c, "v", u).astype(dtype)
        # the one kind of attention layer says which it is: a part of
        # es.attn, as a model with several kinds names each
        with stage(ATTN), part("selected"):
            ctx = lm_blocks.attention_core(
                q, k, v, num_heads=nq, num_kv_heads=nkv,
                scale=1.0 / math.sqrt(d), block=self.attention_block,
                selected=selected)
        return self._dense(p, noise, c, "o", ctx)
