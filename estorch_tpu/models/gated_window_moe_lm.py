"""A sparse-expert decoder whose attention layers are of two kinds that
differ in their HEAD COUNT as well as in band and rotation, each head's
context closed by a gate of its own, behind one dense layer, as an ES policy:
Laguna (``model_type`` ``laguna``; ``config.json`` keys ``layer_types``,
``num_attention_heads_per_layer``, ``sliding_window``, ``gating``,
``rope_parameters``, ``mlp_layer_types``, ``moe_routed_scaling_factor``,
``shared_expert_intermediate_size``).  Per token sequence ``[T]``, with ``d
= head_dim`` and ``n_l = num_attention_heads_per_layer[l]`` query heads over
``num_key_value_heads`` key-value heads in layer ``l``:

    x = E[tokens]
    layer l, of kind ``sliding_attention`` or ``full_attention``:
      a = rmsnorm₁(x)
      q = a W_q -> [T, n_l, d];  k = a W_k, v = a W_v -> [T, kv heads, d]
          (query head j reads key-value head j // (n_l / kv heads))
      the leading ``partial_rotary_factor · d`` of every head of q and k
          turned (halves convention) by the tables of the KIND's
          ``rope_parameters`` group: its own ``rope_theta``, its own rotated
          width and, under ``rope_type`` ``"yarn"``, YaRN's blended
          frequencies with cos and sin times ``attention_factor``
          (``lm_blocks.rotary_tables(scaling=)`` has the formula); the rest
          of the head passes
      sliding: key s visible to query t iff t - ``sliding_window`` < s <= t
      full:    every s <= t visible
      ctx = softmax_s(q kᵀ / √d) v                          float32 softmax
      g   = sigmoid(a W_g) -> [T, n_l]                      ONE number a head
      h   = x + (ctx ⊙ g[..., None]) W_o
      b   = rmsnorm₂(h)
      ``mlp_layer_types[l]`` ``dense``:   x = h + W_down(silu(W_gate b) ⊙ W_up b)
      ``sparse``:  s = sigmoid(b W_r) over ALL experts, float32
                   S = the ``num_experts_per_tok`` largest (ties to the lower
                       index);  w_e = ``moe_routed_scaling_factor`` · s_e /
                       Σ_{e' in S} s_e'
                   x = h + shared(b) + Σ_{e in S, e held here} w_e · expert_e(b)
                                                            (SwiGLU all)
    h = rmsnorm_final(x);  score_t = log p(tokens[t+1] | …) from h W_head (untied)
    behaviour = the head's logits averaged over the last
                ``behaviour_positions`` positions

The leaves of a layer differ in SHAPE by its kind: ``W_q [hidden, n_l · d]``,
``W_o [n_l · d, hidden]`` and ``W_g [hidden, n_l]`` are as wide as the
layer's own head count, so the tree is not a stack of one layer's shapes.
One table of rotations a KIND (two ``rope_theta``, two rotated widths, one
of them scaled) is made once a sequence.  ``declaration()`` names each
kind's band with the attention's rule, and the run's records say which form
each took (``attention_form_by_kind``: the published band, half of the kernel's block,
is the block itself there, ``pallas_attention.call_form``).  The gate is the
head-wise one of arXiv 2505.06708: ``W_g`` is ``n_l`` columns, not a second
query-sized projection.  The router scores by sigmoid and builds NO selection
bias (``lm_blocks.route`` chooses by the scores alone where the tree holds no
``router_bias``).

The expert layer is told which experts it holds, as ``MoELM``'s: the router
scores ``num_experts · expert_group_size`` experts, this program holds the
``num_experts`` of share ``expert_group_rank`` and leaves out what the others
would have added; the shared expert is whole on every share.  The routers
stay float32 in the copy the forward reads (``float32_leaves``).

Every size is a constructor argument under its published key; the published
values live in the benchmark's configuration file only.  The three per-layer
lists are read by their first ``len(layer_types)`` entries and
``rope_parameters`` by its two kinds' groups, kept as the file gives them
(lists and a dict: the class hashes by its text).  Precision as
``lm_blocks`` states.

As an ES policy the module maps ``tokens [T]`` to ``(score [T-1], the head's
logits averaged over the last ``behaviour_positions`` positions [vocab],
(token, k) pairs per held expert summed over the layers [held])``.  Left out:
q/k norms, biases (``attention_bias`` false), an element-wise gate, a
selection bias, router weights applied on the experts' input
(``moe_apply_router_weight_on_input`` false), tied embeddings.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from ..obs.trace import ATTN, DENSE, HEAD, ROPE, part, stage
from ..ops import pallas_attention, pallas_combine, pallas_head
from . import lm_blocks
from .lm_blocks import layer_name, rmsnorm, subtree
from .perturbed import (F32, PolicyDeclaration, perturbed_dense,
                        perturbed_embed, perturbed_leaf)

# How this model's leaves (``param_shapes``) are cut over a mesh's ``model``
# axis: the decoder's frame and the expert layer (models/lm_blocks.py: q, k,
# v, o as wide as the layer's own heads, norms, dense FFN, router, shared
# and stacked experts), and the gate's projection, one column a head, 48 or
# 64 of them: it replicates, as the other narrow projections do.
PARTITION_RULES = (
    lm_blocks.DECODER_PARTITION_RULES + lm_blocks.EXPERT_PARTITION_RULES + (
        (r"attn/head_gate$", P()),
    ))

FULL_LAYER, SLIDING_LAYER = "full_attention", "sliding_attention"
# what a kind is called in a trace (``of.<kind>`` under es.attn) and in the
# records' ``attention_form_by_kind``
KIND = {SLIDING_LAYER: "sliding", FULL_LAYER: "full"}
DENSE_MLP, SPARSE_MLP = "dense", "sparse"
EXPERT_LEAVES = ("gate", "up", "down")


@dataclasses.dataclass(frozen=True)
class GatedWindowMoELM:
    layer_types: Sequence[str]
    mlp_layer_types: Sequence[str]
    num_attention_heads_per_layer: Sequence[int]
    rope_parameters: dict
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    shared_expert_intermediate_size: int
    sliding_window: int
    num_key_value_heads: int = 2
    head_dim: int = 8
    num_experts: int = 8               # held HERE
    expert_group_size: int = 1         # chips that share a layer's experts
    expert_group_rank: int = 0         # which share this program holds
    num_experts_per_tok: int = 2
    moe_routed_scaling_factor: float = 1.0
    moe_apply_router_weight_on_input: bool = False
    gating: bool = True
    attention_bias: bool = False
    behaviour_positions: int = 512
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    init_std: float = 0.02
    attention_block: int = 512
    head_block: int = 512

    is_recurrent = False
    use_vbn = False

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        n = len(self.layer_types)
        bad = set(self.layer_types) - set(KIND)
        if bad or not n:
            raise ValueError(f"layer_types holds {sorted(bad)}; a layer is "
                             f"{SLIDING_LAYER!r} or {FULL_LAYER!r}")
        if (len(self.mlp_layer_types) < n
                or len(self.num_attention_heads_per_layer) < n):
            raise ValueError(
                "mlp_layer_types and num_attention_heads_per_layer name "
                f"{len(self.mlp_layer_types)} and "
                f"{len(self.num_attention_heads_per_layer)} layers, "
                f"layer_types {n}")
        bad = set(self.mlp_kinds) - {DENSE_MLP, SPARSE_MLP}
        if bad:
            raise ValueError(f"mlp_layer_types holds {sorted(bad)}; a layer's "
                             f"FFN is {DENSE_MLP!r} or {SPARSE_MLP!r}")
        lm_blocks.refuse_unwritten(self, {
            "gating": True, "attention_bias": False,
            "moe_apply_router_weight_on_input": False,
            "tie_word_embeddings": False})
        # heads are a KIND's: the declaration, the trace's parts and the
        # benchmark's counts all say "a sliding layer's heads"
        for kind in set(self.layer_types):
            counts = {h for h, k in zip(self.heads, self.layer_types)
                      if k == kind}
            if len(counts) != 1 or min(counts) < 1 or (
                    min(counts) % self.num_key_value_heads):
                raise ValueError(
                    f"the {kind} layers have {sorted(counts)} query heads: "
                    "one count a kind, a multiple of num_key_value_heads "
                    f"{self.num_key_value_heads}")
            group = self.rope_parameters.get(kind)
            if not isinstance(group, dict) or "rope_theta" not in group:
                raise ValueError(f"rope_parameters has no group {kind!r} "
                                 "with a rope_theta")
            turned = self.rotary_dim(kind)
            if turned < 2 or turned % 2 or turned > self.head_dim:
                raise ValueError(
                    f"partial_rotary_factor {group.get('partial_rotary_factor')}"
                    f" of head_dim {self.head_dim} is {turned}: the rotation "
                    "turns pairs inside the head")
        if self.sliding_window < 1:
            raise ValueError("sliding_window must be >= 1, got "
                             f"{self.sliding_window}")
        if not 0 <= self.expert_group_rank < self.expert_group_size:
            raise ValueError(
                f"expert_group_rank {self.expert_group_rank} is not one of "
                f"the {self.expert_group_size} shares")
        if self.num_experts_per_tok > self.experts_total:
            raise ValueError("more experts per token than experts")
        if self.behaviour_positions < 1:
            raise ValueError("behaviour_positions must be >= 1, got "
                             f"{self.behaviour_positions}")

    def __hash__(self):
        # the per-layer lists and the rope groups stay what the
        # configuration file gives (lists and a dict), so that the file
        # describes what was built key for key
        return hash(repr(self))

    # ------------------------------------------------------------ sizes

    @property
    def heads(self) -> tuple:
        """Query heads of each layer that is built."""
        return tuple(int(h) for h in self.num_attention_heads_per_layer[
            :len(self.layer_types)])

    @property
    def mlp_kinds(self) -> tuple:
        return tuple(self.mlp_layer_types[:len(self.layer_types)])

    def heads_of(self, kind: str) -> int:
        """Query heads of a ``kind`` layer (one count a kind)."""
        return self.heads[self.layer_types.index(kind)]

    def rotary_dim(self, kind: str) -> int:
        """The leading part of a ``kind`` layer's heads that turns."""
        return int(self.head_dim * self._rope(kind).get(
            "partial_rotary_factor", 1.0))

    @property
    def experts_total(self) -> int:
        """Experts the router scores: every share's."""
        return self.num_experts * self.expert_group_size

    @property
    def first_expert_held(self) -> int:
        return self.num_experts * self.expert_group_rank

    def _layer_shapes(self, heads: int, mlp: str) -> dict:
        h, d, nkv = self.hidden_size, self.head_dim, self.num_key_value_heads
        tree: dict[str, Any] = {
            "norm1": {"scale": (h,)}, "norm2": {"scale": (h,)},
            "attn": {"q": (h, heads * d), "k": (h, nkv * d),
                     "v": (h, nkv * d), "o": (heads * d, h),
                     "head_gate": (h, heads)}}
        if mlp == DENSE_MLP:
            ff = self.intermediate_size
            tree["mlp"] = {"gate": (h, ff), "up": (h, ff), "down": (ff, h)}
        else:
            e, w = self.num_experts, self.moe_intermediate_size
            sw = self.shared_expert_intermediate_size
            tree["moe"] = {
                "router": (h, self.experts_total),
                "shared": {"gate": (h, sw), "up": (h, sw), "down": (sw, h)},
                "experts": {"gate": (e, h, w), "up": (e, h, w),
                            "down": (e, w, h)}}
        return tree

    def param_shapes(self) -> dict:
        """The parameter tree as shapes (float32)."""
        h = self.hidden_size
        tree: dict[str, Any] = {
            "embed": {"embedding": (self.vocab_size, h)},
            "head": {"kernel": (h, self.vocab_size)},
            "final_norm": {"scale": (h,)}}
        for i, (heads, mlp) in enumerate(zip(self.heads, self.mlp_kinds)):
            tree[layer_name(i)] = self._layer_shapes(heads, mlp)
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s, F32), tree,
            is_leaf=lambda s: isinstance(s, tuple))

    def _moe_paths(self) -> list[str]:
        return [f"{layer_name(i)}/moe" for i, mlp in enumerate(self.mlp_kinds)
                if mlp == SPARSE_MLP]

    @property
    def stacked_leaves(self) -> tuple:
        """The leaves whose leading axis indexes experts: one factor pair
        per expert (ops/lowrank.py)."""
        return tuple(f"{p}/experts/{n}" for p in self._moe_paths()
                     for n in EXPERT_LEAVES)

    @property
    def float32_leaves(self) -> tuple:
        """Leaves the forward reads in float32 whatever the compute dtype:
        the routers."""
        return tuple(f"{p}/router" for p in self._moe_paths())

    def declaration(self) -> PolicyDeclaration:
        """What the engine that runs this model and the run's records read
        of it, stated once (models/perturbed.py::PolicyDeclaration)."""
        # rows a stacked expert leaf is applied to per position: the (token,
        # k) pairs routed to the held experts, with the layer's margin
        rows = (self.num_experts_per_tok * lm_blocks.EXPERT_CAPACITY_MARGIN
                / self.expert_group_size)
        bands = {SLIDING_LAYER: self.sliding_window, FULL_LAYER: None}
        count = self.layer_types.count
        return PolicyDeclaration(
            partition_rules=PARTITION_RULES,
            kernels=(
                # heads scored and summed at one width in both kinds; each
                # kind of attention layer the stack holds, with its band
                (pallas_attention.attention_facts,
                 (self.head_dim, self.num_key_value_heads,
                  tuple((KIND[kind], band) for kind, band in bands.items()
                        if kind in self.layer_types),
                  # the query heads of each of those kinds' calls
                  tuple(self.heads_of(kind) for kind in bands
                        if kind in self.layer_types))),
                (pallas_head.head_facts, (self.hidden_size,)),
                # the token rows the expert layer's combine adds into
                (pallas_combine.combine_facts, (self.hidden_size,))),
            # the head runs in blocks of ``head_block`` positions
            leaf_rows={"head/kernel": self.head_block},
            leaf_rows_per_token=dict.fromkeys(self.stacked_leaves, rows),
            stacked_leaves=self.stacked_leaves,
            float32_leaves=self.float32_leaves,
            # after what the env scores: the pairs per held expert
            outputs=("expert_load",),
            # the sparse-expert facts under MoELM's names (no MTP module),
            # the band, and each kind's layers and heads
            facts={"experts_held": self.num_experts,
                   "experts_total": self.experts_total,
                   "experts_per_token": self.num_experts_per_tok,
                   "mtp_depth": 0,
                   "sliding_window": self.sliding_window,
                   "dense_layers": self.mlp_kinds.count(DENSE_MLP),
                   **{f"{KIND[kind]}_layers": count(kind) for kind in KIND},
                   **{f"{KIND[kind]}_heads": self.heads_of(kind)
                      for kind in KIND if kind in self.layer_types}})

    # ------------------------------------------------------------- init

    def init(self, key, tokens=None) -> dict:
        """``{"params": tree}``, drawn in ONE jitted program: matrices and
        embedding normal ``init_std``, norm scales 1."""
        del tokens  # flax's signature; the shapes come from the sizes
        return {"params": jax.jit(self._draw)(key)}

    def _draw(self, key):
        def value_of(name, k, shape):
            if name == "scale":
                return jnp.ones(shape, F32)
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
        # one table of rotations a kind of layer
        rotary = {kind: self._tables(kind, t)
                  for kind in KIND if kind in self.layer_types}
        kernel, k_noise = params["head"]["kernel"], subtree(
            noise, "head", "kernel")

        x = perturbed_embed(tokens, params["embed"]["embedding"],
                            subtree(noise, "embed", "embedding"), c)
        load = jnp.zeros((self.num_experts,), jnp.int32)
        for i, (kind, heads, mlp) in enumerate(zip(
                self.layer_types, self.heads, self.mlp_kinds)):
            name = layer_name(i)
            x, n_pairs = self._layer(params[name], subtree(noise, name), c,
                                     x, kind, heads, mlp, rotary[kind],
                                     dtype)
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
    # the attention, of the dense FFN and of the shared expert; the hooks
    # ``_rope`` / ``_band`` / ``_core`` / ``_gate`` / ``_routed`` /
    # ``_shared`` are what benchmark/rehearse/coarse_swg.py's degraded
    # forms override

    @staticmethod
    def _dense(p, noise, c, name, x):
        return lm_blocks.dense(p, noise, c, name, x)

    def _norm(self, p, noise, c, name, y):
        """float32 RMSNorm of ``y`` by the perturbed ``p[name]["scale"]``."""
        return rmsnorm(y, perturbed_leaf(
            p[name]["scale"], subtree(noise, name, "scale"), c),
            self.rms_norm_eps)

    def _rope(self, kind: str) -> dict:
        """The ``rope_parameters`` group of a ``kind`` layer."""
        return self.rope_parameters[kind]

    def _tables(self, kind: str, length: int):
        """``(cos, sin) [T, rotary_dim / 2]`` of a ``kind`` layer."""
        group = self._rope(kind)
        return lm_blocks.rotary_tables(
            length, self.rotary_dim(kind), group["rope_theta"], scaling=group)

    def _band(self, kind: str) -> int | None:
        """The keys a query of a ``kind`` layer sees: ``(t - band, t]``;
        ``None``: every earlier one."""
        return self.sliding_window if kind == SLIDING_LAYER else None

    def _layer(self, p, noise, c, x, kind, heads, mlp, rotary, dtype):
        """One decoder layer: ``(x + attention + FFN, pairs per held
        expert)``; the FFN dense, or the shared expert and the held
        experts' part."""
        a = self._norm(p, noise, c, "norm1", x).astype(dtype)
        x = x + self._attention(p["attn"], subtree(noise, "attn"), c, a,
                                kind, heads, rotary)
        b = self._norm(p, noise, c, "norm2", x)
        if mlp == DENSE_MLP:
            return x + lm_blocks.gated_mlp(
                self._dense, p["mlp"], subtree(noise, "mlp"), c,
                b.astype(dtype)), jnp.zeros((self.num_experts,), jnp.int32)
        moe, m_noise = p["moe"], subtree(noise, "moe")
        routed, load = self._routed(moe, m_noise, c, b, dtype)
        shared = self._shared(moe, m_noise, c, b.astype(dtype))
        return x + shared + routed, load

    def _routed(self, moe, noise, c, b, dtype):
        """``(the held experts' part of the float32 ``b``, pairs per held
        expert)``: sigmoid scores over ALL experts, the chosen ones'
        renormalised to sum ``moe_routed_scaling_factor``."""
        return lm_blocks.routed_ffn(
            moe, noise, c, b, dtype, top_k=self.num_experts_per_tok,
            scaling=self.moe_routed_scaling_factor,
            first_held=self.first_expert_held, total=self.experts_total)

    def _shared(self, moe, noise, c, u):
        """The shared expert, whole on every share, added as it is."""
        with part("shared"):    # its leaves read ``shared.gate`` … in a trace
            return lm_blocks.gated_mlp(self._dense, moe["shared"],
                                       subtree(noise, "shared"), c, u)

    def _attention(self, p, noise, c, u, kind, heads, rotary):
        """Grouped-query attention of ``u [T, hidden]`` (compute dtype) with
        ``heads`` query heads: the kind's rotation and band, each head's
        context times its gate."""
        dtype, t = u.dtype, u.shape[0]
        d, turned = self.head_dim, self.rotary_dim(kind)

        def head_part(name, n):
            y = self._dense(p, noise, c, name, u)
            with stage(ROPE):
                return lm_blocks.rotate(y.reshape(t, n, d), *rotary,
                                        rotary_dim=turned).astype(dtype)

        q, k = head_part("q", heads), head_part("k", self.num_key_value_heads)
        v = self._dense(p, noise, c, "v", u).astype(dtype)
        opened = self._dense(p, noise, c, "head_gate", u)
        # each kind of attention layer says which it is: a part of es.attn
        with stage(ATTN), part(KIND[kind]):
            ctx = self._core(q, k, v, kind, heads)
        with stage(DENSE), part("head_gate"):
            ctx = (ctx.astype(F32).reshape(t, heads, d)
                   * self._gate(opened)[..., None]).reshape(
                       t, heads * d).astype(dtype)
        return self._dense(p, noise, c, "o", ctx)

    def _core(self, q, k, v, kind, heads):
        """``softmax(q kᵀ / √d) v`` under the kind's band, ``[T, heads ·
        d]``."""
        return lm_blocks.attention_core(
            q, k, v, num_heads=heads, num_kv_heads=self.num_key_value_heads,
            scale=1.0 / math.sqrt(self.head_dim), block=self.attention_block,
            window=self._band(kind))

    @staticmethod
    def _gate(opened):
        """A head's gate from its column of ``a W_g``, float32 ``[T,
        heads]``."""
        return jax.nn.sigmoid(opened)
