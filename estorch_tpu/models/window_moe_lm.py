"""A sparse-expert decoder whose router reads the layer's INPUT, ahead of
attention, with ReLU-gated experts and two kinds of attention layer in one
stack, as an ES policy: SmallThinker (``config.json`` keys
``moe_num_primary_experts``, ``moe_num_active_primary_experts``,
``moe_ffn_hidden_size``, ``sliding_window_size``, ``sliding_window_layout``,
``rope_layout``).  Per token sequence ``[T]``:

    x = E[tokens]
    each layer:
      a = rmsnorm₁ x
      r = a W_r                       the router, AHEAD of attention: [hidden -> all experts]
      S = the ``moe_num_active_primary_experts`` largest of softmax(r) over
          ALL experts (ties to the lower index), float32
      w_e = p_e / Σ_{e' in S} p_e'                           (``norm_topk_prob``)
      q = a W_q -> [T, heads, d];  k = a W_k, v = a W_v -> [T, kv heads, d]
      a ``window`` layer: q, k rotated over their whole d (halves
          convention, inv_freq_i = θ^(-2i/d)); key s visible to query t
          iff t - ``sliding_window_size`` < s <= t
      a ``global`` layer: NO rotation and no position term at all; every
          s <= t visible
      h = x + softmax_s(q kᵀ / √d) v W_o
      b = rmsnorm₂ h
      y = Σ_{e in S, e held here} w_e · down_e(relu(gate_e b) ⊙ up_e b)    (ReGLU)
      x = h + y
    h = rmsnorm_final(x);  score_t = log p(tokens[t+1] | …) from h W_head (untied)
    behaviour = the head's logits averaged over the last
                ``behaviour_positions`` positions

The routes of a layer do not depend on the attention beside them: ``es.route``
and the sort that plans the dispatch read ``a``, the experts read ``b`` with
those routes (``lm_blocks.route`` and ``lm_blocks.routed_experts``, called
apart; every other expert model routes and computes from ONE state through
``lm_blocks.routed_ffn``).  The two kinds of layer differ in BOTH band and
position: ``declaration()`` names each kind's band with the attention's
rule, and the run's records say which form each took
(``attention_form_by_kind``: where the kernel is traced a ``window`` layer
takes it too if its band spans at least one of the kernel's blocks or can
be the block, ``pallas_attention.call_form``).  The expert layer is told which experts
it holds, as ``MoELM``'s: the router scores ``moe_num_primary_experts ·
expert_group_size`` experts, this program holds the
``moe_num_primary_experts`` of share ``expert_group_rank`` and leaves out
what the others would have added.  The routers stay float32 in the copy the
forward reads (``float32_leaves``): they decide a discrete choice.

Every size is a constructor argument under its published key; the published
values live in the benchmark's configuration file only.  ``layer_types`` is
derived there from the two published layouts (``window`` where both are 1,
``global`` where both are 0; no other combination is written).  Precision as
``lm_blocks`` states.

As an ES policy the module maps ``tokens [T]`` to ``(score [T-1], the head's
logits averaged over the last ``behaviour_positions`` positions [vocab],
(token, k) pairs per held expert summed over the layers [held])``;
``TokenScoreEnv`` scores the first two, the engine sums the third into its
records.  Left out: q/k norms, biases, secondary experts, a shared expert,
un-normalised routing weights, a rope scaling (``rope_scaling`` is null and
anything else is refused; the scaling the tree has is
``lm_blocks.rotary_tables(scaling=)``, read by models/gated_window_moe_lm.py).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from ..obs.trace import ATTN, HEAD, ROPE, part, stage
from ..ops import pallas_attention, pallas_combine, pallas_head
from . import lm_blocks
from .lm_blocks import layer_name, rmsnorm, subtree
from .perturbed import (F32, PolicyDeclaration, perturbed_dense,
                        perturbed_embed, perturbed_leaf)

# How this model's leaves (``param_shapes``) are cut over a mesh's ``model``
# axis: it has none of its own beside the decoder's frame and the expert
# layer (models/lm_blocks.py).
PARTITION_RULES = (lm_blocks.DECODER_PARTITION_RULES
                   + lm_blocks.EXPERT_PARTITION_RULES)

WINDOW_LAYER, GLOBAL_LAYER = "window", "global"
EXPERT_LEAVES = ("gate", "up", "down")


@dataclasses.dataclass(frozen=True)
class WindowMoELM:
    layer_types: Sequence[str]
    vocab_size: int
    hidden_size: int
    moe_ffn_hidden_size: int
    sliding_window_size: int
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 8
    moe_num_primary_experts: int = 8           # held HERE
    expert_group_size: int = 1         # chips that share a layer's experts
    expert_group_rank: int = 0         # which share this program holds
    moe_num_active_primary_experts: int = 2
    moe_primary_router_apply_softmax: bool = True
    norm_topk_prob: bool = True
    behaviour_positions: int = 512
    rope_theta: float = 10000.0
    rope_scaling: Any = None
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    init_std: float = 0.02
    attention_block: int = 512
    head_block: int = 512

    is_recurrent = False
    use_vbn = False

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        bad = set(self.layer_types) - {WINDOW_LAYER, GLOBAL_LAYER}
        if bad or not self.layer_types:
            raise ValueError(
                f"layer_types holds {sorted(bad)}; a layer is "
                f"{WINDOW_LAYER!r} (rotary, banded) or {GLOBAL_LAYER!r} (no "
                "position term, every earlier key)")
        lm_blocks.refuse_unwritten(self, {
            "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
            "rope_scaling": None, "tie_word_embeddings": False})
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of key heads")
        if self.head_dim % 2:
            raise ValueError(f"head_dim {self.head_dim} must be even: the "
                             "rotation turns pairs")
        if self.sliding_window_size < 1:
            raise ValueError("sliding_window_size must be >= 1, got "
                             f"{self.sliding_window_size}")
        if not 0 <= self.expert_group_rank < self.expert_group_size:
            raise ValueError(
                f"expert_group_rank {self.expert_group_rank} is not one of "
                f"the {self.expert_group_size} shares")
        if self.moe_num_active_primary_experts > self.experts_total:
            raise ValueError("more experts per token than experts")
        if self.behaviour_positions < 1:
            raise ValueError("behaviour_positions must be >= 1, got "
                             f"{self.behaviour_positions}")

    # ------------------------------------------------------------ sizes

    @property
    def experts_total(self) -> int:
        """Experts the router scores: every share's."""
        return self.moe_num_primary_experts * self.expert_group_size

    @property
    def first_expert_held(self) -> int:
        return self.moe_num_primary_experts * self.expert_group_rank

    def _layer_shapes(self) -> dict:
        h, d = self.hidden_size, self.head_dim
        nq, nkv = self.num_attention_heads, self.num_key_value_heads
        e, w = self.moe_num_primary_experts, self.moe_ffn_hidden_size
        return {
            "norm1": {"scale": (h,)}, "norm2": {"scale": (h,)},
            "attn": {"q": (h, nq * d), "k": (h, nkv * d), "v": (h, nkv * d),
                     "o": (nq * d, h)},
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
        the routers."""
        return tuple(f"{p}/moe/router" for p in self._layers())

    def declaration(self) -> PolicyDeclaration:
        """What the engine that runs this model and the run's records read
        of it, stated once (models/perturbed.py::PolicyDeclaration)."""
        # rows a stacked expert leaf is applied to per position: the (token,
        # k) pairs routed to the held experts, with the layer's margin
        rows = (self.moe_num_active_primary_experts
                * lm_blocks.EXPERT_CAPACITY_MARGIN / self.expert_group_size)
        bands = {WINDOW_LAYER: self.sliding_window_size, GLOBAL_LAYER: None}
        return PolicyDeclaration(
            partition_rules=PARTITION_RULES,
            kernels=(
                # heads scored and summed at one width; each kind of
                # attention layer the stack holds, with its band
                (pallas_attention.attention_facts,
                 (self.head_dim, self.num_key_value_heads,
                  tuple((kind, band) for kind, band in bands.items()
                        if kind in self.layer_types),
                  self.num_attention_heads)),
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
            # and the band
            facts={"experts_held": self.moe_num_primary_experts,
                   "experts_total": self.experts_total,
                   "experts_per_token": self.moe_num_active_primary_experts,
                   "mtp_depth": 0,
                   "sliding_window": self.sliding_window_size,
                   "window_layers": self.layer_types.count(WINDOW_LAYER),
                   "global_layers": self.layer_types.count(GLOBAL_LAYER)})

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
        # only the window layers turn their queries and keys
        rotary = (lm_blocks.rotary_tables(t, self.head_dim, self.rope_theta)
                  if WINDOW_LAYER in self.layer_types else None)
        kernel, k_noise = params["head"]["kernel"], subtree(
            noise, "head", "kernel")

        x = perturbed_embed(tokens, params["embed"]["embedding"],
                            subtree(noise, "embed", "embedding"), c)
        load = jnp.zeros((self.moe_num_primary_experts,), jnp.int32)
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
    # the attention

    @staticmethod
    def _dense(p, noise, c, name, x):
        return lm_blocks.dense(p, noise, c, name, x)

    def _norm(self, p, noise, c, name, y):
        """float32 RMSNorm of ``y`` by the perturbed ``p[name]["scale"]``."""
        return rmsnorm(y, perturbed_leaf(
            p[name]["scale"], subtree(noise, name, "scale"), c),
            self.rms_norm_eps)

    def _layer(self, p, noise, c, x, kind, rotary, dtype):
        """One decoder layer: ``(x + attn + the held experts' part, pairs
        per held expert)``.  The routes are taken from the layer's normed
        INPUT, before the attention that reads the same state."""
        a = self._norm(p, noise, c, "norm1", x)
        experts, weights = self._routes(p["moe"], subtree(noise, "moe"), c, a)
        x = x + self._attention(p["attn"], subtree(noise, "attn"), c,
                                a.astype(dtype), kind, rotary)
        b = self._norm(p, noise, c, "norm2", x)
        routed, load = self._experts(p["moe"], subtree(noise, "moe"), c,
                                     b.astype(dtype), experts, weights)
        return x + routed, load

    def _routes(self, moe, noise, c, a):
        """``(experts [T, k], weights [T, k])`` of the float32 normed layer
        input ``a``, over ALL experts."""
        return lm_blocks.route(
            moe, noise, c, a, top_k=self.moe_num_active_primary_experts,
            scaling=1.0, scoring="softmax")

    def _experts(self, moe, noise, c, b, experts, weights):
        """The held experts' part of ``b`` (compute dtype) under routes
        taken elsewhere: ReGLU."""
        return lm_blocks.routed_experts(
            moe["experts"], subtree(noise, "experts"), c, b, experts,
            weights, first_held=self.first_expert_held,
            total=self.experts_total, activation=jax.nn.relu)

    def _band(self, kind: str) -> int | None:
        """The keys a query of a ``kind`` layer sees: ``(t - band, t]``;
        ``None``: every earlier one."""
        return self.sliding_window_size if kind == WINDOW_LAYER else None

    def _turns(self, kind: str) -> bool:
        """Whether a ``kind`` layer rotates its queries and keys."""
        return kind == WINDOW_LAYER

    def _attention(self, p, noise, c, u, kind, rotary):
        """Grouped-query attention of ``u [T, hidden]`` (compute dtype): a
        ``window`` layer rotated and banded, a ``global`` one neither."""
        dtype, t = u.dtype, u.shape[0]
        nq, nkv, d = (self.num_attention_heads, self.num_key_value_heads,
                      self.head_dim)
        turns = self._turns(kind)

        def head_part(name, heads):
            y = self._dense(p, noise, c, name, u)
            if not turns:
                return y.astype(dtype)
            with stage(ROPE):
                return lm_blocks.rotate(
                    y.reshape(t, heads, d), *rotary).astype(dtype)

        q, k = head_part("q", nq), head_part("k", nkv)
        v = self._dense(p, noise, c, "v", u).astype(dtype)
        # each kind of attention layer says which it is: a part of es.attn
        with stage(ATTN), part(kind):
            ctx = lm_blocks.attention_core(
                q, k, v, num_heads=nq, num_kv_heads=nkv,
                scale=1.0 / math.sqrt(d), block=self.attention_block,
                window=self._band(kind))
        return self._dense(p, noise, c, "o", ctx)
