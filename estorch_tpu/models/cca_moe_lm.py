"""A sparse-expert decoder whose attention is computed INSIDE A COMPRESSED
LATENT, as an ES policy: ZAYA1's layer (``model_type`` ``zaya``; CCA, arXiv
2510.04476, and the router of arXiv 2511.17127).  q lives in ``heads · d``
and k, v in ``kv heads · d``, both narrower than the residual; q and k pass
two causal convolutions and a mean of each other before the scores, half of
the values come from the position before, the scores are cosines under a
learned temperature, and HALF of each head is rotated.  The router is an
MLP over a state that each layer hands to the next, and picks ONE expert a
token.  Per token sequence ``[T]`` (``u`` the normed residual):

    x = E[tokens];  r_{-1} = 0 [T, router width]
    each layer l:  x += cca(rmsnorm₁ x);  (y, r_l) = moe(rmsnorm₂ x, r_{l-1});  x += y

    cca(u):  q̃ = u W_q -> [T, heads, d];   k̃ = u W_k -> [T, kv heads, d]
             v = u W_v -> [T, kv heads, d], the LAST half of its heads read
                 from the position before (u_{-1} = 0): the value shift
      conv:  z = concat(q̃, k̃) over the heads -> [T, heads + kv heads, d]
             z¹_t = Σ_{j<cca_time0} a_j ⊙ z_{t-(cca_time0-1-j)} + b¹       depthwise over time
             z²_t[h] = Σ_{j<cca_time1} z¹_{t-(cca_time1-1-j)}[h] · C_j[h] + b²[h]
                                                    mixes the d channels of ONE head; both causal, zero-padded
             (q', k') = split(z²)
      mean:  q = q' + ½ (q̃ + repeat(k̃, group))          q head i pairs with kv head i // group
             k = k' + ½ (mean of the group's q̃ heads + k̃)
      norm:  q̂ = √d · q / ‖q‖₂;   k̂ = √d · τ_g · k / ‖k‖₂    per head over its d; τ one a kv head
      rope:  the first ``partial_rotary_factor · d`` of each head turned (halves
             convention inside the slice, inv_freq_i = θ^(-2i / that width)),
             the rest left as it is
      score_h[t, s] = q̂_h[t] · k̂_{h // group}[s] / √d  for s <= t   (= √d · τ · cos: bounded)
      P = softmax_s;  ctx = P v;  out = ctx W_o              (W_o: the way back out of the latent)

    moe(u, r_below):
             r = u W_dn + b_dn + γ ⊙ r_below                  the state carried upward
             z = W₃ gelu(W₂ gelu(W₁ rmsnorm(r) + b₁) + b₂) + b₃
             p = softmax(z) over ALL experts;  e = argmax(p + β)   (ties to the lower index;
                                                                    β enters the choice only)
             y = p[e] · expert_e(u)  if e is held here, else 0      (gated SiLU; the weight NOT renormalised)
             return (y, r)

    h = rmsnorm_final(x);  score_t = log p(tokens[t+1] | …) from h Eᵀ (tied)
    behaviour = the head's logits averaged over the last
                ``behaviour_positions`` positions

The temperature reaches the scores folded into ``k̂`` (``lm_blocks.l2_scale``);
``lm_blocks.attention_core`` is called as every other model calls it, with
the one scale ``1/√d``.  The expert layer is told which experts it holds, as
``MoELM``'s: the router scores ``num_experts · expert_group_size`` experts,
this program holds the ``num_experts`` of share ``expert_group_rank`` and
leaves out what the others would have added.  The whole router (its
down-projection, γ, its norm, the MLP, β) stays float32 in the copy the
forward reads (``float32_leaves``): it decides a discrete choice, and at one
expert a token a swapped choice replaces the token's whole routed output.

Every size is a constructor argument under its published key; the published
values live in the benchmark's configuration file only.  Precision as
``lm_blocks`` states: matmul operands in the dtype of the parameters handed
in, float32 accumulation; residual stream, norms, convolution sums, L2
norms, rotation, softmax, the whole router and log-softmax in float32.

As an ES policy the module maps ``tokens [T]`` to ``(score [T-1], the
head's logits averaged over the last ``behaviour_positions`` positions
[vocab], (token, expert) pairs per held expert summed over the layers
[held])``; ``TokenScoreEnv`` scores the first two, the engine sums the third
into its records.  Left out (the configuration names neither a key nor a
size for them): a learned scaling of the residual stream, a routed "skip"
choice, a sliding window, an untied head, more than one expert a token.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from ..obs.trace import HEAD, MIX, ROPE, part, stage
from ..ops import pallas_attention, pallas_combine, pallas_head
from . import lm_blocks
from .lm_blocks import layer_name, rmsnorm, subtree
from .perturbed import (F32, MODEL_AXIS, PolicyDeclaration, perturbed_dense,
                        perturbed_embed, perturbed_leaf)

# How this model's leaves (``param_shapes``) are cut over a mesh's ``model``
# axis: the decoder's frame and the expert layer (models/lm_blocks.py: q, k,
# v, o, the embedding, the stacked experts, the selection bias), and the
# latent's own.  The head-mixing convolution's STACKED ``[taps · heads, d,
# d]`` matrices shard their (tap, head) axis, as the experts shard theirs;
# the depthwise taps, both convolutions' biases and the temperatures are a
# few thousand values that every head's slice reads and replicate.  The
# router (a down-projection into a norm over its whole width, a state's
# scale, a three-matrix MLP 256 wide) replicates, as the one-matrix routers
# do: every device routes every token.
PARTITION_RULES = (
    lm_blocks.DECODER_PARTITION_RULES + lm_blocks.EXPERT_PARTITION_RULES + (
        (r"attn/conv_head$", P(MODEL_AXIS, None, None)),
        (r"attn/(conv_time|conv_time_bias|conv_head_bias|temperature)$", P()),
        (r"moe/(router_down|router_down_bias|router_state)$", P()),
        (r"moe/router_norm/scale$", P()),
        (r"moe/router_mlp/[wb][123]$", P()),
    ))

CCA_LAYER = "hybrid"
EXPERT_LEAVES = ("gate", "up", "down")
ROUTER_LEAVES = (
    "router_down", "router_down_bias", "router_state", "router_norm/scale",
    "router_mlp/w1", "router_mlp/b1", "router_mlp/w2", "router_mlp/b2",
    "router_mlp/w3", "router_mlp/b3", "router_bias")
# learned scales that start at one, as a norm's weight does
ONES = ("scale", "router_state", "temperature")


@dataclasses.dataclass(frozen=True)
class CCAMoELM:
    layer_types: Sequence[str]
    vocab_size: int
    hidden_size: int
    moe_intermediate_size: int
    num_attention_heads: int = 8
    num_key_value_heads: int = 2
    head_dim: int = 16
    cca_time0: int = 2                 # taps of the depthwise convolution
    cca_time1: int = 2                 # taps of the head-mixing one
    partial_rotary_factor: float = 0.5
    router_hidden_size: int = 16
    num_experts: int = 2               # held HERE
    expert_group_size: int = 1         # chips that share a layer's experts
    expert_group_rank: int = 0         # which share this program holds
    num_experts_per_tok: int = 1
    sliding_window: int | None = None
    behaviour_positions: int = 512
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    attention_bias: bool = False
    lm_head_bias: bool = False
    tie_word_embeddings: bool = True
    init_std: float = 0.02
    attention_block: int = 512
    head_block: int = 512

    is_recurrent = False
    use_vbn = False

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        bad = set(self.layer_types) - {CCA_LAYER}
        if bad or not self.layer_types:
            raise ValueError(f"layer_types holds {sorted(bad)}; every layer "
                             f"is {CCA_LAYER!r}")
        lm_blocks.refuse_unwritten(self, {
            "num_experts_per_tok": 1, "sliding_window": None,
            "tie_word_embeddings": True, "attention_bias": False,
            "lm_head_bias": False})
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of key heads")
        if self.num_key_value_heads % 2:
            raise ValueError(
                f"num_key_value_heads {self.num_key_value_heads} must be "
                "even: half of the value heads read the position before")
        if self.rotary_dim < 2 or self.rotary_dim % 2 or (
                self.rotary_dim > self.head_dim):
            raise ValueError(
                f"partial_rotary_factor {self.partial_rotary_factor} of "
                f"head_dim {self.head_dim} is {self.rotary_dim} channels: "
                "the rotation turns pairs inside a head")
        if self.cca_time0 < 1 or self.cca_time1 < 1:
            raise ValueError("cca_time0 and cca_time1 count taps: >= 1, got "
                             f"{self.cca_time0} and {self.cca_time1}")
        if not 0 <= self.expert_group_rank < self.expert_group_size:
            raise ValueError(
                f"expert_group_rank {self.expert_group_rank} is not one of "
                f"the {self.expert_group_size} shares")
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
        """Leading channels of a head the rotation turns."""
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def latent_heads(self) -> int:
        """Heads the convolutions pass: q's and k's side by side."""
        return self.num_attention_heads + self.num_key_value_heads

    def _layer_shapes(self) -> dict:
        h, d, r = self.hidden_size, self.head_dim, self.router_hidden_size
        nq, nkv, lat = (self.num_attention_heads, self.num_key_value_heads,
                        self.latent_heads)
        e, w = self.num_experts, self.moe_intermediate_size
        return {
            "norm1": {"scale": (h,)}, "norm2": {"scale": (h,)},
            "attn": {"q": (h, nq * d), "k": (h, nkv * d), "v": (h, nkv * d),
                     "o": (nq * d, h),
                     "conv_time": (self.cca_time0, 1, lat * d),
                     "conv_time_bias": (lat * d,),
                     # the (tap, head) matrices, tap-major
                     "conv_head": (self.cca_time1 * lat, d, d),
                     "conv_head_bias": (lat * d,),
                     "temperature": (nkv,)},
            "moe": {"router_down": (h, r), "router_down_bias": (r,),
                    "router_state": (r,), "router_norm": {"scale": (r,)},
                    "router_mlp": {"w1": (r, r), "b1": (r,), "w2": (r, r),
                                   "b2": (r,), "w3": (r, self.experts_total),
                                   "b3": (self.experts_total,)},
                    "router_bias": (self.experts_total,),
                    "experts": {"gate": (e, h, w), "up": (e, h, w),
                                "down": (e, w, h)}}}

    def param_shapes(self) -> dict:
        """The parameter tree as shapes (float32); no head: it is tied."""
        h = self.hidden_size
        tree: dict[str, Any] = {
            "embed": {"embedding": (self.vocab_size, h)},
            "final_norm": {"scale": (h,)}}
        for i in range(len(self.layer_types)):
            tree[layer_name(i)] = self._layer_shapes()
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s, F32), tree,
            is_leaf=lambda s: isinstance(s, tuple))

    def _layers(self) -> list[str]:
        return [layer_name(i) for i in range(len(self.layer_types))]

    @property
    def expert_leaves(self) -> tuple:
        return tuple(f"{p}/moe/experts/{n}" for p in self._layers()
                     for n in EXPERT_LEAVES)

    @property
    def stacked_leaves(self) -> tuple:
        """The leaves whose leading axis indexes matrices that take one
        factor pair each (ops/lowrank.py): the experts, and the head-mixing
        convolution's (tap, head) matrices."""
        return self.expert_leaves + tuple(
            f"{p}/attn/conv_head" for p in self._layers())

    @property
    def float32_leaves(self) -> tuple:
        """Leaves the forward reads in float32 whatever the compute dtype:
        the whole router."""
        return tuple(f"{p}/moe/{n}" for p in self._layers()
                     for n in ROUTER_LEAVES)

    def declaration(self) -> PolicyDeclaration:
        """What the engine that runs this model and the run's records read
        of it, stated once (models/perturbed.py::PolicyDeclaration)."""
        # rows a stacked expert leaf is applied to per position: the tokens
        # routed to the held experts, with the layer's margin (the
        # convolution's matrices see every position: the default)
        rows = (self.num_experts_per_tok * lm_blocks.EXPERT_CAPACITY_MARGIN
                / self.expert_group_size)
        return PolicyDeclaration(
            partition_rules=PARTITION_RULES,
            kernels=(
                # heads scored and summed at one width; one kind of
                # attention layer, full causal
                (pallas_attention.attention_facts,
                 (self.head_dim, self.num_key_value_heads, None,
                  self.num_attention_heads)),
                (pallas_head.head_facts, (self.hidden_size,)),
                # the token rows the expert layer's combine adds into
                (pallas_combine.combine_facts, (self.hidden_size,))),
            leaf_rows_per_token=dict.fromkeys(self.expert_leaves, rows),
            stacked_leaves=self.stacked_leaves,
            float32_leaves=self.float32_leaves,
            # after what the env scores: the pairs per held expert
            outputs=("expert_load",),
            facts={"experts_held": self.num_experts,
                   "experts_total": self.experts_total,
                   "experts_per_token": self.num_experts_per_tok,
                   "mtp_depth": 0,
                   "latent_q_width": self.num_attention_heads * self.head_dim,
                   "latent_kv_width": (self.num_key_value_heads
                                       * self.head_dim),
                   "conv_taps": self.cca_time0 + self.cca_time1,
                   "router_hidden": self.router_hidden_size})

    # ------------------------------------------------------------- init

    def init(self, key, tokens=None) -> dict:
        """``{"params": tree}``, drawn in ONE jitted program: matrices,
        embedding and the convolutions' taps normal ``init_std``; norm
        scales, γ and the temperatures 1; every bias (β among them) 0."""
        del tokens  # flax's signature; the shapes come from the sizes
        return {"params": jax.jit(self._draw)(key)}

    def _draw(self, key):
        def value_of(name, k, shape):
            if name in ONES:
                return jnp.ones(shape, F32)
            if name.endswith("bias") or name in ("b1", "b2", "b3"):
                return jnp.zeros(shape, F32)
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
        table = params["embed"]["embedding"]
        t_noise = subtree(noise, "embed", "embedding")
        dtype = table.dtype
        rotary = lm_blocks.rotary_tables(t, self.rotary_dim, self.rope_theta)

        x = perturbed_embed(tokens, table, t_noise, c)
        state = jnp.zeros((t, self.router_hidden_size), F32)
        load = jnp.zeros((self.num_experts,), jnp.int32)
        for name in self._layers():
            x, state, n_pairs = self._layer(
                params[name], subtree(noise, name), c, x, state, rotary,
                dtype)
            load = load + n_pairs
        h = self._norm(params, noise, c, "final_norm", x).astype(dtype)
        score, _ = lm_blocks.score_next_tokens(
            h, tokens, table, t_noise, c, self.head_block, leaf="embed",
            transposed=True)
        with stage(HEAD), part("embed"):
            last = jnp.mean(perturbed_dense(
                h[-self.behaviour_positions:], table, t_noise, c,
                transposed=True), axis=0)
        return score, last, load

    # ----------------------------------------------------------- layers

    # a subclass that replaces ``_dense`` changes the attention's four
    # projections

    @staticmethod
    def _dense(p, noise, c, name, x):
        return lm_blocks.dense(p, noise, c, name, x)

    def _norm(self, p, noise, c, name, y):
        """float32 RMSNorm of ``y`` by the perturbed ``p[name]["scale"]``."""
        return rmsnorm(y, perturbed_leaf(
            p[name]["scale"], subtree(noise, name, "scale"), c),
            self.rms_norm_eps)

    def _layer(self, p, noise, c, x, state, rotary, dtype):
        """One decoder layer: ``(x + cca + moe, the router's state for the
        layer above, pairs per held expert)``."""
        u = self._norm(p, noise, c, "norm1", x).astype(dtype)
        x = x + self._attention(p["attn"], subtree(noise, "attn"), c, u,
                                rotary)
        routed, state, load = self._routed(
            p["moe"], subtree(noise, "moe"), c,
            self._norm(p, noise, c, "norm2", x), state, dtype)
        return x + routed, state, load

    def _routed(self, moe, noise, c, u, below, dtype):
        """``(the held experts' part [T, hidden], the router's state, pairs
        per held expert)`` of the float32 tokens ``u``."""
        logits, state = lm_blocks.state_router(moe, noise, c, u, below,
                                               self.rms_norm_eps)
        experts, weights = lm_blocks.route(
            moe, noise, c, u, top_k=self.num_experts_per_tok, scaling=1.0,
            scoring="softmax", logits=logits, renormalise=False)
        y, load = lm_blocks.routed_experts(
            moe["experts"], subtree(noise, "experts"), c, u.astype(dtype),
            experts, weights, first_held=self.first_expert_held,
            total=self.experts_total)
        return y, state, load

    def _mixed(self, p, noise, c, q_before, k_before):
        """``(q̂ [T, heads, d], k̂ [T, kv heads, d])`` float32, not yet
        rotated: the two convolutions, the mean and the L2 scale of the
        projections' outputs ``q̃``, ``k̃`` (float32)."""
        t, dtype = q_before.shape[0], p["conv_head"].dtype
        nq, d = self.num_attention_heads, self.head_dim

        def leaf(name):
            return perturbed_leaf(p[name], subtree(noise, name), c)

        with stage(MIX), part("conv_time"):
            z = lm_blocks.causal_conv(
                jnp.concatenate([q_before, k_before], axis=1).reshape(t, -1),
                leaf("conv_time"), leaf("conv_time_bias"))
            z = z.reshape(t, self.latent_heads, d).astype(dtype)
        z = lm_blocks.head_conv(z, p["conv_head"], subtree(noise, "conv_head"),
                                c, leaf("conv_head_bias"), self.cca_time1)
        q, k = lm_blocks.qk_mean(z[:, :nq], z[:, nq:], q_before, k_before)
        return (lm_blocks.l2_scale(q, 1.0, self.rms_norm_eps),
                lm_blocks.l2_scale(k, leaf("temperature")[:, None],
                                   self.rms_norm_eps))

    def _attention(self, p, noise, c, u, rotary):
        """Attention inside the latent of ``u [T, hidden]`` (compute
        dtype), and the way back out of it."""
        dtype, t = u.dtype, u.shape[0]
        nq, nkv, d = (self.num_attention_heads, self.num_key_value_heads,
                      self.head_dim)
        q, k = self._mixed(
            p, noise, c,
            self._dense(p, noise, c, "q", u).reshape(t, nq, d),
            self._dense(p, noise, c, "k", u).reshape(t, nkv, d))
        v = lm_blocks.value_shift(
            self._dense(p, noise, c, "v", u).reshape(t, nkv, d)).astype(dtype)
        with stage(ROPE):
            q = lm_blocks.rotate(q, *rotary,
                                 rotary_dim=self.rotary_dim).astype(dtype)
            k = lm_blocks.rotate(k, *rotary,
                                 rotary_dim=self.rotary_dim).astype(dtype)
        ctx = lm_blocks.attention_core(
            q, k, v, num_heads=nq, num_kv_heads=nkv,
            scale=1.0 / math.sqrt(d), block=self.attention_block)
        return self._dense(p, noise, c, "o", ctx)
