"""A sparse-expert decoder with latent attention and a next-token-plus-one
head as an ES policy: the DeepSeek-V3 architecture (arXiv 2412.19437), whose
keys the JoyAI-LLM-Flash family's ``config.json`` uses (``model_type``
``joyai_llm_flash``).  Per token sequence ``[T]``:

    x = E[tokens]
    each layer:   x += attn(rmsnorm₁ x);   x += ffn(rmsnorm₂ x)
    attn(u):  c_q = rmsnorm(u W_qa);  q = c_q W_qb -> heads x [q_nope ; q_rope]
              [c_kv ; k_rope] = u W_kva;  [k_nope ; v] per head = rmsnorm(c_kv) W_kvb
              q_rope, k_rope rotated by position (interleaved pairs); k_rope
              is ONE vector, read by every head
              score = [q_nope;q_rope]·[k_nope;k_rope] / √(nope + rope), causal
              softmax, context = P·v -> W_o
    ffn, "dense" layers:  W_down(silu(W_gate u) ⊙ W_up u)
    ffn, "moe" layers:    s = sigmoid(u W_r);  idx = top-k of (s + b);
                          w = scaling · s[idx] / Σ s[idx]
                          y = shared(u) + Σ_{k: idx_k held here} w_k · expert_{idx_k}(u)
    h = rmsnorm_final(x);  main_t = log p(tokens[t+1] | …) from h W_head
    MTP (one module):  z_t = [rmsnorm_e(E[tokens[t+1]]) ; rmsnorm_h(h_t)] W_eh
                       z <- one more "moe" layer of its own
                       mtp_t = log p(tokens[t+2] | …) from rmsnorm_mtp(z) W_head
    score_t = main_t + mtp_lambda · mtp_t      (mtp_t = 0 past the end)
    behaviour = the MAIN head's logits, averaged over the last
                ``behaviour_positions`` positions

Latent attention (MLA): queries through a rank-``q_lora_rank`` bottleneck
with a norm, keys and values through a rank-``kv_lora_rank`` latent with a
norm; a head is ``qk_nope_head_dim + qk_rope_head_dim`` wide where it is
scored and ``v_head_dim`` wide where it is summed.  The core that takes
those two widths is ``lm_blocks.attention_core``, the one ``HybridLM`` and
``LoopedLM`` call.

The expert layer is told which experts it holds (``lm_blocks``): the
router scores ``n_routed_experts · expert_group_size`` experts, this
program holds the ``n_routed_experts`` of share ``expert_group_rank``, and
what the others would have added is left out (the partial result goes on to
the next layer).  ``expert_group_size`` 1 is the uncut model.  The selection
bias ``b`` is a leaf of the tree, perturbed and updated like any other: ES
has no gradient to keep it out of, and a value that moves the choice and
never the weights is one forward-only search can tune.  Router and bias
stay float32 in the copy the forward reads (``float32_leaves``).

Every size is a constructor argument; the published ones live in the
benchmark's configuration file only.  Precision as ``lm_blocks`` states:
matmul operands in the dtype of the parameters handed in, float32
accumulation; residual stream, norms, rotation, softmax, router and
log-softmax in float32.

As an ES policy the module maps ``tokens [T]`` to ``(score [T-1], the MAIN
head's logits averaged over the last ``behaviour_positions`` positions
[vocab], (token, k) pairs per held expert summed over the expert layers
[held])``; ``TokenScoreEnv`` scores the first two.  Why a mean and not the
last position alone, as the other two models give: a token's choice of
experts is discrete, and ONE token whose eighth and ninth scores lie within
rounding of each other moves its own logits by a third of their spread when
it picks the other (measured on the chip: one member in 25, PERF.md §6); a
mean over hundreds of positions does not jump, so whoever compares
behaviours compares the weights and not one coin.  Left out: group-limited routing (``n_group`` and ``topk_group``
must be 1), the rotary scale correction (``rope_scaling`` null: the class has
no argument for one; the scaling the tree has is
``lm_blocks.rotary_tables(scaling=)``, read by
models/gated_window_moe_lm.py), more than one MTP module.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from ..obs.trace import DENSE, HEAD, ROPE, part, stage
from ..ops import pallas_attention, pallas_combine, pallas_head
from . import lm_blocks
from .lm_blocks import layer_name, rmsnorm, subtree
from .perturbed import (F32, MODEL_AXIS, PolicyDeclaration, leaf_columns,
                        perturbed_dense, perturbed_embed, perturbed_leaf)

# How this model's leaves (``param_shapes``) are cut over a mesh's ``model``
# axis: the decoder's frame and the expert layer (models/lm_blocks.py), and
# latent attention's own.  Its up-projections go by head (column-parallel,
# closed by the row-parallel ``attn/o``); its two down-projections are
# narrow and feed a norm over their whole width, so they replicate, as do
# those norms and the MTP module's; the MTP module's ``eh`` is
# column-parallel.
PARTITION_RULES = (
    lm_blocks.DECODER_PARTITION_RULES + lm_blocks.EXPERT_PARTITION_RULES + (
        (r"attn/(q_b|kv_b)$", P(None, MODEL_AXIS)),
        (r"attn/(q_a|kv_a)$", P()),
        (r"(q_norm|kv_norm|embed_norm|hidden_norm)/scale$", P()),
        (r"mtp/eh$", P(None, MODEL_AXIS)),
    ))

DENSE_LAYER, MOE_LAYER = "dense", "moe"
EXPERT_LEAVES = ("gate", "up", "down")


@dataclasses.dataclass(frozen=True)
class MoELM:
    layer_types: Sequence[str]
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_attention_heads: int = 4
    q_lora_rank: int = 16
    kv_lora_rank: int = 8
    qk_nope_head_dim: int = 8
    qk_rope_head_dim: int = 4
    v_head_dim: int = 8
    n_routed_experts: int = 8          # held HERE
    expert_group_size: int = 1         # chips that share a layer's experts
    expert_group_rank: int = 0         # which share this program holds
    n_shared_experts: int = 1
    num_experts_per_tok: int = 2
    routed_scaling_factor: float = 1.0
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    rope_interleave: bool = True
    num_nextn_predict_layers: int = 1
    mtp_lambda: float = 0.1
    behaviour_positions: int = 512
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    init_std: float = 0.02
    attention_block: int = 512
    head_block: int = 512

    is_recurrent = False
    use_vbn = False

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        bad = set(self.layer_types) - {DENSE_LAYER, MOE_LAYER}
        if bad or not self.layer_types:
            raise ValueError(f"layer_types holds {sorted(bad)}; the kinds "
                             f"are {DENSE_LAYER!r} and {MOE_LAYER!r}")
        lm_blocks.refuse_unwritten(self, {
            "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
            "scoring_func": "sigmoid", "topk_method": "noaux_tc",
            "rope_interleave": True, "n_shared_experts": 1,
            "num_nextn_predict_layers": 1, "tie_word_embeddings": False})
        if self.qk_rope_head_dim % 2:
            raise ValueError(f"qk_rope_head_dim {self.qk_rope_head_dim} must "
                             "be even: the rotation turns pairs")
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
        return self.n_routed_experts * self.expert_group_size

    @property
    def first_expert_held(self) -> int:
        return self.n_routed_experts * self.expert_group_rank

    @property
    def qk_head_dim(self) -> int:
        """A head's width where it is scored."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def _layer_shapes(self, kind: str) -> dict:
        h, nh = self.hidden_size, self.num_attention_heads
        tree: dict[str, Any] = {
            "norm1": {"scale": (h,)}, "norm2": {"scale": (h,)},
            "attn": {
                "q_a": (h, self.q_lora_rank),
                "q_norm": {"scale": (self.q_lora_rank,)},
                "q_b": (self.q_lora_rank, nh * self.qk_head_dim),
                "kv_a": (h, self.kv_lora_rank + self.qk_rope_head_dim),
                "kv_norm": {"scale": (self.kv_lora_rank,)},
                "kv_b": (self.kv_lora_rank,
                         nh * (self.qk_nope_head_dim + self.v_head_dim)),
                "o": (nh * self.v_head_dim, h)},
        }
        if kind == DENSE_LAYER:
            ff = self.intermediate_size
            tree["mlp"] = {"gate": (h, ff), "up": (h, ff), "down": (ff, h)}
        else:
            e, w = self.n_routed_experts, self.moe_intermediate_size
            tree["moe"] = {
                "router": (h, self.experts_total),
                "router_bias": (self.experts_total,),
                "shared": {"gate": (h, w), "up": (h, w), "down": (w, h)},
                "experts": {"gate": (e, h, w), "up": (e, h, w),
                            "down": (e, w, h)}}
        return tree

    def param_shapes(self) -> dict:
        """The parameter tree as shapes (float32)."""
        h = self.hidden_size
        tree: dict[str, Any] = {
            "embed": {"embedding": (self.vocab_size, h)},
            "head": {"kernel": (h, self.vocab_size)},
            "final_norm": {"scale": (h,)},
            "mtp": {"embed_norm": {"scale": (h,)},
                    "hidden_norm": {"scale": (h,)},
                    "eh": (2 * h, h),
                    "layer": self._layer_shapes(MOE_LAYER),
                    "final_norm": {"scale": (h,)}},
        }
        for i, kind in enumerate(self.layer_types):
            tree[layer_name(i)] = self._layer_shapes(kind)
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s, F32), tree,
            is_leaf=lambda s: isinstance(s, tuple))

    def _moe_paths(self) -> list[str]:
        return ["mtp/layer/moe"] + [
            f"{layer_name(i)}/moe" for i, kind in enumerate(self.layer_types)
            if kind == MOE_LAYER]

    @property
    def stacked_leaves(self) -> tuple:
        """The leaves whose leading axis indexes experts: one factor pair
        per expert (ops/lowrank.py)."""
        return tuple(f"{p}/experts/{n}" for p in self._moe_paths()
                     for n in EXPERT_LEAVES)

    @property
    def float32_leaves(self) -> tuple:
        """Leaves the forward reads in float32 whatever the compute dtype:
        the routers and their selection biases."""
        return tuple(f"{p}/{n}" for p in self._moe_paths()
                     for n in ("router", "router_bias"))

    def declaration(self) -> PolicyDeclaration:
        """What the engine that runs this model and the run's records read
        of it, stated once (models/perturbed.py::PolicyDeclaration)."""
        # rows a stacked expert leaf is applied to per position: the (token,
        # k) pairs routed to the held experts, with the expert layer's margin
        rows = (self.num_experts_per_tok * lm_blocks.EXPERT_CAPACITY_MARGIN
                / self.expert_group_size)
        return PolicyDeclaration(
            partition_rules=PARTITION_RULES,
            kernels=(
                # (a head's own query/key part, the rotated part whose key
                # all heads share, the value width)
                (pallas_attention.attention_facts,
                 ((self.qk_nope_head_dim, self.qk_rope_head_dim,
                   self.v_head_dim), None, None, self.num_attention_heads)),
                # the width the next-token head contracts
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
            facts={"experts_held": self.n_routed_experts,
                   "experts_total": self.experts_total,
                   "experts_per_token": self.num_experts_per_tok,
                   "mtp_depth": self.num_nextn_predict_layers})

    # ------------------------------------------------------------- init

    def init(self, key, tokens=None) -> dict:
        """``{"params": tree}``, drawn in ONE jitted program: matrices and
        embedding normal ``init_std``, norm scales 1, selection biases 0."""
        del tokens  # flax's signature; the shapes come from the sizes
        return {"params": jax.jit(self._draw)(key)}

    def _draw(self, key):
        def value_of(name, k, shape):
            if name == "scale":
                return jnp.ones(shape, F32)
            if name == "router_bias":
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
        ``tokens [T]``: ``(main_t + mtp_lambda · mtp_t [T-1], the main
        head's logits averaged over the last ``behaviour_positions``
        positions [vocab], pairs per held expert [held])``."""
        main, mtp, last, load = self.heads(params, noise, c, tokens)
        with stage(HEAD):
            return main + self.mtp_lambda * mtp, last, load

    def heads(self, params, noise, c, tokens):
        """``(main_t = log p(tokens[t+1] | tokens[:t+1]) [T-1], mtp_t =
        log p(tokens[t+2] | tokens[:t+2]) [T-1] with 0 where t+2 is past
        the end, the main head's logits averaged over the last
        ``behaviour_positions`` positions [vocab], pairs per held expert
        over all expert layers [held])``, float32."""
        t = tokens.shape[0]
        dtype = params["embed"]["embedding"].dtype
        rotary = lm_blocks.rotary_tables(t, self.qk_rope_head_dim,
                                         self.rope_theta)
        table, t_noise = params["embed"]["embedding"], subtree(
            noise, "embed", "embedding")
        kernel, k_noise = params["head"]["kernel"], subtree(
            noise, "head", "kernel")

        def norm(p, n, name, y):
            return self._norm(p, n, c, name, y)

        def scored(h32, targets):
            return lm_blocks.score_next_tokens(
                h32.astype(dtype), targets, kernel, k_noise, c,
                self.head_block, leaf="head")

        x = perturbed_embed(tokens, table, t_noise, c)
        load = jnp.zeros((self.n_routed_experts,), jnp.int32)
        for i, kind in enumerate(self.layer_types):
            x, n_pairs = self._layer(kind, params[layer_name(i)],
                                     subtree(noise, layer_name(i)), c, x,
                                     rotary, dtype)
            load = load + n_pairs
        h = norm(params, noise, "final_norm", x)
        main, _ = scored(h, tokens)
        with stage(HEAD), part("head"):
            last = jnp.mean(perturbed_dense(
                h.astype(dtype)[-self.behaviour_positions:], kernel, k_noise,
                c), axis=0)

        # the MTP module reads position t's state beside token t+1 and
        # predicts token t+2; the last position has no next token (it is
        # given token 0: causal, so nothing before it sees that)
        p, n = params["mtp"], subtree(noise, "mtp")
        shifted = jnp.concatenate([tokens[1:], jnp.zeros((1,), tokens.dtype)])
        with stage(DENSE):
            rows = perturbed_embed(shifted, table, t_noise, c)
            with part("eh"):        # the operand ``eh`` multiplies
                both = jnp.concatenate(
                    [norm(p, n, "embed_norm", rows),
                     norm(p, n, "hidden_norm", h)], axis=-1).astype(dtype)
        z = lm_blocks.dense(p, n, c, "eh", both)
        z, n_pairs = self._layer(MOE_LAYER, p["layer"], subtree(n, "layer"),
                                 c, z, rotary, dtype)
        mtp, _ = scored(norm(p, n, "final_norm", z), shifted)
        with stage(HEAD):
            mtp = jnp.where(jnp.arange(t - 1) < t - 2, mtp, 0.0)
        return main, mtp, last, load + n_pairs

    # ----------------------------------------------------------- layers

    # a subclass that replaces ``_dense`` changes every 2-D projection

    @staticmethod
    def _dense(p, noise, c, name, x):
        return lm_blocks.dense(p, noise, c, name, x)

    def _norm(self, p, noise, c, name, y):
        """float32 RMSNorm of ``y`` by the perturbed ``p[name]["scale"]``."""
        return rmsnorm(y, perturbed_leaf(
            p[name]["scale"], subtree(noise, name, "scale"), c),
            self.rms_norm_eps)

    def _layer(self, kind, p, noise, c, x, rotary, dtype):
        """One decoder layer: ``(x + attn + ffn, pairs per held expert)``."""
        def norm(name, y):
            return self._norm(p, noise, c, name, y)

        x = x + self._attention(p["attn"], subtree(noise, "attn"), c,
                                norm("norm1", x).astype(dtype), rotary)
        u = norm("norm2", x)
        if kind == DENSE_LAYER:
            return x + lm_blocks.gated_mlp(
                self._dense, p["mlp"], subtree(noise, "mlp"), c,
                u.astype(dtype)), jnp.zeros((self.n_routed_experts,),
                                            jnp.int32)
        moe, m_noise = p["moe"], subtree(noise, "moe")
        routed, load = lm_blocks.routed_ffn(
            moe, m_noise, c, u, dtype, top_k=self.num_experts_per_tok,
            scaling=self.routed_scaling_factor,
            first_held=self.first_expert_held, total=self.experts_total)
        u = u.astype(dtype)
        with part("shared"):    # its leaves read ``shared.gate`` … in a trace
            shared = lm_blocks.gated_mlp(
                self._dense, moe["shared"], subtree(m_noise, "shared"), c, u)
        return x + shared + routed, load

    def _attention(self, p, noise, c, u, rotary):
        """Latent attention of ``u [T, hidden]`` (compute dtype)."""
        dtype, t = u.dtype, u.shape[0]
        nh, dn, dr, dv = (self.num_attention_heads, self.qk_nope_head_dim,
                          self.qk_rope_head_dim, self.v_head_dim)

        def norm(name, y):
            return self._norm(p, noise, c, name, y).astype(dtype)

        c_q = norm("q_norm", self._dense(p, noise, c, "q_a", u))

        def q_part(cols):
            # q_b a part at a time: what each head scores with its own key
            # goes to the core as the matmul writes it, and only the part
            # to rotate takes the rotation's layout
            w, n = leaf_columns(p["q_b"], subtree(noise, "q_b"), nh, cols)
            return self._dense({"q_b": w}, None if n is None else {"q_b": n},
                               c, "q_b", c_q).reshape(t, nh, -1)

        q_nope, q_rope = q_part(slice(0, dn)), q_part(slice(dn, None))
        kv_a = self._dense(p, noise, c, "kv_a", u)
        kv = self._dense(p, noise, c, "kv_b", norm(
            "kv_norm", kv_a[:, :self.kv_lora_rank])).reshape(t, nh, dn + dv)
        with stage(ROPE):
            q_rope = lm_blocks.rotate(q_rope, *rotary, interleaved=True)
            k_rope = lm_blocks.rotate(kv_a[:, None, self.kv_lora_rank:],
                                      *rotary, interleaved=True)[:, 0]
        # kv_b wrote each head's unrotated key with its values beside it:
        # the core reads both out of that one array
        ctx = lm_blocks.attention_core(
            q_nope.astype(dtype), kv.astype(dtype), None,
            q_shared=q_rope.astype(dtype), k_shared=k_rope.astype(dtype),
            num_heads=nh, num_kv_heads=nh, scale=1.0 / math.sqrt(dn + dr),
            block=self.attention_block)
        return self._dense(p, noise, c, "o", ctx)
