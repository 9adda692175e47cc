"""A looped decoder language model as an ES policy: ByteDance's Ouro family
("Scaling Latent Reasoning via Looped Language Models", ``model_type``
``ouro``).  One stack of identical decoder layers is run ``total_ut_steps``
times over the SAME weights; the final norm closes every pass, an exit gate
(a linear map of the normed state to one logit) gives each pass a
probability, and the model's score is the expected log-likelihood under that
exit distribution.  Per token sequence ``[T]``:

    x⁰ = E[tokens]
    for s = 1 … total_ut_steps:                    (same leaves every pass)
        for each layer:  x += rmsnorm₂(attn(rmsnorm₁(x)))
                         x += rmsnorm₄(W_down(silu(W_gate u) ⊙ W_up u)),
                                                          u = rmsnorm₃(x)
        h^s = rmsnorm_final(x);  x ← h^s          (the next pass reads h^s)
        logits^s = h^s W_head;   λ_s = sigmoid(h^s w_gate + b_gate)
    p_s = λ_s Π_{j<s}(1 − λ_j)  for s < S,   p_S = Π_{j<S}(1 − λ_j)

``attn``: full causal attention, queries and keys rotated by position
(``rope_theta``, halves convention), scores scaled by ``1/√head_dim``.  The
head is NOT tied to the embedding.  Every size is a constructor argument; the
published ones live in the benchmark's configuration file only.

The loop over passes is a ``lax.scan`` whose body is the stack, so the
program holds the stack ONCE however many passes there are; the weights and
the noise tree are closed over, un-batched under a ``vmap`` over members.
One leaf and ONE ``(A, B)`` factor pair serve every pass: the perturbation of
a tied weight is one perturbation, read ``total_ut_steps`` times.

Attention, the gated FFN, the blocked head scorer and the RMSNorm are the
functions ``HybridLM`` calls (models/lm_blocks.py); rotary positions enter
the one attention as its ``rotary`` argument.  Precision as there: matmul
operands in the dtype of the parameters handed in, float32 accumulation;
residual stream, norms, rotation, softmax, gate and exit distribution in
float32.

As an ES policy the module maps a token sequence ``[T]`` to ``(Σ_s p_s ·
log p_s(next token) [T-1], the last pass's logits at the last position
[vocab])``: what ``envs/sequence.py::TokenScoreEnv`` scores, unchanged.
Left out: the entropy term of the published training loss (its coefficient
is a training setting the config does not give), and early exit that SKIPS
passes (every member runs every pass; ``early_exit_threshold`` 1.0 in the
published config means the same).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from ..obs.trace import EXIT, part, stage
from ..ops import pallas_attention, pallas_head
from . import lm_blocks
from .lm_blocks import layer_name, rmsnorm, subtree
from .perturbed import (F32, PolicyDeclaration, perturbed_dense,
                        perturbed_embed, perturbed_leaf)

# How this model's leaves (``param_shapes``) are cut over a mesh's ``model``
# axis: the decoder's frame (models/lm_blocks.py), and the exit gate, one
# column, which replicates.
PARTITION_RULES = lm_blocks.DECODER_PARTITION_RULES + (
    (r"exit_gate/(kernel|bias)$", P()),
)

FULL_ATTENTION = "full_attention"
NORMS = ("norm1", "norm2", "norm3", "norm4")


@dataclasses.dataclass(frozen=True)
class LoopedLM:
    layer_types: Sequence[str]
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_attention_heads: int = 4
    num_key_value_heads: int = 4
    head_dim: int | None = None
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    total_ut_steps: int = 4
    tie_word_embeddings: bool = False
    init_std: float = 0.02
    attention_block: int = 512
    head_block: int = 512

    is_recurrent = False
    use_vbn = False

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        bad = set(self.layer_types) - {FULL_ATTENTION}
        if bad or not self.layer_types:
            raise ValueError(f"layer_types holds {sorted(bad)}; the one kind "
                             f"is {FULL_ATTENTION!r}")
        if self.head_dim is None:
            object.__setattr__(
                self, "head_dim", self.hidden_size // self.num_attention_heads)
        if self.head_dim % 2:
            raise ValueError(f"head_dim {self.head_dim} must be even: the "
                             "rotation pairs its two halves")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of key/value "
                             "heads")
        if self.total_ut_steps < 1:
            raise ValueError(f"total_ut_steps must be >= 1, got "
                             f"{self.total_ut_steps}")
        if self.tie_word_embeddings:
            raise ValueError("a tied head is not written: the published "
                             "model's is its own matrix")

    # ------------------------------------------------------------ sizes

    def param_shapes(self) -> dict:
        """The parameter tree as shapes (float32)."""
        h, ff, hd = self.hidden_size, self.intermediate_size, self.head_dim
        tree: dict[str, Any] = {
            "embed": {"embedding": (self.vocab_size, h)},
            "head": {"kernel": (h, self.vocab_size)},
            "exit_gate": {"kernel": (h, 1), "bias": (1,)},
            "final_norm": {"scale": (h,)},
        }
        for i in range(len(self.layer_types)):
            tree[layer_name(i)] = {
                **{n: {"scale": (h,)} for n in NORMS},
                "attn": {"q": (h, self.num_attention_heads * hd),
                         "k": (h, self.num_key_value_heads * hd),
                         "v": (h, self.num_key_value_heads * hd),
                         "o": (self.num_attention_heads * hd, h)},
                "mlp": {"gate": (h, ff), "up": (h, ff), "down": (ff, h)},
            }
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s, F32), tree,
            is_leaf=lambda s: isinstance(s, tuple))

    def declaration(self) -> PolicyDeclaration:
        """What the engine that runs this model and the run's records read
        of it, stated once (models/perturbed.py::PolicyDeclaration)."""
        return PolicyDeclaration(
            partition_rules=PARTITION_RULES,
            kernels=(
                # heads of ONE width, scored and summed
                (pallas_attention.attention_facts,
                 (self.head_dim, self.num_key_value_heads, None,
                  self.num_attention_heads)),
                # the width the next-token head contracts
                (pallas_head.head_facts, (self.hidden_size,))),
            # the head runs in blocks of ``head_block`` positions: its
            # widest activation is ``[head_block, vocab]``, not ``[T, vocab]``
            leaf_rows={"head/kernel": self.head_block},
            # its passes, and the layer-applications a token goes through
            facts={"loop_steps": self.total_ut_steps,
                   "layer_applications_per_token":
                       self.total_ut_steps * len(self.layer_types)})

    # ------------------------------------------------------------- init

    def init(self, key, tokens=None) -> dict:
        """``{"params": tree}``, drawn in ONE jitted program: matrices and
        embedding normal ``init_std``, norm scales 1, the gate's bias 0."""
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

    def apply(self, variables, tokens):
        """flax's calling convention: ``apply({"params": p}, tokens)`` is
        the policy output of the centre."""
        return self.perturbed_apply(variables["params"], None, 0.0, tokens)

    def perturbed_apply(self, params, noise, c, tokens):
        """The policy output of ``params + c·noise`` for one sequence
        ``tokens [T]``: ``(Σ_s p_s · log p_s(tokens[t+1] | tokens[:t+1])
        [T-1], the last pass's logits at the last position [vocab])``,
        float32.  ``noise`` mirrors ``params`` with ``(A, B)`` factors or a
        dense array at each leaf (ops/lowrank.py ``unpack``); ``None`` is
        the centre alone."""
        logp, exit_p, last = self.passes(params, noise, c, tokens)
        with stage(EXIT):
            score = jnp.sum(exit_p[:, :-1] * logp, axis=0)
        return score, last[-1]

    def passes(self, params, noise, c, tokens):
        """Every pass's ``(log p of each next token [S, T-1], exit
        probability of each position [S, T], last position's logits [S,
        vocab])``, float32, by a ``lax.scan`` over the passes."""
        rotary = lm_blocks.rotary_tables(
            tokens.shape[0], self.head_dim, self.rope_theta)

        def body(carry, step):
            return self._pass(params, noise, c, tokens, rotary, carry, step)

        _, out = jax.lax.scan(body, self._embed(params, noise, c, tokens),
                              jnp.arange(self.total_ut_steps))
        return out

    def _embed(self, params, noise, c, tokens):
        """The loop's carry before the first pass: ``(x⁰ [T, hidden], the
        probability that no pass has exited yet [T])``, float32."""
        x = perturbed_embed(tokens, params["embed"]["embedding"],
                            subtree(noise, "embed", "embedding"), c)
        return x, jnp.ones(tokens.shape, F32)

    def _pass(self, params, noise, c, tokens, rotary, carry, step):
        """One pass of the stack over ``carry``: the next carry and this
        pass's ``(log p [T-1], exit probability [T], last logits)``."""
        x, remain = carry
        dtype = params["embed"]["embedding"].dtype
        for i in range(len(self.layer_types)):
            x = self._layer(params[layer_name(i)],
                            subtree(noise, layer_name(i)), c, x, rotary, dtype)
        h32 = rmsnorm(x, perturbed_leaf(
            params["final_norm"]["scale"],
            subtree(noise, "final_norm", "scale"), c), self.rms_norm_eps)
        h = h32.astype(dtype)
        logp, last = lm_blocks.score_next_tokens(
            h, tokens, params["head"]["kernel"],
            subtree(noise, "head", "kernel"), c, self.head_block,
            leaf="head")
        with stage(EXIT):
            gate = params["exit_gate"]
            with part("exit_gate"):
                lam = jax.nn.sigmoid(
                    perturbed_dense(
                        h, gate["kernel"],
                        subtree(noise, "exit_gate", "kernel"), c)[:, 0]
                    + perturbed_leaf(gate["bias"],
                                     subtree(noise, "exit_gate", "bias"), c))
            # the last pass takes whatever probability is left
            exit_p = jnp.where(step == self.total_ut_steps - 1, remain,
                               lam * remain)
            remain = remain * (1.0 - lam)
        return (h32, remain), (logp, exit_p, last)

    # ----------------------------------------------------------- layers

    # a subclass that replaces ``_dense`` changes every layer projection

    @staticmethod
    def _dense(p, noise, c, name, x):
        return lm_blocks.dense(p, noise, c, name, x)

    def _layer(self, p, noise, c, x, rotary, dtype):
        """One decoder layer with sandwich norms: each sub-layer reads a
        normed state and its output is normed again before the residual
        add."""
        def norm(name, y):
            return rmsnorm(y, perturbed_leaf(
                p[name]["scale"], subtree(noise, name, "scale"), c),
                self.rms_norm_eps)

        a = lm_blocks.causal_attention(
            self._dense, p["attn"], subtree(noise, "attn"), c,
            norm("norm1", x).astype(dtype),
            num_heads=self.num_attention_heads,
            num_kv_heads=self.num_key_value_heads, head_dim=self.head_dim,
            scale=1.0 / math.sqrt(self.head_dim),
            block=self.attention_block, rotary=rotary)
        x = x + norm("norm2", a)
        m = lm_blocks.gated_mlp(self._dense, p["mlp"], subtree(noise, "mlp"),
                                c, norm("norm3", x).astype(dtype))
        return x + norm("norm4", m)
