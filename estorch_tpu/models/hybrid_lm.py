"""A Mamba-2 / grouped-query-attention hybrid language model as an ES policy.

The block of IBM's ``granitemoehybrid`` family without experts (e.g.
``granite-4.0-h-micro``): per layer

    x += residual_multiplier · mixer(rmsnorm(x))
    x += residual_multiplier · mlp(rmsnorm(x))          (gated SiLU)

with ``mixer`` a Mamba-2 layer or causal attention with grouped heads and
no positional encoding, embeddings scaled by ``embedding_multiplier``, tied
output head, logits divided by ``logits_scaling``.  Every size is a
constructor argument; the published ones live in the benchmark's
configuration file only.

Every projection goes through the perturbed-dense primitive
(models/perturbed.py): called with ``noise=None`` this is the plain model;
called by an engine with a member's noise tree and ``c = σ·sign`` it
evaluates ``θ + c·E`` without a perturbed copy of any matrix.  Under a
``vmap`` over members the centre enters un-batched, so each ``x @ W`` is one
population-wide matmul.

Layout.  The published checkpoint fuses ``[z | x B C | dt]`` into one
``in_proj`` and gate/up into one ``input_linear``.  Here they are separate
leaves (``in_z, in_x, in_bc, in_dt``; ``gate, up``), and the conv taps are
split the same way, so that each leaf shards by head or by column over the
``model`` mesh axis and one all-reduce closes each column/row-parallel pair
(parallel/mesh.py ``DEFAULT_PARTITION_RULES``).  The plain reference
(benchmark/reference/hybrid_lm.py) keeps the fused layout and maps one onto
the other.

Precision: matmul operands in the dtype of the parameters handed in (the
engine casts the centre to bfloat16 once a generation), float32
accumulation; residual stream, softmax, the SSM's decay, state and gated
norm, and the log-softmax in float32.

Mamba-2 in the chunked form (chunk ``mamba_chunk_size``): inside a chunk
``y = (C·Bᵀ ∘ decay) · (dt·x)``, across chunks a scan over the per-chunk
states.  Attention in blocks of queries, each scored against the keys it can
see and no others, so that no ``[T, T]`` score exists; the head in blocks of
positions, so that the ``[T, vocab]`` logits never exist.

As an ES policy the module maps a token sequence ``[T]`` to ``(log p of
each next token [T-1], the last position's logits [vocab])``; a sequence
env (envs/sequence.py) turns that into fitness and behaviour.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from ..obs.trace import HEAD, SSM, part, stage
from ..ops import pallas_attention, pallas_head
from . import lm_blocks
from .lm_blocks import (causal_conv as _causal_conv, layer_name,
                        rmsnorm as _rmsnorm, subtree)
from .perturbed import (F32, MODEL_AXIS, PolicyDeclaration, perturbed_dense,
                        perturbed_embed, perturbed_leaf)

MAMBA, ATTENTION = "mamba", "attention"

# How this model's leaves (``param_shapes``) are cut over a mesh's ``model``
# axis.  The Mamba-2 mixer's projections are a column- then row-parallel
# pair (in_z/in_x/in_dt -> out_proj), so one all-reduce closes it; its
# heads, their conv channels, dt, A_log, D and the gated norm go by head; B
# and C (one group, read by every head) replicate.  The rest is the
# decoder's frame (models/lm_blocks.py).
PARTITION_RULES = (
    (r"mamba/(in_z|in_x|in_dt)$", P(None, MODEL_AXIS)),
    (r"mamba/conv_x_kernel$", P(None, None, MODEL_AXIS)),
    (r"mamba/(conv_x_bias|A_log|D|dt_bias|norm_scale)$", P(MODEL_AXIS)),
    (r"mamba/(in_bc|conv_bc_kernel|conv_bc_bias)$", P()),
    (r"mamba/out_proj$", P(MODEL_AXIS, None)),
) + lm_blocks.DECODER_PARTITION_RULES


@dataclasses.dataclass(frozen=True)
class HybridLM:
    layer_types: Sequence[str]
    vocab_size: int
    hidden_size: int
    mamba_n_heads: int
    mamba_d_head: int
    mamba_d_state: int
    mamba_d_conv: int = 4
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    attention_head_dim: int | None = None
    shared_intermediate_size: int = 0
    attention_multiplier: float | None = None
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    rms_norm_eps: float = 1e-5
    init_std: float = 0.02
    attention_block: int = 512
    head_block: int = 512

    is_recurrent = False
    use_vbn = False

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        bad = set(self.layer_types) - {MAMBA, ATTENTION}
        if bad or not self.layer_types:
            raise ValueError(f"layer_types holds {sorted(bad)}; the kinds are "
                             f"{MAMBA!r} and {ATTENTION!r}")
        if self.mamba_n_groups != 1:
            raise ValueError("mamba_n_groups other than 1 is not written")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of key/value "
                             "heads")

    # ------------------------------------------------------------ sizes

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def head_dim(self) -> int:
        return (self.attention_head_dim
                or self.hidden_size // self.num_attention_heads)

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
                (pallas_head.head_facts, (self.hidden_size,))))

    def param_shapes(self) -> dict:
        """The parameter tree as shapes (float32)."""
        h, n, k = self.hidden_size, self.mamba_d_state, self.mamba_d_conv
        d, nh, ff = self.d_inner, self.mamba_n_heads, self.shared_intermediate_size
        hd = self.head_dim
        mamba = {
            "in_z": (h, d), "in_x": (h, d), "in_bc": (h, 2 * n),
            "in_dt": (h, nh),
            "conv_x_kernel": (k, 1, d), "conv_x_bias": (d,),
            "conv_bc_kernel": (k, 1, 2 * n), "conv_bc_bias": (2 * n,),
            "A_log": (nh,), "D": (nh,), "dt_bias": (nh,),
            "norm_scale": (d,), "out_proj": (d, h),
        }
        attn = {
            "q": (h, self.num_attention_heads * hd),
            "k": (h, self.num_key_value_heads * hd),
            "v": (h, self.num_key_value_heads * hd),
            "o": (self.num_attention_heads * hd, h),
        }
        tree: dict[str, Any] = {
            "embed": {"embedding": (self.vocab_size, h)},
            "final_norm": {"scale": (h,)},
        }
        for i, kind in enumerate(self.layer_types):
            tree[layer_name(i)] = {
                "norm1": {"scale": (h,)}, "norm2": {"scale": (h,)},
                kind_key(kind): dict(mamba if kind == MAMBA else attn),
                "mlp": {"gate": (h, ff), "up": (h, ff), "down": (ff, h)},
            }
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s, F32), tree,
            is_leaf=lambda s: isinstance(s, tuple))

    # ------------------------------------------------------------- init

    def init(self, key, tokens=None) -> dict:
        """``{"params": tree}``, drawn in ONE jitted program (the Mamba-2
        defaults: normal ``init_std`` matrices, unit norms and ``D``,
        ``A_log = log U[1, 16]``, ``dt_bias`` the inverse softplus of a
        log-uniform step in [1e-3, 1e-1], conv taps and bias uniform
        ``±1/√d_conv``)."""
        del tokens  # flax's signature; the shapes come from the sizes
        return {"params": jax.jit(self._draw)(key)}

    def _draw(self, key):
        bound = 1.0 / math.sqrt(self.mamba_d_conv)

        def value_of(name, k, shape):
            if name in ("scale", "norm_scale", "D"):
                return jnp.ones(shape, F32)
            if name == "A_log":
                return jnp.log(jax.random.uniform(k, shape, F32, 1.0, 16.0))
            if name == "dt_bias":
                dt = jnp.exp(jax.random.uniform(
                    k, shape, F32, math.log(1e-3), math.log(1e-1)))
                return dt + jnp.log(-jnp.expm1(-dt))
            if name.startswith("conv_"):
                return jax.random.uniform(k, shape, F32, -bound, bound)
            return self.init_std * jax.random.normal(k, shape, F32)

        return lm_blocks.draw_tree(self.param_shapes(), key, value_of)

    # ------------------------------------------------------------ apply

    def apply(self, variables, tokens, method: str | None = None):
        """flax's calling convention: ``apply({"params": p}, tokens)`` is
        the policy output; ``method="logits"`` the whole ``[T, vocab]``
        logits (small sizes only)."""
        p = variables["params"]
        if method == "logits":
            return self.logits(p, tokens)
        return self.perturbed_apply(p, None, 0.0, tokens)

    def perturbed_apply(self, params, noise, c, tokens):
        """The policy output of ``params + c·noise`` for one sequence
        ``tokens [T]``: ``(log p(tokens[t+1] | tokens[:t+1]) [T-1], the last
        position's logits [vocab])``, float32.  ``noise`` mirrors ``params``
        with ``(A, B)`` factors or a dense array at each leaf
        (ops/lowrank.py ``unpack``); ``None`` is the centre alone."""
        h = self.hidden(params, noise, c, tokens)
        # the head reads the embedding transposed: its part is ``embed``
        return lm_blocks.score_next_tokens(
            h, tokens, params["embed"]["embedding"],
            subtree(noise, "embed", "embedding"), c, self.head_block,
            self.logits_scaling, leaf="embed", transposed=True)

    def logits(self, params, tokens, noise=None, c=0.0):
        h = self.hidden(params, noise, c, tokens)
        e_noise = subtree(noise, "embed", "embedding")
        with stage(HEAD), part("embed"):
            return perturbed_dense(
                h, params["embed"]["embedding"], e_noise, c, transposed=True
            ) / self.logits_scaling

    def hidden(self, params, noise, c, tokens):
        """Final-norm hidden states ``[T, hidden]`` in the compute dtype."""
        dtype = params["embed"]["embedding"].dtype
        nz = functools.partial(subtree, noise)
        x = self.embedding_multiplier * perturbed_embed(
            tokens, params["embed"]["embedding"], nz("embed", "embedding"), c)
        for i, kind in enumerate(self.layer_types):
            name = layer_name(i)
            lp = params[name]
            u = _rmsnorm(x, perturbed_leaf(
                lp["norm1"]["scale"], nz(name, "norm1", "scale"), c),
                self.rms_norm_eps).astype(dtype)
            key = kind_key(kind)
            mixer = self._mamba if kind == MAMBA else self._attention
            x = x + self.residual_multiplier * mixer(
                lp[key], nz(name, key), c, u)
            u = _rmsnorm(x, perturbed_leaf(
                lp["norm2"]["scale"], nz(name, "norm2", "scale"), c),
                self.rms_norm_eps).astype(dtype)
            x = x + self.residual_multiplier * self._mlp(
                lp["mlp"], nz(name, "mlp"), c, u)
        return _rmsnorm(x, perturbed_leaf(
            params["final_norm"]["scale"], nz("final_norm", "scale"), c),
            self.rms_norm_eps).astype(dtype)

    # ----------------------------------------------------------- layers

    # the pieces shared with the looped model (models/lm_blocks.py); a
    # subclass that replaces ``_dense`` changes every projection

    @staticmethod
    def _dense(p, noise, c, name, x):
        return lm_blocks.dense(p, noise, c, name, x)

    def _mlp(self, p, noise, c, u):
        return lm_blocks.gated_mlp(self._dense, p, noise, c, u)

    def _attention(self, p, noise, c, u):
        """Block-causal attention with grouped heads and no positional
        encoding (``lm_blocks.causal_attention``)."""
        scale = (self.attention_multiplier
                 if self.attention_multiplier is not None
                 else 1.0 / math.sqrt(self.head_dim))
        return lm_blocks.causal_attention(
            self._dense, p, noise, c, u,
            num_heads=self.num_attention_heads,
            num_kv_heads=self.num_key_value_heads, head_dim=self.head_dim,
            scale=scale, block=self.attention_block)

    def _mamba(self, p, noise, c, u):
        dtype, t = u.dtype, u.shape[0]
        nh, hd, n = self.mamba_n_heads, self.mamba_d_head, self.mamba_d_state
        z = self._dense(p, noise, c, "in_z", u)
        xs = self._dense(p, noise, c, "in_x", u)
        bc = self._dense(p, noise, c, "in_bc", u)
        dt = self._dense(p, noise, c, "in_dt", u)

        def leaf(name):
            return perturbed_leaf(
                p[name], None if noise is None else noise[name], c)

        with stage(SSM):
            xs = jax.nn.silu(_causal_conv(
                xs, leaf("conv_x_kernel"), leaf("conv_x_bias")))
            bc = jax.nn.silu(_causal_conv(
                bc, leaf("conv_bc_kernel"), leaf("conv_bc_bias")))
            dt = jax.nn.softplus(dt + leaf("dt_bias"))          # [T, nh]
            a = -jnp.exp(leaf("A_log"))                          # [nh]
            y = self._ssd(xs.reshape(t, nh, hd), dt, a,
                          bc[:, :n], bc[:, n:], dtype)
            y = y + leaf("D")[:, None] * xs.reshape(t, nh, hd)
            y = y.reshape(t, nh * hd) * jax.nn.silu(z)
            y = _rmsnorm(y, leaf("norm_scale"), self.rms_norm_eps)
        return self._dense(p, noise, c, "out_proj", y.astype(dtype))

    def _ssd(self, x, dt, a, b, c_mat, dtype):
        """Chunked selective scan: ``h_t = exp(dt_t·a)·h_{t-1} + dt_t·x_t⊗b_t``,
        ``y_t = c_t·h_t``.  ``x [T, nh, hd]``, ``dt [T, nh]``, ``b, c [T, N]``
        float32; matmul operands in ``dtype``, decay and state float32."""
        t = x.shape[0]
        length = min(self.mamba_chunk_size, t)
        n_chunks = -(-t // length)
        pad = n_chunks * length - t

        def chunked(v):     # zero dt in the padding: the state passes through
            v = jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
            return v.reshape((n_chunks, length) + v.shape[1:])

        xc, dtc, bc, cc = chunked(x), chunked(dt), chunked(b), chunked(c_mat)
        cum = jnp.cumsum(dtc * a, axis=1)                # [C, L, nh], <= 0
        xdt = (xc * dtc[..., None]).astype(dtype)        # [C, L, nh, hd]
        # inside a chunk: (c_t·b_s) · exp(cum_t - cum_s) for s <= t
        cb = jnp.einsum("cln,csn->cls", cc.astype(dtype), bc.astype(dtype),
                        preferred_element_type=F32)
        causal = jnp.tril(jnp.ones((length, length), bool))
        seg = cum[:, :, None, :] - cum[:, None, :, :]    # [C, L, L, nh]
        decay = jnp.where(causal[None, :, :, None], jnp.exp(
            jnp.where(causal[None, :, :, None], seg, 0.0)), 0.0)
        y = jnp.einsum("clsh,cshd->clhd",
                       (cb[..., None] * decay).astype(dtype), xdt,
                       preferred_element_type=F32)
        # each chunk's own contribution to the state at its end
        to_end = jnp.exp(cum[:, -1:, :] - cum)           # [C, L, nh]
        states = jnp.einsum("cln,clh,clhd->chdn", bc, to_end,
                            xdt.astype(F32))

        def carry(h, xs):
            s_c, total = xs
            return jnp.exp(total)[:, None, None] * h + s_c, h

        _, before = jax.lax.scan(
            carry, jnp.zeros(states.shape[1:], F32), (states, cum[:, -1, :]))
        y = y + jnp.einsum("cln,clh,chdn->clhd", cc, jnp.exp(cum), before)
        return y.reshape((n_chunks * length,) + y.shape[2:])[:t]


def kind_key(kind: str) -> str:
    return "mamba" if kind == MAMBA else "attn"
