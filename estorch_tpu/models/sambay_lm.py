"""A SambaY decoder with differential attention as an ES policy: the block of
Microsoft's ``phi4flash`` family (Phi-4-mini-flash-reasoning; the
architecture is "SambaY", arXiv 2507.06607, "enhanced with Differential
Attention", arXiv 2410.05258).  A decoder-hybrid-decoder: a self-decoder of
Mamba-1 and sliding-window attention layers, ONE full-attention layer whose
keys and values every attention layer above it reads, and a cross-decoder
whose other layers are Gated Memory Units that gate by the scan output of
the LAST Mamba layer.  No positional encoding anywhere.  Per token sequence:

    x = E[tokens]
    every layer i:  x += mixer_i(LN1(x));  x += down(silu(gate u) ⊙ up u),
                    u = LN2(x)                 (LayerNorm with bias, ε 1e-5)
    h = LN_f(x);  logits = h Eᵀ                (tied embedding)

The mixer by the layer's PUBLISHED index ``i`` of ``L`` (:func:`layer_kinds`;
``mb_per_layer`` 2):

    i even, < L/2     "mamba"      [x̃ | z] = u W_in;  x̃ = silu(conv1d(x̃) + b)
                                   [δ | B | C] = x̃ W_x;  Δ = softplus(δ W_dt + b_dt)
                                   A = -exp(A_log)  [d_inner, d_state]
                                   h_t = exp(Δ_t ⊙ A) ⊙ h_{t-1} + (Δ_t ⊙ x̃_t) ⊗ B_t
                                   y_t = h_t C_t + D ⊙ x̃_t
                                   out = (y ⊙ silu(z)) W_out
    i odd,  < L/2     "window"     differential attention, causal, keys (t - w, t]
    i = L/2           "mamba_mem"  the same Mamba-1 layer; it also hands m = y
                                   (before the gate, with the D term) upward
    i = L/2 + 1       "full_kv"    differential attention, full causal; it also
                                   hands its (K, V) upward
    i even, > L/2     "gmu"        out = (silu(u W₁) ⊙ m) W₂
    i odd,  > L/2+1   "cross"      differential attention whose only projections
                                   are W_q and W_o; keys and values are
                                   full_kv's, full causal

Differential attention: ``[q | k | v] = u W_qkv + b``; ADJACENT heads pair:
``q -> [T, H/2, 2, d]``, ``k -> [T, G/2, 2, d]``, ``v -> [T, G/2, 2d]`` (a
pair's two value heads side by side); diff-head ``j`` reads key/value pair
``j // (H/G)``; ``A₁ = softmax(q₁k₁ᵀ/√d)``, ``A₂ = softmax(q₂k₂ᵀ/√d)`` under
the layer's mask; ``o = (A₁ - λA₂) v``; ``λ = exp(λ_q1·λ_k1) - exp(λ_q2·λ_k2)
+ λ_init``, ``λ_init = 0.8 - 0.6·exp(-0.3·i)`` with ``i`` the published
index; ``o <- RMSNorm(o; γ [2d], ε) · (1 - λ_init)``; out ``concat(o) W_o +
b_o``.  Through ``lm_blocks.attention_core`` this is ONE call, handed the
pairs as published (``paired``): ``H`` score heads of ``d`` over ``G`` key
heads with ONE value block ``2d`` wide a key pair, read by both maps; its
context is ordered (key pair, map, group), where
``lm_blocks.differential_combine`` reads it.  Where Mosaic kernels may be
traced (``pallas_attention.traced_why``) the two
full-causal kinds of layer take the attention kernel, which reads a pair as
the one 128-lane block it is (``d`` 64); the windowed kind takes it only
where its band spans at least one of the kernel's blocks
(``pallas_attention.call_form``: the published 512 keys over 8,192 positions
do not, nor may PAIRS take a narrower band as the block: the XLA form).

Two values are carried ACROSS layers inside one member's forward: ``m [T,
d_inner]`` float32 and ``(K, V)`` in the compute dtype, as the ``qkv``
projection wrote them.  Both are functions
of perturbed leaves, so under the engine's ``vmap``s they are per member.

``layer_indices`` picks WHICH published layers this program holds (a
benchmark's cut keeps one period of each decoder and the two boundary
layers); each keeps the ``λ_init`` of its published index.  A cut must hold
``mamba_mem`` before any ``gmu`` and ``full_kv`` before any ``cross``.

Layout: the published checkpoint's fused ``in_proj`` and ``Wqkv`` are fused
leaves here too (``in_proj``, ``qkv``; on a ``model`` axis GSPMD cuts them
where their halves are read); its fused ``gate_up`` is the two leaves
``gate`` and ``up`` of ``lm_blocks.gated_mlp``.  ``A_log [d_inner,
d_state]`` is 2-D but no matmul reads it: it takes dense noise
(``dense_noise_leaves``), and it, ``D``, ``dt_bias``, the conv taps and the
``λ`` vectors stay float32 in the copy the forward reads
(``float32_leaves``: the decay runs over thousands of steps).

Precision as ``lm_blocks`` states: matmul operands in the dtype of the
parameters handed in, float32 accumulation; residual stream, norms, conv,
``Δ``, decay, state, ``y`` and ``m``, softmax, the differential combine and
the log-softmax in float32.

The scan (:func:`selective_scan`) has two forms, as the attention and the
head have.  ``"xla"``: a ``lax.scan`` over time with the ``[d_state,
d_inner]`` state as its carry, ``scan_chunk`` steps unrolled an iteration;
the state and the decay's operand cross HBM at every step.  ``"kernel"``:
ops/pallas_scan.py, the same recurrence with the state in vector registers
and VMEM, ``Δ``, ``x``, ``B``, ``C`` streamed a time chunk at a time and
``y`` written once.  It takes the kernel inside an engine's
``pallas_attention.kernel_scope`` (so: TPU devices and whole members on a
chip, ``pallas_attention.traced_why``) where its own shapes fit
(``pallas_scan.fits``: ``d_inner`` whole 128-lane blocks, at most 16
states, the sequence whole time chunks of 256), whatever form the attention
takes, and the ``lax.scan`` anywhere else; the run's records say
which (``scan_form``, by ``pallas_scan.scan_facts`` in ``declaration()``
with the scans' widths).  Mamba-1's decay is per (channel, state), so there is no
matmul form of it as Mamba-2's.

As an ES policy the module maps ``tokens [T]`` to ``(log p of each next
token [T-1], the last position's logits [vocab])``, as ``HybridLM`` does.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from ..obs.trace import ATTN, DIFF, GMU, HEAD, SSM, part, stage
from ..ops import pallas_attention, pallas_head, pallas_scan
from . import lm_blocks
from .lm_blocks import layer_name, subtree
from .perturbed import (F32, MODEL_AXIS, PolicyDeclaration, perturbed_dense,
                        perturbed_embed, perturbed_leaf)

# How this model's leaves (``param_shapes``) are cut over a mesh's ``model``
# axis.  Its fused projections are column-parallel (``in_proj``, ``qkv``,
# and the cross layers' ``q``, which the decoder's frame names), closed by
# the row-parallel ``out_proj`` / ``attn/o``; Mamba-1's channels, their conv
# taps, ``dt_proj``'s columns, ``A_log``'s rows, ``dt_bias`` and ``D`` go by
# channel; ``x_proj`` contracts the channels into Δ's rank, B and C, which
# every channel reads (row-parallel, one all-reduce); a gated memory unit is
# a column- then row-parallel pair whose gate product is by channel, like
# the memory it multiplies.  The differential λ vectors and the norm over a
# head pair's values are a head wide and replicate, as do the LayerNorms'
# biases.  The rest is the decoder's frame (models/lm_blocks.py).
PARTITION_RULES = lm_blocks.DECODER_PARTITION_RULES + (
    (r"mamba/in_proj$", P(None, MODEL_AXIS)),
    (r"mamba/conv_kernel$", P(None, None, MODEL_AXIS)),
    (r"mamba/(conv_bias|A_log|D|dt_bias)$", P(MODEL_AXIS)),
    (r"mamba/x_proj$", P(MODEL_AXIS, None)),
    (r"mamba/dt_proj$", P(None, MODEL_AXIS)),
    (r"mamba/out_proj$", P(MODEL_AXIS, None)),
    (r"attn/qkv$", P(None, MODEL_AXIS)),
    (r"attn/(qkv_bias|q_bias)$", P(MODEL_AXIS)),
    (r"attn/(o_bias|subln|lambda_[qk][12])$", P()),
    (r"gmu/gmu_in$", P(None, MODEL_AXIS)),
    (r"gmu/gmu_out$", P(MODEL_AXIS, None)),
    (r"(norm[1-4]|final_norm)/bias$", P()),
)

MAMBA, WINDOW, MAMBA_MEM, FULL_KV, GMU_LAYER, CROSS = (
    "mamba", "window", "mamba_mem", "full_kv", "gmu", "cross")
# the attention kinds, and the part each names its core with in a trace
ATTENTION_PARTS = {WINDOW: "window", FULL_KV: "full", CROSS: "cross"}
LAMBDAS = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")


def layer_kinds(num_layers: int, mb_per_layer: int = 2) -> tuple:
    """The kind of each of a ``num_layers`` deep model's layers: the
    self-decoder's ``mamba`` / ``window`` periods, the two boundary layers,
    the cross-decoder's ``gmu`` / ``cross`` periods."""
    if mb_per_layer != 2 or num_layers % 4 or num_layers < 4:
        raise ValueError("the pattern is written for mb_per_layer 2 and a "
                         "depth that is a multiple of 4; got "
                         f"{mb_per_layer}, {num_layers}")
    half = num_layers // 2
    return tuple(
        (MAMBA if i % 2 == 0 else WINDOW) if i < half
        else MAMBA_MEM if i == half
        else FULL_KV if i == half + 1
        else (GMU_LAYER if i % 2 == 0 else CROSS)
        for i in range(num_layers))


def lambda_init(index: int) -> float:
    """Differential attention's ``λ_init`` at published layer ``index``."""
    return 0.8 - 0.6 * math.exp(-0.3 * index)


def kind_key(kind: str) -> str:
    return ("mamba" if kind in (MAMBA, MAMBA_MEM)
            else "gmu" if kind == GMU_LAYER else "attn")


@dataclasses.dataclass(frozen=True)
class SambaYLM:
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    published_layers: int = 4
    layer_indices: Sequence[int] | None = None   # None: every layer
    mb_per_layer: int = 2
    sliding_window: int = 512
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int | None = None             # None: ceil(hidden / 16)
    layer_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    init_std: float = 0.02
    lambda_std: float = 0.1
    scan_chunk: int = 16
    attention_block: int = 512
    head_block: int = 512

    is_recurrent = False
    use_vbn = False

    def __post_init__(self):
        kinds = layer_kinds(self.published_layers, self.mb_per_layer)
        indices = tuple(range(len(kinds)) if self.layer_indices is None
                        else self.layer_indices)
        object.__setattr__(self, "layer_indices", indices)
        if (not indices or list(indices) != sorted(set(indices))
                or not 0 <= indices[0] <= indices[-1] < len(kinds)):
            raise ValueError(f"layer_indices {indices} must be ascending "
                             f"indices of the {len(kinds)} published layers")
        held = self.layer_types
        for reader, source in ((GMU_LAYER, MAMBA_MEM), (CROSS, FULL_KV)):
            if reader in held and (
                    source not in held
                    or held.index(source) > held.index(reader)):
                raise ValueError(f"a {reader!r} layer reads what the "
                                 f"{source!r} layer hands on: hold it too")
        if not self.tie_word_embeddings:
            raise ValueError("an untied head is not written")
        nq, nkv = self.num_attention_heads, self.num_key_value_heads
        if nq % 2 or nkv % 2 or nq % nkv:
            raise ValueError(
                "differential attention pairs adjacent heads: query and "
                "key/value heads must be even, the first a multiple of the "
                f"second; got {nq}, {nkv}")
        if self.hidden_size % nq:
            raise ValueError("hidden_size must divide into the query heads")

    # ------------------------------------------------------------ sizes

    @property
    def layer_types(self) -> tuple:
        """The kind of each layer HELD, in order."""
        kinds = layer_kinds(self.published_layers, self.mb_per_layer)
        return tuple(kinds[i] for i in self.layer_indices)

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def dt_rank(self) -> int:
        return self.mamba_dt_rank or -(-self.hidden_size // 16)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def kv_shared_by(self) -> int:
        """Layers that read the ``full_kv`` layer's keys and values."""
        return self.layer_types.count(CROSS)

    @property
    def memory_shared_by(self) -> int:
        """Layers that gate by the ``mamba_mem`` layer's scan output."""
        return self.layer_types.count(GMU_LAYER)

    def _mixer_shapes(self, kind: str) -> dict:
        h, d, n = self.hidden_size, self.d_inner, self.mamba_d_state
        r, hd = self.dt_rank, self.head_dim
        q, kv = self.num_attention_heads * hd, self.num_key_value_heads * hd
        if kind in (MAMBA, MAMBA_MEM):
            return {"in_proj": (h, 2 * d),
                    "conv_kernel": (self.mamba_d_conv, 1, d),
                    "conv_bias": (d,), "x_proj": (d, r + 2 * n),
                    "dt_proj": (r, d), "dt_bias": (d,), "A_log": (d, n),
                    "D": (d,), "out_proj": (d, h)}
        if kind == GMU_LAYER:
            return {"gmu_in": (h, d), "gmu_out": (d, h)}
        diff = {**{name: (hd,) for name in LAMBDAS}, "subln": (2 * hd,),
                "o": (q, h), "o_bias": (h,)}
        if kind == CROSS:
            return {"q": (h, q), "q_bias": (q,), **diff}
        return {"qkv": (h, q + 2 * kv), "qkv_bias": (q + 2 * kv,), **diff}

    def param_shapes(self) -> dict:
        """The parameter tree as shapes (float32)."""
        h, ff = self.hidden_size, self.intermediate_size

        def norm():
            return {"scale": (h,), "bias": (h,)}

        tree: dict[str, Any] = {
            "embed": {"embedding": (self.vocab_size, h)},
            "final_norm": norm()}
        for i, kind in enumerate(self.layer_types):
            tree[layer_name(i)] = {
                "norm1": norm(), "norm2": norm(),
                kind_key(kind): self._mixer_shapes(kind),
                "mlp": {"gate": (h, ff), "up": (h, ff), "down": (ff, h)}}
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s, F32), tree,
            is_leaf=lambda s: isinstance(s, tuple))

    def _small_leaves(self, names) -> tuple:
        """The paths of the mixers' leaves among ``names``."""
        return tuple(
            f"{layer_name(i)}/{kind_key(kind)}/{n}"
            for i, kind in enumerate(self.layer_types)
            for n in self._mixer_shapes(kind) if n in names)

    @property
    def dense_noise_leaves(self) -> tuple:
        """2-D leaves no matmul reads: dense noise, whatever the factoring
        rule says of their shape (ops/lowrank.py)."""
        return self._small_leaves(("A_log",))

    @property
    def float32_leaves(self) -> tuple:
        """Leaves the forward reads in float32 whatever the compute dtype:
        what the scan's decay and the differential ``λ`` are made of."""
        return self._small_leaves(
            ("A_log", "D", "dt_bias", "conv_kernel", "conv_bias") + LAMBDAS)

    def declaration(self) -> PolicyDeclaration:
        """What the engine that runs this model and the run's records read
        of it, stated once (models/perturbed.py::PolicyDeclaration)."""
        mamba = {MAMBA, MAMBA_MEM} & set(self.layer_types)
        return PolicyDeclaration(
            partition_rules=PARTITION_RULES,
            kernels=(
                (pallas_attention.attention_facts, (
                    # (a score head's width, no shared part, the value
                    # width): a map's heads are ``head_dim`` wide where they
                    # are scored and a PAIR's values, twice that, where they
                    # are summed; at the published 64 a pair is one 128-lane
                    # block, two score heads side by side over one value
                    # block, which the kernel takes as it lies
                    (self.head_dim, 0, 2 * self.head_dim),
                    self.num_key_value_heads,
                    # (attention layer kind held, the band of its calls of
                    # the core | None), in layer order: a call with a window
                    # takes the kernel or the XLA form by its band
                    # (pallas_attention.call_form)
                    tuple((kind, self.sliding_window if kind == WINDOW
                           else None)
                          for kind in dict.fromkeys(self.layer_types)
                          if kind in ATTENTION_PARTS),
                    self.num_attention_heads)),
                # the width the next-token head contracts
                (pallas_head.head_facts, (self.hidden_size,)),
                # the selective scans, where a Mamba layer is held
                *([(pallas_scan.scan_facts,
                    (self.d_inner, self.mamba_d_state))] if mamba else [])),
            dense_noise_leaves=self.dense_noise_leaves,
            float32_leaves=self.float32_leaves,
            # layers of several kinds, two of which hand state to the
            # layers above them
            facts={"layer_kinds": ",".join(self.layer_types),
                   "window": self.sliding_window,
                   "scan_chunk": self.scan_chunk,
                   "kv_shared_by": self.kv_shared_by,
                   "memory_shared_by": self.memory_shared_by})

    # ------------------------------------------------------------- init

    def init(self, key, tokens=None) -> dict:
        """``{"params": tree}``, drawn in ONE jitted program: matrices and
        embedding normal ``init_std``; norm scales, ``subln`` and ``D`` one,
        biases zero; the Mamba-1 defaults ``A_log = log(1 … d_state)`` per
        state, ``dt_bias`` the inverse softplus of a log-uniform step in
        [1e-3, 1e-1], conv taps and bias uniform ``±1/√d_conv``; the ``λ``
        vectors normal ``lambda_std``."""
        del tokens  # flax's signature; the shapes come from the sizes
        return {"params": jax.jit(self._draw)(key)}

    def _draw(self, key):
        bound = 1.0 / math.sqrt(self.mamba_d_conv)

        def value_of(name, k, shape):
            if name in ("scale", "subln", "D"):
                return jnp.ones(shape, F32)
            if name in ("bias", "qkv_bias", "q_bias", "o_bias"):
                return jnp.zeros(shape, F32)
            if name == "A_log":
                return jnp.broadcast_to(jnp.log(jnp.arange(
                    1, shape[1] + 1, dtype=F32)), shape)
            if name == "dt_bias":
                dt = jnp.exp(jax.random.uniform(
                    k, shape, F32, math.log(1e-3), math.log(1e-1)))
                return dt + jnp.log(-jnp.expm1(-dt))
            if name.startswith("conv_"):
                return jax.random.uniform(k, shape, F32, -bound, bound)
            if name in LAMBDAS:
                return self.lambda_std * jax.random.normal(k, shape, F32)
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
            leaf="embed", transposed=True)

    def logits(self, params, tokens, noise=None, c=0.0):
        h = self.hidden(params, noise, c, tokens)
        with stage(HEAD), part("embed"):
            return perturbed_dense(
                h, params["embed"]["embedding"],
                subtree(noise, "embed", "embedding"), c, transposed=True)

    def hidden(self, params, noise, c, tokens, carried=None):
        """Final-norm hidden states ``[T, hidden]`` in the compute dtype.
        ``carried``: a dict that receives what the boundary layers hand
        upward (``"memory"``, ``"kv"``), for whoever wants to look."""
        dtype = params["embed"]["embedding"].dtype
        x = perturbed_embed(tokens, params["embed"]["embedding"],
                            subtree(noise, "embed", "embedding"), c)
        carried = {} if carried is None else carried
        for i, (kind, index) in enumerate(zip(self.layer_types,
                                              self.layer_indices)):
            name = layer_name(i)
            lp, ln = params[name], subtree(noise, name)
            key = kind_key(kind)
            u = self._norm(lp, ln, c, "norm1", x).astype(dtype)
            x = x + self._mixer(kind, index, lp[key], subtree(ln, key), c, u,
                                carried)
            u = self._norm(lp, ln, c, "norm2", x).astype(dtype)
            x = x + lm_blocks.gated_mlp(self._dense, lp["mlp"],
                                        subtree(ln, "mlp"), c, u)
        return self._norm(params, noise, c, "final_norm", x).astype(dtype)

    # ----------------------------------------------------------- layers

    # a subclass that replaces ``_dense`` changes every projection

    @staticmethod
    def _dense(p, noise, c, name, x, bias=None):
        return lm_blocks.dense(p, noise, c, name, x, bias)

    def _norm(self, p, noise, c, name, y):
        """float32 LayerNorm of ``y`` by the perturbed ``p[name]``."""
        return lm_blocks.layernorm(
            y,
            perturbed_leaf(p[name]["scale"], subtree(noise, name, "scale"), c),
            perturbed_leaf(p[name]["bias"], subtree(noise, name, "bias"), c),
            self.layer_norm_eps)

    def _mixer(self, kind, index, p, noise, c, u, carried):
        """Layer ``index``'s mixer of ``u [T, hidden]`` (compute dtype),
        float32; the boundary layers write what they hand on into
        ``carried`` and the cross-decoder's layers read it there."""
        if kind in (MAMBA, MAMBA_MEM):
            out, y = self._mamba(p, noise, c, u)
            if kind == MAMBA_MEM:
                carried["memory"] = y
            return out
        if kind == GMU_LAYER:
            return self._gmu(p, noise, c, u, carried["memory"])
        return self._attention(kind, index, p, noise, c, u, carried)

    def _mamba(self, p, noise, c, u):
        """``(the Mamba-1 mixer's output [T, hidden], y [T, d_inner])``,
        float32; ``y`` is the scan's output with the ``D`` term, before
        the gate."""
        dtype = u.dtype
        d, n, r = self.d_inner, self.mamba_d_state, self.dt_rank
        xz = self._dense(p, noise, c, "in_proj", u)

        def leaf(name):
            return perturbed_leaf(p[name], subtree(noise, name), c)

        with stage(SSM):
            xs = jax.nn.silu(lm_blocks.causal_conv(
                xz[:, :d], leaf("conv_kernel"), leaf("conv_bias")))
        dbc = self._dense(p, noise, c, "x_proj", xs.astype(dtype))
        delta = self._dense(p, noise, c, "dt_proj",
                            dbc[:, :r].astype(dtype), bias="dt_bias")
        with stage(SSM):
            y = selective_scan(
                xs, jax.nn.softplus(delta), -jnp.exp(leaf("A_log")),
                dbc[:, r:r + n], dbc[:, r + n:], self.scan_chunk)
            y = y + leaf("D") * xs
            gated = (y * jax.nn.silu(xz[:, d:])).astype(dtype)
        return self._dense(p, noise, c, "out_proj", gated), y

    def _gmu(self, p, noise, c, u, memory):
        """Gated Memory Unit: ``(silu(u W₁) ⊙ m) W₂``."""
        gate = self._dense(p, noise, c, "gmu_in", u)
        with stage(GMU):
            gated = (jax.nn.silu(gate) * memory).astype(u.dtype)
        return self._dense(p, noise, c, "gmu_out", gated)

    def _attention(self, kind, index, p, noise, c, u, carried):
        """Differential attention of ``u`` (the module's text): ONE call
        of the shared core with both maps as its heads."""
        dtype, t = u.dtype, u.shape[0]
        nq, nkv, hd = (self.num_attention_heads, self.num_key_value_heads,
                       self.head_dim)
        pairs, group = nkv // 2, nq // nkv
        if kind == CROSS:
            q = self._dense(p, noise, c, "q", u, bias="q_bias")
            k, v = carried["kv"]
            # whoever hands on a copy of a pair's values a map ([T, pairs,
            # 2, 2·hd], the layout before the core took pairs: the
            # benchmark's degraded forms do): one of them
            v = v.reshape(t, pairs, -1, 2 * hd)[:, :, 0]
        else:
            qkv = self._dense(p, noise, c, "qkv", u, bias="qkv_bias")
            q = qkv[:, :nq * hd]
            k = qkv[:, nq * hd:(nq + nkv) * hd].astype(dtype)
            # a pair's values, read by both of its maps
            v = qkv[:, (nq + nkv) * hd:].astype(dtype)
            if kind == FULL_KV:
                carried["kv"] = (k, v)
        # as published: diff-head j = pair · group + g holds its two maps
        # (q₁, q₂) side by side, a key pair (k₁, k₂), and ONE value block
        with stage(ATTN), part(ATTENTION_PARTS[kind]):
            ctx = lm_blocks.attention_core(
                q.astype(dtype), k, v, num_heads=nq, num_kv_heads=nkv,
                scale=1.0 / math.sqrt(hd), block=self.attention_block,
                window=self.sliding_window if kind == WINDOW else None,
                paired=True)

        def leaf(name):
            return perturbed_leaf(p[name], subtree(noise, name), c)

        with stage(DIFF):
            lam = (jnp.exp(jnp.sum(leaf("lambda_q1") * leaf("lambda_k1")))
                   - jnp.exp(jnp.sum(leaf("lambda_q2") * leaf("lambda_k2")))
                   + lambda_init(index))
        out = lm_blocks.differential_combine(
            ctx, lam, leaf("subln"), pairs=pairs, group=group,
            lambda_init=lambda_init(index), eps=self.layer_norm_eps)
        return self._dense(p, noise, c, "o", out.astype(dtype),
                           bias="o_bias")


def selective_scan(x, delta, a, b, c, unroll: int = 1):
    """Mamba-1's recurrence, float32: ``h_t = exp(Δ_t ⊙ A) ⊙ h_{t-1} + (Δ_t
    ⊙ x_t) ⊗ B_t``, ``y_t = h_t C_t``, from ``x, delta [T, d_inner]``, ``a
    [d_inner, d_state]`` (negative), ``b, c [T, d_state]``; ``y [T,
    d_inner]``.  Inside an engine's ``pallas_attention.kernel_scope``,
    where the shapes fit (``pallas_scan.fits``), the Pallas kernel of
    ops/pallas_scan.py, whose state never leaves the chip's registers and
    VMEM; anywhere else a ``lax.scan`` over time, ``unroll`` steps an
    iteration, the state carried ``[d_state, d_inner]``, channels in the
    lanes."""
    interpret = pallas_attention.scoped_interpret()
    if interpret is not None and pallas_scan.fits(
            x.shape[1], a.shape[1], x.shape[0]):
        return pallas_scan.selective_scan(x, delta, a, b, c,
                                          interpret=interpret)
    a = a.astype(F32).T                                 # [N, D]

    def step(h, xs):
        dt, dtx, b_t, c_t = xs
        h = jnp.exp(dt[None, :] * a) * h + b_t[:, None] * dtx[None, :]
        return h, jnp.sum(h * c_t[:, None], axis=0)

    delta = delta.astype(F32)
    _, y = jax.lax.scan(
        step, jnp.zeros(a.shape, F32),
        (delta, delta * x.astype(F32), b.astype(F32), c.astype(F32)),
        unroll=max(1, min(int(unroll), x.shape[0])))
    return y
