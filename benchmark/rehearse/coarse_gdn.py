"""Degraded forms of the decoder of gated-delta-rule and gated full-attention
layers (``models/delta_moe_lm.py``), for the rehearsals that the reference
check has to fail (``test_gdn_cell.py``, ``gdn_tolerance.py``,
``tests/test_delta_moe_lm.py``): a configuration copy names one as its
``policy`` and nothing else changes.  Each says of itself what the honest
model says (the same heads, chunk, experts and share), so the
file-against-build comparison passes and only the numbers can give it
away."""

import dataclasses

import jax
import jax.numpy as jnp

from estorch_tpu.models import DeltaMoELM, lm_blocks
from estorch_tpu.models.delta_moe_lm import gated_delta_rule

HIGHEST = jax.lax.Precision.HIGHEST


def fp8(x):
    """``x`` rounded to float8_e4m3 and back, the rounded array WRITTEN: a
    round trip left inside one fusion is dropped on the TPU
    (``coarse_dsa.fp8``, PERF.md §6, PR 39)."""
    return jax.lax.optimization_barrier(
        x.astype(jnp.float8_e4m3fn)).astype(x.dtype)


def bf16(x):
    """``x`` rounded to bfloat16 and back, the rounded array written."""
    return jax.lax.optimization_barrier(
        x.astype(jnp.bfloat16)).astype(x.dtype)


def recurrence(q, k, v, g, beta, rounded=lambda s: s, corrected=True):
    """The gated delta rule one position after the other, the state passed
    through ``rounded`` after every step; ``corrected=False`` leaves out
    what the state already holds for the key (``- S~ᵀ k``): plain decayed
    linear attention.  Shapes as ``delta_moe_lm.gated_delta_rule``."""
    t, nk, dk = q.shape
    nv, dv = v.shape[1:]
    rep = nv // nk

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = jnp.exp(g_t)[..., None, None] * state
        held = jnp.einsum("hrkv,hk->hrv", state, k_t, precision=HIGHEST)
        write = b_t[..., None] * (v_t - held if corrected else v_t)
        state = rounded(state + k_t[:, None, :, None] * write[:, :, None, :])
        return state, jnp.einsum("hrkv,hk->hrv", state, q_t,
                                 precision=HIGHEST)

    _, o = jax.lax.scan(
        step, jnp.zeros((nk, rep, dk, dv), jnp.float32),
        (q, k, v.reshape(t, nk, rep, dv), g.reshape(t, nk, rep),
         beta.reshape(t, nk, rep)))
    return o.reshape(t, nv, dv)


@dataclasses.dataclass(frozen=True)
class Fp8Gdn(DeltaMoELM):
    """The activations every projection AND every expert reads rounded to
    float8_e4m3 (3 bits of mantissa): a forward in a lower precision than
    the configuration states; weights and router stay as they are."""

    @staticmethod
    def _dense(p, noise, c, name, x):
        return DeltaMoELM._dense(p, noise, c, name, fp8(x))

    def _routed(self, moe, noise, c, b, dtype):
        experts, weights = lm_blocks.route(
            moe, noise, c, b, top_k=self.num_experts_per_tok, scaling=1.0,
            scoring="softmax")
        return lm_blocks.routed_experts(
            moe["experts"], lm_blocks.subtree(noise, "experts"), c,
            fp8(b.astype(dtype)), experts, weights,
            first_held=self.first_expert_held, total=self.experts_total)


@dataclasses.dataclass(frozen=True)
class AllBf16Gdn(DeltaMoELM):
    """Everything the configuration keeps in float32 in bfloat16: the
    residual stream between the layers, every norm's output, the router's
    input and matrix, the decay ``g`` and its inputs, ``beta``, and the
    delta rule's state after every position."""

    def _norm(self, p, noise, c, name, y):
        return bf16(DeltaMoELM._norm(self, p, noise, c, name, bf16(y)))

    def _routed(self, moe, noise, c, b, dtype):
        return DeltaMoELM._routed(
            self, {**moe, "router": bf16(moe["router"])}, noise, c, bf16(b),
            dtype)

    def _layer(self, p, noise, c, x, kind, rotary, dtype):
        x, load = DeltaMoELM._layer(self, p, noise, c, bf16(x), kind, rotary,
                                    dtype)
        return bf16(x), load

    @staticmethod
    def _decay(a, a_log, dt_bias):
        return bf16(DeltaMoELM._decay(bf16(a), bf16(a_log), bf16(dt_bias)))

    def _rule(self, q, k, v, g, beta):
        return recurrence(bf16(q), bf16(k), bf16(v), g, bf16(beta),
                          rounded=bf16)

    def _gated_norm(self, o, scale, z):
        return bf16(DeltaMoELM._gated_norm(self, bf16(o), scale, bf16(z)))


@dataclasses.dataclass(frozen=True)
class NoCorrectionGdn(DeltaMoELM):
    """The correction ``- S~ᵀ k`` dropped: plain decayed linear attention,
    a state that is written and decays and is never corrected."""

    def _rule(self, q, k, v, g, beta):
        return recurrence(q, k, v, g, beta, corrected=False)


@dataclasses.dataclass(frozen=True)
class NoDecayGdn(DeltaMoELM):
    """The decay dropped: ``g = 0``, the delta rule without its gate."""

    @staticmethod
    def _decay(a, a_log, dt_bias):
        return jnp.zeros_like(a)


@dataclasses.dataclass(frozen=True)
class ChunkResetGdn(DeltaMoELM):
    """The state reset to zero at every chunk boundary: each chunk of
    ``delta_chunk`` positions by itself, nothing carried."""

    def _rule(self, q, k, v, g, beta):
        t, size = q.shape[0], min(self.delta_chunk, q.shape[0])
        pad = -t % size

        def chunks(x):
            x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
            return x.reshape((-1, size) + x.shape[1:])

        out = jax.vmap(lambda *xs: gated_delta_rule(*xs, size))(
            chunks(q), chunks(k), chunks(v), chunks(g), chunks(beta))
        return out.reshape((-1,) + out.shape[2:])[:t]


@dataclasses.dataclass(frozen=True)
class NoConvGdn(DeltaMoELM):
    """The causal conv left out: q, k and v pass their SiLU alone."""

    @staticmethod
    def _conv(qkv, taps):
        return jax.nn.silu(qkv)


@dataclasses.dataclass(frozen=True)
class NoConvSiluGdn(DeltaMoELM):
    """The SiLU after the conv left out."""

    @staticmethod
    def _conv(qkv, taps):
        return lm_blocks.causal_conv(qkv, taps, 0.0)


@dataclasses.dataclass(frozen=True)
class NoQkNormGdn(DeltaMoELM):
    """q and k not L2-normalised (q keeps its ``1/√dk``)."""

    @staticmethod
    def _unit(x):
        return x


@dataclasses.dataclass(frozen=True)
class NoNormGateGdn(DeltaMoELM):
    """The gated norm's gate ``silu(z)`` left out."""

    def _gated_norm(self, o, scale, z):
        return lm_blocks.rmsnorm(o, scale, self.rms_norm_eps)


@dataclasses.dataclass(frozen=True)
class NoOutputGateGdn(DeltaMoELM):
    """The attention's output gate ``sigmoid(gate)`` left out."""

    @staticmethod
    def _output_gate(gate):
        return jnp.ones_like(gate)


@dataclasses.dataclass(frozen=True)
class WholeHeadRotationGdn(DeltaMoELM):
    """The rotation over the whole head, not its leading quarter."""

    @property
    def rotary_dim(self) -> int:
        return self.head_dim


@dataclasses.dataclass(frozen=True)
class NoSharedSigmoidGdn(DeltaMoELM):
    """The shared expert added whole: its ``sigmoid(b w_s)`` left out."""

    @staticmethod
    def _shared_scale(opened):
        return jnp.ones_like(opened)
