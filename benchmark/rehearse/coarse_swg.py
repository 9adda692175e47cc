"""Degraded forms of the sparse-expert decoder whose two kinds of attention
layer differ in head count, band and rotation, with a gate a head
(``models/gated_window_moe_lm.py``), for the rehearsals that the reference
check has to fail (``test_swg_cell.py``, ``swg_tolerance.py``): a
configuration copy names one as its ``policy`` and nothing else changes.  Each
says of itself what the honest model says (the same heads, band, experts and
share), so the file-against-build comparison passes and only the numbers can
give it away.  Plain subclasses: no field is added, and the honest class's
hash (by its text) is theirs."""

import jax
import jax.numpy as jnp

from estorch_tpu.models import GatedWindowMoELM, lm_blocks
from estorch_tpu.models.gated_window_moe_lm import FULL_LAYER, SLIDING_LAYER


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


def _routed(model, moe, noise, c, b, dtype, read=lambda x: x, **over):
    """The honest ``_routed`` with the experts' input through ``read`` and
    ``route``'s arguments replaced by ``over``."""
    experts, weights = lm_blocks.route(moe, noise, c, b, **{
        "top_k": model.num_experts_per_tok,
        "scaling": model.moe_routed_scaling_factor, **over})
    return lm_blocks.routed_experts(
        moe["experts"], lm_blocks.subtree(noise, "experts"), c,
        read(b.astype(dtype)), experts, weights,
        first_held=model.first_expert_held, total=model.experts_total)


class Fp8Swg(GatedWindowMoELM):
    """The activations every projection AND every expert reads rounded to
    float8_e4m3 (3 bits of mantissa): a forward in a lower precision than
    the configuration states; weights and router stay as they are."""

    @staticmethod
    def _dense(p, noise, c, name, x):
        return GatedWindowMoELM._dense(p, noise, c, name, fp8(x))

    def _routed(self, moe, noise, c, b, dtype):
        return _routed(self, moe, noise, c, b, dtype, read=fp8)


class AllBf16Swg(GatedWindowMoELM):
    """Everything the configuration keeps in float32 in bfloat16: the
    residual stream between the layers, every norm's output and the
    router's input and matrix."""

    def _norm(self, p, noise, c, name, y):
        return bf16(GatedWindowMoELM._norm(self, p, noise, c, name, bf16(y)))

    def _routed(self, moe, noise, c, b, dtype):
        return GatedWindowMoELM._routed(
            self, {**moe, "router": bf16(moe["router"])}, noise, c, b, dtype)

    def _layer(self, p, noise, c, x, *rest):
        x, load = GatedWindowMoELM._layer(self, p, noise, c, bf16(x), *rest)
        return bf16(x), load


class NoGateSwg(GatedWindowMoELM):
    """The gate left out: each head's context goes to ``W_o`` as it is."""

    @staticmethod
    def _gate(opened):
        return jnp.ones_like(opened)


class WholeHeadRotationSwg(GatedWindowMoELM):
    """The full layers rotated over the whole head, as the sliding ones
    are: ``partial_rotary_factor`` 0.5 read as 1."""

    def _rope(self, kind):
        group = GatedWindowMoELM._rope(self, kind)
        return ({**group, "partial_rotary_factor": 1.0}
                if kind == FULL_LAYER else group)


class PlainRopeSwg(GatedWindowMoELM):
    """The full layers under plain rope: YaRN's blend of the frequencies
    and its ``attention_factor`` left out."""

    def _rope(self, kind):
        return {**GatedWindowMoELM._rope(self, kind), "rope_type": "default"}


class NoBandSwg(GatedWindowMoELM):
    """The sliding layers' band dropped: every earlier key visible."""

    def _band(self, kind):
        return None


class WiderBandSwg(GatedWindowMoELM):
    """The sliding layers' band widened by one block of the attention."""

    def _band(self, kind):
        band = GatedWindowMoELM._band(self, kind)
        return None if band is None else band + self.attention_block


class FullGroupingSwg(GatedWindowMoELM):
    """The FULL layers' grouping applied to a sliding layer: query head j
    reads key-value head ``j // (full heads / kv heads)`` (the last one
    from there on), as a program that derives ONE group size from
    ``num_attention_heads`` would."""

    def _core(self, q, k, v, kind, heads):
        nkv, t = self.num_key_value_heads, q.shape[0]
        if kind != SLIDING_LAYER or FULL_LAYER not in self.layer_types:
            return GatedWindowMoELM._core(self, q, k, v, kind, heads)
        group = self.heads_of(FULL_LAYER) // nkv
        read = jnp.minimum(jnp.arange(heads) // group, nkv - 1)
        k = jnp.take(k.reshape(t, nkv, -1), read, axis=1)
        v = jnp.take(v.reshape(t, nkv, -1), read, axis=1)
        return lm_blocks.attention_core(
            q, k, v, num_heads=heads, num_kv_heads=heads,
            scale=self.head_dim ** -0.5, block=self.attention_block,
            window=self._band(kind))


class SoftmaxRouterSwg(GatedWindowMoELM):
    """A softmax over all experts where the model scores by sigmoid: the
    same experts chosen, other weights."""

    def _routed(self, moe, noise, c, b, dtype):
        return _routed(self, moe, noise, c, b, dtype, scoring="softmax")


class UnscaledRouterSwg(GatedWindowMoELM):
    """``moe_routed_scaling_factor`` left out: the chosen weights sum 1."""

    def _routed(self, moe, noise, c, b, dtype):
        return _routed(self, moe, noise, c, b, dtype, scaling=1.0)


class NoSharedSwg(GatedWindowMoELM):
    """The shared expert left out."""

    def _shared(self, moe, noise, c, u):
        return jnp.zeros((u.shape[0], self.hidden_size), jnp.float32)


class OtherRankSwg(GatedWindowMoELM):
    """The held experts taken for those of the NEXT share of the group."""

    @property
    def first_expert_held(self) -> int:
        return self.num_experts * (
            (self.expert_group_rank + 1) % self.expert_group_size)
