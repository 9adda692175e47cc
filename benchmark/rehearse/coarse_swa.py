"""Degraded forms of the sparse-expert decoder whose router reads the layer's
input ahead of attention (``models/window_moe_lm.py``), for the rehearsals
that the reference check has to fail (``test_swa_cell.py``,
``swa_tolerance.py``): a configuration copy names one as its ``policy`` and
nothing else changes.  Each says of itself what the honest model says (the
same heads, band, experts and share), so the file-against-build comparison
passes and only the numbers can give it away."""

import dataclasses

import jax
import jax.numpy as jnp

from estorch_tpu.models import WindowMoELM, lm_blocks


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


@dataclasses.dataclass(frozen=True)
class Fp8Swa(WindowMoELM):
    """The activations every projection AND every expert reads rounded to
    float8_e4m3 (3 bits of mantissa): a forward in a lower precision than
    the configuration states; weights and router stay as they are."""

    @staticmethod
    def _dense(p, noise, c, name, x):
        return WindowMoELM._dense(p, noise, c, name, fp8(x))

    def _experts(self, moe, noise, c, b, experts, weights):
        return WindowMoELM._experts(self, moe, noise, c, fp8(b), experts,
                                    weights)


@dataclasses.dataclass(frozen=True)
class AllBf16Swa(WindowMoELM):
    """Everything the configuration keeps in float32 in bfloat16: the
    residual stream between the layers, every norm's output and the
    router's input and matrix."""

    def _norm(self, p, noise, c, name, y):
        return bf16(WindowMoELM._norm(self, p, noise, c, name, bf16(y)))

    def _routes(self, moe, noise, c, a):
        return WindowMoELM._routes(
            self, {**moe, "router": bf16(moe["router"])}, noise, c, a)

    def _layer(self, p, noise, c, x, kind, rotary, dtype):
        x, load = WindowMoELM._layer(self, p, noise, c, bf16(x), kind,
                                     rotary, dtype)
        return bf16(x), load


@dataclasses.dataclass(frozen=True)
class HalfWindowSwa(WindowMoELM):
    """A band of half the published width."""

    def _band(self, kind):
        band = WindowMoELM._band(self, kind)
        return None if band is None else band // 2


@dataclasses.dataclass(frozen=True)
class DoubleWindowSwa(WindowMoELM):
    """A band of twice the published width."""

    def _band(self, kind):
        band = WindowMoELM._band(self, kind)
        return None if band is None else band * 2


@dataclasses.dataclass(frozen=True)
class RotatedGlobalSwa(WindowMoELM):
    """The global layers rotate their queries and keys as the window layers
    do: a position term where the model has none."""

    def _turns(self, kind):
        return True


@dataclasses.dataclass(frozen=True)
class SiluSwa(WindowMoELM):
    """SiLU for ReLU in the experts' gate: the expert every other model of
    this repository has."""

    def _experts(self, moe, noise, c, b, experts, weights):
        return lm_blocks.routed_experts(
            moe["experts"], lm_blocks.subtree(noise, "experts"), c, b,
            experts, weights, first_held=self.first_expert_held,
            total=self.experts_total)


@dataclasses.dataclass(frozen=True)
class RoutesAfterSwa(WindowMoELM):
    """The routes taken AFTER attention, from the state the experts read,
    as every other expert model of this repository routes."""

    def _experts(self, moe, noise, c, b, experts, weights):
        experts, weights = self._routes(moe, noise, c, b.astype(jnp.float32))
        return WindowMoELM._experts(self, moe, noise, c, b, experts, weights)


@dataclasses.dataclass(frozen=True)
class OtherRankSwa(WindowMoELM):
    """The held experts taken for those of the NEXT share of the group."""

    @property
    def first_expert_held(self) -> int:
        return self.moe_num_primary_experts * (
            (self.expert_group_rank + 1) % self.expert_group_size)
