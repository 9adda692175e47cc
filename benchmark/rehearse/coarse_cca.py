"""Degraded forms of the sparse-expert decoder whose attention is computed
inside a compressed latent, for the rehearsals that the reference check has to
fail (``test_cca_cell.py``, ``cca_tolerance.py``): a configuration copy names
one as its ``policy`` and nothing else changes.  Each says of itself what the
honest model says (the same heads, taps, experts and share), so the
file-against-build comparison passes and only the numbers can give it away.
Where the model calls a piece of ``lm_blocks`` by name, the degraded form
stands in for that piece while its own forward is traced."""

import contextlib
import dataclasses

import jax.numpy as jnp
from coarse_dsa import fp8, standing_in   # the rounding behind a barrier

from estorch_tpu.models import CCAMoELM, lm_blocks


def _traced_with(*stand_ins):
    """``perturbed_apply`` of ``CCAMoELM`` with each ``(name, replacement)``
    of ``lm_blocks`` replaced while it is traced."""
    def perturbed_apply(self, params, noise, c, tokens):
        with contextlib.ExitStack() as stack:
            for name, replacement in stand_ins:
                stack.enter_context(standing_in(name, replacement))
            return CCAMoELM.perturbed_apply(self, params, noise, c, tokens)
    return perturbed_apply


@dataclasses.dataclass(frozen=True)
class Fp8Cca(CCAMoELM):
    """The activations every projection, the head-mixing convolution AND
    every expert reads rounded to float8_e4m3 (3 bits of mantissa): a forward
    in a lower precision than the configuration states; weights and the
    float32 router stay as they are."""

    @staticmethod
    def _dense(p, noise, c, name, x):
        return lm_blocks.dense(p, noise, c, name, fp8(x))

    perturbed_apply = _traced_with(
        ("routed_experts", lambda honest: lambda p, noise, c, u, *a, **kw:
         honest(p, noise, c, fp8(u), *a, **kw)),
        ("head_conv", lambda honest: lambda x, *a, **kw:
         honest(fp8(x), *a, **kw)))


@dataclasses.dataclass(frozen=True)
class NoValueShiftCca(CCAMoELM):
    """The value shift left out: every value head read from ``u_t``."""

    perturbed_apply = _traced_with(("value_shift", lambda honest: lambda v: v))


@dataclasses.dataclass(frozen=True)
class NoMeanCca(CCAMoELM):
    """The q-k mean left out: q and k as the convolutions leave them."""

    perturbed_apply = _traced_with(
        ("qk_mean", lambda honest: lambda q, k, q_before, k_before: (q, k)))


@dataclasses.dataclass(frozen=True)
class NoStateCca(CCAMoELM):
    """``γ = 0``: every layer routes by its own input alone."""

    perturbed_apply = _traced_with(
        ("state_router", lambda honest: lambda p, noise, c, u, below, eps:
         honest(p, noise, c, u, jnp.zeros_like(below), eps)))


@dataclasses.dataclass(frozen=True)
class RenormalisedCca(CCAMoELM):
    """The routing weight renormalised over the ONE chosen expert: 1,
    whatever the router says."""

    perturbed_apply = _traced_with(
        ("route", lambda honest: lambda *a, renormalise, **kw:
         honest(*a, renormalise=True, **kw)))


@dataclasses.dataclass(frozen=True)
class WholeRotationCca(CCAMoELM):
    """The WHOLE head rotated (``partial_rotary_factor`` taken for 1): 64
    frequency pairs ``theta^(-2i/128)`` instead of 32 over the first half."""

    @property
    def rotary_dim(self) -> int:
        return self.head_dim


@dataclasses.dataclass(frozen=True)
class OtherRankCca(CCAMoELM):
    """The held experts taken for those of the NEXT share of the group: the
    tokens routed to experts this program does not hold are computed with the
    weights of the ones it holds."""

    @property
    def first_expert_held(self) -> int:
        return self.num_experts * (
            (self.expert_group_rank + 1) % self.expert_group_size)
