"""Degraded forms of the sparse-expert sequence model, for the rehearsals that
the reference check has to fail (``test_moe_cell.py``, ``moe_tolerance.py``):
a configuration copy names one as its ``policy`` and nothing else changes.
Each says of itself what the honest model says (256 experts, 8 a token and so
on), so the file-against-build comparison passes and only the numbers can
give it away.  Where the model calls a piece of ``lm_blocks`` by name, the
degraded form stands in for that piece while its own forward is traced."""

import contextlib
import dataclasses

import jax
import jax.numpy as jnp

from estorch_tpu.models import MoELM, lm_blocks
from estorch_tpu.models.perturbed import F32, perturbed_dense, perturbed_leaf


def fp8(x):
    return x.astype(jnp.float8_e4m3fn).astype(x.dtype)


@contextlib.contextmanager
def standing_in(name, replacement):
    honest = getattr(lm_blocks, name)
    setattr(lm_blocks, name, replacement(honest))
    try:
        yield
    finally:
        setattr(lm_blocks, name, honest)


def _traced_with(name, replacement):
    """``perturbed_apply`` of ``MoELM`` with ``lm_blocks.<name>`` replaced
    while it is traced."""
    def perturbed_apply(self, params, noise, c, tokens):
        with standing_in(name, replacement):
            return MoELM.perturbed_apply(self, params, noise, c, tokens)
    return perturbed_apply


@dataclasses.dataclass(frozen=True)
class Fp8Moe(MoELM):
    """The activations every projection AND every expert reads rounded to
    float8_e4m3 (3 bits of mantissa): a forward in a lower precision than
    the configuration states; weights and router stay as they are."""

    @staticmethod
    def _dense(p, noise, c, name, x):
        return lm_blocks.dense(p, noise, c, name, fp8(x))

    perturbed_apply = _traced_with(
        "routed_experts", lambda honest: lambda p, noise, c, u, *a, **kw:
        honest(p, noise, c, fp8(u), *a, **kw))


def _bf16_route(honest):
    def route(p, noise, c, u, *, top_k, scaling):
        half = jnp.bfloat16
        s = jax.nn.sigmoid(perturbed_dense(
            u.astype(half), p["router"].astype(half),
            None if noise is None else noise["router"], c))
        bias = perturbed_leaf(
            p["router_bias"],
            None if noise is None else noise["router_bias"], c)
        _, experts = jax.lax.top_k(s + bias, top_k)
        w = jnp.take_along_axis(s, experts, axis=-1)
        return experts, scaling * w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return route


@dataclasses.dataclass(frozen=True)
class Bf16RouterMoe(MoELM):
    """The router's matmul on bfloat16 operands: scores good to three
    digits, so that tokens whose eighth and ninth scores lie closer pick
    another expert than the float32 router does."""

    perturbed_apply = _traced_with("route", _bf16_route)


def _dropped_down(honest):
    def routed_experts(p, noise, c, u, *a, **kw):
        if noise is not None:
            noise = {**noise, "down": tuple(
                jnp.zeros_like(f) for f in noise["down"])}
        return honest(p, noise, c, u, *a, **kw)
    return routed_experts


@dataclasses.dataclass(frozen=True)
class DroppedExpertCorrectionMoe(MoELM):
    """The per-(member, expert) rank-r correction left out of ONE stacked
    leaf (the experts' down projection, in every expert layer): part of the
    mathematics missing."""

    perturbed_apply = _traced_with("routed_experts", _dropped_down)


@dataclasses.dataclass(frozen=True)
class NoMtpMoe(MoELM):
    """The MTP term left out of the score: the main head alone."""

    def perturbed_apply(self, params, noise, c, tokens):
        main, _, last, load = self.heads(params, noise, c, tokens)
        return main, last, load


def _bias_in_weights(honest):
    def route(p, noise, c, u, *, top_k, scaling):
        experts, _ = honest(p, noise, c, u, top_k=top_k, scaling=scaling)
        with jax.default_matmul_precision("highest"):
            s = jax.nn.sigmoid(perturbed_dense(
                u.astype(F32), p["router"].astype(F32),
                None if noise is None else noise["router"], c))
        s = s + perturbed_leaf(
            p["router_bias"],
            None if noise is None else noise["router_bias"], c)
        w = jnp.take_along_axis(s, experts, axis=-1)
        return experts, scaling * w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return route


@dataclasses.dataclass(frozen=True)
class BiasInWeightsMoe(MoELM):
    """The selection bias added to the WEIGHTS too (it belongs in the
    choice only)."""

    perturbed_apply = _traced_with("route", _bias_in_weights)
