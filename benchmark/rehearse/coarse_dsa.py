"""Degraded forms of the sparse-expert decoder whose attention reads a learned
selection of keys, for the rehearsals that the reference check has to fail
(``test_dsa_cell.py``, ``dsa_tolerance.py``): a configuration copy names one
as its ``policy`` and nothing else changes.  Each says of itself what the
honest model says (the same heads, ``topk``, experts and share), so the
file-against-build comparison passes and only the numbers can give it away.
Where the model calls a piece of ``lm_blocks`` by name, the degraded form
stands in for that piece while its own forward is traced."""

import contextlib
import dataclasses

import jax
import jax.numpy as jnp

from estorch_tpu.models import IndexedMoELM, lm_blocks
from estorch_tpu.models.perturbed import F32, perturbed_dense


def fp8(x):
    """``x`` rounded to float8_e4m3 and back.  The barrier makes the
    compiler WRITE the rounded array: a round trip left inside one fusion
    is dropped on the TPU (it may keep excess precision), as the experts'
    input's was: without the barrier this form read 0.0008 on the v5e
    where it reads 0.0025 with it and 0.0029 on the CPU (PERF.md §6, PR
    39)."""
    return jax.lax.optimization_barrier(
        x.astype(jnp.float8_e4m3fn)).astype(x.dtype)


@contextlib.contextmanager
def standing_in(name, replacement):
    honest = getattr(lm_blocks, name)
    setattr(lm_blocks, name, replacement(honest))
    try:
        yield
    finally:
        setattr(lm_blocks, name, honest)


def _traced_with(name, replacement):
    """``perturbed_apply`` of ``IndexedMoELM`` with ``lm_blocks.<name>``
    replaced while it is traced."""
    def perturbed_apply(self, params, noise, c, tokens, positions=None):
        with standing_in(name, replacement):
            return IndexedMoELM.perturbed_apply(self, params, noise, c,
                                                tokens, positions)
    return perturbed_apply


@dataclasses.dataclass(frozen=True)
class Fp8Dsa(IndexedMoELM):
    """The activations every projection (the indexer's among them) AND every
    expert reads rounded to float8_e4m3 (3 bits of mantissa): a forward in a
    lower precision than the configuration states; weights, router and the
    indexer's float32 leaves stay as they are."""

    @staticmethod
    def _dense(p, noise, c, name, x, under=lm_blocks.DENSE):
        return lm_blocks.dense(p, noise, c, name, fp8(x), under=under)

    def _index_weights(self, p, noise, c, u):
        return IndexedMoELM._index_weights(self, p, noise, c, fp8(u))

    perturbed_apply = _traced_with(
        "routed_experts", lambda honest: lambda p, noise, c, u, *a, **kw:
        honest(p, noise, c, fp8(u), *a, **kw))


def _select_with(topk_of):
    """``_select`` of ``IndexedMoELM`` with ``topk_of(model, T)`` keys a
    query in place of the model's ``topk``."""
    def _select(self, p, noise, c, u, dtype, index_rotary):
        other = dataclasses.replace(self, topk=topk_of(self, u.shape[0]))
        return IndexedMoELM._select(other, p, noise, c, u, dtype,
                                    index_rotary)
    return _select


@dataclasses.dataclass(frozen=True)
class IgnoredSelectionDsa(IndexedMoELM):
    """The selection ignored: every query attends to every visible key (full
    causal attention), the indexer computed and thrown away."""

    _select = _select_with(lambda model, t: t)


@dataclasses.dataclass(frozen=True)
class HalfTopkDsa(IndexedMoELM):
    """Half as many keys a query as the configuration states."""

    _select = _select_with(lambda model, t: model.topk // 2)


def _no_relu(_honest):
    def index_scores(q_i, k_i, w, first_query):
        dots = jnp.einsum("qhd,sd->qhs", q_i, k_i,
                          preferred_element_type=F32)
        scores = jnp.sum(dots * w[:, :, None], axis=1)
        queries = first_query + jnp.arange(q_i.shape[0])[:, None]
        return jnp.where(jnp.arange(k_i.shape[0])[None, :] <= queries,
                         scores, -jnp.inf)
    return index_scores


@dataclasses.dataclass(frozen=True)
class NoReluDsa(IndexedMoELM):
    """``relu`` left out of the indexer: ``I = sum_j w_j (q_j . k)``, which
    orders the keys otherwise."""

    perturbed_apply = _traced_with("index_scores", _no_relu)


@dataclasses.dataclass(frozen=True)
class OnesWeightsDsa(IndexedMoELM):
    """The indexer's per-head weights ``w`` replaced by ones: every index
    head counts alike (and none negatively)."""

    def _index_weights(self, p, noise, c, u):
        return jnp.ones((u.shape[0], self.indexer_num_heads), F32)


def _sigmoid_route(_honest):
    def route(p, noise, c, u, *, top_k, scaling, scoring):
        del scoring
        with jax.default_matmul_precision("highest"):
            s = jax.nn.sigmoid(perturbed_dense(
                u.astype(F32), p["router"].astype(F32),
                None if noise is None else noise["router"], c))
        _, experts = jax.lax.top_k(s, top_k)
        w = jnp.take_along_axis(s, experts, axis=-1)
        return experts, scaling * w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return route


@dataclasses.dataclass(frozen=True)
class SigmoidRouterDsa(IndexedMoELM):
    """The router's scores by a sigmoid each instead of a softmax over all
    experts: the same experts chosen (both are monotone in the logit), other
    weights after the renormalisation."""

    perturbed_apply = _traced_with("route", _sigmoid_route)


@dataclasses.dataclass(frozen=True)
class OtherRankDsa(IndexedMoELM):
    """The held experts taken for those of the NEXT share of the group: the
    pairs routed to experts this program does not hold are computed with the
    weights of the ones it holds."""

    @property
    def first_expert_held(self) -> int:
        return self.num_experts * (
            (self.expert_group_rank + 1) % self.expert_group_size)
