"""Decomposed population forward: one shared matmul + a noise term.

For a linear layer with shared center weights W and per-member noise E_i,

    z_i = x_i @ (W + c_i E_i)  =  x_i @ W  +  c_i (x_i @ E_i),   c_i = σ s_i

— exact (a reordering of the same contractions, not an approximation).  The
materialized path builds W + c_i E_i per member, so every layer is a batched
per-member matvec that streams one weight set per member from HBM at every
step.  Decomposed, the W-term of every layer is a SINGLE dense
(population, d) @ (d, h) matmul (W enters vmap un-batched), a shape the MXU
eats whole.  The noise term pays off when an antithetic pair shares it
(parallel/engine.py ``_eval_local_pairs``, the engine's choice for every
mirrored run this forward applies to): members 2k and 2k+1 are θ ± σ·ε_k,
so the engine vmaps over PAIRS with ε_k un-batched across the pair's two
members — x₊@ε_k and x₋@ε_k are one [2,d]×[d,h] product and ε is read once
per pair per step: half the weight bytes of the materialized path.  Vmapped
per member instead it would read one ε tree per member, the same bytes per
step as materialized weights, so unmirrored runs take the materialised
forward.

Scope: MLPPolicy-shaped networks (Dense stacks, tanh/… activations,
optional continuous squash).  VBN layers are not yet supported here — the
affine is decomposable too, but stats plumbing is deferred (engine rejects
the combination loudly).
"""

from __future__ import annotations

from typing import Any

import jax.numpy as jnp


def _ordered_dense_names(params: Any) -> list[str]:
    names = sorted(
        (n for n in params if n.startswith("dense_")),
        key=lambda n: int(n.split("_")[1]),
    )
    names.append("head")
    return names


def supports_decomposed(module) -> bool:
    """True for modules whose forward this file can reproduce exactly."""
    from .policies import MLPPolicy

    # exact type: an MLPPolicy SUBCLASS may override __call__, which this
    # file would silently fail to reproduce — fail loudly instead
    return type(module) is MLPPolicy and not module.use_vbn


def mlp_decomposed_apply(
    module, shared_params: Any, noise_params: Any, scale, obs: jnp.ndarray
) -> jnp.ndarray:
    """Exact MLPPolicy forward with weights (shared + scale·noise), never
    materializing the sum.

    ``noise_params`` is the member's ε unraveled into the SAME pytree shape
    as ``shared_params`` (ops/params.py spec.unravel of the raw table
    slice); ``scale`` is σ·sign (a traced float32 scalar).

    Rounds as often as the materialized layer does and no more: both dots
    accumulate into float32, the sum is formed in float32 and cast ONCE to
    the compute dtype (that of the kernels).
    """
    names = _ordered_dense_names(shared_params)
    x = obs
    f32 = jnp.float32
    for name in names:
        w = shared_params[name]["kernel"]
        b = shared_params[name]["bias"]
        nw = noise_params[name]["kernel"]
        nb = noise_params[name]["bias"]
        # x @ w is shared across members (un-batched under vmap → one dense
        # population-wide matmul); x @ nw is the noise term
        z = (jnp.dot(x, w, preferred_element_type=f32)
             + scale * jnp.dot(x, nw, preferred_element_type=f32)
             + b.astype(f32) + scale * nb.astype(f32))
        x = z.astype(w.dtype)
        if name != "head":
            x = module.activation(x)
    if not module.discrete:
        x = jnp.tanh(x) * module.action_scale
    return x


def mlp_lowrank_apply(
    module, shared_params: Any, lr_noise: dict, scale, obs: jnp.ndarray
) -> jnp.ndarray:
    """Exact MLPPolicy forward with weights (shared + scale·A Bᵀ/√r), never
    materializing any dense noise matrix.

    ``lr_noise`` mirrors the params with ``(A, B)`` factors (or, where
    factoring would not save, the dense E) at each kernel and the dense
    bias noise (ops/lowrank.py ``LowRankTreeSpec.unpack``); ``scale`` is
    σ·sign.  Every layer is the perturbed-dense primitive
    (models/perturbed.py): the noise term costs O((m+n)·r) per step
    instead of O(m·n).
    """
    from .perturbed import perturbed_dense

    names = _ordered_dense_names(shared_params)
    x = obs
    for name in names:
        w = shared_params[name]["kernel"]
        b = shared_params[name]["bias"]
        nb = lr_noise[name]["bias"]
        z = perturbed_dense(x, w, lr_noise[name]["kernel"], scale)
        x = (z + b + scale * nb).astype(w.dtype)
        if name != "head":
            x = module.activation(x)
    if not module.discrete:
        x = jnp.tanh(x) * module.action_scale
    return x
