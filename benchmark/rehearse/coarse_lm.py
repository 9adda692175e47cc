"""Two degraded forms of the sequence model, for the rehearsals that the
reference check has to fail (``test_lm_cell.py``, ``lm_tolerance.py``): a
configuration copy names one as its ``policy`` and nothing else changes."""

import dataclasses

import jax.numpy as jnp

from estorch_tpu.models import HybridLM


@dataclasses.dataclass(frozen=True)
class Fp8LM(HybridLM):
    """The activations every projection reads rounded to float8_e4m3 (3
    bits of mantissa): a forward in a lower precision than the
    configuration states; the weights stay as they are."""

    @staticmethod
    def _dense(p, noise, c, name, x):
        coarse = x.astype(jnp.float8_e4m3fn).astype(x.dtype)
        return HybridLM._dense(p, noise, c, name, coarse)


@dataclasses.dataclass(frozen=True)
class DroppedCorrectionLM(HybridLM):
    """The rank-r correction left out of ONE projection (attention's
    output): part of the mathematics missing."""

    @staticmethod
    def _dense(p, noise, c, name, x):
        return HybridLM._dense(p, None if name == "o" else noise, c, name, x)
