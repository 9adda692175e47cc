"""Degraded forms of the looped sequence model, for the rehearsals that the
reference check has to fail (``test_loop_cell.py``, ``loop_tolerance.py``):
a configuration copy names one as its ``policy`` and nothing else changes.
Each says of itself what the honest model says (``total_ut_steps`` 4 and
so on), so the file-against-build comparison passes and only the numbers
can give it away."""

import dataclasses

import jax.numpy as jnp

from estorch_tpu.models import LoopedLM, lm_blocks


@dataclasses.dataclass(frozen=True)
class Fp8Loop(LoopedLM):
    """The activations every layer projection reads rounded to float8_e4m3
    (3 bits of mantissa): a forward in a lower precision than the
    configuration states; the weights stay as they are."""

    @staticmethod
    def _dense(p, noise, c, name, x):
        coarse = x.astype(jnp.float8_e4m3fn).astype(x.dtype)
        return lm_blocks.dense(p, noise, c, name, coarse)


@dataclasses.dataclass(frozen=True)
class DroppedCorrectionLoop(LoopedLM):
    """The rank-r correction left out of ONE leaf (attention's output, in
    every layer and so in all four passes): part of the mathematics
    missing."""

    @staticmethod
    def _dense(p, noise, c, name, x):
        return lm_blocks.dense(p, None if name == "o" else noise, c, name, x)


@dataclasses.dataclass(frozen=True)
class ThreePassLoop(LoopedLM):
    """One pass fewer than ``total_ut_steps`` says."""

    def passes(self, params, noise, c, tokens):
        fewer = {**dataclasses.asdict(self),
                 "total_ut_steps": self.total_ut_steps - 1}
        return LoopedLM(**fewer).passes(params, noise, c, tokens)


@dataclasses.dataclass(frozen=True)
class UnrotatedKeysLoop(LoopedLM):
    """The rotation left out of the keys: the key projection comes out
    turned BACK by its position, so the one attention's rotation of it
    cancels and the scores are of rotated queries against plain keys."""

    def _dense(self, p, noise, c, name, x):
        y = lm_blocks.dense(p, noise, c, name, x)
        if name != "k":
            return y
        t = x.shape[0]
        cos, sin = lm_blocks.rotary_tables(t, self.head_dim, self.rope_theta)
        return lm_blocks.rotate(
            y.reshape(t, self.num_key_value_heads, self.head_dim),
            cos, -sin).reshape(t, -1)


@dataclasses.dataclass(frozen=True)
class LastPassScoreLoop(LoopedLM):
    """The score read from the last pass alone, not weighted by the exit
    distribution."""

    def perturbed_apply(self, params, noise, c, tokens):
        logp, _, last = self.passes(params, noise, c, tokens)
        return logp[-1], last[-1]
