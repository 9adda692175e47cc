"""Degraded forms of the SambaY decoder with differential attention, for the
rehearsals that the reference check has to fail (``test_sambay_cell.py``,
``sambay_tolerance.py``): a configuration copy names one as its ``policy`` and
nothing else changes.  Each says of itself what the honest model says (the
same layers, widths and window), so the file-against-build comparison passes
and only the numbers can give it away."""

import dataclasses

import jax.numpy as jnp

from estorch_tpu.models import SambaYLM, lm_blocks
from estorch_tpu.models import sambay_lm


def fp8(x):
    return x.astype(jnp.float8_e4m3fn).astype(x.dtype)


@dataclasses.dataclass(frozen=True)
class Fp8SambaY(SambaYLM):
    """The activations every projection reads rounded to float8_e4m3 (3 bits
    of mantissa): a forward in a lower precision than the configuration
    states; weights, scan and softmax stay as they are."""

    @staticmethod
    def _dense(p, noise, c, name, x, bias=None):
        return lm_blocks.dense(p, noise, c, name, fp8(x), bias)


@dataclasses.dataclass(frozen=True)
class DroppedCorrectionSambaY(SambaYLM):
    """The rank-r correction left out of ONE projection (every Mamba layer's
    ``in_proj``): part of the mathematics missing."""

    @staticmethod
    def _dense(p, noise, c, name, x, bias=None):
        if name == "in_proj" and noise is not None:
            noise = {**noise, name: tuple(jnp.zeros_like(f)
                                          for f in noise[name])}
        return lm_blocks.dense(p, noise, c, name, x, bias)


@dataclasses.dataclass(frozen=True)
class PlainAttentionSambaY(SambaYLM):
    """``λ`` set to 0: the second softmax map never subtracted, which is
    plain attention with a norm behind it."""

    def _attention(self, kind, index, p, noise, c, u, carried):
        honest = lm_blocks.differential_combine

        def combine(ctx, lam, gamma, **kw):
            return honest(ctx, 0.0 * lam, gamma, **kw)

        lm_blocks.differential_combine = combine
        try:
            return SambaYLM._attention(self, kind, index, p, noise, c, u,
                                       carried)
        finally:
            lm_blocks.differential_combine = honest


@dataclasses.dataclass(frozen=True)
class NoWindowSambaY(SambaYLM):
    """The window ignored: the self-decoder's attention full causal."""

    def _attention(self, kind, index, p, noise, c, u, carried):
        if kind == sambay_lm.WINDOW:
            # full causal, as ``full_kv`` computes it, but handing on nothing
            kind, carried = sambay_lm.FULL_KV, dict(carried)
        return SambaYLM._attention(self, kind, index, p, noise, c, u,
                                   carried)


@dataclasses.dataclass(frozen=True)
class FirstMemorySambaY(SambaYLM):
    """``m`` taken from the FIRST Mamba layer's scan, not from the last
    one's (published layer 0 instead of layer 16)."""

    def _mixer(self, kind, index, p, noise, c, u, carried):
        if kind not in (sambay_lm.MAMBA, sambay_lm.MAMBA_MEM):
            return SambaYLM._mixer(self, kind, index, p, noise, c, u,
                                   carried)
        out, y = self._mamba(p, noise, c, u)
        carried.setdefault("memory", y)
        return out


@dataclasses.dataclass(frozen=True)
class OwnKeysCrossSambaY(SambaYLM):
    """The cross layer given keys and values of its OWN: its query
    projection's first columns of its own input, where the model reads the
    ``full_kv`` layer's."""

    def _attention(self, kind, index, p, noise, c, u, carried):
        if kind == sambay_lm.CROSS:
            t, hd = u.shape[0], self.head_dim
            nkv = self.num_key_value_heads
            q = self._dense(p, noise, c, "q", u, bias="q_bias").astype(
                u.dtype)
            own_v = q[:, nkv * hd:2 * nkv * hd].reshape(t, nkv // 2, 1,
                                                        2 * hd)
            carried = dict(carried, kv=(
                q[:, :nkv * hd],
                jnp.broadcast_to(own_v, (t, nkv // 2, 2, 2 * hd))))
        return SambaYLM._attention(self, kind, index, p, noise, c, u,
                                   carried)
