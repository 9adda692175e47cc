"""Operations and bytes of what attention inside a compressed latent (CCA:
two causal convolutions over q and k, a q-k mean, a value shift, an L2 scale,
then full causal attention over the latent's heads) adds to the benchmark's
arithmetic, computed from shapes.  The benchmark's own counts, kept with it
(as ``costs.py``, ``costs_moe.py``, ``costs_dsa.py``), so that a later PR
cannot change a utilisation by changing a cost model, and so that a share of
a roofline reads the same WORK whatever implements it later."""

from __future__ import annotations


def causal_pairs(length: int) -> int:
    """(query, key) pairs a causal mask leaves visible over ``length``
    positions: what full causal attention reads, exactly."""
    return length * (length + 1) // 2


def attention_flops_per_pair(num_heads: int, head_dim: int) -> int:
    """Multiply-adds x 2 attention spends on one visible (query, key) pair:
    every query head scores ``head_dim`` deep and sums values ``head_dim``
    wide.  8 heads of 128: 4,096."""
    return 2 * num_heads * (head_dim + head_dim)


def mix_bytes(length: int, num_heads: int, num_kv_heads: int, head_dim: int,
              operand_bytes: int = 2) -> int:
    """HBM bytes the latent's mixing moves for ONE layer and sequence, at
    the least a single fused pass could: the projections' outputs q~ and k~
    read once and the scaled q^, k^ and the shifted v written once, all in
    the compute dtype (``operand_bytes`` each).  The two convolutions' taps
    reach one position back each, the mean and the norm are per position, so
    nothing needs a second read; the convolutions' weights (a third of a
    megabyte a layer) are left out.  8 + 2 heads of 128 over 8,192
    positions: 46,137,344."""
    read = length * (num_heads + num_kv_heads) * head_dim
    written = length * (num_heads + 2 * num_kv_heads) * head_dim
    return operand_bytes * (read + written)
