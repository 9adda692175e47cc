"""Operations of what a stack of BANDED and full attention layers (a rotary
window of some thousand keys beside position-free global layers) adds to the
benchmark's arithmetic, computed from shapes.  The benchmark's own counts,
kept with it (as ``costs.py``, ``costs_moe.py``, ``costs_sambay.py``), so that
a later PR cannot change a utilisation by changing a cost model, and so that
a share of a roofline reads the same WORK whatever implements it later: the
pairs a mask leaves VISIBLE, not the pairs a tile multiplied and masked."""

from __future__ import annotations


def visible_pairs(length: int, window: int | None = None) -> int:
    """(query, key) pairs a causal mask leaves visible over ``length``
    positions: query ``t`` sees the keys ``[0, t]``, or ``(t - window, t]``
    under a window: the exact count, band and diagonal included.  16,384
    positions: 134,225,920 causal, 58,722,304 under a band of 4,096."""
    if window is None or window >= length:
        return length * (length + 1) // 2
    return window * (window + 1) // 2 + (length - window) * window


def attention_flops_per_pair(num_heads: int, head_dim: int) -> int:
    """Multiply-adds x 2 grouped-query attention spends on one visible
    (query, key) pair: every query head scores ``head_dim`` deep and sums
    values ``head_dim`` wide.  28 heads of 128: 14,336."""
    return 2 * num_heads * (head_dim + head_dim)


def attention_flops_per_sequence(kinds, length: int, window: int,
                                 num_heads: int, head_dim: int) -> dict:
    """``{"window": FLOPs, "global": FLOPs}`` of one sequence through the
    attention layers among ``kinds``: the ``window`` ones by the banded
    count, the ``global`` ones by the full causal one."""
    per_pair = attention_flops_per_pair(num_heads, head_dim)
    kinds = list(kinds)
    return {"window": kinds.count("window")
            * visible_pairs(length, window) * per_pair,
            "global": kinds.count("global")
            * visible_pairs(length) * per_pair}
