"""Operations of what a stack of sub-block BANDED layers beside full causal
ones, with a head count of its own a KIND, adds to the benchmark's
arithmetic, computed from shapes.  The benchmark's own counts, kept with it
(as ``costs.py``, ``costs_moe.py``, ``costs_swa.py``), so that a later PR
cannot change a utilisation by changing a cost model, and so that a share of a
peak reads the same WORK whatever implements it later: the pairs a mask
leaves VISIBLE, not the pairs a tile multiplied and masked.  The gate's
sigmoid and product and YaRN's blend are elementwise and count no FLOP here;
the gate's projection is one of the layer's matmuls (``describe`` of the
reference)."""

from __future__ import annotations

from benchmark.costs_swa import visible_pairs

SLIDING, FULL = "sliding_attention", "full_attention"


def attention_flops_per_pair(num_heads: int, head_dim: int) -> int:
    """Multiply-adds x 2 grouped-query attention spends on one visible
    (query, key) pair: every query head scores ``head_dim`` deep and sums
    values ``head_dim`` wide.  64 heads of 128: 32,768; 48: 24,576."""
    return 2 * num_heads * (head_dim + head_dim)


def attention_flops_per_sequence(kinds, heads, length: int, window: int,
                                 head_dim: int) -> dict:
    """``{"sliding": FLOPs, "full": FLOPs}`` of one sequence through the
    attention layers ``kinds`` (the published names) of ``heads`` query
    heads each: the sliding ones by the banded count, the full ones by the
    causal one.  16,384 positions: 134,225,920 causal pairs, 8,257,792
    under a band of 512."""
    out = {"sliding": 0, "full": 0}
    for kind, n in zip(kinds, heads):
        if kind == SLIDING:
            out["sliding"] += visible_pairs(length, window) * (
                attention_flops_per_pair(n, head_dim))
        else:
            out["full"] += visible_pairs(length) * (
                attention_flops_per_pair(n, head_dim))
    return out
