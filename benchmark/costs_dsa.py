"""Operations of what a learned sparse attention (an indexer that chooses a
query's keys, attention over the chosen) adds to the benchmark's arithmetic,
computed from shapes.  The benchmark's own counts, kept with it (as
``costs.py``, ``costs_moe.py`` and ``costs_sambay.py``), so that a later PR
cannot change a utilisation by changing a cost model, and so that a share of
a roofline reads the same WORK whatever implements it later: the pairs a
query SELECTED, not the pairs a kernel multiplied and masked."""

from __future__ import annotations


def causal_pairs(length: int) -> int:
    """(query, key) pairs a causal mask leaves visible over ``length``
    positions: what the indexer scores."""
    return length * (length + 1) // 2


def selected_pairs(length: int, topk: int) -> int:
    """(query, key) pairs of ONE layer and sequence the attention reads:
    query ``t`` selects ``min(t + 1, topk)`` keys.  Exact: the program's own
    count (``selected_pairs`` in a generation record, summed over members
    and layers) has to equal members x layers x this."""
    full = min(length, topk)
    return full * (full + 1) // 2 + (length - full) * topk


def attention_flops_per_pair(num_heads: int, head_dim: int) -> int:
    """Multiply-adds x 2 grouped-query attention spends on one selected
    (query, key) pair: every query head scores ``head_dim`` deep and sums
    values ``head_dim`` wide.  32 heads of 128: 16,384."""
    return 2 * num_heads * (head_dim + head_dim)


def index_flops_per_pair(index_heads: int, index_head_dim: int) -> int:
    """Multiply-adds x 2 the indexer spends on one visible (query, key)
    pair: ``index_heads`` dot products ``index_head_dim`` deep against ONE
    key head (the ``relu`` and the weighted sum over heads left out).  16
    heads of 64: 2,048."""
    return 2 * index_heads * index_head_dim
