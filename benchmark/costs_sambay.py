"""Operations and bytes of what a SambaY decoder with differential attention
adds to the benchmark's arithmetic, computed from shapes.  The benchmark's
own counts, kept with it (as ``costs.py`` and ``costs_moe.py``), so that a
later PR cannot change a utilisation by changing a cost model, and so that a
share of a roofline reads the same WORK whatever implements it later."""

from __future__ import annotations


def visible_pairs(length: int, window: int | None = None) -> int:
    """(query, key) pairs a causal mask leaves visible over ``length``
    positions: query ``t`` sees the keys ``[0, t]``, or ``(t - window, t]``
    under a window: the exact count, band and diagonal included, and no
    tile's masked scores."""
    if window is None or window >= length:
        return length * (length + 1) // 2
    return window * (window + 1) // 2 + (length - window) * window


def diff_attention_flops_per_pair(num_heads: int, head_dim: int) -> int:
    """Multiply-adds x 2 that differential attention spends on ONE visible
    (query, key) pair: ``num_heads / 2`` diff-heads x 2 softmax maps x (a
    score ``head_dim`` deep + a weighted sum of values ``2 head_dim``
    wide).  Phi-4-mini-flash: 20 x 2 x 2 x (64 + 128) = 15,360."""
    return (num_heads // 2) * 2 * 2 * (head_dim + 2 * head_dim)


def attention_flops_per_sequence(kinds, length: int, window: int,
                                 num_heads: int, head_dim: int) -> dict:
    """``{"window": FLOPs, "full": FLOPs}`` of one sequence through the
    attention layers among ``kinds``: the windowed ones by the banded
    count, ``full_kv`` and ``cross`` by the full causal one."""
    per_pair = diff_attention_flops_per_pair(num_heads, head_dim)
    banded = sum(k == "window" for k in kinds)
    full = sum(k in ("full_kv", "cross") for k in kinds)
    return {"window": banded * visible_pairs(length, window) * per_pair,
            "full": full * visible_pairs(length) * per_pair}


def scan_bytes_per_sequence(kinds, length: int, d_inner: int,
                            d_state: int) -> int:
    """HBM bytes the selective scans of one sequence move AT THE LEAST:
    per Mamba-1 layer ``Δ``, ``x̃`` and ``y`` ``[length, d_inner]`` and ``B``,
    ``C`` ``[length, d_state]``, once each, float32.  The state never needs
    to leave the chip; what an implementation moves beyond this (a carried
    state, a re-read decay) is what the share of the roofline shows."""
    scans = sum(k in ("mamba", "mamba_mem") for k in kinds)
    return scans * 4 * length * (3 * d_inner + 2 * d_state)
