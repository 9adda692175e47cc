"""Operations and bytes of an expert layer's grouped matmul and dispatch,
computed from shapes.  The benchmark's own arithmetic, kept with it (as
``costs.py``), so that a later PR cannot change a utilisation by changing a
cost model.  XLA gives the grouped matmul (a custom call) no cost of its
own, so its count comes from here."""

from __future__ import annotations


def expected_pairs_per_token(top_k: int, held: int, total: int) -> float:
    """(token, k) pairs of ONE expert layer that land on the ``held`` of
    ``total`` experts, per token, under a router that spreads its choices
    evenly: the count is an expectation, not what a run routed (the run's
    own count is ``routed_pairs`` in its generation records)."""
    return top_k * held / total


def expert_flops_per_pair(hidden: int, width: int) -> int:
    """2 x the weights one routed row passes: an expert's gate, up and
    down matmuls (gated SiLU, ``hidden -> width -> hidden``)."""
    return 2 * 3 * hidden * width


def dispatch_bytes_per_pair(hidden: int, operand_bytes: int = 2) -> int:
    """HBM bytes the dispatch moves for one routed row, at the least: the
    gather into expert order reads the token's row and writes it
    (``operand_bytes`` each way), and the combine reads the expert's
    float32 output row and adds it into the token's float32 row (a read
    and a write)."""
    return hidden * (2 * operand_bytes + 3 * 4)
