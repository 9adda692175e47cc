"""Operations the algorithm needs, computed from shapes.  The benchmark's
own arithmetic (copied from ``bench.py::policy_flops_per_member_step``), so
that a later PR cannot change a utilisation by changing a cost model.  A
reference module's ``describe`` calls it with its policy's matmuls."""

from __future__ import annotations


def matmul_flops(shapes) -> int:
    """2 x sum(m x n) over ``[(m, n), ...]``: the multiply-adds of one
    vector through each ``m x n`` matmul once."""
    return 2 * sum(m * n for m, n in shapes)
