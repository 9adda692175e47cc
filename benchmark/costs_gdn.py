"""Operations and bytes of what a stack of gated-delta-rule layers beside a
gated full-attention layer adds to the benchmark's arithmetic, computed from
shapes.  The benchmark's own counts, kept with it (as ``costs.py``,
``costs_moe.py``, ``costs_sambay.py``, ``costs_swa.py``), so that a later PR
cannot change a utilisation by changing a cost model, and so that a share of
a roofline reads the same WORK whatever implements it later: the first file
here that counts a scan's MATRIX PRODUCTS (``costs.py`` counts none,
``costs_sambay.py`` a selective scan's bytes only).

The delta rule is counted in its chunked form (arXiv 2412.06464) at the
chunk the configuration states, a term at a time (:func:`delta_rule_terms`),
each at the work its mathematics needs: a product against a lower-triangular
``[L, L]`` matrix costs its ``L (L + 1) / 2`` entries (``L (L - 1) / 2``
where the diagonal is excluded), not the ``L²`` a dense tile multiplies; the
triangular inverse costs a substitution's multiply-adds.  Padding of a last
short chunk is not work."""

from __future__ import annotations


def visible_pairs(length: int) -> int:
    """(query, key) pairs a causal mask leaves visible over ``length``
    positions: 16,384 positions: 134,225,920."""
    return length * (length + 1) // 2


def attention_flops_per_pair(num_heads: int, head_dim: int) -> int:
    """Multiply-adds x 2 grouped-query attention spends on one visible
    (query, key) pair: every query head scores ``head_dim`` deep and sums
    values ``head_dim`` wide.  16 heads of 256: 16,384."""
    return 2 * num_heads * (head_dim + head_dim)


def attention_flops_per_sequence(kinds, length: int, num_heads: int,
                                 head_dim: int) -> int:
    """FLOPs of one sequence through the ``full`` layers among ``kinds``."""
    return (list(kinds).count("full") * visible_pairs(length)
            * attention_flops_per_pair(num_heads, head_dim))


def triangular_inverse_flops(rows: int) -> int:
    """Multiply-adds x 2 of inverting a unit lower-triangular ``[rows,
    rows]`` matrix by substitution: entry ``(i, j)`` below the diagonal is
    ``-Σ_{k=j}^{i-1} M_ik X_kj``, ``i - j`` multiply-adds; summed, ``rows
    (rows² - 1) / 6``.  64 rows: 87,360."""
    return rows * (rows * rows - 1) // 3


def delta_rule_terms(rows: int, key_heads: int, value_heads: int,
                     key_dim: int, value_dim: int) -> dict:
    """FLOPs (multiply-adds x 2) of ONE chunk of ``rows`` positions through
    the chunked gated delta rule, by term, over all heads (``key_dim``,
    ``value_dim`` a head's): ``K Kᵀ`` below the diagonal and ``Q Kᵀ`` on and
    below it, once a KEY head; a value head's triangular inverse, ``W = T
    (β K e^γ)`` and ``U = T (β V)`` against the lower-triangular ``T``; the
    chain's ``V' = U - W S``, ``Q̃ S`` and ``K̃ᵀ V'`` against the ``[key_dim,
    value_dim]`` state; and ``(Q Kᵀ ∘ decay) V'`` inside the chunk."""
    on_and_below = rows * (rows + 1) // 2
    below = rows * (rows - 1) // 2
    state = 2 * rows * key_dim * value_dim
    return {
        "k_kt": key_heads * 2 * below * key_dim,
        "q_kt": key_heads * 2 * on_and_below * key_dim,
        "inverse": value_heads * triangular_inverse_flops(rows),
        "w": value_heads * 2 * on_and_below * key_dim,
        "u": value_heads * 2 * on_and_below * value_dim,
        "v_new": value_heads * state,
        "out_state": value_heads * state,
        "state": value_heads * state,
        "out_inside": value_heads * 2 * on_and_below * value_dim}


def delta_rule_flops_per_sequence(kinds, length: int, chunk: int,
                                  key_heads: int, value_heads: int,
                                  key_dim: int, value_dim: int) -> int:
    """FLOPs of one sequence through the delta rule of the ``linear``
    layers among ``kinds``: whole chunks of ``chunk`` positions and one
    short last chunk counted at its own length."""
    def chunk_of(rows):
        return sum(delta_rule_terms(rows, key_heads, value_heads, key_dim,
                                    value_dim).values())

    rows = min(chunk, length)
    whole, rest = divmod(length, rows)
    return list(kinds).count("linear") * (
        whole * chunk_of(rows) + (chunk_of(rest) if rest else 0))


def delta_rule_bytes_per_sequence(kinds, length: int, key_heads: int,
                                  value_heads: int, key_dim: int,
                                  value_dim: int) -> int:
    """HBM bytes the delta rule of one sequence moves AT THE LEAST: per
    ``linear`` layer ``q`` and ``k`` ``[length, key heads, key_dim]``, ``v``
    and the output ``o`` ``[length, value heads, value_dim]``, ``g`` and
    ``β`` ``[length, value heads]``, once each, float32.  The state and the
    chunk's triangular system never need to leave the chip; what an
    implementation moves beyond this (``W``, ``U``, the decay's ``[L, L]``
    tiles, a carried state) is what the share of the roofline shows."""
    floats = (2 * key_heads * key_dim + 2 * value_heads * value_dim
              + 2 * value_heads)
    return list(kinds).count("linear") * 4 * length * floats
