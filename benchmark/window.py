"""Window arithmetic: from completion fences to the end-to-end number.

Pure Python, no JAX: the rehearsals run it on synthetic fence times.

A *fence* is the host-clock time at which one generation was complete (its
updated parameters were ready and its record was written).  A *reading* is
the interval between two consecutive fences: one whole generation, host
time between generations included.  The window opens at a fence and closes
at the first fence at or after ``seconds`` later, never mid-generation.
"""

from __future__ import annotations

import statistics


def close_window(fences: list[float], seconds: float) -> list[float]:
    """The fences of the window: from ``fences[0]`` to the first fence at
    or after ``fences[0] + seconds``.  Fences past it (the rest of a batch
    of generations) are dropped.  Raises if the window never closed."""
    end = fences[0] + seconds
    for i, t in enumerate(fences):
        if i > 0 and t >= end:
            return fences[: i + 1]
    raise ValueError(
        f"window not closed: {len(fences)} fences span "
        f"{fences[-1] - fences[0]:.3f} s of the {seconds} s asked for")


def intervals(fences: list[float]) -> list[float]:
    return [b - a for a, b in zip(fences[:-1], fences[1:])]


def clamp_share(x: float) -> float:
    """A share is a fraction between 0 and 1 (a mean below the median
    would otherwise give a slightly negative stall share)."""
    return min(1.0, max(0.0, x))


def steps_per_s_per_chip(fences: list[float], steps_per_generation: int,
                         chips: int) -> float:
    """population x horizon / chips / MEDIAN inter-fence interval.

    ``steps_per_generation`` is scanned member-steps, alive or masked: a
    constant of the configuration.  The median drops a rare stalled
    reading on purpose; ``stall_share`` is where a stall shows."""
    return steps_per_generation / chips / statistics.median(intervals(fences))


def stall_share(fences: list[float]) -> float:
    """1 - n x median interval / window: the share of the window that the
    median does not account for."""
    d = intervals(fences)
    window = fences[-1] - fences[0]
    return clamp_share(1.0 - len(d) * statistics.median(d) / window)


def between_share(fences: list[float], inside_s: list[float]) -> float:
    """1 - sum(time inside generations) / window: host time between the
    end of one generation's fence and the start of the next."""
    window = fences[-1] - fences[0]
    return clamp_share(1.0 - sum(inside_s) / window)


def mean_over_median(xs: list[float]) -> float:
    return statistics.fmean(xs) / statistics.median(xs)
