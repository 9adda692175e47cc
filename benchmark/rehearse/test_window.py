"""The median and window arithmetic on synthetic fence times."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import window  # noqa: E402

STEPS = 10240 * 400


def fences(n, gen_s, stall_at=None, stall_s=0.0):
    out, t = [100.0], 100.0
    for i in range(n):
        t += gen_s + (stall_s if i == stall_at else 0.0)
        out.append(t)
    return out


def test_one_stalled_generation_leaves_the_rate_and_moves_the_stall_share():
    steady = fences(60, 0.5)
    stalled = fences(60, 0.5, stall_at=17, stall_s=0.5)
    rate = window.steps_per_s_per_chip(steady, STEPS, 1)
    assert rate == pytest.approx(STEPS / 0.5)
    assert window.steps_per_s_per_chip(stalled, STEPS, 1) == pytest.approx(rate)
    assert window.stall_share(steady) == pytest.approx(0.0, abs=1e-9)
    assert window.stall_share(stalled) == pytest.approx(0.5 / 30.5)
    # a mean over the window, as PR 22 took it, would have moved by 1.6%
    mean_rate = STEPS * 60 / (stalled[-1] - stalled[0])
    assert mean_rate / rate == pytest.approx(30.0 / 30.5)


def test_rate_is_per_chip():
    f = fences(50, 1.25)
    assert window.steps_per_s_per_chip(f, 4 * STEPS, 4) == pytest.approx(
        STEPS / 1.25)


def test_window_closes_at_the_first_fence_at_or_after_seconds():
    f = fences(12, 1.0)                      # fences at 100, 101, ... 112
    assert window.close_window(f, 9.5) == f[:11]      # closes at 110
    assert window.close_window(f, 10.0) == f[:11]     # exactly on a fence
    assert window.close_window(f, 10.001) == f[:12]
    with pytest.raises(ValueError):
        window.close_window(f, 12.5)


def test_between_share_and_ratios():
    f = fences(50, 0.5)
    assert window.between_share(f, [0.49] * 50) == pytest.approx(0.02)
    assert window.mean_over_median([1.0] * 9 + [2.0]) == pytest.approx(1.1)


def test_shares_are_fractions():
    # a mean below the median must not print a negative share
    f = [0.0, 1.0, 2.0, 3.0, 3.5]
    assert 0.0 <= window.stall_share(f) <= 1.0
    assert window.between_share(f, [1.0] * 4) == 0.0
