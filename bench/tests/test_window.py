"""The closed loop's window on synthetic completions: on one chip it
closes on the first completion ``seconds`` after its opening; on several
chips, each completing a task per period in a phase of its own, it
spans whole periods, so the rate it reads is the chips' whatever their
phases."""

from __future__ import annotations

import numpy as np
import pytest

from bench.lib import farm


def _window(times, chips, seconds):
    """(t_open, t_close, tasks counted) of the closed-loop window over
    completions at ``times``."""
    served = farm.Served(t_open=None, t_close=None, window_s=0.0,
                         prompts={})
    drv = farm._Closed(None, None, 0, served, seconds=seconds, chips=chips)
    for t in sorted(times):
        with drv.lock:
            drv.on_done(t)
    counted = sum(served.t_open < t <= served.t_close for t in times)
    return served.t_open, served.t_close, counted


def _completions(phases, period, n):
    return [p + k * period for p in phases for k in range(1, n)]


def test_one_chip_closes_on_the_first_completion_past_seconds():
    times = _completions([0.0], 2.342, 40)
    t_open, t_close, counted = _window(times, 1, 45.0)
    assert t_open == pytest.approx(2.342)
    assert t_close == min(t for t in times if t >= t_open + 45.0)
    assert counted / (t_close - t_open) == pytest.approx(1 / 2.342,
                                                         rel=1e-12)


@pytest.mark.parametrize("phases", [
    (0.0, 0.0002, 0.0004, 0.0006),  # in step, seen together
    (0.0, 0.3, 0.31, 1.9),
    (0.0, 0.5855, 1.171, 1.7565),  # evenly spread
    tuple(np.random.default_rng(5).uniform(0, 2.342, 4)),
])
def test_four_chips_read_their_rate_whatever_their_phases(phases):
    period = 2.342
    t_open, t_close, counted = _window(_completions(phases, period, 40), 4,
                                       45.0)
    assert t_close >= t_open + 45.0
    assert counted % 4 == 0
    assert counted / (t_close - t_open) == pytest.approx(4 / period,
                                                         rel=1e-9)
