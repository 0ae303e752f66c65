"""Percentile and rate arithmetic over a whole window."""
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import stats  # noqa: E402


def test_percentiles_take_every_step():
    lat = [0.010] * 90 + [0.050] * 10
    assert stats.percentile_ms(lat, 50) == pytest.approx(10.0)
    assert stats.percentile_ms(lat, 95) == pytest.approx(50.0)
    assert stats.percentile_ms(list(range(1, 101)), 95) == \
        pytest.approx(np.percentile(np.arange(1, 101) * 1e3, 95))


def test_rate_over_all_the_window():
    assert stats.rate_per_s(500, 20.0) == 25.0
    with pytest.raises(ValueError):
        stats.rate_per_s(1, 0.0)
    with pytest.raises(ValueError):
        stats.percentile_ms([], 50)


def test_window_counts_every_robot_step():
    import run
    calls = [{"t0": 0.0 + 0.1 * i, "t1": 0.1 * i + 0.08, "robots": 4,
              "failed": 1 if i == 3 else 0} for i in range(10)]
    e = run.end_to_end(calls)
    assert e["steps"] == 39
    assert e["window_s"] == pytest.approx(0.98)
    assert e["steps_per_s"] == pytest.approx(39 / 0.98)
    assert e["step_p50_ms"] == pytest.approx(80.0)
