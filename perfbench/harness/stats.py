"""Arithmetic of the end-to-end metrics over one measured window."""
from __future__ import annotations

from typing import Sequence

import numpy as np


def percentile_ms(latencies_s: Sequence[float], q: float) -> float:
    """The ``q``-th percentile, in milliseconds, of every latency in the
    window (linear interpolation between order statistics)."""
    if len(latencies_s) == 0:
        raise ValueError("no completed step in the window")
    return float(np.percentile(np.asarray(latencies_s, np.float64) * 1e3, q))


def rate_per_s(n_done: int, window_s: float) -> float:
    """Work completed over all the time of the window."""
    if window_s <= 0:
        raise ValueError(f"window of {window_s} s")
    return n_done / window_s
