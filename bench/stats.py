"""Percentiles as the benchmark states them."""
from __future__ import annotations

import math

import numpy as np


def percentile(values, q: float) -> float | None:
    """Nearest-rank percentile: the smallest value with at least ``q``
    percent of the values at or below it; ``None`` for no values."""
    v = np.sort(np.asarray(values, dtype=np.float64))
    if v.size == 0:
        return None
    rank = max(1, math.ceil(q / 100.0 * v.size))
    return float(v[rank - 1])
