"""Small statistics shared by the metric readers."""

from __future__ import annotations

import numpy as np


def p95(values) -> float | None:
    """95th percentile (linear interpolation); None for no sample."""
    values = list(values)
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), 95.0))


def union_length(intervals) -> float:
    """Total length covered by (start, end) intervals."""
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total
