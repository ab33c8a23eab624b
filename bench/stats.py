"""Order statistics over all samples of a window (no buckets, no chunks)."""
from __future__ import annotations

import math


def percentile(values, q: float):
    """Nearest-rank percentile: the smallest sample with at least ``q``
    percent of all samples at or below it.  None for no samples."""
    v = sorted(values)
    if not v:
        return None
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


def mean(values):
    v = list(values)
    return sum(v) / len(v) if v else None
