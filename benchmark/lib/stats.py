"""Order statistics used by the harness and the metric readers."""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple


def percentile(xs: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile ``q`` in [0, 100] of a non-empty list."""
    s = sorted(xs)
    k = (len(s) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return s[lo] + (s[hi] - s[lo]) * (k - lo)


def honest_tail(xs: Sequence[float], beyond: int = 10) -> Optional[Tuple[float, float]]:
    """(percentile, value) of the highest percentile that still has ``beyond``
    samples above it; None when the list is too short for any."""
    n = len(xs)
    if n <= beyond:
        return None
    q = 100.0 * (n - beyond - 1) / (n - 1)
    return q, percentile(xs, q)
