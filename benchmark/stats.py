"""Percentiles and sample counts, one definition for every metric."""

from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(xs: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-quantile (0..1) by linear interpolation between order
    statistics; None for an empty sample."""
    if not xs:
        return None
    s = sorted(xs)
    pos = q * (len(s) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def samples_beyond(n: int, q: float) -> int:
    """How many of ``n`` samples lie beyond the ``q``-quantile: a tail
    percentile wants at least ten."""
    return int(math.floor(n * (1.0 - q)))
