"""Order statistics the benchmark reports."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Nearest-rank ``q``-th percentile (0 < q <= 100) over ALL values:
    the smallest value with at least ``q`` percent of the values at or
    below it.  No interpolation, so a tail is always a latency that some
    request really had."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    if not 0 < q <= 100:
        raise ValueError(f"percentile must be in (0, 100], got {q}")
    rank = max(math.ceil(q / 100.0 * len(xs)), 1)
    return float(xs[rank - 1])

