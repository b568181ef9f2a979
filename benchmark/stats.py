"""Percentiles and shares, as every metric reader takes them."""
from __future__ import annotations

import math

# A request that failed or never finished has no latency: it counts as a
# miss above every limit. Where a percentile lands on a miss, the metric
# reads this many seconds, far beyond any limit a user would set.
MISS_S = 3600.0


def percentile(values, q: float):
    """The nearest-rank ``q``-th percentile (0 < q <= 100): the smallest
    value with at least ``q`` percent of the values at or below it. None
    where there are no values."""
    vals = sorted(values)
    if not vals:
        return None
    v = vals[max(0, math.ceil(q / 100 * len(vals)) - 1)]
    return MISS_S if math.isinf(v) else v


def share(part: float, whole: float):
    """``part`` over ``whole`` in percent; None where nothing was read."""
    if not whole or part is None:
        return None
    return 100.0 * part / whole
