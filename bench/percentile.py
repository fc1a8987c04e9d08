"""Order statistics over raw samples: exact nearest-rank percentiles and the
quartile spread the benchmark's bounds are set from."""
from __future__ import annotations

import math
import statistics
from typing import Sequence


def nearest_rank(samples: Sequence[float], q: float) -> float | None:
    """The ``q``-th percentile (0 < q <= 100) by the nearest-rank rule: the
    smallest sample with at least q% of the samples at or below it.  None
    when there are no samples."""
    if not samples:
        return None
    if not 0 < q <= 100:
        raise ValueError(f"percentile out of range: {q}")
    ordered = sorted(samples)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return float(ordered[rank - 1])


def quartile_spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, with the quartiles of ``statistics.quantiles(values, n=4)``."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
