"""Order statistics the benchmark reports, kept apart from the program's
own copies so that no change to the program moves them."""
from __future__ import annotations

import math


def percentile(values, p: float) -> float:
    """Nearest-rank percentile: the ceil(p/100 * n)-th smallest value
    (copied from ``repro.obs.metrics.percentile``). NaN when empty."""
    a = sorted(float(v) for v in values)
    if not a:
        return float("nan")
    rank = min(len(a) - 1, int(math.ceil(p / 100 * len(a))) - 1)
    return a[max(rank, 0)]

