"""Order statistics used by every latency metric.

``nearest_rank`` is a copy of ``repro.serve.metrics.nearest_rank``: the
benchmark keeps its own arithmetic so that no change to the program can
change how a number is computed.
"""
from __future__ import annotations

import math
import statistics
from typing import Optional


def nearest_rank(xs, q: float) -> Optional[float]:
    """Nearest-rank percentile (``sorted[ceil(q/100 * n) - 1]``), no
    interpolation; None for an empty sample."""
    if not len(xs):
        return None
    s = sorted(xs)
    k = max(1, math.ceil(q / 100.0 * len(s)))
    return float(s[k - 1])


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, with the quartiles of ``statistics.quantiles(values, n=4)``."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
