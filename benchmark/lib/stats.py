"""Percentiles and spreads, the benchmark's own copy of the arithmetic
(nearest rank, as ``apex_tpu.obs.slo.percentile``: always a real sample)."""

from __future__ import annotations

import math
import statistics


def percentile(samples, q: float) -> float:
    """Smallest sample x with CDF(x) >= q; NaN for no samples."""
    if not 0 <= q <= 1:
        raise ValueError(f"quantile must be in [0, 1], got {q}")
    if not samples:
        return float("nan")
    ordered = sorted(samples)
    return float(ordered[max(math.ceil(q * len(ordered)), 1) - 1])


def iqr_share(values) -> float:
    """Distance between the first and third quartile as a share of the
    median — the spread the benchmark's bounds are set from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
