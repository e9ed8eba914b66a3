"""Percentiles and spreads. ``percentile`` is a copy of
``benches/common.percentile`` (numpy's linear interpolation); ``spread`` is
the contract's: (Q3 - Q1) / median with ``statistics.quantiles(n=4)``."""

from __future__ import annotations

import statistics


def percentile(xs, q: float) -> float:
    import numpy as np

    return float(np.percentile(list(xs), q))


def median(xs) -> float:
    return float(statistics.median(xs))


def spread(xs) -> float:
    """Interquartile distance as a share of the median (needs >= 2 values)."""
    q1, _, q3 = statistics.quantiles(list(xs), n=4)
    return (q3 - q1) / statistics.median(xs)


def histogram(xs, edges) -> dict:
    """Counts of xs in [edge_i, edge_{i+1}) plus an overflow bin, keyed by
    the lower edge — for the output-length line a run prints."""
    out = {str(e): 0 for e in edges}
    for x in xs:
        lo = edges[0]
        for e in edges:
            if x >= e:
                lo = e
        out[str(lo)] += 1
    return out
