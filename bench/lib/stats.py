"""Order statistics of the benchmark."""

from __future__ import annotations

import statistics

import numpy as np


def percentile(values, weights, q: float) -> float:
    """Nearest-rank ``q``-th percentile of ``values``, each counted
    ``weights`` times: the least value at or below which at least ``q``% of
    all the weighted samples lie.  A round's latency weighted by its op
    count gives the percentile over every op, not over rounds."""
    values = np.asarray(values, np.float64)
    weights = np.asarray(weights, np.float64)
    if values.shape[0] == 0 or weights.sum() <= 0:
        raise ValueError("percentile of no samples")
    order = np.argsort(values, kind="stable")
    cum = np.cumsum(weights[order])
    rank = np.ceil(q / 100.0 * cum[-1])
    return float(values[order][np.searchsorted(cum, max(rank, 1.0))])


def spread(values) -> float:
    """Interquartile distance as a share of the median, with the quartiles
    of ``statistics.quantiles(values, n=4)``."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
