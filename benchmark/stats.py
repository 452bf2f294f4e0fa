"""Sample arithmetic the metrics share."""

import statistics


def percentile(values, q):
    """The q-th percentile (0 < q < 100) by linear interpolation between
    order statistics, or None where fewer than ten samples lie beyond
    it: a tail read from fewer is one request's luck."""
    n = len(values)
    if n == 0 or n * (1 - q / 100.0) < 10:
        return None
    ordered = sorted(values)
    at = (n - 1) * q / 100.0
    lo = int(at)
    hi = min(lo + 1, n - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (at - lo)


def median(values):
    return statistics.median(values) if values else None


def spread(values):
    """Distance between the first and third quartile as a share of the
    median: the statistic the bounds are set from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
