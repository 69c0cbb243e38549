"""Arithmetic the metrics share: percentiles with their sample-count
rule, the quartile spread the bounds are set from, and unions of
intervals (device busy time, idle gaps by host span)."""

import math
import statistics


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least
    ``q`` of the sample at or below it. ``q`` in (0, 1]."""
    if not values:
        raise ValueError("percentile of an empty sample")
    ordered = sorted(values)
    rank = max(1, math.ceil(q * len(ordered)))
    return ordered[rank - 1]


def beyond(n: int, q: float) -> int:
    """Samples strictly beyond the nearest-rank ``q`` percentile."""
    return n - max(1, math.ceil(q * n))


def supported(n: int, q: float, least_beyond: int = 10) -> bool:
    """A percentile is reported only with at least ten samples beyond
    it (choosing-metrics section 1); the median needs only a sample."""
    return n > 0 and (q <= 0.5 or beyond(n, q) >= least_beyond)


def quartile_spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, as ``statistics.quantiles(values, n=4)`` gives them."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def union(intervals) -> list:
    """Sorted, disjoint ``[(start, end), ...]`` covering the same points."""
    out = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def total(intervals) -> float:
    return sum(hi - lo for lo, hi in intervals)


def intersect(a, b) -> list:
    """Both sorted and disjoint → their common part, sorted, disjoint."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a, b) -> list:
    """The part of ``a`` outside ``b`` (both sorted and disjoint)."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k, cur = j, lo
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def busy_and_gaps(events, window):
    """``events``: device operation intervals; ``window``: (start, end).
    → (busy seconds' worth of time units, the idle gaps inside the
    window). Busy is the union of the intervals, clipped to the window."""
    busy = intersect(union(events), [window])
    return total(busy), subtract([window], busy)
