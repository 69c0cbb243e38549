"""Readers at the output: from the flush timer's tick to the chunk in
the output's hands."""

import bisect


def drain_s(readings):
    """Mean seconds from the start of ``flush_all`` to the arrival of
    each chunk it dispatched at the output callback, over the window."""
    w = readings["window"]
    ticks = sorted(readings["spans"].starts("flush"))
    waits = []
    for t in readings["sink"].arrivals:
        if w["start_ns"] <= t <= w["end_ns"]:
            i = bisect.bisect_right(ticks, t)
            if i:
                waits.append((t - ticks[i - 1]) / 1e9)
    return sum(waits) / len(waits) if waits else None
