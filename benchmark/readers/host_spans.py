"""Readers over the host spans ``run.py`` wraps around the calls into
each layer (decode, append, filter:<plugin>, flush). A span that never
opened in the window gives nothing."""

import stats


def _covered_ns(readings, span: str):
    w = readings["window"]
    mine = [(t0, t1) for n, t0, t1 in list(readings["spans"].spans)
            if n == span]
    if not mine:
        return None
    return stats.total(stats.intersect(stats.union(mine),
                                       [(w["start_ns"], w["end_ns"])]))


def share(readings, span: str):
    """Share of the window's wall time inside the span, in per cent
    (inclusive of what the span calls; nested spans of one name count
    once)."""
    ns = _covered_ns(readings, span)
    return None if ns is None else 100.0 * ns / (
        readings["window"]["seconds"] * 1e9)


def ms_per_frame(readings, span: str):
    """Milliseconds inside the span for each frame acked in the window."""
    ns = _covered_ns(readings, span)
    frames = sum(1 for f in readings["frames"] if f["ack_ns"])
    return None if ns is None or not frames else ns / 1e6 / frames
