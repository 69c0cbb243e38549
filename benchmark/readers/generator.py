"""Readers over the generator's own log: how late it ran."""

import stats


def lag_ms(readings, q: float):
    """How long after a frame was due the generator began to send it."""
    lags = [(f["created_ns"] - f["due_ns"]) / 1e6
            for f in readings["frames"]]
    if not stats.supported(len(lags), q):
        return None
    return stats.percentile(lags, q)
