"""Readers over the generator's log of acks: the tail of the time to
ack at a percentile that is no end-to-end metric of the cell."""

import stats


def percentile_ms(readings, q: float):
    """Milliseconds from when a frame was due to its ack, at ``q``, over
    every frame of the window that was acked."""
    ms = [(f["ack_ns"] - f["due_ns"]) / 1e6 for f in readings["frames"]
          if f["ack_ns"]]
    if not stats.supported(len(ms), q):
        return None
    return stats.percentile(ms, q)
