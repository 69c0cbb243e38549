"""Open loop: one frame every ``frame_lines / rate_lines_per_s`` seconds
on a schedule fixed before the window, whatever the aggregator does — a
fleet of agents that flush on their own timers. A frame is due at its
place in the schedule; a late send counts against the system only from
then (the generator's own lateness is reported beside it)."""

import time


def run(link, params: dict, start_ns: int, seconds: float) -> None:
    interval_ns = int(1e9 * int(params["frame_lines"])
                      / float(params["rate_lines_per_s"]))
    for k in range(int(seconds * 1e9) // interval_ns):
        due = start_ns + k * interval_ns
        wait = (due - time.monotonic_ns()) / 1e9
        if wait > 0:
            time.sleep(wait)
        if link.broken:
            return
        link.send(k, due, "window")
