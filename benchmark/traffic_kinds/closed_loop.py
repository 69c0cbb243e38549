"""Closed loop: ``in_flight`` frames outstanding, the next one sent the
moment an ack returns — an edge replaying its backlog as fast as the
aggregator takes it. A frame is due when its slot in flight frees."""

import time


def run(link, params: dict, start_ns: int, seconds: float) -> None:
    end_ns = start_ns + int(seconds * 1e9)
    slot = 0
    for _ in range(int(params["in_flight"])):
        link.send(slot, time.monotonic_ns(), "window")
        slot += 1
    while True:
        left = (end_ns - time.monotonic_ns()) / 1e9
        if left <= 0 or not link.wait_ack(left):
            return
        now = time.monotonic_ns()
        if now >= end_ns:
            return
        link.send(slot, now, "window")
        slot += 1
