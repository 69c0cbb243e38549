"""The bytes bound of a span launch, reckoned from shapes alone, over
the device time a launch took: what goes in is one ``[B, L]`` u8 plane
and its ``[B]`` i32 lengths, what comes out is the ``[B, G, 2]`` offsets
(``offset_bytes`` each: two hold any offset into a plane of 512) and the
``[B]`` one-byte verdicts. ``B`` is the rows a launch carried
(``device_records`` over the lane's launches in the traced interval),
``L`` the metric's ``plane_len`` (the length bucket the cell's ordinary
lines stage into), ``G`` the named groups of the parser's regex. **No
table term**: the tables are whatever implements the program, and the
yardstick has to read the same work before and after a change to them.
The two passes are chains of dependent steps, one gather a byte, so
this is the bytes bound only and reads far under 1 %. Without a trace,
or with a program that has no span program or no such counters (the
parent of the PR that added them), it gives nothing."""

import kernel_cost


def span_launch_bytes(rows: float, groups: int, plane_len: int,
                      offset_bytes: int) -> float:
    """Bytes one span launch has to move through HBM at least once."""
    return rows * (plane_len + 4) + rows * (2 * groups * offset_bytes + 1)


def span_roofline_share(readings, plugin: str, lane: str, plane_len: int,
                        offset_bytes: int):
    t = readings["trace"]
    if t is None:
        return None
    n = t["counters"].get(f"lane.{lane}.launches")
    records = t["counters"].get(f"filter.{plugin}.device_records")
    groups = 0
    for p in readings["filters"]:
        prog = getattr(p, "_spans", None)
        if p.name == plugin and prog is not None:
            groups += len(prog.names)
    if not n or not records or not groups or not t["busy_s"]:
        return None
    need = span_launch_bytes(records / n, groups, plane_len, offset_bytes)
    peak = kernel_cost.peaks(readings["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / peak) / (t["busy_s"] / n)
