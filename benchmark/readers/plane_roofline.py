"""The bytes bound of a match launch with its staged planes reckoned
from shapes: one ``[B, L]`` u8 plane and one ``[B]`` i32 length vector
for each DISTINCT key the plugin's rules read, whatever the plugin
stages and counts as ``h2d_bytes`` — so that the yardstick reads the
same work before and after a change to staging. ``B`` is the rows a
launch carried (``device_records`` over the lane's launches in the
traced interval), ``L`` the metric's ``plane_len``: the length bucket
the cell's ordinary lines stage into (a launch at a wider bucket moves
more than the bound says, which only lowers the share). Without a
trace, or with a program that has no such counters or planes (the
parent of the PR that added them), it gives nothing."""

import kernel_cost


def match_roofline_share(readings, plugin: str, lane: str, plane_len: int):
    """The least time the chip could take for one launch — planes,
    stride tables, class maps and verdicts (``kernel_cost
    .grep_match_bytes``) over the HBM rate — over the device time a
    launch took, in per cent."""
    t = readings["trace"]
    if t is None:
        return None
    n = t["counters"].get(f"lane.{lane}.launches")
    records = t["counters"].get(f"filter.{plugin}.device_records")
    rules, planes = [], 0
    for p in readings["filters"]:
        prog = getattr(p, "_program", None)
        if p.name == plugin and prog is not None \
                and hasattr(prog, "n_planes"):
            rules += prog.decision()["rules"]
            planes += prog.n_planes
    if not n or not records or not rules or not t["busy_s"]:
        return None
    rows = records / n
    plane_bytes = planes * rows * (plane_len + 4)
    need = kernel_cost.grep_match_bytes(rules, plane_bytes, rows)
    peak = kernel_cost.peaks(readings["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / peak) / (t["busy_s"] / n)
