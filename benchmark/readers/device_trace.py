"""Readers over the reduced profiler trace (``trace_reduce.py``): device
time on the device's clock, set against the lane launches the program
counted in the same interval. Without a trace they give nothing."""

import kernel_cost


def idle_share(readings):
    t = readings["trace"]
    if t is None:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def _lane_launches(readings):
    t = readings["trace"]
    if t is None:
        return None
    lanes = readings["cell"].config["device_lanes"]
    n = sum(t["counters"].get(f"lane.{lane}.launches", 0) for lane in lanes)
    return n or None


def device_ms_per_launch(readings):
    """Device busy time per lane launch (one launch is one segment
    through every program of the filter), in milliseconds."""
    n = _lane_launches(readings)
    return None if n is None else 1e3 * readings["trace"]["busy_s"] / n


def grep_roofline_share(readings):
    """The least time the chip could take for one grep launch — the
    bytes the match has to move (``kernel_cost.grep_match_bytes``) over
    the HBM rate: the bytes bound — over the device time a launch took."""
    n = _lane_launches(readings)
    if n is None:
        return None
    t = readings["trace"]
    rules, plane = [], t["counters"].get("filter.grep.h2d_bytes")
    records = t["counters"].get("filter.grep.device_records")
    for plugin in readings["filters"]:
        prog = getattr(plugin, "_program", None)
        if plugin.name == "grep" and prog is not None:
            rules += prog.decision()["rules"]
    if not rules or not plane or not records:
        return None
    need = kernel_cost.grep_match_bytes(rules, plane / n, records / n)
    peak = kernel_cost.peaks(readings["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / peak) / (t["busy_s"] / n)
