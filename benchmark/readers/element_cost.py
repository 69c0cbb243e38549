"""What one gathered element of a match launch costs on the device: the
device time of one launch's scan modules over the elements the program
says a launch steps through (``filter.<plugin>.scan_elements`` over the
lane's launches in the traced interval: ``Σ R_c · Bp · (⌈L/k_c⌉ + 1)``
over the program's children, from the staged shape) — the number
PERF.md reckons launches in (8-11 ns on a v5e), read and not re-derived.

A launch's device time is the sum, over the module names that hold
``module``, of the **mean** duration of that name's *whole* events on
``XLA Modules``: the name's first and last event of the traced interval
are left out, since the interval may have cut either. Where a launch
takes a fifth of the interval, busy time over the launches the program
*dispatched* in it reads a tenth low (PERF.md, PR 34: 9.13 ns where
whole launches say 10.3). The mean, not the median: where a fifth of
the frames stage at the wider length bucket, the elements a launch are
a mean over both shapes too. Without a trace file, or with a program
that has no such counter (the parent of the PR that added it), it gives
nothing."""

import statistics

from lookup import load_py

import kernel_cost
import trace_reduce


def launch_seconds(planes: list, module: str) -> float:
    """Σ over module names holding ``module`` of the mean duration of
    the name's whole events on the device planes' ``XLA Modules`` lines:
    all but its first and its last (by start), or the median where a
    name has fewer than three."""
    by_name = {}
    for plane in planes:
        if not trace_reduce.DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            if line["name"] != "XLA Modules":
                continue
            for name, start, dur, *_ in line["events"]:
                name = name.split("(")[0]
                if module in name:
                    by_name.setdefault(name, []).append((start, dur / 1e9))
    total = 0.0
    for events in by_name.values():
        whole = [d for _start, d in sorted(events)[1:-1]]
        total += statistics.fmean(whole) if whole \
            else statistics.median(d for _start, d in events)
    return total


def _launch_seconds_of_run(module: str):
    """``launch_seconds`` over this run's trace file, or None."""
    spans = load_py("readers", "program_spans")
    path = spans.newest_xplane()
    if not path:
        return None
    return launch_seconds(spans.read_planes(path), module) or None


def ns_per_element(readings, plugin: str, lane: str, module: str):
    t = readings["trace"]
    if t is None:
        return None
    elements = t["counters"].get(f"filter.{plugin}.scan_elements")
    launches = t["counters"].get(f"lane.{lane}.launches")
    if not elements or not launches:
        return None
    seconds = _launch_seconds_of_run(module)
    if not seconds:
        return None
    return 1e9 * seconds / (elements / launches)


def match_roofline_share(readings, plugin: str, lane: str, plane_len: int,
                         module: str):
    """``plane_roofline.match_roofline_share``'s bytes bound (planes
    from shapes, each rule's stride table and class map, the verdicts:
    ``kernel_cost.grep_match_bytes``) over a *whole* launch's device
    time (``launch_seconds``) instead of busy time over dispatched
    launches, which reads a launch of a fifth of the traced interval a
    tenth short and a ``better: higher`` share as much too high. The
    rows a launch carried are ``device_records`` over the launches that
    *ended* in the interval (``ok``): both are counted at the end of a
    launch. In per cent; nothing without a trace, a program, its
    counters or a module of that name."""
    t = readings["trace"]
    if t is None:
        return None
    ended = t["counters"].get(f"lane.{lane}.ok")
    records = t["counters"].get(f"filter.{plugin}.device_records")
    rules, planes = [], 0
    for p in readings["filters"]:
        prog = getattr(p, "_program", None)
        if p.name == plugin and prog is not None \
                and hasattr(prog, "n_planes"):
            rules += prog.decision()["rules"]
            planes += prog.n_planes
    if not ended or not records or not rules:
        return None
    seconds = _launch_seconds_of_run(module)
    if not seconds:
        return None
    rows = records / ended
    need = kernel_cost.grep_match_bytes(
        rules, planes * rows * (plane_len + 4), rows)
    peak = kernel_cost.peaks(readings["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / peak) / seconds
