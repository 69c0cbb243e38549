"""What a launch sharded over a mesh costs **one chip**: the readers of
``element_cost.py`` divide one chip's module time by the elements and
bytes of the *whole* launch, which on a mesh of four reads a quarter.
Here a chip's time is set against a chip's share.

A chip's time is ``element_cost.launch_seconds`` over that chip's plane
alone (per module name the mean of its whole events, the children
summed), averaged over the device planes. A chip's share comes from the
program's counters over the traced interval: the launch's elements
(``filter.<plugin>.scan_elements``) or rows (``device_records``) over
the launches that *ended* in it (``ok``: all three are counted at the
end of a launch, so the ratio does not swing by the one launch in
seventeen that the interval's edge cuts between its dispatch and its
end), over the devices a launch was sharded across (``mesh_devices``
over ``mesh_launches``, both counted where a launch is dispatched). The rows are sharded and the
tables replicated (the ``batch`` variant), so a chip moves its share of
the planes and verdicts and the whole of every table. Without a trace,
or with a program that lacks the mesh counters (the parent of the PR
that added them) or sharded nothing, they give nothing."""

import statistics

from lookup import load_py

import kernel_cost
import trace_reduce


def chip_launch_seconds(planes: list, module: str):
    """``element_cost.launch_seconds`` of each device plane by itself,
    averaged over the planes on which a module of that name ran."""
    cost = load_py("readers", "element_cost")
    per_chip = [cost.launch_seconds([p], module) for p in planes
                if trace_reduce.DEVICE_PLANE.match(p["name"])]
    per_chip = [s for s in per_chip if s]
    return statistics.fmean(per_chip) if per_chip else None


def _chip_seconds_of_run(module: str):
    spans = load_py("readers", "program_spans")
    path = spans.newest_xplane()
    if not path:
        return None
    return chip_launch_seconds(spans.read_planes(path), module)


def devices_per_launch(counters: dict, plugin: str):
    sharded = counters.get(f"filter.{plugin}.mesh_launches")
    devices = counters.get(f"filter.{plugin}.mesh_devices")
    if not sharded or not devices:
        return None
    return devices / sharded


def chip_ns_per_element(readings, plugin: str, lane: str, module: str):
    t = readings["trace"]
    if t is None:
        return None
    elements = t["counters"].get(f"filter.{plugin}.scan_elements")
    ended = t["counters"].get(f"lane.{lane}.ok")
    devices = devices_per_launch(t["counters"], plugin)
    if not elements or not ended or not devices:
        return None
    seconds = _chip_seconds_of_run(module)
    if not seconds:
        return None
    return 1e9 * seconds / (elements / ended / devices)


def chip_match_roofline_share(readings, plugin: str, lane: str,
                              plane_len: int, module: str):
    """``element_cost.match_roofline_share`` for one chip of the mesh:
    the bytes bound of a chip's rows (their planes, lengths and
    verdicts) and the whole tables, over a chip's whole-launch time.
    In per cent."""
    t = readings["trace"]
    if t is None:
        return None
    ended = t["counters"].get(f"lane.{lane}.ok")
    records = t["counters"].get(f"filter.{plugin}.device_records")
    devices = devices_per_launch(t["counters"], plugin)
    rules, planes = [], 0
    for p in readings["filters"]:
        prog = getattr(p, "_program", None)
        if p.name == plugin and prog is not None \
                and hasattr(prog, "n_planes"):
            rules += prog.decision()["rules"]
            planes += prog.n_planes
    if not ended or not records or not devices or not rules:
        return None
    seconds = _chip_seconds_of_run(module)
    if not seconds:
        return None
    rows = records / ended / devices
    need = kernel_cost.grep_match_bytes(
        rules, planes * rows * (plane_len + 4), rows)
    peak = kernel_cost.peaks(readings["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / peak) / seconds
