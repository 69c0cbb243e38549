"""From a ``jax.profiler`` trace (``.xplane.pb``) to the device numbers:
busy time as the union of the ``XLA Ops`` intervals of each device
plane, program launches as the events on ``XLA Modules``, the operations
that took most time, and the device's idle gaps attributed to the host
spans (``bench:<name>`` annotations) that were open during them."""

import glob
import os
import re

import stats
from spans import TRACE_PREFIX

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
#: innermost first: a gap under filter is also under append
GAP_ORDER = ("filter", "flush", "append", "decode")


def find_xplane(trace_dir: str):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def op_label(name: str) -> str:
    """``%fusion.1 = s32[1048576]{...} fusion(...)`` → ``fusion.1
    s32[1048576]``: opcode instance and result shape, 48 characters."""
    m = re.match(r"%?(\S+) = \(?([a-z0-9]+\[[^\]]*\])?", name)
    label = f"{m.group(1)} {m.group(2) or ''}".strip() if m else name
    return label[:48]


def reduce_planes(planes: list) -> dict:
    """``planes``: ``[{"name", "lines": [{"name", "events": [(name,
    start_ns, dur_ns), ...]}]}]`` → the numbers. None without a device
    plane on which an operation ran."""
    per_device, launches, ops, host = [], 0, {}, {}
    lo = hi = None
    for plane in planes:
        for line in plane["lines"]:
            for _n, s, d in line["events"]:
                lo = s if lo is None else min(lo, s)
                hi = s + d if hi is None else max(hi, s + d)
        if DEVICE_PLANE.match(plane["name"]):
            events = []
            for line in plane["lines"]:
                if line["name"] == "XLA Ops":
                    for n, s, d in line["events"]:
                        events.append((s, s + d))
                        ops[n] = ops.get(n, 0.0) + d
                elif line["name"] == "XLA Modules":
                    launches += len(line["events"])
            per_device.append(events)
        elif plane["name"].startswith("/host:"):
            for line in plane["lines"]:
                for n, s, d in line["events"]:
                    if n.startswith(TRACE_PREFIX):
                        key = n[len(TRACE_PREFIX):].split(":")[0]
                        host.setdefault(key, []).append((s, s + d))
    if not per_device or not any(per_device):
        return None
    window = (lo, hi)
    busy = [stats.busy_and_gaps(ev, window) for ev in per_device]
    busy_ns = sum(b for b, _g in busy) / len(busy)
    gaps = busy[0][1]  # idle gaps of the first device, by host span
    by_span, rest = [], gaps
    for key in GAP_ORDER:
        cover = stats.union(host.get(key, []))
        by_span.append([key, stats.total(stats.intersect(rest, cover)) / 1e9])
        rest = stats.subtract(rest, cover)
    by_span.append(["unattributed", stats.total(rest) / 1e9])
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": busy_ns / 1e9,
        "span_s": (hi - lo) / 1e9,
        "launches": launches,
        "devices": len(per_device),
        "device_ops": [[op_label(n), d / 1e9] for n, d in top],
        "idle_gaps": sorted(by_span, key=lambda kv: -kv[1]),
    }


def read_planes(path: str) -> list:
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        if not (DEVICE_PLANE.match(plane.name)
                or plane.name.startswith("/host:")):
            continue
        lines = []
        for line in plane.lines:
            if DEVICE_PLANE.match(plane.name) and line.name not in (
                    "XLA Ops", "XLA Modules"):
                continue
            lines.append({"name": line.name, "events": [
                (e.name, e.start_ns, e.duration_ns) for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def reduce_trace(trace_dir: str):
    path = find_xplane(trace_dir)
    return reduce_planes(read_planes(path)) if path else None
