"""Readers over the program's own spans (``fluentbit_tpu/core/spans.py``):
``fbtpu:<name>`` annotations the program writes into the profiler's
trace while a session is active, on the device trace's clock, each with
its ids (``chunk``, ``seg``) as stats.

``readings`` does not carry the trace directory, so the run's
``.xplane.pb`` is found where ``run.py`` put it: the newest
``<tempdir>/fbtpu-bench-*/trace`` (removed only after the readers ran)
whose file was written after this process started — a killed run leaves
its directory behind, and an older file is another run's. Without a
file, or with a program that writes no such span (the parent of the PR
that brought them), every reader gives ``None``. (``readings["trace"]``
does not decide: it is ``None`` on a CPU rehearsal, where the trace has
no TPU plane and the host plane is as good as on the chip.)

Shares are of the traced interval (first to last traced event), like the
device metrics. The engine thread is an asyncio loop, so spans of other
tasks may open inside ``forward.read`` and end after it: self time is
taken by interval arithmetic — at each instant the *innermost* span of a
thread is the one that started last — never from a stack.

Run as a script on an xplane path (or with none: the newest) it prints
the whole table: inclusive and self time per span, the device's idle
gaps by span, device time per module name.

    python3 benchmark/readers/program_spans.py [<file>.xplane.pb]
"""

import functools
import glob
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
if os.path.dirname(HERE) not in sys.path:  # run as a script
    sys.path.insert(0, os.path.dirname(HERE))

import stats  # noqa: E402
import trace_reduce  # noqa: E402

PREFIX = "fbtpu:"
WAIT = "lane.wait"      # the thread that absorbs is the line it is on;
#                         idle under it belongs to the lane worker's spans
READ = "forward.read"   # the engine loop's thread: what the first leaves


def process_start() -> float:
    """Epoch seconds at which this process started, from ``/proc``
    (whole seconds of the boot time, so good to a second); 0.0 where
    there is none, which rejects no file."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            boot = next(int(ln.split()[1]) for ln in f
                        if ln.startswith("btime "))
        return boot + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, StopIteration):
        return 0.0


def newest_xplane(since=None):
    """This run's trace file: the newest one written since this process
    started (``since``, epoch seconds), or None."""
    since = process_start() - 1.0 if since is None else since
    dirs = glob.glob(os.path.join(tempfile.gettempdir(),
                                  "fbtpu-bench-*", "trace"))
    found = [p for p in map(trace_reduce.find_xplane, dirs)
             if p and os.path.getmtime(p) >= since]
    return max(found, key=os.path.getmtime) if found else None


def read_planes(path: str) -> list:
    """``[{"name", "lines": [{"name", "events": [(name, start_ns,
    dur_ns, stats), ...]}]}]``: the host planes' ``fbtpu:`` events with
    their stats, the device planes' ``XLA Ops`` and ``XLA Modules``, and
    one ``("", lo, hi - lo, {})`` event on a line ``"extent"`` of a plane
    ``"extent"`` for everything else, so that the traced interval is the
    one ``trace_reduce`` takes."""
    from jax.profiler import ProfileData

    planes, lo, hi = [], None, None
    for plane in ProfileData.from_file(path).planes:
        device = bool(trace_reduce.DEVICE_PLANE.match(plane.name))
        if not (device or plane.name.startswith("/host:")):
            continue
        lines = []
        for line in plane.lines:
            if device and line.name not in ("XLA Ops", "XLA Modules"):
                continue
            events = []
            for e in line.events:
                s, d = e.start_ns, e.duration_ns
                lo = s if lo is None else min(lo, s)
                hi = s + d if hi is None else max(hi, s + d)
                if device:
                    events.append((e.name, s, d, {}))
                elif e.name.startswith(PREFIX):
                    events.append((e.name, s, d, dict(e.stats)))
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    if lo is not None:
        planes.append({"name": "extent", "lines": [
            {"name": "extent", "events": [("", lo, hi - lo, {})]}]})
    return planes


def innermost(spans: list) -> list:
    """``spans``: ``[(start, end, key), ...]`` of one thread, in any
    order and nested or overlapping anyhow → disjoint pieces ``[(lo, hi,
    key), ...]`` covering their union, each under the span that started
    last among those open there."""
    cuts = sorted({t for s, e, _k in spans if e > s for t in (s, e)})
    order = sorted((s for s in spans if s[1] > s[0]),
                   key=lambda s: s[0])
    out, open_, i = [], [], 0
    for lo, hi in zip(cuts, cuts[1:]):
        while i < len(order) and order[i][0] <= lo:
            open_.append(order[i])
            i += 1
        open_ = [s for s in open_ if s[1] > lo]
        if open_:
            top = max(open_, key=lambda s: s[0])
            if out and out[-1][1] == lo and out[-1][2] is top[2]:
                out[-1] = (out[-1][0], hi, top[2])
            else:
                out.append((lo, hi, top[2]))
    return out


def reduce_planes(planes: list):
    """→ the table, or None when the program wrote no span: traced
    interval, per span name count / inclusive / self seconds (and under
    ``lanes`` count / inclusive seconds of those that carry a ``lane``
    stat, by lane), the first device's idle gaps by the span that was
    innermost on the engine loop's thread, device seconds per module
    name."""
    lo = hi = None
    threads, devices = [], []
    for plane in planes:
        for line in plane["lines"]:
            for _n, s, d, _st in line["events"]:
                lo = s if lo is None else min(lo, s)
                hi = s + d if hi is None else max(hi, s + d)
            if trace_reduce.DEVICE_PLANE.match(plane["name"]):
                continue
            mine = [(s, s + d, (n[len(PREFIX):], st))
                    for n, s, d, st in line["events"]
                    if n.startswith(PREFIX)]
            if mine:
                threads.append(mine)
        if trace_reduce.DEVICE_PLANE.match(plane["name"]):
            devices.append(plane)
    if not threads:
        return None
    spans = {}
    for mine in threads:
        by_name = {}
        for s, e, (name, st) in mine:
            by_name.setdefault((name, None), []).append((s, e))
            if "lane" in st:
                by_name.setdefault((name, st["lane"]), []).append((s, e))
        for (name, lane), ivs in by_name.items():
            row = spans.setdefault(name, {"count": 0, "total_s": 0.0,
                                          "self_s": 0.0, "lanes": {}})
            if lane is not None:
                row = row["lanes"].setdefault(
                    lane, {"count": 0, "total_s": 0.0})
            row["count"] += len(ivs)
            row["total_s"] += stats.total(stats.union(ivs)) / 1e9
        for s, e, (name, _st) in innermost(mine):
            spans[name]["self_s"] += (e - s) / 1e9
    out = {"interval_s": (hi - lo) / 1e9, "spans": spans,
           "modules": {}, "idle_s": None, "idle_by_span": {}}
    for plane in devices:
        for line in plane["lines"]:
            if line["name"] == "XLA Modules":
                for n, _s, d, _st in line["events"]:
                    name = n.split("(")[0]
                    out["modules"][name] = \
                        out["modules"].get(name, 0.0) + d / 1e9
    ops = [(s, s + d) for plane in devices[:1] for line in plane["lines"]
           if line["name"] == "XLA Ops"
           for _n, s, d, _st in line["events"]]
    if ops:
        _busy, gaps = stats.busy_and_gaps(ops, (lo, hi))
        out["idle_s"] = stats.total(gaps) / 1e9
        out["idle_by_span"] = _idle_by_span(gaps, threads)
    return out


def _idle_by_span(gaps: list, threads: list) -> dict:
    """Idle gaps of the device → seconds by the innermost span open on
    the thread that holds ``lane.wait`` (since the input absorbs on a
    worker of its own that is no longer the engine loop's); where that
    span is ``lane.wait`` itself, by the lane worker's innermost span
    under the same ``chunk`` and ``seg`` (the device idle while the host
    waits for it is dispatch latency and copy-out). What that thread
    leaves uncovered goes to the thread that holds ``forward.read``
    (which comes first where no ``lane.wait`` is found), then to any
    other thread's spans. What no span of the program covers is
    ``unattributed``."""
    def rank(thread):
        names = {k[0] for _s, _e, k in thread}
        return 0 if WAIT in names else 1 if READ in names else 2

    by, rest = {}, gaps
    for mine in sorted(threads, key=rank):
        others = [sp for t in threads if t is not mine for sp in t]
        for s, e, (name, st) in innermost(mine):
            piece = stats.intersect(rest, [(s, e)])
            if not piece:
                continue
            rest = stats.subtract(rest, [(s, e)])
            if name == WAIT:
                ids = (st.get("chunk"), st.get("seg"))
                work = [sp for sp in others
                        if (sp[2][1].get("chunk"),
                            sp[2][1].get("seg")) == ids]
                for ws, we, (wname, _st) in innermost(work):
                    got = stats.intersect(piece, [(ws, we)])
                    by[wname] = by.get(wname, 0.0) + stats.total(got) / 1e9
                    piece = stats.subtract(piece, [(ws, we)])
            by[name] = by.get(name, 0.0) + stats.total(piece) / 1e9
    by["unattributed"] = stats.total(rest) / 1e9
    return by


@functools.lru_cache(maxsize=2)
def _table(path: str):
    return reduce_planes(read_planes(path))


def table(readings=None):
    """The reduced table of this run's trace, or None."""
    del readings  # the trace directory is not among them
    path = newest_xplane()
    return _table(path) if path else None


# ------------------------------------------------------------- readers

def _row(readings, span: str, lane=None):
    """The table and the span's row in it: of every lane, or of the
    spans that carry ``lane`` alone."""
    t = table(readings)
    if t is None:
        return None, None
    row = t["spans"].get(span)
    if row is not None and lane is not None:
        row = row["lanes"].get(lane)
    return t, row


def share(readings, span: str):
    """Share of the traced interval inside the span, in per cent
    (inclusive; nested spans of one name on one thread count once)."""
    t, row = _row(readings, span)
    return None if row is None else 100.0 * row["total_s"] / t["interval_s"]


def self_share(readings, span: str):
    """Share of the traced interval in which the span was the innermost
    one open on its thread, in per cent: its time less what the spans
    that opened inside it cover."""
    t, row = _row(readings, span)
    return None if row is None else 100.0 * row["self_s"] / t["interval_s"]


def count_ratio(readings, num: str, den: str):
    """Spans named ``num`` for each span named ``den``."""
    _t, row = _row(readings, num)
    _t, per = _row(readings, den)
    if row is None or per is None:
        return None
    return row["count"] / per["count"]


def ms_per(readings, span: str, per: str, lane=None):
    """Milliseconds inside ``span`` for each span named ``per``; with
    ``lane``, of the spans that carry that ``lane`` stat alone."""
    _t, row = _row(readings, span, lane)
    _t, den = _row(readings, per, lane)
    if row is None or den is None:
        return None
    return 1e3 * row["total_s"] / den["count"]


def main(argv) -> int:
    path = argv[1] if len(argv) > 1 else newest_xplane()
    if not path:
        print("no .xplane.pb found", file=sys.stderr)
        return 2
    t = _table(path)
    if t is None:
        print("the trace holds no fbtpu: span", file=sys.stderr)
        return 1
    iv = t["interval_s"]
    print(f"{path}\ntraced interval {iv:.4f} s")
    print(f"\n{'span':22s} {'count':>6s} {'total_s':>9s} {'%':>6s} "
          f"{'self_s':>9s} {'%':>6s}")
    for name, r in sorted(t["spans"].items(),
                          key=lambda kv: -kv[1]["self_s"]):
        print(f"{name:22s} {r['count']:6d} {r['total_s']:9.4f} "
              f"{100 * r['total_s'] / iv:6.2f} {r['self_s']:9.4f} "
              f"{100 * r['self_s'] / iv:6.2f}")
        for lane, lr in sorted(r["lanes"].items()):
            print(f"{'  lane=' + lane:22s} {lr['count']:6d} "
                  f"{lr['total_s']:9.4f} {100 * lr['total_s'] / iv:6.2f}")
    if t["idle_s"] is not None:
        print(f"\ndevice idle {t['idle_s']:.4f} s "
              f"({100 * t['idle_s'] / iv:.2f} % of the interval), by span")
        for name, s in sorted(t["idle_by_span"].items(),
                              key=lambda kv: -kv[1]):
            print(f"{name:22s} {s:9.4f} "
                  f"{100 * s / t['idle_s'] if t['idle_s'] else 0:6.2f}")
    if t["modules"]:
        print("\ndevice seconds by module")
        for name, s in sorted(t["modules"].items(), key=lambda kv: -kv[1]):
            print(f"{name:40s} {s:9.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
