"""A launch and a frame accounted piece by piece, from the program's own
spans and the device planes of the run's trace (one clock).

**The launch, joined to its own device modules.** For each *whole*
``lane.launch`` span of the lane in the traced interval — the first and
the last are left out, and so is one whose ``lane.begin`` or
``lane.wait`` the interval's edge cut off — take its ``lane.begin`` and
``lane.wait`` (same ``chunk`` and ``seg``), the ``grep.put`` /
``grep.call`` / ``grep.force`` spans inside it on its thread, and its
``XLA Modules`` events on each device plane. Where the xplane carries a
``run_id`` on both sides (the runtime's ``DoEnqueueProgram`` on the
host, the module on the device: :func:`read_runs`), a module is the
launch's whose window (first ``grep.call`` start → last ``grep.force``
end) holds its enqueue — a join on the host's clock alone. Else a
module goes to the launch whose window it shares most time with. A
launch that holds no module — the host fallback served it — is counted
and left out.

**The device's clock.** The profiler lays the device's clock over the
host's only to a millisecond or two (v5e under the sandbox: the device
0.3-1.7 ms early, another offset every run), which is the size of the
pieces on either side of the device. With ``run_id`` the offset is
bounded from both sides — no module starts before the host enqueued it
(``DoEnqueueProgram`` start), none ends after the host heard of its end
(``CompleteCallbacks`` start) — and the device plane is shifted to the
middle of that band (0.1-0.5 ms wide) before ``start_lag`` and
``copyout`` are taken. ``device``, ``device_gaps`` and the sum
``start_lag + copyout`` do not depend on it. Pieces, in ms a launch:

``spawn``        ``lane.begin`` start → ``lane.launch`` start
``put``          inside ``grep.put`` (the copy-in)
``call``         inside ``grep.call`` (the jitted calls and the merge)
``start_lag``    first ``grep.call`` start → first module start
``device``       the union of its modules
``device_gaps``  first module start → last module end, less ``device``:
                 the device idle *between a launch's children*
``copyout``      last module end → last ``grep.force`` end
``tail``         ``lane.launch`` end → ``lane.wait`` end
``total``        ``lane.begin`` start → ``lane.wait`` end
``unaccounted``  the part of ``total`` that none of the pieces covers
                 (taken as a union of intervals, so that a device which
                 starts on the first child while the host still
                 enqueues the second is not counted twice)

On several chips ``start_lag``, ``device`` and ``device_gaps`` are taken
per device plane and averaged; the last end is the latest chip's.

**The frame.** ``hop_ms``: ``forward.handover`` less ``forward.absorb``
by ``chunk`` (the two thread hops). ``thread_idle_share``: the share of
the traced interval in which the thread that holds a span has none open.
``idle_under``: the device's idle time (``program_spans``'
``idle_by_span``) under a list of spans, as a share of all of it.
``frame_ms``: ms inside a span for each frame, 0 where the program
writes the span and none fell into the interval (a GC pass, a wait that
never happened).

Every reader gives ``None`` on a trace without ``forward.handover``
(``grep.put`` for the launch): a program from before this account, whose
runs leave these metrics out instead of failing.

Run as a script on an xplane path (or with none: the newest) it prints
the whole account with mean, p95 and max of each piece:

    python3 benchmark/readers/launch_account.py [<file>.xplane.pb]
"""

import fnmatch
import functools
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if os.path.dirname(HERE) not in sys.path:  # run as a script
    sys.path.insert(0, os.path.dirname(HERE))

import stats  # noqa: E402
import trace_reduce  # noqa: E402
from lookup import load_py  # noqa: E402

program_spans = load_py("readers", "program_spans")

PREFIX = program_spans.PREFIX
#: written once a frame / once a launch by a program that has this
#: account; a trace without them is read as nothing
FRAME_MARK, LAUNCH_MARK = "forward.handover", "grep.put"
PIECES = ("spawn", "put", "call", "start_lag", "device", "device_gaps",
          "copyout", "tail", "unaccounted", "total")


def _threads(planes: list) -> list:
    """The host threads that hold a span: ``[[(name, start, end,
    stats), ...], ...]``, names without the prefix."""
    out = []
    for plane in planes:
        if trace_reduce.DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            mine = [(n[len(PREFIX):], s, s + d, st)
                    for n, s, d, st in line["events"]
                    if n.startswith(PREFIX)]
            if mine:
                out.append(mine)
    return out


def _modules(planes: list) -> list:
    """``XLA Modules`` of each device plane: ``[[(start, end), ...],
    ...]``, sorted."""
    return [sorted((s, s + d) for line in plane["lines"]
                   if line["name"] == "XLA Modules"
                   for _n, s, d, _st in line["events"])
            for plane in planes
            if trace_reduce.DEVICE_PLANE.match(plane["name"])]


def _interval(planes: list):
    lo = hi = None
    for plane in planes:
        for line in plane["lines"]:
            for _n, s, d, _st in line["events"]:
                lo = s if lo is None else min(lo, s)
                hi = s + d if hi is None else max(hi, s + d)
    return lo, hi


def _ids(st: dict):
    return st.get("chunk"), st.get("seg")


def read_runs(path: str):
    """What the xplane says of each program execution, by ``(device
    ordinal, run_id)``: ``{"modules": {plane name: {(start, end): key}},
    "enqueue": {key: start_ns}, "done": {key: start_ns}}`` — the device's
    ``XLA Modules`` events, the host's ``DoEnqueueProgram`` and
    ``CompleteCallbacks``. None where either side carries no
    ``run_id``."""
    from jax.profiler import ProfileData

    modules, host = {}, {"DoEnqueueProgram": {}, "CompleteCallbacks": {}}
    for plane in ProfileData.from_file(path).planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            ordinal = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for e in line.events:
                        run = dict(e.stats).get("run_id")
                        if run is not None:
                            modules.setdefault(plane.name, {})[
                                (e.start_ns, e.start_ns + e.duration_ns)] \
                                = (ordinal, run)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in host:
                        st = dict(e.stats)
                        if "run_id" in st:
                            host[e.name].setdefault(
                                (st.get("device_ordinal", 0), st["run_id"]),
                                e.start_ns)
    if not modules or not host["DoEnqueueProgram"]:
        return None
    return {"modules": modules, "enqueue": host["DoEnqueueProgram"],
            "done": host["CompleteCallbacks"]}


def clock_band(runs: dict, plane: str):
    """→ ``(lo, hi)`` ns: the shifts of the device plane's clock under
    which no module starts before its enqueue and none ends after its
    completion was heard; either may be None."""
    lo = hi = None
    for (s, e), key in runs["modules"].get(plane, {}).items():
        if key in runs["enqueue"]:
            d = runs["enqueue"][key] - s
            lo = d if lo is None else max(lo, d)
        if key in runs["done"]:
            d = runs["done"][key] - e
            hi = d if hi is None else min(hi, d)
    return lo, hi


def clock_shift(runs: dict, plane: str) -> float:
    lo, hi = clock_band(runs, plane)
    if lo is None or hi is None:
        return lo or hi or 0.0
    return (lo + hi) / 2


def _join(windows: list, modules: list) -> list:
    """``windows``: ``[(c0, f1), ...]`` of every launch seen, sorted and
    apart (one launch in flight); ``modules``: one device plane's, sorted
    → the modules of each window: a module goes to the window it shares
    most time with, or, sharing none, to the nearest one if that is
    nearer than the window is long."""
    out = [[] for _ in windows]
    for m in modules:
        def shared(w):
            return min(m[1], w[1]) - max(m[0], w[0])  # < 0: the distance

        i = max(range(len(windows)), key=lambda k: shared(windows[k]),
                default=None)
        if i is not None and shared(windows[i]) > \
                windows[i][0] - windows[i][1]:
            out[i].append(m)
    return out


def _join_by_run(windows: list, plane: str, runs: dict,
                 shift: float) -> list:
    """→ the plane's modules (on the shifted clock) of each window:
    those the host enqueued inside it (with the call, or from the
    runtime's own thread once the copy-in had landed)."""
    out = [[] for _ in windows]
    for (s, e), key in sorted(runs["modules"].get(plane, {}).items()):
        t = runs["enqueue"].get(key)
        if t is None:
            continue
        for i, (c0, f1) in enumerate(windows):
            if c0 <= t <= f1:
                out[i].append((s + shift, e + shift))
                break
    return out


def account(planes: list, lane: str = "grep", runs=None):
    """→ ``{"seen", "launches", "cut", "no_module", "clock_ms", "pieces":
    {piece: [ms of each whole launch]}}``, or None where the program
    wrote no ``grep.put`` (it does not split its dispatch). ``runs``:
    what :func:`read_runs` gave for the same file, or None."""
    threads = _threads(planes)
    if not any(n == LAUNCH_MARK for t in threads for n, *_r in t):
        return None
    begins, waits, launches = {}, {}, []
    for ti, mine in enumerate(threads):
        for name, s, e, st in mine:
            if st.get("lane") != lane:
                continue
            if name == "lane.begin":
                begins.setdefault(_ids(st), []).append((s, e))
            elif name == "lane.wait":
                waits.setdefault(_ids(st), []).append((s, e))
            elif name == "lane.launch":
                launches.append((s, e, _ids(st), ti))
    launches.sort()
    # what each launch holds on its own thread, and its window on the
    # device: first grep.call start → last grep.force end
    held, windows = [], []
    for l0, l1, ids, ti in launches:
        inside = {name: [(s, e) for n, s, e, st in threads[ti]
                         if n == name and l0 <= s and e <= l1
                         and _ids(st) == ids]
                  for name in ("grep.put", "grep.call", "grep.force")}
        held.append(inside)
        if inside["grep.call"]:
            windows.append((
                min(s for s, _e in inside["grep.call"]),
                max((e for _s, e in inside["grep.force"]), default=l1)))
        else:
            windows.append(None)
    served = [i for i, w in enumerate(windows) if w is not None]
    joined, clock = [], {}
    for plane in planes:
        if not trace_reduce.DEVICE_PLANE.match(plane["name"]):
            continue
        if runs is not None and plane["name"] in runs["modules"]:
            lo, hi = clock_band(runs, plane["name"])
            clock[plane["name"]] = [None if v is None else v / 1e6
                                    for v in (lo, hi)]
            joined.append(_join_by_run(
                [windows[i] for i in served], plane["name"], runs,
                clock_shift(runs, plane["name"])))
        else:
            joined.append(_join([windows[i] for i in served],
                                _modules([plane])[0]))
    out = {"seen": len(launches), "launches": 0, "cut": 0, "no_module": 0,
           "clock_ms": clock, "pieces": {p: [] for p in PIECES}}
    at_of = {i: at for at, i in enumerate(served)}
    for i in range(1, len(launches) - 1):  # the whole ones
        if i not in at_of:
            out["no_module"] += 1
            continue
        at = at_of[i]
        l0, l1, ids, _ti = launches[i]
        begin = max((b for b in begins.get(ids, ()) if b[0] <= l0),
                    default=None)
        wait = min((w for w in waits.get(ids, ()) if w[1] >= l1),
                   key=lambda w: w[1], default=None)
        if begin is None or wait is None:
            out["cut"] += 1
            continue
        mine = [dev[at] for dev in joined if dev[at]]
        if not mine:
            out["no_module"] += 1
            continue
        c0, f1 = windows[i]
        first = [min(s for s, _e in m) for m in mine]
        last = [max(e for _s, e in m) for m in mine]
        busy = [stats.total(stats.union(m)) for m in mine]
        n = len(mine)
        covered = stats.union([(begin[0], l0), (c0, f1), (l1, wait[1])]
                              + held[i]["grep.put"])
        total = wait[1] - begin[0]
        piece = {
            "spawn": l0 - begin[0],
            "put": stats.total(stats.union(held[i]["grep.put"])),
            "call": stats.total(stats.union(held[i]["grep.call"])),
            "start_lag": sum(f - c0 for f in first) / n,
            "device": sum(busy) / n,
            "device_gaps": sum(b - a - d for a, b, d
                               in zip(first, last, busy)) / n,
            "copyout": f1 - max(last),
            "tail": wait[1] - l1,
            "total": total,
            "unaccounted": total - stats.total(
                stats.intersect(covered, [(begin[0], wait[1])])),
        }
        out["launches"] += 1
        for name, ns in piece.items():
            out["pieces"][name].append(ns / 1e6)
    return out


def hops(planes: list):
    """``{chunk: ms}``: ``forward.handover`` less ``forward.absorb`` of
    the frames that have both, or None without a ``forward.handover``."""
    over, absorb = {}, {}
    for mine in _threads(planes):
        for name, s, e, st in mine:
            if name == "forward.handover":
                over[st.get("chunk")] = over.get(st.get("chunk"), 0) + e - s
            elif name == "forward.absorb":
                absorb[st.get("chunk")] = \
                    absorb.get(st.get("chunk"), 0) + e - s
    if not over:
        return None
    return {c: (ns - absorb[c]) / 1e6 for c, ns in over.items()
            if c in absorb}


def idle_share_of_thread(planes: list, span: str):
    """Share of the traced interval, in per cent, in which the thread
    that holds spans named ``span`` has no span open (the mean over such
    threads, where several do); None where none does."""
    lo, hi = _interval(planes)
    shares = []
    for mine in _threads(planes):
        if any(n == span for n, *_r in mine):
            open_ = stats.total(stats.intersect(
                stats.union([(s, e) for _n, s, e, _st in mine]),
                [(lo, hi)]))
            shares.append(100.0 * (1.0 - open_ / (hi - lo)))
    return sum(shares) / len(shares) if shares else None


# ------------------------------------------------------------- readers

@functools.lru_cache(maxsize=2)
def _planes(path: str) -> list:
    return program_spans.read_planes(path)


@functools.lru_cache(maxsize=2)
def _account(path: str, lane: str):
    return account(_planes(path), lane, read_runs(path))


def _run_planes():
    path = program_spans.newest_xplane()
    return _planes(path) if path else None


def _has(planes, mark: str) -> bool:
    return planes is not None and any(
        n == mark for t in _threads(planes) for n, *_r in t)


def piece_ms(readings, piece: str, lane: str = "grep"):
    """Mean of one piece over the whole launches of the lane, in ms."""
    del readings  # the trace directory is not among them
    path = program_spans.newest_xplane()
    acc = _account(path, lane) if path else None
    if acc is None or not acc["launches"]:
        return None
    return sum(acc["pieces"][piece]) / acc["launches"]


def hop_ms(readings):
    """The two thread hops of a frame, in ms: mean over ``chunk``s of
    ``forward.handover`` less ``forward.absorb``."""
    del readings
    planes = _run_planes()
    got = hops(planes) if planes is not None else None
    return sum(got.values()) / len(got) if got else None


def thread_idle_share(readings, span: str):
    del readings
    planes = _run_planes()
    if not _has(planes, FRAME_MARK):
        return None
    return idle_share_of_thread(planes, span)


def _frame_table(readings):
    t = program_spans.table(readings)
    return t if t is not None and FRAME_MARK in t["spans"] else None


def frame_ms(readings, span: str, per: str = "forward.reencode"):
    """Milliseconds inside ``span`` for each span named ``per`` (one a
    frame); 0 where no span of that name fell into the interval."""
    t = _frame_table(readings)
    if t is None or per not in t["spans"]:
        return None
    row = t["spans"].get(span)
    return 1e3 * (row["total_s"] if row else 0.0) / t["spans"][per]["count"]


def idle_under(readings, spans: list):
    """The device's idle time under the named spans (``fnmatch``
    patterns over ``program_spans``' ``idle_by_span``) as a share of all
    its idle time, in per cent."""
    t = _frame_table(readings)
    if t is None or not t["idle_s"]:
        return None
    under = sum(s for name, s in t["idle_by_span"].items()
                if any(fnmatch.fnmatchcase(name, p) for p in spans))
    return 100.0 * under / t["idle_s"]


# -------------------------------------------------------------- script

def main(argv) -> int:
    path = argv[1] if len(argv) > 1 else program_spans.newest_xplane()
    if not path:
        print("no .xplane.pb found", file=sys.stderr)
        return 2
    planes = _planes(path)
    lo, hi = _interval(planes)
    print(f"{path}\ntraced interval {(hi - lo) / 1e9:.4f} s")
    acc = _account(path, "grep")
    if acc is None:
        print(f"the trace holds no {PREFIX}{LAUNCH_MARK} span",
              file=sys.stderr)
    else:
        print(f"\nlane=grep: {acc['seen']} launches seen, "
              f"{acc['launches']} whole, {acc['cut']} cut by the edge, "
              f"{acc['no_module']} without a device module")
        for plane, (early, late) in sorted(acc["clock_ms"].items()):
            print(f"{plane}: its clock is early by {early} to {late} ms "
                  f"(no start before the enqueue, no end after the "
                  f"completion was heard); shifted to the middle")
        if acc["launches"]:
            print(f"{'piece':14s} {'mean_ms':>10s} {'p95_ms':>10s} "
                  f"{'max_ms':>10s}")
            for name in PIECES:
                v = acc["pieces"][name]
                print(f"{name:14s} {sum(v) / len(v):10.4f} "
                      f"{stats.percentile(v, 0.95):10.4f} {max(v):10.4f}")
    got = hops(planes)
    if got is None:
        print(f"the trace holds no {PREFIX}{FRAME_MARK} span",
              file=sys.stderr)
        return 1
    if got:
        v = list(got.values())
        print(f"\n{'hop (handover - absorb)':24s} frames {len(v)}  mean "
              f"{sum(v) / len(v):.4f}  p95 "
              f"{stats.percentile(v, 0.95):.4f}  max {max(v):.4f} ms")
    idle = idle_share_of_thread(planes, "forward.absorb")
    if idle is not None:
        print(f"the absorbing thread has no span open {idle:.2f} % of "
              f"the interval")
    t = program_spans.reduce_planes(planes)
    if t and t["idle_s"]:
        print(f"\ndevice idle {t['idle_s']:.4f} s, by span")
        for name, s in sorted(t["idle_by_span"].items(),
                              key=lambda kv: -kv[1]):
            if s:
                print(f"{name:22s} {s:9.4f} {100 * s / t['idle_s']:6.2f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
