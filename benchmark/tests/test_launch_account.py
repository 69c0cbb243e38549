"""The launch and the frame accounted piece by piece
(``readers/launch_account.py``), on hand-made planes. Not part of
tier-1:

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

from lookup import load_json, load_py  # noqa: E402

acct = load_py("readers", "launch_account")
spans = load_py("readers", "program_spans")

PERIOD = 2000
NEW = ("forward.handover", "forward.await", "grep.put", "grep.call",
       "gc.collect")


def ev(name, start, end, **stats):
    return ("fbtpu:" + name, start, end - start, stats)


def frame(t, chunk, modules=((300, 500), (600, 800)), begin=True):
    """One frame that starts at ``t`` → its events on the loop's
    thread, the input's worker and the lane's worker, and its device
    modules. The device starts on the first child (300) while the host
    still enqueues (the call ends at 390)."""
    c = {"chunk": chunk}
    ids = {"chunk": chunk, "seg": 0, "lane": "grep"}
    loop = [ev("forward.reencode", t - 40, t - 10, **c),
            ev("forward.handover", t, t + 1000, **c),
            ev("forward.ack", t + 1000, t + 1020, **c)]
    worker = [ev("forward.absorb", t + 10, t + 990, **c),
              ev("engine.append", t + 20, t + 980, **c),
              ev("filter.grep", t + 30, t + 970, **c),
              ev("grep.stage", t + 40, t + 100, seg=0, **c),
              ev("lane.wait", t + 130, t + 900, **ids),
              ev("grep.compact", t + 900, t + 960, **c)]
    if begin:
        worker.append(ev("lane.begin", t + 100, t + 120, **ids))
    lane = [ev("lane.launch", t + 150, t + 880, **ids),
            ev("grep.dispatch", t + 160, t + 400, **ids),
            ev("grep.put", t + 170, t + 250, **ids),
            ev("grep.call", t + 260, t + 390, children=2, **ids),
            ev("grep.force", t + 400, t + 870, **ids)]
    return loop, worker, lane, [(t + a, t + b) for a, b in modules]


def planes_of(frames, devices=1, shift=0):
    """``frames``: what :func:`frame` gives → the plane list; device
    ``d`` runs every module ``d * shift`` later."""
    loop, worker, lane, mods = [], [], [], []
    for lo, wo, la, mo in frames:
        loop += lo
        worker += wo
        lane += la
        mods += mo
    hi = max(s + d for _n, s, d, _st in loop)
    out = [{"name": "/host:CPU", "lines": [
        {"name": "flb-engine", "events": loop},
        {"name": "flb-fw-forward.0", "events": worker},
        {"name": "flb-lane-grep", "events": lane}]}]
    for d in range(devices):
        events = [(f"jit_grep_scan_{i % 2}(1)", s + d * shift, e - s, {})
                  for i, (s, e) in enumerate(mods)]
        out.append({"name": f"/device:TPU:{d}", "lines": [
            {"name": "XLA Modules", "events": events},
            {"name": "XLA Ops", "events": [
                ("%fusion.1 = s32[8]{0} fusion(...)", s, d_, {})
                for _n, s, d_, _st in events]}]})
    out.append({"name": "extent", "lines": [
        {"name": "extent", "events": [("", -100, hi + 300, {})]}]})
    return out


def five(**middle):
    """Five frames; the keyword arguments go to the third."""
    return [frame(i * PERIOD, f"c{i}", **(middle if i == 2 else {}))
            for i in range(5)]


def strip(planes, names):
    """The same trace from a program that writes none of ``names``."""
    return [{"name": p["name"], "lines": [
        {"name": ln["name"], "events": [
            e for e in ln["events"]
            if e[0][len("fbtpu:"):] not in names]}
        for ln in p["lines"]]} for p in planes]


def test_pieces_of_a_whole_launch():
    got = acct.account(planes_of(five()))
    assert (got["seen"], got["launches"], got["cut"], got["no_module"]) \
        == (5, 3, 0, 0)
    want = {"spawn": 50, "put": 80, "call": 130, "start_lag": 40,
            "device": 400, "device_gaps": 100, "copyout": 70, "tail": 20,
            "total": 800,
            # 150-170, 250-260 and 870-880: the launch outside its pieces
            "unaccounted": 40}
    for name, ns in want.items():
        assert got["pieces"][name] == [pytest.approx(ns / 1e6)] * 3, name
    # the device began inside the call: the lag (260-300) and the
    # device's first 390 - 300 lie under it, and the union that gives
    # ``unaccounted`` does not count them twice
    pieces = {k: v[0] * 1e6 for k, v in got["pieces"].items()}
    assert sum(pieces[k] for k in want) - 2 * pieces["total"] \
        == pytest.approx(40 + 90)


def test_first_and_last_launch_and_one_cut_by_the_edge_are_left_out():
    got = acct.account(planes_of(five(begin=False)))
    assert (got["seen"], got["launches"], got["cut"]) == (5, 2, 1)
    two = acct.account(planes_of(five()[:2]))
    assert two["launches"] == 0 and two["pieces"]["device"] == []


def test_two_children_with_a_gap_read_apart_from_one_without():
    one = acct.account(planes_of(five(modules=((300, 800),))))
    assert one["pieces"]["device"][1] == pytest.approx(500e-6)
    assert one["pieces"]["device_gaps"][1] == pytest.approx(0.0)
    assert one["pieces"]["device"][0] == pytest.approx(400e-6)
    assert one["pieces"]["device_gaps"][0] == pytest.approx(100e-6)


def test_a_launch_without_a_module_is_counted_and_left_out():
    got = acct.account(planes_of(five(modules=())))
    assert (got["launches"], got["no_module"], got["cut"]) == (2, 1, 0)
    assert got["pieces"]["device"] == [pytest.approx(400e-6)] * 2


def test_four_device_planes_average_and_the_last_end_is_the_latest():
    got = acct.account(planes_of(five(), devices=4, shift=10))
    p = {k: v[0] * 1e6 for k, v in got["pieces"].items()}
    assert p["device"] == pytest.approx(400)
    assert p["device_gaps"] == pytest.approx(100)
    assert p["start_lag"] == pytest.approx(40 + (0 + 10 + 20 + 30) / 4)
    assert p["copyout"] == pytest.approx(70 - 30)


def runs_of(planes, early, enqueue_lag=30, heard_lag=50):
    """What ``read_runs`` would give for ``planes`` had the runtime
    written its events: each module enqueued ``enqueue_lag`` before its
    true start and heard of ``heard_lag`` after its true end, the device
    plane's clock ``early`` against the host's (the planes are shifted
    in place)."""
    runs = {"modules": {}, "enqueue": {}, "done": {}}
    for plane in planes:
        if not plane["name"].startswith("/device:"):
            continue
        ordinal = int(plane["name"].rsplit(":", 1)[1])
        for line in plane["lines"]:
            line["events"] = [(n, s - early, d, st)
                              for n, s, d, st in line["events"]]
            if line["name"] != "XLA Modules":
                continue
            for run, (_n, s, d, _st) in enumerate(line["events"]):
                key = (ordinal, run)
                runs["modules"].setdefault(plane["name"], {})[
                    (s, s + d)] = key
                true = s + early
                runs["enqueue"][key] = true - enqueue_lag
                runs["done"][key] = true + d + heard_lag
    return runs


def test_run_ids_join_on_the_hosts_clock_and_bound_the_devices():
    """The device's clock 500 early: every first module reads as begun
    before its call (300 - 500 < 260), and before the copy-in ended.
    The enqueue of each run lies inside its launch's window on the
    host's clock, which joins it; no start before an enqueue and no end
    after its completion was heard bound the offset to 470-550, and the
    plane is read from the middle of that band."""
    planes = planes_of(five())
    runs = runs_of(planes, early=500)
    (lo, hi), = [acct.clock_band(runs, "/device:TPU:0")]
    assert (lo, hi) == (470, 550)
    assert acct.clock_shift(runs, "/device:TPU:0") == 510
    got = acct.account(planes, runs=runs)
    assert (got["launches"], got["no_module"]) == (3, 0)
    assert got["clock_ms"] == {"/device:TPU:0": [
        pytest.approx(470e-6), pytest.approx(550e-6)]}
    p = {k: v[0] * 1e6 for k, v in got["pieces"].items()}
    # off by the half band at most: 510 - 500
    assert p["start_lag"] == pytest.approx(40 + 10)
    assert p["copyout"] == pytest.approx(70 - 10)
    # what does not ride on the clock
    assert (p["device"], p["device_gaps"], p["unaccounted"]) == (
        pytest.approx(400), pytest.approx(100), pytest.approx(40))
    assert p["start_lag"] + p["copyout"] == pytest.approx(40 + 70)
    # without run ids the same planes are joined by the time they share
    # with a window, on the device's own clock
    raw = acct.account(planes)
    assert raw["launches"] == 3 and raw["clock_ms"] == {}
    assert raw["pieces"]["device"][0] == pytest.approx(400e-6)
    assert raw["pieces"]["start_lag"][0] == pytest.approx((40 - 500) / 1e6)


def test_a_module_enqueued_after_the_call_returned_is_still_the_launchs():
    """The runtime enqueues a program once its inputs have landed: from
    its own thread, after ``grep.call`` is over (the parser cell)."""
    planes = planes_of(five(modules=((450, 600), (650, 800))))
    runs = runs_of(planes, early=0)
    assert runs["enqueue"][(0, 4)] == 4000 + 450 - 30   # the call: -4390
    got = acct.account(planes, runs=runs)
    assert (got["launches"], got["no_module"]) == (3, 0)
    assert got["pieces"]["device"][1] == pytest.approx(300e-6)
    assert got["pieces"]["start_lag"][1] == pytest.approx((450 - 260 + 10)
                                                          / 1e6)


def test_a_launch_of_another_lane_is_not_taken():
    planes = planes_of(five())
    planes[0]["lines"].append({"name": "flb-lane-flux", "events": [
        ev("lane.launch", 2500, 2600, chunk="c1", lane="flux")]})
    assert acct.account(planes)["seen"] == 5
    assert acct.account(planes, lane="flux")["seen"] == 1


def test_hop_pairs_by_chunk():
    planes = planes_of(five())
    # a handover whose absorb the interval cut off, and an absorb alone
    planes[0]["lines"][0]["events"].append(
        ev("forward.handover", 10500, 10900, chunk="late"))
    planes[0]["lines"][1]["events"].append(
        ev("forward.absorb", -90, -10, chunk="early"))
    got = acct.hops(planes)
    assert got == {f"c{i}": pytest.approx(20e-6) for i in range(5)}
    # two tries of one frame (a deferred ack) add up under its chunk
    planes[0]["lines"][0]["events"].append(
        ev("forward.handover", 1100, 1200, chunk="c0"))
    planes[0]["lines"][1]["events"].append(
        ev("forward.absorb", 1110, 1150, chunk="c0"))
    assert acct.hops(planes)["c0"] == pytest.approx(80e-6)


def test_the_absorbing_thread_is_idle_between_frames():
    planes = planes_of(five())
    lo, hi = acct._interval(planes)
    assert (lo, hi) == (-100, 9220)
    assert acct.idle_share_of_thread(planes, "forward.absorb") \
        == pytest.approx(100 * (1 - 5 * 980 / (hi - lo)))
    assert acct.idle_share_of_thread(planes, "no.such") is None


@pytest.fixture
def run_of(monkeypatch):
    """The readers over a run whose trace is the given planes."""
    def install(planes, path="a-file"):
        monkeypatch.setattr(spans, "newest_xplane", lambda: path)
        monkeypatch.setattr(spans, "_table",
                            lambda _path: spans.reduce_planes(planes))
        monkeypatch.setattr(acct, "_planes", lambda _path: planes)
        monkeypatch.setattr(acct, "_account",
                            lambda _path, lane: acct.account(planes, lane))
    return install


def metric_args(name):
    spec = load_json(os.path.join(BENCH, "layer_metrics", name + ".json"))
    module, func = spec["reader"].split(":")
    assert module == "launch_account"
    return getattr(acct, func), spec["args"]


IDLE = ("device.idle_handover_share", "device.idle_launch_share",
        "device.idle_host_share")


def test_the_three_idle_shares_and_unattributed_sum_to_100(run_of):
    planes = planes_of(five())
    # the flush timer on the loop's thread, a GC pass on the worker
    planes[0]["lines"][0]["events"].append(ev("engine.flush", 1300, 1500))
    planes[0]["lines"][1]["events"].append(
        ev("gc.collect", 2050, 2090, chunk="c1", gen=2, collected=7))
    run_of(planes)
    t = spans.reduce_planes(planes)
    shares = {}
    for name in IDLE:
        reader, args = metric_args(name)
        shares[name] = reader({}, **args)
    unattributed = load_py("readers", "program_trace") \
        .idle_unattributed_share({})
    assert sum(shares.values()) + unattributed == pytest.approx(100.0)
    assert all(v > 0 for v in shares.values()) and unattributed > 0
    # inside a launch: spawn to the first module, between the children,
    # the copy-out and the wake-up
    by = t["idle_by_span"]
    assert shares["device.idle_launch_share"] == pytest.approx(
        100 * sum(by.get(k, 0) for k in (
            "lane.begin", "lane.launch", "grep.dispatch", "grep.put",
            "grep.call", "grep.force", "lane.wait")) / t["idle_s"])
    assert by["gc.collect"] == pytest.approx(40e-9)
    assert by["engine.flush"] == pytest.approx(200e-9)


def test_the_metrics_over_a_run(run_of):
    run_of(planes_of(five()))
    for piece, ns in (("put", 80), ("call", 130), ("device", 400),
                      ("unaccounted", 40)):
        assert acct.piece_ms({}, piece) == pytest.approx(ns / 1e6)
    assert acct.hop_ms({}) == pytest.approx(20e-6)
    assert acct.frame_ms({}, "forward.ack") == pytest.approx(20e-6)
    # the program writes the span, none fell into the interval: 0
    assert acct.frame_ms({}, "forward.await") == 0.0
    assert acct.frame_ms({}, "gc.collect") == 0.0
    assert acct.thread_idle_share({}, "forward.absorb") > 40


def test_every_reader_gives_none_on_a_trace_of_the_parent(run_of):
    """No ``forward.handover``, no ``grep.put``: the parent's traced
    runs leave the new metrics out instead of failing — ``forward.ack``,
    which the parent writes, among them."""
    parent = strip(planes_of(five()), NEW)
    run_of(parent)
    assert acct.account(parent) is None and acct.hops(parent) is None
    names = [m["name"] for m in load_json(
        os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"))["per_layer"]]
    mine = 0
    for name in names:
        spec = load_json(os.path.join(BENCH, "layer_metrics",
                                      name + ".json"))
        if spec["reader"].startswith("launch_account:"):
            reader, args = metric_args(name)
            assert reader({}, **args) is None, name
            mine += 1
    assert mine == 15
    # and without a trace file at all
    run_of(planes_of(five()), path=None)
    assert acct.piece_ms({}, "device") is None
    assert acct.hop_ms({}) is None
    assert acct.thread_idle_share({}, "forward.absorb") is None


def test_the_counter_metrics_read_nothing_from_the_parents_counters():
    counters = load_py("readers", "counters")
    parent = {"counters": {"lane.grep.launches": 9, "lane.grep.run_s": 1.0}}
    change = {"counters": dict(parent["counters"], **{
        "lane.grep.wake_s": 0.0045, "lane.grep.launches_over_1s": 0})}
    for name, want in (("lane.wake_ms", 0.5), ("lane.launches_over_1s", 0)):
        spec = load_json(os.path.join(BENCH, "layer_metrics",
                                      name + ".json"))
        module, func = spec["reader"].split(":")
        assert module == "counters"
        reader = getattr(counters, func)
        assert reader(parent, **spec["args"]) is None
        assert reader(change, **spec["args"]) == pytest.approx(want)


def test_a_trace_without_a_device_plane_has_no_runs(tmp_path):
    """``read_runs`` on a real file: a CPU session has no device plane,
    so the account falls back to the time a module shares with a window
    (and finds none)."""
    jax = pytest.importorskip("jax")
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.numpy.arange(8).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    import trace_reduce

    path = trace_reduce.find_xplane(str(tmp_path))
    assert path and acct.read_runs(path) is None
