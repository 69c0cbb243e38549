"""The readers over the program's own spans and named device programs
(``readers/program_spans.py``, ``readers/program_trace.py``), on a
hand-built plane list. Not part of tier-1:

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import os
import shutil
import sys
import tempfile

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

from lookup import load_py  # noqa: E402

spans = load_py("readers", "program_spans")
trace = load_py("readers", "program_trace")


def ev(name, start, end, **stats):
    return ("fbtpu:" + name, start, end - start, stats)


ID = {"chunk": "c1", "seg": 0, "lane": "grep"}

#: one frame on the engine thread (a read that another task's span
#: outlives, two unpack attempts, the re-encode, an append holding the
#: filter and its lane wait), the lane's worker on a thread of its own,
#: and a device that works 400-500 and 700-800 of the interval 0-1000
PLANES = [
    {"name": "/host:CPU", "lines": [
        {"name": "python", "events": [
            ev("forward.read", 0, 100, bytes=9),
            ev("output.flush", 60, 130),          # another task's span
            ev("forward.unpack", 130, 150, done=0),
            ev("forward.unpack", 150, 200, done=1),
            ev("forward.reencode", 200, 300, chunk="c1"),
            ev("forward.absorb", 300, 900, chunk="c1"),
            ev("engine.append", 310, 890, chunk="c1"),
            ev("filter.grep", 320, 880, chunk="c1"),
            ev("lane.wait", 350, 850, **ID),
        ]},
        {"name": "python", "events": [
            ev("lane.launch", 360, 840, **ID),
            ev("grep.dispatch", 370, 420, **ID),
            ev("grep.force", 420, 830, **ID),
            # a launch of another segment must not be taken for this one
            ev("grep.force", 845, 850, chunk="c1", seg=1, lane="grep"),
        ]},
        {"name": "python", "events": [   # another lane's worker
            ev("lane.launch", 860, 870, chunk="c1", lane="flux"),
            ev("flux.force", 862, 868, chunk="c1", lane="flux"),
        ]},
    ]},
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ("jit_grep_assoc_S10_k5(123)", 400, 100, {}),
            ("jit_grep_scan_S690_k3(456)", 700, 100, {}),
            ("jit_concatenate(7)", 800, 0, {})]},
        {"name": "XLA Ops", "events": [
            ("%fusion.1 = s32[8]{0} fusion(...)", 400, 100, {}),
            ("%while.2 = s32[8]{0} while(...)", 700, 100, {})]},
    ]},
    {"name": "extent", "lines": [
        {"name": "extent", "events": [("", 0, 1000, {})]}]},
]


def test_innermost_is_the_span_that_started_last():
    got = spans.innermost([(0, 100, "a"), (20, 40, "b"), (30, 120, "c"),
                           (200, 210, "d"), (5, 5, "empty")])
    assert got == [(0, 20, "a"), (20, 30, "b"), (30, 120, "c"),
                   (200, 210, "d")]
    assert spans.innermost([]) == []


def test_self_time_subtracts_what_opened_inside():
    t = spans.reduce_planes(PLANES)
    assert t["interval_s"] == pytest.approx(1000e-9)
    s = t["spans"]
    # the read: 0-100 less the other task's span from 60 on
    assert s["forward.read"]["total_s"] == pytest.approx(100e-9)
    assert s["forward.read"]["self_s"] == pytest.approx(60e-9)
    assert s["output.flush"]["self_s"] == pytest.approx(70e-9)
    assert s["forward.unpack"]["count"] == 2
    assert s["forward.unpack"]["total_s"] == pytest.approx(70e-9)
    # append 310-890 less the filter 320-880
    assert s["engine.append"]["self_s"] == pytest.approx(20e-9)
    assert s["filter.grep"]["self_s"] == pytest.approx(60e-9)
    assert s["lane.wait"]["self_s"] == pytest.approx(500e-9)
    # the worker's thread: the launch less dispatch and force
    # (and the other lane's: 860-870 less its force)
    assert s["lane.launch"]["self_s"] == pytest.approx(24e-9)
    assert s["lane.launch"]["lanes"] == {
        "grep": {"count": 1, "total_s": pytest.approx(480e-9)},
        "flux": {"count": 1, "total_s": pytest.approx(10e-9)}}
    assert s["grep.force"]["count"] == 2


def test_idle_gaps_go_to_the_innermost_span_and_through_the_wait():
    t = spans.reduce_planes(PLANES)
    assert t["idle_s"] == pytest.approx(800e-9)
    by = {k: v for k, v in t["idle_by_span"].items() if v}
    assert by == {
        "forward.read": pytest.approx(60e-9),
        "output.flush": pytest.approx(70e-9),
        "forward.unpack": pytest.approx(70e-9),
        "forward.reencode": pytest.approx(100e-9),
        "forward.absorb": pytest.approx(20e-9),
        "engine.append": pytest.approx(20e-9),
        "filter.grep": pytest.approx(60e-9),
        # under lane.wait 350-850 the worker's spans of the same chunk
        # and seg take the gap: dispatch 370-400, force 500-700 and
        # 800-830, the launch's own 360-370 and 830-840; what is left
        # (350-360, 840-850) stays with the wait
        "grep.dispatch": pytest.approx(30e-9),
        "grep.force": pytest.approx(230e-9),
        "lane.launch": pytest.approx(20e-9),
        "lane.wait": pytest.approx(20e-9),
        "unattributed": pytest.approx(100e-9),   # 900-1000
    }
    assert sum(by.values()) == pytest.approx(t["idle_s"])


def on_two_threads():
    """PLANES as the program writes it since the input absorbs on a
    worker of its own: read, unpack and re-encode stay on the loop's
    thread, the absorb and everything beneath it — ``lane.wait`` among
    it — move to a second one; the loop reads the next frame meanwhile."""
    loop, lane, flux = (line["events"] for line in PLANES[0]["lines"])
    moved = ("forward.absorb", "engine.append", "filter.grep", "lane.wait")
    host = {"name": "/host:CPU", "lines": [
        {"name": "MainThread", "events": [
            e for e in loop if e[0][len("fbtpu:"):] not in moved]
            + [ev("forward.read", 600, 950, bytes=9)]},
        {"name": "flb-fw-forward.0", "events": [
            e for e in loop if e[0][len("fbtpu:"):] in moved]},
        {"name": "python", "events": lane},
        {"name": "python", "events": flux}]}
    return [host] + PLANES[1:]


def test_idle_starts_from_the_thread_that_holds_the_wait():
    """The wait on a second thread: the idle under it still goes to the
    lane worker's spans, what the absorbing thread leaves uncovered goes
    to the loop's, and the next frame's read (600-950), which lies under
    the absorb, takes nothing of it."""
    one, two = (spans.reduce_planes(p)["idle_by_span"]
                for p in (PLANES, on_two_threads()))
    want = dict(one, unattributed=50e-9)                  # 950-1000
    want["forward.read"] = 60e-9 + 50e-9                  # and 900-950
    assert {k for k, v in two.items() if v} == {k for k in want}
    for name, seconds in want.items():
        assert two[name] == pytest.approx(seconds), name
    # from the loop's thread alone the same trace reads as it did before
    # the reader was put right: the whole absorb under the next read
    loop_only = spans._idle_by_span(
        [(0, 400), (500, 700), (800, 1000)],
        [[(s, s + d, (n[len("fbtpu:"):], st)) for n, s, d, st in
          on_two_threads()[0]["lines"][0]["events"]]])
    assert loop_only["forward.read"] == pytest.approx(60e-9 + 250e-9)
    assert "grep.force" not in loop_only


def test_modules_by_the_name_the_program_gave():
    t = spans.reduce_planes(PLANES)
    assert t["modules"] == {
        "jit_grep_assoc_S10_k5": pytest.approx(100e-9),
        "jit_grep_scan_S690_k3": pytest.approx(100e-9),
        "jit_concatenate": 0.0}


def test_a_program_without_spans_reads_as_nothing():
    assert spans.reduce_planes(PLANES[1:]) is None
    host_only = spans.reduce_planes(PLANES[:1])
    assert host_only["idle_s"] is None and host_only["modules"] == {}


@pytest.fixture
def one_table(monkeypatch):
    monkeypatch.setattr(spans, "newest_xplane", lambda: "a-file")
    monkeypatch.setattr(spans, "_table",
                        lambda _path: spans.reduce_planes(PLANES))


def test_readers_over_the_table(one_table):
    r = {"trace": {"counters": {"lane.grep.launches": 2}}}
    assert spans.share(r, "forward.unpack") == pytest.approx(7.0)
    assert spans.share(r, "forward.reencode") == pytest.approx(10.0)
    assert spans.self_share(r, "forward.read") == pytest.approx(6.0)
    assert spans.self_share(r, "engine.append") == pytest.approx(2.0)
    assert spans.count_ratio(r, "forward.unpack", "forward.reencode") == 2
    # a launch of another lane is not among the divisor's
    assert spans.ms_per(r, "grep.force", "lane.launch", lane="grep") \
        == pytest.approx(415e-6)
    assert spans.ms_per(r, "grep.force", "lane.launch") \
        == pytest.approx(415e-6 / 2)
    assert spans.ms_per(r, "flux.force", "lane.launch", lane="flux") \
        == pytest.approx(6e-6)
    assert spans.ms_per(r, "grep.force", "lane.launch", lane="mesh") \
        is None
    assert trace.module_ms_per_launch(r, "grep_assoc", "grep") \
        == pytest.approx(50e-6)
    assert trace.module_ms_per_launch(r, "grep_scan", "grep") \
        == pytest.approx(50e-6)
    assert trace.idle_unattributed_share(r) == pytest.approx(12.5)
    # a span, a module or a lane the run does not have
    assert spans.share(r, "l2m.query") is None
    assert spans.count_ratio(r, "forward.unpack", "l2m.query") is None
    assert spans.ms_per(r, "grep.force", "l2m.query") is None
    # the lane launched, the device ran modules, none by this name
    assert trace.module_ms_per_launch(r, "flux_absorb", "grep") == 0.0
    assert trace.module_ms_per_launch(r, "grep_scan", "flux") is None
    assert trace.module_ms_per_launch({"trace": None}, "grep_scan",
                                      "grep") is None


def test_a_trace_older_than_this_process_is_another_runs(monkeypatch):
    tmp = tempfile.mkdtemp()
    monkeypatch.setattr(tempfile, "tempdir", tmp)
    run = os.path.join(tmp, "fbtpu-bench-left", "trace", "plugins",
                       "profile", "2026_01_01")
    os.makedirs(run)
    path = os.path.join(run, "host.xplane.pb")
    open(path, "wb").close()
    assert spans.newest_xplane() == path  # written by this process
    start = spans.process_start()
    assert 0 < start <= os.path.getmtime(path) + 1.0
    os.utime(path, (start - 60, start - 60))  # left by a killed run
    assert spans.newest_xplane() is None
    assert spans.table({"trace": None}) is None
    assert spans.share({"trace": None}, "forward.unpack") is None
    assert spans.newest_xplane(since=0.0) == path
    shutil.rmtree(tmp)


def test_none_without_a_file(monkeypatch):
    empty = tempfile.mkdtemp()
    monkeypatch.setattr(tempfile, "tempdir", empty)
    assert spans.newest_xplane() is None
    assert spans.table({}) is None
    assert spans.share({}, "forward.read") is None
    assert spans.self_share({}, "forward.read") is None
    assert spans.count_ratio({}, "forward.unpack", "forward.reencode") \
        is None
    assert spans.ms_per({}, "grep.force", "lane.launch") is None
    assert trace.module_ms_per_launch({"trace": None}, "grep_scan",
                                      "grep") is None
    assert trace.idle_unattributed_share({}) is None
    os.rmdir(empty)


def test_every_new_metric_has_its_file_and_a_reader_that_exists():
    import json

    root = os.path.dirname(BENCH)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seen, theirs = 0, 0
    for m in bench["per_layer"]:
        with open(os.path.join(BENCH, "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        module, func = spec["reader"].split(":")
        theirs += '"reader": "program_' in open(os.path.join(
            BENCH, "layer_metrics", m["name"] + ".json")).read()
        if module in ("program_spans", "program_trace"):
            assert callable(getattr(load_py("readers", module), func))
            assert m["workloads"], m["name"]
            seen += 1
    # counted, not pinned: later PRs add such metrics
    assert seen == theirs >= 9
