"""Spans and counters inside the program (core/spans.py): ``span()`` on
the profiler's own buffer and clock, lane and plugin counters where the
work happens, named device programs.

A forward frame goes socket → ``in_forward`` → grep → ``lib`` output
under a CPU ``jax.profiler`` session. On a CPU backend grep's
``process_batch`` takes the native twin, so the device lane is forced the way
``test_launchgraph.py::test_static_matches_dynamic_grep_chain`` does it
(``FBTPU_MESH=1``, ``tpu_batch_records 1``, skipped without a mesh).
"""

import glob
import os
import re
import socket
import subprocess
import sys
import threading
import time

import pytest

import fluentbit_tpu as flb
from fluentbit_tpu.codec.msgpack import Unpacker, packb
from fluentbit_tpu.core import spans
from fluentbit_tpu.ops import fault
from fluentbit_tpu.plugins import net_forward

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
APACHE2 = (r'^(?<host>[^ ]*) [^ ]* (?<user>[^ ]*) \[(?<time>[^\]]*)\] '
           r'"(?<method>\S+)(?: +(?<path>[^ ]*) +\S*)?" (?<code>[^ ]*) '
           r'(?<size>[^ ]*)(?: "(?<referer>[^\"]*)" "(?<agent>.*)")?$')
OK_LINE = ('10.0.0.1 - frank [10/Oct/2000:13:55:36 -0700] '
           '"GET /a HTTP/1.1" 200 23 "http://r" "curl"')
N_LINES = 96
CHUNK = "frame-0001"
#: ``log_to_metrics``' ``raw_timings``: seconds by phase of an append
L2M_KEYS = ("select_s", "stage_s", "update_s", "query_s")
#: the count-min read-back's own keys: only the frequency instance adds to them
L2M_CMS_KEYS = ("cms_query_s", "query_values", "query_scalar_values")


def wait_for(cond, timeout=20.0, interval=0.01):
    deadline = time.time() + timeout
    while time.time() < deadline:
        v = cond()
        if v:
            return v
        time.sleep(interval)
    raise TimeoutError("condition not met")


def frame(chunk=CHUNK, n=N_LINES, pad=0) -> bytes:
    """One Forward-mode frame; every fourth line fails the regex."""
    more = {"pad": "x" * pad} if pad else {}
    entries = [[1700000000 + i,
                {"log": OK_LINE if i % 4 else f"kernel: oom {i}", **more}]
               for i in range(n)]
    return packb(["app", entries, {"chunk": chunk}])


def short_frame(chunk: str) -> bytes:
    """24 lines, one segment of the 32 ``mesh_env`` sets, and padded
    past what ``in_forward`` absorbs on the loop (``_INLINE_BYTES``)."""
    return frame(chunk, n=24, pad=200)


class Aggregator:
    """forward input → grep (device lane forced) → lib output."""

    def __init__(self):
        self.ctx = flb.create(flush="50ms", grace="1")
        self.ctx.input("forward", listen="127.0.0.1", port="0")
        self.ctx.filter("grep", match="*", regex=f"log {APACHE2}",
                        tpu_batch_records="1")
        self.got = []
        self.ctx.output("lib", match="*",
                        callback=lambda d, _t: self.got.append(bytes(d)))
        self.engine = self.ctx.engine
        self.grep = self.engine.filters[0].plugin
        self.ctx.start()
        self.port = wait_for(
            lambda: self.engine.inputs[0].plugin.bound_port)

    def send(self, data: bytes, chunk=CHUNK, piece=0) -> None:
        """Send one frame (in ``piece``-byte writes when given) and wait
        for its ack."""
        with socket.create_connection(("127.0.0.1", self.port)) as s:
            s.settimeout(60)
            if piece:
                for i in range(0, len(data), piece):
                    s.sendall(data[i:i + piece])
                    time.sleep(0.002)
            else:
                s.sendall(data)
            u = Unpacker()
            while True:
                u.feed(s.recv(4096))
                for msg in u:
                    assert msg == {"ack": chunk}
                    return

    def output(self, n_bytes_least=1) -> bytes:
        self.ctx.flush_now()
        wait_for(lambda: sum(map(len, self.got)) >= n_bytes_least)
        return b"".join(self.got)

    def stop(self) -> None:
        self.ctx.stop()


@pytest.fixture(scope="module")
def mesh_env():
    jax = pytest.importorskip("jax")
    if len(jax.devices()) < 2:
        pytest.skip("need a multi-device mesh")
    saved = {k: os.environ.get(k)
             for k in ("FBTPU_MESH", "FBTPU_SEGMENT_RECORDS")}
    os.environ["FBTPU_MESH"] = "1"
    os.environ["FBTPU_SEGMENT_RECORDS"] = "32"
    yield jax
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def read_events(trace_dir: str) -> list:
    """Every ``fbtpu:`` event of the trace: dicts with name, start, end,
    stats and the thread line it lies on."""
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for li, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(spans.PREFIX):
                    out.append({
                        "name": e.name[len(spans.PREFIX):],
                        "start": e.start_ns,
                        "end": e.start_ns + e.duration_ns,
                        "stats": dict(e.stats),
                        "line": (plane.name, li)})
    return out


@pytest.fixture(scope="module")
def runs(mesh_env, tmp_path_factory):
    """The same frame three times through one aggregator: untraced (it
    also compiles the program), under a profiler session, untraced
    again. → per-run output bytes, the session's events, and what
    ``span()`` returned outside the session."""
    jax = mesh_env
    agg = Aggregator()
    try:
        seen = {}
        # each send has a chunk id of its own: a redelivered id is acked
        # from the dedup ledger and absorbed zero times
        agg.send(frame("frame-0000"), "frame-0000")
        seen["cold"] = agg.output()
        agg.got.clear()

        trace_dir = str(tmp_path_factory.mktemp("trace"))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            seen["enabled_in_session"] = spans.enabled()
            # 997-byte writes: the frame arrives over several reads
            agg.send(frame(), piece=997)
            seen["traced"] = agg.output()
        finally:
            jax.profiler.stop_trace()
        agg.got.clear()
        seen["events"] = read_events(trace_dir)

        seen["span_off"] = spans.span("forward.read", bytes=1)
        seen["bind_off"] = spans.bind(chunk="x")
        seen["ids_off"] = spans.current_ids()
        agg.send(frame("frame-0002"), "frame-0002")
        seen["untraced"] = agg.output()
        seen["mesh_on"] = agg.grep._mesh is not None
    finally:
        agg.stop()
    return seen


def by_name(events, name):
    return [e for e in events if e["name"] == name]


def inside(inner, outer) -> bool:
    return outer["start"] <= inner["start"] and inner["end"] <= outer["end"]


# ------------------------------------------------------ span(), alone


def test_spans_module_does_not_import_jax():
    code = (
        "import importlib.util, sys\n"
        f"spec = importlib.util.spec_from_file_location('spans_alone', "
        f"{os.path.join(REPO, 'fluentbit_tpu', 'core', 'spans.py')!r})\n"
        "m = importlib.util.module_from_spec(spec)\n"
        "sys.modules['spans_alone'] = m\n"
        "spec.loader.exec_module(m)\n"
        "assert 'jax' not in sys.modules\n"
        "assert m.span('forward.read', bytes=3) is m.NOOP\n"
        "assert m.bind(chunk='c') is m.NOOP\n"
        "assert m.current_ids() is None and not m.enabled()\n"
        "with m.span('x') as sp:\n"
        "    sp.set_metadata(bytes=1)\n"
        "assert 'jax' not in sys.modules\n"
        "print('ok')\n")
    proc = subprocess.run([sys.executable, "-c", code], timeout=60,
                          capture_output=True, text=True)
    assert proc.returncode == 0 and proc.stdout.strip() == "ok", \
        proc.stdout + proc.stderr


def test_span_is_the_shared_noop_without_a_session():
    pytest.importorskip("jax")
    assert not spans.enabled()
    assert spans.span("engine.append") is spans.NOOP
    assert spans.span("lane.launch", chunk="c", seg=1) is spans.NOOP
    assert spans.bind(chunk="c") is spans.NOOP
    assert spans.current_ids() is None


def test_spanned_keeps_the_name_and_the_result():
    @spans.spanned("engine.append")
    def input_log_append(a, b=2):
        """doc"""
        return a + b

    assert input_log_append.__name__ == "input_log_append"
    assert input_log_append.__doc__ == "doc"
    assert input_log_append(1, b=3) == 4


def test_bind_nests_and_restores_under_a_session(tmp_path):
    jax = pytest.importorskip("jax")
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert spans.enabled() and spans.current_ids() is None
        with spans.bind(chunk="c1", skipped=None):
            assert spans.current_ids() == {"chunk": "c1"}
            with spans.bind(seg=2):
                assert spans.current_ids() == {"chunk": "c1", "seg": 2}
                seen = {}
                t = threading.Thread(
                    target=lambda: seen.update(ids=spans.current_ids()))
                t.start()
                t.join(10)
                assert seen == {"ids": None}  # a new thread binds anew
            assert spans.current_ids() == {"chunk": "c1"}
        assert spans.current_ids() is None
        assert spans.span("x", a=1) is not spans.NOOP
    finally:
        jax.profiler.stop_trace()
    assert spans.current_ids() is None


def test_sharded_timings_sum_and_timed():
    tm = spans.ShardedTimings(("a_s", "n"))
    assert list(tm) == ["a_s", "n"] and "n" in tm and "x" not in tm
    tm.add("n", 2)
    t = threading.Thread(target=tm.add, args=("n", 3))
    t.start()
    t.join(10)
    assert tm["n"] == 5 and len(tm._shards) == 2
    with tm.timed("a_s", "grep.compact"):
        time.sleep(0.01)
    assert 0.009 < tm["a_s"] < 5.0 and tm["n"] == 5


# ------------------------------------------ one frame, traced and not


SPANS_OF_A_FRAME = ("forward.read", "forward.unpack", "forward.reencode",
                    "forward.handover", "forward.absorb", "engine.append",
                    "filter.grep", "grep.stage", "lane.begin",
                    "lane.launch", "grep.dispatch", "grep.put",
                    "grep.call", "grep.force", "lane.wait",
                    "grep.compact", "forward.ack")


@pytest.mark.mesh
@pytest.mark.parametrize("name", SPANS_OF_A_FRAME)
def test_traced_frame_has_span(runs, name):
    assert runs["mesh_on"] and runs["enabled_in_session"]
    assert by_name(runs["events"], name), \
        sorted({e["name"] for e in runs["events"]})


@pytest.mark.mesh
def test_every_span_from_reencode_on_carries_the_chunk(runs):
    events = runs["events"]
    start = by_name(events, "forward.reencode")[0]["start"]
    end = by_name(events, "forward.ack")[0]["end"]
    # (the flush timer's spans may fall in between: they are no frame's)
    after = [e for e in events if start <= e["start"] and e["end"] <= end
             and e["name"] in SPANS_OF_A_FRAME[2:]]
    assert len(after) >= 15
    assert {e["stats"].get("chunk") for e in after} == {CHUNK}
    # three threads carry it: the engine's loop decodes and acks, the
    # input's worker absorbs, a lane worker runs each launch
    loop_line = by_name(events, "forward.read")[0]["line"]
    absorb_line = by_name(events, "forward.absorb")[0]["line"]
    assert absorb_line != loop_line
    on = {line: {e["name"] for e in after if e["line"] == line}
          for line in {e["line"] for e in after}}
    assert on.pop(loop_line) == {"forward.reencode", "forward.handover",
                                 "forward.ack"}
    assert on.pop(absorb_line) == {
        "forward.absorb", "engine.append", "filter.grep", "grep.stage",
        "lane.begin", "lane.wait", "grep.compact"}
    assert set().union(*on.values()) == {"lane.launch", "grep.dispatch",
                                         "grep.put", "grep.call",
                                         "grep.force"}
    # before the frame is whole nobody knows its chunk
    for e in by_name(events, "forward.read") \
            + by_name(events, "forward.unpack"):
        assert "chunk" not in e["stats"]


@pytest.mark.mesh
def test_segment_spans_carry_seg_across_the_thread_hop(runs):
    events = runs["events"]
    n_seg = N_LINES // 32
    for name in ("grep.stage", "lane.begin", "lane.launch", "lane.wait",
                 "grep.dispatch", "grep.force"):
        assert sorted(e["stats"]["seg"] for e in by_name(events, name)) \
            == list(range(n_seg)), name
    for launch in by_name(events, "lane.launch"):
        for name in ("grep.dispatch", "grep.force"):
            inner = [e for e in by_name(events, name)
                     if e["stats"]["seg"] == launch["stats"]["seg"]]
            assert len(inner) == 1 and inside(inner[0], launch)
            assert inner[0]["line"] == launch["line"]


@pytest.mark.mesh
def test_lane_spans_name_their_lane(runs):
    """``lane.force_ms`` divides by the launches of the grep lane, not
    by every lane's: the lane's spans, the worker's too, say whose."""
    for name in ("lane.begin", "lane.launch", "lane.wait",
                 "grep.dispatch", "grep.force"):
        assert {e["stats"].get("lane")
                for e in by_name(runs["events"], name)} == {"grep"}, name
    for name in ("grep.stage", "filter.grep", "engine.append"):
        assert all("lane" not in e["stats"]
                   for e in by_name(runs["events"], name)), name


@pytest.mark.mesh
def test_flush_spans_belong_to_no_frame(runs):
    """``bind`` is a ContextVar, and a callback or a task scheduled
    from inside a bound frame would copy the frame's ids: the engine
    schedules its flushes from its own housekeeping task, so that they
    carry no frame's ``chunk``."""
    flushes = by_name(runs["events"], "engine.flush") \
        + by_name(runs["events"], "output.flush")
    assert {e["name"] for e in flushes} == {"engine.flush",
                                            "output.flush"}
    for e in flushes:
        assert not {"chunk", "seg", "lane"} & set(e["stats"]), e


@pytest.fixture(scope="module")
def pipelined_events(mesh_env, tmp_path_factory):
    """Two frames on one connection under a profiler session, the first
    held in the filter until the second is decoded (``n_overlapped``
    says so), then a third that finds the worker idle, and a flush. →
    the session's events."""
    jax = mesh_env
    agg = Aggregator()
    srv = agg.engine.inputs[0].plugin
    try:
        agg.send(frame("pipe-warm"), "pipe-warm")  # compiles
        real = agg.grep.process_batch
        held = []

        def hold_the_first(chunk):
            if not held:
                held.append(wait_for(lambda: srv.n_overlapped >= 1))
            return real(chunk)

        agg.grep.process_batch = hold_the_first
        trace_dir = str(tmp_path_factory.mktemp("pipelined"))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            with socket.create_connection(("127.0.0.1", agg.port)) as s:
                s.settimeout(60)
                s.sendall(frame("pipe-a") + frame("pipe-b"))
                u, acks = Unpacker(), []
                while len(acks) < 2:
                    u.feed(s.recv(4096))
                    acks.extend(msg["ack"] for msg in u)
            agg.send(frame("pipe-c"), "pipe-c")
            agg.output()
        finally:
            jax.profiler.stop_trace()
            del agg.grep.process_batch
        assert acks == ["pipe-a", "pipe-b"] and held == [True]
        assert srv.n_overlapped == 1
    finally:
        agg.stop()
    return read_events(trace_dir)


@pytest.mark.mesh
def test_worker_spans_carry_their_frames_chunk(pipelined_events):
    """The input's worker absorbs frame after frame on one thread: each
    span it opens carries the chunk of the frame it is absorbing, bound
    anew for every frame, and so do the lane workers' beneath it."""
    events = pipelined_events
    absorbs = by_name(events, "forward.absorb")
    assert [e["stats"]["chunk"] for e in sorted(
        absorbs, key=lambda e: e["start"])] == ["pipe-a", "pipe-b",
                                                "pipe-c"]
    assert len({e["line"] for e in absorbs}) == 1
    loop_line = by_name(events, "forward.read")[0]["line"]
    assert absorbs[0]["line"] != loop_line
    for absorb in absorbs:
        beneath = [e for e in events if e["line"] != loop_line
                   and inside(e, absorb)]
        assert {e["name"] for e in beneath} >= {
            "forward.absorb", "engine.append", "filter.grep",
            "lane.begin", "lane.launch", "grep.force", "lane.wait"}
        assert {e["stats"].get("chunk") for e in beneath} \
            == {absorb["stats"]["chunk"]}
    # nothing on the worker's thread lies outside an absorb
    for e in events:
        if e["line"] == absorbs[0]["line"]:
            assert any(inside(e, absorb) for absorb in absorbs), e


@pytest.mark.mesh
def test_overlap_span_marks_the_overlapped_frame_alone(pipelined_events,
                                                       runs):
    events = pipelined_events
    (overlap,) = by_name(events, "forward.overlap")
    assert overlap["stats"]["chunk"] == "pipe-b"
    assert overlap["line"] == by_name(events, "forward.read")[0]["line"]
    first = min(by_name(events, "forward.absorb"),
                key=lambda e: e["start"])
    reencode_b = [e for e in by_name(events, "forward.reencode")
                  if e["stats"]["chunk"] == "pipe-b"][0]
    # b was decoded after a was handed to the worker and before a's
    # absorb was over
    reencode_a = [e for e in by_name(events, "forward.reencode")
                  if e["stats"]["chunk"] == "pipe-a"][0]
    assert reencode_a["end"] <= reencode_b["start"]
    assert reencode_b["end"] <= overlap["start"] \
        and overlap["end"] <= first["end"]
    assert len(by_name(events, "forward.reencode")) == 3
    # a frame sent alone overlaps nothing
    assert not by_name(runs["events"], "forward.overlap")


@pytest.mark.mesh
def test_flush_spans_of_a_pipelined_run_belong_to_no_frame(
        pipelined_events):
    flushes = by_name(pipelined_events, "engine.flush") \
        + by_name(pipelined_events, "output.flush")
    assert {e["name"] for e in flushes} == {"engine.flush", "output.flush"}
    for e in flushes:
        assert not {"chunk", "seg", "lane"} & set(e["stats"]), e


@pytest.mark.mesh
def test_spans_lie_inside_one_another_as_the_table_says(runs):
    events = runs["events"]

    def one(name):
        got = by_name(events, name)
        assert len(got) == 1, (name, len(got))
        return got[0]

    absorb, append = one("forward.absorb"), one("engine.append")
    grep, ack = one("filter.grep"), one("forward.ack")
    reencode = one("forward.reencode")
    assert inside(append, absorb) and inside(grep, append)
    for name in ("grep.stage", "lane.begin", "lane.wait", "grep.compact"):
        for e in by_name(events, name):
            assert inside(e, grep), name
            assert e["line"] == grep["line"]
    assert reencode["end"] <= absorb["start"]
    assert absorb["end"] <= ack["start"]
    # the frame came in pieces: failed attempts (done=0), then the one
    # that took the message (done=1), all before the re-encode
    unpack = by_name(events, "forward.unpack")
    done = [e for e in unpack if e["stats"]["done"] == 1]
    failed = [e for e in unpack if e["stats"]["done"] == 0
              and e["end"] <= reencode["start"]]
    assert len(done) == 1 and len(failed) >= 2
    assert done[0]["end"] <= reencode["start"]
    reads = by_name(events, "forward.read")
    assert sum(e["stats"]["bytes"] for e in reads) == len(frame())


@pytest.mark.mesh
def test_unpack_spans_say_whether_the_extension_served_them(runs):
    """``native=1`` on every attempt, the failed ones too, where the C
    codec is loaded: a benchmark run that reads 0 measured the Python
    fallback."""
    from fluentbit_tpu.codec import _native_codec

    want = int(_native_codec.load() is not None)
    unpack = by_name(runs["events"], "forward.unpack")
    assert len(unpack) >= 3
    assert {e["stats"]["native"] for e in unpack} == {want}
    assert {e["stats"]["done"] for e in unpack} == {0, 1}


@pytest.mark.mesh
def test_cut_span_marks_the_frame_the_c_cut_served(runs):
    """``forward.reencode`` stays one span a frame (three metrics divide
    by its count) and says ``cut=0|1``; ``forward.cut`` is written for
    exactly the frames whose events C cut from the wire bytes, on the
    loop's thread, with the frame's ``chunk``: a benchmark run whose
    ``input.cut_per_frame`` reads under 1 measured the object path."""
    from fluentbit_tpu.codec import _native_codec

    served = int(_native_codec.load() is not None)
    events = runs["events"]
    (reencode,) = by_name(events, "forward.reencode")
    assert reencode["stats"]["cut"] == served
    cut = by_name(events, "forward.cut")
    assert len(cut) == served
    for e in cut:
        assert e["stats"]["chunk"] == CHUNK
        assert e["line"] == reencode["line"] and reencode["end"] <= e["start"]


@pytest.mark.mesh
def test_untraced_frame_records_nothing(runs):
    assert runs["span_off"] is spans.NOOP
    assert runs["bind_off"] is spans.NOOP
    assert runs["ids_off"] is None
    # every event of the session belongs to the one traced frame
    chunks = {e["stats"].get("chunk") for e in runs["events"]}
    assert chunks == {CHUNK, None}
    assert len(by_name(runs["events"], "forward.absorb")) == 1


@pytest.mark.mesh
def test_output_bytes_equal_with_and_without_a_session(runs):
    assert runs["traced"] and runs["traced"] == runs["untraced"]
    assert runs["traced"] == runs["cold"]
    # the traced frame went through the hand-over and the split dispatch
    for name in ("forward.handover", "grep.put", "grep.call"):
        assert by_name(runs["events"], name), name
    from fluentbit_tpu.codec.events import decode_events

    assert len(decode_events(runs["traced"])) == N_LINES - N_LINES // 4


# ------------------------- the hand-overs and the halves of a dispatch


@pytest.mark.mesh
def test_handover_is_on_the_loops_thread_around_the_absorb(runs):
    """``forward.handover`` − ``forward.absorb`` by ``chunk`` is the two
    thread hops (``input.hop_ms_per_frame``)."""
    events = runs["events"]
    (over,) = by_name(events, "forward.handover")
    (absorb,) = by_name(events, "forward.absorb")
    assert over["line"] == by_name(events, "forward.read")[0]["line"]
    assert absorb["line"] != over["line"] and inside(absorb, over)
    assert over["stats"]["chunk"] == absorb["stats"]["chunk"] == CHUNK
    (reencode,) = by_name(events, "forward.reencode")
    (ack,) = by_name(events, "forward.ack")
    assert reencode["end"] <= over["start"] and over["end"] <= ack["start"]


@pytest.fixture(scope="module")
def prelaunched_events(mesh_env, tmp_path_factory):
    """Three frames of ONE segment each on one connection under a
    profiler session, the first held in the filter until the second's
    launch has been begun ahead of its turn (``n_prelaunched`` says
    so). → the session's events and the input's count."""
    jax = mesh_env
    agg = Aggregator()
    srv = agg.engine.inputs[0].plugin
    ids = ["pre-a", "pre-b", "pre-c"]
    try:
        agg.send(short_frame("pre-warm"), "pre-warm")  # compiles
        real = agg.grep.process_batch
        held = []

        def hold_the_first(chunk):
            if not held:
                # ... and the loop holds every frame it may decode
                held.append(wait_for(
                    lambda: srv.n_prelaunched >= 1 and srv.n_overlapped
                    >= 1 + net_forward._DECODE_AHEAD))
            return real(chunk)

        agg.grep.process_batch = hold_the_first
        trace_dir = str(tmp_path_factory.mktemp("prelaunched"))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            with socket.create_connection(("127.0.0.1", agg.port)) as s:
                s.settimeout(60)
                s.sendall(b"".join(short_frame(c) for c in ids))
                u, acks = Unpacker(), []
                while len(acks) < 3:
                    u.feed(s.recv(4096))
                    acks.extend(msg["ack"] for msg in u)
        finally:
            jax.profiler.stop_trace()
            del agg.grep.process_batch
        assert acks == ids and held == [True]
        n_prelaunched = srv.n_prelaunched
    finally:
        agg.stop()
    return {"events": read_events(trace_dir), "prelaunched": n_prelaunched}


@pytest.mark.mesh
def test_await_span_marks_the_frame_that_waited_for_the_worker(
        prelaunched_events, runs):
    """``forward.await`` is opened only when the loop holds as many
    decoded frames as it may (``1 + _DECODE_AHEAD``) behind one that is
    still with the worker, and carries the chunk of the newest, the one
    it cannot go on from."""
    events = prelaunched_events["events"]
    (wait,) = by_name(events, "forward.await")
    waiting = ["pre-b", "pre-c"][net_forward._DECODE_AHEAD]
    assert wait["stats"]["chunk"] == waiting
    assert wait["line"] == by_name(events, "forward.read")[0]["line"]
    absorb = {e["stats"]["chunk"]: e
              for e in by_name(events, "forward.absorb")}
    over = {e["stats"]["chunk"]: e
            for e in by_name(events, "forward.handover")}
    assert set(over) == set(absorb) == {"pre-a", "pre-b", "pre-c"}
    # it waited for a's absorb to end; its own hand-over came later
    assert wait["start"] <= absorb["pre-a"]["end"] <= wait["end"]
    assert wait["end"] <= over[waiting]["start"]
    for chunk in over:
        assert inside(absorb[chunk], over[chunk]), chunk
    # a frame sent alone waits for nobody
    assert not by_name(runs["events"], "forward.await")


@pytest.mark.mesh
def test_prelaunch_span_marks_the_frames_whose_launch_was_begun_ahead(
        prelaunched_events, runs):
    """``forward.prelaunch`` is written for exactly the frames whose
    launch was begun while an earlier frame was with the worker, with
    the frame's ``chunk``, on the input's second thread; that frame's
    staging and ``lane.begin`` lie on that thread too, before its
    absorb begins, and its absorb stages nothing."""
    events = prelaunched_events["events"]
    marks = by_name(events, "forward.prelaunch")
    assert len(marks) == prelaunched_events["prelaunched"] >= 1
    chunks = [e["stats"]["chunk"] for e in marks]
    assert "pre-b" in chunks and set(chunks) <= {"pre-b", "pre-c"}
    loop_line = by_name(events, "forward.read")[0]["line"]
    absorb = {e["stats"]["chunk"]: e
              for e in by_name(events, "forward.absorb")}
    assert {e["line"] for e in marks}.isdisjoint(
        {loop_line, absorb["pre-a"]["line"]})
    for mark in marks:
        chunk = mark["stats"]["chunk"]
        for name in ("grep.stage", "lane.begin"):
            (ahead,) = [e for e in by_name(events, name)
                        if e["stats"]["chunk"] == chunk]
            assert ahead["line"] == mark["line"], name
            assert ahead["end"] <= mark["start"] \
                and mark["end"] <= absorb[chunk]["start"], name
        (wait,) = [e for e in by_name(events, "lane.wait")
                   if e["stats"]["chunk"] == chunk]
        assert inside(wait, absorb[chunk])
    # the frame that found the worker idle staged inside its absorb
    (stage_a,) = [e for e in by_name(events, "grep.stage")
                  if e["stats"]["chunk"] == "pre-a"]
    assert inside(stage_a, absorb["pre-a"])
    assert not by_name(runs["events"], "forward.prelaunch")


@pytest.mark.mesh
@pytest.mark.parametrize("which", ["segments", "begun_ahead"])
def test_launch_number_joins_a_launch_to_its_own_spans(
        which, runs, prelaunched_events):
    """Every span of one launch — ``lane.begin`` and ``lane.wait`` on
    the calling threads, ``lane.launch`` and the ``grep.dispatch`` /
    ``put`` / ``call`` / ``force`` inside it on the lane's worker —
    carries the same ``launch`` number, another one each launch: with
    two flights open a reader joins by it, not by overlapping windows."""
    events = runs["events"] if which == "segments" \
        else prelaunched_events["events"]
    launches = by_name(events, "lane.launch")
    numbers = [e["stats"]["launch"] for e in launches]
    assert len(set(numbers)) == len(numbers) == 3
    assert all(isinstance(n, int) and n > 0 for n in numbers)
    for launch in launches:
        n = launch["stats"]["launch"]
        for name in ("grep.dispatch", "grep.put", "grep.call",
                     "grep.force"):
            own = [e for e in by_name(events, name)
                   if e["stats"].get("launch") == n]
            assert own and all(inside(e, launch) for e in own), name
            assert {e["line"] for e in own} == {launch["line"]}, name
            assert {e["stats"]["chunk"] for e in own} \
                == {launch["stats"]["chunk"]}, name
        for name in ("lane.begin", "lane.wait"):
            (own,) = [e for e in by_name(events, name)
                      if e["stats"].get("launch") == n]
            assert own["line"] != launch["line"], name
    for name in ("grep.stage", "forward.absorb", "engine.append"):
        assert all("launch" not in e["stats"]
                   for e in by_name(events, name)), name


@pytest.fixture(scope="module")
def span_program_events(tmp_path_factory):
    """One chunk through ``filter_parser``'s span program (the platform
    gate forced open), once to compile and once under a profiler session
    beneath a bound ``chunk``. → the session's events."""
    jax = pytest.importorskip("jax")
    from fluentbit_tpu.codec.events import encode_event
    from fluentbit_tpu.core.chunk_batch import RawChunk
    from fluentbit_tpu.core.engine import Engine
    from fluentbit_tpu.parsers import create_parser
    from fluentbit_tpu.ops import device

    assert device.wait(120)
    saved = device.platform
    device.platform = lambda: "tpu"
    try:
        e = Engine()
        e.parsers["apache2"] = create_parser(
            "apache2", Format="regex", Regex=APACHE2)
        f = e.filter("parser")
        for k, v in {"key_name": "log", "parser": "apache2",
                     "tpu_batch_records": "1"}.items():
            f.set(k, v)
        e.input("dummy")
        for x in e.inputs + e.filters:
            x.configure()
            x.plugin.init(x, e)
        plugin = e.filters[0].plugin
        data = b"".join(encode_event({"log": OK_LINE}, float(i))
                        for i in range(40))
        if not plugin._span_serves():
            pytest.skip("no span program")
        plugin.process_batch(RawChunk(data, "t", 40))  # compiles
        trace_dir = str(tmp_path_factory.mktemp("span_program"))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            with spans.bind(chunk="sp-0001"):
                n, _out, _n_in = plugin.process_batch(
                    RawChunk(data, "t", 40))
        finally:
            jax.profiler.stop_trace()
        assert n == 40 and plugin.raw_timings["parsed"] == 80
    finally:
        device.platform = saved
    return read_events(trace_dir)


@pytest.mark.mesh
@pytest.mark.parametrize("path", ["mesh", "one_chip", "span_program"])
def test_put_and_call_lie_inside_the_dispatch(path, request):
    """``grep.dispatch`` in two: the copy-in (``grep.put``) and the
    jitted calls with the merge (``grep.call``, with ``children``), on
    the lane worker's thread with the launch's ``lane``, ``chunk`` and
    ``seg`` — on the sharded path (one put and one call a child), on
    one chip (``rewrite_tag``'s program) and in the span program."""
    fixture = {"mesh": "runs", "one_chip": "rewrite_events",
               "span_program": "span_program_events"}[path]
    events = request.getfixturevalue(fixture)
    if isinstance(events, dict):
        events = events["events"]
    dispatches = by_name(events, "grep.dispatch")
    assert dispatches
    halves = by_name(events, "grep.put") + by_name(events, "grep.call")
    for d in dispatches:
        mine = [e for e in halves if inside(e, d) and e["line"] == d["line"]]
        puts = [e for e in mine if e["name"] == "grep.put"]
        calls = [e for e in mine if e["name"] == "grep.call"]
        assert puts and calls, (path, d)
        for e in mine:
            for key in ("lane", "chunk", "seg"):
                assert e["stats"][key] == d["stats"][key], (e, key)
        assert d["stats"]["lane"] == "grep"
        assert all(c["stats"]["children"] >= 1 for c in calls)
        assert min(p["end"] for p in puts) <= min(c["start"] for c in calls)
        if path != "mesh":
            # one copy-in a launch, whatever the children
            assert len(puts) == 1 and len(calls) == 1
            assert puts[0]["end"] <= calls[0]["start"]
    # none outside a dispatch
    assert all(any(inside(e, d) for d in dispatches) for e in halves)
    # the force is not part of either (of its own launch: the next
    # segment's dispatch may run beside it on another thread)
    for f in by_name(events, "grep.force"):
        assert not any(inside(f, e) for e in halves
                       if e["line"] == f["line"]
                       and e["stats"]["seg"] == f["stats"]["seg"])


@pytest.mark.mesh
def test_children_share_one_put_on_one_chip_and_have_their_own_on_the_mesh(
        mesh_env, tmp_path):
    """The grep cell's program has two per-stride children. On one chip
    the planes cross once (one ``grep.put``, one ``grep.call`` with
    ``children=2``); on the mesh each child places the host planes
    itself (a donated buffer cannot be shared), so a launch holds a put
    and a call a child, and the parent's merge is a call of its own."""
    import numpy as np

    from fluentbit_tpu.ops.batch import assemble
    from fluentbit_tpu.ops.grep import GrepProgram
    from fluentbit_tpu.ops.mesh import build_mesh
    from fluentbit_tpu.regex.dfa import compile_dfa

    jax = mesh_env
    prog = GrepProgram([compile_dfa(r"curl/8\.5"), compile_dfa(APACHE2)],
                       512, plane_of=(0, 0))
    assert len(prog._children) == 2
    b = assemble([OK_LINE.encode()] * 16, max_len=512)
    planes, lengths = b.batch[None], b.lengths[None]
    mesh = build_mesh(8)
    want = np.asarray(prog.dispatch(planes, lengths))           # compiles
    out, _c, B, _bp = prog.dispatch_mesh(mesh, planes, lengths,
                                         with_counts=False)
    assert (np.asarray(out).astype(bool)[:, :B] == want).all()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with spans.span("grep.dispatch", path="one"):
            np.asarray(prog.dispatch(planes, lengths))
        with spans.span("grep.dispatch", path="mesh"):
            np.asarray(prog.dispatch_mesh(mesh, planes, lengths,
                                          with_counts=False)[0])
    finally:
        jax.profiler.stop_trace()
    events = read_events(str(tmp_path))
    one, sharded = sorted(by_name(events, "grep.dispatch"),
                          key=lambda e: e["start"])
    assert (one["stats"]["path"], sharded["stats"]["path"]) \
        == ("one", "mesh")

    def halves(outer, name):
        return sorted((e for e in by_name(events, name)
                       if inside(e, outer)), key=lambda e: e["start"])

    assert len(halves(one, "grep.put")) == 1
    assert [e["stats"]["children"] for e in halves(one, "grep.call")] == [2]
    assert len(halves(sharded, "grep.put")) == 2
    assert [e["stats"]["children"]
            for e in halves(sharded, "grep.call")] == [1, 1, 2]
    # put, call, put, call, merge: none inside another
    seq = sorted(halves(sharded, "grep.put") + halves(sharded, "grep.call"),
                 key=lambda e: e["start"])
    assert [e["name"] for e in seq] == ["grep.put", "grep.call"] * 2 \
        + ["grep.call"]
    for x, y in zip(seq, seq[1:]):
        assert x["end"] <= y["start"]


# ----------------------------------------------------- a GC pass


def gc_hooks() -> list:
    import gc

    return [cb for cb in gc.callbacks
            if getattr(cb, "func", None) is spans._on_gc]


def test_gc_pass_is_a_span_under_a_session_and_nothing_without(tmp_path):
    import gc

    jax = pytest.importorskip("jax")
    hook = spans.watch_gc()
    was_on = gc.isenabled()
    gc.disable()  # no pass but the ones forced here
    try:
        gc.collect(2)  # no session: the hook returns after enabled()
        assert spans._gc_span is None
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            junk = [[] for _ in range(10)]
            for x in junk:
                x.append(x)  # ten cycles for the pass to collect
            del junk, x
            with spans.bind(chunk="gc-frame"):
                gc.collect(2)
            assert spans._gc_span is None
        finally:
            jax.profiler.stop_trace()
        gc.collect(2)
    finally:
        if was_on:
            gc.enable()
        spans.unwatch_gc(hook)
    (one,) = by_name(read_events(str(tmp_path)), "gc.collect")
    assert one["stats"]["gen"] == 2 and one["stats"]["collected"] >= 10
    # it lands inside the frame it delayed
    assert one["stats"]["chunk"] == "gc-frame"
    assert hook not in __import__("gc").callbacks


def test_gc_hook_is_installed_while_an_engine_runs(tmp_path):
    """One hook a running engine, gone after its stop; with two engines
    a pass is still one span."""
    import gc

    jax = pytest.importorskip("jax")
    before = len(gc_hooks())
    a = flb.create(flush="50ms", grace="1")
    a.input("dummy", tag="t", dummy='{"log":"x"}', rate="1")
    a.output("null", match="*")
    b = flb.create(flush="50ms", grace="1")
    b.input("dummy", tag="t", dummy='{"log":"x"}', rate="1")
    b.output("null", match="*")
    a.start()
    try:
        assert len(gc_hooks()) == before + 1
        b.start()
        try:
            assert len(gc_hooks()) == before + 2
            jax.profiler.start_trace(str(tmp_path))
            try:
                gc.collect(2)
            finally:
                jax.profiler.stop_trace()
        finally:
            b.stop()
        assert len(gc_hooks()) == before + 1
    finally:
        a.stop()
    assert len(gc_hooks()) == before
    forced = [e for e in by_name(read_events(str(tmp_path)), "gc.collect")
              if e["stats"]["gen"] == 2]
    assert len(forced) >= 1
    # never two spans for one pass: no two of them overlap
    forced.sort(key=lambda e: e["start"])
    for x, y in zip(forced, forced[1:]):
        assert x["end"] <= y["start"]


# ----------------------------------- a rewrite_tag frame, traced


REWRITE_RULES = ("$log kernel: sys.kernel false",
                 "$log ERROR app.error false",
                 r"$log cron\[\d+\] sys.cron false")


@pytest.fixture(scope="module")
def rewrite_events(mesh_env, tmp_path_factory):
    """One 96-line frame through forward → rewrite_tag (the platform
    gate forced open, three 32-line segments) → emitter → ``lib``,
    once to compile and once under a profiler session. → the traced
    frame's events, the records by tag and the plugin's timings."""
    from fluentbit_tpu.codec.events import decode_events
    from fluentbit_tpu.ops import device

    jax = mesh_env
    assert device.wait(120)
    saved = device.platform
    device.platform = lambda: "tpu"
    ctx = flb.create(flush="50ms", grace="1")
    try:
        ctx.input("forward", listen="127.0.0.1", port="0")
        f = ctx.filter("rewrite_tag", match="app", tpu_batch_records="1")
        for rule in REWRITE_RULES:
            ctx.set(f, rule=rule)
        got = {}
        ctx.output("lib", match="*", callback=lambda d, t: got.setdefault(
            t, []).extend(decode_events(bytes(d))))
        ctx.start()
        port = wait_for(lambda: ctx.engine.inputs[0].plugin.bound_port)
        lines = ["kernel: oom", "app ERROR x", "cron[7]: job", "plain"]
        entries = [[1700000000 + i, {"log": f"{lines[i % 4]} {i}"}]
                   for i in range(N_LINES)]

        def send(chunk):
            with socket.create_connection(("127.0.0.1", port)) as s:
                s.settimeout(120)
                s.sendall(packb(["app", entries, {"chunk": chunk}]))
                u = Unpacker()
                while True:
                    u.feed(s.recv(4096))
                    for msg in u:
                        assert msg == {"ack": chunk}
                        return

        send("rw-0000")  # compiles the programs
        trace_dir = str(tmp_path_factory.mktemp("rewrite_trace"))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            send("rw-0001")
        finally:
            jax.profiler.stop_trace()
        ctx.flush_now()
        wait_for(lambda: sum(map(len, got.values())) == 2 * N_LINES)
        plugin = ctx.engine.filters[0].plugin
        timings = {k: plugin.raw_timings[k] for k in plugin.raw_timings}
    finally:
        ctx.stop()
        device.platform = saved
    events = [e for e in read_events(trace_dir)
              if e["stats"].get("chunk") == "rw-0001"]
    return {"events": events, "got": got, "timings": timings}


@pytest.mark.mesh
@pytest.mark.parametrize("name", ["filter.rewrite_tag", "rewrite.stage",
                                  "rewrite.emit", "grep.stage",
                                  "lane.begin", "lane.launch",
                                  "grep.dispatch", "grep.force",
                                  "lane.wait"])
def test_traced_rewrite_frame_has_span_with_its_chunk(rewrite_events, name):
    assert by_name(rewrite_events["events"], name), \
        sorted({e["name"] for e in rewrite_events["events"]})


@pytest.mark.mesh
def test_rewrite_spans_carry_seg_lane_tag_and_rows(rewrite_events):
    events = rewrite_events["events"]
    n_seg = N_LINES // 32
    assert rewrite_events["timings"]["device_records"] == 2 * N_LINES
    for name in ("grep.stage", "lane.begin", "lane.launch", "lane.wait",
                 "grep.dispatch", "grep.force"):
        assert sorted(e["stats"]["seg"] for e in by_name(events, name)) \
            == list(range(n_seg)), name
    for name in ("lane.begin", "lane.launch", "lane.wait",
                 "grep.dispatch", "grep.force"):
        assert {e["stats"].get("lane")
                for e in by_name(events, name)} == {"grep"}, name
    (stage,) = by_name(events, "rewrite.stage")
    (outer,) = by_name(events, "filter.rewrite_tag")
    assert inside(stage, outer) and "lane" not in stage["stats"]
    for name in ("grep.stage", "lane.begin", "lane.wait"):
        assert all(inside(e, stage) for e in by_name(events, name)), name
    emits = by_name(events, "rewrite.emit")
    assert [(e["stats"]["tag"], e["stats"]["rows"]) for e in emits] == [
        ("sys.kernel", N_LINES // 4), ("app.error", N_LINES // 4),
        ("sys.cron", N_LINES // 4)]
    for e in emits:
        assert inside(e, outer) and stage["end"] <= e["start"]
    # the re-entry under the emitter's append is inside the emit span
    appends = by_name(events, "engine.append")
    assert len(appends) == 1 + len(emits)
    got = rewrite_events["got"]
    assert {t: len(v) for t, v in got.items()} == {
        "sys.kernel": N_LINES // 2, "app.error": N_LINES // 2,
        "sys.cron": N_LINES // 2, "app": N_LINES // 2}


# ------------------------------------------------------ lane counters


def test_lane_stats_seconds_grow_over_a_launch():
    lane = fault.DeviceLane("spans-test")
    before = lane.stats()
    for key in ("spawn_s", "run_s", "blocked_s"):
        assert before[key] == 0.0

    def launch():
        time.sleep(0.02)
        return 7

    assert lane.run(launch, lambda: -1) == 7
    st = lane.stats()
    assert st["launches"] == st["ok"] == 1
    assert st["run_s"] >= 0.019 and st["spawn_s"] > 0.0
    assert st["blocked_s"] > 0.0
    assert st["blocked_s"] <= st["spawn_s"] + st["run_s"] + 0.005
    # begin/finish apart: the caller blocks for less than the launch ran
    fl = lane.begin(launch, lambda: -1)
    time.sleep(0.03)
    assert lane.finish(fl) == 7
    st2 = lane.stats()
    assert st2["run_s"] - st["run_s"] >= 0.019
    assert st2["blocked_s"] - st["blocked_s"] < 0.019


def test_lane_wake_seconds_are_the_end_of_the_blocked_ones():
    """``wake_s``: the worker's ``done.set()`` → the waiting thread
    running again; ``blocked_s`` is what was left of ``run_s`` plus
    that."""
    lane = fault.DeviceLane("spans-wake")
    assert lane.stats()["wake_s"] == 0.0

    def launch():
        time.sleep(0.02)
        return 7

    assert lane.run(launch, lambda: -1) == 7
    st = lane.stats()
    assert 0.0 < st["wake_s"] <= st["blocked_s"]
    assert st["wake_s"] < 0.015 < st["blocked_s"]
    # a launch that was over before the caller came to wait: nobody was
    # woken, the wait returns at once and counts from its own start
    fl = lane.begin(launch, lambda: -1)
    time.sleep(0.05)
    assert lane.finish(fl) == 7
    st2 = lane.stats()
    assert 0.0 <= st2["wake_s"] - st["wake_s"] < 0.005
    assert st2["wake_s"] <= st2["blocked_s"]
    # a launch that timed out woke nobody
    lane3 = fault.DeviceLane("spans-wake-late", deadline=0.01)
    assert lane3.run(lambda: time.sleep(0.2), lambda: -1) == -1
    assert lane3.stats()["wake_s"] == 0.0 < lane3.stats()["blocked_s"]


def test_a_launch_over_a_second_is_counted_and_logged_once(caplog):
    """The B11 catcher: sums hide one stalled launch among thousands,
    ``launches_over_1s`` and its log line do not."""
    from fluentbit_tpu import failpoints

    lane = fault.DeviceLane("spans-slow")
    seen = []
    fault.add_listener(listener := lambda *a: seen.append(a))
    failpoints.enable("device.launch_hang", "1*hang(1200)")
    try:
        with caplog.at_level("WARNING", logger="flb.device.fault"):
            assert lane.run(lambda: 7, lambda: -1) == 7   # hangs 1.2 s
            assert lane.run(lambda: 8, lambda: -1) == 8   # does not
    finally:
        failpoints.reset()
        fault.remove_listener(listener)
    st = lane.stats()
    assert st["launches"] == st["ok"] == 2
    assert st["launches_over_1s"] == 1 and st["run_s"] >= 1.2
    lines = [r.getMessage() for r in caplog.records
             if "a launch ran for" in r.getMessage()]
    assert len(lines) == 1 and "spans-slow" in lines[0]
    assert [a for a in seen if a[1] == "slow_launch"] \
        == [("spans-slow", "slow_launch", 1)]


def test_lane_seconds_on_health_and_prometheus():
    ctx = flb.create(flush="50ms", grace="1")
    ctx.input("dummy", tag="t", dummy='{"log":"x"}', rate="1")
    ctx.output("null", match="*")
    lane = fault.lane("grep")
    lane.run(lambda: time.sleep(0.005), lambda: None)
    ctx.start()
    try:
        ctx.flush_now()
        text = wait_for(lambda: (
            lambda t: t if 'phase="run"' in t else None)(
                ctx.engine.metrics.to_prometheus()))
    finally:
        ctx.stop()
    for phase in ("spawn", "run", "blocked", "wake"):
        assert re.search(
            r'fluentbit_device_lane_seconds\{lane="grep",phase="%s"\} '
            r'[0-9.e+-]+' % phase, text), phase
    lanes = fault.health_block()["lanes"]["grep"]
    assert lanes["run_s"] >= 0.005 and "spawn_s" in lanes \
        and "blocked_s" in lanes
    assert 0.0 < lanes["wake_s"] <= lanes["blocked_s"]
    assert lanes["launches_over_1s"] == 0


def test_slow_launches_are_a_prometheus_counter_by_lane():
    ctx = flb.create(flush="50ms", grace="1")
    ctx.input("dummy", tag="t", dummy='{"log":"x"}', rate="1")
    ctx.output("null", match="*")
    ctx.start()
    try:
        fault.notify("grep", "slow_launch", 1)
        text = ctx.engine.metrics.to_prometheus()
    finally:
        ctx.stop()
    assert re.search(
        r'fluentbit_device_launches_over_1s_total\{lane="grep"\} 1\b', text)


@pytest.mark.mesh
def test_lane_workers_make_no_timing_shards(mesh_env):
    """The trap: ShardedTimings keeps one shard per thread that adds,
    and the lane starts a thread per launch."""
    from fluentbit_tpu.codec.events import encode_event
    from fluentbit_tpu.core.engine import Engine

    saved = os.environ["FBTPU_SEGMENT_RECORDS"]
    os.environ["FBTPU_SEGMENT_RECORDS"] = "8"
    try:
        e = Engine()
        f = e.filter("grep")
        f.set("regex", f"log {APACHE2}")
        f.set("tpu_batch_records", "1")
        ins = e.input("dummy")
        for x in e.inputs + e.filters:
            x.configure()
            x.plugin.init(x, e)
        chunk = b"".join(
            encode_event({"log": OK_LINE if i % 4 else f"oom {i}"},
                         float(i)) for i in range(1600))
        before = fault.lane("grep").stats()
        assert e.input_log_append(ins, "t", chunk) == 1200
        after = fault.lane("grep").stats()
    finally:
        os.environ["FBTPU_SEGMENT_RECORDS"] = saved
    plugin = e.filters[0].plugin
    assert plugin._mesh is not None
    assert after["launches"] - before["launches"] == 200
    assert after["run_s"] > before["run_s"]
    tm = plugin.raw_timings
    assert len(tm._shards) == 1  # the one ingest thread
    assert tm["device_records"] == 1600 and tm["kernel_s"] > 0
    # the keys the benchmark and the smoke read, and no other
    assert set(tm) == {"extract_s", "kernel_s", "compact_s", "records",
                       "device_records", "overflow_rows", "h2d_bytes",
                       "d2h_bytes", "scan_elements", "mesh_launches",
                       "mesh_devices", "unsharded_launches",
                       "split_launches", "long_rows"}
    # the mesh stages a frame at its one width: never in two groups
    assert tm["split_launches"] == tm["long_rows"] == 0
    # the layout of each launch, counted on the dispatching thread
    assert tm["mesh_launches"] == 200 and tm["unsharded_launches"] == 0
    assert tm["mesh_devices"] == 200 * plugin._mesh.devices.size
    # the mesh's verdict is copied out as i32: four bytes a rule and row
    assert tm["d2h_bytes"] >= 4 * 1600 and tm["d2h_bytes"] % (4 * 200) == 0
    assert tm["scan_elements"] >= 1600 * (512 // plugin._program.k + 1)


# ------------------------------------------------- plugin raw_timings


def test_log_to_metrics_and_flux_have_raw_timings():
    from fluentbit_tpu.codec.events import encode_event
    from fluentbit_tpu.core.engine import Engine

    e = Engine()
    for mode, field in (("cardinality", "user"), ("frequency", "path")):
        f = e.filter("log_to_metrics")
        for k, v in {"metric_mode": mode, "value_field": field,
                     "metric_name": f"m_{mode}", "tag": "metrics",
                     "metric_description": "d"}.items():
            f.set(k, v)
    f = e.filter("flux")
    for k, v in {"group_by": "tenant", "distinct_field": "user",
                 "export_interval_sec": "0"}.items():
        f.set(k, v)
    ins = e.input("dummy")
    for x in e.inputs + e.filters:
        x.configure()
        x.plugin.init(x, e)
    raw = b"".join(
        encode_event({"tenant": "a", "user": f"u{i % 13}",
                      "path": f"/p{i % 7}"}, float(i)) for i in range(64))
    assert e.input_log_append(ins, "t", raw) == 64
    card, freq, flux = (f.plugin for f in e.filters)
    for plugin in (card, freq):
        tm = plugin.raw_timings
        assert set(tm) == set(L2M_KEYS + L2M_CMS_KEYS)
        for key in L2M_KEYS:
            assert tm[key] > 0, (plugin.mode, key)
    assert [card.raw_timings[k] for k in L2M_CMS_KEYS] == [0, 0, 0]
    tm = freq.raw_timings
    assert 0 < tm["cms_query_s"] <= tm["query_s"]
    assert tm["query_values"] == 7 and tm["query_scalar_values"] == 0
    tm = flux.raw_timings
    assert tm is flux.state.timings
    from fluentbit_tpu.flux.state import TIMING_KEYS

    assert set(tm) == set(TIMING_KEYS) and tm["absorb_s"] > 0
    assert 0 < tm["group_s"] <= tm["absorb_s"]
    assert tm["host_absorbs"] == 1 and tm["fused_absorbs"] == 0
    assert len(tm._shards) == 1


@pytest.mark.parametrize("key", L2M_KEYS + ("absorb_s", "compact_s",
                                             "cms_query_s",
                                             "query_scalar_values"))
def test_every_timing_key_this_pr_adds_feeds_a_metric(key):
    """An always-on counter that nothing reads is only a cost: each
    seconds key of ``log_to_metrics`` and ``flux`` (and grep's
    ``compact_s``, read by nothing before) is the numerator of one
    data-only per-layer metric of the benchmark."""
    import json

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        declared = {m["name"] for m in json.load(f)["per_layer"]}
    read = {}
    for path in glob.glob(os.path.join(REPO, "benchmark",
                                       "layer_metrics", "*.json")):
        with open(path) as f:
            spec = json.load(f)
        if spec["reader"] == "counters:ratio":
            read[spec["args"]["num"]] = os.path.basename(path)[:-5]
    plugin = {"absorb_s": "flux", "compact_s": "grep"}.get(
        key, "log_to_metrics")
    counter = f"filter.{plugin}.{key}"
    assert read.get(counter) in declared, counter


#: ``rewrite_tag``'s ``raw_timings`` (the staged launch's keys through
#: the helper it shares with grep, and its own three)
REWRITE_KEYS = ("extract_s", "kernel_s", "h2d_bytes", "d2h_bytes",
                "scan_elements", "device_records", "overflow_rows",
                "split_launches", "long_rows",
                "records", "emit_s", "emits", "emit_backpressure")


@pytest.mark.parametrize("key", REWRITE_KEYS)
def test_every_rewrite_timing_key_feeds_a_metric_or_a_check(
        key, counters_of_declared_metrics):
    """The same rule for ``rewrite_tag``'s keys: each is read by a
    declared data-only metric (``conftest.py``), or by a named check of
    the configuration's plain reference."""
    from fluentbit_tpu.plugins.filter_rewrite_tag import _TIMING_KEYS

    assert set(_TIMING_KEYS) == set(REWRITE_KEYS)
    numerators = counters_of_declared_metrics
    with open(os.path.join(REPO, "benchmark", "reference",
                           "rewrite-syslog.py")) as f:
        reference = f.read()
    counter = f"filter.rewrite_tag.{key}"
    # the reference reads the plugin's counters as ``pre + "<key>"``
    # inside the named verdicts of its ``checks``
    checked = 'pre = "filter.rewrite_tag."' in reference \
        and f'c.get(pre + "{key}"' in reference
    assert counter in numerators or checked, counter


# --------------------------------------- the benchmark's outside hooks


def test_outside_wrappers_of_the_benchmark_are_still_called(monkeypatch):
    """``benchmark/run.py::install_spans`` subclasses the Unpacker,
    rebinds ``_entries_to_events`` and setattr's wrappers on the engine
    and the plugins: each name must still be looked up at call time.
    The C cut of a chunk lies beneath the Unpacker's ``__next__``; a
    frame it hands back (here: entries of three elements) is re-encoded
    beneath ``_entries_to_events``."""
    from fluentbit_tpu.codec import _native_codec
    from fluentbit_tpu.plugins import net_forward

    calls = {}

    def counted(fn, key):
        def call(*a, **kw):
            calls[key] = calls.get(key, 0) + 1
            return fn(*a, **kw)
        return call

    class TimedUnpacker(net_forward.Unpacker):
        feed = counted(net_forward.Unpacker.feed, "feed")
        __next__ = counted(net_forward.Unpacker.__next__, "next")

    monkeypatch.setattr(net_forward, "Unpacker", TimedUnpacker)
    monkeypatch.setattr(
        net_forward, "_entries_to_events",
        counted(net_forward._entries_to_events, "reencode"))
    ctx = flb.create(flush="50ms", grace="1")
    ctx.input("forward", listen="127.0.0.1", port="0")
    ctx.filter("grep", match="*", regex="log GET")
    got = []
    ctx.output("lib", match="*", callback=lambda d, _t: got.append(d))
    engine = ctx.engine
    for attr in ("input_log_append", "flush_all"):
        setattr(engine, attr, counted(getattr(engine, attr), attr))
    plugin = engine.filters[0].plugin
    for attr in ("process_batch", "filter"):
        setattr(plugin, attr, counted(getattr(plugin, attr), "grep"))
    handed_back = packb(["app", [[1700000000 + i, {"log": OK_LINE}, None]
                                 for i in range(8)], {"chunk": "back"}])
    ctx.start()
    try:
        port = wait_for(lambda: engine.inputs[0].plugin.bound_port)
        with socket.create_connection(("127.0.0.1", port)) as s:
            s.settimeout(30)
            for data in (frame(n=8), handed_back):
                s.sendall(data)
                assert s.recv(4096)
        ctx.flush_now()
        wait_for(lambda: got)
    finally:
        ctx.stop()
    cut = int(_native_codec.load() is not None)
    assert engine.inputs[0].plugin.n_cut == cut
    assert calls["feed"] >= 2 and calls["next"] >= 4
    assert calls["reencode"] == 2 - cut and calls["input_log_append"] == 2
    assert calls["grep"] >= 1 and calls["flush_all"] >= 1


# ------------------------------------------------ named device programs


@pytest.mark.parametrize("staging", ["whole", "two-groups"])
def test_jitted_grep_program_has_a_stable_module_name(staging):
    """The module a launch runs has the program's name whether the frame
    goes whole or in two groups (``dispatch(..., long=)``): the trace's
    readers sum a launch by it."""
    pytest.importorskip("jax")
    import numpy as np

    from fluentbit_tpu.ops import device
    from fluentbit_tpu.ops.grep import GrepProgram
    from fluentbit_tpu.regex.dfa import compile_dfa

    assert device.wait(120)
    prog = GrepProgram([compile_dfa(r"curl/8\.5")], max_len=64)
    batch = np.zeros((1, 8, 64), dtype=np.uint8)
    lengths = np.full((1, 8), -1, dtype=np.int32)
    prog.match(batch, lengths)
    if staging == "whole":
        fn, args = prog._jit, (batch, lengths)
    else:
        fn, args = prog._jit_long, (
            batch[:, :, :32], lengths, batch[:, :4], lengths[:, :4],
            np.arange(4, dtype=np.int32))
    text = fn.lower(*args).as_text()
    m = re.search(r"module @(\w+)", text)
    assert re.fullmatch(r"jit_grep_scan_S\d+_k\d+", m.group(1))
    assert m.group(1) == f"jit_{prog.program_name()}"
    assert "grep.symbols" in fn.lower(*args).as_text(debug_info=True)


def test_rewrite_tag_programs_have_stable_module_names():
    """Config 3's eight rules: three per-stride scan children, the
    merge and the first-match reduction, each a named module."""
    pytest.importorskip("jax")
    import numpy as np

    from fluentbit_tpu.ops import device
    from fluentbit_tpu.ops import grep as ops_grep
    from fluentbit_tpu.regex.dfa import compile_dfa

    assert device.wait(120)
    patterns = ("sshd", "kernel:", r"systemd\[1\]", "ERROR", "WARN",
                "nginx", r"cron\[\d+\]", ".*OOM.*")
    prog = ops_grep.GrepProgram([compile_dfa(p) for p in patterns],
                                max_len=64, plane_of=(0,) * len(patterns))
    planes = np.zeros((1, 8, 64), dtype=np.uint8)
    lengths = np.full((1, 8), -1, dtype=np.int32)
    first = prog.match(planes, lengths, first_match=True)
    assert first.tolist() == [-1] * 8

    def module(fn, *args):
        return re.search(r"module @(\w+)",
                         fn.lower(*args).as_text()).group(1)

    names = [module(c._jit, planes, lengths) for c in prog._children]
    assert names == [f"jit_{c.program_name()}" for c in prog._children]
    assert [re.fullmatch(r"jit_grep_scan_S\d+_k(\d)", n).group(1)
            for n in names] == ["4", "5", "6"]
    masks = [np.zeros((len(c.dfas), 8), dtype=bool)
             for c in prog._children]
    assert module(prog._merge_jit, *masks) == "jit_grep_merge"
    assert module(ops_grep.first_match_of,
                  np.zeros((8, 8), dtype=bool)) == "jit_grep_first_match"


def test_sketch_and_flux_programs_are_named():
    jax = pytest.importorskip("jax")
    import numpy as np

    from fluentbit_tpu.flux import kernels
    from fluentbit_tpu.ops import device
    from fluentbit_tpu.ops.sketch import CountMin, HyperLogLog

    assert device.wait(120)
    batch = np.zeros((8, 16), dtype=np.uint8)
    lengths = np.full((8,), 3, dtype=np.int32)

    def module(fn, *args):
        return re.search(r"module @(\w+)",
                         fn.lower(*args).as_text()).group(1)

    hll, cms = HyperLogLog(p=4), CountMin(depth=2, width=16)
    assert module(hll._device_jit(wait=True), hll.registers, batch,
                  lengths) == "jit_hll_update"
    assert module(cms._device_jit(wait=True), cms.table, batch, lengths,
                  np.ones((8,), np.int32)) == "jit_cms_update"
    fn = kernels.build_fused_absorb(None, 8, 1, 4)
    seg = np.zeros((8,), np.int32)
    regs = np.zeros((8, 16), np.int32)
    assert module(fn, seg, seg, batch, lengths, regs) == "jit_flux_absorb"
    del jax


def test_profiler_port_is_a_service_key_off_by_default():
    from fluentbit_tpu.core.config import ServiceConfig

    svc = ServiceConfig()
    assert svc.profiler_port == 0
    svc.set("Profiler_Port", "9012")
    assert svc.profiler_port == 9012 and "profiler_port" not in svc.extra


def run_with_profiler_port(monkeypatch, port, start_server,
                           attached=True):
    """An engine started with ``profiler_port``: → what
    ``jax.profiler.start_server`` was called with (and whether the
    device was attached by then), and whether ``_serve_profiler`` ran
    to its end without raising."""
    import jax

    from fluentbit_tpu.core.engine import Engine
    from fluentbit_tpu.ops import device

    calls, ended = [], threading.Event()
    serve = Engine._serve_profiler

    def fake(p):
        calls.append((p, device.ready()))
        return start_server(p)

    def watched(engine):
        assert threading.current_thread().name == "flb-profiler"
        serve(engine)  # a raise here leaves ``ended`` unset
        ended.set()

    monkeypatch.setattr(jax.profiler, "start_server", fake)
    monkeypatch.setattr(Engine, "_serve_profiler", watched)
    if not attached:
        monkeypatch.setattr(device, "wait", lambda _timeout=None: False)
    ctx = flb.create(flush="50ms", grace="1")
    if port:
        ctx.service_set(profiler_port=str(port))
    ctx.input("dummy", tag="t", dummy='{"log":"x"}', rate="1")
    ctx.output("null", match="*")
    ctx.start()
    try:
        served = ended.wait(60 if port else 0.2)
        assert ctx.engine._thread.is_alive()  # the engine serves on
    finally:
        ctx.stop()
    return calls, served


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_profiler_port_starts_the_server_once_after_the_attach(
        monkeypatch):
    port = free_port()
    calls, served = run_with_profiler_port(monkeypatch, port,
                                           lambda _p: None)
    assert served and calls == [(port, True)]  # once, device attached


def test_profiler_port_off_starts_nothing(monkeypatch):
    calls, served = run_with_profiler_port(monkeypatch, 0,
                                           lambda _p: None)
    assert not served and calls == []


def test_profiler_port_failure_is_logged_and_does_not_raise(
        monkeypatch, caplog):
    def refuse(_p):
        raise RuntimeError("address already in use")

    with caplog.at_level("ERROR", logger="flb.engine"):
        calls, served = run_with_profiler_port(monkeypatch, free_port(),
                                               refuse)
    assert served and len(calls) == 1
    assert any("profiler server failed to start" in r.getMessage()
               for r in caplog.records)


def test_profiler_port_without_a_device_starts_no_server(monkeypatch,
                                                         caplog):
    with caplog.at_level("WARNING", logger="flb.engine"):
        calls, served = run_with_profiler_port(
            monkeypatch, free_port(), lambda _p: None, attached=False)
    assert served and calls == []
    assert any("no profiler server" in r.getMessage()
               for r in caplog.records)
