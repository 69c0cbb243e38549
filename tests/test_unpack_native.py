"""The streaming ``Unpacker`` behind the C codec's ``unpack_from``
(native/fbtpu_codec.c): iteration finds a message's end without building
objects and decodes it once it is whole. The pure-Python walk stays the
reference: every case here holds the native-backed iteration to it —
same objects, same types, same ``tell()``, same exceptions — and runs on
the Python path alone where the extension is not built.

Nesting between the Python walk's recursion limit (some 490 levels under
the default ``sys.getrecursionlimit()``) and the C decoder's
``MAX_DEPTH`` (512) is the one place the two may differ (C decodes what
Python gives up on), as ``decode_events`` always did; the corpus stays
on either side of it.
"""

import math
import socket
import struct
import time
import tracemalloc

import pytest

import fluentbit_tpu as flb
import fluentbit_tpu.codec._native_codec as nc
from fluentbit_tpu.codec import msgpack
from fluentbit_tpu.codec.events import encode_event
from fluentbit_tpu.codec.msgpack import EventTime, ExtType, Unpacker, packb

mod = nc.load()
needs_native = pytest.mark.skipif(mod is None,
                                  reason="codec extension unavailable")
MODES = [pytest.param("native", marks=needs_native), "python"]
_NO_MSG = object()


@pytest.fixture
def python_only(monkeypatch):
    """The state ``FBTPU_NO_NATIVE`` leaves: ``load()`` gives None."""
    def switch():
        monkeypatch.setattr(nc, "_mod", None)
        monkeypatch.setattr(nc, "_tried", True)
    return switch


@pytest.fixture
def no_python_walk(monkeypatch):
    """From the call on ``_unpack_one`` raises: the extension has to
    serve alone (the reference is walked before it)."""
    def boom(self):
        raise AssertionError("_unpack_one entered")
    return lambda: monkeypatch.setattr(Unpacker, "_unpack_one", boom)


@pytest.fixture
def mode(request, python_only):
    if request.param == "python":
        python_only()
    return request.param


def reference(buf: bytes = b"") -> Unpacker:
    """An Unpacker the pure-Python walk serves, whatever ``load()``
    says: the hook is not *the* default one, though it does the same
    (``test_custom_ext_hook_is_served_by_python`` holds the rule)."""
    return Unpacker(buf, ext_hook=lambda code, data:
                    msgpack._default_ext_hook(code, data))


def walk_python(buf: bytes):
    """→ (outcomes, tells) of the pure-Python walk over ``buf``."""
    return drain(reference(buf))


def messages(buf: bytes) -> list:
    """The whole messages of ``buf``, by the pure-Python walk."""
    return [o[1] for o in walk_python(buf)[0][:-1]]


def drain(u: Unpacker):
    """Take messages until the Unpacker stops or raises → (outcomes,
    tell() after each step); an outcome is ("ok", obj), ("stop",) or
    ("raise", type)."""
    outcomes, tells = [], []
    while True:
        try:
            obj = next(u)
        except StopIteration:
            outcomes.append(("stop",))
        except (ValueError, RecursionError, MemoryError) as e:
            outcomes.append(("raise", type(e)))
        else:
            outcomes.append(("ok", obj))
        tells.append(u.tell())
        if outcomes[-1][0] != "ok":
            return outcomes, tells


def same(a, b) -> bool:
    """Equal, and of the same types all the way down (NaN equals NaN,
    0.0 does not equal -0.0, True is not 1)."""
    if type(a) is not type(b):
        return False
    if isinstance(a, float):
        return struct.pack(">d", a) == struct.pack(">d", b)
    if isinstance(a, list):
        return len(a) == len(b) and all(map(same, a, b))
    if isinstance(a, dict):
        return len(a) == len(b) and all(
            same(ka, kb) and same(va, vb)
            for (ka, va), (kb, vb) in zip(a.items(), b.items()))
    if isinstance(a, EventTime):
        return (a.sec, a.nsec) == (b.sec, b.nsec)
    return a == b


def same_outcomes(got, want) -> bool:
    return len(got) == len(want) and all(
        g[0] == w[0] and (same(g[1], w[1]) if g[0] == "ok" else g == w)
        for g, w in zip(got, want))


# ----------------------------------------------------------- the corpus

ENTRIES = [[EventTime(1700000000 + i, i * 1000), {"log": f"line {i}",
                                                   "n": i}]
           for i in range(5)]
PACKED = b"".join(packb([EventTime(1700000000, i), {"log": "p" * i}])
                  for i in range(4))


# widths wider than the value needs: legal on the wire, never packb's
WIDE_STRS = b"\xd9\x01x\xda\x00\x01y\xdb\x00\x00\x00\x01z"
WIDE_BINS = b"\xc4\x01x\xc5\x00\x01x\xc6\x00\x00\x00\x01y"
WIDE_ARRAYS = b"\xdc\x00\x02\x01\x02\xdd\x00\x00\x00\x02\x01\x02"
WIDE_MAPS = b"\xde\x00\x01\xa1k\x01\xdf\x00\x00\x00\x01\xa1k\x02"


def nested(depth: int) -> bytes:
    return b"\x91" * depth + b"\x01"


CORPUS = {
    "forward": packb(["app.tag", ENTRIES, {"chunk": "abc==", "size": 5}]),
    "forward_no_option": packb(["app.tag", ENTRIES]),
    "packed_forward": packb(["app", PACKED, {"chunk": "p1", "size": 4}]),
    "packed_gzip_option": packb(["app", b"\x1f\x8b" + bytes(40),
                                 {"compressed": "gzip"}]),
    "message_mode": packb(["app", 1700000000, {"k": "v"},
                           {"chunk": "m1"}]),
    "message_mode_eventtime": packb(["app", EventTime(7, 8), {"k": 1}]),
    "ack": packb({"ack": "abc=="}),
    "helo_ping_pong": packb(["HELO", {"nonce": bytes(16), "auth": b"",
                                      "keepalive": True}])
    + packb(["PING", "host", b"salt", "0" * 128, "", ""])
    + packb(["PONG", True, "", "host", "f" * 128]),
    "nested_maps_arrays": packb(
        {"a": [1, [2, [3, {"b": {"c": [None, True, False, {}], "d": []}}]]],
         "e": {"f": {"g": {"h": "i"}}}}),
    "nil_bool": b"\xc0\xc2\xc3",
    "fixint": bytes([0x00, 0x01, 0x7f, 0xe0, 0xff]),
    "uint_widths": b"\xcc\x80\xcc\x01\xcd\x01\x00\xcd\x00\x01"
                   b"\xce\x00\x01\x00\x00\xce\xff\xff\xff\xff"
                   b"\xcf\x00\x00\x00\x01\x00\x00\x00\x00"
                   b"\xcf\xff\xff\xff\xff\xff\xff\xff\xff",
    "int_widths": b"\xd0\x80\xd0\x7f\xd1\x80\x00\xd1\xff\xff"
                  b"\xd2\x80\x00\x00\x00\xd2\x00\x00\x00\x05"
                  b"\xd3\x80\x00\x00\x00\x00\x00\x00\x00"
                  b"\xd3\xff\xff\xff\xff\xff\xff\xff\xfe",
    "float32": b"\xca" + struct.pack(">f", 1.5)
               + b"\xca" + struct.pack(">f", 0.1)
               + b"\xca\x7f\xc0\x00\x00\xca\x80\x00\x00\x00"
               + b"\xca\x7f\x80\x00\x00",
    "float64": packb([0.1, -0.0, 1e308, math.inf, -math.inf])
               + b"\xcb\x7f\xf8\x00\x00\x00\x00\x00\x01",
    "str_widths": packb(["", "a" * 31, "b" * 32, "c" * 255, "d" * 256,
                         "e" * 65535, "f" * 65536, "héllo ☃ \U0001f600"])
    + WIDE_STRS,
    "bin_widths": packb([b"", b"\x00" * 255, b"\x01" * 256,
                         b"\x02" * 65535, b"\x03" * 65536])
    + WIDE_BINS,
    "array_widths": packb([list(range(15)), list(range(16)),
                           list(range(65535)), list(range(65536))])
    + WIDE_ARRAYS,
    "map_widths": packb([{str(i): i for i in range(15)},
                         {str(i): i for i in range(16)},
                         {str(i): i for i in range(65536)}])
    + WIDE_MAPS,
    "eventtime": packb([EventTime(0, 0), EventTime(2**32 - 1, 2**32 - 1),
                        EventTime(1700000000, 999999999)])
    + b"\xc7\x08\x00" + bytes(range(8))            # ext8, type 0, len 8
    + b"\xc8\x00\x08\x00" + bytes(range(8))        # ext16
    + b"\xc9\x00\x00\x00\x08\x00" + bytes(range(8)),  # ext32
    "eventtime_as_map_key_and_value":
        b"\x81" + packb(EventTime(3, 4)) + packb(EventTime(5, 6)),
    "unhashable_map_keys":
        b"\x83" + packb([1, [2]]) + packb("list key")
        + packb({"k": {"n": 1}}) + packb("map key")
        + packb([EventTime(1, 2)]) + packb("list of EventTime"),
    "colliding_map_keys":
        b"\x86\x01\xa1a\xcb" + struct.pack(">d", 1.0) + b"\xa1b\xc3\xa1c"
        b"\xa1k\x01\xa1k\x02\xc4\x01k\x03",
    "invalid_utf8_in_str":
        b"\xa4\xff\xfeab" + b"\xa3\xe2\x82x" + b"\xa2\xc0\xaf"
        + b"\xa4\xf0\x9f\x98z" + b"\xa3\xed\xa0\x80"
        + b"\x81\xa2\xff\xff\xa1v",
    "concatenated_small": b"".join(packb(i) for i in range(-40, 300, 7)),
    "nested_100": nested(100),
    "nested_400_maps": b"\x81\xa1k" * 200 + b"\x01",
    # what the extension hands back (FallbackError): the Python walk
    # builds the ExtType
    "ext_other_type": packb(ExtType(5, b"x")) + packb(ExtType(1, bytes(8)))
    + packb(ExtType(0, bytes(4))) + packb(ExtType(-1, bytes(300)))
    + packb(ExtType(127, bytes(70000))),
    "ext_inside_forward": packb(["t", [[ExtType(9, b"zz"), {"a": 1}]]])
    + packb({"ack": "after"}),
}


@pytest.mark.parametrize("mode", MODES, indirect=True)
@pytest.mark.parametrize("name", sorted(CORPUS))
def test_iteration_equals_the_python_walk(name, mode):
    """(a) Equal objects of equal types, and ``tell()`` agrees after
    each message and at the end."""
    buf = CORPUS[name]
    want, want_tells = walk_python(buf)
    assert want[-1] == ("stop",) and want_tells[-1] == len(buf), \
        "the corpus holds whole messages only"
    got, tells = drain(Unpacker(buf))
    assert same_outcomes(got, want)
    assert tells == want_tells
    # through a memoryview and past a feed(), as the callers use it
    u = Unpacker(memoryview(b"\x01" + buf))
    assert next(u) == 1
    got, tells = drain(u)
    assert same_outcomes(got, want)
    assert tells == [t + 1 for t in want_tells]
    u = Unpacker()
    u.feed(buf)
    assert same_outcomes(drain(u)[0], want)


@needs_native
@pytest.mark.parametrize("name", sorted(
    n for n in CORPUS if not n.startswith("ext_")))
def test_whole_messages_never_enter_the_python_walk(name, no_python_walk):
    no_python_walk()
    u = Unpacker(CORPUS[name])
    n = sum(1 for _ in u)
    assert n >= 1 and u.native and u.tell() == len(CORPUS[name])


def test_python_only_fixture_is_what_no_native_leaves(python_only):
    python_only()
    assert nc.load() is None
    u = Unpacker(CORPUS["forward"])
    assert next(u)[0] == "app.tag" and u.native is False


@needs_native
def test_native_attribute_follows_each_attempt():
    u = Unpacker()
    assert u.native is False          # nothing asked yet
    assert next(u, _NO_MSG) is _NO_MSG and u.native is True
    u.feed(packb(ExtType(5, b"x")) + packb(1))
    assert next(u) == ExtType(5, b"x") and u.native is False
    assert next(u) == 1 and u.native is True
    assert next(u, _NO_MSG) is _NO_MSG and u.native is True


# ---------------------------------------------------------- split feeds

STREAMS = {
    "forward": CORPUS["forward"] + CORPUS["ack"] + CORPUS["message_mode"]
    + CORPUS["packed_forward"],
    "widths": CORPUS["uint_widths"] + CORPUS["int_widths"]
    + CORPUS["float32"] + CORPUS["eventtime"]
    + WIDE_ARRAYS + WIDE_MAPS + WIDE_STRS + WIDE_BINS,
    "keys_and_utf8": CORPUS["unhashable_map_keys"]
    + CORPUS["invalid_utf8_in_str"] + CORPUS["colliding_map_keys"]
    + CORPUS["nested_100"],
}


def fed_in_pieces(pieces):
    """What ``_handle_conn`` does: feed a read, take what is whole."""
    u, out = Unpacker(), []
    for piece in pieces:
        u.feed(piece)
        while True:
            msg = next(u, _NO_MSG)
            if msg is _NO_MSG:
                break
            out.append(msg)
    return out, u


@pytest.mark.parametrize("mode", MODES, indirect=True)
@pytest.mark.parametrize("name", sorted(STREAMS))
def test_stream_cut_at_every_offset(name, mode):
    """(b) Two feeds, cut at every byte offset: the same messages in
    the same order, nothing left over."""
    stream = STREAMS[name]
    want = messages(stream)
    assert len(want) >= 4
    for cut in range(len(stream) + 1):
        got, u = fed_in_pieces((stream[:cut], stream[cut:]))
        assert same(got, want), cut
        assert u.tell() == len(u._buf), cut


@pytest.mark.parametrize("mode", MODES, indirect=True)
@pytest.mark.parametrize("name", sorted(STREAMS))
def test_stream_fed_a_byte_at_a_time(name, mode):
    stream = STREAMS[name]
    want = messages(stream)
    got, u = fed_in_pieces(stream[i:i + 1] for i in range(len(stream)))
    assert same(got, want) and u.tell() == len(u._buf)


@needs_native
@pytest.mark.parametrize("name", sorted(STREAMS))
def test_incomplete_message_never_enters_the_python_walk(name,
                                                         no_python_walk):
    """(b) With the extension loaded the tear costs a span walk: no
    object is built, ``_unpack_one`` is not entered, at any offset."""
    stream = STREAMS[name]
    want = messages(stream)
    no_python_walk()
    for cut in range(len(stream) + 1):
        got, u = fed_in_pieces((stream[:cut], stream[cut:]))
        assert same(got, want) and u.native, cut
    got, _u = fed_in_pieces(stream[i:i + 1] for i in range(len(stream)))
    assert same(got, want)


@pytest.mark.parametrize("mode", MODES, indirect=True)
def test_failed_attempt_leaves_the_position(mode):
    frame = CORPUS["forward"]
    u = Unpacker(packb(1) + frame[:-1])
    assert next(u) == 1
    at = u.tell()
    for _ in range(3):
        assert next(u, _NO_MSG) is _NO_MSG and u.tell() == at
    u.feed(frame[-1:])
    assert next(u)[0] == "app.tag" and u.tell() == len(frame)


# -------------------------------------------------------- hostile bytes

HOSTILE = {
    # name: (bytes, the pure-Python path's outcome today)
    "c1": (b"\xc1", ("raise", ValueError)),
    "c1_inside_an_array": (b"\x93\x01\xc1\x02", ("raise", ValueError)),
    "c1_after_a_message": (b"\x01\xc1", ("raise", ValueError)),
    "c1_in_a_torn_frame": (b"\x93\xa1t\xdc\x10\x00\xc1", ("raise",
                                                          ValueError)),
    "nested_over_max_depth": (nested(600), ("raise", RecursionError)),
    "nested_over_max_depth_torn": (b"\x91" * 600, ("raise",
                                                   RecursionError)),
    "nested_maps_over_max_depth": (b"\x81\xa1k" * 600 + b"\x01",
                                   ("raise", RecursionError)),
    "nested_under_max_depth_torn": (b"\x91" * 300, ("stop",)),
    "array32_of_4g_then_nothing": (b"\xdd\xff\xff\xff\xff", ("stop",)),
    "array32_of_4g_then_a_little": (b"\xdd\xff\xff\xff\xff\x01\x02\x03",
                                    ("stop",)),
    "map32_of_4g_then_nothing": (b"\xdf\xff\xff\xff\xff", ("stop",)),
    "map32_of_4g_then_a_little": (b"\xdf\xff\xff\xff\xff\xa1k\x01\xa1j",
                                  ("stop",)),
    "array32_of_4g_inside_a_frame": (
        b"\x93\xa1t\xdd\xff\xff\xff\xff\x92\x01\x80", ("stop",)),
    "str32_of_4g": (b"\xdb\xff\xff\xff\xffabc", ("stop",)),
    "bin32_of_4g": (b"\xc6\xff\xff\xff\xffabc", ("stop",)),
    "ext32_of_4g": (b"\xc9\xff\xff\xff\xff\x00abc", ("stop",)),
    "torn_headers": (b"\xdd\x00\x00", ("stop",)),
    "empty": (b"", ("stop",)),
}


@pytest.mark.parametrize("mode", MODES, indirect=True)
@pytest.mark.parametrize("name", sorted(HOSTILE))
def test_hostile_bytes_end_as_on_the_python_path(name, mode):
    """(c) A peer that sends these is dropped (an exception leaves
    ``_handle_conn``) or waited on (stop) exactly as before."""
    buf, first_bad = HOSTILE[name]
    want, want_tells = walk_python(buf)
    assert want[-1] == first_bad
    got, tells = drain(Unpacker(buf))
    assert same_outcomes(got, want)
    if first_bad != ("raise", RecursionError):
        # (where the interpreter's frame limit strikes depends on the
        # caller's own depth: the position after it says nothing)
        assert tells == want_tells


@needs_native
@pytest.mark.parametrize("name", sorted(
    n for n, (_b, bad) in HOSTILE.items() if bad == ("stop",)))
def test_torn_hostile_header_allocates_nothing(name, no_python_walk):
    """(c) ``array 32`` claiming 2**32 - 1 entries: the walk runs into
    the end before any list is asked for."""
    no_python_walk()
    u = Unpacker(HOSTILE[name][0])
    tracemalloc.start()
    try:
        assert next(u, _NO_MSG) is _NO_MSG
        _now, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert peak < 64 * 1024 and u.tell() == 0 and u.native


@needs_native
def test_array32_whose_count_outruns_its_bytes_builds_no_list():
    """The walk comes first, so ``PyList_New`` is never asked for more
    entries than the message has bytes: 2**28 claimed, 3 present."""
    with pytest.raises(StopIteration):
        next(Unpacker(b"\xdd\x10\x00\x00\x00\x01\x02\x03"))
    assert mod.unpack_from(b"\xdd\x10\x00\x00\x00\x01\x02\x03", 0) is None


@needs_native
@pytest.mark.parametrize("buf", [
    b"\xc1", b"\x92\x01\xc1", nested(512), nested(600), b"\x91" * 600,
    packb(ExtType(5, b"x")), packb([1, ExtType(0, bytes(4))]),
], ids=["c1", "c1_inside", "depth_512", "depth_600", "depth_600_torn",
        "ext_5", "ext_0_len_4"])
def test_unpack_from_hands_back_what_it_cannot_reproduce(buf):
    with pytest.raises(mod.FallbackError):
        mod.unpack_from(buf, 0)


@needs_native
def test_unpack_from_contract():
    buf = packb("x") + packb([EventTime(1, 2), {"a": [1]}])
    assert mod.unpack_from(buf, 0) == ("x", 2)
    obj, end = mod.unpack_from(memoryview(buf), 2)
    assert same(obj, [EventTime(1, 2), {"a": [1]}]) and end == len(buf)
    assert mod.unpack_from(buf, len(buf)) is None
    assert mod.unpack_from(buf[:-1], 2) is None
    assert mod.unpack_from(nested(511), 0)[1] == 512
    for pos in (-1, len(buf) + 1):
        with pytest.raises(ValueError):
            mod.unpack_from(buf, pos)
    with pytest.raises(TypeError):
        mod.unpack_from("str", 0)


@pytest.mark.parametrize("mode", MODES, indirect=True)
def test_non_eventtime_ext_is_an_exttype(mode):
    u = Unpacker(packb([ExtType(5, b"abc"), EventTime(1, 2)]))
    got = next(u)
    assert same(got, [ExtType(5, b"abc"), EventTime(1, 2)])
    assert u.native is False


@pytest.mark.parametrize("mode", MODES, indirect=True)
def test_custom_ext_hook_is_served_by_python(mode, monkeypatch):
    if mod is not None:
        def boom(*_a):
            raise AssertionError("unpack_from called under a custom hook")
        monkeypatch.setattr(mod, "unpack_from", boom)
    seen = []

    def hook(code, data):
        seen.append((code, data))
        return ("ext", code, len(data))

    buf = packb([EventTime(1, 2), ExtType(5, b"abc")])
    u = Unpacker(buf + buf[:-1], ext_hook=hook)
    assert next(u) == [("ext", 0, 8), ("ext", 5, 3)]
    assert next(u, _NO_MSG) is _NO_MSG
    assert u.native is False and u.tell() == len(buf)
    assert seen[:2] == [(0, struct.pack(">II", 1, 2)), (5, b"abc")]


@pytest.mark.parametrize("mode", MODES, indirect=True)
def test_unpack_and_unpackb_stay_on_the_python_path(mode, monkeypatch):
    if mod is not None:
        def boom(*_a):
            raise AssertionError("unpack() went through unpack_from")
        monkeypatch.setattr(mod, "unpack_from", boom)
    buf = CORPUS["forward"]
    assert msgpack.unpackb(buf)[0] == "app.tag"
    u = Unpacker(buf[:-1])
    with pytest.raises(msgpack.OutOfData):
        u.unpack()


# ------------------------------- the benchmark's wrappers (decode_share)


@pytest.mark.parametrize("mode", MODES, indirect=True)
def test_subclass_wrappers_of_feed_and_next_see_every_call(mode):
    """(d) ``benchmark/run.py::install_spans`` subclasses the Unpacker
    with wrappers of the base class's ``feed`` and ``__next__``: every
    attempt, failed or not, still goes through them, whoever serves
    it — ``input.decode_share`` reads these calls."""
    calls = {"feed": 0, "next": 0}

    def counted(fn, key):
        def call(*a, **kw):
            calls[key] += 1
            return fn(*a, **kw)
        return call

    class TimedUnpacker(Unpacker):
        feed = counted(Unpacker.feed, "feed")
        __next__ = counted(Unpacker.__next__, "next")

    stream = STREAMS["forward"]
    want = messages(stream)
    u, got, attempts = TimedUnpacker(), [], 0
    for i in range(0, len(stream), 7):
        u.feed(stream[i:i + 7])
        while True:
            attempts += 1
            msg = next(u, _NO_MSG)
            if msg is _NO_MSG:
                break
            got.append(msg)
    assert same(got, want)
    assert calls == {"feed": -(-len(stream) // 7), "next": attempts}
    assert u.native is (mode == "native")
    # the for-loop protocol goes through the wrapper too
    calls["next"] = 0
    assert same(list(TimedUnpacker(stream)), want)
    assert calls["next"] == len(want) + 1


# ------------------------------------------- _handle_conn over loopback


class SpanLog:
    """Stands in for ``core.spans.span``: names and metadata, no
    profiler."""

    def __init__(self):
        self.spans = []

    def __call__(self, name, **ids):
        log = self

        class One:
            def __enter__(self):
                self.meta = dict(ids)
                return self

            def __exit__(self, *_exc):
                log.spans.append((name, self.meta))
                return False

            def set_metadata(self, **more):
                self.meta.update(more)

        return One()


def wait_for(cond, timeout=30.0, interval=0.01):
    deadline = time.time() + timeout
    while time.time() < deadline:
        v = cond()
        if v:
            return v
        time.sleep(interval)
    raise TimeoutError("condition not met")


@pytest.mark.parametrize("mode", MODES, indirect=True)
def test_forward_frame_in_64k_pieces_over_loopback(mode, monkeypatch):
    """(e) A 4,096-entry frame written in 64 KiB pieces: one ack, one
    absorb, the engine gets the bytes ``_entries_to_events`` gives for
    the Python walk's objects, and every ``forward.unpack`` span says
    who served it."""
    from fluentbit_tpu.plugins import net_forward

    entries = [[EventTime(1700000000 + i, i),
                {"log": f"10.0.0.{i % 250} - - GET /p/{i} " + "x" * 60,
                 "i": i}]
               for i in range(4096)]
    frame = packb(["app", entries, {"chunk": "frame-e"}])
    assert len(frame) > 6 * 65536
    want_buf = b"".join(encode_event(rec, ts)
                        for ts, rec in next(reference(frame))[1])

    log = SpanLog()
    monkeypatch.setattr(net_forward, "span", log)
    ctx = flb.create(flush="50ms", grace="1")
    ctx.input("forward", listen="127.0.0.1", port="0")
    ctx.output("null", match="*")
    engine = ctx.engine
    appended = []
    real_append = engine.input_log_append

    def append(ins, tag, data, n_records=None):
        appended.append((tag, bytes(data), n_records))
        return real_append(ins, tag, data, n_records)

    monkeypatch.setattr(engine, "input_log_append", append)
    ctx.start()
    try:
        plugin = engine.inputs[0].plugin
        port = wait_for(lambda: plugin.bound_port)
        with socket.create_connection(("127.0.0.1", port)) as s:
            s.settimeout(60)
            for i in range(0, len(frame), 65536):
                s.sendall(frame[i:i + 65536])
                time.sleep(0.005)
            acks = Unpacker()
            acks.feed(s.recv(4096))
            assert list(acks) == [{"ack": "frame-e"}]
            s.settimeout(0.3)
            with pytest.raises(socket.timeout):
                s.recv(4096)              # one ack, and no more
        assert plugin.n_absorbed == 1
    finally:
        ctx.stop()
    assert appended == [("app", want_buf, 4096)]
    unpack = [m for n, m in log.spans if n == "forward.unpack"]
    assert len(unpack) >= 7               # six or more reads, one after
    assert [m["done"] for m in unpack].count(1) == 1
    assert {m["native"] for m in unpack} == {int(mode == "native")}
    assert sum(m["bytes"] for n, m in log.spans
               if n == "forward.read") == len(frame)
