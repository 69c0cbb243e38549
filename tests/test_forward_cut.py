"""The C cut of a Forward chunk (``fbtpu_codec.forward_cut``,
native/fbtpu_codec.c) against the object path it stands in for.

``net_forward.Unpacker`` asks the extension for a chunk-shaped message's
V2 events straight from the wire bytes; the object path — ``unpack_from``
→ ``ForwardInput._decode`` → ``_entries_to_events`` — stays, serves
whatever the cut hands back, and is the oracle here: for every message
the two give the same tag, the same event bytes, the same count, option,
ack reference and ledger key. A message the cut serves never enters
``_entries_to_events``; a message it hands back arrives as the very
objects the plain Unpacker yields.
"""

import gzip
import importlib.util
import json
import os
import struct
import sys
import threading
import time

import pytest

import fluentbit_tpu as flb
import fluentbit_tpu.codec._native_codec as nc
from fluentbit_tpu.codec import msgpack
from fluentbit_tpu.codec.msgpack import EventTime, ExtType, packb
from fluentbit_tpu.plugins import net_forward
from fluentbit_tpu.plugins.net_forward import CutChunk

mod = nc.load()
pytestmark = pytest.mark.skipif(mod is None,
                                reason="codec extension unavailable")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
T0 = 1700000000


def bench_module(folder: str, stem: str):
    """A file of the benchmark by path (it imports ``wire`` from its own
    directory)."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        "cut_" + stem.replace("-", "_"),
        os.path.join(BENCH, folder, stem + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def plugin():
    """A live ``in_forward``: ``_decode`` is its loop's stage."""
    ctx = flb.create(flush="50ms", grace="1")
    ctx.input("forward", listen="127.0.0.1", port="0")
    ctx.output("null", match="*")
    ctx.start()
    try:
        yield ctx.engine.inputs[0].plugin
    finally:
        ctx.stop()


# ------------------------------------------------------------- framings

def forward(tag, entries, option=None) -> bytes:
    return packb([tag, entries] + ([] if option is None else [option]))


def packed(tag, entries, option=None) -> bytes:
    blob = b"".join(packb(e) for e in entries)
    return packb([tag, blob] + ([] if option is None else [option]))


def gzipped(tag, entries, option=None) -> bytes:
    blob = gzip.compress(b"".join(packb(e) for e in entries))
    return packb([tag, blob, {**(option or {}), "compressed": "gzip"}])


FRAMINGS = [forward, packed, gzipped]


def raw_forward(tag: bytes, entries: list, option: bytes = b"") -> bytes:
    """Forward mode from entries that are msgpack bytes already."""
    n = len(entries)
    head = bytes((0x90 | n,)) if n < 16 else b"\xdc" + struct.pack(">H", n)
    return (b"\x93" if option else b"\x92") + tag + head \
        + b"".join(entries) + option


def raw_packed(tag: bytes, entries: list, option: bytes = b"") -> bytes:
    blob = b"".join(entries)
    return (b"\x93" if option else b"\x92") + tag \
        + b"\xc6" + struct.pack(">I", len(blob)) + blob + option


# ------------------------------------------------------------ both paths

def object_path(plugin, wire: bytes):
    """The oracle: the plain Unpacker's objects (``unpack_from``, or the
    Python walk where that hands back) through ``_decode``."""
    u = msgpack.Unpacker(wire)
    msg = next(u)
    return msg, plugin._decode(msg), u.tell()


def served_path(plugin, wire: bytes):
    u = net_forward.Unpacker(wire)
    msg = next(u)
    assert u.native or not isinstance(msg, CutChunk)
    return msg, plugin._decode(msg), u.tell()


def assert_cut_equals_oracle(plugin, wire: bytes, n: int) -> None:
    cut0 = plugin.n_cut
    msg, got, end = served_path(plugin, wire)
    assert isinstance(msg, CutChunk) and plugin.n_cut == cut0 + 1
    _m, want, want_end = object_path(plugin, wire)
    assert plugin.n_cut == cut0 + 1  # the oracle re-encoded
    assert got == want and end == want_end == len(wire)
    assert got[2] == n and type(got[1]) is bytes


def assert_falls_back_to_oracle(plugin, wire: bytes, compare=True):
    cut0 = plugin.n_cut
    msg, got, end = served_path(plugin, wire)
    want_msg, want, want_end = object_path(plugin, wire)
    assert not isinstance(msg, CutChunk) and plugin.n_cut == cut0
    assert repr(msg) == repr(want_msg) and end == want_end
    if compare:
        assert got == want
    return got


# --------------------------------------------------------------- shapes

INTS = [0, 1, 127, 128, 255, 256, 65535, 65536, 2**32 - 1, 2**32,
        2**63, 2**64 - 1, -1, -32, -33, -128, -129, -32768, -32769,
        -2**31, -2**31 - 1, -2**63]
FLOATS = [0.0, -0.0, 1.5, 1e308, -1e-308, float("inf"), float("nan")]
STRS = ["", "a" * 31, "b" * 32, "c" * 255, "d" * 256, "e" * 65535,
        "f" * 65536, "héllo ✓ 日本", "\x00\x7f"]


def nested(depth: int):
    value = "leaf"
    for i in range(depth):
        value = {"k": value} if i % 2 else [value, i]
    return value


def records_of(config: str, n: int = 48) -> list:
    """The first records of a benchmark configuration's corpus."""
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        corpus = json.load(f)["corpus"]
    # the tails come every 20,000th line and more: bring them near
    params = {k: (7 if k.endswith("_every") else v)
              for k, v in corpus["params"].items()}
    records, _labels = bench_module("corpora", corpus["maker"]).make(
        n, 11, params)
    return records


def shapes() -> dict:
    out = {
        "int_times": [[T0 + i, {"log": f"line {i}"}] for i in range(5)],
        "small_and_negative_int_times":
            [[t, {"i": i}] for i, t in enumerate(INTS)],
        "float64_times": [[T0 + i / 8, {"i": i}] for i in range(5)]
            + [[f, {"f": True}] for f in FLOATS],
        "event_times": [[EventTime(T0 + i, i * 999), {"i": i}]
                        for i in range(5)]
            + [[EventTime(2**32 - 1, 2**32 - 1), {}]],
        "every_int_width": [[T0, {"v": v, "l": [v, -1]}] for v in INTS],
        "every_float": [[T0, {"v": v}] for v in FLOATS],
        "every_str_width": [[T0, {"s": s, s[:9] + "k": 1}] for s in STRS],
        "scalars": [[T0, {"n": None, "t": True, "f": False, "e": {},
                          "l": [], "et": EventTime(1, 2)}]],
        "nested_maps_and_arrays":
            [[T0, {"m": {"a": {"b": [1, [2, {"c": None}]]}},
                   "deep": nested(d)}] for d in (1, 7, 40, 60)],
        "bin_values": [[T0, {"b": bytes(range(n % 256)) * (n // 256 + 1)}]
                       for n in (0, 1, 255, 256, 65535, 65536)],
        "long_arrays": [[T0, {"a15": list(range(15)),
                              "a16": list(range(16)),
                              "a65536": [0] * 65536}]],
        "empty_frame": [],
        "empty_records": [[T0, {}], [T0 + 1, {}]],
        "keys_15": [[T0, {f"k{j}": j for j in range(15)}]],
        "keys_16": [[T0, {f"k{j}": j for j in range(16)}]] * 3,
    }
    for n in (1, 15, 16, 17):     # around the array16 header
        out[f"entries_{n}"] = [[EventTime(T0, i), {"i": i, "pad": "x" * i}]
                               for i in range(n)]
    for config in ("grep-apache2", "sketch-firehose", "rewrite-syslog",
                   "grep-tenants"):
        out[f"corpus_{config}"] = [[EventTime(T0, i), rec] for i, rec
                                   in enumerate(records_of(config))]
    return out


SHAPES = shapes()


@pytest.mark.parametrize("framing", FRAMINGS, ids=lambda f: f.__name__)
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_cut_equals_the_object_path(plugin, shape, framing):
    entries = SHAPES[shape]
    for option in ({"chunk": f"id-{shape}", "size": len(entries)}, None):
        assert_cut_equals_oracle(plugin, framing("app.tag", entries, option),
                                 len(entries))


@pytest.mark.parametrize("mode", ["forward", "packed"])
@pytest.mark.parametrize("config", ["grep-apache2", "sketch-firehose",
                                    "rewrite-syslog", "parser-apache2",
                                    "grep-tenants"])
def test_the_benchmarks_own_frames_take_the_cut(plugin, config, mode):
    """Every cell's wire bytes as its generator writes them
    (``benchmark/wire.py``): served by the cut, and equal to what the
    harness says the engine must get."""
    wire = bench_module("", "wire")
    bodies = [wire.pack_str_map(r) for r in records_of(config, 96)]
    tag = wire.pack_str("bench.tag")
    frame = wire.FRAMERS[mode](tag, T0 * 10**9 + 5, bodies, "chunk-7")
    assert_cut_equals_oracle(plugin, frame, 96)
    _msg, got, _end = served_path(plugin, frame)
    assert got[1] == wire.output_events(T0 * 10**9 + 5, bodies)
    assert got[4] == "chunk-7"


# ------------------------------------------------ what the cut hands back

TAG = b"\xa3app"
OPT = b"\x81\xa5chunk\xa2c1"
TIME = b"\xd7\x00" + struct.pack(">II", T0, 7)
GOOD = b"\x92" + TIME + b"\x81\xa1k\xa1v"


def entry(record: bytes, time: bytes = TIME, head: bytes = b"\x92",
          more: bytes = b"") -> bytes:
    return head + time + record + more


HANDED_BACK = {
    # non-canonical msgpack: decode → pack would write other bytes
    "str8_where_fixstr_fits": entry(b"\x81\xd9\x01k\xa1v"),
    "str16_where_str8_fits": entry(b"\x81\xa1k\xda\x00\x01v"),
    "uint16_where_fixint_fits": entry(b"\x81\xa1k\xcd\x00\x05"),
    "int8_where_fixint_fits": entry(b"\x81\xa1k\xd0\xff"),
    "bin16_where_bin8_fits": entry(b"\x81\xa1k\xc5\x00\x01x"),
    "map16_where_fixmap_fits": entry(b"\xde\x00\x01\xa1k\xa1v"),
    "array16_where_fixarray_fits": entry(b"\x81\xa1k\xdc\x00\x01\x01"),
    "duplicate_key": entry(b"\x82\xa1k\x01\xa1k\x02"),
    "invalid_utf8_value": entry(b"\x81\xa1k\xa2\xff\xfe"),
    "invalid_utf8_key": entry(b"\x81\xa2\xc3\x28\x01"),
    "float32": entry(b"\x81\xa1k\xca\x3f\x80\x00\x00"),
    "foreign_ext": entry(b"\x81\xa1k\xd4\x05\x00"),
    "event_time_as_ext8": entry(b"\x81\xa1k\x01",
                                time=b"\xc7\x08\x00" + TIME[2:]),
    "int_key": entry(b"\x81\x01\xa1v"),
    "nil_key": entry(b"\x81\xc0\xa1v"),
    "keys_17": entry(b"\xde\x00\x11" + b"".join(
        b"\xa3k%02d\x01" % j for j in range(17))),
    "nested_keys_17": entry(b"\x81\xa1m\xde\x00\x11" + b"".join(
        b"\xa3k%02d\x01" % j for j in range(17))),
    "nesting_past_the_canonical_walk": entry(
        b"\x81\xa1k" + b"\x91" * 70 + b"\x01"),
    # entries of another shape (the object path skips or trims them)
    "entry_of_three": entry(b"\x81\xa1k\xa1v", head=b"\x93", more=b"\xc0"),
    "entry_of_one": b"\x91" + TIME,
    "entry_array16_of_two": entry(b"\x81\xa1k\xa1v", head=b"\xdc\x00\x02"),
    "entry_not_an_array": b"\xa5entry",
    "record_not_a_map": entry(b"\xa3abc"),
    "record_an_array": entry(b"\x92\x01\x02"),
    "str_time": entry(b"\x80", time=b"\xa3now"),
    "bool_time": entry(b"\x80", time=b"\xc3"),
    "map_time": entry(b"\x80", time=b"\x80"),
}


@pytest.mark.parametrize("framing", [raw_forward, raw_packed],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("case", sorted(HANDED_BACK))
def test_what_the_cut_hands_back_equals_the_oracle(plugin, case, framing):
    """One odd entry among good ones hands the whole message back, in
    first, middle and last place alike."""
    odd = HANDED_BACK[case]
    for entries in ([odd], [odd, GOOD], [GOOD, odd, GOOD],
                    [GOOD] * 16 + [odd]):
        got = assert_falls_back_to_oracle(
            plugin, framing(TAG, entries, OPT))
        assert got is not None and got[4] == "c1"


def test_nil_time_is_the_clocks_and_so_the_object_paths(plugin):
    """``encode_event`` puts the time of day where an entry has nil:
    nothing to copy."""
    for framing in (raw_forward, raw_packed):
        wire = framing(TAG, [GOOD, entry(b"\x80", time=b"\xc0")], OPT)
        got = assert_falls_back_to_oracle(plugin, wire, compare=False)
        assert got[2] == 2 and got[1].startswith(b"\x92\x92" + TIME)


MESSAGES = {
    "message_mode": packb(["app", T0, {"k": "v"}, {"chunk": "m1"}]),
    "message_mode_no_option": packb(["app", EventTime(T0, 1), {"k": "v"}]),
    "ping": packb(["PING", "host", b"salt", "digest", "", ""]),
    "helo": packb(["HELO", {"nonce": b"n", "auth": b"", "keepalive": True}]),
    "ack": packb({"ack": "c1"}),
    "non_str_tag": packb([7, [[T0, {"k": "v"}]], {"chunk": "c"}]),
    "bin_tag": packb([b"app", [[T0, {"k": "v"}]], {"chunk": "c"}]),
    "option_nil": packb(["app", [[T0, {"k": "v"}]], None]),
    "option_a_str": packb(["app", [[T0, {"k": "v"}]], "opt"]),
    "option_with_ext": packb(["app", [[T0, {"k": "v"}]],
                              {"chunk": "c", "x": ExtType(5, b"z")}]),
    "four_elements": packb(["app", [[T0, {"k": "v"}]], {"chunk": "c"}, 1]),
    "outer_array16": b"\xdc\x00\x02" + TAG + b"\x91" + GOOD,
    "tag_alone": packb(["app"]),
    "blob_with_a_torn_tail": raw_packed(TAG, [GOOD, GOOD[:-2]], OPT),
    "blob_with_bytes_after": raw_packed(TAG, [GOOD, b"\xc1"], OPT),
    "blob_of_scalars": raw_packed(TAG, [b"\x01\x02\x03"], OPT),
    "a_scalar": packb(5),
}


@pytest.mark.parametrize("case", sorted(MESSAGES))
def test_messages_that_are_no_canonical_chunk_go_the_old_way(plugin, case):
    wire = MESSAGES[case]
    cut0 = plugin.n_cut
    u, ref = net_forward.Unpacker(wire), msgpack.Unpacker(wire)
    try:
        want = next(ref)
    except ValueError as e:   # 0xC1: the Python walk's to raise
        with pytest.raises(type(e)):
            next(u)
        return
    msg = next(u)
    assert not isinstance(msg, CutChunk)
    assert repr(msg) == repr(want) and u.tell() == ref.tell() == len(wire)
    if isinstance(msg, list) and len(msg) >= 2:
        try:
            decoded = plugin._decode(want)
        except ValueError as e:   # 0xC1 inside the blob
            with pytest.raises(type(e)):
                plugin._decode(msg)
        else:
            assert plugin._decode(msg) == decoded
    assert plugin.n_cut == cut0


def test_a_compressed_blob_that_is_no_gzip_or_no_entries(plugin):
    """``compressed`` of another kind is not inflated, by either path; a
    gzip of odd entries is inflated, handed back and re-encoded."""
    entries = [[T0 + i, {"i": i}] for i in range(4)]
    blob = b"".join(packb(e) for e in entries)
    plain = packb(["app", blob, {"compressed": "text", "chunk": "z"}])
    assert_cut_equals_oracle(plugin, plain, 4)
    odd = gzip.compress(blob + packb([T0, {"i": 4}, None]))
    wire = packb(["app", odd, {"compressed": "gzip", "chunk": "g"}])
    cut0 = plugin.n_cut
    msg, got, _end = served_path(plugin, wire)
    assert isinstance(msg, CutChunk) and msg.n == -1 and msg.events == odd
    assert got == object_path(plugin, wire)[1] and got[2] == 5
    assert plugin.n_cut == cut0


# ------------------------------------------- counts, bounds and contract

def test_a_size_option_that_lies_does_not_change_n(plugin):
    entries = [[T0 + i, {"i": i}] for i in range(5)]
    for framing in FRAMINGS:
        for size in (0, 4, 6, 2**40, "five", None):
            wire = framing("app", entries, {"size": size, "chunk": "s"})
            assert_cut_equals_oracle(plugin, wire, 5)
            assert served_path(plugin, wire)[1][3]["size"] == size


def test_tag_prefix_and_chunk_keys_are_the_object_paths():
    ctx = flb.create(flush="50ms", grace="1")
    ctx.input("forward", listen="127.0.0.1", port="0", tag_prefix="edge")
    ctx.output("null", match="*")
    ctx.start()
    try:
        plugin = ctx.engine.inputs[0].plugin
        for chunk in ("id", b"\x00\xffraw", 77):
            wire = forward("app", [[T0, {"k": "v"}]], {"chunk": chunk})
            assert_cut_equals_oracle(plugin, wire, 1)
            assert served_path(plugin, wire)[1][0] == "edge.app"
        wire = raw_forward(b"\xa2\xff\xfe", [GOOD])  # tag: errors=replace
        assert_cut_equals_oracle(plugin, wire, 1)
        assert served_path(plugin, wire)[1][0] == "edge.��"
    finally:
        ctx.stop()


@pytest.mark.parametrize("framing", [forward, packed],
                         ids=lambda f: f.__name__)
def test_every_truncation_point_is_not_whole_yet(framing):
    """None at every cut of one frame — also where the bytes that would
    complete it lie right behind the view's end: nothing is read past
    the buffer (the sanitizer runs hold the same on hostile bytes)."""
    entries = [[EventTime(T0, i), {"log": "x" * (i * 9), "n": [i, {"m": i}]}]
               for i in range(18)]
    wire = framing("app", entries, {"chunk": "t", "size": 18})
    whole = memoryview(b"\x01" + wire + wire)
    for cut in range(len(wire)):
        assert mod.forward_cut(wire[:cut], 0) is None, cut
        assert mod.forward_cut(whole[:1 + cut], 1) is None, cut
    tag, events, n, option, end = mod.forward_cut(whole, 1)
    assert (tag, n, option, end) == ("app", 18, {"chunk": "t", "size": 18},
                                     1 + len(wire))
    assert mod.forward_cut(whole, end) == (tag, events, n, option,
                                           1 + 2 * len(wire))
    assert mod.forward_cut(whole, len(whole)) is None


@pytest.mark.parametrize("framing", [forward, packed, gzipped],
                         ids=lambda f: f.__name__)
def test_a_frame_fed_in_pieces_comes_out_once_and_whole(plugin, framing):
    entries = [[T0 + i, {"log": "y" * 40, "i": i}] for i in range(300)]
    wire = framing("app", entries, {"chunk": "p"}) \
        + packb(["app", T0, {"k": "v"}])          # then Message mode
    want = object_path(plugin, wire)[1]
    for step in (1, 7, 4096):
        u, out = net_forward.Unpacker(), []
        for i in range(0, len(wire), step):
            u.feed(wire[i:i + step])
            out.extend(u)
        assert isinstance(out[0], CutChunk) and out[1] == ["app", T0,
                                                           {"k": "v"}]
        assert len(out) == 2 and plugin._decode(out[0]) == want


def test_entry_point_contracts():
    wire = forward("t", [[1, {}]])
    assert mod.forward_cut(wire, 0) == ("t", b"\x92\x92\x01\x80\x80", 1,
                                        None, len(wire))
    assert mod.forward_cut(memoryview(wire), 0)[2] == 1
    assert mod.forward_cut(bytearray(wire), 0)[2] == 1
    assert mod.forward_cut(b"", 0) is None
    for pos in (-1, len(wire) + 1):
        with pytest.raises(ValueError):
            mod.forward_cut(wire, pos)
    with pytest.raises(TypeError):
        mod.forward_cut("str", 0)
    assert mod.forward_cut_entries(b"") == (b"", 0)
    assert mod.forward_cut_entries(memoryview(GOOD * 3)) == (
        (b"\x92" + GOOD[:11] + b"\x80" + GOOD[11:]) * 3, 3)
    for torn in (GOOD[:-1], GOOD + b"\x92", b"\x93" + GOOD[1:] + b"\xc0",
                 b"\xc1"):
        with pytest.raises(mod.FallbackError):
            mod.forward_cut_entries(torn)
    with pytest.raises(TypeError):
        mod.forward_cut_entries("str")


def test_hostile_headers_allocate_nothing_and_hand_back():
    """array32 / bin32 / str32 headers of 2**32 - 1 with nothing behind
    them: not whole (None), never a reservation by the header's word;
    nesting at the bound is the Python walk's."""
    huge = b"\xff\xff\xff\xff"
    assert mod.forward_cut(b"\x93" + TAG + b"\xdd" + huge, 0) is None
    assert mod.forward_cut(b"\x93" + TAG + b"\xc6" + huge, 0) is None
    assert mod.forward_cut(b"\x93\xdb" + huge, 0) is None
    with pytest.raises(mod.FallbackError):
        mod.forward_cut(b"\x92" + TAG + b"\x91" * 600 + b"\x01", 0)
    with pytest.raises(mod.FallbackError):   # an array of 3 entries, 2 there
        mod.forward_cut(b"\x92" + TAG + b"\x93" + GOOD + GOOD + b"\x01", 0)


def test_the_gil_is_released_while_a_large_frame_is_cut():
    """Another thread runs while ``forward_cut`` walks and copies 25 MB,
    and does not while ``unpack_from`` builds the same frame's objects.
    The switch interval is set to seconds, so that the only way the
    ticking thread gets the interpreter inside the call is the call
    letting go of it."""
    n = 200_000
    one = b"\x92" + TIME + packb({"log": "z" * 100, "i": 12345})
    wire = b"\x92" + TAG + b"\xdd" + struct.pack(">I", n) + one * n
    ticks, stop = [0], threading.Event()

    def tick():
        while not stop.is_set():
            ticks[0] += 1
            time.sleep(0)  # hands the GIL to whoever waits for it

    during = {}
    saved = sys.getswitchinterval()
    th = threading.Thread(target=tick, daemon=True)
    try:
        sys.setswitchinterval(5.0)
        th.start()
        while ticks[0] < 100:
            time.sleep(0.001)
        for name, call in (("cut", mod.forward_cut),
                           ("objects", mod.unpack_from)):
            before = ticks[0]
            got = call(wire, 0)
            during[name] = ticks[0] - before
            assert got is not None
            del got
    finally:
        stop.set()
        sys.setswitchinterval(saved)
        th.join(20)
    assert not th.is_alive()
    assert during["objects"] <= 2 < 50 <= during["cut"], during
