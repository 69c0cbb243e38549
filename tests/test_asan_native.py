"""AddressSanitizer pass over the C++ data plane.

Round 4 shipped a heap overflow in the fused grep filter that plain
tests missed (dead-lane scratch reads); ASan found it in minutes. This
test makes that check repeatable: build fbtpu_native with
-fsanitize=address,undefined and drive the hot entry points (fused
filter over odd block sizes + mutated msgpack, threaded staging, the
scanner trio over byte soup) in a subprocess that fails on any
sanitizer report."""

import os
import subprocess
import sys

import pytest

from test_ubsan_native import SPANS_BUILD_DRIVER

pytestmark = pytest.mark.sanitizer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DRIVER = r"""
import os, random, sys
sys.path.insert(0, %(repo)r)
import fluentbit_tpu.native as native
native._SO = %(so)r
native._tried = False
native._lib = None
os.environ.pop("FBTPU_NO_NATIVE", None)
from fluentbit_tpu.codec.events import encode_event
from fluentbit_tpu.regex.dfa import compile_dfa

assert native.available(), "asan .so failed to load"
apache2 = (
    r'^(?P<host>[^ ]*) [^ ]* [^ ]* \[[^\]]*\] "[^"]*" [^ ]* [^ ]*$'
    .replace("?P<host>", "?<host>")
)
tables = native.GrepFilterTables(
    [(b"log", compile_dfa("GET"), False),
     (b"log", compile_dfa(apache2), True)], "legacy")
rng = random.Random(17)
for n in (1, 2, 15, 16, 17, 100, 4097):
    buf = bytearray()
    for i in range(n):
        roll = rng.random()
        if roll < 0.2:
            body = {}
        elif roll < 0.4:
            body = {"log": i}
        else:
            body = {"log": "GET /x " + "a" * rng.randrange(0, 300)}
        buf += encode_event(body, float(i))
    raw = bytes(buf)
    assert native.grep_filter(raw, tables) is not None
    native.stage_field(raw, b"log", 128, n_hint=n)
    # mutated copies must never fault (may decode or be rejected)
    for _ in range(20):
        mut = bytearray(raw)
        for _ in range(rng.randrange(1, 8)):
            mut[rng.randrange(len(mut))] = rng.randrange(256)
        cut = bytes(mut[: rng.randrange(1, len(mut) + 1)])
        native.grep_filter(cut, tables)
        native.stage_field(cut, b"log", 64)
        native.count_records(cut)
        native.scan_offsets(cut)
native.grep_filter(b"", tables)

# --- codec extension (C parsing of untrusted bytes) ---
import fluentbit_tpu.codec._native_codec as nc
nc._SO = %(codec_so)r
nc._mod, nc._tried = None, False
mod = nc.load()
assert mod is not None, "asan codec extension failed to load"
from fluentbit_tpu.codec.msgpack import EventTime
good = b"".join(
    encode_event({"log": "x" * rng.randrange(0, 200), "n": i,
                  "d": {"a": [1, "b"]}},
                 EventTime(1700000000 + i, 5) if i %% 2 else float(i))
    for i in range(200))
evs = mod.decode_events(good)
assert len(evs) == 200
for _ in range(300):
    mut = bytearray(good)
    for _ in range(rng.randrange(1, 10)):
        mut[rng.randrange(len(mut))] = rng.randrange(256)
    cut = bytes(mut[: rng.randrange(1, len(mut) + 1)])
    try:
        mod.decode_events(cut)
    except ValueError:
        pass  # malformed is fine; faulting is not
try:
    mod.decode_events(b"\x91" * 100000 + b"\x90")  # depth bound
except ValueError:
    pass
for _ in range(100):  # pack side round-trips
    body = {"s": "y" * rng.randrange(300), "l": [1, {"k": (2, 3)}],
            "b": bytes(range(rng.randrange(50)))}
    mod.pack_event(EventTime(1, 2), {}, body)
# --- in_forward's chunk cut: whole, torn and hostile messages ---
from fluentbit_tpu.codec.msgpack import packb
entries = [[EventTime(1700000000 + i, i) if i %% 3 else 1700000000 + i,
            {"log": "x" * rng.randrange(0, 200), "n": i - 30,
             "d": {"a": [1.5, b"b", None]}}] for i in range(60)]
blob = b"".join(packb(e) for e in entries)
want = b"".join(encode_event(rec, ts) for ts, rec in entries)
chunks = [packb(["app", entries, {"chunk": "c", "size": 60}]),
          packb(["app", blob, {"chunk": "c", "size": 7}]),
          packb(["app", entries])]
others = [packb(["app", blob, {"compressed": "gzip"}]),
          packb(["app", 1700000000, {"k": "v"}, {"chunk": "m"}]),
          packb(["PING", "host", b"salt", "digest", "", ""]),
          packb(["app", [[1, {"k": 1}, None]], {"chunk": "three"}])]
for fr in chunks:
    tag, events, n, option, end = mod.forward_cut(b"\x00" + fr, 1)
    assert (tag, events, n, end) == ("app", want, 60, 1 + len(fr))
assert mod.forward_cut(others[0], 0)[1:3] == (blob, -1)
assert mod.forward_cut_entries(blob) == (want, 60)
for fr in chunks + others:
    for cut in range(0, len(fr), 1 if len(fr) < 400 else 7):
        torn = bytes(fr[:cut])      # a heap copy that ends at the tear
        try:
            assert mod.forward_cut(torn, 0) is None
        except mod.FallbackError:
            pass  # told from its first bytes: not a chunk
    for _ in range(150):
        mut = bytearray(fr)
        for _ in range(rng.randrange(1, 10)):
            mut[rng.randrange(len(mut))] = rng.randrange(256)
        hostile = bytes(mut[: rng.randrange(1, len(mut) + 1)])
        for call in (lambda b: mod.forward_cut(b, 0),
                     lambda b: mod.forward_cut(b, len(b) // 2),
                     mod.forward_cut_entries, lambda b: mod.unpack_from(b, 0)):
            try:
                call(hostile)
            except ValueError:
                pass  # handed back or malformed is fine; a fault is not
try:
    mod.forward_cut(b"\x92\xa1t" + b"\x91" * 100000 + b"\x90", 0)
except mod.FallbackError:
    pass  # depth bound
""" + SPANS_BUILD_DRIVER + """
print("ASAN_DRIVER_OK")
"""


@pytest.mark.skipif(sys.platform != "linux", reason="linux toolchain")
def test_native_data_plane_under_asan(tmp_path):
    libasan = subprocess.run(
        ["g++", "-print-file-name=libasan.so"],
        capture_output=True, text=True).stdout.strip()
    if not libasan or not os.path.exists(libasan):
        pytest.skip("libasan unavailable")
    so = str(tmp_path / "fbtpu_asan.so")
    build = subprocess.run(
        ["g++", "-O1", "-g", "-fPIC", "-shared", "-std=c++17",
         "-pthread", "-fsanitize=address,undefined",
         os.path.join(REPO, "native", "fbtpu_native.cpp"), "-o", so],
        capture_output=True, text=True, timeout=300)
    if build.returncode != 0:
        pytest.skip(f"asan build failed: {build.stderr[-400:]}")
    import sysconfig

    include = sysconfig.get_paths().get("include")
    codec_so = str(tmp_path / "fbtpu_codec_asan.so")
    cbuild = subprocess.run(
        ["gcc", "-O1", "-g", "-fPIC", "-shared",
         "-fsanitize=address,undefined", "-I", include or ".",
         os.path.join(REPO, "native", "fbtpu_codec.c"),
         "-o", codec_so],
        capture_output=True, text=True, timeout=300)
    if cbuild.returncode != 0:
        pytest.skip(f"asan codec build failed: {cbuild.stderr[-400:]}")
    env = dict(os.environ)
    env.update({
        "LD_PRELOAD": libasan,
        "ASAN_OPTIONS": "detect_leaks=0:abort_on_error=1:exitcode=99",
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO,
        # every bytes object from the system allocator, so that a torn
        # message of a few hundred bytes has redzones too (pymalloc's
        # arenas have none: a read past a small buffer would go unseen)
        "PYTHONMALLOC": "malloc",
        # exercise the pool dispatch under ASan too
        "FBTPU_THREADS_NO_HW_CAP": "1",
        "FBTPU_DFA_THREADS": "4",
    })
    proc = subprocess.run(
        [sys.executable, "-c",
         DRIVER % {"repo": REPO, "so": so, "codec_so": codec_so}],
        capture_output=True, text=True, timeout=420, env=env)
    assert proc.returncode == 0, (
        f"sanitizer report (rc={proc.returncode}):\n"
        f"{proc.stdout[-1000:]}\n{proc.stderr[-3000:]}")
    assert "ASAN_DRIVER_OK" in proc.stdout
