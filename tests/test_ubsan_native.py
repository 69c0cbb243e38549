"""UndefinedBehaviorSanitizer pass over the native data plane.

ASan/TSan cover memory safety and races; this lane isolates UB —
signed overflow in offset math, misaligned loads in the byte-pair
staging, shift overflows in the msgpack width packing, invalid bool
loads — with ``-fsanitize=undefined`` alone and
``-fno-sanitize-recover`` so the FIRST report aborts the driver (an
ASan+UBSan combined build, as in test_asan_native.py, keeps UBSan in
recovering mode and a report there only prints). Drives the scanner
trio + fused filter over byte soup AND the whole-chunk JSON transcoder
(``parser_json_batch``), which the ASan driver predates, and
filter_parser's build from spans (``parser_spans_build``), which both
drivers run.

Shares the ``sanitizer`` marker (tests/conftest.py) with the other
lanes: ``-m sanitizer`` selects, ``-m 'not sanitizer'`` sheds.
"""

import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.sanitizer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: filter_parser's record build from spans (parser_spans_build) over whole,
#: hostile and torn chunks: spans, lengths and offsets anywhere, the plane's
#: bytes past ASCII. It has to build, or raise; never read out of bounds.
#: (No percent sign: the drivers it joins are %-formatted.)
SPANS_BUILD_DRIVER = r"""
# --- filter_parser's record build from spans: whole, hostile, torn ---
import numpy as np
def spans_case(n, width=48):
    vals = [(f"h{i}|{i * 7 - 30}|{1 + i // 40:02d}/Oct/2000:13:55:"
             f"{i // 9:02d} -0700").encode() for i in range(n)]
    recs = [encode_event({"log": v.decode()}, EventTime(1700000000 + i, 5))
            for i, v in enumerate(vals)]
    offs = np.zeros(n + 1, dtype=np.int64)
    offs[1:] = np.cumsum([len(r) for r in recs])
    plane = np.zeros((n, width), dtype=np.uint8)
    lens = np.zeros(n, dtype=np.int32)
    spans = np.full((n, 3, 2), -1, dtype=np.int32)
    for i, v in enumerate(vals):
        plane[i, :len(v)] = np.frombuffer(v, dtype=np.uint8)
        lens[i] = len(v)
        a, b = v.index(b"|"), v.rindex(b"|")
        spans[i] = [(0, a), (a + 1, b), (b + 1, len(v))]
    return [b"".join(recs), offs, [plane], lens, np.ones(n, dtype=bool),
            spans, (b"a", b"n", b"time"), b"\x00\x01\x00", 2, False,
            b"dL/bL/YL:HL:ML:SWz", 0, True, True, True, b"\x81\xa3log", -1]
out, left, native_rows, host_rows = mod.parser_spans_build(*spans_case(64))
assert (native_rows, host_rows, left) == (64, 0, []), (native_rows, left)
assert len(mod.decode_events(out)) == 64
assert mod.parser_spans_build(*spans_case(0)) == (b"", [], 0, 0)
for _ in range(400):
    args = spans_case(rng.randrange(1, 40))
    n = len(args[3])
    kind = rng.randrange(6)
    if kind == 0:    # spans anywhere: past the row, backwards, negative
        args[5] = np.array([rng.randrange(-3, 60) for _ in range(n * 6)],
                           dtype=np.int32).reshape(n, 3, 2)
    elif kind == 1:  # lengths anywhere: negative, past the row
        args[3] = np.array([rng.randrange(-3, 52) for _ in range(n)],
                           dtype=np.int32)
    elif kind == 2:  # a torn chunk, a heap copy that ends at the tear
        args[0] = bytes(args[0][: rng.randrange(0, len(args[0]) + 1)])
    elif kind == 3:  # offsets anywhere, before the buffer and past it
        args[1] = np.array(sorted(rng.randrange(-5, len(args[0]) + 5)
                                  for _ in range(n + 1)), dtype=np.int64)
    elif kind == 4:  # the plane's bytes anywhere, past ASCII too
        args[2] = [np.frombuffer(bytes(rng.randrange(256)
                                       for _ in range(n * 48)),
                                 dtype=np.uint8).reshape(n, 48)]
    else:            # the chunk's bytes mutated under its offsets
        mut = bytearray(args[0])
        for _ in range(rng.randrange(1, 10)):
            mut[rng.randrange(len(mut))] = rng.randrange(256)
        args[0] = bytes(mut)
    args[4] = np.array([rng.random() < 0.8 for _ in range(n)])
    try:
        mod.parser_spans_build(*args)
    except ValueError:
        pass  # handed back is fine; a fault is not
"""

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

assert native.available(), "ubsan .so failed to load"
tables = native.GrepFilterTables(
    [(b"log", compile_dfa("GET|time?out"), False)], "legacy")
rng = random.Random(23)
for n in (1, 3, 16, 257, 4097):
    buf = bytearray()
    for i in range(n):
        buf += encode_event(
            {"log": ("GET /x " if i %% 2 else "zzz ") + "a" * (i %% 97)},
            float(i))
    raw = bytes(buf)
    assert native.grep_filter(raw, tables) is not None
    native.stage_field(raw, b"log", 96, n_hint=n)
    native.count_records(raw)
    native.scan_offsets(raw)
    for _ in range(15):
        mut = bytearray(raw)
        for _ in range(rng.randrange(1, 8)):
            mut[rng.randrange(len(mut))] = rng.randrange(256)
        cut = bytes(mut[: rng.randrange(1, len(mut) + 1)])
        native.grep_filter(cut, tables)
        native.stage_field(cut, b"log", 64)
        native.count_records(cut)
        native.scan_offsets(cut)

# --- codec extension: decode/pack + the JSON transcoder ---
import fluentbit_tpu.codec._native_codec as nc
nc._SO = %(codec_so)r
nc._mod, nc._tried = None, False
mod = nc.load()
assert mod is not None, "ubsan codec extension failed to load"
from fluentbit_tpu.codec.msgpack import EventTime

docs = [
    '{"a": 1, "wide": 5000000000, "neg": -2147483649}',
    '{"f": 1e308, "tiny": -1e-308, "nan": NaN, "inf": -Infinity}',
    '{"esc": "\\u00e9\\ud834\\udd1e\\n", "nest": {"x": [1, 2.5]}}',
    '{"dup": 1, "dup": {"last": true}}',
    'not json', '[]', '{}',
]
good = b"".join(
    encode_event({"log": docs[i %% len(docs)], "n": i},
                 EventTime(1700000000 + i, 7) if i %% 2 else float(i))
    for i in range(256))
out, n, parsed = mod.parser_json_batch(good, b"log")
assert n == 256 and parsed > 0, (n, parsed)
assert mod.decode_events(out)
for _ in range(200):
    mut = bytearray(good)
    for _ in range(rng.randrange(1, 10)):
        mut[rng.randrange(len(mut))] = rng.randrange(256)
    cut = bytes(mut[: rng.randrange(1, len(mut) + 1)])
    for fn in (lambda b: mod.parser_json_batch(b, b"log"),
               mod.decode_events):
        try:
            fn(cut)
        except ValueError:
            pass  # malformed/declined is fine; UB is not
for _ in range(60):
    body = {"s": "y" * rng.randrange(300), "l": [1, {"k": (2, 3)}],
            "i": rng.randrange(-2**63, 2**64 - 1)}
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
    for _ in range(100):
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
print("UBSAN_DRIVER_OK")
"""


@pytest.mark.skipif(sys.platform != "linux", reason="linux toolchain")
def test_native_data_plane_under_ubsan(tmp_path):
    libubsan = subprocess.run(
        ["g++", "-print-file-name=libubsan.so"],
        capture_output=True, text=True).stdout.strip()
    if not libubsan or not os.path.exists(libubsan):
        pytest.skip("libubsan unavailable")
    so = str(tmp_path / "fbtpu_ubsan.so")
    build = subprocess.run(
        ["g++", "-O1", "-g", "-fPIC", "-shared", "-std=c++17",
         "-pthread", "-fsanitize=undefined",
         "-fno-sanitize-recover=undefined",
         os.path.join(REPO, "native", "fbtpu_native.cpp"), "-o", so],
        capture_output=True, text=True, timeout=300)
    if build.returncode != 0:
        pytest.skip(f"ubsan build failed: {build.stderr[-400:]}")
    import sysconfig

    include = sysconfig.get_paths().get("include")
    codec_so = str(tmp_path / "fbtpu_codec_ubsan.so")
    cbuild = subprocess.run(
        ["gcc", "-O1", "-g", "-fPIC", "-shared",
         "-fsanitize=undefined", "-fno-sanitize-recover=undefined",
         "-I", include or ".",
         os.path.join(REPO, "native", "fbtpu_codec.c"),
         "-o", codec_so],
        capture_output=True, text=True, timeout=300)
    if cbuild.returncode != 0:
        pytest.skip(f"ubsan codec build failed: {cbuild.stderr[-400:]}")
    env = dict(os.environ)
    env.update({
        "LD_PRELOAD": libubsan,
        "UBSAN_OPTIONS": "print_stacktrace=1:halt_on_error=1",
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO,
        "FBTPU_THREADS_NO_HW_CAP": "1",
        "FBTPU_DFA_THREADS": "2",
    })
    proc = subprocess.run(
        [sys.executable, "-c",
         DRIVER % {"repo": REPO, "so": so, "codec_so": codec_so}],
        capture_output=True, text=True, timeout=420, env=env)
    assert proc.returncode == 0, (
        f"ubsan report (rc={proc.returncode}):\n"
        f"{proc.stdout[-1000:]}\n{proc.stderr[-3000:]}")
    assert "UBSAN_DRIVER_OK" in proc.stdout
    assert "runtime error:" not in proc.stderr
