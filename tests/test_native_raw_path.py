"""Native msgpack scanner + raw ingest path.

Differential contract: the native staging/compaction path must be
byte-identical to the Python decode path across record shapes (missing
fields, non-string values, overflow rows, nested maps, legacy events,
EventTime timestamps).
"""

import json
import random

import pytest

from fluentbit_tpu import native
from fluentbit_tpu.codec.events import count_records, decode_events, encode_event
from fluentbit_tpu.codec.msgpack import EventTime, packb
from fluentbit_tpu.core.engine import Engine

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native library unavailable"
)


def corpus(seed=0, n=400):
    rng = random.Random(seed)
    buf = bytearray()
    for i in range(n):
        body = {"log": f"{rng.choice(['GET', 'POST', 'PUT'])} /r/{i} "
                       f"{rng.choice(['200', '404', '500'])}"}
        roll = rng.random()
        if roll < 0.08:
            body.pop("log")                      # missing field
        elif roll < 0.14:
            body["log"] = rng.randrange(1000)    # non-string value
        elif roll < 0.2:
            body["log"] = "y" * 900 + " GET tail 200"  # overflow row
        if rng.random() < 0.3:
            body["nested"] = {"a": [1, 2, {"b": "c"}]}
        if rng.random() < 0.2:
            body["v"] = rng.random()
        ts = EventTime(1700000000 + i, 500) if i % 2 else float(i)
        buf += encode_event(body, ts)
    # legacy form records too
    buf += packb([1234, {"log": "GET legacy 200"}])
    return bytes(buf)


def test_native_count_matches_python():
    buf = corpus()
    assert native.count_records(buf) == count_records(buf)


def test_native_offsets_match_raw_spans():
    buf = corpus(seed=1)
    offs = native.scan_offsets(buf)
    evs = decode_events(buf)
    assert len(offs) == len(evs) + 1
    pos = 0
    for i, ev in enumerate(evs):
        assert offs[i] == pos
        pos += len(ev.raw)
    assert offs[-1] == len(buf)


def test_native_stage_field_matches_python_extraction():
    buf = corpus(seed=2)
    batch, lengths, offs, n = native.stage_field(buf, b"log", 256)
    evs = decode_events(buf)
    assert n == len(evs)
    for i, ev in enumerate(evs):
        v = ev.body.get("log")
        if isinstance(v, str):
            enc = v.encode("utf-8")
            if len(enc) > 256:
                assert lengths[i] == -2
            else:
                assert lengths[i] == len(enc)
                assert bytes(batch[i][: lengths[i]]) == enc
        else:
            assert lengths[i] == -1


def test_malformed_buffer_rejected():
    assert native.count_records(b"\xd9") is None  # truncated str8
    assert native.count_records(b"\x91") is None  # fixarray missing elem


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_raw_ingest_path_byte_identical(seed):
    """engine raw path (native staging + kernel + raw compaction) ==
    decode path, including overflow/missing/non-string rows."""
    buf = corpus(seed=seed)

    def build(tpu_on):
        e = Engine()
        f = e.filter("grep")
        f.set("regex", "log GET")
        f.set("exclude", "log 500$")
        f.set("tpu_batch_records", "1")
        if not tpu_on:
            f.set("tpu.enable", "off")
        ins = e.input("dummy")
        for x in e.inputs + e.filters:
            x.configure()
            x.plugin.init(x, e)
        return e, ins

    e1, i1 = build(True)
    e2, i2 = build(False)
    n1 = e1.input_log_append(i1, "t", buf)
    n2 = e2.input_log_append(i2, "t", buf)
    out1 = b"".join(bytes(c.buf) for c in i1.pool.drain())
    out2 = b"".join(bytes(c.buf) for c in i2.pool.drain())
    assert n1 == n2
    assert out1 == out2


def test_raw_path_declines_for_nested_accessor():
    """Rules with nested RA paths must use the decode path."""
    e = Engine()
    f = e.filter("grep")
    f.set("regex", "$k['a'] x")
    ins = e.input("dummy")
    for x in e.inputs + e.filters:
        x.configure()
        x.plugin.init(x, e)
    assert not e.filters[0].plugin.can_process_batch()
    buf = encode_event({"k": {"a": "x"}}, 1.0)
    assert e.input_log_append(ins, "t", buf) == 1


def test_unfiltered_fast_append_counts():
    e = Engine()
    ins = e.input("dummy")
    ins.configure()
    ins.plugin.init(ins, e)
    buf = corpus(seed=6, n=50)
    n = e.input_log_append(ins, "t", buf)
    assert n == count_records(buf)
    chunks = ins.pool.drain()
    assert b"".join(bytes(c.buf) for c in chunks) == buf


def test_native_scanner_fuzz_robustness():
    """Random byte soup must never crash or hang the native scanner;
    valid buffers must count identically to the Python codec."""
    import random

    from fluentbit_tpu import native
    from fluentbit_tpu.codec.events import count_records, encode_event

    if not native.available():
        pytest.skip("native unavailable")
    rng = random.Random(99)
    for _ in range(300):
        junk = bytes(rng.randrange(256) for _ in range(rng.randrange(64)))
        native.count_records(junk)        # may be None; must not crash
        native.scan_offsets(junk)
        native.stage_field(junk, b"log", 32)
    for _ in range(50):
        buf = b"".join(
            encode_event({"log": "x" * rng.randrange(20),
                          "n": rng.randrange(1000)}, float(i))
            for i in range(rng.randrange(1, 30))
        )
        assert native.count_records(buf) == count_records(buf)


@pytest.mark.parametrize("n", [1, 2, 15, 16, 17, 31, 33])
def test_fused_filter_odd_block_sizes(n):
    """Regression for the uninitialized dead-lane read: any chunk whose
    record count isn't a multiple of 16, or with missing/non-string
    fields, leaves prepass lanes DEAD — those columns must still hold
    valid symbols for the lockstep walk (fbtpu_native.cpp
    dfa_prepass_block)."""
    from fluentbit_tpu.regex import FlbRegex
    from fluentbit_tpu.regex.dfa import compile_dfa

    tables = native.GrepFilterTables(
        [(b"log", compile_dfa("GET"), False),
         (b"log", compile_dfa("500$"), True)], "legacy")
    rx = FlbRegex("GET")
    rng = random.Random(n)
    buf = bytearray()
    bodies = []
    for i in range(n):
        roll = rng.random()
        if roll < 0.25:
            body = {}                       # missing field
        elif roll < 0.5:
            body = {"log": i}               # non-string
        else:
            body = {"log": f"GET /x/{i} 200"}
        bodies.append(body)
        buf += encode_event(body, float(i))
    got = native.grep_filter(bytes(buf), tables)
    assert got is not None
    n_rec, n_keep, out = got
    assert n_rec == n
    expect = sum(
        1 for b in bodies
        if isinstance(b.get("log"), str) and rx.match(b["log"]))
    assert n_keep == expect
    kept = decode_events(bytes(out))
    assert len(kept) == expect
    for ev in kept:
        assert isinstance(ev.body.get("log"), str)
        assert rx.match(ev.body["log"])


def test_fused_filter_empty_buffer():
    """Zero-record chunks must return (0, 0, input) — the slice-count
    arithmetic once divided by zero here (SIGFPE)."""
    from fluentbit_tpu.regex.dfa import compile_dfa

    tables = native.GrepFilterTables(
        [(b"log", compile_dfa("GET"), False)], "legacy")
    got = native.grep_filter(b"", tables)
    assert got is not None
    assert got[0] == 0 and got[1] == 0


def test_accel_engine_differential(monkeypatch):
    """The opt-in escape-byte hybrid matcher (FBTPU_ACCEL=1) must be
    verdict-identical to the default lockstep engine across corpora
    incl. long self-loop runs (its winning case) and odd blocks."""
    from fluentbit_tpu.regex import FlbRegex
    from fluentbit_tpu.regex.dfa import compile_dfa

    monkeypatch.setenv("FBTPU_ACCEL", "1")
    apache2 = (
        r'^(?<host>[^ ]*) [^ ]* (?<user>[^ ]*) \[(?<time>[^\]]*)\] '
        r'"(?<method>\S+)(?: +(?<path>[^ ]*) +\S*)?" (?<code>[^ ]*) '
        r'(?<size>[^ ]*)(?: "(?<referer>[^\"]*)" "(?<agent>.*)")?$'
    )
    patterns = [apache2, "ERROR|WARN", "GET"]
    rng = random.Random(77)
    bodies = []
    buf = bytearray()
    for i in range(333):
        roll = rng.random()
        if roll < 0.2:
            line = ('10.0.0.9 - u [10/Oct/2000:13:55:36 -0700] '
                    f'"GET /l{i} HTTP/1.1" 200 77 "r" "a"')
        elif roll < 0.4:
            line = "x" * rng.randrange(500, 4000) + " ERROR tail"
        elif roll < 0.5:
            line = ""
        else:
            line = f"plain WARN line {i} " + "y" * rng.randrange(50)
        body = {"log": line} if rng.random() > 0.1 else {"n": i}
        bodies.append(body)
        buf += encode_event(body, float(i))
    for pattern in patterns:
        dfa = compile_dfa(pattern)
        tables = native.GrepFilterTables([(b"log", dfa, False)], "legacy")
        assert tables.aoffs[0] >= 0, f"accel not engaged for {pattern}"
        rx = FlbRegex(pattern)
        got = native.grep_filter(bytes(buf), tables)
        assert got is not None
        expect = sum(1 for b in bodies
                     if isinstance(b.get("log"), str)
                     and rx.match(b["log"]))
        assert got[1] == expect, pattern


def test_fused_filter_fuzz_mutated_msgpack():
    """fbtpu_grep_filter / fbtpu_stage_field must survive arbitrary
    byte-flipped msgpack without crashing; valid buffers must keep the
    same records as the Python regex engine."""
    from fluentbit_tpu.regex import FlbRegex
    from fluentbit_tpu.regex.dfa import compile_dfa

    tables = native.GrepFilterTables(
        [(b"log", compile_dfa("ERROR|WARN"), False)], "legacy")
    rx = FlbRegex("ERROR|WARN")
    rng = random.Random(1234)
    for trial in range(120):
        n = rng.randrange(1, 24)
        buf = bytearray()
        bodies = []
        for i in range(n):
            body = {"log": rng.choice(
                ["ERROR boom", "WARN hm", "info ok", "", "x" * 300])}
            if rng.random() < 0.2:
                body["log"] = rng.randrange(10**6)
            bodies.append(body)
            buf += encode_event(body, float(i))
        raw = bytes(buf)
        got = native.grep_filter(raw, tables)
        assert got is not None
        expect = sum(1 for b in bodies
                     if isinstance(b["log"], str) and rx.match(b["log"]))
        assert got[1] == expect
        # mutate: flip bytes / truncate — must not crash, may return None
        mut = bytearray(raw)
        for _ in range(rng.randrange(1, 6)):
            mut[rng.randrange(len(mut))] = rng.randrange(256)
        mut = bytes(mut[: rng.randrange(1, len(mut) + 1)])
        native.grep_filter(mut, tables)
        native.stage_field(mut, b"log", 64)


def test_native_grep_match_differential():
    """One-pass C++ DFA matcher vs the Python regex engine over mixed
    corpora: apache2, alternation, anchors, bounded reps; missing /
    empty / non-string values; odd+even lengths (exercises every k
    super-step variant)."""
    import random

    from fluentbit_tpu import native
    from fluentbit_tpu.codec.events import encode_event
    from fluentbit_tpu.regex import FlbRegex
    from fluentbit_tpu.regex.dfa import compile_dfa

    if not native.available():
        pytest.skip("native unavailable")
    apache2 = (
        r'^(?<host>[^ ]*) [^ ]* (?<user>[^ ]*) \[(?<time>[^\]]*)\] '
        r'"(?<method>\S+)(?: +(?<path>[^ ]*) +\S*)?" (?<code>[^ ]*) '
        r'(?<size>[^ ]*)(?: "(?<referer>[^\"]*)" "(?<agent>.*)")?$'
    )
    patterns = [("log", apache2), ("log", "ERROR|WARN"),
                ("msg", "^kernel:"), ("log", "a{2,5}b?$")]
    tables = native.GrepTables(
        [(k.encode(), compile_dfa(p)) for k, p in patterns]
    )
    regexes = [(k, FlbRegex(p)) for k, p in patterns]
    rng = random.Random(11)
    buf = bytearray()
    records = []
    for i in range(3000):
        kind = rng.random()
        if kind < 0.3:
            line = (f'10.0.0.{rng.randrange(256)} - frank '
                    f'[10/Oct/2000:13:55:36 -0700] "GET /p{i} HTTP/1.1" '
                    f'200 {i} "r" "a"')
            body = {"log": line[: rng.randrange(0, 120)]}
        elif kind < 0.5:
            body = {"log": "a" * rng.randrange(8) + "b" * rng.randrange(3),
                    "msg": f"kernel: oops {i}"}
        elif kind < 0.7:
            body = {"msg": rng.choice(["kernel: x", "user: y"]), "n": i}
        elif kind < 0.85:
            body = {"log": ""}
        else:
            body = {"other": "zz", "log": 123}
        buf += encode_event(body, float(i))
        records.append(body)
    mask, offsets, n = native.grep_match(bytes(buf), tables)
    assert n == len(records)
    assert offsets[-1] == len(buf)
    for r, (k, rx) in enumerate(regexes):
        for i, body in enumerate(records):
            v = body.get(k)
            exp = rx.match(v) if isinstance(v, str) else False
            assert bool(mask[r, i]) == bool(exp), (r, i, body)


def test_pool_dispatch_paths_exercised(monkeypatch):
    """The worker-pool fan-out (staging MT + fused-filter phase 2) is
    normally clamped to host cores and would first run IN PRODUCTION on
    a multicore box; FBTPU_THREADS_NO_HW_CAP lifts the clamp so this
    box exercises the dispatch/slice machinery and verifies results are
    identical to the serial path."""
    from fluentbit_tpu.regex import FlbRegex
    from fluentbit_tpu.regex.dfa import compile_dfa

    monkeypatch.setenv("FBTPU_THREADS_NO_HW_CAP", "1")
    monkeypatch.setenv("FBTPU_DFA_THREADS", "4")
    # staging reads its thread count in PYTHON (_stage_threads, cached
    # per process) — set + uncache it so the MT entry point really
    # dispatches on this box instead of the nthreads<2 serial fallback
    monkeypatch.setenv("FBTPU_STAGE_THREADS", "4")
    monkeypatch.setattr(native, "_stage_threads_cached", None)
    # the DFA thread count IS read inside the C call per invocation;
    # build a >=4096-record chunk so phase 2 engages the pool
    rng = random.Random(42)
    buf = bytearray()
    bodies = []
    for i in range(5000):
        roll = rng.random()
        if roll < 0.1:
            body = {}
        elif roll < 0.2:
            body = {"log": i}
        else:
            body = {"log": f"{rng.choice(['GET', 'POST'])} /p{i} "
                           f"{rng.choice(['200', '500'])}"}
        bodies.append(body)
        buf += encode_event(body, float(i))
    raw = bytes(buf)
    tables = native.GrepFilterTables(
        [(b"log", compile_dfa("GET"), False),
         (b"log", compile_dfa("500$"), True)], "legacy")
    rx = FlbRegex("GET")
    got = native.grep_filter(raw, tables)
    assert got is not None
    expect = sum(1 for b in bodies
                 if isinstance(b.get("log"), str) and rx.match(b["log"]))
    assert got[0] == 5000 and got[1] == expect
    # staging MT path: identical to the Python extraction
    batch, lengths, offs, n = native.stage_field(raw, b"log", 128,
                                                 n_hint=5000)
    assert n == 5000
    evs = decode_events(raw)
    for i in (0, 1, 2499, 4998, 4999):
        v = evs[i].body.get("log")
        if isinstance(v, str):
            assert bytes(batch[i][: lengths[i]]) == v.encode()
        else:
            assert lengths[i] == -1


def test_stage_field_into_caller_buffer_parity():
    """The mesh plane's direct-into-matrix stager: staging one
    rule-row slice of a [R, Bp, L] segment matrix lands bit-identical
    bytes/lengths to the arena-based stage_field (incl. missing
    fields, non-string values, overflow -2 rows)."""
    import numpy as np

    buf = corpus(seed=7, n=600)
    ref = native.stage_field(buf, b"log", 128)
    assert ref is not None
    rb, rl, _, n = ref
    rb, rl = rb.copy(), rl.copy()  # arena views: next call overwrites
    R, Bp = 3, 608  # mesh-aligned pad (608 % 8 == 0)
    batch = np.empty((R, Bp, 128), dtype=np.uint8)
    lengths = np.full((R, Bp), -1, dtype=np.int32)
    got = native.stage_field_into(buf, b"log", batch[1], lengths[1],
                                  n_hint=n)
    assert got == n
    assert np.array_equal(lengths[1, :n], rl[:n])
    for i in range(n):
        if lengths[1, i] > 0:
            assert np.array_equal(batch[1, i, :lengths[1, i]],
                                  rb[i, :rl[i]])
    assert (lengths[1, n:] == -1).all()  # pad rows untouched


def test_stage_field_into_pooled_parity(monkeypatch):
    """Oversubscribed pool fan-out (FBTPU_STAGE_THREADS>1 behind
    FBTPU_THREADS_NO_HW_CAP on this box) produces bytes identical to
    the serial walk — the multi-core lane's correctness half; the
    throughput half is the bench's staging_mt stage on real cores."""
    import numpy as np

    monkeypatch.setenv("FBTPU_THREADS_NO_HW_CAP", "1")
    buf = corpus(seed=9, n=2000)  # >=1024: the pooled path engages
    b1 = np.empty((2048, 128), dtype=np.uint8)
    l1 = np.full((2048,), -1, dtype=np.int32)
    n1 = native.stage_field_into(buf, b"log", b1, l1, threads=1)
    b4 = np.empty((2048, 128), dtype=np.uint8)
    l4 = np.full((2048,), -1, dtype=np.int32)
    n4 = native.stage_field_into(buf, b"log", b4, l4, threads=4)
    assert n1 == n4 and n1 is not None
    assert np.array_equal(l1, l4)
    for i in range(n1):
        if l1[i] > 0:
            assert np.array_equal(b1[i, :l1[i]], b4[i, :l1[i]])


def test_stage_field_into_rejects_bad_buffers():
    import numpy as np

    buf = corpus(seed=3, n=100)
    # too small
    b = np.empty((10, 64), dtype=np.uint8)
    ln = np.full((10,), -1, dtype=np.int32)
    assert native.stage_field_into(buf, b"log", b, ln) is None
    # wrong dtype
    b2 = np.empty((128, 64), dtype=np.int32)
    l2 = np.full((128,), -1, dtype=np.int32)
    assert native.stage_field_into(buf, b"log", b2, l2) is None
    # non-contiguous slice (column stride)
    b3 = np.empty((128, 128), dtype=np.uint8)[:, ::2]
    l3 = np.full((128,), -1, dtype=np.int32)
    assert native.stage_field_into(buf, b"log", b3, l3) is None
    # strided lengths view: the base pointer would corrupt the
    # skipped elements — must reject, not write
    b4 = np.empty((128, 64), dtype=np.uint8)
    l4 = np.full((256,), -1, dtype=np.int32)[::2]
    assert native.stage_field_into(buf, b"log", b4, l4) is None
    # undersized / mistyped offsets_out
    l5 = np.full((128,), -1, dtype=np.int32)
    o_small = np.empty((10,), dtype=np.int64)
    assert native.stage_field_into(buf, b"log", b4, l5,
                                   offsets_out=o_small) is None
    o_f32 = np.empty((256,), dtype=np.float32)
    assert native.stage_field_into(buf, b"log", b4, l5,
                                   offsets_out=o_f32) is None
    # a correctly-sized offsets_out comes back as the boundary table
    o_ok = np.empty((256,), dtype=np.int64)
    n = native.stage_field_into(buf, b"log", b4, l5, offsets_out=o_ok)
    assert n == native.count_records(buf)
    ref = native.scan_offsets(buf)
    assert np.array_equal(o_ok[: n + 1], ref)


def test_stage_threads_introspection(monkeypatch):
    """stage_threads_effective reports the post-cap slice count the
    pool will really use (the truth the bench RESULT records)."""
    eff = native.stage_threads_effective(4)
    if eff is None:
        pytest.skip("older .so without the probe")
    import os

    hw = os.cpu_count() or 1
    assert 1 <= eff <= min(max(hw, 1), 16)
    assert native.stage_threads_effective(1) == 1
    assert native.stage_threads() >= 1
