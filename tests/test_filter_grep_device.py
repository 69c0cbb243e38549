"""Device-path filter_grep: bit-exact equivalence vs the CPU verdict path.

The north star contract (BASELINE.md): surviving records byte-identical to
the CPU chain. We run the same event list through GrepFilter with the
device path forced on and forced off and require identical surviving raw
bytes, across legacy/AND/OR modes, missing fields, and overflow rows.
"""

import random

import pytest

from fluentbit_tpu.codec.events import decode_events, encode_event
from fluentbit_tpu.core.plugin import registry

APACHE_HOSTISH = r"^(?<host>[^ ]*) [^ ]* (?<user>[^ ]*) \[(?<time>[^\]]*)\]"


def make_filter(props):
    ins = registry.create_filter("grep")
    for k, v in props:
        ins.set(k, v)
    ins.configure()
    ins.plugin.init(ins, None)
    return ins.plugin


def make_events(n, seed=0, long_every=None):
    rng = random.Random(seed)
    events = []
    for i in range(n):
        method = rng.choice(["GET", "POST", "PUT", "DELETE"])
        code = rng.choice(["200", "404", "500"])
        body = {"log": f"{method} /path/{i} HTTP/1.1 {code}", "n": i}
        if rng.random() < 0.1:
            body.pop("log")  # missing field rows
        if long_every and i % long_every == 0:
            body["log"] = "x" * 2000 + " GET /long 200"
        buf = encode_event(body, float(i))
        events.append(decode_events(buf)[0])
    return events


def run_both(props, events):
    f_dev = make_filter(props)
    if f_dev._program is None:
        pytest.skip("device program unavailable for these rules")
    f_cpu = make_filter(props + [("tpu.enable", "off")])
    assert f_cpu._program is None
    _, kept_dev = f_dev.filter(list(events), "t", None)
    _, kept_cpu = f_cpu.filter(list(events), "t", None)
    assert [e.raw for e in kept_dev] == [e.raw for e in kept_cpu]
    return kept_dev


@pytest.mark.parametrize("props", [
    [("regex", "log GET"), ("tpu_batch_records", "1")],
    [("exclude", "log 500$"), ("tpu_batch_records", "1")],
    [("regex", "log ^(GET|POST)"), ("exclude", "log 404"),
     ("tpu_batch_records", "1")],
    [("exclude", "log 404"), ("regex", "log ^(GET|POST)"),
     ("tpu_batch_records", "1")],
    [("regex", "log GET"), ("regex", "log 200"), ("logical_op", "AND"),
     ("tpu_batch_records", "1")],
    [("regex", "log GET"), ("regex", "log 500"), ("logical_op", "OR"),
     ("tpu_batch_records", "1")],
    [("exclude", "log GET"), ("exclude", "log 500"), ("logical_op", "OR"),
     ("tpu_batch_records", "1")],
    [("exclude", "log GET"), ("exclude", "log POST"), ("logical_op", "AND"),
     ("tpu_batch_records", "1")],
])
def test_device_equals_cpu(props):
    events = make_events(257, seed=hash(str(props)) & 0xFFFF)
    run_both(props, events)


def test_overflow_rows_resolve_on_cpu():
    events = make_events(200, seed=7, long_every=13)
    kept = run_both(
        [("regex", "log GET"), ("tpu_batch_records", "1"),
         ("tpu_max_record_len", "256")], events)
    # some long rows match "GET" and must survive via the CPU fallback
    assert any(len(e.body.get("log", "")) > 256 for e in kept)


def test_small_batches_use_cpu_path():
    f = make_filter([("regex", "log GET"), ("tpu_batch_records", "64")])
    events = make_events(8)
    _, kept = f.filter(list(events), "t", None)
    expected = [e for e in events if f.keep_record(e.body)]
    assert [e.raw for e in kept] == [e.raw for e in expected]


def test_program_built_only_when_capable():
    # backreference-free rules → program; lookahead rule → CPU only
    f = make_filter([("regex", "log GET")])
    assert f._program is not None
    f2 = make_filter([("regex", r"log (?=G)GET")])
    assert f2._program is None


def test_staged_multi_key_rules_raw_path():
    """Rules over TWO different field heads through the staged raw path:
    stage_field returns per-thread arena views, so the per-key staging
    loop must copy each key's batch out before staging the next key
    (regression: the second call overwrote the first key's bytes and
    every rule matched against the last key's field)."""
    from fluentbit_tpu import native

    if not native.available():
        pytest.skip("native unavailable")
    f = make_filter([
        ("regex", "log GET"), ("exclude", "stream stderr"),
        ("tpu_batch_records", "1"),
    ])
    if f._program is None or not f._program.try_ready():
        pytest.skip("device program unavailable")
    # force the staged (by_key) path: no fused/native tables
    f._native_filter = None
    f._native_tables = None
    rng = random.Random(5)
    buf = bytearray()
    bodies = []
    for i in range(300):
        body = {
            "log": f"{rng.choice(['GET', 'POST'])} /x/{i} 200",
            "stream": rng.choice(["stdout", "stderr"]),
        }
        if rng.random() < 0.1:
            body.pop("log")
        bodies.append(body)
        buf += encode_event(body, float(i))
    from fluentbit_tpu.core.chunk_batch import RawChunk

    got = f.process_batch(RawChunk(bytes(buf), "t", len(bodies)))
    assert got is not None
    n_keep, out = got
    kept = decode_events(bytes(out))
    expected = [b for b in bodies if f.keep_record(b)]
    assert n_keep == len(expected)
    assert [e.body for e in kept] == expected
    # sanity: the expectation itself must depend on BOTH fields
    assert any(b.get("stream") == "stderr" for b in bodies)
    assert 0 < len(expected) < len(bodies)


#: rule sets by ``logical_op`` (AND/OR take one kind of rule only)
_BY_OP = {
    "legacy": [("exclude", "log 404"), ("regex", "log ^(GET|POST)")],
    "AND": [("regex", "log GET"), ("regex", "log 200$"),
            ("logical_op", "AND")],
    "OR": [("exclude", "log ^DELETE"), ("exclude", "log 500$"),
           ("logical_op", "OR")],
}


@pytest.mark.parametrize("op", list(_BY_OP))
@pytest.mark.parametrize("engine", ["fused", "native", "device"])
def test_grep_process_batch_engines(engine, op):
    """Every engine the raw hook chooses from — the fused native walk,
    the native matcher, the staged device launch through the lane (on
    the CPU backend here) — ends in the per-record chain's bytes, over
    kept, dropped, missing-key and overflow rows."""
    from fluentbit_tpu import native
    from fluentbit_tpu.core.chunk_batch import RawChunk

    if not native.available():
        pytest.skip("native unavailable")
    props = _BY_OP[op] + [("tpu_batch_records", "1"),
                          ("tpu_max_record_len", "64")]
    f = make_filter(props)
    if f._program is None:
        pytest.skip("device program unavailable")
    if engine != "fused":
        f._native_filter = None
    if engine == "device":
        if not f._program.try_ready():
            pytest.skip("device program unavailable")
        f._native_tables = None
    events = make_events(300, seed=11, long_every=17)
    n_long = len(range(0, 300, 17))
    f_cpu = make_filter(props + [("tpu.enable", "off")])
    _, kept = f_cpu.filter(list(events), "t", None)
    assert 0 < len(kept) < len(events)
    # an overflow row is decided both ways across the rule sets
    assert any(len(e.body.get("log", "")) > 64 for e in kept) \
        == (op != "legacy")
    assert f.can_process_batch()
    got = f.process_batch(
        RawChunk(b"".join(e.raw for e in events), "t", None))
    # the triple only where the fused walk counted its input
    assert got[2:] == ((len(events),) if engine == "fused" else ())
    assert got[0] == len(kept)
    assert bytes(got[1]) == b"".join(e.raw for e in kept)
    tm = f.raw_timings
    assert tm["records"] == len(events)
    on_device = engine == "device"
    assert tm["device_records"] == (len(events) if on_device else 0)
    assert tm["overflow_rows"] == (n_long if on_device else 0)


def test_non_string_values_never_match():
    """String-only matching (src/flb_ra_key.c:418): ints don't match."""
    f = make_filter([("regex", r"n \d+")])
    events = make_events(4, seed=3)
    for ev in events:
        ev.body["n"] = 123  # int field
    _, kept = f.filter(list(events), "t", None)
    assert kept == []  # Regex-miss ⇒ EXCLUDE in legacy mode
