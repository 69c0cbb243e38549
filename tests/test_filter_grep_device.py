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


# --------------------------------------- the staged launch in two halves

_HALVES_RULES = [("exclude", "log 404"), ("regex", "log ^(GET|POST)"),
                 ("exclude", "log /path/7")]
_HALVES_SPANS = r"^(?<method>[A-Z]+) (?<path>[^ ]*) HTTP/1\.1 (?<code>\d+)$"


def _halves_data(n=200):
    """Kept, dropped and missing-key rows, one row that alone stages at
    L=512 and overflow rows past ``tpu_max_record_len``."""
    events = make_events(n, seed=23, long_every=41)
    events[5].body["log"] = "GET /" + "p" * 400 + " HTTP/1.1 200"
    return b"".join(encode_event(e.body, float(i))
                    for i, e in enumerate(events)), n


def _halves_setup(kind, path):
    """→ ``(rules, program, lane, how)``: ``staged_match``'s arguments
    for one kind of verdict on one path, with a lane of their own."""
    from fluentbit_tpu.ops import fault
    from fluentbit_tpu.ops.grep import span_program_for
    from fluentbit_tpu.plugins.filter_parser import _KeyRule
    from fluentbit_tpu.regex import FlbRegex

    lane = fault.DeviceLane(f"t-halves-{kind}-{path}")
    mesh = lane.current_mesh() if path == "mesh" else None
    if path == "mesh" and mesh is None:
        pytest.skip("need a multi-device mesh")
    how = {"max_len": 512, "min_records": 1, "mesh": mesh}
    if kind == "spans":
        program = span_program_for(_HALVES_SPANS, 512)
        rules = [_KeyRule("log", FlbRegex(_HALVES_SPANS))]
        how["spans"] = True
    else:
        f = make_filter(_HALVES_RULES + [("tpu_batch_records", "1")])
        if f._program is None or not f._program.try_ready():
            pytest.skip("device program unavailable")
        rules, program = f.rules, f._program
        how["first_match"] = kind == "first_match"
    return rules, program, lane, how


def _same_verdict(kind, a, b) -> None:
    import numpy as np

    (va, offs_a, n_a), (vb, offs_b, n_b) = a, b
    assert n_a == n_b and np.array_equal(offs_a, offs_b)
    if kind == "spans":
        assert np.array_equal(va.ok, vb.ok) and va.ok.any()
        assert np.array_equal(va.spans, vb.spans)
        assert np.array_equal(va.lengths, vb.lengths)
        # the staged rows up to each value's length: past it a row holds
        # whatever ``np.empty`` held (the kernel reads it as padding)
        (p,), (q,) = va.planes, vb.planes               # one segment
        live = np.arange(p.shape[1]) < np.maximum(va.lengths, 0)[:, None]
        assert p.shape == q.shape and np.array_equal(p[live], q[live])
    else:
        assert va.dtype == vb.dtype and np.array_equal(va, vb)
        assert va.any()


@pytest.mark.parametrize("kind,path", [
    ("mask", "one_chip"), ("first_match", "one_chip"),
    ("spans", "one_chip"), ("mask", "mesh"), ("first_match", "mesh")])
def test_begin_then_finish_equals_the_one_call(kind, path):
    """``staged_match(begin=True)`` then ``staged_match(begun=...)`` is
    the one call bit for bit — verdict, offsets, count and every
    ``raw_timings`` count — for the three clients' verdicts, on one
    chip's path and the mesh's, with overflow rows and an L=512 row;
    the begin half touches no counter but the mesh's layout counts
    (a launch is laid out where it is dispatched, used or not), and the
    two flights of the lane are both finished."""
    from fluentbit_tpu.core.spans import ShardedTimings
    from fluentbit_tpu.plugins.filter_grep import (_TIMING_KEYS, Begun,
                                                   staged_match)

    rules, program, lane, how = _halves_setup(kind, path)
    data, n = _halves_data()
    tm_one, tm_two = ShardedTimings(_TIMING_KEYS), ShardedTimings(_TIMING_KEYS)
    one = staged_match(rules, program, lane, tm_one, data, n, **how)
    begun = staged_match(rules, program, lane, tm_two, data, n, begin=True,
                         **how)
    assert isinstance(begun, Begun)
    assert lane.stats()["launches"] == 2 and lane.stats()["ok"] == 1
    layout = {"mesh_launches": 1,
              "mesh_devices": how["mesh"].devices.size} \
        if path == "mesh" else {}
    assert all(tm_two[k] == layout.get(k, 0) for k in _TIMING_KEYS)
    two = staged_match(rules, program, lane, tm_two, data, n, begun=begun,
                       **how)
    _same_verdict(kind, one, two)
    for key in ("device_records", "overflow_rows", "h2d_bytes",
                "d2h_bytes", "scan_elements", *layout):
        assert tm_one[key] == tm_two[key] > 0, key
    assert tm_two["unsharded_launches"] == 0
    assert tm_two["extract_s"] > 0 and tm_two["kernel_s"] > 0
    st = lane.stats()
    assert st["launches"] == st["ok"] == 2 and st["begun_in_flight"] == 0
    begun.drop()  # ended already: nothing more is finished
    assert lane.stats()["ok"] == 2


def test_a_chunk_of_two_segments_is_not_begun_ahead(monkeypatch):
    from fluentbit_tpu.plugins.filter_grep import staged_match

    monkeypatch.setenv("FBTPU_SEGMENT_RECORDS", "128")
    rules, program, lane, how = _halves_setup("mask", "one_chip")
    data, n = _halves_data()
    assert staged_match(rules, program, lane, None, data, n, begin=True,
                        **how) is None
    assert lane.stats()["launches"] == 0
    # under the configured minimum: declined before any staging, too
    assert staged_match(rules, program, lane, None, data, n, begin=True,
                        **{**how, "min_records": 1000}) is None


def test_a_handle_made_for_other_bytes_or_rules_is_dropped():
    """The finishing call checks what the handle was made for — the
    very bytes, rules and program — and a handle that does not answer
    it is finished and thrown away, the call starting over."""
    from fluentbit_tpu.core.spans import ShardedTimings
    from fluentbit_tpu.plugins.filter_grep import _TIMING_KEYS, staged_match

    rules, program, lane, how = _halves_setup("mask", "one_chip")
    data, n = _halves_data()
    tm = ShardedTimings(_TIMING_KEYS)
    one = staged_match(rules, program, lane, tm, data, n, **how)
    for other in ({"data": bytes(bytearray(data))},
                  {"rules": list(rules)}):
        begun = staged_match(rules, program, lane, None, data, n,
                             begin=True, **how)
        before = lane.stats()["launches"]
        got = staged_match(other.get("rules", rules), program, lane, tm,
                           other.get("data", data), n, begun=begun, **how)
        _same_verdict("mask", one, got)
        st = lane.stats()
        assert st["launches"] == before + 1 == st["ok"]  # staged anew
    assert tm["device_records"] == 3 * n
