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


# ------------------- a frame's few long rows as a group of their own

_SPLIT_RULES = [("exclude", "log 404$"), ("regex", "log ^(GET|POST)"),
                ("exclude", "log /path/7")]
_TWO_KEY_RULES = _SPLIT_RULES + [("exclude", "stream stderr$")]

#: ``rows`` records a frame with ``long`` of them 300-500 B (the verdict
#: of each hangs on its last bytes), ``overflow`` past
#: ``tpu_max_record_len`` among the long ones, ``mids`` of 70-120 B;
#: ``split``: how many of its segments go out in two groups, and at
#: which widths
_SPLIT_CASES = {
    "no_long_row": dict(rows=4096, long=0, split=0, widths=(64,)),
    "one_long_row": dict(rows=4096, long=1, split=1, widths=(64, 512)),
    "a_full_group": dict(rows=4096, long=256, split=1, widths=(64, 512)),
    "one_row_too_many": dict(rows=4096, long=257, split=0, widths=(512,)),
    "a_frame_of_1024_rows": dict(rows=1024, long=3, split=0,
                                 widths=(512,)),
    "overflow_rows_among_them": dict(rows=4096, long=9, overflow=4,
                                     split=1, widths=(64, 512)),
    "the_rest_at_the_width_it_needs": dict(rows=4096, long=5, mids=300,
                                           split=1, widths=(128, 512)),
    "long_in_one_key_only": dict(rows=4096, long=6, overflow=2,
                                 two_keys=True, split=1,
                                 widths=(64, 512)),
    "two_segments": dict(rows=4096 + 600, long=12, split=2,
                         widths=(64, 512)),
}


def _split_frame(rows, long=0, overflow=0, mids=0, two_keys=False, **_):
    """→ ``(data, bodies)``: the long, overflow and mid rows spread over
    the frame (and over both segments of a frame of two)."""
    rng = random.Random(rows + long)
    bodies = []
    for i in range(rows):
        method = rng.choice(["GET", "POST", "PUT", "DELETE"])
        code = rng.choice(["200", "404", "500"])
        body = {"log": f"{method} /path/{i % 97} HTTP/1.1 {code}"}
        if two_keys:
            body["stream"] = rng.choice(["stdout", "stderr"])
        if rng.random() < 0.05:
            body.pop("log")  # a row without a value
        bodies.append(body)
    special = rng.sample(range(rows), long + overflow + mids)
    if rows > 4096:  # some in the second segment too
        special[0], special[1] = 4100, rows - 1
    for j, i in enumerate(special):
        code = rng.choice(["200", "404"])
        if j < long:
            fill = "p" * rng.randrange(280, 470)
            key = "stream" if two_keys and j % 2 else "log"
        elif j < long + overflow:
            fill, key = "p" * rng.randrange(600, 900), "log"
        else:
            fill, key = "p" * rng.randrange(50, 90), "log"
        bodies[i][key] = f"GET /{fill} {code}" if key == "log" \
            else f"{fill} std{'err' if code == '404' else 'out'}"
        if two_keys and j == 2:
            # long in one key, past the limit in the other
            bodies[i]["stream"] = "s" * 700 + " stderr"
    return b"".join(encode_event(b, float(i))
                    for i, b in enumerate(bodies)), bodies


def _split_setup(kind, two_keys=False):
    from fluentbit_tpu.ops import fault

    f = make_filter((_TWO_KEY_RULES if two_keys else _SPLIT_RULES)
                    + [("tpu_batch_records", "1")])
    if f._program is None or not f._program.try_ready():
        pytest.skip("device program unavailable")
    how = {"max_len": 512, "min_records": 1,
           "first_match": kind == "first_match"}
    return f.rules, f._program, fault.DeviceLane(f"t-split-{kind}"), how


def _host_chain(kind, rules, bodies):
    """The per-record host chain's verdict, a record at a time."""
    import numpy as np

    from fluentbit_tpu.plugins.filter_grep import first_of_mask

    mask = np.array([[rule.match(b) for b in bodies] for rule in rules])
    return mask if kind == "mask" else first_of_mask(mask)


def _both_groups(program, case, K):
    """``(h2d_bytes, scan_elements)`` of the case's launches, from
    their shapes: the planes, lengths and (of a long group) row indices
    of both groups."""
    from fluentbit_tpu.ops.batch import bucket_size

    segments = 2 if case["rows"] > 4096 else 1
    Bp = bucket_size(min(case["rows"], 4096))
    L = case["widths"][0]
    h2d, elements = K * Bp * (L + 4), program.scan_elements(Bp, L)
    if case["split"]:
        L_long = case["widths"][1]
        h2d += K * 256 * (L_long + 4) + 256 * 4
        elements += program.scan_elements(256, L_long)
    return segments * h2d, segments * elements


@pytest.mark.parametrize("kind", ["mask", "first_match"])
@pytest.mark.parametrize("name", list(_SPLIT_CASES))
def test_a_frame_in_two_groups_is_the_whole_frame_and_the_host_chain(
        name, kind, monkeypatch):
    """``staged_match`` sends a frame's few long rows as a 256-row group
    of their own and the rest at the width it needs — where they fit
    one group and the frame pads to 4,096 rows — and the verdict is the
    whole frame's and the per-record host chain's, bit for bit; the
    counters count both groups, and a frame is one launch either way."""
    import numpy as np

    from fluentbit_tpu.core.spans import ShardedTimings
    from fluentbit_tpu.plugins import filter_grep
    from fluentbit_tpu.plugins.filter_grep import _TIMING_KEYS, staged_match

    case = _SPLIT_CASES[name]
    rules, program, lane, how = _split_setup(kind, case.get("two_keys"))
    data, bodies = _split_frame(**case)
    n, K = len(bodies), program.n_planes
    segments = 2 if n > 4096 else 1
    tm = ShardedTimings(_TIMING_KEYS)
    got, offsets, n_got = staged_match(rules, program, lane, tm, data, n,
                                       **how)
    assert n_got == n and len(offsets) == n + 1
    st = lane.stats()
    assert st["launches"] == st["ok"] == segments
    assert st["fallback_segments"] == 0
    assert tm["split_launches"] == case["split"]
    assert tm["long_rows"] == (case["long"] if case["split"] else 0)
    # (of two keys, one row is long in one and past the limit in the
    # other: in the long group, and the host's for that key's rule)
    assert tm["overflow_rows"] == case.get("overflow", 0) \
        + (1 if case.get("two_keys") else 0)
    assert (tm["h2d_bytes"], tm["scan_elements"]) == _both_groups(
        program, case, K)
    assert tm["device_records"] == n

    # the whole frame, as before this rule: one width, the longest row's
    monkeypatch.setattr(filter_grep, "_LONG_SHARE", 1 << 30)
    tm_whole = ShardedTimings(_TIMING_KEYS)
    whole, _offs, _n = staged_match(rules, program, lane, tm_whole, data,
                                    n, **how)
    assert tm_whole["split_launches"] == tm_whole["long_rows"] == 0
    assert whole.dtype == got.dtype and np.array_equal(whole, got)
    assert tm_whole["scan_elements"] >= tm["scan_elements"]
    want = _host_chain(kind, rules, bodies)
    assert np.array_equal(got, want)
    # the cases decide something: long rows on both sides of a verdict
    long_rows = [i for i, b in enumerate(bodies)
                 if 256 < len(b.get("log", "")) <= 512]
    if kind == "mask" and len(long_rows) >= 9:
        assert got[0, long_rows].any() and not got[0, long_rows].all()


@pytest.mark.parametrize("kind", ["mask", "first_match"])
def test_a_failed_launch_of_two_groups_falls_back_bit_exact(kind):
    """The lane's fallback over a frame in two groups is ``host_mask``
    over both, joined as on the device: exactly one of the two decides
    a frame, and they agree."""
    import numpy as np

    from fluentbit_tpu import failpoints
    from fluentbit_tpu.core.spans import ShardedTimings
    from fluentbit_tpu.plugins.filter_grep import _TIMING_KEYS, staged_match

    case = _SPLIT_CASES["overflow_rows_among_them"]
    rules, program, lane, how = _split_setup(kind)
    data, bodies = _split_frame(**case)
    n = len(bodies)
    tm = ShardedTimings(_TIMING_KEYS)
    dev, _offs, _n = staged_match(rules, program, lane, tm, data, n, **how)
    failpoints.enable("device.dispatch", "1*return(split)")
    try:
        host, _offs, _n = staged_match(rules, program, lane, tm, data, n,
                                       **how)
    finally:
        failpoints.reset()
    st = lane.stats()
    assert st["launches"] == 2 and st["ok"] == 1
    assert st["fallback_segments"] == 1
    assert tm["split_launches"] == 2 and tm["long_rows"] == 2 * case["long"]
    assert host.dtype == dev.dtype and np.array_equal(host, dev)
    assert np.array_equal(host, _host_chain(kind, rules, bodies))


@pytest.mark.parametrize("kind", ["mask", "first_match"])
def test_a_frame_in_two_groups_begun_ahead_and_a_handle_dropped(kind):
    """``begin=True`` / ``begun=`` on a frame that goes out in two
    groups: one flight, the finishing half counts both groups once; a
    handle that is dropped counts in the lane and nowhere else."""
    import numpy as np

    from fluentbit_tpu.core.spans import ShardedTimings
    from fluentbit_tpu.plugins.filter_grep import (_TIMING_KEYS, Begun,
                                                   staged_match)

    case = _SPLIT_CASES["the_rest_at_the_width_it_needs"]
    rules, program, lane, how = _split_setup(kind)
    data, bodies = _split_frame(**case)
    n = len(bodies)
    tm_one, tm_two = ShardedTimings(_TIMING_KEYS), ShardedTimings(_TIMING_KEYS)
    one = staged_match(rules, program, lane, tm_one, data, n, **how)
    begun = staged_match(rules, program, lane, tm_two, data, n, begin=True,
                         **how)
    assert isinstance(begun, Begun)
    assert all(tm_two[k] == 0 for k in _TIMING_KEYS)
    two = staged_match(rules, program, lane, tm_two, data, n, begun=begun,
                       **how)
    _same_verdict(kind, one, two)
    assert np.array_equal(two[0], _host_chain(kind, rules, bodies))
    for key in ("split_launches", "long_rows", "h2d_bytes", "d2h_bytes",
                "scan_elements", "device_records"):
        assert tm_one[key] == tm_two[key] > 0, key
    assert tm_two["split_launches"] == 1 and tm_two["long_rows"] == 5
    dropped = staged_match(rules, program, lane, tm_two, data, n,
                           begin=True, **how)
    dropped.drop()
    st = lane.stats()
    assert st["launches"] == st["ok"] == 3 and st["begun_in_flight"] == 0
    assert tm_two["split_launches"] == 1 and tm_two["device_records"] == n


def test_the_mesh_keeps_its_one_width():
    """Staged for the mesh a frame goes out at ``tpu_max_record_len``
    whatever its rows, as before: each mesh child places the planes
    itself, and a second group there is another contract."""
    import numpy as np

    from fluentbit_tpu.core.spans import ShardedTimings
    from fluentbit_tpu.plugins.filter_grep import _TIMING_KEYS, staged_match

    case = _SPLIT_CASES["the_rest_at_the_width_it_needs"]
    rules, program, lane, how = _split_setup("mask")
    mesh = lane.current_mesh()
    if mesh is None:
        pytest.skip("need a multi-device mesh")
    data, bodies = _split_frame(**case)
    tm = ShardedTimings(_TIMING_KEYS)
    got, _offs, n = staged_match(rules, program, lane, tm, data,
                                 len(bodies), mesh=mesh, **how)
    assert tm["mesh_launches"] == 1
    assert tm["split_launches"] == tm["long_rows"] == 0
    assert tm["h2d_bytes"] == 4096 * (512 + 4)
    assert tm["scan_elements"] == program.scan_elements(4096, 512)
    assert np.array_equal(got, _host_chain("mask", rules, bodies))
