"""filter_parser's regex mode from the staged plane: capture spans on the
device (``regex/spans.py``, ``ops.grep.SpanProgram``), records built
from them (``filter_parser._build_from_spans``), the third verdict kind
of ``filter_grep.staged_match``.

On a CPU backend ``process_batch`` takes the host path, so the platform
gate is forced open the way ``tests/test_rewrite_device.py`` does it.
The span program is held to ``FlbRegex.parse_spans`` (Python ``re``'s
group offsets) and its verdict to ``GrepProgram._match_impl``'s; the
filter to the per-record host chain (``tpu.enable off``), byte for
byte.
"""

import glob
import json
import os
import random

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from fluentbit_tpu import failpoints  # noqa: E402
from fluentbit_tpu.codec.events import (decode_events, encode_event,  # noqa: E402
                                        reencode_event)
from fluentbit_tpu.core.chunk_batch import RawChunk  # noqa: E402
from fluentbit_tpu.core.engine import Engine  # noqa: E402
from fluentbit_tpu.ops import device, fault  # noqa: E402
from fluentbit_tpu.ops.grep import (GrepProgram, SpanProgram,  # noqa: E402
                                    span_program_for)
from fluentbit_tpu.parsers import create_parser  # noqa: E402
from fluentbit_tpu.regex import FlbRegex, parse  # noqa: E402
from fluentbit_tpu.regex.dfa import compile_dfa  # noqa: E402
from fluentbit_tpu.regex.parser import UnsupportedRegex  # noqa: E402
from fluentbit_tpu.regex.spans import SpanDecline, compile_spans  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

APACHE2 = (r'^(?<host>[^ ]*) [^ ]* (?<user>[^ ]*) \[(?<time>[^\]]*)\] '
           r'"(?<method>\S+)(?: +(?<path>[^ ]*) +\S*)?" (?<code>[^ ]*) '
           r'(?<size>[^ ]*)(?: "(?<referer>[^\"]*)" "(?<agent>.*)")?$')
ACCESS = ('10.1.2.3 - frank [10/Oct/2000:13:55:36 -0700] "GET /path/1 '
          'HTTP/1.1" 200 2326 "http://referer.example/0" "curl/8.5.0"')

#: in-class regexes besides apache2: conf/parsers.conf's shapes (syslog
#: rfc3164, cri) and tests/test_parsers.py's, with a lazy quantifier, an
#: alternation, a counted repetition and a mid-pattern line anchor
PATTERNS = {
    "apache2": APACHE2,
    "syslog-rfc3164": (
        r'^\<(?<pri>[0-9]+)\>(?<time>[^ ]* {1,2}[^ ]* [^ ]*) '
        r'(?<host>[^ ]*) (?<ident>[a-zA-Z0-9_\/\.\-]*)'
        r'(?:\[(?<pid>[0-9]+)\])?(?:[^\:]*\:)? *(?<message>.*)$'),
    "cri": (r'^(?<time>[^ ]+) (?<stream>stdout|stderr) (?<logtag>[^ ]*) '
            r'(?<log>.*)$'),
    "kv-lazy": r'(?<k>[a-z]+?)=(?<v>\d+|"[^"]*")(?: |$)',
    "lazy-optional": r'^(?<a>[^ ]+) (?<b>.*?)(?: x(?<c>\d{1,3}))?$',
    "second-line": r'^(?<first>\w+)\n^(?<second>\w+)$',
}
SEEDS = {
    "apache2": [ACCESS, ACCESS.replace(' "http://referer.example/0" '
                                       '"curl/8.5.0"', ""),
                '1.1.1.1 - - [t] "GET" 200 -',
                ACCESS.replace("curl/8.5.0", 'a "quoted" agent'),
                ACCESS.replace("GET", 'G"T'), "\n" + ACCESS, ACCESS + "\n",
                ACCESS.replace("frank", ""), ACCESS.replace("2326", "")],
    "syslog-rfc3164": ["<13>Oct 11 22:14:15 host app[123]: msg here",
                       "<13>Oct  1 22:14:15 host app: msg", "<1>a b c d e"],
    "cri": ["2024-01-01T00:00:00Z stdout F hello there",
            "t stderr P partial"],
    "kv-lazy": ['a=12 b="q r" ', "key=7", 'x="" y=1'],
    "lazy-optional": ["abc def x12", "abc def ghi x1234", "a "],
    "second-line": ["ab\ncd", "ab\ncd\nef", "x\n\ny"],
}
ALPHABET = ' ""[]<>-=:abcxstdout019/\n.'


def lines_for(name: str, n: int, L: int) -> list:
    rng = random.Random(f"spans-{name}")
    out = [b"", b"x" * (L - 1), b"y" * L, b"z" * (L + 1)]
    seeds = SEEDS[name]
    while len(out) < n:
        parts = list(rng.choice(seeds))
        for _ in range(rng.randrange(0, 4)):
            parts[rng.randrange(len(parts))] = rng.choice(ALPHABET)
        if rng.random() < 0.25:
            parts = parts[:rng.randrange(len(parts))]
        if rng.random() < 0.15:
            parts.insert(rng.randrange(len(parts) + 1), "\n")
        if rng.random() < 0.3:
            parts = [rng.choice(ALPHABET)
                     for _ in range(rng.randrange(0, 40))]
        if name == "apache2" and rng.random() < 0.2:
            # non-ASCII UTF-8 (no Unicode white space: \S reads bytes)
            parts.insert(rng.randrange(len(parts) + 1),
                         rng.choice(["é", "日本", "ü"]))
        out.append("".join(parts).encode("utf-8")[:L + 1])
    # a cut may have split a character: keep what still decodes
    return [v if _decodes(v) else v[:-1] if _decodes(v[:-1]) else b"cut"
            for v in out]


def _decodes(v: bytes) -> bool:
    try:
        v.decode("utf-8")
        return True
    except UnicodeDecodeError:
        return False


def stage(values: list, L: int):
    plane = np.zeros((1, len(values), L), dtype=np.uint8)
    lengths = np.zeros((1, len(values)), dtype=np.int32)
    for i, v in enumerate(values):
        if len(v) > L:
            lengths[0, i] = -2  # an overflow row
            continue
        plane[0, i, :len(v)] = np.frombuffer(v, dtype=np.uint8)
        lengths[0, i] = len(v)
    return plane, lengths


def byte_spans(rx: FlbRegex, value: bytes):
    """``parse_spans`` in bytes of the value."""
    text = value.decode("utf-8")
    got = rx.parse_spans(text)
    if got is None:
        return None
    return [(-1, -1) if s < 0 else
            (len(text[:s].encode()), len(text[:e].encode()))
            for s, e in got]


# ------------------------------------------------ (a) the span program


@pytest.mark.parametrize("name", sorted(PATTERNS))
def test_span_program_equals_python_re_offsets(name):
    L, B = 128, 256
    rx = FlbRegex(PATTERNS[name])
    prog = SpanProgram(compile_spans(rx.parsed), 512)
    values = lines_for(name, B, L)
    plane, lengths = stage(values, L)
    ok, spans = prog.spans(plane, lengths)
    assert spans.shape == (B, len(prog.names), 2) \
        and spans.dtype == np.int16
    # the verdict is the match kernel's, row for row
    mask = GrepProgram([compile_dfa(PATTERNS[name])], 512,
                       kernel="scan").match(plane, lengths)[0]
    assert (np.asarray(mask) == ok).all()
    matched = 0
    for i, v in enumerate(values):
        want = None if lengths[0, i] < 0 else byte_spans(rx, v)
        if want is None:
            assert not ok[i] and (spans[i] == -1).all(), (name, v)
            continue
        matched += 1
        assert ok[i] and spans[i].tolist() == [list(x) for x in want], \
            (name, v, spans[i].tolist(), want)
        # and the host walker over the same tables
        assert prog.tables.run(v) == want
    assert 10 < matched < B - 4, matched  # both kinds in the sample
    assert prog.program_name().startswith("grep_spans_")


def test_reverse_state_of_the_whole_row_gives_the_verdict():
    """Matched ⇔ start ∈ C_0: pass 1 alone decides what the walk of
    pass 2 ends in."""
    rx = FlbRegex(APACHE2)
    tb = compile_spans(rx.parsed)
    for v in lines_for("apache2", 200, 128):
        cls = [int(tb.class_map[b]) for b in v]
        r = tb.r_eol
        for c in reversed(cls):
            r = int(tb.rev[r, c])
        assert bool(tb.matches[r]) == (tb.run(v) is not None) \
            == rx.dfa.match_bytes(v)


# ------------------------------------------------ (b) outside the class


DECLINES = {
    "nullable-loop-body": (r"(?<a>x*)*y", "nullable"),
    "nullable-optional-body": (r"(?:(?<a>x)?)+y", "nullable"),
    "group-under-plus": (r"(?<a>x)+", "under a repetition"),
    "group-under-star": (r"(?:(?<a>\d)-)*z", "under a repetition"),
    "counted-over-a-group": (r"(?:(?<a>x)y){2,3}", "under a repetition"),
    "possessive": (r"(?<a>x*+)y", "possessive"),
    "ruby-Z": (r"(?<a>x)\Z", "eos_nl"),
    "no-named-group": (r"(x)y", "no named group"),
}


@pytest.mark.parametrize("name", sorted(DECLINES))
def test_out_of_class_shapes_decline_with_their_reason(name):
    pattern, reason = DECLINES[name]
    with pytest.raises(SpanDecline, match=reason):
        compile_spans(parse(pattern))


def test_back_reference_never_reaches_the_span_compiler():
    with pytest.raises(UnsupportedRegex):
        parse(r"(?<a>x)\1")
    with pytest.raises(UnsupportedRegex):
        span_program_for(r"(?<a>x)\k<a>")


# ------------------------------------------------- (c) the filter level


@pytest.fixture(scope="module")
def gate_open():
    """``device.platform()`` says "tpu" for this module: the selection
    points take the device path on the CPU backend."""
    assert device.wait(120)
    saved = device.platform
    device.platform = lambda: "tpu"
    yield
    device.platform = saved


def parser_engine(props=None, parser_props=None, tpu=True):
    e = Engine()
    pp = {"Format": "regex", "Regex": APACHE2, "Time_Key": "time",
          "Time_Format": "%d/%b/%Y:%H:%M:%S %z",
          "Types": "code:integer size:integer"}
    pp.update(parser_props or {})
    e.parsers["apache2"] = create_parser("apache2", **pp)
    f = e.filter("parser")
    for k, v in {"key_name": "log", "parser": "apache2",
                 "tpu_batch_records": "1",
                 "tpu.enable": "on" if tpu else "off",
                 **(props or {})}.items():
        f.set(k, v)
    ins = e.input("dummy")
    for x in e.inputs + e.filters:
        x.configure()
        x.plugin.init(x, e)
    return e, e.filters[0].plugin


def access(i: int, tail: str = "") -> str:
    return ACCESS.replace("13:55:36", "13:55:%02d" % (i % 60)) \
        .replace("/path/1", f"/path/{i}") + tail


def mixed_records(n: int) -> list:
    """Everything a chunk can hold: kernel lines, other fields, a
    missing key, a bin value, a byte past ASCII, an overflow row, a
    time that does not parse, empty captures."""
    out = []
    for i in range(n):
        if i % 4 == 0:
            out.append({"log": f"kernel: oom {i}"})
        elif i % 7 == 0:
            out.append({"log": access(i), "other": i, "host": "kept?"})
        elif i % 11 == 0:
            out.append({"nolog": 1})
        elif i % 13 == 0:
            out.append({"log": access(i).encode()})
        elif i % 17 == 0:
            out.append({"log": access(i, " é")})
        elif i % 19 == 0:
            out.append({"log": access(i, "x" * 600)})
        elif i % 23 == 0:
            out.append({"log": '1.1.1.1 - - [bad time] "GET" 200 -'})
        elif i % 29 == 0:
            out.append({"log": access(i).replace("frank", "")})
        else:
            out.append({"log": access(i)})
    return out


def chunk_of(records: list) -> bytes:
    return b"".join(encode_event(r, 1700000000.0 + i)
                    for i, r in enumerate(records))


def host_chain(data: bytes, props=None, parser_props=None) -> bytes:
    """The per-record chain (``tpu.enable off``) over the decoded
    events: what every batched path has to equal."""
    e, plugin = parser_engine(props, parser_props, tpu=False)
    assert plugin._spans is None and plugin._prefilter is None
    _rc, events = plugin.filter(decode_events(data), "t", e)
    return b"".join(ev.raw if ev.raw is not None else reencode_event(ev)
                    for ev in events)


OPTIONS = {
    "defaults": ({}, {}),
    "reserve_data": ({"reserve_data": "on"}, {}),
    "preserve_key": ({"preserve_key": "on"}, {}),
    "reserve_and_preserve": ({"reserve_data": "on",
                              "preserve_key": "on"}, {}),
    "time_keep": ({"reserve_data": "on"}, {"Time_Keep": "on"}),
    "failing_time_format": ({}, {"Time_Format": "%Y-%m-%d"}),
    "no_time_format": ({"reserve_data": "on"}, {"Time_Format": ""}),
    "keep_empty_values": ({}, {"Skip_Empty_Values": "off"}),
}


@pytest.mark.parametrize("name", sorted(OPTIONS))
def test_batched_device_path_equals_the_host_chain(gate_open, name):
    props, parser_props = OPTIONS[name]
    records = mixed_records(300)
    data = chunk_of(records)
    want = host_chain(data, props, parser_props)
    e, plugin = parser_engine(props, parser_props)
    assert plugin.decision()["span_decline"] is None
    assert plugin._span_serves()
    before = fault.lane("grep").stats()
    n, out, n_in = plugin.process_batch(RawChunk(data, "t", len(records)))
    after = fault.lane("grep").stats()
    assert (n, n_in) == (300, 300)
    assert out == want
    assert after["launches"] - before["launches"] == 1 \
        == after["ok"] - before["ok"]
    tm = plugin.raw_timings
    long_rows = sum(1 for r in records
                    if len(r.get("log", "")) > 512)
    assert tm["device_records"] == 300 and tm["overflow_rows"] == long_rows
    # overflow rows, the missing key, the bin values, the é lines
    host_rows = sum(1 for r in records if not isinstance(r.get("log"), str)
                    or len(r["log"]) > 512 or not r["log"].isascii())
    assert tm["host_rows"] == host_rows > long_rows
    assert tm["d2h_bytes"] > 0 and tm["build_s"] > 0 and tm["parsed"] > 0
    # a row and byte in the reverse pass, one in the walk and its EOL:
    # Bp * (2 L + 1) at the staged bucket (L a power of two)
    assert tm["scan_elements"] >= 300 * (2 * 64 + 1) \
        and tm["scan_elements"] % 2 == 0


def test_chunk_with_no_match_returns_its_buffer(gate_open):
    data = chunk_of([{"log": f"kernel: oom {i}"} for i in range(70)])
    _e, plugin = parser_engine()
    n, out, _n = plugin.process_batch(RawChunk(data, "t", 70))
    assert n == 70 and out is data
    assert plugin.raw_timings["parsed"] == 0


def test_segment_boundaries_through_the_engine(gate_open):
    """n = 4,097: a full segment and one row more, through
    ``input_log_append`` → the raw hook; no decline."""
    records = [{"log": access(i) if i % 3 else f"kernel: oom {i}"}
               for i in range(4097)]
    data = chunk_of(records)
    want = host_chain(data)
    e, plugin = parser_engine()
    got = []
    out = e.output("lib")
    out.set("match", "*")
    out.set("callback", lambda d, _t: got.append(bytes(d)))
    out.configure()
    out.plugin.init(out, e)
    before = fault.lane("grep").stats()
    assert e.input_log_append(e.inputs[0], "t", data) == 4097
    after = fault.lane("grep").stats()
    assert after["launches"] - before["launches"] == 2 \
        == after["ok"] - before["ok"]
    assert sum(v for _l, v in e.m_filter_batch_decline.samples()) == 0
    assert plugin.raw_timings["device_records"] == 4097
    e.flush_all()
    assert b"".join(got) == want


@pytest.mark.parametrize("name", sorted(DECLINES))
def test_filter_with_an_out_of_class_regex_serves_as_before(gate_open,
                                                            name):
    pattern, reason = DECLINES[name]
    records = [{"log": "xxy xyxyxy 1-2-z"}, {"log": "nothing"}] * 40
    data = chunk_of(records)
    pp = {"Regex": pattern, "Time_Format": "", "Types": ""}
    want = host_chain(data, parser_props=pp)
    _e, plugin = parser_engine(parser_props=pp)
    assert plugin._spans is None
    assert reason in plugin.decision()["span_decline"]
    before = fault.lane("grep").stats()["launches"]
    n, out, _n = plugin.process_batch(RawChunk(data, "t", len(records)))
    assert n == len(records) and out == want
    assert fault.lane("grep").stats()["launches"] == before


# ------------------------------------------------- (d) the lane fallback


def test_failed_launch_gives_the_hosts_spans(gate_open):
    records = mixed_records(120)
    data = chunk_of(records)
    want = host_chain(data, {"reserve_data": "on"})
    fault.reset()
    failpoints.reset()
    _e, plugin = parser_engine({"reserve_data": "on"})
    failpoints.enable("device.dispatch", "1*return(injected)")
    try:
        n, out, _n = plugin.process_batch(RawChunk(data, "t", 120))
    finally:
        failpoints.reset()
    st = fault.lane("grep").stats()
    fault.reset()
    assert st["launches"] == 1 and st["fallback_segments"] == 1 \
        and st["ok"] == 0
    assert n == 120 and out == want


# --------------------------------------------- (e) spans and counters


PARSER_KEYS = ("extract_s", "kernel_s", "h2d_bytes", "d2h_bytes",
               "scan_elements", "device_records", "overflow_rows",
               "build_s", "parsed", "host_rows", "native_rows")


def test_parser_spans_and_their_ids(gate_open, monkeypatch):
    from fluentbit_tpu.plugins import filter_grep, filter_parser

    seen = []

    class Recorded:
        def __init__(self, name, **ids):
            self.ids = ids
            seen.append((name, ids))

        def set_metadata(self, **ids):
            self.ids.update(ids)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    from fluentbit_tpu.core import spans as core_spans

    monkeypatch.setattr(filter_parser, "span", Recorded)
    monkeypatch.setattr(filter_grep, "span", Recorded)
    monkeypatch.setattr(core_spans, "span", Recorded)  # tm.timed's
    records = mixed_records(100)
    _e, plugin = parser_engine()
    plugin.process_batch(RawChunk(chunk_of(records), "t", 100))
    names = [n for n, _ids in seen]
    assert names[0] == "parser.stage" and names[-1] == "parser.build"
    for name in ("grep.stage", "grep.dispatch", "grep.force"):
        assert name in names, names
    build = dict(seen)["parser.build"]
    assert build["rows"] == 100 and 0 < build["parsed"] < 100
    # the span program takes its one plane whole: one width, no group
    assert dict(seen)["grep.stage"] == {"seg": 0, "L": 128}
    assert set(plugin.raw_timings) == set(PARSER_KEYS)


@pytest.mark.parametrize(
    "key", [k for k in PARSER_KEYS if k != "extract_s"])
def test_every_parser_timing_key_feeds_a_metric_or_a_check(
        key, counters_of_declared_metrics):
    """An always-on counter that nothing reads is only a cost: each key
    is read by a declared per-layer metric of the benchmark
    (``conftest.py``), or by a named check of the configuration's plain reference. (``extract_s``
    is the shared launch's: ``staged_match`` adds it for every client,
    and ``kernel_s``, which a metric reads, is the wall time less it.)"""
    from fluentbit_tpu.plugins.filter_parser import _TIMING_KEYS

    assert set(_TIMING_KEYS) == set(PARSER_KEYS)
    read = counters_of_declared_metrics
    with open(os.path.join(REPO, "benchmark", "reference",
                           "parser-apache2.py")) as f:
        reference = f.read()
    checked = 'pre = "filter.parser."' in reference \
        and f'c.get(pre + "{key}")' in reference
    assert f"filter.parser.{key}" in read or checked, key
