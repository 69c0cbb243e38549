"""Bit-exactness of the batched filter fast path (process_batch).

The engine prefers ``process_batch`` on the raw ingest path; these
tests drive identical corpora through (a) the batched path and (b) the
per-record decode path (batch hook force-disabled) and require
byte-identical chunk output, identical emitter traffic, and identical
metric state — the ISSUE 2 "bit-exact either way" contract for
filter_parser (json + apache2 regex), the 8-rule rewrite_tag chain,
and log_to_metrics counters, including non-ASCII and truncated records
(crafted against ops/utf8.py's validator so the vectors provably are /
are not well-formed UTF-8).

Also here: the ops.batch.bucket_size pad-budget clamp regression
(satellite: 65536-bucket × long-syslog max_len overflow).
"""

import json
import random
import struct

import numpy as np
import pytest

from fluentbit_tpu.codec.events import encode_event
from fluentbit_tpu.codec.msgpack import Unpacker
from fluentbit_tpu.core.engine import Engine
from fluentbit_tpu.ops.batch import bucket_size
from fluentbit_tpu.ops.utf8 import validate_bytes

APACHE2 = (
    r'^(?<host>[^ ]*) [^ ]* (?<user>[^ ]*) \[(?<time>[^\]]*)\] '
    r'"(?<method>\S+)(?: +(?<path>[^ ]*) +\S*)?" (?<code>[^ ]*) '
    r'(?<size>[^ ]*)(?: "(?<referer>[^\"]*)" "(?<agent>.*)")?$'
)


def _disable_batch(engine):
    for f in engine.filters:
        f.plugin.can_process_batch = lambda: False


def _drain(ins):
    return b"".join(bytes(c.buf) for c in ins.pool.drain())


# ---------------------------------------------------------------------
# filter_parser — json
# ---------------------------------------------------------------------

def _parser_engine(fmt="json", **parser_props):
    e = Engine()
    e.parser("p0", format=fmt, **parser_props)
    f = e.filter("parser")
    f.set("key_name", "log")
    f.set("parser", "p0")
    ins = e.input("dummy")
    for x in e.inputs + e.filters:
        x.configure()
        x.plugin.init(x, e)
    return e, ins


def _run_parser_both(buf, fmt="json", **props):
    e1, i1 = _parser_engine(fmt, **props)
    calls = []
    orig = e1.filters[0].plugin.process_batch
    e1.filters[0].plugin.process_batch = \
        lambda c: calls.append(1) or orig(c)
    n1 = e1.input_log_append(i1, "t", buf)
    out1 = _drain(i1)
    e2, i2 = _parser_engine(fmt, **props)
    _disable_batch(e2)
    n2 = e2.input_log_append(i2, "t", buf)
    out2 = _drain(i2)
    assert n1 == n2
    assert out1 == out2
    return out1, bool(calls)


def test_parser_json_bit_exact_and_engaged():
    rng = random.Random(1)
    recs = []
    docs = [
        '{"a": 1, "b": "x", "nest": {"y": [1, 2.5, null, true]}}',
        '{"dup": 1, "mid": 2, "dup": {"replaced": [3]}}',
        '{"esc": "q\\u00e9\\ud834\\udd1e\\n\\t\\"", "s": "\\/"}',
        '{"neg": -129, "wide": 5000000000, "tiny": -0.0, "e": 1e-7}',
        '{"n": NaN, "inf": Infinity, "minf": -Infinity}',
        '{}',
        'not json',
        '[1, 2, 3]',
        '{"trailing": 1} x',
        '{"bad": 01}',
    ]
    for i in range(300):
        recs.append(encode_event(
            {"log": rng.choice(docs), "other": i},
            rng.choice([float(i), i])))
    buf = b"".join(recs)
    _out, engaged = _run_parser_both(buf)
    assert engaged, "batched json path did not engage"


def test_parser_json_non_ascii_bit_exact():
    # valid multi-byte UTF-8 stays on the fast path (proved well-formed
    # by the ops/utf8 oracle)
    doc = '{"msg": "héllo wörld ✓ 日本語 𝄞", "k": "ünïcode"}'
    assert validate_bytes(doc.encode("utf-8"))
    buf = b"".join(encode_event({"log": doc}, float(i)) for i in range(64))
    _out, engaged = _run_parser_both(buf)
    assert engaged


def test_parser_json_invalid_utf8_falls_back_bit_exact():
    # a log value holding an ill-formed byte (0xFF can begin no UTF-8
    # sequence — ops/utf8 rejects it) cannot transcode bit-exactly in
    # C (the Python path decodes with errors="replace"); the chunk must
    # decline to the per-record path and still match byte-for-byte
    bad = b'{"a":"' + b"\xff" + b'"}'
    assert not validate_bytes(bad)
    rec = (b"\x92\x92\xcb" + struct.pack(">d", 1.0) + b"\x80"
           + b"\x81\xa3log" + bytes([0xA0 | len(bad)]) + bad)
    good = encode_event({"log": '{"ok": 1}'}, 2.0)
    _out, _engaged = _run_parser_both(rec + good)


def test_parser_json_truncated_record_bit_exact():
    # torn trailing record: the decoder treats it as end-of-stream and
    # keeps the prefix; the batch path declines and must match that
    full = b"".join(encode_event({"log": '{"i": %d}' % i}, float(i))
                    for i in range(8))
    torn = full[:-3]
    _out, _engaged = _run_parser_both(torn)


def test_parser_json_exotic_options_keep_per_record_path():
    # reserve_data / a time_format are outside the fast-transcode set:
    # the filter must not advertise the batch hook at init
    e = Engine()
    e.parser("p0", format="json")
    f = e.filter("parser")
    f.set("key_name", "log")
    f.set("parser", "p0")
    f.set("reserve_data", "true")
    ins = e.input("dummy")
    for x in e.inputs + e.filters:
        x.configure()
        x.plugin.init(x, e)
    assert not e.filters[0].plugin.can_process_batch()

    e2 = Engine()
    e2.parser("pt", format="json", time_format="%s", time_key="t")
    f2 = e2.filter("parser")
    f2.set("key_name", "log")
    f2.set("parser", "pt")
    ins2 = e2.input("dummy")
    for x in e2.inputs + e2.filters:
        x.configure()
        x.plugin.init(x, e2)
    assert not e2.filters[0].plugin.can_process_batch()


def test_parser_regex_apache2_bit_exact():
    rng = random.Random(2)
    recs = []
    for i in range(400):
        if rng.random() < 0.7:
            line = (f"10.0.0.{i % 256} - frank "
                    f"[10/Oct/2000:13:55:{i % 60:02d} -0700] "
                    f'"GET /p/{i} HTTP/1.1" 200 {i * 7} '
                    f'"http://r.example/" "curl/8"')
        else:
            line = f"kernel: oom-killer invoked pid={i}"
        recs.append(encode_event({"log": line}, float(i)))
    buf = b"".join(recs)

    def run(disable):
        e, ins = _parser_engine("regex", regex=APACHE2)
        if disable:
            _disable_batch(e)
        else:
            assert e.filters[0].plugin.can_process_batch()
            assert e.filters[0].plugin._batch_mode == "regex"
        n = e.input_log_append(ins, "t", buf)
        return n, _drain(ins)

    assert run(False) == run(True)


# ---------------------------------------------------------------------
# filter_rewrite_tag — 8-rule chain
# ---------------------------------------------------------------------

WORDS = ["alpha", "beta", "gamma", "delta",
         "epsilon", "zeta", "eta", "theta"]


def _rt_engine(rules):
    e = Engine()
    rt = e.filter("rewrite_tag")
    for r in rules:
        rt.set("rule", r)
    ins = e.input("dummy")
    for x in e.inputs + e.filters:
        x.configure()
        x.plugin.init(x, e)
    return e, ins


def _run_rt_both(rules, buf, expect_engaged=True):
    def run(disable):
        e, ins = _rt_engine(rules)
        if disable:
            _disable_batch(e)
        elif expect_engaged:
            assert e.filters[0].plugin.can_process_batch()
        em = e.filters[0].plugin.emitter.instance
        n = e.input_log_append(ins, "orig.tag", buf)
        kept = _drain(ins)
        emitted = [(c.tag, bytes(c.buf), c.records)
                   for c in em.pool.drain()]
        return n, kept, emitted

    a, b = run(False), run(True)
    assert a == b
    return a


def test_rewrite_tag_8rule_chain_bit_exact():
    rng = random.Random(3)
    rules = [f"$log ^{w} routed.{w} false" for w in WORDS]
    buf = b"".join(
        encode_event(
            {"log": rng.choice(WORDS + ["omega", "psi"]) + f" v {i}"},
            float(i))
        for i in range(512))
    n, kept, emitted = _run_rt_both(rules, buf)
    assert emitted, "no records re-emitted"
    # groups arrive in first-seen order with byte-identical spans
    assert sum(cnt for _t, _b, cnt in emitted) + n == 512


def test_rewrite_tag_capture_template_bit_exact():
    # $1 capture + $TAG part + keep=true mixed with static rules:
    # capture rules take the per-record branch of the batched path
    rules = [
        r"$log ^(alpha)\w* routed.$1.$TAG[1] true",
        "$log ^beta routed.beta false",
    ]
    rng = random.Random(4)
    buf = b"".join(
        encode_event({"log": rng.choice(
            ["alphaX 1", "beta 2", "other 3"]) + f" {i}"}, float(i))
        for i in range(300))
    _run_rt_both(rules, buf)


def test_rewrite_tag_emitter_reentry_untouched():
    # the re-emitted records re-enter the pipeline under their new tag
    # and must pass through the filter untouched (recursion guard)
    rules = ["$log ^alpha routed.alpha false"]
    buf = b"".join(encode_event({"log": f"alpha {i}"}, float(i))
                   for i in range(64))
    e, ins = _rt_engine(rules)
    em = e.filters[0].plugin.emitter.instance
    n = e.input_log_append(ins, "orig", buf)
    assert n == 0  # keep=false: all re-tagged
    chunks = em.pool.drain()
    assert len(chunks) == 1 and chunks[0].records == 64
    assert bytes(chunks[0].buf) == buf  # byte-identical spans


def test_stateful_batch_then_decline_does_not_double_emit():
    # chain [rewrite_tag, parser(json)]: rewrite_tag's batched hook
    # emits, then the parser declines (bigint JSON is outside the C
    # transcode set). The engine must FINISH the chain per-record on
    # the current bytes — a full decode-path re-run would emit the
    # rewrite_tag records a second time.
    def build():
        e = Engine()
        e.parser("jp", format="json")
        rt = e.filter("rewrite_tag")
        rt.set("rule", "$tagkey ^go moved.out false")
        pf = e.filter("parser")
        pf.set("key_name", "log")
        pf.set("parser", "jp")
        ins = e.input("dummy")
        for x in e.inputs + e.filters:
            x.configure()
            x.plugin.init(x, e)
        return e, ins

    recs = []
    for i in range(64):
        # bin-typed log values are outside the C transcode set (decline
        # trigger) but parse fine per-record (_to_str decodes them)
        doc = '{"v": %d}' % i
        body = {"log": doc.encode() if i % 8 == 0 else doc}
        if i % 4 == 0:
            body["tagkey"] = "go"
        recs.append(encode_event(body, float(i)))
    buf = b"".join(recs)

    def run(disable):
        e, ins = build()
        if disable:
            _disable_batch(e)
        em = e.filters[0].plugin.emitter.instance
        n = e.input_log_append(ins, "t", buf)
        kept = _drain(ins)
        emitted = [(c.tag, bytes(c.buf), c.records)
                   for c in em.pool.drain()]
        return n, kept, emitted

    a, b = run(False), run(True)
    assert a == b
    total_emitted = sum(cnt for _t, _b, cnt in a[2])
    assert total_emitted == 16  # each matching record emitted ONCE


# ---------------------------------------------------------------------
# filter_log_to_metrics — counters
# ---------------------------------------------------------------------

def _lm_engine(extra=()):
    e = Engine()
    lm = e.filter("log_to_metrics")
    lm.set("regex", "log ERROR")
    for k, v in extra:
        lm.set(k, v)
    lm.set("metric_mode", "counter")
    lm.set("metric_name", "errors")
    lm.set("metric_description", "t")
    lm.set("tag", "metrics")
    ins = e.input("dummy")
    for x in e.inputs + e.filters:
        x.configure()
        x.plugin.init(x, e)
    return e, ins


def _strip_ts(payload):
    out = []
    for obj in Unpacker(payload):
        obj["meta"]["ts"] = 0
        for m in obj["metrics"]:
            m["ts"] = 0
        out.append(obj)
    return out


def test_log_to_metrics_counter_bit_exact():
    rng = random.Random(5)
    buf = b"".join(
        encode_event({"log": rng.choice(
            ["ERROR a", "info b", "ERROR TIMEOUT c", "warn d"]) + str(i)},
            float(i))
        for i in range(512))

    def run(disable, extra=()):
        e, ins = _lm_engine(extra)
        if disable:
            _disable_batch(e)
        else:
            assert e.filters[0].plugin.can_process_batch()
        em = e.filters[0].plugin.emitter.instance
        n = e.input_log_append(ins, "t", buf)
        kept = _drain(ins)
        snaps = [(c.tag, _strip_ts(bytes(c.buf)), c.records, c.event_type)
                 for c in em.pool.drain()]
        return n, kept, snaps

    assert run(False) == run(True)
    # exclude rule stacked before the keep rule (legacy first-rule-
    # decides) and static labels
    extra = (("exclude", "log TIMEOUT"),
             ("add_label", "env prod"))
    assert run(False, extra) == run(True, extra)


def test_log_to_metrics_dynamic_labels_stay_per_record():
    e, _ins = _lm_engine(extra=(("label_field", "svc"),))
    assert not e.filters[0].plugin.can_process_batch()


# ---------------------------------------------------------------------
# ops.batch.bucket_size pad-budget clamp (satellite regression)
# ---------------------------------------------------------------------

def test_bucket_size_unclamped_shapes_unchanged():
    assert bucket_size(10) == 256
    assert bucket_size(300) == 1024
    assert bucket_size(70000) == 131072


def test_bucket_size_clamps_long_record_padding():
    # top bucket × 64 KiB rows = 4 GiB of pad — must clamp
    budget = 256 * 1024 * 1024
    got = bucket_size(20000, max_len=65536)
    assert got >= 20000
    assert got * 65536 <= budget or got < 65536  # no top-bucket jump
    assert got == ((20000 + 63) // 64) * 64
    # counts whose smallest bucket is affordable keep the ladder
    assert bucket_size(1000, max_len=65536) == 1024
    # smallest bucket >= n over budget -> minimal padding
    assert bucket_size(5000, max_len=131072) == ((5000 + 63) // 64) * 64
    # short rows keep the plain bucket ladder
    assert bucket_size(20000, max_len=512) == 65536


def test_auto_kernel_resolves_scan_on_cpu():
    pytest.importorskip("jax")
    from fluentbit_tpu.ops import device
    from fluentbit_tpu.ops.grep import GrepProgram
    from fluentbit_tpu.regex.dfa import compile_dfa

    device.attach_async()
    assert device.wait(120.0)
    prog = GrepProgram([compile_dfa("abc")], 64)
    assert prog.kernel == "auto"
    batch = np.zeros((1, 2, 64), np.uint8)
    lens = np.zeros((1, 2), np.int32)
    prog.match(batch, lens)  # materializes → resolves
    assert prog.kernel_resolved == "scan"  # assoc is 300× off on cpu
