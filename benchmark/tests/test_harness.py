"""The harness's own arithmetic and its lookup by file, checked without
a chip or a pipeline. Run by hand and in the rehearsal:

    python -m pytest benchmark/tests -q -p no:cacheprovider

Not part of tier-1 (which collects ``tests/`` only).
"""

import ast
import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import stats  # noqa: E402
import trace_reduce  # noqa: E402
import wire  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# ------------------------------------------------------------ percentiles

@pytest.mark.parametrize("values,q,want", [
    (list(range(1, 101)), 0.5, 50),
    (list(range(1, 101)), 0.95, 95),
    (list(range(100, 0, -1)), 0.95, 95),   # order does not matter
    ([7], 0.5, 7),
    ([1, 2, 3, 4], 0.5, 2),                # nearest rank, no interpolation
])
def test_percentile_is_nearest_rank(values, q, want):
    assert stats.percentile(values, q) == want


@pytest.mark.parametrize("n,q,ok", [
    (1320, 0.95, True),    # 30 s of the steady cell: 66 beyond
    (200, 0.95, True),     # exactly ten beyond
    (199, 0.95, False),    # nine beyond
    (176, 0.95, False),
    (1, 0.5, True),        # a median needs only a sample
    (0, 0.5, False),
])
def test_percentile_needs_ten_samples_beyond(n, q, ok):
    assert stats.supported(n, q) is ok


def test_quartile_spread_is_the_contracts():
    values = [100.0, 101.0, 102.0, 103.0, 104.0, 105.0]
    import statistics
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.quartile_spread(values) == pytest.approx(
        (q3 - q1) / statistics.median(values))


# ---------------------------------------------------------- interval union

def test_busy_is_the_union_not_the_sum():
    events = [(0, 10), (5, 15), (20, 30), (22, 25), (100, 110)]
    busy, gaps = stats.busy_and_gaps(events, (0, 50))
    assert busy == 25                       # 0-15 and 20-30; 100-110 outside
    assert gaps == [(15, 20), (30, 50)]


def test_intersect_and_subtract():
    a, b = [(0, 10), (20, 30)], [(5, 25)]
    assert stats.intersect(a, b) == [(5, 10), (20, 25)]
    assert stats.subtract(a, b) == [(0, 5), (25, 30)]
    assert stats.subtract(a, []) == a
    assert stats.total(stats.union([(3, 4), (1, 2), (2, 3)])) == 3


def test_trace_reduction_on_a_synthetic_trace():
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [("jit_a(1)", 100, 300),
                                               ("jit_a(1)", 600, 200)]},
            {"name": "XLA Ops", "events": [
                ("%fusion.1 = s32[8]{0} fusion(...)", 100, 200),
                ("%while.2 = (s32[], s32[4]) while(...)", 250, 150),
                ("%fusion.1 = s32[8]{0} fusion(...)", 600, 200)]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ("bench:decode", 0, 100), ("bench:append", 400, 200),
            ("bench:filter:grep", 450, 100), ("other", 800, 200)]}]},
    ]
    got = trace_reduce.reduce_planes(planes)
    assert got["launches"] == 2 and got["devices"] == 1
    assert got["busy_s"] == pytest.approx(500e-9)   # 100-400 and 600-800
    assert got["span_s"] == pytest.approx(1000e-9)
    gaps = dict(got["idle_gaps"])
    assert gaps["decode"] == pytest.approx(100e-9)
    assert gaps["filter"] == pytest.approx(100e-9)
    assert gaps["append"] == pytest.approx(100e-9)  # 400-600 less the filter
    assert gaps["unattributed"] == pytest.approx(200e-9)
    assert got["device_ops"][0] == ["fusion.1 s32[8]", pytest.approx(400e-9)]
    assert trace_reduce.reduce_planes(planes[1:]) is None


# ------------------------------------------------------------------- wire

def test_wire_round_trip_and_ack():
    rec = {"log": "x" * 300, "k": "v"}
    assert wire.unpack_str_map(wire.pack_str_map(rec)) == rec
    assert wire.ack_message("ab") == b"\x81\xa3ack\xa2ab"
    frame = wire.forward_frame(wire.pack_str("t"), 1_500_000_007,
                               [wire.pack_str_map({"a": "b"})] * 2, "c1")
    assert frame.startswith(b"\x93\xa1t\x92\x92\xd7\x00")
    assert frame.count(b"\x81\xa1a\xa1b") == 2
    assert wire.output_events(5, []) == b""


# ------------------------------------------------- BENCHMARK.json and files

def test_names_and_units_are_within_the_allowed_characters():
    b = bench()
    names = [c["name"] for c in b["configs"]]
    for w in b["workloads"]:
        names += [w["name"], w["config"], w["traffic"]]
    for group in ("end_to_end", "per_layer"):
        for m in b[group]:
            names.append(m["name"])
            assert UNIT.match(m["unit"]), m
            assert m["better"] in ("lower", "higher")
    for c in b["configs"]:
        names += c["reduced"]
    assert all(NAME.match(n) for n in names), \
        [n for n in names if not NAME.match(n)]
    metric_names = [m["name"] for g in ("end_to_end", "per_layer")
                    for m in b[g]]
    assert len(set(metric_names)) == len(metric_names)


def test_every_cell_config_and_metric_resolves_by_file():
    b = bench()
    cells = {w["name"] for w in b["workloads"]}
    for c in b["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        folder = os.path.dirname(os.path.join(ROOT, c["file"]))
        assert os.path.isfile(os.path.join(folder, cfg["pipeline"]))
        assert os.path.isfile(os.path.join(
            BENCH, "corpora", cfg["corpus"]["maker"] + ".py"))
        assert os.path.isfile(os.path.join(
            BENCH, "reference", c["name"] + ".py"))
    for w in b["workloads"]:
        path = os.path.join(BENCH, "traffic", w["traffic"] + ".json")
        with open(path) as f:
            kind = json.load(f)["kind"]
        assert os.path.isfile(os.path.join(BENCH, "traffic_kinds",
                                           kind + ".py"))
    e2e = {m["name"]: m for m in b["end_to_end"]}
    for m in b["per_layer"]:
        with open(os.path.join(BENCH, "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        module, func = spec["reader"].split(":")
        with open(os.path.join(BENCH, "readers", module + ".py")) as f:
            tree = ast.parse(f.read())
        assert func in {n.name for n in tree.body
                        if isinstance(n, ast.FunctionDef)}, m["name"]
        # every per-layer metric names its cells, and the metric it
        # moves is reported in every one of them
        moved = e2e[m["moves"]]
        mine = set(m["workloads"])
        assert mine, m["name"]
        assert mine <= set(moved.get("workloads", cells)) and mine <= cells


@pytest.mark.parametrize("path", [
    "generator.py", "wire.py", "lookup.py", "traffic_kinds/closed_loop.py",
    "traffic_kinds/open_loop.py", "corpora/grep_lines.py",
    "corpora/firehose_events.py", "corpora/syslog_lines.py"])
def test_the_generator_imports_neither_jax_nor_the_program(path):
    stdlib = set(sys.stdlib_module_names) | {"wire", "lookup"}
    with open(os.path.join(BENCH, path)) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        mods = []
        if isinstance(node, ast.Import):
            mods = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            mods = [node.module or ""]
        for mod in mods:
            assert mod.split(".")[0] in stdlib, (path, mod)


def test_corpus_labels_are_the_same_work_for_every_seed():
    sys.path.insert(0, os.path.join(BENCH, "corpora"))
    import grep_lines

    a = grep_lines.make(40000, 1, {})
    b = grep_lines.make(40000, 2 ** 31 + 11, {})
    assert a[0] != b[0]
    assert abs(sum(x & 1 for x in a[1]) - sum(x & 1 for x in b[1])) <= 2
    assert sum(1 for x in a[1] if x & 2) == sum(1 for x in b[1] if x & 2) == 0
    long_a = grep_lines.make(100000, 3, {})
    assert sum(1 for x in long_a[1] if x & 2) == 2


# ------------------------------ what a configuration and a traffic file state

PINNED_WALL = 1_790_000_000_123_456_789
#: taken on the parent of the PR that brought ``mode`` and the reference's
#: ``expected_output`` (commit d7ce668): a cell whose files state neither
#: is generated and judged by the bytes it always was
PARENT_FRAME_SHA256 = \
    "0cf3af84daf170d1f4f7cccf6882ce0de9d922c819691dfc1e3ddecf243a6d17"
PARENT_EXPECTED = (
    "801cb3088e0d8cc8579cfb5de4dc3e4adbcccda73b2e77764e8f5b1e8d7489b7",
    [86676, 0, 77614, 86676])


@pytest.fixture(scope="module")
def grep_frame():
    """4,096 lines of the grep corpus under seed 7, as the generator
    packs them: (config, bodies, labels)."""
    sys.path.insert(0, os.path.join(BENCH, "corpora"))
    import grep_lines

    with open(os.path.join(BENCH, "configs", "grep-apache2.json")) as f:
        config = json.load(f)
    records, labels = grep_lines.make(4096, 7, config["corpus"]["params"])
    return config, [wire.pack_str_map(r) for r in records], labels


def some_frames():
    return [{"slot": slot, "lines": 1024, "ack_ns": ack,
             "wall_ns": 1_790_000_000_000_000_001 + i}
            for i, (slot, ack) in enumerate(((0, 5), (2, 0), (3, 9),
                                             (0, 11)))]


def test_a_traffic_file_without_mode_sends_the_frame_it_always_did(
        grep_frame):
    import hashlib

    config, bodies, _labels = grep_frame
    with open(os.path.join(BENCH, "traffic", "catchup.json")) as f:
        framer = wire.FRAMERS[json.load(f).get("mode", "forward")]
    assert framer is wire.forward_frame
    frame = framer(wire.pack_str(config["tag"]), PINNED_WALL, bodies,
                   "%08x%08x" % (7, 0))
    assert len(frame) == 522722
    assert hashlib.sha256(frame).hexdigest() == PARENT_FRAME_SHA256


def test_a_reference_without_expected_output_is_judged_as_before(
        grep_frame):
    import run

    _config, bodies, labels = grep_frame
    for name in ("grep-apache2", "sketch-firehose", "rewrite-syslog"):
        with open(os.path.join(BENCH, "reference", name + ".py")) as f:
            assert "def expected_output" not in f.read(), name
    assert run.expected_output(some_frames(), bodies, labels) \
        == PARENT_EXPECTED
    assert run.expected_output(some_frames(), bodies, labels,
                               reference=object()) == PARENT_EXPECTED


def test_a_reference_with_expected_output_decides_the_main_sinks_check(
        grep_frame):
    """A toy reference whose chain upper-cases one field of what it
    keeps: the check passes on the transformed bytes, fails on the sent
    ones, and the per-frame sizes are the transformed ones."""
    import hashlib
    import types

    import run

    _config, bodies, labels = grep_frame

    def upper(body: bytes) -> bytes:
        record = wire.unpack_str_map(body)
        record["log"] = record["log"].upper()
        return wire.pack_str_map(record)

    def one_frame(frame, bodies, labels, wire):
        lo = frame["slot"] * frame["lines"]
        return wire.output_events(frame["wall_ns"], [
            upper(bodies[i]) for i in range(lo, lo + frame["lines"])
            if labels[i] & wire.KEEP])

    toy = types.SimpleNamespace(expected_output=one_frame)
    frames = [f for f in some_frames() if f["ack_ns"]]
    digest, sizes = run.expected_output(frames, bodies, labels, toy)
    plain_digest, plain_sizes = run.expected_output(frames, bodies, labels)
    assert sizes == plain_sizes and digest != plain_digest  # same lengths

    def verdict(sink: bytes) -> bool:
        n = len(frames)
        counters = {"forward.withheld_acks": 0, "forward.dedup_hits": 0,
                    "forward.absorbed": n, "engine.raw_declines": 0,
                    "engine.records_in": sum(f["lines"] for f in frames)}
        numbers = run.wire_numbers(
            frames, counters, hashlib.sha256(sink).hexdigest(), digest,
            len(sink), sum(sizes))
        checks, _skipped = run.wire_checks(
            frames, {}, counters, {"broken": None}, numbers, sum(sizes),
            [], True)
        others = {k: v for k, v in checks.items()
                  if k != "output_equal_expected_survivors_in_order"}
        assert all(others.values()), others
        return checks["output_equal_expected_survivors_in_order"]

    transformed = b"".join(one_frame(f, bodies, labels, wire)
                           for f in frames)
    sent = b"".join(run.kept_unchanged()(f, bodies, labels, wire)
                    for f in frames)
    assert verdict(transformed) is True
    assert verdict(sent) is False
    assert verdict(transformed[:-1]) is False


def test_packed_frames_decode_to_the_events_of_the_forward_frame(
        grep_frame):
    """4,096 corpus lines framed both ways through ``in_forward``'s own
    decode (CPU, no socket): the same tag, the same V2 events, the same
    chunk id to ack."""
    sys.path.insert(0, ROOT)
    import fluentbit_tpu as flb
    from fluentbit_tpu.plugins import net_forward

    config, bodies, _labels = grep_frame
    tag, chunk = wire.pack_str(config["tag"]), "%08x%08x" % (7, 3)
    frames = {mode: framer(tag, PINNED_WALL, bodies, chunk)
              for mode, framer in wire.FRAMERS.items()}
    assert set(frames) == {"forward", "packed"}
    assert frames["packed"].startswith(b"\x93" + tag + b"\xc6")   # bin32
    assert frames["packed"].endswith(
        b"\x82\xa4size\xcd\x10\x00\xa5chunk" + wire.pack_str(chunk))
    assert wire.packed_forward_frame(tag, 5, [], "c") \
        == b"\x93" + tag + b"\xc4\x00\x82\xa4size\x00\xa5chunk\xa1c"

    ctx = flb.create(flush="50ms", grace="1")
    ctx.input("forward", listen="127.0.0.1", port="0")
    ctx.output("lib", match="*", callback=lambda _data, _tag: None)
    ctx.start()
    try:
        plugin = next(i.plugin for i in ctx.engine.inputs
                      if i.plugin.name == "forward")
        decoded = {}
        for mode, frame in frames.items():
            unpacker = net_forward.Unpacker()
            unpacker.feed(frame)
            decoded[mode] = plugin._decode(next(unpacker))
    finally:
        ctx.stop()
    for mode, (got_tag, buf, n, _option, ack_ref, _cid) in decoded.items():
        assert (got_tag, n, ack_ref) == (config["tag"], 4096, chunk), mode
        assert buf == wire.output_events(PINNED_WALL, bodies), mode
    assert decoded["packed"][3]["size"] == 4096


# -------------------------- the references guard the deployment, not a kernel

def grep_patterns(pipeline: str) -> list:
    with open(os.path.join(BENCH, "configs", pipeline)) as f:
        return [line.split(None, 2)[2].strip() for line in f
                if line.split(None, 1)[:1] in (["Regex"], ["Exclude"])]


@pytest.mark.parametrize("name", ["grep-apache2", "rewrite-syslog"])
def test_either_device_kernel_serves_a_child_and_the_host_does_not(name):
    """The grep program with ``scan`` forced on every child, the S=10
    rule's among them (on the chip ``auto`` gives that one ``assoc``):
    the check holds. A child that never materialised — what it decides,
    the host decides — fails it, and so does a filter without a
    program."""
    sys.path.insert(0, ROOT)
    from lookup import load_py

    from fluentbit_tpu.ops import device
    from fluentbit_tpu.ops.grep import GrepProgram
    from fluentbit_tpu.regex.dfa import compile_dfa

    check = load_py("reference", name).children_on_device_kernels
    patterns = grep_patterns("grep-apache2.conf")
    assert len(patterns) == 2
    dfas = [compile_dfa(p) for p in patterns]
    assert sorted(d.n_states <= 64 for d in dfas) == [False, True]
    assert device.wait(120)
    for kernel in ("scan", "auto"):
        program = GrepProgram(dfas, 512, kernel=kernel, plane_of=(0, 0))
        assert len(program._children) == 2
        assert check([program]) is False            # nothing materialised
        assert program.try_ready()
        assert {ch.kernel_resolved for ch in program._children} \
            <= {"scan", "assoc"}
        assert check([program]) is True, kernel
    program._children[1].kernel_resolved = "assoc"  # as the chip resolves it
    assert check([program]) is True
    program._children[1].kernel_resolved = None     # resolved on the host
    assert check([program]) is False
    single = GrepProgram(dfas[:1], 512, kernel="scan")
    assert single._children is None and check([single]) is False
    assert single.try_ready() and check([single]) is True
    assert check([]) is False
