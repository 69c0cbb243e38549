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
        # the metric it moves is reported in every cell where this one is
        moved = e2e[m["moves"]]
        mine = set(m.get("workloads", cells))
        assert mine <= set(moved.get("workloads", cells)) and mine <= cells


@pytest.mark.parametrize("path", [
    "generator.py", "wire.py", "lookup.py", "traffic_kinds/closed_loop.py",
    "traffic_kinds/open_loop.py", "corpora/grep_lines.py",
    "corpora/firehose_events.py"])
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
