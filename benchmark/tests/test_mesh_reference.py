"""``reference/grep-tenants-x4.py``'s layout checks shown to fail, each
on a made-up run that breaks one thing, and ``readers/mesh_cost.py`` on
a hand-built trace of four device planes: a chip's time over a chip's
share, not the mesh's. Not part of tier-1:

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import kernel_cost  # noqa: E402
from lookup import load_py  # noqa: E402

reference = load_py("reference", "grep-tenants-x4")
mesh_cost = load_py("readers", "mesh_cost")
element_cost = load_py("readers", "element_cost")

CHILDREN = [(2, 5), (3, 38), (4, 6), (5, 1)]       # (k, rules)


def decision(variant="batch", devices=4, children=CHILDREN):
    return {"rules": [{"s": 10, "c": 4, "k": k}
                      for k, n in CHILDREN for _ in range(n)],
            "mesh_children": [{"k": k, "rules": n, "variant": variant,
                               "devices": devices} for k, n in children]}


def counters(sharded=55, devices=220, unsharded=0, ok=55):
    return {"filter.grep.mesh_launches": sharded,
            "filter.grep.mesh_devices": devices,
            "filter.grep.unsharded_launches": unsharded,
            "lane.grep.ok": ok}


def failed(c, d) -> list:
    return sorted(k for k, v in reference.layout_checks(c, [d]).items()
                  if not v)


def test_a_run_laid_out_as_the_configuration_says_passes():
    assert failed(counters(), decision()) == []
    # a launch begun and dropped unused was laid out too: more than ok
    assert failed(counters(sharded=56, devices=224), decision()) == []


@pytest.mark.parametrize("c,d,names", [
    # three of four launches sharded, the fourth on one chip
    (counters(sharded=3, devices=12, unsharded=1, ok=4), decision(),
     ["no_launch_served_unsharded",
      "sharded_launches_some_and_no_fewer_than_lane_ok"]),
    # three devices a launch: the mesh shrank
    (counters(devices=165), decision(devices=3),
     ["every_child_shards_rows_over_four_devices",
      "four_devices_every_sharded_launch"]),
    # one launch of the run on three devices
    (counters(devices=219), decision(),
     ["four_devices_every_sharded_launch"]),
    # a child on the rules variant
    (counters(), dict(decision(), mesh_children=[
        {"k": 2, "rules": 5, "variant": "batch", "devices": 4},
        {"k": 3, "rules": 38, "variant": "rules", "devices": 4},
        {"k": 4, "rules": 6, "variant": "batch", "devices": 4},
        {"k": 5, "rules": 1, "variant": "batch", "devices": 4}]),
     ["every_child_shards_rows_over_four_devices"]),
    # a child that never built a mesh handle: its rules are unaccounted
    (counters(), decision(children=CHILDREN[:3]),
     ["every_child_shards_rows_over_four_devices"]),
    # nothing sharded: one chip served the run
    (counters(sharded=0, devices=0), dict(decision(), mesh_children=[]),
     ["every_child_shards_rows_over_four_devices",
      "four_devices_every_sharded_launch",
      "sharded_launches_some_and_no_fewer_than_lane_ok"]),
    # the parent of the PR: no such counter, no such key
    ({"lane.grep.ok": 55}, {"rules": decision()["rules"]},
     ["every_child_shards_rows_over_four_devices",
      "four_devices_every_sharded_launch",
      "no_launch_served_unsharded",
      "sharded_launches_some_and_no_fewer_than_lane_ok"]),
], ids=["three-of-four-sharded", "three-devices", "one-launch-short",
        "a-child-on-rules", "a-child-without-handle", "nothing-sharded",
        "parent-program"])
def test_layout_check_reads_false(c, d, names):
    assert failed(c, d) == names


# ------------------------------------------------- the per-chip readers

def modules(scale=1.0):
    """One chip's ``XLA Modules`` line: five launches of two children,
    the first and last of each name cut by the traced interval."""
    out = []
    for name, ms in (("jit_grep_scan_S74_k3_mesh(7)", 120.0),
                     ("jit_grep_scan_S80_k2_mesh(9)", 30.0)):
        for i, part in enumerate((0.4, 1.0, 1.0, 1.0, 0.3)):
            out.append((name, i * 200_000_000,
                        int(ms * scale * part * 1e6), {}))
    out.append(("jit_grep_merge(3)", 10, 5_000, {}))
    return out


PLANES = [{"name": f"/device:TPU:{d}", "lines": [
    {"name": "XLA Modules", "events": modules(scale)},
    {"name": "XLA Ops", "events": [("%while.1", 0, 900_000_000, {})]}]}
    for d, scale in enumerate((1.0, 1.02, 0.98, 1.0))] + [
    {"name": "/host:CPU", "lines": [
        {"name": "t", "events": [("fbtpu:grep_scan", 0, 9, {})]}]}]


def test_a_chips_time_is_one_planes_not_the_sum_nor_the_mix():
    assert mesh_cost.chip_launch_seconds(PLANES, "grep_scan") \
        == pytest.approx(0.150)
    # the one-chip reader over the same planes pools the four chips'
    # events under a name and drops only the first and last of all
    pooled = element_cost.launch_seconds(PLANES, "grep_scan")
    assert pooled != pytest.approx(0.150, rel=1e-3)
    assert mesh_cost.chip_launch_seconds(PLANES[-1:], "grep_scan") is None
    assert mesh_cost.chip_launch_seconds(PLANES, "grep_spans") is None


class Prog:
    n_planes = 1

    def decision(self):
        return decision()


class Plugin:
    name, _program = "grep", Prog()


def test_per_chip_metrics_divide_a_chips_time_by_a_chips_share(monkeypatch):
    spans = load_py("readers", "program_spans")
    monkeypatch.setattr(spans, "newest_xplane", lambda: "a.xplane.pb")
    monkeypatch.setattr(spans, "read_planes", lambda path: PLANES)
    elements = 35631104            # GrepProgram.scan_elements(4096, 512)
    c = {"filter.grep.scan_elements": 19 * elements,
         "lane.grep.launches": 20, "lane.grep.ok": 19,
         "filter.grep.device_records": 19 * 4096,
         "filter.grep.mesh_launches": 20, "filter.grep.mesh_devices": 80}
    args = {"plugin": "grep", "lane": "grep", "module": "grep_scan"}
    readings = {"trace": {"busy_s": 2.9, "counters": c},
                "filters": [Plugin()], "device": {"kind": "TPU v5 lite"}}
    assert mesh_cost.devices_per_launch(c, "grep") == 4.0
    ns = mesh_cost.chip_ns_per_element(readings, **args)
    assert ns == pytest.approx(1e9 * 0.150 / (elements / 4))
    # the one-chip reader sets a chip's time (pooled over the planes,
    # their cut events among it) against the whole launch: about a
    # quarter
    assert element_cost.ns_per_element(readings, **args) <= ns / 3.8
    rows = 4096 / 4
    need = kernel_cost.grep_match_bytes(
        decision()["rules"], rows * 516, rows)
    whole = kernel_cost.grep_match_bytes(
        decision()["rules"], 4096 * 516, 4096)
    assert need < whole                  # a chip's rows, the whole tables
    peak = kernel_cost.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    roof = mesh_cost.chip_match_roofline_share(
        readings, plane_len=512, **args)
    assert roof == pytest.approx(100 * need / peak / 0.150)
    assert 0 < roof < 100

    # the parent has no mesh counters; a run that sharded nothing; a
    # rehearsal has no trace: nothing, and no exception
    for gone in ("filter.grep.mesh_launches", "filter.grep.mesh_devices",
                 "filter.grep.scan_elements"):
        r = dict(readings, trace={"busy_s": 2.9, "counters": {
            k: v for k, v in c.items() if k != gone}})
        assert mesh_cost.chip_ns_per_element(r, **args) is None
    r = dict(readings, trace={"busy_s": 2.9, "counters": dict(
        c, **{"filter.grep.mesh_launches": 0,
              "filter.grep.mesh_devices": 0})})
    assert mesh_cost.chip_ns_per_element(r, **args) is None
    assert mesh_cost.chip_match_roofline_share(
        r, plane_len=512, **args) is None
    none = dict(readings, trace=None)
    assert mesh_cost.chip_ns_per_element(none, **args) is None
    assert mesh_cost.chip_match_roofline_share(
        none, plane_len=512, **args) is None
    monkeypatch.setattr(spans, "newest_xplane", lambda: None)
    assert mesh_cost.chip_ns_per_element(readings, **args) is None
