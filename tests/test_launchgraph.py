"""fbtpu-xray: the interprocedural device launch-graph analyzer.

Three layers of pinning, mirroring test_lint.py's contract for every
other rule pack:

- **fixtures** — each of the five launch-graph rules fires on a
  known-bad snippet, stays quiet on the good twin, and honors
  ``# fbtpu-lint: allow(...)``;
- **the shipped tree** — the graph's per-chain launch counts, scatter
  passes, and canonical transfer bytes are pinned to today's reality
  (the numbers the committed ``analysis/launch_budget.json`` gates,
  and the numbers the fusion PR — ROADMAP item 1 — must improve);
- **static == dynamic** — the analyzer's launches-per-segment must
  equal the DeviceLane launch counters observed on the simulated
  8-device mesh for the grep, flux, parser-regex, and rewrite_tag
  chains.  A walker bug that over- or under-counts a chain fails HERE,
  not three PRs later when the budget gate lies.
"""

import copy
import json
import os
import subprocess
import sys

import pytest

from fluentbit_tpu.analysis import lint_paths, lint_source
from fluentbit_tpu.analysis.launchgraph import (LaunchGraphRules,
                                                budget_snapshot,
                                                build_launch_graph,
                                                canonical_env,
                                                compare_budget,
                                                graph_to_dot)
from fluentbit_tpu.analysis.registry import BUDGET_PARAMS, budget_path

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "fluentbit_tpu")

_FIX = "fluentbit_tpu/plugins/filter_fixture.py"


def rules(findings):
    return sorted({f.rule for f in findings})


def by_rule(findings, rule):
    return [f for f in findings if f.rule == rule]


# ---------------------------------------------------------------------
# device-multi-launch-chain
# ---------------------------------------------------------------------

BAD_MULTI_LAUNCH = """
class F:
    def process_batch(self, chunk):
        data, n_records = chunk.data, chunk.n
        lane = self._lane()
        mask = lane.run(
            lambda: self._program.dispatch_mesh(self._mesh, data,
                                                n_records),
            lambda: self._host(data),
        )
        extra = lane.run(
            lambda: self._counts.dispatch_mesh(self._mesh, data,
                                               n_records),
            lambda: self._host_counts(data),
        )
        return mask, extra
"""

GOOD_SINGLE_LAUNCH = """
class F:
    def process_batch(self, chunk):
        data, n_records = chunk.data, chunk.n
        lane = self._lane()
        return lane.run(
            lambda: self._program.dispatch_mesh(self._mesh, data,
                                                n_records),
            lambda: self._host(data),
        )
"""


def test_multi_launch_chain_fires():
    got = lint_source(BAD_MULTI_LAUNCH, _FIX)
    hits = by_rule(got, "device-multi-launch-chain")
    assert len(hits) == 1
    assert "2 device launches per staged segment" in hits[0].message
    assert hits[0].severity == "warning"


def test_single_launch_chain_quiet():
    got = lint_source(GOOD_SINGLE_LAUNCH, _FIX)
    assert "device-multi-launch-chain" not in rules(got)


def test_multi_launch_interprocedural():
    # the second launch hides two calls deep — the walker must chain
    # through self-method edges to find it
    src = """
class F:
    def process_batch(self, chunk):
        data, n_records = chunk.data, chunk.n
        mask = self._match(data, n_records)
        return self._sketch(mask)

    def _match(self, data, n):
        lane = self._lane()
        return lane.run(
            lambda: self._program.dispatch_mesh(self._mesh, data, n),
            lambda: self._host(data),
        )

    def _sketch(self, mask):
        lane = self._lane()
        return lane.run(
            lambda: self._counts.dispatch_mesh(self._mesh, mask, 0),
            lambda: self._host_counts(mask),
        )
"""
    got = lint_source(src, _FIX)
    assert "device-multi-launch-chain" in rules(got)


def test_multi_launch_branches_take_max_not_sum():
    # an if/else picking ONE of two launch paths is still a one-launch
    # chain; a branch that returns must not chain into the fallthrough
    src = """
class F:
    def process_batch(self, chunk):
        data, n_records = chunk.data, chunk.n
        lane = self._lane()
        if self._mesh is not None:
            return lane.run(
                lambda: self._program.dispatch_mesh(self._mesh, data,
                                                    n_records),
                lambda: self._host(data),
            )
        return lane.run(
            lambda: self._program.dispatch_jit(data, n_records),
            lambda: self._host(data),
        )
"""
    got = lint_source(src, _FIX)
    assert "device-multi-launch-chain" not in rules(got)


def test_multi_launch_suppression():
    src = BAD_MULTI_LAUNCH.replace(
        "    def process_batch(self, chunk):",
        "    # fbtpu-lint: allow(device-multi-launch-chain)\n"
        "    def process_batch(self, chunk):")
    got = lint_source(src, _FIX)
    assert "device-multi-launch-chain" not in rules(got)


# ---------------------------------------------------------------------
# device-undonated-buffer
# ---------------------------------------------------------------------

BAD_DONATE_OFF = """
class F:
    def process_batch(self, chunk):
        data, n_records = chunk.data, chunk.n
        lane = self._lane()
        return lane.run(
            lambda: self._program.dispatch_mesh(self._mesh, data,
                                                n_records, donate="off"),
            lambda: self._host(data),
        )
"""


def test_undonated_donate_off_is_an_error():
    got = by_rule(lint_source(BAD_DONATE_OFF, _FIX),
                  "device-undonated-buffer")
    assert len(got) == 1
    assert got[0].severity == "error"
    assert "donation disabled" in got[0].message


def test_undonated_structural_gap_is_a_warning():
    # the default donate set still cannot alias the u8 batch (no
    # same-aval output exists) — a warning pointing at the fusion fix
    got = by_rule(lint_source(GOOD_SINGLE_LAUNCH, _FIX),
                  "device-undonated-buffer")
    assert len(got) == 1
    assert got[0].severity == "warning"
    assert "R*Bp*L" in got[0].message


def test_undonated_suppression():
    src = BAD_DONATE_OFF.replace(
        "            lambda: self._program.dispatch_mesh(self._mesh, "
        "data,\n",
        "            # fbtpu-lint: allow(device-undonated-buffer)\n"
        "            lambda: self._program.dispatch_mesh(self._mesh, "
        "data,\n")
    assert "device-undonated-buffer" not in rules(lint_source(src, _FIX))


# ---------------------------------------------------------------------
# device-host-roundtrip
# ---------------------------------------------------------------------

BAD_ROUNDTRIP = """
class F:
    def process_batch(self, chunk):
        data, n_records = chunk.data, chunk.n
        lane = self._lane()
        mask = lane.run(
            lambda: self._program.dispatch_mesh(self._mesh, data,
                                                n_records),
            lambda: self._host(data),
        )
        keep, n_kept = native.compact(data, mask)
        return keep
"""

GOOD_MASK_ONLY = """
class F:
    def process_batch(self, chunk):
        data, n_records = chunk.data, chunk.n
        lane = self._lane()
        mask = lane.run(
            lambda: self._program.dispatch_mesh(self._mesh, data,
                                                n_records),
            lambda: self._host(data),
        )
        return mask
"""


def test_host_roundtrip_fires_on_compact_after_launch():
    got = by_rule(lint_source(BAD_ROUNDTRIP, _FIX),
                  "device-host-roundtrip")
    assert len(got) == 1
    assert "compact" in got[0].message
    assert got[0].severity == "warning"


def test_host_roundtrip_quiet_without_scatter():
    assert "device-host-roundtrip" not in rules(
        lint_source(GOOD_MASK_ONLY, _FIX))


def test_host_roundtrip_quiet_without_launch():
    # compact on a host-computed mask is not a PCIe roundtrip
    src = """
class F:
    def process_batch(self, chunk):
        data, n_records = chunk.data, chunk.n
        mask = self._host(data)
        keep, n_kept = native.compact(data, mask)
        return keep
"""
    assert "device-host-roundtrip" not in rules(lint_source(src, _FIX))


def test_host_roundtrip_suppression():
    src = BAD_ROUNDTRIP.replace(
        "        keep, n_kept = native.compact(data, mask)",
        "        # fbtpu-lint: allow(device-host-roundtrip)\n"
        "        keep, n_kept = native.compact(data, mask)")
    assert "device-host-roundtrip" not in rules(lint_source(src, _FIX))


# ---------------------------------------------------------------------
# device-sync-in-staging-loop
# ---------------------------------------------------------------------

BAD_SYNC_IN_LOOP = """
import numpy as np

class F:
    def process_batch(self, chunk):
        data, n_records = chunk.data, chunk.n
        lane = self._lane()
        out = []
        for lo, hi in segment_bounds(n_records, 4096):
            out.append(np.asarray(lane.run(
                lambda: self._program.dispatch_mesh(self._mesh, data,
                                                    hi - lo),
                lambda: self._host(data),
            )))
        return out
"""

GOOD_FORCE_AFTER_LOOP = """
import numpy as np

class F:
    def process_batch(self, chunk):
        data, n_records = chunk.data, chunk.n
        lane = self._lane()
        flights = []
        for lo, hi in segment_bounds(n_records, 4096):
            flights.append(lane.run(
                lambda: self._program.dispatch_mesh(self._mesh, data,
                                                    hi - lo),
                lambda: self._host(data),
            ))
        return np.asarray(flights)
"""


def test_sync_in_staging_loop_fires():
    got = by_rule(lint_source(BAD_SYNC_IN_LOOP, _FIX),
                  "device-sync-in-staging-loop")
    assert len(got) == 1
    assert got[0].severity == "error"
    assert "asarray" in got[0].message


def test_sync_after_loop_quiet():
    assert "device-sync-in-staging-loop" not in rules(
        lint_source(GOOD_FORCE_AFTER_LOOP, _FIX))


def test_sync_suppression():
    src = BAD_SYNC_IN_LOOP.replace(
        "            out.append(np.asarray(lane.run(",
        "            # fbtpu-lint: allow(device-sync-in-staging-loop)\n"
        "            out.append(np.asarray(lane.run(")
    assert "device-sync-in-staging-loop" not in rules(
        lint_source(src, _FIX))


# ---------------------------------------------------------------------
# stage-redundant-copy
# ---------------------------------------------------------------------

BAD_ARENA_COPY = """
class F:
    def _stage(self, span, key):
        got = native.stage_field(span, key, 96, 8)
        b, ln, offs, n = got
        b = b.copy()
        return b, ln, offs, n
"""

GOOD_STAGE_INTO = """
import numpy as np

class F:
    def _stage(self, span, key, cnt):
        wide = np.empty((cnt, 96), dtype=np.uint8)
        wlen = np.full((cnt,), -1, dtype=np.int32)
        count = native.stage_field_into(span, key, wide, wlen,
                                        n_hint=cnt)
        return wide, wlen, count
"""


def test_arena_copy_fires():
    got = by_rule(lint_source(BAD_ARENA_COPY, _FIX),
                  "stage-redundant-copy")
    assert len(got) == 1
    assert got[0].severity == "error"
    assert "stage_field_into" in got[0].message


def test_stage_into_quiet():
    assert "stage-redundant-copy" not in rules(
        lint_source(GOOD_STAGE_INTO, _FIX))


def test_arena_copy_through_subscript_fires():
    # `.copy()` on a subscript of the tainted arena view still fires
    src = """
class F:
    def _stage(self, span, key):
        b, ln, offs, n = native.stage_field(span, key, 96, 8)
        return b[0].copy()
"""
    assert "stage-redundant-copy" in rules(lint_source(src, _FIX))


def test_arena_copy_suppression():
    src = BAD_ARENA_COPY.replace(
        "        b = b.copy()",
        "        # fbtpu-lint: allow(stage-redundant-copy)\n"
        "        b = b.copy()")
    assert "stage-redundant-copy" not in rules(lint_source(src, _FIX))


def test_copy_on_untainted_buffer_quiet():
    src = """
class F:
    def _stage(self, span, key):
        b = self._scratch
        return b.copy()
"""
    assert "stage-redundant-copy" not in rules(lint_source(src, _FIX))


# ---------------------------------------------------------------------
# scope: the rules live on the plugin/flux planes only
# ---------------------------------------------------------------------

def test_rules_scoped_to_device_planes():
    for src in (BAD_MULTI_LAUNCH, BAD_ROUNDTRIP, BAD_ARENA_COPY):
        assert lint_source(src, "fluentbit_tpu/ops/fixture.py") == []


# ---------------------------------------------------------------------
# the shipped tree: today's launch-graph reality, pinned
# ---------------------------------------------------------------------

def _chain(graph, suffix):
    hits = [c for cid, c in graph["chains"].items()
            if cid.endswith(suffix)]
    assert len(hits) == 1, sorted(graph["chains"])
    return hits[0]


@pytest.fixture(scope="module")
def graph():
    return build_launch_graph()


def test_shipped_grep_chain(graph):
    ch = _chain(graph, "filter_grep.py::GrepFilter.process_batch")
    assert ch["launches_per_segment"] == 1
    assert ch["staged"] is True
    assert ch["sync_hits"] == []          # overlap intact
    (site,) = [s for s in ch["sites"] if s["kind"] == "grep-mesh"]
    assert site["lane"] is True           # armor-guarded
    # the one verdict -> compaction tail is the one true roundtrip
    assert ch["scatter_passes"] == 1


def test_shipped_flux_chain(graph):
    # post-fuseplan: the counts→hll→cms chain is one fused shard_map
    # program — a single launch, no per-group loop (the per-group HLL
    # and CMS absorbs now ride a masked [Gp, ...] lane inside it)
    ch = _chain(graph, "flux/state.py::FluxState.absorb_batch")
    assert ch["launches_per_segment"] == 1
    kinds = sorted(s["kind"] for s in ch["sites"])
    assert kinds == ["flux-fused"]
    assert not ch["sites"][0]["in_loop"]


def test_shipped_rewrite_tag_chain(graph):
    # rewrite_tag launches through the staged helper it shares with
    # filter_grep (known to the walker by name across the module
    # boundary): one lane-guarded launch a segment, and the two
    # compacts (per tag, survivors) that re-walk the chunk with the
    # verdict — recorded debt in launch_budget.json
    ch = _chain(graph, "filter_rewrite_tag.py::RewriteTagFilter"
                       ".process_batch")
    assert ch["launches_per_segment"] == 1
    assert ch["sync_hits"] == []
    (site,) = ch["sites"]
    assert site["what"] == "staged_match" and site["lane"] is True
    assert site["kind"] == "grep-mesh"
    assert ch["scatter_passes"] == 2


def test_shipped_parser_chain(graph):
    # the parser's regex mode launches its span program through the
    # same staged helper (one lane-guarded launch a segment) and builds
    # records from the spans: no compaction pass re-walks the chunk
    ch = _chain(graph, "filter_parser.py::ParserFilter.process_batch")
    assert ch["launches_per_segment"] == 1
    assert ch["sync_hits"] == []
    (site,) = ch["sites"]
    assert site["what"] == "staged_match" and site["lane"] is True
    assert ch["scatter_passes"] == 0


def test_shipped_host_only_entries(graph):
    for suffix in ("flux/plugin.py::FluxFilter.process_batch",
                   "filter_log_to_metrics.py::LogToMetricsFilter"
                   ".process_batch"):
        ch = _chain(graph, suffix)
        assert ch["launches_per_segment"] == 0, suffix
        assert ch["sync_hits"] == [], suffix


def test_shipped_transfer_budget_numbers(graph):
    env = canonical_env()
    assert env["Bp"] == 4096 and env["R"] == 2 and env["L"] == 512
    grep = _chain(graph, "GrepFilter.process_batch")["transfers"]
    # batch u8 [R,Bp,L] un-donated + lengths i32 [R,Bp] aliased
    assert grep["undonated_h2d_bytes_canonical"] == \
        env["R"] * env["Bp"] * env["L"]
    assert grep["d2h_bytes_canonical"] == 4 * env["R"] * env["Bp"]
    donated = {t["buffer"]: t["donated"] for t in grep["h2d"]}
    assert donated == {"batch": False, "lengths": True}
    flux = _chain(graph, "FluxState.absorb_batch")["transfers"]
    # fused program: seg/valid/lengths/comp_len 4*Bp i32 each, batch +
    # comp Bp*L u8, cms table 8*M_cms — registers are donated; d2h
    # returns counts [Gp] + registers [Gp, M_hll] + table
    assert flux["undonated_h2d_bytes_canonical"] == 4784128
    assert flux["d2h_bytes_canonical"] == 557088


def test_shipped_donation_crosscheck(graph):
    d = graph["donation"]
    # static expectation == live aliasable_donations on the 8-device
    # mesh: only lengths aliases the mask; the u8 batch has no
    # same-aval output to alias (the undonated-buffer warning's basis)
    assert d["lengths_donated"] is True
    assert d["batch_donated"] is False


def test_shipped_table_bytes(graph):
    tables = graph["tables"]
    apache2 = tables["filter_grep[apache2]"]
    # the minimized apache2 DFA: shrink already ran (the carried-over
    # ROADMAP item — rewrite_tag/log_to_metrics compile through the
    # same reducer, reported via m_shrink_* at init)
    assert apache2["rules"][0]["states_eliminated"] > 0
    assert apache2["bytes"] == tables["filter_rewrite_tag[apache2]"][
        "bytes"]
    assert apache2["replicated_bytes"] == \
        apache2["bytes"] * BUDGET_PARAMS["n_dev"]
    assert tables["filter_log_to_metrics[5xx]"]["bytes"] < 1024


# ---------------------------------------------------------------------
# the budget file: round-trip + regression gate
# ---------------------------------------------------------------------

def _committed():
    with open(budget_path(), "r", encoding="utf-8") as fh:
        return json.load(fh)


def test_budget_file_matches_the_tree(graph):
    # `--write-budget` run today must reproduce the committed file
    # byte-for-byte in content: the budget snapshot...
    committed = _committed()
    assert budget_snapshot(graph) == committed["budget"]
    # ...and the findings baseline (the recorded launch-graph debt)
    from fluentbit_tpu.analysis.__main__ import _canon

    names = set(LaunchGraphRules.RULE_NAMES)
    live = {(_canon(f.path), f.rule, f.message)
            for f in lint_paths([PKG]) if f.rule in names}
    recorded = {(d["path"], d["rule"], d["message"])
                for d in committed["findings"]}
    assert live == recorded, "stale launch_budget.json — regenerate " \
        "with: python -m fluentbit_tpu.analysis --write-budget"


def test_budget_self_comparison_clean(graph):
    current = budget_snapshot(graph)
    regressions, notes = compare_budget(current, _committed()["budget"])
    assert regressions == []


def test_budget_catches_regressions(graph):
    current = budget_snapshot(graph)
    key = next(k for k in current["chains"] if "GrepFilter" in k)
    # more launches than the baseline → regression
    base = copy.deepcopy(current)
    base["chains"][key]["launches_per_segment"] = 0
    regs, _ = compare_budget(current, base)
    assert any("launches" in r for r in regs)
    # more un-donated bytes → regression
    base = copy.deepcopy(current)
    base["chains"][key]["undonated_h2d_bytes"] = 1
    regs, _ = compare_budget(current, base)
    assert any("donated" in r for r in regs)
    # a brand-new device chain → regression (no silent growth)
    base = copy.deepcopy(current)
    del base["chains"][key]
    regs, _ = compare_budget(current, base)
    assert regs
    # fewer launches than the baseline → a note, not a failure
    base = copy.deepcopy(current)
    base["chains"][key]["launches_per_segment"] = 9
    regs, notes = compare_budget(current, base)
    assert regs == [] and notes


# ---------------------------------------------------------------------
# CLI plumbing: --graph / --changed / the implicit baseline / --all
# ---------------------------------------------------------------------

def _cli(*args, timeout=240):
    return subprocess.run(
        [sys.executable, "-m", "fluentbit_tpu.analysis", *args],
        capture_output=True, text=True, cwd=REPO, timeout=timeout)


def test_cli_graph_json():
    proc = _cli("--graph", "json")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    data = json.loads(proc.stdout)
    assert "GrepFilter.process_batch" in "".join(data["chains"])
    assert data["budget_regressions"] == []
    assert data["budget"] == _committed()["budget"]


def test_cli_graph_dot():
    proc = _cli("--graph", "dot")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert proc.stdout.lstrip().startswith("digraph")
    assert "grep-mesh" in proc.stdout


def test_cli_default_gate_is_zero_findings_with_baseline():
    # the committed launch_budget.json acts as the implicit baseline:
    # the recorded multi-launch/roundtrip/undonated debt is subtracted,
    # the default invocation stays a zero-findings gate
    proc = _cli()
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 findings" in proc.stdout
    assert "baselined" in proc.stdout


def test_cli_changed_smoke():
    # git-diff-scoped pre-commit run: whatever the tree state, the
    # shipped files must come back clean (baselined debt subtracted)
    proc = _cli("--changed")
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_full_gate_budget_comparison():
    # `--all` adds the launch/transfer budget comparison to the PR
    # gate: zero un-baselined findings on the shipped tree (native
    # layers may individually skip, but never silently)
    proc = _cli("--all", "--json", timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    data = json.loads(proc.stdout)
    assert data["findings"] == []


def test_cli_missing_budget_file_is_a_finding(tmp_path, monkeypatch):
    # the gate must never silently lose its baseline: point the
    # registry at a nonexistent budget file and --all must fail
    import fluentbit_tpu.analysis.__main__ as cli

    monkeypatch.setattr("fluentbit_tpu.analysis.registry.budget_path",
                        lambda: str(tmp_path / "nope.json"))
    findings, notes = cli._budget_findings()
    assert [f.rule for f in findings] == ["launch-budget-regression"]
    assert "missing" in findings[0].message


# ---------------------------------------------------------------------
# static == dynamic: the launch counts must match the lane counters
# on the simulated 8-device mesh
# ---------------------------------------------------------------------

APACHE2 = (
    r'^(?<host>[^ ]*) [^ ]* (?<user>[^ ]*) \[(?<time>[^\]]*)\] '
    r'"(?<method>\S+)(?: +(?<path>[^ ]*) +\S*)?" '
    r'(?<code>[^ ]*) (?<size>[^ ]*)'
    r'(?: "(?<referer>[^\"]*)" "(?<agent>.*)")?$'
)


def _grep_engine():
    from fluentbit_tpu.core.engine import Engine

    e = Engine()
    f = e.filter("grep")
    f.set("regex", f"log {APACHE2}")
    f.set("tpu_batch_records", "1")
    ins = e.input("dummy")
    for x in e.inputs + e.filters:
        x.configure()
        x.plugin.init(x, e)
    return e, ins


def _log_chunk(n):
    from fluentbit_tpu.codec.events import encode_event

    ok = ('10.0.0.1 - frank [10/Oct/2000:13:55:36 -0700] '
          '"GET /a HTTP/1.1" 200 23 "http://r" "curl"')
    return b"".join(
        encode_event({"log": ok if i % 4 else f"kernel: oom {i}"},
                     float(i))
        for i in range(n))


def _lane_launches(name):
    from fluentbit_tpu.ops import fault

    return fault.lane(name).stats()["launches"]


@pytest.mark.mesh
def test_static_matches_dynamic_grep_chain(graph, monkeypatch):
    jax = pytest.importorskip("jax")
    if len(jax.devices()) < 2:
        pytest.skip("need a multi-device mesh")
    static = _chain(graph, "GrepFilter.process_batch")[
        "launches_per_segment"]
    from fluentbit_tpu.ops import device, fault

    monkeypatch.setenv("FBTPU_MESH", "1")
    monkeypatch.setenv("FBTPU_SEGMENT_RECORDS", "128")
    n, seg = 700, 128
    n_segments = -(-n // seg)
    # the "grep" lane is process-global: whatever breaker or shrunken
    # mesh another file of this worker left on it is not this test's
    fault.reset()
    try:
        e, ins = _grep_engine()
        chunk = _log_chunk(n)
        before = fault.snapshot().get("grep", {"launches": 0})
        e.input_log_append(ins, "bench", chunk)
        after = fault.snapshot().get("grep")
        ins.pool.drain()
    finally:
        fault.reset()
    assert e.filters[0].plugin._mesh is not None, (  # lane engaged
        device.status(), after)
    observed = after["launches"] - before["launches"]
    assert observed == n_segments * static, (
        f"analyzer says {static} launch(es)/segment × {n_segments} "
        f"segments, the lane counted {observed}: {before} -> {after}")


@pytest.mark.mesh
def test_static_matches_dynamic_flux_chain(graph, monkeypatch):
    jax = pytest.importorskip("jax")
    if len(jax.devices()) < 2:
        pytest.skip("need a multi-device mesh")
    from fluentbit_tpu.codec.events import encode_event
    from fluentbit_tpu.core.engine import Engine

    static = _chain(graph, "FluxState.absorb_batch")[
        "launches_per_segment"]
    e = Engine()
    f = e.filter("flux")
    for k, v in {"group_by": "tenant", "distinct_field": "user",
                 "topk_field": "user", "window": "tumbling 60",
                 "export_interval_sec": "0", "mesh": "on"}.items():
        f.set(k, v)
    ins = e.input("dummy")
    for x in e.inputs + e.filters:
        x.configure()
        x.plugin.init(x, e)
    assert e.filters[0].plugin.state._mesh is not None
    # one tenant → one group → the ×G loops run once; one chunk → one
    # absorbed segment
    raw = b"".join(
        encode_event({"tenant": "a", "user": f"u{i % 13}", "size": i},
                     float(i))
        for i in range(256))
    before = _lane_launches("flux")
    e.input_log_append(ins, "t", raw)
    observed = _lane_launches("flux") - before
    assert observed == static, (
        f"analyzer says {static} launches per absorbed segment, the "
        f"flux lane counted {observed}")


@pytest.mark.mesh
def test_static_matches_dynamic_rewrite_tag_chain(graph, monkeypatch):
    # the platform gate forced open (a CPU backend serves on the native
    # twin): one launch a staged segment, as the analyzer says
    pytest.importorskip("jax")
    from fluentbit_tpu.codec.events import encode_event
    from fluentbit_tpu.core.engine import Engine
    from fluentbit_tpu.ops import device

    static = _chain(graph, "RewriteTagFilter.process_batch")[
        "launches_per_segment"]
    assert device.wait(120)
    monkeypatch.setattr(device, "platform", lambda: "tpu")
    monkeypatch.setenv("FBTPU_SEGMENT_RECORDS", "128")
    n, seg = 700, 128
    e = Engine()
    rt = e.filter("rewrite_tag")
    rt.set("rule", "$log ^alpha routed.alpha false")
    rt.set("rule", "$log ERROR routed.error false")
    ins = e.input("dummy")
    for x in e.inputs + e.filters:
        x.configure()
        x.plugin.init(x, e)
    raw = b"".join(
        encode_event({"log": f"ERROR {i}" if i % 2 else f"alpha {i}"},
                     float(i))
        for i in range(n))
    before = _lane_launches("grep")
    e.input_log_append(ins, "t", raw)
    assert e.filters[0].plugin.raw_timings["device_records"] == n
    assert _lane_launches("grep") - before == -(-n // seg) * static


@pytest.mark.mesh
def test_static_matches_dynamic_host_only_chains():
    # parser-regex, and rewrite_tag on a CPU backend (the native twin
    # serves): no lane anywhere may tick while they process a batch
    pytest.importorskip("jax")
    from fluentbit_tpu.codec.events import encode_event
    from fluentbit_tpu.core.engine import Engine
    from fluentbit_tpu.ops import fault

    def total_launches():
        return sum(ln.stats()["launches"]
                   for ln in fault.lanes().values())

    e = Engine()
    e.parser("rp", format="regex", regex=r"^(?<w>ERROR) (?<n>\d+)$")
    pf = e.filter("parser")
    pf.set("key_name", "log")
    pf.set("parser", "rp")
    rt = e.filter("rewrite_tag")
    rt.set("rule", "$log ^alpha routed.alpha false")
    ins = e.input("dummy")
    for x in e.inputs + e.filters:
        x.configure()
        x.plugin.init(x, e)
    raw = b"".join(
        encode_event({"log": f"ERROR {i}" if i % 2 else f"alpha {i}"},
                     float(i))
        for i in range(64))
    before = total_launches()
    e.input_log_append(ins, "t", raw)
    ins.pool.drain()
    assert total_launches() - before == 0
