"""The grep-tenants configuration at a small size on the CPU: 50
``Exclude`` rules on ``log`` (``benchmark/configs/grep-tenants.conf``:
``BASELINE.json`` config 5's filter_grep at the top of its rule sweep)
as the ``GrepProgram`` the filter builds — nine scan children at R < 64,
one stride a child and no child's laid-out tables over 16 MiB — against
Python's ``re``; at the sweep's other sizes (1, 20); the partition
itself (``partition_children``: the budget kept, every rule in one child,
the verdict in file order and bit-equal whatever the budget, a name a
child, the other configurations' programs laid out as they were);
``process_batch`` on the configuration's own pipeline
file against the host chain's bytes; what the program decides
(``decision()``), what the staged launch counts (``d2h_bytes``,
``scan_elements``) and what the corpus maker promises
(``benchmark/corpora/tenant_lines.py``: labels by construction equal to
the reference's verdict). The platform gate is forced open, as
``tests/test_rewrite_tag.py`` does: the kernels run on the CPU backend.
"""

import collections
import importlib.util
import json
import os
import re
import sys

import numpy as np
import pytest

from fluentbit_tpu.codec.events import decode_events, encode_event
from fluentbit_tpu.config_format import load_config_file
from fluentbit_tpu.core.chunk_batch import RawChunk
from fluentbit_tpu.core.plugin import registry
from fluentbit_tpu.ops import device
from fluentbit_tpu.ops import grep as grep_ops
from fluentbit_tpu.ops.grep import GrepProgram, program_for, scan_steps
from fluentbit_tpu.ops.mesh import replicated_table_bytes

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
CONF = os.path.join(BENCH, "configs", "grep-tenants.conf")
SWEEP = (1, 20, 50)     # configs/grep-tenants.json: rule_axis.sizes


def bench_module(folder: str, stem: str):
    """A file of the benchmark by path (it imports ``wire`` and
    ``lookup`` from its own directory)."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    path = os.path.join(BENCH, folder, stem + ".py")
    spec = importlib.util.spec_from_file_location(
        "tenants_" + stem.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def grep_props() -> list:
    """The ``[FILTER]`` section's properties, in file order."""
    section = next(s for s in load_config_file(CONF).sections
                   if s.name == "filter")
    return [(k, v) for k, v in section.properties
            if k.lower() not in ("name", "match")]


RULES = [tuple(v.split(None, 1)) for k, v in grep_props()
         if k.lower() == "exclude"]          # (field, pattern), file order
PATTERNS = tuple(p for _f, p in RULES)
PLANE_OF = (0,) * len(RULES)    # one staged plane: every rule reads log


@pytest.fixture(scope="module")
def maker():
    return bench_module("corpora", "tenant_lines")


@pytest.fixture(scope="module")
def corpus(maker):
    """2,048 lines: 49 of 257-500 B, two over 512, every tenant's noise."""
    return maker.make(2048, 3400000101, {})


@pytest.fixture(scope="module")
def program():
    assert device.wait(120)
    prog = program_for(PATTERNS, 512, plane_of=PLANE_OF)
    assert prog.try_ready()
    return prog


@pytest.fixture
def gate_open(monkeypatch):
    """The selection points take the device path on the CPU backend, on
    one device: the mesh would take the eight virtual ones."""
    assert device.wait(120)
    monkeypatch.setattr(device, "platform", lambda: "tpu")
    monkeypatch.setenv("FBTPU_MESH", "off")


@pytest.fixture
def seen(monkeypatch):
    """``filter_grep``'s spans as ``(name, ids)``, in the order they
    were opened, ``set_metadata``'s ids among them."""
    from fluentbit_tpu.plugins import filter_grep

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

    monkeypatch.setattr(filter_grep, "span", Recorded)
    return seen


def pick(records, n: int) -> list:
    """``n`` row indices: every line over 256 B first, then the rest in
    order."""
    tail = [i for i, r in enumerate(records) if len(r["log"]) > 256]
    rest = [i for i in range(len(records)) if len(records[i]["log"]) <= 256]
    return sorted((tail + rest)[:n])


# ---------------------------------------------------- the 50 rules

def test_the_file_holds_config_5s_rule_and_49_more_on_log():
    assert len(RULES) == 50 == max(SWEEP)
    assert {f for f, _p in RULES} == {"log"}
    assert RULES[0] == ("log", "health-check")     # conf/baseline5-k8s.conf
    with open(os.path.join(REPO, "conf", "baseline5-k8s.conf")) as f:
        assert "Exclude log health-check" in f.read()
    assert len(set(PATTERNS)) == 50
    with open(os.path.join(BENCH, "configs", "grep-tenants.json")) as f:
        cfg = json.load(f)
    assert tuple(cfg["rule_axis"]["sizes"]) == SWEEP
    assert sorted(cfg["rule_axis"]["sizes_from"]) == sorted(map(str, SWEEP))
    assert cfg["assumed_rules"] == [f"{f} {p}" for f, p in RULES]
    assert cfg["corpus"]["params"]["tenants"] == 50
    props = dict((k.lower(), v) for k, v in grep_props()
                 if k.lower() != "exclude")
    assert props == {"tpu_max_record_len": "512"}   # legacy mode, no option


@pytest.mark.parametrize("r", range(50))
def test_rule_compiles_to_a_dfa_that_agrees_with_re(r, maker, program):
    """Inside the DFA class, the same to ``re`` on the corpus's lines,
    and met by its own witness."""
    import random

    _field, pattern = RULES[r]
    dfa = program.dfas[r]
    assert dfa.pattern == pattern and dfa.n_states > 2
    rx = re.compile(pattern)
    rng = random.Random(r)
    lines = ["".join(maker.WITNESS[r](rng, i)[:2]) for i in range(12)]
    assert all(rx.search(ln) for ln in lines), pattern
    lines += ["".join(w(rng, r)[:2]) for w in maker.WITNESS]
    lines += ["".join(nm(rng, i)[:2]) for nm in maker.NORMAL
              for i in range(4)]
    assert not any(rx.search("".join(nm(rng, i)[:2]))
                   for nm in maker.NORMAL for i in range(20)), pattern
    for ln in lines:
        assert dfa.match_bytes(ln.encode()) == bool(rx.search(ln)), \
            (pattern, ln)


def test_no_two_rules_are_the_same_automaton(program):
    shapes = {(d.n_states, d.n_classes, d.trans.tobytes(),
               d.class_map.tobytes()) for d in program.dfas}
    assert len(shapes) == 50


#: the scan children the constructor builds at each size of the sweep,
#: ``(k, rules)`` in launch order — one stride a child, a stride's rules
#: by table size in children of at most 16 MiB as laid out: PERF.md
#: section 4, ``grep-tenants``
CHILDREN = {1: [(4, 1)],
            20: [(2, 2), (3, 4), (3, 7), (3, 3), (4, 4)],
            50: [(2, 5), (3, 4), (3, 5), (3, 6), (3, 11), (3, 12),
                 (4, 4), (4, 2), (5, 1)]}
#: the same lists with one child a stride (``child_budget`` set high):
#: the layout up to PR 40
PER_STRIDE = {1: [(4, 1)], 20: [(2, 2), (3, 14), (4, 4)],
              50: [(2, 5), (3, 38), (4, 6), (5, 1)]}
NO_BUDGET = 1 << 40


@pytest.mark.parametrize("size", SWEEP)
def test_program_decision_is_what_perf_md_says(size, program):
    """R < 64: the first ``size`` rules of the file in scan children,
    each rule at its own stride, each child within the table budget
    (PERF.md section 4); the elements a launch steps through are the
    per-stride layout's to the element."""
    prog = program if size == 50 else GrepProgram(
        program.dfas[:size], 512, plane_of=PLANE_OF[:size])
    d = prog.decision()
    by_k = collections.Counter(r["k"] for r in d["rules"])
    assert sorted(by_k.items()) == PER_STRIDE[size]
    children = prog._children or [prog]
    assert [(c.k, len(c.dfas)) for c in children] == CHILDREN[size]
    assert d["k_groups"] == [k for k, _n in CHILDREN[size]]
    assert [(c["k"], c["rules"]) for c in d["children"]] == CHILDREN[size]
    assert [c["table_bytes"] for c in d["children"]] \
        == [c.table_bytes for c in children]
    assert prog.n_planes == 1 and prog.plane_of == PLANE_OF[:size]
    assert prog.scan_elements(4096, 512) == 4096 * sum(
        n * scan_steps(512, k) for k, n in PER_STRIDE[size])
    if size == 50:
        assert len(by_k) >= 3
        assert d["max_states"] == 80
        assert max(r["class_runs"] for r in d["rules"]) == 50
        assert d["kernel_resolved"] == "scan"
        assert prog.scan_elements(4096, 512) == 35631104
        assert prog.scan_elements(4096, 256) == 17997824
        # 94 MB of tables where one child a stride laid out 169.5
        assert [round(c["table_bytes"] / 1e6, 1) for c in d["children"]] \
            == [0.9, 15.2, 15.8, 16.1, 16.3, 9.6, 14.6, 3.2, 2.4]


# ------------------------------------------------- the partition

def rules_of_children(prog) -> list:
    """Each child's rules as indices into the parent's list (file
    order), by identity: a child holds the parent's DFA objects."""
    at = {id(d): i for i, d in enumerate(prog.dfas)}
    return [[at[id(d)] for d in c.dfas]
            for c in prog._children or [prog]]


def laid_out(size: int, budget: int) -> GrepProgram:
    dfas = program_for(PATTERNS, 512, plane_of=PLANE_OF).dfas[:size]
    return GrepProgram(dfas, 512, plane_of=PLANE_OF[:size],
                       child_budget=budget)


@pytest.mark.parametrize("size", SWEEP)
@pytest.mark.parametrize("mib", [1, 8, 16, 32, 48])
def test_every_child_is_within_the_budget_or_one_rule(size, mib):
    """The budget bounds a child's tables as laid out — ``R_c`` times the
    widest, and the class runs: the whole pytree as the mesh weighs it
    — unless the child is one rule, which is always a child; and what
    ``partition_children`` reckons from shapes is what was built."""
    prog = laid_out(size, mib << 20)
    children = prog._children or [prog]
    assert sum(len(c.dfas) for c in children) == size
    for c in children:
        assert c._children is None
        assert c.table_bytes == replicated_table_bytes(c._np) \
            == grep_ops.laid_out_bytes(
                len(c.dfas),
                max(d.n_states * d.n_classes ** c.k for d in c.dfas),
                max(c._class_runs))
        assert c.table_bytes <= mib << 20 or len(c.dfas) == 1
    for c, idxs in zip(children, rules_of_children(prog)):
        assert {prog.k_by_rule[i] for i in idxs} == {c.k}
    assert prog.table_bytes == sum(c.table_bytes for c in children)
    # a smaller budget never lays out more bytes than one child a stride
    assert prog.table_bytes <= laid_out(size, NO_BUDGET).table_bytes
    if mib == 16:
        assert grep_ops._CHILD_TABLE_BUDGET == 16 << 20
        assert [(c.k, len(c.dfas)) for c in children] == CHILDREN[size]


@pytest.mark.parametrize("mib", [1, 8, 16, 48])
def test_each_rule_is_in_one_child_and_a_strides_children_go_by_size(mib):
    """Every rule of the file in exactly one child; the children of a
    stride ordered by table size, the widest rules first; a child's
    rules in file order."""
    prog = laid_out(50, mib << 20)
    where = rules_of_children(prog)
    assert sorted(i for idxs in where for i in idxs) == list(range(50))
    assert all(idxs == sorted(idxs) for idxs in where)
    assert (np.argsort(np.concatenate(where)) == prog._inv_perm).all()
    size = [d.n_states * d.n_classes ** k
            for d, k in zip(prog.dfas, prog.k_by_rule)]
    for (a, ia), (b, ib) in zip(zip(prog._children, where),
                                zip(prog._children[1:], where[1:])):
        assert a.k <= b.k
        if a.k == b.k:
            assert min(size[i] for i in ia) >= max(size[i] for i in ib)


@pytest.mark.parametrize("mib,n_children", [(16, 9), (8, 15), (1, 42)])
def test_a_name_a_child_and_a_strides_first_keeps_the_strides_name(
        mib, n_children):
    """The trace's readers sum a launch by module name
    (``readers/element_cost.py::launch_seconds``): two children under
    one name would be read as one. The first child of a stride is named
    as a stride's one child always was, the later ones ``_c1``, ``_c2``
    … after it; the mesh's names follow."""
    prog = laid_out(50, mib << 20)
    names = [c.program_name() for c in prog._children]
    assert len(names) == n_children == len(set(names))
    assert names == [c["name"] for c in prog.decision()["children"]]
    seen = collections.Counter()
    for c, name in zip(prog._children, names):
        n = seen[c.k]
        seen[c.k] += 1
        assert name == f"grep_scan_S{c.max_states}_k{c.k}" \
            + (f"_c{n}" if n else "")
        assert c.program_name("_mesh") == name + "_mesh"
    if mib == 16:
        assert names == [
            "grep_scan_S80_k2", "grep_scan_S58_k3", "grep_scan_S74_k3_c1",
            "grep_scan_S52_k3_c2", "grep_scan_S41_k3_c3",
            "grep_scan_S37_k3_c4", "grep_scan_S32_k4",
            "grep_scan_S14_k4_c1", "grep_scan_S10_k5"]


def test_one_child_a_stride_is_named_as_it_was():
    """With no budget to speak of the layout and the names are PR 40's."""
    prog = laid_out(50, NO_BUDGET)
    assert [(c.k, len(c.dfas)) for c in prog._children] == PER_STRIDE[50]
    assert [c.program_name() for c in prog._children] == [
        "grep_scan_S80_k2", "grep_scan_S74_k3", "grep_scan_S32_k4",
        "grep_scan_S10_k5"]
    assert round(prog._children[1].table_bytes / 1e6, 1) == 144.3


#: the programs of the benchmark's other configurations: no child of
#: theirs comes near the budget, so each is laid out — name, stride,
#: rules — as on the commit before the budget (PR 40)
OTHER_PROGRAMS = {
    "grep-apache2": ("grep", [("grep_scan_S690_k3", 3, 1),
                              ("grep_scan_S10_k5", 5, 1)]),
    "rewrite-syslog": ("rewrite_tag", [("grep_scan_S12_k4", 4, 1),
                                       ("grep_scan_S9_k5", 5, 2),
                                       ("grep_scan_S7_k6", 6, 5)]),
    "nexmark-q5": ("grep", [("grep_scan_S7_k6", 6, 1)]),
}


@pytest.mark.parametrize("conf", sorted(OTHER_PROGRAMS))
def test_the_other_configurations_programs_are_laid_out_as_they_were(conf):
    plugin, want = OTHER_PROGRAMS[conf]
    path = os.path.join(BENCH, "configs", conf + ".conf")
    section = next(s for s in load_config_file(path).sections
                   if s.name == "filter"
                   and s.get("name").lower() == plugin)
    ins = registry.create_filter(plugin)
    for k, v in section.properties:
        if k.lower() != "name":
            ins.set(k, v)
    ins.configure()
    ins.plugin.init(ins, None)
    prog = ins.plugin._program
    children = prog._children or [prog]
    assert [(c.program_name(), c.k, len(c.dfas)) for c in children] == want
    assert [(c["name"], c["k"], c["rules"])
            for c in prog.decision()["children"]] == want
    # rules in file order inside a child, as one child a stride had them
    assert all(idxs == sorted(idxs) for idxs in rules_of_children(prog))
    assert max(c.table_bytes for c in children) < 8 << 20


@pytest.mark.parametrize("L,k,steps", [(512, 2, 257), (256, 2, 129),
                                       (256, 3, 87), (256, 5, 53),
                                       (512, 3, 172), (512, 4, 129)])
def test_scan_steps_are_the_strides_and_one_of_eol(L, k, steps):
    assert scan_steps(L, k) == steps == -(-L // k) + 1


# ------------------------------------------- the program against re

def staged(records, rows, L: int):
    """The plane ``[1, B, L]`` and lengths (-1 missing, -2 longer than
    L) as ``staged_match`` stages them."""
    planes = np.zeros((1, len(rows), L), dtype=np.uint8)
    lengths = np.full((1, len(rows)), -1, dtype=np.int32)
    for j, i in enumerate(rows):
        v = records[i].get("log")
        if v is None:
            continue
        b = v.encode()
        if len(b) > L:
            lengths[0, j] = -2
            continue
        planes[0, j, :len(b)] = np.frombuffer(b, dtype=np.uint8)
        lengths[0, j] = len(b)
    return planes, lengths


@pytest.mark.parametrize("L", [256, 512])
def test_50_rules_on_one_plane_equal_re(L, corpus, program):
    rows = pick(corpus[0], 256)
    records = [dict(r) for r in corpus[0]]
    for j, i in enumerate(rows):          # a missing key
        if j % 11 == 5:
            del records[i]["log"]
    planes, lengths = staged(records, rows, L)
    got = program.match(planes, lengths)
    assert got.shape == (50, 256) and got.dtype == bool
    want = np.zeros_like(got)
    for r, (_field, pattern) in enumerate(RULES):
        rx = re.compile(pattern)
        for j, i in enumerate(rows):
            v = records[i].get("log")
            want[r, j] = v is not None and len(v) <= L \
                and rx.search(v) is not None
    assert (got == want).all(), np.argwhere(got != want)[:5]
    over = int((lengths[0] == -2).sum())    # overflow rows: never a match
    assert over == sum(len(records[i].get("log", "")) > L for i in rows) \
        and (over > 40 if L == 256 else over == 2)
    assert not got[:, lengths[0] < 0].any()
    assert got.any(axis=1).sum() >= 20     # the hot tenants' rules fire


@pytest.mark.parametrize("mib", [1, 4])
def test_the_verdict_is_the_same_whatever_the_budget(mib, corpus):
    """A small list under a budget that splits its strides (20 rules:
    1 MiB makes 17 children of them, 4 MiB twelve) against the same
    rules one child a stride (three children) and against ``re``, rule
    by rule in file order: the frame staged whole at L=512, and in two
    groups — the main group at L=256 with the long rows as rows without
    a value, those as a ``LongGroup`` at L=512 — as ``staged_match``
    sends a 4,096-row frame."""
    from fluentbit_tpu.plugins.filter_grep import LongGroup

    split, per_stride = laid_out(20, mib << 20), laid_out(20, NO_BUDGET)
    assert [(c.k, len(c.dfas)) for c in per_stride._children] \
        == PER_STRIDE[20]
    assert len(split._children) == {1: 17, 4: 12}[mib]
    records = corpus[0]
    rows = pick(records, 320)             # 49 of 257-500 B, two over 512
    planes, lengths = staged(records, rows, 512)
    want = np.array([[len(records[i]["log"]) <= 512
                      and re.search(p, records[i]["log"]) is not None
                      for i in rows] for p in PATTERNS[:20]])
    assert want.any(axis=1).sum() >= 8
    whole = split.match(planes, lengths)
    assert whole.shape == (20, 320) and whole.dtype == bool
    assert (whole == per_stride.match(planes, lengths)).all()
    assert (whole == want).all(), np.argwhere(whole != want)[:5]

    at = np.flatnonzero(lengths[0] > 256).astype(np.int32)
    assert at.size == 49
    group = LongGroup.of([(planes[0], lengths[0])], at, 512, 320)
    main_len = lengths.copy()
    main_len[0, at] = -1
    main = np.ascontiguousarray(planes[:, :, :256])
    for prog in (split, per_stride):
        two = np.asarray(prog.dispatch(main, main_len, long=group[:3]))
        assert (two == want).all(), np.argwhere(two != want)[:5]
    first = np.asarray(split.dispatch(planes, lengths, first_match=True))
    assert (first == np.where(want.any(axis=0),
                              want.argmax(axis=0), -1)).all()


def test_the_smokes_probe_says_what_each_child_costs(corpus, monkeypatch):
    """``chip_smoke.py --rules-sweep … --child-budgets``: a line a
    child — name, stride, rules, laid-out table bytes, ms a launch, ns
    an element — and one for the whole launch, its verdicts held to
    ``re``; here on the CPU at 512 rows (the numbers are the host's)."""
    import chip_smoke

    said = []
    monkeypatch.setattr(chip_smoke, "say", lambda **kw: said.append(kw))
    monkeypatch.setattr(chip_smoke, "SEGMENT", 512)
    assert device.wait(120)
    prog = laid_out(8, 1 << 20)
    assert prog.try_ready()
    values = [r["log"].encode() for r in corpus[0]]
    chip_smoke.child_probe(prog, PATTERNS[:8], values, budget_mib=1)
    lines = [kw for kw in said if kw["stage"] == "rules_sweep:child"]
    assert [(ln["name"], ln["k"], ln["rules"], ln["table_bytes"])
            for ln in lines] \
        == [(c["name"], c["k"], c["rules"], c["table_bytes"])
            for c in prog.decision()["children"]]
    assert len(lines) == 7 and len({ln["name"] for ln in lines}) == 7
    for ln, c in zip(lines, prog._children):
        assert ln["budget_mib"] == 1 and ln["list_rules"] == 8
        assert ln["elements"] == c.scan_elements(512, 256) \
            + c.scan_elements(256, 512)
        assert ln["launch_ms"] > 0 and ln["ns_per_element"] > 0
    (launch,) = [kw for kw in said if kw["stage"] == "rules_sweep:launch"]
    assert launch["children"] == 7 and launch["equal"] is True
    assert launch["elements"] == sum(ln["elements"] for ln in lines)
    assert launch["table_bytes"] == prog.table_bytes


# --------------------------- the filter on the configuration's file

def make_filter(extra=()):
    ins = registry.create_filter("grep")
    for k, v in grep_props() + list(extra):
        ins.set(k, v)
    ins.configure()
    ins.plugin.init(ins, None)
    return ins.plugin


@pytest.mark.parametrize("rows_n", [256, 100])
def test_process_batch_equals_the_host_chain(rows_n, corpus, gate_open):
    records, labels = corpus
    rows = pick(records, rows_n)
    data = b"".join(encode_event(records[i], float(i)) for i in rows)
    events = decode_events(data)
    dev = make_filter()
    assert dev._program is not None and len(dev._program._children) == 9
    assert dev.can_process_batch()
    n_keep, out = dev.process_batch(RawChunk(data, "kube.tenants", rows_n))
    host = make_filter([("tpu.enable", "off")])
    assert host._program is None
    _res, kept = host.filter(list(events), "kube.tenants", None)
    assert bytes(out) == b"".join(e.raw for e in kept)
    assert n_keep == len(kept) == sum(labels[i] & 1 for i in rows)
    assert 0 < n_keep < rows_n

    # what the staged launch counted, from the shapes it had
    tm = dev.raw_timings
    Bp = 256                              # ops.batch.bucket_size's rung
    assert tm["records"] == tm["device_records"] == rows_n
    assert tm["overflow_rows"] == sum(labels[i] >> 1 & 1 for i in rows) == 2
    assert tm["h2d_bytes"] == Bp * (512 + 4)
    assert tm["d2h_bytes"] == 50 * Bp                    # 50 B a row
    assert tm["scan_elements"] == dev._program.scan_elements(Bp, 512) \
        == Bp * sum(n * scan_steps(512, k) for k, n in PER_STRIDE[50])


def test_dispatch_and_verdict_spans_say_what_was_launched(corpus, gate_open,
                                                          seen):
    records = corpus[0]
    rows = pick(records, 64)
    data = b"".join(encode_event(records[i], float(i)) for i in rows)
    make_filter().process_batch(RawChunk(data, "kube.tenants", 64))
    got = dict(seen)
    # 64 rows: too few to send the long ones apart, one width
    assert got["grep.stage"] == {"seg": 0, "L": 512}
    assert got["grep.dispatch"] == {"rules": 50, "planes": 1, "L": 512}
    assert got["grep.verdict"] == {"rules": 50}
    names = [n for n, _ids in seen]
    assert names.index("grep.force") < names.index("grep.verdict")


def test_a_frame_of_4096_lines_sends_its_long_lines_apart(maker, gate_open,
                                                          seen):
    """A whole frame of the cell: about a hundred of its 4,096 lines are
    257-500 B, and they no longer set the width of all of them — the
    frame stages at L=256 and they at L=512 as a 256-row group of their
    own, in one launch (20.2 M gathered elements where the whole frame
    at L=512 was 35.6 M); the bytes are the host chain's."""
    from fluentbit_tpu.ops import fault

    records, labels = maker.make(4096, 3400000103, {})
    data = b"".join(encode_event(r, float(i))
                    for i, r in enumerate(records))
    long_lines = sum(256 < len(r["log"]) <= 512 for r in records)
    assert 90 < long_lines < 110
    dev = make_filter()
    before = fault.lane("grep").stats()
    n_keep, out = dev.process_batch(RawChunk(data, "kube.tenants", 4096))
    after = fault.lane("grep").stats()
    assert after["launches"] - before["launches"] == 1 \
        == after["ok"] - before["ok"]
    host = make_filter([("tpu.enable", "off")])
    _res, kept = host.filter(decode_events(data), "kube.tenants", None)
    assert bytes(out) == b"".join(e.raw for e in kept)
    assert n_keep == len(kept) == sum(lb & 1 for lb in labels)

    tm, prog = dev.raw_timings, dev._program
    assert tm["split_launches"] == 1 and tm["long_rows"] == long_lines
    assert tm["overflow_rows"] == 4
    assert tm["h2d_bytes"] == 4096 * (256 + 4) + 256 * (512 + 4 + 4)
    assert tm["d2h_bytes"] == 50 * 4096                   # as it was
    assert prog.scan_elements(4096, 512) == 35631104      # the whole frame
    assert tm["scan_elements"] == 20224768 \
        == prog.scan_elements(4096, 256) + prog.scan_elements(256, 512)
    got = dict(seen)
    widths = {"L": 256, "L_long": 512, "long": long_lines}
    assert got["grep.stage"] == {"seg": 0, **widths}
    assert got["grep.dispatch"] == {"rules": 50, "planes": 1, **widths}


def test_one_fused_program_counts_every_rule_at_the_least_stride(program):
    """What ``FBTPU_MESH_RULE_SHARD_R`` (64, untouched) would make of a
    longer list: 64 rules — these 50 and 14 of them again — ride one
    program at k=2, and ``scan_elements`` says so."""
    dfas = program.dfas + program.dfas[:14]
    fused = GrepProgram(dfas, 512, plane_of=(0,) * 64)
    assert fused._children is None and fused.k == 2
    assert fused.scan_elements(4096, 512) == 64 * 4096 * 257 == 67371008


# ----------------------------------------------------- the corpus

@pytest.fixture(scope="module")
def reference():
    return bench_module("reference", "grep-tenants")


@pytest.mark.parametrize("seed", [3400000102, 7])
def test_labels_by_construction_equal_the_reference(seed, maker, reference):
    records, labels = maker.make(4096, seed, {})
    rules = reference.rules_of(CONF)
    assert len(rules) == 50 and all(ex and f == "log"
                                    for ex, f, _p in rules)
    assert bytes(reference.keep(rules, r) for r in records) \
        == bytes(lb & 1 for lb in labels)
    assert [i for i, lb in enumerate(labels) if lb & 2] \
        == [i for i, r in enumerate(records) if len(r["log"]) > 512] \
        == [999, 1999, 2999, 3999]


def test_the_mix_is_exact_and_only_its_order_is_the_seeds(maker):
    params = {"shift_every": 4096, "shift_by": 16}
    a_rec, a_lab = maker.make(8192, 1, params)
    b_rec, b_lab = maker.make(8192, 2, params)
    assert a_lab != b_lab
    for bit in (1, 2):                    # kept, and overflow rows
        assert sum(lb & bit for lb in a_lab) == sum(lb & bit for lb in b_lab)
    share = sum(lb & 1 for lb in a_lab) / len(a_lab)
    assert share == pytest.approx(0.70, abs=0.005)
    for recs in (a_rec, b_rec):
        for i, r in enumerate(recs):
            n = len(r["log"])
            assert list(r) == ["log", "stream", "kubernetes_namespace_name",
                               "kubernetes_pod_name",
                               "kubernetes_container_name", "kubernetes_host"]
            if i % 1000 == 999:
                assert 600 <= n <= 2000
            elif i % 40 == 39:
                assert 257 <= n <= 500
            else:
                assert 40 <= n <= 250
    # Zipf over the ranks, and the hot set moves on by 16 tenants a block
    for recs in (a_rec, b_rec):
        for block, hot in ((0, "tenant-00"), (1, "tenant-16")):
            ns = collections.Counter(
                r["kubernetes_namespace_name"]
                for r in recs[block * 4096:(block + 1) * 4096])
            assert ns.most_common(1)[0][0] == hot
            assert 0.2 < ns[hot] / 4096 < 0.3
            assert len(ns) == 50 and all(
                re.fullmatch(r"tenant-[0-4]\d", k) for k in ns)
    assert sum(1 for i in range(4096) if i % 40 == 39) == 102


# ------------------------------------------------ the new reader

def test_ns_per_element_reader_takes_whole_launches(monkeypatch):
    """Five launches of which the traced interval cut the first and the
    last: a launch is the mean of the module's whole events, over the
    elements of one launch (the counter over the launches dispatched)."""
    reader = bench_module("readers", "element_cost")
    whole = [698_300_000, 698_100_000, 698_500_000]
    events = [("jit_grep_scan_S80_k2(1)", 0, 300_000_000)] + [
        ("jit_grep_scan_S80_k2(1)", 310_000_000 + i * 730_000_000, d)
        for i, d in enumerate(whole)] + [
        ("jit_grep_scan_S80_k2(1)", 2_920_000_000, 280_000_000),
        ("jit_grep_merge(2)", 10, 5_000)]
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [e + ({},) for e in events]},
        {"name": "XLA Ops", "events": [("%fusion.14", 0, 3_000_000_000, {})]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "t", "events": [("fbtpu:grep_scan", 0, 9, {})]}]}]
    assert reader.launch_seconds(planes, "grep_scan") \
        == pytest.approx(0.6983)
    # a split program: one module a child, the launch is their sum; two
    # length buckets under one name: the mean over both shapes
    planes[0]["lines"][0]["events"] += [
        ("jit_grep_scan_S74_k3(3)", t, d, {}) for t, d in
        ((0, 50_000_000), (1, 80_000_000), (2, 120_000_000),
         (3, 100_000_000), (4, 10_000_000))]
    assert reader.launch_seconds(planes, "grep_scan") \
        == pytest.approx(0.7983)
    # fewer than three events of a name: their median
    planes[0]["lines"][0]["events"] += [
        ("jit_grep_scan_S10_k5(4)", 0, 4_000_000, {}),
        ("jit_grep_scan_S10_k5(4)", 9, 6_000_000, {})]
    assert reader.launch_seconds(planes, "grep_scan") \
        == pytest.approx(0.8033)
    del planes[0]["lines"][0]["events"][-2:]

    import lookup          # the benchmark's: the reader's own loader

    spans = lookup.load_py("readers", "program_spans")
    monkeypatch.setattr(spans, "newest_xplane", lambda: "a.xplane.pb")
    monkeypatch.setattr(spans, "read_planes", lambda path: planes)
    counters = {"filter.grep.scan_elements": 5 * 67371008,
                "lane.grep.launches": 5}
    args = {"plugin": "grep", "lane": "grep", "module": "grep_scan"}
    assert reader.ns_per_element(
        {"trace": {"busy_s": 3.07, "counters": counters}}, **args) \
        == pytest.approx(1e9 * 0.7983 / 67371008)
    # the parent has no such counter; a rehearsal has no trace
    assert reader.ns_per_element(
        {"trace": {"busy_s": 3.07, "counters": {"lane.grep.launches": 5}}},
        **args) is None
    assert reader.ns_per_element({"trace": None}, **args) is None
    # the roofline over the same whole launch: rows a launch from the
    # launches that ended, the bytes bound from shapes
    import kernel_cost

    class Prog:
        n_planes = 1

        def decision(self):
            return {"rules": [{"s": 10, "c": 4, "k": 3}] * 50}

    class Plugin:
        name, _program = "grep", Prog()

    readings = {"trace": {"busy_s": 3.07, "counters": dict(
        counters, **{"lane.grep.ok": 4,
                     "filter.grep.device_records": 4 * 4096})},
        "filters": [Plugin()], "device": {"kind": "TPU v5 lite"}}
    need = kernel_cost.grep_match_bytes(
        Prog().decision()["rules"], 4096 * 516, 4096)
    peak = kernel_cost.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    roof = dict(args, plane_len=512)
    assert reader.match_roofline_share(readings, **roof) \
        == pytest.approx(100 * need / peak / 0.7983)
    assert reader.match_roofline_share({"trace": None}, **roof) is None
    readings["trace"]["counters"].pop("lane.grep.ok")
    assert reader.match_roofline_share(readings, **roof) is None
    monkeypatch.setattr(spans, "newest_xplane", lambda: None)
    assert reader.ns_per_element(
        {"trace": {"busy_s": 3.07, "counters": counters}}, **args) is None
