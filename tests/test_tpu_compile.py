"""The kernels of the main path, compiled for the chip — without one.

The TPU compiler is installed in the sandbox and compiles for a chip
that is *described*, not attached (guide: on-chip-measurement §2). These
tests lower the programs ``chip_smoke.py`` runs, at the shapes it runs
them, for a described ``v5e:2x2``: what the chip's compiler would refuse
(tiling, memory, partitioning) fails here at no chip time.

Rules this file keeps (each one has cost a whole tier-1 run somewhere):

- ONE file: only the xdist worker that is handed it loads libtpu.
- The topology is described inside a module-scoped fixture that skips
  when it cannot be described — never at import, never in conftest,
  never in a ``skipif``/``parametrize`` argument, never ``autouse``.
- Everything compiles in the test's own process (a child could not load
  the library its parent holds).
- ``device.platform()`` still answers "cpu" here, so the selection
  points would pick their CPU branches: the kernels are lowered with
  shapes directly instead of steering the selection through an option.
- The persistent compile cache is off around the compiles: a TPU
  executable written from here cannot be read back without a chip, and
  the next run would warn about every entry.
"""

import os
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from fluentbit_tpu.flux import kernels  # noqa: E402
from fluentbit_tpu.ops.grep import GrepProgram  # noqa: E402
from fluentbit_tpu.ops.sketch import (CountMin, HyperLogLog,  # noqa: E402
                                      build_sharded_cms, build_sharded_hll)
from fluentbit_tpu.regex.dfa import compile_dfa  # noqa: E402

#: chip_smoke.py's small Exclude rule (S=10, k=5); the apache2 rule is
#: read from conf/baseline1-grep.conf, so this compiles what ships
SMALL = r"curl/8\.5"


def _apache2():
    from fluentbit_tpu.config_format import load_config_file

    conf = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "conf", "baseline1-grep.conf")
    rule = next(s.get("regex") for s in load_config_file(conf).sections
                if s.name == "filter")
    return rule.split(None, 1)[1]


APACHE2 = _apache2()

SEGMENT = 4096      # filter_grep's segment (a bucket_size rung)
PUSH = 16384        # chip_smoke's records per append
FIELD_LEN = 256     # log_to_metrics / flux staged width
HLL_P = 14
CMS_SHAPE = (4, 16384)
GROUPS_PAD = 8      # flux's padded segment table for 4 tenants


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "skip"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    assert len(topo.devices) == 4
    return lambda axis: Mesh(np.asarray(topo.devices), (axis,))


def sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _program(pattern, kernel):
    return GrepProgram([compile_dfa(pattern)], 512, kernel=kernel)


def _table_shapes(prog, sharding):
    return {k: sds(v.shape, v.dtype, sharding)
            for k, v in prog._np.items() if v is not None}


# -- one chip: the grep children, per (Bp, L) bucket the smoke produces --

@pytest.mark.parametrize("length", [256, 512])
@pytest.mark.parametrize("pattern,kernel,impl,max_states", [
    (APACHE2, "scan", "_match_impl", 690),
    (SMALL, "assoc", "_match_assoc_impl", 10),
    (SMALL, "scan", "_match_impl", 10),
], ids=["apache2-scan", "small-assoc", "small-scan"])
def test_grep_child_compiles_for_one_chip(one_chip, pattern, kernel, impl,
                                          max_states, length):
    prog = _program(pattern, kernel)
    assert prog.max_states == max_states
    compiled = jax.jit(getattr(prog, impl)).lower(
        _table_shapes(prog, one_chip),
        sds((1, SEGMENT, length), jnp.uint8, one_chip),
        sds((1, SEGMENT), jnp.int32, one_chip)).compile()
    out = compiled.output_shardings
    assert out.device_set == {one_chip._device}
    # well inside one chip's 16 GB next to everything else it holds
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


#: BASELINE config 3's eight rewrite_tag rules (conf/baseline3-rewrite
#: .conf): all S <= 12, in three stride groups; scan on the chip since
#: PR 33, assoc by the constructor's argument only
CONFIG3 = ("sshd", "kernel:", r"systemd\[1\]", "ERROR", "WARN", "nginx",
           r"cron\[\d+\]", ".*OOM.*")


@pytest.mark.parametrize("length", [256, 512])
@pytest.mark.parametrize("k,n_rules", [(6, 5), (5, 2), (4, 1)],
                         ids=["k6x5", "k5x2", "k4x1"])
@pytest.mark.parametrize("kernel,impl", [("scan", "_match_impl"),
                                         ("assoc", "_match_assoc_impl")],
                         ids=["scan", "assoc"])
def test_config3_child_compiles_for_one_chip(one_chip, kernel, impl, k,
                                             n_rules, length):
    """rewrite-syslog's program: each per-stride child takes the ONE
    staged plane ``[1, B, L]`` and gathers its rules' inputs from it —
    on the scan kernel, which is what the chip runs, and on assoc, which
    the probe and the differential tests still build."""
    prog = GrepProgram([compile_dfa(p) for p in CONFIG3], 512,
                       kernel=kernel, plane_of=(0,) * len(CONFIG3))
    child = next(c for c in prog._children if c.k == k)
    assert len(child.dfas) == n_rules and child.n_planes == 1
    assert child.plane_of == (0,) * n_rules

    def step(tables, planes, lengths):
        return getattr(child, impl)(
            tables, *child._gather_planes(planes, lengths))

    compiled = jax.jit(step).lower(
        _table_shapes(child, one_chip),
        sds((1, SEGMENT, length), jnp.uint8, one_chip),
        sds((1, SEGMENT), jnp.int32, one_chip)).compile()
    assert compiled.output_shardings.device_set == {one_chip._device}
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def _tenants_patterns():
    """``benchmark/configs/grep-tenants.conf``'s 50 ``Exclude log``
    patterns, in file order."""
    from fluentbit_tpu.config_format import load_config_file

    conf = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "grep-tenants.conf")
    section = next(s for s in load_config_file(conf).sections
                   if s.name == "filter")
    return [v.split(None, 1)[1] for k, v in section.properties
            if k.lower() == "exclude"]


#: grep-tenants' nine scan children, ``(k, rules)`` in launch order
#: (``tests/test_grep_tenants.py::CHILDREN``)
TENANTS_CHILDREN = [(2, 5), (3, 4), (3, 5), (3, 6), (3, 11), (3, 12),
                    (4, 4), (4, 2), (5, 1)]


@pytest.mark.parametrize("length", [256, 512])
@pytest.mark.parametrize("at", range(len(TENANTS_CHILDREN)),
                         ids=["c%d-k%dx%d" % (i, k, n) for i, (k, n)
                              in enumerate(TENANTS_CHILDREN)])
def test_tenants_child_compiles_for_one_chip(one_chip, at, length):
    """grep-tenants' program (PR 34): 50 rules on one key, under
    ``FBTPU_MESH_RULE_SHARD_R``, so scan children — one stride a child
    and at most 16 MiB of laid-out tables (PR 41); each takes the one
    staged plane ``[1, B, L]`` and makes ``[R_c, B, L]`` of it on the
    device, every rule classed at its child's widest rule's
    breakpoints."""
    patterns = _tenants_patterns()
    prog = GrepProgram([compile_dfa(p) for p in patterns], 512,
                       plane_of=(0,) * len(patterns))
    assert [(c.k, len(c.dfas)) for c in prog._children] == TENANTS_CHILDREN
    child = prog._children[at]
    assert child.n_planes == 1 and child.table_bytes <= 16 << 20

    def step(tables, planes, lengths):
        return child._match_impl(
            tables, *child._gather_planes(planes, lengths))

    compiled = jax.jit(step).lower(
        _table_shapes(child, one_chip),
        sds((1, SEGMENT, length), jnp.uint8, one_chip),
        sds((1, SEGMENT), jnp.int32, one_chip)).compile()
    assert compiled.output_shardings.device_set == {one_chip._device}
    # [12, 4096, L/3 + 1] i32 super-symbols and their transpose at the
    # child of most rules: well inside one chip's 16 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("length", [256, 512])
def test_span_program_compiles_for_one_chip(one_chip, length):
    """parser-apache2's program: the two dependent scans over the one
    staged plane, the ``[L, B]`` reverse states between them, ``(ok[B],
    spans[B, 9, 2] i16)`` out."""
    from fluentbit_tpu.ops.grep import SpanProgram
    from fluentbit_tpu.regex import parse
    from fluentbit_tpu.regex.spans import compile_spans

    # its own program: the cached one drops its host tables (``_np``)
    # once another test of this process has dispatched it
    prog = SpanProgram(compile_spans(parse(APACHE2)), 512)
    compiled = jax.jit(prog._spans_impl).lower(
        {k: sds(v.shape, v.dtype, one_chip)
         for k, v in prog._np.items()},
        sds((SEGMENT, length), jnp.uint8, one_chip),
        sds((SEGMENT,), jnp.int32, one_chip)).compile()
    for sh in compiled.output_shardings:
        assert sh.device_set == {one_chip._device}
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_first_match_reduction_compiles_for_one_chip(one_chip):
    from fluentbit_tpu.ops.grep import grep_first_match

    compiled = jax.jit(grep_first_match).lower(
        sds((len(CONFIG3), SEGMENT), jnp.bool_, one_chip)).compile()
    assert compiled.output_shardings.device_set == {one_chip._device}


def test_kernel_selection_rule_matches_what_was_compiled():
    """``_resolve_kernel`` on an accelerator — the platform no CPU test
    process is attached to — picks scan on both sides of the old
    ``S <= 64`` line, as on the CPU: the (pattern, scan) pairs compiled
    above are what ships (the chip's probe, PERF.md PR 33)."""
    from fluentbit_tpu.ops import device

    was = device._platform
    device._platform = "tpu"
    try:
        assert _program(APACHE2, "auto")._resolve_kernel() == "scan"
        assert _program(SMALL, "auto")._resolve_kernel() == "scan"
        assert _program(SMALL, "assoc")._resolve_kernel() == "assoc"
    finally:
        device._platform = was
    assert _program(SMALL, "auto")._resolve_kernel() == "scan"  # cpu


# -- one chip: the sketches ---------------------------------------------

def test_hll_update_compiles_for_one_chip(one_chip):
    hll = HyperLogLog(p=HLL_P)
    compiled = jax.jit(hll._update_impl).lower(
        sds((hll.m,), jnp.int32, one_chip),
        sds((PUSH, FIELD_LEN), jnp.uint8, one_chip),
        sds((PUSH,), jnp.int32, one_chip)).compile()
    assert compiled.output_shardings.device_set == {one_chip._device}


def test_cms_update_compiles_for_one_chip(one_chip):
    cms = CountMin(*CMS_SHAPE)
    compiled = jax.jit(cms._update_impl).lower(
        sds(CMS_SHAPE, cms._dtype, one_chip),
        sds((PUSH, FIELD_LEN), jnp.uint8, one_chip),
        sds((PUSH,), jnp.int32, one_chip),
        sds((PUSH,), jnp.int32, one_chip)).compile()
    assert compiled.output_shardings.device_set == {one_chip._device}


def _fused_args(cms, place):
    """The fused absorb's flat argument list for one distinct column
    plus the count-min top-k, each placed by ``place(name)``."""
    m = 1 << HLL_P
    return [
        sds((PUSH,), jnp.int32, place("seg")),
        sds((PUSH,), jnp.int32, place("valid")),
        sds((PUSH, FIELD_LEN), jnp.uint8, place("batch")),
        sds((PUSH,), jnp.int32, place("lengths")),
        sds((GROUPS_PAD, m), jnp.int32, place("registers")),
        sds(CMS_SHAPE, cms._dtype, place("table")),
        sds((PUSH, FIELD_LEN), jnp.uint8, place("comp")),
        sds((PUSH,), jnp.int32, place("comp_len")),
    ]


def test_donating_fused_absorb_compiles_for_one_chip(one_chip):
    """``donate = plat not in (None, "cpu")``: the program a chip runs
    and no CPU test has ever built. The register stack must alias."""
    cms = CountMin(*CMS_SHAPE)
    fn = kernels.build_fused_absorb(None, GROUPS_PAD, 1, HLL_P, cms,
                                    donate=True)
    compiled = fn.lower(*_fused_args(cms, lambda _n: one_chip)).compile()
    stack_bytes = GROUPS_PAD * (1 << HLL_P) * 4
    assert compiled.memory_analysis().alias_size_in_bytes == stack_bytes


def test_count_only_fused_absorb_compiles_for_one_chip(one_chip):
    """The absorb of a state with no sketch (``nexmark-q5``: COUNT(*)
    GROUP BY an integer key): segment ids and validity in, the one
    512-slot count table out, nothing donated."""
    from fluentbit_tpu.flux.state import FluxState

    slots = FluxState._FUSED_MAX_GROUPS
    fn = kernels.build_fused_absorb(None, kernels._pad_segments(slots), 0,
                                    HLL_P, None, donate=True)
    compiled = fn.lower(sds((PUSH,), jnp.int32, one_chip),
                        sds((PUSH,), jnp.int32, one_chip)).compile()
    (counts,) = compiled.out_info
    assert counts.shape == (slots,) and counts.dtype == jnp.int32
    assert compiled.memory_analysis().alias_size_in_bytes == 0


# -- four chips: the mesh programs and their collectives ------------------

@pytest.mark.parametrize("with_counts,collective", [(True, True),
                                                    (False, False)],
                         ids=["counts-psum", "engine-countsfree"])
def test_grep_mesh_program_compiles_for_four_chips(mesh4, with_counts,
                                                   collective):
    mesh = mesh4("batch")
    prog = _program(APACHE2, "scan")
    prog._materialize()  # tables on the test's CPU backend: names+shapes
    fn, tsh, sh_b, sh_l, variant, donate_idx = prog._mesh_program(
        mesh, "auto", with_counts)
    assert variant == "batch" and donate_idx == (2,)
    tables = {k: sds(v.shape, v.dtype, tsh[k])
              for k, v in prog._tbl.items()}
    compiled = fn.lower(tables, sds((1, SEGMENT, 512), jnp.uint8, sh_b),
                        sds((1, SEGMENT), jnp.int32, sh_l)).compile()
    # the engine variant must stay free of the per-segment sync point
    assert ("all-reduce" in compiled.as_text()) is collective
    # the donated lengths shard aliases the i32 verdict shard
    assert compiled.memory_analysis().alias_size_in_bytes \
        == SEGMENT // 4 * 4


def _while_body(hlo: str) -> str:
    """The text of the computation the module's one ``while`` runs."""
    name = re.search(r"while\([^\n]*body=%?([\w.\-]+)", hlo).group(1)
    start = hlo.index(f"\n%{name} (")
    return hlo[start:hlo.index("\n}\n", start)]


@pytest.mark.parametrize("which", ["apache2-S10-k5", "tenants-k5x1"])
def test_one_rule_mesh_child_lays_its_table_out_once(mesh4, which):
    """A one-rule child's ``[1, N]`` table is an argument of the mesh
    program, and XLA made it 1-D inside the scan's ``while`` — every
    step, ``reduce.2 s32[N]``: 5.5 of the 7.7 ms of an apache2 launch a
    chip, 3.2 ms of a tenants launch (PERF.md, PRs 33 and 38).
    ``_match_impl`` hands the scan the 1-D table, made once a launch:
    no table-sized op is left in the loop's body."""
    mesh = mesh4("batch")
    if which == "apache2-S10-k5":
        child = _program(SMALL, "scan")
    else:
        patterns = _tenants_patterns()
        child = GrepProgram([compile_dfa(p) for p in patterns], 512,
                            plane_of=(0,) * len(patterns))._children[-1]
    assert len(child.dfas) == 1 and child.k == 5
    child.kernel_resolved, child._tbl = "scan", child._np  # names+shapes
    fn, tsh, sh_b, sh_l, variant, _donate = child._mesh_program(
        mesh, "auto", False)
    assert variant == "batch"
    tables = {k: sds(v.shape, v.dtype, tsh[k])
              for k, v in child._np.items()}
    hlo = fn.lower(tables, sds((1, SEGMENT, 512), jnp.uint8, sh_b),
                   sds((1, SEGMENT), jnp.int32, sh_l)).compile().as_text()
    n = child._np["trans_flat"].shape[1]
    body = _while_body(hlo)
    assert f"s32[{n}]" in hlo                 # the 1-D table exists,
    assert " reduce(" not in body             # and is made outside
    assert " copy(" not in body


@pytest.mark.parametrize("at", [1, 5, 6], ids=["k3x4", "k3x12", "k4x4"])
def test_a_tenants_child_that_divides_by_four_still_shards_rows(mesh4, at):
    """The three tenants children whose rule count divides the mesh
    (PR 41): within the child budget their tables, replicated four
    times, do not cross ``ops.mesh.TABLE_BUDGET``, so each compiles for
    the four chips as a ``batch`` program under a name of its own —
    rows sharded, the ``[R_c, N]`` table whole on every chip."""
    mesh = mesh4("batch")
    patterns = _tenants_patterns()
    child = GrepProgram([compile_dfa(p) for p in patterns], 512,
                        plane_of=(0,) * len(patterns))._children[at]
    assert (child.k, len(child.dfas)) == TENANTS_CHILDREN[at]
    assert len(child.dfas) % 4 == 0
    child.kernel_resolved, child._tbl = "scan", child._np  # names+shapes
    fn, tsh, sh_b, sh_l, variant, _donate = child._mesh_program(
        mesh, "auto", False)
    assert variant == "batch"
    tables = {k: sds(v.shape, v.dtype, tsh[k])
              for k, v in child._np.items()}
    compiled = fn.lower(tables, sds((1, SEGMENT, 512), jnp.uint8, sh_b),
                        sds((1, SEGMENT), jnp.int32, sh_l)).compile()
    hlo = compiled.as_text()
    assert child.program_name("_mesh") in hlo.split("\n", 1)[0]
    assert "s32[%d,%d]" % child._np["trans_flat"].shape in hlo
    (out,) = jax.tree_util.tree_leaves(compiled.out_info)
    assert out.shape == (len(child.dfas), SEGMENT)


@pytest.mark.parametrize("which", ["whole", "two-groups", "mesh"])
def test_a_frame_in_two_groups_is_one_module_over_one_table(
        one_chip, mesh4, which):
    """``GrepProgram.dispatch(..., long=...)`` (PR 39): a child scans a
    frame's main group and its 256 long rows in ONE module — two
    ``while``s over one copy of the table (a second program a child
    would fold the table's constant twice, 4.6 s a time for the tenants'
    k=3 child, and would read as two launches to whoever sums a launch's
    modules by name) — and scatters the long rows' verdicts into the
    mask, which keeps its shape. A frame without long rows runs the
    program it ran before, text for text, and the mesh program has no
    second group."""
    prog = GrepProgram([compile_dfa(p) for p in CONFIG3], 512,
                       plane_of=(0,) * len(CONFIG3))
    child = next(c for c in prog._children if c.k == 5)
    child._materialize()  # tables on the test's CPU backend, closed over
    table = "s32[%d,%d]" % child._tbl["trans_flat"].shape
    main = (sds((1, SEGMENT, 256), jnp.uint8, one_chip),
            sds((1, SEGMENT), jnp.int32, one_chip))
    if which == "whole":
        def parents(planes, lengths):  # _materialize's impl before PR 39
            return child._match_impl(
                child._tbl, *child._gather_planes(planes, lengths))

        parents.__name__ = child.program_name()
        lowered = child._jit.lower(*main)
        assert lowered.as_text() == jax.jit(parents).lower(*main).as_text()
        hlo = lowered.compile().as_text()
        assert len(re.findall(r" while\(", hlo)) == 1
        assert " scatter(" not in hlo
    elif which == "two-groups":
        long = (sds((1, 256, 512), jnp.uint8, one_chip),
                sds((1, 256), jnp.int32, one_chip),
                sds((256,), jnp.int32, one_chip))
        compiled = child._jit_long.lower(*main, *long).compile()
        hlo = compiled.as_text()
        assert hlo.count("HloModule ") == 1
        assert child.program_name() in hlo.split("\n", 1)[0]
        assert len(re.findall(r" while\(", hlo)) == 2
        assert len(re.findall(re.escape(table) + r"\S* constant\(",
                              hlo)) == 1
        assert " scatter(" in hlo
        (out,) = jax.tree_util.tree_leaves(compiled.out_info)
        assert out.shape == (2, SEGMENT) and out.dtype == jnp.bool_
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
    else:
        mesh = mesh4("batch")
        fn, tsh, sh_b, sh_l, variant, _donate = child._mesh_program(
            mesh, "auto", False)
        tables = {k: sds(v.shape, v.dtype, tsh[k])
                  for k, v in child._tbl.items()}
        hlo = fn.lower(tables, sds((1, SEGMENT, 512), jnp.uint8, sh_b),
                       sds((1, SEGMENT), jnp.int32, sh_l)).compile(
                           ).as_text()
        assert variant == "batch"
        assert len(re.findall(r" while\(", hlo)) == 1
        assert " scatter(" not in hlo


@pytest.mark.parametrize("sketch", ["hll-pmax", "cms-psum"])
def test_sharded_sketch_compiles_for_four_chips(mesh4, sketch):
    mesh = mesh4("flux")
    rep = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P("flux"))
    plane = NamedSharding(mesh, P("flux", None))
    if sketch == "hll-pmax":
        hll = HyperLogLog(p=HLL_P)
        lowered = build_sharded_hll(hll, mesh).lower(
            sds((hll.m,), jnp.int32, rep),
            sds((PUSH, FIELD_LEN), jnp.uint8, plane),
            sds((PUSH,), jnp.int32, rows))
    else:
        cms = CountMin(*CMS_SHAPE)
        lowered = build_sharded_cms(cms, mesh).lower(
            sds(CMS_SHAPE, cms._dtype, rep),
            sds((PUSH, FIELD_LEN), jnp.uint8, plane),
            sds((PUSH,), jnp.int32, rows),
            sds((PUSH,), jnp.int32, rows))
    assert "all-reduce" in lowered.compile().as_text()


def test_donating_fused_absorb_compiles_for_four_chips(mesh4):
    """The flux merge ``chip_smoke.py --chips 4`` runs: counts psum,
    register stack pmax, count-min psum — one program, stack donated."""
    mesh = mesh4("flux")
    cms = CountMin(*CMS_SHAPE)
    fn = kernels.build_fused_absorb(mesh, GROUPS_PAD, 1, HLL_P, cms,
                                    donate=True)
    specs = {"seg": P("flux"), "valid": P("flux"), "lengths": P("flux"),
             "comp_len": P("flux"), "batch": P("flux", None),
             "comp": P("flux", None), "registers": P(), "table": P()}
    compiled = fn.lower(*_fused_args(
        cms, lambda n: NamedSharding(mesh, specs[n]))).compile()
    assert compiled.as_text().count("all-reduce") >= 3
    assert compiled.memory_analysis().alias_size_in_bytes \
        == GROUPS_PAD * (1 << HLL_P) * 4
