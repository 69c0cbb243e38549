"""fbtpu-mesh: the explicitly partitioned pjit/shard_map grep plane.

Tier-1 ``mesh``-marked lane on the simulated 8-device CPU mesh
(conftest forces ``--xla_force_host_platform_device_count=8``). The
contract: the partitioned program's verdicts are BIT-EXACT against
both the single-device kernel and the pure-Python CPU chain, across
adversarial shapes (B not divisible by the mesh, single records, empty
batches, max_states-boundary programs), donation of the staged buffers
actually holds (input→output alias in the lowered module, donated
buffer consumed, zero copy-fallback warnings), and the engine's raw
path under ``FBTPU_MESH=1`` re-emits byte-identical chunks. The full
device-count × kernel matrix rides behind ``slow``.
"""

import os
import warnings

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from fluentbit_tpu.ops.batch import assemble
from fluentbit_tpu.ops.grep import GrepProgram, program_for
from fluentbit_tpu.ops.mesh import (build_mesh, match_partition_rules,
                                    mesh_info, mesh_key, pad_to_devices)
from fluentbit_tpu.regex import FlbRegex
from fluentbit_tpu.regex.dfa import compile_dfa

pytestmark = pytest.mark.mesh

APACHE2 = (
    r'^(?<host>[^ ]*) [^ ]* (?<user>[^ ]*) \[(?<time>[^\]]*)\] '
    r'"(?<method>\S+)(?: +(?<path>[^ ]*) +\S*)?" '
    r'(?<code>[^ ]*) (?<size>[^ ]*)'
    r'(?: "(?<referer>[^\"]*)" "(?<agent>.*)")?$'
)

CORPUS = [
    b'10.0.0.1 - frank [10/Oct/2000:13:55:36 -0700] '
    b'"GET /a HTTP/1.1" 200 23 "http://r" "curl"',
    b"POST /api/v1 500",
    b"kernel: panic at cpu0",
    b"",
    None,  # missing field row
    b"DELETE /x 404",
    b"GET with trailing spaces   ",
]


def _mesh(n=8, axis="batch"):
    if len(jax.devices()) < n:
        pytest.skip(f"need {n} devices, have {len(jax.devices())}")
    return build_mesh(n, axis=axis)


def _stage(vals, R, L=96):
    b = assemble(vals, L)
    return np.stack([b.batch] * R), np.stack([b.lengths] * R)


def _cpu_chain(patterns, vals):
    """The pure-Python reference verdict: per-rule regex over each
    value (None/missing rows never match) — the chain the partitioned
    program must reproduce bit-for-bit."""
    regs = [FlbRegex(p) for p in patterns]
    out = np.zeros((len(patterns), len(vals)), dtype=bool)
    for r, rx in enumerate(regs):
        for i, v in enumerate(vals):
            if v is None:
                continue
            out[r, i] = rx.match(v.decode("utf-8", "surrogateescape"))
    return out


# -- sharded-vs-unsharded bit-exactness, adversarial shapes -----------

@pytest.mark.parametrize("n_rows", [42, 1, 0, 8, 17])
def test_mesh_bit_exact_vs_cpu_chain(n_rows):
    """B not divisible by the mesh (42, 17), a single record, an empty
    batch, and an exact multiple — all bit-exact vs the single-device
    kernel AND the Python chain, with correct global counts."""
    mesh = _mesh()
    patterns = ("GET|POST", "^kernel:", "50[0-9]$")
    vals = (CORPUS * 7)[:n_rows]
    prog = program_for(patterns, 96)
    batch, lengths = _stage(vals, len(patterns))
    ref_chain = _cpu_chain(patterns, vals)
    mask, counts, Bp = prog.match_mesh(mesh, batch, lengths)
    assert Bp % mesh.devices.size == 0
    assert np.array_equal(mask, prog.match(batch, lengths))
    assert np.array_equal(mask, ref_chain)
    assert np.array_equal(counts, ref_chain.sum(axis=1))


def test_mesh_max_states_boundary_programs():
    """The apache2 parser DFA (S=690, k capped by the table budget) and
    a tiny literal (deep k, a handful of states) — both on the scan
    kernel — survive partitioning bit-exactly."""
    mesh = _mesh()
    vals = (CORPUS * 11)[:59]  # uneven tail on every device
    for patterns in ((APACHE2,), ("panic",), (APACHE2, "panic")):
        prog = program_for(patterns, 128)
        batch, lengths = _stage(vals, len(patterns), L=128)
        mask, counts, _ = prog.match_mesh(mesh, batch, lengths)
        assert np.array_equal(mask, _cpu_chain(patterns, vals))
        assert np.array_equal(counts, mask.sum(axis=1))


def test_mesh_assoc_kernel_bit_exact():
    """The parallel-in-time (assoc) kernel under the partitioned
    program — the shard_map varying-axes tie-in (`+ 0 * lengths`) must
    hold for the compose-tree variant too."""
    mesh = _mesh()
    vals = (CORPUS * 5)[:29]
    prog = GrepProgram([compile_dfa("GET|POST"), compile_dfa("50[0-9]$")],
                       96, kernel="assoc")
    batch, lengths = _stage(vals, 2)
    mask, _, _ = prog.match_mesh(mesh, batch, lengths)
    assert np.array_equal(mask, _cpu_chain(("GET|POST", "50[0-9]$"), vals))


def test_mesh_padded_class_runs_bit_exact():
    """Two rules of one stride whose class maps have unequal breakpoint
    counts: the shorter rule's padded runs (start 256, delta 0) ride
    the partitioned program as replicated leaves and add nothing."""
    mesh = _mesh()
    vals = (CORPUS * 4)[:21]
    patterns = ("panic", "ra")  # one stride (k=6), 10 and 4 runs
    prog = GrepProgram([compile_dfa(p) for p in patterns], 96)
    assert prog._children is None and prog.mesh_variant(mesh) == "batch"
    runs = [r["class_runs"] for r in prog.decision()["rules"]]
    assert runs[0] != runs[1]
    assert prog._np["run_start"].shape == (2, max(runs))
    assert (prog._np["run_start"][int(np.argmin(runs)), min(runs):]
            == 256).all()
    batch, lengths = _stage(vals, 2)
    mask, _, _ = prog.match_mesh(mesh, batch, lengths)
    assert np.array_equal(mask, _cpu_chain(patterns, vals))


def test_rule_sharded_variant_bit_exact(monkeypatch):
    """Large-R table sharding: R splits across devices (tables AND the
    per-rule batches), counts come back global, verdicts bit-exact."""
    monkeypatch.setenv("FBTPU_MESH_RULE_SHARD_R", "8")
    mesh = _mesh()
    patterns = ("GET", "POST", "DELETE", "panic", "200", "404",
                "50[0-9]$", "curl")
    prog = GrepProgram([compile_dfa(p) for p in patterns], 96)
    assert prog.mesh_variant(mesh) == "rules"
    vals = (CORPUS * 6)[:37]
    batch, lengths = _stage(vals, len(patterns))
    ref = _cpu_chain(patterns, vals)
    mask, counts, Bp = prog.match_mesh(mesh, batch, lengths)
    assert Bp == 37  # rules variant shards R, B travels unpadded
    assert np.array_equal(mask, ref)
    assert np.array_equal(counts, ref.sum(axis=1))


def test_rule_shard_gate_requires_divisible_R():
    """R that does not divide the mesh falls back to batch sharding
    (a dead-rule pad row would cost a full batch scan)."""
    mesh = _mesh()
    prog = GrepProgram([compile_dfa(p) for p in ("a", "b", "c")], 64)
    os.environ.get("FBTPU_MESH_RULE_SHARD_R")  # default 64 untouched
    assert prog.mesh_variant(mesh) == "batch"


# -- the partition-rules layer ----------------------------------------

def test_match_partition_rules_layer():
    from jax.sharding import PartitionSpec as P

    tree = {
        "trans_flat": np.zeros((4, 128), np.int32),
        "starts": np.zeros((4,), np.int32),
        "scalar": np.zeros((1,), np.int32),
    }
    specs = match_partition_rules(
        ((r"trans_flat", P("batch", None)), (r".*", P("batch"))), tree)
    assert specs["trans_flat"] == P("batch", None)
    assert specs["starts"] == P("batch")
    assert specs["scalar"] == P()  # scalars never partition
    with pytest.raises(ValueError):
        match_partition_rules(((r"^starts$", P()),), tree)


@pytest.mark.parametrize("table,sharded_dims", [
    ("grep-batch", {}),
    ("grep-rules", {"trans_flat": 2, "run_start": 2, "run_delta": 2,
                    "class_base": 1, "C": 1, "Ck": 1, "eol_cls": 1,
                    "starts": 1}),
])
def test_grep_partition_rules_name_every_leaf(table, sharded_dims):
    """The registry places every leaf of the program's table pytree —
    the class-run leaves on the rule axis in the rule-sharded variant,
    replicated in the batch variant — and names nothing the pytree
    does not hold (a stale name is dead text that hides a rename)."""
    import re

    from jax.sharding import PartitionSpec as P

    from fluentbit_tpu.ops.mesh import PARTITION_RULES, partition_rules

    prog = GrepProgram([compile_dfa(p) for p in ("GET", "50[0-9]$")], 96)
    leaves = set(prog._np)
    assert {"class_base", "run_start", "run_delta"} <= leaves
    specs = match_partition_rules(partition_rules(table, "batch"), prog._np)
    assert set(specs) == leaves
    for name, spec in specs.items():
        n = sharded_dims.get(name)
        want = P() if n is None else P("batch", *([None] * (n - 1)))
        assert spec == want, name
    named = set()
    for regex, _ in PARTITION_RULES[table]:
        m = re.fullmatch(r"\^\(([A-Za-z_|]+)\)\$", regex)
        assert m, f"{regex!r}: not an anchored alternation of leaf names"
        named |= set(m.group(1).split("|"))
    assert named == leaves


def test_mesh_helpers():
    mesh = _mesh()
    info = mesh_info(mesh)
    assert info["devices"] == 8 and info["axis_names"] == ["batch"]
    assert info["simulated"] is True  # the tier-1 lane IS simulated
    assert mesh_key(mesh) == mesh_key(build_mesh(8))
    assert pad_to_devices(42, 8) == 48 and pad_to_devices(16, 8) == 16
    assert build_mesh(1) is None  # no 1-device mesh: pure overhead


# -- donation ---------------------------------------------------------

def test_donation_declared_and_aliased_in_module():
    """Compile-level half of the donation contract: the staged lengths
    buffer is declared donated and the lowered module carries the
    input→output alias (the i32 verdict lands in the staging buffer)."""
    mesh = _mesh()
    prog = program_for(("GET|POST", "^kernel:"), 96)
    rep = prog.donation_info(mesh, B=42)
    assert rep["declared"] == ["lengths"]
    assert rep["held"] is True and rep["alias_count"] >= 1
    assert rep["variant"] == "batch"
    assert rep["per_device_batch_share"] == pad_to_devices(42, 8) // 8


def test_donation_actually_consumes_buffer_no_warning():
    """Run-time half: after a dispatch the donated staging buffer is
    DELETED (XLA reused it — use-after-donate raises instead of
    silently reading verdict bytes), the un-donatable batch buffer is
    untouched, and no "donated buffers were not usable" copy-fallback
    warning ever fires."""
    mesh = _mesh()
    prog = program_for(("GET|POST", "^kernel:"), 96)
    vals = (CORPUS * 3)[:16]
    batch, lengths = _stage(vals, 2)
    h = prog._mesh_handle(mesh)
    assert h.donate_idx == (2,)  # lengths only: batch has no alias
    bd = jax.device_put(np.ascontiguousarray(batch), h.sh_b)
    ld = jax.device_put(np.ascontiguousarray(lengths), h.sh_l)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        mask_i32, counts = h.fn(h.tables, bd, ld)
        np.asarray(mask_i32)
    assert not [x for x in w if "donated" in str(x.message).lower()]
    assert ld.is_deleted()      # donation held: buffer consumed
    assert not bd.is_deleted()  # not declared: still readable
    assert np.array_equal(np.asarray(mask_i32).astype(bool),
                          _cpu_chain(("GET|POST", "^kernel:"), vals))


def test_donation_all_mode_warns_for_unaliasable_batch():
    """The auto policy is load-bearing: force-donating the batch buffer
    (no aliasable u8 output exists) produces exactly the silent-copy
    warning the default set is computed to avoid."""
    mesh = _mesh()
    prog = program_for(("GET|POST",), 96)
    vals = (CORPUS * 3)[:16]
    batch, lengths = _stage(vals, 1)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        mask, _, _ = prog.match_mesh(mesh, batch, lengths, donate="all")
    assert np.array_equal(mask, _cpu_chain(("GET|POST",), vals))
    assert [x for x in w if "donated buffers were not usable"
            in str(x.message)]


# -- engine end-to-end (the raw dispatch path) ------------------------

def _build_engine(mesh_on: bool, device: bool = True):
    from fluentbit_tpu.core.engine import Engine

    e = Engine()
    f = e.filter("grep")
    f.set("regex", f"log {APACHE2}")
    f.set("tpu_batch_records", "1")
    if not device:
        f.set("tpu.enable", "off")
    ins = e.input("dummy")
    for x in e.inputs + e.filters:
        x.configure()
        x.plugin.init(x, e)
    return e, ins


def _corpus_chunk(n):
    from fluentbit_tpu.codec.events import encode_event

    ok = ('10.0.0.1 - frank [10/Oct/2000:13:55:36 -0700] '
          '"GET /a HTTP/1.1" 200 23 "http://r" "curl"')
    return b"".join(
        encode_event({"log": ok if i % 4 else f"kernel: oom {i}"},
                     float(i))
        for i in range(n))


@pytest.mark.parametrize("seg,n", [(None, 700), (128, 700), (1, 12)])
def test_engine_mesh_raw_path_byte_exact(monkeypatch, seg, n):
    """FBTPU_MESH=1 routes filter_grep's raw path through the
    partitioned matcher (single segment, uneven-tail multi-segment,
    and single-record segments) — surviving records re-emit
    byte-identical to the pure-Python chain."""
    if len(jax.devices()) < 2:
        pytest.skip("need a multi-device mesh")
    monkeypatch.setenv("FBTPU_MESH", "1")
    if seg is not None:
        monkeypatch.setenv("FBTPU_SEGMENT_RECORDS", str(seg))
    chunk = _corpus_chunk(n)
    e1, i1 = _build_engine(mesh_on=True)
    monkeypatch.setenv("FBTPU_MESH", "off")
    e2, i2 = _build_engine(mesh_on=False, device=False)
    monkeypatch.setenv("FBTPU_MESH", "1")
    n1 = e1.input_log_append(i1, "bench", chunk)
    n2 = e2.input_log_append(i2, "bench", chunk)
    o1 = b"".join(bytes(c.buf) for c in i1.pool.drain())
    o2 = b"".join(bytes(c.buf) for c in i2.pool.drain())
    assert e1.filters[0].plugin._mesh is not None  # lane engaged
    assert (n1, o1) == (n2, o2)


def test_mesh_resolution_survives_mid_attach_chunks(monkeypatch):
    """A chunk arriving while the device is still ATTACHING must not
    pin the mesh lane off for the plugin's lifetime: resolution stays
    open until the attach controller reaches ready/failed, then auto
    engages on a real multi-device attach (regression: the first raw
    chunk used to cache None forever)."""
    from fluentbit_tpu.ops import device as dev
    from fluentbit_tpu.plugins.filter_grep import GrepFilter

    monkeypatch.setenv("FBTPU_MESH", "auto")
    plug = GrepFilter.__new__(GrepFilter)
    plug._program = object()  # only truthiness matters here
    plug._mesh = None
    plug._mesh_resolved = False
    # mid-attach: neither ready nor failed — must NOT resolve
    monkeypatch.setattr(dev, "ready", lambda: False)
    monkeypatch.setattr(dev, "failed", lambda: False)
    monkeypatch.setattr(dev, "attach_async", lambda: None)
    assert plug._grep_mesh() is None
    assert plug._mesh_resolved is False  # next chunk re-probes
    # attach lands on a multi-device accelerator: auto engages
    monkeypatch.setattr(dev, "ready", lambda: True)
    monkeypatch.setattr(dev, "platform", lambda: "tpu")
    monkeypatch.setattr(dev, "device_count", lambda: 8)
    assert plug._grep_mesh() is not None
    assert plug._mesh_resolved is True
    # failed attach pins the unsharded path (fresh plugin state)
    plug2 = GrepFilter.__new__(GrepFilter)
    plug2._program = object()
    plug2._mesh = None
    plug2._mesh_resolved = False
    monkeypatch.setattr(dev, "ready", lambda: False)
    monkeypatch.setattr(dev, "failed", lambda: True)
    assert plug2._grep_mesh() is None
    assert plug2._mesh_resolved is True


def test_engine_mesh_auto_stays_off_on_cpu(monkeypatch):
    """auto never shadows the native fused matcher on a CPU backend —
    the 1-core bench hot path must not regress."""
    monkeypatch.delenv("FBTPU_MESH", raising=False)
    e, ins = _build_engine(mesh_on=False)
    e.input_log_append(ins, "bench", _corpus_chunk(64))
    ins.pool.drain()
    assert e.filters[0].plugin._mesh is None


# -- full matrix (slow) -----------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("n_dev", [2, 4, 8])
@pytest.mark.parametrize("kernel", ["scan", "assoc"])
@pytest.mark.parametrize("n_rows", [0, 1, 5, 42, 137])
def test_mesh_full_matrix(n_dev, kernel, n_rows):
    mesh = _mesh(n_dev)
    patterns = ("GET|POST", "^kernel:", "50[0-9]$", "curl")
    prog = GrepProgram([compile_dfa(p) for p in patterns], 96,
                       kernel=kernel)
    vals = (CORPUS * 25)[:n_rows]
    batch, lengths = _stage(vals, len(patterns))
    ref = _cpu_chain(patterns, vals)
    mask, counts, Bp = prog.match_mesh(mesh, batch, lengths)
    assert Bp % n_dev == 0
    assert np.array_equal(mask, ref)
    assert np.array_equal(counts, ref.sum(axis=1))
