"""HLL/count-min dual-path parity — the drift-risk property test.

The sketches have THREE update paths that must stay bit-identical for
the same byte streams: the device kernel (``update()`` once the backend
attaches — the jax jit), the C batch twin (``host_update`` —
fbtpu_hll_update / fbtpu_cms_update), and the Python per-value loop
(``add_cpu``).  Any drift silently corrupts merged multichip state, so
this suite drives randomized workloads through all of them, including
the ``merge_registers``/``merge_table`` cross-shard merge and the
sharded (mesh) update, and asserts register/table equality.
"""

import random

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from fluentbit_tpu import native
from fluentbit_tpu.ops.batch import assemble
from fluentbit_tpu.ops.sketch import (
    CountMin,
    HyperLogLog,
    sharded_cms_update,
    sharded_hll_update,
)


def corpus(seed, n=400, max_len=24, none_rate=0.1):
    rng = random.Random(seed)
    out = []
    for _ in range(n):
        if rng.random() < none_rate:
            out.append(None)  # missing field rows must never count
        else:
            out.append(bytes(rng.randrange(256)
                             for _ in range(rng.randrange(0, max_len))))
    return out


def _mesh(n=8):
    devs = jax.devices()
    if len(devs) < n:
        pytest.skip(f"need {n} devices, have {len(devs)}")
    from jax.sharding import Mesh

    return Mesh(np.asarray(devs[:n]), ("batch",))


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_hll_three_paths_identical(seed):
    vals = corpus(seed)
    staged = assemble(vals, 32)

    h_py = HyperLogLog(p=10)
    for v in vals:
        if v is not None:
            h_py.add_cpu(v)

    h_c = HyperLogLog(p=10)
    h_c.host_update(staged.batch, staged.lengths)
    for i in staged.overflow:
        h_c.add_cpu(vals[i])

    h_dev = HyperLogLog(p=10)
    h_dev.update(staged.batch, staged.lengths)  # device path (cpu jit)
    for i in staged.overflow:
        h_dev.add_cpu(vals[i])

    regs_py = np.asarray(h_py.registers)
    assert np.array_equal(regs_py, np.asarray(h_c.registers))
    assert np.array_equal(regs_py, np.asarray(h_dev.registers))


@pytest.mark.parametrize("seed", [4, 5])
def test_cms_three_paths_identical(seed):
    vals = corpus(seed)
    staged = assemble(vals, 32)

    c_py = CountMin(4, 512)
    for v in vals:
        if v is not None:
            c_py.add_cpu(v)

    c_c = CountMin(4, 512)
    c_c.host_update(staged.batch, staged.lengths)
    for i in staged.overflow:
        c_c.add_cpu(vals[i])

    c_dev = CountMin(4, 512)
    c_dev.update(staged.batch, staged.lengths)
    for i in staged.overflow:
        c_dev.add_cpu(vals[i])

    t_py = np.asarray(c_py.table)
    assert np.array_equal(t_py, np.asarray(c_c.table))
    assert np.array_equal(t_py, np.asarray(c_dev.table))


def test_cross_shard_merge_is_union():
    """merge_registers/merge_table over disjoint halves == one sketch
    over the whole stream (the multichip merge contract)."""
    vals = corpus(7, n=600, none_rate=0.0)
    half = len(vals) // 2

    whole_h = HyperLogLog(p=10)
    whole_c = CountMin(4, 512)
    for v in vals:
        whole_h.add_cpu(v)
        whole_c.add_cpu(v)

    a_h, b_h = HyperLogLog(p=10), HyperLogLog(p=10)
    a_c, b_c = CountMin(4, 512), CountMin(4, 512)
    sa = assemble(vals[:half], 32)
    sb = assemble(vals[half:], 32)
    a_h.host_update(sa.batch, sa.lengths)
    b_h.host_update(sb.batch, sb.lengths)
    a_c.host_update(sa.batch, sa.lengths)
    b_c.host_update(sb.batch, sb.lengths)
    for i in sa.overflow:
        a_h.add_cpu(vals[i])
        a_c.add_cpu(vals[i])
    for i in sb.overflow:
        b_h.add_cpu(vals[half + i])
        b_c.add_cpu(vals[half + i])
    a_h.merge_registers(np.asarray(b_h.registers))
    a_c.merge_table(np.asarray(b_c.table))

    assert np.array_equal(np.asarray(whole_h.registers),
                          np.asarray(a_h.registers))
    assert np.array_equal(np.asarray(whole_c.table),
                          np.asarray(a_c.table))


@pytest.mark.mesh
def test_sharded_hll_matches_host():
    """The mesh (pmax-merged) HLL update is bit-identical to the host
    twin — sharding must not change a single register."""
    vals = corpus(9, n=333, none_rate=0.05)  # not divisible by 8
    staged = assemble(vals, 32)
    host = HyperLogLog(p=10)
    host.host_update(staged.batch, staged.lengths)

    mesh = _mesh(8)
    sh = HyperLogLog(p=10)
    sharded_hll_update(sh, mesh, staged.batch, staged.lengths)
    assert np.array_equal(np.asarray(host.registers),
                          np.asarray(sh.registers))


@pytest.mark.mesh
def test_sharded_cms_matches_host():
    vals = corpus(10, n=333, none_rate=0.05)
    staged = assemble(vals, 32)
    host = CountMin(4, 512)
    host.host_update(staged.batch, staged.lengths)

    mesh = _mesh(8)
    sh = CountMin(4, 512)
    sharded_cms_update(sh, mesh, staged.batch, staged.lengths)
    assert np.array_equal(np.asarray(host.table), np.asarray(sh.table))


@pytest.mark.mesh
def test_segment_counts_three_paths_identical():
    """flux window counts: host bincount == device scatter-add ==
    mesh psum merge (integers — exact everywhere)."""
    from fluentbit_tpu.flux import kernels

    rng = np.random.default_rng(3)
    seg = rng.integers(0, 13, size=401).astype(np.int64)
    valid = (rng.random(401) < 0.8).astype(np.int32)
    host = kernels.host_segment_counts(seg, valid, 13)
    dev = kernels.segment_counts(seg, valid, 13)
    assert np.array_equal(host, dev)
    mesh = kernels.flux_mesh()
    if mesh is not None:
        sh = kernels.sharded_segment_counts(mesh, seg, valid, 13)
        assert np.array_equal(host, sh)


def test_native_twins_present():
    """The C batch kernels exist in this build (a stale prebuilt .so
    would silently fall back to the Python loop — still correct, but
    the flux ingest-rate path wants the C twins)."""
    if not native.available():
        pytest.skip("native plane unavailable")
    regs = np.zeros(1 << 8, dtype=np.int32)
    staged = assemble([b"x", b"y"], 8)
    assert native.hll_update(regs, staged.batch, staged.lengths, 8)
    table = np.zeros((2, 64), dtype=np.int64)
    assert native.cms_update(table, staged.batch, staged.lengths)


def _readback_values(kind):
    """Values for the count-min read-back: paths shaped like the sketch
    corpus, edge bytes, and every length from 1 to 600 — those past
    ``QUERY_VECTOR_WIDTH`` take the one-at-a-time hash."""
    rng = random.Random(kind)
    if kind == "none":
        return []
    if kind == "one":
        return [b"/api/v1/item/7"]
    if kind == "edge_bytes":
        return [b"", b"\x00", b"a", b"\x00\x00", b"\xff\xfe\x80",
                b"caf\xc3\xa9", bytes(range(256))]
    if kind == "lengths_1_600":
        return [bytes(rng.randrange(256) for _ in range(n))
                for n in range(1, 601)]
    assert kind == "corpus_4096"
    return [f"/api/v1/item/{i}".encode()
            for i in rng.sample(range(10_000), 4096)]


@pytest.mark.parametrize("geometry", [(4, 16384), (2, 512)])
@pytest.mark.parametrize("table_kind", ["numpy", "jnp"])
@pytest.mark.parametrize("kind", ["none", "one", "edge_bytes",
                                  "lengths_1_600", "corpus_4096"])
def test_cms_query_many_matches_per_value(kind, table_kind, geometry):
    """The vectorised read-back gives every value the row-min of the
    columns ``_cols_cpu`` computes for it (the device kernel's hash),
    as a Python int, and counts the values it hashed one at a time."""
    from fluentbit_tpu.ops.sketch import QUERY_VECTOR_WIDTH

    depth, width = geometry
    cms = CountMin(depth, width)
    rng = np.random.default_rng(depth * width)
    table = rng.integers(0, 1 << 20, size=(depth, width)).astype(cms._dtype)
    cms.table = table if table_kind == "numpy" else jax.numpy.asarray(table)
    vals = _readback_values(kind)

    expect = [min(int(table[r, c]) for r, c in enumerate(cms._cols_cpu(v)))
              for v in vals]
    got = cms.query_many(vals)
    assert got == expect
    assert all(type(e) is int for e in got)
    ests, n_scalar = cms.query_array(vals)
    assert ests.tolist() == expect
    assert n_scalar == sum(len(v) > QUERY_VECTOR_WIDTH for v in vals)
    if kind == "lengths_1_600":
        assert n_scalar == 600 - QUERY_VECTOR_WIDTH
