"""fbtpu-flux: state, window semantics, plugin paths, snapshot/crash.

Covers the satellite matrix: tumbling vs sliding (hopping) boundary
records, late/out-of-order timestamps (event-time lane), window
rollover under a concurrent snapshot, crash-recovery of persisted flux
state through the armed ``flux.snapshot`` failpoint, and the
batched-vs-per-record bit-identity of the filter itself.
"""

import os
import pickle
import subprocess
import sys

import numpy as np
import pytest

pytest.importorskip("jax")

import fluentbit_tpu  # noqa: F401  (registers plugins)
from fluentbit_tpu.codec.events import decode_events, encode_event
from fluentbit_tpu.core.engine import Engine
from fluentbit_tpu.flux.state import FluxSpec, FluxState, WindowSpec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def ev_buf(bodies, ts0=1000.0):
    buf = bytearray()
    for i, b in enumerate(bodies):
        buf += encode_event(b, ts0 + i)
    return bytes(buf)


def absorb_py(state, bodies, ts0=1000.0):
    state.absorb_events(decode_events(ev_buf(bodies, ts0)))


# ------------------------------------------------------------- windows

def clocked_state(**kw):
    t = [1000.0]
    st = FluxState(FluxSpec("t", **kw), now=lambda: t[0])
    return st, t


def test_tumbling_window_boundary():
    st, t = clocked_state(window=WindowSpec("tumbling", 60))
    absorb_py(st, [{"a": "x"}] * 3)
    assert st.tick() == []                     # window still open
    t[0] = 1059.999
    assert st.tick() == []
    t[0] = 1060.0                              # boundary is inclusive
    closed = st.tick()
    assert len(closed) == 1 and closed[0][1].count == 3
    assert st.tick() == []                     # already emitted
    # records after the boundary land in the NEXT window
    absorb_py(st, [{"a": "x"}])
    t[0] = 1121.0
    closed = st.tick()
    assert closed[0][1].count == 1
    # boundary advance is whole periods: no drift from late ticks
    assert st._window_start == 1120.0


def test_hopping_window_pane_ring():
    st, t = clocked_state(window=WindowSpec("hopping", 60, 20))
    # pane 1: 4 records
    absorb_py(st, [{"a": "x"}] * 4)
    t[0] = 1020.0
    closed = st.tick()
    assert closed[0][1].count == 4             # 1 pane in the window
    absorb_py(st, [{"a": "x"}] * 2)            # pane 2
    t[0] = 1040.0
    assert st.tick()[0][1].count == 6          # panes 1+2
    t[0] = 1060.0
    assert st.tick()[0][1].count == 6          # panes 1+2+3(empty)
    t[0] = 1080.0
    # pane 1 slid out of the 60 s window: only pane 2 remains
    assert st.tick()[0][1].count == 2
    t[0] = 1100.0
    assert st.tick() == []                     # everything expired


def test_hopping_drain_merges_open_panes():
    st, t = clocked_state(window=WindowSpec("hopping", 60, 20))
    absorb_py(st, [{"a": "x"}] * 2)
    t[0] = 1020.0
    st.tick()
    absorb_py(st, [{"a": "x"}] * 3)
    closed = st.drain()
    assert closed[0][1].count == 5


def test_event_time_late_and_out_of_order():
    st, _ = clocked_state(window=WindowSpec("tumbling", 60),
                          event_time=True, group_by=("tenant",))
    # in-window disorder is fine
    absorb_py(st, [{"tenant": "a"}], ts0=1010.0)
    absorb_py(st, [{"tenant": "a"}], ts0=1005.0)
    assert st.tick() == []                     # watermark still in w16
    # watermark jumps two windows ahead → w16 closes
    absorb_py(st, [{"tenant": "b"}], ts0=1130.0)
    closed = st.tick()
    assert len(closed) == 1
    key, g = closed[0]
    assert key == (b"a",) and g.count == 2
    # a record behind the watermark's window is LATE: counted, dropped
    before = st.late_records_total
    absorb_py(st, [{"tenant": "a"}], ts0=1001.0)
    assert st.late_records_total == before + 1
    assert st.tick() == []                     # no resurrected window


def test_snapshot_restore_roundtrip_under_rollover():
    """A snapshot taken mid-window restores to the same continuation:
    rollover after restore emits exactly what the original would."""
    st, t = clocked_state(window=WindowSpec("tumbling", 60),
                          group_by=("tenant",), distinct=("user",),
                          numeric=("size",))
    absorb_py(st, [{"tenant": "a", "user": f"u{i}", "size": i}
                   for i in range(50)])
    snap = pickle.dumps(st.snapshot(), protocol=4)
    # original continues: more records, then rollover
    absorb_py(st, [{"tenant": "a", "user": "u0", "size": 7}])
    t[0] = 1060.0
    orig = st.tick()

    st2, t2 = clocked_state(window=WindowSpec("tumbling", 60),
                            group_by=("tenant",), distinct=("user",),
                            numeric=("size",))
    st2.restore(pickle.loads(snap))
    absorb_py(st2, [{"tenant": "a", "user": "u0", "size": 7}])
    t2[0] = 1060.0
    got = st2.tick()

    (k1, g1), (k2, g2) = orig[0], got[0]
    assert k1 == k2 and g1.count == g2.count
    assert g1.cols["size"].sum == g2.cols["size"].sum
    assert np.array_equal(np.asarray(g1.hlls["user"].registers),
                          np.asarray(g2.hlls["user"].registers))
    # and the snapshot itself did not perturb the original's windows
    assert st._window_start == st2._window_start


def test_topk_oversize_group_prefix_does_not_crash():
    """A group label at/near max_len makes the composite prefix exceed
    the staging width: the group must simply have no top-k (on both
    paths), never raise mid-absorb (a partial absorb would be an
    implicit decline after commit)."""
    st, _ = clocked_state(group_by=("tenant",), topk_field="user",
                          max_len=64)
    big = "T" * 64  # prefix = 64 label bytes + 1 separator > 64
    absorb_py(st, [{"tenant": big, "user": "u1"},
                   {"tenant": "ok", "user": "u2"}])
    assert st.records_total == 2
    assert st.topk((big.encode(),)) == []
    assert [v for _, v in st.topk((b"ok",))] == [b"u2"]


@pytest.mark.parametrize("geometry", [(4, 16384), (2, 64)])
def test_topk_order_matches_per_value_sort(geometry):
    """A group's top-k is what the per-value row-min and the sort by
    (-estimate, value) gave: eight values tied across the k-th place go
    by their bytes, a value past the read-back's vector width among
    them."""
    depth, width = geometry
    st, _ = clocked_state(group_by=("tenant",), topk_field="user",
                          topk=5, cms_depth=depth, cms_width=width,
                          max_len=512)
    rng = np.random.default_rng(width)
    users = (["h0"] * 100 + ["h1"] * 90
             + [f"tie{j}" for j in range(8)] * 40 + ["L" * 300] * 40
             + [f"u{min(z, 500)}" for z in rng.zipf(1.5, 150)])
    rng.shuffle(users)
    bodies = [{"tenant": "a", "user": u} for u in users]
    bodies += [{"tenant": "b", "user": f"u{min(z, 500)}"}
               for z in rng.zipf(1.5, 300)]
    rng.shuffle(bodies)
    for i in range(0, len(bodies), 50):  # every row nominated
        absorb_py(st, bodies[i:i + 50])
    table = np.asarray(st.cms.table)
    ties = 0
    for key in ((b"a",), (b"b",)):
        cand = list(st._candidates[key])
        prefix = st._group_prefix(key)
        ests = [min(int(table[r, c]) for r, c in
                    enumerate(st.cms._cols_cpu(prefix + v)))
                for v in cand]
        ranked = sorted(zip(ests, cand), key=lambda t: (-t[0], t[1]))
        ties += ranked[4][0] == ranked[5][0]
        got = st.topk(key)
        assert got == ranked[:5]
        assert all(type(e) is int for e, _ in got)
    assert b"L" * 300 in st._candidates[(b"a",)]
    assert ties


def test_event_time_requires_tumbling_window():
    from fluentbit_tpu.flux.state import FluxSpec as FS

    with pytest.raises(ValueError):
        FS("t", event_time=True)                 # no window at all
    with pytest.raises(ValueError):
        FS("t", event_time=True,
           window=WindowSpec("hopping", 10, 2))  # hopping panes


def test_snapshot_rejects_mismatched_shape(tmp_path):
    """A snapshot persisted under a different config must not restore
    (wrong group-key arity would misalign every window row)."""
    st, _ = clocked_state(group_by=("tenant",), distinct=("user",))
    absorb_py(st, [{"tenant": "a", "user": "u"}])
    path = str(tmp_path / "flux.snap")
    st.persist(path)
    other, _ = clocked_state(group_by=("tenant", "region"),
                             distinct=("user",))
    assert not other.load(path)                  # shape mismatch
    assert other.records_total == 0              # stayed fresh
    renamed = FluxState(FluxSpec("elsewhere", group_by=("tenant",),
                                 distinct=("user",)))
    assert not renamed.load(path)                # name mismatch
    # sketch-geometry change is the MEMORY-SAFETY case: p=12 registers
    # into a p=14 state would hand the C kernel an undersized buffer
    resized, _ = clocked_state(group_by=("tenant",),
                               distinct=("user",), hll_p=14)
    assert not resized.load(path)
    assert resized.records_total == 0
    absorb_py(resized, [{"tenant": "a", "user": "x"}])  # must not crash
    same, _ = clocked_state(group_by=("tenant",), distinct=("user",))
    assert same.load(path)                       # matching spec loads


def test_persist_load_roundtrip(tmp_path):
    st, _ = clocked_state(distinct=("user",), topk_field="user")
    absorb_py(st, [{"user": f"u{i % 7}"} for i in range(100)])
    path = str(tmp_path / "flux.snap")
    st.persist(path)
    st2, _ = clocked_state(distinct=("user",), topk_field="user")
    assert st2.load(path)
    assert st2.records_total == st.records_total
    assert np.array_equal(np.asarray(st.cms.table),
                          np.asarray(st2.cms.table))
    g1 = dict(st.live_groups())[()]
    g2 = dict(st2.live_groups())[()]
    assert np.array_equal(np.asarray(g1.hlls["user"].registers),
                          np.asarray(g2.hlls["user"].registers))
    assert st2.topk(()) == st.topk(())


_CRASH_CHILD = r"""
import os, sys
sys.path.insert(0, %(repo)r)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from fluentbit_tpu.codec.events import decode_events, encode_event
from fluentbit_tpu.flux.state import FluxSpec, FluxState
from fluentbit_tpu import failpoints

path = sys.argv[1]
mode = sys.argv[2]
st = FluxState(FluxSpec("t", distinct=("user",)))
buf = b"".join(encode_event({"user": "u%%d" %% i}, float(i))
               for i in range(64))
st.absorb_events(decode_events(buf))
st.persist(path)            # snapshot 1 lands cleanly
buf2 = b"".join(encode_event({"user": "v%%d" %% i}, float(i))
                for i in range(64))
st.absorb_events(decode_events(buf2))
if mode == "crash":
    failpoints.enable("flux.snapshot", "crash")
st.persist(path)            # crash fires AFTER tmp write, BEFORE rename
print("SURVIVED")
"""


def test_snapshot_crash_recovery(tmp_path):
    """flux.snapshot armed with crash: the process dies between the
    tmp fsync and the atomic rename — the previous snapshot must load
    intact (old-or-new, never torn)."""
    path = str(tmp_path / "flux.snap")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # clean run first: both snapshots land
    p = subprocess.run(
        [sys.executable, "-c", _CRASH_CHILD % {"repo": REPO},
         path, "clean"], capture_output=True, text=True, env=env,
        timeout=120)
    assert "SURVIVED" in p.stdout
    clean = FluxState(FluxSpec("t", distinct=("user",)))
    assert clean.load(path)
    assert clean.records_total == 128

    path2 = str(tmp_path / "flux2.snap")
    p = subprocess.run(
        [sys.executable, "-c", _CRASH_CHILD % {"repo": REPO},
         path2, "crash"], capture_output=True, text=True, env=env,
        timeout=120)
    assert p.returncode != 0              # the failpoint killed it
    assert "SURVIVED" not in p.stdout
    rec = FluxState(FluxSpec("t", distinct=("user",)))
    assert rec.load(path2)                # old file intact
    assert rec.records_total == 64        # snapshot 1's state
    # no torn tmp leftovers pollute the directory contract
    leftovers = [f for f in os.listdir(str(tmp_path))
                 if f.startswith(".flux-snap-")]
    assert leftovers == [] or all(
        not f.endswith("flux2.snap") for f in leftovers)


# ---------------------------------------------------- plugin bit-exactness

def build_engine(props):
    e = Engine()
    f = e.filter("flux")
    for k, v in props.items():
        f.set(k, v)
    ins = e.input("dummy")
    for x in e.inputs + e.filters:
        x.configure()
        x.plugin.init(x, e)
    return e, ins, e.filters[0].plugin


PROPS = {
    "group_by": "tenant", "distinct_field": "user",
    "aggregate_field": "size", "topk_field": "user",
    "window": "tumbling 60", "export_interval_sec": "0",
}


def corpus_bodies(seed=3, n=300):
    import random

    rng = random.Random(seed)
    out = []
    for i in range(n):
        body = {"tenant": rng.choice(["a", "b", None, "c"]),
                "user": f"u{rng.randrange(40)}",
                "size": rng.choice(
                    [rng.randrange(10**9), rng.random() * 100, "NaNish",
                     None, True])}
        if body["tenant"] is None:
            del body["tenant"]
        if rng.random() < 0.1:
            body["size"] = float("inf")
        out.append(body)
    out.append("not-a-dict")  # non-map body: skipped on both paths
    return out


def _state_fingerprint(state):
    out = []
    for key, g in state.live_groups():
        cols = {f: (st.has, st.sum, st.min, st.max, st.min_int,
                    st.max_int) for f, st in g.cols.items()}
        hlls = {f: np.asarray(h.registers).tobytes()
                for f, h in g.hlls.items()}
        out.append((key, g.count, cols, hlls))
    cms = np.asarray(state.cms.table).tobytes() if state.cms is not None \
        else None
    return out, cms, state.records_total


def test_batched_and_per_record_paths_bit_identical():
    bodies = corpus_bodies()
    raw = bytes(b"".join(encode_event(b, 1.0) for b in bodies))

    e1, ins1, p1 = build_engine(PROPS)           # batched (native)
    e1.input_log_append(ins1, "t", raw)
    assert sum(v for _, v in e1.m_filter_batch_decline.samples()) == 0

    # force the decode path: the hook declines, filter() runs per-record
    e2, ins2, p2 = build_engine(PROPS)
    p2._batch_ok = False
    assert not p2.can_process_batch()
    e2.input_log_append(ins2, "t", raw)

    f1 = _state_fingerprint(p1.state)
    f2 = _state_fingerprint(p2.state)
    assert f1[0] == f2[0]          # groups, counts, cols, registers
    assert f1[1] == f2[1]          # CMS tables
    assert f1[2] == f2[2]          # absorbed record totals


def test_records_pass_through_untouched():
    bodies = [{"tenant": "a", "user": "u1", "size": 5}] * 10
    raw = ev_buf(bodies)
    e, ins, _ = build_engine(PROPS)
    n = e.input_log_append(ins, "t", raw)
    assert n == 10
    chunks = ins.pool.drain()
    assert b"".join(bytes(c.buf) for c in chunks) == raw


def test_exporter_families(tmp_path):
    e, ins, plug = build_engine(PROPS)
    e.input_log_append(ins, "t", ev_buf(
        [{"tenant": "a", "user": f"u{i % 5}", "size": i}
         for i in range(40)]))
    plug.exporter.refresh()
    text = e.metrics.to_prometheus()
    assert "fluentbit_flux_records_total" in text
    assert "fluentbit_flux_cardinality" in text
    assert "fluentbit_flux_topk_estimate" in text
    assert 'group="a"' in text


def test_two_exporters_do_not_clobber_each_other():
    """The flux families are SHARED engine metrics: one instance's
    stale-series refresh must only drop its own series."""
    e = Engine()
    f1 = e.filter("flux")
    f2 = e.filter("flux")
    for f, alias in ((f1, "one"), (f2, "two")):
        f.set("alias", alias)
        f.set("group_by", "tenant")
        f.set("distinct_field", "user")
        f.set("export_interval_sec", "0")
    ins = e.input("dummy")
    for x in e.inputs + e.filters:
        x.configure()
        x.plugin.init(x, e)
    e.input_log_append(ins, "t", ev_buf(
        [{"tenant": "a", "user": f"u{i}"} for i in range(5)]))
    f1.plugin.exporter.refresh()
    f2.plugin.exporter.refresh()   # must not wipe f1's series
    text = e.metrics.to_prometheus()
    assert 'name="one"' in text and 'name="two"' in text
    card = [ln for ln in text.splitlines()
            if ln.startswith("fluentbit_flux_cardinality")]
    assert any('name="one"' in ln for ln in card)
    assert any('name="two"' in ln for ln in card)


def test_window_rows_emitted_through_hidden_emitter():
    t = [1000.0]
    e, ins, plug = build_engine(dict(PROPS, tag="flux.out"))
    plug.state._now = lambda: t[0]
    plug.state._window_start = 1000.0
    e.input_log_append(ins, "t", ev_buf(
        [{"tenant": "a", "user": "u1", "size": 2},
         {"tenant": "a", "user": "u2", "size": 4}]))
    t[0] = 1061.0
    plug._on_tick(e)
    em = plug._emitter_ins
    chunks = em.pool.drain()
    assert chunks and chunks[0].tag == "flux.out"
    rows = [ev.body for ev in decode_events(bytes(chunks[0].buf))]
    assert rows[0]["count"] == 2
    assert rows[0]["size_sum"] == 6.0
    assert rows[0]["size_min"] == 2 and rows[0]["size_max"] == 4
    assert rows[0]["user_distinct"] == 2
    assert {t["value"] for t in rows[0]["topk"]} == {"u1", "u2"}


@pytest.mark.mesh
def test_mesh_state_matches_single_device():
    """Cross-chip merge + windowed flux state on the simulated 8-device
    mesh: bit-identical to the unsharded state (the tier-1 acceptance
    lane)."""
    if len(__import__("jax").devices()) < 8:
        pytest.skip("need the simulated 8-device mesh")
    bodies = [{"tenant": ["a", "b", "c"][i % 3], "user": f"u{i % 11}",
               "size": i} for i in range(100)]
    plain = FluxState(FluxSpec("t", group_by=("tenant",),
                               distinct=("user",), numeric=("size",),
                               topk_field="user"))
    meshy = FluxState(FluxSpec("t", group_by=("tenant",),
                               distinct=("user",), numeric=("size",),
                               topk_field="user", mesh=True))
    assert meshy._mesh is not None
    absorb_py(plain, bodies)
    absorb_py(meshy, bodies)
    f1 = _state_fingerprint(plain)
    f2 = _state_fingerprint(meshy)
    assert f1 == f2
