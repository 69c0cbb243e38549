"""Typed GROUP BY keys in the flux plane, on NEXmark's Query 5 ("hot
items"): ``COUNT(*) ... GROUP BY auction`` over a hopping window, the
auction a msgpack integer, behind a grep filter that keeps the bids.

The contract (FLUX.md "Typed group keys"): the batched path, the
per-record twin and the mesh lane give the exact ``_Agg`` path's rows —
values **and Python types** — and the plain reference's counts
(``tests/nexmark_reference.py``); a chunk whose key column holds what
the batched path cannot key exactly declines before any commit; no
input falls silently into the null group.
"""

import importlib.util
import json
import os
import random
import socket
import sys
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import fluentbit_tpu as flb  # noqa: E402
from fluentbit_tpu.codec.events import decode_events, encode_event  # noqa: E402
from fluentbit_tpu.codec.msgpack import Unpacker, packb  # noqa: E402
from fluentbit_tpu.flux.state import (TIMING_KEYS, FluxSpec,  # noqa: E402
                                      FluxState, KeyCol, WindowSpec)

import nexmark_reference as ref  # noqa: E402
from test_flux_sql import make_engine, same_value  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")

HOPPING = ("CREATE STREAM q5 WITH (tag='nexmark.q5') AS SELECT auction, "
           "COUNT(*) AS num FROM TAG:'nexmark' WINDOW HOPPING (10 SECOND, "
           "ADVANCE BY 5 SECOND) GROUP BY auction;")
TUMBLING = HOPPING.replace("HOPPING (10 SECOND, ADVANCE BY 5 SECOND)",
                           "TUMBLING (5 SECOND)")
N_PANES = {HOPPING: 2, TUMBLING: 1}


@pytest.fixture(scope="module")
def nexmark():
    """``benchmark/corpora/nexmark_events.py`` (it imports ``wire`` from
    the benchmark's directory)."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        "t1_nexmark_events",
        os.path.join(BENCH, "corpora", "nexmark_events.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def chunk_of(records, t=1000.0) -> bytes:
    return b"".join(encode_event(r, t) for r in records)


def q5_engine(sql, allow_flux, mesh=False):
    """``make_engine`` with the configuration's bid filter, registered
    after the task as a config file's [FILTER] is."""
    e, ins, task, out, clk = make_engine(sql, allow_flux, mesh=mesh)
    g = e.filter("grep")
    g.set("regex", "event_type ^bid$")
    g.configure()
    g.plugin.init(g, e)
    return e, ins, task, out, clk


def flux_of(e):
    return next(f.plugin for f in e.filters if f.plugin.name == "flux")


def declines(e) -> int:
    return int(sum(v for _, v in e.m_filter_batch_decline.samples()))


def run_panes(sql, panes, allow_flux, mesh=False, twin=False):
    """Feed each pane's chunks, close after each; drain at the end.
    → (emissions, engine)."""
    e, ins, task, out, clk = q5_engine(sql, allow_flux, mesh=mesh)
    if twin:
        # the hook declines every chunk: the engine finishes it on the
        # per-record twin (the decoded-tail continuation)
        flux_of(e).process_batch = lambda chunk: None
    for k, pane in enumerate(panes):
        for records in pane:
            e.input_log_append(ins, "nexmark", chunk_of(records))
        clk[0] = 1000.0 + 5.0 * (k + 1) + 0.25
        task.tick()
    task.drain()
    return [rows for _tag, rows in out], e


def assert_same_rows(got, want):
    assert len(got) == len(want)
    for rows1, rows2 in zip(got, want):
        assert len(rows1) == len(rows2)
        for r1, r2 in zip(rows1, rows2):
            assert list(r1) == list(r2) == ["auction", "num"]
            for k in r2:
                assert same_value(r1[k], r2[k]), (k, r1[k], r2[k])


# ------------------------------------ flux ≡ the exact path ≡ reference

@pytest.mark.parametrize("sql", [HOPPING, TUMBLING],
                         ids=["hopping_10_5", "tumbling_5"])
@pytest.mark.parametrize("seed", [5, 2**31 + 9])
def test_integer_keys_equal_exact_path_and_plain_reference(nexmark, sql,
                                                           seed):
    events = nexmark.events(6000, seed, {})
    chunks = [events[i:i + 1000] for i in range(0, 6000, 1000)]
    panes = [chunks[0:2], chunks[2:3], [], chunks[3:6]]  # one pane empty
    flux, e1 = run_panes(sql, panes, True)
    exact, _e2 = run_panes(sql, panes, False)
    assert declines(e1) == 0 and flux_of(e1).state.records_total == sum(
        ref.is_bid(r) for r in events)
    assert_same_rows(flux, exact)
    flat = [[r for chunk in pane for r in chunk] for pane in panes]
    n = N_PANES[sql]
    want = [ref.rows_of(ref.window_counts(flat, k, n))
            for k in range(len(flat))]
    want.append(ref.rows_of(ref.drain_counts(flat, [],
                                             n if sql is HOPPING else 0)))
    want = [w for w in want if w]      # a window that holds no bid
    assert len(flux) == len(want)      # emits nothing
    for rows, counts in zip(flux, want):
        assert {r["auction"]: r["num"] for r in rows} == counts
        assert all(type(r["auction"]) is int and type(r["num"]) is int
                   for r in rows)
    last_close = flux[len([w for w in want[:len(flat)] if w]) - 1] \
        if sql is TUMBLING else flux[-2]
    top = max(r["num"] for r in last_close)
    assert sorted(r["auction"] for r in last_close if r["num"] == top) \
        == ref.hot_items(ref.window_counts(flat, len(flat) - 1, n))


def test_batched_equals_per_record_twin(nexmark):
    events = nexmark.events(3000, 17, {})
    panes = [[events[:1000]], [events[1000:2000], events[2000:]]]
    batched, e1 = run_panes(HOPPING, panes, True)
    twin, e2 = run_panes(HOPPING, panes, True, twin=True)
    assert declines(e1) == 0 and declines(e2) == 3
    assert json.dumps(batched) == json.dumps(twin)
    assert_same_rows(batched, twin)


# ------------------------------------------------ the typed-key rule

def mixed(key_values, t=1000.0) -> bytes:
    return chunk_of([({"event_type": "bid", "auction": v} if v is not ...
                      else {"event_type": "bid"}) for v in key_values], t)


MIXED_CASES = {
    # strings, integers (one past 2^53, the ends of int64) and a missing
    # key: what the batched path keys exactly
    "strings_integers_missing": (
        ["a1", 1007, ..., 2**53 + 1, "a1", 1007, -2**63, 2**63 - 1,
         None, "1007", 0, ""], 0),
    "a_float": ([1007, 1.5, "a1", 1007.0, ...], 1),
    "a_bool": ([1, True, 0, False, "a1"], 1),
    "an_integer_past_int64": ([2**63, 2**64 - 1, 2**63 - 1, 5], 1),
    "a_bin": ([b"a1", "a1", 5], 1),
}


@pytest.mark.parametrize("case", sorted(MIXED_CASES))
def test_mixed_key_column_takes_the_stated_rule(case):
    values, want_declines = MIXED_CASES[case]
    raws = [mixed(values), mixed([1007, "a1", ..., 3])]
    got = {}
    for allow_flux in (True, False):
        e, ins, task, out, clk = q5_engine(HOPPING, allow_flux)
        for k, raw in enumerate(raws):
            e.input_log_append(ins, "nexmark", raw)
            clk[0] = 1000.0 + 5.0 * (k + 1) + 0.25
            task.tick()
        task.drain()
        got[allow_flux] = [rows for _t, rows in out]
        if allow_flux:
            # the chunk that holds such a key declined BEFORE any
            # commit: every record counted once, by the twin
            assert declines(e) == want_declines
            st = flux_of(e).state
            assert st.records_total == len(values) + 4
            assert st.batches_total == 2
    assert_same_rows(got[True], got[False])
    # never a silent null group: the null group holds the missing and
    # the nil keys alone
    nulls = values.count(...) + values.count(None)
    first = {(type(r["auction"]), r["auction"]): r["num"]
             for r in got[True][0]}
    assert first.get((type(None), None), 0) == nulls


def test_shipped_flux_hook_still_commits_last():
    """``analysis.batch``: every decline of the flux hook — the new one
    on a key column among them — is dominated by zero commits."""
    from fluentbit_tpu.analysis import lint_paths

    assert lint_paths([os.path.join(REPO, "fluentbit_tpu", "flux")]) == []


def test_native_key_stager_kinds():
    from fluentbit_tpu import native

    values = [1007, "abc", 1.5, True, None, 2**63, 2**63 - 1, -5, [1],
              {"a": 1}, b"xx", -2**63, 70000, 2**40]
    buf = b"".join(encode_event({"k": v}, 1.0) for v in values) \
        + encode_event({"z": 1}, 1.0) + encode_event("no map", 1.0)
    ints, kinds, n = native.stage_field_i64(buf, b"k")
    assert n == len(values) + 2
    assert kinds.tolist() == [2, 1, 3, 3, 0, 3, 2, 2, 3, 3, 3, 2, 2, 2,
                              0, 0]
    assert ints[kinds == 2].tolist() == [1007, 2**63 - 1, -5, -2**63,
                                         70000, 2**40]
    assert not ints[kinds != 2].any()


# --------------------------------------------------- state: snapshots

def int_state(mesh=False, **kw) -> FluxState:
    return FluxState(FluxSpec("q5", group_by=("auction",), mesh=mesh,
                              window=WindowSpec("hopping", 10, 5), **kw),
                     now=lambda: 1000.0)


def absorb_ints(state, ints, strings=()):
    """One staged chunk: integer keys, then string keys."""
    n = len(ints) + len(strings)
    kind = np.asarray([2] * len(ints) + [1] * len(strings), np.uint8)
    vals = np.asarray(list(ints) + [0] * len(strings), np.int64)
    sb = sl = None
    if strings:
        sb = np.zeros((n, 256), np.uint8)
        sl = np.full((n,), -1, np.int32)
        for i, s in enumerate(strings, len(ints)):
            sb[i, :len(s)] = np.frombuffer(s, np.uint8)
            sl[i] = len(s)
    state.absorb_batch(n, {}, {}, {"auction": KeyCol(kind, vals, sb, sl)})


def counts_of(state) -> dict:
    return {key: g.count for key, g in state.live_groups()}


def test_snapshot_restore_carries_typed_keys(tmp_path):
    st = int_state()
    absorb_ints(st, [1007, 1100, 1007, 2**62], [b"1007", b"x"])
    st.tick(1005.5)                       # one pane into the ring
    absorb_ints(st, [1100, 5])
    path = str(tmp_path / "q5.snap")
    st.persist(path)
    back = int_state()
    assert back.load(path)
    assert counts_of(back) == counts_of(st) == {(1100,): 1, (5,): 1}
    assert [dict((k, g.count) for k, g in p.items())
            for p in back._panes] == [{(1007,): 2, (1100,): 1,
                                       (2**62,): 1, (b"1007",): 1,
                                       (b"x",): 1}]
    assert all(type(k[0]) in (int, bytes) for p in back._panes for k in p)
    assert back.window_closes_total == st.window_closes_total == 1
    # and goes on as the original does
    for s in (st, back):
        absorb_ints(s, [1007, 5])
    a = sorted((repr(k), g.count) for k, g in st.tick(1010.5))
    b = sorted((repr(k), g.count) for k, g in back.tick(1010.5))
    assert a == b and ("(1007,)", 3) in a and ("(b'1007',)", 1) in a


# ------------------------------------------------------ the mesh lane

@pytest.mark.mesh
def test_mesh_state_equals_single_device_state_with_integer_keys(nexmark):
    if len(jax.devices()) < 8:
        pytest.skip("need the simulated 8-device mesh")
    events = nexmark.events(2000, 23, {})
    panes = [[events[:1000]], [events[1000:]]]
    on_mesh, e1 = run_panes(HOPPING, panes, True, mesh=True)
    single, _e2 = run_panes(HOPPING, panes, True)
    assert flux_of(e1).state._mesh is not None and declines(e1) == 0
    assert_same_rows(on_mesh, single)
    tm = flux_of(e1).raw_timings
    assert tm["fused_absorbs"] == 2 and tm["host_absorbs"] == 0


@pytest.mark.mesh
def test_more_groups_than_the_fused_table_count_on_the_host_twin():
    """A chunk with more than ``_FUSED_MAX_GROUPS`` groups takes the
    host twin, and says so; the next, under it, the device program."""
    if len(jax.devices()) < 8:
        pytest.skip("need the simulated 8-device mesh")
    from fluentbit_tpu.core.spans import ShardedTimings

    st = int_state(mesh=True)
    assert st._mesh is not None
    st.timings = tm = ShardedTimings(TIMING_KEYS)
    many = FluxState._FUSED_MAX_GROUPS + 88
    absorb_ints(st, list(range(many)) + [3, 3])
    assert (tm["host_absorbs"], tm["fused_absorbs"]) == (1, 0)
    absorb_ints(st, list(range(300)) + [3])
    assert (tm["host_absorbs"], tm["fused_absorbs"]) == (1, 1)
    got = counts_of(st)
    assert len(got) == many and got[(3,)] == 5 and got[(299,)] == 2 \
        and got[(many - 1,)] == 1
    assert st.counts_platform == "cpu"


# ------------------------------------- one record time an emission

@pytest.mark.parametrize("allow_flux", [True, False],
                         ids=["flux", "exact"])
def test_rows_of_one_close_carry_one_time_the_next_close_a_later(
        nexmark, allow_flux):
    """``StreamProcessor._emit`` reads the clock once an emission."""
    e, ins, task, out, clk = q5_engine(HOPPING, allow_flux)
    task.emit = lambda tag, rows: e.sp._emit(task, tag, rows)
    appended = []
    e.sp._emitter.add_record = \
        lambda tag, data, n: appended.append((tag, data, n))
    events = nexmark.events(2000, 3, {})
    for k in range(2):
        e.input_log_append(ins, "nexmark",
                           chunk_of(events[k * 1000:(k + 1) * 1000]))
        clk[0] = 1000.0 + 5.0 * (k + 1) + 0.25
        task.tick()
        time.sleep(0.002)
    task.drain()
    assert [tag for tag, _d, _n in appended] == ["nexmark.q5"] * 3
    times = []
    for _tag, data, n in appended:
        evs = decode_events(data)
        assert len(evs) == n > 10
        assert len({ev.ts_float for ev in evs}) == 1
        times.append(evs[0].ts_float)
    assert times[0] < times[1] < times[2]


# ------------------- grep → flux in one raw chain, launches in flight

def forward_frame(tag, records, chunk_id) -> bytes:
    return packb([tag, [[1700000000, r] for r in records],
                  {"chunk": chunk_id}])


def test_grep_then_flux_with_launches_in_flight_commit_in_arrival_order(
        nexmark, monkeypatch):
    """The first chain in which a filter with a begin half (grep, two
    launches in flight) stands before a ``stateful_batch`` filter: flux
    absorbs what ``native.compact`` has just rewritten, in arrival
    order — the first-seen order of the drained rows says so."""
    from fluentbit_tpu.ops import fault
    from fluentbit_tpu.plugins.filter_grep import GrepFilter

    fault.reset()
    # grep on the staged launch through its lane (jax's CPU backend
    # stands where the chip is): what a chip attached would choose
    monkeypatch.setattr(GrepFilter, "_raw_engine",
                        lambda self: (None, False))
    ctx = flb.create(flush="50ms", grace="2")
    ctx.input("forward", listen="127.0.0.1", port="0")
    ctx.sp_task(HOPPING.replace("10 SECOND", "3600 SECOND")
                .replace("5 SECOND", "1800 SECOND"))
    ctx.filter("grep", match="nexmark", regex="event_type ^bid$",
               tpu_batch_records="1")
    main, side = [], []
    ctx.output("lib", match="nexmark",
               callback=lambda d, _t: main.append(bytes(d)))
    ctx.output("lib", match="nexmark.q5",
               callback=lambda d, _t: side.append(bytes(d)))
    engine = ctx.engine
    assert [f.plugin.name for f in engine.filters] == ["grep", "flux"]
    srv = engine.inputs[0].plugin
    events = nexmark.events(12 * 512, 41, {})
    frames = [events[i * 512:(i + 1) * 512] for i in range(12)]
    ctx.start()
    try:
        deadline = time.time() + 20
        while not srv.bound_port and time.time() < deadline:
            time.sleep(0.005)
        sock = socket.create_connection(("127.0.0.1", srv.bound_port))
        sock.settimeout(20)
        sock.sendall(b"".join(forward_frame("nexmark", fr, f"c{i:02d}")
                              for i, fr in enumerate(frames)))
        acks, un = [], Unpacker()
        while len(acks) < len(frames):
            un.feed(sock.recv(65536))
            acks += [m["ack"] for m in un]
        sock.close()
        assert acks == [f"c{i:02d}" for i in range(12)]
        assert srv.n_prelaunched >= 1
        assert declines(engine) == 0
        lane = fault.snapshot()["grep"]
        assert lane["launches"] == lane["ok"] == 12
        assert lane["begun_in_flight"] >= 1
        state = flux_of(engine).state
        bids = [r for fr in frames for r in fr if ref.is_bid(r)]
        assert state.records_total == len(bids)
    finally:
        ctx.stop()
    # the main sink: the bids, unchanged, in order
    kept = [ev.body for part in main for ev in decode_events(part)]
    assert kept == bids
    # the drain's rows: first-seen order over the frames as they came
    rows = [ev.body for part in side for ev in decode_events(part)]
    want = ref.bids_by_auction(bids)
    assert [(r["auction"], r["num"]) for r in rows] == list(want.items())


# ------------------------------------------------ counters are read

@pytest.mark.parametrize("key", [k for k in TIMING_KEYS
                                 if k != "absorb_s"])
def test_every_flux_counter_this_pr_adds_is_read(key):
    """An always-on counter that nothing reads is only a cost: each new
    key of the flux filter's ``raw_timings`` is a term of a declared
    per-layer metric of the benchmark, or compared by the reference of
    ``nexmark-q5``."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        declared = {m["name"] for m in json.load(f)["per_layer"]}
    counter = f"filter.flux.{key}"
    terms = set()
    for name in declared:
        with open(os.path.join(BENCH, "layer_metrics",
                               name + ".json")) as f:
            spec = json.load(f)
        if spec["reader"] == "counters:ratio":
            terms |= {spec["args"]["num"], spec["args"]["den"]}
    with open(os.path.join(BENCH, "reference", "nexmark-q5.py")) as f:
        reference = f.read()
    assert counter in terms or f'"{counter}"' in reference \
        or f'pre + "{key}"' in reference, counter
