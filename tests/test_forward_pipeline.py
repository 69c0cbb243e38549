"""in_forward as a two-stage pipeline (plugins/net_forward.py): decode on
the engine's loop, absorb on one worker thread per instance, acks on
the loop in arrival order.

Loopback, no chip: the filter chain is one raw-path filter (``fwd_gate``)
whose ``process_batch`` can be held on an ``Event``, which is what a
device launch is to the worker — a wait with the GIL released. Every
behaviour is a case of ``test_forward_pipeline``.

The ``staged`` cases put ``filter_grep`` before the gate, on the staged
launch through the "grep" lane (jax's CPU backend stands where the chip
is): the filter that offers the begin half of its launch, so that a
frame decoded behind a busy worker has its launch begun ahead of its
turn (``forward.prelaunch``).
"""

import gzip
import logging
import socket
import sys
import threading
import time

import pytest

import fluentbit_tpu as flb
from fluentbit_tpu import failpoints
from fluentbit_tpu.codec import _native_codec
from fluentbit_tpu.codec.events import decode_events
from fluentbit_tpu.codec.msgpack import Unpacker, packb
from fluentbit_tpu.core.plugin import FilterPlugin, registry
from fluentbit_tpu.ops import fault
from fluentbit_tpu.plugins import net_forward
from fluentbit_tpu.plugins.filter_grep import GrepFilter

WAIT_S = 20.0
#: whether the C cut serves here (``fbtpu_codec.forward_cut``): without
#: a toolchain, or under ``FBTPU_NO_NATIVE``, every frame takes the
#: object path and the cases hold for it
HAVE_CUT = _native_codec.load() is not None


def wait_for(cond, timeout=WAIT_S, interval=0.005):
    deadline = time.time() + timeout
    while time.time() < deadline:
        v = cond()
        if v:
            return v
        time.sleep(interval)
    raise TimeoutError("condition not met")


def _register_gate():
    if "fwd_gate" in registry.filters:
        return

    @registry.register
    class GateFilter(FilterPlugin):
        """Passes every chunk untouched on the raw path; while ``open``
        is clear a chunk waits inside ``process_batch``."""

        name = "fwd_gate"

        def init(self, instance, engine) -> None:
            self.open = threading.Event()
            self.open.set()
            self.entered = 0
            self.seen = []  # (tag, pool stamp, thread name) per chunk
            self.pause_s = 0.0

        def can_process_batch(self) -> bool:
            return True

        def process_batch(self, chunk):
            if chunk.n is None:
                return None  # in_forward always counts; decline else
            self.entered += 1
            self.seen.append((chunk.tag, chunk.src.pool.stamp,
                              threading.current_thread().name))
            if self.pause_s:
                time.sleep(self.pause_s)
            assert self.open.wait(WAIT_S), "the gate was never opened"
            return chunk.n, chunk.as_bytes()


#: the staged cases' rules: a record passes unless its ``chunk`` says drop
#: (legacy mode: the first rule that decides a record decides it)
GREP_RULES = {"exclude": "chunk ^drop", "regex": "chunk ."}


class Aggregator:
    """forward input → gate filter → lib output; ``staged``: forward
    input → grep on the staged launch → gate filter → lib output, and a
    closed gate holds a frame before its grep as well."""

    def __init__(self, tmp_path=None, staged=False, monkeypatch=None,
                 **props):
        _register_gate()
        self.threads_before = set(threading.enumerate())
        svc = {"flush": "50ms", "grace": "2"}
        if tmp_path is not None:
            svc["storage.path"] = str(tmp_path / "agg")
        self.ctx = flb.create(**svc)
        self.ctx.input("forward", listen="127.0.0.1", port="0", **props)
        if staged:
            fault.reset()  # a lane of this aggregator's own
            # every grep instance (a reloaded one too) on the staged
            # launch: what a chip attached would choose
            monkeypatch.setattr(GrepFilter, "_raw_engine",
                                lambda self: (None, False))
            self.ctx.filter("grep", match="*", tpu_batch_records="1",
                            **GREP_RULES)
        self.ctx.filter("fwd_gate", match="*")
        self.got = []
        self.ctx.output("lib", match="*",
                        callback=lambda d, t: self.got.append((t, bytes(d))))
        self.engine = self.ctx.engine
        self.srv = self.engine.inputs[0].plugin
        self.gate = self.engine.filters[-1].plugin
        self.grep_entered = 0
        if staged:
            real = GrepFilter.process_batch

            def held(plugin, chunk):
                self.grep_entered += 1
                assert self.gate.open.wait(WAIT_S), "never opened"
                return real(plugin, chunk)

            monkeypatch.setattr(GrepFilter, "process_batch", held)
        self.ctx.start()
        self.port = wait_for(lambda: self.srv.bound_port)
        self.stopped = False
        self.edges = []

    def lane(self) -> dict:
        """The "grep" lane's counters (the staged cases' own lane)."""
        return fault.lane("grep").stats()

    def connect(self) -> "Edge":
        self.edges.append(Edge(self.port))
        return self.edges[-1]

    def records(self) -> list:
        return [ev.body for _t, d in self.got for ev in decode_events(d)]

    def workers(self) -> list:
        return [t for t in threading.enumerate()
                if t.name.startswith("flb-fw-") and t.is_alive()
                and t not in self.threads_before]

    def stop(self) -> None:
        if not self.stopped:
            self.stopped = True
            self.gate.open.set()
            self.ctx.stop()
        for edge in self.edges:
            edge.close()


class Edge:
    """A raw Forward-protocol client: frames out, acks back."""

    def __init__(self, port: int):
        self.sock = socket.create_connection(("127.0.0.1", port))
        self.sock.settimeout(WAIT_S)
        self.u = Unpacker()

    def send(self, data: bytes) -> None:
        self.sock.sendall(data)

    def acks(self, n: int, timeout=WAIT_S) -> list:
        """The next ``n`` acks (fewer when ``timeout`` runs out)."""
        out = []
        self.sock.settimeout(timeout)
        try:
            while len(out) < n:
                for msg in self.u:
                    out.append(msg["ack"])
                if len(out) < n:
                    data = self.sock.recv(65536)
                    if not data:
                        break
                    self.u.feed(data)
        except socket.timeout:
            pass
        return out

    def close(self) -> None:
        self.sock.close()


def frame(chunk: str, n=8, tag="app", pad=600, **option) -> bytes:
    """A Forward-mode frame: 5 KB as events with the defaults, more
    than in_forward absorbs on the loop (``_INLINE_BYTES``)."""
    entries = [[1700000000 + i, {"chunk": chunk, "i": i, "pad": "x" * pad}]
               for i in range(n)]
    return packb([tag, entries, {"chunk": chunk, **option}])


def odd_frame(chunk: str, n=8) -> bytes:
    """``frame``'s records in entries of three elements: what the C cut
    hands back whole, and the object path trims to ``[time, record]``."""
    entries = [[1700000000 + i, {"chunk": chunk, "i": i, "pad": "x" * 600},
                None] for i in range(n)]
    return packb(["app", entries, {"chunk": chunk}])


def packed_frame(chunk: str, n=8, compress=False, **option) -> bytes:
    """PackedForward as ``out_forward`` frames it, gzip'd or not."""
    blob = b"".join(
        packb([1700000000 + i, {"chunk": chunk, "i": i, "pad": "x" * 600}])
        for i in range(n))
    option = {"size": n, "chunk": chunk, **option}
    if compress:
        blob, option["compressed"] = gzip.compress(blob), "gzip"
    return packb(["app", blob, option])


@pytest.fixture
def no_codec(monkeypatch):
    """From the call on, the state ``FBTPU_NO_NATIVE=1`` leaves the
    codec in: ``load()`` gives None and every message is objects."""
    def switch():
        monkeypatch.setattr(_native_codec, "_mod", None)
        monkeypatch.setattr(_native_codec, "_tried", True)
    return switch


class SpanLog:
    """Stands where ``net_forward.span`` is: names and threads."""

    def __init__(self):
        self.names = []

    def __call__(self, name, **_ids):
        self.names.append((name, threading.current_thread().name))
        return net_forward.bind()  # the shared no-op outside a session

    def count(self, name) -> int:
        return sum(1 for n, _t in self.names if n == name)


# ------------------------------------------------------------- the cases


def case_next_frame_is_decoded_while_the_last_is_absorbed(
        agg, monkeypatch, **_):
    spans = SpanLog()
    monkeypatch.setattr(net_forward, "span", spans)
    agg.gate.open.clear()
    edge = agg.connect()
    edge.send(frame("a") + frame("b"))
    wait_for(lambda: agg.gate.entered == 1)
    # b is whole and re-encoded while a sits in the filter
    wait_for(lambda: agg.srv.n_overlapped == 1)
    assert spans.count("forward.reencode") == 2
    assert spans.count("forward.overlap") == 1
    assert agg.srv.n_absorbed == 0 and agg.gate.entered == 1
    assert agg.srv.health_block()["overlapped"] == 1
    # the absorb is on the instance's worker, the decode is not
    assert agg.gate.seen[0][2].startswith("flb-fw-")
    decode_threads = {t for n, t in spans.names if n == "forward.reencode"}
    absorb_threads = {t for n, t in spans.names if n == "forward.absorb"}
    assert len(decode_threads) == 1 and not decode_threads & absorb_threads
    agg.gate.open.set()
    assert edge.acks(2) == ["a", "b"]
    assert agg.srv.n_absorbed == 2 and agg.srv.n_overlapped == 1
    # a frame that found the worker idle wrote no overlap span
    edge.send(frame("c"))
    assert edge.acks(1) == ["c"]
    assert spans.count("forward.overlap") == 1
    text = agg.engine.metrics.to_prometheus()
    assert "fluentbit_forward_overlapped_chunks_total" in text
    edge.close()


def case_acks_leave_in_send_order(agg, **_):
    agg.gate.pause_s = 0.01
    edge = agg.connect()
    ids = [f"c{i:02d}" for i in range(12)]
    edge.send(b"".join(frame(c) for c in ids))
    assert edge.acks(len(ids)) == ids
    assert agg.srv.n_absorbed == len(ids)
    assert agg.srv.n_cut == (len(ids) if HAVE_CUT else 0)
    # every frame but the first was decoded while the one before it was
    # with the worker: a frame that has waited its turn is handed over
    # before the loop decodes the next, not after
    assert agg.srv.n_overlapped >= len(ids) - 2
    # one worker, first come first served: the chunks entered the
    # filter in the order they were sent
    assert agg.gate.entered == len(ids)
    agg.ctx.flush_now()
    wait_for(lambda: len(agg.records()) == 8 * len(ids))
    order = [r["chunk"] for r in agg.records() if r["i"] == 0]
    assert order == ids
    edge.close()


def case_no_ack_before_absorb_and_ledger(agg, monkeypatch, **_):
    at_ack = []
    real = net_forward.packb

    def packb_seeing(obj, *a, **kw):
        if isinstance(obj, dict) and "ack" in obj:
            at_ack.append((obj["ack"], agg.srv.n_absorbed,
                           agg.srv._ledger.snapshot().get(obj["ack"])))
        return real(obj, *a, **kw)

    monkeypatch.setattr(net_forward, "packb", packb_seeing)
    agg.gate.open.clear()
    edge = agg.connect()
    edge.send(frame("a") + frame("b"))
    wait_for(lambda: agg.srv.n_overlapped == 1)
    # nothing on the wire, nothing counted, nothing in the ledger
    assert edge.acks(1, timeout=0.3) == []
    assert agg.srv.n_absorbed == 0
    assert agg.srv._ledger.snapshot() == {}
    agg.gate.open.set()
    assert edge.acks(2) == ["a", "b"]
    assert at_ack == [("a", 1, 1), ("b", 2, 1)]
    edge.close()


def case_handover_full_stops_reading(agg, monkeypatch, **_):
    fed = []

    class CountingUnpacker(net_forward.Unpacker):
        def feed(self, data):
            fed.append(len(data))
            return super().feed(data)

    decoded = []  # entries of each frame the loop has made events of
    real = net_forward._chunk_events

    def chunk_events(msg, option):
        buf, n, cut = real(msg, option)
        decoded.append(n)
        return buf, n, cut

    monkeypatch.setattr(net_forward, "Unpacker", CountingUnpacker)
    monkeypatch.setattr(net_forward, "_chunk_events", chunk_events)
    agg.gate.open.clear()
    edge = agg.connect()
    # one frame for the worker and as many as the loop may hold decoded
    # behind it, then one of 1 MB
    ids = ["a", "b", "c"][:2 + net_forward._DECODE_AHEAD]
    held = b"".join(frame(c) for c in ids)
    big = frame("z", n=64, pad=16384)
    sender = threading.Thread(target=edge.send, args=(held + big,))
    sender.start()
    wait_for(lambda: agg.srv.n_overlapped == len(ids) - 1)
    time.sleep(0.3)
    # a is being absorbed, the others wait beside it, z is nowhere: the
    # handler has not taken another byte off the connection (what the
    # kernel's buffers do not hold either stops the peer: TCP flow
    # control)
    assert decoded == [8] * len(ids)
    assert sum(fed) <= len(held) + 65536 < len(held + big)
    agg.gate.open.set()
    assert edge.acks(len(ids) + 1) == ids + ["z"]
    sender.join(WAIT_S)
    assert not sender.is_alive() and decoded == [8] * len(ids) + [64]
    edge.close()


def case_withheld_ack_then_the_next_frame(agg, **_):
    """The tenant of frame a is over quota for longer than the defer
    window: a's ack is withheld, b waits behind a and is then absorbed
    and acked, as when everything ran on the loop."""
    t = agg.engine.qos.tenant("slow", rate=1.0, overflow="defer")
    assert t.bucket.try_take(100_000)
    edge = agg.connect()
    t0 = time.monotonic()
    edge.send(frame("a", tenant="slow") + frame("b", tenant="fast"))
    assert edge.acks(1) == ["b"]
    assert time.monotonic() - t0 >= 0.3  # b waited a's window out
    assert agg.srv.n_withheld_acks == 1 and agg.srv.n_deferred_acks == 1
    assert agg.srv.n_absorbed == 1
    assert [tag_stamp[1] for tag_stamp in agg.gate.seen] == [("fast", None)]
    assert edge.acks(1, timeout=0.2) == []
    assert list(agg.srv._ledger.snapshot()) == ["b"]
    edge.close()


def case_deferred_frame_is_absorbed_when_the_quota_allows(agg, **_):
    """A DEFER that clears inside the window: a is acked late, b after
    it; meanwhile another connection's chunk is not held up."""
    t = agg.engine.qos.tenant("slow", rate=1.0, overflow="defer")
    assert t.bucket.try_take(100_000)
    edge, other = agg.connect(), agg.connect()
    edge.send(frame("a", tenant="slow") + frame("b", tenant="slow"))
    wait_for(lambda: agg.srv.n_deferred_acks == 1)
    other.send(frame("o"))
    assert other.acks(1) == ["o"]  # a's wait does not block the worker
    assert agg.srv.n_absorbed == 1
    # the quota is raised: the bucket admits again
    t.bucket.capacity = t.bucket.tokens = 1e9
    assert edge.acks(2) == ["a", "b"]
    assert agg.srv.n_withheld_acks == 0 and agg.srv.n_absorbed == 3
    edge.close()
    other.close()


case_deferred_frame_is_absorbed_when_the_quota_allows.props = {
    "defer_ack_window": "15"}


def case_two_tenants_never_see_each_others_stamp(agg, **_):
    agg.gate.pause_s = 0.002
    edges = {"acme": agg.connect(), "zeta": agg.connect()}
    n = 15
    senders = [threading.Thread(target=e.send, args=(b"".join(
        frame(f"{name}-{i}", tag=name, tenant=name, priority=k)
        for i in range(n)),)) for k, (name, e) in enumerate(edges.items())]
    for s in senders:
        s.start()
    for k, (name, e) in enumerate(edges.items()):
        assert e.acks(n) == [f"{name}-{i}" for i in range(n)]
    for s in senders:
        s.join(WAIT_S)
    assert len(agg.gate.seen) == 2 * n
    for tag, stamp, _thread in agg.gate.seen:
        assert stamp == (tag, 0 if tag == "acme" else 1)
    # and the stamp is gone between chunks
    ins = agg.engine.inputs[0]
    assert ins.pool.stamp is None and ins.qos_exempt is False
    assert len({th for _t, _s, th in agg.gate.seen}) == 1
    for e in edges.values():
        e.close()


def case_connection_closed_mid_absorb(agg, caplog, **_):
    caplog.set_level(logging.WARNING)
    agg.gate.open.clear()
    edge = agg.connect()
    edge.send(frame("a"))
    wait_for(lambda: agg.gate.entered == 1)
    edge.close()
    time.sleep(0.1)
    agg.gate.open.set()
    wait_for(lambda: agg.srv.n_absorbed == 1)
    assert agg.srv._ledger.snapshot() == {"a": 1}  # absorbed once
    # the resend on a new connection is acked from the ledger
    again = agg.connect()
    again.send(frame("a"))
    assert again.acks(1) == ["a"]
    assert agg.gate.entered == 1 and agg.srv.n_absorbed == 1
    again.close()
    agg.ctx.flush_now()
    wait_for(lambda: len(agg.records()) == 8)
    agg.stop()
    bad = [r for r in caplog.records
           if r.name in ("asyncio", "flb.forward")]
    assert not bad, [r.getMessage() for r in bad]


def case_engine_stop_with_a_frame_in_the_worker(agg, **_):
    agg.gate.open.clear()
    edge = agg.connect()
    edge.send(frame("a") + frame("b"))
    wait_for(lambda: agg.srv.n_overlapped == 1)
    assert len(agg.workers()) == 1
    stopper = threading.Thread(target=agg.ctx.stop)
    agg.stopped = True
    stopper.start()
    time.sleep(0.3)
    assert stopper.is_alive()  # the stop waits for the worker
    agg.gate.open.set()
    stopper.join(WAIT_S)
    assert not stopper.is_alive()
    # a was handed over: absorbed whole, flushed, acked. b was not:
    # no ack, not absorbed, the edge would resend it
    assert agg.workers() == []
    assert [r["chunk"] for r in agg.records() if r["i"] == 0] == ["a"]
    assert len(agg.records()) == 8
    assert edge.acks(2, timeout=1.0) == ["a"]
    assert agg.srv.n_absorbed == 1
    assert agg.srv._tries_handed == agg.srv._tries_done == 1
    edge.close()


def case_wrappers_set_after_init_see_every_call(agg, monkeypatch, **_):
    """What the benchmark's ``install_spans`` does after the pipeline
    has started: a subclass in ``net_forward.Unpacker``, a wrapper in
    ``net_forward._entries_to_events``, an instance attribute over
    ``engine.input_log_append``. A frame the C cut serves is decoded
    beneath ``__next__`` alone; one it hands back goes through all
    three names as it always did."""
    calls = {"feed": 0, "next": 0, "entries": 0, "append": 0}
    threads = {"entries": set(), "append": set()}

    class TimedUnpacker(net_forward.Unpacker):
        def feed(self, data):
            calls["feed"] += 1
            return super().feed(data)

        def __next__(self):
            calls["next"] += 1
            return super().__next__()

    real_entries = net_forward._entries_to_events

    def entries_seen(entries):
        calls["entries"] += 1
        threads["entries"].add(threading.current_thread().name)
        return real_entries(entries)

    real_append = agg.engine.input_log_append

    def append_seen(*a, **kw):
        calls["append"] += 1
        threads["append"].add(threading.current_thread().name)
        return real_append(*a, **kw)

    def packed(chunk, entry):
        return packb(["app", b"".join(
            packb(entry(chunk, i)) for i in range(8)), {"chunk": chunk}])

    def plain(chunk, i):
        return [1700000000 + i, {"chunk": chunk, "i": i, "pad": "x" * 600}]

    def of_three(chunk, i):  # the cut hands the whole message back
        return plain(chunk, i) + [None]

    monkeypatch.setattr(net_forward, "Unpacker", TimedUnpacker)
    monkeypatch.setattr(net_forward, "_entries_to_events", entries_seen)
    agg.engine.input_log_append = append_seen
    try:
        edge = agg.connect()
        ids = ["w0", "w1", "w2", "w3"]
        edge.send(b"".join(frame(c) for c in ids))
        assert edge.acks(4) == ids
        edge.send(packed("p", plain))
        assert edge.acks(1) == ["p"]
        served = agg.srv.n_cut
        assert served == (5 if HAVE_CUT else 0)
        assert calls["entries"] == 5 - served and calls["next"] >= 5
        edge.send(packb(["app", [of_three("o", i) for i in range(8)],
                         {"chunk": "o"}]))
        edge.send(packed("q", of_three))  # an inner Unpacker as well
        assert edge.acks(2) == ["o", "q"]
        edge.close()
    finally:
        del agg.engine.input_log_append
    assert agg.srv.n_cut == served
    assert calls["entries"] == 7 - served and calls["append"] == 7
    assert calls["feed"] >= 2 and calls["next"] >= 7 + 8
    assert len(threads["append"]) == 1
    assert not threads["append"] & threads["entries"]
    agg.ctx.flush_now()
    wait_for(lambda: len(agg.records()) == 8 * 7)
    assert [r["i"] for r in agg.records()] == list(range(8)) * 7


def case_same_chunk_on_two_connections_is_absorbed_once(agg, **_):
    """A resend that arrives while the first delivery is still being
    absorbed: the dedup check and the ledger record are one step on the
    worker, so the second delivery finds the first one's record."""
    agg.gate.open.clear()
    first, second = agg.connect(), agg.connect()
    first.send(frame("dup"))
    wait_for(lambda: agg.gate.entered == 1)
    second.send(frame("dup"))
    time.sleep(0.2)
    agg.gate.open.set()
    assert first.acks(1) == ["dup"] and second.acks(1) == ["dup"]
    assert agg.gate.entered == 1 and agg.srv.n_absorbed == 1
    assert agg.srv._ledger.snapshot() == {"dup": 1}
    assert agg.srv._ledger.dedup_hits == 1
    first.close()
    second.close()


def case_many_connections_lose_nothing(agg, **_):
    """More connections than cores, a short switch interval: every
    frame acked in its connection's order, absorbed once, the tries
    handed to the worker all accounted for."""
    n_conns, n_frames = 12, 15
    saved = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        edges = [agg.connect() for _ in range(n_conns)]
        acked = [None] * n_conns

        def drive(k):
            ids = [f"k{k}-{i}" for i in range(n_frames)]
            # two in three go to the worker, the third is small enough
            # to be absorbed on the loop when the worker is idle
            edges[k].send(b"".join(
                frame(c, n=8 if i % 3 else 1, tag=f"t{k}",
                      tenant=f"ten{k % 3}")
                for i, c in enumerate(ids)))
            acked[k] = (edges[k].acks(n_frames), ids)

        drivers = [threading.Thread(target=drive, args=(k,))
                   for k in range(n_conns)]
        for d in drivers:
            d.start()
        for d in drivers:
            d.join(4 * WAIT_S)
            assert not d.is_alive()
    finally:
        sys.setswitchinterval(saved)
    for got, ids in acked:
        assert got == ids
    total = n_conns * n_frames
    assert agg.srv.n_absorbed == total == agg.gate.entered
    assert agg.srv._tries_handed == agg.srv._tries_done == total
    assert set(agg.srv._ledger.snapshot().values()) == {1}
    for tag, stamp, _th in agg.gate.seen:
        assert stamp == (f"ten{int(tag[1:]) % 3}", None)
    threads = {th.startswith("flb-fw-") for _t, _s, th in agg.gate.seen}
    assert threads == {True, False}
    agg.ctx.flush_now()
    wait_for(lambda: len(agg.records()) == n_conns * sum(
        8 if i % 3 else 1 for i in range(n_frames)))
    for e in edges:
        e.close()


def case_small_chunks_are_absorbed_on_the_loop_in_order(agg, **_):
    """Message mode and other chunks under ``_INLINE_BYTES``: absorbed
    where they were decoded when the worker is idle, behind it when it
    is not — one absorb at a time either way, acks in send order."""
    edge = agg.connect()
    small = [packb(["app", 1700000000 + i, {"chunk": f"m{i}", "i": 0},
                    {"chunk": f"m{i}"}]) for i in range(20)]
    edge.send(b"".join(small))
    assert edge.acks(20) == [f"m{i}" for i in range(20)]
    assert agg.srv.n_absorbed == 20 and agg.workers() == []
    assert not any(th.startswith("flb-fw-") for _t, _s, th in agg.gate.seen)
    assert agg.srv.n_overlapped == 0
    # a small chunk behind a large one that is held in the filter
    agg.gate.open.clear()
    edge.send(frame("big") + small[0].replace(b"m0", b"s0"))
    wait_for(lambda: agg.gate.entered == 21)
    time.sleep(0.1)
    assert agg.gate.entered == 21 and agg.srv.n_absorbed == 20
    agg.gate.open.set()
    assert edge.acks(2) == ["big", "s0"]
    assert agg.gate.seen[20][2].startswith("flb-fw-")
    assert [r["chunk"] for r in agg.records() if r["i"] == 0][-2:] \
        != ["s0", "big"]
    assert agg.srv._tries_handed == agg.srv._tries_done == 22
    edge.close()


def case_cut_and_object_path_land_the_same_chunk_bytes(
        agg, monkeypatch, no_codec, **_):
    """Forward, PackedForward and gzip frames, and one small enough for
    the loop's own absorb: what the engine is handed, and what the
    output gets, does not say which path decoded the frame. The counter,
    ``health_block()`` and the ``forward.cut`` span count the frames the
    C cut served, and only those."""
    spans = SpanLog()
    monkeypatch.setattr(net_forward, "span", spans)
    appended = []
    real_append = agg.engine.input_log_append

    def append(ins, tag, data, n_records=None):
        appended.append((tag, bytes(data), n_records))
        return real_append(ins, tag, data, n_records)

    monkeypatch.setattr(agg.engine, "input_log_append", append)

    def frames(run: str) -> list:
        # the same entries under chunk ids of the run's own: the ledger
        # would drop a second delivery, and the id is in no event
        def entries(n, pad, more=()):
            return [[1700000000 + i, {"i": i, "pad": "x" * pad}, *more]
                    for i in range(n)]

        def blob(n):
            return b"".join(packb(e) for e in entries(n, 600))

        return [
            packb(["app", entries(8, 600), {"chunk": run + "-fwd"}]),
            packb(["app", blob(8), {"size": 8, "chunk": run + "-bin"}]),
            packb(["app", gzip.compress(blob(8)),
                   {"chunk": run + "-gz", "compressed": "gzip"}]),
            packb(["app", entries(2, 4), {"chunk": run + "-small"}]),
            packb(["app", entries(8, 600, (None,)), {"chunk": run + "-odd"}]),
        ]

    kinds = ["-fwd", "-bin", "-gz", "-small", "-odd"]
    edge = agg.connect()
    edge.send(b"".join(frames("cut")))
    assert edge.acks(5) == ["cut" + k for k in kinds]
    served = 4 if HAVE_CUT else 0
    assert agg.srv.n_cut == agg.srv.health_block()["cut"] == served
    assert spans.count("forward.cut") == served
    assert spans.count("forward.reencode") == 5
    text = agg.engine.metrics.to_prometheus()
    assert "fluentbit_forward_cut_chunks_total" in text
    if HAVE_CUT:
        assert f'fluentbit_forward_cut_chunks_total{{instance="' \
               f'{agg.srv.instance.display_name}"}} 4' in text
    with_cut, appended[:] = list(appended), []
    no_codec()
    edge.send(b"".join(frames("obj")))
    assert edge.acks(5) == ["obj" + k for k in kinds]
    assert agg.srv.n_cut == spans.count("forward.cut") == served
    assert spans.count("forward.reencode") == 10
    assert appended == with_cut and len(with_cut) == 5
    assert [n for _t, _d, n in with_cut] == [8, 8, 8, 2, 8]
    agg.ctx.flush_now()
    wait_for(lambda: len(agg.records()) == 2 * 34)
    assert agg.records()[:34] == agg.records()[34:]
    edge.close()


def case_torn_frame_absorbs_nothing_on_either_path(agg, no_codec, **_):
    """``forward.partial_write``: the frame stops mid-entry and the link
    dies. Nothing of it reaches the engine, with the cut or without;
    the next connection's whole frame does."""
    for run, whole in (("cut", frame), ("packed", packed_frame),
                       ("objects", frame)):
        if run == "objects":
            no_codec()
        data = whole(f"torn-{run}")
        for stop in (1, 9, len(data) // 2, len(data) - 1):
            edge = agg.connect()
            edge.send(data[:stop])
            time.sleep(0.05)
            edge.close()
        edge = agg.connect()
        edge.send(whole(f"whole-{run}"))
        assert edge.acks(1) == [f"whole-{run}"]
        edge.close()
    assert agg.srv.n_absorbed == agg.gate.entered == 3
    assert agg.srv.n_cut == (2 if HAVE_CUT else 0)
    assert {r["chunk"] for r in agg.records()} <= {
        "whole-cut", "whole-packed", "whole-objects"}
    assert sorted(agg.srv._ledger.snapshot()) == [
        "whole-cut", "whole-objects", "whole-packed"]


def case_cut_and_handed_back_frames_keep_their_order(agg, **_):
    """Frames the C cut serves between frames it hands back, on one
    connection: absorbed and acked in the order sent."""
    agg.gate.pause_s = 0.005
    edge = agg.connect()
    ids = [f"k{i:02d}" for i in range(16)]
    makers = (frame, odd_frame, packed_frame, odd_frame,
              lambda c: packed_frame(c, compress=True))
    edge.send(b"".join(makers[i % 5](c) for i, c in enumerate(ids)))
    assert edge.acks(len(ids)) == ids
    assert agg.srv.n_absorbed == agg.gate.entered == len(ids)
    assert agg.srv.n_cut == (10 if HAVE_CUT else 0)
    agg.ctx.flush_now()
    wait_for(lambda: len(agg.records()) == 8 * len(ids))
    assert [r["chunk"] for r in agg.records() if r["i"] == 0] == ids
    edge.close()


# ------------------------------------- the staged cases: launch beside commit


def staged(case):
    case.staged = True
    return case


def lane_is_clean(agg, launches: int) -> None:
    """Every flight that was begun was finished, on the device."""
    st = agg.lane()
    assert st["launches"] == st["ok"] == launches, st
    for key in ("failures", "timeouts", "fallback_segments", "abandoned",
                "short_circuits"):
        assert st[key] == 0, (key, st)


@staged
def case_second_of_two_frames_is_prelaunched_a_lone_one_is_not(
        agg, monkeypatch, **_):
    spans = SpanLog()
    monkeypatch.setattr(net_forward, "span", spans)
    edge = agg.connect()
    edge.send(frame("lone"))
    assert edge.acks(1) == ["lone"]
    assert agg.srv.n_prelaunched == 0 and agg.lane()["begun_in_flight"] == 0
    assert spans.count("forward.prelaunch") == 0
    agg.gate.open.clear()
    edge.send(frame("a") + frame("b"))
    wait_for(lambda: agg.srv.n_prelaunched == 1)
    # b's launch is begun, on the instance's second thread, while a has
    # not had its own; nothing of b is committed
    assert agg.grep_entered == 2 and agg.gate.entered == 1
    assert agg.lane()["launches"] == 2 and agg.srv.n_absorbed == 1
    assert [t for n, t in spans.names if n == "forward.prelaunch"][0] \
        .startswith("flb-fw-pre-")
    assert agg.srv._ledger.snapshot() == {"lone": 1}
    agg.gate.open.set()
    assert edge.acks(2) == ["a", "b"]
    # the span, the counter, the health block and the lane agree
    assert spans.count("forward.prelaunch") == 1
    assert agg.srv.health_block()["prelaunched"] == 1
    assert agg.lane()["begun_in_flight"] == 1
    name = agg.srv.instance.display_name
    assert f'fluentbit_forward_prelaunched_chunks_total{{instance="' \
           f'{name}"}} 1' in agg.engine.metrics.to_prometheus()
    # b's absorb found its launch begun: three launches for three frames
    lane_is_clean(agg, 3)
    assert agg.engine.filters[0].plugin.raw_timings["device_records"] == 24
    edge.close()


@staged
def case_prelaunch_leaves_bytes_order_and_acks_as_they_were(
        agg, monkeypatch, **_):
    """The same frames with the begin half and with it taken away: the
    bytes handed on, their order and the acks' order do not say which."""
    agg.gate.pause_s = 0.005

    def run(name: str) -> None:
        ids = [f"{name}{i:02d}" for i in range(12)]
        edge = agg.connect()
        # the records say nothing of the run: `chunk` is the frame's
        # place (every fourth frame is dropped whole), the option's id
        # is the run's own
        edge.send(b"".join(packb(["app", [
            [1700000000 + k, {"chunk": ("drop" if i % 4 == 3 else "")
                              + f"{i:02d}", "i": k, "pad": "x" * 600}]
            for k in range(8)], {"chunk": c}]) for i, c in enumerate(ids)))
        assert edge.acks(len(ids)) == ids
        edge.close()
        agg.ctx.flush_now()

    run("a")
    wait_for(lambda: len(agg.records()) == 8 * 9)
    with_half, agg.got[:] = [d for _t, d in agg.got], []
    begun, launches = agg.srv.n_prelaunched, agg.lane()["launches"]
    assert begun >= 6 and launches == 12
    monkeypatch.delattr(GrepFilter, "begin_batch")
    run("b")
    wait_for(lambda: len(agg.records()) == 8 * 9)
    assert agg.srv.n_prelaunched == begun
    assert b"".join(d for _t, d in agg.got) == b"".join(with_half)
    assert [r["chunk"] for r in agg.records() if r["i"] == 0] == [
        f"{i:02d}" for i in range(12) if i % 4 != 3]
    lane_is_clean(agg, 24)


@staged
def case_no_ack_before_the_ledger_with_a_launch_begun_ahead(
        agg, monkeypatch, **_):
    at_ack = []
    real = net_forward.packb

    def packb_seeing(obj, *a, **kw):
        if isinstance(obj, dict) and "ack" in obj:
            at_ack.append((obj["ack"], agg.srv.n_absorbed,
                           agg.srv._ledger.snapshot().get(obj["ack"])))
        return real(obj, *a, **kw)

    monkeypatch.setattr(net_forward, "packb", packb_seeing)
    agg.gate.open.clear()
    edge = agg.connect()
    edge.send(frame("a") + frame("b"))
    wait_for(lambda: agg.srv.n_prelaunched == 1)
    # b's launch runs: nothing on the wire, nothing counted, nothing in
    # the ledger, nothing handed on
    assert edge.acks(1, timeout=0.3) == []
    assert agg.srv.n_absorbed == 0 and agg.gate.entered == 0
    assert agg.srv._ledger.snapshot() == {}
    agg.gate.open.set()
    assert edge.acks(2) == ["a", "b"]
    assert at_ack == [("a", 1, 1), ("b", 2, 1)]
    lane_is_clean(agg, 2)
    edge.close()


@staged
def case_duplicate_of_a_chunk_in_flight_has_its_launch_dropped(agg, **_):
    """The same chunk id on a second connection, behind another frame
    there, while its first delivery is held: its launch is begun ahead,
    the worker then finds the first delivery's record — absorbed once,
    and the launch nobody used is finished."""
    agg.gate.open.clear()
    first, second = agg.connect(), agg.connect()
    first.send(frame("dup"))
    wait_for(lambda: agg.grep_entered == 1)
    second.send(frame("x") + frame("dup"))
    wait_for(lambda: agg.srv.n_prelaunched == 1)
    agg.gate.open.set()
    assert first.acks(1) == ["dup"] and second.acks(2) == ["x", "dup"]
    assert agg.gate.entered == 2 and agg.srv.n_absorbed == 2
    assert agg.srv._ledger.snapshot() == {"dup": 1, "x": 1}
    assert agg.srv._ledger.dedup_hits == 1
    lane_is_clean(agg, 3)  # dup, x, and dup's unused one
    wait_for(lambda: agg.srv._begun == set())
    first.close()
    second.close()


@staged
def case_deferred_frame_keeps_the_launch_begun_for_it(agg, **_):
    """DEFER, then the quota allows: the retry takes the handle along,
    the frame is absorbed once, from the launch begun ahead."""
    t = agg.engine.qos.tenant("slow", rate=1.0, overflow="defer")
    assert t.bucket.try_take(100_000)
    agg.gate.open.clear()
    edge = agg.connect()
    edge.send(frame("a") + frame("b", tenant="slow"))
    wait_for(lambda: agg.srv.n_prelaunched == 1)
    agg.gate.open.set()
    assert edge.acks(1) == ["a"]
    wait_for(lambda: agg.srv.n_deferred_acks == 1)
    time.sleep(0.1)  # a retry or two, each deferred again
    assert agg.lane()["launches"] == 2 and agg.lane()["ok"] == 1
    t.bucket.capacity = t.bucket.tokens = 1e9
    assert edge.acks(1) == ["b"]
    assert agg.srv.n_absorbed == 2 and agg.srv.n_withheld_acks == 0
    assert agg.srv._ledger.snapshot() == {"a": 1, "b": 1}
    lane_is_clean(agg, 2)  # b was not staged a second time
    edge.close()


case_deferred_frame_keeps_the_launch_begun_for_it.props = {
    "defer_ack_window": "15"}


@staged
def case_shed_frame_has_its_launch_dropped(agg, **_):
    t = agg.engine.qos.tenant("loud", rate=1.0, overflow="shed")
    assert t.bucket.try_take(100_000)
    agg.gate.open.clear()
    edge = agg.connect()
    edge.send(frame("a") + frame("b", tenant="loud"))
    wait_for(lambda: agg.srv.n_prelaunched == 1)
    agg.gate.open.set()
    assert edge.acks(2) == ["a", "b"]  # shed by policy: acked
    assert agg.srv.n_shed_remote == 1 and agg.srv.n_absorbed == 1
    assert agg.gate.entered == 1
    lane_is_clean(agg, 2)
    wait_for(lambda: agg.srv._begun == set())
    edge.close()


@staged
def case_withheld_frame_has_its_launch_dropped(agg, **_):
    """The defer window runs out on a frame whose launch was begun
    ahead: no ack, not absorbed, the launch finished."""
    t = agg.engine.qos.tenant("slow", rate=1.0, overflow="defer")
    assert t.bucket.try_take(100_000)
    agg.gate.open.clear()
    edge = agg.connect()
    edge.send(frame("a") + frame("b", tenant="slow"))
    wait_for(lambda: agg.srv.n_prelaunched == 1)
    agg.gate.open.set()
    assert edge.acks(2, timeout=1.5) == ["a"]
    assert agg.srv.n_withheld_acks == 1 and agg.srv.n_absorbed == 1
    wait_for(lambda: agg.srv._begun == set())
    lane_is_clean(agg, 2)
    edge.close()


@staged
def case_engine_stop_with_a_launch_begun_ahead(agg, **_):
    agg.gate.open.clear()
    edge = agg.connect()
    edge.send(frame("a") + frame("b"))
    wait_for(lambda: agg.srv.n_prelaunched == 1)
    stopper = threading.Thread(target=agg.ctx.stop)
    agg.stopped = True
    stopper.start()
    time.sleep(0.3)
    assert stopper.is_alive()  # the stop waits for the worker
    agg.gate.open.set()
    stopper.join(WAIT_S)
    assert not stopper.is_alive()
    # a was handed over: absorbed, flushed, acked. b was not: no ack,
    # not absorbed — and its launch is not left open
    assert agg.workers() == []
    assert [r["chunk"] for r in agg.records() if r["i"] == 0] == ["a"]
    assert edge.acks(2, timeout=1.0) == ["a"]
    assert agg.srv.n_absorbed == 1 and agg.srv._begun == set()
    lane_is_clean(agg, 2)
    edge.close()


@staged
def case_filter_reloaded_between_begin_and_absorb(agg, **_):
    """b's launch is begun under the old rules, the filter is swapped
    before b's turn: the handle is discarded, b's verdict is the new
    rules', and the old launch is finished all the same."""
    go = threading.Event()
    real = agg.engine.input_log_append

    def held(*a, **kw):  # a waits outside the engine: a reload can land
        assert go.wait(WAIT_S), "never let go"
        return real(*a, **kw)

    agg.engine.input_log_append = held
    try:
        edge = agg.connect()
        edge.send(frame("a") + frame("b") + frame("c"))
        wait_for(lambda: agg.srv.n_prelaunched == 1)
        old = agg.engine.filters[0].plugin
        txn = agg.engine.reload_txn()
        txn.replace_filter("grep.0", match="*", tpu_batch_records="1",
                           exclude="chunk ^b", regex="chunk .")
        txn.commit()
        assert agg.engine.filters[0].plugin is not old
        go.set()
        assert edge.acks(3) == ["a", "b", "c"]
    finally:
        del agg.engine.input_log_append
    agg.ctx.flush_now()
    wait_for(lambda: len(agg.records()) == 16)
    # b — begun under rules that keep it — is dropped by the new ones
    assert [r["chunk"] for r in agg.records() if r["i"] == 0] == ["a", "c"]
    assert agg.srv.n_absorbed == 3
    assert old.raw_timings["device_records"] == 0
    new = agg.engine.filters[0].plugin.raw_timings
    assert new["device_records"] == new["records"] == 24
    # a, b begun ahead under the old rules and dropped, b again, c
    lane_is_clean(agg, 4)
    edge.close()


@staged
def case_faults_on_a_launch_begun_ahead_fall_back_to_the_host(agg, **_):
    """``device.dispatch`` and ``device.launch_hang`` on flights that
    were begun ahead of their frames' turn: each resolves to the host
    twin at the frame's own finish, and the bytes are the sound run's."""
    lane = fault.lane("grep")
    edge = agg.connect()
    edge.send(frame("warm"))  # compiled before a deadline is short
    assert edge.acks(1) == ["warm"]
    try:
        for n, (site, spec, counted) in enumerate((
                ("device.dispatch", "1*off->1*return(injected)",
                 "failures"),
                ("device.launch_hang", "1*off->1*hang(3000)", "timeouts"))):
            lane.deadline = 0.5
            agg.gate.open.clear()
            failpoints.enable(site, spec)  # a's launch passes, b's not
            edge.send(frame(f"a{n}") + frame(f"drop{n}") + frame(f"c{n}"))
            wait_for(lambda: agg.srv.n_prelaunched >= 2 * n + 1)
            agg.gate.open.set()
            assert edge.acks(3) == [f"a{n}", f"drop{n}", f"c{n}"]
            failpoints.reset()
            st = lane.stats()
            assert st[counted] == 1 and st["fallback_segments"] == n + 1
    finally:
        failpoints.reset()
    agg.ctx.flush_now()
    wait_for(lambda: len(agg.records()) == 8 * 5)
    assert [r["chunk"] for r in agg.records() if r["i"] == 0] == [
        "warm", "a0", "c0", "a1", "c1"]
    st = lane.stats()
    assert st["launches"] == 7
    assert st["ok"] + st["failures"] + st["timeouts"] == 7
    wait_for(lambda: agg.srv._begun == set())
    edge.close()


CASES = [v for k, v in sorted(globals().items()) if k.startswith("case_")]


@pytest.mark.parametrize("case", CASES,
                         ids=[c.__name__[5:] for c in CASES])
def test_forward_pipeline(case, tmp_path, monkeypatch, caplog, no_codec):
    props = {"defer_ack_window": "0.4", **getattr(case, "props", {})}
    agg = Aggregator(tmp_path, staged=getattr(case, "staged", False),
                     monkeypatch=monkeypatch, **props)
    try:
        case(agg=agg, monkeypatch=monkeypatch, caplog=caplog,
             no_codec=no_codec)
    finally:
        agg.stop()
    assert agg.workers() == []


def test_engine_start_shortens_the_gil_switch_interval():
    """At CPython's 5 ms the absorb worker, back from a launch, waits
    that long for the GIL while the loop decodes: the two stages then
    gain nothing (PERF.md section 6, PR 30)."""
    from fluentbit_tpu.core.engine import GIL_SWITCH_INTERVAL_S

    def started_and_stopped():
        ctx = flb.create(flush="50ms", grace="1")
        ctx.input("forward", listen="127.0.0.1", port="0")
        ctx.output("null", match="*")
        ctx.start()
        try:
            return sys.getswitchinterval()
        finally:
            ctx.stop()

    saved = sys.getswitchinterval()
    try:
        sys.setswitchinterval(0.005)
        assert started_and_stopped() == GIL_SWITCH_INTERVAL_S == 0.001
        # left in place at stop: another engine may be running
        assert sys.getswitchinterval() == GIL_SWITCH_INTERVAL_S
        sys.setswitchinterval(0.0001)  # a shorter one is the embedder's
        assert started_and_stopped() == pytest.approx(0.0001)
    finally:
        sys.setswitchinterval(saved)
