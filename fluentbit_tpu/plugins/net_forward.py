"""Fluentd Forward protocol — in_forward server + out_forward client.

Reference: plugins/in_forward (Fluentd protocol server, fw_prot.c) and
plugins/out_forward (forward.c: msgpack over TCP, modes Message /
Forward / PackedForward, ack via the ``chunk`` option, shared-key
HELO/PING/PONG handshake :259-340). The protocol is the reference's
"cluster fabric" (SURVEY §5): agent→aggregator fan-in/out over DCN.

Wire formats accepted by the server:
- Message:        ``[tag, time, record, option?]``
- Forward:        ``[tag, [[time, record], ...], option?]``
- PackedForward:  ``[tag, bin(msgpack stream of [time, record]), option?]``
  (CompressedPackedForward when option.compressed == "gzip")
When ``option.chunk`` is present the server replies ``{"ack": chunk}``
(at-least-once). The client sends PackedForward, optionally gzip'd,
with ``require_ack_response`` waiting for the matching ack.

fbtpu-relay hardening (FAULTS.md "fbtpu-relay") on top of the base
protocol:

- **Effectively-once absorption.** The client's chunk-id is a CONTENT
  digest (core/relay.stable_chunk_id) — stable across reconnect
  resends, backoff interleavings, and post-crash storage replays of
  the same chunk. The server keeps a durable
  :class:`~..core.relay.DedupLedger`: a redelivered id inside the
  retry window is acked WITHOUT re-absorbing, so the aggregator's flux
  sketches (duplicate-sensitive counts/sums) see every edge chunk at
  most once. The ledger record is persisted BEFORE the ack leaves —
  the lost-ack window (``forward.ack_drop``) can only ever produce a
  dedup hit, never a double-absorb. The one deliberate trade: two
  legitimately byte-identical (tag, entries) chunks inside the TTL
  dedup to one absorb — with real per-record timestamps in the stream
  that requires a digest collision in practice, and it is the price of
  ids that survive an edge crash (a random id would not).

- **Wire QoS stamps.** The client copies the flushed chunk's
  tenant/priority (core/plugin.FLUSH_CHUNK) into the option map; the
  server restores them onto the aggregator-side chunk (ChunkPool.stamp)
  and meters the REMOTE tenant's token bucket (qos.admit_stamped), so
  per-tenant quotas and QoS classes hold fleet-wide across the hop.

- **Backpressure instead of blind acks.** A DEFER verdict (tenant
  over quota, or local buffer pressure) delays the ack up to
  ``defer_ack_window``; exhausted, the ack is WITHHELD — the peer's
  ack timeout turns into RETRY + backoff, pausing the stream without
  losing a byte (resends dedup at the ledger).

- **Decode beside absorb.** The server is a two-stage pipeline (the
  reference's threaded-input shape, flb_input_thread.c): a connection
  reads and decodes on the engine's event loop, and every absorb —
  dedup check, tenant metering, ``engine.input_log_append`` and all
  beneath it, ledger record — runs on ONE worker thread per instance,
  first come first served. The loop's decode of a chunk (Forward or
  PackedForward) is one C call, ``fbtpu_codec.forward_cut``: it finds
  the message's end, proves that every entry is a ``[time, map]`` whose
  wire bytes are what the codec itself would pack, and writes the V2
  events from those bytes with the GIL released — no Python object per
  entry, nothing packed again (:class:`Unpacker`). A message it cannot
  prove that of (Message mode, the handshake, non-canonical msgpack,
  an odd entry) and a process without the extension take the object
  path, whole: ``unpack_from`` → :meth:`ForwardInput._decode` →
  :func:`_entries_to_events`, the reference the cut is held to byte
  for byte (``tests/test_forward_cut.py``). While the worker is on a
  frame the loop decodes the connection's next ones; it holds
  ``1 + _DECODE_AHEAD`` decoded frames behind the one with the worker,
  reading nothing more, until the oldest is acked (TCP flow control
  holds the peer beyond that). Acks are written on the loop, in
  arrival order, after the absorb returned and the ledger holds the
  chunk. A chunk too small to be worth the hand-over
  (``_INLINE_BYTES``: Message mode) is absorbed by the loop itself
  while the worker is idle — still one absorb at a time, since only
  the loop hands the worker its work.

- **Launch beside commit.** A frame decoded while the frame before it
  is still with the worker has its device launch begun at once
  (``engine.input_log_prelaunch``: the first matching filter stages
  the frame's bytes and begins its guarded launch, on the instance's
  second thread) — copy-in, the jitted calls and the kernel then run
  while the worker collects, compacts, appends, records and acks the
  frame before. The frame's own absorb, when its turn comes on the one
  ordered worker, is all it ever was, except that the filter finds its
  launch begun and goes straight to the wait. At most two launches a
  connection are open: the frame with the worker and the one behind
  it. Nothing is committed ahead of a frame's turn: the dedup check,
  the tenant's metering, backpressure, the append, the ledger's record
  and the ack stay on the worker, one try at a time, in arrival order.
  A launch begun for a frame that is then not absorbed (a duplicate, a
  shed, a stop, a filter reloaded in between) is finished and dropped,
  never left open; a deferred frame keeps it for its retry. A frame
  that finds the worker idle is handed over as ever: a lone frame
  waits for no partner.

- **Armored client.** Per-upstream circuit breakers (core/guard.py,
  visible in /api/v1/health), UpstreamHA failover mid-stream, full-
  jitter backoff between attempts; when EVERY upstream refuses (a
  partition), the already-packed entry stream degrades to an fstore
  spool under the tenant's storage quota and replays via the mmap +
  offset-sidecar path on heal, carrying the SAME chunk-id.
"""

from __future__ import annotations

import asyncio
import collections
import concurrent.futures
import gzip
import hashlib
import logging
import os
import socket
import time
from types import SimpleNamespace
from typing import NamedTuple, Optional

from ..codec import _native_codec
from ..codec import msgpack as _msgpack
from ..codec.events import encode_event
from ..codec.msgpack import EventTime, OutOfData, packb
from ..core.config import ConfigMapEntry
from ..core.guard import io_deadline
from ..core.plugin import FLUSH_CHUNK, FlushResult, InputPlugin, \
    OutputPlugin, registry
from ..core.relay import DedupLedger, ForwardSpool, stable_chunk_id
from ..core.scheduler import backoff_full_jitter
from ..core.spans import bind, span
from ..core.upstream import close_quietly
from .. import failpoints as _fp

log = logging.getLogger("flb.forward")

#: wire-stamp hygiene: the tenant name is attacker-adjacent input
#: (any peer with the shared key can send one) — bound it before it
#: becomes a metric label / quota bucket key
_TENANT_MAX_LEN = 128
_PRIORITY_MAX = 7

_NO_MSG = object()  # the Unpacker holds no complete message

#: a chunk smaller than this (as V2 events) is absorbed on the loop when
#: the worker is idle: handing it over and back costs about 0.1 ms each
#: way, more than the decode of a next chunk that size could hide behind
#: its absorb — Message mode sends one record a message
_INLINE_BYTES = 4096

#: decoded frames a connection holds beyond the one whose launch is
#: begun behind the worker's. With 0 the decode of frame N+2 starts when
#: frame N is acked: where it takes as long as N+1's commit the worker
#: is free before N+2 is decoded, and N+2 is launched in series after
#: all (grep `.catchup`: three frames in ten, the worker idle 21 %);
#: with 1 it is decoded by then and every frame's launch is begun ahead
#: (PERF.md section 6, PR 37). A constant: nothing in reach of an
#: operator keys on it
_DECODE_AHEAD = 1

# what one try at absorbing a chunk came to (ForwardInput._attempt)
_ABSORBED, _DUPLICATE, _SHED, _DEFER = range(4)


class CutChunk(NamedTuple):
    """A chunk as ``fbtpu_codec.forward_cut`` hands it over: the
    entries are V2 events already, counted, and were never objects."""

    tag: str
    #: the V2 buffer — or, with ``n`` < 0, a PackedForward blob left
    #: uncut because its option map names a compression
    events: bytes
    n: int
    option: Optional[dict]


class Unpacker(_msgpack.Unpacker):
    """The connection's Unpacker. ``next()`` asks the C codec for the
    chunk cut first and yields a :class:`CutChunk`; what the cut hands
    back (``FallbackError``), and every message where the extension is
    not loaded, is the base class's to decode into objects. The same
    three outcomes either way: a message, ``StopIteration`` while it is
    not whole, ``native`` saying whether C served the call.

    (The benchmark and the tests time the loop's decode by subclassing
    *this* name and wrapping ``feed`` and ``__next__``: the cut lies
    beneath ``__next__`` for that reason too.)"""

    def __init__(self, buf: bytes = b"", chunks: bool = True):
        super().__init__(buf)
        # False over a PackedForward blob's entries: none is a chunk
        self._chunks = chunks

    def __next__(self):
        mod = _native_codec.load() if self._chunks else None
        if mod is not None:
            try:
                got = mod.forward_cut(self._buf, self._pos)
            except mod.FallbackError:
                pass  # not a chunk of canonical entries: objects decide
            else:
                self.native = True
                if got is None:
                    raise StopIteration
                tag, events, n, option, self._pos = got
                return CutChunk(tag, events, n, option)
        return super().__next__()


def _entries_to_events(entries) -> tuple:
    """Forward entries [[time, record], ...] → (encoded V2 buffer, n).
    The object path's re-encode, and the oracle of the C cut."""
    out = bytearray()
    n = 0
    for entry in entries:
        if not isinstance(entry, (list, tuple)) or len(entry) < 2:
            continue
        ts, record = entry[0], entry[1]
        if not isinstance(record, dict):
            continue
        out += encode_event(record, ts)
        n += 1
    return bytes(out), n


def _inflated(blob, option) -> bytes:
    if option and option.get("compressed") == "gzip":
        return gzip.decompress(blob)
    return bytes(blob)


def _chunk_events(msg, option) -> tuple:
    """A chunk-shaped message → ``(V2 buffer, n, cut)``; ``cut`` says
    that C wrote the events from the wire bytes and no entry was ever an
    object."""
    if isinstance(msg, CutChunk):
        if msg.n >= 0:
            return msg.events, msg.n, True
        # a compressed blob comes uncut: inflate, then the same walk
        blob = _inflated(msg.events, option)
        mod = _native_codec.load()
        try:
            return (*mod.forward_cut_entries(blob), True)
        except mod.FallbackError:
            entries = list(Unpacker(blob, chunks=False))
    elif isinstance(msg[1], (bytes, memoryview)):
        entries = list(Unpacker(_inflated(msg[1], option), chunks=False))
    elif isinstance(msg[1], (list, tuple)):
        entries = msg[1]
    else:
        entries = [[msg[1], msg[2]]]
    return (*_entries_to_events(entries), False)


def _wire_stamp(option) -> tuple:
    """(tenant, priority) from a forward option map, validated: the
    stamp crosses a trust boundary, so an oversized/typed-wrong value
    degrades to unstamped rather than poisoning quota keys."""
    if not isinstance(option, dict):
        return None, None
    tenant = option.get("tenant")
    if not isinstance(tenant, str) or not tenant \
            or len(tenant) > _TENANT_MAX_LEN:
        tenant = None
    priority = option.get("priority")
    if isinstance(priority, bool) or not isinstance(priority, int):
        priority = None
    else:
        priority = min(max(priority, 0), _PRIORITY_MAX)
    return tenant, priority


@registry.register
class ForwardInput(InputPlugin):
    name = "forward"
    description = "Fluentd Forward protocol server"
    server_task_needed = True
    config_map = [
        ConfigMapEntry("listen", "str", default="0.0.0.0"),
        ConfigMapEntry("port", "int", default=24224),
        ConfigMapEntry("shared_key", "str"),
        ConfigMapEntry("self_hostname", "str", default="fluentbit-tpu"),
        ConfigMapEntry("tag_prefix", "str"),
        ConfigMapEntry("dedup", "bool", default=True,
                       desc="effectively-once absorption: dedup "
                            "redelivered chunk ids against the durable "
                            "ledger before they reach engine/flux state"),
        ConfigMapEntry("dedup_ttl", "time", default="300",
                       desc="retry window: how long an absorbed "
                            "chunk-id stays in the dedup ledger"),
        ConfigMapEntry("defer_ack_window", "time", default="5",
                       desc="max time an ack is delayed while the "
                            "append defers (tenant quota / buffer "
                            "pressure); exhausted, the ack is withheld "
                            "and the peer's own timeout backpressures"),
    ]

    def init(self, instance, engine) -> None:
        self.bound_port: Optional[int] = None
        self._ledger: Optional[DedupLedger] = None
        if self.dedup:
            root = getattr(engine.service, "storage_path", None)
            self._ledger = DedupLedger(root, ttl=self.dedup_ttl)
        # plain ints mirror the exported counters for /api/v1/health
        # (the metrics registry has no read-back API)
        self.n_absorbed = 0
        self.n_overlapped = 0
        self.n_cut = 0
        self.n_prelaunched = 0
        self.n_deferred_acks = 0
        self.n_withheld_acks = 0
        self.n_shed_remote = 0
        # the absorb stage: one thread, first come first served (made
        # at the first frame). A connection has one try there at a time
        # and holds one decoded frame behind it (_handle_conn), so the
        # queue is never longer than the connections are many
        self._absorber = concurrent.futures.ThreadPoolExecutor(
            max_workers=1,
            thread_name_prefix=f"flb-fw-{instance.display_name}")
        # the launch stage: one more thread, which stages a frame and
        # begins its launch while the worker is on the frame before
        # (made at the first such frame); `_begun` holds the launches
        # begun and not yet finished or dropped
        self._stager = concurrent.futures.ThreadPoolExecutor(
            max_workers=1,
            thread_name_prefix=f"flb-fw-pre-{instance.display_name}")
        self._begun: set = set()
        # tries handed to the worker (counted on the loop) and tries it
        # is through with (counted on the worker): unequal while an
        # absorb is running or waiting its turn
        self._tries_handed = 0
        self._tries_done = 0
        self._stopped = False
        m = engine.metrics
        self._m_dedup = m.counter(
            "fluentbit", "forward", "dedup_hits_total",
            "Redelivered chunk ids absorbed zero times (acked from "
            "the dedup ledger)", ("instance",))
        self._m_absorbed = m.counter(
            "fluentbit", "forward", "absorbed_chunks_total",
            "Forward chunks absorbed into engine state", ("instance",))
        self._m_overlapped = m.counter(
            "fluentbit", "forward", "overlapped_chunks_total",
            "Forward chunks decoded while an earlier chunk's absorb "
            "was still running", ("instance",))
        self._m_cut = m.counter(
            "fluentbit", "forward", "cut_chunks_total",
            "Forward chunks whose V2 events were cut from the wire "
            "bytes in C (the others were decoded into objects and "
            "packed again)", ("instance",))
        self._m_prelaunched = m.counter(
            "fluentbit", "forward", "prelaunched_chunks_total",
            "Forward chunks whose device launch was begun while an "
            "earlier chunk was still being absorbed", ("instance",))
        self._m_deferred = m.counter(
            "fluentbit", "forward", "deferred_acks_total",
            "Acks delayed by quota/buffer backpressure", ("instance",))
        self._m_withheld = m.counter(
            "fluentbit", "forward", "withheld_acks_total",
            "Acks withheld after the defer window (peer retries)",
            ("instance",))

    async def start_server(self, engine) -> None:
        open_conns = set()

        async def handle(reader, writer):
            open_conns.add(writer)
            try:
                await self._handle_conn(reader, writer, engine)
            except (ConnectionError, asyncio.IncompleteReadError):
                pass
            except Exception:
                log.exception("in_forward connection failed")
            finally:
                open_conns.discard(writer)
                close_quietly(writer)

        from ..core.tls import server_context

        server = await asyncio.start_server(
            handle, self.listen, self.port,
            ssl=server_context(self.instance),
        )
        self.bound_port = server.sockets[0].getsockname()[1]
        try:
            # (not serve_forever(): cancelled, it waits for the
            # connections before anything here could close them)
            await asyncio.get_running_loop().create_future()
        finally:
            # cancelled (engine stop, hot reload): wait_closed() waits
            # for the connections too, and an edge that stays connected
            # would hold the stop up for good — close them; a handler
            # still lets the frame it handed over run to its end
            server.close()
            for writer in list(open_conns):
                close_quietly(writer)
            await server.wait_closed()

    async def _handle_conn(self, reader, writer, engine) -> None:
        nonce = b""
        if self.shared_key:
            # HELO/PING/PONG handshake (forward.c:259-340 client side)
            nonce = os.urandom(16)
            writer.write(packb(["HELO", {"nonce": nonce, "auth": b"",
                                         "keepalive": True}]))
            await writer.drain()
        u = Unpacker()
        authed = not self.shared_key
        loop = asyncio.get_running_loop()
        # the connection's frames that are decoded and not yet through,
        # oldest first: each one's task waits for the task before it,
        # then absorbs and acks its frame, so absorbs and acks keep
        # their arrival order; the handler reads nothing more while
        # 1 + _DECODE_AHEAD of them stand behind the worker's (TCP
        # flow control holds the peer back)
        chain: collections.deque = collections.deque()

        async def settle(keep: int, cid) -> None:
            """Wait until at most ``keep`` frames are not through."""
            while len(chain) > keep:
                if not chain[0].done():
                    # the loop holds a decoded frame (``cid``) and may
                    # not go on: the wait is that frame's
                    with span("forward.await", chunk=cid):
                        await asyncio.wait((chain[0],))
                chain.popleft().result()
            while chain and chain[0].done():
                chain.popleft().result()

        try:
            while True:
                with span("forward.read") as sp:
                    data = await reader.read(65536)
                    sp.set_metadata(bytes=len(data))
                if not data:
                    return
                fed = False
                while True:
                    # one span per attempt to take a message off the
                    # Unpacker. A frame comes in several reads; until
                    # it is whole an attempt (done=0) costs the C codec
                    # one walk over its spans (native=1), or the Python
                    # walk a decode thrown away where the extension is
                    # not loaded or handed the bytes back (native=0).
                    # The attempt that finds a chunk whole (done=1) is
                    # its whole decode where the C cut serves: the walk
                    # and the V2 events, GIL released
                    with span("forward.unpack") as sp:
                        if not fed:
                            u.feed(data)
                            fed = True
                        msg = next(u, _NO_MSG)
                        sp.set_metadata(done=int(msg is not _NO_MSG),
                                        native=int(u.native))
                    if msg is _NO_MSG:
                        break
                    if not isinstance(msg, (list, tuple)) or not msg:
                        continue
                    if not authed:
                        authed = self._check_ping(msg, nonce, writer)
                        if not authed:
                            return
                        await writer.drain()
                        continue
                    frame = self._decode(msg)
                    if frame is None:
                        continue
                    cid = frame[5]
                    if len(frame[1]) < _INLINE_BYTES:
                        # a few events: nothing worth decoding beside
                        # this absorb, no launch worth beginning ahead
                        # of it, and no task to carry it
                        await settle(0, cid)
                        await self._finish(frame, writer, engine)
                        continue
                    # two launches a connection at most, the worker's
                    # frame's and this one's: whatever else stands
                    # before this frame has to be through
                    await settle(1, cid)
                    pre = None
                    begin = engine.input_log_prelaunch(
                        self.instance, frame[0]) \
                        if chain and not self._stopped else None
                    if begin is not None:
                        # the worker is on the frame before: this one's
                        # staging and launch begin now, beside it
                        pre = loop.run_in_executor(
                            self._stager, self._prelaunch, begin, frame)
                    # started here, beneath no bind: the task binds the
                    # frame's chunk itself
                    chain.append(asyncio.ensure_future(self._in_turn(
                        chain[-1] if chain else None, frame, pre,
                        writer, engine)))
                    await settle(1 + _DECODE_AHEAD, cid)
                    # then two passes of the loop before the next frame
                    # is decoded. The first runs a task whose turn has
                    # come as far as the hand-over (it is woken by the
                    # same completion as this handler, after it — or
                    # the worker would sit idle through the next
                    # decode, and a read that brought several frames
                    # would decode them one absorb late); the second goes
                    # through select(), which releases the GIL: the
                    # worker starts on the frame now, not behind the C
                    # unpack call that comes next (9 % of the grep
                    # cell's lines/s, PERF.md section 6, PR 30)
                    await asyncio.sleep(0)
                    await asyncio.sleep(0)
        finally:
            if chain:
                # a frame handed over is absorbed or not, never half:
                # the peer may be gone, the absorb runs to its end (the
                # ack then fails on the closed socket, which ends the
                # connection like any lost link), and the frames behind
                # it end with it, their launches dropped
                await asyncio.wait(chain)
                for task in chain:
                    task.result()

    def _prelaunch(self, begin, frame):
        """The launch stage (its own thread): stage a decoded frame and
        begin the first filter's launch over it (``begin``, from
        ``engine.input_log_prelaunch``) → the handle its absorb takes
        along, or None where the filter declined."""
        _tag, buf, n, _option, _ack_ref, cid = frame
        with bind(chunk=cid):
            begun = begin(buf, n)
            if begun is not None:
                self._begun.add(begun)
                self.n_prelaunched += 1
                self._m_prelaunched.inc(1, (self.instance.display_name,))
                # written empty, as forward.cut and forward.overlap
                # are, for exactly the frames whose launch was begun
                # ahead: grep.stage and lane.begin beneath this bind
                # carry the time
                with span("forward.prelaunch"):
                    pass
        return begun

    async def _in_turn(self, prev, frame, pre, writer, engine) -> None:
        """A decoded frame's task: after the frame before it is
        through, its absorb and its ack (:meth:`_finish`); whatever
        ends the frame, the launch begun for it is not left open."""
        begun = None
        try:
            try:
                if prev is not None:
                    # (not `await prev`: a cancel of this task would
                    # cancel the frame before it with it)
                    await asyncio.wait((prev,))
                    prev.result()
            finally:
                # (begun beside `prev`: long done, as a rule)
                if pre is not None:
                    begun = await pre
            await self._finish(frame, writer, engine, begun)
        finally:
            if begun is not None:
                # a no-op once a filter has finished it
                begun.drop()
                self._begun.discard(begun)

    def _check_ping(self, msg, nonce: bytes, writer) -> bool:
        if msg[0] != "PING" or len(msg) < 6:
            return False
        _, hostname, salt, digest = msg[0], msg[1], msg[2], msg[3]
        salt = salt if isinstance(salt, bytes) else str(salt).encode()
        want = hashlib.sha512(
            salt + str(hostname).encode() + nonce + self.shared_key.encode()
        ).hexdigest()
        ok = digest == want
        shared_key_digest = hashlib.sha512(
            salt + self.self_hostname.encode() + nonce
            + self.shared_key.encode()
        ).hexdigest()
        writer.write(packb(["PONG", ok, "" if ok else "shared_key mismatch",
                            self.self_hostname, shared_key_digest]))
        return ok

    def _decode(self, msg) -> Optional[tuple]:
        """The loop's stage: one wire message → ``(tag, buf, n, option,
        ack_ref, cid)`` with the entries as V2 events, or None for a
        message that is no chunk."""
        if isinstance(msg, CutChunk):
            tag, option = msg.tag, msg.option
        else:
            tag = msg[0]
            if not isinstance(tag, str):
                return None
            body = msg[1]
            if isinstance(body, (bytes, memoryview, list, tuple)):
                # PackedForward / CompressedPackedForward, or Forward mode
                opt_at = 2
            else:
                # Message mode [tag, time, record, option?]
                if len(msg) < 3 or not isinstance(msg[2], dict):
                    return None
                opt_at = 3
            option = msg[opt_at] if len(msg) > opt_at \
                and isinstance(msg[opt_at], dict) else None
        if self.tag_prefix:
            tag = f"{self.tag_prefix}.{tag}"
        ack_ref = option.get("chunk") if option else None
        cid = self._chunk_key(ack_ref)
        # the chunk id is taken before the events are made so that every
        # span of this frame from here to the ack carries it
        with bind(chunk=cid):
            # one span a frame around whatever produces the V2 buffer:
            # next to nothing where the Unpacker's cut already has
            # (cut=1), the object path's re-encode where it has not
            with span("forward.reencode") as sp:
                buf, n, cut = _chunk_events(msg, option)
                sp.set_metadata(cut=int(cut))
            if cut:
                self.n_cut += 1
                self._m_cut.inc(1, (self.instance.display_name,))
                with span("forward.cut"):
                    pass
            if self._tries_handed != self._tries_done:
                # decoded while an earlier frame's absorb was running:
                # the overlap the two stages exist for
                self.n_overlapped += 1
                self._m_overlapped.inc(1, (self.instance.display_name,))
                with span("forward.overlap"):
                    pass
        return tag, buf, n, option, ack_ref, cid

    async def _finish(self, frame, writer, engine, begun=None) -> None:
        """A decoded frame from its absorb to its ack; ``begun``: its
        launch, where one was begun ahead (the caller's to drop)."""
        tag, buf, n, option, ack_ref, cid = frame
        with bind(chunk=cid):
            if n:
                tenant, priority = _wire_stamp(option)
                absorbed = await self._absorb(
                    engine, tag, buf, n, tenant, priority, cid, begun)
                if not absorbed:
                    # backpressure: NO ack — the peer's ack timeout
                    # turns into RETRY+backoff, pausing the stream;
                    # the resend dedups if a later pass absorbed it
                    self.n_withheld_acks += 1
                    self._m_withheld.inc(
                        1, (self.instance.display_name,))
                    return
            if ack_ref is not None:
                if _fp.ACTIVE:
                    try:
                        # absorb recorded, ack not yet written: the
                        # classic lost-ack window — the edge resends,
                        # the ledger dedups (connection stays up: a
                        # dropped ack is not a dropped link)
                        _fp.fire("forward.ack_drop")
                    except _fp.FailpointError:
                        return
                with span("forward.ack"):
                    writer.write(packb({"ack": ack_ref}))
                    await writer.drain()

    @staticmethod
    def _chunk_key(ack_ref) -> Optional[str]:
        """Ledger key for a wire ``chunk`` option (str or bytes)."""
        if ack_ref is None:
            return None
        if isinstance(ack_ref, (bytes, memoryview)):
            return bytes(ack_ref).decode("latin-1")
        return str(ack_ref)

    async def _absorb(self, engine, tag: str, buf: bytes, n: int,
                      tenant, priority, cid: Optional[str],
                      begun=None) -> bool:
        """Absorb one decoded chunk into engine state effectively once.

        Each try (:meth:`_attempt`) runs on the worker, a small chunk's
        here when the worker is idle; DEFER verdicts become delayed
        acks bounded by ``defer_ack_window``, slept out here on the
        loop so that other connections' chunks go on being absorbed
        meanwhile. Returns False when the window exhausts —
        the caller withholds the ack entirely.
        """
        ins = self.instance
        loop = asyncio.get_running_loop()
        deadline = loop.time() + self.defer_ack_window
        deferred = False
        while True:
            if self._stopped:
                # after drain()/exit() nothing is absorbed: what came
                # in now would miss the last flush — no ack, the peer
                # resends it
                raise ConnectionError("in_forward has stopped")
            args = (engine, tag, buf, n, tenant, priority, cid, begun)
            idle = self._tries_handed == self._tries_done
            self._tries_handed += 1
            if idle and len(buf) < _INLINE_BYTES:
                # the worker is idle and only this loop hands it work,
                # so a try made here is still the one absorb running
                got = self._attempt(*args)
            else:
                # forward.absorb (the worker's thread) lies inside it
                # in time: what is left over is the two thread hops
                with span("forward.handover"):
                    got = await loop.run_in_executor(
                        self._absorber, self._attempt, *args)
            if got == _ABSORBED:
                self.n_absorbed += 1
                self._m_absorbed.inc(1, (ins.display_name,))
                return True
            if got == _DUPLICATE:
                # redelivery inside the retry window: lost ack,
                # ambiguous-ack resend, post-crash replay, or a
                # concurrent delivery that was absorbed while this one
                # slept in the defer loop — acked, absorbed zero times
                self._m_dedup.inc(1, (ins.display_name,))
                return True
            if got == _SHED:
                self.n_shed_remote += 1
                return True
            # backpressure (remote-tenant DEFER or local buffer/quota
            # pause) — delay the ack and retry
            if not deferred:
                deferred = True
                self.n_deferred_acks += 1
                self._m_deferred.inc(1, (ins.display_name,))
            remaining = deadline - loop.time()
            if remaining <= 0:
                return False
            if tenant is not None:
                hint = engine.qos.stamped_defer_hint(tenant, len(buf))
            else:
                hint = 0.05
            await asyncio.sleep(min(max(hint, 0.02), 0.25, remaining))

    def _attempt(self, engine, tag: str, buf: bytes, n: int,
                 tenant, priority, cid: Optional[str],
                 begun=None) -> int:
        """One try at absorbing a chunk (:meth:`_try`), on the thread
        that runs it: the frame's ``chunk`` is bound anew for its
        spans, and a launch begun ahead that the try did not use is
        dropped here, where waiting for the device holds nobody else up
        — unless the try was deferred: the retry takes it along."""
        try:
            with bind(chunk=cid), span("forward.absorb"):
                got = self._try(engine, tag, buf, n, tenant, priority,
                                cid, begun)
                if begun is not None and got != _DEFER:
                    begun.drop()
                return got
        finally:
            self._tries_done += 1

    def _try(self, engine, tag: str, buf: bytes, n: int,
             tenant, priority, cid: Optional[str], begun) -> int:
        """The dedup check, the wire-stamped tenant's metering
        (fleet-wide quota), the append with the stamp on the
        aggregator-side chunk, and the ledger record. Tries run one at
        a time — on the worker, or on the loop while the worker is idle
        (:meth:`_absorb`) — so nothing comes between the check and the
        record, nor between setting the stamp and clearing it."""
        ins = self.instance
        led = self._ledger if cid is not None else None
        if led is not None and led.seen(cid):
            return _DUPLICATE
        stamped = tenant is not None
        if stamped:
            verdict = engine.qos.admit_stamped(tenant, len(buf))
            if verdict == 2:  # SHED: consumed by the tenant's
                # declared overflow policy — acked, not absorbed
                # (the edge must not resend policy-shed bytes)
                return _SHED
            if verdict == 1:
                return _DEFER
            # the stamp joins the pool key and lands on the
            # chunk; qos_exempt skips the LOCAL tenant's bucket
            # (the remote tenant was already metered above) —
            # the instance's tries run one at a time, so no
            # other dispatch interleaves while these are set
            ins.pool.stamp = (tenant, priority)
            ins.qos_exempt = True
        try:
            # looked up on the engine at every call: whoever
            # wraps the method after init() is still called
            # (the launch rides along only where there is one:
            # a wrapper of the four-argument call still fits)
            rc = engine.input_log_append(ins, tag, buf, n) \
                if begun is None else engine.input_log_append(
                    ins, tag, buf, n, begun=begun)
        finally:
            if stamped:
                ins.pool.stamp = None
                ins.qos_exempt = False
        if rc < 0:
            return _DEFER
        if led is not None:
            # durable BEFORE the ack leaves: an ack whose
            # absorb-record died with the process would turn
            # the peer's next resend into a double-absorb
            led.record(cid)
        return _ABSORBED

    def drain(self, engine) -> None:
        """Engine stop, before the last flush: the tries already handed
        to the worker run to their end and the thread is joined, so
        what they appended is flushed; later frames are refused."""
        self._stopped = True
        self._absorber.shutdown(wait=True)
        self._stager.shutdown(wait=True)
        # no frame is absorbed from here on: the launches begun for
        # frames that were still waiting their turn are finished now,
        # not whenever their tasks come to see the stop
        for begun in list(self._begun):
            begun.drop()
        self._begun.clear()

    def exit(self) -> None:
        self._stopped = True
        self._absorber.shutdown(wait=False)
        self._stager.shutdown(wait=False)

    def health_block(self) -> dict:
        out = {
            "role": "server",
            "absorbed": self.n_absorbed,
            "overlapped": self.n_overlapped,
            "cut": self.n_cut,
            "prelaunched": self.n_prelaunched,
            "deferred_acks": self.n_deferred_acks,
            "withheld_acks": self.n_withheld_acks,
            "shed_remote": self.n_shed_remote,
        }
        if self._ledger is not None:
            out["dedup_hits"] = self._ledger.dedup_hits
            out["dedup_entries"] = self._ledger.size()
        return out


@registry.register
class ForwardOutput(OutputPlugin):
    name = "forward"
    description = "Fluentd Forward protocol client"
    config_map = [
        ConfigMapEntry("host", "str", default="127.0.0.1"),
        ConfigMapEntry("port", "int", default=24224),
        ConfigMapEntry("shared_key", "str"),
        ConfigMapEntry("self_hostname", "str"),
        ConfigMapEntry("require_ack_response", "bool", default=False),
        ConfigMapEntry("compress", "str"),
        ConfigMapEntry("time_as_integer", "bool", default=False),
        ConfigMapEntry("ack_timeout", "time", default="10"),
        ConfigMapEntry("upstream", "str",
                       desc="upstream HA definition file: weighted "
                            "[NODE] sections with failover"),
        ConfigMapEntry("storage_spool", "str",
                       desc="partition-degrade spool directory: when "
                            "every upstream refuses, packed chunks "
                            "buffer here (under the tenant storage "
                            "quota) and replay on heal via the mmap + "
                            "offset-sidecar path"),
    ]

    def init(self, instance, engine) -> None:
        self._engine = engine
        self._reader = None
        self._writer = None
        # one connection per output instance: concurrent flush coroutines
        # must not interleave writes or steal each other's acks
        self._lock = asyncio.Lock()
        # upstream HA (flb_upstream_ha.c): weighted nodes + failover
        self._ha = None
        self._node = None
        self._cur_breaker = None
        self._cur_target = None
        if self.upstream:
            from ..core.upstream import parse_upstream_file

            self._ha = parse_upstream_file(self.upstream)
        self._spool: Optional[ForwardSpool] = None
        if self.storage_spool:
            self._spool = ForwardSpool(self.storage_spool)
        self._replay_task = None
        self._replay_failures = 0
        self._quota_seq = 0
        # ids already sent once (bounded): a re-entry means the engine
        # is retrying a chunk the wire already saw — a RESEND, counted
        # distinctly from first sends so dashboards can tell loss-driven
        # retries from volume
        self._sent_ids: dict = {}
        self._ack_rtts: list = []
        self.n_acks_waited = 0
        self.n_acks_lost = 0
        self.n_resends = 0
        self.n_spooled = 0
        self.n_replayed = 0
        self._iname = instance.display_name
        m = engine.metrics
        self._m_waited = m.counter(
            "fluentbit", "forward", "acks_waited_total",
            "Forward flushes that waited for a chunk ack", ("instance",))
        self._m_lost = m.counter(
            "fluentbit", "forward", "acks_lost_total",
            "Acks that timed out or mismatched (flush retried)",
            ("instance",))
        self._m_resends = m.counter(
            "fluentbit", "forward", "resends_total",
            "Chunks re-sent with an already-used chunk id", ("instance",))
        self._m_spooled = m.counter(
            "fluentbit", "forward", "spooled_chunks_total",
            "Chunks degraded to the partition spool", ("instance",))
        self._m_replayed = m.counter(
            "fluentbit", "forward", "replayed_chunks_total",
            "Spooled chunks replayed and acked after heal", ("instance",))
        self._m_rtt = m.histogram(
            "fluentbit", "forward", "ack_rtt_seconds",
            "Send → ack round-trip per chunk", ("instance",))
        self._m_breaker = m.gauge(
            "fluentbit", "forward", "breaker_state",
            "Per-upstream breaker state (0 closed / 1 half-open / "
            "2 open)", ("upstream",))

    def exit(self) -> None:
        if self._replay_task is not None:
            try:
                self._replay_task.cancel()
            except RuntimeError:
                pass  # loop already closed at engine teardown
            self._replay_task = None
        if self._writer is not None:
            close_quietly(self._writer)
            self._reader = self._writer = None

    # -- connection -----------------------------------------------------

    def _breaker_for(self, host: str, port: int):
        name = f"forward:{host}:{port}"
        br = self._engine.guard.breaker(name)
        self._m_breaker.set(br.state_code(), (name,))
        return br

    async def _connect(self):
        if self._writer is not None and not self._writer.is_closing():
            return
        from ..core.tls import open_connection

        host, port = self.host, self.port
        self._node = None
        if self._ha is not None:
            self._node = self._ha.pick()
            host, port = self._node.host, self._node.port
        self._cur_target = f"forward:{host}:{port}"
        brk = self._breaker_for(host, port)
        self._cur_breaker = brk
        if not brk.allow():
            # a breaker refusal is not fresh evidence of failure —
            # don't let the error path re-arm the cooldown forever
            self._cur_breaker = None
            raise ConnectionError(
                f"forward: breaker open for {host}:{port}")
        self._reader, self._writer = await open_connection(
            self.instance, host, port, timeout=10
        )
        if self.shared_key:
            await self._handshake()

    def _conn_failed(self) -> None:
        """Error-path bookkeeping: tear the socket, mark the node down
        (HA failover on the next pick), record breaker evidence."""
        if self._writer is not None:
            close_quietly(self._writer)
        self._reader = self._writer = None
        if self._ha is not None and self._node is not None:
            self._ha.mark_down(self._node)
        if self._cur_breaker is not None:
            self._cur_breaker.record_failure()
            self._m_breaker.set(self._cur_breaker.state_code(),
                                (self._cur_target,))
            self._cur_breaker = None

    def _conn_ok(self) -> None:
        if self._ha is not None and self._node is not None:
            self._ha.mark_up(self._node)
        if self._cur_breaker is not None:
            self._cur_breaker.record_ok()
            self._m_breaker.set(self._cur_breaker.state_code(),
                                (self._cur_target,))
            self._cur_breaker = None

    async def _handshake(self) -> None:
        if _fp.ACTIVE:
            # an aggregator that accepts the dial but never finishes
            # auth — the failure shape of a half-up peer
            _fp.fire("forward.handshake")
        u = Unpacker()
        helo = await self._read_msg(u)
        if not (isinstance(helo, list) and helo and helo[0] == "HELO"):
            raise ConnectionError("forward: expected HELO")
        nonce = helo[1].get("nonce", b"")
        nonce = nonce if isinstance(nonce, bytes) else str(nonce).encode()
        hostname = self.self_hostname or socket.gethostname()
        salt = os.urandom(16)
        digest = hashlib.sha512(
            salt + hostname.encode() + nonce + self.shared_key.encode()
        ).hexdigest()
        self._writer.write(packb(["PING", hostname, salt, digest, "", ""]))
        await io_deadline(self._writer.drain(), 10)
        pong = await self._read_msg(u)
        if not (isinstance(pong, list) and len(pong) >= 2 and pong[0] == "PONG"
                and pong[1]):
            raise ConnectionError("forward: handshake rejected")

    async def _read_msg(self, u: Unpacker):
        while True:
            try:
                return u.unpack()
            except OutOfData:
                data = await io_deadline(self._reader.read(65536))
                if not data:
                    raise ConnectionError("forward: peer closed")
                u.feed(data)

    # -- framing --------------------------------------------------------

    def _packed_entries(self, data: bytes) -> tuple:
        """V2 events buffer → (entry stream, count, record END offsets).

        The END offsets feed the spool's record-offset sidecar
        (core/sidecar.py) so a partition-degraded chunk replays without
        re-walking its msgpack payload."""
        from ..codec.events import iter_events

        out = bytearray()
        n = 0
        ends = []
        for ev in iter_events(data):
            ts = ev.timestamp
            if self.time_as_integer:
                ts = int(ev.ts_float)
            elif isinstance(ts, float):
                ts = EventTime.from_float(ts)
            out += packb([ts, ev.body])
            ends.append(len(out))
            n += 1
        return bytes(out), n, ends

    def _frame(self, tag: str, blob: bytes, n: int,
               chunk_id: Optional[str], tenant, priority) -> bytes:
        option = {"size": n, "fluent_signal": 1}
        payload = blob
        if (self.compress or "").lower() == "gzip":
            payload = gzip.compress(blob)
            option["compressed"] = "gzip"
        if tenant is not None:
            option["tenant"] = tenant
        if priority is not None:
            option["priority"] = int(priority)
        if chunk_id is not None:
            option["chunk"] = chunk_id
        return packb([tag, payload, option])

    def _note_sent(self, chunk_id: str) -> bool:
        """True on FIRST send of this id; False marks a resend. LRU-
        bounded — eviction only ever under-counts resends."""
        if chunk_id in self._sent_ids:
            self._sent_ids[chunk_id] = True
            return False
        if len(self._sent_ids) >= 4096:
            for k in list(self._sent_ids)[:256]:
                del self._sent_ids[k]
        self._sent_ids[chunk_id] = True
        return True

    # -- delivery -------------------------------------------------------

    async def flush(self, data: bytes, tag: str, engine) -> FlushResult:
        chunk = FLUSH_CHUNK.get()
        async with self._lock:
            return await self._flush_locked(data, tag, chunk)

    async def _flush_locked(self, data: bytes, tag: str,
                            chunk) -> FlushResult:
        blob, n, ends = self._packed_entries(data)
        if n == 0:
            return FlushResult.OK
        tenant = getattr(chunk, "qos_tenant", None) \
            if chunk is not None else None
        priority = getattr(chunk, "priority", None) \
            if chunk is not None else None
        chunk_id = None
        if self.require_ack_response:
            chunk_id = stable_chunk_id(tag, blob)
            if not self._note_sent(chunk_id):
                self.n_resends += 1
                self._m_resends.inc(1, (self._iname,))
        wire = self._frame(tag, blob, n, chunk_id, tenant, priority)
        budget = max(2, len(self._ha.nodes)) if self._ha is not None \
            else 2
        attempt = 0
        while True:
            attempt += 1
            try:
                await self._connect()
                await self._send_chunk(wire, chunk_id)
            except (ConnectionError, OSError, asyncio.TimeoutError):
                self._conn_failed()
                if attempt >= budget:
                    break
                # full-jitter backoff between in-flush failover
                # attempts (core/scheduler.py) — resend-not-duplicate:
                # the retry reuses the SAME chunk_id, so a delivery
                # whose ack was lost dedups at the aggregator
                await asyncio.sleep(
                    backoff_full_jitter(0.05, 0.5, attempt))
                continue
            self._conn_ok()
            return FlushResult.OK
        return self._degrade(tag, blob, ends, n, chunk_id,
                             tenant, priority)

    async def _send_chunk(self, wire: bytes,
                          chunk_id: Optional[str]) -> None:
        if _fp.ACTIVE:
            # connection torn mid-stream, before any frame byte (RST)
            _fp.fire("forward.conn_reset")
            d = _fp.fire("forward.partial_write")
            if d and d[0] == "partial":
                # frame truncated after n bytes, then the link dies:
                # the receiver must discard the torn msgpack tail
                # without absorbing
                self._writer.write(wire[: max(1, int(d[1]))])
                await io_deadline(self._writer.drain())
                raise ConnectionError("forward: injected partial write")
        self._writer.write(wire)
        await io_deadline(self._writer.drain())
        if chunk_id is None:
            return
        u = Unpacker()
        self.n_acks_waited += 1
        self._m_waited.inc(1, (self._iname,))
        t0 = time.monotonic()
        try:
            ack = await asyncio.wait_for(
                self._read_msg(u), timeout=self.ack_timeout
            )
        except asyncio.TimeoutError:
            # TCP-alive-but-hung peer: surfaced as a connection error
            # so the caller fails over exactly like a dial failure
            self.n_acks_lost += 1
            self._m_lost.inc(1, (self._iname,))
            raise
        if not (isinstance(ack, dict) and ack.get("ack") == chunk_id):
            self.n_acks_lost += 1
            self._m_lost.inc(1, (self._iname,))
            raise ConnectionError("forward: ack mismatch")
        rtt = time.monotonic() - t0
        self._ack_rtts.append(rtt)
        if len(self._ack_rtts) > 256:
            del self._ack_rtts[:128]
        self._m_rtt.observe(rtt, (self._iname,))
        if _fp.ACTIVE:
            try:
                _fp.fire("forward.dup_delivery")
            except _fp.FailpointError:
                # ambiguous-ack shape: the SAME frame delivered again
                # after a successful ack — the aggregator's ledger must
                # absorb it zero times (its ack is consumed here so it
                # cannot be mistaken for the next chunk's)
                self.n_resends += 1
                self._m_resends.inc(1, (self._iname,))
                self._writer.write(wire)
                await io_deadline(self._writer.drain())
                await asyncio.wait_for(
                    self._read_msg(u), timeout=self.ack_timeout
                )

    # -- partition degrade + heal replay --------------------------------

    def _degrade(self, tag: str, blob: bytes, ends, n: int,
                 chunk_id: Optional[str], tenant, priority
                 ) -> FlushResult:
        """Every upstream refused within this flush's budget. With a
        spool configured, buffer the packed chunk on disk — gated by
        the tenant's storage quota — and hand delivery to the heal
        replay; otherwise RETRY through the engine's backoff."""
        if self._spool is None:
            return FlushResult.RETRY
        self._quota_seq += 1
        quota_id = f"fwd-spool:{self._iname}:{self._quota_seq}"
        shim = SimpleNamespace(id=quota_id, qos_tenant=tenant,
                               priority=priority)
        verdict = self._engine.qos.admit_storage(None, shim, len(blob))
        if verdict == 2:  # SHED: quota says no disk — the chunk stays
            # in memory and the engine's retry loop keeps ownership
            self._engine.qos.release_storage(shim)
            return FlushResult.RETRY
        self._spool.put(tag, blob, ends, {
            "tag": tag, "chunk": chunk_id, "tenant": tenant,
            "priority": priority, "quota_id": quota_id,
        })
        self.n_spooled += 1
        self._m_spooled.inc(1, (self._iname,))
        self._ensure_replay()
        return FlushResult.OK

    def _ensure_replay(self) -> None:
        if self._replay_task is None or self._replay_task.done():
            self._replay_task = asyncio.get_running_loop().create_task(
                self._replay_spool())

    async def _replay_spool(self) -> None:
        """Heal replay: drain the partition spool in spool order, each
        chunk mmap'd + framed from its sidecars (ForwardSpool.load) and
        sent with its ORIGINAL chunk-id — a replay that races a
        pre-partition delivery dedups at the aggregator's ledger."""
        spool = self._spool
        while True:
            files = spool.pending()
            if not files:
                self._replay_failures = 0
                return
            progressed = False
            for f in files:
                got = spool.load(f)
                if got is None:
                    # unframeable husk (torn payload + no usable
                    # sidecar): nothing can be replayed from it
                    spool.drop(f)
                    continue
                blob, n, meta = got
                cid = meta.get("chunk")
                wire = self._frame(meta.get("tag") or "", blob, n, cid,
                                   meta.get("tenant"),
                                   meta.get("priority"))
                if cid is not None and not self._note_sent(cid):
                    self.n_resends += 1
                    self._m_resends.inc(1, (self._iname,))
                async with self._lock:
                    try:
                        await self._connect()
                        await self._send_chunk(wire, cid)
                    except (ConnectionError, OSError,
                            asyncio.TimeoutError):
                        self._conn_failed()
                        break
                    self._conn_ok()
                qid = meta.get("quota_id")
                if qid:
                    self._engine.qos.release_storage(
                        SimpleNamespace(id=qid))
                spool.drop(f)
                self.n_replayed += 1
                self._m_replayed.inc(1, (self._iname,))
                progressed = True
            if progressed:
                # ANY drained chunk this round counts: a mid-list
                # failure after progress must not inflate the backoff
                self._replay_failures = 0
            else:
                self._replay_failures += 1
                # replay is the heal path — cap the idle gap low so a
                # flaky-but-up upstream still drains the spool quickly
                await asyncio.sleep(backoff_full_jitter(
                    0.1, 1.0, self._replay_failures))

    # -- health ---------------------------------------------------------

    def ack_p50(self) -> Optional[float]:
        if not self._ack_rtts:
            return None
        s = sorted(self._ack_rtts)
        return s[len(s) // 2]

    def health_block(self) -> dict:
        out = {
            "role": "client",
            "acks_waited": self.n_acks_waited,
            "acks_lost": self.n_acks_lost,
            "resends": self.n_resends,
            "spooled": self.n_spooled,
            "replayed": self.n_replayed,
        }
        upstreams = {}
        if self._ha is not None:
            for node in self._ha.nodes:
                upstreams[f"{node.host}:{node.port}"] = \
                    node.breaker.state_name()
        else:
            br = self._engine.guard.breaker(
                f"forward:{self.host}:{self.port}")
            upstreams[f"{self.host}:{self.port}"] = br.state_name()
        out["upstreams"] = upstreams
        if self._spool is not None:
            out["spool_pending"] = len(self._spool.pending())
        p50 = self.ack_p50()
        if p50 is not None:
            out["ack_p50_ms"] = round(p50 * 1000.0, 3)
        return out
