"""out_kafka native-protocol tests against a stub broker.

The stub implements the broker side independently (decodes Metadata v1
and Produce v3 per the spec, validates RecordBatch CRC-32C), so
protocol bugs can't self-confirm. Mirrors the runtime-test stance the
reference applies to socket outputs."""

import json
import socket
import struct
import threading
import time

import fluentbit_tpu as flb
from fluentbit_tpu.utils import kafka_protocol as kp


class StubBroker:
    """Single-threaded Kafka broker stub: answers Metadata v1 and
    Produce v3; records every produced batch."""

    def __init__(self, n_partitions=2, produce_error=0):
        self.n_partitions = n_partitions
        self.produce_error = produce_error
        self.produced = []  # (topic, partition, crc_ok, records)
        # consumer-side log: {(topic, pid): [batch_bytes]}
        self.log = {}
        # consumer-group state (single-group coordinator)
        self.generation = 0
        self.members = {}           # member_id -> metadata bytes
        self.assignments = {}       # member_id -> assignment bytes
        self.committed = {}         # (topic, pid) -> offset
        self.commits = []           # every (generation, member, dict)
        self.heartbeats = 0
        self.force_rebalance = False
        self._member_seq = 0
        self.sock = socket.socket()
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(8)
        self.port = self.sock.getsockname()[1]
        self._stop = False
        self.thread = threading.Thread(target=self._serve, daemon=True)
        self.thread.start()

    def _read_req(self, conn):
        raw = b""
        while len(raw) < 4:
            chunk = conn.recv(4 - len(raw))
            if not chunk:
                return None
            raw += chunk
        n = int.from_bytes(raw, "big")
        payload = b""
        while len(payload) < n:
            chunk = conn.recv(n - len(payload))
            if not chunk:
                return None
            payload += chunk
        return payload

    def _serve(self):
        self.sock.settimeout(0.2)
        while not self._stop:
            try:
                conn, _ = self.sock.accept()
            except socket.timeout:
                continue
            # persistent connections, like a real broker (the client
            # side pools and reuses them)
            threading.Thread(target=self._conn_loop, args=(conn,),
                             daemon=True).start()

    def _conn_loop(self, conn):
        with conn:
            conn.settimeout(8)
            while not self._stop:
                try:
                    payload = self._read_req(conn)
                except (socket.timeout, OSError):
                    return
                if payload is None:
                    return
                api, version, corr = struct.unpack(">hhi", payload[:8])
                klen = struct.unpack(">h", payload[8:10])[0]
                body = payload[10 + max(klen, 0):]
                if api == kp.API_METADATA:
                    resp = self._metadata(body)
                elif api == kp.API_PRODUCE:
                    resp = self._produce(body)
                elif api == kp.API_LIST_OFFSETS:
                    resp = self._list_offsets(body)
                elif api == kp.API_FETCH:
                    resp = self._fetch(body)
                elif api == kp.API_FIND_COORDINATOR:
                    resp = self._find_coordinator(body)
                elif api == kp.API_JOIN_GROUP:
                    resp = self._join_group(body)
                elif api == kp.API_SYNC_GROUP:
                    resp = self._sync_group(body)
                elif api == kp.API_HEARTBEAT:
                    resp = self._heartbeat(body)
                elif api == kp.API_OFFSET_FETCH:
                    resp = self._offset_fetch(body)
                elif api == kp.API_OFFSET_COMMIT:
                    resp = self._offset_commit(body)
                elif api == kp.API_LEAVE_GROUP:
                    r = kp._Reader(body)
                    r.string()
                    mid = r.string() or ""
                    self.members.pop(mid, None)
                    if not hasattr(self, "left"):
                        self.left = []
                    self.left.append(mid)
                    resp = struct.pack(">h", 0)
                else:
                    return
                out = struct.pack(">i", corr) + resp
                try:
                    conn.sendall(struct.pack(">i", len(out)) + out)
                except OSError:
                    return

    def _metadata(self, body):
        r = kp._Reader(body)
        topics = [r.string() for _ in range(r.i32())]
        out = struct.pack(">i", 1)  # one broker
        out += struct.pack(">i", 0) + kp._str("127.0.0.1") \
            + struct.pack(">i", self.port) + kp._str(None)
        out += struct.pack(">i", 0)  # controller
        out += struct.pack(">i", len(topics))
        for t in topics:
            out += struct.pack(">h", 0) + kp._str(t) + b"\x00"
            out += struct.pack(">i", self.n_partitions)
            for pid in range(self.n_partitions):
                out += struct.pack(">hii", 0, pid, 0)
                out += struct.pack(">i", 1) + struct.pack(">i", 0)
                out += struct.pack(">i", 1) + struct.pack(">i", 0)
        return out

    def _produce(self, body):
        r = kp._Reader(body)
        r.string()          # transactional id
        r.i16()             # acks
        r.i32()             # timeout
        resp_topics = []
        for _ in range(r.i32()):
            topic = r.string()
            parts = []
            for _ in range(r.i32()):
                pid = r.i32()
                blen = r.i32()
                batch = r.take(blen)
                crc_ok, records, _last = kp.decode_record_batch(batch)
                # producer-side views keep the (key, value, ts) shape
                records = [(k, v, ts) for k, v, ts, _d in records]
                self.produced.append((topic, pid, crc_ok, records))
                parts.append(pid)
            resp_topics.append((topic, parts))
        out = struct.pack(">i", len(resp_topics))
        for topic, parts in resp_topics:
            out += kp._str(topic) + struct.pack(">i", len(parts))
            for pid in parts:
                out += struct.pack(">ihqq", pid, self.produce_error,
                                   0, -1)
        out += struct.pack(">i", 0)  # throttle
        return out

    def append_log(self, topic, pid, records, base=None):
        """Make records fetchable (the broker-side log)."""
        key = (topic, pid)
        batches = self.log.setdefault(key, [])
        if base is None:
            base = sum(len(kp.decode_record_batch(b)[1])
                       for _o, b in batches)
        raw = kp.encode_record_batch(records, 1700000000000)
        # stamp the real base offset into the batch header
        raw = struct.pack(">q", base) + raw[8:]
        batches.append((base, raw))

    def _next_offset(self, topic, pid):
        batches = self.log.get((topic, pid), [])
        if not batches:
            return 0
        base, raw = batches[-1]
        return base + kp.decode_record_batch(raw)[2] + 1

    def _list_offsets(self, body):
        r = kp._Reader(body)
        r.i32()  # replica
        topics = []
        for _ in range(r.i32()):
            t = r.string()
            plist = []
            for _ in range(r.i32()):
                pid = r.i32()
                ts = r.i64()
                plist.append((pid, ts))
            topics.append((t, plist))
        out = struct.pack(">i", len(topics))
        for t, plist in topics:
            out += kp._str(t) + struct.pack(">i", len(plist))
            for pid, ts in plist:
                off = 0 if ts == -2 else self._next_offset(t, pid)
                out += struct.pack(">ihqq", pid, 0, -1, off)
        return out

    def _fetch(self, body):
        r = kp._Reader(body)
        r.i32(); r.i32(); r.i32(); r.i32(); r.i8()
        topics = []
        for _ in range(r.i32()):
            t = r.string()
            plist = []
            for _ in range(r.i32()):
                pid = r.i32()
                off = r.i64()
                r.i32()  # partition max bytes
                plist.append((pid, off))
            topics.append((t, plist))
        out = struct.pack(">i", 0)  # throttle
        out += struct.pack(">i", len(topics))
        for t, plist in topics:
            out += kp._str(t) + struct.pack(">i", len(plist))
            for pid, off in plist:
                record_set = b"".join(
                    raw for base, raw in self.log.get((t, pid), [])
                    if base >= off)
                hw = self._next_offset(t, pid)
                out += struct.pack(">ihqq", pid, 0, hw, -1)
                out += struct.pack(">i", 0)  # aborted txns
                out += struct.pack(">i", len(record_set)) + record_set
        return out

    # -- consumer-group coordinator (single group) --

    def _find_coordinator(self, body):
        kp._Reader(body).string()  # group id
        return struct.pack(">hi", 0, 1) + kp._str("127.0.0.1") \
            + struct.pack(">i", self.port)

    def _join_group(self, body):
        r = kp._Reader(body)
        r.string()                    # group
        r.i32()                       # session timeout
        member_id = r.string() or ""
        r.string()                    # protocol type
        meta = b""
        for _ in range(r.i32()):
            r.string()                # protocol name
            n = r.i32()
            meta = bytes(r.take(n)) if n > 0 else b""
        if not member_id:
            self._member_seq += 1
            member_id = f"member-{self._member_seq}"
        self.members[member_id] = meta
        self.generation += 1
        self.force_rebalance = False
        leader = sorted(self.members)[0]
        out = struct.pack(">hi", 0, self.generation)
        out += kp._str("range") + kp._str(leader) + kp._str(member_id)
        members = list(self.members.items()) if member_id == leader \
            else []
        out += struct.pack(">i", len(members))
        for mid, mmeta in members:
            out += kp._str(mid) + struct.pack(">i", len(mmeta)) + mmeta
        return out

    def _sync_group(self, body):
        r = kp._Reader(body)
        r.string()                    # group
        gen = r.i32()
        member_id = r.string() or ""
        for _ in range(r.i32()):
            mid = r.string() or ""
            n = r.i32()
            self.assignments[mid] = bytes(r.take(n)) if n > 0 else b""
        if gen != self.generation:
            return struct.pack(">hi", kp.ERR_ILLEGAL_GENERATION, 0)
        blob = self.assignments.get(member_id, b"")
        return struct.pack(">hi", 0, len(blob)) + blob

    def _heartbeat(self, body):
        r = kp._Reader(body)
        r.string()
        gen = r.i32()
        self.heartbeats += 1
        if self.force_rebalance or gen != self.generation:
            return struct.pack(">h", kp.ERR_REBALANCE_IN_PROGRESS)
        return struct.pack(">h", 0)

    def _offset_fetch(self, body):
        r = kp._Reader(body)
        r.string()                    # group
        topics = []
        for _ in range(r.i32()):
            t = r.string() or ""
            topics.append((t, [r.i32() for _ in range(r.i32())]))
        out = struct.pack(">i", len(topics))
        for t, pids in topics:
            out += kp._str(t) + struct.pack(">i", len(pids))
            for pid in pids:
                off = self.committed.get((t, pid), -1)
                out += struct.pack(">iq", pid, off) + kp._str("") \
                    + struct.pack(">h", 0)
        return out

    def _offset_commit(self, body):
        r = kp._Reader(body)
        r.string()                    # group
        gen = r.i32()
        member = r.string() or ""
        r.i64()                       # retention
        got = {}
        topics = []
        for _ in range(r.i32()):
            t = r.string() or ""
            plist = []
            for _ in range(r.i32()):
                pid = r.i32()
                off = r.i64()
                r.string()            # metadata
                got[(t, pid)] = off
                plist.append(pid)
            topics.append((t, plist))
        err = 0 if gen == self.generation else \
            kp.ERR_ILLEGAL_GENERATION
        if err == 0:
            self.committed.update(got)
            self.commits.append((gen, member, got))
        out = struct.pack(">i", len(topics))
        for t, plist in topics:
            out += kp._str(t) + struct.pack(">i", len(plist))
            for pid in plist:
                out += struct.pack(">ih", pid, err)
        return out

    def close(self):
        self._stop = True
        self.thread.join(timeout=3)
        self.sock.close()


def wait_for(cond, timeout=8.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        v = cond()
        if v:
            return v
        time.sleep(0.05)
    raise TimeoutError()


def test_record_batch_roundtrip():
    batch = kp.encode_record_batch(
        [(b"k1", b"v1"), (None, b"v2")], 1700000000000)
    crc_ok, records, last_delta = kp.decode_record_batch(batch)
    assert crc_ok and last_delta == 1
    assert records == [(b"k1", b"v1", 1700000000000, 0),
                       (None, b"v2", 1700000000000, 1)]


def test_out_kafka_produces_json():
    broker = StubBroker()
    ctx = flb.create(flush="50ms", grace="1")
    in_ffd = ctx.input("lib", tag="t")
    ctx.output("kafka", match="t",
               brokers=f"127.0.0.1:{broker.port}", topics="logs")
    ctx.start()
    try:
        ctx.push(in_ffd, json.dumps({"msg": "to kafka", "n": 1}))
        ctx.flush_now()
        wait_for(lambda: broker.produced)
    finally:
        ctx.stop()
        broker.close()
    topic, pid, crc_ok, records = broker.produced[0]
    assert topic == "logs" and crc_ok
    ((key, value, _ts),) = records
    body = json.loads(value)
    assert body["msg"] == "to kafka"
    assert "@timestamp" in body  # timestamp_key default


def test_out_kafka_message_key_partitioning():
    broker = StubBroker(n_partitions=4)
    ctx = flb.create(flush="50ms", grace="1")
    in_ffd = ctx.input("lib", tag="t")
    ctx.output("kafka", match="t",
               brokers=f"127.0.0.1:{broker.port}", topics="logs",
               message_key_field="user")
    ctx.start()
    try:
        for i in range(8):
            ctx.push(in_ffd, json.dumps({"user": f"u{i % 2}", "i": i}))
        ctx.flush_now()
        wait_for(lambda: len(broker.produced) >= 2)
        time.sleep(0.3)
    finally:
        ctx.stop()
        broker.close()
    by_user = {}
    for _t, pid, crc_ok, records in broker.produced:
        assert crc_ok
        for key, _v, _ts in records:
            by_user.setdefault(key, set()).add(pid)
    # same key → same partition, different keys spread
    assert all(len(p) == 1 for p in by_user.values())
    assert len(by_user) == 2


def test_out_kafka_dynamic_topic():
    broker = StubBroker()
    ctx = flb.create(flush="50ms", grace="1")
    in_ffd = ctx.input("lib", tag="t")
    ctx.output("kafka", match="t",
               brokers=f"127.0.0.1:{broker.port}", topics="fallback",
               topic_key="dest", dynamic_topic="on")
    ctx.start()
    try:
        ctx.push(in_ffd, json.dumps({"dest": "audit", "m": 1}))
        ctx.push(in_ffd, json.dumps({"m": 2}))
        ctx.flush_now()
        wait_for(lambda: len(broker.produced) >= 2)
    finally:
        ctx.stop()
        broker.close()
    topics = {t for t, *_ in broker.produced}
    assert topics == {"audit", "fallback"}


def test_out_kafka_broker_error_retries():
    broker = StubBroker(produce_error=6)  # NOT_LEADER_FOR_PARTITION
    ctx = flb.create(flush="50ms", grace="1")
    in_ffd = ctx.input("lib", tag="t")
    ctx.output("kafka", match="t",
               brokers=f"127.0.0.1:{broker.port}", topics="logs",
               retry_limit="1")
    ctx.start()
    try:
        ctx.push(in_ffd, json.dumps({"m": 1}))
        ctx.flush_now()
        wait_for(lambda: broker.produced)
    finally:
        time.sleep(0.2)
        ctx.stop()
        broker.close()
    m = ctx.metrics.to_prometheus()
    assert 'fluentbit_output_retries_total{name="kafka.0"} 1' in m


def test_out_kafka_acks_zero_fire_and_forget():
    broker = StubBroker()
    ctx = flb.create(flush="50ms", grace="1")
    in_ffd = ctx.input("lib", tag="t")
    ctx.output("kafka", match="t",
               brokers=f"127.0.0.1:{broker.port}", topics="logs",
               required_acks="0")
    ctx.start()
    try:
        ctx.push(in_ffd, json.dumps({"m": "noack"}))
        ctx.flush_now()
        wait_for(lambda: broker.produced)
    finally:
        ctx.stop()
        broker.close()
    # delivered (broker decoded it) AND accounted OK without a response
    m = ctx.metrics.to_prometheus()
    assert 'fluentbit_output_proc_records_total{name="kafka.0"} 1' in m
    assert 'retries_total{name="kafka.0"}' not in m


def test_out_kafka_requires_topics():
    import pytest
    ctx = flb.create(flush="50ms", grace="1")
    ctx.input("dummy", tag="t")
    ctx.output("kafka", match="t", topics="  ")
    ctx.output("null", match="*")
    with pytest.raises(Exception):
        ctx.start()
    ctx.stop()


def test_in_kafka_consumes_from_latest():
    from fluentbit_tpu.codec.events import decode_events

    broker = StubBroker(n_partitions=2)
    broker.append_log("logs", 0, [(None, b"old-before-subscribe")])
    ctx = flb.create(flush="50ms", grace="1")
    ctx.input("kafka", tag="k", brokers=f"127.0.0.1:{broker.port}",
              topics="logs", poll_ms="100", format="json")
    got = []
    ctx.output("lib", match="*", callback=lambda d, t: got.append(d))
    ctx.start()
    try:
        time.sleep(0.6)  # let it bootstrap at LATEST (past the old rec)
        broker.append_log("logs", 0,
                          [(b"key1", json.dumps({"n": 1}).encode())],
                          base=1)
        broker.append_log("logs", 1, [(None, b"plain text")], base=0)
        wait_for(lambda: sum(len(decode_events(d)) for d in got) >= 2)
    finally:
        ctx.stop()
        broker.close()
    evs = [e.body for d in got for e in decode_events(d)]
    by_part = {e["partition"]: e for e in evs}
    assert by_part[0]["payload"] == {"n": 1}       # format json parsed
    assert by_part[0]["key"] == "key1"
    assert by_part[0]["offset"] == 1
    assert by_part[1]["payload"] == "plain text"   # non-JSON kept raw
    assert all(e["topic"] == "logs" for e in evs)
    assert all(e["error"] is None for e in evs)
    # the pre-subscribe record was skipped (initial_offset latest)
    assert not any(e["offset"] == 0 and e["partition"] == 0 for e in evs)


def test_in_kafka_earliest_reads_backlog():
    from fluentbit_tpu.codec.events import decode_events

    broker = StubBroker(n_partitions=1)
    broker.append_log("logs", 0, [(None, b"one"), (None, b"two")])
    ctx = flb.create(flush="50ms", grace="1")
    ctx.input("kafka", tag="k", brokers=f"127.0.0.1:{broker.port}",
              topics="logs", poll_ms="100", initial_offset="earliest")
    got = []
    ctx.output("lib", match="*", callback=lambda d, t: got.append(d))
    ctx.start()
    try:
        wait_for(lambda: sum(len(decode_events(d)) for d in got) >= 2)
    finally:
        ctx.stop()
        broker.close()
    evs = [e.body for d in got for e in decode_events(d)]
    assert [e["payload"] for e in evs[:2]] == ["one", "two"]
    assert [e["offset"] for e in evs[:2]] == [0, 1]


def test_in_kafka_group_join_commit_resume():
    """group_id: coordinator discovery, join/sync (leader range
    assignment over both partitions), commit after consumption, and a
    second consumer generation resuming from the committed offsets."""
    from fluentbit_tpu.codec.events import decode_events

    broker = StubBroker(n_partitions=2)
    broker.append_log("logs", 0, [(None, b"a"), (None, b"b")])
    broker.append_log("logs", 1, [(None, b"c")], base=0)
    ctx = flb.create(flush="50ms", grace="1")
    ctx.input("kafka", tag="k", brokers=f"127.0.0.1:{broker.port}",
              topics="logs", poll_ms="100", group_id="g1",
              initial_offset="earliest", session_timeout_ms="3000")
    got = []
    ctx.output("lib", match="*", callback=lambda d, t: got.append(d))
    ctx.start()
    try:
        wait_for(lambda: sum(len(decode_events(d)) for d in got) >= 3)
        # commits arrive with the member's generation
        wait_for(lambda: broker.committed.get(("logs", 0)) == 2
                 and broker.committed.get(("logs", 1)) == 1)
        joined = dict(broker.members)
    finally:
        ctx.stop()
        broker.close()
    assert joined  # member registered while running
    assert broker.commits and broker.commits[0][1].startswith("member-")

    # a NEW consumer in the same group resumes at the committed
    # offsets — the backlog is NOT re-read despite earliest
    broker2 = StubBroker(n_partitions=2)
    broker2.committed = {("logs", 0): 2, ("logs", 1): 1}
    broker2.append_log("logs", 0, [(None, b"a"), (None, b"b")])
    broker2.append_log("logs", 0, [(None, b"new")], base=2)
    ctx2 = flb.create(flush="50ms", grace="1")
    ctx2.input("kafka", tag="k", brokers=f"127.0.0.1:{broker2.port}",
               topics="logs", poll_ms="100", group_id="g1",
               initial_offset="earliest", session_timeout_ms="3000")
    got2 = []
    ctx2.output("lib", match="*", callback=lambda d, t: got2.append(d))
    ctx2.start()
    try:
        wait_for(lambda: sum(len(decode_events(d)) for d in got2) >= 1)
        time.sleep(0.3)
    finally:
        ctx2.stop()
        broker2.close()
    evs = [e.body for d in got2 for e in decode_events(d)]
    assert [e["payload"] for e in evs] == ["new"]
    assert evs[0]["offset"] == 2


def test_in_kafka_group_rebalance_rejoins():
    from fluentbit_tpu.codec.events import decode_events

    broker = StubBroker(n_partitions=1)
    ctx = flb.create(flush="50ms", grace="1")
    ctx.input("kafka", tag="k", brokers=f"127.0.0.1:{broker.port}",
              topics="logs", poll_ms="100", group_id="g1",
              initial_offset="earliest", session_timeout_ms="3000")
    got = []
    ctx.output("lib", match="*", callback=lambda d, t: got.append(d))
    ctx.start()
    try:
        wait_for(lambda: broker.generation >= 1)
        gen_before = broker.generation
        broker.force_rebalance = True  # heartbeat answers 27
        wait_for(lambda: broker.generation > gen_before, timeout=12)
        # after the rejoin, consumption still works
        broker.append_log("logs", 0, [(None, b"post-rebalance")])
        wait_for(lambda: got)
    finally:
        ctx.stop()
        broker.close()
    evs = [e.body for d in got for e in decode_events(d)]
    assert evs[0]["payload"] == "post-rebalance"


def test_in_kafka_clean_stop_sends_leave_group():
    broker = StubBroker(n_partitions=1)
    ctx = flb.create(flush="50ms", grace="1")
    ctx.input("kafka", tag="k", brokers=f"127.0.0.1:{broker.port}",
              topics="logs", poll_ms="100", group_id="g1",
              session_timeout_ms="3000")
    ctx.output("null", match="*")
    broker.left = []
    orig = broker._conn_loop  # noqa: F841

    ctx.start()
    try:
        wait_for(lambda: broker.members)
    finally:
        ctx.stop()
        time.sleep(0.2)
    assert broker.left, "LeaveGroup not received on clean stop"
    broker.close()


def test_in_kafka_oor_partitions_bypass_offset_fetch():
    """OFFSET_OUT_OF_RANGE re-resolution: partitions whose COMMITTED
    offset was trimmed must resolve via ListOffsets, never OffsetFetch
    (the committed offset would be handed back forever — the round-3
    livelock)."""
    import asyncio

    from fluentbit_tpu.core.plugin import registry
    from fluentbit_tpu.utils import kafka_protocol as kp

    ins = registry.create_input("kafka")
    ins.set("brokers", "127.0.0.1:19092")
    ins.set("topics", "t")
    ins.set("group_id", "g")
    ins.configure()
    ins.plugin.init(ins, None)
    p = ins.plugin
    p._assignment = {"t": [0, 1]}
    p._coordinator = ("127.0.0.1", 19092)
    p._oor = {("t", 0)}  # partition 0's committed offset was trimmed
    calls = []

    async def fake_rpc_to(addr, api, ver, payload):
        calls.append(("to", api))
        assert api == kp.API_OFFSET_FETCH
        return _offset_fetch(1, 77)  # committed offset ONLY for part 1

    async def fake_rpc(api, ver, payload):
        calls.append(("rpc", api))
        assert api == kp.API_LIST_OFFSETS
        return _list_offsets("t", 0, 1000)

    def _offset_fetch(pid, off):
        # [throttle? v1: [topics]] — build via the protocol helpers'
        # inverse: craft the response the parser expects
        import struct

        def s(x):
            b = x.encode()
            return struct.pack(">h", len(b)) + b

        return (struct.pack(">i", 1) + s("t") + struct.pack(">i", 1)
                + struct.pack(">iq", pid, off) + s("") +
                struct.pack(">h", 0))

    def _list_offsets(topic, pid, off):
        import struct

        def s(x):
            b = x.encode()
            return struct.pack(">h", len(b)) + b

        # v1: [topics: name [partitions: pid err ts offset]]
        return (struct.pack(">i", 1) + s(topic) + struct.pack(">i", 1)
                + struct.pack(">ihqq", pid, 0, -1, off))

    p._rpc_to = fake_rpc_to
    p._rpc = fake_rpc
    asyncio.run(p._resolve_group_offsets())
    # partition 0 resolved via ListOffsets, partition 1 via OffsetFetch
    assert p._offsets[("t", 0)] == 1000
    assert p._offsets[("t", 1)] == 77
    assert ("rpc", kp.API_LIST_OFFSETS) in calls
    # the OOR partition is cleared and queued for a prompt commit
    assert ("t", 0) not in p._oor
    assert p._uncommitted

def test_in_kafka_group_reset_clears_oor_markers():
    """round-5 advisor (low): a rebalance (group reset) must clear
    OFFSET_OUT_OF_RANGE markers — another member may have committed a
    valid offset since, so post-rebalance resolution for the partition
    must go through OffsetFetch again, not be reset to latest."""
    import asyncio
    import struct

    from fluentbit_tpu.core.plugin import registry
    from fluentbit_tpu.utils import kafka_protocol as kp

    ins = registry.create_input("kafka")
    ins.set("brokers", "127.0.0.1:19092")
    ins.set("topics", "t")
    ins.set("group_id", "g")
    ins.configure()
    ins.plugin.init(ins, None)
    p = ins.plugin
    p._oor = {("t", 0)}
    p._reset_group()
    assert p._oor == set(), "rebalance must drop stale OOR markers"

    # post-rebalance resolution uses OffsetFetch for the formerly-OOR
    # partition (the other member's committed offset wins)
    p._assignment = {"t": [0]}
    p._coordinator = ("127.0.0.1", 19092)
    calls = []

    def s(x):
        b = x.encode()
        return struct.pack(">h", len(b)) + b

    async def fake_rpc_to(addr, api, ver, payload):
        calls.append(api)
        assert api == kp.API_OFFSET_FETCH
        return (struct.pack(">i", 1) + s("t") + struct.pack(">i", 1)
                + struct.pack(">iq", 0, 555) + s("")
                + struct.pack(">h", 0))

    async def fake_rpc(api, ver, payload):
        raise AssertionError(
            f"must not fall back to ListOffsets (api={api})")

    p._rpc_to = fake_rpc_to
    p._rpc = fake_rpc
    asyncio.run(p._resolve_group_offsets())
    assert calls == [kp.API_OFFSET_FETCH]
    assert p._offsets[("t", 0)] == 555
